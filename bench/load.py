"""The one traffic generator: reads a mix's parameters and drives the program.

A mix (``bench/traffic/<name>.json``) names its ``mode``:

* ``batch`` -- a closed loop of ``SearchPlan.run`` calls, back to back,
  each on a fresh carry of ``plan.queries`` queries.  Query ``i`` of the
  run searches class ``i mod num_classes``.  Each class has a fixed pool
  of ``pool_per_class`` query keys, which the run takes in an order drawn
  from the seed, a new order for each pass through the pool.
  ``detector`` is ``all_classes`` (one class-agnostic detector, each
  query picks its class through ``select``) or ``own_class`` (one
  detector per class, single-query plans).  The detector itself is the
  one the configuration names (``bench/detectors``).
* ``service`` -- an open loop of tenants submitted to a ``SearchService``
  through ``launch/serve_search.handle_request`` at ``rate_per_s``.  The
  tenants are one fixed sequence: classes in Zipf(``zipf_s``) proportions,
  each with a key of its own, and before each a gap of a Poisson
  process's for that many arrivals in the window.  The seed rotates the
  sequence to start at another tenant.

So every seed offers the same work in another order (and a run's spread
is the system's, not the draw's); what differs is which queries share a
batch, and when each tenant arrives.

Each load does its set-up and warm-up (``setup``), runs the measured
window (``window``), reports its counters, and hands ``sample`` the
queries the reference replays.
"""
from __future__ import annotations

import time

import numpy as np

from bench import detectors, reference
from bench.data import repository


POOL_KEY, WARM_KEY = 0, 1   # PRNG roots of the query pool and the warm-up


def pool_index(seed: int, pool: int, num_classes: int, i: int) -> int:
    """Index into the fixed query pool of the run's ``i``-th query: class
    ``i mod num_classes``, that class's pool in a seed-drawn order per pass."""
    c, k = i % num_classes, i // num_classes
    order = np.random.default_rng([seed, 7, c, k // pool]).permutation(pool)
    return c * pool + int(order[k % pool])


def _span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class _Detector:
    """The configuration's detector module, as both loads hold it."""

    def _init_detector(self, root: str) -> None:
        self.det_cfg = self.cfg["detector"]
        self.det_kind = detectors.kind(self.det_cfg)
        self.det_mod = detectors.module(self.det_kind, root)

    def _matcher(self):
        from repro.core import init_matcher

        m = self.cfg["matcher"]
        return init_matcher(
            max_results=self.mix["max_results"],
            feat_dim=detectors.feat_dim(self.det_mod, self.det_cfg, self.cfg["repository"]),
            iou_thresh=m["iou_thresh"], time_gate=m["time_gate"],
            feat_thresh=m.get("feat_thresh", -1.0))

    def _query(self, **kw) -> reference.Query:
        m, s = self.cfg["matcher"], self.cfg["sampler"]
        return reference.Query(
            max_dets=self.det_cfg["max_dets"], iou_thresh=m["iou_thresh"],
            time_gate=m["time_gate"], feat_thresh=m.get("feat_thresh", -1.0),
            alpha0=s["alpha0"], beta0=s["beta0"], detector=self.det_kind,
            detector_cfg=self.det_cfg, **kw)


def _pick(rng, outcomes, k: int):
    """The longest query and ``k - 1`` others drawn by ``rng``."""
    if not outcomes:
        return []
    longest = max(range(len(outcomes)), key=lambda i: outcomes[i]["step"])
    rest = [i for i in range(len(outcomes)) if i != longest]
    take = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [outcomes[longest]] + [outcomes[rest[int(i)]] for i in take]


class BatchLoad(_Detector):
    """Closed loop of batch ``SearchPlan`` runs."""

    def __init__(self, cfg: dict, mix: dict, arrays, seed: int,
                 root: str = detectors.ROOT):
        import jax

        from repro.core import SearchPlan
        from repro.core.exsample import detector_chunk

        self.cfg, self.mix, self.arrays, self.seed = cfg, mix, arrays, seed
        self.inst, self.chunks = repository.to_device(arrays)
        self.plan = SearchPlan.from_dict(mix["plan"])
        self.kind, self.method = self.plan.resolve()
        self.q_n = self.plan.queries
        self.multi = self.kind in ("multi", "multi_sharded", "async_multi")
        self.num_classes = int(cfg["repository"]["num_classes"])
        self.all_classes = mix["detector"] == "all_classes"
        self._init_detector(root)
        self._dets, self._sels = {}, {}
        lanes = self.q_n * self.plan.cohorts // self.plan.execution.shards
        self.detector_width = detector_chunk(lanes)
        self._fold = jax.jit(jax.vmap(jax.random.fold_in, (None, 0)))
        self.pool = int(mix["pool_per_class"])
        self.window_key = jax.random.PRNGKey(POOL_KEY)
        self.warm_key = jax.random.PRNGKey(WARM_KEY)
        self.records = []
        self.run_s = 0.0

    def _classes(self, first: int):
        return tuple((first + q) % self.num_classes for q in range(self.q_n))

    def _detector(self, cls):
        c = None if self.all_classes else cls
        if c not in self._dets:
            self._dets[c] = self.det_mod.program(self.det_cfg, self.inst, query_class=c)
        return self._dets[c]

    def check_detector(self):
        """The class-agnostic program-side detector, for the module's check."""
        return self._detector(None) if self.all_classes else self.det_mod.program(
            self.det_cfg, self.inst, query_class=None)

    def _select(self, classes):
        if not (self.all_classes and self.multi):
            return None
        if classes not in self._sels:
            self._sels[classes] = self.det_mod.program_select(self.det_cfg, self.inst, classes)
        return self._sels[classes]

    def _carry(self, keys):
        from repro.core import init_carry, init_carry_multi, init_state

        s = self.cfg["sampler"]
        state = init_state(self.chunks.length, alpha0=s["alpha0"], beta0=s["beta0"])
        matcher = self._matcher()
        if self.multi:
            return init_carry_multi(state, matcher, keys)
        return init_carry(state, matcher, keys[0])

    def _key_index(self, i: int) -> int:
        return pool_index(self.seed, self.pool, self.num_classes, i)

    def _run(self, key_root, first: int):
        classes = self._classes(first)
        with _span("bench.init"):
            idx = np.asarray([self._key_index(i) for i in range(first, first + self.q_n)],
                             np.uint32)
            keys = self._fold(key_root, idx)
            carry = self._carry(keys)
        with _span("bench.search"):
            res = self.plan.run(carry, self.chunks,
                                detector=self._detector(classes[0]),
                                select=self._select(classes))
        return classes, res

    def setup(self) -> None:
        """Warm up every program the window runs: one batch per detector
        (and class set) the window uses."""
        seen = set()
        for first in range(0, self.num_classes * self.q_n, self.q_n):
            classes = self._classes(first)
            sig = (None if self.all_classes else classes[0],
                   classes if self.all_classes and self.multi else None)
            if sig in seen:
                continue
            seen.add(sig)
            self._run(self.warm_key, first)

    def window(self, seconds: float, tick=None) -> None:
        t0 = time.monotonic()
        first = 0
        while True:
            if tick:
                tick(time.monotonic() - t0)
            classes, res = self._run(self.window_key, first)
            sampler = res.carry.sampler
            self.records.append({
                "first": first, "classes": classes, "steps": res.steps,
                "results": res.results, "stats": res.stats,
                "n": sampler.n, "n1": sampler.n1,
            })
            first += self.q_n
            if time.monotonic() - t0 >= seconds:
                break
        self.run_s = time.monotonic() - t0

    def counters(self) -> dict:
        st = [r["stats"] for r in self.records]
        limit = int(self.plan.result_limit)
        stopped = sum(
            r >= limit or s >= self.plan.max_steps
            for rec in self.records for s, r in zip(rec["steps"], rec["results"])
        )
        done = self.q_n * len(self.records)
        return {
            "window_s": self.run_s,
            "queries_done": done,
            "attempted": done,
            "failed": done - stopped,
            "frames_sampled": sum(s.frames_sampled for s in st),
            "results": sum(sum(r["results"]) for r in self.records),
            "detector_invocations": sum(s.detector_invocations for s in st),
            "detector_lanes": sum(s.detector_lanes for s in st),
            "cache_hits": sum(s.cache_hits for s in st),
        }

    def outcomes(self) -> list:
        out = []
        for rec in self.records:
            n = np.asarray(rec["n"]).reshape(self.q_n, -1)
            n1 = np.asarray(rec["n1"]).reshape(self.q_n, -1)
            for q in range(self.q_n):
                out.append({"index": rec["first"] + q, "cls": rec["classes"][q],
                            "step": rec["steps"][q], "results": rec["results"][q],
                            "n": n[q], "n1": n1[q]})
        return out

    def sample(self, k: int):
        """[(reference.Query, program outcome)] for ``k`` queries of the
        window, the longest among them; frees the rest."""
        rng = np.random.default_rng([self.seed, 4])
        picked = _pick(rng, self.outcomes(), k)
        self.records = []
        keys = np.asarray(self._fold(self.window_key, np.asarray(
            [self._key_index(p["index"]) for p in picked], np.uint32)))
        ex = self.plan.execution
        return [
            (self._query(
                key=keys[j], query_class=p["cls"], cohorts=self.plan.cohorts,
                result_limit=int(self.plan.result_limit),
                max_steps=self.plan.max_steps, method=self.method,
                all_classes=self.all_classes,
                shards=ex.shards, sync_every=ex.sync_every), p)
            for j, p in enumerate(picked)
        ]

    def close(self) -> None:
        self.records = []


def zipf_classes(n: int, num_classes: int, s: float) -> list[int]:
    """A fixed multiset of ``n`` classes in Zipf(``s``) proportions, by
    largest remainder, class 0 the most popular."""
    p = 1.0 / np.arange(1, num_classes + 1) ** s
    p = p / p.sum() * n
    counts = np.floor(p).astype(int)
    for c in np.argsort(-(p - counts), kind="stable")[: n - counts.sum()]:
        counts[c] += 1
    return [c for c in range(num_classes) for _ in range(counts[c])]


def _rotation(n: int, seed: int) -> int:
    return int(np.random.default_rng([seed, 3]).integers(n)) if n else 0


def tenant_set(n: int, num_classes: int, zipf_s: float, seed: int):
    """(classes, key seeds) of ``n`` tenants: one fixed sequence (Zipf
    classes in a fixed shuffled order, a key each), rotated to start at a
    point drawn from ``seed``."""
    rng = np.random.default_rng(POOL_KEY)
    classes = rng.permutation(zipf_classes(n, num_classes, zipf_s))
    tseeds = rng.integers(0, 2**31 - 1, n)
    r = _rotation(n, seed)
    return np.roll(classes, -r), np.roll(tseeds, -r)


def arrival_offsets(n: int, seconds: float, seed: int) -> np.ndarray:
    """``n`` arrival times in ``[0, seconds)``: one fixed sequence of
    Poisson gaps scaled to the window, rotated with the tenants, so each
    tenant keeps the gap before it and the queueing it meets."""
    gaps = np.random.default_rng(0).exponential(size=n + 1)
    gaps = gaps[:n] / gaps.sum() * seconds   # the rest of the window: the tail
    return np.cumsum(np.roll(gaps, -_rotation(n, seed)))


class ServiceLoad(_Detector):
    """Open loop of tenants through the search service's request handler."""

    def __init__(self, cfg: dict, mix: dict, arrays, seed: int,
                 root: str = detectors.ROOT):
        from repro.core.exsample import detector_chunk

        self.cfg, self.mix, self.arrays, self.seed = cfg, mix, arrays, seed
        self.inst, self.chunks = repository.to_device(arrays)
        self.num_classes = int(cfg["repository"]["num_classes"])
        self._init_detector(root)
        sv = mix["service"]
        self.detector_width = detector_chunk(sv["slots_per_batch"] * sv["cohorts"])
        self.tenants = []
        self.service = self.det = None
        self.window_end = 0.0
        self._driver0 = {}

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.core import init_carry_multi, init_state
        from repro.serve.service import SearchService
        from repro.sim.costmodel import CostRates

        sv, s = self.mix["service"], self.cfg["sampler"]
        self.det = self.det_mod.program(self.det_cfg, self.inst, query_class=None)
        select = self.det_mod.program_select(
            self.det_cfg, self.inst, list(range(self.num_classes)))
        proto = init_carry_multi(
            init_state(self.chunks.length, alpha0=s["alpha0"], beta0=s["beta0"]),
            self._matcher(),
            jnp.stack([jax.random.PRNGKey(0)]),
        )
        self.service = SearchService(
            proto, self.chunks, self.det, select=select,
            budget_s=float("inf"), rates=CostRates(),
            cohorts=sv["cohorts"], num_workers=sv["workers"],
            max_steps=sv["max_steps"],
            cache_frames=self.arrays.total_frames if sv["cache"] else 0,
            slots_per_batch=sv["slots_per_batch"],
        )
        self.service.start()
        warm = np.random.default_rng(WARM_KEY).integers(
            0, 2**31 - 1, sv["warmup_tenants"])
        names = []
        for i, ts in enumerate(warm):
            names.append(f"warm{i}")
            self._submit(names[-1], i % self.num_classes, int(ts))
        self._wait(names, deadline_s=900.0)

    def _submit(self, name: str, cls: int, tseed: int) -> dict:
        from repro.launch.serve_search import handle_request

        resp = handle_request(self.service, {
            "op": "submit", "tenant": name, "class": cls, "seed": tseed,
            "plan": self.mix["plan"],
        })
        if not resp.get("ok"):
            raise RuntimeError(f"tenant {name} not admitted: {resp}")
        return resp

    def _wait(self, names, deadline_s: float) -> bool:
        t_end = time.monotonic() + deadline_s
        while time.monotonic() < t_end:
            if all(self.service.tenants[n].state == "finished" for n in names):
                return True
            time.sleep(0.005)
        return False

    def window(self, seconds: float, tick=None, rate: float | None = None,
               prefix: str = "t") -> None:
        rate = self.mix["rate_per_s"] if rate is None else rate
        n = int(round(rate * seconds))
        offs = arrival_offsets(n, seconds, self.seed)
        classes, tseeds = tenant_set(n, self.num_classes, self.mix["zipf_s"], self.seed)
        self._driver0 = self._detector_stats()
        t0 = time.monotonic()
        for i in range(n):
            due = t0 + float(offs[i])
            self._sleep_until(due, t0, tick)
            name = f"{prefix}{i}"
            with _span("bench.submit"):
                self._submit(name, int(classes[i]), int(tseeds[i]))
            self.tenants.append({"name": name, "due": due, "sent": time.monotonic(),
                                 "cls": int(classes[i]), "tseed": int(tseeds[i])})
        self._sleep_until(t0 + seconds, t0, tick)
        self.window_end = time.monotonic()
        self.run_s = self.window_end - t0

    @staticmethod
    def _sleep_until(due: float, t0: float, tick) -> None:
        """Wait in short steps, each its own ``bench.wait`` span, so that a
        trace begun mid-wait still sees what the generator was doing."""
        while (now := time.monotonic()) < due:
            if tick:
                tick(now - t0)
            with _span("bench.wait"):
                time.sleep(min(due - now, 0.002))

    def drain(self, deadline_s: float) -> None:
        """Wait, after the window, for the tenants still running."""
        self._wait([t["name"] for t in self.tenants], deadline_s)

    def _detector_stats(self) -> dict:
        st = self.service.driver.stats
        return {k: int(st[k]) for k in ("detector_invocations", "detector_lanes")}

    def check_detector(self):
        return self.det

    def counters(self) -> dict:
        lat, wait, lag, failed = [], [], [], 0
        for t in self.tenants:
            tenant = self.service.tenants[t["name"]]
            row = tenant.row_obj
            lag.append(t["sent"] - t["due"])
            if tenant.state != "finished":
                failed += 1
                continue
            lat.append(row.finished_s - t["due"])
            wait.append(row.admitted_s - t["due"])
        since = {k: v - self._driver0[k] for k, v in self._detector_stats().items()}
        return {
            "window_s": self.run_s, "attempted": len(self.tenants),
            "failed": failed, "queries_done": len(lat),
            "latency_s": lat, "admission_wait_s": wait, "arrival_lag_s": lag,
            **since,
        }

    def sample(self, k: int):
        rng = np.random.default_rng([self.seed, 4])
        outcomes = []
        for t in self.tenants:
            tenant = self.service.tenants[t["name"]]
            if tenant.state != "finished":
                continue
            c = tenant.row_obj.carry
            outcomes.append({"tseed": t["tseed"], "cls": t["cls"],
                             "step": int(c.step), "results": int(c.results),
                             "n": np.asarray(c.sampler.n), "n1": np.asarray(c.sampler.n1)})
        picked = _pick(rng, outcomes, k)
        import jax

        plan = self.mix["plan"]
        return [
            (self._query(
                key=np.asarray(jax.random.PRNGKey(p["tseed"])), query_class=p["cls"],
                cohorts=self.mix["service"]["cohorts"],
                result_limit=int(plan["result_limit"]), max_steps=int(plan["max_steps"]),
                method="exact", all_classes=True), p)
            for p in picked
        ]

    def close(self) -> None:
        if self.service is not None:
            self.service.stop()
            self.service = None


LOADS = {"batch": BatchLoad, "service": ServiceLoad}
