"""Plain reference for ``correct``: one ExSample query replayed frame by frame.

The paper's Algorithm 1 as the program defines it, written out again in
numpy, one frame at a time, with no batching, dedup, cache, kernel or
sharding: per round, Thompson-sample ``cohorts`` chunks from round-start
statistics, take each chunk's next frame in random+ order, detect it with
the reference side of the configuration's detector (``bench/detectors``),
match the detections against the query's results (same video, within
``time_gate`` frames, IoU at least ``iou_thresh`` or, where
``feat_thresh`` is above -1, feature cosine at least that; best score
first), and update N1 and n per chunk (paper §3.4 cross-chunk rule
included).  Only the random numbers come from JAX (``jax.random``
on the same keys), because the keys are part of the query.

Two float32 computations of the same score may order two chunks
differently when they lie within rounding of each other, and so may an
IoU or a cosine within rounding of its threshold or of another entry's,
or a detector's decision that its reference side reports.  The replay
marks a query *ambiguous* at the first such step and stops: an ambiguous
query is left out of the comparison rather than guessed at.  ``TIE`` is
that rounding margin.

A query with ``shards > 1`` is replayed under the merge schedule of
DESIGN.md section 8 instead (``_replay_mesh``): each shard draws its own
normals for its slice of the chunks, every shard folds its share of the
round's cohorts against its own copy of the result memory, and every
``sync_every`` rounds the statistics add up, the duplicate d1
decrements are added back and the copies merge.

``precision="bfloat16"`` computes the Thompson choice in bfloat16 (its
statistics, the Gamma shapes or the normals, and the scores): the
control that the comparison has to reject.
"""
from __future__ import annotations

import copy
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

TIE = 1e-5   # relative margin within which float32 orderings may differ


@dataclasses.dataclass(frozen=True)
class Query:
    """One query as its plan and the cell define it."""

    key: np.ndarray          # u32[2] raw PRNG key
    query_class: int
    cohorts: int
    result_limit: int
    max_steps: int
    method: str              # "exact" (Gamma draws) or "wilson_hilferty"
    all_classes: bool        # class-agnostic detector + per-query select
    max_dets: int = 16
    iou_thresh: float = 0.5
    time_gate: int = 900
    alpha0: float = 0.1
    beta0: float = 1.0
    shards: int = 1          # chips the statistics are sharded over (section 8)
    sync_every: int = 1      # rounds between two merges of the shards
    feat_thresh: float = -1.0  # feature cosine that matches; -1 is off
    detector: str = "oracle"   # bench/detectors/<detector>.py
    detector_cfg: dict = dataclasses.field(default_factory=dict, hash=False)


@dataclasses.dataclass
class Outcome:
    step: int
    results: int
    n: np.ndarray            # i64[M] frames sampled per chunk
    n1: np.ndarray           # i64[M] N1 per chunk
    ambiguous: str = ""      # why the replay could not decide, if it stopped


@partial(jax.jit, static_argnames=("cohorts", "method"))
def _draws(key, alpha, *, cohorts: int, method: str):
    """(next key, draws [C, M]) in ``alpha``'s precision."""
    key_next, k_choice, _ = jax.random.split(key, 3)
    shape = (cohorts, alpha.shape[0])
    if method == "exact":
        d = jax.random.gamma(k_choice, jnp.broadcast_to(alpha, shape))
    else:
        d = jax.random.normal(k_choice, shape, alpha.dtype)
    return key_next, d


@partial(jax.jit, static_argnames=("cohorts", "shards"))
def _draws_sharded(key, alpha, *, cohorts: int, shards: int):
    """(next key, normals [C, M]) of a round on ``shards`` shards: shard s
    draws ``[C, M/S]`` for its own slice of the chunks from
    ``fold_in(k_choice, s)``, and the slices lie side by side in shard
    order, so chunk c's column is its owner's draw."""
    key_next, k_choice, _ = jax.random.split(key, 3)
    local = alpha.shape[0] // shards
    d = [jax.random.normal(jax.random.fold_in(k_choice, s), (cohorts, local), alpha.dtype)
         for s in range(shards)]
    return key_next, jnp.concatenate(d, axis=1)


def _bit_reverse(x: int, bits: int) -> int:
    return int(format(x, f"0{bits}b")[::-1], 2) if bits > 0 else 0


def randomplus_frame(a, c: int, k: int) -> int:
    """Global frame id of chunk ``c``'s ``k``-th random+ sample."""
    length = int(a.chunk_length[c])
    raw = k % max(int(a.chunk_pow2[c]), 1)
    cand = _bit_reverse(raw, int(a.chunk_bits[c]))
    off = cand if cand < length else raw
    return int(a.chunk_start[c]) + (off + int(a.chunk_rotation[c])) % max(length, 1)


def _params(q: Query, n1, n, dt):
    """Gamma shape and rate of every chunk (paper Eq. 10) in ``dt``."""
    alpha = np.maximum(n1.astype(dt) + dt(q.alpha0), dt(q.alpha0 * 0.5))
    return alpha, n.astype(dt) + dt(q.beta0)


def _scores(q: Query, n1, n, frames, draws, precision: str):
    """Thompson scores f[C, M] of every chunk, -inf where exhausted: from
    float32 statistics in float64 arithmetic, or all in bfloat16."""
    if precision == "bfloat16":
        import ml_dtypes

        dt = ml_dtypes.bfloat16
        alpha, beta = _params(q, n1, n, dt)
    else:
        dt = np.float64
        alpha, beta = (x.astype(dt) for x in _params(q, n1, n, np.float32))
    d = draws.astype(dt)
    if q.method == "exact":
        s = d / beta
    else:
        one, nine, three = dt(1), dt(9), dt(3)
        cc = one - one / (nine * alpha) + d / (three * np.sqrt(alpha))
        s = alpha * np.maximum(cc, dt(0)) ** 3 / beta
    s = s.astype(np.float64)
    return np.where(n >= frames, -np.inf, s)


def _iou(a, b):
    """float32 IoU [D, R], the same operations as the program's matcher."""
    z = np.float32(0)
    area_a = np.maximum(a[:, 2] - a[:, 0], z) * np.maximum(a[:, 3] - a[:, 1], z)
    area_b = np.maximum(b[:, 2] - b[:, 0], z) * np.maximum(b[:, 3] - b[:, 1], z)
    lt = np.maximum(a[:, None, :2], b[None, :, :2])
    rb = np.minimum(a[:, None, 2:], b[None, :, 2:])
    wh = np.maximum(rb - lt, z)
    inter = wh[..., 0] * wh[..., 1]
    union = area_a[:, None] + area_b[None, :] - inter
    return inter / np.maximum(union, np.float32(1e-9))


def _cosine(a, b):
    """float32 cosine similarity [D, R], the program matcher's operations."""
    eps = np.float32(1e-9)
    an = a / np.maximum(np.linalg.norm(a, axis=-1, keepdims=True), eps)
    bn = b / np.maximum(np.linalg.norm(b, axis=-1, keepdims=True), eps)
    return an @ bn.T


class _Results:
    """The query's result memory: first sightings with their counts
    (features only where the matcher uses them)."""

    def __init__(self):
        self.boxes = np.zeros((0, 4), np.float32)
        self.feats = np.zeros((0, 0), np.float32)
        self.video = np.zeros(0, np.int64)
        self.frame = np.zeros(0, np.int64)
        self.chunk = np.zeros(0, np.int64)
        self.seen = np.zeros(0, np.int64)


def _match(q: Query, mem: _Results, boxes, feats, video: int, f: int, c: int):
    """Match one frame; returns (d0, d1 local, cross homes, why ambiguous)."""
    r = mem.boxes.shape[0]
    best = np.full(boxes.shape[0], -1)
    by_feat = q.feat_thresh > -1.0
    if r and boxes.shape[0]:
        iou = _iou(boxes, mem.boxes)
        near = (mem.video == video) & (np.abs(mem.frame - f) <= q.time_gate)
        thr = np.float32(q.iou_thresh)
        if np.any(near[None, :] & (np.abs(iou - thr) <= TIE * thr)):
            return 0, 0, [], f"IoU within rounding of the threshold at frame {f}"
        ok = near[None, :] & (iou >= thr)
        score = iou
        if by_feat:
            sim = _cosine(feats, mem.feats)
            fthr = np.float32(q.feat_thresh)
            if np.any(near[None, :] & (np.abs(sim - fthr) <= TIE)):
                return 0, 0, [], f"cosine within rounding of the threshold at frame {f}"
            ok |= near[None, :] & (sim >= fthr)
            score = np.maximum(iou, sim)
        for d in range(boxes.shape[0]):
            cand = np.flatnonzero(ok[d])
            if cand.size == 0:
                continue
            vals = score[d, cand]
            top = cand[np.argmax(vals)]
            if cand.size > 1:
                two = np.sort(vals)[-2:]
                if two[1] - two[0] <= TIE * abs(two[1]):
                    return 0, 0, [], f"two results within rounding at frame {f}"
            best[d] = top
    matched = best >= 0
    bump = np.bincount(best[matched], minlength=r) if r else np.zeros(0, np.int64)
    went = (mem.seen == 1) & (mem.seen + bump >= 2)
    crossed = went & (mem.chunk != c)
    mem.seen = mem.seen + bump
    new = np.flatnonzero(~matched)
    k = new.size
    if k:
        mem.boxes = np.concatenate([mem.boxes, boxes[new]])
        nf = feats[new] if by_feat else np.zeros((k, 0), np.float32)
        mem.feats = np.concatenate([mem.feats.reshape(r, nf.shape[1]), nf])
        mem.video = np.concatenate([mem.video, np.full(k, video)])
        mem.frame = np.concatenate([mem.frame, np.full(k, f)])
        mem.chunk = np.concatenate([mem.chunk, np.full(k, c)])
        mem.seen = np.concatenate([mem.seen, np.ones(k, np.int64)])
    d1_local = int(went.sum()) - int(crossed.sum())
    return k, d1_local, list(mem.chunk[np.flatnonzero(crossed)]), ""


def detector(a, q: Query, root: str | None = None):
    """The reference side of ``q``'s detector over repository ``a``."""
    from bench import detectors

    mod = detectors.module(q.detector, root or detectors.ROOT)
    return mod.reference(q.detector_cfg, a)


def _prefetch(det, frames) -> None:
    if frames and hasattr(det, "prefetch"):
        det.prefetch(frames)


def replay(a, q: Query, precision: str = "float32", det=None) -> Outcome:
    """Run query ``q`` over repository ``a`` (``data.repository.Arrays``),
    detecting with ``det`` (``detector(a, q)`` where not given)."""
    det = detector(a, q) if det is None else det
    if q.shards > 1:
        return _replay_mesh(a, q, precision, det)
    m = a.num_chunks
    frames = a.chunk_length.astype(np.int64)
    n1 = np.zeros(m, np.int64)
    n = np.zeros(m, np.int64)
    step = results = 0
    mem = _Results()
    key = jnp.asarray(q.key, jnp.uint32)
    method = "exact" if q.method == "exact" else "normal"
    if precision == "bfloat16":
        import ml_dtypes

        pdt = ml_dtypes.bfloat16
    else:
        pdt = np.float32

    def out(why=""):
        return Outcome(step, results, n.copy(), n1.copy(), why)

    while results < q.result_limit and step < q.max_steps and not np.all(n >= frames):
        alpha = jnp.asarray(_params(q, n1, n, pdt)[0])
        key, draws = _draws(key, alpha, cohorts=q.cohorts, method=method)
        s = _scores(q, n1, n, frames, np.asarray(draws), precision)
        choice = np.argmax(s, axis=1)
        if precision == "float32":
            top2 = np.sort(s, axis=1)[:, -2:]
            close = top2[:, 1] - top2[:, 0] <= TIE * np.abs(top2[:, 1])
            if np.any(close):
                return out(f"Thompson draws within rounding in round at step {step}")
        picks = []
        for g, c in enumerate(choice):
            c = int(c)
            occ = int(np.sum(choice[:g] == c))
            picks.append((c, randomplus_frame(a, c, int(n[c]) + occ)))
        _prefetch(det, [f for _, f in picks])
        for c, f in picks:
            boxes, feats, why = det.detect(f, q)
            if why:
                return out(why)
            d0, d1_local, homes, why = _match(
                q, mem, boxes, feats, int(a.chunk_video[c]), f, c
            )
            if why:
                return out(why)
            n1[c] += d0 - d1_local
            n[c] += 1
            for h in homes:
                n1[h] -= 1
            results += d0
            step += 1
    return out()


def _add_back(snap: _Results, mems, size: int) -> np.ndarray:
    """N1 to give back per chunk at a merge: where k shards each took one
    snapshot entry from seen once to seen twice, its home chunk was
    decremented k times for one transition, so k - 1 go back."""
    r0 = snap.seen.size
    k = sum(((snap.seen == 1) & (mem.seen[:r0] >= 2)).astype(np.int64) for mem in mems)
    back = np.zeros(size, np.int64)
    np.add.at(back, snap.chunk[k > 0], k[k > 0] - 1)
    return back


def _merge(snap: _Results, mems) -> _Results:
    """The shards' copies merged: the snapshot's entries with every copy's
    sightings since the snapshot added up, then each copy's new entries,
    in shard order.  Two shards that inserted one object both keep it."""
    r0 = snap.seen.size
    out = _Results()
    seen = snap.seen + sum(mem.seen[:r0] - snap.seen for mem in mems)
    out.seen = np.concatenate([seen] + [mem.seen[r0:] for mem in mems])
    for name in ("boxes", "video", "frame", "chunk"):
        setattr(out, name, np.concatenate(
            [getattr(snap, name)] + [getattr(mem, name)[r0:] for mem in mems]))
    f = max(mem.feats.shape[1] for mem in mems)
    out.feats = np.concatenate([x.reshape(x.shape[0], f) for x in
                                [snap.feats] + [mem.feats[r0:] for mem in mems]])
    return out


def _rival(q: Query, n1, n, draws, s, choice, live) -> bool:
    """Whether a live cohort's winner has a rival within ``TIE`` whose
    inputs differ from its own.  A rival with the same float32 statistics
    and the same draw scores the same in any precision, and both the
    program's argmax and the replay's take the lower index: float32
    normals lie on a grid in their tails, so among thousands of chunks
    that are yet unsampled such exact ties are common and decided."""
    rows = np.arange(s.shape[0])
    top = s[rows, choice]
    close = s >= (top - TIE * np.abs(top))[:, None]
    alpha, beta = _params(q, n1, n, np.float32)
    same = ((draws == draws[rows, choice][:, None]) & (alpha == alpha[choice][:, None])
            & (beta == beta[choice][:, None]))
    return bool(np.any(close[live] & ~same[live]))


def _replay_mesh(a, q: Query, precision: str, det) -> Outcome:
    """``replay`` under the section 8 merge schedule on ``q.shards`` shards.

    The chunks are padded with exhausted ones to a multiple of the shard
    count; shard s owns chunks ``[s·M/S, (s+1)·M/S)`` and folds cohorts
    ``[s·C/S, (s+1)·C/S)`` of each round.  Within a window of
    ``q.sync_every`` rounds a chunk's statistics, as its owner sees them,
    are the window's start plus the owner's own updates; a pick's random+
    rank is its owner's n plus the earlier picks of the window made on
    other shards plus its occurrence within the round.  The query stops,
    at the end of a window, as ``replay`` stops at the end of a round.
    The global choice is the argmax of every shard's scores side by side,
    so a choice is undecided by ``_rival`` over the whole row.
    """
    shards, m = q.shards, a.num_chunks
    per = q.cohorts // shards
    mp = -(-m // shards) * shards
    owner = np.arange(mp) // (mp // shards)
    cols = np.arange(mp)
    frames = np.zeros(mp, np.int64)
    frames[:m] = a.chunk_length
    n1 = np.zeros(mp, np.int64)
    n = np.zeros(mp, np.int64)
    n[m:] = 1                      # padding: sampled once, no frames: exhausted
    step = results = 0
    snap = _Results()
    key = jnp.asarray(q.key, jnp.uint32)
    if precision == "bfloat16":
        import ml_dtypes

        pdt = ml_dtypes.bfloat16
    else:
        pdt = np.float32

    def out(why=""):
        return Outcome(step, results, n[:m].copy(), n1[:m].copy(), why)

    while results < q.result_limit and step < q.max_steps and not np.all(n[:m] >= frames[:m]):
        dn1 = np.zeros((shards, mp), np.int64)
        dn = np.zeros((shards, mp), np.int64)
        foreign = np.zeros(mp, np.int64)
        mems = [copy.copy(snap) for _ in range(shards)]
        w_step = w_results = 0
        for _ in range(q.sync_every):
            vn1, vn = n1 + dn1[owner, cols], n + dn[owner, cols]
            alpha = jnp.asarray(_params(q, vn1, vn, pdt)[0])
            key, draws = _draws_sharded(key, alpha, cohorts=q.cohorts, shards=shards)
            draws = np.asarray(draws)
            s = _scores(q, vn1, vn, frames, draws, precision)
            choice = np.argmax(s, axis=1)
            live = np.isfinite(s[np.arange(q.cohorts), choice])
            if precision == "float32" and _rival(q, vn1, vn, draws, s, choice, live):
                return out(f"Thompson draws within rounding in round at step {step}")
            rank = np.zeros(q.cohorts, np.int64)
            for g, c in enumerate(choice):
                occ = int(np.sum(live[:g] & (choice[:g] == c)))
                rank[g] = vn[c] + foreign[c] + occ
            for g, c in enumerate(choice):
                if live[g] and g // per != owner[c]:
                    foreign[c] += 1
            picked = {g: randomplus_frame(a, int(choice[g]), int(rank[g]))
                      for g in range(q.cohorts) if live[g]}
            _prefetch(det, list(picked.values()))
            for sh in range(shards):
                for g in range(sh * per, (sh + 1) * per):
                    if not live[g]:
                        continue
                    c, f = int(choice[g]), picked[g]
                    boxes, feats, why = det.detect(f, q)
                    if why:
                        return out(why)
                    d0, d1_local, homes, why = _match(
                        q, mems[sh], boxes, feats, int(a.chunk_video[c]), f, c
                    )
                    if why:
                        return out(why)
                    dn1[sh, c] += d0 - d1_local
                    dn[sh, c] += 1
                    for h in homes:
                        dn1[sh, h] -= 1
                    w_results += d0
                    w_step += 1
        n1 = n1 + dn1.sum(axis=0) + _add_back(snap, mems, mp)
        n = n + dn.sum(axis=0)
        snap = _merge(snap, mems)
        step += w_step
        results += w_results
    return out()


def differences(program: dict, ref: Outcome) -> list[str]:
    """Names of the quantities on which the program's query (``step``,
    ``results``, ``n``, ``n1`` as numpy) and the replay disagree."""
    diff = []
    if int(program["step"]) != ref.step:
        diff.append("step")
    if int(program["results"]) != ref.results:
        diff.append("results")
    for name in ("n", "n1"):
        if not np.array_equal(np.asarray(program[name]).astype(np.int64),
                              getattr(ref, name)):
            diff.append(name)
    return diff
