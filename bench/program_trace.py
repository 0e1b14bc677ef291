"""What the program's own spans, scopes and stamps say about one traced run.

  python3 bench/program_trace.py --workload dashcam.service --seed 7 --seconds 51

Runs a cell as ``bench/run.py --trace 1`` does (set-up, then the window,
its last seconds profiled), then prints one JSON line of the per-layer
numbers the program's instrumentation makes readable, and the device idle
time attributed to the program's host spans:

* ``stage_share.<stage>`` -- device seconds of the ops under each stage
  scope (``jax.named_scope`` ``choose``, ``detect``, ``dedup_cache``,
  ``match``, ``update``) over the traced window; ``unscoped_share`` the
  ops under none.  Ops that run others (loops) are left out, as
  ``bench/trace.py`` leaves them out of op totals.
* ``detector_lane_use`` -- detector invocations over detector lanes
  (``SearchStats``), batch cells.
* ``round_p50_s``, ``slot_wait_p90_s`` -- from the slot rounds merged in
  the window (the driver's ``recent_rounds`` stamps), service cells.
* ``pump_busy_share`` -- the union of the ``exsample.*`` spans on the
  service pump's thread over the traced window.
* ``idle_gaps`` -- each device idle gap goes to the innermost
  ``exsample.*`` span over its middle on any thread, else to the
  ``bench.*`` span ``bench/trace.py`` names.
* ``end_to_end`` -- the cell's end-to-end metrics in this traced run,
  against an untraced ``bench/run.py`` run for what tracing costs.

``bench/run.py`` reads none of this yet; ``PERF.md`` says which edit to
its trace reduction and loads would.  The trace is read with the
``XSpace`` protobuf itself, since ``jax.profiler.ProfileData`` does not
give an op's metadata, where the stage scope is.
"""
from __future__ import annotations

import argparse
import collections
import functools
import json
import os
import re
import shutil
import sys
import tempfile
import time

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import trace  # noqa: E402
from bench.stats import percentile  # noqa: E402

STAGES = ("choose", "detect", "dedup_cache", "match", "update")
PROGRAM_PREFIX = "exsample."
PUMP_SPANS = ("exsample.merge", "exsample.reap", "exsample.admit")
SCOPE_STAT = "tf_op"    # the op-metadata stat that holds an op's name path
HLO_PLANE, HLO_STAT = "/host:metadata", "Hlo Proto"   # each program's HloProto
HARVEST_S = 0.5         # how often the window copies the driver's round stamps


@functools.cache
def _messages():
    """The ``XSpace`` message class of the profiler's ``xplane.proto`` and
    XLA's ``HloProto``, built in a private descriptor pool from the fields
    this module reads."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    T = descriptor_pb2.FieldDescriptorProto
    scalar = {"int64": T.TYPE_INT64, "uint64": T.TYPE_UINT64,
              "string": T.TYPE_STRING, "bytes": T.TYPE_BYTES}
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")

    def msg(name, *fields, oneof=()):
        """Field kinds: a scalar or message name, "*" before it for a
        repeated field, "map:" before a message name for an int64 map."""
        m = fdp.message_type.add(name=name)
        for fname, number, kind in fields:
            f = m.field.add(name=fname, number=number, label=T.LABEL_OPTIONAL)
            if kind.startswith(("*", "map:")):
                f.label = T.LABEL_REPEATED
            kind = kind.lstrip("*")
            if kind in scalar:
                f.type = scalar[kind]
                continue
            f.type = T.TYPE_MESSAGE
            f.type_name = ".bench_xplane." + kind
            if kind.startswith("map:"):
                e = m.nested_type.add(name=fname.title().replace("_", "") + "Entry")
                e.options.map_entry = True
                e.field.add(name="key", number=1, type=T.TYPE_INT64, label=T.LABEL_OPTIONAL)
                e.field.add(name="value", number=2, type=T.TYPE_MESSAGE,
                            label=T.LABEL_OPTIONAL, type_name=".bench_xplane." + kind[4:])
                f.type_name = f".bench_xplane.{name}.{e.name}"
        for f in m.field:
            if f.name in oneof:
                f.oneof_index = 0
        if oneof:
            m.oneof_decl.add(name="value")

    msg("XStat", ("metadata_id", 1, "int64"), ("uint64_value", 3, "uint64"),
        ("int64_value", 4, "int64"), ("str_value", 5, "string"),
        ("bytes_value", 6, "bytes"), ("ref_value", 7, "uint64"),
        oneof=("uint64_value", "int64_value", "str_value", "bytes_value", "ref_value"))
    msg("XEvent", ("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
        ("duration_ps", 3, "int64"), ("stats", 4, "*XStat"))
    msg("XLine", ("id", 1, "int64"), ("name", 2, "string"), ("timestamp_ns", 3, "int64"),
        ("events", 4, "*XEvent"))
    msg("XEventMetadata", ("name", 2, "string"), ("display_name", 4, "string"),
        ("stats", 5, "*XStat"))
    msg("XStatMetadata", ("name", 2, "string"))
    msg("XPlane", ("name", 2, "string"), ("lines", 3, "*XLine"),
        ("event_metadata", 4, "map:XEventMetadata"),
        ("stat_metadata", 5, "map:XStatMetadata"))
    msg("XSpace", ("planes", 1, "*XPlane"))
    msg("OpMetadata", ("op_name", 2, "string"))
    msg("HloInstructionProto", ("name", 1, "string"), ("metadata", 7, "OpMetadata"),
        ("called_computation_ids", 38, "*int64"))
    msg("HloComputationProto", ("instructions", 2, "*HloInstructionProto"),
        ("id", 5, "int64"))
    msg("HloModuleProto", ("computations", 3, "*HloComputationProto"))
    msg("HloProto", ("hlo_module", 1, "HloModuleProto"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return tuple(message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane." + n)) for n in ("XSpace", "HloProto"))


def _stat_str(stat, stat_names):
    if stat.HasField("str_value"):
        return stat.str_value
    if stat.HasField("ref_value"):
        return stat_names.get(stat.ref_value, "")
    return None


def _fusion_stages(hlo: bytes) -> dict:
    """{instruction: stage} over one compiled program's instructions that
    call computations (fusions): the stage that most of the instructions
    inside carry.  XLA leaves some fusions without metadata of their own
    (a scatter it rewrote, say) while the instructions they fuse keep it."""
    proto = _messages()[1]()
    proto.ParseFromString(hlo)
    comps = {c.id: c for c in proto.hlo_module.computations}
    out = {}
    for comp in comps.values():
        for ins in comp.instructions:
            votes = collections.Counter(
                stage_of(i.metadata.op_name) for cid in ins.called_computation_ids
                for i in comps[cid].instructions)
            votes.pop("", None)
            if votes:
                out[ins.name] = votes.most_common(1)[0][0]
    return out


def extract(path: str, device_plane: str = trace.DEVICE_PLANE,
            op_line: str = trace.OP_LINE) -> dict:
    """{"devices": {plane: [[op, start_ns, end_ns, scope], ...]},
    "spans": [[name, start_ns, end_ns, thread, {arg: value}], ...]}: every
    device op with the name path of its metadata (``SCOPE_STAT``; for a
    fusion whose path names no stage, the stage its fused instructions
    carry, from the program's ``HloProto`` in the trace), and every ``bench.*``
    and ``exsample.*`` host span with its thread (plane line) and integer
    arguments."""
    space = _messages()[0]()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    programs, fused = {}, {}
    for plane in space.planes:
        if plane.name == HLO_PLANE:
            for pid, md in plane.event_metadata.items():
                for st in md.stats:
                    if plane.stat_metadata[st.metadata_id].name == HLO_STAT:
                        programs[pid % 2**64] = st.bytes_value

    def scope(md, stat_names):
        stats = {stat_names.get(st.metadata_id): st for st in md.stats}
        path = ""
        if SCOPE_STAT in stats:
            path = _stat_str(stats[SCOPE_STAT], stat_names) or ""
        pid = stats["program_id"].uint64_value if "program_id" in stats else None
        if stage_of(path) or pid not in programs:
            return path
        if pid not in fused:
            fused[pid] = _fusion_stages(programs[pid])
        return fused[pid].get(md.display_name, path)

    devices, spans = {}, []
    for plane in space.planes:
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        meta = plane.event_metadata
        is_dev = re.match(device_plane, plane.name) is not None
        scope_of = {}
        for line in plane.lines:
            take_ops = is_dev and re.match(op_line, line.name) is not None
            thread = f"{plane.name}/{line.name}#{line.id}"
            base = float(line.timestamp_ns)
            for e in line.events:
                md = meta[e.metadata_id]
                # whole nanoseconds, as jax.profiler.ProfileData gives them
                start = base + e.offset_ps // 1000
                end = start + e.duration_ps // 1000
                if take_ops and end > start:
                    if e.metadata_id not in scope_of:
                        scope_of[e.metadata_id] = scope(md, stat_names)
                    devices.setdefault(plane.name, []).append(
                        [md.name, start, end, scope_of[e.metadata_id]])
                elif md.name.startswith((trace.SPAN_PREFIX, PROGRAM_PREFIX)):
                    args = {stat_names.get(st.metadata_id, ""): st.int64_value
                            for st in e.stats if st.HasField("int64_value")}
                    spans.append([md.name, start, end, thread, args])
    return {"devices": devices, "spans": spans}


def stage_of(scope: str) -> str:
    """The innermost stage scope in an op's name path ("jit(f)/.../update/
    scatter-add:" in a TPU trace), "" for none."""
    return next((p for p in reversed(scope.split("/")) if p in STAGES), "")


def _innermost(spans, mids):
    """For each sorted time in ``mids``, the shortest span of ``spans``
    (sorted by start) that covers it, or None."""
    out, active, i = [], [], 0
    for t in mids:
        while i < len(spans) and spans[i][1] <= t:
            active.append(spans[i])
            i += 1
        active = [s for s in active if s[2] >= t]
        out.append(min(active, key=lambda s: s[2] - s[1]) if active else None)
    return out


def reduce(ev: dict, top: int = 10) -> dict:
    """Stage seconds, the pump's busy seconds and the attributed idle gaps
    inside the ``bench.window`` span (seconds averaged over devices)."""
    windows = [s for s in ev["spans"] if s[0] == trace.WINDOW_SPAN]
    every = [x for ops in ev["devices"].values() for x in ops]
    lo, hi = ((windows[0][1], windows[0][2]) if windows else
              (min((x[1] for x in every), default=0.0),
               max((x[2] for x in every), default=0.0)))
    n_dev = max(len(ev["devices"]), 1)
    stages, unscoped = collections.Counter(), collections.Counter()
    gaps = []
    for evs in ev["devices"].values():
        merged = trace._union(trace._clip([[s, e] for _, s, e, _ in evs], lo, hi))
        for name, s, e, scope in evs:
            c = min(e, hi) - max(s, lo)
            if c > 0 and not trace.CONTAINER.search(name):
                stage = stage_of(scope)
                stages[stage] += c * 1e-9 / n_dev
                if not stage:
                    unscoped[(name[:trace.NAME_CHARS], scope)] += c * 1e-9 / n_dev
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(0.5 * (g0 + g1), g1 - g0)
                 for g0, g1 in zip(edges[::2], edges[1::2]) if g1 > g0]
    gaps.sort()
    prog = sorted((s for s in ev["spans"] if s[0].startswith(PROGRAM_PREFIX)),
                  key=lambda s: s[1])
    host = sorted((s[:3] for s in ev["spans"]
                   if s[0].startswith(trace.SPAN_PREFIX) and s[0] != trace.WINDOW_SPAN),
                  key=lambda s: s[1])
    starts = [s[1] for s in host]
    by_span = collections.Counter()
    for (mid, width), cover in zip(gaps, _innermost(prog, [m for m, _ in gaps])):
        name = cover[0] if cover else trace._covering(host, starts, mid)
        by_span[name] += width * 1e-9 / n_dev
    threads = collections.defaultdict(set)
    for s in prog:
        threads[s[3]].add(s[0])
    pump = [s[1:3] for s in prog if threads[s[3]] & set(PUMP_SPANS)]
    return {
        "window_s": (hi - lo) * 1e-9,
        "stages_s": {k: stages[k] for k in STAGES if k in stages},
        "unscoped_s": stages[""],
        "unscoped_ops": [[k, v, path] for (k, path), v in unscoped.most_common(top)],
        "pump_busy_s": sum(e - s for s, e in trace._union(trace._clip(pump, lo, hi))) * 1e-9,
        "threads": {t: sorted(v) for t, v in threads.items()},
        "idle_gaps": [[k, v] for k, v in by_span.most_common(top)],
    }


def metrics(red: dict, counters: dict, rounds=None) -> dict:
    """The per-layer numbers of one traced run, None where there is
    nothing to read (a program without the scope, counter or stamps)."""
    w = red["window_s"]
    out = {}
    if red["stages_s"] and w > 0:
        for k in STAGES:
            out[f"stage_share.{k}"] = 100.0 * red["stages_s"].get(k, 0.0) / w
        out["unscoped_share"] = 100.0 * red["unscoped_s"] / w
    if counters.get("detector_lanes"):
        out["detector_lane_use"] = (
            100.0 * counters["detector_invocations"] / counters["detector_lanes"])
    if rounds:
        out["round_p50_s"] = percentile([m - i for i, _, _, m in rounds], 50)
        out["slot_wait_p90_s"] = percentile(
            [(t - i) + (m - d) for i, t, d, m in rounds], 90)
    if red["threads"] and w > 0 and any(
            set(PUMP_SPANS) & set(v) for v in red["threads"].values()):
        out["pump_busy_share"] = 100.0 * red["pump_busy_s"] / w
    return out


class RoundHarvest:
    """Copies the slot rounds a service driver merged, every ``HARVEST_S``
    seconds of the window, before its fixed-length history drops them."""

    def __init__(self, load):
        self.load, self.rounds, self.at = load, [], 0.0

    def poll(self, force: bool = False) -> None:
        svc = getattr(self.load, "service", None)
        recent = getattr(getattr(svc, "driver", None), "recent_rounds", None)
        now = time.monotonic()
        if recent is None or (not force and now - self.at < HARVEST_S):
            return
        self.at = now
        last = self.rounds[-1][3] if self.rounds else 0.0
        self.rounds += [r for r in recent() if r[3] > last]

    def in_window(self):
        if not self.rounds:
            return []
        end = self.load.window_end
        return [r for r in self.rounds if end - self.load.run_s <= r[3] <= end]


def run(argv=None, *, allow_cpu: bool = False, root: str | None = None) -> int:
    from bench import harness, load
    from bench.data import repository

    root = root or harness.ROOT
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default="", help="directory to keep the trace in")
    args = ap.parse_args(argv)
    spec = harness.load_json(root, "BENCHMARK.json")
    cell, cfg, mix = harness.lookup(spec, args.workload, root)
    try:
        harness.devices(cell["chips"], allow_cpu)
    except harness.NoChip as e:
        print(f"program_trace: {e}; nothing was run", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(root, "src"))
    if not allow_cpu:
        harness.enable_compile_cache()
    tdir = args.keep or tempfile.mkdtemp(prefix="program-trace-")
    driver = load.LOADS[mix["mode"]](cfg, mix, repository.generate(cfg["repository"]),
                                     args.seed)
    try:
        driver.setup()
        tracer = harness.Tracer(tdir, args.seconds, harness.TRACE_SECONDS)
        harvest = RoundHarvest(driver)

        def tick(elapsed):
            tracer.poll(elapsed)
            harvest.poll()

        try:
            driver.window(args.seconds, tick=tick)
        finally:
            tracer.stop()
        harvest.poll(force=True)
        if mix["mode"] == "service":
            driver.drain(deadline_s=mix["drain_s"])
        counters = driver.counters()
        records = getattr(driver, "records", [])
        lanes = [getattr(r["stats"], "detector_lanes", 0) for r in records]
        if records and all(lanes):
            counters["detector_lanes"] = sum(lanes)
        red = reduce(extract(trace.xplane_file(tdir)))
        # the cell's end-to-end numbers in this traced run, read as
        # bench/run.py reads them, for what tracing costs
        e2e = {m: harness.metric_module(m, root).value({"counters": counters})
               for m in harness.metric_names(spec, cell, False) if m != "setup_s"}
        result = {"metrics": metrics(red, counters, harvest.in_window()),
                  "end_to_end": e2e, **red}
    finally:
        driver.close()
        if not args.keep:
            shutil.rmtree(tdir, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run())
