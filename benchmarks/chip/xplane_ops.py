"""Device time per engine phase, read from the op metadata of a profiler
trace (`.xplane.pb`), and the idle gaps labelled by the program's own host
spans.

`trace_reduce.py` reads the trace through `jax.profiler.ProfileData`, which
gives each device op's name and interval but not its event metadata. There,
each TPU plane's `event_metadata` holds, per op, a `tf_op` stat: the op's
name-scope path in its program (`jit(_run_stream_body)/while/body/...
/wharf/mav/gather`). The program names each phase of its stream step with a
`wharf/<phase>` scope (src/repro/obs/trace.py), so the innermost `wharf/`
element of an op's `tf_op` is its phase.

This module decodes the raw trace bytes with a small protobuf wire-format
reader, no TensorFlow: of each TPU plane only the `event_metadata` and
`stat_metadata` maps, skipping every other field by its length. Field
numbers (tsl/profiler/protobuf/xplane.proto):

  XSpace          1 planes
  XPlane          2 name, 4 event_metadata (map: 1 key, 2 XEventMetadata),
                  5 stat_metadata (map: 1 key, 2 XStatMetadata)
  XEventMetadata  2 name, 5 stats (XStat)
  XStat           1 metadata_id, 5 str_value, 7 ref_value (a stat
                  metadata id whose name is the string)
  XStatMetadata   2 name

Each device op's self time in the window (trace_reduce.busy_and_self) goes
to the phase of its `tf_op`; an op with no `wharf/` element goes to
`unscoped`, and so does an op whose metadata names two different phases
(ops of two phases merged into one, or one HLO text in two programs), which
is logged.
"""
from __future__ import annotations

import collections
import gzip
import re
import sys

import trace_reduce

TF_OP = "tf_op"
SCOPE = re.compile(r"(?:^|/)wharf/([A-Za-z0-9_]+)")
UNSCOPED = "unscoped"
# host spans of the program (src/repro/obs/trace.py, serve/) that label idle
# gaps before the benchmark's own bench/ spans do
PROGRAM_SPANS = ("wharf/", "serve/")
TPU_PLANE = re.compile(r"/device:TPU:\d+$")


# ------------------------------------------------------ protobuf wire format


def _varint(buf, i: int) -> tuple:
    out, shift = 0, 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf, start: int = 0, end: int | None = None):
    """(field number, wire type, value) of each field of one message in
    buf[start:end]; a length-delimited value is a (start, end) pair."""
    i = start
    end = len(buf) if end is None else end
    while i < end:
        tag, i = _varint(buf, i)
        num, wt = tag >> 3, tag & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 1:
            v, i = bytes(buf[i:i + 8]), i + 8
        elif wt == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wt == 5:
            v, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"unsupported wire type {wt} at byte {i}")
        yield num, wt, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entries(buf, entries):
    """The XEventMetadata / XStatMetadata value spans of map entries."""
    for s, e in entries:
        for num, wt, v in fields(buf, s, e):
            if num == 2 and wt == 2:
                yield v


def plane_ops(buf, plane) -> dict:
    """Op name -> `tf_op` of one plane (only ops that carry the stat)."""
    ev_entries, st_entries = [], []
    for num, wt, v in fields(buf, *plane):
        if wt != 2:
            continue
        if num == 4:
            ev_entries.append(v)
        elif num == 5:
            st_entries.append(v)
    stat_names = {}
    for s, e in _map_entries(buf, st_entries):
        sid, name = None, ""
        for num, wt, v in fields(buf, s, e):
            if num == 1 and wt == 0:
                sid = v
            elif num == 2 and wt == 2:
                name = _text(buf, v)
        if sid is not None:
            stat_names[sid] = name
    tf_op_ids = {k for k, n in stat_names.items() if n == TF_OP}
    out = {}
    for s, e in _map_entries(buf, ev_entries):
        name, tf_op = None, None
        for num, wt, v in fields(buf, s, e):
            if num == 2 and wt == 2:
                name = _text(buf, v)
            elif num == 5 and wt == 2:
                mid, val = None, None
                for n2, w2, v2 in fields(buf, *v):
                    if n2 == 1 and w2 == 0:
                        mid = v2
                    elif n2 == 5 and w2 == 2:
                        val = _text(buf, v2)
                    elif n2 == 7 and w2 == 0:
                        val = stat_names.get(v2)
                if mid in tf_op_ids and val is not None:
                    tf_op = val
        if name is not None and tf_op is not None:
            out[name] = tf_op
    return out


def read_bytes(path: str) -> bytes:
    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return f.read()
    with open(path, "rb") as f:
        return f.read()


def tf_ops(raw: bytes) -> dict:
    """Plane name -> {op name: tf_op} for every TPU plane of a trace."""
    buf = memoryview(raw)
    out = {}
    for num, wt, plane in fields(buf):
        if num != 1 or wt != 2:
            continue
        name = next((_text(buf, v) for n, w, v in fields(buf, *plane)
                     if n == 2 and w == 2), "")
        if TPU_PLANE.match(name):
            out[name] = plane_ops(buf, plane)
    return out


# ------------------------------------------------------------- reduction


def phases_of(tf_op: str | None) -> set:
    """The phases of a `tf_op`: the innermost `wharf/<phase>` of each of its
    scope paths. XLA joins the paths of ops it merged into one with ";"
    (and ends the stat with ":"); a path with no `wharf/` adds nothing."""
    out = set()
    for path in (tf_op or "").rstrip(":").split(";"):
        found = SCOPE.findall(path)
        if found:
            out.add(found[-1])
    return out


def innermost_labels(gaps: list, spans: list, fallback: list) -> \
        collections.Counter:
    """Idle nanoseconds of each gap by the innermost (shortest) program span
    covering each part of it; parts no program span covers go to the
    innermost `fallback` span (the benchmark's own), else "host/other"."""
    out = collections.Counter()
    tiers = [sorted(spans), sorted(fallback)]
    ptr = [0, 0]
    pool = [[], []]
    for gs, ge in gaps:
        for t in (0, 1):
            while ptr[t] < len(tiers[t]) and tiers[t][ptr[t]][0] < ge:
                pool[t].append(tiers[t][ptr[t]])
                ptr[t] += 1
            pool[t] = [x for x in pool[t] if x[1] > gs]
        cuts = sorted({gs, ge} | {min(max(x, gs), ge)
                                  for t in (0, 1) for s, e, _ in pool[t]
                                  for x in (s, e)})
        for a, b in zip(cuts, cuts[1:]):
            label = "host/other"
            for t in (0, 1):
                cover = [x for x in pool[t] if x[0] <= a and x[1] >= b]
                if cover:
                    label = min(cover, key=lambda x: x[1] - x[0])[2]
                    break
            out[label] += b - a
    return out


def log(msg: str) -> None:
    print(f"[xplane_ops] {msg}", file=sys.stderr, flush=True)


def reduce_phases(path: str, data=None, top: int = 10) -> dict:
    """Keys to add to trace_reduce.reduce()'s result, averaged over the TPU
    planes: `phase_s` (device self seconds in the window per `wharf/` phase,
    and `unscoped`), `self_s` (their sum) and `idle_gaps_program`. `data`
    is the trace at `path` as trace_reduce.load gives it (loaded when not
    given)."""
    data = trace_reduce.load(path) if data is None else data
    names = tf_ops(read_bytes(path))
    window = trace_reduce.window_of(data)
    planes = trace_reduce.device_planes(data)
    if not planes:
        raise ValueError("trace has no TPU plane")
    # an op name whose metadata names two phases (one HLO text in two
    # programs, or two planes) cannot be told apart: it counts unscoped
    seen = collections.defaultdict(set)
    for ops in names.values():
        for op, tf_op in ops.items():
            seen[op] |= phases_of(tf_op)
    phase_by_name, clash = {}, set()
    for op, phases in seen.items():
        if len(phases) > 1:
            clash.add(op)
        phase_by_name[op] = phases.pop() if len(phases) == 1 else UNSCOPED
    for op in sorted(clash):
        log(f"op in two phases, counted unscoped: "
            f"{trace_reduce.short(op, 120)}")
    program = trace_reduce.host_spans(data, PROGRAM_SPANS)
    bench = [x for x in trace_reduce.host_spans(data)
             if x[2] != trace_reduce.WINDOW_SPAN]
    phase_ns, gaps_ns = collections.Counter(), collections.Counter()
    for plane in planes:
        evs = trace_reduce.op_events(plane, window)
        _, self_ns, intervals = trace_reduce.busy_and_self(evs)
        for op, ns in self_ns.items():
            phase_ns[phase_by_name.get(op, UNSCOPED)] += ns
        gaps_ns.update(innermost_labels(
            trace_reduce.idle_gaps(intervals, window), program, bench))
    n = len(planes)
    phase_s = {k: v / n / 1e9 for k, v in sorted(phase_ns.items())}
    return {
        "phase_s": phase_s,
        "self_s": sum(phase_ns.values()) / n / 1e9,
        "idle_gaps_program": [[k, v / n / 1e9]
                              for k, v in gaps_ns.most_common(top)],
    }


def per_batch(ctx: dict, *phases: str):
    """Device seconds of `phases` per window batch, or None when the trace
    has no phase times or none of these phases."""
    trace = ctx.get("trace") or {}
    phase_s = trace.get("phase_s")
    batches = (ctx.get("counters") or {}).get("window_batches")
    if not phase_s or not batches or not any(p in phase_s for p in phases):
        return None
    return sum(phase_s.get(p, 0.0) for p in phases) / batches
