"""Reduction of a JAX profiler trace (`.xplane.pb`) to what the per-layer
metrics read: device busy time, self time per device op, the idle gaps
labelled by what the host was doing, and the time of one kernel.

Device ops are the events of each TPU plane's "XLA Ops" line, named by
their HLO text. Busy time is the union of their intervals; an op's self
time is its duration less that of the ops nested inside it (a while loop's
body). Device and host events share one clock in the trace. Host spans are
the benchmark's own `jax.profiler.TraceAnnotation`s, named `bench/...`;
`bench/window` brackets the measured window.
"""
from __future__ import annotations

import collections
import re

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench/window"
HOST_PREFIX = "bench/"


def load(path: str):
    """A trace file; a `.gz` one is decompressed in memory."""
    import jax
    if str(path).endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            return jax.profiler.ProfileData.from_serialized_xspace(f.read())
    return jax.profiler.ProfileData.from_file(path)


def device_planes(data) -> list:
    return [p for p in data.planes if re.match(r"/device:TPU:\d+$", p.name)]


def host_spans(data, prefix: str = HOST_PREFIX) -> list:
    """(start_ns, end_ns, name) of every host event whose name starts with
    `prefix`, sorted by start."""
    out = []
    for p in data.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            out += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events if e.name.startswith(prefix)]
    return sorted(out)


def window_of(data) -> tuple:
    """(start_ns, end_ns) of the `bench/window` span."""
    spans = [(s, e) for s, e, n in host_spans(data, WINDOW_SPAN)
             if n == WINDOW_SPAN]
    if not spans:
        raise ValueError("trace has no bench/window span")
    return spans[0]


def op_events(plane, window: tuple) -> list:
    """(start, end, name) of the plane's device ops, clipped to the window,
    sorted by start (outer ops before the ops they hold)."""
    lo, hi = window
    line = next((x for x in plane.lines if x.name == OPS_LINE), None)
    if line is None:
        return []
    evs = []
    for e in line.events:
        s, t = max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi)
        if t > s:
            evs.append((s, t, e.name))
    return sorted(evs, key=lambda x: (x[0], -x[1]))


def busy_and_self(evs: list) -> tuple:
    """(busy_ns, self_ns Counter, busy intervals)."""
    self_ns = collections.Counter()
    busy, stack, cover_end = 0.0, [], float("-inf")
    intervals = []
    for s, e, name in evs:
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            self_ns[stack[-1][2]] -= min(e, stack[-1][1]) - s
        self_ns[name] += e - s
        stack.append((s, e, name))
        if e > cover_end:
            busy += e - max(s, cover_end)
            if intervals and s <= intervals[-1][1]:
                intervals[-1] = (intervals[-1][0], e)
            else:
                intervals.append((s, e))
            cover_end = e
    return busy, self_ns, intervals


def idle_gaps(intervals: list, window: tuple) -> list:
    """The stretches of the window with no device op: (start, end)."""
    gaps, t = [], window[0]
    for s, e in intervals:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if window[1] > t:
        gaps.append((t, window[1]))
    return gaps


def label_gaps(gaps: list, spans: list) -> collections.Counter:
    """Idle nanoseconds by the innermost `bench/*` host span overlapping
    each part of each gap ("host/other" where none does)."""
    spans = [x for x in spans if x[2] != WINDOW_SPAN]
    out = collections.Counter()
    j = 0
    for gs, ge in gaps:
        while j < len(spans) and spans[j][1] <= gs:
            j += 1
        covered = 0
        k = j
        while k < len(spans) and spans[k][0] < ge:
            s, e, name = spans[k]
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                out[name] += ov
                covered += ov
            k += 1
        if ge - gs - covered > 0:
            out["host/other"] += ge - gs - covered
    return out


def short(name: str, width: int = 160) -> str:
    return name if len(name) <= width else name[:width] + "..."


def reduce(data, kernels: dict | None = None, top: int = 10) -> dict:
    """The traced window's numbers, averaged over the TPU planes.

    `kernels` maps a kernel's name to a function of an op's HLO text that
    gives the items (queries, rows) one call of that kernel handles, and
    None for any other op; each kernel's time is the summed duration of its
    ops in the window, its items the sum over its calls."""
    window = window_of(data)
    planes = device_planes(data)
    if not planes:
        raise ValueError("trace has no TPU plane")
    spans = host_spans(data)
    busy_total, self_all, gaps_all = 0.0, collections.Counter(), \
        collections.Counter()
    kernel_ns = collections.Counter()
    kernel_calls = collections.Counter()
    kernel_items = collections.Counter()
    for plane in planes:
        evs = op_events(plane, window)
        busy, self_ns, intervals = busy_and_self(evs)
        busy_total += busy
        self_all.update(self_ns)
        gaps_all.update(label_gaps(idle_gaps(intervals, window), spans))
        for kname, items in (kernels or {}).items():
            for s, e, name in evs:
                k = items(name)
                if k:
                    kernel_ns[kname] += e - s
                    kernel_calls[kname] += 1
                    kernel_items[kname] += k
    n = len(planes)
    return {
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": busy_total / n / 1e9,
        "kernel_s": {k: v / n / 1e9 for k, v in kernel_ns.items()},
        "kernel_calls": dict(kernel_calls),
        "kernel_items": {k: v / n for k, v in kernel_items.items()},
        "device_ops": [[short(k), v / n / 1e9]
                       for k, v in self_all.most_common(top)],
        "idle_gaps": [[k, v / n / 1e9] for k, v in gaps_all.most_common(top)],
    }


def idle_share(reduced: dict | None):
    """Percent of the traced window with no device op running."""
    if not reduced or reduced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
