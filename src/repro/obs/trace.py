"""Host-side phase tracing: profiler annotations + a JSONL span log.

Two complementary mechanisms behind one `phase(...)` context manager:

  * `jax.profiler.TraceAnnotation` + `jax.named_scope` — the span shows up
    on the host timeline of a `jax.profiler` capture, and any op traced
    inside a jit under the scope carries the phase name in its HLO op
    metadata (so XLA profiles attribute device time to engine phases).
  * an optional process-global `TraceLog` — each span is appended as one
    Chrome-trace "complete" (`"ph": "X"`) event per line to a JSONL file.
    `python -c 'import json,sys; print(json.dumps([json.loads(l) for l in
    sys.stdin]))' < spans.jsonl > trace.json` produces a file chrome://
    tracing / Perfetto loads directly; keeping the log line-oriented means
    crashes lose at most one span and benchmarks can append concurrently.

Phase taxonomy (DESIGN.md §10) — use these constants so trace consumers can
group spans: GRAPH_UPDATE (the edge batch applied to the graph), MAV (the
touched-vertex mask, segment gather, Szudzik unpair and p_min reduction),
FINDNEXT (packed-chunk search / order-2 prefix traversal), SAMPLE
(SAMPLENEXT draws), INTERSECT (order-2 neighbor-window intersection, nested
in SAMPLE), WRITE_BACK (triplet encode, version-block append, slot-epoch
bump), MERGE (pending consolidation), COLLECTIVE (cross-shard pmin /
all_to_all), plus free-form "serve/<query>" spans from the serving layer.

Inside a jit, `scope(phase)` names the device ops `wharf/<phase>`: each op's
HLO metadata (`op_name`, the profiler's `tf_op` stat) carries the path of
scopes it was traced under, and the innermost `wharf/` element is its
phase. The stream step's scope tree:

  wharf/merge            the forced / eager pending merge (lax.cond branch)
  wharf/graph_update     StreamingGraph.apply_batch
  wharf/mav              touched mask, segment gather, unpair, p_min
                         reduction, the affected-walk lanes
    wharf/collective     (sharded) the two int32 pmins of the MAV keys
  wharf/findnext         order-2 prefix traversal through the overlay
    wharf/findnext       the packed-chunk search (kernel `wharf_findnext`)
  wharf/sample           the rewalk's step scan: SAMPLENEXT, walker state
    wharf/intersect      factorized order-2 groups (kernel `wharf_intersect`)
    wharf/write_back     each step's triplet encode
    wharf/collective     (sharded) each step's all_to_all handoff
  wharf/write_back       version-block append, slot-epoch bump

Scopes change op metadata only: the compiled instructions are the same
with or without them. Host spans stay out of compiled programs (a jit
traces under a fresh name stack), so `phase(...)` around a jitted call
names only the host's side of it: `WalkEngine.run_stream` opens
`wharf/run_stream` with children `wharf/stage` (argument staging, key
split) and `wharf/dispatch` (the jitted call); `WalkEngine._update` and
`WalkEngine.merge` open `wharf/update` and `wharf/consolidate`.

A span measures HOST wall time between enter and exit. Around a jitted
call that includes dispatch plus however much device work the call blocks
on — honest for end-to-end driver timing, NOT a per-phase device profile
(that is what the TraceAnnotation/named_scope side of the same span is
for, under a real profiler capture).
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Optional

import jax

# in-jit engine phases (named_scope spelling: "wharf/<phase>")
PREFIX = "wharf/"
GRAPH_UPDATE = "graph_update"
MAV = "mav"
FINDNEXT = "findnext"
INTERSECT = "intersect"
SAMPLE = "sample"
WRITE_BACK = "write_back"
MERGE = "merge"
COLLECTIVE = "collective"
PHASES = (GRAPH_UPDATE, MAV, FINDNEXT, INTERSECT, SAMPLE, WRITE_BACK, MERGE,
          COLLECTIVE)


class TraceLog:
    """Append-only Chrome-trace JSONL span sink (one event object per line).

    Timestamps are microseconds since the log was opened (`ts`), durations
    microseconds (`dur`) — the Chrome trace-event "X" convention."""

    def __init__(self, path: str):
        self.path = path
        self._f = open(path, "a", buffering=1)
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()

    def now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def event(self, name: str, cat: str, ts_us: float, dur_us: float,
              args: Optional[dict] = None) -> None:
        ev = {"name": name, "cat": cat, "ph": "X",
              "ts": round(ts_us, 3), "dur": round(dur_us, 3),
              "pid": os.getpid(), "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        line = json.dumps(ev, sort_keys=True)
        with self._lock:
            self._f.write(line + "\n")

    def close(self) -> None:
        self._f.close()


_LOG: Optional[TraceLog] = None

# span observers: callables (name, cat, dur_us, args, error) notified on
# every phase() exit (whether or not a TraceLog is installed) — the hook
# obs/slo.py rides to build latency histograms without touching the query
# code. `error` is the exception instance if the span body raised, else
# None. Observers must not raise on the serving hot path; exceptions are
# deliberately NOT swallowed here (an observer bug should fail tests, not
# silently drop telemetry).
_OBSERVERS: list = []


def add_observer(fn) -> None:
    """Register a span observer `(name, cat, dur_us, args, error)`."""
    if fn not in _OBSERVERS:
        _OBSERVERS.append(fn)


def remove_observer(fn) -> None:
    if fn in _OBSERVERS:
        _OBSERVERS.remove(fn)


def install(path: str) -> TraceLog:
    """Open `path` as the process-global span log (appending). Subsequent
    `phase(...)` spans are recorded until `uninstall()`."""
    global _LOG
    if _LOG is not None:
        _LOG.close()
    _LOG = TraceLog(path)
    return _LOG


def uninstall() -> None:
    global _LOG
    if _LOG is not None:
        _LOG.close()
    _LOG = None


def active() -> Optional[TraceLog]:
    return _LOG


def scope(name: str):
    """`jax.named_scope("wharf/<name>")` for one of the PHASES: the device
    ops traced inside carry the phase in their metadata."""
    if name not in PHASES:
        raise ValueError(f"unknown engine phase {name!r}; one of {PHASES}")
    return jax.named_scope(PREFIX + name)


@contextlib.contextmanager
def phase(name: str, cat: str = "engine", **args):
    """Span a host-side phase: profiler annotation + named_scope + JSONL.

    `name` is free-form ("serve/ppr_row") or one of the PHASES constants;
    `args` become the Chrome-trace event's `args` payload. Cheap beyond
    the two jax context managers when no TraceLog or observer is
    installed.

    A raised query still flushes its span: the exception is captured in
    the event's `args.error` field ("TypeName: message") and re-raised, so
    the JSONL tail holds the failing span instead of silently losing it,
    and SLO observers see the error for their error-rate counters."""
    log = _LOG
    t0 = time.perf_counter()
    err: Optional[BaseException] = None
    with jax.profiler.TraceAnnotation(name), jax.named_scope(name):
        try:
            yield
        except BaseException as e:
            err = e
            raise
        finally:
            dur = (time.perf_counter() - t0) * 1e6
            payload = dict(args) if args else None
            if err is not None:
                payload = dict(payload or {})
                payload["error"] = f"{type(err).__name__}: {err}"
            if log is not None:
                log.event(name, cat, (t0 - log._t0) * 1e6, dur, payload)
            for fn in list(_OBSERVERS):
                fn(name, cat, dur, args or {}, err)


def read_spans(path: str) -> list:
    """Parse a JSONL span log back into a list of event dicts (helper for
    tests and for wrapping into a chrome://tracing-loadable JSON array)."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out
