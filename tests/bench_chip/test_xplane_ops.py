"""Tests of the per-phase trace reduction (benchmarks/chip/xplane_ops.py) on
traces recorded on one v5e, and of the readers of its per-layer metrics.
They run on the CPU in seconds and need no TensorFlow."""
from __future__ import annotations

import collections
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import bench  # noqa: E402
import roofline  # noqa: E402
import trace_reduce  # noqa: E402
import xplane_ops  # noqa: E402

READ_TRACE = BENCH / "testdata" / "read.xplane.pb.gz"
# one order-2 factorized update batch (2^8 vertices, node2vec-er's walk
# model, 2,500 + 500 edges), recorded on one v5e with its merge: every phase
# of the order-2 step runs. To fit 1 MB, the /host:metadata plane (the
# programs' HLO) and the per-event stats of the TPU plane's lines were
# dropped from the recording; nothing here or in trace_reduce reads them
# (both reductions read the same numbers from the full recording)
UPDATE_TRACE = BENCH / "testdata" / "update.xplane.pb.gz"
ORDER2_PHASES = {"graph_update", "mav", "findnext", "sample", "intersect",
                 "write_back", "merge"}
READERS = ("graph_device_s.update", "mav_device_s.update",
           "findnext_device_s.update", "sample_device_s.update",
           "write_back_device_s.update", "merge_device_s.update")


@pytest.fixture(scope="module")
def traces():
    out = {}
    for name, path in (("read", READ_TRACE), ("update", UPDATE_TRACE)):
        data = trace_reduce.load(str(path))
        reduced = trace_reduce.reduce(
            data, kernels={"findnext": roofline.findnext_queries})
        out[name] = (str(path), data, reduced)
    return out


def test_wire_decoder_reads_varints_and_skips_by_length():
    # field 1 varint 300, field 2 bytes "ab", field 3 fixed32, field 4
    # fixed64
    buf = bytes([0x08, 0xAC, 0x02, 0x12, 2, ord("a"), ord("b"),
                 0x1D, 1, 2, 3, 4, 0x21] + [0] * 8)
    got = list(xplane_ops.fields(buf))
    assert [(n, w) for n, w, _ in got] == [(1, 0), (2, 2), (3, 5), (4, 1)]
    assert got[0][2] == 300 and got[1][2] == (5, 7)
    assert buf[slice(*got[1][2])] == b"ab"


def test_tf_op_of_the_recorded_read_trace():
    """Of the 244 op kinds on the TPU plane's "XLA Ops" line, 166 carry a
    `tf_op`: the op's scope path in its program."""
    ops = xplane_ops.tf_ops(xplane_ops.read_bytes(str(READ_TRACE)))
    assert list(ops) == ["/device:TPU:0"]
    tf = ops["/device:TPU:0"]
    data = trace_reduce.load(str(READ_TRACE))
    (plane,) = trace_reduce.device_planes(data)
    line = next(x for x in plane.lines if x.name == trace_reduce.OPS_LINE)
    kinds = {e.name for e in line.events}
    assert len(kinds) == 244
    assert sum(k in tf for k in kinds) == 166
    assert "jit(find_next_batch)/gather:" in tf.values()
    # an op made from an argument is named by the argument's path
    assert "ov.skey:" in tf.values()
    # the recorded read program predates the scopes: all of it unscoped,
    # and its serve/ host spans never reach a device op
    assert not any("wharf/" in v or "serve/" in v for v in tf.values())


def test_phases_of_takes_the_innermost_wharf_scope_of_each_path():
    p = xplane_ops.phases_of
    assert p("jit(s)/while/body/wharf/sample/while/body/wharf/intersect/"
             "jit(f)/add:") == {"intersect"}
    assert p("jit(s)/wharf/mav/gather;jit(s)/wharf/mav/add:") == {"mav"}
    assert p("jit(s)/wharf/mav/a;jit(s)/wharf/merge/b:") == {"mav", "merge"}
    assert p("jit(s)/wharf/mav/a;jit(s)/add:") == {"mav"}
    assert p("jit(find_next_batch)/gather:") == set()
    assert p("jit(s)/mywharf/mav/add") == set()
    assert p(None) == set()


def test_idle_gaps_go_to_the_innermost_program_span():
    gaps = [(0, 10), (20, 40), (50, 60)]
    program = [(0, 40, "wharf/run_stream"), (22, 30, "wharf/stage")]
    fallback = [(0, 45, "bench/update_batch")]
    got = xplane_ops.innermost_labels(gaps, program, fallback)
    assert got == {"wharf/run_stream": 10 + 12, "wharf/stage": 8,
                   "host/other": 10}
    assert sum(got.values()) == sum(e - s for s, e in gaps)
    got = xplane_ops.innermost_labels([(40, 50)], program, fallback)
    assert got == {"bench/update_batch": 5, "host/other": 5}


@pytest.mark.parametrize("name", ["read", "update"])
def test_reduce_phases_adds_keys_and_leaves_reduce_as_it_was(traces, name):
    path, data, reduced = traces[name]
    before = json.dumps(reduced, sort_keys=True)
    new = xplane_ops.reduce_phases(path, data=data)
    assert set(new) == {"phase_s", "self_s", "idle_gaps_program"}
    assert not set(new) & set(reduced)
    assert set(reduced) == {"window_s", "busy_s", "kernel_s", "kernel_calls",
                            "kernel_items", "device_ops", "idle_gaps"}
    assert json.dumps(reduced, sort_keys=True) == before
    assert json.dumps(trace_reduce.reduce(
        data, kernels={"findnext": roofline.findnext_queries}),
        sort_keys=True) == before
    # the same reduction with the trace loaded from its file
    assert xplane_ops.reduce_phases(path) == new


@pytest.mark.parametrize("name", ["read", "update"])
def test_phase_seconds_add_up_to_the_summed_self_time(traces, name):
    path, data, reduced = traces[name]
    new = xplane_ops.reduce_phases(path, data=data)
    window = trace_reduce.window_of(data)
    self_ns = collections.Counter()
    for plane in trace_reduce.device_planes(data):
        self_ns.update(trace_reduce.busy_and_self(
            trace_reduce.op_events(plane, window))[1])
    total = sum(self_ns.values()) / 1e9
    assert sum(new["phase_s"].values()) == pytest.approx(total, rel=1e-9)
    assert new["self_s"] == pytest.approx(total, rel=1e-9)
    # ops nest properly, so self time covers the busy time exactly
    assert total == pytest.approx(reduced["busy_s"], rel=1e-6)
    assert sum(s for _, s in new["idle_gaps_program"]) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)


def test_every_phase_of_the_order2_step_shows_in_the_update_trace(traces):
    path, data, reduced = traces["update"]
    new = xplane_ops.reduce_phases(path, data=data)
    phase_s = new["phase_s"]
    assert ORDER2_PHASES <= set(phase_s)
    assert all(phase_s[p] > 0 for p in ORDER2_PHASES)
    assert set(phase_s) <= ORDER2_PHASES | {xplane_ops.UNSCOPED}
    assert phase_s.get(xplane_ops.UNSCOPED, 0.0) < 0.05 * reduced["busy_s"]
    labels = {k for k, _ in new["idle_gaps_program"]}
    assert labels & {"wharf/run_stream", "wharf/stage", "wharf/dispatch"}


def test_readers_of_the_phase_metrics(traces):
    path, data, reduced = traces["update"]
    trace = dict(reduced, **xplane_ops.reduce_phases(path, data=data))
    ctx = {"trace": trace, "counters": {"window_batches": 1}}
    values = {m: bench.load_reader(m)(ctx) for m in READERS}
    phase_s = trace["phase_s"]
    assert values["sample_device_s.update"] == pytest.approx(
        phase_s["sample"] + phase_s["intersect"])
    assert values["merge_device_s.update"] == pytest.approx(phase_s["merge"])
    assert all(v > 0 for v in values.values())
    assert sum(values.values()) <= trace["self_s"] * (1 + 1e-9)
    ctx2 = dict(ctx, counters={"window_batches": 2})
    assert bench.load_reader("mav_device_s.update")(ctx2) == pytest.approx(
        values["mav_device_s.update"] / 2)
    share = bench.load_reader("unscoped_device_share.update")(ctx)
    assert 0 <= share < 5
    # a trace reduced without phase times (the benchmark as it stands, or
    # the parent's program) reads nothing and raises nothing
    bare = {"trace": reduced, "counters": {"window_batches": 1}}
    for m in READERS + ("unscoped_device_share.update",):
        assert bench.load_reader(m)(bare) is None
        assert bench.load_reader(m)({"trace": None, "counters": {}}) is None
    # a deepwalk trace has no FINDNEXT phase
    no_fn = dict(trace, phase_s={k: v for k, v in phase_s.items()
                                 if k != "findnext"})
    assert bench.load_reader("findnext_device_s.update")(
        {"trace": no_fn, "counters": {"window_batches": 1}}) is None


def test_the_named_findnext_kernel_is_still_told_apart(traces):
    """The FINDNEXT kernel named wharf_findnext keeps the signature
    roofline.findnext_queries reads: each call of the order-2 traversal
    answers a slab of queries."""
    _, _, reduced = traces["update"]
    assert reduced["kernel_calls"]["findnext"] > 0
    assert reduced["kernel_items"]["findnext"] % reduced["kernel_calls"][
        "findnext"] == 0
