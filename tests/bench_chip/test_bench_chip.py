"""Tests of the chip benchmark (benchmarks/chip/) that run on the CPU in
seconds: discovery by name, the arithmetic of its metrics, the trace
reducer, the peak table, the plain reference, and whole runs of tiny cells
with faults planted under them. No test compiles at a real size or
describes a TPU."""
from __future__ import annotations

import json
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import bench  # noqa: E402
import peaks  # noqa: E402
import roofline  # noqa: E402
import trace_reduce  # noqa: E402

SPEC = bench.load_spec(ROOT / "BENCHMARK.json")
REF = bench.load_reference({"reference": "walk_reference"})

# The point-read cell: its mix, readers and harness path are committed, but
# its p95 is not steady enough to bound yet, so BENCHMARK.json leaves it
# out. Adding it back is these entries alone.
READ_ENTRIES = {
    "workloads": [{"name": "deepwalk-er.read", "config": "deepwalk-er",
                   "traffic": "point-reads", "chips": 1, "why": "reads"}],
    "end_to_end": [{"name": "read_p95_ms", "unit": "ms", "better": "lower",
                    "bound": 0.25, "source": "host_clock",
                    "workloads": ["deepwalk-er.read"]}],
    "per_layer": [
        {"name": n, "unit": "%", "better": b, "source": "device_trace",
         "layer": layer, "moves": "read_p95_ms",
         "workloads": ["deepwalk-er.read"]}
        for n, b, layer in (
            ("idle_share.read", "lower", "device"),
            ("findnext_roofline.read", "higher",
             "FINDNEXT kernel (kernels/range_search.py)"))],
}


def with_read_cell(spec: dict) -> dict:
    out = json.loads(json.dumps(spec))
    for key, entries in READ_ENTRIES.items():
        out[key] = out[key] + entries
    return out


# ------------------------------------------------------------- discovery


def test_every_cell_config_mix_and_metric_is_found_by_name():
    names = set()
    spec = with_read_cell(SPEC)
    for cell in spec["workloads"]:
        cfg = bench.load_config(spec, cell["config"])
        mix = bench.load_traffic(cell["traffic"])
        assert cfg["name"] == cell["config"] and mix["name"] == cell["traffic"]
        assert bench.load_reference(cfg).check_state
        e2e = {m["name"] for m in bench.end_to_end_for(spec, cell["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = bench.per_layer_for(spec, cell["name"])
        assert layer, cell["name"]
        for m in layer:
            assert m["moves"] in e2e
            assert callable(bench.load_reader(m["name"]))
        names.add(cell["name"])
    assert names == {"deepwalk-er.update", "node2vec-er.update",
                     "deepwalk-er.read"}


def test_a_new_cell_is_new_files_and_entries_only(tmp_path):
    """A dummy configuration, mix and metric added as files in a directory
    of their own are found by name, with no file of the benchmark
    edited."""
    base = tmp_path / "benchmarks" / "chip"
    for d in ("configs", "traffic", "metrics"):
        (base / d).mkdir(parents=True)
    (base / "configs" / "dummy.json").write_text(json.dumps(
        {"name": "dummy", "log2_n": 4, "reference": "dummy_ref"}))
    (base / "configs" / "dummy_ref.py").write_text(
        "def check_state(state, edges, last_changed, cfg):\n"
        "    return None, {}\n")
    (base / "traffic" / "trickle.json").write_text(json.dumps(
        {"name": "trickle", "requests": "update_batches"}))
    (base / "metrics" / "dummy_share.update.py").write_text(
        "def read(ctx):\n    return ctx['counters'].get('dummy')\n")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "dummy", "source": "https://example.org",
                            "file": "benchmarks/chip/configs/dummy.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "dummy.trickle", "config": "dummy",
                              "traffic": "trickle", "chips": 1, "why": "t"})
    spec["end_to_end"][0]["workloads"].append("dummy.trickle")
    spec["per_layer"].append({"name": "dummy_share.update", "unit": "%",
                              "better": "lower", "source": "program_counter",
                              "layer": "test", "moves": "update_edges_per_s",
                              "workloads": ["dummy.trickle"]})
    cell = bench.workload(spec, "dummy.trickle")
    cfg = bench.load_config(spec, cell["config"], base)
    assert cfg["log2_n"] == 4
    assert bench.load_traffic(cell["traffic"], base)["requests"] in (
        "update_batches")
    assert bench.load_reference(cfg, base).check_state(
        None, None, None, cfg) == (None, {})
    layer = [m["name"] for m in bench.per_layer_for(spec, "dummy.trickle")]
    assert layer == ["dummy_share.update"]
    assert bench.load_reader(layer[0], base)({"counters": {"dummy": 7}}) == 7
    # the committed cells are untouched by the addition
    assert bench.per_layer_for(spec, "node2vec-er.update") == \
        bench.per_layer_for(SPEC, "node2vec-er.update")
    with pytest.raises(FileNotFoundError):
        bench.load_traffic("absent", base)


def test_config_file_must_match_its_entry(tmp_path):
    spec = json.loads(json.dumps(SPEC))
    spec["configs"][0]["file"] = "elsewhere/deepwalk-er.json"
    with pytest.raises(ValueError):
        bench.load_config(spec, spec["configs"][0]["name"])


# ------------------------------------------------------------ arithmetic


def test_closed_loop_rate_counts_whole_batches_over_the_whole_window():
    # three 12,000-edge batches complete at 13, 26.5 and 40 s after the
    # window opened at t0 = 100 s: the rate runs to the last completion
    done = [(113.0, 12000), (126.5, 12000), (140.0, 12000)]
    assert bench.closed_loop_rate(100.0, done) == pytest.approx(36000 / 40)
    # the order of completions does not matter, an empty window is refused
    assert bench.closed_loop_rate(100.0, done[::-1]) == \
        bench.closed_loop_rate(100.0, done)
    with pytest.raises(ValueError):
        bench.closed_loop_rate(0.0, [])


def test_p95_from_due_times_counts_the_wait_behind_a_stall():
    # requests due every 10 ms, each served in 1 ms, but request 50 stalls
    # for 200 ms: the 19 due during the stall wait behind it
    due = [0.010 * i for i in range(200)]
    done, free = [], 0.0
    for i, u in enumerate(due):
        start = max(u, free)
        free = start + (0.200 if i == 50 else 0.001)
        done.append(free)
    lat = bench.open_loop_latencies(due, done, give_up=100.0)
    assert max(lat) == pytest.approx(0.200)
    # the 19 due during the stall wait out its rest, and the backlog delays
    # three more: 23 of 200 latencies exceed the 1 ms service time, so the
    # nearest-rank p95 (the 190th smallest, the 11th largest) is one of
    # them: request 60, due 100 ms before the queue reached it, + 10 ms
    # behind the 9 before it + its own 1 ms
    assert sum(x > 0.0011 for x in lat) == 23
    assert bench.percentile(lat, 95) == pytest.approx(0.110)
    # timed from the send instead of the due time it would read 1 ms
    assert bench.percentile([0.001] * 200, 95) == pytest.approx(0.001)
    # a failed request counts as waiting until the give-up time
    lat = bench.open_loop_latencies([0.0, 1.0], [0.5, None], give_up=61.0)
    assert lat == [0.5, 60.0]


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert bench.percentile(v, 95) == 95
    assert bench.percentile(v, 100) == 100
    assert bench.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        bench.percentile([], 95)


def test_result_line_puts_checks_last():
    line = bench.result_line(True, 3, 0, {"setup_s": {"value": 1.0,
                                                      "unit": "s"}},
                             {"platform": "tpu"}, {"a": {"value": 0,
                                                         "limit": 0}},
                             breakdown={"device_ops": [], "idle_gaps": []})
    out = json.loads(line)
    assert list(out)[-1] == "checks"
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert bench.check_lines(out["checks"]) == ["check a 0 <= 0"]


# ----------------------------------------------------- roofline and peaks


def test_findnext_min_bytes_counts_queries_answers_and_capped_codes():
    assert roofline.findnext_min_bytes(1000, 10 ** 9) == 1000 * 18
    # more queries than codes: the codes cost at most the store once
    assert roofline.findnext_min_bytes(1000, 100) == 1000 * 17 + 100
    # 16 queries in 1 us of kernel time on an 819 GB/s chip
    share = roofline.roofline_share(16 * 18, 819e9, 1e-6)
    assert share == pytest.approx(100 * 288 / 819e9 / 1e-6)
    assert roofline.roofline_share(100, 819e9, 0.0) is None
    # the readers' context: the queries the trace's kernel calls answered;
    # nothing without the kernel in the trace
    ctx = {"counters": {"store_codes": 10 ** 9},
           "trace": {"kernel_s": {"findnext": 1e-6},
                     "kernel_items": {"findnext": 16}},
           "peaks": peaks.peaks("TPU v5 lite")}
    assert roofline.findnext_share(ctx) == pytest.approx(share)
    assert roofline.findnext_share(dict(ctx, trace=None)) is None
    assert roofline.findnext_share(
        dict(ctx, trace={"kernel_s": {}, "kernel_items": {}})) is None


def test_peak_table_is_keyed_by_device_kind_and_refuses_unknown_kinds():
    p = peaks.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9
    assert p["bf16_flops_per_s"] == 197e12 and p["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v4")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_findnext_kernel_is_told_apart_by_its_signature():
    # as the chip's traces name the kernel: after the enclosing jit, or as
    # a closed_call inside the order-2 traversal's loop; its output vectors
    # hold one answer per query of the call
    assert roofline.findnext_queries(
        '%find_next_packed.1 = (s32[16]{0:T(128)S(1)}, s32[16]{0:T(128)S(1)}'
        ') custom-call(s32[128]{0:T(128)S(1)} %reshape.19, s32[16]{0:T(128)'
        'S(1)} %g, u32[51200,256]{1,0:T(8,128)} %ov_base_packed.1), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints=') \
        == 16
    assert roofline.findnext_queries(
        '%closed_call.27 = (s32[256]{0:T(256)S(1)}, s32[256]{0:T(256)S(1)}) '
        'custom-call(s32[2048]{0:T(1024)S(1)} %reshape.3335, u32[6400,256]'
        '{1,0:T(8,128)S(1)} %get-tuple-element.18141), custom_call_target='
        '"tpu_custom_call", operand_layout_constraints=') == 256
    # the intersect kernel, and XLA's own custom calls, are not it
    assert roofline.findnext_queries(
        '%factorized_next_pallas.10 = (u32[10240,1]{1,0:T(8,128)S(1)}, '
        'u32[10240,1]{1,0:T(8,128)S(1)}) custom-call(u32[10240,128]{1,0:'
        'T(8,128)S(1)} %copy-done.90), custom_call_target="tpu_custom_call"'
    ) is None
    assert roofline.findnext_queries(
        '%custom-call.1 = u32[6553600]{0:T(1024)S(1)} custom-call(u64[6553600]'
        '{0:T(1024)} %ov_skey.1), custom_call_target="X64SplitLow"') is None


def test_findnext_queries_of_an_order2_window_match_the_traversal():
    """The order-2 rewalk traverses every lane's prefix: one FINDNEXT query
    per lane and position. At node2vec-er's size that is 40 calls of 256
    queries per traversal step, 79 steps a batch (the chip's trace of a
    window of 3 batches: 9,480 calls)."""
    cfg = bench.load_config(SPEC, "node2vec-er")
    lanes = (1 << cfg["log2_n"]) * cfg["n_walks_per_vertex"]
    calls, block, batches = 9480, 256, 3
    assert calls * block == batches * lanes * (cfg["length"] - 1)


# -------------------------------------------------------- trace reducer


def test_busy_self_time_and_labelled_idle_gaps():
    # two ops, the second nested in a loop op; a gap at 30-50 while the
    # host waited for the generator and 80-100 while it did other work
    evs = [(0, 30, "while"), (10, 20, "fusion"), (50, 80, "fusion")]
    busy, self_ns, intervals = trace_reduce.busy_and_self(evs)
    assert busy == 60
    assert self_ns["while"] == 20 and self_ns["fusion"] == 40
    assert intervals == [(0, 30), (50, 80)]
    gaps = trace_reduce.idle_gaps(intervals, (0, 100))
    assert gaps == [(30, 50), (80, 100)]
    spans = [(25, 45, "bench/generator_wait"), (0, 100, "bench/window")]
    lab = trace_reduce.label_gaps(gaps, spans)
    assert lab == {"bench/generator_wait": 15, "host/other": 25}
    assert trace_reduce.idle_share({"busy_s": 6.0, "window_s": 10.0}) == \
        pytest.approx(40.0)
    assert trace_reduce.idle_share(None) is None


TRACE = BENCH / "testdata" / "read.xplane.pb.gz"


@pytest.mark.skipif(not TRACE.is_file(), reason="no recorded trace")
def test_reduce_a_trace_recorded_on_the_chip():
    """A traced deepwalk-er.read window recorded on one v5e: busy time
    within the window, the FINDNEXT kernel found, idle time labelled by the
    benchmark's host spans."""
    r = trace_reduce.reduce(trace_reduce.load(str(TRACE)),
                            kernels={"findnext": roofline.findnext_queries})
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["kernel_s"]["findnext"] > 0
    # each read request is one FINDNEXT call of 16 queries
    assert r["kernel_items"]["findnext"] == 16 * r["kernel_calls"]["findnext"]
    assert r["kernel_s"]["findnext"] <= r["busy_s"]
    labels = {name for name, _ in r["idle_gaps"]}
    assert labels & {"bench/generator_wait", "bench/read/next_vertices",
                     "bench/read/walks_of"}
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10


# ------------------------------------------------------------- reference


def test_reference_unpair_is_exact_against_integer_isqrt():
    rng = np.random.default_rng(0)
    z = np.concatenate([
        rng.integers(0, 2 ** 63, 2000, dtype=np.uint64) * np.uint64(2),
        np.asarray([0, 1, 2, 3, 2 ** 32 - 1, 2 ** 32, 2 ** 64 - 1,
                    (2 ** 32 - 1) ** 2, (2 ** 32 - 1) ** 2 - 1,
                    2 ** 62 + 12345], np.uint64)])
    r = REF.isqrt(z)
    assert all(int(a) == math.isqrt(int(b)) for a, b in zip(r, z))
    x = rng.integers(0, 2 ** 32, 5000, dtype=np.uint64)
    y = rng.integers(0, 2 ** 32, 5000, dtype=np.uint64)
    ux, uy = REF.unpair(REF.pair(x, y))
    assert np.array_equal(ux, x) and np.array_equal(uy, y)


def test_reference_graph_deletes_then_inserts_both_directions():
    e, last = REF.replay([0, 1], [1, 2], [
        (np.array([[2]]), np.array([[3]]), np.array([[1]]), np.array([[0]])),
        (np.array([[0]]), np.array([[1]]), np.array([[5]]), np.array([[5]])),
        (np.array([[2]]), np.array([[1]]), np.array([[3]]), np.array([[4]])),
    ], 6)
    got = {(int(c >> np.uint64(32)), int(c & np.uint64(0xFFFFFFFF)))
           for c in e}
    assert got == {(1, 2), (2, 1), (2, 3), (3, 2), (0, 1), (1, 0)}
    assert list(REF.degrees(e, 4)) == [1, 2, 2, 1]
    # the last batch that changed each vertex's neighbours: batch 3 adds an
    # edge that is there and deletes one that is not, changing nothing
    assert list(last) == [2, 2, 1, 1, 0, 0]


def test_stale_slots_are_those_older_than_their_owners_last_change():
    walks = np.array([[0, 1, 2], [2, 1, 0]], np.uint32)
    epochs = np.array([[0, 3, 3], [1, 1, 2]])
    last = np.array([0, 2, 1])
    # slot (1, 1): vertex 1 changed in batch 2, its slot holds epoch 1
    assert REF.stale_slots(walks, epochs, last) == {"stale_slots": 1}


# ----------------------------------------------------- runs of tiny cells


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A benchmark directory with tiny copies of the committed cells: the
    committed reference and readers, configurations cut to 64 vertices and
    walks of 8, batches of 16 + 4 edges."""
    tmp = tmp_path_factory.mktemp("tinybench")
    base = tmp / "benchmarks" / "chip"
    shutil.copytree(BENCH, base, ignore=shutil.ignore_patterns(
        "__pycache__", "testdata"))
    spec = with_read_cell(SPEC)
    for c in spec["configs"]:
        cfg = bench.load_config(SPEC, c["name"])
        cfg.update(log2_n=6, avg_degree=8, n_walks_per_vertex=2, length=8,
                   edge_capacity=64 * 8 + 2 * 16 * 401)
        (base / "configs" / f"{c['name']}.json").write_text(json.dumps(cfg))
    for name in ("mixed-10k", "point-reads"):
        mix = bench.load_traffic(name)
        if "batch_inserts" in mix:
            mix.update(batch_inserts=16, batch_deletes=4,
                       max_window_batches=400)
        else:
            mix.update(rate_per_s=60, walks_of_capacity=64, walks_of_checked=8)
            mix["setup_update"].update(batch_inserts=16, batch_deletes=4)
        (base / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    return spec, base


def _run(tiny, cell, seconds, fault, monkeypatch):
    import faults
    import run
    monkeypatch.setenv("LIBTPU_INIT_ARGS", "")
    spec, base = tiny
    with faults.plant(fault):
        line, checks = run.run_cell(spec, cell, 2 ** 33 + 5, seconds, False,
                                    base=base, require_tpu=False,
                                    cache=False)
    return json.loads(line), checks


RUNS = [("deepwalk-er.update", 0.0, f) for f in (
    "none", "state_unchanged", "half_batch", "token_altered", "skip_rewalk",
    "mav_deletes_only")
] + [("node2vec-er.update", 0.0, f) for f in (
    "none", "skip_rewalk", "mav_deletes_only")] + [
    ("deepwalk-er.read", 0.5, f) for f in ("none", "answer_altered",
                                            "base_only_reads",
                                            "walks_of_fails")]


@pytest.mark.parametrize("cell,seconds,fault", RUNS,
                         ids=[f"{c}-{f}" for c, _, f in RUNS])
def test_a_run_is_correct_and_each_fault_makes_it_not(tiny, cell, seconds,
                                                      fault, monkeypatch):
    out, checks = _run(tiny, cell, seconds, fault, monkeypatch)
    assert out["correct"] is (fault == "none"), checks
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] >= 1
    assert out["attempted"] >= 1
    assert (out["failed"] > 0) is (fault == "walks_of_fails")
    assert set(out["metrics"]) == {
        m["name"] for m in bench.end_to_end_for(tiny[0], cell)}
    assert any(c.endswith("FAILED") for c in checks) is (fault != "none")
    if fault == "mav_deletes_only":
        # the walks are valid on the final graph: only the stale slots
        # show the rewalks the MAV left out
        assert out["checks"]["stale_slots"]["value"] > 0
        assert out["checks"]["invalid_transitions"]["value"] == 0


def test_reference_agrees_with_the_engine_and_not_with_a_corrupted_corpus(
        tiny):
    import jax
    import system
    import traffic
    _, base = tiny
    cfg = bench.load_config(SPEC, "deepwalk-er", base)
    mix = bench.load_traffic("mixed-10k", base)
    n = 1 << cfg["log2_n"]
    src, dst = traffic.base_graph_edges(cfg, 3)
    b = traffic.update_batches(mix, cfg["log2_n"], 3, 3)
    g, s = system.build(src, dst, traffic.key(3, 0), n=n,
                        capacity=system.edge_capacity(cfg, mix, 3),
                        wcfg=system.walk_config(cfg))
    eng = system.engine(cfg, g, s)
    for i in range(3):
        eng.run_stream(*traffic.batch(b, i))
    state = system.host_state(eng)
    edges, last = REF.replay(*jax.device_get((src, dst)),
                             [traffic.batch(b, i)[1:] for i in range(3)], n)
    walks, counts = REF.check_state(state, edges, last, cfg)
    assert counts and all(v <= REF.LIMITS.get(k, 0)
                          for k, v in counts.items()), counts
    assert walks.shape == (n * 2, 8)
    # one live triplet of the pending block, altered, breaks the corpus
    bad = {k: {kk: np.array(vv, copy=True) for kk, vv in v.items()}
           for k, v in state.items()}
    live = np.flatnonzero(bad["pending"]["epoch"].ravel() != 0xFFFFFFFF)
    bad["pending"]["code"].reshape(-1)[live[0]] += np.uint64(1)
    _, c = REF.check_state(bad, edges, last, cfg)
    assert c["chain_breaks"] + c["slots_not_once"] > 0
    # a graph that missed an edge is caught
    _, c = REF.check_state(state, edges[1:], last, cfg)
    assert c["graph_edges_differ"] == 1
    # a vertex changed after its slots were written: they are stale
    _, c = REF.check_state(state, edges, last + 4, cfg)
    assert c["stale_slots"] > 0


DIST = [(1, 20, 80, 128, "bf16_draw"), (2, 10, 40, 100, "uniform_order2")]


@pytest.mark.parametrize("order,n_w,length,dmax,control", DIST,
                         ids=[c for *_, c in DIST])
def test_distribution_numbers_hold_the_program_and_fail_the_control(
        order, n_w, length, dmax, control):
    """On an er-k graph of 2^10 vertices and degree about 100, the program's
    walks (order 2 with dmax 100, so about half of the steps take the
    rejection fallback) read under the limits, and the reference control's
    walks on the same graph read over them."""
    import jax
    import system
    import traffic
    from repro.core import StreamingGraph
    from repro.core.corpus import generate_walk_matrix
    n = 1 << 10
    cfg = dict(bench.load_config(SPEC, "node2vec-er"), log2_n=10,
               n_walks_per_vertex=n_w, length=length)
    cfg["walk_model"] = dict(cfg["walk_model"], order=order, dmax=dmax)
    src, dst = traffic.base_graph_edges(cfg, 11)
    graph = StreamingGraph.from_edges(src, dst, n, 2 * n * 100)
    walks = np.asarray(generate_walk_matrix(traffic.key(11, 0), graph,
                                            system.walk_config(cfg)))
    edges, _ = REF.replay(*jax.device_get((src, dst)), [], n)
    if order == 2:
        assert 0.2 < np.mean(REF.degrees(edges, n) > dmax) < 0.8
    sound = REF.check_distribution(walks, edges, cfg)
    ctl = REF.control_numbers(control, edges, cfg, 11)
    assert set(sound) == set(ctl) and sound
    for k in sound:
        assert sound[k] <= REF.LIMITS[k] < ctl[k], (k, sound[k], ctl[k])


def test_reference_controls_read_beside_a_sound_run(tiny, monkeypatch):
    """control.py's controls: each read on the run's final graph, only for
    its own walk model, and the run's own checks left as they are."""
    import faults
    sink = {}
    with faults.reference_controls(["bf16_draw", "uniform_order2"], 5,
                                   sink):
        out, _ = _run(tiny, "deepwalk-er.update", 0.0, "none", monkeypatch)
    assert out["correct"] is True
    assert set(sink) == {"bf16_draw.uniform_chi2_z"}


def test_no_tpu_exits_nonzero_and_prints_no_result(tmp_path):
    """Without a TPU the run exits 3 and prints nothing on stdout; in a
    directory holding only BENCHMARK.json and the benchmark's paths it
    fails too, with no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "benchmarks/chip/run.py", "--workload",
           "deepwalk-er.update", "--seed", str(2 ** 33 + 1), "--seconds",
           "1", "--trace", "0"]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 3 and p.stdout == ""
    assert "no TPU" in p.stderr
    only = tmp_path / "only"
    for rel in SPEC["paths"]:
        shutil.copytree(ROOT / rel, only / rel,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", only / "BENCHMARK.json")
    env["PYTHONPATH"] = ""
    p = subprocess.run(cmd, cwd=only, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
