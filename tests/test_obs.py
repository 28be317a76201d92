"""Observability subsystem tests (DESIGN.md §10): the hard contract is

  (a) metrics OFF (the WalkConfig default) is compiled OUT — the streaming
      drivers lower to the exact pre-observability HLO (checked against an
      in-test reconstruction of the pre-PR scan, byte-identical modulo the
      jit module name), and no "obs_metrics"-scoped op leaks into the OFF
      executable;
  (b) metrics ON leaves engine outputs BIT-identical, on mixed
      insert+delete streams, for both merge policies, single-host and on
      the 8-shard shard_map engine (subprocess, forced host devices);
  (c) the exported counters match a pure-python/numpy replay of the same
      stream: |MAV| totals, the p_min suffix histogram, the merge schedule
      closed form, the deg>dmax fallback lanes, and (sharded) the global
      all_to_all handoff volume.

Plus format/plumbing coverage: export JSON schema + Prometheus text, trace
JSONL roundtrip, maintainer metrics, and launch/profile_cell import purity.
"""
import importlib
import os
import re
import subprocess
import sys
import textwrap
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import StreamingGraph, WalkConfig, generate_corpus
from repro.core.update import (EngineState, I32, WalkEngine, _apply_update,
                               _merge_state, _run_stream_jit,
                               _run_stream_obs_jit)
from repro.core.walkers import WalkModel
from repro.data.streams import mixed_edge_stream, rmat_edges
from repro.obs import NEVER, PMIN_BUCKETS, StreamMetrics
from repro.obs import trace as obs_trace
from repro.obs.export import summary, to_prometheus, write_summary

LOG2_N = 6
N = 2 ** LOG2_N
CAP = 128
MAX_PENDING = 4
N_BATCHES = 5


def run_sub(code: str):
    """8-forced-host-device subprocess runner (same contract as
    tests/test_distr.py): the main test process keeps its single-device
    view; JAX_PLATFORMS=cpu skips accelerator-plugin retry backoff."""
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=600,
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
        cwd=".",
    )
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    return res.stdout


def make_graph_store(cfg, seed=0):
    src, dst = rmat_edges(jax.random.PRNGKey(seed), 200, LOG2_N)
    g = StreamingGraph.from_edges(src, dst, N, 4096)
    store = generate_corpus(jax.random.PRNGKey(seed + 1), g, cfg)
    return g, store


def make_stream(n_batches=N_BATCHES, seed=7):
    i_s, i_d, d_s, d_d = mixed_edge_stream(jax.random.PRNGKey(seed),
                                           n_batches, 10, 4, LOG2_N)
    return i_s, i_d, d_s, d_d


def make_engine(g, store, cfg, policy):
    # run_stream DONATES the engine buffers: every engine gets its own
    # copies so OFF/ON runs on "the same" graph+store really are
    return WalkEngine(graph=jax.tree.map(jnp.array, g),
                      store=jax.tree.map(jnp.array, store), cfg=cfg,
                      merge_policy=policy, rewalk_capacity=CAP,
                      max_pending=MAX_PENDING)


# ---------------------------------------------------------------- (a) HLO


def _normalize_hlo(text: str) -> str:
    """Strip the jit module name (the only legitimate OFF/ref difference)."""
    return re.sub(r"@jit_[A-Za-z0-9_]+", "@jit_X", text)


@pytest.mark.parametrize("policy", ["on-demand", "eager"])
def test_metrics_off_hlo_identity(policy):
    """OFF path lowers byte-identical to a reconstruction of the PRE-PR
    stream scan (cond-merge + _apply_update + eager merge, no metrics
    anywhere near the trace) — observability off is compiled out, not just
    disabled."""
    cfg = WalkConfig(n_walks_per_vertex=2, length=8)
    g, store = make_graph_store(cfg)
    i_s, i_d, d_s, d_d = make_stream()
    keys = jax.random.split(jax.random.PRNGKey(3), N_BATCHES)
    state = EngineState.create(g, store, MAX_PENDING, CAP * cfg.length)
    mav_cap = store.size

    off = _run_stream_jit.lower(
        state, keys, i_s, i_d, d_s, d_d, cfg=cfg, capacity=CAP,
        mav_capacity=mav_cap, max_pending=MAX_PENDING, merge_policy=policy,
        merge_impl="interleave").as_text()
    assert "obs_metrics" not in off

    merge = partial(_merge_state, cfg=cfg, merge_impl="interleave")

    @partial(jax.jit, donate_argnums=(0,))
    def ref(state, keys, i_s, i_d, d_s, d_d):
        def body(s, xs):
            k, a, b, c, d = xs
            s = jax.lax.cond(s.n_pending >= jnp.asarray(MAX_PENDING, I32),
                             merge, lambda x: x, s)
            s, _ = _apply_update(s, a, b, c, d, k, cfg, CAP, mav_cap)
            if policy == "eager":
                s = merge(s)
            return s, s.last_affected
        return jax.lax.scan(body, state, (keys, i_s, i_d, d_s, d_d))

    ref_text = ref.lower(state, keys, i_s, i_d, d_s, d_d).as_text()
    assert _normalize_hlo(off) == _normalize_hlo(ref_text), \
        "metrics-OFF run_stream no longer traces the pre-observability HLO"


def test_metrics_scope_in_compiled_executables():
    """named_scope survives into the COMPILED HLO op metadata: the ON
    executable carries "obs_metrics" (so the OFF-side leak detector in the
    identity test above is a live check, not vacuously true) and the OFF
    executable does not. Tiny config to keep the two compiles cheap."""
    cfg = WalkConfig(n_walks_per_vertex=1, length=4)
    g, store = make_graph_store(cfg)
    i_s, i_d, d_s, d_d = make_stream(n_batches=2)
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    kw = dict(cfg=cfg, capacity=32, mav_capacity=store.size,
              max_pending=MAX_PENDING, merge_policy="on-demand",
              merge_impl="interleave")
    state = EngineState.create(g, store, MAX_PENDING, 32 * cfg.length)
    off = _run_stream_jit.lower(state, keys, i_s, i_d, d_s, d_d,
                                **kw).compile().as_text()
    assert "obs_metrics" not in off
    on = _run_stream_obs_jit.lower(state, StreamMetrics.empty(), keys, i_s,
                                   i_d, d_s, d_d, **kw).compile().as_text()
    assert "obs_metrics" in on


# ------------------------------------------ phase scopes (DESIGN.md §10)

# the stream step's own ops, as the compiled metadata names them: under the
# scan body's call, and not the call boundary itself (XLA attributes the
# broadcasts it makes at an inlined call to the call: `.../jit(_rewalk)`)
STEP_PATH = "jit(_run_stream_body)/while/body/closed_call/"
SCOPED_KINDS = ("fusion", "custom-call", "sort", "scatter", "gather")
STEP_PHASES = {
    "order1": {"graph_update", "mav", "sample", "write_back", "merge"},
    "order2-factorized": {"graph_update", "mav", "findnext", "sample",
                          "intersect", "write_back", "merge"},
}
STEP_MODELS = {
    "order1": WalkModel(),
    "order2-factorized": WalkModel(order=2, p=0.5, q=2.0,
                                   sampler="factorized", dmax=16),
}


def _step_ops(text: str):
    """(kind, op_name) of each fusion, custom-call, sort, scatter and
    gather instruction the stream step traced, from compiled HLO text."""
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = .*? ([\w-]+)\(", line)
        if not m or m.group(1) not in SCOPED_KINDS:
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        name = name.group(1) if name else ""
        last = name.rsplit("/", 1)[-1]
        if name.startswith(STEP_PATH) and not last.startswith("jit("):
            yield m.group(1), name


@pytest.mark.parametrize("model", list(STEP_MODELS))
def test_every_stream_step_op_carries_a_wharf_scope(model):
    """The compiled stream step names every phase of section 1 in its op
    metadata, and every fusion, custom call, sort, scatter and gather the
    step traces carries a `wharf/` scope: a trace can charge each op's
    device time to a phase (benchmarks/chip/xplane_ops.py)."""
    cfg = WalkConfig(n_walks_per_vertex=2, length=8,
                     model=STEP_MODELS[model])
    g, store = make_graph_store(cfg)
    i_s, i_d, d_s, d_d = make_stream(n_batches=2)
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    state = EngineState.create(g, store, 1, 32 * cfg.length)
    text = _run_stream_jit.lower(
        state, keys, i_s, i_d, d_s, d_d, cfg=cfg, capacity=32,
        mav_capacity=store.size, max_pending=1, merge_policy="on-demand",
        merge_impl="interleave").compile().as_text()
    ops = list(_step_ops(text))
    assert len(ops) > 100
    unscoped = [(k, n) for k, n in ops if "wharf/" not in n]
    assert not unscoped, unscoped[:5]
    phases = {re.findall(r"wharf/(\w+)", n)[-1] for _, n in ops}
    assert phases == STEP_PHASES[model]
    assert phases <= set(obs_trace.PHASES)


def test_scope_and_phase_spell_names_as_documented():
    assert obs_trace.PHASES == ("graph_update", "mav", "findnext",
                                "intersect", "sample", "write_back",
                                "merge", "collective")
    assert (obs_trace.GRAPH_UPDATE, obs_trace.MAV) == ("graph_update", "mav")

    def f(x):
        with obs_trace.scope(obs_trace.MAV):
            return x + 1

    text = jax.jit(f).lower(jnp.ones(3)).as_text(debug_info=True)
    assert '"jit(f)/wharf/mav/add"' in text
    with pytest.raises(ValueError, match="unknown engine phase"):
        obs_trace.scope("wharf/mav")
    with pytest.raises(ValueError, match="unknown engine phase"):
        obs_trace.scope("rewalk")


def test_engine_host_spans_name_stage_and_dispatch(tmp_path):
    """run_stream opens wharf/run_stream around wharf/stage and
    wharf/dispatch; the per-batch driver and merge open wharf/update and
    wharf/consolidate. The spans stay on the host: a second stream under
    them compiles nothing, and no host span name reaches the program."""
    cfg = WalkConfig(n_walks_per_vertex=1, length=4)
    g, store = make_graph_store(cfg)
    i_s, i_d, d_s, d_d = make_stream(n_batches=2)
    eng = WalkEngine(graph=g, store=store, cfg=cfg, rewalk_capacity=CAP,
                     max_pending=MAX_PENDING)
    compiles = []

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    path = str(tmp_path / "spans.jsonl")
    obs_trace.install(path)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        eng.run_stream(jax.random.PRNGKey(1), i_s, i_d, d_s, d_d)
        jax.block_until_ready(eng.state)
        first = len(compiles)
        eng.run_stream(jax.random.PRNGKey(2), i_s, i_d, d_s, d_d)
        jax.block_until_ready(eng.state)
        assert len(compiles) == first
        eng.insert_edges(jax.random.PRNGKey(3), i_s[0], i_d[0])
        eng.merge()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
        obs_trace.uninstall()
    spans = obs_trace.read_spans(path)
    names = [e["name"] for e in spans]
    # a span is written when it closes: children before their parent
    assert names[:6] == ["wharf/stage", "wharf/dispatch", "wharf/run_stream"
                         ] * 2
    assert spans[2]["args"] == {"n_batches": 2}
    # four batches filled the pending buffer: the update merges first
    assert names[6:] == ["wharf/consolidate", "wharf/update",
                         "wharf/consolidate"]
    by = {e["name"]: e for e in spans[:3]}
    run = by["wharf/run_stream"]
    for child in ("wharf/stage", "wharf/dispatch"):
        c = by[child]
        assert run["ts"] <= c["ts"] and (c["ts"] + c["dur"]
                                         <= run["ts"] + run["dur"] + 1e-3)
    state = EngineState.create(g, store, MAX_PENDING, CAP * cfg.length)
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    kw = dict(cfg=cfg, capacity=CAP, mav_capacity=store.size,
              max_pending=MAX_PENDING, merge_policy="on-demand",
              merge_impl="interleave")
    with obs_trace.phase("wharf/run_stream"), obs_trace.phase(
            "wharf/dispatch"):
        inside = _run_stream_jit.lower(state, keys, i_s, i_d, d_s, d_d,
                                       **kw).as_text(debug_info=True)
    assert "wharf/dispatch" not in inside
    assert "wharf/run_stream" not in inside
    assert inside == _run_stream_jit.lower(
        state, keys, i_s, i_d, d_s, d_d, **kw).as_text(debug_info=True)


# ------------------------------------------------- (b) + (c) single host


def _replay_counters(affected, aux, length, n_batches, policy):
    """Pure-numpy replay of the single-host counters from the OFF run's
    per-step outputs (affected counts + stacked UpdateAux)."""
    affected = np.asarray(affected)
    p_min = np.asarray(aux.p_min)          # [n_batches, CAP]
    valid = np.asarray(aux.lane_valid)
    hist = np.zeros(PMIN_BUCKETS, np.int64)
    suffix = length - p_min
    bucket = np.clip((suffix * PMIN_BUCKETS) // length, 0, PMIN_BUCKETS - 1)
    for b in range(PMIN_BUCKETS):
        hist[b] = int(((bucket == b) & valid).sum())
    # merge-schedule closed form, step by step (stream_step order: forced
    # cond-merge -> append -> eager merge; hwm reads post-append fill)
    fill = hwm = forced = eager = 0
    for _ in range(n_batches):
        if fill >= MAX_PENDING:
            fill = 0
            forced += 1
        fill += 1
        hwm = max(hwm, fill)
        if policy == "eager":
            fill = 0
            eager += 1
    return {
        "steps": n_batches,
        "affected_total": int(affected.sum()),
        "affected_max": int(affected.max()),
        "pmin_hist": hist,
        "pending_hwm": hwm,
        "merges_forced": forced,
        "merges_eager": eager,
        # global all_to_all volume: each valid lane is routed once per
        # non-terminal re-walked position, i.e. (l-1) - p_min times
        "handoff_sent": int((np.maximum(length - 1 - p_min, 0)
                             * valid).sum()),
    }


@pytest.mark.parametrize("policy", ["on-demand", "eager"])
def test_metrics_on_bit_identity_and_replay(policy):
    """Metrics ON vs OFF on the same mixed stream: identical per-step
    affected counts, identical UpdateAux, identical merged store + graph;
    the ON run's exported counters equal the numpy replay of the OFF run."""
    cfg = WalkConfig(n_walks_per_vertex=2, length=8)
    g, store = make_graph_store(cfg)
    i_s, i_d, d_s, d_d = make_stream()
    key = jax.random.PRNGKey(3)

    eng_off = make_engine(g, store, cfg, policy)
    aff_off, aux_off = eng_off.run_stream(key, i_s, i_d, d_s, d_d,
                                          return_masks=True)
    eng_on = make_engine(g, store, cfg._replace(metrics=True), policy)
    aff_on, aux_on = eng_on.run_stream(key, i_s, i_d, d_s, d_d,
                                       return_masks=True)
    assert eng_on.metrics is not None

    np.testing.assert_array_equal(np.asarray(aff_off), np.asarray(aff_on))
    for f in ("walk_ids", "lane_valid", "p_min"):
        np.testing.assert_array_equal(np.asarray(getattr(aux_off, f)),
                                      np.asarray(getattr(aux_on, f)),
                                      err_msg=f)
    eng_off.merge()
    eng_on.merge()
    assert not eng_off.mav_overflowed and not eng_on.mav_overflowed
    np.testing.assert_array_equal(np.asarray(eng_off.graph.codes),
                                  np.asarray(eng_on.graph.codes))
    for f in ("owner", "code", "epoch", "slot_epoch", "offsets", "packed",
              "widths"):
        np.testing.assert_array_equal(np.asarray(getattr(eng_off.store, f)),
                                      np.asarray(getattr(eng_on.store, f)),
                                      err_msg=(policy, f))

    want = _replay_counters(aff_off, aux_off, cfg.length, N_BATCHES, policy)
    s = summary(eng_on.metrics)
    assert s["steps"] == want["steps"]
    assert s["affected"]["total"] == want["affected_total"]
    assert s["affected"]["max_per_step"] == want["affected_max"]
    assert s["rewalk_suffix_hist"]["counts"] == list(want["pmin_hist"])
    assert s["pending"]["high_water_mark"] == want["pending_hwm"]
    assert s["merges"] == {"forced": want["merges_forced"],
                           "eager": want["merges_eager"]}
    assert s["order2"]["deg_fallback_lane_steps"] == 0  # order-1 model
    assert s["handoff"]["sent_total"] == 0              # single host
    assert all(v is None for v in s["overflow_first_epoch"].values())


def test_deg_fallback_counter_replay():
    """Order-2 factorized stream, ONE batch (so the final graph is the
    graph every lane sampled against): deg_fallback_lanes equals the numpy
    count of emitted non-terminal positions whose current vertex has
    deg > dmax, read off the final corpus + final degrees."""
    model = WalkModel(order=2, p=0.5, q=2.0, sampler="factorized", dmax=4)
    cfg = WalkConfig(n_walks_per_vertex=2, length=8, model=model,
                     metrics=True)
    g, store = make_graph_store(cfg)
    i_s, i_d, d_s, d_d = make_stream(n_batches=1)
    eng = make_engine(g, store, cfg, "on-demand")
    aff, aux = eng.run_stream(jax.random.PRNGKey(3), i_s, i_d, d_s, d_d,
                              return_masks=True)
    walks = np.asarray(eng.walk_matrix())       # post-update corpus
    deg = np.asarray(eng.graph.degrees())
    p_min = np.asarray(aux.p_min[0])
    valid = np.asarray(aux.lane_valid[0])
    wids = np.asarray(aux.walk_ids[0])
    want = 0
    for w, pm, ok in zip(wids, p_min, valid):
        if not ok:
            continue
        for p in range(int(pm), cfg.length - 1):   # emitted non-terminal
            if deg[walks[w, p]] > model.dmax:
                want += 1
    got = summary(eng.metrics)["order2"]["deg_fallback_lane_steps"]
    assert got == want
    assert want > 0, "fixture too sparse to exercise the deg>dmax fallback"


def _szudzik_py(a: int, b: int) -> int:
    return b * b + a if a < b else a * a + a + b


def test_findnext_counters_replay():
    """Order-2 stream, ONE batch from a fresh corpus (so the traversal
    reads the initial corpus): findnext_queries counts every lane's l-1
    prefix FINDNEXTs, and findnext_chunks equals the numpy sum over them of
    c1 - c0 + 1, the chunks each query's candidate range [lo, hi) spans in
    the initial store. With metrics OFF no obs_metrics op is traced."""
    model = WalkModel(order=2, p=0.5, q=2.0, sampler="factorized", dmax=4)
    cfg = WalkConfig(n_walks_per_vertex=2, length=8, model=model)
    g, store = make_graph_store(cfg)
    i_s, i_d, d_s, d_d = make_stream(n_batches=1)
    keys = jax.random.split(jax.random.PRNGKey(3), 1)
    state = EngineState.create(g, store, MAX_PENDING, CAP * cfg.length)
    off = _run_stream_jit.lower(
        state, keys, i_s, i_d, d_s, d_d, cfg=cfg, capacity=CAP,
        mav_capacity=store.size, max_pending=MAX_PENDING,
        merge_policy="on-demand", merge_impl="interleave").as_text()
    assert "obs_metrics" not in off

    walks = np.asarray(make_engine(g, store, cfg, "on-demand").walk_matrix())
    eng = make_engine(g, store, cfg._replace(metrics=True), "on-demand")
    _, aux = eng.run_stream(jax.random.PRNGKey(3), i_s, i_d, d_s, d_d,
                            return_masks=True)
    code = np.asarray(store.code).astype(object)
    offsets = np.asarray(store.offsets)
    vmin, vmax = np.asarray(store.vmin), np.asarray(store.vmax)
    chunk, length = 128, cfg.length
    want_q = want_c = 0
    for w in np.asarray(aux.walk_ids[0]):
        for p in range(length - 1):
            v = walks[w, p]
            f = int(w) * length + p
            seg = list(code[offsets[v]:offsets[v + 1]])
            lo = offsets[v] + int(np.searchsorted(
                seg, _szudzik_py(f, int(vmin[v])), side="left"))
            hi = offsets[v] + int(np.searchsorted(
                seg, _szudzik_py(f, int(vmax[v])), side="right"))
            c0, c1 = lo // chunk, max(hi - 1, lo) // chunk
            want_q += 1
            want_c += c1 - c0 + 1
    got = summary(eng.metrics)["order2"]
    assert got["findnext_queries"] == want_q == CAP * (length - 1)
    assert got["findnext_chunks"] == want_c
    assert want_q <= want_c < 2 * want_q


# ------------------------------------------------------------ (b) sharded


def test_sharded_metrics_bit_identity_and_replay():
    """8-shard shard_map engine with metrics ON: bit-identical to the
    single-host metrics-OFF run (stores, graph, affected); replicated
    counters uniform across shards; combined counters match the numpy
    replay (including the exact global handoff volume)."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import StreamingGraph, generate_corpus
        from repro.core.corpus import WalkConfig
        from repro.core.update import WalkEngine, pending_after_stream
        from repro.data.streams import mixed_edge_stream, rmat_edges
        from repro.distr.sharded import (ShardSpec, shard_state,
                                         sharded_run_stream, unshard_state)
        from repro.obs.export import summary
        from repro.obs.metrics import PMIN_BUCKETS

        n, ecap, cap, nb = 64, 4096, 128, 6
        cfg = WalkConfig(n_walks_per_vertex=2, length=8, megakernel="off")
        src, dst = rmat_edges(jax.random.PRNGKey(0), 200, 6)
        graph = StreamingGraph.from_edges(src, dst, n, ecap)
        store = generate_corpus(jax.random.PRNGKey(1), graph, cfg)
        i_s, i_d, d_s, d_d = mixed_edge_stream(
            jax.random.PRNGKey(2), nb, 16, 4, 6)
        key = jax.random.PRNGKey(3)
        spec = ShardSpec(n_shards=8, n_vertices=n, edge_capacity=1024,
                         store_capacity=512, mav_capacity=512, slab=cap)

        for policy in ("on-demand", "eager"):
            eng = WalkEngine(graph=jax.tree.map(jnp.array, graph),
                             store=jax.tree.map(jnp.array, store),
                             cfg=cfg, merge_policy=policy,
                             rewalk_capacity=cap, max_pending=4)
            ref_aff, ref_aux = eng.run_stream(key, i_s, i_d, d_s, d_d,
                                              return_masks=True)
            eng.merge()
            assert not eng.mav_overflowed

            cfg_on = cfg._replace(metrics=True)
            stacked = shard_state(jax.tree.map(jnp.array, graph),
                                  jax.tree.map(jnp.array, store), spec,
                                  cap, max_pending=4)
            stacked, aff, m = sharded_run_stream(
                stacked, key, i_s, i_d, d_s, d_d, cfg=cfg_on, spec=spec,
                capacity=cap, max_pending=4, merge_policy=policy)
            g2, s2, ovf = unshard_state(stacked, ecap)
            assert not ovf
            assert np.array_equal(np.asarray(ref_aff), np.asarray(aff))
            assert np.array_equal(np.asarray(eng.graph.codes),
                                  np.asarray(g2.codes)), policy
            for f in ("owner", "code", "epoch", "slot_epoch"):
                assert np.array_equal(np.asarray(getattr(eng.store, f)),
                                      np.asarray(getattr(s2, f))), \\
                    (policy, f)

            # replicated counters are uniform across the 8 shards
            for leaf in (m.n_steps, m.affected_total, m.affected_max,
                         m.pending_hwm, m.merges_forced, m.merges_eager):
                assert np.ptp(np.asarray(leaf)) == 0, policy
            assert (np.asarray(m.pmin_hist)
                    == np.asarray(m.pmin_hist)[0]).all()

            # combined counters vs numpy replay of the reference run
            s = summary(m)   # [S,...]-stacked -> combine_shards inside
            aff_np = np.asarray(ref_aff)
            p_min = np.asarray(ref_aux.p_min)
            valid = np.asarray(ref_aux.lane_valid)
            assert s["steps"] == nb
            assert s["affected"]["total"] == int(aff_np.sum())
            assert s["affected"]["max_per_step"] == int(aff_np.max())
            suffix = cfg.length - p_min
            bucket = np.clip((suffix * PMIN_BUCKETS) // cfg.length, 0,
                             PMIN_BUCKETS - 1)
            hist = [int(((bucket == b) & valid).sum())
                    for b in range(PMIN_BUCKETS)]
            assert s["rewalk_suffix_hist"]["counts"] == hist, policy
            if policy == "eager":
                assert s["merges"] == {"forced": 0, "eager": nb}
            else:
                fill = pending_after_stream(0, nb, 4, policy)
                assert s["merges"]["eager"] == 0
                assert s["merges"]["forced"] == (nb - fill) // 4
            # exact global handoff volume: each valid lane is routed once
            # per non-terminal re-walked position
            want_sent = int((np.maximum(cfg.length - 1 - p_min, 0)
                             * valid).sum())
            assert s["handoff"]["sent_total"] == want_sent, policy
            assert 0 <= s["handoff"]["cross_shard_total"] <= want_sent
            assert s["handoff"]["max_dest_load_per_step"] <= cap
            assert all(v is None
                       for v in s["overflow_first_epoch"].values())

            # staleness (DESIGN.md 12): lag counters ride the sharded scan
            # replicated (slot_epoch is replicated), the auditor is skipped
            # (store partitioned -> its counters stay 0), and the lag
            # counters equal a single-host metrics-ON run of the same stream
            st = m.staleness
            for leaf in (st.lag_hist, st.lag_sum, st.lag_max,
                         st.walk_steps, st.stale_walk_steps):
                arr = np.asarray(leaf)
                assert (arr == arr[0]).all(), policy
            for leaf in (st.audit_walks, st.audit_transitions,
                         st.audit_invalid):
                assert np.asarray(leaf).sum() == 0, policy
            eng_on = WalkEngine(graph=jax.tree.map(jnp.array, graph),
                                store=jax.tree.map(jnp.array, store),
                                cfg=cfg_on, merge_policy=policy,
                                rewalk_capacity=cap, max_pending=4)
            eng_on.run_stream(key, i_s, i_d, d_s, d_d)
            ss = eng_on.metrics.staleness
            assert np.array_equal(np.asarray(st.lag_hist)[0],
                                  np.asarray(ss.lag_hist)), policy
            for a, b in ((st.lag_max, ss.lag_max),
                         (st.walk_steps, ss.walk_steps),
                         (st.stale_walk_steps, ss.stale_walk_steps)):
                assert int(np.asarray(a)[0]) == int(b), policy
            print("OK", policy, "sent", want_sent)
        print("OK sharded metrics bit-identical + replay")
    """)


# ------------------------------------------------- staleness (DESIGN.md §12)


def _replay_staleness(aux, slot_epoch0, n_walks, length, n_batches,
                      epoch0=0):
    """Pure-numpy replay of the walk-freshness counters from the per-step
    UpdateAux: slot_epoch evolves by stamping each valid lane's rewritten
    suffix [p_min, l), then per-walk lag = epoch - max(slot_epoch) (the min
    slot-lag — rewalks always rewrite through the terminal slot)."""
    from repro.obs.staleness import LAG_BUCKETS, LAG_THRESHOLDS, STALE_LAG

    se = np.asarray(slot_epoch0, np.int64).reshape(n_walks, length).copy()
    wids = np.asarray(aux.walk_ids)
    p_min = np.asarray(aux.p_min)
    valid = np.asarray(aux.lane_valid)
    hist = np.zeros(LAG_BUCKETS, np.int64)
    lag_sum = 0.0
    lag_max = walk_steps = stale = 0
    for step in range(n_batches):
        epoch = epoch0 + step + 1
        for w, pm, ok in zip(wids[step], p_min[step], valid[step]):
            if ok:
                se[int(w), int(pm):] = epoch
        lag = epoch - se.max(axis=1)
        bucket = (lag[:, None] >= np.asarray(LAG_THRESHOLDS)[None]).sum(1)
        np.add.at(hist, bucket, 1)
        lag_sum += float(lag.sum())
        lag_max = max(lag_max, int(lag.max()))
        walk_steps += n_walks
        stale += int((lag >= STALE_LAG).sum())
    return {"slot_epoch": se, "hist": hist, "lag_sum": lag_sum,
            "lag_max": lag_max, "walk_steps": walk_steps, "stale": stale}


@pytest.mark.parametrize("policy", ["on-demand", "eager"])
def test_staleness_counters_match_numpy_replay(policy):
    """The scan-carried freshness counters equal the numpy replay of the
    same stream (lag histogram, sum/max, stale-walk steps), the replayed
    slot_epoch equals the engine's, and the auditor reads 0 invalid
    transitions on a maintained engine."""
    cfg = WalkConfig(n_walks_per_vertex=2, length=8, metrics=True)
    g, store = make_graph_store(cfg)
    i_s, i_d, d_s, d_d = make_stream()
    eng = make_engine(g, store, cfg, policy)
    aff, aux = eng.run_stream(jax.random.PRNGKey(3), i_s, i_d, d_s, d_d,
                              return_masks=True)
    assert not eng.mav_overflowed

    want = _replay_staleness(aux, store.slot_epoch, store.n_walks,
                             cfg.length, N_BATCHES)
    np.testing.assert_array_equal(
        np.asarray(eng.store.slot_epoch).reshape(store.n_walks, cfg.length),
        want["slot_epoch"], err_msg="slot_epoch replay diverged")
    st = eng.metrics.staleness
    np.testing.assert_array_equal(np.asarray(st.lag_hist), want["hist"])
    assert float(st.lag_sum) == want["lag_sum"]
    assert int(st.lag_max) == want["lag_max"]
    assert int(st.walk_steps) == want["walk_steps"]
    assert int(st.stale_walk_steps) == want["stale"]

    s = summary(eng.metrics)["staleness"]
    assert s["walk_lag_hist"]["counts"] == list(want["hist"])
    assert s["stale_fraction"] == round(want["stale"]
                                        / want["walk_steps"], 6)
    # divergence auditor: k walks x (l-1) transitions per step, 0 invalid
    # on a maintained engine (the engine's own rewalks track every update)
    assert s["audit"]["walks"] == cfg.audit_k * N_BATCHES
    assert s["audit"]["transitions"] == cfg.audit_k * (cfg.length - 1) \
        * N_BATCHES
    assert s["audit"]["invalid"] == 0


def test_divergence_auditor_detects_foreign_edits():
    """Deleting graph edges BEHIND the engine's back (state surgery, no
    maintenance step) makes the auditor count invalid transitions — and the
    count matches an independent numpy replay over the reconstructed walks
    and the same fold_in-derived sample."""
    from repro.core.corpus import walk_start_vertex
    from repro.obs.staleness import AUDIT_SALT

    cfg = WalkConfig(n_walks_per_vertex=2, length=8, metrics=True,
                     audit_k=16)
    src, dst = rmat_edges(jax.random.PRNGKey(0), 200, LOG2_N)
    g = StreamingGraph.from_edges(src, dst, N, 4096)
    store = generate_corpus(jax.random.PRNGKey(1), g, cfg)
    eng = make_engine(g, store, cfg, "on-demand")
    i_s, i_d, d_s, d_d = make_stream(n_batches=1)
    key1, key2 = jax.random.PRNGKey(3), jax.random.PRNGKey(4)
    eng.run_stream(key1, i_s, i_d, d_s, d_d)
    assert int(eng.metrics.staleness.audit_invalid) == 0

    # foreign edit: a graph rebuilt WITHOUT most original edges, swapped in
    # under the engine — walks still reference the removed edges
    g_cut = StreamingGraph.from_edges(src[:40], dst[:40], N, 4096)
    eng.state = eng.state.replace(graph=jax.tree.map(jnp.array, g_cut))
    i2, j2, k2, l2 = make_stream(n_batches=1, seed=11)
    eng.run_stream(key2, i2, j2, k2, l2)
    invalid = int(eng.metrics.staleness.audit_invalid)
    assert invalid > 0, "auditor blind to foreign graph edits"

    # numpy replay of the second step's audit: same sampled walk ids (the
    # audit key folds off the per-step update key), walks reconstructed
    # from the merged corpus, transitions checked against the live graph
    step_key = jax.random.split(key2, 1)[0]
    akey = jax.random.fold_in(step_key, AUDIT_SALT)
    wids = np.asarray(jax.random.randint(akey, (cfg.audit_k,), 0,
                                         store.n_walks))
    walks = np.asarray(eng.walk_matrix())
    deg = np.asarray(eng.graph.degrees())
    starts = np.asarray(walk_start_vertex(jnp.asarray(wids, jnp.uint32),
                                          cfg.n_walks_per_vertex))
    np.testing.assert_array_equal(walks[wids, 0], starts)
    u = jnp.asarray(walks[wids, :-1].reshape(-1), jnp.uint32)
    x = jnp.asarray(walks[wids, 1:].reshape(-1), jnp.uint32)
    has = np.asarray(eng.graph.has_edge(u, x)).reshape(len(wids), -1)
    loop_ok = ((walks[wids, :-1] == walks[wids, 1:])
               & (deg[walks[wids, :-1]] == 0))
    assert invalid == int((~(has | loop_ok)).sum())


def test_audit_k_zero_compiles_auditor_out():
    """audit_k=0 keeps the lag counters but no audit sampling: the audit
    counters stay 0 even against a corrupted graph."""
    cfg = WalkConfig(n_walks_per_vertex=2, length=8, metrics=True,
                     audit_k=0)
    src, dst = rmat_edges(jax.random.PRNGKey(0), 200, LOG2_N)
    g = StreamingGraph.from_edges(src, dst, N, 4096)
    store = generate_corpus(jax.random.PRNGKey(1), g, cfg)
    eng = make_engine(g, store, cfg, "on-demand")
    g_cut = StreamingGraph.from_edges(src[:40], dst[:40], N, 4096)
    eng.state = eng.state.replace(graph=jax.tree.map(jnp.array, g_cut))
    i_s, i_d, d_s, d_d = make_stream(n_batches=1)
    eng.run_stream(jax.random.PRNGKey(3), i_s, i_d, d_s, d_d)
    st = eng.metrics.staleness
    assert int(st.audit_walks) == 0
    assert int(st.audit_transitions) == 0
    assert int(st.audit_invalid) == 0
    assert int(st.walk_steps) == store.n_walks


# --------------------------------------------------------------- maintainer


def test_maintainer_metrics_bit_identity():
    """cfg.walk.metrics on the co-scheduled maintainer: per-step training
    metrics and the final (engine + model) state stay bit-identical, and
    the engine-side counters accumulate across run_stream calls."""
    from repro.downstream import EmbeddingMaintainer, MaintainerConfig

    wcfg = WalkConfig(n_walks_per_vertex=2, length=8)
    g, store = make_graph_store(wcfg)
    i_s, i_d, d_s, d_d = make_stream()

    def build(metrics):
        cfg = MaintainerConfig(walk=wcfg._replace(metrics=metrics),
                               n_vertices=N, dim=16, window=2, n_negative=3,
                               rewalk_capacity=CAP, max_pending=MAX_PENDING)
        return EmbeddingMaintainer(graph=jax.tree.map(jnp.array, g),
                                   store=jax.tree.map(jnp.array, store),
                                   cfg=cfg, key=jax.random.PRNGKey(5))

    key = jax.random.PRNGKey(6)
    mt_off, mt_on = build(False), build(True)
    out_off = mt_off.run_stream(key, i_s, i_d, d_s, d_d)
    out_on = mt_on.run_stream(key, i_s, i_d, d_s, d_d)
    for a, b in zip(jax.tree.leaves(out_off), jax.tree.leaves(out_on)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(mt_off.state),
                    jax.tree.leaves(mt_on.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(mt_on.metrics.n_steps) == N_BATCHES
    assert (int(mt_on.metrics.affected_total)
            == int(mt_on.state.engine.total_affected))
    # a second stream continues the same counters (accumulate, not reset)
    i2, j2, k2, l2 = make_stream(n_batches=2, seed=8)
    mt_on.run_stream(jax.random.PRNGKey(7), i2, j2, k2, l2)
    assert int(mt_on.metrics.n_steps) == N_BATCHES + 2


# ------------------------------------------------------- export + trace


def _fake_metrics():
    m = StreamMetrics.empty()
    return m.replace(
        n_steps=jnp.asarray(4, I32), affected_total=jnp.asarray(100, I32),
        affected_max=jnp.asarray(40, I32),
        pmin_hist=jnp.asarray([0, 1, 2, 3, 4, 5, 6, 79], I32),
        pending_hwm=jnp.asarray(3, I32), merges_forced=jnp.asarray(1, I32),
        merges_eager=jnp.asarray(0, I32),
        handoff_sent=jnp.asarray(64, I32),
        handoff_cross=jnp.asarray(16, I32),
        handoff_max_load=jnp.asarray(9, I32),
        overflow_first_epoch=jnp.asarray([NEVER, 3, NEVER, NEVER],
                                         jnp.uint32))


def test_export_summary_schema_and_prometheus(tmp_path):
    s = summary(_fake_metrics(), serve={"ppr_cache_hit": 7,
                                        "ppr_cache_miss": 2})
    assert s["schema"] == 2
    assert s["affected"] == {"total": 100, "max_per_step": 40,
                             "mean_per_step": 25.0}
    assert sum(s["rewalk_suffix_hist"]["counts"]) == 100
    assert len(s["rewalk_suffix_hist"]["edges"]) == PMIN_BUCKETS + 1
    assert s["overflow_first_epoch"] == {"graph": None, "store_merge": 3,
                                         "mav_gather": None,
                                         "handoff_slab": None}
    assert s["serve"] == {"ppr_cache_hit": 7, "ppr_cache_miss": 2}

    text = to_prometheus(s)
    assert "wharf_stream_steps_total 4" in text
    assert "wharf_affected_walks_total 100" in text
    assert 'wharf_merges_total{cause="forced"} 1' in text
    assert 'wharf_overflow_first_epoch{source="store_merge"} 3' in text
    assert 'source="graph"' not in text          # never tripped -> no line
    assert 'wharf_rewalk_suffix_fraction_bucket{le="1.0"} 100' in text
    assert "wharf_serve_ppr_cache_hit_total 7" in text
    # to_prometheus accepts the raw pytree too and agrees with the dict
    assert to_prometheus(_fake_metrics()).splitlines()[0] == \
        text.splitlines()[0]

    p = tmp_path / "counters.json"
    out = write_summary(str(p), _fake_metrics())
    import json
    assert json.loads(p.read_text()) == out


def test_export_combines_stacked_shards():
    """summary() on a [S,...]-stacked pytree reduces per combine_shards:
    shard-0 replicated counters, summed handoff, earliest overflow."""
    a, b = _fake_metrics(), _fake_metrics().replace(
        handoff_sent=jnp.asarray(36, I32),
        handoff_max_load=jnp.asarray(11, I32),
        overflow_first_epoch=jnp.asarray([5, 9, NEVER, NEVER], jnp.uint32))
    stacked = jax.tree.map(lambda *ls: jnp.stack(ls), a, b)
    s = summary(stacked)
    assert s["affected"]["total"] == 100          # shard 0, not the sum
    assert s["handoff"]["sent_total"] == 100      # 64 + 36
    assert s["handoff"]["max_dest_load_per_step"] == 11
    assert s["overflow_first_epoch"]["graph"] == 5
    assert s["overflow_first_epoch"]["store_merge"] == 3


def test_trace_jsonl_roundtrip(tmp_path):
    path = str(tmp_path / "spans.jsonl")
    obs_trace.install(path)
    try:
        with obs_trace.phase("serve/ppr_row", cat="serve", v=3):
            pass
        with obs_trace.phase(obs_trace.MERGE):
            pass
    finally:
        obs_trace.uninstall()
    assert obs_trace.active() is None
    spans = obs_trace.read_spans(path)
    assert [e["name"] for e in spans] == ["serve/ppr_row", "merge"]
    for e in spans:
        assert e["ph"] == "X" and e["dur"] >= 0 and e["ts"] >= 0
    assert spans[0]["cat"] == "serve" and spans[0]["args"] == {"v": 3}
    assert spans[1]["cat"] == "engine"
    # with no log installed, phase() is a pure annotation no-op
    with obs_trace.phase("uninstalled"):
        pass
    assert len(obs_trace.read_spans(path)) == 2


def test_serve_counters():
    from repro.serve.walk_queries import WalkQueryService

    cfg = WalkConfig(n_walks_per_vertex=2, length=8)
    g, store = make_graph_store(cfg)
    eng = make_engine(g, store, cfg, "on-demand")
    svc = WalkQueryService(engine=eng)
    svc.walk_matrix()
    svc.walk_matrix()            # same epoch -> cache hit
    c = svc.obs_counters()
    assert c["ppr_cache_miss"] == 1 and c["ppr_cache_hit"] == 1
    assert c["overlay_rebuilds"] >= 1


def test_summary_v1_upgrades_to_v2():
    """Schema v2 is append-only: a v1 payload upgrades by zero-filling the
    staleness section; v2 round-trips unchanged; unknown schemas raise."""
    from repro.obs.export import upgrade_summary

    s2 = summary(_fake_metrics())
    v1 = {k: v for k, v in s2.items() if k != "staleness"}
    v1["schema"] = 1
    up = upgrade_summary(dict(v1))
    assert up["schema"] == 2
    assert up["staleness"]["walk_steps"] == 0
    assert up["staleness"]["stale_fraction"] == 0.0
    assert up["staleness"]["audit"]["divergence_rate"] == 0.0
    # every v1 key survives untouched
    for k, v in v1.items():
        if k != "schema":
            assert up[k] == v
    assert upgrade_summary(dict(s2)) == s2          # idempotent on v2
    with pytest.raises(ValueError):
        upgrade_summary({"schema": 99})


def test_prometheus_escaping_and_headers():
    """Exposition-format hygiene: label values escape backslash, quote and
    newline; metric names sanitize; every emitted sample family carries
    exactly one # HELP and one # TYPE line."""
    from repro.obs.export import escape_label_value, metric_name

    assert escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
    assert escape_label_value("plain") == "plain"
    assert metric_name("serve/walk matrix-reads") == \
        "serve_walk_matrix_reads"

    weird = 'serve/we"ird\\kind\nq'
    hist = {"count": 3, "mean_us": 10.0, "p50_us": 8.0, "p95_us": 16.0,
            "p99_us": 16.0}
    sl = {"window_s": 2.0,
          "kinds": {weird: dict(hist, errors=1, validation_errors=0,
                                qps=1.5, by={"live/percall": hist})},
          "targets": {weird: {"latency_us": 1000.0, "objective": 0.99}},
          "burn_rates": {weird: 0.25}}
    text = to_prometheus(_fake_metrics(),
                         serve={'odd key': 2, "ppr_cache_hit": 7}, slo=sl)
    assert 'kind="serve/we\\"ird\\\\kind\\nq"' in text
    assert "wharf_serve_odd_key_total 2" in text
    assert "wharf_walk_freshness_lag_bucket" in text
    assert 'wharf_serve_latency_us{kind="serve/we\\"ird\\\\kind\\nq",' \
        'quantile="p99"} 16.0' in text

    # HELP/TYPE exactly once per family, for every family with samples
    import collections
    help_c = collections.Counter()
    type_c = collections.Counter()
    sampled = set()
    for line in text.splitlines():
        if line.startswith("# HELP "):
            help_c[line.split()[2]] += 1
        elif line.startswith("# TYPE "):
            type_c[line.split()[2]] += 1
        elif line and not line.startswith("#"):
            name = re.split(r"[{ ]", line, 1)[0]
            sampled.add(name)
    for name in sampled:
        fam = re.sub(r"_(bucket|count|sum)$", "", name)
        ok = ({help_c.get(name, 0), type_c.get(name, 0)} == {1}
              or {help_c.get(fam, 0), type_c.get(fam, 0)} == {1})
        assert ok, f"missing/duplicated HELP/TYPE for {name}"


def test_trace_phase_flushes_on_exception():
    """Satellite fix: a phase body that raises still writes its span (with
    an `error` field in args) and still notifies observers; the exception
    propagates."""
    import tempfile

    seen = []

    def watch(name, cat, dur, args, err):
        seen.append((name, err))

    obs_trace.add_observer(watch)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "spans.jsonl")
        obs_trace.install(path)
        try:
            with pytest.raises(RuntimeError, match="boom"):
                with obs_trace.phase("serve/explodes", cat="serve", v=1):
                    raise RuntimeError("boom")
            spans = obs_trace.read_spans(path)
        finally:
            obs_trace.uninstall()
            obs_trace.remove_observer(watch)
    assert [e["name"] for e in spans] == ["serve/explodes"]
    assert spans[0]["args"]["v"] == 1
    assert spans[0]["args"]["error"] == "RuntimeError: boom"
    assert len(seen) == 1
    assert seen[0][0] == "serve/explodes"
    assert isinstance(seen[0][1], RuntimeError)


def test_serve_slo_collector():
    """ServeSLO: log2-bucket quantiles, exact burn rates, span-observer
    wiring through real phase() spans, live/pinned x batched/percall keys."""
    from repro.obs import slo

    h = slo.LatencyHistogram()
    for d in (0.5, 3.0, 3.0, 100.0):
        h.observe(d)
    assert h.count == 4 and h.counts[0] == 1
    assert h.quantile_us(0.50) == 4.0       # covering-bucket upper bound
    assert h.quantile_us(0.99) == 128.0
    assert slo.LatencyHistogram().quantile_us(0.5) == 0.0

    c = slo.ServeSLO(targets={"serve/x": slo.SLOTarget(latency_us=15.0,
                                                       objective=0.9)})
    c.observe("serve/x", 10.0)
    c.observe("serve/x", 20.0, view="pinned", mode="batched")
    assert c.burn_rates() == {"serve/x": 5.0}   # (1/2) / (1 - 0.9)
    s = c.summary()
    k = s["kinds"]["serve/x"]
    assert k["count"] == 2
    assert set(k["by"]) == {"live/percall", "pinned/batched"}
    assert k["p50_us"] == 16.0 and k["qps"] > 0
    assert s["targets"]["serve/x"] == {"latency_us": 15.0,
                                       "objective": 0.9}

    col = slo.install(slo.ServeSLO())
    try:
        assert slo.active() is col
        with obs_trace.phase("serve/q", cat="serve", view="pinned",
                             batch=8):
            pass
        with obs_trace.phase("serve/q", cat="serve"):
            pass
        with obs_trace.phase("engine/ignored"):
            pass
    finally:
        slo.uninstall()
    assert slo.active() is None
    ks = col.summary()["kinds"]
    assert set(ks) == {"serve/q"}
    assert set(ks["serve/q"]["by"]) == {"pinned/batched", "live/percall"}
    # uninstalled -> spans no longer land
    with obs_trace.phase("serve/q", cat="serve"):
        pass
    assert col.summary()["kinds"]["serve/q"]["count"] == 2


def test_serve_validation_error_counter():
    """Host-side ValueError rejections count in `serve_validation_errors`
    (and per kind in an installed SLO collector); the error still raises."""
    from repro.obs import slo
    from repro.serve.walk_queries import WalkQueryService

    cfg = WalkConfig(n_walks_per_vertex=2, length=8)
    g, store = make_graph_store(cfg)
    svc = WalkQueryService(engine=make_engine(g, store, cfg, "on-demand"))
    col = slo.install(slo.ServeSLO())
    try:
        with pytest.raises(ValueError):
            svc.ppr_rows([N + 5])                   # out-of-range vertex
        with pytest.raises(ValueError):
            svc.neighborhoods([0], hops=0)          # bad hops
        with pytest.raises(ValueError):
            svc.ppr_rows([0], restart_prob=1.5)     # bad restart prob
    finally:
        slo.uninstall()
    assert svc.obs_counters()["serve_validation_errors"] == 3
    v = col.summary()["kinds"]
    # validation kinds use the SPAN names (serve/ppr_row covers both the
    # batched and singleton forms) so latency and rejections aggregate
    assert v["serve/ppr_row"]["validation_errors"] == 2
    assert v["serve/neighborhoods"]["validation_errors"] == 1
    # valid queries keep working and don't bump the counter
    svc.ppr_rows([0])
    assert svc.obs_counters()["serve_validation_errors"] == 3


# ------------------------------------------------- regression sentinel (§12)


def test_regress_compare_semantics(tmp_path):
    """Cell statuses (pass/fail/info/new/missing), direction awareness,
    wall-clock-never-gates, config-exact, and override-rule priority."""
    from repro.obs import regress

    base = {"config": {"n": 64}, "t_us": 100.0, "qps": 50.0,
            "counters": {"c": 100}, "acc": 0.80, "gone": 1}
    cur = {"config": {"n": 64}, "t_us": 500.0, "qps": 10.0,
           "counters": {"c": 150}, "acc": 0.78, "fresh": 2}
    v = regress.compare(base, cur)
    by = {c["path"]: c for c in v["cells"]}
    assert v["verdict"] == "fail"
    assert by["counters.c"]["status"] == "fail"      # +50% > 5% gated band
    assert by["t_us"]["status"] == "info"            # wall-clock never gates
    assert by["qps"]["status"] == "info"
    assert by["gone"]["status"] == "missing"
    assert by["fresh"]["status"] == "new"
    assert "acc" not in by                           # -0.02 within abs band

    # direction awareness: a large accuracy GAIN passes, the same-size drop
    # fails (higher_better); quality_gap is the mirror image
    assert regress.compare({"acc": 0.5}, {"acc": 0.9})["verdict"] == "pass"
    assert regress.compare({"acc": 0.9}, {"acc": 0.5})["verdict"] == "fail"
    assert regress.compare({"quality_gap": 0.30},
                           {"quality_gap": 0.02})["verdict"] == "pass"
    assert regress.compare({"quality_gap": 0.02},
                           {"quality_gap": 0.30})["verdict"] == "fail"

    # config cells are exact (any move fails -> forces baseline regen)
    assert regress.compare({"config": {"n": 64}},
                           {"config": {"n": 128}})["verdict"] == "fail"
    # non-numeric cells compare by equality
    assert regress.compare({"pin": {"ok": True}},
                           {"pin": {"ok": False}})["verdict"] == "fail"

    # override rules prepend to (and win over) the defaults
    p = tmp_path / "thresholds.json"
    p.write_text('{"rules": [{"pattern": "counters.c", '
                 '"max_rel_delta": 0.1, "gate": false}]}')
    rules = regress.load_rules(str(p))
    v2 = regress.compare(base, cur, rules)
    assert v2["verdict"] == "pass"
    assert {c["path"]: c["status"] for c in v2["cells"]}["counters.c"] \
        == "info"

    # multi-file verdict aggregation
    vd = regress.Verdict(mode="smoke")
    vd.add("A", {"verdict": "pass", "counts": {}})
    assert vd.verdict == "pass"
    vd.add("B", v)
    out = vd.to_json()
    assert out["verdict"] == "fail" and out["schema"] == 1
    assert set(out["files"]) == {"A", "B"}


def test_check_regression_cli(tmp_path):
    """End-to-end sentinel: --update-baselines copies, a clean re-check
    passes, a gated regression returns exit code 1 with the cell named in
    the verdict JSON."""
    import json

    from benchmarks import check_regression as cr

    fresh = tmp_path / "fresh"
    basedir = tmp_path / "baselines"
    fresh.mkdir()
    payload = {"config": {"n": 8}, "counters": {"c": 100}, "t_us": 5.0}
    (fresh / "BENCH_MEMORY.smoke.json").write_text(json.dumps(payload))

    rc = cr.run_check(True, baseline_dir=str(basedir),
                      thresholds=str(tmp_path / "missing.json"),
                      fresh_dir=str(fresh), update_baselines=True)
    assert rc == 0
    assert (basedir / "BENCH_MEMORY.smoke.json").exists()

    rc = cr.run_check(True, baseline_dir=str(basedir),
                      thresholds=str(tmp_path / "missing.json"),
                      fresh_dir=str(fresh))
    assert rc == 0                                  # identical -> pass

    payload["counters"]["c"] = 200                  # gated counter moved
    payload["t_us"] = 50.0                          # info-only move
    (fresh / "BENCH_MEMORY.smoke.json").write_text(json.dumps(payload))
    rc = cr.run_check(True, baseline_dir=str(basedir),
                      thresholds=str(tmp_path / "missing.json"),
                      fresh_dir=str(fresh))
    assert rc == 1
    verdict = json.loads(
        (fresh / "bench_regression.smoke.json").read_text())
    assert verdict["verdict"] == "fail"
    cells = {c["path"]: c["status"]
             for c in verdict["files"]["BENCH_MEMORY"]["cells"]}
    assert cells["counters.c"] == "fail"
    assert cells["t_us"] == "info"


# ----------------------------------------------------------- import purity


def test_profile_cell_import_is_pure():
    """Importing launch.profile_cell must not mutate XLA_FLAGS (the
    device-topology poisoning ISSUE 8 satellite 2 removed)."""
    before = os.environ.get("XLA_FLAGS")
    sys.modules.pop("repro.launch.profile_cell", None)
    mod = importlib.import_module("repro.launch.profile_cell")
    assert os.environ.get("XLA_FLAGS") == before
    # the mutation is an explicit opt-in helper now
    assert callable(mod._force_host_devices)
