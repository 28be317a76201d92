"""Distributed tests: run in a subprocess with 8 forced host devices so the
main test process keeps its single-device view (per the project brief)."""
import subprocess
import sys
import textwrap

import pytest


def run_sub(code: str):
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=600,
        # JAX_PLATFORMS=cpu: without it jax's backend/plugin discovery can
        # spend minutes in retry backoff on hosts with no accelerator,
        # starving the child (observed as near-zero CPU while tracing)
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
        cwd=".",
    )
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    return res.stdout


def test_distributed_walk_update_equivalence():
    """The pjit-sharded distributed update step must produce the exact same
    store as the single-host WalkEngine (same PRNG stream)."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.wharf_stream import WharfStreamConfig
        from repro.core import StreamingGraph, generate_corpus
        from repro.core.update import WalkEngine
        from repro.distr.engine import (distributed_update_step,
                                        graph_to_dict, store_to_dict,
                                        dict_to_store, wharf_shardings)
        from repro.data.streams import rmat_edges

        cfg = WharfStreamConfig(n_vertices=64, edge_capacity=4096,
                                n_walks_per_vertex=2, length=8,
                                batch_edges=16, rewalk_capacity=128)
        wcfg = cfg.walk_config()
        src, dst = rmat_edges(jax.random.PRNGKey(0), 200, 6)
        g = StreamingGraph.from_edges(src, dst, 64, 4096)
        store = generate_corpus(jax.random.PRNGKey(1), g, wcfg)
        isrc, idst = rmat_edges(jax.random.PRNGKey(2), 16, 6)
        key = jax.random.PRNGKey(3)

        # reference: single-host engine, eager merge
        eng = WalkEngine(graph=g, store=store, cfg=wcfg, merge_policy="eager",
                         rewalk_capacity=128)
        eng.insert_edges(key, isrc, idst)
        ref_codes = np.asarray(eng.store.code)

        # distributed: 2x4 mesh
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        g_sh, s_sh = wharf_shardings(mesh, cfg)
        with mesh:
            step = jax.jit(
                lambda gd, sd, a, b, e, k: distributed_update_step(
                    gd, sd, a, b, e, k, cfg),
                in_shardings=(g_sh, s_sh, None, None, None, None),
                out_shardings=s_sh)
            out = step(graph_to_dict(g), store_to_dict(store), isrc, idst,
                       jnp.uint32(1), key)
        dist_codes = np.asarray(out["code"])
        assert (np.sort(dist_codes) == np.sort(ref_codes)).all(), \
            "distributed and single-host stores diverge"
        print("OK distributed == single-host")
    """)


def test_gspmd_mixed_stream_equivalence():
    """`distributed_run_stream` on a MIXED insert+delete stream must match
    the single-host pipelined driver bit-for-bit, for both merge policies."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.wharf_stream import WharfStreamConfig
        from repro.core import StreamingGraph, generate_corpus
        from repro.core.update import WalkEngine
        from repro.data.streams import mixed_edge_stream, rmat_edges
        from repro.distr.engine import (distributed_run_stream,
                                        graph_to_dict, store_to_dict,
                                        stream_shardings, wharf_shardings)

        cfg = WharfStreamConfig(n_vertices=64, edge_capacity=4096,
                                n_walks_per_vertex=2, length=8,
                                batch_edges=16, rewalk_capacity=128,
                                max_pending=4)
        wcfg = cfg.walk_config()
        src, dst = rmat_edges(jax.random.PRNGKey(0), 200, 6)
        g = StreamingGraph.from_edges(src, dst, 64, 4096)
        store = generate_corpus(jax.random.PRNGKey(1), g, wcfg)
        i_s, i_d, d_s, d_d = mixed_edge_stream(
            jax.random.PRNGKey(2), 6, 16, 4, 6)
        key = jax.random.PRNGKey(3)
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        g_sh, s_sh = wharf_shardings(mesh, cfg)
        st_sh = stream_shardings(mesh)

        for policy in ("on-demand", "eager"):
            eng = WalkEngine(graph=jax.tree.map(jnp.array, g),
                             store=jax.tree.map(jnp.array, store),
                             cfg=wcfg, merge_policy=policy,
                             rewalk_capacity=128, max_pending=4)
            ref_aff = eng.run_stream(key, i_s, i_d, d_s, d_d)
            eng.merge()
            assert not eng.mav_overflowed

            keys = jax.random.split(key, 6)
            with mesh:
                f = jax.jit(
                    lambda gd, sd, ks, a, b, c, d:
                        distributed_run_stream(
                            gd, sd, ks, a, b, cfg,
                            merge_policy=policy,
                            max_pending=cfg.max_pending,
                            del_src=c, del_dst=d),
                    in_shardings=(g_sh, s_sh, st_sh["keys"],
                                  st_sh["ins_src"], st_sh["ins_dst"],
                                  st_sh["del_src"], st_sh["del_dst"]),
                    out_shardings=(g_sh, s_sh, None))
                g_out, s_out, aff = f(
                    graph_to_dict(jax.tree.map(jnp.array, g)),
                    store_to_dict(jax.tree.map(jnp.array, store)),
                    keys, i_s, i_d, d_s, d_d)
            assert np.array_equal(np.asarray(ref_aff), np.asarray(aff))
            assert np.array_equal(np.asarray(eng.graph.codes),
                                  np.asarray(g_out["codes"])), policy
            for k in ("owner", "code", "epoch", "slot_epoch"):
                assert np.array_equal(np.asarray(getattr(eng.store, k)),
                                      np.asarray(s_out[k])), (policy, k)
            print("OK", policy)
        print("OK gspmd mixed == single-host")
    """)


def test_sharded_engine_bit_equivalence():
    """The explicitly partitioned shard_map engine (distr/sharded.py) on an
    8-shard mesh must reproduce the single-host `run_stream` BIT-FOR-BIT on
    mixed insert+delete streams: graph codes, every store array (triplets,
    slot epochs, packed chunks), and the traversed walk corpus — for both
    merge policies."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import StreamingGraph, generate_corpus
        from repro.core.corpus import WalkConfig, walk_start_vertex
        from repro.core.update import WalkEngine
        from repro.data.streams import mixed_edge_stream, rmat_edges
        from repro.distr.sharded import (ShardSpec, shard_state,
                                         sharded_run_stream, unshard_state)

        n, ecap, cap = 64, 4096, 128
        cfg = WalkConfig(n_walks_per_vertex=2, length=8, megakernel="off")
        src, dst = rmat_edges(jax.random.PRNGKey(0), 200, 6)
        graph = StreamingGraph.from_edges(src, dst, n, ecap)
        store = generate_corpus(jax.random.PRNGKey(1), graph, cfg)
        i_s, i_d, d_s, d_d = mixed_edge_stream(
            jax.random.PRNGKey(2), 6, 16, 4, 6)
        key = jax.random.PRNGKey(3)
        spec = ShardSpec(n_shards=8, n_vertices=n, edge_capacity=1024,
                         store_capacity=512, mav_capacity=512, slab=cap)

        for policy in ("on-demand", "eager"):
            eng = WalkEngine(graph=jax.tree.map(jnp.array, graph),
                             store=jax.tree.map(jnp.array, store),
                             cfg=cfg, merge_policy=policy,
                             rewalk_capacity=cap, max_pending=4)
            ref_aff = eng.run_stream(key, i_s, i_d, d_s, d_d)
            eng.merge()
            assert not eng.mav_overflowed

            stacked = shard_state(jax.tree.map(jnp.array, graph),
                                  jax.tree.map(jnp.array, store), spec,
                                  cap, max_pending=4)
            stacked, aff = sharded_run_stream(
                stacked, key, i_s, i_d, d_s, d_d, cfg=cfg, spec=spec,
                capacity=cap, max_pending=4, merge_policy=policy)
            g2, s2, ovf = unshard_state(stacked, ecap)
            assert not ovf
            assert np.array_equal(np.asarray(ref_aff), np.asarray(aff))
            assert np.array_equal(np.asarray(eng.graph.codes),
                                  np.asarray(g2.codes)), policy
            for f in ("owner", "code", "epoch", "slot_epoch", "offsets",
                      "vmin", "vmax", "packed", "widths"):
                assert np.array_equal(np.asarray(getattr(eng.store, f)),
                                      np.asarray(getattr(s2, f))), \\
                    (policy, f)
            w = jnp.arange(s2.n_walks, dtype=jnp.uint32)
            start = walk_start_vertex(w, cfg.n_walks_per_vertex)
            assert np.array_equal(
                np.asarray(eng.store.traverse(w, start, cfg.length - 1)),
                np.asarray(s2.traverse(w, start, cfg.length - 1))), policy
            print("OK", policy, np.asarray(aff))
        print("OK sharded == single-host (bit-exact)")
    """)


def test_compact_lanes_by_shard():
    """Pure lane-bucketing unit test (no mesh needed): every active lane
    lands in its destination row in ascending lane order; overflow flags."""
    import jax.numpy as jnp
    import numpy as np

    import repro.core  # noqa: F401  (x64)
    from repro.core.corpus import compact_lanes_by_shard

    dest = jnp.asarray([2, 0, 4, 0, 2, 2, 4, 0, 1, 4, 4, 4], jnp.int32)
    send, ovf = compact_lanes_by_shard(dest, n_shards=4, slab=3)
    send = np.asarray(send)
    assert send.shape == (4, 3)
    assert list(send[0]) == [1, 3, 7]          # dest 0, ascending lanes
    assert list(send[1]) == [8, 12, 12]        # one lane + sentinel pad
    assert list(send[2]) == [0, 4, 5]
    # dest 3 is empty -> all sentinel
    assert list(send[3]) == [12, 12, 12]
    # dest 4 == n_shards marks inactive lanes: dropped entirely
    assert bool(ovf) is False

    # overflow: 4 lanes to shard 0 with slab=3
    dest = jnp.asarray([0, 0, 0, 0, 1, 1], jnp.int32)
    send, ovf = compact_lanes_by_shard(dest, n_shards=2, slab=3)
    assert bool(ovf) is True
    assert list(np.asarray(send)[0]) == [0, 1, 2]  # first `slab` kept


def test_multihost_lm_train_step():
    """Sharded LM train step on a 2x4 mesh: loss finite, params update."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_arch
        from repro.models import transformer as tfm
        from repro.train.optim import AdamWConfig, adamw_init, adamw_update

        cfg = get_arch("qwen2-moe-a2.7b").make_config(smoke=True)
        params = tfm.init_params(jax.random.PRNGKey(0), cfg)
        opt = adamw_init(params)
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0,
                                  cfg.vocab_size, dtype=jnp.int32)
        ocfg = AdamWConfig()

        def step(params, opt, toks):
            loss, g = jax.value_and_grad(tfm.lm_loss)(params, toks, cfg)
            params, opt, gn = adamw_update(g, opt, params, ocfg)
            return params, opt, loss

        with mesh:
            f = jax.jit(step, in_shardings=(None, None,
                        NamedSharding(mesh, P("data", None))))
            p2, o2, loss = f(params, opt, toks)
        assert np.isfinite(float(loss))
        changed = any((np.asarray(a) != np.asarray(b)).any()
                      for a, b in zip(jax.tree.leaves(params),
                                      jax.tree.leaves(p2)))
        assert changed
        print("OK sharded train step, loss", float(loss))
    """)


def test_cross_pod_int8_allreduce():
    """shard_map int8-compressed cross-pod gradient reduction."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import AxisType, PartitionSpec as P
        from repro.train.compression import (cross_pod_mean_int8,
                                             zeros_error_feedback)

        # Auto axes: inputs placed by the shard_map specs, as in the
        # collectives-only use compression.py is written for
        mesh = jax.make_mesh((8,), ("pod",), axis_types=(AxisType.Auto,))
        grads = {"w": jnp.arange(8 * 256, dtype=jnp.float32).reshape(8, 256)
                 / 100.0}
        err = zeros_error_feedback({"w": grads["w"][0]})

        @partial(jax.shard_map, mesh=mesh,
                 in_specs=({"w": P("pod", None)}, {"w": P()}),
                 out_specs=({"w": P("pod", None)}, {"w": P("pod", None)}))
        def reduce_fn(g, e):
            out, err = cross_pod_mean_int8(
                {"w": g["w"][0]}, {"w": e["w"]}, "pod")
            return {"w": out["w"][None]}, {"w": err["w"][None]}

        out, _ = reduce_fn(grads, err)
        expected = np.asarray(grads["w"]).mean(axis=0)
        got = np.asarray(out["w"][0])
        rel = np.abs(got - expected).max() / (np.abs(expected).max() + 1e-9)
        assert rel < 0.02, rel
        print("OK int8 cross-pod reduce, rel err", rel)
    """)
