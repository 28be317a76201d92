"""Training/streaming launcher.

  PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b --smoke \
      --steps 20 --ckpt-dir /tmp/ckpt
  PYTHONPATH=src python -m repro.launch.train --arch wharf-stream --smoke \
      --steps 10
  PYTHONPATH=src python -m repro.launch.train --arch wharf-stream --smoke \
      --mode downstream --steps 10

LM archs run next-token training on synthetic token streams; wharf-stream
runs the paper's streaming walk-update loop (RMAT edge batches), and
`--mode downstream` co-schedules incremental SGNS embedding maintenance
with the same stream (downstream/maintainer.py): each TrainLoop step is one
edge batch -> walk update -> affected-only embedding retrain, and the
checkpoint carries (EngineState, SGNS params, opt) as one pytree so
streaming and training resume together. All modes go through the
fault-tolerant TrainLoop (checkpoint/restart, straggler monitor).
Real-cluster deployment points `--mesh` at the production mesh; on CPU it
runs single-device with the same code path.
"""
from __future__ import annotations

import argparse
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.train.checkpoint import CheckpointManager
from repro.train.optim import AdamWConfig, adamw_init, adamw_update
from repro.train.runtime import TrainLoop


def lm_trainer(arch: str, smoke: bool, batch: int, seq: int):
    from repro.models import transformer as tfm

    cfg = get_arch(arch).make_config(smoke)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    opt_cfg = AdamWConfig(lr=1e-3)
    state = {"params": params, "opt": adamw_init(params)}

    @jax.jit
    def _step(state, tokens):
        loss, grads = jax.value_and_grad(tfm.lm_loss)(state["params"],
                                                      tokens, cfg)
        params, opt, gnorm = adamw_update(grads, state["opt"],
                                          state["params"], opt_cfg)
        return {"params": params, "opt": opt}, loss, gnorm

    def step_fn(state, tokens, key):
        state, loss, gnorm = _step(state, tokens)
        return state, {"loss": float(loss), "gnorm": float(gnorm)}

    def batch_fn(step, key):
        return jax.random.randint(key, (batch, seq + 1), 0, cfg.vocab_size,
                                  dtype=jnp.int32)

    return state, step_fn, batch_fn


def wharf_trainer(arch: str, smoke: bool, batch_edges: int):
    from repro.core import StreamingGraph, generate_corpus
    from repro.core.update import WalkEngine
    from repro.data.streams import rmat_edges
    import math

    cfg = get_arch(arch).make_config(smoke)
    wcfg = cfg.walk_config()
    log2n = int(math.log2(cfg.n_vertices))
    src, dst = rmat_edges(jax.random.PRNGKey(1), batch_edges * 4, log2n)
    graph = StreamingGraph.from_edges(src, dst, cfg.n_vertices,
                                      cfg.edge_capacity)
    store = generate_corpus(jax.random.PRNGKey(2), graph, wcfg)
    engine = WalkEngine(graph=graph, store=store, cfg=wcfg,
                        rewalk_capacity=cfg.rewalk_capacity)
    state = {"store_code": store.code}  # checkpointable view

    def step_fn(state, batch, key):
        isrc, idst = batch
        n_aff = engine.update_batch(key, isrc, idst, None, None)
        # metrics are host-printed anyway; sync the lazy count here
        return {"store_code": engine.store.code}, {"affected_walks": int(n_aff)}

    def batch_fn(step, key):
        return rmat_edges(key, batch_edges, log2n)

    return state, step_fn, batch_fn


def downstream_trainer(arch: str, smoke: bool, batch_edges: int, dim: int,
                       max_pairs: int = 1 << 16):
    """The co-scheduled streaming trainer: walk updates + SGNS maintenance.

    Returns (state, step_fn, batch_fn, on_restore): the TrainLoop carry IS
    the maintainer's (EngineState, params, opt) pytree, so the standard
    checkpoint path snapshots streaming and training state atomically;
    `on_restore` hands a restored carry back to the maintainer (host-mirror
    re-sync) before the loop continues."""
    from repro.core import StreamingGraph, generate_corpus
    from repro.data.streams import rmat_edges
    from repro.downstream import EmbeddingMaintainer, MaintainerConfig
    import math

    cfg = get_arch(arch).make_config(smoke)
    wcfg = cfg.walk_config()
    log2n = int(math.log2(cfg.n_vertices))
    src, dst = rmat_edges(jax.random.PRNGKey(1), batch_edges * 4, log2n)
    graph = StreamingGraph.from_edges(src, dst, cfg.n_vertices,
                                      cfg.edge_capacity)
    store = generate_corpus(jax.random.PRNGKey(2), graph, wcfg)
    # max_pairs bounds the static pair batch: at production scale
    # (rewalk_capacity 2^20, length 80) the unbounded affected-pair set is
    # ~5e8 pairs per step — the budget subsamples deterministically
    mcfg = MaintainerConfig(walk=wcfg, n_vertices=cfg.n_vertices, dim=dim,
                            rewalk_capacity=cfg.rewalk_capacity,
                            max_pending=cfg.max_pending,
                            max_pairs=max_pairs)
    mt = EmbeddingMaintainer(graph=graph, store=store, cfg=mcfg,
                             key=jax.random.PRNGKey(3))

    def step_fn(state, batch, key):
        mt.state = state  # the loop's carry is authoritative
        isrc, idst = batch
        k_u, k_t = jax.random.split(key)
        m = mt.step(k_u, k_t, isrc, idst)
        return mt.state, {"loss": float(m.loss_sum),
                          "pairs": int(m.n_pairs),
                          "affected_walks": int(m.n_affected)}

    def batch_fn(step, key):
        return rmat_edges(jax.random.fold_in(key, 1), batch_edges, log2n)

    def on_restore(state, step):
        mt.load_state(state)
        return mt.state

    return mt.state, step_fn, batch_fn, on_restore


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch-edges", type=int, default=64)
    ap.add_argument("--mode", default="stream",
                    choices=("stream", "downstream"),
                    help="wharf family: plain walk maintenance, or "
                         "co-scheduled embedding maintenance")
    ap.add_argument("--dim", type=int, default=64,
                    help="embedding dim (--mode downstream)")
    ap.add_argument("--max-pairs", type=int, default=1 << 16,
                    help="per-step trained-pair budget (--mode downstream)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    args = ap.parse_args()
    from repro.launch.compile_cache import (enable_compile_cache,
                                            raise_compiler_stack)
    raise_compiler_stack()
    enable_compile_cache()

    spec = get_arch(args.arch)
    on_restore = None
    if spec.family == "lm":
        state, step_fn, batch_fn = lm_trainer(args.arch, args.smoke,
                                              args.batch, args.seq)
    elif spec.family == "wharf" and args.mode == "downstream":
        state, step_fn, batch_fn, on_restore = downstream_trainer(
            args.arch, args.smoke, args.batch_edges, args.dim,
            args.max_pairs)
    elif spec.family == "wharf":
        state, step_fn, batch_fn = wharf_trainer(args.arch, args.smoke,
                                                 args.batch_edges)
    else:
        raise SystemExit(f"use examples/ drivers for family {spec.family}")

    loop = TrainLoop(step_fn=step_fn, batch_fn=batch_fn,
                     ckpt=CheckpointManager(args.ckpt_dir),
                     ckpt_every=args.ckpt_every, on_restore=on_restore)
    state, start = loop.resume(state)
    print(f"starting at step {start}")

    def on_metrics(step, dt, metrics):
        print(f"step {step}: {dt * 1e3:.1f}ms {metrics}")

    loop.run(state, start, args.steps, on_metrics)
    if loop.straggler.events:
        print("straggler events:", loop.straggler.events)


if __name__ == "__main__":
    main()
