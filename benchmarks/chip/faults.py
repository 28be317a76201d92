"""Faults planted under a run, to show that `correct` comes out false.

Each is a context manager that patches the program's entry point the
window drives (WalkEngine.run_stream, WalkQueryService) for as long as it
is open; the harness runs unchanged on top. The tests plant each at a tiny
size on the CPU, and control.py at a cell's own size on the chip.

  state_unchanged  an update step that returns its state unchanged
  half_batch       half of each batch's inserts and deletes left out
  token_altered    one stored triplet altered where the rewalk writes it
  skip_rewalk      the graph is updated but no walk is rewalked, so walks
                   keep steps over deleted edges
  mav_deletes_only the MAV built from the deletes alone: only the walks
                   through a deleted edge's endpoints are rewalked (and
                   through one insert's, which keeps the step's shapes);
                   the graph still takes every insert
  answer_altered   the first answer of every next_vertices request altered
                   where it is produced
  base_only_reads  the control: reads skip the pending version block and
                   see the base store alone
  walks_of_fails   every walks_of request after the warm-up raises, so its
                   answer never comes

`reference_controls` is no fault of the program: it has the reference read
its controls (walk_reference.CONTROLS) on each run's final graph beside the
run's own checks, which it leaves as they are.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

import bench

from repro.core.overlay import Overlay
from repro.core.store import PAD_EPOCH
from repro.core.update import PendingBlocks, WalkEngine
from repro.serve.walk_queries import WalkQueryService


@contextlib.contextmanager
def _patched(cls, name, fn):
    orig = getattr(cls, name)
    setattr(cls, name, fn(orig))
    try:
        yield
    finally:
        setattr(cls, name, orig)


def _state_unchanged(orig):
    def run_stream(self, key, ins_src, *a, **k):
        return jnp.zeros((ins_src.shape[0],), jnp.int32)
    return run_stream


def _half_batch(orig):
    def run_stream(self, key, ins_src, ins_dst, del_src=None, del_dst=None,
                   **k):
        h, d = ins_src.shape[1] // 2, del_src.shape[1] // 2
        return orig(self, key, ins_src[:, :h], ins_dst[:, :h],
                    del_src[:, :d], del_dst[:, :d], **k)
    return run_stream


def _token_altered(orig):
    def run_stream(self, *a, **k):
        out = orig(self, *a, **k)
        p = self.state.pending
        i = jnp.argmax(p.epoch.reshape(-1) != PAD_EPOCH)
        code = p.code.reshape(-1).at[i].add(1).reshape(p.code.shape)
        self.state = self.state.replace(pending=p._replace(code=code))
        return out
    return run_stream


_apply_batch = jax.jit(lambda g, a, b, c, d: g.apply_batch(a, b, c, d))


def _skip_rewalk(orig):
    def run_stream(self, key, ins_src, ins_dst, del_src=None, del_dst=None,
                   **k):
        g = self.state.graph
        for i in range(ins_src.shape[0]):
            g = _apply_batch(g, jnp.asarray(ins_src[i]),
                             jnp.asarray(ins_dst[i]),
                             jnp.asarray(del_src[i]), jnp.asarray(del_dst[i]))
        self.state = self.state.replace(graph=g)
        return jnp.zeros((ins_src.shape[0],), jnp.int32)
    return run_stream


_insert_edges = jax.jit(lambda g, s, d: g.insert_edges(s, d))


def _mav_deletes_only(orig):
    def run_stream(self, key, ins_src, ins_dst, del_src=None, del_dst=None,
                   **k):
        ins_src = jnp.asarray(ins_src, jnp.uint32)
        ins_dst = jnp.asarray(ins_dst, jnp.uint32)
        # the step sees the deletes and one insert repeated (an insert of an
        # edge that is there changes nothing); the other inserts reach the
        # graph after it, as the reference has them
        out = orig(self, key, jnp.broadcast_to(ins_src[:, :1], ins_src.shape),
                   jnp.broadcast_to(ins_dst[:, :1], ins_dst.shape), del_src,
                   del_dst, **k)
        g = self.state.graph
        for i in range(ins_src.shape[0]):
            g = _insert_edges(g, ins_src[i], ins_dst[i])
        self.state = self.state.replace(graph=g)
        return out
    return run_stream


def _answer_altered(orig):
    def next_vertices(self, v, w, p, snapshot=None):
        nxt, found = orig(self, v, w, p, snapshot=snapshot)
        return nxt.at[0].set(nxt[0] ^ jnp.uint32(1)), found
    return next_vertices


def _walks_of_fails(orig):
    calls = [0]

    def walks_of(self, *a, **k):
        calls[0] += 1
        if calls[0] > 1:    # every request after the warm-up
            raise RuntimeError("planted fault: walks_of failed")
        return orig(self, *a, **k)
    return walks_of


def _base_only(orig):
    built = {}

    def snapshot(self):
        # built once per engine state, as the service caches its own
        key = (id(self.engine), self.engine.epoch_counter)
        if key not in built:
            s = self.engine.state
            built.clear()
            built[key] = Overlay.build(s.store,
                                       PendingBlocks.empty_like(s.pending))
        return built[key]
    return snapshot


FAULTS = {
    "state_unchanged": (WalkEngine, "run_stream", _state_unchanged),
    "half_batch": (WalkEngine, "run_stream", _half_batch),
    "token_altered": (WalkEngine, "run_stream", _token_altered),
    "skip_rewalk": (WalkEngine, "run_stream", _skip_rewalk),
    "mav_deletes_only": (WalkEngine, "run_stream", _mav_deletes_only),
    "answer_altered": (WalkQueryService, "next_vertices", _answer_altered),
    "base_only_reads": (WalkQueryService, "snapshot", _base_only),
    "walks_of_fails": (WalkQueryService, "walks_of", _walks_of_fails),
}


def plant(name: str):
    """Context manager: the run inside it has fault `name` ("none": no
    fault)."""
    if name == "none":
        return contextlib.nullcontext()
    cls, attr, fn = FAULTS[name]
    return _patched(cls, attr, fn)


@contextlib.contextmanager
def reference_controls(names, seed: int, sink: dict):
    """While open, each reference the harness loads also reads the named
    controls on the run's final graph and puts their numbers in `sink`, as
    `<control>.<number>`."""
    def load(orig):
        def load_reference(cfg, base=bench.HERE):
            ref = orig(cfg, base)
            check_state = ref.check_state

            def checked(state, edges, last_changed, cfg):
                out = check_state(state, edges, last_changed, cfg)
                for name in names:
                    sink.update({f"{name}.{k}": v for k, v in
                                 ref.control_numbers(name, edges, cfg,
                                                     seed).items()})
                return out
            ref.check_state = checked
            return ref
        return load_reference
    with _patched(bench, "load_reference", load):
        yield
