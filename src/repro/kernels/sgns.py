"""Fused skip-gram negative-sampling step behind a backend registry.

The downstream hot loop of the paper's embedding application (§7.6): per
batch row, u·v+ and u·V- logits, logsigmoid losses, and ALL input gradients
in one pass — logits/probs never round-trip to HBM on the kernel path
(flash-attention-style fusion; XLA handles the surrounding gather/scatter of
embedding rows, which it already fuses well).

  u      [B, D]     center rows     (gathered)
  v_pos  [B, D]     context rows
  v_neg  [B, K, D]  negative rows
  ->
  loss   [B]        per-row loss
  du     [B, D]     dL/du
  dvp    [B, D]     dL/dv_pos
  dvn    [B, K, D]  dL/dv_neg

Backends (the same registry pattern as FINDNEXT, core/packed_store.py):

  "pallas"           — the Pallas TPU kernel: rows tiled by 8 (f32 sublane),
                       D padded to 128 lanes; the [B, K] negative logits are
                       VPU multiply + lane reductions per tile (Mosaic has
                       no batched matmul for this contraction). The auto
                       path pads off-tile shapes (`sgns_padded`).
  "interpret"        — the SAME closed-form kernel math (`_sgns_math`, shared
                       with the kernel body) vectorized over the whole batch
                       in XLA; the automatic CPU fallback, shape-flexible.
  "pallas-interpret" — pl.pallas_call(interpret=True); exact kernel-body
                       validation off-TPU (slow: grid is trace-unrolled).
  "xla-ref"          — jax.vjp of the reference per-row loss (pure jnp, AD
                       gradients): the semantics oracle the closed-form
                       backward is checked against (tests/test_sgns.py).

"auto" resolves to "pallas" on TPU and "interpret" elsewhere; an explicit
"pallas" request off-TPU runs "interpret" (the same math in XLA). On TPU
nothing falls back: every shape runs the kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

F32 = jnp.float32
ROWS = 8

# ------------------------------------------------------------------ registry

BACKENDS = ("pallas", "interpret", "pallas-interpret", "xla-ref")

_default_backend: Optional[str] = None   # None -> hardware auto-selection


def set_default_backend(name: Optional[str]) -> None:
    """Set the process-wide SGNS backend ("auto"/None = hardware pick).

    Resolution happens at trace time: already-compiled jitted callers keep
    the backend they were traced with until their cache is invalidated."""
    global _default_backend
    if name in (None, "auto"):
        _default_backend = None
        return
    if name not in BACKENDS:
        raise ValueError(f"unknown sgns backend {name!r}; "
                         f"expected one of {BACKENDS + ('auto',)}")
    _default_backend = name


def get_default_backend() -> str:
    return resolve_backend(None)


def resolve_backend(name: Optional[str]) -> str:
    """None/"auto" -> "pallas" on TPU, "interpret" otherwise; "pallas"
    off-TPU falls back to "interpret" (the kernel math run in XLA)."""
    name = _default_backend if name in (None, "auto") else name
    on_tpu = jax.default_backend() == "tpu"
    if name is None:
        return "pallas" if on_tpu else "interpret"
    if name not in BACKENDS:
        raise ValueError(f"unknown sgns backend {name!r}; "
                         f"expected one of {BACKENDS + ('auto',)}")
    if name == "pallas" and not on_tpu:
        return "interpret"
    return name


# ------------------------------------------------------- shared kernel math


def _sgns_math(u, vp, vn):
    """The fused forward + closed-form backward, shared verbatim by the
    Pallas kernel body (per 8-row tile) and the "interpret" backend (whole
    batch): loss = -log σ(u·v+) - Σ log σ(-u·v-) and all three input grads.

    Row-independent math, so tile-by-8 and whole-batch execution produce
    bit-identical results."""
    pos = jnp.sum(u * vp, axis=-1)                        # [R]
    # the per-row dots as VPU multiply + lane reduction: Mosaic cannot
    # lower the batched [R, D] x [R, K, D] contraction as a matmul
    neg = jnp.sum(u[:, None, :] * vn, axis=-1)            # [R, K]
    loss = jnp.logaddexp(0.0, -pos) + jnp.logaddexp(0.0, neg).sum(-1)
    gpos = -jax.nn.sigmoid(-pos)                          # dL/dpos
    gneg = jax.nn.sigmoid(neg)                            # dL/dneg  [R, K]
    du = gpos[:, None] * vp + jnp.sum(gneg[..., None] * vn, axis=1)
    dvp = gpos[:, None] * u
    dvn = gneg[..., None] * u[:, None, :]
    return loss, du, dvp, dvn


def _sgns_kernel(u_ref, vp_ref, vn_ref, loss_ref, du_ref, dvp_ref, dvn_ref):
    loss, du, dvp, dvn = _sgns_math(u_ref[...], vp_ref[...], vn_ref[...])
    loss_ref[...] = loss[:, None]
    du_ref[...] = du
    dvp_ref[...] = dvp
    dvn_ref[...] = dvn


def sgns_reference_loss(u, vp, vn):
    """Per-row reference loss [B] (pure jnp; the "xla-ref" forward)."""
    pos = jnp.sum(u * vp, axis=-1)
    neg = jnp.sum(u[:, None, :] * vn, axis=-1)
    return -(jax.nn.log_sigmoid(pos) + jax.nn.log_sigmoid(-neg).sum(-1))


# ----------------------------------------------------------------- backends


@functools.partial(jax.jit, static_argnames=("interpret",))
def sgns_fused(u, v_pos, v_neg, interpret: bool = False):
    """The Pallas path: u, v_pos f32 [B, D]; v_neg f32 [B, K, D]
    (B % 8 == 0, D % 128 == 0). Returns (loss [B], du, dvp, dvn)."""
    b, d = u.shape
    k = v_neg.shape[1]
    grid = (b // ROWS,)
    z = np.int32(0)   # int32 block indices even under x64
    row2 = pl.BlockSpec((ROWS, d), lambda i: (i, z))
    row3 = pl.BlockSpec((ROWS, k, d), lambda i: (i, z, z))
    scal = pl.BlockSpec((ROWS, 1), lambda i: (i, z))
    loss, du, dvp, dvn = pl.pallas_call(
        _sgns_kernel,
        grid=grid,
        in_specs=[row2, row2, row3],
        out_specs=[scal, row2, row2, row3],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1), F32),
            jax.ShapeDtypeStruct((b, d), F32),
            jax.ShapeDtypeStruct((b, d), F32),
            jax.ShapeDtypeStruct((b, k, d), F32),
        ],
        interpret=interpret,
    )(u, v_pos, v_neg)
    return loss[:, 0], du, dvp, dvn


def _sgns_xla_ref(u, vp, vn):
    loss, pullback = jax.vjp(sgns_reference_loss, u, vp, vn)
    du, dvp, dvn = pullback(jnp.ones_like(loss))
    return loss, du, dvp, dvn


def sgns_padded(u, v_pos, v_neg, interpret: bool = False):
    """The Pallas path on any shape: rows padded to the 8-row tile and the
    embedding dim to 128 lanes with zeros (padded lanes add nothing to the
    dots; padded rows are sliced away). Returns (loss [B], du, dvp, dvn)."""
    b, d = u.shape
    padb = (-b) % ROWS
    padd = (-d) % 128
    if padb or padd:
        u = jnp.pad(u, ((0, padb), (0, padd)))
        v_pos = jnp.pad(v_pos, ((0, padb), (0, padd)))
        v_neg = jnp.pad(v_neg, ((0, padb), (0, 0), (0, padd)))
    loss, du, dvp, dvn = sgns_fused(u, v_pos, v_neg, interpret=interpret)
    return loss[:b], du[:b, :d], dvp[:b, :d], dvn[:b, :, :d]


def sgns_apply(u, v_pos, v_neg, backend: Optional[str] = None):
    """Dispatch one fused SGNS forward+backward to the resolved backend.

    Traceable (usable inside jit/scan) as long as `backend` is concrete at
    trace time. Returns (loss [B], du, dvp, dvn). Tiling contract
    (B % 8 == 0, D % 128 == 0): the auto-resolved kernel path pads
    off-tile shapes to the tile (`sgns_padded`), so on TPU the kernel runs
    on every shape; an EXPLICIT "pallas"/"pallas-interpret" request raises
    on them, so a kernel-validation run sees exactly the shapes it asked
    for."""
    explicit = backend not in (None, "auto")
    backend = resolve_backend(backend)
    if backend in ("pallas", "pallas-interpret"):
        b, d = u.shape
        interpret = backend == "pallas-interpret"
        if b % ROWS or d % 128:
            if explicit:
                raise ValueError(
                    f"sgns backend {backend!r} requires B % {ROWS} == 0 and "
                    f"D % 128 == 0, got B={b}, D={d}; use backend='auto' "
                    f"to pad to the tile")
            return sgns_padded(u, v_pos, v_neg, interpret=interpret)
        return sgns_fused(u, v_pos, v_neg, interpret=interpret)
    if backend == "interpret":
        return _sgns_math(u, v_pos, v_neg)
    if backend == "xla-ref":
        return _sgns_xla_ref(u, v_pos, v_neg)
    raise ValueError(f"sgns_apply cannot serve backend {backend!r}")
