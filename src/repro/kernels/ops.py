"""Jit'd public wrappers around the Pallas kernels.

Handle padding/reshaping to lane tiles and auto-select interpret mode off-TPU
(kernels are TPU-target; interpret=True executes the kernel body in Python
for CPU validation, per the project brief).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import delta as _delta
from repro.kernels import intersect as _intersect
from repro.kernels import range_search as _rs
from repro.kernels import sgns as _sgns
from repro.kernels import szudzik as _szudzik

U32 = jnp.uint32
LANES = _szudzik.LANES


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _to_tiles(x):
    n = x.shape[0]
    pad = (-n) % (LANES * _szudzik.BLOCK_ROWS)
    xp = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)]) if pad else x
    return xp.reshape(-1, LANES), n


def szudzik_pair(x, y, interpret: bool | None = None):
    """u32 [N] operands -> (hi, lo) u32 [N] codes (Pallas)."""
    interpret = _interpret_default() if interpret is None else interpret
    xt, n = _to_tiles(jnp.asarray(x, U32))
    yt, _ = _to_tiles(jnp.asarray(y, U32))
    hi, lo = _szudzik.pair_tiles(xt, yt, interpret=interpret)
    return hi.reshape(-1)[:n], lo.reshape(-1)[:n]


def szudzik_unpair(z_hi, z_lo, interpret: bool | None = None):
    """(hi, lo) u32 [N] codes -> (x, y) u32 [N] operands (Pallas)."""
    interpret = _interpret_default() if interpret is None else interpret
    ht, n = _to_tiles(jnp.asarray(z_hi, U32))
    lt, _ = _to_tiles(jnp.asarray(z_lo, U32))
    x, y = _szudzik.unpair_tiles(ht, lt, interpret=interpret)
    return x.reshape(-1)[:n], y.reshape(-1)[:n]


def delta_pack(code_hi, code_lo):
    """Sorted (hi, lo) u32 [C, 128] -> (packed, widths, anchor_hi, anchor_lo)."""
    return _delta.encode_chunks(code_hi, code_lo)


def delta_unpack(packed, widths, anchors_hi, anchors_lo,
                 interpret: bool | None = None):
    interpret = _interpret_default() if interpret is None else interpret
    c = packed.shape[0]
    pad = (-c) % _delta.ROWS
    if pad:
        packed = jnp.concatenate(
            [packed, jnp.zeros((pad, packed.shape[1]), U32)])
        widths = jnp.concatenate([widths, jnp.full((pad,), 32, U32)])
        anchors_hi = jnp.concatenate([anchors_hi, jnp.zeros((pad,), U32)])
        anchors_lo = jnp.concatenate([anchors_lo, jnp.zeros((pad,), U32)])
    hi, lo = _delta.decode_chunks(packed, widths, anchors_hi, anchors_lo,
                                  interpret=interpret)
    return hi[:c], lo[:c]


def find_next_packed(packed, widths, anchors_hi, anchors_lo, chunk_idx,
                     f_targets, interpret: bool | None = None):
    interpret = _interpret_default() if interpret is None else interpret
    return _rs.find_next_packed(packed, widths, anchors_hi, anchors_lo,
                                chunk_idx, f_targets, interpret=interpret)


candidate_chunks = _rs.candidate_chunks


def intersect_next(nbrs_v, nbrs_p, prev, u_group, u_rank, p: float,
                   q: float, interpret: bool | None = None):
    """Exact factorized node2vec selection via the intersect kernel on any
    shape (intersect.factorized_padded). Returns (nxt u32 [B], found [B])."""
    interpret = _interpret_default() if interpret is None else interpret
    return _intersect.factorized_padded(nbrs_v, nbrs_p, prev, u_group,
                                        u_rank, float(1.0 / p),
                                        float(1.0 / q), interpret=interpret)


def sgns_step(u, v_pos, v_neg, interpret: bool | None = None):
    interpret = _interpret_default() if interpret is None else interpret
    return _sgns.sgns_padded(u, v_pos, v_neg, interpret=interpret)
