"""Pallas TPU kernel: frame-of-reference delta decode of compressed chunks.

The paper's §4.4 difference encoding uses variable-byte codes — byte-serial
decode is VPU-hostile, so the TPU adaptation packs per-chunk deltas at a
quantized bit width w ∈ {8, 16, 32, 64}:

  chunk (128 sorted codes as (hi, lo) u32)
    -> anchor (code[0]) + 127 deltas packed into 128*w/32 u32 words
  w = 64 is the raw fallback for chunks that cross an owner boundary
  (non-monotone) or have >32-bit deltas.

Decode kernel (the search hot path): branch-free unpack of all width classes
+ select, then a carry-correct 64-bit prefix sum built from two 16-bit-limb
u32 prefix sums. Both steps move data across lanes with rotations only
(`pltpu.roll`), which Mosaic lowers and which XLA runs as `jnp.roll`, so the
kernels and the XLA backends share one decode. Encode is pure jnp (ops.py)
— also 32-bit-native, so both directions run on TPU. Compression ratio
matches the paper's DE study (benchmarks/bench_memory.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.szudzik import _add64, _sub64

U32 = jnp.uint32
I32 = jnp.int32

CHUNK = 128           # paper's b, aligned to the VPU lane count
WORDS = 2 * CHUNK     # packed buffer words per chunk (w=64 raw worst case)
ROWS = 8              # chunks per block


@functools.partial(jax.jit, static_argnums=1)
def _roll(x, shift: int):
    """Lane rotation that lowers both in Mosaic and in XLA (pltpu.roll has
    jnp.roll semantics; the shift is an int32 operand so no 64-bit value
    reaches the kernel under x64). Jitted because the primitive has no
    eager rule."""
    return pltpu.roll(x, np.int32(shift), 1)


def _shift_up(x, s: int, lane):
    """y[:, i] = x[:, i - s] for i >= s, else 0, along the lane axis.

    Built from two rotations and the rotated lane iota, so the result does
    not depend on the rotation direction the backend implements."""
    n = x.shape[1]
    src = _roll(lane, s)
    y = jnp.where(src == lane - s, _roll(x, s), _roll(x, n - s))
    return jnp.where(lane >= s, y, jnp.zeros_like(y))


def lane_cumsum(x, lane):
    """Inclusive prefix sum along the lane axis: a log-step scan of lane
    shifts. `lane` is the int32 lane iota of x's shape.
    Exact as long as the running sums do not wrap x's dtype."""
    s = 1
    while s < x.shape[1]:
        x = x + _shift_up(x, s, lane)
        s *= 2
    return x


def _tile_lanes(x, lane, period: int):
    """y[:, i] = x[:, i % period]: repeat the first `period` lanes across
    the row. The rotations cover every multiple of `period`, so the union
    is the same for either rotation direction."""
    head = jnp.where(lane < period, x, jnp.zeros_like(x))
    out = head
    for s in range(period, CHUNK, period):
        out = out | _roll(head, s)
    return out


def _unpack_all_widths(packed, lane):
    """packed: [R, WORDS]; lane: [R, CHUNK] int32 iota. Returns w8/w16/w32
    unpacks ([R, CHUNK] u32 deltas) and the raw (hi, lo) interpretation.

    The narrow widths are packed lane-strided (encode_chunks): delta i sits
    in word i % (CHUNK * w / 32) at bit offset w * (i // (CHUNK * w / 32)),
    so unpacking is a lane tile plus a per-lane shift."""
    words = packed[:, :CHUNK]
    ulane = lane.astype(U32)
    v8 = (_tile_lanes(words, lane, CHUNK // 4) >> ((ulane >> 5) << 3)) \
        & np.uint32(0xFF)
    v16 = (_tile_lanes(words, lane, CHUNK // 2) >> ((ulane >> 6) << 4)) \
        & np.uint32(0xFFFF)
    raw_hi = words
    raw_lo = packed[:, CHUNK:]
    return v8, v16, words, raw_hi, raw_lo


def _cumsum64_u32(d, lane):
    """Exact 64-bit prefix sum of u32 deltas via 16-bit limb prefix sums.

    A prefix sum of 128 values each < 2^16 stays < 2^23 — no u32 overflow —
    so the two limb sums are exact; recomposition handles the carry."""
    lo16 = lane_cumsum(d & np.uint32(0xFFFF), lane)
    hi16 = lane_cumsum(d >> 16, lane)
    lo = lo16 + (hi16 << 16)
    carry = (lo < lo16).astype(U32)
    hi = (hi16 >> 16) + carry
    return hi, lo


def decode_block(packed, width, a_hi, a_lo):
    """The chunk-decode math, shared by every consumer (this module's Pallas
    kernel, range_search's per-chunk decode, and the XLA "interpret"
    backend in core/packed_store): packed u32 [R, WORDS], width/a_hi/a_lo
    u32 [R, 1] -> (hi, lo) u32 [R, CHUNK]. Pure jnp plus lane rotations —
    valid both inside and outside kernel bodies."""
    lane = jax.lax.broadcasted_iota(I32, (packed.shape[0], CHUNK), 1)
    v8, v16, v32, raw_hi, raw_lo = _unpack_all_widths(packed, lane)
    d = jnp.where(width == 8, v8, jnp.where(width == 16, v16, v32))
    c_hi, c_lo = _cumsum64_u32(d, lane)
    hi, lo = _add64(jnp.broadcast_to(a_hi, c_hi.shape),
                    jnp.broadcast_to(a_lo, c_lo.shape), c_hi, c_lo)
    is_raw = width == 64
    return jnp.where(is_raw, raw_hi, hi), jnp.where(is_raw, raw_lo, lo)


def _decode_kernel(packed_ref, width_ref, a_hi_ref, a_lo_ref,
                   out_hi_ref, out_lo_ref):
    hi, lo = decode_block(packed_ref[...], width_ref[...], a_hi_ref[...],
                          a_lo_ref[...])
    out_hi_ref[...] = hi
    out_lo_ref[...] = lo


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_chunks(packed, widths, anchors_hi, anchors_lo,
                  interpret: bool = False):
    """packed u32 [C, WORDS]; widths u32 [C]; anchors (hi, lo) u32 [C]
    -> (code_hi, code_lo) u32 [C, CHUNK]."""
    c = packed.shape[0]
    grid = (c // ROWS,)

    def rows(i):
        return i, np.int32(0)   # int32 block index even under x64

    return pl.pallas_call(
        _decode_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROWS, WORDS), rows),
            pl.BlockSpec((ROWS, 1), rows),
            pl.BlockSpec((ROWS, 1), rows),
            pl.BlockSpec((ROWS, 1), rows),
        ],
        out_specs=[pl.BlockSpec((ROWS, CHUNK), rows)] * 2,
        out_shape=[jax.ShapeDtypeStruct((c, CHUNK), U32)] * 2,
        interpret=interpret,
    )(packed, widths.reshape(-1, 1), anchors_hi.reshape(-1, 1),
      anchors_lo.reshape(-1, 1))


# --------------------------------------------------------------- encode (jnp)


def encode_chunks(code_hi, code_lo):
    """FOR-pack sorted (hi, lo) u32 [C, CHUNK] chunks.

    Returns (packed u32 [C, WORDS], widths u32 [C], anchors (hi, lo) u32 [C]).
    Pure jnp on u32 — runs on TPU via XLA (no 64-bit types needed)."""
    c = code_hi.shape[0]
    d_hi, d_lo = _sub64(code_hi[:, 1:], code_lo[:, 1:],
                        code_hi[:, :-1], code_lo[:, :-1])
    zero = jnp.zeros((c, 1), U32)
    d_hi = jnp.concatenate([zero, d_hi], axis=1)
    d_lo = jnp.concatenate([zero, d_lo], axis=1)
    # monotone chunk <=> every 64-bit delta non-negative <=> no borrow wrapped:
    # detect via (delta <= original) is unreliable; use direct compare instead
    ge = (code_hi[:, 1:] > code_hi[:, :-1]) | (
        (code_hi[:, 1:] == code_hi[:, :-1]) &
        (code_lo[:, 1:] >= code_lo[:, :-1]))
    mono = jnp.all(ge, axis=1)
    small = mono & jnp.all(d_hi == 0, axis=1)
    dmax = jnp.max(d_lo, axis=1)
    width = jnp.where(~small, 64,
                      jnp.where(dmax < 256, 8,
                                jnp.where(dmax < 65536, 16, 32))).astype(U32)

    # pack each width class lane-strided (vectorized over all chunks; select
    # at the end): word j holds deltas j, j + 32, j + 64, j + 96 (w = 8) or
    # j, j + 64 (w = 16), low bits first — the layout _unpack_all_widths
    # reads back with lane rotations only
    shifts4 = (np.arange(4, dtype=np.uint32) * 8)[None, :, None]
    p8 = (d_lo.reshape(c, 4, CHUNK // 4) << shifts4).sum(1, dtype=U32)
    shifts2 = (np.arange(2, dtype=np.uint32) * 16)[None, :, None]
    p16 = (d_lo.reshape(c, 2, CHUNK // 2) << shifts2).sum(1, dtype=U32)

    def pad(x):
        return jnp.concatenate(
            [x, jnp.zeros((c, WORDS - x.shape[1]), U32)], axis=1)

    packed8 = pad(p8)
    packed16 = pad(p16)
    packed32 = pad(d_lo)
    packed64 = jnp.concatenate([code_hi, code_lo], axis=1)
    w = width[:, None]
    packed = jnp.where(w == 8, packed8,
                       jnp.where(w == 16, packed16,
                                 jnp.where(w == 32, packed32, packed64)))
    return packed, width, code_hi[:, 0], code_lo[:, 0]


def packed_nbytes(widths) -> int:
    """Actual compressed footprint (words used, not buffer capacity)."""
    widths = np.asarray(widths)
    words = np.where(widths == 64, 2 * CHUNK, CHUNK * widths // 32)
    return int(words.sum() * 4 + widths.size * (1 + 8))  # + width + anchor
