"""Pallas TPU kernel: batched Szudzik pair/unpair on (hi, lo) u32 lane pairs.

TPU has no 64-bit integers, so codes are (hi, lo) u32 pairs and all 64-bit
arithmetic is emulated on the VPU:
  * add/sub with carry/borrow
  * 32x32 -> 64 multiply via 16-bit limb decomposition
  * compare via (hi, lo) lexicographic test
  * exact isqrt via 32-step bit-by-bit restoration (mul + cmp per bit) —
    float estimates are NOT exact at 64 bits (f32 has 24 mantissa bits),
    and exactness is required for unpairing correctness.

Blocks are (8, 128) u32 tiles in VMEM (VPU register shape); ops.py reshapes
flat arrays into lane tiles. ref.py is the pure-jnp uint64 oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

U32 = jnp.uint32

BLOCK_ROWS = 8
LANES = 128

_MASK16 = np.uint32(0xFFFF)  # numpy scalar: not captured as a traced const


def _mul32_64(a, b):
    """(a * b) for u32 arrays -> (hi, lo) u32 of the 64-bit product."""
    ah = a >> 16
    al = a & _MASK16
    bh = b >> 16
    bl = b & _MASK16
    p0 = al * bl                      # < 2^32
    mid1 = al * bh                    # < 2^32
    mid2 = ah * bl
    mid = mid1 + mid2
    mid_carry = (mid < mid1).astype(U32)   # overflow of the 2^16 coefficient
    lo = p0 + (mid << 16)
    lo_carry = (lo < p0).astype(U32)
    hi = ah * bh + (mid >> 16) + (mid_carry << 16) + lo_carry
    return hi, lo


def _add64(a_hi, a_lo, b_hi, b_lo):
    lo = a_lo + b_lo
    carry = (lo < a_lo).astype(U32)
    return a_hi + b_hi + carry, lo


def _sub64(a_hi, a_lo, b_hi, b_lo):
    lo = a_lo - b_lo
    borrow = (a_lo < b_lo).astype(U32)
    return a_hi - b_hi - borrow, lo


def _le64(a_hi, a_lo, b_hi, b_lo):
    return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo <= b_lo))


def _lt64(a_hi, a_lo, b_hi, b_lo):
    return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo < b_lo))


def _isqrt64(z_hi, z_lo, unroll: bool = True):
    """Exact floor(sqrt(z)) for z = (hi, lo), via bit-restoration.

    Builds the root from bit 31 down; per bit: candidate = r | (1 << k),
    keep if candidate^2 <= z. 32 emulated mul+cmp rounds, branch-free —
    unrolled in kernel bodies, a fori_loop in XLA programs (the unrolled
    chain of ~700 elementwise ops per call site costs the TPU compiler
    minutes and can overflow its stack)."""
    def round_(k, r):
        cand = r | (np.uint32(1) << (np.uint32(31) - k.astype(U32)))
        c_hi, c_lo = _mul32_64(cand, cand)
        return jnp.where(_le64(c_hi, c_lo, z_hi, z_lo), cand, r)

    r = jnp.zeros_like(z_lo)
    if not unroll:
        return jax.lax.fori_loop(np.int32(0), np.int32(32), round_, r)
    for k in range(32):
        r = round_(np.int32(k), r)
    return r


def szudzik_pair_math(x, y):
    """(hi, lo) of Szudzik(x, y), pure u32 math (shared by kernel and tests)."""
    # x < y:  y^2 + x ; x >= y: x^2 + x + y
    lt = x < y
    m = jnp.where(lt, y, x)   # max(x, y) by select: Mosaic has no u32 max
    sq_hi, sq_lo = _mul32_64(m, m)
    add1 = jnp.where(lt, x, x)          # +x in both branches
    add2 = jnp.where(lt, jnp.zeros_like(y), y)
    hi, lo = _add64(sq_hi, sq_lo, jnp.zeros_like(add1), add1)
    hi, lo = _add64(hi, lo, jnp.zeros_like(add2), add2)
    return hi, lo


def szudzik_unpair_math(z_hi, z_lo, unroll: bool = True):
    s = _isqrt64(z_hi, z_lo, unroll)
    s2_hi, s2_lo = _mul32_64(s, s)
    rem_hi, rem_lo = _sub64(z_hi, z_lo, s2_hi, s2_lo)
    # rem < s  -> (x, y) = (rem, s)   [rem fits u32 in this branch]
    # rem >= s -> (x, y) = (s, rem - s)
    is_lt = _lt64(rem_hi, rem_lo, jnp.zeros_like(s), s)
    y_hi, y_lo = _sub64(rem_hi, rem_lo, jnp.zeros_like(s), s)
    x = jnp.where(is_lt, rem_lo, s)
    y = jnp.where(is_lt, s, y_lo)
    return x, y


def _pair_kernel(x_ref, y_ref, hi_ref, lo_ref):
    hi, lo = szudzik_pair_math(x_ref[...], y_ref[...])
    hi_ref[...] = hi
    lo_ref[...] = lo


def _unpair_kernel(hi_ref, lo_ref, x_ref, y_ref):
    x, y = szudzik_unpair_math(hi_ref[...], lo_ref[...])
    x_ref[...] = x
    y_ref[...] = y


def _tiled_call(kernel, a, b, interpret: bool):
    """a, b: u32 [M, 128] -> two u32 [M, 128] outputs, tiled (8, 128)."""
    m = a.shape[0]
    grid = (m // BLOCK_ROWS,)
    # int32 block index even under x64 (Mosaic rejects i64 index maps)
    spec = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, np.int32(0)))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((m, LANES), U32)] * 2,
        interpret=interpret,
    )(a, b)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pair_tiles(x, y, interpret: bool = False):
    """x, y: u32 [M, 128] -> (hi, lo) u32 [M, 128]."""
    return _tiled_call(_pair_kernel, x, y, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def unpair_tiles(z_hi, z_lo, interpret: bool = False):
    """(hi, lo) u32 [M, 128] -> (x, y) u32 [M, 128]."""
    return _tiled_call(_unpair_kernel, z_hi, z_lo, interpret)
