"""Vectorized search/compaction helpers shared by the Wharf core."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

I32 = jnp.int32


def seg_searchsorted(sorted_vals, lo, hi, target, side: str = "left"):
    """Per-query binary search of `target` within [lo, hi) of `sorted_vals`.

    sorted_vals must be sorted within each queried segment. lo/hi/target are
    equal-shaped query arrays. Fixed-iteration (log2 N) branch-free binary search —
    the vectorized analogue of the paper's root-to-leaf tree descent (§5.3).
    """
    n = sorted_vals.shape[0]
    iters = max(1, math.ceil(math.log2(max(n, 2))) + 1)

    # a loop, not unrolled steps: on 64-bit keys each step is a long chain
    # of emulated ops, and unrolled chains overflow the TPU compiler's stack
    def step(_, bounds):
        lo, hi = bounds
        mid = (lo + hi) >> 1
        v = sorted_vals[jnp.clip(mid, 0, n - 1)]
        go_right = (v < target) if side == "left" else (v <= target)
        cont = lo < hi
        return (jnp.where(cont & go_right, mid + 1, lo),
                jnp.where(cont & ~go_right, mid, hi))

    lo, _ = jax.lax.fori_loop(0, iters, step, (jnp.asarray(lo, I32),
                                               jnp.asarray(hi, I32)))
    return lo


def compact_nonzero(mask, size: int, fill_value: int = 0):
    """Indices of True entries (int32, ascending), padded to `size` (static
    shape) with `fill_value`; plus the lane-validity mask. The same indices
    as `jnp.nonzero(mask, size=size)` from one int32 prefix sum and a
    scatter — jnp.nonzero runs 64-bit prefix sums under x64, which the TPU
    emulates (and which its compiler can fail to fit in VMEM)."""
    n = mask.shape[0]
    pos = jnp.cumsum(mask, dtype=I32) - 1
    dest = jnp.where(mask & (pos < size), pos, size)
    idx = jnp.full((size,), fill_value, I32).at[dest].set(
        jnp.arange(n, dtype=I32), mode="drop")
    valid = jnp.arange(size, dtype=I32) < jnp.sum(mask, dtype=I32)
    return idx, valid
