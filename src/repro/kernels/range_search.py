"""Pallas TPU kernel: FINDNEXT over compressed chunks (paper Alg. 1 / §5).

The paper's output-sensitive range search maps to TPU as a paged-attention
style kernel: XLA computes each query's candidate chunk window [via
searchsorted on the O(1) chunk heads — the §5.2 head optimization], then the
kernel walks the K candidate chunks per query with their indices delivered
through *scalar prefetch* (block-table indirection):

  grid = (Q,); step q:
    the 8-row block of packed [C, WORDS] holding the query's first chunk
    arrives by the BlockSpec's index map (pipelined with the previous
    query; Mosaic blocks must be (8, 128)-aligned); a later candidate in
    another block is fetched by hand from HBM
    for each DISTINCT candidate chunk, in order, until the first hit:
      select its row, decode it (FOR bit-unpack + 64-bit limb prefix sum)
      unpair codes       (emulated-u64 Szudzik inverse)
      match f == f_target[q] -> (v_next, found) into out[q]

A candidate equal to the one before it is not decoded again: the store
(WalkStore.find_next) clamps each window to the chunks c0..c1 its
candidate range spans, so a range inside one chunk decodes one chunk
however wide the window — the paper's §5.3 output sensitivity. Chunks
outside every query's candidates are never fetched. The per-query scalars
(candidate ids, their widths and anchors, the f target) ride scalar
prefetch in SMEM and the outputs are SMEM vectors, so no block is narrower
than a tile; every value in the kernel is 32-bit under x64.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.delta import WORDS, decode_block
from repro.kernels.szudzik import szudzik_unpair_math

U32 = jnp.uint32
I32 = jnp.int32

ROWS = 8      # packed rows per DMA block: the u32 sublane tile
SLAB = 256    # queries per pallas_call; bounds the scalar-prefetch (SMEM) use


def _row_reduce(fn, x):
    """(1, n) int32 -> int32 scalar. A reduction straight to a scalar makes
    Mosaic re-issue it without our dtype (int64 under x64), so reduce the
    lane axis to (1, 1) and extract the element."""
    return jnp.squeeze(fn(x, axis=1, keepdims=True))


def _umax_bits(x):
    """Max of a (1, n) u32 array, returned as the int32 scalar holding its
    bits: a signed max of the sign-flipped bits (Mosaic has no unsigned
    max reduction)."""
    flip = np.int32(-(1 << 31))
    m = _row_reduce(jnp.max, jax.lax.bitcast_convert_type(x, I32) ^ flip)
    return m ^ flip


def _match_row(rows, c, width, a_hi, a_lo, f_target):
    """Decode chunk c out of its 8-row block `rows` (u32 [ROWS, WORDS]) and
    match it against the query: (hits, max matching v) as int32 scalars."""
    # select row c % 8 (a masked int32 sum: Mosaic reduces only signed
    # integers)
    rows = jax.lax.bitcast_convert_type(rows, I32)
    sub = jax.lax.broadcasted_iota(I32, rows.shape, 0)
    mine = jnp.where(sub == (c & (ROWS - 1)), rows, jnp.zeros_like(rows))
    row = jax.lax.bitcast_convert_type(
        jnp.sum(mine, axis=0, keepdims=True, dtype=I32), U32)  # (1, WORDS)

    def scalar(x):
        return jax.lax.bitcast_convert_type(jnp.full((1, 1), x, I32), U32)

    hi, lo = decode_block(row, scalar(width), scalar(a_hi), scalar(a_lo))
    f, v = szudzik_unpair_math(hi, lo)
    hit = f == scalar(f_target)
    n_hit = _row_reduce(functools.partial(jnp.sum, dtype=I32),
                        hit.astype(I32))
    return n_hit, _umax_bits(jnp.where(hit, v, jnp.zeros_like(v)))


def _search_kernel(cidx_ref, width_ref, ahi_ref, alo_ref, ft_ref, block_ref,
                   packed_hbm, vout_ref, found_ref, spill_ref, sem, *,
                   k_total):
    """One query: its candidate chunks in order, each decoded once. The
    first chunk's block arrives through the pipelined BlockSpec; a chunk in
    another block is fetched by hand into `spill_ref`."""
    q = pl.program_id(0)
    base = q * k_total
    b0 = jax.lax.div(cidx_ref[base], np.int32(ROWS))
    vout_ref[q] = jnp.int32(0)
    found_ref[q] = jnp.int32(0)

    def step(kk):
        j = base + kk
        c = cidx_ref[j]
        # a chunk repeated from the previous candidate (a window clamped to
        # the range's last chunk) is not decoded again, nor any chunk after
        # the first hit
        prev = cidx_ref[j - jnp.minimum(kk, np.int32(1))]
        fresh = (kk == np.int32(0)) | (c != prev)

        @pl.when(fresh & (found_ref[q] == 0))
        def _search():
            b = jax.lax.div(c, np.int32(ROWS))

            @pl.when(b != b0)
            def _fetch():
                cp = pltpu.make_async_copy(
                    packed_hbm.at[pl.ds(pl.multiple_of(b * ROWS, ROWS),
                                        ROWS)], spill_ref, sem)
                cp.start()
                cp.wait()

            rows = jnp.where(b == b0, block_ref[...], spill_ref[...])
            n_hit, val = _match_row(rows, c, width_ref[j], ahi_ref[j],
                                    alo_ref[j], ft_ref[q])

            # first hitting chunk wins (max matching v within it)
            @pl.when(n_hit > 0)
            def _take():
                vout_ref[q] = val
                found_ref[q] = jnp.int32(1)

        return kk + np.int32(1)

    # a while loop keeps the counter int32 (a fori_loop with static bounds
    # becomes a scan over a 64-bit counter under x64, which Mosaic rejects)
    jax.lax.while_loop(lambda kk: kk < k_total, step, np.int32(0))


def _search_slab(packed, cidx, width, ahi, alo, ft, interpret: bool):
    """One pallas_call over a slab of queries: grid (Q,); all per-query
    scalars (candidate chunk ids, their widths/anchors, the f targets) ride
    scalar prefetch, the block of each query's first chunk is picked by the
    index map and `packed` is passed a second time, left in HBM, for the
    rare candidate in another block."""
    q, k = cidx.shape

    # index maps return int32 explicitly: under x64 a Python 0 (or a
    # jnp.floor_divide by one) traces as int64, which Mosaic rejects
    def first_block(qi, cidx_ref, *_):
        return jax.lax.div(cidx_ref[qi * k], np.int32(ROWS)), np.int32(0)

    def i32(x):
        return jax.lax.bitcast_convert_type(x.reshape(-1), I32)

    smem = pl.BlockSpec((q,), lambda *_: (np.int32(0),),
                        memory_space=pltpu.SMEM)
    out_v, out_f = pl.pallas_call(
        functools.partial(_search_kernel, k_total=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(q,),
            in_specs=[pl.BlockSpec((ROWS, WORDS), first_block),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[smem, smem],
            scratch_shapes=[pltpu.VMEM((ROWS, WORDS), U32),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=[jax.ShapeDtypeStruct((q,), I32),
                   jax.ShapeDtypeStruct((q,), I32)],
        name="wharf_findnext",
        interpret=interpret,
    )(cidx.reshape(-1), i32(width), i32(ahi), i32(alo), i32(ft), packed,
      packed)
    return jax.lax.bitcast_convert_type(out_v, U32), out_f > 0


@functools.partial(jax.jit, static_argnames=("interpret",))
def find_next_packed(packed, widths, anchors_hi, anchors_lo, chunk_idx,
                     f_targets, interpret: bool = False):
    """packed u32 [C, WORDS]; widths/anchors [C]; chunk_idx i32 [Q, K]
    candidate chunks per query; f_targets u32 [Q].
    Returns (v_next u32 [Q], found bool [Q]).

    Queries run in slabs of SLAB per kernel call (a `lax.map` over slabs
    when Q is larger), so the scalar-prefetch arrays stay a few KiB."""
    q, k = chunk_idx.shape
    c = packed.shape[0]
    if c % ROWS:  # whole 8-row blocks only (real stores are 8-aligned)
        packed = jnp.pad(packed, ((0, ROWS - c % ROWS), (0, 0)))
    chunk_idx = chunk_idx.astype(I32)
    slab = min(SLAB, -(-q // ROWS) * ROWS)
    qp = -(-q // slab) * slab
    pad = qp - q
    cidx = jnp.pad(chunk_idx, ((0, pad), (0, 0)))
    ft = jnp.pad(jnp.asarray(f_targets, U32), (0, pad))
    meta = [jnp.asarray(a, U32)[cidx] for a in (widths, anchors_hi,
                                                anchors_lo)]
    n = qp // slab
    args = [x.reshape(n, slab, *x.shape[1:]) for x in [cidx, *meta, ft]]

    def one(xs):
        ci, w, ah, al, f = xs
        return _search_slab(packed, ci, w, ah, al, f, interpret)

    if n == 1:
        v, found = one([x[0] for x in args])
    else:
        v, found = jax.lax.map(one, args)
    return v.reshape(-1)[:q], found.reshape(-1)[:q]


def candidate_chunks(chunk_first_hi, chunk_first_lo, lb_hi, lb_lo, k: int):
    """XLA-side helper: first chunk whose head could cover lb, plus the next
    k-1 chunks (the §5.1 pruned window). Pure u32 lexicographic searchsorted
    via a composed u64 key is avoided — two-level search on (hi, lo).

    NOTE: assumes the chunk heads are GLOBALLY sorted by code — true for a
    single-segment corpus (the kernel micro-benches/tests) but not for the
    owner-major WalkStore layout, where codes sort only within each vertex
    segment. The store path (WalkStore.find_next) therefore derives its
    candidate window from segment-local positions instead."""
    key = (jnp.asarray(chunk_first_hi, jnp.uint64) << jnp.uint64(32)) | \
        jnp.asarray(chunk_first_lo, jnp.uint64)
    q = (jnp.asarray(lb_hi, jnp.uint64) << jnp.uint64(32)) | \
        jnp.asarray(lb_lo, jnp.uint64)
    pos = jnp.searchsorted(key, q, side="right").astype(jnp.int32)
    start = jnp.maximum(pos - 1, 0)
    idx = start[:, None] + jnp.arange(k, dtype=jnp.int32)[None]
    return jnp.clip(idx, 0, chunk_first_hi.shape[0] - 1)
