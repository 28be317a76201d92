"""Pallas TPU kernel: neighbor-set intersection + membership-rank selection —
the primitive behind the EXACT second-order (node2vec) sampler.

BINGO (PAPERS.md) observes that the node2vec bias alpha(prev, x) over the
neighbors x of the current vertex v takes only three constant values, so the
transition can be sampled EXACTLY by factorizing into constant-bias groups:

    group 0  x == prev              weight 1/p   (|G0| in {0, 1})
    group 1  x in N(prev), x!=prev  weight 1     (|G1| = |N(v) ∩ N(prev)|)
    group 2  otherwise              weight 1/q   (|G2| = deg(v) - |G0| - |G1|)

Sample the GROUP with probability proportional to its aggregate mass
|G_i| * w_i, then a MEMBER uniformly within the group — overall probability
alpha(prev, x) / sum_x alpha(prev, x), exactly, with two uniform draws and no
rejection loop. The per-lane work is one neighbor-window intersection
(classify each x of N(v) by membership in N(prev)) plus a rank-select of the
r-th member of the chosen class — this module's kernel.

Inputs are gathered neighbor WINDOWS (XLA-side CSR gathers, sentinel-padded
to a static width D): nbrs_v / nbrs_p u32 [B, D]. Degrees above D cannot be
classified exactly from a window; the caller (core/walkers.py) detects those
lanes and falls back to the rejection sampler for them only.

Backends (the registry pattern of FINDNEXT / SGNS):

  "pallas"           — the Pallas TPU kernel: 8-row f32/u32 tiles, the
                       [D, D] equality intersection per row on the VPU as
                       D lane rotations of the prev-window. The auto path
                       pads off-tile shapes (`factorized_padded`).
  "interpret"        — the SAME selection math (`_choose_math`, shared with
                       the kernel body) over the whole batch in XLA, with
                       membership via per-row binary search on the sorted
                       prev-window (exact booleans, ~D/log2(D) x cheaper on
                       CPU than the kernel's all-pairs compare — same
                       precedent as packed_store.packed_search_xla swapping
                       the unpair subroutine). The automatic CPU fallback.
  "pallas-interpret" — pl.pallas_call(interpret=True): exact kernel-body
                       validation off-TPU (slow: grid is trace-unrolled).
  "xla-ref"          — straight-line re-implementation of the factorization
                       (all-pairs membership + argmax rank-select), written
                       independently of the kernel-body helpers: the
                       readable semantics oracle (tests/test_kernels.py
                       additionally checks all backends against a pure
                       python/numpy per-row loop).

All four backends consume the same two uniforms per lane and are bit-exact
w.r.t. each other: class counts are integers, group masses are computed as
count * weight in f32 in a fixed order, so every comparison resolves
identically (tested).

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
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.delta import lane_cumsum

U32 = jnp.uint32
I32 = jnp.int32
F32 = jnp.float32

ROWS = 8     # queries per kernel tile (f32/u32 sublane count)
LANES = 128  # neighbor-window lane alignment for the kernel path

# neighbor-window padding sentinel: never a valid vertex id in this system
# (vertex ids are < n_vertices <= 2^32 - 1; graph.SENTINEL reserves the top).
# A numpy scalar so the Pallas kernel body can close over it as a constant.
SENT = np.uint32(0xFFFFFFFF)

# ------------------------------------------------------------------ registry

BACKENDS = ("pallas", "interpret", "pallas-interpret", "xla-ref")

_default_backend: Optional[str] = None   # None -> hardware auto-selection


def set_default_backend(name: Optional[str]) -> None:
    """Set the process-wide intersect backend ("auto"/None = hardware pick).

    Resolution happens at trace time: already-compiled jitted callers keep
    the backend they were traced with until their cache is invalidated."""
    global _default_backend
    if name in (None, "auto"):
        _default_backend = None
        return
    if name not in BACKENDS:
        raise ValueError(f"unknown intersect backend {name!r}; "
                         f"expected one of {BACKENDS + ('auto',)}")
    _default_backend = name


def get_default_backend() -> str:
    return resolve_backend(None)


def default_backend_request() -> Optional[str]:
    """The raw installed request (None = "auto"), NOT hardware-resolved.

    Callers that dispatch later (e.g. sample_next passing a static backend
    into a jitted step) must forward THIS value so `factorized_next` can
    still distinguish an auto pick (pads off-tile shapes) from an explicit
    kernel request (raises off-tile)."""
    return _default_backend


def resolve_backend(name: Optional[str]) -> str:
    """None/"auto" -> "pallas" on TPU, "interpret" otherwise; "pallas"
    off-TPU falls back to "interpret" (the kernel math run in XLA)."""
    name = _default_backend if name in (None, "auto") else name
    on_tpu = jax.default_backend() == "tpu"
    if name is None:
        return "pallas" if on_tpu else "interpret"
    if name not in BACKENDS:
        raise ValueError(f"unknown intersect backend {name!r}; "
                         f"expected one of {BACKENDS + ('auto',)}")
    if name == "pallas" and not on_tpu:
        return "interpret"
    return name


# ------------------------------------------------------- shared kernel math


def member_allpairs(nbrs_v, nbrs_p):
    """Membership of each window-v entry in window-p: bool [R, D].

    The kernel-body intersection: an all-pairs [R, D, D] equality reduced
    over the prev axis — branch-free, lane-parallel, no sortedness
    assumption. Sentinel lanes match sentinel padding; callers mask them
    with the validity mask."""
    eq = (nbrs_v[:, :, None] == nbrs_p[:, None, :]).astype(I32)
    return jnp.max(eq, axis=-1) > 0


def member_sorted(nbrs_v, nbrs_p):
    """Membership via per-row binary search on the SORTED prev-window.

    Exact-boolean equivalent of `member_allpairs` (CSR neighbor segments are
    code-sorted; sentinel padding keeps rows sorted) at O(D log D) per row —
    the "interpret" backend's cheap subroutine."""

    def row(p_row, v_row):
        pos = jnp.clip(jnp.searchsorted(p_row, v_row, side="left"),
                       0, p_row.shape[0] - 1)
        return p_row[pos] == v_row

    return jax.vmap(row)(nbrs_p, nbrs_v)


def member_rolled(nbrs_v, nbrs_p):
    """The kernel-body intersection: window-v entries compared against every
    rotation of window-p along the lanes, OR-accumulated — all D x D pairs
    without the [R, D, D] broadcast Mosaic cannot lay out. Exact-boolean
    equivalent of `member_allpairs`; the full cycle of rotations makes the
    result independent of the rotation direction."""
    d = nbrs_p.shape[1]

    def body(s, acc):
        return acc | (nbrs_v == pltpu.roll(nbrs_p, s, 1)).astype(I32)

    acc = jax.lax.fori_loop(np.int32(0), np.int32(d), body,
                            jnp.zeros(nbrs_v.shape, I32))
    return acc > 0


def _umax_rows(x):
    """Row max of a u32 [R, D] array as [R, 1]: a signed max of the
    sign-flipped bits (Mosaic reduces only signed integers). Equal to
    jnp.max(x, axis=1, keepdims=True) bit for bit."""
    flip = np.int32(-(1 << 31))
    m = jnp.max(jax.lax.bitcast_convert_type(x, I32) ^ flip, axis=1,
                keepdims=True)
    return jax.lax.bitcast_convert_type(m ^ flip, U32)


def _choose_rows(nbrs_v, valid, member, prev, u_group, u_rank,
                 inv_p, inv_q):
    """Group-then-member selection, shared verbatim by the Pallas kernel
    body (per 8-row tile) and the "interpret" backend (whole batch).

    nbrs_v u32 [R, D]; valid/member bool [R, D]; prev u32 [R, 1];
    u_group/u_rank f32 [R, 1] in [0, 1). Returns (nxt u32 [R, 1], found
    bool [R, 1]). Row-independent math, so tile-by-8 and whole-batch
    execution produce bit-identical results. Every integer is int32 and the
    rank prefix sum is a lane scan (kernels/delta.lane_cumsum), so the same
    code lowers in Mosaic with x64 on.

    Group masses are count * weight with the cumulative thresholds formed in
    a fixed order — every backend resolves the group pick identically. The
    one f32 hazard (u_group * total rounding up to exactly `total` when
    u_group -> 1) is closed by clamping the group id to the last non-empty
    group, which is also the measure-correct choice at the top boundary."""
    inv_p = jnp.asarray(inv_p, F32)
    inv_q = jnp.asarray(inv_q, F32)
    one, two, zero = np.int32(1), np.int32(2), np.int32(0)
    is_prev = valid & (nbrs_v == prev)
    is_common = valid & member & ~is_prev
    is_far = valid & ~member & ~is_prev

    def count(m):
        return jnp.sum(m.astype(I32), axis=1, keepdims=True, dtype=I32)

    c0, c1, c2 = count(is_prev), count(is_common), count(is_far)  # [R, 1]
    m0 = c0.astype(F32) * inv_p
    m1 = c1.astype(F32)
    m2 = c2.astype(F32) * inv_q
    t = u_group * (m0 + m1 + m2)
    grp = (t >= m0).astype(I32) + (t >= m0 + m1).astype(I32)    # [R, 1]
    last_nonempty = jnp.where(c2 > 0, two, jnp.where(c1 > 0, one, zero))
    grp = jnp.minimum(grp, last_nonempty)

    cg = jnp.where(grp == 0, c0, jnp.where(grp == 1, c1, c2))
    r = jnp.minimum((u_rank * cg.astype(F32)).astype(I32), cg - one)
    cls = jnp.where(grp == 0, is_prev.astype(I32),
                    jnp.where(grp == 1, is_common.astype(I32),
                              is_far.astype(I32)))               # [R, D]
    lane = jax.lax.broadcasted_iota(I32, cls.shape, 1)
    rank = lane_cumsum(cls, lane)                # 1-indexed at members
    hit = (cls > 0) & (rank == r + one)
    nxt = _umax_rows(jnp.where(hit, nbrs_v, jnp.zeros_like(nbrs_v)))
    found = (c0 + c1 + c2) > 0
    return nxt, found


def _choose_math(nbrs_v, valid, member, prev, u_group, u_rank,
                 inv_p, inv_q):
    """`_choose_rows` with [R]-shaped outputs (nxt u32, found bool): the
    whole-batch form the XLA backends and the megakernel use."""
    nxt, found = _choose_rows(nbrs_v, valid, member, prev, u_group, u_rank,
                              inv_p, inv_q)
    return nxt[:, 0], found[:, 0]


def _intersect_kernel(nv_ref, np_ref, prev_ref, ug_ref, ur_ref,
                      nxt_ref, found_ref, *, inv_p, inv_q):
    nbrs_v = nv_ref[...]
    valid = nbrs_v != SENT
    member = member_rolled(nbrs_v, np_ref[...])
    nxt, found = _choose_rows(nbrs_v, valid, member, prev_ref[...],
                              ug_ref[...], ur_ref[...], inv_p, inv_q)
    nxt_ref[...] = nxt
    found_ref[...] = found.astype(U32)


# ----------------------------------------------------------------- backends


@functools.partial(jax.jit,
                   static_argnames=("inv_p", "inv_q", "interpret"))
def factorized_next_pallas(nbrs_v, nbrs_p, prev, u_group, u_rank,
                           inv_p: float, inv_q: float,
                           interpret: bool = False):
    """The Pallas path: nbrs_v/nbrs_p u32 [B, D] sentinel-padded windows
    (B % 8 == 0, D % 128 == 0); prev u32 [B]; u_group/u_rank f32 [B].
    Returns (nxt u32 [B], found bool [B])."""
    b, d = nbrs_v.shape
    grid = (b // ROWS,)
    # int32 block indices even under x64 (Mosaic rejects i64 index maps)
    win = pl.BlockSpec((ROWS, d), lambda i: (i, np.int32(0)))
    scal = pl.BlockSpec((ROWS, 1), lambda i: (i, np.int32(0)))
    kernel = functools.partial(_intersect_kernel, inv_p=inv_p, inv_q=inv_q)
    nxt, found = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[win, win, scal, scal, scal],
        out_specs=[scal, scal],
        out_shape=[jax.ShapeDtypeStruct((b, 1), U32),
                   jax.ShapeDtypeStruct((b, 1), U32)],
        interpret=interpret,
    )(nbrs_v, nbrs_p, prev.reshape(-1, 1),
      u_group.astype(F32).reshape(-1, 1),
      u_rank.astype(F32).reshape(-1, 1))
    return nxt[:, 0], found[:, 0] > 0


def _factorized_interpret(nbrs_v, nbrs_p, prev, u_group, u_rank,
                          inv_p, inv_q):
    """The "interpret" backend: shared `_choose_math` over the whole batch,
    membership via the sorted-window binary search."""
    valid = nbrs_v != SENT
    member = member_sorted(nbrs_v, nbrs_p)
    return _choose_math(nbrs_v, valid, member, prev.reshape(-1, 1),
                        u_group.astype(F32).reshape(-1, 1),
                        u_rank.astype(F32).reshape(-1, 1), inv_p, inv_q)


def _factorized_ref(nbrs_v, nbrs_p, prev, u_group, u_rank, inv_p, inv_q):
    """The "xla-ref" backend: the factorization written straight-line,
    independent of the kernel-body helpers (all-pairs membership, argmax
    rank-select). Same draws, same fixed-order f32 mass arithmetic ->
    bit-identical selections (tests/test_kernels.py)."""
    inv_p = jnp.asarray(inv_p, F32)
    inv_q = jnp.asarray(inv_q, F32)
    valid = nbrs_v != SENT
    member = (nbrs_v[:, :, None] == nbrs_p[:, None, :]).any(-1)
    is_prev = valid & (nbrs_v == prev[:, None])
    is_common = valid & member & ~is_prev
    is_far = valid & ~member & ~is_prev
    c0 = is_prev.sum(axis=1).astype(I32)
    c1 = is_common.sum(axis=1).astype(I32)
    c2 = is_far.sum(axis=1).astype(I32)
    m0 = c0.astype(F32) * inv_p
    m1 = c1.astype(F32)
    m2 = c2.astype(F32) * inv_q
    t = u_group.astype(F32) * (m0 + m1 + m2)
    grp = (t >= m0).astype(I32) + (t >= m0 + m1).astype(I32)
    grp = jnp.minimum(grp, jnp.where(c2 > 0, 2, jnp.where(c1 > 0, 1, 0)))
    cg = jnp.where(grp == 0, c0, jnp.where(grp == 1, c1, c2))
    r = jnp.minimum((u_rank.astype(F32) * cg.astype(F32)).astype(I32),
                    cg - 1)
    cls = jnp.where((grp == 0)[:, None], is_prev,
                    jnp.where((grp == 1)[:, None], is_common, is_far))
    rank = jnp.cumsum(cls, axis=1, dtype=I32)
    idx = jnp.argmax((rank == (r + 1)[:, None]) & cls, axis=1)
    nxt = jnp.take_along_axis(nbrs_v, idx[:, None], axis=1)[:, 0]
    found = (c0 + c1 + c2) > 0
    return jnp.where(found, nxt, jnp.zeros_like(nxt)), found


def factorized_padded(nbrs_v, nbrs_p, prev, u_group, u_rank, inv_p: float,
                      inv_q: float, interpret: bool = False):
    """The Pallas path on any shape: rows padded to the 8-row tile with
    all-sentinel windows (found=False there), lanes padded to 128 with the
    sentinel (never matches a vertex). Returns (nxt u32 [B], found [B])."""
    b, d = nbrs_v.shape
    padb = (-b) % ROWS
    padd = (-d) % LANES
    if padb or padd:
        nbrs_v = jnp.pad(nbrs_v, ((0, padb), (0, padd)), constant_values=SENT)
        nbrs_p = jnp.pad(nbrs_p, ((0, padb), (0, padd)), constant_values=SENT)
        prev = jnp.pad(prev, (0, padb))
        u_group = jnp.pad(u_group, (0, padb))
        u_rank = jnp.pad(u_rank, (0, padb))
    nxt, found = factorized_next_pallas(nbrs_v, nbrs_p, prev, u_group,
                                        u_rank, inv_p, inv_q,
                                        interpret=interpret)
    return nxt[:b], found[:b]


def factorized_next(nbrs_v, nbrs_p, prev, u_group, u_rank, p: float,
                    q: float, backend: Optional[str] = None):
    """Dispatch one exact group-factorized node2vec selection.

    nbrs_v/nbrs_p u32 [B, D] sentinel-padded neighbor windows of the current
    and previous vertex; prev u32 [B]; u_group/u_rank f32 [B] uniforms.
    Returns (nxt u32 [B], found bool [B]); found=False (isolated v) leaves
    the caller to keep the walker in place.

    Traceable inside jit/scan for a concrete `backend`. Tiling contract
    (B % 8 == 0, D % 128 == 0): the auto-resolved kernel path pads off-tile
    shapes to the tile (`factorized_padded`), so on TPU the kernel runs on
    every shape; an EXPLICIT "pallas"/"pallas-interpret" request raises on
    them, so a kernel-validation run sees exactly the shapes it asked for."""
    explicit = backend not in (None, "auto")
    backend = resolve_backend(backend)
    inv_p = float(1.0 / p)
    inv_q = float(1.0 / q)
    if backend in ("pallas", "pallas-interpret"):
        b, d = nbrs_v.shape
        interpret = backend == "pallas-interpret"
        if b % ROWS or d % LANES:
            if explicit:
                raise ValueError(
                    f"intersect backend {backend!r} requires B % {ROWS} == 0 "
                    f"and D % {LANES} == 0, got B={b}, D={d}; use "
                    f"backend='auto' to pad to the tile")
            return factorized_padded(nbrs_v, nbrs_p, prev, u_group, u_rank,
                                     inv_p, inv_q, interpret=interpret)
        return factorized_next_pallas(nbrs_v, nbrs_p, prev, u_group, u_rank,
                                      inv_p, inv_q, interpret=interpret)
    if backend == "interpret":
        return _factorized_interpret(nbrs_v, nbrs_p, prev, u_group, u_rank,
                                     inv_p, inv_q)
    if backend == "xla-ref":
        return _factorized_ref(nbrs_v, nbrs_p, prev, u_group, u_rank,
                               inv_p, inv_q)
    raise ValueError(f"factorized_next cannot serve backend {backend!r}")
