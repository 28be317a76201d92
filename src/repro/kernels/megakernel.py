"""Step-centric walk megakernel: one fused Pallas kernel per rewalk step.

The unfused `core/update._rewalk` hot path round-trips four primitives
through HBM per step: packed FINDNEXT decode (prefix traversal), the
neighbor-window intersection of the exact factorized sampler
(kernels/intersect.py), the group-mass sampling draw, and the Szudzik
write-back encode. ThunderRW (PAPERS.md) shows walk engines are
memory-latency-bound — the win is interleaving the whole per-lane step in
registers instead of materializing each intermediate. This module fuses the
four stages into ONE kernel launch per step:

  (i)   FINDNEXT decode — each lane's candidate chunk window is selected by
        scalar prefetch (the BlockSpec index map reads `cidx[q, k]`, exactly
        the range_search.py block-table indirection), so the pipeline
        double-buffers candidate-chunk DMAs while the previous chunk is
        decoded in-register (FOR bit-unpack + u64-limb cumsum).
  (ii)  Intersection — the factorized sampler's neighbor-window membership
        and the three constant-alpha group masses, computed in-register on
        the decoded lane (shared `intersect._choose_math`).
  (iii) Sampling — the next vertex from the SAME two uniforms the unfused
        path consumes (draw discipline below), so fused selections are
        bit-identical to unfused.
  (iv)  Write-back — the Szudzik (hi, lo) encode of the updated walk slot
        (shared `szudzik.szudzik_pair_math`), emitted directly from the
        kernel; the XLA epilogue only scatters the version block.

Prefix traversal (the FINDNEXT consumer) is folded INTO the step scan: the
carry tracks the true walk, so the separate upfront `Overlay.traverse`
dispatch chain of the unfused order-2 path disappears entirely. The
in-kernel hit test is

    hit = (pos in [lo, hi)) & (f == f_target) & (epoch == slot_epoch[slot])

which is exactly `WalkStore.find_next`'s search + post-verification: between
merges the base store holds at most ONE entry per slot f (merges keep only
live entries; later rewrites land in pending), the [lo, hi) segment bounds
reject live same-f entries of other owners, and the epoch stamp rejects
stale versions. Pending-overlay precedence is resolved with the same
slot-epoch key math as `Overlay._pending_next` (the cur-independent half
runs XLA-side; the owner check joins in the finalize).

Exceptional lanes keep the unfused path's exactness at cost PROPORTIONAL to
the exception count — the lane-compaction contract:

  * candidate windows wider than the static K chunks (`over` lanes): fixed
    up by the reference scan `store._scan_ref` (zero-trip when none);
  * factorized lanes with deg > dmax: `walkers.rejection_fallback` compacts
    them into a per-lane-keyed rejection side-batch (bit-identical to the
    whole-batch re-run because every fallback draw is keyed by
    fold_in(key, lane_id) alone).

Draw discipline (what makes fused == unfused bit-exact): per step,
`k_u, k_fb = split(kp)`; the two factorization uniforms come from
`uniform(k_u, (capacity, 2))` whose per-lane values depend only on
(k_u, lane); the rejection fallback consumes k_fb with per-lane fold_in
keys. Prefix lanes (p < p_min) sample garbage in both paths and discard it;
emitted lanes see identical (cur, prev, uniforms) in both paths.

Backends (the registry pattern of FINDNEXT / intersect / SGNS):

  "pallas"           — the fused TPU kernel, grid (B, K): per (lane, k) one
                       candidate chunk is DMA'd/decoded; first-hit-wins
                       accumulation across k; intersection + sampling +
                       write-back at the last k. It does not lower in Mosaic
                       yet, so on TPU this request raises.
  "interpret"        — the SAME kernel math (decode_rows, unpair_math, the
                       shared hit/finalize helpers, member_sorted +
                       _choose_math) vectorized over the whole batch in XLA:
                       the automatic CPU twin, and the bench's
                       per-fusion-stage instrument (`stages` gate).
  "pallas-interpret" — pl.pallas_call(interpret=True): exact kernel-body
                       validation off-TPU (slow: grid is trace-unrolled).
  "xla-ref"          — the step composed from the EXISTING primitives
                       (Overlay/WalkStore.find_next + sample_next +
                       pairing.szudzik_pair): the independent oracle.

The registry default is None = megakernel OFF (the unfused path): fusion is
opt-in via `WalkConfig.megakernel` / `configs/wharf_stream`. There is no
hardware auto-ON. An enabled kernel backend with an off-tile factorized
window (dmax % 128 != 0) raises — a kernel-validation run can never
silently validate a fallback. Corpora with n_walks * length > 2^32 - 1
exceed the kernel's u32 f-match and raise for every backend but "xla-ref"
(the same guard WalkStore.find_next applies by silent fallback; megakernel
selection is always explicit, so it refuses loudly instead).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import packed_store, pairing
from repro.core.corpus import walk_start_vertex
from repro.core.overlay import Overlay
from repro.core.packed_store import decode_rows
from repro.core.store import PAD_EPOCH, WalkStore
from repro.core.utils import seg_searchsorted
from repro.core.walkers import (_neighbor_window, rejection_fallback,
                                sample_next)
from repro.kernels.delta import CHUNK, WORDS
from repro.kernels.intersect import (LANES, SENT, _choose_math,
                                     member_allpairs, member_sorted)
from repro.kernels.szudzik import szudzik_pair_math, szudzik_unpair_math

U64 = jnp.uint64
U32 = jnp.uint32
I32 = jnp.int32
F32 = jnp.float32

# ------------------------------------------------------------------ registry

BACKENDS = ("pallas", "interpret", "pallas-interpret", "xla-ref")

_default_backend: Optional[str] = None   # None -> megakernel OFF (unfused)


def set_default_backend(name: Optional[str]) -> None:
    """Install the process-wide megakernel backend.

    None / "off" / "auto" all mean OFF — unlike the other registries there
    is no hardware auto-selection: fusion changes the dispatch structure of
    `_rewalk`, so it is strictly opt-in. Resolution happens at trace time:
    already-compiled jitted callers keep the selection they were traced
    with until their cache is invalidated (same caveat as the FINDNEXT and
    intersect registries)."""
    global _default_backend
    if name in (None, "off", "auto"):
        _default_backend = None
        return
    if name not in BACKENDS:
        raise ValueError(f"unknown megakernel backend {name!r}; expected "
                         f"one of {BACKENDS + ('off', 'auto')}")
    _default_backend = name


def default_backend_request() -> Optional[str]:
    """The raw installed request (None = off), NOT hardware-resolved."""
    return _default_backend


def resolve_backend(name: Optional[str]) -> Optional[str]:
    """Resolve a request to a concrete backend, or None for OFF.

    "auto" consults the registry (whose default is OFF). An explicit
    "pallas" off-TPU runs the same kernel math as "interpret" (the
    established fallback rule), keeping CPU runs free of unlowerable
    Mosaic calls. On TPU "pallas" raises: the fused kernel's (1, WORDS) /
    (1, 1) blocks do not lower in Mosaic, and a request for the kernel must
    never quietly run something else."""
    if name in (None, "off"):
        return None
    if name == "auto":
        name = _default_backend
        if name is None:
            return None
    if name not in BACKENDS:
        raise ValueError(f"unknown megakernel backend {name!r}; expected "
                         f"one of {BACKENDS + ('off', 'auto')}")
    if name == "pallas":
        if jax.default_backend() != "tpu":
            return "interpret"
        raise NotImplementedError(
            "megakernel backend 'pallas' does not compile for TPU (Mosaic "
            "rejects its (1, WORDS) and (1, 1) blocks); use megakernel='off' "
            "(the default, unfused Pallas kernels) or 'interpret'")
    return name


def check_supported(store: WalkStore, cfg, backend: str) -> None:
    """Trace-time validity of an (explicitly selected) fused rewalk.

    Raises instead of silently falling back: a megakernel selection is
    always explicit (registry default is OFF), so a run that asked for the
    kernel must never validate something else."""
    if backend == "xla-ref":
        return
    if store.n_walks * store.length > 0xFFFFFFFF:
        raise ValueError(
            f"megakernel backend {backend!r} matches FINDNEXT targets in "
            f"u32 but n_walks*length = {store.n_walks * store.length} "
            f"exceeds 2^32 - 1; use megakernel='off' or 'xla-ref'")
    model = cfg.model
    if (backend in ("pallas", "pallas-interpret") and model.order == 2
            and model.sampler == "factorized" and model.dmax % LANES):
        raise ValueError(
            f"megakernel backend {backend!r} requires the factorized "
            f"window dmax % {LANES} == 0, got dmax={model.dmax}; use "
            f"'interpret' (same math, untiled) for off-tile windows")


# ------------------------------------------------------- shared kernel math


def findnext_hit_mask(pos, f, ep, lo, hi, ft, we):
    """The fused FINDNEXT verification, shared by the kernel body and the
    "interpret" twin: position inside the pruned segment range, slot-code
    match, live-epoch match. Equivalent to WalkStore.find_next's search +
    post-verification under the one-live-entry-per-slot invariant (module
    docstring)."""
    return (pos >= lo) & (pos < hi) & (f == ft) & (ep == we)


def finalize_math(fn_v, fn_found, pend_hit, pend_nxt, samp, cur,
                  is_prefix, is_term):
    """Per-lane step resolution, shared by the kernel finalize and the
    "interpret" twin. Pending-overlay precedence, traverse's stay-in-place
    fallthrough, and the terminal slot's self-pointer — elementwise, so
    (1,1)-tile and whole-batch execution are bit-identical."""
    pfx = jnp.where(pend_hit, pend_nxt, jnp.where(fn_found, fn_v, cur))
    nxt = jnp.where(is_prefix, pfx, samp)
    nxt_eff = jnp.where(is_term, cur, nxt)
    return nxt, nxt_eff


# per-lane scalar pack column layout (u32 [B, SC_WIDTH]); lo/hi are segment
# positions (< 2^31) carried as u32 and re-cast in the kernel
(SC_FT, SC_WE, SC_LO, SC_HI, SC_CUR, SC_PREV, SC_PNXT, SC_PHIT, SC_PFX,
 SC_TERM, SC_EXT) = range(11)
SC_WIDTH = 16


def _fused_kernel_body(cidx_ref, packed_ref, width_ref, ahi_ref, alo_ref,
                       ep_ref, sc_ref, fnv_ref, fnf_ref, nxt_ref, chi_ref,
                       clo_ref, u_ref=None, nv_ref=None, np_ref=None, *,
                       k_total, inv_p, inv_q, mode):
    """Grid (B, K): one candidate chunk of one lane per step. Stages (i)
    decode+match with first-hit-wins accumulation across k; at the last k,
    (ii) intersection, (iii) sampling, (iv) write-back encode."""
    qi = pl.program_id(0)
    k = pl.program_id(1)
    c = cidx_ref[qi, k]

    # (i) decode the candidate chunk + FINDNEXT match
    dhi, dlo = decode_rows(packed_ref[...], width_ref[...], ahi_ref[...],
                           alo_ref[...])
    f, v = szudzik_unpair_math(dhi, dlo)                  # (1, CHUNK) u32
    lane = jax.lax.broadcasted_iota(I32, (1, CHUNK), 1)
    pos = c * CHUNK + lane
    ft = sc_ref[:, SC_FT:SC_FT + 1]                       # (1, 1) u32
    we = sc_ref[:, SC_WE:SC_WE + 1]
    lo = sc_ref[:, SC_LO:SC_LO + 1].astype(I32)
    hi = sc_ref[:, SC_HI:SC_HI + 1].astype(I32)
    hit = findnext_hit_mask(pos, f, ep_ref[...], lo, hi, ft, we)
    any_hit = jnp.any(hit)
    val = jnp.max(jnp.where(hit, v, jnp.zeros_like(v)))

    @pl.when(k == 0)
    def _init():
        fnv_ref[...] = jnp.zeros_like(fnv_ref)
        fnf_ref[...] = jnp.zeros_like(fnf_ref)
        nxt_ref[...] = jnp.zeros_like(nxt_ref)
        chi_ref[...] = jnp.zeros_like(chi_ref)
        clo_ref[...] = jnp.zeros_like(clo_ref)

    prev_found = fnf_ref[0, 0] > 0
    take = any_hit & ~prev_found
    fnv_ref[...] = jnp.where(take, val, fnv_ref[...])
    fnf_ref[...] = jnp.where(take, jnp.ones_like(fnf_ref), fnf_ref[...])

    @pl.when(k == k_total - 1)
    def _final():
        cur = sc_ref[:, SC_CUR:SC_CUR + 1]                # (1, 1) u32
        pend_hit = sc_ref[:, SC_PHIT:SC_PHIT + 1] > 0
        pend_nxt = sc_ref[:, SC_PNXT:SC_PNXT + 1]
        is_prefix = sc_ref[:, SC_PFX:SC_PFX + 1] > 0
        is_term = sc_ref[:, SC_TERM:SC_TERM + 1] > 0
        fn_v = fnv_ref[...]
        fn_found = fnf_ref[...] > 0
        if mode == "factorized":
            # (ii) + (iii): intersection, group masses, sampling in-register
            nbrs_v = nv_ref[...]
            valid = nbrs_v != SENT
            member = member_allpairs(nbrs_v, np_ref[...])
            s_nxt, s_found = _choose_math(
                nbrs_v, valid, member, sc_ref[:, SC_PREV:SC_PREV + 1],
                u_ref[:, 0:1], u_ref[:, 1:2], inv_p, inv_q)
            samp = jnp.where(s_found[:, None], s_nxt[:, None], cur)
        else:
            samp = sc_ref[:, SC_EXT:SC_EXT + 1]
        nxt, nxt_eff = finalize_math(fn_v, fn_found, pend_hit, pend_nxt,
                                     samp, cur, is_prefix, is_term)
        # (iv) write-back: the Szudzik (hi, lo) encode of the new slot
        chi, clo = szudzik_pair_math(ft, nxt_eff)
        nxt_ref[...] = nxt
        chi_ref[...] = chi
        clo_ref[...] = clo


def _kernel_factorized(cidx, packed, width, ahi, alo, ep, sc, u, nv, np_,
                       fnv, fnf, nxt, chi, clo, *, k_total, inv_p, inv_q):
    _fused_kernel_body(cidx, packed, width, ahi, alo, ep, sc, fnv, fnf, nxt,
                       chi, clo, u_ref=u, nv_ref=nv, np_ref=np_,
                       k_total=k_total, inv_p=inv_p, inv_q=inv_q,
                       mode="factorized")


def _kernel_external(cidx, packed, width, ahi, alo, ep, sc,
                     fnv, fnf, nxt, chi, clo, *, k_total, inv_p, inv_q):
    _fused_kernel_body(cidx, packed, width, ahi, alo, ep, sc, fnv, fnf, nxt,
                       chi, clo, k_total=k_total, inv_p=inv_p, inv_q=inv_q,
                       mode="external")


def _fused_step_pallas(store: WalkStore, epoch_grid, cidx, sc, u, nbrs_v,
                       nbrs_p, inv_p, inv_q, mode, interpret):
    """One fused step through pl.pallas_call (grid (B, K), scalar-prefetched
    chunk window as in range_search.find_next_packed)."""
    b, k = cidx.shape
    import functools

    def chunk_map(qi, ki, cidx_):
        return (cidx_[qi, ki], 0)

    def q_map(qi, ki, cidx_):
        return (qi, 0)

    in_specs = [
        pl.BlockSpec((1, WORDS), chunk_map),
        pl.BlockSpec((1, 1), chunk_map),
        pl.BlockSpec((1, 1), chunk_map),
        pl.BlockSpec((1, 1), chunk_map),
        pl.BlockSpec((1, CHUNK), chunk_map),
        pl.BlockSpec((1, SC_WIDTH), q_map),
    ]
    inputs = [store.packed, store.widths.reshape(-1, 1),
              store.anchors_hi.reshape(-1, 1),
              store.anchors_lo.reshape(-1, 1), epoch_grid, sc]
    if mode == "factorized":
        d = nbrs_v.shape[1]
        in_specs += [pl.BlockSpec((1, 2), q_map),
                     pl.BlockSpec((1, d), q_map),
                     pl.BlockSpec((1, d), q_map)]
        inputs += [u, nbrs_v, nbrs_p]
        kernel = functools.partial(_kernel_factorized, k_total=k,
                                   inv_p=inv_p, inv_q=inv_q)
    else:
        kernel = functools.partial(_kernel_external, k_total=k,
                                   inv_p=inv_p, inv_q=inv_q)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, k),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, 1), q_map)] * 5,
        ),
        out_shape=[jax.ShapeDtypeStruct((b, 1), U32)] * 5,
        name="wharf_megakernel",
        interpret=interpret,
    )(cidx, *inputs)
    fnv, fnf, nxt, chi, clo = out
    return (fnv[:, 0], fnf[:, 0] > 0, nxt[:, 0], chi[:, 0], clo[:, 0])


def _fused_step_interpret(store: WalkStore, epoch_grid, cidx, lo, hi, ft,
                          we, pend_hit, pend_nxt, cur, prev, u, nbrs_v,
                          nbrs_p, ext_nxt, is_prefix, is_term, inv_p, inv_q,
                          mode, stages="full"):
    """The kernel math vectorized over the whole batch in XLA — the same
    decode (decode_rows), unpair (szudzik_unpair_math), hit mask, selection
    (_choose_math via the sorted-window membership), and finalize as the
    kernel body, with the kernel's first-hit-chunk-wins accumulation.

    `stages` is the bench's per-fusion-stage instrument (cumulative gates):
    "decode" stops after stage (i), "intersect" additionally computes the
    membership + group counts (folded into a stub sample so XLA cannot
    dead-code it), "sample" runs the real selection, "full" adds the
    write-back encode. Gated outputs are timing artifacts ONLY — anything
    but "full" returns garbage codes by construction."""
    b, k = cidx.shape
    flat = cidx.reshape(-1)
    dhi, dlo = decode_rows(store.packed[flat], store.widths[flat][:, None],
                           store.anchors_hi[flat][:, None],
                           store.anchors_lo[flat][:, None])
    f, v = szudzik_unpair_math(dhi, dlo)                # [B*K, CHUNK] u32
    pos = flat[:, None] * CHUNK + jnp.arange(CHUNK, dtype=I32)[None]
    ep = epoch_grid[flat]

    def rep(x):
        return jnp.repeat(x, k)[:, None]

    hit = findnext_hit_mask(pos, f, ep, rep(lo), rep(hi), rep(ft), rep(we))
    hit = hit.reshape(b, k, CHUNK)
    chunk_hit = jnp.any(hit, axis=-1)
    fn_found = jnp.any(chunk_hit, axis=-1)
    first_k = jnp.argmax(chunk_hit, axis=-1)
    sel_hit = jnp.take_along_axis(hit, first_k[:, None, None], 1)[:, 0]
    sel_v = jnp.take_along_axis(v.reshape(b, k, CHUNK),
                                first_k[:, None, None], 1)[:, 0]
    fn_v = jnp.max(jnp.where(sel_hit, sel_v, jnp.zeros_like(sel_v)),
                   axis=-1)

    if mode == "factorized" and stages in ("intersect", "sample", "full"):
        valid = nbrs_v != SENT
        member = member_sorted(nbrs_v, nbrs_p)
        if stages == "intersect":
            is_prev = valid & (nbrs_v == prev[:, None])
            c1 = jnp.sum((valid & member & ~is_prev).astype(I32), axis=1)
            samp = c1.astype(U32)     # timing stub: keeps stage (ii) live
        else:
            s_nxt, s_found = _choose_math(nbrs_v, valid, member,
                                          prev[:, None], u[:, 0:1],
                                          u[:, 1:2], inv_p, inv_q)
            samp = jnp.where(s_found, s_nxt, cur)
    elif mode == "factorized":
        samp = cur                    # stage gate: sampling not yet fused
    else:
        samp = ext_nxt
    nxt, nxt_eff = finalize_math(fn_v, fn_found, pend_hit, pend_nxt, samp,
                                 cur, is_prefix, is_term)
    if stages == "full":
        chi, clo = szudzik_pair_math(ft, nxt_eff)
    else:
        chi, clo = jnp.zeros_like(nxt_eff), nxt_eff
    return fn_v, fn_found, nxt, chi, clo


# ----------------------------------------------------------- the fused scan


def fused_scan(key, graph, store: WalkStore, pending, walk_ids, lane_valid,
               p_min, v_at_pmin, cfg, backend: str,
               window: Optional[int] = None, stages: str = "full"):
    """The fused replacement of `_rewalk`'s prefix-traverse + sample scan.

    Same lane layout and key discipline as the unfused path; returns the
    scan-stacked (owners, codes, emits), each [length, capacity], for the
    caller's shared version-block tail. The carry tracks the TRUE walk:
    prefix positions advance through the in-kernel FINDNEXT (overlay
    precedence included), so no upfront Overlay.traverse dispatch chain
    remains. Emitted triplets are bit-identical to the unfused path
    (tests/test_megakernel.py).

    `stages` (interpret backend only) is the bench's cumulative fusion gate
    — see `_fused_step_interpret`."""
    length = store.length
    capacity = walk_ids.shape[0]
    model = cfg.model
    mode = ("factorized"
            if model.order == 2 and model.sampler == "factorized"
            else "external")
    if stages != "full" and backend != "interpret":
        raise ValueError("per-stage gating is an interpret-backend bench "
                         "instrument; kernel backends always run 'full'")
    k_chunks = window or packed_store.get_default_window()
    start = walk_start_vertex(walk_ids, cfg.n_walks_per_vertex)
    w64 = walk_ids.astype(U64)
    l64 = jnp.asarray(length, U64)
    keys = jax.random.split(key, length)
    ps = jnp.arange(length, dtype=I32)

    if backend == "xla-ref":
        # the composed-primitives oracle: existing find_next / sample_next /
        # szudzik_pair per step, with the fused carry discipline
        view = store if pending is None else Overlay.build(store, pending)

        def step_ref(carry, inp):
            cur, prev = carry
            p, kp = inp
            cur = jnp.where(p == p_min, v_at_pmin, cur)
            is_prefix = p < p_min
            is_term = p == length - 1
            f64 = w64 * l64 + p.astype(U64)
            fn_v, fn_found = view.find_next(cur, walk_ids,
                                            jnp.full_like(walk_ids, p))
            pfx = jnp.where(fn_found, fn_v, cur)
            samp = sample_next(kp, graph, cur, prev, model)
            nxt = jnp.where(is_prefix, pfx, samp)
            nxt_eff = jnp.where(is_term, cur, nxt)
            code = pairing.szudzik_pair(f64, nxt_eff.astype(U64))
            emit = lane_valid & (p >= p_min)
            cur_new = jnp.where(is_term, cur, nxt)
            return (cur_new, cur), (cur, code, emit)

        _, out = jax.lax.scan(step_ref, (start, start), (ps, keys))
        return out

    # ---- kernel-math backends ("pallas" / "interpret" / "pallas-interpret")
    if pending is None:
        skey = jnp.full((1,), 0xFFFFFFFFFFFFFFFF, U64)   # never matches
        scode = jnp.zeros((1,), U64)
        sowner = jnp.zeros((1,), U32)
    else:
        ov = Overlay.build(store, pending)
        skey, scode, sowner = ov.skey, ov.scode, ov.sowner
    n_chunks = store.n_chunks
    ep_pad = jnp.full((n_chunks * CHUNK,), PAD_EPOCH,
                      U32).at[:store.size].set(store.epoch)
    epoch_grid = ep_pad.reshape(n_chunks, CHUNK)
    inv_p = float(1.0 / model.p)
    inv_q = float(1.0 / model.q)
    dmax = model.dmax

    def step(carry, inp):
        cur, prev = carry
        p, kp = inp
        cur = jnp.where(p == p_min, v_at_pmin, cur)
        is_prefix = p < p_min
        is_term = jnp.broadcast_to(p == length - 1, cur.shape)

        # XLA prologue: pruned candidate window (paper §5.1) + the
        # cur-independent half of the pending-overlay point lookup
        f64 = w64 * l64 + p.astype(U64)
        lb, ub = pairing.search_range(f64, store.vmin[cur], store.vmax[cur])
        seg_lo = store.offsets[cur]
        seg_hi = store.offsets[cur + jnp.asarray(1, U32)]
        lo = seg_searchsorted(store.code, seg_lo, seg_hi, lb, side="left")
        hi = seg_searchsorted(store.code, seg_lo, seg_hi, ub, side="right")
        want = store.slot_epoch[f64.astype(I32)]         # slot == f
        pkey = (f64 << jnp.asarray(32, U64)) | want.astype(U64)
        pc = jnp.clip(jnp.searchsorted(skey, pkey, side="left"), 0,
                      skey.shape[0] - 1)
        _, pnxt64 = pairing.szudzik_unpair(scode[pc])
        pend_hit = (skey[pc] == pkey) & (sowner[pc] == cur)
        pend_nxt = pnxt64.astype(U32)
        c0 = lo // CHUNK
        c1 = jnp.maximum(hi - 1, lo) // CHUNK
        cidx = jnp.clip(c0[:, None] + jnp.arange(k_chunks, dtype=I32)[None],
                        0, n_chunks - 1)
        over = (hi > lo) & ((c1 - c0) >= k_chunks)
        ft = f64.astype(U32)

        if mode == "factorized":
            k_u, k_fb = jax.random.split(kp)
            u = jax.random.uniform(k_u, (capacity, 2), dtype=F32)
            nbrs_v, deg_v = _neighbor_window(graph, cur, dmax)
            nbrs_p, deg_p = _neighbor_window(graph, prev, dmax)
            overflow = (deg_v > dmax) | (deg_p > dmax)
            ext_nxt = jnp.zeros_like(cur)
        else:
            u = nbrs_v = nbrs_p = None
            k_fb = kp
            overflow = jnp.zeros_like(is_prefix)
            ext_nxt = sample_next(kp, graph, cur, prev, model)

        if backend == "interpret":
            fn_v, fn_found, nxt, chi, clo = _fused_step_interpret(
                store, epoch_grid, cidx, lo, hi, ft, want, pend_hit,
                pend_nxt, cur, prev, u, nbrs_v, nbrs_p, ext_nxt, is_prefix,
                is_term, inv_p, inv_q, mode, stages)
        else:
            sc = jnp.stack(
                [ft, want, lo.astype(U32), hi.astype(U32), cur, prev
                 if mode == "factorized" else cur, pend_nxt,
                 pend_hit.astype(U32), is_prefix.astype(U32),
                 is_term.astype(U32), ext_nxt], axis=1)
            sc = jnp.pad(sc, ((0, 0), (0, SC_WIDTH - sc.shape[1])))
            fn_v, fn_found, nxt, chi, clo = _fused_step_pallas(
                store, epoch_grid, cidx, sc, u, nbrs_v, nbrs_p, inv_p,
                inv_q, mode, interpret=(backend == "pallas-interpret"))
        code64 = pairing.join_u64(chi, clo)

        # epilogue: exceptional-lane fixups, cost proportional to the count
        fix = is_prefix & over
        o_out, o_found = store._scan_ref(jnp.where(over, lo, hi), hi, f64,
                                         want)
        pfx_fix = jnp.where(pend_hit, pend_nxt,
                            jnp.where(o_found, o_out, cur))
        nxt = jnp.where(fix, pfx_fix, nxt)
        changed = fix
        if mode == "factorized":
            ov_mask = overflow & ~is_prefix
            nxt = rejection_fallback(k_fb, graph, cur, prev, ov_mask, nxt,
                                     model.p, model.q, model.n_trials)
            changed = changed | ov_mask
        nxt_eff = jnp.where(is_term, cur, nxt)
        code64 = jnp.where(changed,
                           pairing.szudzik_pair(f64, nxt_eff.astype(U64)),
                           code64)
        emit = lane_valid & (p >= p_min)
        cur_new = jnp.where(is_term, cur, nxt)
        return (cur_new, cur), (cur, code64, emit)

    _, out = jax.lax.scan(step, (start, start), (ps, keys))
    return out
