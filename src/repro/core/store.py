"""WalkStore — the hybrid-tree (paper §4) adapted to TPU-resident flat arrays.

Paper structure                      ->  TPU-native structure (this file)
-----------------------------------------------------------------------------
vertex-tree (outer PAM)              ->  `offsets[n+1]` CSR over owner vertex
walk-tree of v (inner C-tree)        ->  segment [offsets[v], offsets[v+1]) of the
                                         (owner, code)-lexsorted flat code array
C-tree chunks (size b=128) + heads   ->  device-resident FOR bit-packed chunks
                                         (`packed/widths`); `anchors_*/last_*`
                                         head arrays (O(1) c_first/c_last, §5.2)
per-walk-tree {v_min, v_max}         ->  `vmin/vmax[n]` (search bounds, §5.1)
walk-tree *versions* (on-demand      ->  `epoch[T]` stamps + dense `slot_epoch`
merge, §6.2/App. A)                      (latest version per corpus slot)
variable-byte difference encoding    ->  frame-of-reference bit-packing (§4.4;
                                         branch-free decode — kernels/delta.py)

The compressed chunks are the query-path source of truth: FINDNEXT routes
through the packed-chunk backend registry (core/packed_store.py; Pallas kernel
on TPU, XLA-interpreted kernel math on CPU, the legacy scalar while-loop as
the "xla-ref" reference backend). The uncompressed `owner/code/epoch` arrays
remain resident for the update path (MAV gathers, merges) and for the
slot-epoch liveness verification of mid-update reads.

Invariant: for a graph with `n_cap` addressable vertices the corpus holds exactly
T = n_cap * n_w * l triplets — re-walks replace slots one-for-one, so every array
is static-shaped. Snapshots (paper's PF-tree motivation) are free: JAX arrays are
immutable, any reference is a serializable snapshot (DESIGN.md §2).

Between merges the live corpus is this base store PLUS the engine's pending
version blocks; `core/overlay.py::Overlay` wraps the pair with the same
`find_next`/`traverse` signatures (slot-epoch precedence, DESIGN.md §5), so
readers never force a merge. `find_next` here already implements the base
half of that contract: entries whose slot was rewritten by a pending version
fail the `epoch == slot_epoch[slot]` verification and report not-found.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import packed_store, pairing
from repro.core.packed_store import CHUNK, PackedWalkStore
from repro.core.utils import seg_searchsorted

U64 = jnp.uint64
U32 = jnp.uint32
I32 = jnp.int32

PAD_EPOCH = jnp.asarray(0xFFFFFFFF, U32)


def _query_arrays(v, w, p):
    """FINDNEXT query operands as 1-d arrays: v u32, w and p u64."""
    return (jnp.atleast_1d(jnp.asarray(v, U32)),
            jnp.atleast_1d(jnp.asarray(w, U64)),
            jnp.atleast_1d(jnp.asarray(p, U64)))


def _last_chunk(lo, hi):
    """The chunk of [lo, hi)'s last code (lo's chunk when it is empty)."""
    return jnp.maximum(hi - 1, lo) // CHUNK


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class WalkStore:
    owner: jax.Array        # uint32[T] vertex at (w, p); primary sort key
    code: jax.Array         # uint64[T] Szudzik codes; secondary sort key
    epoch: jax.Array        # uint32[T] version stamp of each entry
    offsets: jax.Array      # int32[n+1] per-vertex segment bounds
    vmin: jax.Array         # uint32[n] min next-vertex id per vertex (paper §5.1)
    vmax: jax.Array         # uint32[n]
    packed: jax.Array       # uint32[C, WORDS] FOR bit-packed chunks (§4.4)
    widths: jax.Array       # uint32[C] per-chunk width class {8,16,32,64}
    anchors_hi: jax.Array   # uint32[C] chunk head code as (hi, lo) (§5.2)
    anchors_lo: jax.Array
    last_hi: jax.Array      # uint32[C] chunk tail code as (hi, lo)
    last_lo: jax.Array
    slot_epoch: jax.Array   # uint32[n_walks * l] latest version per corpus slot
    length: int = dataclasses.field(metadata=dict(static=True))
    n_walks: int = dataclasses.field(metadata=dict(static=True))
    n_vertices: int = dataclasses.field(metadata=dict(static=True))
    chunk_b: int = dataclasses.field(metadata=dict(static=True))

    def replace(self, **kw) -> "WalkStore":
        return dataclasses.replace(self, **kw)

    # ------------------------------------------------------------------ build

    @staticmethod
    def build(owner, code, epoch, slot_epoch, length: int, n_walks: int,
              n_vertices: int, chunk_b: int = 128) -> "WalkStore":
        """Sort by (owner, code) and derive all index metadata."""
        order = jnp.lexsort((code, owner))
        return WalkStore.from_sorted(
            owner[order].astype(U32), code[order], epoch[order].astype(U32),
            slot_epoch, length, n_walks, n_vertices, chunk_b)

    @staticmethod
    def from_sorted(owner, code, epoch, slot_epoch, length: int,
                    n_walks: int, n_vertices: int, chunk_b: int = 128,
                    prev: Optional["WalkStore"] = None) -> "WalkStore":
        """Derive metadata from an ALREADY (owner, code)-sorted stream
        (used by the O(T) interleave merge — §Perf).

        `prev`: the pre-merge store. When given (and shape-compatible), only
        chunks whose codes were dirtied by the merge are re-encoded; clean
        chunks keep their previous packed rows bit-identically (the
        dirty-chunk invariant, tests/test_packed_store.py). The per-chunk
        encode is data-parallel jnp, so under XLA's static shapes the select
        is how "encode only dirty chunks" is expressed; the mask also feeds
        incremental checkpoint/shard-diff accounting.
        """
        offsets = jnp.searchsorted(
            owner, jnp.arange(n_vertices + 1, dtype=U32), side="left"
        ).astype(I32)
        _, v_next = pairing.szudzik_unpair(code)
        v_next32 = v_next.astype(U32)
        vmin = jax.ops.segment_min(v_next32, owner.astype(I32),
                                   num_segments=n_vertices)
        vmax = jax.ops.segment_max(v_next32, owner.astype(I32),
                                   num_segments=n_vertices)
        packed, widths, a_hi, a_lo, l_hi, l_lo = \
            packed_store.encode_codes(code)
        if prev is not None and prev.code.shape == code.shape \
                and prev.packed.shape == packed.shape:
            dirty = jnp.any(packed_store.pad_chunk_codes(prev.code)
                            != packed_store.pad_chunk_codes(code), axis=1)
            packed = jnp.where(dirty[:, None], packed, prev.packed)
            widths = jnp.where(dirty, widths, prev.widths)
            a_hi = jnp.where(dirty, a_hi, prev.anchors_hi)
            a_lo = jnp.where(dirty, a_lo, prev.anchors_lo)
            l_hi = jnp.where(dirty, l_hi, prev.last_hi)
            l_lo = jnp.where(dirty, l_lo, prev.last_lo)
        return WalkStore(owner, code, epoch, offsets, vmin, vmax,
                         packed, widths, a_hi, a_lo, l_hi, l_lo, slot_epoch,
                         length, n_walks, n_vertices, chunk_b)

    @property
    def size(self) -> int:
        return self.code.shape[0]

    @property
    def n_chunks(self) -> int:
        return self.packed.shape[0]

    def packed_view(self) -> PackedWalkStore:
        """The standalone compressed abstraction (shares device arrays)."""
        return PackedWalkStore(self.packed, self.widths, self.anchors_hi,
                               self.anchors_lo, self.last_hi, self.last_lo,
                               self.offsets, self.vmin, self.vmax,
                               self.length, self.n_vertices)

    # ------------------------------------------------------------- traversal

    def find_next(self, v, w, p, backend: Optional[str] = None,
                  window: Optional[int] = None):
        """FINDNEXT (paper Alg. 1), batched over query arrays.

        Returns (v_next uint32, found bool). Implements the §5.1 pruned range
        search — candidates limited to [lb, ub] = [<f, vmin[v]>, <f, vmax[v]>]
        within v's segment — routed through the packed-chunk backend registry
        (module docstring). Exactness is never sacrificed: lanes whose
        candidate range exceeds the backend's static cap fall back to the
        reference scan, and every packed hit is verified against the
        authoritative code/epoch arrays, which restores the slot-epoch
        liveness check so stale pre-merge versions are skipped exactly as
        in "xla-ref". `window` caps the chunks per query of the
        pallas/pallas-interpret kernels, which decode only the chunks the
        query's candidate range spans (`_kernel_window`); the "interpret"
        backend uses a fixed 2-chunk window with a MAX_CANDIDATES
        output-sensitive cap.
        """
        backend = packed_store.resolve_backend(backend)
        if self.n_walks * self.length > 0xFFFFFFFF:
            backend = "xla-ref"  # kernel f-match is u32; huge corpora scan
        v, w64, p64 = _query_arrays(v, w, p)
        f, lo, hi, seg_lo, seg_hi = self._candidate_range(v, w64, p64)
        slot = (w64 * jnp.asarray(self.length, U64) + p64).astype(I32)
        want_epoch = self.slot_epoch[slot]

        if backend == "xla-ref":
            return self._scan_ref(lo, hi, f, want_epoch,
                                  unpair=pairing.szudzik_unpair_u64)

        c0 = lo // CHUNK
        if backend == "interpret":
            # output-sensitive XLA interpretation: decode a 2-chunk window
            # (always covers MAX_CANDIDATES < CHUNK positions from lo) with
            # branch-free bit ops, then unpair only the <= MAX_CANDIDATES
            # codes inside [lo, hi) — the paper's §5.3 k term
            wmax = packed_store.MAX_CANDIDATES
            cidx = jnp.clip(c0[:, None] + jnp.arange(2, dtype=I32)[None],
                            0, self.n_chunks - 1)
            cand = packed_store.packed_candidates(
                self.packed, self.widths, self.anchors_hi, self.anchors_lo,
                cidx, lo, wmax)
            cf, cv = pairing.szudzik_unpair(cand.reshape(-1))
            cf = cf.reshape(cand.shape)
            cv = cv.reshape(cand.shape)
            in_rng = jnp.arange(wmax, dtype=I32)[None] < (hi - lo)[:, None]
            hit = in_rng & (cf == f[:, None])
            f_k = jnp.any(hit, axis=1)
            v_k = jnp.max(jnp.where(hit, cv, jnp.zeros_like(cv)),
                          axis=1).astype(U32)
            over = (hi - lo) > wmax
        else:  # "pallas" / "pallas-interpret": the packed-chunk kernel
            k = window or packed_store.get_default_window()
            cidx = self._kernel_window(lo, hi, k)
            v_k, f_k = packed_store.packed_search(
                self.packed, self.widths, self.anchors_hi, self.anchors_lo,
                cidx, f, backend)
            over = (hi > lo) & ((_last_chunk(lo, hi) - c0) >= k)
        # verification against the authoritative arrays: the hit must sit in
        # v's segment AND carry the slot's live epoch (mid-update liveness)
        tgt = pairing.szudzik_pair(f, v_k.astype(U64))
        pos = seg_searchsorted(self.code, seg_lo, seg_hi, tgt, side="left")
        pc = jnp.clip(pos, 0, self.size - 1)
        ok = (pos < seg_hi) & (self.code[pc] == tgt) \
            & (self.epoch[pc] == want_epoch)
        found = f_k & ok
        out = jnp.where(found, v_k, jnp.zeros_like(v_k))
        # lanes whose candidate window exceeds the static caps: ref fallback
        o_out, o_found = self._scan_ref(jnp.where(over, lo, hi), hi, f,
                                        want_epoch)
        return (jnp.where(over, o_out, out).astype(U32),
                jnp.where(over, o_found, found))

    def _candidate_range(self, v, w64, p64):
        """The query's f, v's segment [seg_lo, seg_hi) and the candidate
        range [lo, hi) in it: the codes in [<f, vmin[v]>, <f, vmax[v]>]
        (§5.1), which hold every code of v's segment whose first component
        is f."""
        f = pairing.pack_wp(w64, p64, self.length)
        lb, ub = pairing.search_range(f, self.vmin[v], self.vmax[v])
        seg_lo = self.offsets[v]
        seg_hi = self.offsets[v + jnp.asarray(1, U32)]
        lo = seg_searchsorted(self.code, seg_lo, seg_hi, lb, side="left")
        hi = seg_searchsorted(self.code, seg_lo, seg_hi, ub, side="right")
        return f, lo, hi, seg_lo, seg_hi

    def _kernel_window(self, lo, hi, k: int):
        """The packed kernel's k candidate chunks per query: the chunks
        c0..c1 that [lo, hi) spans, the last repeated to fill the window
        (the kernel decodes a repeated chunk once), i32 [Q, k]."""
        c0 = lo // CHUNK
        idx = jnp.minimum(c0[:, None] + jnp.arange(k, dtype=I32)[None],
                          _last_chunk(lo, hi)[:, None])
        return jnp.clip(idx, 0, self.n_chunks - 1)

    def findnext_chunks(self, v, w, p, window: Optional[int] = None):
        """Distinct chunks the packed kernel decodes for each FINDNEXT
        query (v, w, p), i32 [Q]: the chunks its candidate range spans, at
        most `window`. The FINDNEXT engagement counter of StreamMetrics."""
        v, w64, p64 = _query_arrays(v, w, p)
        _, lo, hi, _, _ = self._candidate_range(v, w64, p64)
        cidx = self._kernel_window(
            lo, hi, window or packed_store.get_default_window())
        return 1 + jnp.sum(cidx[:, 1:] != cidx[:, :-1], axis=1, dtype=I32)

    def _scan_ref(self, lo, hi, f, want_epoch,
                  unpair=pairing.szudzik_unpair):
        """Scalar while-loop over the uncompressed codes (the seed's
        original FINDNEXT; reference semantics). The "xla-ref" backend
        passes the plain u64 unpair, so on TPU it shares no math with the
        kernels; the kernels' over-cap fallback keeps the platform's form."""

        def scan_one(lo1, hi1, f1, we1):
            def cond(state):
                i, found, _ = state
                return (~found) & (i < hi1)

            def body(state):
                i, _, _ = state
                c = self.code[jnp.clip(i, 0, self.size - 1)]
                cf, cv = unpair(c)
                ok = (cf == f1) & (self.epoch[jnp.clip(i, 0, self.size - 1)] == we1)
                return (i + 1, ok, jnp.where(ok, cv.astype(U32), jnp.asarray(0, U32)))

            _, found, out = jax.lax.while_loop(
                cond, body, (lo1, False, jnp.asarray(0, U32)))
            return out, found

        return jax.vmap(scan_one)(jnp.atleast_1d(lo), jnp.atleast_1d(hi),
                                  jnp.atleast_1d(f), jnp.atleast_1d(want_epoch))

    def find_next_simple(self, v, w, p):
        """Baseline 'simple search' (paper §7.5): decode the whole segment."""
        v = jnp.asarray(v, U32)
        f = pairing.pack_wp(jnp.asarray(w, U64), jnp.asarray(p, U64), self.length)
        slot = (jnp.asarray(w, U64) * jnp.asarray(self.length, U64)
                + jnp.asarray(p, U64)).astype(I32)
        want_epoch = self.slot_epoch[slot]
        seg_lo = self.offsets[v]
        seg_hi = self.offsets[v + jnp.asarray(1, U32)]

        def scan_one(lo1, hi1, f1, we1):
            def body(i, state):
                found, out = state
                c = self.code[jnp.clip(i, 0, self.size - 1)]
                cf, cv = pairing.szudzik_unpair(c)
                ok = ((i >= lo1) & (i < hi1) & (cf == f1)
                      & (self.epoch[jnp.clip(i, 0, self.size - 1)] == we1))
                return (found | ok, jnp.where(ok, cv.astype(U32), out))

            return jax.lax.fori_loop(
                0, self.size, body, (False, jnp.asarray(0, U32)))

        found, out = jax.vmap(scan_one)(
            jnp.atleast_1d(seg_lo), jnp.atleast_1d(seg_hi),
            jnp.atleast_1d(f), jnp.atleast_1d(want_epoch))
        return out, found

    def traverse(self, w, start_vertex, upto: int,
                 backend: Optional[str] = None):
        """Reconstruct walk w's vertices [0..upto] by repeated FINDNEXT."""
        backend = packed_store.resolve_backend(backend)
        w = jnp.atleast_1d(jnp.asarray(w, U32))
        cur = jnp.atleast_1d(jnp.asarray(start_vertex, U32))

        def step(cur, p):
            nxt, found = self.find_next(cur, w, jnp.full_like(w, p),
                                        backend=backend)
            nxt = jnp.where(found, nxt, cur)
            return nxt, cur

        out, path = jax.lax.scan(step, cur, jnp.arange(upto, dtype=U32))
        return jnp.moveaxis(jnp.concatenate([path, out[None]], axis=0), 0, 1)

    # ------------------------------------------------------------- memory

    def nbytes_uncompressed(self) -> int:
        """Tree-based-equivalent footprint: raw codes + index metadata."""
        return int(self.owner.nbytes + self.code.nbytes + self.epoch.nbytes
                   + self.offsets.nbytes + self.vmin.nbytes + self.vmax.nbytes
                   + self.anchors_hi.nbytes + self.anchors_lo.nbytes
                   + self.last_hi.nbytes + self.last_lo.nbytes)

    def nbytes_packed(self) -> int:
        """Deployed compressed footprint — delegates to the packed view,
        which counts the words the kernels actually consume
        (kernels/delta.py::packed_nbytes) plus serving metadata."""
        return self.packed_view().nbytes()

    def nbytes_packed_capacity(self) -> int:
        """Device-resident packed buffer bytes (worst-case [C, WORDS] cap)."""
        return self.packed_view().nbytes_capacity()
