"""Mergeless overlay reads: base store + pending version blocks (PF-tree view).

The paper's walk-tree *versions* make snapshots free: a reader holds the
version it started with while the writer appends new ones. Between merges our
engine state is exactly that — an immutable base `WalkStore` plus pending
`PendingBlocks` rows whose slot-epoch stamps supersede the base. The overlay
is the read path over that pair:

  * precedence is decided per corpus slot by `slot_epoch[slot]`: the live
    entry is the one (base or pending) whose `epoch` equals it. Rewritten
    slots fail the base's liveness check and resolve from pending; untouched
    slots resolve from the base exactly as post-merge.
  * pending entries carry their slot explicitly, so the overlay indexes them
    once per build: a (slot, epoch)-sorted key array for FINDNEXT point
    lookups, and an owner-sorted view for the walks_of inverted-index reads.

An `Overlay` answers `find_next` / `traverse` with the same signature as a
`WalkStore`, so every consumer of the store abstraction (serving, the
walk-based neighborhood sampler, the node2vec prefix traversal inside the
update itself) reads base+pending without forcing a merge. Reads through an
overlay equal post-merge reads bit-for-bit (tests/test_stream.py).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import pairing
from repro.core.store import WalkStore, PAD_EPOCH

U64 = jnp.uint64
U32 = jnp.uint32
I32 = jnp.int32

_SHIFT = jnp.asarray(32, U64)


class Overlay(NamedTuple):
    """Read view over `base` + a pending accumulator, indexed two ways."""

    base: WalkStore
    # (slot << 32 | epoch)-sorted pending entries: exact-match point lookups
    skey: jax.Array    # uint64 [E]
    scode: jax.Array   # uint64 [E]
    sowner: jax.Array  # uint32 [E]
    # owner-sorted pending entries (dead rows keyed past every vertex id):
    # segment reads
    okey: jax.Array    # uint32 [E]
    ocode: jax.Array   # uint64 [E]
    oepoch: jax.Array  # uint32 [E]
    oslot: jax.Array   # int32  [E]

    # ------------------------------------------------------------------ build

    @staticmethod
    def build(store: WalkStore, pending) -> "Overlay":
        """Index the pending buffer for overlay reads (one sort per version).

        `pending` is a PendingBlocks (any leading shape; flattened here).
        Dead rows (epoch == PAD_EPOCH) can never match a live slot-epoch, so
        they need no masking in the point-lookup index; the owner index keys
        them at 2^32 - 1, past every vertex id, instead.
        """
        return _build_jit(store, pending.owner.reshape(-1),
                          pending.code.reshape(-1),
                          pending.epoch.reshape(-1),
                          pending.slot.reshape(-1))

    @property
    def n_pending_entries(self) -> int:
        return self.skey.shape[0]

    def copy_pending(self) -> "Overlay":
        """Same view with the pending index arrays in FRESH device buffers.

        The copy-on-pin half of the serving pin contract (DESIGN.md §11):
        the per-batch update driver donates the pending accumulator, so a
        pinned reader must own its O(|pending|) index copies. The base store
        is shared, not copied — keeping its O(T) buffers alive is the
        refcount half (WalkEngine.pin_buffers suppresses stream donation
        while pins are outstanding)."""
        cp = jnp.copy
        return self._replace(skey=cp(self.skey), scode=cp(self.scode),
                             sowner=cp(self.sowner), okey=cp(self.okey),
                             ocode=cp(self.ocode), oepoch=cp(self.oepoch),
                             oslot=cp(self.oslot))

    # ------------------------------------------------------------- traversal

    def _pending_next(self, v, w64, p64):
        """Live pending entry for slot (w, p) owned by v, if any."""
        length = jnp.asarray(self.base.length, U64)
        slot = w64 * length + p64
        want = self.base.slot_epoch[slot.astype(I32)]
        key = (slot << _SHIFT) | want.astype(U64)
        pos = jnp.searchsorted(self.skey, key, side="left")
        pc = jnp.clip(pos, 0, self.n_pending_entries - 1)
        hit = (self.skey[pc] == key) & (self.sowner[pc] == v)
        _, nxt = pairing.szudzik_unpair(self.scode[pc])
        return jnp.where(hit, nxt.astype(U32), jnp.zeros_like(v)), hit

    def find_next(self, v, w, p, backend: Optional[str] = None,
                  window: Optional[int] = None):
        """FINDNEXT over base + pending (slot-epoch precedence).

        Same contract as `WalkStore.find_next`. A slot rewritten by a pending
        version fails the base's liveness verification (its slot_epoch was
        bumped), so base and pending hits are mutually exclusive.
        """
        v = jnp.atleast_1d(jnp.asarray(v, U32))
        w64 = jnp.atleast_1d(jnp.asarray(w, U64))
        p64 = jnp.atleast_1d(jnp.asarray(p, U64))
        base_out, base_found = self.base.find_next(v, w64, p64,
                                                   backend=backend,
                                                   window=window)
        pend_out, pend_found = self._pending_next(v, w64, p64)
        return (jnp.where(pend_found, pend_out, base_out),
                base_found | pend_found)

    def traverse(self, w, start_vertex, upto: int,
                 backend: Optional[str] = None):
        """Reconstruct walk w's vertices [0..upto] via overlay FINDNEXT."""
        w = jnp.atleast_1d(jnp.asarray(w, U32))
        cur = jnp.atleast_1d(jnp.asarray(start_vertex, U32))

        def step(cur, p):
            nxt, found = self.find_next(cur, w, jnp.full_like(w, p),
                                        backend=backend)
            nxt = jnp.where(found, nxt, cur)
            return nxt, cur

        out, path = jax.lax.scan(step, cur, jnp.arange(upto, dtype=U32))
        return jnp.moveaxis(jnp.concatenate([path, out[None]], axis=0), 0, 1)

    # ---------------------------------------------------------- segment reads

    def pending_walks_of(self, vertices, capacity: int):
        """Walk ids with a LIVE pending triplet owned by each vertex.

        int32 [B, capacity], -1 padded — the pending-side complement of the
        base walks_of segment read (serve/walk_queries.py combines the two).
        """
        vertices = jnp.asarray(vertices, U32)
        lo = jnp.searchsorted(self.okey, vertices, side="left")
        hi = jnp.searchsorted(self.okey, vertices + jnp.asarray(1, U32),
                              side="left")
        idx = lo[:, None] + jnp.arange(capacity, dtype=I32)[None]
        in_seg = idx < hi[:, None]
        pc = jnp.clip(idx, 0, self.n_pending_entries - 1)
        slot = self.oslot[pc]
        live = self.oepoch[pc] == self.base.slot_epoch[
            jnp.clip(slot, 0, self.base.n_walks * self.base.length - 1)]
        w = slot // self.base.length
        return jnp.where(in_seg & live, w, -1)


@jax.jit
def _build_jit(store: WalkStore, owner, code, epoch, slot) -> Overlay:
    slot64 = jnp.clip(slot, 0, store.n_walks * store.length - 1).astype(U64)
    skey = (slot64 << _SHIFT) | epoch.astype(U64)
    order = jnp.argsort(skey)
    # a 32-bit key: each 64-bit sort key costs the TPU compile minutes
    okey = jnp.where(epoch == PAD_EPOCH, jnp.asarray(0xFFFFFFFF, U32),
                     owner.astype(U32))
    oorder = jnp.argsort(okey)
    return Overlay(base=store,
                   skey=skey[order], scode=code[order], sowner=owner[order],
                   okey=okey[oorder], ocode=code[oorder],
                   oepoch=epoch[oorder], oslot=slot[oorder])
