"""PackedWalkStore — the FOR bit-packed corpus as a first-class, device-resident
subsystem (paper §4.4; DESIGN.md §3).

The seed kept two parallel representations: the uncompressed u64 code array
(which every query scanned) and a host-side numpy accounting of the packed
chunks (which nothing but the memory benchmark ever touched). This module
promotes the packed chunks to the production read path:

  * the corpus is encoded ON DEVICE (kernels/delta.py::encode_chunks via
    kernels/ops.delta_pack — pure u32 jnp, TPU-native) into
        packed      u32 [C, WORDS]   FOR bit-packed deltas (w ∈ {8,16,32,64})
        widths      u32 [C]          per-chunk width class
        anchors     (hi, lo) u32 [C] chunk head codes  (§5.2 c_first)
        last        (hi, lo) u32 [C] chunk tail codes  (§5.2 c_last)
  * FINDNEXT routes through a *backend registry*:
        "pallas"           — the Pallas packed-chunk kernel
                             (kernels/range_search.py, scalar-prefetch DMA of
                             only the candidate chunks)
        "interpret"        — the same packed-chunk math (shared kernel body
                             functions) vectorized in XLA over gathered
                             candidate chunks; the automatic CPU fallback
        "pallas-interpret" — pl.pallas_call(interpret=True); exact kernel-body
                             validation (slow: grid is trace-unrolled)
        "xla-ref"          — the legacy scalar while-loop over the
                             uncompressed codes (reference semantics)
    "auto" resolves to "pallas" on TPU and "interpret" elsewhere; an explicit
    "pallas" request off-TPU also falls back to "interpret".

Chunks are always CHUNK(=128)-wide — the VPU lane count the kernels are built
around — independent of the store's logical chunk_b metadata parameter.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pairing
from repro.kernels import ops
from repro.kernels.delta import CHUNK, decode_block, packed_nbytes
from repro.obs import trace

U64 = jnp.uint64
U32 = jnp.uint32
I32 = jnp.int32

# ------------------------------------------------------------------ registry

BACKENDS = ("pallas", "interpret", "pallas-interpret", "xla-ref")

_default_backend: Optional[str] = None   # None -> hardware auto-selection
_default_window: int = 8                 # K candidate chunks per query


def set_default_backend(name: Optional[str]) -> None:
    """Set the process-wide FINDNEXT backend ("auto"/None = hardware pick).

    Resolution happens at trace time: already-compiled jitted callers keep
    the backend they were traced with until their cache is invalidated.
    """
    global _default_backend
    if name in (None, "auto"):
        _default_backend = None
        return
    if name not in BACKENDS:
        raise ValueError(f"unknown find_next backend {name!r}; "
                         f"expected one of {BACKENDS + ('auto',)}")
    _default_backend = name


def get_default_backend() -> str:
    return resolve_backend(None)


def set_default_window(k: int) -> None:
    global _default_window
    if k < 1:
        raise ValueError("find_next window must be >= 1 chunk")
    _default_window = int(k)


def get_default_window() -> int:
    return _default_window


def resolve_backend(name: Optional[str]) -> str:
    """Resolve a backend request to a concrete backend for this process.

    None/"auto" -> "pallas" on TPU, "interpret" otherwise; "pallas" off-TPU
    falls back to "interpret" (the kernel math run in XLA) so CPU runs never
    hit an unlowerable Mosaic call.
    """
    name = _default_backend if name in (None, "auto") else name
    on_tpu = jax.default_backend() == "tpu"
    if name is None:
        return "pallas" if on_tpu else "interpret"
    if name not in BACKENDS:
        raise ValueError(f"unknown find_next backend {name!r}; "
                         f"expected one of {BACKENDS + ('auto',)}")
    if name == "pallas" and not on_tpu:
        return "interpret"
    return name


# ------------------------------------------------------------------- encode


def pad_chunk_codes(code) -> jax.Array:
    """u64 [T] sorted codes -> u64 [C, CHUNK] chunk grid (tail-padded with the
    last code so padding stays monotone and never widens the width class)."""
    t = code.shape[0]
    c = max(1, -(-t // CHUNK))
    pad = c * CHUNK - t
    if pad:
        filler = code[-1] if t else jnp.asarray(0, U64)
        code = jnp.concatenate([code, jnp.full((pad,), filler, U64)])
    return code.reshape(c, CHUNK)


def encode_codes(code):
    """u64 [T] sorted codes -> (packed, widths, a_hi, a_lo, l_hi, l_lo).

    On-device FOR bit-packing (kernels/delta.py::encode_chunks); anchors are
    the chunk head codes (= the paper's §5.2 c_first metadata), last the
    chunk tails (c_last).
    """
    chunks = pad_chunk_codes(code)
    hi, lo = pairing.split_u64(chunks)
    packed, widths, a_hi, a_lo = ops.delta_pack(hi, lo)
    return packed, widths, a_hi, a_lo, hi[:, -1], lo[:, -1]


# ------------------------------------------------------------------- decode


def decode_rows(rows, widths, a_hi, a_lo) -> Tuple[jax.Array, jax.Array]:
    """Decode gathered packed rows with the shared kernel decode math.

    rows u32 [R, WORDS]; widths/a_hi/a_lo u32 [R, 1] -> (hi, lo) u32 [R, CHUNK].
    This is kernels/delta.py::decode_block over an XLA gather — the same
    function the Pallas kernels execute (tested in tests/test_packed_store.py).
    """
    return decode_block(rows, widths, a_hi, a_lo)


def gather_decode(packed, widths, a_hi, a_lo, chunk_idx) -> jax.Array:
    """Decode an arbitrary set of chunks: chunk_idx i32 [...,] -> u64 codes
    [..., CHUNK]. The serving layer's packed read primitive."""
    shape = chunk_idx.shape
    flat = chunk_idx.reshape(-1)
    hi, lo = decode_rows(packed[flat], widths[flat][:, None],
                         a_hi[flat][:, None], a_lo[flat][:, None])
    return pairing.join_u64(hi, lo).reshape(*shape, CHUNK)


def packed_search_xla(packed, widths, a_hi, a_lo, chunk_idx, f_targets):
    """The "interpret" FINDNEXT backend: the packed-chunk search kernel
    (kernels/range_search.py::_search_kernel) vectorized in XLA.

    chunk_idx i32 [Q, K] candidate chunks per query; f_targets u64 [Q].
    Returns (v_next u32 [Q], found bool [Q]) with the kernel's accumulation
    semantics (first hitting chunk wins; max matching v within that chunk).
    Unpairing uses the exact u64 oracle (pairing.szudzik_unpair) rather than
    the kernel's 32-round u32 bit-restoration isqrt — both are exact, the
    former is ~40x cheaper under XLA on CPU.
    """
    q, k = chunk_idx.shape
    codes = gather_decode(packed, widths, a_hi, a_lo, chunk_idx)  # [Q,K,CHUNK]
    f, v = pairing.szudzik_unpair(codes.reshape(-1))
    f = f.reshape(q, k, CHUNK)
    v = v.reshape(q, k, CHUNK)
    hit = f == jnp.asarray(f_targets, U64)[:, None, None]
    chunk_hit = jnp.any(hit, axis=-1)                       # [Q, K]
    found = jnp.any(chunk_hit, axis=-1)
    first_k = jnp.argmax(chunk_hit, axis=-1)                # first hit chunk
    sel_hit = jnp.take_along_axis(hit, first_k[:, None, None], 1)[:, 0]
    sel_v = jnp.take_along_axis(v, first_k[:, None, None], 1)[:, 0]
    val = jnp.max(jnp.where(sel_hit, sel_v, jnp.zeros_like(sel_v)), axis=-1)
    return val.astype(U32), found


def packed_search(packed, widths, a_hi, a_lo, chunk_idx, f_targets,
                  backend: str):
    """Dispatch a packed-chunk FINDNEXT to the resolved backend."""
    with trace.scope(trace.FINDNEXT):
        if backend == "pallas" or backend == "pallas-interpret":
            return ops.find_next_packed(
                packed, widths, a_hi, a_lo, chunk_idx,
                jnp.asarray(f_targets, U32),
                interpret=(backend == "pallas-interpret"))
        if backend == "interpret":
            return packed_search_xla(packed, widths, a_hi, a_lo, chunk_idx,
                                     f_targets)
    raise ValueError(f"packed_search cannot serve backend {backend!r}")


# output-sensitive candidate cap for the "interpret" backend: queries with
# more than this many codes in [lb, ub] fall back to the reference scan
MAX_CANDIDATES = 16


def packed_candidates(packed, widths, a_hi, a_lo, chunk_idx, lo,
                      w: int = MAX_CANDIDATES):
    """Decode candidate windows and return the `w` codes at absolute corpus
    positions lo, lo+1, ... per query (the §5.3 output-sensitive candidates).

    chunk_idx i32 [Q, K] must cover positions [lo, lo + w) (the caller's
    window-overflow fallback handles the rest). Returns u64 [Q, w].
    Decode is the cheap part (branch-free bit ops); callers unpair only
    these w candidates instead of every lane of every chunk.
    """
    q, k = chunk_idx.shape
    codes = gather_decode(packed, widths, a_hi, a_lo,
                          chunk_idx).reshape(q, k * CHUNK)
    rel = (lo - chunk_idx[:, 0] * CHUNK)[:, None] \
        + jnp.arange(w, dtype=I32)[None]
    rel = jnp.clip(rel, 0, k * CHUNK - 1)
    return jnp.take_along_axis(codes, rel, axis=1)


# ---------------------------------------------------------------- dataclass


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class PackedWalkStore:
    """Standalone packed view of a walk corpus: everything a serving replica
    needs to answer FINDNEXT / segment reads without the uncompressed codes.

    Arrays are shared (by reference) with the owning WalkStore — JAX arrays
    are immutable, so this view is also a free consistent snapshot (DESIGN.md
    §2). Valid on CONSOLIDATED corpora: every entry live, each slot f stored
    exactly once (the merge paths guarantee this; WalkStore.find_next adds
    the slot-epoch verification for mid-update reads).
    """

    packed: jax.Array       # u32 [C, WORDS] FOR bit-packed chunks
    widths: jax.Array       # u32 [C] width class per chunk
    anchors_hi: jax.Array   # u32 [C] chunk head code (c_first, §5.2)
    anchors_lo: jax.Array
    last_hi: jax.Array      # u32 [C] chunk tail code (c_last, §5.2)
    last_lo: jax.Array
    offsets: jax.Array      # i32 [n+1] per-vertex segment bounds
    vmin: jax.Array         # u32 [n] per-vertex search bounds (§5.1)
    vmax: jax.Array
    length: int = dataclasses.field(metadata=dict(static=True))
    n_vertices: int = dataclasses.field(metadata=dict(static=True))

    @property
    def n_chunks(self) -> int:
        return self.packed.shape[0]

    def decode(self) -> jax.Array:
        """Full u64 code grid [C * CHUNK] (verification / bulk export)."""
        idx = jnp.arange(self.n_chunks, dtype=I32)
        return gather_decode(self.packed, self.widths, self.anchors_hi,
                             self.anchors_lo, idx).reshape(-1)

    def search(self, chunk_idx, f_targets, backend: Optional[str] = None):
        """Raw packed FINDNEXT over explicit candidate windows."""
        backend = resolve_backend(backend)
        if backend == "xla-ref":  # no uncompressed codes in this view
            backend = "interpret"
        return packed_search(self.packed, self.widths, self.anchors_hi,
                             self.anchors_lo, chunk_idx, f_targets,
                             backend)

    # ------------------------------------------------------------- memory

    def nbytes(self) -> int:
        """Deployed compressed footprint: words actually used at each chunk's
        width class (kernels/delta.py::packed_nbytes — the representation the
        kernels consume) + the serving metadata."""
        meta = int(self.offsets.nbytes + self.vmin.nbytes + self.vmax.nbytes
                   + self.last_hi.nbytes + self.last_lo.nbytes)
        return packed_nbytes(np.asarray(self.widths)) + meta

    def nbytes_capacity(self) -> int:
        """Device-resident buffer bytes (the [C, WORDS] worst-case capacity
        actually allocated; WORDS covers the w=64 raw fallback)."""
        return int(self.packed.nbytes + self.widths.nbytes
                   + self.anchors_hi.nbytes + self.anchors_lo.nbytes
                   + self.last_hi.nbytes + self.last_lo.nbytes
                   + self.offsets.nbytes + self.vmin.nbytes
                   + self.vmax.nbytes)
