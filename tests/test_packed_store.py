"""Packed-store subsystem tests: FINDNEXT backend equivalence on random
streams (insert+delete batches, both merge policies and merge impls), the
dirty-chunk re-encode invariant after merge_interleave, kernel-math
exactness, and the unified compressed-size accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import StreamingGraph, WalkConfig, generate_corpus, pairing
from repro.core import packed_store
from repro.core.packed_store import CHUNK
from repro.core.update import WalkEngine
from repro.data.streams import rmat_edges
from repro.kernels import ops
from repro.kernels.delta import packed_nbytes

U32 = jnp.uint32


def make_engine(seed=0, n_w=2, length=8, policy="on-demand",
                merge_impl="interleave"):
    src, dst = rmat_edges(jax.random.PRNGKey(seed), 300, 6)
    g = StreamingGraph.from_edges(src, dst, 64, 4096)
    cfg = WalkConfig(n_walks_per_vertex=n_w, length=length)
    store = generate_corpus(jax.random.PRNGKey(seed + 1), g, cfg)
    return WalkEngine(graph=g, store=store, cfg=cfg, merge_policy=policy,
                      merge_impl=merge_impl, rewalk_capacity=128)


def stream(eng, n_batches=4, seed=7):
    """Random insert+delete batches."""
    key = jax.random.PRNGKey(seed)
    for i in range(n_batches):
        key, k1, k2 = jax.random.split(key, 3)
        src, dst = rmat_edges(k1, 10, 6)
        if i % 2 == 1:
            eng.delete_edges(k2, src, dst)
        else:
            eng.insert_edges(k2, src, dst)


def queries_from(eng, n=24, seed=3, miss=4):
    """(v, w, p) hit queries from the corpus + `miss` corrupted-v queries."""
    wm = np.asarray(eng.walk_matrix())
    rng = np.random.default_rng(seed)
    ws = rng.integers(0, eng.store.n_walks, size=n)
    ps = rng.integers(0, eng.store.length - 1, size=n)
    vs = wm[ws, ps].copy()
    vs[:miss] = (vs[:miss] + 1) % eng.store.n_vertices  # wrong vertex -> miss
    return (jnp.asarray(vs, U32), jnp.asarray(ws, U32), jnp.asarray(ps, U32),
            wm)


# ------------------------------------------------------- backend equivalence


@pytest.mark.parametrize("policy,merge_impl", [
    ("eager", "interleave"), ("eager", "lexsort"),
    ("on-demand", "interleave"), ("on-demand", "lexsort")])
def test_find_next_backends_equivalent(policy, merge_impl):
    """find_next_packed == find_next (ref) == find_next_simple on random
    insert+delete streams under both merge policies and both merge impls."""
    eng = make_engine(policy=policy, merge_impl=merge_impl)
    stream(eng)
    v, w, p, wm = queries_from(eng)
    s = eng.store
    ref_out, ref_found = s.find_next(v, w, p, backend="xla-ref")
    simple_out, simple_found = s.find_next_simple(v, w, p)
    np.testing.assert_array_equal(np.asarray(ref_found),
                                  np.asarray(simple_found))
    np.testing.assert_array_equal(np.asarray(ref_out), np.asarray(simple_out))
    for backend in ("interpret", "pallas-interpret"):
        out, found = s.find_next(v, w, p, backend=backend)
        np.testing.assert_array_equal(np.asarray(found),
                                      np.asarray(ref_found), backend)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(ref_out), backend)
    # hit queries resolve to the walk matrix's next vertex
    np.testing.assert_array_equal(np.asarray(ref_out)[4:],
                                  wm[np.asarray(w)[4:], np.asarray(p)[4:] + 1])
    assert not bool(np.asarray(ref_found)[:4].any())


def test_backends_equivalent_mid_update():
    """Pre-merge reads (pending blocks live, slot_epoch bumped): packed
    backends must reproduce the reference slot-epoch liveness semantics."""
    eng = make_engine(policy="on-demand")
    v, w, p, _ = queries_from(eng)   # corpus positions BEFORE the updates
    stream(eng, n_batches=2)
    assert eng.n_pending > 0         # store is mid-update
    s = eng.store
    ref_out, ref_found = s.find_next(v, w, p, backend="xla-ref")
    out, found = s.find_next(v, w, p, backend="interpret")
    np.testing.assert_array_equal(np.asarray(found), np.asarray(ref_found))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_out))


def test_xla_ref_unpair_independent_of_platform(monkeypatch):
    """The xla-ref FINDNEXT unpairs in plain u64 math on every platform: on
    TPU it shares no math with the kernels' 32-bit-limb isqrt."""
    eng = make_engine()
    v, w, p, _ = queries_from(eng)
    want = eng.store.find_next(v, w, p, backend="xla-ref")

    def limbs(z):
        raise AssertionError("xla-ref FINDNEXT took the limb unpair")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pairing, "unpair_limbs", limbs)
    got = eng.store.find_next(v, w, p, backend="xla-ref")
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_traverse_backends_equivalent():
    eng = make_engine()
    stream(eng, n_batches=2)
    eng.merge()
    s = eng.store
    w = jnp.arange(s.n_walks, dtype=U32)
    start = (w // eng.cfg.n_walks_per_vertex).astype(U32)
    a = np.asarray(s.traverse(w, start, s.length - 1, backend="interpret"))
    b = np.asarray(s.traverse(w, start, s.length - 1, backend="xla-ref"))
    np.testing.assert_array_equal(a, b)


def test_small_window_falls_back_exactly():
    """A 1-chunk kernel window forces the overflow fallback for candidate
    ranges crossing a chunk boundary — results must still match the
    reference exactly."""
    eng = make_engine(n_w=4, length=10)
    stream(eng, n_batches=2)
    v, w, p, _ = queries_from(eng, n=16)
    s = eng.store
    ref = s.find_next(v, w, p, backend="xla-ref")
    got = s.find_next(v, w, p, backend="pallas-interpret", window=1)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(ref[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(ref[1]))


def test_interpret_matches_pallas_interpret_kernel():
    """The XLA-vectorized backend math must agree with the actual Pallas
    kernel body (pl.pallas_call(interpret=True)) on identical windows."""
    eng = make_engine()
    eng.merge()
    s = eng.store
    rng = np.random.default_rng(5)
    q = 8
    f = np.asarray(
        pairing.szudzik_unpair(s.code[rng.integers(0, s.size, size=q)])[0])
    cidx = jnp.asarray(rng.integers(0, s.n_chunks, size=(q, 4)), jnp.int32)
    ker = ops.find_next_packed(s.packed, s.widths, s.anchors_hi, s.anchors_lo,
                               cidx, jnp.asarray(f, U32), interpret=True)
    xla = packed_store.packed_search_xla(s.packed, s.widths, s.anchors_hi,
                                        s.anchors_lo, cidx,
                                        jnp.asarray(f, jnp.uint64))
    np.testing.assert_array_equal(np.asarray(ker[0]), np.asarray(xla[0]))
    np.testing.assert_array_equal(np.asarray(ker[1]), np.asarray(xla[1]))


# -------------------------------------------------------- dirty-chunk merge


def test_dirty_chunk_reencode_invariant():
    """After merge_interleave, chunks whose codes the accumulator did not
    touch keep bit-identical packed rows; dirtied chunks re-encode to the
    new codes (full packed/raw agreement).

    The accumulator replaces ONE triplet in the highest non-trivial vertex
    segment — every chunk before that segment must come through untouched.
    """
    from repro.core.update import merge_interleave

    eng = make_engine()
    eng.merge()
    base = eng.store
    offs = np.asarray(base.offsets)
    vmin = np.asarray(base.vmin)
    vmax = np.asarray(base.vmax)
    # highest vertex with a segment past chunk 0 and a non-degenerate v-range
    v_sel = max(v for v in range(base.n_vertices)
                if offs[v + 1] > offs[v] and vmin[v] != vmax[v]
                and offs[v] > CHUNK)
    pos = int(offs[v_sel + 1]) - 1
    f, vn = (int(x) for x in pairing.szudzik_unpair(base.code[pos]))
    new_vn = int(vmin[v_sel]) if vn != int(vmin[v_sel]) else int(vmax[v_sel])
    new_code = pairing.szudzik_pair(jnp.uint64(f), jnp.uint64(new_vn))
    new_epoch = jnp.uint32(7)
    store = base.replace(slot_epoch=base.slot_epoch.at[f].set(new_epoch))
    after = merge_interleave(store, jnp.asarray([v_sel], U32),
                             jnp.asarray([new_code]),
                             jnp.asarray([new_epoch]),
                             jnp.asarray([f], jnp.int32))
    old_chunks = np.asarray(packed_store.pad_chunk_codes(base.code))
    new_chunks = np.asarray(packed_store.pad_chunk_codes(after.code))
    clean = (old_chunks == new_chunks).all(axis=1)
    first_seg_chunk = int(offs[v_sel]) // CHUNK
    assert clean[:first_seg_chunk].all()
    assert not clean.all(), "the replacement should have dirtied its chunk"
    np.testing.assert_array_equal(np.asarray(after.packed)[clean],
                                  np.asarray(base.packed)[clean])
    np.testing.assert_array_equal(np.asarray(after.widths)[clean],
                                  np.asarray(base.widths)[clean])
    # dirty or not, the packed representation must decode to the new corpus
    dec = np.asarray(after.packed_view().decode())[:after.size]
    np.testing.assert_array_equal(dec, np.asarray(after.code))


def test_packed_roundtrip_after_consolidate():
    eng = make_engine(policy="eager", merge_impl="lexsort")
    stream(eng, n_batches=2)
    s = eng.store
    dec = np.asarray(s.packed_view().decode())[:s.size]
    np.testing.assert_array_equal(dec, np.asarray(s.code))


# ----------------------------------------------------------- accounting/API


def test_nbytes_packed_unified_with_kernel_accounting():
    """nbytes_packed must report the kernel-quantized representation
    (kernels/delta.py::packed_nbytes) + serving metadata — no more ad-hoc
    host-side bit widths."""
    eng = make_engine()
    s = eng.store
    w = np.asarray(s.widths)
    assert set(np.unique(w)) <= {8, 16, 32, 64}
    meta = (s.offsets.nbytes + s.vmin.nbytes + s.vmax.nbytes
            + s.last_hi.nbytes + s.last_lo.nbytes)
    assert s.nbytes_packed() == packed_nbytes(w) + int(meta)
    assert s.nbytes_packed() < s.nbytes_uncompressed()
    assert s.nbytes_packed_capacity() >= s.packed.nbytes


def test_backend_registry_resolution():
    assert packed_store.resolve_backend("xla-ref") == "xla-ref"
    if jax.default_backend() != "tpu":
        assert packed_store.resolve_backend(None) == "interpret"
        assert packed_store.resolve_backend("pallas") == "interpret"
    else:
        assert packed_store.resolve_backend(None) == "pallas"
    try:
        packed_store.set_default_backend("xla-ref")
        assert packed_store.get_default_backend() == "xla-ref"
    finally:
        packed_store.set_default_backend("auto")
    with pytest.raises(ValueError):
        packed_store.resolve_backend("no-such-backend")
    with pytest.raises(ValueError):
        packed_store.set_default_backend("no-such-backend")


def test_config_selects_backend():
    from repro.configs.wharf_stream import WharfStreamConfig
    cfg = WharfStreamConfig(find_next_backend="xla-ref", find_next_window=4)
    try:
        assert cfg.select_backend() == "xla-ref"
        assert packed_store.get_default_window() == 4
    finally:
        packed_store.set_default_backend("auto")
        packed_store.set_default_window(8)


def test_packed_view_shares_device_arrays():
    eng = make_engine()
    s = eng.store
    pv = s.packed_view()
    assert pv.packed is s.packed and pv.offsets is s.offsets
    assert pv.n_chunks == -(-s.size // CHUNK)
