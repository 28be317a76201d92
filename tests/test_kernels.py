"""Per-kernel interpret=True validation vs ref.py oracles: shape/dtype sweeps
+ hypothesis property tests (exactness for integer kernels, allclose for f32)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro.core  # noqa: F401  (x64 for the oracles)
from repro.core import pairing
from repro.kernels import intersect, ops
from repro.kernels import ref
from repro.kernels.delta import CHUNK, encode_chunks, packed_nbytes

U32 = jnp.uint32


# ----------------------------------------------------------------- szudzik


@pytest.mark.parametrize("n", [1, 7, 128, 1024, 1000, 4096 + 3])
def test_szudzik_kernel_shapes(n):
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.integers(0, 2**32, size=n, dtype=np.uint32))
    y = jnp.asarray(rng.integers(0, 2**32, size=n, dtype=np.uint32))
    hi, lo = ops.szudzik_pair(x, y, interpret=True)
    rhi, rlo = ref.szudzik_pair_ref(x, y)
    np.testing.assert_array_equal(np.asarray(hi), np.asarray(rhi))
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(rlo))
    x2, y2 = ops.szudzik_unpair(hi, lo, interpret=True)
    np.testing.assert_array_equal(np.asarray(x2), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(y2), np.asarray(y))


@given(st.lists(st.tuples(st.integers(0, 2**32 - 1),
                          st.integers(0, 2**32 - 1)),
                min_size=1, max_size=64))
@settings(max_examples=50, deadline=None)
def test_szudzik_kernel_property(pairs):
    x = jnp.asarray([p[0] for p in pairs], U32)
    y = jnp.asarray([p[1] for p in pairs], U32)
    hi, lo = ops.szudzik_pair(x, y, interpret=True)
    z = pairing.join_u64(hi, lo)
    expected = pairing.szudzik_pair(x.astype(jnp.uint64),
                                    y.astype(jnp.uint64))
    np.testing.assert_array_equal(np.asarray(z), np.asarray(expected))


def test_szudzik_kernel_edges():
    vals = [0, 1, 2, 2**16 - 1, 2**16, 2**31, 2**32 - 2, 2**32 - 1]
    x, y = np.meshgrid(vals, vals)
    x = jnp.asarray(x.reshape(-1), U32)
    y = jnp.asarray(y.reshape(-1), U32)
    hi, lo = ops.szudzik_pair(x, y, interpret=True)
    x2, y2 = ops.szudzik_unpair(hi, lo, interpret=True)
    np.testing.assert_array_equal(np.asarray(x2), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(y2), np.asarray(y))


# ------------------------------------------------------------- delta codec


def _chunked_codes(rng, n_chunks, scale):
    base = rng.integers(0, 2**60, size=(n_chunks, 1)).astype(np.uint64)
    deltas = rng.integers(0, scale, size=(n_chunks, CHUNK)).astype(np.uint64)
    return base + np.cumsum(deltas, axis=1)


@pytest.mark.parametrize("n_chunks,scale", [
    (8, 100), (16, 60000), (8, 2**20), (8, 2**34), (1, 10), (9, 100)])
def test_delta_roundtrip(n_chunks, scale):
    rng = np.random.default_rng(int(scale) % 1000)
    codes = _chunked_codes(rng, n_chunks, scale)
    hi, lo = pairing.split_u64(jnp.asarray(codes))
    packed, widths, ahi, alo = ops.delta_pack(hi, lo)
    ohi, olo = ops.delta_unpack(packed, widths, ahi, alo, interpret=True)
    out = np.asarray(pairing.join_u64(ohi, olo))
    np.testing.assert_array_equal(out, codes)


def test_delta_nonmonotone_chunk_uses_raw():
    rng = np.random.default_rng(0)
    codes = np.sort(rng.integers(0, 2**63, size=(4, CHUNK)).astype(np.uint64))
    codes[2] = codes[2][::-1]  # break monotonicity
    hi, lo = pairing.split_u64(jnp.asarray(codes))
    packed, widths, ahi, alo = ops.delta_pack(hi, lo)
    assert int(np.asarray(widths)[2]) == 64
    ohi, olo = ops.delta_unpack(packed, widths, ahi, alo, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(pairing.join_u64(ohi, olo)), codes)


def test_delta_compression_wins_on_clustered_ids():
    """Paper §7.5: difference encoding compresses clustered codes well."""
    rng = np.random.default_rng(1)
    codes = _chunked_codes(rng, 64, 200)
    hi, lo = pairing.split_u64(jnp.asarray(codes))
    _, widths, _, _ = ops.delta_pack(hi, lo)
    assert packed_nbytes(widths) < codes.nbytes / 3


# ------------------------------------------------------------ range search


def _unpair_py(z: int):
    """Szudzik inverse in Python integers (independent of the kernels)."""
    s = math.isqrt(z)
    return (z - s * s, s) if z - s * s < s else (s, z - s * s - s)


def _scan_range_py(codes, lo, hi, f):
    """The scalar reference over [lo, hi): (v of the code with first
    component f, found); the fixtures hold each f once."""
    for i in range(lo, hi):
        fi, vi = _unpair_py(int(codes[i]))
        if fi == f:
            return vi, True
    return 0, False


@pytest.mark.parametrize("window", ["heads", "clamped"])
@pytest.mark.parametrize("n_codes,n_queries,k", [(1024, 32, 4), (4096, 64, 6)])
def test_range_search_kernel(n_codes, n_queries, k, window):
    # f > v throughout: mirrors real per-vertex segments where the candidate
    # window is bounded by the segment size (K chunks). Codes with v > f land
    # near v^2 — the paper's output-sensitive k-term; the wrapper searches
    # within vertex segments so the kernel never needs an unbounded window.
    rng = np.random.default_rng(n_codes)
    f = np.unique(rng.integers(2**21, 2**22,
                               size=2 * n_codes).astype(np.uint64))
    f = f[:n_codes]
    v = rng.integers(0, 2**20, size=n_codes).astype(np.uint64)
    codes = np.sort(np.asarray(pairing.szudzik_pair(jnp.asarray(f),
                                                    jnp.asarray(v))))
    chunks = codes.reshape(-1, CHUNK)
    hi, lo = pairing.split_u64(jnp.asarray(chunks))
    packed, widths, ahi, alo = ops.delta_pack(hi, lo)
    sel = rng.choice(n_codes, size=n_queries, replace=False)
    fq, vq = pairing.szudzik_unpair(jnp.asarray(codes[sel]))
    if window == "heads":
        lbh, lbl = pairing.split_u64(
            pairing.szudzik_pair(fq, jnp.zeros_like(fq)))
        cfh, cfl = pairing.split_u64(jnp.asarray(chunks[:, 0]))
        cidx = ops.candidate_chunks(cfh, cfl, lbh, lbl, k=k)
        v_out, found = ops.find_next_packed(packed, widths, ahi, alo, cidx,
                                            fq.astype(U32), interpret=True)
        assert bool(found.all())
        np.testing.assert_array_equal(np.asarray(v_out),
                                      np.asarray(vq).astype(np.uint32))
        return

    # the store's clamped windows over explicit candidate ranges [lo, hi):
    # each selected code's own range (inside one chunk); a range over the
    # two codes either side of a chunk boundary (a block boundary when the
    # store has a second 8-chunk block), hit in either chunk; and misses
    # (hi == lo) for an absent f
    fq = [int(x) for x in np.asarray(fq)]
    pos = np.searchsorted(codes, codes[sel])
    ranges = [(int(i), int(i) + 1) for i in pos]
    edge = 8 * CHUNK if n_codes > 8 * CHUNK else CHUNK
    for i in (edge - 1, edge):
        fq.append(_unpair_py(int(codes[i]))[0])
        ranges.append((edge - 1, edge + 1))
    absent = int(f.max()) + 1
    lb = int(pairing.szudzik_pair(jnp.asarray(absent, jnp.uint64),
                                  jnp.asarray(0, jnp.uint64)))
    gap = int(np.searchsorted(codes, lb))
    fq += [absent, absent]
    ranges += [(gap, gap), (n_codes // 2, n_codes // 2)]
    lo_r = np.asarray([r[0] for r in ranges])
    hi_r = np.asarray([r[1] for r in ranges])
    c0 = lo_r // CHUNK
    c1 = np.maximum(hi_r - 1, lo_r) // CHUNK
    n_chunks = chunks.shape[0]
    steps = c0[:, None] + np.arange(k)[None]
    clamped = np.clip(np.minimum(steps, c1[:, None]), 0, n_chunks - 1)
    fixed = np.clip(steps, 0, n_chunks - 1)
    assert (clamped[-4:-2] != clamped[-4:-2, :1]).any(axis=1).all()
    assert edge == CHUNK or (c0[-4] // 8 != c1[-4] // 8)

    def search(cidx):
        out, hit = ops.find_next_packed(
            packed, widths, ahi, alo, jnp.asarray(cidx, jnp.int32),
            jnp.asarray(fq, U32), interpret=True)
        return np.asarray(out), np.asarray(hit)

    v_out, found = search(clamped)
    want = [_scan_range_py(codes, a, b, fx)
            for a, b, fx in zip(lo_r, hi_r, fq)]
    np.testing.assert_array_equal(found, [w[1] for w in want])
    np.testing.assert_array_equal(v_out[found],
                                  [w[0] for w in want if w[1]])
    assert found[:-2].all() and not found[-2:].any()
    v_fix, found_fix = search(fixed)
    np.testing.assert_array_equal(v_fix[found], v_out[found])
    assert found_fix[found].all()


def test_range_search_miss():
    """Queries for absent keys must report found=False."""
    rng = np.random.default_rng(7)
    f = (np.unique(rng.integers(0, 2**22, size=2048)) * 2)[:1024]  # even f
    v = rng.integers(0, 2**20, size=1024)
    codes = np.sort(np.asarray(pairing.szudzik_pair(
        jnp.asarray(f, jnp.uint64), jnp.asarray(v, jnp.uint64))))
    chunks = codes.reshape(-1, CHUNK)
    hi, lo = pairing.split_u64(jnp.asarray(chunks))
    packed, widths, ahi, alo = ops.delta_pack(hi, lo)
    fq = jnp.asarray(f[:16] + 1, jnp.uint64)  # odd f: absent
    lbh, lbl = pairing.split_u64(pairing.szudzik_pair(fq, jnp.zeros_like(fq)))
    cfh, cfl = pairing.split_u64(jnp.asarray(chunks[:, 0]))
    cidx = ops.candidate_chunks(cfh, cfl, lbh, lbl, k=4)
    _, found = ops.find_next_packed(packed, widths, ahi, alo, cidx,
                                    fq.astype(U32), interpret=True)
    assert not bool(found.any())


# --------------------------------------------------- intersect (factorized)


def _intersect_case(rng, b, d, n_vertices=None, p=0.5, q=2.0):
    """Random sentinel-padded neighbor windows + prev + uniforms."""
    # universe a small multiple of the window so intersections are common
    # but degrees (up to d) always fit without replacement
    n_vertices = 2 * d if n_vertices is None else n_vertices
    sent = np.uint32(0xFFFFFFFF)
    nbrs_v = np.full((b, d), sent, np.uint32)
    nbrs_p = np.full((b, d), sent, np.uint32)
    deg_v = rng.integers(0, d + 1, size=b)
    deg_p = rng.integers(0, d + 1, size=b)
    prev = np.zeros(b, np.uint32)
    for i in range(b):
        nv = np.sort(rng.choice(n_vertices, size=deg_v[i], replace=False))
        npr = np.sort(rng.choice(n_vertices, size=deg_p[i], replace=False))
        nbrs_v[i, : deg_v[i]] = nv
        nbrs_p[i, : deg_p[i]] = npr
        # prev is a neighbor of v when possible (the walk-context shape)
        prev[i] = nv[rng.integers(deg_v[i])] if deg_v[i] else \
            rng.integers(n_vertices)
    u = rng.random((b, 2)).astype(np.float32)
    return (jnp.asarray(nbrs_v), jnp.asarray(nbrs_p), jnp.asarray(prev),
            jnp.asarray(u[:, 0]), jnp.asarray(u[:, 1]), p, q)


def _intersect_numpy_oracle(nbrs_v, nbrs_p, prev, u_g, u_r, p, q):
    """Per-row python/numpy replay of the group factorization (f32 mass
    arithmetic in the backends' fixed order)."""
    sent = np.uint32(0xFFFFFFFF)
    inv_p, inv_q = np.float32(1.0 / p), np.float32(1.0 / q)
    out_nxt, out_found = [], []
    for i in range(nbrs_v.shape[0]):
        row = [x for x in np.asarray(nbrs_v[i]) if x != sent]
        pset = {int(x) for x in np.asarray(nbrs_p[i]) if x != sent}
        pv = int(np.asarray(prev[i]))
        g0 = [x for x in row if int(x) == pv]
        g1 = [x for x in row if int(x) != pv and int(x) in pset]
        g2 = [x for x in row if int(x) != pv and int(x) not in pset]
        m0 = np.float32(len(g0)) * inv_p
        m1 = np.float32(len(g1))
        m2 = np.float32(len(g2)) * inv_q
        if not row:
            out_nxt.append(0)
            out_found.append(False)
            continue
        t = np.float32(np.asarray(u_g[i])) * np.float32(m0 + m1 + m2)
        grp = int(t >= m0) + int(t >= np.float32(m0 + m1))
        last = 2 if g2 else (1 if g1 else 0)
        grp = min(grp, last)
        members = (g0, g1, g2)[grp]
        r = min(int(np.float32(np.asarray(u_r[i]))
                    * np.float32(len(members))), len(members) - 1)
        out_nxt.append(int(members[r]))
        out_found.append(True)
    return np.asarray(out_nxt, np.uint32), np.asarray(out_found)


@pytest.mark.parametrize("b,d", [(16, 128), (8, 256), (24, 128)])
def test_intersect_backends_bit_agree_and_match_oracle(b, d):
    """interpret / pallas-interpret / xla-ref produce BIT-identical
    selections, all equal to a per-row python/numpy replay."""
    rng = np.random.default_rng(b * d)
    case = _intersect_case(rng, b, d)
    ref_nxt, ref_found = _intersect_numpy_oracle(*case)
    for backend in ("interpret", "pallas-interpret", "xla-ref"):
        nxt, found = intersect.factorized_next(*case, backend=backend)
        np.testing.assert_array_equal(np.asarray(nxt) * np.asarray(found),
                                      ref_nxt * ref_found, err_msg=backend)
        np.testing.assert_array_equal(np.asarray(found), ref_found,
                                      err_msg=backend)


def test_intersect_ops_wrapper_pads_off_tile_shapes():
    """ops.intersect_next pads rows to the 8-row tile and lanes to 128 and
    still bit-agrees with the unpadded interpret backend."""
    rng = np.random.default_rng(5)
    case = _intersect_case(rng, 13, 48)
    ref_nxt, ref_found = intersect.factorized_next(*case,
                                                   backend="interpret")
    nxt, found = ops.intersect_next(*case, interpret=True)
    np.testing.assert_array_equal(np.asarray(nxt) * np.asarray(found),
                                  np.asarray(ref_nxt) * np.asarray(ref_found))
    np.testing.assert_array_equal(np.asarray(found), np.asarray(ref_found))


def test_intersect_explicit_kernel_backend_raises_off_tile():
    """An explicit kernel-backend request must never silently validate the
    fallback (the SGNS registry contract)."""
    rng = np.random.default_rng(9)
    case = _intersect_case(rng, 12, 100)
    with pytest.raises(ValueError, match="requires B %"):
        intersect.factorized_next(*case, backend="pallas-interpret")
    # auto falls back to interpret on the same shapes
    nxt, _ = intersect.factorized_next(*case, backend="auto")
    assert nxt.shape == (12,)


def test_intersect_member_sorted_equals_allpairs():
    """The interpret backend's binary-search membership == the kernel's
    all-pairs membership on valid lanes."""
    rng = np.random.default_rng(3)
    nbrs_v, nbrs_p, *_ = _intersect_case(rng, 32, 64)
    valid = np.asarray(nbrs_v) != np.uint32(0xFFFFFFFF)
    a = np.asarray(intersect.member_sorted(nbrs_v, nbrs_p))
    b = np.asarray(intersect.member_allpairs(nbrs_v, nbrs_p))
    np.testing.assert_array_equal(a & valid, b & valid)


# -------------------------------------------------------------------- sgns


@pytest.mark.parametrize("b,k,d", [(8, 5, 128), (32, 5, 128), (16, 10, 256),
                                   (13, 3, 100)])
def test_sgns_kernel(b, k, d):
    rng = np.random.default_rng(b * d)
    u = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(b, k, d)), jnp.float32)
    loss, du, dvp, dvn = ops.sgns_step(u, vp, vn, interpret=True)
    rl, rdu, rdvp, rdvn = ref.sgns_ref(u, vp, vn)
    np.testing.assert_allclose(float(loss.sum()), float(rl), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(du), np.asarray(rdu), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(dvp), np.asarray(rdvp), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(dvn), np.asarray(rdvn), rtol=1e-4,
                               atol=1e-5)
