"""Compile rehearsal on a described (not attached) TPU v5e chip.

The main-path Pallas kernels are compiled by the TPU compiler for one chip
of a described v5e:2x2 host, at the sizes chip_smoke.py runs them, with x64
on as the engine runs: interpret mode on the CPU accepts kernels that Mosaic
refuses (unaligned blocks, 64-bit scalars, unsupported reductions), and this
file is where such a regression fails without a chip. Each test asserts the
kernel is in the compiled program (`tpu_custom_call`).

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file. Code that asks `jax.default_backend()` sees the CPU here; tests
that need the TPU branch of a registry steer it with monkeypatch.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke as smoke
import repro.core  # noqa: F401  (x64 on, as the engine runs)
from repro.core.store import WalkStore
from repro.kernels import (delta, intersect, megakernel, range_search, sgns,
                           szudzik)

U32, I32, F32 = jnp.uint32, jnp.int32, jnp.float32

# chip_smoke.py sizes: the main phase's walk-matrix read traverses every
# walk (Q = n_walks) with the default K = 8 candidate chunks; order 2 with
# dmax = 128 (B = every walk); the maintainer's 64-dim table padded to 128
# lanes, 2^16 pairs per step, 4 negatives
SMOKE_Q = (1 << smoke.LOG2_N) * smoke.N_WALKS_PER_VERTEX
SMOKE_T = SMOKE_Q * smoke.LENGTH
SMOKE_C = SMOKE_T // delta.CHUNK
SMOKE_K = 8
ORDER2_B = (1 << smoke.SMALL_LOG2_N) * smoke.N_WALKS_PER_VERTEX
SGNS_B, SGNS_K, SGNS_D = 1 << 16, 4, 128
# the update cells' order-2 traversal: node2vec-er's store (2^10 vertices x
# 10 walks x 80 codes, 128 codes a chunk) searched in slabs of 256 queries
CELL_C = (1 << 10) * 10 * 80 // delta.CHUNK
CELL_SLAB = range_search.SLAB


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described chip's executables can be written to the persistent cache
    but never read back; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_for(one_chip, no_persistent_cache):
    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text
        return text
    return run


def test_range_search_compiles_at_smoke_size(compile_for):
    text = compile_for(range_search.find_next_packed,
                       ((SMOKE_C, delta.WORDS), U32), ((SMOKE_C,), U32),
                       ((SMOKE_C,), U32), ((SMOKE_C,), U32),
                       ((SMOKE_Q, SMOKE_K), I32), ((SMOKE_Q,), U32))
    assert "wharf_findnext" in text


def test_named_findnext_kernel_is_found_by_the_benchmark(compile_for):
    """The kernel named wharf_findnext keeps the signature the benchmark's
    roofline.findnext_queries tells FINDNEXT apart by: at the update cells'
    slab, the compiled call answers 256 queries."""
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]
                           / "benchmarks" / "chip"))
    import roofline
    text = compile_for(range_search.find_next_packed,
                       ((CELL_C, delta.WORDS), U32), ((CELL_C,), U32),
                       ((CELL_C,), U32), ((CELL_C,), U32),
                       ((CELL_SLAB, SMOKE_K), I32), ((CELL_SLAB,), U32))
    calls = [line.strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and calls[0].startswith("%wharf_findnext")
    assert roofline.findnext_queries(calls[0]) == CELL_SLAB == 256


def test_store_find_next_compiles_at_the_cell_size(compile_for, monkeypatch):
    """WalkStore.find_next on TPU in one program: each query's window
    clamped to the chunks its candidate range spans, the kernel, the
    verification and the over-window fallback, at node2vec-er's store and
    the cell's slab."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, length = 1 << 10, 80
    t = CELL_C * delta.CHUNK

    def find_next(owner, code, epoch, offsets, vmin, vmax, packed, widths,
                  a_hi, a_lo, l_hi, l_lo, slot_epoch, v, w, p):
        s = WalkStore(owner, code, epoch, offsets, vmin, vmax, packed,
                      widths, a_hi, a_lo, l_hi, l_lo, slot_epoch,
                      length=length, n_walks=t // length, n_vertices=n,
                      chunk_b=delta.CHUNK)
        return s.find_next(v, w, p, backend="pallas")

    chunk_meta = [((CELL_C,), U32)] * 5
    text = compile_for(find_next, ((t,), U32), ((t,), jnp.uint64),
                       ((t,), U32), ((n + 1,), I32), ((n,), U32), ((n,), U32),
                       ((CELL_C, delta.WORDS), U32), *chunk_meta,
                       ((t,), U32), *[((CELL_SLAB,), U32)] * 3)
    assert "wharf_findnext" in text


def test_intersect_compiles_at_dmax_128(compile_for):
    text = compile_for(lambda *a: intersect.factorized_next_pallas(
        *a, inv_p=2.0, inv_q=0.5),
        ((ORDER2_B, 128), U32), ((ORDER2_B, 128), U32), ((ORDER2_B,), U32),
        ((ORDER2_B,), F32), ((ORDER2_B,), F32))
    assert "wharf_intersect" in text


def test_sgns_compiles_at_maintainer_width(compile_for):
    text = compile_for(sgns.sgns_fused, ((SGNS_B, SGNS_D), F32),
                       ((SGNS_B, SGNS_D), F32),
                       ((SGNS_B, SGNS_K, SGNS_D), F32))
    assert "wharf_sgns" in text


@pytest.mark.parametrize("kernel", ["decode", "pair", "unpair"])
def test_standalone_kernels_compile(compile_for, kernel):
    c = 4096
    if kernel == "decode":
        text = compile_for(delta.decode_chunks, ((c, delta.WORDS), U32),
                           ((c,), U32), ((c,), U32), ((c,), U32))
        assert "wharf_delta" in text
    else:
        fn = szudzik.pair_tiles if kernel == "pair" else szudzik.unpair_tiles
        text = compile_for(fn, ((c, szudzik.LANES), U32),
                           ((c, szudzik.LANES), U32))
        assert "wharf_szudzik" in text


def test_tpu_auto_paths_pad_instead_of_falling_back(compile_for,
                                                    monkeypatch):
    """On TPU the auto-resolved SGNS and intersect paths run the kernel on
    off-tile shapes (padded), never the XLA math."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sgns.resolve_backend(None) == "pallas"
    assert intersect.resolve_backend(None) == "pallas"
    compile_for(lambda u, vp, vn: sgns.sgns_apply(u, vp, vn),
                ((10, 64), F32), ((10, 64), F32), ((10, 4, 64), F32))
    compile_for(lambda v, p, prev, ug, ur: intersect.factorized_next(
        v, p, prev, ug, ur, 0.5, 2.0),
        ((12, 100), U32), ((12, 100), U32), ((12,), U32), ((12,), F32),
        ((12,), F32))


def test_megakernel_pallas_raises_on_tpu(monkeypatch):
    """The fused kernel does not lower in Mosaic: asking for it on TPU is a
    plain error, never a quiet switch to another backend."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="does not compile"):
        megakernel.resolve_backend("pallas")
    assert megakernel.resolve_backend("interpret") == "interpret"
    assert megakernel.resolve_backend("auto") is None
