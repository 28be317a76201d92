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
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke as smoke
import repro.core  # noqa: F401  (x64 on, as the engine runs)
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
    compile_for(range_search.find_next_packed,
                ((SMOKE_C, delta.WORDS), U32), ((SMOKE_C,), U32),
                ((SMOKE_C,), U32), ((SMOKE_C,), U32),
                ((SMOKE_Q, SMOKE_K), I32), ((SMOKE_Q,), U32))


def test_intersect_compiles_at_dmax_128(compile_for):
    compile_for(lambda *a: intersect.factorized_next_pallas(
        *a, inv_p=2.0, inv_q=0.5),
        ((ORDER2_B, 128), U32), ((ORDER2_B, 128), U32), ((ORDER2_B,), U32),
        ((ORDER2_B,), F32), ((ORDER2_B,), F32))


def test_sgns_compiles_at_maintainer_width(compile_for):
    compile_for(sgns.sgns_fused, ((SGNS_B, SGNS_D), F32),
                ((SGNS_B, SGNS_D), F32), ((SGNS_B, SGNS_K, SGNS_D), F32))


@pytest.mark.parametrize("kernel", ["decode", "pair", "unpair"])
def test_standalone_kernels_compile(compile_for, kernel):
    c = 4096
    if kernel == "decode":
        compile_for(delta.decode_chunks, ((c, delta.WORDS), U32),
                    ((c,), U32), ((c,), U32), ((c,), U32))
    else:
        fn = szudzik.pair_tiles if kernel == "pair" else szudzik.unpair_tiles
        compile_for(fn, ((c, szudzik.LANES), U32), ((c, szudzik.LANES), U32))


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
