"""Dry-run machinery tests: the trip-count-aware HLO walker (the roofline's
foundation) and the cell registry/plan builders on a tiny mesh."""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_analysis import analyze, parse_module


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_xla_cost_analysis_counts_loops_once():
    """Documents the XLA behaviour the custom walker corrects."""
    def scanned(x, w):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    s = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c = _compile(scanned, s, s)
    xla_flops = c.cost_analysis()["flops"]
    assert xla_flops < 2 * 2 * 128 ** 3  # body counted ~once


def test_walker_scales_by_trip_count():
    def scanned(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    s = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    tot = analyze(_compile(scanned, s, s).as_text())
    assert tot.flops == pytest.approx(10 * 2 * 128 ** 3, rel=1e-6)


def test_walker_nested_loops():
    def nested(x, w):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ w, None
            c2, _ = jax.lax.scan(inner, c, None, length=4)
            return c2, None
        out, _ = jax.lax.scan(outer, x, None, length=3)
        return out

    s = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    tot = analyze(_compile(nested, s, s).as_text())
    assert tot.flops == pytest.approx(12 * 2 * 64 ** 3, rel=1e-6)


def test_walker_parses_tuple_shapes_with_comments():
    """Regression: tuple shapes with /*index=N*/ comments broke regex parse."""
    def multi(x, w):
        def body(carry, _):
            a, b, c, d, e, f = carry
            return (jnp.tanh(a @ w), b + 1.0, c, d, e, f), None
        init = (x,) + tuple(jnp.zeros((64, 64)) for _ in range(5))
        out, _ = jax.lax.scan(body, init, None, length=7)
        return out[0]

    s = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    txt = _compile(multi, s, s).as_text()
    comps, entry = parse_module(txt)
    assert entry is not None
    tot = analyze(txt)
    assert tot.flops == pytest.approx(7 * 2 * 64 ** 3, rel=1e-6)


def test_cell_registry_builds_smoke_plans():
    """build_cell produces consistent plans for every family on a 1x1 mesh."""
    from repro.launch.steps import build_cell
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    for arch, shape in [("gat-cora", "molecule"),
                        ("dlrm-rm2", "serve_p99"),
                        ("graphsage-reddit", "full_graph_sm")]:
        plan = build_cell(arch, shape, mesh, smoke=True)
        assert plan.fn is not None and len(plan.args) >= 2


def test_compile_cache_dir(monkeypatch):
    """Entry points keep the persistent cache in $JAX_COMPILATION_CACHE_DIR
    when set (nothing set in code), else at the fixed <repo>/.jax_cache."""
    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.CHECKOUT / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]


def test_raise_compiler_stack(monkeypatch):
    """Entry points give the TPU compiler's fibers a larger stack through
    LIBTPU_INIT_ARGS, keeping the flags already there and never overriding
    a stack size the caller set."""
    from repro.launch import compile_cache
    flag = compile_cache.COMPILER_STACK_FLAG
    monkeypatch.delenv("LIBTPU_INIT_ARGS", raising=False)
    assert compile_cache.raise_compiler_stack(1 << 26) == f"{flag}=67108864"
    monkeypatch.setenv("LIBTPU_INIT_ARGS", "--xla_tpu_foo=1")
    assert compile_cache.raise_compiler_stack(1 << 26) == (
        f"--xla_tpu_foo=1 {flag}=67108864")
    assert compile_cache.raise_compiler_stack(1 << 20) == (
        f"--xla_tpu_foo=1 {flag}=67108864")
    assert os.environ["LIBTPU_INIT_ARGS"].count(flag) == 1
