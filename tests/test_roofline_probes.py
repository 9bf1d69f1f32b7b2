"""The trip-count-aware HLO cost walker vs analytic flops on loop probes —
the §Roofline methodology's validation (EXPERIMENTS.md)."""
import jax
import jax.numpy as jnp
import pytest

from repro.sharding.hlo_cost import analyze

D = 128
UNIT = 2 * D**3  # one (D,D)@(D,D) matmul


def _flops(fn, *args):
    comp = jax.jit(fn).lower(*args).compile()
    return analyze(comp.as_text())["flops"], comp.cost_analysis()["flops"]


def _xw():
    return (jax.ShapeDtypeStruct((D, D), jnp.float32),
            jax.ShapeDtypeStruct((D, D), jnp.float32))


def _scan_fn(length):
    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=length)
        return y.sum()
    return f


def test_scan_trips_counted():
    got, xla_raw = _flops(_scan_fn(7), *_xw())
    assert got == pytest.approx(7 * UNIT, rel=1e-2)
    # and the documented XLA undercount really exists (body counted once)
    assert xla_raw == pytest.approx(UNIT, rel=1e-2)


def test_grad_of_scan():
    f = _scan_fn(7)

    def g(x, w):
        return jax.grad(lambda ww: f(x, ww))(w).sum()

    got, _ = _flops(g, *_xw())
    # fwd (1 dot) + bwd (2 dots) per iteration
    assert got == pytest.approx(21 * UNIT, rel=1e-2)


def test_nested_scans_multiply():
    def h(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            y, _ = jax.lax.scan(inner, c, None, length=5)
            return y, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y.sum()

    got, _ = _flops(h, *_xw())
    assert got == pytest.approx(15 * UNIT, rel=1e-2)


def test_vmap_counts_real_dims():
    f = _scan_fn(7)

    def v(x, w):
        xx = jnp.stack([x, x, x])
        return jax.vmap(lambda xi: f(xi, w))(xx).sum()

    got, _ = _flops(v, *_xw())
    assert got == pytest.approx(21 * UNIT, rel=1e-2)


def test_bytes_scale_with_trips():
    a5, _ = _flops(_scan_fn(5), *_xw())
    r5 = analyze(jax.jit(_scan_fn(5)).lower(*_xw()).compile().as_text())
    r10 = analyze(jax.jit(_scan_fn(10)).lower(*_xw()).compile().as_text())
    assert r10["bytes"] > 1.5 * r5["bytes"]
