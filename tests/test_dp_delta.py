"""Theorem 3: the DP computes Sigma_hat^{-1}(x0 - xbar) exactly, in the
Gram form (l <= d) and the full-width form (l > d) alike."""
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.dp_delta  # noqa: F401  (module import before package alias)
from repro.testing import given, settings, strategies as st

dp = sys.modules['repro.core.dp_delta']
from repro.core import tree_math as tm
from repro.core.shrinkage import dense_delta

jax.config.update("jax_enable_x64", True)


def _xs(seed, ell, d, scale=1.0):
    r = np.random.default_rng(seed)
    return (jnp.asarray(r.normal(size=d)),
            jnp.asarray(scale * r.normal(size=(ell, d))))


@given(st.integers(1, 10), st.integers(1, 20),
       st.floats(1e-3, 50.0), st.integers(0, 10))
@settings(max_examples=25, deadline=None)
def test_dp_equals_dense(ell, d, rho, seed):
    x0, xs = _xs(seed, ell, d)
    want = np.asarray(dense_delta(x0, xs, rho))
    got = np.asarray(dp.dp_delta(x0, xs, rho))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-8)


@given(st.integers(2, 8), st.integers(2, 12), st.floats(1e-3, 10.0),
       st.integers(0, 5))
@settings(max_examples=15, deadline=None)
def test_online_equals_batch(ell, d, rho, seed):
    x0, xs = _xs(seed, ell, d)
    st_ = dp.online_dp_init(x0, ell, dtype=jnp.float64)
    for t in range(ell):
        st_ = dp.online_dp_update(st_, xs[t], rho)
    got = np.asarray(dp.online_dp_delta(st_, rho))
    want = np.asarray(dp.dp_delta(x0, xs, rho))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-8)


def test_single_sample_is_fedavg():
    """l=1 (or the burn-in stop): Delta == theta_0 - theta — Section 4's
    'FedAvg is FedPA with identity covariance' claim."""
    x0, xs = _xs(0, 1, 7)
    got = np.asarray(dp.dp_delta(x0, xs, rho=3.0))
    np.testing.assert_allclose(got, np.asarray(x0 - xs[0]), rtol=1e-12)


def test_rho_zero_is_mean_fedavg():
    """rho=0 => Sigma_hat = I for every l: delta = x0 - xbar."""
    x0, xs = _xs(1, 5, 6)
    got = np.asarray(dp.dp_delta(x0, xs, rho=0.0))
    np.testing.assert_allclose(got, np.asarray(x0 - xs.mean(axis=0)),
                               rtol=1e-9, atol=1e-10)


def test_pytree_equals_flat():
    x0, xs = _xs(2, 4, 12)
    tree0 = {"w": x0[:4].reshape(2, 2), "b": {"x": x0[4:]}}
    trees = {"w": xs[:, :4].reshape(4, 2, 2), "b": {"x": xs[:, 4:]}}
    got = dp.dp_delta(tree0, trees, 0.4)
    flat = np.concatenate([np.asarray(got["w"]).ravel(),
                           np.asarray(got["b"]["x"]).ravel()])
    want = np.asarray(dp.dp_delta(x0, xs, 0.4))
    np.testing.assert_allclose(flat, want, rtol=1e-9)


def test_anytime_property():
    """Every prefix of the online stream equals the batch DP on that prefix
    (Appendix C: 'online as well as any-time')."""
    x0, xs = _xs(3, 6, 9)
    rho = 0.8
    st_ = dp.online_dp_init(x0, 6, dtype=jnp.float64)
    for t in range(6):
        st_ = dp.online_dp_update(st_, xs[t], rho)
        got = np.asarray(dp.online_dp_delta(st_, rho))
        want = np.asarray(dp.dp_delta(x0, xs[: t + 1], rho))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-8)


def test_moe_sparse_coords():
    """Coordinates whose samples never move (unrouted experts) reduce to the
    FedAvg identity case: delta_j = (x0_j - xbar_j) / rho_l scaled by the
    identity part only — i.e. the DP needs no special-casing for sparse
    expert gradients (DESIGN.md §Arch-applicability)."""
    r = np.random.default_rng(7)
    d, ell, rho = 10, 5, 0.5
    x0 = jnp.asarray(r.normal(size=d))
    xs = np.tile(r.normal(size=d), (ell, 1))
    xs[:, :5] = r.normal(size=(ell, 5))        # only first 5 coords move
    xs = jnp.asarray(xs)
    got = np.asarray(dp.dp_delta(x0, xs, rho))
    want = np.asarray(dense_delta(x0, xs, rho))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-8)
    # frozen coords: Sigma_hat rows are rho_l on the diagonal, 0 elsewhere
    rho_l = 1.0 / (1.0 + (ell - 1) * rho)
    np.testing.assert_allclose(
        got[5:], np.asarray((x0 - xs[0])[5:]) / rho_l, rtol=1e-5
    )


def test_delta_converges_to_exact_with_gaussian_samples():
    """Delta_hat -> Sigma^{-1}(x0 - mu) as l grows (the bias-vanishes claim,
    Appendix A)."""
    r = np.random.default_rng(11)
    d = 6
    A = r.normal(size=(d, d))
    sigma = A @ A.T + 0.5 * np.eye(d)
    mu = r.normal(size=d)
    x0 = jnp.asarray(r.normal(size=d))
    exact = np.linalg.solve(sigma, np.asarray(x0) - mu)
    L = np.linalg.cholesky(sigma)
    errs = []
    for ell in (10, 100, 1000):
        xs = jnp.asarray(mu + r.normal(size=(ell, d)) @ L.T)
        got = np.asarray(dp.dp_delta(x0, xs, rho=1.0))
        errs.append(np.linalg.norm(got - exact) / np.linalg.norm(exact))
    assert errs[2] < errs[0], errs
    assert errs[2] < 0.2, errs


@pytest.mark.parametrize("ell", [2, 4, 8])
def test_gram_form_float32_with_common_offset(ell):
    """f32 samples that share a large offset (100 + 0.01 N(0, 1), as
    parameters do next to their spread): the Gram form works on
    differences from x_1 and keeps f32 precision against the f64 oracle."""
    r = np.random.default_rng(ell)
    d = 1000
    x0 = (100.0 + 0.01 * r.normal(size=d)).astype(np.float32)
    xs = (100.0 + 0.01 * r.normal(size=(ell, d))).astype(np.float32)
    tree0 = {"w": jnp.asarray(x0[:800].reshape(20, 40)),
             "b": jnp.asarray(x0[800:])}
    trees = {"w": jnp.asarray(xs[:, :800].reshape(ell, 20, 40)),
             "b": jnp.asarray(xs[:, 800:])}
    got = dp.dp_delta(tree0, trees, 0.5)
    assert got["w"].dtype == jnp.float32
    got = np.concatenate([np.asarray(got["w"]).ravel(),
                          np.asarray(got["b"])])
    want = np.asarray(dense_delta(jnp.asarray(x0, jnp.float64),
                                  jnp.asarray(xs, jnp.float64), 0.5))
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())


def _loops(jaxpr):
    """Every cond, while and scan equation in ``jaxpr``, nested ones too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("cond", "while", "scan"):
            yield eqn
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (tuple, list))
                        else (param,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _loops(inner)


def test_gram_form_carries_no_full_width_tree():
    """The batch DP's loops run on l x l scalars: no cond, while or scan
    carries a parameter-sized array (the full-width form carries four)."""
    ell = 4
    tree0 = {"w": jnp.zeros((64, 64), jnp.float32),
             "b": jnp.zeros((512,), jnp.float32)}
    trees = tm.tmap(lambda x: jnp.zeros((ell,) + x.shape, x.dtype), tree0)
    width = min(x.size for x in jax.tree_util.tree_leaves(tree0))
    closed = jax.make_jaxpr(lambda a, s: dp.dp_delta(a, s, 0.5))(tree0,
                                                                  trees)
    loops = list(_loops(closed.jaxpr))
    assert loops, "the recurrence is a scan"
    for eqn in loops:
        sizes = [int(np.prod(v.aval.shape)) for v in eqn.invars]
        assert max(sizes) < width, (eqn.primitive.name, sizes)


def test_tree_math_basics():
    a = {"x": jnp.arange(3.0), "y": jnp.ones((2, 2))}
    b = tm.tscale(2.0, a)
    assert float(tm.tvdot(a, a)) == pytest.approx(1 + 4 + 4.0)
    assert float(tm.tnorm(b)) == pytest.approx(2 * float(tm.tnorm(a)))
    c = tm.taxpy(-1.0, a, a)
    assert float(tm.tnorm(c)) == 0.0
