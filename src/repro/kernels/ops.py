"""jit'd public wrappers around the Pallas kernels.

The kernels compile for the TPU by default. ``interpret=True`` runs the
kernel bodies through the Pallas interpreter instead, which is how the CPU
tests check them against ``ref.py``; nothing chooses it implicitly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import fedpa_dp as _dp
from repro.kernels import swa_decode as _swa


@functools.partial(jax.jit, static_argnames=("rho", "interpret"))
def dp_step(u, delta, V, c_hist, t, *, rho: float, interpret: bool = False):
    """One fused Sherman-Morrison DP step (paper eqs. 22-23).

    u: (d,) = x_t - xbar_{t-1}; delta: (d,) = Delta~_{t-1};
    V: (lp, d) history v_2..v_{t-1} (rows >= t-1 ignored);
    c_hist: (lp,) combine coefficients; t: traced scalar sample index (>= 2).

    Returns (v_t, Delta~_t, a_t, c_t). Two HBM passes total (reduce + map)
    instead of the ~2t+2 of the unfused jnp formulation.
    """
    dots, uu, ud = _dp.dp_reduce(u, delta, V, interpret=interpret)
    n_hist = c_hist.shape[0]
    mask = jnp.arange(n_hist) < (t - 1)
    w = jnp.where(mask, c_hist * dots, 0.0)
    tf = jnp.asarray(t, jnp.float32)
    g = (tf - 1.0) * rho / tf
    a = uu - jnp.sum(w * dots)          # <u, v> expanded through the combine
    scale = (1.0 + g * (tf * ud - a) / (1.0 + g * a)) / tf
    v, delta_new = _dp.dp_map(w, scale, u, delta, V, interpret=interpret)
    c_new = g / (1.0 + g * a)
    return v, delta_new, a, c_new


def dp_delta_flat(x0, samples, *, rho: float, interpret: bool = False):
    """Full Delta_hat_l from stacked (l, d) samples using the fused kernels —
    the kernel-path equivalent of ``repro.core.dp_delta.dp_delta`` on flat
    vectors. Python loop over the (static, single-digit) sample count."""
    ell, d = samples.shape
    xbar = samples[0]
    delta = x0 - samples[0]
    lp = max(ell - 1, 1)
    V = jnp.zeros((lp, d), jnp.float32)
    c_hist = jnp.zeros((lp,), jnp.float32)
    for t in range(2, ell + 1):
        u = samples[t - 1] - xbar
        v, delta, _, c_new = dp_step(u, delta, V, c_hist, t, rho=rho,
                                     interpret=interpret)
        V = V.at[t - 2].set(v)
        c_hist = c_hist.at[t - 2].set(c_new)
        xbar = xbar + u / t
    rho_l = 1.0 / (1.0 + (ell - 1.0) * rho)
    return delta / rho_l


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def swa_decode(q, cache_k, cache_v, slot_pos, pos, *, window: int = 0,
               interpret: bool = False):
    """Sliding-window decode attention over a ring-buffer cache.

    q: (B, H, dh) one token's query heads; cache_k/v: (B, L, KV, dh);
    slot_pos: (L,); pos: scalar. Returns (B, H, dh).
    """
    B, H, dh = q.shape
    KV = cache_k.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, dh)
    out = _swa.swa_decode_attention(qg, cache_k, cache_v, slot_pos, pos,
                                    window=window, interpret=interpret)
    return out.reshape(B, H, dh)
