"""xLSTM blocks: mLSTM (matrix memory) and sLSTM (scalar memory with
exponential gating). [arXiv:2405.04517]

TPU adaptation (DESIGN.md §3): the mLSTM is implemented in *chunkwise
recurrent* form — a ``lax.scan`` over sequence chunks carrying the
(C, n, m) state, with the intra-chunk part computed as a decay-masked
quadratic attention block. This keeps compute MXU-shaped (dense matmuls per
chunk), memory linear in sequence length, and the log-decay accumulators
chunk-local so fp32 cumsums never grow with S (they would lose precision at
500k tokens in a global-cumsum formulation). The sLSTM is inherently
sequential (its recurrence is nonlinear), so it scans over time steps.

The sLSTM scan carries its state in float32 whatever the compute dtype,
as the mLSTM's does. Each recurrence runs under a ``jax.named_scope``
(``slstm_scan``, ``mlstm_chunk``) that holds it alone, so a device trace
can tell its time from the projections around it.

Consistency between the chunkwise forward and the per-token decode step is
asserted in tests/test_models_consistency.py.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import causal_conv1d, group_norm_heads, rms_norm


def _e(cfg: ModelConfig) -> int:
    return int(cfg.expansion * cfg.d_model)


def _slstm_ff(cfg: ModelConfig) -> int:
    f = (4 * cfg.d_model) // 3
    return ((f + 127) // 128) * 128


# ===========================================================================
# mLSTM
# ===========================================================================

class MLSTMState(NamedTuple):
    """mLSTM decode state: matrix memory, normalizer, stabilizer, conv tail."""

    C: jnp.ndarray       # (B, H, dh, dh) matrix memory (k-major)
    n: jnp.ndarray       # (B, H, dh) normalizer state
    m: jnp.ndarray       # (B, H) log stabilizer
    conv: jnp.ndarray    # (B, cw-1, e) streaming conv state


def init_mlstm_params(rng, cfg: ModelConfig, dtype=jnp.float32):
    """Init the mLSTM block (up-proj, conv, q/k/v, gates, down-proj)."""
    d, e, H = cfg.d_model, _e(cfg), cfg.num_heads
    ks = jax.random.split(rng, 7)
    s = lambda fan: 1.0 / jnp.sqrt(fan)
    return {
        "w_up": jax.random.normal(ks[0], (d, 2 * e), dtype) * s(d),
        "conv": jax.random.normal(ks[1], (cfg.conv_width, e), dtype) * s(cfg.conv_width),
        "wq": jax.random.normal(ks[2], (e, e), dtype) * s(e),
        "wk": jax.random.normal(ks[3], (e, e), dtype) * s(e),
        "wv": jax.random.normal(ks[4], (e, e), dtype) * s(e),
        "w_gates": jax.random.normal(ks[5], (e, 2 * H), dtype) * s(e),
        # forget-gate bias init positive => long memory at init (xLSTM paper)
        "b_gates": jnp.concatenate(
            [jnp.full((H,), -3.0, dtype), jnp.full((H,), 3.0, dtype)]
        ),
        "gn_scale": jnp.zeros((e,), dtype),
        "w_down": jax.random.normal(ks[6], (e, d), dtype) * s(e),
    }


def init_mlstm_state(cfg: ModelConfig, batch: int, dtype=jnp.float32) -> MLSTMState:
    """Zero-initialise the mLSTM decode state."""
    e, H = _e(cfg), cfg.num_heads
    dh = e // H
    return MLSTMState(
        C=jnp.zeros((batch, H, dh, dh), dtype),
        n=jnp.zeros((batch, H, dh), dtype),
        m=jnp.full((batch, H), -1e30, dtype),
        conv=jnp.zeros((batch, cfg.conv_width - 1, e), dtype),
    )


def _mlstm_qkv_gates(p, x, cfg: ModelConfig, conv_state=None):
    """Shared projection path. x: (B, S, d). Returns q,k,v (B,S,H,dh),
    i_pre/f_pre (B,S,H), z (B,S,e), new conv state."""
    B, S, _ = x.shape
    e, H = _e(cfg), cfg.num_heads
    dh = e // H
    up = x @ p["w_up"]
    xi, z = up[..., :e], up[..., e:]
    xc, conv_state = causal_conv1d(xi, p["conv"], conv_state)
    xc = jax.nn.silu(xc)
    q = (xc @ p["wq"]).reshape(B, S, H, dh)
    k = (xc @ p["wk"]).reshape(B, S, H, dh) / jnp.sqrt(jnp.float32(dh)).astype(x.dtype)
    v = (xi @ p["wv"]).reshape(B, S, H, dh)
    gates = (xc @ p["w_gates"] + p["b_gates"]).astype(jnp.float32)
    i_pre, f_pre = gates[..., :H], gates[..., H:]
    return q, k, v, i_pre, f_pre, z, conv_state


def _mlstm_chunk(q, k, v, i_pre, f_pre, C, n, m):
    """One chunk of the stabilized chunkwise recurrence.

    q,k,v: (B,T,H,dh); i_pre,f_pre: (B,T,H) fp32; state (C,n,m).
    Returns (h (B,T,H,dh), C', n', m').
    """
    B, T, H, dh = q.shape
    lf = jax.nn.log_sigmoid(f_pre)                      # (B,T,H)
    F = jnp.cumsum(lf, axis=1)                          # inclusive: F[t]=sum_{s<=t}
    # log weight of sample s surviving to t (s <= t): F[t] - F[s] + i[s]
    Dt = (F[:, :, None, :] - F[:, None, :, :]
          + i_pre[:, None, :, :])                       # (B, t, s, H)
    causal = jnp.tril(jnp.ones((T, T), bool))
    Dt = jnp.where(causal[None, :, :, None], Dt, -jnp.inf)
    b = F + m[:, None, :]                               # (B,T,H) inter log-scale
    m_t = jnp.maximum(jnp.max(Dt, axis=2), b)           # (B,T,H)
    m_t = jnp.maximum(m_t, -1e30)                       # guard all--inf rows
    w_intra = jnp.exp(Dt - m_t[:, :, None, :])          # (B,t,s,H)
    w_inter = jnp.exp(b - m_t)                          # (B,T,H)

    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bthd,bshd->btsh", qf, kf) * w_intra
    num = jnp.einsum("btsh,bshd->bthd", scores, vf)
    num = num + w_inter[..., None] * jnp.einsum("bthd,bhde->bthe", qf,
                                                C.astype(jnp.float32))
    den = jnp.sum(scores, axis=2)                       # (B,T,H)
    den = den + w_inter * jnp.einsum("bthd,bhd->bth", qf, n.astype(jnp.float32))
    den = jnp.maximum(jnp.abs(den), jnp.exp(-m_t))
    h = (num / den[..., None]).astype(q.dtype)

    # ---- carry update to the chunk end ----
    FT = F[:, -1, :]                                    # (B,H)
    ws = FT[:, None, :] - F + i_pre                     # (B,T,H) log w of s into state
    m_next = jnp.maximum(m + FT, jnp.max(ws, axis=1))
    m_next = jnp.maximum(m_next, -1e30)
    decay = jnp.exp(m + FT - m_next)                    # (B,H)
    w_in = jnp.exp(ws - m_next[:, None, :])             # (B,T,H)
    C_new = decay[..., None, None] * C.astype(jnp.float32) + jnp.einsum(
        "bsh,bshd,bshe->bhde", w_in, kf, vf
    )
    n_new = decay[..., None] * n.astype(jnp.float32) + jnp.einsum(
        "bsh,bshd->bhd", w_in, kf
    )
    return h, C_new.astype(C.dtype), n_new.astype(n.dtype), m_next.astype(m.dtype)


def mlstm_forward(p, x, cfg: ModelConfig, chunk: int = 256,
                  return_cache: bool = False):
    """Full-sequence mLSTM block. x: (B, S, d) -> (B, S, d)."""
    B, S, d = x.shape
    e, H = _e(cfg), cfg.num_heads
    dh = e // H
    q, k, v, i_pre, f_pre, z, conv_state = _mlstm_qkv_gates(p, x, cfg)
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk
    st0 = init_mlstm_state(cfg, B)

    resh = lambda t: t.reshape((B, nc, chunk) + t.shape[2:]).swapaxes(0, 1)
    qs, ks, vs = resh(q), resh(k), resh(v)
    is_, fs_ = resh(i_pre), resh(f_pre)

    def body(carry, xs):
        C, n, m = carry
        qc, kc, vc, ic, fc = xs
        h, C, n, m = _mlstm_chunk(qc, kc, vc, ic, fc, C, n, m)
        return (C, n, m), h

    with jax.named_scope("mlstm_chunk"):
        (C, n, m), hs = jax.lax.scan(body, (st0.C, st0.n, st0.m),
                                     (qs, ks, vs, is_, fs_))
    h = hs.swapaxes(0, 1).reshape(B, S, H, dh)
    h = group_norm_heads(h, p["gn_scale"].reshape(H, dh), cfg.norm_eps)
    y = (h.reshape(B, S, e) * jax.nn.silu(z)) @ p["w_down"]
    state = MLSTMState(C, n, m, conv_state) if return_cache else None
    return y, state


def mlstm_decode(p, x, state: MLSTMState, cfg: ModelConfig):
    """One-token recurrent step. x: (B, 1, d)."""
    B = x.shape[0]
    e, H = _e(cfg), cfg.num_heads
    dh = e // H
    q, k, v, i_pre, f_pre, z, conv_state = _mlstm_qkv_gates(
        p, x, cfg, conv_state=state.conv
    )
    q, k, v = q[:, 0], k[:, 0], v[:, 0]                 # (B,H,dh)
    i_pre, f_pre = i_pre[:, 0], f_pre[:, 0]             # (B,H)
    lf = jax.nn.log_sigmoid(f_pre)
    m_new = jnp.maximum(lf + state.m, i_pre)
    decay = jnp.exp(lf + state.m - m_new)
    inp = jnp.exp(i_pre - m_new)
    kf, vf, qf = (t.astype(jnp.float32) for t in (k, v, q))
    C = decay[..., None, None] * state.C.astype(jnp.float32) + \
        inp[..., None, None] * jnp.einsum("bhd,bhe->bhde", kf, vf)
    n = decay[..., None] * state.n.astype(jnp.float32) + inp[..., None] * kf
    num = jnp.einsum("bhd,bhde->bhe", qf, C)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", qf, n)),
                      jnp.exp(-m_new))
    h = (num / den[..., None]).astype(x.dtype)
    h = group_norm_heads(h, p["gn_scale"].reshape(H, dh), cfg.norm_eps)
    y = (h.reshape(B, 1, e) * jax.nn.silu(z)) @ p["w_down"]
    new_state = MLSTMState(C.astype(state.C.dtype), n.astype(state.n.dtype),
                           m_new.astype(state.m.dtype), conv_state)
    return y, new_state


# ===========================================================================
# sLSTM
# ===========================================================================

class SLSTMState(NamedTuple):
    """sLSTM decode state: cell, normalizer, hidden, stabilizer, conv tail."""

    c: jnp.ndarray       # (B, d)
    n: jnp.ndarray       # (B, d)
    h: jnp.ndarray       # (B, d)
    m: jnp.ndarray       # (B, d)
    conv: jnp.ndarray    # (B, cw-1, d)


def init_slstm_params(rng, cfg: ModelConfig, dtype=jnp.float32):
    """Init the sLSTM block (conv, input/recurrent gate stacks, MLP)."""
    d, H = cfg.d_model, cfg.num_heads
    dh = d // H
    ff = _slstm_ff(cfg)
    ks = jax.random.split(rng, 5)
    s = lambda fan: 1.0 / jnp.sqrt(fan)
    return {
        "conv": jax.random.normal(ks[0], (cfg.conv_width, d), dtype) * s(cfg.conv_width),
        "w": jax.random.normal(ks[1], (d, 4 * d), dtype) * s(d),
        "r": jax.random.normal(ks[2], (H, dh, 4 * dh), dtype) * s(dh),
        # gate order (z, i, f, o); forget bias positive
        "b": jnp.concatenate([
            jnp.zeros((d,), dtype), jnp.full((d,), -3.0, dtype),
            jnp.full((d,), 3.0, dtype), jnp.zeros((d,), dtype),
        ]),
        "gn_scale": jnp.zeros((d,), dtype),
        "mlp_norm": jnp.zeros((d,), dtype),
        "w_mlp_up": jax.random.normal(ks[3], (d, ff), dtype) * s(d),
        "w_mlp_down": jax.random.normal(ks[4], (ff, d), dtype) * s(ff),
    }


def init_slstm_state(cfg: ModelConfig, batch: int, dtype=jnp.float32) -> SLSTMState:
    """Zero-initialise the sLSTM decode state (stabilizer at -inf)."""
    d = cfg.d_model
    z = lambda: jnp.zeros((batch, d), dtype)
    return SLSTMState(c=z(), n=z(), h=z(),
                      m=jnp.full((batch, d), -1e30, dtype),
                      conv=jnp.zeros((batch, cfg.conv_width - 1, d), dtype))


def _slstm_cell(p, wx_t, st: SLSTMState, cfg: ModelConfig):
    """One recurrence step. wx_t: (B, 4d) precomputed input contribution."""
    B, d = st.h.shape
    H = cfg.num_heads
    dh = d // H
    rec = jnp.einsum("bhd,hde->bhe", st.h.reshape(B, H, dh).astype(jnp.float32),
                     p["r"].astype(jnp.float32)).reshape(B, 4 * d)
    g = wx_t.astype(jnp.float32) + rec
    z_, i_, f_, o_ = jnp.split(g, 4, axis=-1)
    lf = jax.nn.log_sigmoid(f_)
    m_new = jnp.maximum(lf + st.m, i_)
    ig = jnp.exp(i_ - m_new)
    fg = jnp.exp(lf + st.m - m_new)
    c = fg * st.c.astype(jnp.float32) + ig * jnp.tanh(z_)
    n = fg * st.n.astype(jnp.float32) + ig
    h = jax.nn.sigmoid(o_) * c / jnp.maximum(n, jnp.exp(-m_new))
    dt = st.h.dtype
    return SLSTMState(c.astype(dt), n.astype(dt), h.astype(dt),
                      m_new.astype(dt), st.conv)


def _slstm_out(p, h, cfg: ModelConfig):
    """GroupNorm + post-MLP (the sLSTM block's internal FFN)."""
    B, S, d = h.shape
    H = cfg.num_heads
    hn = group_norm_heads(h.reshape(B, S, H, d // H),
                          p["gn_scale"].reshape(H, d // H),
                          cfg.norm_eps).reshape(B, S, d)
    u = rms_norm(hn, p["mlp_norm"], cfg.norm_eps)
    return hn + jax.nn.gelu(u @ p["w_mlp_up"]) @ p["w_mlp_down"]


def slstm_forward(p, x, cfg: ModelConfig, return_cache: bool = False):
    """Run the sLSTM over a full sequence via lax.scan over time.

    The scan carries c, n, h and m in float32 (the conv tail stays out of
    it); each step's h leaves the scan in x's dtype."""
    B, S, d = x.shape
    xc, conv_state = causal_conv1d(x, p["conv"])
    xc = jax.nn.silu(xc)
    wx = xc @ p["w"] + p["b"]

    st0 = init_slstm_state(cfg, B)._replace(conv=None)

    def body(st, wx_t):
        st = _slstm_cell(p, wx_t, st, cfg)
        return st, st.h.astype(x.dtype)

    with jax.named_scope("slstm_scan"):
        st, hs = jax.lax.scan(body, st0, wx.swapaxes(0, 1))
    h = hs.swapaxes(0, 1)                               # (B,S,d)
    y = _slstm_out(p, h, cfg)
    state = st._replace(conv=conv_state) if return_cache else None
    return y, state


def slstm_decode(p, x, state: SLSTMState, cfg: ModelConfig):
    """Advance the sLSTM one token from cached state."""
    xc, conv_state = causal_conv1d(x, p["conv"], state.conv)
    xc = jax.nn.silu(xc)
    wx = (xc @ p["w"] + p["b"])[:, 0]
    st = _slstm_cell(p, wx, state, cfg)
    y = _slstm_out(p, st.h[:, None, :], cfg)
    return y, st._replace(conv=conv_state)
