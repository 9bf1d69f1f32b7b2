"""Plain reference of xlstm-125m (arXiv:2405.04517): sLSTM and mLSTM blocks.

Each layer adds its block to the residual stream x, applied to
h = rmsnorm(x); there is no separate feed-forward layer.

mLSTM block (matrix memory), widths e = 2 d, H heads of e / H:
  [a, z] = h W_up; c = silu(conv(a)); q = c Wq, k = c Wk / sqrt(dh),
  v = a Wv; [i, f] = c W_gates + b (one pair per head). Recurrent form, per
  step: m' = max(logsigmoid(f) + m, i), C' = exp(logsigmoid(f) + m - m') C
  + exp(i - m') k v^T, n' likewise with k; out = q C' / max(|q . n'|,
  exp(-m')). Then y = (headnorm(out) * silu(z)) W_down.

sLSTM block (scalar memory), d units in H heads:
  c = silu(conv(h)); g = c W + b + R h_prev (R block-diagonal per head;
  the per-head output (H, 4 dh) is read as one 4 d vector of (z, i, f, o)
  quarters, as the repository does); m' = max(logsigmoid(f) + m, i),
  cell = exp(logsigmoid(f) + m - m') cell + exp(i - m') tanh(z), n
  likewise with 1; h = sigmoid(o) cell / max(n, exp(-m')). Then
  y = hn + W_mlp_down gelu_tanh(W_mlp_up rmsnorm(hn)), hn = headnorm(h).

Written from that description; shares no code with the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import nn

NEG = -1e30


def _slstm_ff(d: int) -> int:
    return -(-(4 * d // 3) // 128) * 128


def _init_mixer(key, model, spec):
    d, h, cw = model["d_model"], model["num_heads"], model["conv_width"]
    if spec["mixer"] == "mlstm":
        e = int(model["expansion"] * d)
        ks = jax.random.split(key, 7)
        return {"w_up": nn.normal(ks[0], (d, 2 * e), d),
                "conv": nn.normal(ks[1], (cw, e), cw),
                "wq": nn.normal(ks[2], (e, e), e),
                "wk": nn.normal(ks[3], (e, e), e),
                "wv": nn.normal(ks[4], (e, e), e),
                "w_gates": nn.normal(ks[5], (e, 2 * h), e),
                "b_gates": jnp.concatenate([jnp.full((h,), -3.0, nn.F32),
                                            jnp.full((h,), 3.0, nn.F32)]),
                "gn_scale": jnp.zeros((e,), nn.F32),
                "w_down": nn.normal(ks[6], (e, d), e)}
    dh, ff = d // h, _slstm_ff(d)
    ks = jax.random.split(key, 5)
    return {"conv": nn.normal(ks[0], (cw, d), cw),
            "w": nn.normal(ks[1], (d, 4 * d), d),
            "r": nn.normal(ks[2], (h, dh, 4 * dh), dh),
            "b": jnp.concatenate([jnp.zeros((d,), nn.F32),
                                  jnp.full((d,), -3.0, nn.F32),
                                  jnp.full((d,), 3.0, nn.F32),
                                  jnp.zeros((d,), nn.F32)]),
            "gn_scale": jnp.zeros((d,), nn.F32),
            "mlp_norm": jnp.zeros((d,), nn.F32),
            "w_mlp_up": nn.normal(ks[3], (d, ff), d),
            "w_mlp_down": nn.normal(ks[4], (ff, d), ff)}


def init(key, model: dict):
    """float32 weights from ``jax.random.PRNGKey(seed)``, in the program's
    tree layout."""
    return nn.lm_params(key, model,
                        lambda k, s: _init_mixer(k, model, s),
                        lambda k, s: {})


def _head_norm(x, scale, heads, eps):
    """RMS-normalize each head of the last axis, times (1 + scale)."""
    shape = x.shape
    xh = x.reshape(shape[:-1] + (heads, shape[-1] // heads))
    return nn.rms_norm(xh, scale.reshape(heads, -1), eps).reshape(shape)


def _mlstm(p, x, model, rnd):
    b, s, d = x.shape
    h, eps = model["num_heads"], model["norm_eps"]
    e = int(model["expansion"] * d)
    dh = e // h
    up = nn.mm(x, p["w_up"], rnd)
    a, z = up[..., :e], up[..., e:]
    c = jax.nn.silu(nn.causal_conv(a, p["conv"]))
    q = nn.mm(c, p["wq"], rnd).reshape(b, s, h, dh)
    k = nn.mm(c, p["wk"], rnd).reshape(b, s, h, dh) / jnp.sqrt(nn.F32(dh))
    v = nn.mm(a, p["wv"], rnd).reshape(b, s, h, dh)
    gates = nn.mm(c, p["w_gates"], rnd) + p["b_gates"]
    ig, fg = gates[..., :h], gates[..., h:]

    def step(carry, xs):
        cmat, nvec, m = carry
        qt, kt, vt, it, ft = xs
        lf = jax.nn.log_sigmoid(ft)
        m_new = jnp.maximum(lf + m, it)
        decay, inp = jnp.exp(lf + m - m_new), jnp.exp(it - m_new)
        cmat = decay[..., None, None] * cmat + inp[..., None, None] * \
            jnp.einsum("bhd,bhe->bhde", kt, vt)
        nvec = decay[..., None] * nvec + inp[..., None] * kt
        num = jnp.einsum("bhd,bhde->bhe", qt, cmat)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", qt, nvec)),
                          jnp.exp(-m_new))
        return (cmat, nvec, m_new), num / den[..., None]

    carry = (jnp.zeros((b, h, dh, dh), nn.F32), jnp.zeros((b, h, dh), nn.F32),
             jnp.full((b, h), NEG, nn.F32))
    seq = lambda t: jnp.moveaxis(t, 1, 0)  # noqa: E731
    _, out = jax.lax.scan(step, carry, (seq(q), seq(k), seq(v), seq(ig),
                                        seq(fg)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, e)
    out = _head_norm(out, p["gn_scale"], h, eps)
    return nn.mm(out * jax.nn.silu(z), p["w_down"], rnd)


def _slstm(p, x, model, rnd):
    b, s, d = x.shape
    h, eps = model["num_heads"], model["norm_eps"]
    dh = d // h
    c = jax.nn.silu(nn.causal_conv(x, p["conv"]))
    wx = nn.mm(c, p["w"], rnd) + p["b"]
    r = rnd(p["r"])

    def step(carry, wx_t):
        cell, n, hid, m = carry
        rec = jnp.einsum("bhd,hde->bhe", rnd(hid.reshape(b, h, dh)), r,
                         preferred_element_type=nn.F32).reshape(b, 4 * d)
        zg, ig, fg, og = jnp.split(wx_t + rec, 4, axis=-1)
        lf = jax.nn.log_sigmoid(fg)
        m_new = jnp.maximum(lf + m, ig)
        fw, iw = jnp.exp(lf + m - m_new), jnp.exp(ig - m_new)
        cell = fw * cell + iw * jnp.tanh(zg)
        n = fw * n + iw
        hid = jax.nn.sigmoid(og) * cell / jnp.maximum(n, jnp.exp(-m_new))
        return (cell, n, hid, m_new), hid

    zeros = jnp.zeros((b, d), nn.F32)
    _, hs = jax.lax.scan(step, (zeros, zeros, zeros,
                                jnp.full((b, d), NEG, nn.F32)),
                         jnp.moveaxis(wx, 1, 0))
    hn = _head_norm(jnp.moveaxis(hs, 0, 1), p["gn_scale"], h, eps)
    u = nn.rms_norm(hn, p["mlp_norm"], eps)
    return hn + nn.mm(jax.nn.gelu(nn.mm(u, p["w_mlp_up"], rnd),
                                  approximate=True), p["w_mlp_down"], rnd)


def _block(p, x, spec, model, rnd):
    hn = nn.rms_norm(x, p["norm"], model["norm_eps"])
    mixer = _mlstm if spec["mixer"] == "mlstm" else _slstm
    return x + mixer(p["mixer"], hn, model, rnd)


def loss(params, tokens, model: dict, rnd=nn.identity):
    """Mean next-token cross entropy of a (B, S+1) batch."""
    return nn.lm_loss(params, tokens, model, rnd,
                      lambda p, x, spec: _block(p, x, spec, model, rnd))
