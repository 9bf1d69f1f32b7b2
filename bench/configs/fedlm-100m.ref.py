"""Plain reference of fedlm-100m: a dense pre-norm decoder.

Per layer: x += Wo attn(rope(qknorm(Wq h)), rope(qknorm(Wk h)), Wv h) with
h = rmsnorm(x), grouped-query heads and a causal softmax; then
x += W_down (silu(W_gate u) * (W_up u)) with u = rmsnorm(x). RMSNorm
scales are (1 + scale), rope splits each head in halves, and the output
head is the transposed embedding. Written from that description; shares
no code with the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import nn


def _init_mixer(key, model, spec):
    d, h, kv = model["d_model"], model["num_heads"], model["num_kv_heads"]
    dh = model["head_dim"]
    ks = jax.random.split(key, 4)
    p = {"wq": nn.normal(ks[0], (d, h * dh), d),
         "wk": nn.normal(ks[1], (d, kv * dh), d),
         "wv": nn.normal(ks[2], (d, kv * dh), d),
         "wo": nn.normal(ks[3], (h * dh, d), h * dh)}
    if model["qk_norm"]:
        p["q_scale"] = jnp.zeros((dh,), nn.F32)
        p["k_scale"] = jnp.zeros((dh,), nn.F32)
    return p


def _init_ffn(key, model, spec):
    d, ff = model["d_model"], model["d_ff"]
    ks = jax.random.split(key, 3)
    return {"norm": jnp.zeros((d,), nn.F32),
            "w_gate": nn.normal(ks[0], (d, ff), d),
            "w_up": nn.normal(ks[1], (d, ff), d),
            "w_down": nn.normal(ks[2], (ff, d), ff)}


def init(key, model: dict):
    """float32 weights from ``jax.random.PRNGKey(seed)``, in the program's
    tree layout."""
    return nn.lm_params(key, model,
                        lambda k, s: _init_mixer(k, model, s),
                        lambda k, s: _init_ffn(k, model, s))


def _rope(x, theta):
    """x: (B, S, H, dh); rotate the two halves of each head."""
    s, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    freq = theta ** (-jnp.arange(half, dtype=nn.F32) / half)
    ang = jnp.arange(s, dtype=nn.F32)[:, None] * freq        # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(p, x, model, rnd):
    b, s, _ = x.shape
    h, kv, dh = model["num_heads"], model["num_kv_heads"], model["head_dim"]
    eps = model["norm_eps"]
    q = nn.mm(x, p["wq"], rnd).reshape(b, s, h, dh)
    k = nn.mm(x, p["wk"], rnd).reshape(b, s, kv, dh)
    v = nn.mm(x, p["wv"], rnd).reshape(b, s, kv, dh)
    if model["qk_norm"]:
        q = nn.rms_norm(q, p["q_scale"], eps)
        k = nn.rms_norm(k, p["k_scale"], eps)
    q, k = _rope(q, model["rope_theta"]), _rope(k, model["rope_theta"])
    # query head j reads key/value head j // (h // kv)
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", rnd(q), rnd(k),
                        preferred_element_type=nn.F32) / jnp.sqrt(nn.F32(dh))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", rnd(probs), rnd(v),
                     preferred_element_type=nn.F32)
    return nn.mm(out.reshape(b, s, h * dh), p["wo"], rnd)


def _block(p, x, spec, model, rnd):
    eps = model["norm_eps"]
    x = x + _attention(p["mixer"], nn.rms_norm(x, p["norm"], eps), model, rnd)
    f = p["ffn"]
    u = nn.rms_norm(x, f["norm"], eps)
    y = jax.nn.silu(nn.mm(u, f["w_gate"], rnd)) * nn.mm(u, f["w_up"], rnd)
    return x + nn.mm(y, f["w_down"], rnd)


def loss(params, tokens, model: dict, rnd=nn.identity):
    """Mean next-token cross entropy of a (B, S+1) batch."""
    return nn.lm_loss(params, tokens, model, rnd,
                      lambda p, x, spec: _block(p, x, spec, model, rnd))
