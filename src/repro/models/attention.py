"""GQA attention: q-chunked full/sliding-window forward + cached decode.

TPU adaptation notes (DESIGN.md §3): the forward pass is chunked over query
blocks with a ``lax.scan`` so the score matrix never materializes beyond
(B, KV, G, q_chunk, S_k) — the flash-attention memory shape without a custom
kernel (XLA fuses the masked-softmax chain well on TPU). Sliding-window
layers slice a (W + q_chunk) key window per chunk, making local layers
O(S * W) instead of O(S^2) — this is what makes gemma3/llama4/recurrentgemma
long-context shapes lowerable. Decode keeps a ring-buffer cache for windowed
layers and a linear cache for full layers, with per-slot positions so one
mask rule covers both.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.models.layers import rms_norm, rope

NEG_INF = -2.0e38


class AttnCache(NamedTuple):
    """Per-layer KV decode cache plus the token position held by each slot."""

    k: jnp.ndarray          # (B, L, KV, dh)
    v: jnp.ndarray          # (B, L, KV, dh)
    slot_pos: jnp.ndarray   # (L,) int32 token position held by each slot (-1 empty)


def init_attn_params(rng, cfg: ModelConfig, dtype=jnp.float32):
    """Init q/k/v/o projections (plus qk-norm scales when enabled)."""
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    ks = jax.random.split(rng, 4)
    s = lambda fan_in: 1.0 / jnp.sqrt(fan_in)
    p = {
        "wq": jax.random.normal(ks[0], (d, qd), dtype) * s(d),
        "wk": jax.random.normal(ks[1], (d, kvd), dtype) * s(d),
        "wv": jax.random.normal(ks[2], (d, kvd), dtype) * s(d),
        "wo": jax.random.normal(ks[3], (qd, d), dtype) * s(qd),
    }
    if cfg.qk_norm:
        p["q_scale"] = jnp.zeros((cfg.head_dim,), dtype)
        p["k_scale"] = jnp.zeros((cfg.head_dim,), dtype)
    return p


def _project_qkv(p, x, cfg: ModelConfig, positions):
    B, S, _ = x.shape
    H, KV, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, dh)
    k = (x @ p["wk"]).reshape(B, S, KV, dh)
    v = (x @ p["wv"]).reshape(B, S, KV, dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_scale"], cfg.norm_eps)
        k = rms_norm(k, p["k_scale"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _scores_softmax_v(q, k, v, qpos, kpos, window: int, dh: int):
    """q: (B,Sq,H,dh) grouped against k/v: (B,Sk,KV,dh). Returns (B,Sq,H*dh)."""
    B, Sq, H, _ = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(dh))
    mask = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] >= 0)
    if window:
        mask = mask & (kpos[None, :] > qpos[:, None] - window)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(B, Sq, H * dh)


def attn_forward(p, x, cfg: ModelConfig, spec: LayerSpec, pos0: int = 0,
                 q_chunk: int = 1024, return_cache: bool = False):
    """Full-sequence attention (train / prefill). Returns (y, kv) where kv is
    the raw (k, v) if ``return_cache`` else None."""
    B, S, d = x.shape
    window = spec.window if spec.mixer == "swa" else 0
    positions = pos0 + jnp.arange(S)
    q, k, v = _project_qkv(p, x, cfg, positions)

    q_chunk = min(q_chunk, S)
    # ragged tails: pad queries up to a whole number of chunks; the padded
    # rows attend causally to nothing new and are sliced off below
    S_pad = ((S + q_chunk - 1) // q_chunk) * q_chunk
    if S_pad != S:
        q = jnp.pad(q, ((0, 0), (0, S_pad - S), (0, 0), (0, 0)))
    n_chunks = S_pad // q_chunk

    if window and window < S:
        # local layer: each q chunk only sees a (window + chunk) key slice
        W = window
        pad = lambda t: jnp.concatenate(
            [jnp.zeros(t.shape[:1] + (W,) + t.shape[2:], t.dtype), t,
             jnp.zeros(t.shape[:1] + (S_pad - S,) + t.shape[2:], t.dtype)],
            axis=1,
        )
        kp, vp = pad(k), pad(v)

        def chunk_fn(_, i):
            qs = i * q_chunk
            qc = jax.lax.dynamic_slice_in_dim(q, qs, q_chunk, axis=1)
            kc = jax.lax.dynamic_slice_in_dim(kp, qs, W + q_chunk, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(vp, qs, W + q_chunk, axis=1)
            qpos = pos0 + qs + jnp.arange(q_chunk)
            kpos = pos0 + qs - W + jnp.arange(W + q_chunk)
            return None, _scores_softmax_v(qc, kc, vc, qpos, kpos, W,
                                           cfg.head_dim)
    else:

        def chunk_fn(_, i):
            qs = i * q_chunk
            qc = jax.lax.dynamic_slice_in_dim(q, qs, q_chunk, axis=1)
            qpos = pos0 + qs + jnp.arange(q_chunk)
            kpos = pos0 + jnp.arange(S)
            return None, _scores_softmax_v(qc, k, v, qpos, kpos, window,
                                           cfg.head_dim)

    _, outs = jax.lax.scan(chunk_fn, None, jnp.arange(n_chunks))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, S_pad, cfg.q_dim)[:, :S]
    y = out @ p["wo"]
    return y, ((k, v) if return_cache else None)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def attn_cache_len(cfg: ModelConfig, spec: LayerSpec, max_len: int) -> int:
    """Cache slots a layer needs: its window if sliding, else ``max_len``."""
    if spec.mixer == "swa" and spec.window < max_len:
        return spec.window
    return max_len


def init_attn_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                    max_len: int, dtype=jnp.bfloat16) -> AttnCache:
    """Allocate an empty KV cache (slot positions start at -1)."""
    L = attn_cache_len(cfg, spec, max_len)
    KV, dh = cfg.num_kv_heads, cfg.head_dim
    return AttnCache(
        k=jnp.zeros((batch, L, KV, dh), dtype),
        v=jnp.zeros((batch, L, KV, dh), dtype),
        slot_pos=jnp.full((L,), -1, jnp.int32),
    )


def cache_from_prefill(cfg: ModelConfig, spec: LayerSpec, kv, max_len: int,
                       dtype=jnp.bfloat16) -> AttnCache:
    """Build a decode cache from prefill's raw (k, v) of S tokens."""
    k, v = kv
    B, S = k.shape[:2]
    L = attn_cache_len(cfg, spec, max_len)
    cache = init_attn_cache(cfg, spec, B, max_len, dtype)
    take = min(S, L)
    kk = k[:, S - take:].astype(dtype)
    vv = v[:, S - take:].astype(dtype)
    if L == spec.window and spec.mixer == "swa":
        # ring layout: token position p lives in slot p % L
        slots = (jnp.arange(S - take, S)) % L
        ck = cache.k.at[:, slots].set(kk)
        cv = cache.v.at[:, slots].set(vv)
        sp = cache.slot_pos.at[slots].set(jnp.arange(S - take, S))
    else:
        ck = cache.k.at[:, S - take : S].set(kk)
        cv = cache.v.at[:, S - take : S].set(vv)
        sp = cache.slot_pos.at[S - take : S].set(jnp.arange(S - take, S))
    return AttnCache(ck, cv, sp)


def attn_decode(p, x, cache: AttnCache, cfg: ModelConfig, spec: LayerSpec,
                pos, pallas: Optional[str] = None):
    """One-token decode. x: (B, 1, d); pos: traced scalar = index of the new
    token. Returns (y, new_cache).

    ``pallas="compiled"`` routes the attention itself through the fused
    ``kernels.swa_decode`` Pallas kernel (flash-decode over the ring
    buffer), compiled for the TPU; ``pallas="interpret"`` runs that kernel
    in the Pallas interpreter (CPU). The default ``None`` is the pure-jnp
    path the kernel is validated against.
    """
    if pallas not in (None, "compiled", "interpret"):
        raise ValueError(f"pallas={pallas!r}: expected None, 'compiled' "
                         "or 'interpret'")
    B = x.shape[0]
    L = cache.k.shape[1]
    window = spec.window if spec.mixer == "swa" else 0
    positions = jnp.asarray(pos)[None]
    q, k, v = _project_qkv(p, x, cfg, positions)

    # token position p lives in slot p % L (identity for linear caches, ring
    # layout for window caches where L == window)
    slot = (pos % L).astype(jnp.int32)
    ck = jax.lax.dynamic_update_index_in_dim(cache.k, k[:, 0].astype(cache.k.dtype), slot, axis=1)
    cv = jax.lax.dynamic_update_index_in_dim(cache.v, v[:, 0].astype(cache.v.dtype), slot, axis=1)
    sp = jax.lax.dynamic_update_index_in_dim(cache.slot_pos,
                                             pos.astype(jnp.int32), slot, axis=0)

    if pallas:
        from repro.kernels import ops as kernel_ops  # lazy: pallas import
        out = kernel_ops.swa_decode(
            q[:, 0], ck.astype(q.dtype), cv.astype(q.dtype), sp,
            jnp.asarray(pos, jnp.int32), window=window,
            interpret=pallas == "interpret",
        ).reshape(B, 1, cfg.q_dim)
    else:
        qpos = jnp.asarray(pos)[None]
        out = _scores_softmax_v(q, ck.astype(q.dtype), cv.astype(q.dtype),
                                qpos, sp, window, cfg.head_dim)
    y = out @ p["wo"]
    return y, AttnCache(ck, cv, sp)
