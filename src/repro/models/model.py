"""The generic pattern decoder: one model builder for all ten assigned
architectures.

A config describes a *pattern* of layers (mixer + ffn) repeated R times plus
an optional tail. Parameters for each pattern position are stacked over
repeats, and the forward pass is a single ``lax.scan`` over repeats — so the
lowered HLO (and XLA compile time, which matters for the 512-device CPU
dry-run) is independent of depth. Mixers: full/sliding-window GQA attention,
mLSTM, sLSTM, RG-LRU. FFNs: SwiGLU, MoE, none.

Decode state mirrors the parameter layout: per-pattern-position caches
stacked over repeats, scanned in lockstep with the params.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import rms_norm, swiglu
from repro.sharding import constrain

# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_mixer(rng, cfg: ModelConfig, spec: LayerSpec, dtype):
    if spec.mixer in ("attn", "swa"):
        return attn.init_attn_params(rng, cfg, dtype)
    if spec.mixer == "mlstm":
        return xlstm_mod.init_mlstm_params(rng, cfg, dtype)
    if spec.mixer == "slstm":
        return xlstm_mod.init_slstm_params(rng, cfg, dtype)
    if spec.mixer == "rglru":
        return rglru_mod.init_rglru_params(rng, cfg, dtype)
    raise ValueError(spec.mixer)


def _init_ffn(rng, cfg: ModelConfig, spec: LayerSpec, dtype):
    if spec.ffn == "none":
        return {}
    if spec.ffn == "dense":
        d, ff = cfg.d_model, cfg.d_ff
        ks = jax.random.split(rng, 3)
        s = lambda fan: 1.0 / jnp.sqrt(fan)
        return {
            "norm": jnp.zeros((d,), dtype),
            "w_gate": jax.random.normal(ks[0], (d, ff), dtype) * s(d),
            "w_up": jax.random.normal(ks[1], (d, ff), dtype) * s(d),
            "w_down": jax.random.normal(ks[2], (ff, d), dtype) * s(ff),
        }
    if spec.ffn == "moe":
        return {"norm": jnp.zeros((cfg.d_model,), dtype),
                "moe": moe_mod.init_moe_params(rng, cfg, dtype)}
    raise ValueError(spec.ffn)


def _init_layer(rng, cfg: ModelConfig, spec: LayerSpec, dtype):
    k1, k2 = jax.random.split(rng)
    return {
        "norm": jnp.zeros((cfg.d_model,), dtype),
        "mixer": _init_mixer(k1, cfg, spec, dtype),
        "ffn": _init_ffn(k2, cfg, spec, dtype),
    }


def init_params(rng, cfg: ModelConfig, dtype=jnp.float32) -> Dict[str, Any]:
    """Init the full decoder: embed + stacked pattern/tail layers + norms."""
    k_embed, k_pat, k_tail, k_un = jax.random.split(rng, 4)
    V, d = cfg.padded_vocab, cfg.d_model
    params: Dict[str, Any] = {
        "embed": jax.random.normal(k_embed, (V, d), dtype) / jnp.sqrt(d),
        "final_norm": jnp.zeros((d,), dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = jax.random.normal(k_un, (d, V), dtype) / jnp.sqrt(d)

    pattern = {}
    for i, spec in enumerate(cfg.pattern):
        keys = jax.random.split(jax.random.fold_in(k_pat, i), cfg.repeats)
        pattern[f"pos_{i}"] = jax.vmap(
            lambda k: _init_layer(k, cfg, spec, dtype)
        )(keys)
    params["pattern"] = pattern

    tail = {}
    for i, spec in enumerate(cfg.tail):
        tail[f"layer_{i}"] = _init_layer(
            jax.random.fold_in(k_tail, i), cfg, spec, dtype
        )
    if tail:
        params["tail"] = tail
    return params


def abstract_params(cfg: ModelConfig, dtype=jnp.float32):
    """ShapeDtypeStruct pytree of the params — no allocation (dry-run path)."""
    return jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg, dtype)
    )


def count_params(cfg: ModelConfig) -> int:
    """Exact parameter count via abstract shapes (no allocation)."""
    leaves = jax.tree_util.tree_leaves(abstract_params(cfg))
    return sum(x.size for x in leaves)


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _apply_layer(p, x, cfg: ModelConfig, spec: LayerSpec, q_chunk: int,
                 return_cache: bool):
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    if spec.mixer in ("attn", "swa"):
        y, cache = attn.attn_forward(p["mixer"], h, cfg, spec,
                                     q_chunk=q_chunk,
                                     return_cache=return_cache)
    elif spec.mixer == "mlstm":
        y, cache = xlstm_mod.mlstm_forward(p["mixer"], h, cfg,
                                           return_cache=return_cache)
    elif spec.mixer == "slstm":
        y, cache = xlstm_mod.slstm_forward(p["mixer"], h, cfg,
                                           return_cache=return_cache)
    elif spec.mixer == "rglru":
        y, cache = rglru_mod.rglru_forward(p["mixer"], h, cfg,
                                           return_cache=return_cache)
    else:
        raise ValueError(spec.mixer)
    if cfg.tp_out_constraint:
        y = constrain(y, "batch", None, None)
    x = x + y.astype(x.dtype)
    x = constrain(x, "batch", None, None)
    aux = jnp.zeros((), jnp.float32)
    if spec.ffn == "dense":
        f = p["ffn"]
        u = rms_norm(x, f["norm"], cfg.norm_eps)
        y = swiglu(u, f["w_gate"], f["w_up"], f["w_down"])
        if cfg.tp_out_constraint:
            y = constrain(y, "batch", None, None)
        x = x + y
    elif spec.ffn == "moe":
        f = p["ffn"]
        u = rms_norm(x, f["norm"], cfg.norm_eps)
        y, aux = moe_mod.moe_ffn(f["moe"], u, cfg)
        if cfg.tp_out_constraint:
            y = constrain(y, "batch", None, None)
        x = x + y
    x = constrain(x, "batch", None, None)
    return x, aux, cache


def _embed_inputs(params, cfg: ModelConfig, tokens, frontend,
                  compute_dtype):
    emb = params["embed"].astype(compute_dtype)
    x = emb[tokens]                                   # (B, S_text, d)
    if cfg.frontend:
        if frontend is None:
            raise ValueError(f"{cfg.name} requires frontend embeddings")
        x = jnp.concatenate([frontend.astype(compute_dtype), x], axis=1)
    return constrain(x, "batch", None, None)


def forward(params, tokens, cfg: ModelConfig, *, frontend=None,
            compute_dtype=jnp.bfloat16, q_chunk: int = 1024,
            remat: str = "full", logits_slice: Optional[int] = None):
    """tokens: (B, S_text) int32 -> (logits (B, S_out, V), aux-loss scalar).

    ``logits_slice``: if given, only the logits of the last N positions are
    computed (prefill wants just the final position's logits).
    """
    cparams = jax.tree_util.tree_map(
        lambda t: t.astype(compute_dtype)
        if jnp.issubdtype(t.dtype, jnp.floating) else t,
        params,
    )
    x = _embed_inputs(cparams, cfg, tokens, frontend, compute_dtype)

    def unit(x, unit_params):
        """Apply one repeat of the whole pattern."""
        aux_total = jnp.zeros((), jnp.float32)
        for i, spec in enumerate(cfg.pattern):
            x, aux, _ = _apply_layer(unit_params[f"pos_{i}"], x, cfg, spec,
                                     q_chunk, False)
            aux_total = aux_total + aux
        return x, aux_total

    if remat == "full":
        unit = jax.checkpoint(unit,
                              policy=jax.checkpoint_policies.nothing_saveable)
    elif remat == "dots":
        unit = jax.checkpoint(
            unit,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        )

    x, auxs = jax.lax.scan(unit, x, cparams["pattern"])
    aux = jnp.sum(auxs)
    for i, spec in enumerate(cfg.tail):
        x, a, _ = _apply_layer(cparams["tail"][f"layer_{i}"], x, cfg, spec,
                               q_chunk, False)
        aux = aux + a
    x = rms_norm(x, cparams["final_norm"], cfg.norm_eps)
    if logits_slice is not None:
        x = x[:, -logits_slice:]
    logits = _unembed(cparams, x)
    return logits, aux


def _unembed(cparams, x):
    if "unembed" in cparams:
        logits = x @ cparams["unembed"]
    else:
        logits = x @ cparams["embed"].T
    return constrain(logits, "batch", None, "vocab")


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

class DecodeState(NamedTuple):
    """All per-layer decode caches plus the current token position."""

    pattern: Dict[str, Any]   # per pattern position: cache stacked over repeats
    tail: Dict[str, Any]
    pos: jnp.ndarray          # scalar int32: number of tokens already consumed


def _init_layer_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                      max_len: int, dtype):
    if spec.mixer in ("attn", "swa"):
        return attn.init_attn_cache(cfg, spec, batch, max_len, dtype)
    if spec.mixer == "mlstm":
        return xlstm_mod.init_mlstm_state(cfg, batch)
    if spec.mixer == "slstm":
        return xlstm_mod.init_slstm_state(cfg, batch)
    if spec.mixer == "rglru":
        return rglru_mod.init_rglru_state(cfg, batch)
    raise ValueError(spec.mixer)


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int,
                      cache_dtype=jnp.bfloat16) -> DecodeState:
    """Allocate empty decode caches for every layer (stacked over repeats)."""
    pattern = {}
    for i, spec in enumerate(cfg.pattern):
        one = _init_layer_cache(cfg, spec, batch, max_len, cache_dtype)
        pattern[f"pos_{i}"] = jax.tree_util.tree_map(
            lambda t: jnp.broadcast_to(t, (cfg.repeats,) + t.shape).copy(), one
        )
    tail = {
        f"layer_{i}": _init_layer_cache(cfg, spec, batch, max_len, cache_dtype)
        for i, spec in enumerate(cfg.tail)
    }
    return DecodeState(pattern, tail, jnp.zeros((), jnp.int32))


def abstract_decode_state(cfg: ModelConfig, batch: int, max_len: int,
                          cache_dtype=jnp.bfloat16):
    """Shape/dtype tree of init_decode_state without allocating."""
    return jax.eval_shape(
        functools.partial(init_decode_state, cfg, batch, max_len, cache_dtype)
    )


def _decode_layer(p, x, cache, cfg: ModelConfig, spec: LayerSpec, pos,
                  pallas: Optional[str] = None):
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    if spec.mixer in ("attn", "swa"):
        y, cache = attn.attn_decode(p["mixer"], h, cache, cfg, spec, pos,
                                    pallas=pallas)
    elif spec.mixer == "mlstm":
        y, cache = xlstm_mod.mlstm_decode(p["mixer"], h, cache, cfg)
    elif spec.mixer == "slstm":
        y, cache = xlstm_mod.slstm_decode(p["mixer"], h, cache, cfg)
    elif spec.mixer == "rglru":
        y, cache = rglru_mod.rglru_decode(p["mixer"], h, cache, cfg)
    else:
        raise ValueError(spec.mixer)
    x = x + y.astype(x.dtype)
    if spec.ffn == "dense":
        f = p["ffn"]
        u = rms_norm(x, f["norm"], cfg.norm_eps)
        x = x + swiglu(u, f["w_gate"], f["w_up"], f["w_down"])
    elif spec.ffn == "moe":
        f = p["ffn"]
        u = rms_norm(x, f["norm"], cfg.norm_eps)
        y, _ = moe_mod.moe_ffn(f["moe"], u, cfg)
        x = x + y
    return x, cache


def decode_step(params, token, state: DecodeState, cfg: ModelConfig, *,
                compute_dtype=jnp.bfloat16, pallas: Optional[str] = None):
    """One token for the whole batch. token: (B,) int32. Returns
    (logits (B, V), new_state). ``pallas``: see ``attention.attn_decode``."""
    cparams = jax.tree_util.tree_map(
        lambda t: t.astype(compute_dtype)
        if jnp.issubdtype(t.dtype, jnp.floating) else t,
        params,
    )
    x = cparams["embed"][token][:, None, :]          # (B, 1, d)
    pos = state.pos

    def unit(x, xs):
        unit_params, unit_cache = xs
        new_caches = {}
        for i, spec in enumerate(cfg.pattern):
            x, c = _decode_layer(unit_params[f"pos_{i}"], x,
                                 unit_cache[f"pos_{i}"], cfg, spec, pos,
                                 pallas=pallas)
            new_caches[f"pos_{i}"] = c
        return x, new_caches

    x, new_pattern = jax.lax.scan(unit, x, (cparams["pattern"], state.pattern))
    new_tail = {}
    for i, spec in enumerate(cfg.tail):
        x, c = _decode_layer(cparams["tail"][f"layer_{i}"], x,
                             state.tail[f"layer_{i}"], cfg, spec, pos,
                             pallas=pallas)
        new_tail[f"layer_{i}"] = c
    x = rms_norm(x, cparams["final_norm"], cfg.norm_eps)
    logits = _unembed(cparams, x)[:, 0]
    return logits, DecodeState(new_pattern, new_tail, pos + 1)


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def prefill(params, tokens, cfg: ModelConfig, max_len: int, *, frontend=None,
            compute_dtype=jnp.bfloat16, q_chunk: int = 1024,
            cache_dtype=jnp.bfloat16):
    """Run the full prompt, build the decode state, return last-token logits.

    Note: implemented as forward-with-cache per layer (no scan-over-repeats
    here would force cache restacking; instead we reuse the scan and rebuild
    attention caches from the returned raw k/v)."""
    cparams = jax.tree_util.tree_map(
        lambda t: t.astype(compute_dtype)
        if jnp.issubdtype(t.dtype, jnp.floating) else t,
        params,
    )
    x = _embed_inputs(cparams, cfg, tokens, frontend, compute_dtype)
    B, S = x.shape[:2]

    def unit(x, xs):
        unit_params = xs
        caches = {}
        for i, spec in enumerate(cfg.pattern):
            x, _, cache = _apply_layer(unit_params[f"pos_{i}"], x, cfg, spec,
                                       q_chunk, True)
            if spec.mixer in ("attn", "swa"):
                cache = attn.cache_from_prefill(cfg, spec, cache, max_len,
                                                cache_dtype)
            caches[f"pos_{i}"] = cache
        return x, caches

    x, pattern_caches = jax.lax.scan(unit, x, cparams["pattern"])
    tail_caches = {}
    for i, spec in enumerate(cfg.tail):
        x, _, cache = _apply_layer(cparams["tail"][f"layer_{i}"], x, cfg,
                                   spec, q_chunk, True)
        if spec.mixer in ("attn", "swa"):
            cache = attn.cache_from_prefill(cfg, spec, cache, max_len,
                                            cache_dtype)
        tail_caches[f"layer_{i}"] = cache
    x = rms_norm(x, cparams["final_norm"], cfg.norm_eps)
    logits = _unembed(cparams, x[:, -1:])[:, 0]
    state = DecodeState(pattern_caches, tail_caches,
                        jnp.asarray(S, jnp.int32))
    return logits, state
