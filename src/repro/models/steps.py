"""Loss / step functions consumed by the federated round, the smoke tests,
and the dry-run."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import model as model_lib
from repro.models.layers import softmax_cross_entropy


def lm_loss(params, batch, cfg: ModelConfig, *, compute_dtype=jnp.bfloat16,
            q_chunk: int = 1024, remat: str = "full"):
    """batch: {"tokens": (B, S_text+1) int32, ["frontend": (B, F, d)]}.
    Returns (total_loss, metrics)."""
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits, aux = model_lib.forward(
        params, inputs, cfg, frontend=batch.get("frontend"),
        compute_dtype=compute_dtype, q_chunk=q_chunk, remat=remat,
    )
    if cfg.frontend:
        logits = logits[:, cfg.frontend_tokens:]
    xent = softmax_cross_entropy(logits, targets, valid_vocab=cfg.vocab_size)
    aux_w = cfg.moe.router_aux_weight if cfg.moe.enabled else 0.0
    total = xent + aux_w * aux
    return total, {"xent": xent, "moe_aux": aux}


def lm_grad_fn(cfg: ModelConfig, **kw):
    """The (loss, grads) client gradient function FedAvg/FedPA scan over."""
    def fn(params, batch):
        (loss, _), grads = jax.value_and_grad(
            functools.partial(lm_loss, cfg=cfg, **kw), has_aux=True
        )(params, batch)
        return loss, grads
    return fn


def centralized_train_step(params, opt_state, batch, cfg: ModelConfig, opt,
                           **kw):
    """Plain (non-federated) SGD step — the MB-SGD baseline of Fig. 1 at LM
    scale, and the smoke tests' single-step sanity check."""
    (loss, metrics), grads = jax.value_and_grad(
        functools.partial(lm_loss, cfg=cfg, **kw), has_aux=True
    )(params, batch)
    updates, opt_state = opt.update(grads, opt_state, params)
    params = jax.tree_util.tree_map(
        lambda p, u: p + u.astype(p.dtype), params, updates
    )
    return params, opt_state, loss, metrics


def serve_step(params, token, state, cfg: ModelConfig, *,
               compute_dtype=jnp.bfloat16, sample: bool = False,
               rng: Optional[jax.Array] = None, temperature: float = 1.0,
               pallas: Optional[str] = None):
    """One decode step for a batch of requests. token: (B,) int32.
    Returns (next_token (B,), logits (B, V), new_state). ``pallas``: see
    ``attention.attn_decode``."""
    logits, state = model_lib.decode_step(params, token, state, cfg,
                                          compute_dtype=compute_dtype,
                                          pallas=pallas)
    # padded vocab rows must never be sampled
    pad_mask = jnp.arange(logits.shape[-1]) >= cfg.vocab_size
    logits = jnp.where(pad_mask, -1e30, logits.astype(jnp.float32))
    if sample:
        nxt = jax.random.categorical(rng, logits / temperature, axis=-1)
    else:
        nxt = jnp.argmax(logits, axis=-1)
    return nxt.astype(jnp.int32), logits, state


def prefill_step(params, tokens, cfg: ModelConfig, max_len: int, *,
                 frontend=None, compute_dtype=jnp.bfloat16,
                 q_chunk: int = 1024):
    """Prompt ingestion: returns (last-token logits, decode state)."""
    return model_lib.prefill(params, tokens, cfg, max_len, frontend=frontend,
                             compute_dtype=compute_dtype, q_chunk=q_chunk)
