"""Plain float32 building blocks shared by the reference models.

``rnd`` is applied to both operands of every matmul, to the embedding
table and to the residual stream after each layer, the values the program
holds in its compute type: the identity for the reference, a round trip
through a lower precision for its control. The control rounds the values only: its
gradient passes straight through the rounding, so that the backward
matmuls see the rounded operands and a float32 cotangent, which float8
would flush to zero.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def identity(x):
    """The reference's rounding: none."""
    return x


def rounding_to(dtype):
    """A control's rounding: through ``dtype`` and back to float32."""
    def rnd(x):
        return x + jax.lax.stop_gradient(x.astype(dtype).astype(F32) - x)
    return rnd


def mm(a, b, rnd):
    """``a @ b`` on rounded operands, accumulated in float32."""
    return jnp.matmul(rnd(a), rnd(b), preferred_element_type=F32)


def rms_norm(x, scale, eps):
    """x / rms(x) * (1 + scale) over the last axis."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + scale)


def padded(vocab: int) -> int:
    """Vocabulary rows held by the table: ``vocab`` rounded up to 128."""
    return -(-vocab // 128) * 128


def xent(logits, targets, vocab: int):
    """Mean next-token cross entropy over the first ``vocab`` logits."""
    logits = logits[..., :vocab]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def causal_conv(x, kernel):
    """Depthwise causal conv over the sequence: y[t] = sum_i x[t-W+1+i] k[i]."""
    w = kernel.shape[0]
    xp = jnp.pad(x, ((0, 0), (w - 1, 0), (0, 0)))
    return sum(xp[:, i:i + x.shape[1]] * kernel[i] for i in range(w))


def normal(key, shape, fan_in):
    """N(0, 1/fan_in) float32 weights."""
    return jax.random.normal(key, shape, F32) * (1.0 / jnp.sqrt(fan_in))


def stacked(key, pos: int, repeats: int, init_layer):
    """``repeats`` layers of pattern position ``pos``, stacked on axis 0."""
    keys = jax.random.split(jax.random.fold_in(key, pos), repeats)
    layers = [init_layer(k) for k in keys]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)


def lm_params(key, model: dict, init_mixer, init_ffn):
    """Embedding, final norm and the stacked pattern layers.

    ``key`` is ``jax.random.PRNGKey(seed)``, made outside any jitted call:
    a seed fixed at trace time would let the compiler fold every weight
    into the program as a constant.
    Keys: one split of ``key`` into (embed, pattern, tail, unembed);
    pattern position ``i`` folds ``i`` into its key and splits it over the
    repeats; each layer splits its key into (mixer, ffn)."""
    d, vocab = model["d_model"], padded(model["vocab_size"])
    k_embed, k_pat, _, _ = jax.random.split(key, 4)
    params = {"embed": normal(k_embed, (vocab, d), d),
              "final_norm": jnp.zeros((d,), F32)}
    pattern = {}
    for i, spec in enumerate(model["pattern"]):
        def layer(k, spec=spec):
            k1, k2 = jax.random.split(k)
            return {"norm": jnp.zeros((d,), F32),
                    "mixer": init_mixer(k1, spec),
                    "ffn": init_ffn(k2, spec)}
        pattern[f"pos_{i}"] = stacked(k_pat, i, model["repeats"], layer)
    params["pattern"] = pattern
    return params


def lm_loss(params, tokens, model: dict, rnd, block):
    """Mean cross entropy of a (B, S+1) token batch.

    ``block(layer_params, x, spec)`` applies one layer's mixer and ffn to
    the residual stream; the head is tied to the embedding. Each layer is
    recomputed in the backward pass (``jax.checkpoint``), so that a
    recurrent layer's per-step states are held for one layer at a time."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    block = jax.checkpoint(block, static_argnums=(2,))
    x = rnd(params["embed"])[inputs]
    for r in range(model["repeats"]):
        for i, spec in enumerate(model["pattern"]):
            layer = jax.tree_util.tree_map(lambda t, r=r: t[r],
                                           params["pattern"][f"pos_{i}"])
            x = rnd(block(layer, x, spec))
    x = rms_norm(x, params["final_norm"], model["norm_eps"])
    logits = mm(x, params["embed"].T, rnd)
    return xent(logits, targets, model["vocab_size"])
