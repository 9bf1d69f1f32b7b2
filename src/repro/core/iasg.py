"""IASG — Iterate-Averaged Stochastic Gradient MCMC (Algorithm 4).

SGD with a fixed learning rate, viewed as a Markov chain whose stationary
distribution approximates the local posterior (Mandt et al. 2017): run B
burn-in steps, then emit one approximate posterior sample per K-step window
as the Polyak average of that window's iterates.

Everything is ``lax.scan``-based so a client's full local computation is one
compiled program; batches arrive with a leading step axis.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import tree_math as tm
from repro.optim import Optimizer

# grad_fn(params, batch) -> (loss, grads)
GradFn = Callable


class IASGResult(NamedTuple):
    """One IASG sampling pass: stacked samples, final iterate, losses."""

    samples: object        # tree, leading axis = num_samples
    params: object         # final iterate (what FedAvg would return)
    opt_state: object
    burn_in_losses: jnp.ndarray
    sample_losses: jnp.ndarray   # (num_samples, steps_per_sample)


def _opt_step(p, opt: Optimizer, s, grads):
    """One client-optimizer step: ``(params, opt_state)`` after ``grads``."""
    with jax.named_scope("client_opt"):
        updates, s = opt.update(grads, s, p)
        p = tm.tmap(lambda pi, u: pi + u.astype(pi.dtype), p, updates)
    return p, s


def sgd_steps(params, opt: Optimizer, opt_state, grad_fn: GradFn, batches):
    """Plain local SGD over the leading axis of ``batches`` (FedAvg client)."""

    def body(carry, batch):
        p, s = carry
        loss, grads = grad_fn(p, batch)
        return _opt_step(p, opt, s, grads), loss

    (params, opt_state), losses = jax.lax.scan(body, (params, opt_state), batches)
    return params, opt_state, losses


def sample_window(p, s, opt: Optimizer, grad_fn: GradFn, window_batches,
                  sample_dtype=jnp.float32):
    """One IASG window: SGD over the leading axis of ``window_batches``;
    the sample is the Polyak average of the window's iterates, in
    ``sample_dtype``. Returns ``(params, opt_state, sample, losses)``."""

    def step(inner, batch):
        p, s, acc = inner
        loss, grads = grad_fn(p, batch)
        p, s = _opt_step(p, opt, s, grads)
        with jax.named_scope("iasg_average"):
            acc = tm.tmap(lambda a, pi: a + pi.astype(sample_dtype), acc, p)
        return (p, s, acc), loss

    acc0 = tm.tzeros_like(p, sample_dtype)
    (p, s, acc), losses = jax.lax.scan(step, (p, s, acc0), window_batches)
    steps = jax.tree_util.tree_leaves(window_batches)[0].shape[0]
    with jax.named_scope("iasg_average"):
        sample = tm.tscale(1.0 / steps, acc)
    return p, s, sample, losses


def iasg_sample(
    params,
    opt: Optimizer,
    opt_state,
    grad_fn: GradFn,
    batches,
    burn_in_steps: int,
    steps_per_sample: int,
    num_samples: int,
    sample_dtype=jnp.float32,
) -> IASGResult:
    """Algorithm 4. ``batches`` must have leading axis
    burn_in_steps + num_samples * steps_per_sample."""
    total = burn_in_steps + num_samples * steps_per_sample
    lead = jax.tree_util.tree_leaves(batches)[0].shape[0]
    if lead != total:
        raise ValueError(f"need {total} batches, got {lead}")

    split = lambda tree, a, b: tm.tmap(lambda x: x[a:b], tree)

    # --- burn-in: mix the chain into the stationary region -----------------
    burn_losses = jnp.zeros((0,))
    if burn_in_steps:
        params, opt_state, burn_losses = sgd_steps(
            params, opt, opt_state, grad_fn, split(batches, 0, burn_in_steps)
        )

    # --- sampling: one Polyak-averaged sample per window --------------------
    sample_batches = tm.tmap(
        lambda x: x[burn_in_steps:].reshape(
            (num_samples, steps_per_sample) + x.shape[1:]
        ),
        batches,
    )

    def window(carry, window_batches):
        p, s, sample, losses = sample_window(*carry, opt, grad_fn,
                                             window_batches, sample_dtype)
        return (p, s), (sample, losses)

    (params, opt_state), (samples, sample_losses) = jax.lax.scan(
        window, (params, opt_state), sample_batches
    )
    return IASGResult(samples, params, opt_state, burn_losses, sample_losses)
