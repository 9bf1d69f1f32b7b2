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

# grad_fn(params, batch[, cparams]) -> (loss, grads); see ``compute_copy``
GradFn = Callable


class IASGResult(NamedTuple):
    """One IASG sampling pass: stacked samples, final iterate, losses."""

    samples: object        # tree, leading axis = num_samples
    params: object         # final iterate (what FedAvg would return)
    opt_state: object
    burn_in_losses: jnp.ndarray
    sample_losses: jnp.ndarray   # (num_samples, steps_per_sample)


def compute_copy(grad_fn: GradFn, params):
    """``params`` cast to the dtype ``grad_fn`` computes in, or None where
    there is nothing to carry.

    A grad_fn that declares ``compute_dtype`` (``models.steps.lm_grad_fn``)
    casts its params to it before the forward pass, and takes an optional
    third argument, that cast made by the caller: it then differentiates at
    the copy and returns the gradient in the params' dtype, the same values
    as from the params. The local-step loops carry that copy beside the
    params, written by the optimizer's update in the pass that already
    writes the params, so no step casts the params again. Where the params
    already hold the compute dtype (float32 compute, or the sequential
    placement's bf16 client params) the copy would be the params
    themselves: None. Hooks that wrap a grad_fn pass the copy through
    (``functools.wraps`` keeps ``compute_dtype``)."""
    dtype = getattr(grad_fn, "compute_dtype", None)
    floating = lambda x: jnp.issubdtype(x.dtype, jnp.floating)  # noqa: E731
    if dtype is None or all(x.dtype == dtype for x in
                            jax.tree_util.tree_leaves(params) if floating(x)):
        return None
    return tm.tmap(lambda x: x.astype(dtype) if floating(x) else x, params)


def _grad(grad_fn: GradFn, p, cp, batch):
    return grad_fn(p, batch) if cp is None else grad_fn(p, batch, cp)


def _opt_step(p, cp, opt: Optimizer, s, grads):
    """One client-optimizer step: ``(params, compute copy, opt_state)``
    after ``grads``; the copy (None: none carried) is cast from the updated
    params, each leaf to its own dtype."""
    with jax.named_scope("client_opt"):
        updates, s = opt.update(grads, s, p)
        p = tm.tmap(lambda pi, u: pi + u.astype(pi.dtype), p, updates)
        if cp is not None:
            cp = tm.tmap(lambda c, pi: pi.astype(c.dtype), cp, p)
    return p, cp, s


def local_steps(p, cp, s, opt: Optimizer, grad_fn: GradFn, batches):
    """Local SGD over the leading axis of ``batches``, from params ``p``,
    their compute copy ``cp`` (``compute_copy``; None: none carried) and
    optimizer state ``s``. Returns ``(p, cp, s, losses)``."""

    def body(carry, batch):
        p, cp, s = carry
        loss, grads = _grad(grad_fn, p, cp, batch)
        return _opt_step(p, cp, opt, s, grads), loss

    (p, cp, s), losses = jax.lax.scan(body, (p, cp, s), batches)
    return p, cp, s, losses


def sgd_steps(params, opt: Optimizer, opt_state, grad_fn: GradFn, batches):
    """Plain local SGD over the leading axis of ``batches`` (FedAvg client),
    from the broadcast params: the compute copy is cast once, here."""
    params, _, opt_state, losses = local_steps(
        params, compute_copy(grad_fn, params), opt_state, opt, grad_fn,
        batches)
    return params, opt_state, losses


def sample_window(p, cp, s, opt: Optimizer, grad_fn: GradFn, window_batches,
                  sample_dtype=jnp.float32):
    """One IASG window: SGD over the leading axis of ``window_batches``,
    carrying the compute copy ``cp`` as ``local_steps`` does; the sample is
    the Polyak average of the window's iterates, in ``sample_dtype``.
    Returns ``(params, compute copy, opt_state, sample, losses)``.

    Where a copy is carried, each step adds to the sum the iterate it reads
    (but the window's first step, whose iterate is the last window's) and
    the last iterate is added after the loop: the same additions in the same
    order, made in the optimizer's pass over the params, which the copy's
    write would otherwise leave for a pass of its own."""
    carried = cp is not None

    def add(acc, p):
        with jax.named_scope("iasg_average"):
            return tm.tmap(lambda a, pi: a + pi.astype(sample_dtype), acc, p)

    def step(inner, xs):
        batch, first = xs
        p, cp, s, acc = inner
        if carried:
            acc = tm.tmap(lambda a, b: jnp.where(first, a, b), acc,
                          add(acc, p))
        loss, grads = _grad(grad_fn, p, cp, batch)
        p, cp, s = _opt_step(p, cp, opt, s, grads)
        if not carried:
            acc = add(acc, p)
        return (p, cp, s, acc), loss

    steps = jax.tree_util.tree_leaves(window_batches)[0].shape[0]
    acc0 = tm.tzeros_like(p, sample_dtype)
    firsts = jnp.arange(steps) == 0 if carried else None
    (p, cp, s, acc), losses = jax.lax.scan(step, (p, cp, s, acc0),
                                           (window_batches, firsts))
    if carried:
        acc = add(acc, p)
    with jax.named_scope("iasg_average"):
        sample = tm.tscale(1.0 / steps, acc)
    return p, cp, s, sample, losses


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
    cparams = compute_copy(grad_fn, params)
    burn_losses = jnp.zeros((0,))
    if burn_in_steps:
        params, cparams, opt_state, burn_losses = local_steps(
            params, cparams, opt_state, opt, grad_fn,
            split(batches, 0, burn_in_steps))

    # --- sampling: one Polyak-averaged sample per window --------------------
    sample_batches = tm.tmap(
        lambda x: x[burn_in_steps:].reshape(
            (num_samples, steps_per_sample) + x.shape[1:]
        ),
        batches,
    )

    def window(carry, window_batches):
        p, cp, s, sample, losses = sample_window(*carry, opt, grad_fn,
                                                 window_batches, sample_dtype)
        return (p, cp, s), (sample, losses)

    (params, _, opt_state), (samples, sample_losses) = jax.lax.scan(
        window, (params, cparams, opt_state), sample_batches
    )
    return IASGResult(samples, params, opt_state, burn_losses, sample_losses)
