"""SCAFFOLD as a registered algorithm (Karimireddy et al. 2020).

The canonical *stateful* federated algorithm: every client keeps a control
variate ``c_i`` (its running estimate of its own drift) and the server
keeps the population mean ``c``; each local step is corrected by
``c - c_i``, cancelling the client-drift bias that makes FedAvg converge
to a heterogeneity-weighted fixed point instead of the global optimum. In
the paper's posterior framing the correction de-biases local inference
toward the *global* posterior mode — exactly the bias FedPA attacks with
covariance estimates, attacked instead with first-order state.

State placement in this codebase:

* ``c_i`` lives in the engine's per-client ``ClientStateStore``
  (``init_client_state`` / the ``client_state`` update argument /
  ``ClientResult.state_update``);
* ``c`` lives in ``ServerState.algo_state`` (``init_algo_state``), is
  broadcast to the cohort through the ``broadcast`` hook, and is updated
  in ``server_update`` from the aggregated ``dc`` half of the payload:
  ``c += scaffold_c_scale * mean_i(c_i^+ - c_i)`` (the exact rule's
  ``|S|/N`` factor is the config knob — 1.0 under full participation).

Clients use *option II* of the paper: after K corrected SGD steps,
``c_i^+ = c_i - c + (theta_0 - theta_K) / (K * lr)`` — the running mean of
the uncorrected local gradients — which reuses the already-computed delta
instead of a second gradient pass.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.algorithms.base import (ClientResult, FedAlgorithm,
                                   register_algorithm)
from repro.core import server as server_lib
from repro.core import tree_math as tm
from repro.core.iasg import sgd_steps
from repro.optim import Optimizer


@register_algorithm("scaffold")
class Scaffold(FedAlgorithm):
    """SCAFFOLD: client + server control variates, option II correction."""

    stateful = True

    def validate(self) -> None:
        """Option II's closed form assumes plain SGD local steps."""
        super().validate()
        if self.fed.client_opt != "sgd":
            raise ValueError(
                f"scaffold requires client_opt='sgd': the option II control "
                f"variate c_i+ = c_i - c + delta/(K*lr) is the mean local "
                f"gradient only for vanilla SGD steps, got "
                f"{self.fed.client_opt!r}")
        if not 0.0 < self.fed.scaffold_c_scale <= 1.0:
            raise ValueError(
                f"scaffold_c_scale must be in (0, 1] (it is |S|/N of the "
                f"exact rule), got {self.fed.scaffold_c_scale}")

    # -- persistent state ----------------------------------------------------
    def init_client_state(self, params):
        """Client control variate c_i (zeros).

        Kept in fp32 REGARDLESS of ``delta_dtype``: the variates are
        running sums updated every participation, and re-rounding them to
        bf16 per round would stall the drift correction once per-round
        increments fall below one ulp — the same per-fold re-rounding the
        fp32 accumulator contract exists to prevent.
        """
        return tm.tzeros_like(params, jnp.float32)

    def init_algo_state(self, params):
        """Server control variate c = mean_i c_i (zeros, fp32 like c_i)."""
        return tm.tzeros_like(params, jnp.float32)

    def broadcast(self, state, server_opt: Optimizer) -> tuple:
        """Ship the server control variate c to the cohort."""
        del server_opt
        return (state.algo_state,)

    # -- client --------------------------------------------------------------
    def make_client_update(self, grad_fn: Callable,
                           client_opt: Optimizer) -> Callable:
        """``update(params, batches, c_i, c) -> ClientResult``.

        K SGD steps on the corrected gradient ``g + c - c_i``; payload is
        ``{"delta": theta_0 - theta_K, "dc": c_i^+ - c_i}`` and the state
        update is ``c_i^+`` (option II).
        """
        lr = self.fed.client_lr
        K = self.fed.local_steps
        delta_dtype = self.delta_dtype

        def update(params, batches, c_i, c):
            @functools.wraps(grad_fn)
            def corrected_grad(p, batch, *cparams):
                loss, g = grad_fn(p, batch, *cparams)
                g = tm.tmap(
                    lambda gi, cs, ci: gi + (cs - ci).astype(gi.dtype),
                    g, c, c_i)
                return loss, g

            opt_state = client_opt.init(params)
            final, _, losses = sgd_steps(params, client_opt, opt_state,
                                         corrected_grad, batches)
            # the control variate folds the UNcast fp32 delta (c_i and c
            # are fp32 persistent state, see init_client_state); only the
            # shipped delta gets the wire dtype
            delta32 = tm.tmap(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                params, final)
            c_new = tm.tmap(lambda ci, cs, d: ci - cs + d / (K * lr),
                            c_i, c, delta32)
            payload = {"delta": tm.tcast(delta32, delta_dtype),
                       "dc": tm.tsub(c_new, c_i)}
            return ClientResult(payload, {"loss_first": losses[0],
                                          "loss_last": losses[-1]},
                                state_update=c_new)

        return update

    # -- aggregation ---------------------------------------------------------
    def init_accum(self, params):
        """fp32 accumulator over both payload halves (delta and dc)."""
        return {"delta": tm.tzeros_like(params, jnp.float32),
                "dc": tm.tzeros_like(params, jnp.float32)}

    def finalize(self, agg):
        """Pseudo-gradient = the mean-delta half, cast once."""
        return tm.tcast(agg["delta"], self.delta_dtype)

    def map_components(self, fn: Callable, obj):
        """Payloads/accumulators are dicts of parameter-shaped trees."""
        return {k: fn(v) for k, v in obj.items()}

    def abstract_payload(self, params):
        """Uplink = wire-dtype delta + fp32 control-variate update."""
        return {
            "delta": jax.eval_shape(
                lambda p: tm.tcast(p, self.delta_dtype), params),
            "dc": jax.eval_shape(
                lambda p: tm.tzeros_like(p, jnp.float32), params),
        }

    def abstract_broadcast_extras(self, params):
        """Downlink extra: the fp32 server control variate c."""
        return (jax.eval_shape(
            lambda p: tm.tzeros_like(p, jnp.float32), params),)

    # -- server --------------------------------------------------------------
    def server_update(self, state, agg, server_opt: Optimizer,
                      discount=None):
        """Server step on the mean delta + control-variate update.

        ``c += scaffold_c_scale * mean_i(dc_i)``; a staleness ``discount``
        scales both the pseudo-gradient and the dc mean (a stale cohort's
        drift estimate is down-weighted exactly like its delta).
        """
        pseudo_grad = self.finalize(agg)
        dc = agg["dc"]
        if discount is not None:
            d = jnp.asarray(discount, jnp.float32)
            pseudo_grad = tm.tmap(
                lambda x: (d * x.astype(jnp.float32)).astype(x.dtype),
                pseudo_grad)
            dc = tm.tmap(lambda x: d * x, dc)
        scale = self.fed.scaffold_c_scale
        # c is fp32 persistent state, dc an fp32 accumulator: no rounding
        c = tm.tmap(lambda cs, dci: cs + scale * dci, state.algo_state, dc)
        new_state = server_lib.server_update(state, pseudo_grad, server_opt)
        return new_state._replace(algo_state=c)

    # payload_accum is the identity: {"delta", "dc"} is already linear.
