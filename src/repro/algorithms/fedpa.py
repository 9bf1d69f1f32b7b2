"""FedPA as a registered algorithm (Algorithms 3 and 4, Appendix C).

IASG posterior sampling + the shrinkage-covariance Sherman-Morrison DP for
the client delta. ``fed.streaming_dp=True`` selects the online/any-time DP
variant (Appendix C): each IASG sample is absorbed into the DP state as its
window closes, so the l x d stacked-sample buffer never exists. Burn-in
rounds run the FedAvg regime (Section 5.2).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax

from repro.algorithms.base import (ClientResult, FedAlgorithm,
                                   get_algorithm_class, register_algorithm)
from repro.core import tree_math as tm
from repro.core.dp_delta import (dp_delta, online_dp_delta, online_dp_init,
                                 online_dp_update)
from repro.core.iasg import (compute_copy, iasg_sample, local_steps,
                             sample_window)
from repro.optim import Optimizer


@register_algorithm("fedpa")
class FedPA(FedAlgorithm):
    """Posterior averaging with the shrinkage-DP delta."""

    supports_streaming_dp = True
    has_burn_regime = True
    supports_step_budgets = True

    @property
    def num_samples(self) -> int:
        """l: posterior samples per client per round (one per IASG window)."""
        fed = self.fed
        return (fed.local_steps - fed.burn_in_steps) // fed.steps_per_sample

    def validate(self) -> None:
        """Reject configs whose local steps don't form whole IASG windows."""
        super().validate()
        if self.num_samples < 1:
            # equality IS valid: local_steps == burn_in_steps +
            # steps_per_sample gives exactly one IASG window (l = 1)
            raise ValueError(
                "fedpa needs local_steps >= burn_in_steps + steps_per_sample"
            )
        fed = self.fed
        sampling_steps = fed.local_steps - fed.burn_in_steps
        if sampling_steps % fed.steps_per_sample != 0:
            raise ValueError(
                f"fedpa sampling steps must divide into whole IASG "
                f"windows: local_steps - burn_in_steps = "
                f"{fed.local_steps} - {fed.burn_in_steps} = "
                f"{sampling_steps} is not a multiple of "
                f"steps_per_sample = {fed.steps_per_sample} "
                f"({sampling_steps % fed.steps_per_sample} leftover "
                f"batches)")

    def burn_algorithm(self) -> FedAlgorithm:
        """FedAvg on the same client/server knobs (the burn-in regime)."""
        return get_algorithm_class("fedavg")(dataclasses.replace(
            self.fed, algorithm="fedavg", streaming_dp=False))

    def make_client_update(self, grad_fn: Callable,
                           client_opt: Optimizer) -> Callable:
        """IASG sampling + shrinkage-DP delta (batch or streaming DP)."""
        if self.fed.streaming_dp:
            return self._make_streaming_update(grad_fn, client_opt)
        return self._make_batch_update(grad_fn, client_opt)

    # -- batch DP (Algorithm 4 + Theorem 3) ---------------------------------
    def _iasg_delta(self, grad_fn, client_opt):
        """Build ``run(params, batches) -> (delta, iasg_result, metrics)``.

        One IASG sampling pass plus the shrinkage-DP delta — the shared
        core of the batch FedPA client and of subclasses that derive extra
        statistics from the samples (``fedpa_precision``).
        """
        fed = self.fed
        delta_dtype = self.delta_dtype
        num_samples = self.num_samples

        def run(params, batches):
            opt_state = client_opt.init(params)
            res = iasg_sample(
                params, client_opt, opt_state, grad_fn, batches,
                burn_in_steps=fed.burn_in_steps,
                steps_per_sample=fed.steps_per_sample,
                num_samples=num_samples,
                sample_dtype=delta_dtype,
            )
            # dp_delta's fp32 scalar coefficients promote bf16 leaves to fp32
            # (jnp weak-typing); pin the configured dtype so scan carries match
            delta = tm.tcast(
                dp_delta(tm.tcast(params, delta_dtype), res.samples,
                         fed.shrinkage_rho),
                delta_dtype,
            )
            first = res.burn_in_losses[0] if fed.burn_in_steps else \
                res.sample_losses[0, 0]
            return delta, res, {"loss_first": first,
                                "loss_last": res.sample_losses[-1, -1]}

        return run

    def _make_batch_update(self, grad_fn, client_opt):
        """Samples stacked first, then the batch DP (``dp_delta``)."""
        run = self._iasg_delta(grad_fn, client_opt)

        def update(params, batches):
            delta, _, metrics = run(params, batches)
            return ClientResult(delta, metrics)

        return update

    # -- streaming / any-time DP (Appendix C) -------------------------------
    def _make_streaming_update(self, grad_fn, client_opt):
        """Each IASG sample is absorbed into the Sherman-Morrison state as
        soon as its window closes — the l x d stacked-sample buffer never
        exists. Numerically identical to the batch DP
        (tests/test_streaming_and_mime.py)."""
        fed = self.fed
        delta_dtype = self.delta_dtype
        ell = self.num_samples
        rho = fed.shrinkage_rho
        K_s = fed.steps_per_sample

        def update(params, batches):
            opt_state = client_opt.init(params)
            split = lambda tree, a, b: tm.tmap(lambda x: x[a:b], tree)
            p, cp, s = params, compute_copy(grad_fn, params), opt_state
            loss_first = None
            if fed.burn_in_steps:
                p, cp, s, burn = local_steps(
                    p, cp, s, client_opt, grad_fn,
                    split(batches, 0, fed.burn_in_steps))
                loss_first = burn[0]
            windows = tm.tmap(
                lambda x: x[fed.burn_in_steps:].reshape(
                    (ell, K_s) + x.shape[1:]),
                batches,
            )
            dp0 = online_dp_init(tm.tcast(params, delta_dtype), ell,
                                 dtype=delta_dtype)

            def window(carry, wb):
                p, cp, s, dp = carry
                # The IASG sample space IS delta_dtype by contract, as in
                # the batch path; the fp32 accumulation happens downstream
                # in the Sherman-Morrison online-DP state.
                p, cp, s, sample, losses = sample_window(
                    p, cp, s, client_opt, grad_fn, wb, delta_dtype)
                dp = online_dp_update(dp, sample, rho)
                return (p, cp, s, dp), losses

            (p, cp, s, dp), losses = jax.lax.scan(window, (p, cp, s, dp0),
                                                  windows)
            delta = tm.tcast(online_dp_delta(dp, rho), delta_dtype)
            first = loss_first if loss_first is not None else losses[0, 0]
            return ClientResult(delta, {"loss_first": first,
                                        "loss_last": losses[-1, -1]})

        return update
