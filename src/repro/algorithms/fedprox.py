"""FedProx as a registered algorithm (Li et al. 2020).

Each client minimizes its local objective plus a proximal anchor to the
broadcast iterate, f_i(theta) + (mu/2) ||theta - theta_0||^2, which bounds
client drift under heterogeneity without any server-side change. In the
paper's posterior framing this is MAP inference against an isotropic
Gaussian prior centered at the server iterate — another instance of the
same local-inference template, which is why it drops into the strategy API
as a pure client-side override.
"""
from __future__ import annotations

import functools
from typing import Callable

from repro.algorithms.base import (ClientResult, FedAlgorithm,
                                   register_algorithm)
from repro.core import tree_math as tm
from repro.core.dp_delta import fedavg_delta
from repro.core.iasg import sgd_steps
from repro.optim import Optimizer


@register_algorithm("fedprox")
class FedProx(FedAlgorithm):
    """FedAvg with a proximal term in the local step (``fed.fedprox_mu``)."""

    def validate(self) -> None:
        """Proximal strength must be non-negative (0 reduces to FedAvg)."""
        super().validate()
        if self.fed.fedprox_mu < 0.0:
            raise ValueError(
                f"fedprox_mu must be >= 0, got {self.fed.fedprox_mu}")

    def make_client_update(self, grad_fn: Callable,
                           client_opt: Optimizer) -> Callable:
        """K local steps on the proximally-regularized objective."""
        mu = self.fed.fedprox_mu
        delta_dtype = self.delta_dtype

        def update(params, batches):
            @functools.wraps(grad_fn)
            def prox_grad_fn(p, batch, *cparams):
                loss, grads = grad_fn(p, batch, *cparams)
                grads = tm.tmap(
                    lambda g, pi, p0: g + (mu * (pi - p0)).astype(g.dtype),
                    grads, p, params)
                return loss, grads

            opt_state = client_opt.init(params)
            final, _, losses = sgd_steps(params, client_opt, opt_state,
                                         prox_grad_fn, batches)
            delta = tm.tcast(fedavg_delta(params, final), delta_dtype)
            return ClientResult(delta, {"loss_first": losses[0],
                                        "loss_last": losses[-1]})

        return update
