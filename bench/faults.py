"""Faults planted in the program, to show that the check catches them.

Each fault is a context manager that patches one function of the program
for as long as it is open; the round programs built inside it trace the
broken function. Used by ``bench/calibrate.py`` on the chip and by the CPU
tests, never by a benchmark run.

* ``unchanged``: the server step returns its state unchanged;
* ``half_batch``: each local step's loss, and so its gradient, is the mean
  over the first half of the batch's rows only;
* ``answer``: every client's delta is altered where it is produced (the
  first element of every leaf is raised by 1).

The exchange between chips does not exist in a one-chip cell.
"""
from __future__ import annotations

import contextlib

import jax


@contextlib.contextmanager
def _patched(module, name, make):
    """Patch ``module.name`` for as long as the context is open. JAX's
    caches are cleared on the way in and out: a program traced through the
    broken function must not outlive the fault in this process."""
    orig = getattr(module, name)
    jax.clear_caches()
    setattr(module, name, make(orig))
    try:
        yield
    finally:
        setattr(module, name, orig)
        jax.clear_caches()


def _raise_first(tree):
    return jax.tree_util.tree_map(
        lambda d: d.at[(0,) * d.ndim].add(1.0), tree)


@contextlib.contextmanager
def unchanged():
    """The server step keeps params and optimizer state as they were."""
    from repro.core import server  # noqa: PLC0415

    def make(_orig):
        return lambda state, *a, **k: state._replace(round=state.round + 1)
    with _patched(server, "server_update", make):
        yield


@contextlib.contextmanager
def half_batch():
    """Local steps see the first half of each batch's rows."""
    from repro.models import steps  # noqa: PLC0415

    def make(orig):
        def loss(params, batch, cfg, **kw):
            half = batch["tokens"].shape[0] // 2
            return orig(params, dict(batch, tokens=batch["tokens"][:half]),
                        cfg, **kw)
        return loss
    with _patched(steps, "lm_loss", make):
        yield


@contextlib.contextmanager
def answer():
    """Each client's delta leaves its client altered."""
    from repro.algorithms import fedavg, fedpa  # noqa: PLC0415

    def make(orig):
        return lambda *a, **k: _raise_first(orig(*a, **k))
    with _patched(fedavg, "fedavg_delta", make), \
            _patched(fedpa, "dp_delta", make):
        yield


FAULTS = {"unchanged": unchanged, "half_batch": half_batch, "answer": answer}
