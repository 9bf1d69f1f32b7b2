"""Production mesh construction + multi-host ``jax.distributed`` setup.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first jax use,
and ``init_distributed`` must run before the backend spins up.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.configs.base import MULTI_POD, SINGLE_POD, MeshConfig
from repro.sharding import make_mesh


def init_distributed(coordinator: Optional[str] = None,
                     process_id: Optional[int] = None,
                     num_processes: Optional[int] = None) -> bool:
    """Initialize ``jax.distributed`` for a multi-process (multi-host) run.

    Call before any other jax use (device queries included). With
    ``num_processes`` unset/0/1 this is a no-op returning False — the
    single-process paths never pay for it. Returns True after
    ``jax.distributed.initialize`` connects this process to the
    coordinator, at which point ``jax.devices()`` spans every host (each
    host's own slice is ``jax.local_devices()``) and collectives cross
    processes. On the CPU backend the gloo collectives implementation is
    selected first (the default ring transport has no cross-host story),
    which is what the 2-process smoke test runs on.
    """
    if not num_processes or num_processes <= 1:
        return False
    if coordinator is None or process_id is None:
        raise ValueError(
            "multi-process launch needs --coordinator host:port and "
            "--process-id (0..num_processes-1) on every process")
    if not 0 <= process_id < num_processes:
        raise ValueError(
            f"process_id {process_id} out of range for "
            f"{num_processes} processes")
    # probing the backend here would initialize it too early; the option
    # is CPU-only and inert elsewhere, so set it unconditionally
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    return True


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading "pod"
    axis. Axis semantics: ("pod",) "data" carry federated clients / batch;
    "model" is tensor/expert parallel.

    With 512 placeholder devices forced (the dry-run), the single-pod mesh
    uses the first 256 — pod 0."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import (launch/dryrun.py does this)"
        )
    return make_mesh(shape, axes, devices=devices)


def mesh_from_config(mc: MeshConfig):
    """Materialize a ``MeshConfig`` as a jax mesh over the visible devices."""
    return make_mesh(mc.shape, mc.axes)


def mesh_config(multi_pod: bool = False) -> MeshConfig:
    """The production ``MeshConfig`` for one pod or the two-pod slice."""
    return MULTI_POD if multi_pod else SINGLE_POD


def make_host_mesh():
    """Whatever devices exist, as a 1D ("data",) mesh — CPU simulation."""
    n = jax.device_count()
    return make_mesh((n,), ("data",))
