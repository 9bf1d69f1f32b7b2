"""The benchmark's fixed arithmetic: chip peaks, model flops, percentiles.

Kept with the benchmark so that a change to the program cannot move the
ruler it is measured with.
"""
from __future__ import annotations

import statistics

#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peak(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown chip is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def tokens_per_round(clients: int, local_steps: int, batch: int,
                     seq_len: int) -> int:
    """Training tokens one federated round consumes: C * K * B * S."""
    return clients * local_steps * batch * seq_len


def model_flops(params: int, tokens: int) -> float:
    """Forward and backward flops the model needs: 6 * N * tokens.

    Recomputation (remat), attention's S^2 term, the eval pass, the
    optimizer and FedPA's sampling and shrinkage are not counted."""
    return 6.0 * params * tokens


def p90(values) -> float:
    """90th percentile (linear interpolation between order statistics)."""
    values = list(values)
    if len(values) < 2:
        raise ValueError(f"need at least 2 values for a percentile, "
                         f"got {len(values)}")
    return statistics.quantiles(values, n=10, method="inclusive")[8]

