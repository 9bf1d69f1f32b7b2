"""dp_delta.device_ms: device self time of FedPA's shrinkage DP per traced
round, on the busiest chip: ops under the ``dp_delta`` scope
(``core/dp_delta``: ``dp_delta``, ``online_dp_update``,
``online_dp_delta``). A fused op counts under its fusion's ``op_name``
(``bench/scopes.py``). A round without the DP (FedAvg, the control)
reads 0."""
from bench import scopes


def read(ctx):
    """Milliseconds of the DP delta per round."""
    return scopes.scope_ms(ctx, "dp_delta")
