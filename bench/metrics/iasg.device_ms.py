"""iasg.device_ms: device self time of IASG sample averaging per traced
round, on the busiest chip: ops under the ``iasg_average`` scope
(``core/iasg.sample_window``: the iterates' sum and its scaling). A fused
op counts under its fusion's ``op_name`` (``bench/scopes.py``). A round
without IASG (FedAvg, the control) reads 0."""
from bench import scopes


def read(ctx):
    """Milliseconds of IASG averaging per round."""
    return scopes.scope_ms(ctx, "iasg_average")
