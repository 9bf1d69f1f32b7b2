"""aggregate_server.device_ms: device self time of the cohort fold and the
server step per traced round, on the busiest chip: ops under the
``aggregate`` scope (``core/round_program``: the placement's fold and
``finish_cohort``) and the ``server_update`` scope (``server_fn``). A
fused op counts under its fusion's ``op_name`` (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    """Milliseconds of aggregation and server update per round."""
    return scopes.scope_ms(ctx, "aggregate", "server_update")
