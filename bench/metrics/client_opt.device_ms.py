"""client_opt.device_ms: device self time of the client optimizer per
traced round, on the busiest chip: ops under the ``client_opt`` scope
(``core/iasg._opt_step``: ``opt.update`` and the params add of every local
step). A fused op counts under its fusion's ``op_name``
(``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    """Milliseconds of the client optimizer per round."""
    return scopes.scope_ms(ctx, "client_opt")
