"""client_forward.device_ms: device self time of the client steps' forward
pass per traced round, on the busiest chip: ops under the ``client_grad``
scope (``models/steps.lm_grad_fn``) whose name stack holds no
``transpose(``. A fused op counts under its fusion's ``op_name``; the
forward that remat recomputes inside the backward pass is backward time
(``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    """Milliseconds of the client forward pass per round."""
    return scopes.scope_ms(ctx, "client_grad", backward=False)
