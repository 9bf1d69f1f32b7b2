"""client_backward.device_ms: device self time of the client steps'
backward pass per traced round, on the busiest chip: ops under the
``client_grad`` scope (``models/steps.lm_grad_fn``) whose name stack holds
``transpose(``, remat's recomputed forward among them. A fused op counts
under its fusion's ``op_name`` (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    """Milliseconds of the client backward pass per round."""
    return scopes.scope_ms(ctx, "client_grad", backward=True)
