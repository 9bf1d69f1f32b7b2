"""param_cast.device_ms: device self time per traced round, on the busiest
chip, of the round program's standalone casts of parameter size: kernels
that only convert a tensor of at least 2**20 elements to another dtype
(``bench/casts.py``), such as a client step's float32 params cast to the
compute dtype outside the optimizer's pass."""
from bench import casts


def read(ctx):
    """Milliseconds of standalone parameter-sized casts per round."""
    return casts.cast_ms(ctx)
