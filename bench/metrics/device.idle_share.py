"""device.idle_share: share of the traced window in which no operation ran
on the chip, as 100 * (1 - union of device-op intervals / window), averaged
over the cell's chips."""
from bench import trace as tr


def read(ctx):
    """Percent of the traced window the device sat idle."""
    t, lo, hi = ctx["trace"], ctx["lo"], ctx["hi"]
    if not t.ops:
        return None
    busy = [tr.busy_ns(ops, lo, hi) for ops in t.ops.values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
