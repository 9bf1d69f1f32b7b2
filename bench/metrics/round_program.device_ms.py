"""round_program.device_ms: device time of the fused round program's
executions per traced round, on the busiest chip."""
from bench import trace as tr


def read(ctx):
    """Milliseconds of round-program execution per round."""
    t, lo, hi = ctx["trace"], ctx["lo"], ctx["hi"]
    per_chip = [tr.module_ns(mods, ctx["round_module"], lo, hi)
                for mods in t.modules.values()]
    if not per_chip or max(per_chip) == 0:
        return None
    return max(per_chip) / ctx["rounds"] / 1e6
