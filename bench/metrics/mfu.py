"""mfu: the whole round's model flops (6 N tokens, bench/yardstick.py) per
second of the traced window, over the chips' bf16 peak."""


def read(ctx):
    """Percent of the chips' bf16 peak the rounds' model flops reach."""
    seconds = (ctx["hi"] - ctx["lo"]) / 1e9
    if ctx["rounds"] == 0 or seconds <= 0:
        return None
    achieved = ctx["flops_per_round"] * ctx["rounds"] / seconds
    return 100.0 * achieved / (ctx["chips"] * ctx["peak"]["bf16_flops_per_s"])
