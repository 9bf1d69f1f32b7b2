"""engine.dispatch.host_ms: host milliseconds per round that the round
loop (``core/engine.RoundEngine.run``) spends enqueueing the round
program, its operands' transfer among it, span ``repro.dispatch``. Read
from the program's spans in the trace, over the rounds it holds whole
(``bench/scopes.span_ms``)."""
from bench import scopes


def read(ctx):
    """Mean host milliseconds of ``repro.dispatch`` per round."""
    return scopes.span_ms(ctx, "dispatch")
