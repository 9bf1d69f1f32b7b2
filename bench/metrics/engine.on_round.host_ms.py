"""engine.on_round.host_ms: host milliseconds per round in the round
loop's ``on_round`` callback (``core/engine.RoundEngine.run``), span
``repro.on_round``: ``launch.train``'s metrics sync, log record and
checkpoint hook. Read from the program's spans in the trace, over the
rounds it holds whole (``bench/scopes.span_ms``)."""
from bench import scopes


def read(ctx):
    """Mean host milliseconds of ``repro.on_round`` per round."""
    return scopes.span_ms(ctx, "on_round")
