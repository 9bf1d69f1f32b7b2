"""engine.cohort_wait.host_ms: host milliseconds per round that the round
loop (``core/engine.RoundEngine.run``) spends getting the next cohort,
span ``repro.cohort_get``: the prefetcher's queue wait and unpickle, or
the inline build. Read from the program's spans in the trace, over the
rounds it holds whole (``bench/scopes.span_ms``)."""
from bench import scopes


def read(ctx):
    """Mean host milliseconds of ``repro.cohort_get`` per round."""
    return scopes.span_ms(ctx, "cohort_get")
