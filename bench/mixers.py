"""Device time of a model's own recurrences, by the scope that holds each.

``models/xlstm.py`` runs its two recurrences under ``jax.named_scope``s of
their own: ``slstm_scan`` (the sLSTM's scan over time steps) and
``mlstm_chunk`` (the mLSTM's scan over chunks). Both nest inside
``client_grad``, and ``bench/scopes.py``, whose innermost scope decides
``client_forward`` and ``client_backward``, leaves them out of its
``SCOPES``: those two go on counting the recurrences, and this module
reads the recurrences beside them.

An op counts for a mixer where its name stack holds the mixer's scope as
a component, maybe wrapped in transformations: the forward, remat's
recompute and the transposed pass alike. Self time (``scopes.self_ns``),
ms per traced round on the busiest chip, as ``scopes.scope_ms`` reads.
"""
from __future__ import annotations

import functools
import re
from typing import Optional

from bench import scopes
from bench import trace as tr

#: The recurrences' scopes, innermost wins where they nest.
MIXERS = ("slstm_scan", "mlstm_chunk")
_MIXER = re.compile(r"(?:^|(?<=[/(;]))(" + "|".join(MIXERS) + r")(?=[/);]|$)")


@functools.lru_cache(maxsize=4096)
def mixer_of(stack: str) -> Optional[str]:
    """The innermost of ``MIXERS`` in a name stack (None: under none)."""
    found = _MIXER.findall(stack)
    return found[-1] if found else None


def _split(t: scopes.ScopedTrace, lo: float, hi: float) -> dict:
    """{plane: {mixer: self ns}} of the ops in [lo, hi)."""
    split = {}
    for plane, ops in t.ops.items():
        kept = [(op, stack) for op, stack in zip(ops, t.stacks[plane])
                if op[2] > lo and op[1] < hi]
        acc = split.setdefault(plane, {})
        for (_, stack), ns in zip(kept, scopes.self_ns(
                tr.clip([op for op, _ in kept], lo, hi))):
            mixer = mixer_of(stack)
            acc[mixer] = acc.get(mixer, 0) + ns
    return split


def mixer_ms(ctx, mixer: str) -> Optional[float]:
    """Self time of the ops under scope ``mixer``, in ms per traced round
    on the busiest chip. 0 where the program marks its scopes but ran no
    such op (a model without this recurrence); None where no op carries
    a scope of ``bench/scopes.py`` (a program without the marks)."""
    if scopes.scope_ms(ctx) is None:
        return None
    t = scopes.of(ctx)
    lo, hi = ctx["lo"], ctx["hi"]
    split = scopes._once(("mixers", id(t), lo, hi), t,
                         lambda: _split(t, lo, hi))
    return max(acc.get(mixer, 0) for acc in split.values()) \
        / ctx["rounds"] / 1e6
