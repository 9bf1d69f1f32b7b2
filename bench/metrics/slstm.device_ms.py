"""slstm.device_ms: device self time of the sLSTM's recurrence per traced
round, on the busiest chip: ops whose name stack holds the ``slstm_scan``
scope (``models/xlstm.slstm_forward``'s scan over time steps), forward,
remat's recompute and backward together. The projections, conv, norms and
MLP around it are not counted. A model without an sLSTM reads 0
(``bench/mixers.py``)."""
from bench import mixers


def read(ctx):
    """Milliseconds of the sLSTM time scan per round."""
    return mixers.mixer_ms(ctx, "slstm_scan")
