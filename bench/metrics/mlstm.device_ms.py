"""mlstm.device_ms: device self time of the mLSTM's recurrence per traced
round, on the busiest chip: ops whose name stack holds the ``mlstm_chunk``
scope (``models/xlstm.mlstm_forward``'s scan over chunks), forward,
remat's recompute and backward together. The projections, convs, norms
and gates around it are not counted. A model without an mLSTM reads 0
(``bench/mixers.py``)."""
from bench import mixers


def read(ctx):
    """Milliseconds of the mLSTM chunk scan per round."""
    return mixers.mixer_ms(ctx, "mlstm_chunk")
