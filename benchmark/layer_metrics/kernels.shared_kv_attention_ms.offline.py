"""Device time per decode step under the scopes `decode_attention` (the
full-attention layer) and `cross_attention` (the cross layers that read
its K and V again): every read of the model's one global K and V pool,
with the streamed attention over it, in this cell."""
from benchmark import span_readings


def read(run):
    return span_readings.scope_ms(
        span_readings.trace(run), span_readings.DECODE_PROGRAMS,
        ("decode_attention", "cross_attention"))
