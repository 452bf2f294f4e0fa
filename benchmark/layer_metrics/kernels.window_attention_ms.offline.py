"""Device time per decode step under the scope `window_attention`: the
window layers' reads of their rings and the streamed attention over
them, in this cell."""
from benchmark import span_readings


def read(run):
    return span_readings.scope_ms(span_readings.trace(run),
                                  span_readings.DECODE_PROGRAMS,
                                  ("window_attention",))
