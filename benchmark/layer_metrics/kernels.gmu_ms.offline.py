"""Device time per decode step under the scope `gmu`: the gated memory
units' two products and the gate over the carried memory, in this
cell."""
from benchmark import span_readings


def read(run):
    return span_readings.scope_ms(span_readings.trace(run),
                                  span_readings.DECODE_PROGRAMS, ("gmu",))
