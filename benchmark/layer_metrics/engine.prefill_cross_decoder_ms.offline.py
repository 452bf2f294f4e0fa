"""Device time per execution of the prefill program under the scope
`cross_decoder`: the full layer's attention, the layers after it and
what they hold, run for each row's last real position alone, in this
cell."""
from benchmark import span_readings


def read(run):
    return span_readings.scope_ms(span_readings.trace(run),
                                  span_readings.PREFILL_PROGRAMS,
                                  ("cross_decoder",))
