"""Device time under the scope `moe_experts` (the held experts' two
matrices over their buffers, in the latent width) per decode step, in
this cell."""
from benchmark import span_readings


def read(run):
    return span_readings.scope_ms(span_readings.trace(run),
                                  span_readings.DECODE_PROGRAMS,
                                  ("moe_experts",))
