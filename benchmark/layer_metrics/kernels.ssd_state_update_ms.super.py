"""Device time under the scope `ssd_state_update` (the one-token update of
every Mamba-2 layer's state, ops/ssm.py `ssd_step`, with the state pool's
read and write-back) per decode step, in this cell."""
from benchmark import span_readings


def read(run):
    return span_readings.scope_ms(span_readings.trace(run),
                                  span_readings.DECODE_PROGRAMS,
                                  ("ssd_state_update",))
