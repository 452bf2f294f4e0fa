"""Device time under the scope `ssm_state_update` (the one-token update of
every Mamba layer's state, ops/ssm.py `selective_step`) per decode step,
in this cell: the scope `kernels.ssm_state_update_ms.reason` reads, where
it moves another end-to-end metric."""
from benchmark import span_readings


def read(run):
    return span_readings.scope_ms(span_readings.trace(run),
                                  span_readings.DECODE_PROGRAMS,
                                  ("ssm_state_update",))
