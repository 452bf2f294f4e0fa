"""Device time under the scope `ssm_scan` (the recurrence of every Mamba
layer over a prefill chunk, ops/ssm.py `selective_scan`) per execution of
`jit__prefill`, in this cell."""
from benchmark import span_readings


def read(run):
    return span_readings.scope_ms(span_readings.trace(run),
                                  span_readings.PREFILL_PROGRAMS,
                                  ("ssm_scan",))
