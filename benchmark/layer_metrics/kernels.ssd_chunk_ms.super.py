"""Device time under the scope `ssd_chunk` (the chunk form of every
Mamba-2 layer over a prefill program's rows, ops/ssm.py `ssd_chunk`, with
the rows' state read and written back) per execution of `jit__prefill`,
in this cell."""
from benchmark import span_readings


def read(run):
    return span_readings.scope_ms(span_readings.trace(run),
                                  span_readings.PREFILL_PROGRAMS,
                                  ("ssd_chunk",))
