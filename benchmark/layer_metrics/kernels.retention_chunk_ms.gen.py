"""Device time under the scope `retention_chunk` (the chunk form of every
retention layer over a prefill program's rows, ops/retention.py `chunk`,
with the rows' state read out of the pool and written back) per execution
of `jit__prefill`, in this cell."""
from benchmark import span_readings


def read(run):
    return span_readings.scope_ms(span_readings.trace(run),
                                  span_readings.PREFILL_PROGRAMS,
                                  ("retention_chunk",))
