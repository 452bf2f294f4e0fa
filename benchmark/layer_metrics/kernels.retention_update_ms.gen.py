"""Device time under the scope `retention_update` per execution of the
decode program, in this cell: the one-token update and query of every
retention layer's state (ops/retention.py, `update_pool`: the Pallas
kernel `retention_update` with the pool's read and write, phi of the
step's q and k, the normaliser's update and the division)."""
from benchmark import span_readings


def read(run):
    return span_readings.scope_ms(span_readings.trace(run),
                                  span_readings.DECODE_PROGRAMS,
                                  ("retention_update",))
