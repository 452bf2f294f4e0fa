"""Device time under `moe_dispatch` and `moe_combine` (the pairs' places in
the held experts' buffers, the scatter in and the weighted gather back)
per decode step, in this cell. The router is a product of its own here
(4,096 x 512 a token) and is read by `kernels.latent_moe_other_ms.super`."""
from benchmark import span_readings


def read(run):
    return span_readings.scope_ms(span_readings.trace(run),
                                  span_readings.DECODE_PROGRAMS,
                                  ("moe_dispatch", "moe_combine"))
