"""Device time per decode step of what a latent expert layer computes
for every token whatever its picks: the router (`moe_router`), the latent
projections (`moe_latent_down`, `moe_latent_up`) and the shared expert
(`moe_shared_expert`), in this cell."""
from benchmark import span_readings


def read(run):
    return span_readings.scope_ms(
        span_readings.trace(run), span_readings.DECODE_PROGRAMS,
        ("moe_shared_expert", "moe_latent_down", "moe_latent_up",
         "moe_router"))
