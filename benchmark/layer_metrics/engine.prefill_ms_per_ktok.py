"""Median over requests of (first token - admission) over prompt tokens,
in ms per 1000 tokens. `busy_prefill_s` is not used: a prefill chunk that
is not a prompt's last returns before the device has done it."""
import statistics


def read(run):
    values = run.get("prefill_ms_per_ktok")
    return statistics.median(values) if values else None
