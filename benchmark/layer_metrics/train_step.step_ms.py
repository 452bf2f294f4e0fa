"""Median host time of one step: the call to `step()` up to
`block_until_ready` on its loss."""
import statistics


def read(run):
    steps = run.get("step_ms")
    return statistics.median(steps) if steps else None
