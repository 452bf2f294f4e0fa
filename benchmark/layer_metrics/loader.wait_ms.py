"""Median host time a step waits for its batch: `next(stream)` and
`shard_batch`, host clock, per step."""
import statistics


def read(run):
    waits = run.get("loader_wait_ms")
    return statistics.median(waits) if waits else None
