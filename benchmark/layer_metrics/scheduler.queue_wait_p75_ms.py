"""75th percentile of the time a request waited in the scheduler's queue:
`t_admit - t_submit` of every request of the window (the highest
percentile that a window of some forty requests leaves ten samples
beyond)."""
from benchmark import stats


def read(run):
    waits = run.get("queue_wait_ms")
    return stats.percentile(waits, 75) if waits else None
