"""Device-idle time of the whole iterations' stretch under none of admission,
delivery, launch and fetch tail (`serve.reap`, the rest of the two timers
and of `serve.iteration`, between iterations), over the slice's whole
iterations, in this cell: benchmark/idle_ledger.py, `idle_ms_per_iter`."""
from benchmark import idle_ledger


def read(run):
    return idle_ledger.idle_ms_per_iter(run, "other")
