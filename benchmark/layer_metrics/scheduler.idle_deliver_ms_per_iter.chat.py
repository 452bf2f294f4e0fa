"""Device-idle time under `serve.deliver` (a step's tokens handed to their
requests), over the slice's whole
iterations, in this cell: benchmark/idle_ledger.py, `idle_ms_per_iter`."""
from benchmark import idle_ledger


def read(run):
    return idle_ledger.idle_ms_per_iter(run, "deliver")
