"""Device-idle time under a fetch span after the execution it awaits has ended
(the result's way back and the thread's wake-up), over the slice's whole
iterations, in this cell: benchmark/idle_ledger.py, `idle_ms_per_iter`."""
from benchmark import idle_ledger


def read(run):
    return idle_ledger.idle_ms_per_iter(run, "fetch_tail")
