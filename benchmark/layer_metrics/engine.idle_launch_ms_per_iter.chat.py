"""Device-idle time under `engine.prefill.dispatch`, `engine.decode.upload` and
`engine.decode.dispatch`, and under a fetch span before the execution it
awaits starts, over the slice's whole
iterations, in this cell: benchmark/idle_ledger.py, `idle_ms_per_iter`."""
from benchmark import idle_ledger


def read(run):
    return idle_ledger.idle_ms_per_iter(run, "launch")
