"""Share of the slice's whole iterations that ran a prefill program beside the
decode step: benchmark/idle_ledger.py, `prefill_iteration_pct`."""
from benchmark.idle_ledger import prefill_iteration_pct as read  # noqa: F401
