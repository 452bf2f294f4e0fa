"""Lanes that decoded a step (the `active` stat of `serve.decode_step`), mean
over the slice's whole iterations: benchmark/idle_ledger.py,
`lanes_per_step`."""
from benchmark.idle_ledger import lanes_per_step as read  # noqa: F401
