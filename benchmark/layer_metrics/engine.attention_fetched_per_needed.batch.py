"""K and V positions the slice's decode steps fetched over those their
queries saw, in this cell (a stack with no attention layer has neither):
benchmark/idle_ledger.py, `attention_fetched_per_needed`."""
from benchmark.idle_ledger import (  # noqa: F401
    attention_fetched_per_needed as read)
