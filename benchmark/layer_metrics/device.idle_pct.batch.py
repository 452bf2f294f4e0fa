"""The device's idle share in this cell: benchmark/readings.py, `idle_pct`."""
from benchmark.readings import idle_pct as read  # noqa: F401
