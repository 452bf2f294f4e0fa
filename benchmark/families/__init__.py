"""A model family is one module here, found by the `family` key of a
configuration file as a driver is found by a traffic file's `kind`. It is
the only place that knows the family's published keys, which of the
program's modules runs it, its parameter tree and its mathematics
(benchmark/README.md, "Adding a family", lists what it exports)."""

import importlib


def load(name):
    return importlib.import_module("benchmark.families." + name)
