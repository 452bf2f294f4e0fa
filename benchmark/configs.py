"""Read a cell's files: the entry in BENCHMARK.json, the configuration
and the traffic mix it names. Everything a cell is made of is data; the
drivers and metric readers are found by the names the data gives."""

import json
import os

from . import families

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def read_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name, benchmark_path=None):
    """(benchmark, cell entry, configuration, traffic) for cell `name`."""
    bench = read_json(benchmark_path or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit("no cell %r in BENCHMARK.json (it has: %s)"
                         % (name, ", ".join(sorted(cells))))
    cell = cells[name]
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == cell["config"])
    root = os.path.dirname(os.path.abspath(benchmark_path)) \
        if benchmark_path else ROOT
    config = read_json(os.path.join(root, config_entry["file"]))
    traffic = read_json(os.path.join(
        root, os.path.dirname(os.path.dirname(config_entry["file"])),
        "traffic", cell["traffic"] + ".json"))
    return bench, cell, config, traffic


def dims(config):
    """The sizes the benchmark's own code reads: what the configuration's
    family (benchmark/families/<family>.py) makes of its published keys,
    and the family's name."""
    d = families.load(config["family"]).dims(config)
    d["family"] = config["family"]
    return d


def program_config(config, max_seq_len):
    """(the program's model module, its own configuration object) for
    this file, as its family maps it."""
    d = dims(config)
    return families.load(d["family"]).program_config(d, max_seq_len)
