"""Read a cell's files: the entry in BENCHMARK.json, the configuration
and the traffic mix it names. Everything a cell is made of is data; the
drivers and metric readers are found by the names the data gives."""

import json
import os

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
    """The sizes the benchmark's own code reads, from the published
    keys of a configuration file."""
    d = {
        "family": config["family"],
        "dim": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config.get(
            "head_dim",
            config["hidden_size"] // config["num_attention_heads"]),
        "ffn_dim": config["intermediate_size"],
        "vocab_size": config["vocab_size"],
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "dtype": config["torch_dtype"],
    }
    if config["family"] == "mixtral":
        d["n_experts"] = config["num_local_experts"]
        d["experts_per_tok"] = config["num_experts_per_tok"]
    if d["head_dim"] * d["n_heads"] != d["dim"]:
        raise ValueError("the program derives head_dim as hidden_size / "
                         "heads; this configuration states another")
    if config.get("sliding_window"):
        raise ValueError("the program has no sliding-window attention")
    return d


def program_config(config, max_seq_len):
    """The program's own configuration object for this file: its
    dataclass fields only, no program file is touched."""
    d = dims(config)
    common = dict(vocab_size=d["vocab_size"], dim=d["dim"],
                  n_layers=d["n_layers"], n_heads=d["n_heads"],
                  n_kv_heads=d["n_kv_heads"], ffn_dim=d["ffn_dim"],
                  max_seq_len=int(max_seq_len), rope_theta=d["rope_theta"],
                  norm_eps=d["norm_eps"], dtype=d["dtype"])
    if d["family"] == "llama":
        from metaflow_tpu.models import llama

        return llama, llama.LlamaConfig(rope_llama3_scaling=False, **common)
    if d["family"] == "mixtral":
        from metaflow_tpu.models import mixtral

        return mixtral, mixtral.MixtralConfig(
            n_experts=d["n_experts"], experts_per_tok=d["experts_per_tok"],
            **common)
    raise ValueError("unknown family %r" % (d["family"],))
