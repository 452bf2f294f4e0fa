"""Model FLOP/s utilization of the window: operations the forward and
backward passes need per token (benchmark/flops.py: matmul parameters
and causal attention, nothing recomputed) times tokens per second, over
chips times the chip's bf16 peak (benchmark/peaks.py)."""


def read(run):
    if run.get("kind") != "train" or not run.get("peak"):
        return None
    return 100.0 * run["flops_per_token"] * run["tokens_per_s"] / (
        run["chips"] * run["peak"]["bf16_flops_per_s"])
