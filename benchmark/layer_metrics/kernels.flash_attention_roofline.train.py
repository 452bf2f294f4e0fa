"""Share of its roofline at which the `flash_attention` kernels ran:
causal attention's operations and bytes of one step, forward and
backward, nothing recomputed (benchmark/kernel_costs.py,
`flash_attention_train`), over the scope's device time per step in the
traced slice, on the cell's chips."""
from benchmark import kernel_costs, span_readings


def read(run):
    ms = span_readings.flash_attention_ms(run)
    if ms is None or not run.get("peak"):
        return None
    cost = kernel_costs.flash_attention_train(
        run["dims"], run["sequences_per_step"], run["seq_len"])
    print("[roofline] flash_attention: %.3f TFLOP, %.3f GB in %.3f ms on "
          "%d chips, bound by %s"
          % (cost[0] / 1e12, cost[1] / 1e9, ms, run["chips"],
             kernel_costs.bound(cost, run["peak"], run["chips"])[1]),
          flush=True)
    return kernel_costs.roofline_pct(cost, ms * 1e-3, run["peak"],
                                     run["chips"])
