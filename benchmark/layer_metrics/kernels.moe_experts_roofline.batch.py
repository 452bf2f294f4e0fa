"""Share of its roofline at which `moe_experts` ran in the decode
program: what the expert matrices of a step need
(benchmark/kernel_costs.py, `moe_experts`, over the tokens a decode step
of the window made on average) over the scope's device time per
execution in the traced slice. The worked example of a roofline reader:
sizes and peaks from `run`, the time from the trace, the cost from a
function of shapes."""
from benchmark import kernel_costs, span_readings


def read(run):
    ms = span_readings.moe_experts_ms(run)
    steps = run["counters"]["decode_steps"]
    if ms is None or not run.get("peak") or not steps:
        return None
    cost = kernel_costs.moe_experts(run["dims"], run["decode_tokens"] / steps)
    print("[roofline] moe_experts: %.1f tokens a step, %.3f GB, %.1f "
          "GFLOP in %.3f ms, bound by %s"
          % (run["decode_tokens"] / steps, cost[1] / 1e9, cost[0] / 1e9, ms,
             kernel_costs.bound(cost, run["peak"])[1]), flush=True)
    return kernel_costs.roofline_pct(cost, ms * 1e-3, run["peak"])
