"""Share of its roofline at which `ssm_state_update` ran in the decode
program: what the one-token update of every Mamba layer needs for the
lanes that decode (a step's mean over the window, `decode_tokens` over
`decode_steps`) over the scope's device time per execution in the traced
slice. The lanes that hold no decoding request need nothing: their state
passes through. The convolution's tail is read and written under
`ssm_conv`, not here, and is not counted."""
from benchmark import kernel_costs, span_readings


def state_update_cost(dims, lanes):
    """(operations, bytes) of one decode step's state updates: per lane
    and Mamba layer the float32 state [d_state, d_inner] is read once
    and written once, and u, delta and y (float32 [d_inner]) and B and C
    ([d_state]) pass once; per state element a decay's exponential and
    product, the input's product and sum, and the output's multiply-add
    (6)."""
    n, di, layers = dims["d_state"], dims["d_inner"], dims["n_mamba_layers"]
    nbytes = layers * lanes * 4 * (2 * n * di + 3 * di + 2 * n)
    return layers * lanes * 6 * n * di, nbytes


def read(run):
    ms = span_readings.scope_ms(span_readings.trace(run),
                                span_readings.DECODE_PROGRAMS,
                                ("ssm_state_update",))
    steps = run["counters"]["decode_steps"]
    if ms is None or not run.get("peak") or not steps:
        return None
    lanes = run["decode_tokens"] / steps
    cost = state_update_cost(run["dims"], lanes)
    print("[roofline] ssm_state_update: %.1f of %d lanes decode a step, "
          "%.3f GB, %.2f GFLOP in %.3f ms, bound by %s"
          % (lanes, run["slots"], cost[1] / 1e9, cost[0] / 1e9, ms,
             kernel_costs.bound(cost, run["peak"])[1]), flush=True)
    return kernel_costs.roofline_pct(cost, ms * 1e-3, run["peak"])
