"""Share of its roofline at which `ssd_state_update` ran in the decode
program: what the one-token update of every Mamba-2 layer needs for the
lanes that decode (a step's mean over the window, `decode_tokens` over
`decode_steps`) over the scope's device time per execution in the traced
slice. The lanes that hold no decoding request need nothing: their state
passes through. The convolution's tail is read and written under
`ssd_conv`, not here, and is not counted."""
from benchmark import kernel_costs, span_readings


def state_update_cost(dims, lanes):
    """(operations, bytes) of one decode step's state updates: per lane
    and Mamba-2 layer the float32 state [heads, head size, state columns]
    is read once and written once, and x and y (float32 [heads, head
    size]), B and C ([groups, columns]) and dt ([heads]) pass once; per
    state element the decay's product, the input's product and sum, and
    the output's multiply-add (5): the decay's exponential is one a head."""
    h, p, n = dims["mamba_heads"], dims["mamba_head_dim"], dims["ssm_state"]
    layers = dims["n_mamba2_layers"]
    vectors = 2 * h * p + 2 * dims["n_groups"] * n + h
    nbytes = layers * lanes * 4 * (2 * h * p * n + vectors)
    return layers * lanes * 5 * h * p * n, nbytes


def read(run):
    ms = span_readings.scope_ms(span_readings.trace(run),
                                span_readings.DECODE_PROGRAMS,
                                ("ssd_state_update",))
    steps = run["counters"]["decode_steps"]
    if ms is None or not run.get("peak") or not steps:
        return None
    lanes = run["decode_tokens"] / steps
    cost = state_update_cost(run["dims"], lanes)
    print("[roofline] ssd_state_update: %.1f of %d lanes decode a step, "
          "%.3f GB, %.2f GFLOP in %.3f ms, bound by %s"
          % (lanes, run["slots"], cost[1] / 1e9, cost[0] / 1e9, ms,
             kernel_costs.bound(cost, run["peak"])[1]), flush=True)
    return kernel_costs.roofline_pct(cost, ms * 1e-3, run["peak"])
