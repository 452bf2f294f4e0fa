"""Share of its roofline at which the window layers' attention ran in
the decode program (scope `window_attention`): per decoding lane the
positions a query sees, min(its context, the window), in every window
layer, over the scope's device time per execution in the traced slice.

The run hands over sums, not each lane's context: the lanes a step are
`decode_tokens` over `decode_steps` and their mean context
`kv_positions_read` over `decode_tokens`, and the need is counted from
min(that mean, the window). A minimum of a mean is at least the mean of
the minima, so where some lanes are still inside the window the need is
counted a little high and the share reads a little high; in this cell a
lane's context passes the window within its first 450 tokens of some
1,800."""
from benchmark import kernel_costs, span_readings


def window_cost(dims, lanes, context):
    """(operations, bytes) of one decode step's window attention:
    `lanes` queries a layer, each over min(context, window) positions
    whose K and V are read once as stored; operations a position as the
    differential form has them (two score maps, a value head twice a key
    head: 6 x heads x head size)."""
    positions = lanes * min(context, dims["window"])
    width = dims["n_kv_heads"] * dims["head_dim"]
    nbytes = 2 * width * kernel_costs.ITEMSIZE[dims["dtype"]]
    ops = 6 * dims["n_heads"] * dims["head_dim"]
    layers = dims["n_window_layers"]
    return layers * positions * ops, layers * positions * nbytes


def read(run):
    ms = span_readings.scope_ms(span_readings.trace(run),
                                span_readings.DECODE_PROGRAMS,
                                ("window_attention",))
    steps, tokens = run["counters"]["decode_steps"], run["decode_tokens"]
    if ms is None or not run.get("peak") or not steps or not tokens:
        return None
    lanes, context = tokens / steps, run["kv_positions_read"] / tokens
    cost = window_cost(run["dims"], lanes, context)
    print("[roofline] window_attention: %.1f lanes a step at a mean context "
          "of %.0f, %.3f GB, %.2f GFLOP in %.3f ms, bound by %s"
          % (lanes, context, cost[1] / 1e9, cost[0] / 1e9, ms,
             kernel_costs.bound(cost, run["peak"])[1]), flush=True)
    return kernel_costs.roofline_pct(cost, ms * 1e-3, run["peak"])
