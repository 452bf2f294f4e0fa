"""Share of its roofline at which `retention_update` ran in the decode
program: what the one-token update of every retention layer needs for
the lanes that decode (a step's mean over the window, `decode_tokens`
over `decode_steps`) over the scope's device time per execution in the
traced slice. A lane that holds no decoding request needs nothing: its
state is not read. The need counts the 8,256 distinct products of a
head's symmetric square (`state_dim`), not the 8,320 the program lays
them out in."""
from benchmark import kernel_costs, span_readings


def update_cost(dims, lanes):
    """(operations, bytes) of one decode step's state updates: per lane,
    layer and KV head the float32 state S [state_dim, head_dim] and its
    normaliser z [state_dim] are read once and written once; per element
    the gate's product, the outer product's and its sum (3), and for each
    of the group's query heads a multiply-add (2). q, k, v and y, one
    position a lane, are left out."""
    kv, hd, sd = dims["n_kv_heads"], dims["head_dim"], dims["state_dim"]
    group = dims["n_heads"] // kv
    elements = dims["n_layers"] * lanes * kv * sd * (hd + 1)
    return elements * (3 + 2 * group), elements * 4 * 2


def read(run):
    ms = span_readings.scope_ms(span_readings.trace(run),
                                span_readings.DECODE_PROGRAMS,
                                ("retention_update",))
    steps = run["counters"]["decode_steps"]
    if ms is None or not run.get("peak") or not steps:
        return None
    lanes = run["decode_tokens"] / steps
    cost = update_cost(run["dims"], lanes)
    print("[roofline] retention_update: %.1f of %d lanes decode a step, "
          "%.3f GB, %.2f GFLOP in %.3f ms, bound by %s"
          % (lanes, run["slots"], cost[1] / 1e9, cost[0] / 1e9, ms,
             kernel_costs.bound(cost, run["peak"])[1]), flush=True)
    return kernel_costs.roofline_pct(cost, ms * 1e-3, run["peak"])
