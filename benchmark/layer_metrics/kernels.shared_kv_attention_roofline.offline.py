"""Share of its roofline at which attention over the one global K and V
pool ran in the decode program (scopes `decode_attention` and
`cross_attention`): what the full-attention layer and the cross layers
need for the positions a step's decoding lanes attend to
(`kv_positions_read` over `decode_steps`, a step's mean over the window)
over the scopes' device time per execution in the traced slice. The
reading layers run one after another, each with queries of its own, so
each has to read the pool: eight reads are the need, not one. The
queries and the outputs, one position a lane, are left out."""
from benchmark import kernel_costs, span_readings


def shared_kv_cost(dims, positions):
    """(operations, bytes) of one decode step's attention over the global
    pool, `positions` cached positions in all lanes together: per
    reading layer K and V of each are read once as stored (2 x kv heads x
    head size), and a position meets every query head's score (2 x
    head size operations) and, the value head being twice a key head,
    its read of the value (2 x 2 x head size)."""
    layers = dims["n_full_layers"] + dims["n_cross_layers"]
    width = dims["n_kv_heads"] * dims["head_dim"]
    nbytes = 2 * width * kernel_costs.ITEMSIZE[dims["dtype"]]
    ops = 6 * dims["n_heads"] * dims["head_dim"]
    return layers * positions * ops, layers * positions * nbytes


def read(run):
    ms = span_readings.scope_ms(
        span_readings.trace(run), span_readings.DECODE_PROGRAMS,
        ("decode_attention", "cross_attention"))
    steps = run["counters"]["decode_steps"]
    if ms is None or not run.get("peak") or not steps:
        return None
    positions = run["kv_positions_read"] / steps
    cost = shared_kv_cost(run["dims"], positions)
    print("[roofline] shared K and V: %.0f positions a step, %.3f GB, "
          "%.2f GFLOP in %.3f ms, bound by %s"
          % (positions, cost[1] / 1e9, cost[0] / 1e9, ms,
             kernel_costs.bound(cost, run["peak"])[1]), flush=True)
    return kernel_costs.roofline_pct(cost, ms * 1e-3, run["peak"])
