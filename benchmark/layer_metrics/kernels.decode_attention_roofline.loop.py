"""Share of its roofline at which `decode_attention` ran in a stack that
is run several times over the same weights (`dims["passes"]`): as
`readings.decode_attention_roofline`, but K and V of a cached position
are counted over the pool's passes x layers indices (every pass attends
to K and V of its own), and the operations likewise;
`kernel_costs.kv_bytes_per_position` counts `dims["n_layers"]` of them, a
quarter here. The time is the `decode_attention` scope's device time an
execution of the decode program (a merged execution's rows' reads are in
it; the need counts the decode tokens alone, so the share reads low)."""
from benchmark import kernel_costs, span_readings


def cost(dims, kv_positions):
    """(operations, bytes) of one decode step's attention where the
    step's queries attend to `kv_positions` cached positions in all: K
    and V of each read once at every pool index (pass and layer); scores
    and values are 2 * heads * head size multiply-adds a position and
    index. The queries and the output are left out."""
    indices = dims.get("passes", 1) * dims["n_layers"]
    ops = indices * 4 * dims["n_heads"] * dims["head_dim"] * kv_positions
    nbytes = (indices * 2 * dims["n_kv_heads"] * dims["head_dim"]
              * kernel_costs.ITEMSIZE[dims["dtype"]] * kv_positions)
    return ops, nbytes


def read(run):
    ms = span_readings.decode_attention_ms(run)
    steps = run["counters"]["decode_steps"]
    if ms is None or not run.get("peak") or not steps:
        return None
    positions = run["kv_positions_read"] / steps
    need = cost(run["dims"], positions)
    print("[roofline] decode_attention over %d pool indices: %.0f cached "
          "positions a step; %.4f GB in %.3f ms, bound by %s"
          % (run["dims"].get("passes", 1) * run["dims"]["n_layers"],
             positions, need[1] / 1e9, ms,
             kernel_costs.bound(need, run["peak"])[1]), flush=True)
    return kernel_costs.roofline_pct(need, ms * 1e-3, run["peak"])
