"""Readings that several per-layer metrics share: a quantity that moves
different end-to-end metrics in different cells is split by suffix into
metrics of its own, and each of those files names the reading here."""

from . import kernel_costs, span_readings


def idle_pct(run):
    """Share of the traced slice in which no operation ran on the device:
    1 - busy over the slice, from the profiler's trace
    (benchmark/trace_reduce.py), averaged over the cell's chips."""
    trace = run.get("trace")
    if not trace or not trace["window_s"] or not trace["n_device_planes"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def decode_step_ms(run):
    """Mean host time of a fused decode step over the window: the change
    in `busy_decode_s` over the change in `decode_steps`. It includes the
    wait for prefill chunks enqueued before the step, which is what a
    decoding request feels."""
    c = run.get("counters")
    if not c or not c["decode_steps"]:
        return None
    return 1e3 * c["busy_decode_s"] / c["decode_steps"]


def decode_attention_roofline(run):
    """Share of its roofline at which `decode_attention` ran: what a
    step's attention needs (benchmark/kernel_costs.py: K and V of the
    positions the window's decode steps had to read, a step's mean) over
    the scope's device time per execution of the decode program in the
    traced slice. The window's mean need against the slice's time: one
    slice is one depth of the deepest slot."""
    ms = span_readings.decode_attention_ms(run)
    steps = run["counters"]["decode_steps"]
    if ms is None or not run.get("peak") or not steps:
        return None
    positions = run["kv_positions_read"] / steps
    cost = kernel_costs.decode_attention(run["dims"], positions)
    pool = run["slots"] * run["max_seq_len"]
    print("[roofline] decode_attention: %.0f cached positions a step, "
          "%.1f %% of the pool's %d; %.4f GB in %.3f ms, bound by %s"
          % (positions, 100.0 * positions / pool, pool, cost[1] / 1e9, ms,
             kernel_costs.bound(cost, run["peak"])[1]), flush=True)
    return kernel_costs.roofline_pct(cost, ms * 1e-3, run["peak"])
