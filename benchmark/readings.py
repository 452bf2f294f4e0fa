"""Readings that several per-layer metrics share: a quantity that moves
different end-to-end metrics in different cells is split by suffix into
metrics of its own, and each of those files names the reading here."""


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
