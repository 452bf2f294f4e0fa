"""Share of its roofline at which `ssm_scan` ran in `jit__prefill`: what
the recurrence of every Mamba layer needs for a chunk's REAL tokens (the
mean of the `tokens` stat of the slice's `serve.prefill_chunk` spans;
the positions that pad a chunk to its bucket need nothing) over the
scope's device time per execution in the traced slice."""
import statistics

from benchmark import kernel_costs, span_readings


def scan_cost(dims, tokens):
    """(operations, bytes) of one chunk's recurrences: per Mamba layer
    the float32 state [d_state, d_inner] is read once and written once
    whatever the chunk's length, and per token u, delta and y (float32
    [d_inner]) and B and C ([d_state]) pass once; 6 operations a state
    element and token (`ssm_state_update`'s count)."""
    n, di, layers = dims["d_state"], dims["d_inner"], dims["n_mamba_layers"]
    nbytes = layers * 4 * (2 * n * di + tokens * (3 * di + 2 * n))
    return layers * tokens * 6 * n * di, nbytes


def read(run):
    t = span_readings.trace(run)
    ms = span_readings.scope_ms(t, span_readings.PREFILL_PROGRAMS,
                                ("ssm_scan",))
    if ms is None or not run.get("peak"):
        return None
    real = [s[3]["tokens"] for s in t.spans
            if s[0] == "serve.prefill_chunk" and "tokens" in s[3]]
    if not real:
        return None
    tokens = statistics.mean(real)
    cost = scan_cost(run["dims"], tokens)
    print("[roofline] ssm_scan: %.1f real tokens a chunk over %d chunks "
          "(chunks of %d), %.4f GB, %.2f GFLOP in %.3f ms, bound by %s"
          % (tokens, len(real), run["prefill_chunk"], cost[1] / 1e9,
             cost[0] / 1e9, ms, kernel_costs.bound(cost, run["peak"])[1]),
          flush=True)
    return kernel_costs.roofline_pct(cost, ms * 1e-3, run["peak"])
