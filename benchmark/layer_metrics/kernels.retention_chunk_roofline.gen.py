"""Share of its roofline at which `retention_chunk` ran in `jit__prefill`:
what the chunk form of every retention layer needs for a program's REAL
tokens and its rows (the means of the `tokens` and `rows` stats of the
slice's `serve.prefill_chunk` spans) over the scope's device time per
execution in the traced slice. Each row of a program carries a state of
its own, so the need counts `rows` states read and written once, where
`kernels.ssm_scan_roofline.reason` counted one a program and under-read
(PERF.md section 7)."""
import statistics

from benchmark import kernel_costs, span_readings


def chunk_cost(dims, rows, tokens):
    """(operations, bytes) of one prefill program's retention layers:
    per layer and row the float32 state (S and z, `state_dim` x
    (head_dim + 1) a KV head) read once and written once; per real token
    the products with the state before the chunk (every query head, 2 a
    state element), the token's own outer product into the state (every
    KV head, 2 a state element), and inside the chunk the causal half of
    the scores and of the values (2 x head_dim a query head and position
    of the row before it, a row holding tokens / rows of them); q, k, v
    and y pass once in the model's dtype."""
    kv, hd, sd, heads = (dims["n_kv_heads"], dims["head_dim"],
                         dims["state_dim"], dims["n_heads"])
    state = kv * sd * (hd + 1)
    per_token = (2 * state * (heads + kv) // kv
                 + 2 * heads * hd * tokens / rows)
    ops = dims["n_layers"] * tokens * per_token
    nbytes = dims["n_layers"] * (
        rows * state * 4 * 2
        + tokens * (2 * heads + 2 * kv) * hd
        * kernel_costs.ITEMSIZE[dims["dtype"]])
    return ops, nbytes


def read(run):
    t = span_readings.trace(run)
    ms = span_readings.scope_ms(t, span_readings.PREFILL_PROGRAMS,
                                ("retention_chunk",))
    if ms is None or not run.get("peak"):
        return None
    spans = [s[3] for s in t.spans if s[0] == "serve.prefill_chunk"
             and "tokens" in s[3] and "rows" in s[3]]
    if not spans:
        return None
    rows = statistics.mean(float(s["rows"]) for s in spans)
    tokens = statistics.mean(float(s["tokens"]) for s in spans)
    cost = chunk_cost(run["dims"], rows, tokens)
    print("[roofline] retention_chunk: %.2f rows and %.1f real tokens a "
          "program over %d programs, %.3f GB, %.2f GFLOP in %.3f ms, bound "
          "by %s" % (rows, tokens, len(spans), cost[1] / 1e9, cost[0] / 1e9,
                     ms, kernel_costs.bound(cost, run["peak"])[1]),
          flush=True)
    return kernel_costs.roofline_pct(cost, ms * 1e-3, run["peak"])
