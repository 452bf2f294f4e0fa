"""Share of its roofline at which `ssd_chunk` ran in `jit__prefill`: what
the chunk form of every Mamba-2 layer needs for a program's rows and
their REAL tokens (the means of the `rows` and `tokens` stats of the
slice's `serve.prefill_chunk` spans; the positions that pad a row to the
program's width need nothing) over the scope's device time per execution
in the traced slice."""
import statistics

from benchmark import kernel_costs, span_readings


def chunk_cost(dims, rows, tokens):
    """(operations, bytes) of one prefill program's chunk forms, per
    Mamba-2 layer. Bytes: a row's float32 state is read once and written
    once whatever its length, and per token x and y (float32 [heads, head
    size]), B and C ([groups, columns]) and dt ([heads]) pass once.
    Operations, with L = tokens / rows positions a row: the scores C B^T
    a group (2 L^2 N, the causal half: L^2 N), their product with the
    inputs a head (the causal half: L^2 P), the carried state's term and
    the state's update a head (2 L P N each): what the recurrence needs
    in this form, no padding and no position above the diagonal."""
    h, p, n, g = (dims["mamba_heads"], dims["mamba_head_dim"],
                  dims["ssm_state"], dims["n_groups"])
    layers = dims["n_mamba2_layers"]
    vectors = 2 * h * p + 2 * g * n + h
    nbytes = layers * 4 * (rows * 2 * h * p * n + tokens * vectors)
    per_row = tokens / rows
    ops = layers * rows * (g * per_row ** 2 * n + h * per_row ** 2 * p
                           + 2 * 2 * per_row * h * p * n)
    return ops, nbytes


def read(run):
    t = span_readings.trace(run)
    ms = span_readings.scope_ms(t, span_readings.PREFILL_PROGRAMS,
                                ("ssd_chunk",))
    if ms is None or not run.get("peak"):
        return None
    chunks = [s[3] for s in t.spans if s[0] == "serve.prefill_chunk"
              and "tokens" in s[3] and "rows" in s[3]]
    if not chunks:
        return None
    rows = statistics.mean(c["rows"] for c in chunks)
    tokens = statistics.mean(c["tokens"] for c in chunks)
    cost = chunk_cost(run["dims"], rows, tokens)
    print("[roofline] ssd_chunk: %.2f rows and %.1f real tokens a program "
          "over %d programs (chunks of %d), %.4f GB, %.2f GFLOP in %.3f ms, "
          "bound by %s"
          % (rows, tokens, len(chunks), run["prefill_chunk"], cost[1] / 1e9,
             cost[0] / 1e9, ms, kernel_costs.bound(cost, run["peak"])[1]),
          flush=True)
    return kernel_costs.roofline_pct(cost, ms * 1e-3, run["peak"])
