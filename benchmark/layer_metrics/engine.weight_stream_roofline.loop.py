"""How near the passes of a looped stack come to streaming their weights
at the chip's bandwidth: what a decode step must read of the weights (the
stack's matrices once a PASS, `dims["passes"]` passes, and the head's
once) at the HBM peak, as a share of an execution's device time less its
`decode_attention` and `kv_cache_update` scopes (the pool's traffic has a
share of its own). Each matrix is counted once a pass and nothing else
is (no norm weight, no embedding row, no activation), so the share cannot
pass 100. The operations of the step's decode tokens are counted beside
the bytes as every roofline here does; below the chip's ridge (about 240
tokens) the bytes bind. Nothing where the trace holds no `loop_pass`."""
import statistics

from benchmark import kernel_costs, span_readings
from benchmark.families import llama


def cost(dims, tokens):
    """(operations, bytes): the four attention projections and the three
    feed-forward matrices of every layer once a pass, `lm_head` once."""
    per_layer = (llama.attention_params(dims)
                 + 3 * dims["dim"] * dims["ffn_dim"])
    params = (dims.get("passes", 1) * dims["n_layers"] * per_layer
              + dims["dim"] * dims["vocab_size"])
    return 2 * tokens * params, params * kernel_costs.ITEMSIZE[dims["dtype"]]


def read(run):
    t = span_readings.trace(run)
    programs = span_readings.DECODE_PROGRAMS
    steps = run["counters"]["decode_steps"]
    if not t or "loop_pass" not in t.marked or not run.get("peak") \
            or not steps:
        return None
    whole = t.whole(programs)
    if not whole:
        return None
    ms = statistics.fmean((t.executions[i][3] - t.executions[i][2]) * 1e-6
                          for i in whole)
    pool = sum(span_readings.scope_ms(t, programs, (scope,)) or 0.0
               for scope in ("decode_attention", "kv_cache_update"))
    need = cost(run["dims"], run["decode_tokens"] / steps)
    print("[roofline] weight stream: %.3f GB a step in %.3f ms (an "
          "execution's %.3f less %.3f under the pool's scopes), bound by %s"
          % (need[1] / 1e9, ms - pool, ms, pool,
             kernel_costs.bound(need, run["peak"])[1]), flush=True)
    return kernel_costs.roofline_pct(need, (ms - pool) * 1e-3, run["peak"])
