"""Device time per decode step under `decode_layers` and under none of
its inner scopes, in this cell: the loop carrying, slicing and copying
the pools (K and V of the attention layers, the convolution tails and
states of the Mamba layers). `kernels.kv_cache_ms.*` is the same
remainder for a KV-only model; its inner scopes lack the `ssm_*` ones,
so this reader brings its own."""
from benchmark import span_readings

INNER = span_readings.DECODE_INNER + (
    "ssm_in_proj", "ssm_conv", "ssm_x_proj", "ssm_scan", "ssm_state_update",
    "ssm_out_proj")


def read(run):
    t = span_readings.trace(run)
    if not t or "ssm_state_update" not in t.marked:
        return None
    whole = set(t.whole(span_readings.DECODE_PROGRAMS))
    if not whole:
        return None
    rest = sum(own for _, own, execution, scope in t.ops
               if execution in whole and scope
               and span_readings.under(scope, "decode_layers")
               and not any(span_readings.under(scope, s) for s in INNER))
    value = rest * 1e-6 / len(whole)
    print("[spans] under decode_layers and no inner scope: %.3f ms an "
          "execution over %d executions" % (value, len(whole)), flush=True)
    return value
