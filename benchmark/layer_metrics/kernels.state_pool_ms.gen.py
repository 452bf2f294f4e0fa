"""Device time per decode step under `decode_layers` and under none of
its inner scopes, in this cell: what the compiler moves unasked around
the state pool (a copy of it, a layer laid out anew) and the loop's own
cost. `kernels.state_pool_ms.reason` is the same remainder for Jamba's
pools; the inner scopes here are the retention layer's."""
from benchmark import span_readings

INNER = span_readings.DECODE_INNER + (
    "retention_qkvg", "retention_update", "retention_chunk", "retention_out")


def read(run):
    t = span_readings.trace(run)
    if not t or "retention_update" not in t.marked:
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
