"""Device time of ONE pass over the stack in the decode program of a
stack that is run several times: what lies under the scope `loop_pass`
(inference/decode.py: one pass of the layer loop, the norm that closes
it and the gate) an execution, over the configuration's passes. Nothing
where the trace holds no such scope (every stack with one pass)."""
from benchmark import span_readings


def read(run):
    ms = span_readings.scope_ms(span_readings.trace(run),
                                span_readings.DECODE_PROGRAMS,
                                ("loop_pass",))
    return None if ms is None else ms / run["dims"].get("passes", 1)
