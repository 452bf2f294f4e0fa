"""Host time of a scheduler iteration outside engine calls: the window
less the engine's prefill and decode seconds, over iterations."""


def read(run):
    c = run.get("counters")
    if not c or not c["iteration"]:
        return None
    outside = c["t"] - c["busy_prefill_s"] - c["busy_decode_s"]
    return 1e3 * max(outside, 0.0) / c["iteration"]
