"""Mean share of the engine's slots that held a request at a decode step:
the change in the scheduler's `_occupancy_sum` over `decode_steps`."""


def read(run):
    c = run.get("counters")
    if not c or not c["decode_steps"]:
        return None
    return 100.0 * c["occupancy_sum"] / c["decode_steps"]
