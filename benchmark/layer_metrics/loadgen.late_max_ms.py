"""The latest the generator sent a request: actual send minus due time,
the worst of the window. A starved generator must not read as a fast
server."""


def read(run):
    late = run.get("late_ms")
    return max(late) if late else None
