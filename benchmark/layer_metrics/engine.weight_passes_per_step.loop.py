"""Passes through the stack's weights a decode step: the mean of the
`passes` stat of the slice's `serve.decode_step` spans (4.0 where every
lane runs every pass of a four-pass stack: the number a later change
that lets lanes leave the loop early would move). Nothing where the
spans carry no such stat."""
from benchmark import idle_ledger, span_readings


def read(run):
    t = span_readings.trace(run)
    passes = [s[3]["passes"] for s in (t.spans if t else ())
              if s[0] == idle_ledger.DECODE_STEP and "passes" in s[3]]
    return sum(passes) / len(passes) if passes else None
