"""Every idle nanosecond of a loaded server's iterations, put down to the
phase of the serving loop it lay under: the readings behind the
`*.idle_*_ms_per_iter` metrics, and four that need no idle time.

Over the STRETCH from the start of the first `serve.iteration` span the
slice holds whole to the end of the last, with `span_readings.trace(run)`'s
`idle`, `spans` and `executions`, an idle nanosecond goes to exactly one
of five, so the five add up to the stretch's idle time:

- `admit`: under `serve.admit` or a span inside it;
- `deliver`: under `serve.deliver`;
- `launch`: under `engine.prefill.dispatch`, `engine.decode.upload` or
  `engine.decode.dispatch`, and under a fetch span BEFORE the execution it
  awaits starts (nothing of this loop's runs yet: an execution queued
  ahead of it is not idle time);
- `fetch_tail`: under a fetch span after the awaited execution's end (the
  result's way back and the thread's wake-up);
- `other`: anywhere else: `serve.reap`, the rest of `serve.prefill_chunk`,
  `serve.decode_step` and `serve.iteration`, between iterations, and the
  gaps inside an awaited execution.

A dispatch span carries `launch=<n>`, the engine's count of its program
launches, and the fetch span that waits for that program `awaits=<n>`;
the execution of launch n is found from its dispatch span, in order (the
first-token program runs behind the prefill program whose logits it
samples, and `engine.first_token.fetch` awaits the pair). Where the trace
holds no such stat (an older program) `launch` and `fetch_tail` are
None, never a guess, and what lay under those spans is in none of the
other three.

The profiler's host and device clocks are not one clock: in a traced
serving run executions start 0.4-1.8 ms BEFORE the span that dispatches
them opens, another amount every session. The pairs bound the difference
from both sides (no execution starts before its dispatch span opens, no
fetch span closes before the execution it awaits has ended); the device's
times are moved by the middle of the two bounds before any idle time is
placed, and the line this module prints gives both. The split between
`launch` and `fetch_tail` is as sure as half their distance (0.55-0.65
ms), and a trace without the stats is read as it stands.
"""

import bisect

from . import span_readings

ADMIT, DELIVER = "serve.admit", "serve.deliver"
PREFILL_CHUNK, DECODE_STEP = "serve.prefill_chunk", "serve.decode_step"
DISPATCH_SPANS = {"engine.prefill.dispatch": span_readings.PREFILL_PROGRAMS,
                  "engine.decode.dispatch": span_readings.DECODE_PROGRAMS}
LAUNCH_SPANS = tuple(DISPATCH_SPANS) + ("engine.decode.upload",)
FIRST_TOKEN_FETCH = "engine.first_token.fetch"
FIRST_TOKEN_PROGRAMS = ("jit__first_token",)
PLACES = ("admit", "deliver", "launch", "fetch_tail", "other")
# the two clocks differ by a millisecond or three and a program of a
# serving cell runs for eleven or more: an execution that starts this
# long before a dispatch span opens is still that span's own
CLOCK_SLACK_NS = 5e6


def stretch_of(t):
    """(the `serve.iteration` spans the slice holds whole, the stretch's
    start, its end); ([], None, None) where it holds none."""
    if not t or not t.window or not t.spans:
        return [], None, None
    lo, hi = t.window
    whole = [s for s in t.spans if s[0] == span_readings.ITERATION
             and s[1] >= lo and s[2] <= hi]
    if not whole:
        return [], None, None
    return whole, whole[0][1], whole[-1][2]


def launched(t):
    """{launch number: its execution} from the dispatch spans that carry
    one: a family's spans and executions pair off in order, an execution
    starting no earlier than CLOCK_SLACK_NS before its span opens."""
    out = {}
    for name, programs in DISPATCH_SPANS.items():
        ran = [x for x in t.executions if x[0] in programs]
        at = 0
        for span in t.spans:
            if span[0] != name or "launch" not in span[3]:
                continue
            while at < len(ran) and ran[at][2] < span[1] - CLOCK_SLACK_NS:
                at += 1
            if at == len(ran):
                break
            out[int(span[3]["launch"])] = ran[at]
            at += 1
    return out


def awaited(t, by_launch):
    """[(fetch span, the execution it waits for)] for every fetch span
    whose `awaits` names a launch found above; a first-token fetch waits
    for the first-token program that follows that prefill execution."""
    first = [x for x in t.executions if x[0] in FIRST_TOKEN_PROGRAMS]
    starts = [x[2] for x in first]
    out = []
    for span in t.spans:
        if span[0] not in span_readings.FETCH_SPANS:
            continue
        ran = by_launch.get(int(span[3].get("awaits", -1)))
        if ran is None:
            continue
        if span[0] == FIRST_TOKEN_FETCH:
            i = bisect.bisect_left(starts, ran[3])
            if i < len(first) and first[i][2] < span[2] + CLOCK_SLACK_NS:
                ran = first[i]
        out.append((span, ran))
    return out


def clock_offset(t, by_launch, waits):
    """(least, most) nanoseconds to add to the device's times to put them
    on the host's clock: no execution starts before its dispatch span
    opens, no fetch span closes before what it awaits has ended. None
    where the trace holds no such pair."""
    least = [s[1] - by_launch[int(s[3]["launch"])][2] for s in t.spans
             if s[0] in DISPATCH_SPANS
             and int(s[3].get("launch", -1)) in by_launch]
    most = [span[2] - ran[3] for span, ran in waits]
    if not least or not most:
        return None
    return max(least), min(most)


def pieces(spans, lo, hi):
    """The scheduler's line from lo to hi cut at every span's edge:
    [(start, end, the spans over it, outermost first)]."""
    out, stack, at = [], [], lo

    def upto(when):
        nonlocal at
        when = min(max(when, lo), hi)
        if when > at:
            out.append((at, when, tuple(stack)))
            at = when

    for span in spans:
        while stack and stack[-1][2] <= span[1]:
            upto(stack[-1][2])
            stack.pop()
        upto(span[1])
        stack.append(span)
    while stack:
        upto(stack[-1][2])
        stack.pop()
    upto(hi)
    return out


def place(cut, awaits):
    """[(place or None, nanoseconds)] of one idle piece: `cut` is (start,
    end, the spans over it); `awaits` maps a fetch span to the (start,
    end) of the execution it waits for, on the host's clock. None marks
    what lay under an engine span that carries no stat to place it by."""
    start, end, over = cut
    names = [s[0] for s in over]
    inner = names[-1] if names else None
    if ADMIT in names:
        return [("admit", end - start)]
    if inner == DELIVER:
        return [("deliver", end - start)]
    if inner in span_readings.FETCH_SPANS:
        if awaits is None:
            return [(None, end - start)]
        ran = awaits.get(over[-1][:3])
        if ran is None:   # its dispatch span lies outside the slice
            return [("other", end - start)]
        before = max(0.0, min(end, ran[0]) - start)
        after = max(0.0, end - max(start, ran[1]))
        return [("launch", before), ("fetch_tail", after),
                ("other", end - start - before - after)]
    if inner in LAUNCH_SPANS:
        return [("launch" if awaits is not None else None, end - start)]
    return [("other", end - start)]


def idle_by_place(t):
    """({place: idle ns or None}, whole iterations, the stretch's idle ns,
    the clock's (least, most, taken) or None), or None where the slice
    holds no whole iteration."""
    whole, lo, hi = stretch_of(t)
    if not whole:
        return None
    by_launch = launched(t)
    waits = awaited(t, by_launch)
    bounds, shift, awaits = clock_offset(t, by_launch, waits), 0.0, None
    if bounds:
        shift = 0.5 * (bounds[0] + bounds[1])
        bounds += (shift,)
        awaits = {span[:3]: (ran[2] + shift, ran[3] + shift)
                  for span, ran in waits}
    idle = [(max(a + shift, lo), min(b + shift, hi)) for a, b in t.idle]
    idle = [(a, b) for a, b in idle if b > a]
    cuts = pieces(t.spans, lo, hi)
    ends = [c[1] for c in cuts]
    out = dict.fromkeys(PLACES, 0.0)
    unplaced = 0.0
    for a, b in idle:
        k = bisect.bisect_right(ends, a)
        while k < len(cuts) and cuts[k][0] < b:
            start, end = max(a, cuts[k][0]), min(b, cuts[k][1])
            for where, ns in place((start, end, cuts[k][2]), awaits):
                if where is None:
                    unplaced += ns
                else:
                    out[where] += ns
            k += 1
    if awaits is None:
        out["launch"] = out["fetch_tail"] = None
    total = sum(b - a for a, b in idle)
    ms = lambda ns: "none" if ns is None else "%.4f" % (ns * 1e-6 / len(whole))
    print("[spans] idle by place, ms an iteration over %d whole iterations: "
          "%s; sum %s of the stretch's %s idle (%.2f %% of its %.3f s)%s; %s"
          % (len(whole), ", ".join("%s %s" % (p, ms(out[p])) for p in PLACES),
             ms(sum(v for v in out.values() if v is not None)), ms(total),
             100.0 * total / (hi - lo), (hi - lo) * 1e-9,
             (", %s under engine spans with no launch or awaits stat"
              % ms(unplaced)) if awaits is None else "",
             ("device clock moved by %.3f ms (between %.3f and %.3f)"
              % (bounds[2] * 1e-6, bounds[0] * 1e-6, bounds[1] * 1e-6))
             if bounds else "clocks as they stand"), flush=True)
    return out, whole, total, bounds


_read = {}


def idle_ms_per_iter(run, where):
    """`*.idle_<where>_ms_per_iter.*`: idle milliseconds an iteration at
    one of PLACES; the trace is placed once a run."""
    t = span_readings.trace(run)
    if t is None:
        return None
    if _read.get("trace") is not t:
        _read.update(trace=t, placed=idle_by_place(t))
    placed = _read["placed"]
    if not placed or placed[0][where] is None:
        return None
    return placed[0][where] * 1e-6 / len(placed[1])


def _inside(t, name):
    """(the `name` spans that lie inside the stretch, its whole
    iterations); both empty where the slice holds no whole iteration."""
    whole, lo, hi = stretch_of(t)
    return [s for s in t.spans if s[0] == name and lo <= s[1]
            and s[2] <= hi] if whole else [], whole


def lanes_per_step(run):
    """scheduler.lanes_per_step.*: mean `active` of the whole iterations'
    `serve.decode_step` spans."""
    steps, _ = _inside(span_readings.trace(run), DECODE_STEP)
    lanes = [s[3]["active"] for s in steps if "active" in s[3]]
    return sum(lanes) / len(lanes) if lanes else None


def prefill_iteration_pct(run):
    """scheduler.prefill_iteration_pct.*: share of the whole iterations
    that hold a `serve.prefill_chunk`."""
    chunks, whole = _inside(span_readings.trace(run), PREFILL_CHUNK)
    return 100.0 * len(chunks) / len(whole) if whole else None


def attention_fetched_per_needed(run):
    """engine.attention_fetched_per_needed.*: K and V positions the
    slice's decode steps fetched over those their queries saw
    (`positions_fetched`, `positions_needed` of `serve.decode_step`)."""
    t = span_readings.trace(run)
    steps = [s[3] for s in t.spans if s[0] == DECODE_STEP
             and "positions_needed" in s[3]] if t else []
    needed = sum(s["positions_needed"] for s in steps)
    if not needed:
        return None
    return sum(s["positions_fetched"] for s in steps) / needed
