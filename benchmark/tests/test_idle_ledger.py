"""benchmark/idle_ledger.py on a trace made by hand: two whole
iterations, one prefill program beside a decode step in each, an idle
interval in each of the five places, one fetch that awaits the second of
two queued executions, and a device clock that runs early."""

import json
import os

import pytest

from benchmark import configs, harness, idle_ledger, span_readings

U = 100_000   # a tenth of a millisecond, in ns: an execution is 4-10 ms
EARLY = 20    # the device's clock is this many units behind the host's

# (program, start, end) on the host's clock; the decode step of launch 11
# waits behind the prefill program of launch 10 and has a gap inside it
EXECUTIONS = [("jit__decode_greedy(1)", 0, 100),      # cut by the slice
              ("jit__prefill(2)", 160, 260),          # launch 10
              ("jit__decode_greedy(1)", 262, 362),    # launch 11
              ("jit__prefill(2)", 420, 470),          # launch 12
              ("jit__first_token(3)", 472, 476),      # behind launch 12
              ("jit__decode_greedy(1)", 510, 660),    # launch 13
              ("jit__decode_greedy(1)", 720, 800)]    # launch 14, cut
GAP_INSIDE = (300, 305)   # no operation runs, inside launch 11's execution

SPANS = [
    ("serve.iteration", 110, 400, {"iteration": 7}),
    ("serve.reap", 110, 112, {}),
    ("serve.admit", 112, 140, {"admitted": 1}),
    ("engine.admit.keys", 115, 135, {}),
    ("serve.prefill_chunk", 140, 175, {"rows": 1, "tokens": 64}),
    ("engine.prefill.dispatch", 142, 150, {"launch": 10}),
    ("serve.decode_step", 180, 380, {"active": 3, "positions_needed": 100,
                                     "positions_fetched": 150}),
    ("engine.decode.upload", 182, 190, {}),
    ("engine.decode.dispatch", 190, 200, {"launch": 11}),
    ("engine.decode.fetch", 200, 378, {"awaits": 11}),
    ("serve.deliver", 382, 395, {"tokens": 3}),
    ("serve.iteration", 402, 700, {"iteration": 8}),
    ("serve.reap", 402, 404, {}),
    ("serve.admit", 404, 406, {"admitted": 0}),
    ("serve.prefill_chunk", 410, 486, {"rows": 1, "tokens": 20}),
    ("engine.prefill.dispatch", 412, 420, {"launch": 12}),
    ("engine.first_token.fetch", 425, 484, {"awaits": 12}),
    ("serve.decode_step", 488, 680, {"active": 5, "positions_needed": 300,
                                     "positions_fetched": 450}),
    ("engine.decode.dispatch", 490, 498, {"launch": 13}),
    ("engine.decode.fetch", 498, 676, {"awaits": 13}),
    ("serve.deliver", 682, 690, {"tokens": 5}),
    ("serve.iteration", 702, 900, {"iteration": 9}),   # not whole
    ("serve.decode_step", 703, 890, {"active": 7}),
    ("engine.decode.dispatch", 705, 712, {"launch": 14}),
]

# idle units inside the stretch 110..700, by hand:
#   admit    112-140 (keys inside it), 404-406
#   deliver  382-395, 682-690
#   launch   the dispatch spans 142-150, 412-420, 490-498; under launch
#            11's fetch between the prefill program's end and its own
#            start, 260-262; under the first-token fetch between the
#            prefill program and the first-token program, 470-472;
#            under launch 13's fetch before it starts, 498-510
#   tail     362-378, 476-484, 660-676
#   other    reap 2 + 2, the rest of the timers and of the iterations,
#            400-402 between them, and the gap inside launch 11
BY_HAND = {"admit": 30, "deliver": 21, "launch": 40, "fetch_tail": 40,
           "other": 60}
IDLE = 191


def by_hand(early=EARLY, stats=True, spans=SPANS):
    ops = []
    for name, start, end in EXECUTIONS:
        cuts = [start, end]
        if start < GAP_INSIDE[0] < end:
            cuts = [start, GAP_INSIDE[0], GAP_INSIDE[1], end]
        ops += [("%op = fusion(...)", (a - early) * U, (b - early) * U)
                for a, b in zip(cuts[::2], cuts[1::2])]
    device = {"XLA Modules": [(n, (s - early) * U, (e - early) * U)
                              for n, s, e in EXECUTIONS],
              "XLA Ops": ops}
    strip = lambda d: d if stats else {
        k: v for k, v in d.items() if k not in ("launch", "awaits")}
    host = {"python3#0": [("bench.send", 0, 900 * U, {})],
            "python3#1": [(n, s * U, e * U, strip(d))
                          for n, s, e, d in spans]}
    return span_readings.Trace(
        [("/device:TPU:0", device), ("/host:CPU", host)], {})


def test_every_idle_unit_goes_to_one_place_and_the_clock_is_found():
    t = by_hand()
    by_launch = idle_ledger.launched(t)
    assert {n: x[2] for n, x in by_launch.items()} == {
        10: (160 - EARLY) * U, 11: (262 - EARLY) * U, 12: (420 - EARLY) * U,
        13: (510 - EARLY) * U, 14: (720 - EARLY) * U}
    waits = idle_ledger.awaited(t, by_launch)
    # the first-token fetch waits for the program behind its prefill
    assert [(s[0], x[0]) for s, x in waits] == [
        ("engine.decode.fetch", "jit__decode_greedy"),
        ("engine.first_token.fetch", "jit__first_token"),
        ("engine.decode.fetch", "jit__decode_greedy")]
    out, whole, total, clock = idle_ledger.idle_by_place(t)
    assert [s[3]["iteration"] for s in whole] == [7, 8]
    # launch 12 starts 8 units into its dispatch span and the first-token
    # fetch closes 8 after its program: the middle is the clock's error
    assert clock == pytest.approx(((EARLY - 8) * U, (EARLY + 8) * U,
                                   EARLY * U))
    assert out == pytest.approx({k: v * U for k, v in BY_HAND.items()})
    assert total == pytest.approx(IDLE * U)
    assert sum(out.values()) == pytest.approx(total, rel=1e-9)


def test_a_trace_without_launch_and_awaits_reads_none_not_a_guess(capsys):
    out, whole, total, clock = idle_ledger.idle_by_place(
        by_hand(early=0, stats=False))
    assert clock is None and len(whole) == 2
    assert out["launch"] is None and out["fetch_tail"] is None
    # all that lay under a fetch span, the gap inside launch 11 too, is
    # in none of the three
    assert {k: out[k] for k in ("admit", "deliver", "other")} \
        == pytest.approx({"admit": 30 * U, "deliver": 21 * U,
                          "other": 55 * U})
    assert total == pytest.approx(IDLE * U)
    line = capsys.readouterr().out
    assert "launch none" in line and "clocks as they stand" in line
    assert idle_ledger.idle_by_place(span_readings.Trace([], {})) is None


def test_the_fourteen_metrics_through_their_files(monkeypatch):
    """Every new reader file, found by name as the harness finds it."""
    with open(os.path.join(configs.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"]
            if "_ms_per_iter." in m["name"] and ".idle_" in m["name"]
            or m["name"].startswith((
                "scheduler.lanes_per_step.",
                "scheduler.prefill_iteration_pct.",
                "engine.attention_fetched_per_needed."))]
    assert len(mine) == 14
    assert all(m["source"] == "program_span" and m["better"] == "lower"
               for m in mine)
    assert {m["moves"] for m in mine if m["name"].endswith(".chat")} \
        == {"itl_p90_ms"}
    assert {m["moves"] for m in mine if m["name"].endswith(".batch")} \
        == {"serve_tokens_per_s"}
    t = by_hand()
    monkeypatch.setattr(span_readings, "trace", lambda run: t)
    only = dict(bench, per_layer=mine)
    chat = harness.read_layer_metrics(
        only, "mistral-7b.chat-steady", {"itl_p90_ms"}, {"trace": {}})
    ms = lambda units: units * U * 1e-6 / 2   # two whole iterations
    assert {k: v["value"] for k, v in chat.items()} == pytest.approx({
        "scheduler.idle_admit_ms_per_iter.chat": ms(30),
        "scheduler.idle_deliver_ms_per_iter.chat": ms(21),
        "engine.idle_launch_ms_per_iter.chat": ms(40),
        "engine.idle_fetch_tail_ms_per_iter.chat": ms(40),
        "scheduler.idle_other_ms_per_iter.chat": ms(60),
        "scheduler.lanes_per_step.chat": 4.0,
        "scheduler.prefill_iteration_pct.chat": 100.0,
        "engine.attention_fetched_per_needed.chat": 1.5})
    batch = harness.read_layer_metrics(
        only, "mixtral-8x7b.batch-offline", {"serve_tokens_per_s"},
        {"trace": {}})
    assert len(batch) == 6 and sum(
        v["value"] for k, v in batch.items() if ".idle_" in k) \
        == pytest.approx(ms(IDLE))
    # a stack with no attention layer says nothing of positions: the
    # cell is not among that metric's
    gen = harness.read_layer_metrics(
        only, "brumby-14b.gen-offline", {"serve_tokens_per_s"},
        {"trace": {}})
    assert len(gen) == 5
    # an older program's trace: the two engine metrics are left out of
    # the line, an iteration without a prefill program counts as one
    old = by_hand(early=0, stats=False, spans=[
        s for s in SPANS if not (s[0] == "serve.prefill_chunk"
                                 and s[1] == 410)])
    monkeypatch.setattr(span_readings, "trace", lambda run: old)
    chat = harness.read_layer_metrics(
        only, "mistral-7b.chat-steady", {"itl_p90_ms"}, {"trace": {}})
    assert "engine.idle_launch_ms_per_iter.chat" not in chat
    assert "engine.idle_fetch_tail_ms_per_iter.chat" not in chat
    assert chat["scheduler.prefill_iteration_pct.chat"]["value"] == 50.0
    assert chat["scheduler.idle_admit_ms_per_iter.chat"]["value"] \
        == pytest.approx(ms(30))
    # and a run that was not traced reads nothing at all
    monkeypatch.undo()
    assert harness.read_layer_metrics(
        only, "mistral-7b.chat-steady", {"itl_p90_ms"}, {}) == {}
