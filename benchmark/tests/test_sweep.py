"""benchmark/sweep.py: the choice of the knee out of a sweep's rows, on
made-up rows, and the sweep itself end to end at a tiny size."""

import json
import os

import pytest

from benchmark import sweep

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = os.path.join(HERE, "cells", "BENCHMARK.json")


def rows(shares_by_seed):
    """{seed: {rate: share}} as the sweep's rows."""
    return [{"rate_rps": rate, "seed": seed, "share": share}
            for seed, by_rate in shares_by_seed.items()
            for rate, share in by_rate.items()]


KNEE_CASES = {
    # every rate held: the edge lies above what was swept (PR 25's sweep)
    "held-at-every-rate": (
        {1: {4: 1.01, 6: 1.0, 8: 0.99}, 2: {4: 1.0, 6: 0.98, 8: 0.975}},
        None, "the highest rate swept, 8, still holds"),
    "failed-at-every-rate": (
        {1: {4: 0.9, 6: 0.8}, 2: {4: 0.95, 6: 0.7}},
        None, "the lowest rate swept, 4, does not hold"),
    "a-clean-bracket": (
        {1: {8: 1.0, 9: 0.99, 10: 0.98, 11: 0.93, 12: 0.85},
         2: {8: 1.02, 9: 1.0, 10: 0.975, 11: 0.95, 12: 0.86}},
        10.0, "10 holds on 2 seeds"),
    # one seed holds at 11 and the other does not: 11 does not hold
    "a-seed-that-disagrees": (
        {1: {9: 1.0, 10: 0.99, 11: 0.98, 12: 0.9},
         2: {9: 1.0, 10: 0.99, 11: 0.96, 12: 0.9}},
        10.0, "11 fails"),
    # the disagreement at the top: one seed's highest rate still holds,
    # the other's fails, so the rate fails and the sweep brackets
    "a-seed-that-disagrees-at-the-highest-rate": (
        {1: {9: 1.0, 10: 0.99}, 2: {9: 1.0, 10: 0.9}},
        9.0, "10 fails"),
    # one seed's share dips below the knee with no backlog (the reason
    # cell at 5.5 a second, PR 37): named, and the knee stays where the
    # higher rates put it
    "a-seed-that-disagrees-below-the-knee": (
        {1: {5: 0.99, 5.5: 1.08, 6: 1.01, 7: 1.03, 8: 0.9},
         2: {5: 1.05, 5.5: 0.954, 6: 0.98, 7: 1.02, 8: 0.91}},
        7.0, "below it 5.5 failed on a seed"),
    # a rate fails and the highest one swept holds: the edge is not in
    # the rows yet
    "the-highest-rate-holds-above-one-that-fails": (
        {1: {8: 1.0, 9: 0.9, 10: 0.99}, 2: {8: 1.0, 9: 0.95, 10: 0.98}},
        None, "the highest rate swept, 10, still holds"),
    # the wide sweep ran on one seed only and nothing finer followed
    "the-knee-on-one-seed": (
        {1: {8: 1.0, 10: 0.99, 12: 0.8}, 2: {12: 0.8}},
        None, "ran on 1 seed(s) of the 2"),
    # the knee is read above a rate that holds between two that fail
    "held-between-two-that-fail": (
        {1: {7: 1.0, 7.5: 0.96, 8: 0.98, 8.5: 0.9},
         2: {7: 1.0, 7.5: 0.99, 8: 0.975, 8.5: 0.91}},
        8.0, "below it 7.5 failed on a seed"),
    "no-rows": ({}, None, "no rows"),
}


@pytest.mark.parametrize("case", sorted(KNEE_CASES))
def test_knee_is_bracketed_or_is_not_given(case):
    shares, want, why = KNEE_CASES[case]
    found = sweep.knee(rows(shares))
    assert found["knee_rps"] == want
    assert found["bracketed"] is (want is not None)
    assert why in found["why"]
    if want is None:
        assert found["why"].startswith("not bracketed: ")
        assert found["four_fifths"] is None
    else:
        assert found["four_fifths"] == pytest.approx(0.8 * want)
        assert found["edge_rps"] > want
        assert all(x < want for x in found["failed_below"])


def test_knee_never_returns_the_highest_rate_it_was_given():
    """What PR 25's sweep did: every rate held and the top one came back
    as the knee."""
    held = rows({1: {1.0: 1.0, 1.2: 1.0, 1.4: 0.99},
                 2: {1.0: 1.0, 1.2: 1.0, 1.4: 0.98}})
    assert sweep.knee(held)["knee_rps"] is None
    assert sweep.knee(held, sustained=0.985)["knee_rps"] == 1.2


def test_sweep_end_to_end_at_a_tiny_size(tmp_path, capsys):
    """One process, two seeds, two rates, and an earlier call's rows
    beside them: every row carries the cell's own tail and the queue
    waits of the window's two ends, and the last line is the knee's."""
    earlier = tmp_path / "rows.jsonl"
    earlier.write_text(
        "commentary\n"
        + json.dumps({"rate_rps": 4000.0, "seed": 1, "share": 0.1}) + "\n"
        + json.dumps({"bracketed": False}) + "\n")
    got, found = sweep.main(
        ["--workload", "tiny.chat", "--seconds", "1.5", "--seeds", "5", "9",
         "--rates", "10", "20", "--rows", str(earlier)],
        require_tpu=False, benchmark_path=CELLS)
    assert [(r["seed"], r["rate_rps"]) for r in got[1:]] == [
        (5, 10.0), (5, 20.0), (9, 10.0), (9, 20.0)]
    for r in got[1:]:
        assert r["requests"] == round(r["rate_rps"] * 1.5)
        assert r["requests_failed"] == 0 and r["compilations_in_window"] == 0
        assert "itl_p95_ms" in r and "itl_p50_ms" in r   # tiny.chat's tail
        assert r["queue_wait_first_quarter_p50_ms"] is not None
        assert r["queue_wait_last_quarter_max_ms"] is not None
        assert 0 < r["lanes_in_use"] <= 8
        assert 0 < r["iterations_prefilling_share"] <= 1
    # the made-up row at 4,000 a second fails, so the rows bracket or a
    # tiny rate failed on this machine: either way no knee is the top rate
    assert found["knee_rps"] != 4000.0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == found
