"""Find the knee of an open-loop cell again: one process, one set-up,
a window at each of a few rates, on each of a few seeds.

    python benchmark/sweep.py --workload mistral-7b.chat-steady \\
        --seconds 40 --seeds 1 2 --rates 6 8 10 12 14

Each rate runs `preload_seconds` before its window, as the cell does, so
that the window is a slice of the steady state. A rate holds where the
output tokens delivered in the window are at least 97 % of those offered
in it (the tokens of the requests due in it), on every seed that ran it.
The knee is the highest rate that holds, and `knee()` gives one only
where the rows bracket it: the highest rate swept fails, the lowest
holds, and the knee held on two seeds or more. Rows of an
earlier call (`--rows <file of their lines>`) count with this call's, so
that a wide sweep and a finer one around the edge make one answer. The
cell's traffic file then states four fifths of the knee as `rate_rps`.
Not the driver's command: run it on the chip by hand when a change to
the program may have moved the knee. A `benchmark` PR then re-sets
`rate_rps` and `rate_from` in the cell's traffic file in place (the cell
keeps its name, and every cell is measured anew); every other kind of PR
leaves the file alone.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SUSTAINED = 0.97
MIN_SEEDS = 2


def knee(rows, sustained=SUSTAINED, min_seeds=MIN_SEEDS):
    """The knee out of a sweep's rows (`rate_rps`, `seed`, `share`), or
    why they do not bracket one: `{"bracketed", "knee_rps",
    "four_fifths", "edge_rps", "failed_below", "why"}`, the two rates
    None where `bracketed` is false. A rate fails where any seed's share
    of the offered tokens delivered is under `sustained`. The knee is
    the highest rate that holds, and the rows bracket it only if the
    highest rate swept fails, the lowest holds, and the knee ran on
    `min_seeds` seeds. A rate that fails below the knee is named in
    `failed_below` and does not move it: a server that holds a higher
    rate on every seed holds a lower one, and the share delivered in one
    window swings by a few per cent where requests live a third of it."""
    shares = {}
    for r in rows:
        shares.setdefault(float(r["rate_rps"]), {})[r["seed"]] = r["share"]
    rates = sorted(shares)
    failed = [x for x in rates if min(shares[x].values()) < sustained]

    def no(why, edge=None):
        return {"bracketed": False, "knee_rps": None, "four_fifths": None,
                "edge_rps": edge, "failed_below": [],
                "why": "not bracketed: " + why}

    if not rates:
        return no("no rows")
    if rates[-1] not in failed:
        return no("the highest rate swept, %g, still holds" % rates[-1])
    if rates[0] in failed:
        return no("the lowest rate swept, %g, does not hold" % rates[0],
                  rates[0])
    at = max(x for x in rates if x not in failed)
    edge = min(x for x in failed if x > at)
    if len(shares[at]) < min_seeds:
        return no("%g, the highest rate that holds, ran on %d seed(s) of "
                  "the %d it has to hold on" % (at, len(shares[at]),
                                                min_seeds), edge)
    below = [x for x in failed if x < at]
    return {"bracketed": True, "knee_rps": at, "four_fifths": 0.8 * at,
            "edge_rps": edge, "failed_below": below,
            "why": "%g holds on %d seeds (least share %.3f) and %g fails "
                   "(least share %.3f)%s" % (
                       at, len(shares[at]), min(shares[at].values()), edge,
                       min(shares[edge].values()),
                       "; below it %s failed on a seed" % ", ".join(
                           "%g" % x for x in below) if below else "")}


def quarters(reqs, due, t0, seconds):
    """The queue waits (ms) of the requests due in the window's first
    quarter and in its last: a backlog that grows shows as the second
    above the first."""
    first, last = [], []
    for r in reqs:
        if r.t_admit is None:
            continue
        at = due[id(r)] - t0
        if at < seconds / 4:
            first.append((r.t_admit - r.t_submit) * 1e3)
        elif at >= 3 * seconds / 4:
            last.append((r.t_admit - r.t_submit) * 1e3)
    return first, last


def main(argv=None, **context):
    """`context` is for the tests alone (`require_tpu`, `benchmark_path`
    of `run.open_context`); the command passes none."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--rows", help="a file of an earlier call's rows, one "
                    "JSON object a line, to choose the knee with")
    args = ap.parse_args(argv)

    from benchmark import run, stats
    from benchmark.drivers import serve_open, serving

    ctx = run.open_context(args.workload, args.seeds[0], args.seconds, 0,
                           t_process=time.perf_counter(), **context)
    traffic = ctx.traffic
    served = serving.Served(ctx)
    served.warm_shapes()
    vocab = ctx.dims["vocab_size"]
    tail_q = traffic["tails"]["itl"]
    rows = []
    if args.rows:
        with open(args.rows) as f:   # commentary and the knee's line are left out
            rows = [r for r in map(json.loads, (line for line in f
                                                if line.startswith("{")))
                    if "share" in r]
    for seed in args.seeds:
        for i, rate in enumerate(args.rates):
            at_rate = dict(traffic, rate_rps=rate)
            todo, offsets = serve_open.schedule(at_rate, vocab, seed + i,
                                                args.seconds)
            served.warm_key_schedules(todo)
            sent_before = len(served.requests)
            sched, opened = served.sched, {}

            def on_open():
                opened["prefill_programs"] = sched.prefill_programs
                opened["compiles"] = ctx.compiles["compiles"]

            reqs, due, before, after, t0 = serve_open.drive(
                served, todo, offsets, args.seconds, opened=on_open)
            prefills = sched.prefill_programs - opened["prefill_programs"]
            compiled = ctx.compiles["compiles"] - opened["compiles"]
            t1 = t0 + args.seconds
            # steady state: what was due in the window against what was
            # delivered in it, by requests due in it or before it
            offered = sum(r.max_new_tokens for r in reqs)
            delivered = sum(t0 <= s < t1
                            for r in served.requests[sent_before:]
                            for s in r.token_times)
            serving.wait_all(served.requests[sent_before:], 900)  # empty again
            ttft, itl = serving.tails(reqs, due)
            waits = [(r.t_admit - r.t_submit) * 1e3 for r in reqs]
            first, last = quarters(reqs, due, t0, args.seconds)
            d = {k: after[k] - before[k] for k in after}
            row = {"rate_rps": rate, "seed": seed, "requests": len(reqs),
                   "offered_tokens_per_s": offered / args.seconds,
                   "delivered_tokens_per_s": delivered / args.seconds,
                   "share": delivered / max(offered, 1),
                   "requests_failed": sum(r.reason != "length" for r in reqs),
                   "compilations_in_window": compiled,
                   "late_max_ms": max((r.t_submit - due[id(r)]) * 1e3
                                      for r in reqs),
                   "queue_wait_p50_ms": stats.median(waits),
                   "queue_wait_max_ms": max(waits),
                   "queue_wait_first_quarter_p50_ms": stats.median(first),
                   "queue_wait_last_quarter_p50_ms": stats.median(last),
                   "queue_wait_first_quarter_max_ms": max(first, default=None),
                   "queue_wait_last_quarter_max_ms": max(last, default=None),
                   "lanes_in_use": d["occupancy_sum"] * served.slots
                   / max(d["decode_steps"], 1),
                   "iterations": d["iteration"],
                   "iterations_prefilling_share": prefills
                   / max(d["iteration"], 1),
                   "ttft_p50_ms": stats.median(ttft),
                   "ttft_p75_ms": stats.percentile(ttft, 75),
                   "itl_samples": len(itl),
                   "itl_p50_ms": stats.median(itl),
                   "itl_p%d_ms" % tail_q: stats.percentile(itl, tail_q),
                   "itl_p95_ms": stats.percentile(itl, 95)}
            rows.append(row)
            print(json.dumps(row), flush=True)
    served.stop()
    found = knee(rows)
    print(json.dumps(found))
    return rows, found


if __name__ == "__main__":
    main()
