"""Find the knee of an open-loop cell again: one process, one set-up,
a window at each of a few rates.

    python benchmark/sweep.py --workload mistral-7b.chat-steady \\
        --seconds 40 --rates 0.6 0.8 1.0 1.2 1.4 1.6

Each rate runs `preload_seconds` before its window, as the cell does, so
that the window is a slice of the steady state. The knee is the highest
rate at which the output tokens delivered in the window are at least
97 % of those offered in it (the tokens of the requests due in it). The cell's traffic file then states four fifths of it as
`rate_rps`. Not the driver's command: run it on the chip by hand when a
change to the program may have moved the knee, and write a new traffic
file (a cell's files are never edited).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SUSTAINED = 0.97


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()

    from benchmark import run, stats
    from benchmark.drivers import serve_open, serving

    ctx = run.open_context(args.workload, args.seed, args.seconds, 0,
                           t_process=time.perf_counter())
    traffic = ctx.traffic
    served = serving.Served(ctx)
    served.warm_shapes()
    vocab = ctx.dims["vocab_size"]
    rows = []
    for i, rate in enumerate(args.rates):
        at_rate = dict(traffic, rate_rps=rate)
        todo, offsets = serve_open.schedule(at_rate, vocab, args.seed + i,
                                            args.seconds)
        served.warm_key_schedules(todo)
        sent_before = len(served.requests)
        reqs, due, before, after, t0 = serve_open.drive(
            served, todo, offsets, args.seconds)
        t1 = t0 + args.seconds
        # steady state: what was due in the window against what was
        # delivered in it, by requests due in it or before it
        offered = sum(r.max_new_tokens for r in reqs)
        delivered = sum(t0 <= s < t1 for r in served.requests[sent_before:]
                        for s in r.token_times)
        serving.wait_all(served.requests[sent_before:], 900)  # empty again
        ttft, itl = serving.tails(reqs, due)
        waits = [(r.t_admit - r.t_submit) * 1e3 for r in reqs]
        row = {"rate_rps": rate, "requests": len(reqs),
               "offered_tokens_per_s": offered / args.seconds,
               "delivered_tokens_per_s": delivered / args.seconds,
               "share": delivered / max(offered, 1),
               "queue_wait_p50_ms": stats.median(waits),
               "queue_wait_max_ms": max(waits),
               "ttft_p50_ms": stats.median(ttft),
               "ttft_p75_ms": stats.percentile(ttft, 75),
               "itl_p50_ms": stats.median(itl),
               "itl_p95_ms": stats.percentile(itl, 95)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    served.stop()
    held = [r["rate_rps"] for r in rows if r["share"] >= SUSTAINED]
    print(json.dumps({"knee_rps": max(held) if held else None,
                      "four_fifths": 0.8 * max(held) if held else None}))


if __name__ == "__main__":
    main()
