"""kind `serve_open`: an open loop. Requests are due at seeded Poisson
arrivals at the traffic file's fixed rate, whatever the server does;
first-token time counts from the due time, and how late the generator
ran is reported beside it."""

import time

import jax

from .. import harness, loadgen, stats
from . import serving


def schedule(traffic, vocab, seed, seconds):
    """The requests and when each is due, in seconds from the window's
    start. The window holds round(rate x seconds) requests for every
    seed: the same sizes and the same gaps in another order, and the sum
    of the gaps does not depend on the order. Before it, from
    `preload_seconds` before the window's start, the same rate runs with
    draws of its own, so that the window opens on a system already
    carrying its steady load; those requests are set-up and are not
    measured."""
    rate, pre = traffic["rate_rps"], traffic.get("preload_seconds", 0)
    n = max(1, round(rate * seconds))
    todo = loadgen.requests(traffic, vocab, seed, n)
    offsets = loadgen.arrivals(rate, n, seed)
    n_pre = round(rate * pre)
    if n_pre:
        before = loadgen.arrivals(rate, n_pre, seed + 1)
        todo = loadgen.requests(traffic, vocab, seed, n_pre, stream=1) + todo
        offsets = [o - before[-1] for o in before[:-1]] + [-1e-9] + offsets
    return todo, offsets


def drive(served, todo, offsets, seconds, opened=None):
    """Send each request when it is due, whatever the server does, until
    `seconds` past the window's start; `opened()` is called as the
    window opens. Returns the window's requests, their due times, the
    scheduler's counters at both ends of the window, and its start."""
    t0 = time.time() - min(0.0, offsets[0])
    before = None
    reqs, due = [], {}
    for (tokens, max_new), offset in zip(todo, offsets):
        if offset > seconds:
            break
        if offset >= 0 and before is None:
            wait = t0 - time.time()
            if wait > 0:
                time.sleep(wait)
            before = served.counters()
            if opened:
                opened()
        wait = t0 + offset - time.time()
        if wait > 0:
            time.sleep(wait)
        with jax.profiler.TraceAnnotation("bench.send"):
            req = served.submit(tokens, max_new)
        if offset >= 0:
            reqs.append(req)
            due[id(req)] = t0 + offset
    wait = t0 + seconds - time.time()
    if wait > 0:
        time.sleep(wait)
    return reqs, due, before or served.counters(), served.counters(), t0


def drain(reqs, cap_s):
    """Wait for the window's requests to finish: below the knee they all
    do. One that has not within `cap_s` of the window's end has failed."""
    end = time.time() + cap_s
    while time.time() < end and not all(serving.done(r) for r in reqs):
        time.sleep(serving.POLL_S * 5)


def run(ctx):
    from metaflow_tpu import device

    t = ctx.traffic
    served = serving.Served(ctx)
    served.warm_shapes()
    vocab = ctx.dims["vocab_size"]
    warm = [served.submit(tok, n) for tok, n in loadgen.requests(
        t, vocab, ctx.seed, t["warmup_requests"], stream=1)]
    serving.wait_all(warm, 600)
    todo, offsets = schedule(t, vocab, ctx.seed, ctx.seconds)
    served.warm_key_schedules(todo)
    ctx.log("warm; %d requests at %.3f a second, %d before the window",
            len(todo), t["rate_rps"], sum(o < 0 for o in offsets))

    # ---- the window (it opens inside drive, after the preload) ----
    slice_ = harness.TraceSlice(ctx)
    opened = {}

    def on_open():
        opened["t"] = time.perf_counter()
        opened["compiles"] = ctx.compiles["compiles"]
        opened["setup_s"] = ctx.setup_seconds(opened["t"])
        slice_.in_thread(opened["t"])

    reqs, due, before, after, t0 = drive(
        served, todo, offsets, ctx.seconds, opened=on_open)
    compiles_before, setup_s = opened["compiles"], opened["setup_s"]
    reduced = slice_.close(1)
    drain(reqs, t["drain_cap_seconds"])
    unfinished = [r for r in reqs if not serving.done(r)]
    served.stop()
    compiled_in_window = ctx.compiles["compiles"] - compiles_before
    memory_peak = device.peak_bytes_in_use()
    finished = [r for r in reqs if serving.done(r)]
    failed = len(unfinished) + sum(r.reason != "length" for r in finished)
    ttft, itl = serving.tails(reqs, due)
    serving.report_samples(ctx, "ttft_ms", ttft)
    serving.report_samples(ctx, "itl_ms", itl)
    run_ = serving.layer_readings(served, reqs, due, before, after,
                                  (t0, t0 + ctx.seconds))
    run_.update(kind="serve_open", trace=reduced, ttft_ms=ttft)

    served.free()
    serving.compare_with_reference(ctx, served, finished)
    ctx.check("compilations_in_window", compiled_in_window, 0)
    ctx.check("requests_failed", failed, 0)

    end_to_end = {"setup_s": setup_s}
    # which tail of each the cell reports is the traffic file's to say:
    # the highest its requests in a window leave ten samples beyond
    for what, values in (("ttft", ttft), ("itl", itl)):
        if what in t["tails"]:
            tail = stats.percentile(values, t["tails"][what])
            if tail is not None:
                end_to_end["%s_p%d_ms" % (what, t["tails"][what])] = tail
    return {"attempted": len(reqs), "failed": failed,
            "memory_peak_bytes": memory_peak, "end_to_end": end_to_end,
            "run": run_}
