"""kind `serve_closed`: a closed loop. As many clients as the engine has
slots, each sending its next request when its last one ends, so every
slot is full all through; the window is a slice of that steady state and
counts the output tokens delivered in it."""

import time

import jax

from .. import harness, loadgen
from . import serving


def run(ctx):
    from metaflow_tpu import device

    t = ctx.traffic
    served = serving.Served(ctx)
    served.warm_shapes()
    first = loadgen.requests(t, ctx.dims["vocab_size"], ctx.seed,
                             t["requests_per_seed"])
    served.warm_key_schedules(first)

    def endless():
        """The seed's requests, and the same sizes in a new order for as
        long as the clients keep asking."""
        yield from first
        stream = 2
        while True:
            yield from loadgen.requests(t, ctx.dims["vocab_size"], ctx.seed,
                                        t["requests_per_seed"], stream=stream)
            stream += 1

    todo = endless()
    clients = served.slots if t["clients"] == "slots" else int(t["clients"])
    in_flight, ended = [], []

    def top_up():
        with jax.profiler.TraceAnnotation("bench.send"):
            for r in [r for r in in_flight if serving.done(r)]:
                in_flight.remove(r)
                ended.append(r)
            while len(in_flight) < clients:
                tokens, max_new = next(todo)
                in_flight.append(served.submit(tokens, max_new))

    def hold(seconds):
        end = time.time() + seconds
        while time.time() < end:
            top_up()
            time.sleep(serving.POLL_S)

    hold(t["warmup_seconds"])
    ctx.log("warm; %d clients", clients)

    # ---- the window ----
    compiles_before = ctx.compiles["compiles"]
    slice_ = harness.TraceSlice(ctx)
    t_window = time.perf_counter()
    setup_s = ctx.setup_seconds(t_window)
    t0 = time.time()
    before = served.counters()
    mark = len(ended)
    slice_.in_thread(t_window)
    hold(ctx.seconds)
    t1 = time.time()
    after = served.counters()
    reduced = slice_.close(1)
    top_up()
    served.stop()   # what is still in flight ends as "shutdown"
    compiled_in_window = ctx.compiles["compiles"] - compiles_before
    memory_peak = device.peak_bytes_in_use()
    finished = ended[mark:]
    delivered = sum(t0 <= s < t1 for r in served.requests
                    for s in r.token_times)
    failed = sum(r.reason != "length" for r in finished)
    ctx.log("window: %d output tokens in %.3f s, %d requests ended",
            delivered, t1 - t0, len(finished))
    run_ = serving.layer_readings(served, finished, {}, before, after,
                                  (t0, t1))
    run_.update(kind="serve_closed", trace=reduced)

    served.free()
    serving.compare_with_reference(ctx, served, finished)
    ctx.check("compilations_in_window", compiled_in_window, 0)
    ctx.check("requests_failed", failed, 0)
    return {"attempted": len(finished), "failed": failed,
            "memory_peak_bytes": memory_peak,
            "end_to_end": {"serve_tokens_per_s": delivered / (t1 - t0),
                           "setup_s": setup_s},
            "run": run_}
