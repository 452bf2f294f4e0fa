"""What the two serving kinds share: the program's slot engine and
scheduler, built in process the way `tpuflow serve` builds them
(`cmd.serve.build_engine`, `Scheduler(engine).start()`), the warm-up of
every shape the traffic uses, the scheduler's counters over the window,
and the comparison with the reference once the window has closed.
"""

import gc
import time

import numpy as np

from .. import configs, reference, stats, weights

POLL_S = 0.002


class Served(object):
    """The engine and its scheduler for one run."""

    def __init__(self, ctx):
        import jax

        from metaflow_tpu.cmd.serve import build_engine
        from metaflow_tpu.serving import Scheduler

        self.ctx = ctx
        s = ctx.config["serving"]
        self.slots, self.max_seq_len = s["slots"], s["max_seq_len"]
        self.prefill_chunk = s["prefill_chunk"]
        _, cfg = configs.program_config(ctx.config, self.max_seq_len)
        dims = ctx.dims
        self.params = jax.jit(lambda k: weights.init_params(k, dims))(
            weights.seed_key(ctx.seed))
        jax.block_until_ready(self.params)
        ctx.log("weights on the device: %d layers", dims["n_layers"])
        self.engine = build_engine(self.params, cfg, slots=self.slots,
                                   max_seq_len=self.max_seq_len,
                                   prefill_chunk=self.prefill_chunk)
        # the queue never refuses: what waits is measured, not shed
        self.sched = Scheduler(self.engine, max_queue=1 << 20).start()
        self.requests = []

    def submit(self, tokens, max_new):
        from metaflow_tpu.serving import Request

        req = self.sched.submit(Request(tokens, max_new_tokens=max_new,
                                        temperature=0.0, eos_id=None, rng=0))
        self.requests.append(req)
        return req

    def warm_shapes(self):
        """One request for each prefill bucket as a prompt's last chunk
        (which also shapes the first-token program) and a full chunk
        before it, then the fused decode step: every program the
        window can call."""
        chunk = self.engine.prefill_chunk
        sizes, b = [], self.engine.min_bucket
        while b <= chunk:
            sizes.append(b)
            b *= 2
        rng = np.random.default_rng(0)
        warm = [self.submit(rng.integers(1, self.ctx.dims["vocab_size"],
                                         chunk + size).tolist(), 4)
                for size in sizes]
        wait_all(warm, 600)

    def warm_key_schedules(self, todo):
        """The program draws a request's sampling keys when it admits it,
        `jax.random.split(key, max_new_tokens - 1)`, and that compiles
        once for every new length. Each length the window will ask for
        is split once here, so that the window compiles nothing."""
        import jax

        key = jax.random.split(jax.random.PRNGKey(0))[0]
        for n in sorted({max_new for _, max_new in todo}):
            if n > 1:
                jax.random.split(key, n - 1)

    def counters(self):
        s = self.sched
        return {"t": time.time(), "busy_prefill_s": s.busy_prefill_s,
                "busy_decode_s": s.busy_decode_s,
                "decode_steps": s.decode_steps, "iteration": s.iteration,
                "occupancy_sum": s._occupancy_sum}

    def stop(self):
        self.sched.stop()

    def free(self):
        """Drop the engine's state; the seeded weights stay for the
        reference, which the benchmark made and the program only read."""
        self.sched = self.engine = None
        gc.collect()


def done(req):
    return req.state in ("finished", "cancelled") or req.t_done is not None


def wait_all(reqs, timeout_s):
    end = time.time() + timeout_s
    while not all(done(r) for r in reqs):
        if time.time() > end:
            raise RuntimeError("requests did not finish in %d s" % timeout_s)
        time.sleep(POLL_S * 5)


def compare_with_reference(ctx, served, finished):
    """A seeded sample of the requests the window finished, the longest
    among them: the widest gap by which a served token's logit lies
    below the reference's best at its position."""
    lim = ctx.traffic["limits"]
    finished = [r for r in finished if r.reason == "length"]
    if not finished:
        ctx.check("served_requests_checked_short_of_1", 1, 0)
        return
    rng = np.random.default_rng([ctx.seed, 3])
    longest = max(finished, key=lambda r: len(r.tokens) + len(r.generated))
    others = [r for r in finished if r is not longest]
    k = min(len(others), ctx.traffic["checked_requests"] - 1)
    sample = [longest] + [others[i] for i in
                          rng.choice(len(others), k, replace=False)]
    t0 = time.perf_counter()
    every, control = [], []
    # one shape for every request: the mix's longest, to a multiple of 128
    longest_mix = (ctx.traffic["prompt_tokens"]["max"]
                   + ctx.traffic["output_tokens"]["max"])
    pad_to = min(served.max_seq_len, -(-longest_mix // 128) * 128)
    for r in sample:
        gaps = reference.served_gaps(served.params, r.tokens, r.generated,
                                     ctx.dims, pad_to=pad_to)
        every.extend(gaps.tolist())
        if ctx.control:
            control.extend(reference.served_gaps(
                served.params, r.tokens, r.generated, ctx.dims,
                pad_to=pad_to, control=True).tolist())
    ctx.log("reference over %d requests, %d served tokens, in %.1f s; "
            "%d of them not the reference's best, %d more than 0.1 below it",
            len(sample), len(every), time.perf_counter() - t0,
            sum(g > 0 for g in every), sum(g > 0.1 for g in every))
    # the widest gap swings by its nature (and a routing near tie in an
    # expert layer moves a token's logits by whole units); the mean over
    # the served tokens is the steady number. A cell's traffic file holds
    # a limit for each number that separates in it; the other is printed.
    readings = {"served_logit_gap": max(every),
                "served_logit_gap_mean": sum(every) / len(every)}
    for name, value in readings.items():
        if name in lim:
            ctx.check(name, value, lim[name])
        else:
            ctx.log("%s %r (no limit in this cell)", name, value)
    if ctx.control:
        ctx.control_reading("served_logit_gap", max(control))
        ctx.control_reading("served_logit_gap_mean",
                            sum(control) / len(control))


def decode_reads(requests, t0, t1):
    """(tokens the decode steps of [t0, t1) made, cached positions those
    steps had to read), over all requests: a request's k-th token
    (k >= 1; its first comes from the prefill) is made by a step that
    attends to its prompt and the k tokens before it."""
    made = [len(r.tokens) + k for r in requests
            for k, at in enumerate(r.token_times) if k and t0 <= at < t1]
    return len(made), sum(made)


def layer_readings(served, reqs, due, before, after, window):
    """What the per-layer readers find in a serving run; `window` is
    its two ends on the clock of the requests' timestamps."""
    d = {k: after[k] - before[k] for k in after}
    admitted = [r for r in reqs if r.t_admit is not None]
    first = [r for r in admitted if r.t_first is not None]
    decode_tokens, kv_positions_read = decode_reads(served.requests, *window)
    return dict(
        served.ctx.run_sizes(), slots=served.slots,
        max_seq_len=served.max_seq_len, prefill_chunk=served.prefill_chunk,
        queue_wait_ms=[(r.t_admit - r.t_submit) * 1e3 for r in admitted],
        prefill_ms_per_ktok=[
            (r.t_first - r.t_admit) * 1e6 / len(r.tokens) for r in first],
        late_ms=[(r.t_submit - due[id(r)]) * 1e3 for r in reqs
                 if id(r) in due],
        counters=d, decode_tokens=decode_tokens,
        kv_positions_read=kv_positions_read)


def tails(reqs, due):
    ttft = [(r.t_first - due[id(r)]) * 1e3 for r in reqs
            if r.t_first is not None]
    itl = [(b - a) * 1e3 for r in reqs
           for a, b in zip(r.token_times, r.token_times[1:])]
    return ttft, itl


def report_samples(ctx, name, values):
    ctx.log("%s: %d samples, median %s, p75 %s, p90 %s, p95 %s", name,
            len(values), stats.median(values), stats.percentile(values, 75),
            stats.percentile(values, 90), stats.percentile(values, 95))
