"""Training-step telemetry: wall time, tokens/sec, MFU, compile cache,
device-memory high-water — emitted through the run's flight recorder.

The papers this repo leans on (arxiv 2011.03641, 2104.06272) attribute
their wins to exactly this per-step timing/utilization telemetry; the
reference framework delegates it to user frameworks. Here it is built in:
wrap any jitted train step with `instrument_train_step` (or pass
`telemetry=...` to `make_trainer`) and every step emits a `train.step`
timer record with tokens/sec and MFU attached, compile events are
detected via the jit cache, and an on-demand `jax.profiler` capture
(telemetry.ProfileTrigger) can be armed on a live run.

Timing semantics: step N's duration is the host wall-clock interval
between the dispatch of step N and step N+1. With donated buffers the
host throttles to the device rate in steady state, so the interval IS
the device step time without inserting a per-step `block_until_ready`
(which would serialize the pipeline the telemetry is measuring).
"""

import functools
import os
import time

from .. import knobs

from .. import progress as progress_mod
from .. import telemetry

# bf16 peak TFLOP/s per chip, from published TPU specs (substring-matched
# against jax Device.device_kind so "TPU v5 lite" and "TPU v5e" both hit).
# The library's own table, for step telemetry (`StepTelemetry`) and
# `chip_smoke.py`; the benchmark keeps its peaks in `benchmark/peaks.py`,
# which the library may not import.
TPU_PEAK_TFLOPS = [
    ("v5p", 459.0),
    ("v5e", 197.0),
    ("v5 lite", 197.0),
    ("v6e", 918.0),
    ("v4", 275.0),
    ("v3", 123.0),
]

# HBM bandwidth GB/s per chip, same sources
TPU_HBM_GBPS = [
    ("v5p", 2765.0),
    ("v5e", 819.0),
    ("v5 lite", 819.0),
    ("v6e", 1640.0),
    ("v4", 1228.0),
    ("v3", 900.0),
]


def _chip_row(table, device_kind, what):
    """The table row a device_kind matches. A CPU has none (None); a TPU
    that matches none is an error — a utilization against no peak, or
    against a guessed one, is not a number."""
    kind = (device_kind or "").lower()
    row = next((r for r in table if r[0] in kind), None)
    if row is None and "tpu" in kind:
        raise ValueError(
            "no %s recorded for device_kind %r: add its published figure "
            "to training/metrics.py" % (what, device_kind))
    return row


def peak_tflops(device_kind):
    """Published bf16 peak TFLOP/s for a chip kind; None on the CPU.

    TPUFLOW_PEAK_TFLOPS overrides the table."""
    override = knobs.get_raw("TPUFLOW_PEAK_TFLOPS")
    if override:
        try:
            return float(override)
        except ValueError:
            pass
    row = _chip_row(TPU_PEAK_TFLOPS, device_kind, "bf16 peak TFLOP/s")
    return row[1] if row else None


def hbm_gbps(device_kind):
    row = _chip_row(TPU_HBM_GBPS, device_kind, "HBM GB/s")
    return row[1] if row else None


def flops_per_token_dense(n_params, n_layers, dim, seq):
    """Train-step FLOPs/token for a dense transformer (fwd+bwd = 3x fwd):
    6*N + 12*L*D*S, the PaLM appendix-B convention (a caller that passes
    every parameter, the embedding table included, overstates it a little)."""
    return 6.0 * n_params + 12.0 * n_layers * dim * seq


def _cache_size(fn):
    try:
        return fn._cache_size()
    except Exception:
        return None


def _device_memory_bytes():
    """(in_use, peak) device memory in bytes for the worst local device;
    falls back to the live-array footprint where the backend exposes no
    allocator stats (CPU)."""
    import jax

    in_use = peak = None
    try:
        for dev in jax.local_devices():
            stats = dev.memory_stats()
            if not stats:
                continue
            in_use = max(in_use or 0, stats.get("bytes_in_use", 0))
            peak = max(peak or 0,
                       stats.get("peak_bytes_in_use",
                                 stats.get("bytes_in_use", 0)))
    except Exception:
        pass
    if in_use is None:
        try:
            in_use = sum(int(a.nbytes) for a in jax.live_arrays())
        except Exception:
            return None, None
    return in_use, peak if peak is not None else in_use


def _tree_device_bytes(tree):
    """Per-device resident bytes for a pytree of sharded arrays.

    Metadata-only (shape/dtype/sharding.shard_shape) so it is safe on
    DONATED buffers — the train step consumed its input state, but the
    layout survives deletion. Replicated leaves count full size (each
    device holds a copy); a ZeRO/fsdp-sharded leaf counts 1/N — this is
    the gauge the sharded-update memory win shows up in. SPMD placement
    is uniform across devices, so one device's sum is every device's."""
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree.leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        sharding = getattr(leaf, "sharding", None)
        try:
            shard_shape = sharding.shard_shape(tuple(shape))
        except Exception:
            shard_shape = tuple(shape)
        n = 1
        for d in shard_shape:
            n *= int(d)
        total += n * np.dtype(dtype).itemsize
    return total


class TrainStepTelemetry(object):
    """Per-step metric emitter driven by instrument_train_step."""

    def __init__(self, tokens_per_step=None, flops_per_step=None,
                 cost_analysis=False, prefix="train", memory_every=10,
                 profile=True):
        self.tokens_per_step = tokens_per_step
        self.flops_per_step = flops_per_step
        self._want_cost_analysis = cost_analysis
        self.prefix = prefix
        self.memory_every = max(1, int(memory_every))
        self.step_num = 0
        self.compiles = 0
        self.compile_ms = 0.0
        self._compile_steps = set()
        self._prev_start = None
        self._prev_return = None
        self._stalls = []
        self._intervals = []
        self._mem_peak = 0
        self._mem_split = {}
        self._update_ms = []
        self._pending_update_ms = None
        self._transfer_ms = []
        self._pending_transfer_ms = None
        self._per_chip = None  # (n_devices, peak_tflops) lazy
        self._profile = None
        self._want_profile = profile
        self._closed = False
        self._step_ema_s = None  # steady-state step-time EMA (hang deadline)

    # ---------- lazy hardware context ----------

    def _chip_context(self):
        if self._per_chip is None:
            import jax

            n = jax.device_count()
            kind = jax.devices()[0].device_kind
            self._per_chip = (n, peak_tflops(kind), kind)
        return self._per_chip

    def _trigger(self):
        if self._profile is None and self._want_profile:
            self._profile = telemetry.ProfileTrigger(
                recorder=telemetry.current_recorder())
        return self._profile

    # ---------- per-step hooks ----------

    def before_step(self):
        now = time.perf_counter()
        trigger = self._trigger()
        if trigger is not None:
            trigger.on_step(self.step_num)
        # host time between the previous step's return and this call is
        # the input stall: the train loop was blocked in next(iterator)
        # (plus loop overhead) instead of dispatching — the signal that a
        # run is INPUT-bound. It lands inside step N-1's wall interval,
        # so it rides that step's record.
        stall_s = (None if self._prev_return is None
                   else now - self._prev_return)
        if self._prev_start is not None:
            self._emit_step(self.step_num - 1, now - self._prev_start,
                            stall_s=stall_s)
        self._prev_start = now
        # per-rank progress beat: the hang watchdog's liveness channel.
        # Deadline is adaptive (max(floor, mult × EMA)); while a compile
        # is still POSSIBLE — no steady-state interval yet, or the step
        # just before this one compiled (retraces come in bursts) — the
        # much larger compile grace applies, so a long first-step compile
        # never reads as a hang.
        compile_possible = (
            self._step_ema_s is None
            or (self.step_num - 1) in self._compile_steps)
        progress_mod.beat(
            step_num=self.step_num, phase=self.prefix,
            deadline_s=progress_mod.hang_deadline_s(
                ema_s=self._step_ema_s,
                compile_possible=compile_possible))
        return now

    def after_step(self, step_fn, call_started, pre_cache, args, kwargs):
        """Compile detection + one-time cost-analysis FLOPs resolution."""
        dt = time.perf_counter() - call_started
        size = _cache_size(step_fn)
        if size is not None and pre_cache is not None and size > pre_cache:
            # the jit cache grew during this call: it traced + compiled
            self.compiles += size - pre_cache
            self.compile_ms += dt * 1000
            self._compile_steps.add(self.step_num)
            telemetry.emit("timer", "%s.compile" % self.prefix,
                           ms=dt * 1000, ok=True, step_num=self.step_num)
            telemetry.counter("%s.compile_cache_miss" % self.prefix)
        # cache hits are derived in report() (calls - compiles): a
        # per-step hit counter would be pure record noise
        if (self.flops_per_step is None and self._want_cost_analysis
                and self.step_num == 0):
            self.flops_per_step = self._flops_from_cost_analysis(
                step_fn, args, kwargs)
        if self.step_num % self.memory_every == 0:
            in_use, peak = _device_memory_bytes()
            if in_use is not None:
                self._mem_peak = max(self._mem_peak, peak or in_use)
                telemetry.gauge(
                    "%s.device_memory_bytes" % self.prefix, in_use,
                    step_num=self.step_num,
                    data={"peak": peak} if peak else None)
            self._emit_memory_split(args, peak or in_use)
        # diagnostic split-step mode (make_train_step timed_update=True)
        # exposes the update's wall time as an attribute; ride it into the
        # NEXT emitted record — _emit_step(N) fires before after_step(N+1)
        update_ms = getattr(step_fn, "last_update_ms", None)
        if update_ms is not None:
            self._pending_update_ms = float(update_ms)
        # MPMD stage steps expose the wall-clock they spent BLOCKED on
        # the stage transport (spmd/mpmd.py) the same way — the
        # PIPELINE-BOUND signal `tpuflow metrics` surfaces per stage
        transfer_ms = getattr(step_fn, "last_transfer_stall_ms", None)
        if transfer_ms is not None:
            self._pending_transfer_ms = float(transfer_ms)
        self.step_num += 1
        self._prev_return = time.perf_counter()

    def _emit_memory_split(self, args, peak):
        """Split the high-water gauge: params vs optimizer state are
        metadata-exact per device (see _tree_device_bytes); activations is
        the remainder of the allocator peak — on backends with no
        allocator stats (CPU) the remainder is live-footprint-derived and
        only a rough upper bound, but the params/opt split stays exact."""
        state = args[0] if args else None
        if not (isinstance(state, dict) and "params" in state
                and "opt_state" in state):
            return
        try:
            params_b = _tree_device_bytes(state["params"])
            opt_b = _tree_device_bytes(state["opt_state"])
        except Exception:
            return
        split = {"params": params_b, "opt_state": opt_b}
        if peak:
            split["activations"] = max(0, int(peak) - params_b - opt_b)
        self._mem_split = split
        for key, value in split.items():
            telemetry.gauge("%s.memory.%s_bytes" % (self.prefix, key),
                            value, step_num=self.step_num)

    def _flops_from_cost_analysis(self, step_fn, args, kwargs):
        """XLA cost-model FLOPs for the exact step — pays ONE extra
        lower+compile (AOT path), so it is opt-in (cost_analysis=True).
        Pass flops_per_step explicitly when the analytic count is known
        (flops_per_token_dense)."""
        try:
            cost = step_fn.lower(*args, **kwargs).compile().cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0]
            flops = float(cost.get("flops", 0.0))
            if flops > 0:
                telemetry.event(
                    "%s.cost_analysis" % self.prefix,
                    data={"flops_per_step": flops})
                return flops
        except Exception:
            pass
        return None

    def _emit_step(self, step_num, interval_s, stall_s=None):
        if interval_s <= 0:
            return
        data = {}
        if step_num in self._compile_steps:
            # a compile happened inside this interval: the record is
            # still emitted (with the flag), but it stays out of the
            # steady-state summary — compile time is tracked separately
            data["compile"] = True
        else:
            self._intervals.append(interval_s)
            if stall_s is not None:
                self._stalls.append(stall_s)
            self._step_ema_s = (
                interval_s if self._step_ema_s is None
                else 0.8 * self._step_ema_s + 0.2 * interval_s)
        if stall_s is not None:
            data["input_stall_ms"] = round(stall_s * 1000, 3)
        if self._pending_update_ms is not None:
            data["optimizer_update_ms"] = round(self._pending_update_ms, 3)
            if "compile" not in data:
                self._update_ms.append(self._pending_update_ms)
            self._pending_update_ms = None
        if self._pending_transfer_ms is not None:
            data["transfer_stall_ms"] = round(self._pending_transfer_ms, 3)
            if "compile" not in data:
                self._transfer_ms.append(self._pending_transfer_ms)
            self._pending_transfer_ms = None
        if self.tokens_per_step:
            data["tokens_per_sec"] = round(
                self.tokens_per_step / interval_s, 1)
        if self.flops_per_step:
            n_devices, peak, _kind = self._chip_context()
            achieved_tflops = (
                self.flops_per_step / interval_s / n_devices / 1e12)
            data["tflops_per_chip"] = round(achieved_tflops, 3)
            if peak:
                data["mfu"] = round(achieved_tflops / peak, 4)
        telemetry.emit("timer", "%s.step" % self.prefix,
                       ms=interval_s * 1000, ok=True, step_num=step_num,
                       data=data or None)

    # ---------- finalization ----------

    def close(self):
        """Emit the trailing step + summary gauges; stop any in-flight
        profiler capture. Idempotent."""
        if self._closed:
            return
        self._closed = True
        if self._prev_start is not None and self.step_num > 0:
            self._emit_step(self.step_num - 1,
                            time.perf_counter() - self._prev_start)
        # terminal progress beat: the loop is over — a control rank
        # idling in worker reap after its last step is NOT hung
        progress_mod.done(step_num=self.step_num)
        if self._profile is not None:
            self._profile.stop(self.step_num)
        interval = self._goodput_interval()
        if interval is not None:
            # per-rank chip-second rollup in the goodput categories
            # (metaflow_tpu/goodput.py): rides the crash-safe recorder
            # so the ledger CLI can cross-check its derivation against
            # what the rank itself tallied
            telemetry.event("goodput.interval", data=interval)
        summary = self.report()
        for key in ("steps", "mean_step_ms", "tokens_per_sec", "mfu",
                    "input_stall_ms", "optimizer_update_ms",
                    "transfer_stall_ms",
                    "memory_params_bytes", "memory_opt_state_bytes",
                    "memory_activations_bytes",
                    "compiles", "compile_ms", "device_memory_peak_bytes"):
            value = summary.get(key)
            if value is not None:
                telemetry.gauge("%s.summary.%s" % (self.prefix, key), value)
        telemetry.flush()

    def _goodput_interval(self):
        """This rank's step time split into goodput categories
        (seconds): the `goodput.interval` event payload, schema pinned
        in tests/schema_validate.py::GOODPUT_INTERVAL_DATA_SCHEMA."""
        steady_s = sum(self._intervals)
        compile_s = self.compile_ms / 1000.0
        if steady_s <= 0 and compile_s <= 0:
            return None
        stall_s = sum(self._stalls)
        update_s = sum(self._update_ms) / 1000.0
        transfer_s = sum(self._transfer_ms) / 1000.0
        productive = max(0.0, steady_s - stall_s - update_s - transfer_s)
        return {
            "span_s": round(steady_s + compile_s, 3),
            "steps": len(self._intervals),
            "categories": {
                "productive_step": round(productive, 3),
                "compile": round(compile_s, 3),
                "input_stall": round(stall_s, 3),
                "transfer_stall": round(transfer_s, 3),
                "update": round(update_s, 3),
            },
        }

    def report(self):
        """Summary dict over the recorded steps (steady-state: the first
        post-compile interval is included; compile time is separate)."""
        out = {"steps": len(self._intervals), "compiles": self.compiles,
               "compile_cache_hits": max(0, self.step_num - self.compiles),
               "compile_ms": round(self.compile_ms, 1)}
        if self._mem_peak:
            out["device_memory_peak_bytes"] = self._mem_peak
        for key, value in self._mem_split.items():
            out["memory_%s_bytes" % key] = value
        if self._update_ms:
            out["optimizer_update_ms"] = round(
                sum(self._update_ms) / len(self._update_ms), 3)
        if self._transfer_ms:
            out["transfer_stall_ms"] = round(
                sum(self._transfer_ms) / len(self._transfer_ms), 3)
        if not self._intervals:
            return out
        mean = sum(self._intervals) / len(self._intervals)
        out["mean_step_ms"] = round(mean * 1000, 3)
        if self._stalls:
            out["input_stall_ms"] = round(
                sum(self._stalls) / len(self._stalls) * 1000, 3)
        if self.tokens_per_step:
            out["tokens_per_sec"] = round(self.tokens_per_step / mean, 1)
        if self.flops_per_step:
            n_devices, peak, kind = self._chip_context()
            achieved = self.flops_per_step / mean / n_devices / 1e12
            out["tflops_per_chip"] = round(achieved, 3)
            out["device_kind"] = kind
            if peak:
                out["mfu"] = round(achieved / peak, 4)
        return out


def instrument_train_step(step_fn, tokens_per_step=None, flops_per_step=None,
                          cost_analysis=False, prefix="train",
                          memory_every=10, profile=True):
    """Wrap a (jitted) train step so every call emits per-step telemetry.

    The wrapper adds only host-side bookkeeping (no device syncs): two
    perf_counter reads, a cache-size probe, and one buffered record per
    step.

    tokens_per_step: GLOBAL tokens consumed per step (batch*seq) — enables
        tokens/sec on every record.
    flops_per_step: GLOBAL FLOPs per step (e.g. flops_per_token_dense(...)
        * tokens) — enables achieved-TFLOPs and, on TPU, MFU.
    cost_analysis: resolve flops_per_step from XLA's cost model instead
        (pays one extra lower+compile on the first step).
    profile: arm telemetry.ProfileTrigger (TPUFLOW_PROFILE_STEPS window,
        file/signal triggers) on this step counter.

    Returns the wrapped callable; `.telemetry` is the TrainStepTelemetry
    (call `.telemetry.close()` after the loop — or rely on the task
    finalization flush for the buffered records).
    """
    import jax

    tel = TrainStepTelemetry(
        tokens_per_step=tokens_per_step, flops_per_step=flops_per_step,
        cost_analysis=cost_analysis, prefix=prefix,
        memory_every=memory_every, profile=profile)

    # chaos harness tick (TPUFLOW_CHAOS): any instrumented train loop
    # gets deterministic fault injection for free — the scheduled kill
    # lands at a step boundary, before the step's compute is issued
    chaos_on = bool(knobs.get_str("TPUFLOW_CHAOS"))

    @functools.wraps(step_fn, assigned=("__name__", "__doc__"), updated=())
    def wrapped(*args, **kwargs):
        if chaos_on:
            from ..devtools.chaos import maybe_chaos_step

            maybe_chaos_step(tel.step_num)
        started = tel.before_step()
        pre_cache = _cache_size(step_fn)
        # cuts a profiler capture (TPUFLOW_PROFILE_STEPS) into steps
        with jax.profiler.StepTraceAnnotation(
                "%s.step" % prefix, step_num=tel.step_num):
            out = step_fn(*args, **kwargs)
        tel.after_step(step_fn, started, pre_cache, args, kwargs)
        return out

    wrapped.telemetry = tel
    return wrapped
