"""What every driver shares: the run's context, the comparison lines,
the profiler slice, and the readers of per-layer metrics, found by
name."""

import importlib
import importlib.util
import os
import shutil
import threading
import time

from . import configs, peaks, trace_reduce

# a fixed place inside the checkout; each traced run replaces the last
TRACE_DIR = os.path.join(configs.ROOT, ".tpuflow", "bench_trace")
TRACE_AFTER_S = 2.0   # the slice starts this far into the window
TRACE_SLICE_S = 3.0   # and is closed at the first boundary after this long


class Context(object):
    """One run of one cell."""

    def __init__(self, bench, cell, config, traffic, seed, seconds, trace,
                 t_process, devices):
        self.bench, self.cell = bench, cell
        self.config, self.traffic = config, traffic
        self.dims = configs.dims(config)
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.t_process = t_process
        self.devices = devices
        self.checks = []
        self.excluded_s = 0.0   # time inside set-up that only the check costs
        self.control = False    # benchmark/control.py reads the control too
        self.control_readings = {}

    def log(self, msg, *args):
        print("[bench %7.1fs] %s" % (time.perf_counter() - self.t_process,
                                     msg % args if args else msg), flush=True)

    def check(self, name, value, limit, ok=None):
        """Record one compared number beside its limit (value <= limit
        unless `ok` says otherwise) and print it."""
        ok = bool(value <= limit) if ok is None else bool(ok)
        self.checks.append((name, value, limit, ok))
        print("[check] %-28s %-22r limit %-12r %s"
              % (name, value, limit, "ok" if ok else "NOT CORRECT"),
              flush=True)
        return ok

    def control_reading(self, name, value):
        """What the lower-precision control reads where the program read
        the check of the same name."""
        self.control_readings[name] = value
        print("[control] %-26s %r" % (name, value), flush=True)

    @property
    def correct(self):
        return bool(self.checks) and all(c[3] for c in self.checks)

    def run_sizes(self):
        """What every driver's `run` carries beside its own readings, so
        that a reader can turn a time into a share of a roofline
        (benchmark/kernel_costs.py): the configuration's sizes, the
        cell's chips, and the device's row of peaks.py (None off the
        TPU: a share of a peak comes only from a chip run)."""
        device = self.devices[0]
        return {"dims": self.dims, "chips": self.cell["chips"],
                "peak": peaks.peak(device.device_kind)
                if device.platform == "tpu" else None}

    def setup_seconds(self, t_window):
        return t_window - self.t_process - self.excluded_s


class TraceSlice(object):
    """The profiler over a slice of the window, opened and closed at
    boundaries the driver names (a step's end, a poll), so that whole
    steps lie inside it. Without --trace 1 every call is a no-op."""

    def __init__(self, ctx):
        self.on = bool(ctx.trace)
        self.started = self.stopped = None
        self.reduced = self.thread = None

    def boundary(self, t_window_start):
        if not self.on or self.stopped is not None:
            return
        import jax

        now = time.perf_counter()
        if self.started is None:
            if now - t_window_start >= TRACE_AFTER_S:
                shutil.rmtree(TRACE_DIR, ignore_errors=True)
                jax.profiler.start_trace(TRACE_DIR)
                self.started = time.perf_counter()
        elif now - self.started >= TRACE_SLICE_S:
            self.stopped = now
            jax.profiler.stop_trace()

    def in_thread(self, t_window_start):
        """For a driver whose own thread has a schedule to keep (a load
        generator): a thread opens and closes the slice by the clock, so
        that stopping the profiler, which takes seconds, delays no
        request."""
        if not self.on:
            return

        def body():
            while self.stopped is None:
                self.boundary(t_window_start)
                time.sleep(0.05)

        self.thread = threading.Thread(target=body, name="bench-trace-slice",
                                       daemon=True)
        self.thread.start()

    def close(self, n_devices):
        """Stop if still open, and reduce the trace."""
        if self.thread is not None:
            self.thread.join(timeout=120)
        if not self.on or self.started is None:
            return None
        if self.stopped is None:
            import jax

            self.stopped = time.perf_counter()
            jax.profiler.stop_trace()
        path = trace_reduce.find_xplane(TRACE_DIR)
        self.reduced = trace_reduce.reduce_file(
            path, window_s=self.stopped - self.started, n_devices=n_devices)
        return self.reduced


def load_driver(kind):
    return importlib.import_module("benchmark.drivers." + kind)


def read_layer_metrics(bench, cell_name, moved, run):
    """Every per-layer metric of this cell: each has a reader of its own
    in layer_metrics/<name>.py, `read(run)`, which returns the number or
    None where it finds nothing to read."""
    out = {}
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if cells is not None and cell_name not in cells:
            continue
        if cells is None and m["moves"] not in moved:
            continue
        path = os.path.join(configs.HERE, "layer_metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location(
            "benchmark_layer_metric_" + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
