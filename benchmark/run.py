"""Run one cell of the benchmark once:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result, one JSON object; every
earlier line is commentary. A run off the TPU, or with fewer chips than
the cell asks for, exits non-zero and prints no result.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def open_context(name, seed, seconds, trace, require_tpu=True,
                 benchmark_path=None, t_process=None, control=False):
    """Read the cell's files, place the compile cache, look for the chips
    and start counting compilations: what a run, or a sweep of runs in one
    process, starts from."""
    from benchmark import configs, harness

    bench, cell, config, traffic = configs.load_cell(name, benchmark_path)

    from metaflow_tpu import device

    device.setup_compile_cache()
    import jax

    # every program of the cell is kept, however quickly it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit("benchmark: JAX's devices are %s, not a TPU: "
                         "no result" % (devices[0].platform,))
    if len(devices) < cell["chips"]:
        raise SystemExit("benchmark: cell %s needs %d chips, JAX reports %d"
                         % (name, cell["chips"], len(devices)))
    ctx = harness.Context(bench, cell, config, traffic, seed, seconds,
                          bool(trace), t_process or T_PROCESS,
                          devices[:cell["chips"]])
    ctx.compiles = device.watch_compiles()
    ctx.control = control
    ctx.log("cell %s seed %d seconds %g trace %d on %d x %s", name, ctx.seed,
            ctx.seconds, int(ctx.trace), len(devices), devices[0].device_kind)
    return ctx


def run_cell(name, seed, seconds, trace, **kw):
    """Drive one run and return the result line's object. The tests
    call this with require_tpu=False and benchmark/control.py with
    control=True (the lower-precision control is read beside the
    program); the command never does either."""
    import jax

    from benchmark import harness

    ctx = open_context(name, seed, seconds, trace, **kw)
    bench, traffic, devices = ctx.bench, ctx.traffic, jax.devices()
    out = harness.load_driver(traffic["kind"]).run(ctx)

    reported = {}
    if not ctx.trace:
        for m in bench["end_to_end"]:
            cells = m.get("workloads")
            if (cells is None or name in cells) and m["name"] in out["end_to_end"]:
                reported[m["name"]] = {
                    "value": float(out["end_to_end"][m["name"]]),
                    "unit": m["unit"]}
    else:
        reported = harness.read_layer_metrics(
            bench, name, set(out["end_to_end"]), out["run"])
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices),
           "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": ctx.correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": reported, "device": dev}
    if ctx.control:
        result["checks"] = {c[0]: c[1] for c in ctx.checks}
        result["control"] = ctx.control_readings
    reduced = out["run"].get("trace")
    if ctx.trace and reduced:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    ctx.log("compiles %(compiles)d (%(compile_s).1f s), persistent cache "
            "hits %(cache_hits)d misses %(cache_misses)d" % ctx.compiles)
    # every number compared beside its limit, last in the line and last
    # on standard error: what a record of a run that is not correct keeps
    number = lambda x: float(x) if math.isfinite(float(x)) else None
    result["compared"] = {c[0]: {"value": number(c[1]), "limit": number(c[2]),
                                 "ok": c[3]} for c in ctx.checks}
    for check, value, limit, ok in ctx.checks:
        print("[check] %s %r limit %r %s" % (
            check, value, limit, "ok" if ok else "NOT CORRECT"),
            file=sys.stderr, flush=True)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, args.trace)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
