"""One serving replica: a subprocess worker for the fleet tier.

`python -m metaflow_tpu.serving.replica` builds a SlotEngine + Scheduler
+ ServingServer in THIS process and serves until SIGTERM (graceful
drain), exactly like single-process `tpuflow serve` — the fleet router
(serving/fleet.py) forks N of these and fronts them.

Two ways to get weights:

  --flow/--run-id ...       the production path: the checkpoint comes
                            off the run's datastore through
                            inference/loading.load_run_checkpoint, same
                            as `tpuflow serve` without --replicas.
  --synthetic-config JSON   hermetic path for tests: params are
                            initialized from PRNGKey(--synthetic-seed),
                            a pure function of (seed, config), so every
                            replica of a fleet materializes IDENTICAL
                            weights with no datastore involved.

Ready protocol: after the HTTP listener is up (and the engine warmed so
the first real request never pays a compile), the replica atomically
writes {"pid", "host", "port"} to --port-file. The supervisor waits on
that file, then health-checks /healthz.

--step-delay-ms adds a fixed sleep to every engine device call: a test
affordance (tests/test_fleet.py) that holds requests in flight long
enough for a replica to be killed under them. It measures nothing.
"""

import argparse
import json
import os
import sys
import time

from .. import knobs


def _add_step_delay(engine, delay_s):
    """Each prefill program / fused decode step holds its slots for
    `delay_s` more wall seconds (GIL released), where the scheduler
    launches the one and collects the other."""
    def delayed(call):
        def slower(*args):
            out = call(*args)
            time.sleep(delay_s)
            return out
        return slower

    engine.decode_step = delayed(engine.decode_step)
    engine.launch_prefill = delayed(engine.launch_prefill)


def _warm(engine):
    """Compile the engine's program set before declaring ready: both
    decode variants, first-token, and the common prefill buckets —
    a replica that joins the fleet must serve at steady-state speed
    from its first request (the restarted-replica rejoin path counts)."""
    from .scheduler import Request, Scheduler

    warm = Scheduler(engine)
    # two full chunks + a short tail: compiles the full-chunk bucket and
    # a tail bucket; temperature>0 exercises the sampled decode + the
    # sampled first-token program
    long_prompt = list(range(1, engine.prefill_chunk * 2 + 4))
    if len(long_prompt) + 3 > engine.max_seq_len:
        long_prompt = long_prompt[: max(1, engine.max_seq_len - 4)]
    warm.submit(Request(long_prompt, max_new_tokens=3, temperature=0.7))
    warm.submit(Request([1, 2, 3], max_new_tokens=2))  # greedy variant
    warm.run_until_idle(100_000)


def _build_synthetic(args):
    """Deterministic weights from (seed, config): the hermetic fleet
    path. Every process computes the same pytree bit-for-bit."""
    import jax

    from ..cmd.serve import build_config, build_engine
    from ..models import llama

    cfg = build_config(None, config_json=args.synthetic_config,
                       model=args.model)
    params = llama.init_params(
        jax.random.PRNGKey(int(args.synthetic_seed)), cfg)
    return build_engine(params, cfg, slots=args.slots,
                        max_seq_len=args.max_seq_len,
                        prefill_chunk=args.prefill_chunk,
                        mesh_spec=args.mesh or None, paged=args.paged,
                        page_tokens=args.page_tokens, spec_k=args.spec_k)


def _build_from_checkpoint(args):
    from ..cmd.serve import build_config, build_engine, extract_params
    from ..inference import load_run_checkpoint

    restored = load_run_checkpoint(args.flow, run_id=args.run_id,
                                   step_name=args.step_name or None,
                                   ckpt_step=args.ckpt_step)
    cfg = build_config(restored, config_json=args.config_json or None,
                       model=args.model)
    params = extract_params(restored, params_key=args.params_key)
    return build_engine(params, cfg, slots=args.slots,
                        max_seq_len=args.max_seq_len,
                        prefill_chunk=args.prefill_chunk,
                        mesh_spec=args.mesh or None, paged=args.paged,
                        page_tokens=args.page_tokens, spec_k=args.spec_k)


def _init_replica_telemetry(flow_name, run_id, index):
    """Per-replica flight recorder under the served run's `_serve` step
    (task `replica<i>-<pid>`), next to the router's fleet events."""
    from .. import telemetry
    from .. import metaflow_config as cfg
    from ..datastore import STORAGE_BACKENDS, FlowDataStore

    if not telemetry.enabled():
        return None
    try:
        storage = STORAGE_BACKENDS[cfg.default_datastore()]
        fds = FlowDataStore(flow_name, storage)
        return telemetry.init_recorder(
            fds, run_id, "_serve", "replica%d-%d" % (index, os.getpid()))
    except Exception:
        return None  # the replica must come up even if telemetry cannot


def _write_port_file(path, host, port):
    payload = json.dumps({"pid": os.getpid(), "host": host, "port": port})
    tmp = "%s.tmp.%d" % (path, os.getpid())
    with open(tmp, "w") as f:
        f.write(payload)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def build_parser():
    p = argparse.ArgumentParser(prog="metaflow_tpu.serving.replica")
    p.add_argument("--flow", default=None)
    p.add_argument("--run-id", default=None)
    p.add_argument("--step-name", default=None)
    p.add_argument("--ckpt-step", type=int, default=None)
    p.add_argument("--params-key", default="params")
    p.add_argument("--config-json", default=None)
    p.add_argument("--model", default="llama")
    p.add_argument("--synthetic-config", default=None)
    p.add_argument("--synthetic-seed", type=int, default=0)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--port-file", default=None)
    p.add_argument("--replica-index", type=int, default=0)
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--max-seq-len", type=int, default=None)
    p.add_argument("--prefill-chunk", type=int, default=64)
    p.add_argument("--max-queue", type=int, default=64)
    p.add_argument("--mesh", default=None)
    p.add_argument("--no-warmup", action="store_true")
    p.add_argument("--step-delay-ms", type=float, default=0.0)
    p.add_argument("--role", default="unified",
                   choices=("unified", "prefill", "decode"))
    p.add_argument("--prefix-cache-mb", type=int, default=None)
    p.add_argument("--paged", action="store_true")
    p.add_argument("--page-tokens", type=int, default=None)
    p.add_argument("--spec-k", type=int, default=None)
    return p


def main(argv=None):
    args = build_parser().parse_args(argv)
    if bool(args.flow) == bool(args.synthetic_config):
        print("replica: exactly one of --flow or --synthetic-config "
              "is required", file=sys.stderr)
        return 2

    from .. import device, telemetry
    from .scheduler import Scheduler
    from .server import ServingServer

    device.setup_compile_cache()
    if args.synthetic_config:
        engine = _build_synthetic(args)
        # hermetic fleets have no run of their own, but a harness can
        # still point every replica at a shared run's datastore so the
        # chaos / trace e2e can reassemble request trees from replica-
        # side records (TPUFLOW_DATASTORE_SYSROOT_LOCAL scopes the root)
        t_flow = knobs.get_str("TPUFLOW_REPLICA_TELEMETRY_FLOW")
        t_run = knobs.get_str("TPUFLOW_REPLICA_TELEMETRY_RUN")
        if t_flow and t_run:
            _init_replica_telemetry(t_flow, t_run, args.replica_index)
    else:
        engine = _build_from_checkpoint(args)
        _init_replica_telemetry(args.flow, args.run_id,
                                args.replica_index)
    if not args.no_warmup:
        _warm(engine)
    if args.step_delay_ms > 0:
        _add_step_delay(engine, args.step_delay_ms / 1000.0)

    from ..cmd.serve import build_prefix_cache

    cache = build_prefix_cache(engine, args.prefix_cache_mb)
    scheduler = Scheduler(engine, max_queue=args.max_queue,
                          prefix_cache=cache)
    server = ServingServer(scheduler, host=args.host, port=args.port,
                           role=args.role)
    server.install_signal_handlers()
    server.start()
    if args.port_file:
        _write_port_file(args.port_file, server.host, server.port)
    print("replica %d: pid=%d serving on http://%s:%d"
          % (args.replica_index, os.getpid(), server.host, server.port),
          flush=True)
    try:
        server._done.wait()
    except KeyboardInterrupt:
        server.shutdown()
    finally:
        telemetry.close_recorder()
    return 0


if __name__ == "__main__":
    sys.exit(main())
