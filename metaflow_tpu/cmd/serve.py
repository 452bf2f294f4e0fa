"""`tpuflow serve FLOW/RUN`: serve a trained run's checkpoint over HTTP.

train -> checkpoint -> serve in one framework: the checkpoint comes off
the run's datastore through inference/loading.load_run_checkpoint, the
mesh/sharding reuses the training rule table (spmd/sharding.py), and the
continuous-batching engine + scheduler + HTTP server come from
metaflow_tpu/serving/. Telemetry lands in the SERVED run's
`_telemetry/` prefix (step `_serve`), so `tpuflow metrics FLOW/RUN`
shows serving TTFT/latency/occupancy next to the run's training
records.
"""

import json
import os

from .. import device, knobs
from ..exception import TpuFlowException


def build_config(restored, config_json=None, model="llama"):
    """Resolve the model config for a restored checkpoint pytree.

    Priority: --config-json (a file path or inline JSON object of
    LlamaConfig/MixtralConfig field overrides) > a 'cfg'/'config' dict
    the checkpoint itself carries. The named `model` family supplies the
    dataclass (inference/decode.py's table of families)."""
    from ..inference.decode import family_config_class

    config_cls = family_config_class(model)
    fields = None
    if config_json:
        if os.path.exists(config_json):
            with open(config_json) as f:
                fields = json.load(f)
        else:
            try:
                fields = json.loads(config_json)
            except ValueError:
                raise TpuFlowException(
                    "--config-json is neither a file nor valid JSON: %r"
                    % (config_json,))
    elif isinstance(restored, dict):
        for key in ("cfg", "config"):
            if isinstance(restored.get(key), dict):
                fields = dict(restored[key])
                break
    if fields is None:
        raise TpuFlowException(
            "no model config: pass --config-json (LlamaConfig fields as "
            "JSON) or checkpoint a 'cfg' dict next to the params")
    if not isinstance(fields, dict):
        raise TpuFlowException("model config must be a JSON object")
    known = {f.name for f in config_cls.__dataclass_fields__.values()}
    unknown = sorted(set(fields) - known)
    if unknown:
        raise TpuFlowException(
            "unknown %s field(s): %s" % (config_cls.__name__,
                                         ", ".join(unknown)))
    return config_cls(**fields)


def extract_params(restored, params_key="params"):
    """The weight pytree inside a checkpoint: restored[params_key] when
    present, else the whole tree (a bare-params checkpoint)."""
    if isinstance(restored, dict) and params_key in restored:
        return restored[params_key]
    return restored


def build_engine(params, cfg, slots=8, max_seq_len=None, prefill_chunk=64,
                 mesh_spec=None, paged=False, page_tokens=None,
                 spec_k=None):
    """Shard params over a mesh (the training rule table) and build the
    engine: the slot engine, or (paged=True / TPUFLOW_PAGED=1) the
    paged-KV engine with optional speculative decoding. mesh_spec:
    None, or a MeshSpec factory name ('dp'|'fsdp'|'fsdp_tp')."""
    from ..serving import PagedEngine, SlotEngine

    device.platform()  # a server on a quiet CPU fallback is an error
    mesh = None
    if mesh_spec:
        import jax

        from ..spmd import MeshSpec, create_mesh, shard_tree

        factory = getattr(MeshSpec, mesh_spec, None)
        if factory is None:
            raise TpuFlowException(
                "unknown mesh spec %r (want dp, fsdp or fsdp_tp)"
                % (mesh_spec,))
        mesh = create_mesh(factory() if mesh_spec != "fsdp_tp"
                           else factory(min(2, len(jax.devices()))))
        # the rule tree must come from the checkpoint's model family: a
        # Mixtral tree has router/expert axes the Llama table lacks
        from ..inference.decode import family

        params = shard_tree(params, family(cfg).module.logical_axes(cfg),
                            mesh)
    if paged or knobs.get_bool("TPUFLOW_PAGED"):
        return PagedEngine(params, cfg, max_slots=slots,
                           max_seq_len=max_seq_len,
                           prefill_chunk=prefill_chunk, mesh=mesh,
                           page_tokens=page_tokens, spec_k=spec_k)
    return SlotEngine(params, cfg, max_slots=slots,
                      max_seq_len=max_seq_len, prefill_chunk=prefill_chunk,
                      mesh=mesh)


def build_prefix_cache(engine, prefix_cache_mb=None):
    """The prefix cache matched to the engine: a zero-copy
    PagedPrefixIndex over the paged engine's own pool, a host-side
    RadixPrefixCache otherwise. Same opt-in contract either way:
    no byte budget (flag or TPUFLOW_PREFIX_CACHE_MB), no cache. A model
    that carries recurrent state is refused a cache by name: a KV range
    is not a prefix of it."""
    from ..serving.engine import refuse_recurrent

    cache = _prefix_cache_for(engine, prefix_cache_mb)
    if cache is not None:
        refuse_recurrent(engine.cfg, "a prefix cache")
    return cache


def _prefix_cache_for(engine, prefix_cache_mb):
    from ..serving import PagedPrefixIndex, RadixPrefixCache

    pool = getattr(engine, "pool", None)
    if pool is not None:
        if prefix_cache_mb is None:
            return PagedPrefixIndex.from_env(pool)
        if int(prefix_cache_mb) <= 0:
            return None
        pages = max(1, (int(prefix_cache_mb) << 20)
                    // max(1, pool.page_bytes()))
        return PagedPrefixIndex(pool,
                                max_pages=min(pages, pool.usable_pages))
    if prefix_cache_mb is None:
        return RadixPrefixCache.from_env()
    return (RadixPrefixCache(int(prefix_cache_mb) << 20)
            if int(prefix_cache_mb) > 0 else None)


def _init_serve_telemetry(flow_name, run_id, task_prefix="server"):
    """Record serving telemetry into the served run's datastore under a
    synthetic `_serve` step, riding the existing FlightRecorder. The
    fleet router records as task `fleet-<pid>` next to the replicas'
    `replica<i>-<pid>` tasks."""
    from .. import telemetry
    from .. import metaflow_config as cfg
    from ..datastore import STORAGE_BACKENDS, FlowDataStore

    if not telemetry.enabled():
        return None
    try:
        storage = STORAGE_BACKENDS[cfg.default_datastore()]
        fds = FlowDataStore(flow_name, storage)
        return telemetry.init_recorder(
            fds, run_id, "_serve",
            "%s-%d" % (task_prefix, os.getpid()))
    except Exception:
        return None  # serving must come up even if telemetry cannot


def _resolve_flow_run(flow_run, run_id):
    """FLOW/RUN (or FLOW + --run-id) -> (flow_name, run_id), falling
    back to the latest successful run so telemetry lands under the real
    run id."""
    if run_id is None:
        flow_name, _, run_id = flow_run.rpartition("/")
        if not flow_name:
            flow_name, run_id = flow_run, None
    else:
        flow_name = flow_run
    if run_id is None:
        from ..inference.loading import _latest_successful_run_id

        run_id = _latest_successful_run_id(flow_name, None)
        if run_id is None:
            raise TpuFlowException(
                "No successful run of %s to serve." % flow_name)
    return flow_name, run_id


def serve_fleet(flow_run, run_id=None, step_name=None, ckpt_step=None,
                params_key="params", config_json=None, model="llama",
                host="127.0.0.1", port=8000, replicas=2, slots=8,
                max_seq_len=None, prefill_chunk=64, max_queue=64,
                mesh_spec=None, prefill_workers=0, prefix_cache_mb=None,
                paged=False, page_tokens=None, spec_k=None, echo=print,
                block=True):
    """`tpuflow serve FLOW/RUN --replicas N`: fork N replica workers
    (each loading the run's checkpoint through load_run_checkpoint) and
    front them with the health-checked failover router
    (serving/fleet.py). `--prefill-workers K` adds K dedicated prefill
    replicas (disaggregated prefill/decode, docs/serving.md#disagg).
    Returns the running ServingFleet when block=False (tests);
    otherwise serves until SIGTERM/SIGINT, draining the whole fleet
    before exit."""
    from .. import telemetry
    from ..devtools import chaos as chaos_mod
    from ..serving import FleetConfig, ServingFleet, \
        SubprocessReplicaSpawner

    # replica workers are processes of this host and inherit this
    # environment (a rolling reload adds one more for the surge)
    device.refuse_chip_sharing(
        int(replicas) + int(prefill_workers),
        "tpuflow serve --replicas/--prefill-workers")
    flow_name, run_id = _resolve_flow_run(flow_run, run_id)
    replica_args = [
        "--flow", flow_name, "--run-id", str(run_id),
        "--params-key", params_key, "--model", model,
        "--slots", str(slots), "--prefill-chunk", str(prefill_chunk),
        "--max-queue", str(max_queue),
    ]
    if step_name:
        replica_args += ["--step-name", step_name]
    if ckpt_step is not None:
        replica_args += ["--ckpt-step", str(ckpt_step)]
    if config_json:
        replica_args += ["--config-json", config_json]
    if max_seq_len is not None:
        replica_args += ["--max-seq-len", str(max_seq_len)]
    if mesh_spec:
        replica_args += ["--mesh", mesh_spec]
    if prefix_cache_mb is not None:
        replica_args += ["--prefix-cache-mb", str(prefix_cache_mb)]
    if paged:
        replica_args += ["--paged"]
    if page_tokens is not None:
        replica_args += ["--page-tokens", str(page_tokens)]
    if spec_k is not None:
        replica_args += ["--spec-k", str(spec_k)]
    config = FleetConfig.from_env()
    spawner = SubprocessReplicaSpawner(
        replica_args, spawn_timeout_s=config.spawn_timeout_s)
    _init_serve_telemetry(flow_name, run_id, task_prefix="fleet")
    fleet = ServingFleet(
        spawner, replicas, config=config, host=host, port=port,
        chaos=chaos_mod.fleet_from_env(replicas), echo=echo,
        prefill_workers=int(prefill_workers))
    fleet.start()
    echo("fleet: serving %s/%s on http://%s:%d (%d replicas x %d "
         "slots%s)" % (flow_name, run_id, fleet.host, fleet.port,
                       replicas, slots,
                       ", %d prefill workers" % prefill_workers
                       if prefill_workers else ""))
    echo("  POST /v1/generate  {\"tokens\": [...], \"max_new_tokens\":"
         " N, \"stream\": true, \"session\": \"...\"}")
    if not block:
        return fleet
    try:
        fleet.serve_forever()
    finally:
        telemetry.close_recorder()
    echo("fleet drained — all replicas stopped")


def reload_fleet(flow_run, run_id=None, step_name=None, ckpt_step=None,
                 host="127.0.0.1", port=8000, echo=print,
                 timeout_s=600.0):
    """`tpuflow serve FLOW/RUN --reload`: roll a RUNNING fleet (at
    --host/--port) onto a new checkpoint generation. POSTs
    /v1/admin/reload with the replica-arg updates, then polls
    /v1/admin/rollout until the surge rollout (spawn replacement ->
    ready -> drain old -> retire, one replica at a time) finishes.
    Returns the final rollout record; raises on abort/timeout."""
    import time
    from http.client import HTTPConnection

    flow_name, run_id = _resolve_flow_run(flow_run, run_id)
    args_update = {"--flow": flow_name, "--run-id": str(run_id)}
    if step_name:
        args_update["--step-name"] = step_name
    if ckpt_step is not None:
        args_update["--ckpt-step"] = str(ckpt_step)

    def _call(method, path, body=None):
        conn = HTTPConnection(host, port, timeout=30)
        try:
            headers = {"Content-Type": "application/json"} if body \
                else {}
            conn.request(method, path,
                         body=json.dumps(body).encode() if body
                         else None, headers=headers)
            resp = conn.getresponse()
            return resp.status, json.loads(resp.read().decode() or "{}")
        finally:
            conn.close()

    status, ack = _call("POST", "/v1/admin/reload",
                        {"args_update": args_update})
    if status != 202:
        raise TpuFlowException(
            "fleet refused reload (%d): %s" % (status, ack))
    target = int(ack.get("fleet_generation", 0))
    echo("rollout: fleet -> generation %d (%s/%s)"
         % (target, flow_name, run_id))
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        _, ro = _call("GET", "/v1/admin/rollout")
        last = ro.get("last") or {}
        if (not ro.get("active")
                and int(ro.get("fleet_generation", 0)) >= target):
            echo("rollout: done — replaced %s replica(s), shed %s, "
                 "%.0f ms" % (last.get("replaced"),
                              last.get("shed_requests"),
                              float(last.get("ms") or 0.0)))
            return last
        time.sleep(0.5)
    raise TpuFlowException("rollout did not finish within %.0fs"
                           % timeout_s)


def serve_federate(fleet_urls, host="127.0.0.1", port=8000, echo=print,
                   block=True):
    """`tpuflow serve --federate URL,URL`: run the thin federation
    front tier over already-running fleets. No checkpoint is loaded
    here — the front only forwards, polls fleet /healthz for capacity
    rollups, and spreads tenants across fleets
    (docs/serving.md#federation)."""
    from ..serving import FederationRouter

    urls = [u.strip() for u in fleet_urls.split(",") if u.strip()]
    if not urls:
        raise TpuFlowException("--federate needs at least one fleet URL")
    router = FederationRouter(urls, host=host, port=port)
    router.start()
    echo("federating %d fleet(s) on http://%s:%d" % (len(urls),
                                                     router.host,
                                                     router.port))
    for i, url in enumerate(urls):
        echo("  fleet %d: %s" % (i, url))
    echo("  POST /v1/generate  {\"tokens\": [...], \"tenant\": \"...\"}")
    if not block:
        return router
    try:
        router._stop.wait()
    except KeyboardInterrupt:
        pass
    router.close()


def serve(flow_run, run_id=None, step_name=None, ckpt_step=None,
          params_key="params", config_json=None, model="llama",
          host="127.0.0.1", port=8000, replicas=1, slots=8,
          max_seq_len=None, prefill_chunk=64, max_queue=64,
          mesh_spec=None, prefill_workers=0, prefix_cache_mb=None,
          paged=False, page_tokens=None, spec_k=None,
          reload_checkpoint=False, federate=None, echo=print, block=True):
    """Load FLOW/RUN's checkpoint and serve it. Returns the running
    ServingServer when block=False (tests); otherwise serves until
    SIGTERM/SIGINT, draining in-flight requests before exit. With
    --replicas N > 1 (or --prefill-workers K > 0) the work moves to the
    fleet tier (serve_fleet): forked replica workers behind the
    failover router. With --reload, no server starts: the named
    checkpoint is rolled onto the ALREADY-RUNNING fleet at
    --host/--port via a zero-shed rolling upgrade."""
    from .. import telemetry
    from ..inference import load_run_checkpoint
    from ..serving import Scheduler, ServingServer

    if federate:
        return serve_federate(federate, host=host, port=port, echo=echo,
                              block=block)

    if reload_checkpoint:
        return reload_fleet(flow_run, run_id=run_id,
                            step_name=step_name, ckpt_step=ckpt_step,
                            host=host, port=port, echo=echo)

    if int(replicas) > 1 or int(prefill_workers) > 0:
        return serve_fleet(
            flow_run, run_id=run_id, step_name=step_name,
            ckpt_step=ckpt_step, params_key=params_key,
            config_json=config_json, model=model, host=host, port=port,
            replicas=int(replicas), slots=slots,
            max_seq_len=max_seq_len, prefill_chunk=prefill_chunk,
            max_queue=max_queue, mesh_spec=mesh_spec,
            prefill_workers=int(prefill_workers),
            prefix_cache_mb=prefix_cache_mb, paged=paged,
            page_tokens=page_tokens, spec_k=spec_k, echo=echo,
            block=block)

    # resolve the run HERE (not only inside load_run_checkpoint) so
    # telemetry lands under the real run id, next to its training
    # records — never under a synthetic label
    flow_name, run_id = _resolve_flow_run(flow_run, run_id)
    compiles = device.watch_compiles()
    restored = load_run_checkpoint(flow_name, run_id=run_id,
                                   step_name=step_name,
                                   ckpt_step=ckpt_step)
    cfg = build_config(restored, config_json=config_json, model=model)
    params = extract_params(restored, params_key=params_key)
    engine = build_engine(params, cfg, slots=slots,
                          max_seq_len=max_seq_len,
                          prefill_chunk=prefill_chunk,
                          mesh_spec=mesh_spec, paged=paged,
                          page_tokens=page_tokens, spec_k=spec_k)
    _init_serve_telemetry(flow_name, run_id)
    cache = build_prefix_cache(engine, prefix_cache_mb)
    scheduler = Scheduler(engine, max_queue=max_queue,
                          prefix_cache=cache)
    server = ServingServer(scheduler, host=host, port=port)
    if hasattr(engine, "pool"):
        echo("serving %s/%s on http://%s:%d  (paged: %d slots, %d pages "
             "x %d tokens, spec_k=%d, attn=%s)"
             % (flow_name, run_id, server.host, server.port,
                engine.max_slots, engine.pool.usable_pages,
                engine.page_tokens, engine.spec_k, engine.attn_impl))
    else:
        echo("serving %s/%s on http://%s:%d  (%d slots x %d positions, "
             "attn=%s)" % (flow_name, run_id, server.host,
                           server.port, engine.max_slots,
                           engine.max_seq_len, engine.attn_impl))
    echo("  POST /v1/generate  {\"tokens\": [...], \"max_new_tokens\": N,"
         " \"stream\": true}")
    echo("  device: %s" % json.dumps(device.describe()))
    if not block:
        server.start()
        return server
    try:
        server.serve_forever()
    finally:
        telemetry.close_recorder()
    echo("drained — all in-flight requests finished")
    echo("  compiles: %s  peak_bytes_in_use: %s"
         % (json.dumps(compiles), device.peak_bytes_in_use()))
