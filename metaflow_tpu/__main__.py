"""The framework-level CLI: `python -m metaflow_tpu <cmd>`.

Reference behavior: metaflow/cmd/main_cli.py (`metaflow configure/
tutorials/develop`). Subcommands:

    version                      print the framework version
    configure show               resolved config + its sources
    configure set KEY VALUE      persist a key to the profile JSON
    configure unset KEY          remove a key
    tutorials list|pull [DIR]    list / copy the tutorial episodes
    stubs [OUT_DIR]              generate .pyi type stubs
    dataset build|info|list      sharded streaming corpora (docs/data.md)
    metrics FLOW/RUN             aggregate a run's telemetry
    serve FLOW/RUN               serve a checkpoint over HTTP
"""

import os
import shutil
import sys

import click

from . import knobs


@click.group()
def main():
    pass


@main.command()
def version():
    import metaflow_tpu

    click.echo("metaflow_tpu %s" % metaflow_tpu.__version__)


@main.group()
def configure():
    pass


@configure.command(name="show")
def configure_show():
    from . import metaflow_config as cfg

    click.echo("profile file: %s" % cfg._profile_path())
    for name, fn in (
        ("DATASTORE_SYSROOT_LOCAL", cfg.datastore_sysroot_local),
        ("DATASTORE_SYSROOT_GS", cfg.datastore_sysroot_gs),
        ("DEFAULT_DATASTORE", cfg.default_datastore),
        ("DEFAULT_METADATA", cfg.default_metadata),
        ("SERVICE_URL", cfg.service_url),
    ):
        click.echo("  %-26s = %s" % (name, fn()))


@configure.command(name="set")
@click.argument("key")
@click.argument("value")
def configure_set(key, value):
    from .metaflow_config import set_conf

    path = set_conf(key, value)
    click.echo("wrote %s=%s to %s" % (key.upper(), value, path))


@configure.command(name="unset")
@click.argument("key")
def configure_unset(key):
    from .metaflow_config import set_conf

    path = set_conf(key, None)
    click.echo("removed %s from %s" % (key.upper(), path))


@configure.command(
    name="reset",
    help="Delete the active profile (reverts to local defaults; the "
         "reference's `configure reset`).",
)
@click.option("--yes", is_flag=True, help="delete without prompting")
def configure_reset(yes):
    from .metaflow_config import _profile_path

    path = _profile_path()
    if not os.path.exists(path):
        click.echo("nothing to reset (%s does not exist)" % path)
        return
    if not yes and not click.confirm(
            "Delete %s and revert to local defaults?" % path):
        click.echo("aborted")
        return
    os.unlink(path)
    click.echo("removed %s — runs now use local datastore/metadata "
               "defaults" % path)


@configure.command(name="list", help="List configuration profiles.")
def configure_list():
    import json

    from .metaflow_config import _profile_path

    root = os.path.dirname(_profile_path())
    active = knobs.get_str("TPUFLOW_PROFILE") or "(default)"
    if not os.path.isdir(root):
        click.echo("no profiles yet (%s does not exist)" % root)
        return
    for name in sorted(os.listdir(root)):
        if not (name == "config.json" or (name.startswith("config_")
                                          and name.endswith(".json"))):
            continue
        prof = name[len("config_"):-len(".json")] if name != "config.json" \
            else "(default)"
        try:
            with open(os.path.join(root, name)) as f:
                n_keys = len(json.load(f))
        except (OSError, ValueError):
            n_keys = "?"
        click.echo("%s %-20s %s keys  (%s)"
                   % ("*" if prof == active else " ", prof, n_keys, name))


@configure.command(name="export", help="Print the active profile as JSON.")
@click.argument("out", required=False, type=click.Path())
def configure_export(out):
    import json

    from .metaflow_config import _profile_path

    try:
        with open(_profile_path()) as f:
            payload = f.read()
        json.loads(payload)
    except FileNotFoundError:
        payload = "{}"
    except ValueError as ex:
        raise click.ClickException(
            "profile %s is not valid JSON: %s" % (_profile_path(), ex))
    if out:
        with open(out, "w") as f:
            f.write(payload)
        click.echo("exported %s to %s" % (_profile_path(), out))
    else:
        click.echo(payload)


@configure.command(name="import", help="Load a JSON file into the profile.")
@click.argument("src", type=click.Path(exists=True))
def configure_import(src):
    import json

    from .metaflow_config import _profile_path

    with open(src) as f:
        payload = json.load(f)
    if not isinstance(payload, dict):
        raise click.ClickException("profile must be a JSON object")
    # the resolver only matches uppercase names (set_conf uppercases too)
    payload = {k.upper(): v for k, v in payload.items()}
    path = _profile_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    click.echo("imported %d keys into %s" % (len(payload), path))


@configure.command(
    name="gcp",
    help="Guided GCP/TPU setup: shared GCS datastore (+ optional metadata "
         "service). Prompts when flags are omitted (reference: the "
         "interactive `metaflow configure` flows, non-cloud-specific "
         "parts re-homed for GCS/TPU).")
@click.option("--datastore-root", default=None,
              help="gs://bucket/prefix for artifacts")
@click.option("--service-url", default=None,
              help="metadata service URL (empty = keep local metadata)")
@click.option("--yes", is_flag=True, help="accept without prompting")
def configure_gcp(datastore_root, service_url, yes):
    from .metaflow_config import set_conf

    if datastore_root is None:
        if yes:
            raise click.ClickException(
                "--yes needs --datastore-root (nothing to prompt for)")
        datastore_root = click.prompt(
            "GCS datastore root (gs://bucket/prefix)", type=str)
    if not datastore_root.startswith("gs://"):
        raise click.ClickException(
            "datastore root must be a gs:// URL, got %r" % datastore_root)
    if service_url is None and not yes:
        service_url = click.prompt(
            "metadata service URL (blank keeps local metadata)",
            default="", show_default=False)
    updates = {
        "DEFAULT_DATASTORE": "gs",
        "DATASTORE_SYSROOT_GS": datastore_root,
    }
    if service_url:
        updates["DEFAULT_METADATA"] = "service"
        updates["SERVICE_URL"] = service_url
    if not yes:
        for k, v in updates.items():
            click.echo("  %s = %s" % (k, v))
        click.confirm("write these to the profile?", abort=True)
    for k, v in updates.items():
        path = set_conf(k, v)
    click.echo("configured for GCP (%s)" % path)


@configure.command(name="local",
                   help="Reset to local datastore + local metadata.")
def configure_local():
    from .metaflow_config import set_conf

    for key in ("DEFAULT_DATASTORE", "DATASTORE_SYSROOT_GS",
                "DEFAULT_METADATA", "SERVICE_URL"):
        path = set_conf(key, None)
    click.echo("reset to local defaults (%s)" % path)


@configure.command(
    name="validate",
    help="Probe the configured providers: local root writable, GCS "
         "endpoint reachable, metadata service answering /ping.")
def configure_validate():
    from . import metaflow_config as cfg

    failures = 0

    def report(name, ok, detail=""):
        nonlocal failures
        failures += 0 if ok else 1
        click.echo("  [%s] %-18s %s" % ("ok" if ok else "FAIL", name,
                                        detail))

    root = cfg.datastore_sysroot_local()
    try:
        os.makedirs(root, exist_ok=True)
        probe = os.path.join(root, ".configure-probe")
        with open(probe, "w") as f:
            f.write("ok")
        os.unlink(probe)
        report("local datastore", True, root)
    except OSError as ex:
        report("local datastore", False, "%s: %s" % (root, ex))

    if cfg.default_datastore() == "gs" or cfg.datastore_sysroot_gs():
        gs_root = cfg.datastore_sysroot_gs()
        if not gs_root:
            report("gs datastore", False, "DATASTORE_SYSROOT_GS unset")
        else:
            try:
                from .gsop import GSClient, parse_gs_url

                bucket, prefix = parse_gs_url(gs_root)
                GSClient().list(bucket, prefix=prefix, delimiter="/")
                report("gs datastore", True, gs_root)
            except Exception as ex:
                report("gs datastore", False, "%s (%s)" % (gs_root, ex))

    if cfg.default_metadata() == "service" or cfg.service_url():
        url = cfg.service_url()
        if not url:
            report("metadata service", False, "SERVICE_URL unset")
        else:
            try:
                import json
                import urllib.request

                with urllib.request.urlopen(url.rstrip("/") + "/ping",
                                            timeout=5) as resp:
                    info = json.loads(resp.read() or b"{}")
                report("metadata service", True,
                       "%s (version %s)" % (url, info.get("version", "?")))
            except Exception as ex:
                report("metadata service", False, "%s (%s)" % (url, ex))

    if failures:
        raise click.ClickException("%d probe(s) failed" % failures)
    click.echo("configuration valid")


@main.group(help="Developer tooling (reference: `metaflow develop`).")
def develop():
    pass


@develop.command(name="stubs", help="Generate .pyi stubs (alias of "
                                    "`python -m metaflow_tpu stubs`).")
@click.argument("out_dir", default="metaflow_tpu-stubs")
def develop_stubs(out_dir):
    from .cmd.stubgen import generate

    click.echo("wrote %s" % generate(out_dir))


def _run_flow_subcommand(flow_file, subcommand):
    import subprocess

    try:
        proc = subprocess.run(
            [sys.executable, flow_file, subcommand], capture_output=True,
            text=True, timeout=120,
        )
    except subprocess.TimeoutExpired:
        raise click.ClickException(
            "`%s %s` timed out after 120s (hanging import?)"
            % (flow_file, subcommand))
    if proc.returncode != 0:
        # both streams: the error usually lands on stderr while partial
        # output sits on stdout
        for stream in (proc.stdout, proc.stderr):
            if stream.strip():
                click.echo(stream.strip(), err=True)
        raise SystemExit(proc.returncode)
    click.echo(proc.stdout.strip() or proc.stderr.strip())


@develop.command(name="check",
                 help="Import a flow file and run the full linter without "
                      "executing anything.")
@click.argument("flow_file", type=click.Path(exists=True))
def develop_check(flow_file):
    _run_flow_subcommand(flow_file, "check")


@develop.command(name="graph",
                 help="Print a flow's DAG (text, or graphviz dot with "
                      "--dot).")
@click.argument("flow_file", type=click.Path(exists=True))
@click.option("--dot", is_flag=True)
def develop_graph(flow_file, dot):
    _run_flow_subcommand(flow_file, "output-dot" if dot else "show")


@main.group()
def tutorials():
    pass


def _tutorials_dir():
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, "tutorials")


@tutorials.command(name="list")
def tutorials_list():
    root = _tutorials_dir()
    if not os.path.isdir(root):
        click.echo("no tutorials directory found")
        return
    for name in sorted(os.listdir(root)):
        if os.path.isdir(os.path.join(root, name)):
            click.echo(name)


@tutorials.command(name="pull")
@click.argument("dest", default="tpuflow-tutorials")
def tutorials_pull(dest):
    root = _tutorials_dir()
    if not os.path.isdir(root):
        raise click.ClickException("no tutorials directory found")
    shutil.copytree(root, dest, dirs_exist_ok=True)
    click.echo("tutorials copied to %s" % dest)


@main.command()
@click.argument("out_dir", default="metaflow_tpu-stubs")
def stubs(out_dir):
    from .cmd.stubgen import generate

    click.echo("wrote %s" % generate(out_dir))


@main.command(
    help="Aggregate a run's flight-recorder telemetry: "
         "`metrics FLOW/RUN_ID` (or `metrics FLOW RUN_ID`). Shows "
         "per-task durations, training throughput (tokens/sec, MFU) "
         "aggregated across gang ranks, and captured profiles — all "
         "from datastore-persisted records, no worker disk needed.")
@click.argument("flow_run")
@click.argument("run_id", required=False)
@click.option("--datastore", default=None,
              type=click.Choice(["local", "gs"]),
              help="Storage backend (default: configured default).")
@click.option("--datastore-root", default=None,
              help="Datastore root override.")
@click.option("--json", "as_json", is_flag=True,
              help="Emit the aggregation as JSON.")
@click.option("--timeline", is_flag=True,
              help="Per-train-step wall/tokens-per-sec/MFU series.")
@click.option("--spans", default=0, type=int,
              help="Show the N slowest timer spans of the run.")
@click.option("--step", "step_filter", default=None,
              help="Only records from this flow step.")
@click.option("--rank", "rank_filter", default=None, type=int,
              help="Only records from this gang rank.")
def metrics(flow_run, run_id, datastore, datastore_root, as_json,
            timeline, spans, step_filter, rank_filter):
    from .cmd.metrics import show_metrics

    fds, run_id = _resolve_run(flow_run, run_id, datastore,
                               datastore_root)
    show_metrics(fds, run_id, as_json=as_json, timeline=timeline,
                 spans=spans, step=step_filter, rank=rank_filter,
                 echo=click.echo)


@main.command(
    help="Chip-second accounting for a run: `goodput FLOW/RUN_ID`. "
         "Derives the goodput ledger from persisted telemetry — every "
         "chip-second bucketed into the pinned set of categories (productive "
         "step, compile, input/transfer stall, checkpoint, restore "
         "replay, capacity wait, serve prefill/decode/idle) — "
         "reconciles it against observed chip-time, and names the "
         "dominant loss. Exits non-zero when the ledger fails to "
         "reconcile within tolerance.")
@click.argument("flow_run")
@click.argument("run_id", required=False)
@click.option("--datastore", default=None,
              type=click.Choice(["local", "gs"]),
              help="Storage backend (default: configured default).")
@click.option("--datastore-root", default=None,
              help="Datastore root override.")
@click.option("--json", "as_json", is_flag=True,
              help="Emit the full ledger document as JSON.")
@click.option("--openmetrics", is_flag=True,
              help="Emit the run-scope OpenMetrics text exposition.")
@click.option("--persist", is_flag=True,
              help="Persist the ledger to _telemetry/goodput/.")
def goodput(flow_run, run_id, datastore, datastore_root, as_json,
            openmetrics, persist):
    from .cmd.goodput import show_goodput

    fds, run_id = _resolve_run(flow_run, run_id, datastore,
                               datastore_root)
    rc = show_goodput(fds, run_id, as_json=as_json,
                      openmetrics=openmetrics, persist=persist,
                      echo=click.echo)
    if rc:
        raise SystemExit(rc)


def _resolve_run(flow_run, run_id, datastore, datastore_root):
    """FLOW/RUN_ID (or FLOW RUN_ID) + backend flags -> (fds, run_id);
    shared by the read-side commands (metrics / trace / watch)."""
    from .datastore import STORAGE_BACKENDS, FlowDataStore
    from . import metaflow_config as cfg

    if run_id is None:
        flow_name, _, run_id = flow_run.rpartition("/")
        if not flow_name:
            raise click.ClickException(
                "specify a run as FLOW/RUN_ID (or: FLOW RUN_ID)")
    else:
        flow_name = flow_run
    storage_impl = STORAGE_BACKENDS[datastore or cfg.default_datastore()]
    fds = FlowDataStore(flow_name, storage_impl, ds_root=datastore_root)
    return fds, run_id


@main.command(
    help="Reassemble per-request distributed traces from a run's "
         "telemetry: `trace FLOW/RUN_ID`. Shows each serving request "
         "as one tree (queued -> dispatch -> prefill -> first_token -> "
         "finished/failover, across replicas) with a TTFT critical-path "
         "decomposition; --perfetto exports Chrome/Perfetto trace-event "
         "JSON (train runs export their timer spans instead).")
@click.argument("flow_run")
@click.argument("run_id", required=False)
@click.option("--datastore", default=None,
              type=click.Choice(["local", "gs"]),
              help="Storage backend (default: configured default).")
@click.option("--datastore-root", default=None,
              help="Datastore root override.")
@click.option("--request", "request_id", default=None,
              help="Only this request id.")
@click.option("--perfetto", default=None, metavar="OUT.json",
              help="Write Chrome/Perfetto trace-event JSON here.")
@click.option("--json", "as_json", is_flag=True,
              help="Emit assembled trees as JSON.")
def trace(flow_run, run_id, datastore, datastore_root, request_id,
          perfetto, as_json):
    from .cmd.trace import show_trace

    fds, run_id = _resolve_run(flow_run, run_id, datastore,
                               datastore_root)
    show_trace(fds, run_id, request=request_id, perfetto=perfetto,
               as_json=as_json, echo=click.echo)


@main.command(
    help="Live watchtower over a (possibly in-progress) run: "
         "`watch FLOW/RUN_ID`. Tails _telemetry/ part files "
         "incrementally and renders tok/s, MFU, input-stall fraction, "
         "queue depth, slot occupancy, rolling TTFT/ITL percentiles, "
         "replica flaps and straggler skew. --check evaluates the "
         "configured SLO rules (--slo / TPUFLOW_SLO_*) and exits "
         "non-zero on breach.")
@click.argument("flow_run")
@click.argument("run_id", required=False)
@click.option("--datastore", default=None,
              type=click.Choice(["local", "gs"]),
              help="Storage backend (default: configured default).")
@click.option("--datastore-root", default=None,
              help="Datastore root override.")
@click.option("--once", is_flag=True,
              help="Render a single frame and exit.")
@click.option("--check", is_flag=True,
              help="Exit non-zero when an SLO rule is breached.")
@click.option("--interval", default=2.0, type=float,
              help="Refresh interval in seconds.")
@click.option("--slo", "slo_path", default=None,
              help="JSON SLO rule file (default: TPUFLOW_SLO_* env).")
@click.option("--json", "as_json", is_flag=True,
              help="Emit one machine-readable JSON snapshot per poll "
                   "instead of the rendered frame.")
def watch(flow_run, run_id, datastore, datastore_root, once, check,
          interval, slo_path, as_json):
    from .cmd.watch import watch as watch_run

    fds, run_id = _resolve_run(flow_run, run_id, datastore,
                               datastore_root)
    rc = watch_run(fds, run_id, once=once, check=check,
                   interval=interval, slo_path=slo_path,
                   as_json=as_json, echo=click.echo)
    if rc:
        raise SystemExit(rc)


@main.command(
    help="Serve a trained run's checkpoint over HTTP with the "
         "continuous-batching engine: `serve FLOW/RUN_ID` (or `serve "
         "FLOW` for the newest successful run). Slot-based KV cache, "
         "per-request admission/eviction, streamed token output, "
         "graceful SIGTERM drain — docs/serving.md. With --federate "
         "URL,URL no checkpoint is loaded: a thin front router spreads "
         "tenants across the listed running fleets behind one API "
         "(docs/serving.md#federation).")
@click.argument("flow_run", required=False)
@click.argument("run_id", required=False)
@click.option("--step-name", default=None,
              help="The @checkpoint step (auto-detected when unique).")
@click.option("--ckpt-step", default=None, type=int,
              help="Which saved step to serve (default: latest).")
@click.option("--params-key", default="params",
              help="Key of the weight pytree inside the checkpoint.")
@click.option("--config-json", default=None,
              help="Model config as a JSON file or inline object "
                   "(default: the checkpoint's 'cfg' entry).")
@click.option("--model", default="llama",
              type=click.Choice(["brumby", "jamba", "llama", "mixtral",
                                 "nemotron_h", "ouro", "phi4flash"]),
              help="Model family of the checkpoint.")
@click.option("--host", default="127.0.0.1")
@click.option("--port", default=8000, type=int)
@click.option("--replicas", default=1, type=int,
              help="Engine replica processes behind the failover "
                   "router (1 = single-process serving). The fleet "
                   "health-checks replicas, re-dispatches a dead "
                   "replica's in-flight requests token-identically, "
                   "and restarts it with backoff "
                   "(docs/serving.md#fleet).")
@click.option("--slots", default=8, type=int,
              help="Concurrent sequences (KV-cache pool size).")
@click.option("--max-seq-len", default=None, type=int,
              help="KV-cache depth per slot (default: config max).")
@click.option("--prefill-chunk", default=64, type=int,
              help="Prompt tokens prefilled per chunk.")
@click.option("--max-queue", default=64, type=int,
              help="Queued requests before 429 backpressure.")
@click.option("--mesh", "mesh_spec", default=None,
              type=click.Choice(["dp", "fsdp", "fsdp_tp"]),
              help="Shard params over a device mesh (training rules).")
@click.option("--prefill-workers", default=0, type=int,
              help="Dedicated prefill replicas (disaggregated "
                   "prefill/decode): K workers run only chunked "
                   "prefill and hand finished KV state to the decode "
                   "pool. 0 = unified replicas "
                   "(docs/serving.md#disagg).")
@click.option("--prefix-cache-mb", default=None, type=int,
              help="Radix prefix-cache budget per replica in MiB "
                   "(0 disables; default: TPUFLOW_PREFIX_CACHE_MB). "
                   "Cached prompt-prefix KV skips recompute on shared "
                   "system prompts (docs/serving.md#prefix-cache).")
@click.option("--paged", is_flag=True,
              help="Use the paged-KV engine: a global page pool + "
                   "per-slot block tables instead of one static KV "
                   "stripe per slot. Prefix hits share pages zero-copy "
                   "and page exhaustion backpressures admission "
                   "(docs/serving.md#paged-kv).")
@click.option("--page-tokens", default=None, type=int,
              help="Tokens per KV page (default: "
                   "TPUFLOW_KV_PAGE_TOKENS or 16). Paged engine only.")
@click.option("--spec-k", default=None, type=int,
              help="Speculative decoding draft length: propose K "
                   "self-drafted tokens and verify them in one fused "
                   "step (greedy traffic only; 0 disables; default: "
                   "TPUFLOW_SPEC_K). Paged engine only "
                   "(docs/serving.md#speculative-decoding).")
@click.option("--reload", "reload_checkpoint", is_flag=True,
              help="Don't start a server: roll the named checkpoint "
                   "onto the RUNNING fleet at --host/--port via a "
                   "zero-shed rolling upgrade "
                   "(docs/serving.md#rollouts).")
@click.option("--federate", default=None, metavar="URLS",
              help="Don't load a checkpoint: run the federation front "
                   "tier over these comma-separated RUNNING fleet "
                   "URLs, spreading tenants across them behind one "
                   "API (docs/serving.md#federation).")
def serve(flow_run, run_id, step_name, ckpt_step, params_key, config_json,
          model, host, port, replicas, slots, max_seq_len, prefill_chunk,
          max_queue, mesh_spec, prefill_workers, prefix_cache_mb, paged,
          page_tokens, spec_k, reload_checkpoint, federate):
    from . import device
    from .cmd.serve import serve as serve_impl
    from .exception import TpuFlowException

    device.setup_compile_cache()
    if not flow_run and not federate:
        raise click.ClickException(
            "FLOW_RUN is required (or pass --federate URL,URL)")
    try:
        serve_impl(flow_run, run_id=run_id, step_name=step_name,
                   ckpt_step=ckpt_step, params_key=params_key,
                   config_json=config_json, model=model, host=host,
                   port=port, replicas=replicas, slots=slots,
                   max_seq_len=max_seq_len,
                   prefill_chunk=prefill_chunk, max_queue=max_queue,
                   mesh_spec=mesh_spec, prefill_workers=prefill_workers,
                   prefix_cache_mb=prefix_cache_mb,
                   paged=paged, page_tokens=page_tokens, spec_k=spec_k,
                   reload_checkpoint=reload_checkpoint,
                   federate=federate, echo=click.echo)
    except TpuFlowException as ex:
        raise click.ClickException(str(ex))


@main.command(
    name="knobs",
    help="The TPUFLOW_* knob registry (metaflow_tpu/knobs.py): every "
         "environment knob with its type, default, unit, and owning "
         "subsystem. --markdown regenerates docs/knobs.md; --check-env "
         "validates the live environment against the deadline-ordering "
         "lattice and exits non-zero on violations.")
@click.option("--json", "as_json", is_flag=True,
              help="Machine-readable registry dump.")
@click.option("--markdown", is_flag=True,
              help="Emit docs/knobs.md content (byte-identical).")
@click.option("--ordering", is_flag=True,
              help="Show the deadline-ordering lattice edges.")
@click.option("--check-env", is_flag=True,
              help="Validate the live environment against the lattice; "
                   "exit 1 on any violation.")
def knobs_cmd(as_json, markdown, ordering, check_env):
    from .cmd.knobs import show_knobs

    rc = show_knobs(as_json=as_json, markdown=markdown, ordering=ordering,
                    check_env=check_env, echo=click.echo)
    if rc:
        raise SystemExit(rc)


@main.group(help="Sharded streaming dataset corpora: pack token files "
                 "into on-datastore shard blobs + manifest for "
                 "StreamingTokenBatches (docs/data.md).")
def dataset():
    pass


def _dataset_cmd(fn, *args, **kwargs):
    from .exception import TpuFlowException

    try:
        return fn(*args, **kwargs)
    except TpuFlowException as ex:
        raise click.ClickException(str(ex))


@dataset.command(name="build",
                 help="Pack a token file (.npy, or raw binary with "
                      "--dtype) into shards + manifest; --append grows "
                      "an existing corpus instead (new shards + "
                      "manifest revision bump, old readers unaffected).")
@click.argument("flow_name")
@click.argument("name")
@click.option("--input", "input_path", required=True,
              type=click.Path(exists=True),
              help="Token corpus: .npy or raw little-endian binary.")
@click.option("--shard-tokens", default=4 * 1024 * 1024, type=int,
              show_default=True, help="Tokens per shard blob.")
@click.option("--dtype", default=None,
              help="Token dtype (required for raw binary input; "
                   "optional cast for .npy).")
@click.option("--datastore", default=None,
              type=click.Choice(["local", "gs"]),
              help="Storage backend (default: configured default).")
@click.option("--datastore-root", default=None,
              help="Datastore root override.")
@click.option("--overwrite", is_flag=True,
              help="Rebuild over an existing dataset of this name.")
@click.option("--append", "append_", is_flag=True,
              help="Append to an EXISTING dataset (packed at its "
                   "manifest's shard size; --shard-tokens ignored).")
@click.option("--generation", default=None, type=int,
              help="With --append: stamp the new shards with this "
                   "weight generation (online replay freshness key).")
def dataset_build(flow_name, name, input_path, shard_tokens, dtype,
                  datastore, datastore_root, overwrite, append_,
                  generation):
    from .cmd.dataset import append_dataset, build_dataset

    if append_:
        if overwrite:
            raise click.ClickException(
                "--append and --overwrite are mutually exclusive")
        _dataset_cmd(append_dataset, flow_name, name, input_path,
                     dtype=dtype, generation=generation,
                     datastore=datastore, datastore_root=datastore_root,
                     echo=click.echo)
        return
    if generation is not None:
        raise click.ClickException(
            "--generation only applies to --append (a fresh build's "
            "shards are generation 0 by definition)")
    _dataset_cmd(build_dataset, flow_name, name, input_path, shard_tokens,
                 dtype=dtype, datastore=datastore,
                 datastore_root=datastore_root, overwrite=overwrite,
                 echo=click.echo)


@dataset.command(name="info", help="Show a dataset's manifest.")
@click.argument("flow_name")
@click.argument("name")
@click.option("--datastore", default=None,
              type=click.Choice(["local", "gs"]))
@click.option("--datastore-root", default=None)
@click.option("--json", "as_json", is_flag=True)
def dataset_info_cmd(flow_name, name, datastore, datastore_root, as_json):
    from .cmd.dataset import dataset_info

    _dataset_cmd(dataset_info, flow_name, name, datastore=datastore,
                 datastore_root=datastore_root, as_json=as_json,
                 echo=click.echo)


@dataset.command(name="list", help="List a flow's built datasets.")
@click.argument("flow_name")
@click.option("--datastore", default=None,
              type=click.Choice(["local", "gs"]))
@click.option("--datastore-root", default=None)
def dataset_list_cmd(flow_name, datastore, datastore_root):
    from .cmd.dataset import dataset_list

    _dataset_cmd(dataset_list, flow_name, datastore=datastore,
                 datastore_root=datastore_root, echo=click.echo)


@main.command(name="online",
              help="Run the closed actor-learner loop: serve rollouts, "
                   "score them, append to the replay corpus, train, "
                   "push weights back (docs/online.md).")
@click.argument("flow_name")
@click.option("--dataset", default="replay", show_default=True,
              help="Replay corpus name in the flow's datastore.")
@click.option("--run-id", default="online", show_default=True,
              help="Run id telemetry records under.")
@click.option("--rounds", default=None, type=int,
              help="Loop rounds (default: TPUFLOW_ONLINE_ROUNDS).")
@click.option("--rollouts", default=None, type=int,
              help="Rollouts per round (TPUFLOW_ONLINE_ROLLOUTS).")
@click.option("--steps-per-round", default=None, type=int,
              help="Learner steps per round "
                   "(TPUFLOW_ONLINE_STEPS_PER_ROUND).")
@click.option("--push-every", default=None, type=int,
              help="Weight-push cadence in rounds "
                   "(TPUFLOW_ONLINE_PUSH_EVERY).")
@click.option("--max-lag", default=None, type=int,
              help="Off-policy guard in generations "
                   "(TPUFLOW_ONLINE_MAX_LAG).")
@click.option("--max-new-tokens", default=None, type=int,
              help="Decode budget per rollout "
                   "(TPUFLOW_ONLINE_MAX_NEW_TOKENS).")
@click.option("--seq-len", default=32, show_default=True, type=int)
@click.option("--batch-size", default=4, show_default=True, type=int)
@click.option("--prompt-len", default=8, show_default=True, type=int)
@click.option("--seed", default=0, show_default=True, type=int)
@click.option("--vocab-size", default=128, show_default=True, type=int)
@click.option("--dim", default=32, show_default=True, type=int)
@click.option("--n-layers", default=1, show_default=True, type=int)
@click.option("--n-heads", default=2, show_default=True, type=int)
@click.option("--fresh-generations", default=None, type=int,
              help="Replay freshness window "
                   "(TPUFLOW_ONLINE_FRESH_GENERATIONS; 0 = no filter).")
@click.option("--concurrent/--serial", default=False,
              help="Prefetch the next round's rollouts while the "
                   "learner trains (one-round Sebulba pipeline).")
@click.option("--checkpoint-name", default="online", show_default=True,
              help="AsyncCheckpointManager name (resume key).")
@click.option("--reward", default="length", show_default=True,
              type=click.Choice(["length", "diversity", "logprob"]),
              help="Rollout scoring function.")
@click.option("--datastore", default=None,
              type=click.Choice(["local", "gs"]))
@click.option("--datastore-root", default=None)
@click.option("--json-out", default=None, type=click.Path(),
              help="Write the run summary JSON here (harness hook).")
def online_cmd(flow_name, dataset, run_id, rounds, rollouts,
               steps_per_round, push_every, max_lag, max_new_tokens,
               seq_len, batch_size, prompt_len, seed, vocab_size, dim,
               n_layers, n_heads, fresh_generations, concurrent,
               checkpoint_name, reward, datastore, datastore_root,
               json_out):
    from .cmd.online import run_online
    from .exception import TpuFlowException

    try:
        run_online(flow_name, dataset=dataset, run_id=run_id,
                   rounds=rounds, rollouts=rollouts,
                   steps_per_round=steps_per_round,
                   push_every=push_every, max_lag=max_lag,
                   max_new_tokens=max_new_tokens, seq_len=seq_len,
                   batch_size=batch_size, prompt_len=prompt_len,
                   seed=seed, vocab_size=vocab_size, dim=dim,
                   n_layers=n_layers, n_heads=n_heads,
                   fresh_generations=fresh_generations,
                   concurrent=concurrent,
                   checkpoint_name=checkpoint_name, reward=reward,
                   datastore=datastore, datastore_root=datastore_root,
                   json_out=json_out, echo=click.echo)
    except TpuFlowException as ex:
        raise click.ClickException(str(ex))


@main.group(help="Local full-stack dev harness: fake GCS + metadata "
                 "service (the reference's metaflow-dev, containerless).")
def devstack():
    pass


@devstack.command(name="up", help="Start the stack and serve until Ctrl-C.")
@click.option("--gs-port", default=0, help="fake GCS port (0 = ephemeral)")
@click.option("--metadata-port", default=0,
              help="metadata service port (0 = ephemeral)")
@click.option("--root", default=None,
              help="data directory (default: $TMPDIR/tpuflow_devstack_data)")
def devstack_up(gs_port, metadata_port, root):
    from . import devtools

    if devtools.read_state() is not None:
        raise click.ClickException(
            "a devstack is already running (devstack status / down)"
        )
    stack = devtools.DevStack(
        gs_port=gs_port, metadata_port=metadata_port, root=root
    ).start()
    stack.write_state()
    click.echo("devstack up:", err=True)
    click.echo("  fake GCS:  %s" % stack.gs_endpoint, err=True)
    click.echo("  metadata:  %s" % stack.metadata_url, err=True)
    click.echo("in another shell:", err=True)
    click.echo('  eval "$(python -m metaflow_tpu devstack env)"', err=True)
    click.echo("  python myflow.py run", err=True)
    import signal as _signal
    import threading

    done = threading.Event()
    for sig in (_signal.SIGINT, _signal.SIGTERM):
        _signal.signal(sig, lambda *a: done.set())
    try:
        done.wait()
    finally:
        stack.stop()
        try:
            os.unlink(devtools.STATE_FILE)
        except OSError:
            pass
        click.echo("devstack stopped", err=True)


@devstack.command(name="env",
                  help="Print `export` lines for the running stack.")
def devstack_env():
    from . import devtools

    state = devtools.read_state()
    if state is None:
        raise click.ClickException("no devstack running (devstack up)")
    for key, value in state["env"].items():
        click.echo("export %s=%s" % (key, value))


@devstack.command(name="status")
def devstack_status():
    from . import devtools

    state = devtools.read_state()
    if state is None:
        click.echo("devstack: not running")
    else:
        click.echo("devstack: running (pid %d)" % state["pid"])
        for key, value in state["env"].items():
            click.echo("  %s=%s" % (key, value))


@devstack.command(name="down", help="Stop a running stack.")
def devstack_down():
    from . import devtools

    if devtools.stop_running():
        click.echo("devstack stopped")
    else:
        click.echo("no devstack running")


if __name__ == "__main__":
    main()
