"""Declarative registry of every ``TPUFLOW_*`` environment knob.

This module is the single source of truth for knob names, types,
defaults, units, owning subsystems, and the cross-knob deadline
ordering lattice. Library code reads knobs through the typed
accessors (:func:`get_str` / :func:`get_int` / :func:`get_float` /
:func:`get_bool`) instead of raw ``os.environ`` lookups — the
`contracts` static-analysis pass (metaflow_tpu/analysis/contracts.py)
flags any raw ``TPUFLOW_*`` read outside this file, and
``tests/test_contracts.py`` keeps the library self-scan at zero
errors, so a default can no longer drift between two call sites.

Semantics, pinned so migration is behavior-preserving:

* unset OR empty-string value -> registry default (CI templates export
  ``VAR=`` to mean "use the default"; metaflow_config always treated
  empty as unset, and the registry extends that to every knob);
* malformed int/float -> registry default (the historical
  ``util.env_int`` degrade-don't-crash contract: a typo'd knob must
  never kill a gang at import time);
* bool: a set value counts as false only for ``0/false/no/off``
  (case-insensitive) — everything else is true, matching the dominant
  ``!= "0"`` convention at the old read sites;
* ``fallback=`` overrides the registry default at one call site for
  *computed* defaults (cpu counts, tmp dirs, "inherit the recv
  timeout"). Literal fallbacks that disagree with the registry are
  exactly the drift the contracts pass exists to catch — keep
  fallbacks dynamic.

``python -m metaflow_tpu knobs`` renders this registry (``--markdown``
regenerates docs/knobs.md byte-identically; ``--check-env`` runs the
ordering lattice against the live environment).
"""

import json
import os

_UNSET = object()

#: values (lowercased, stripped) that make a *set* bool knob false
_FALSEY = ("0", "false", "no", "off")

#: subsystem render order for docs/CLI — append, never reorder, or the
#: docs/knobs.md byte-identity test goes red
SUBSYSTEM_ORDER = (
    "config", "runtime", "datastore", "data", "training", "ops", "spmd",
    "progress", "elastic", "serving", "fleet", "slo", "telemetry",
    "analysis", "tpu", "conda", "chaos", "internal", "online", "tenancy",
)


class UnknownKnobError(KeyError):
    """Raised when an accessor is called with an unregistered name."""

    def __init__(self, name, suggestion=None):
        self.name = name
        self.suggestion = suggestion
        msg = "unregistered knob %r" % (name,)
        if suggestion:
            msg += " (did you mean %r?)" % (suggestion,)
        super(UnknownKnobError, self).__init__(msg)


class Knob(object):
    """One registered knob: declarative metadata, no behavior."""

    __slots__ = ("name", "ktype", "default", "unit", "subsystem", "doc")

    def __init__(self, name, ktype, default, unit, subsystem, doc):
        self.name = name
        self.ktype = ktype          # "str" | "int" | "float" | "bool" | "path"
        self.default = default      # typed, or None for "no default"
        self.unit = unit            # "s" | "ms" | "MB" | ... | ""
        self.subsystem = subsystem  # one of SUBSYSTEM_ORDER
        self.doc = doc              # one line, rendered into docs/knobs.md

    def to_dict(self):
        return {
            "name": self.name,
            "type": self.ktype,
            "default": self.default,
            "unit": self.unit,
            "subsystem": self.subsystem,
            "doc": self.doc,
        }


KNOBS = {}

#: dynamic knob families read by prefix iteration, not by literal name
PREFIXES = {
    "TPUFLOW_PARAM_": "flow parameter values injected per-pod by the "
                      "Argo compiler (--params-from-env)",
}


def _k(name, ktype, default, unit, subsystem, doc):
    assert name not in KNOBS, name
    assert subsystem in SUBSYSTEM_ORDER, subsystem
    KNOBS[name] = Knob(name, ktype, default, unit, subsystem, doc)


# --- config ----------------------------------------------------------------
_k("TPUFLOW_PROFILE", "str", "", "", "config",
   "active config profile name ('' = default profile)")
_k("TPUFLOW_HOME", "path", "~/.tpuflowconfig", "", "config",
   "directory holding config profiles")
_k("TPUFLOW_SERVICE_URL", "str", None, "", "config",
   "metadata REST service URL (via from_conf; METAFLOW_ fallback)")
_k("TPUFLOW_DEFAULT_DATASTORE", "str", "local", "", "config",
   "datastore backend when a flow does not pick one (via from_conf)")
_k("TPUFLOW_DEFAULT_METADATA", "str", "local", "", "config",
   "metadata provider when a flow does not pick one (via from_conf)")
_k("TPUFLOW_DATASTORE_SYSROOT_LOCAL", "path", None, "", "config",
   "local datastore root (default: ./.tpuflow; via from_conf)")
_k("TPUFLOW_DATASTORE_SYSROOT_GS", "str", None, "", "config",
   "gs:// datastore root for the gs backend (via from_conf)")
_k("TPUFLOW_USER", "str", None, "", "config",
   "username recorded in run metadata (falls back to USER et al.)")
_k("TPUFLOW_DEBUG", "bool", False, "", "config",
   "print tracebacks for framework exceptions")
_k("TPUFLOW_MONITOR", "str", "file", "", "config",
   "monitor sidecar backend")
_k("TPUFLOW_EVENT_LOGGER", "str", "file", "", "config",
   "event-logger sidecar backend")
_k("TPUFLOW_DISABLE_EXTENSIONS", "bool", False, "", "config",
   "skip loading metaflow_extensions packages")
_k("TPUFLOW_GS_ENDPOINT", "str", "https://storage.googleapis.com", "",
   "config", "GS JSON-API endpoint (point at a fake-gcs for tests)")
_k("TPUFLOW_ARGO_EVENTS_URL", "str", None, "", "config",
   "Argo Events webhook URL for @trigger publishing")
_k("TPUFLOW_KUBECTL", "str", "kubectl", "", "config",
   "kubectl binary used by the Argo deployer")
_k("TPUFLOW_OTEL_ENDPOINT", "str", None, "", "config",
   "OTLP endpoint enabling OpenTelemetry span export")

# --- runtime ---------------------------------------------------------------
_k("TPUFLOW_ELASTIC", "bool", True, "", "runtime",
   "route gang retries through the elastic supervisor (0 = legacy "
   "immediate re-fork)")
_k("TPUFLOW_FORK_WORKERS", "bool", True, "", "runtime",
   "fork local step workers instead of spawning fresh interpreters")
_k("TPUFLOW_GANG_FINALIZE_TIMEOUT", "float", 300.0, "s", "runtime",
   "deadline for gang-wide finalize barrier at task exit")
_k("TPUFLOW_GANG_NODE_WAIT_TIMEOUT_S", "float", 0.0, "s", "runtime",
   "deadline for multi-node gang peers to appear (0 = wait forever)")
_k("TPUFLOW_DAEMON_SOCKET", "path", None, "", "runtime",
   "devstack daemon control socket (default: per-uid tmp path)")
_k("TPUFLOW_DATATOOLS_ROOT", "path", None, "", "runtime",
   "root for datatools blob uploads (default: cwd)")
_k("TPUFLOW_INCLUDEFILE_MAX_MB", "int", 10240, "MB", "runtime",
   "size cap for IncludeFile uploads")
_k("TPUFLOW_ESCAPE_SOCKET", "path", None, "", "runtime",
   "env-escape server socket (set by the server process)")

# --- datastore -------------------------------------------------------------
_k("TPUFLOW_BLOB_CACHE", "bool", True, "", "datastore",
   "share the host-local CAS blob cache for non-local datastores")
_k("TPUFLOW_PERSIST_PIPELINE", "bool", True, "", "datastore",
   "overlap artifact persist with step execution")
_k("TPUFLOW_PERSIST_WORKERS", "int", None, "count", "datastore",
   "persist pipeline serializer threads (default: min(8, max(2, cpus)))")
_k("TPUFLOW_PERSIST_UPLOADS", "int", None, "count", "datastore",
   "persist pipeline upload threads (default: min(8, max(2, cpus)))")
_k("TPUFLOW_PERSIST_INFLIGHT_MB", "int", 0, "MB", "datastore",
   "persist pipeline in-flight byte budget (0 = built-in 512)")
_k("TPUFLOW_STORAGE_RETRIES", "int", 3, "count", "datastore",
   "retry budget for storage operations")
_k("TPUFLOW_STORAGE_TIMEOUT_S", "float", 0.0, "s", "datastore",
   "per-attempt deadline for blocking storage ops (0 = no deadline)")
_k("TPUFLOW_SCRATCH_DIR", "path", None, "", "datastore",
   "scratch spill directory for large blob staging")
_k("TPUFLOW_CLIENT_CACHE", "path", None, "", "datastore",
   "client-side artifact cache dir (default: $TMPDIR/tpuflow_cache)")

# --- data ------------------------------------------------------------------
_k("TPUFLOW_DATA_READAHEAD_MB", "float", 64.0, "MB", "data",
   "shard readahead budget per reader")
_k("TPUFLOW_DATA_WORKERS", "int", 8, "count", "data",
   "shard fetch worker threads")

# --- training --------------------------------------------------------------
_k("TPUFLOW_PEAK_TFLOPS", "float", None, "TFLOP/s", "training",
   "per-chip peak TFLOPs override for MFU accounting")
_k("TPUFLOW_ZERO", "bool", False, "", "training",
   "ZeRO-style optimizer-state sharding over the data axis")

# --- ops -------------------------------------------------------------------
_k("TPUFLOW_GMM_BLOCK_S", "int", 128, "", "ops",
   "grouped matmul block size along tokens")
_k("TPUFLOW_GMM_BLOCK_F", "int", 128, "", "ops",
   "grouped matmul block size along features")
_k("TPUFLOW_GMM_BLOCK_D", "int", 128, "", "ops",
   "grouped matmul block size along model dim")
_k("TPUFLOW_RING_IMPL", "str", "auto", "", "ops",
   "ring-attention implementation (auto|collective|manual)")

# --- spmd ------------------------------------------------------------------
_k("TPUFLOW_SANITIZE", "bool", False, "", "spmd",
   "enable the gang sanitizer (cross-rank divergence probes)")
_k("TPUFLOW_SANITIZE_EVERY", "int", 64, "steps", "spmd",
   "steps between sanitizer probes")
_k("TPUFLOW_SANITIZE_WINDOW", "int", 512, "steps", "spmd",
   "sanitizer rolling-window length")
_k("TPUFLOW_SANITIZE_TIMEOUT", "float", 30.0, "s", "spmd",
   "sanitizer collective barrier deadline")
_k("TPUFLOW_MPMD_RECV_TIMEOUT_S", "float", 60.0, "s", "spmd",
   "MPMD activation recv deadline per hop")
_k("TPUFLOW_MPMD_SEND_TIMEOUT_S", "float", None, "s", "spmd",
   "MPMD activation send deadline (default: inherit recv timeout)")
_k("TPUFLOW_MPMD_CONNECT_TIMEOUT_S", "float", 30.0, "s", "spmd",
   "MPMD stage link connect deadline")
_k("TPUFLOW_MPMD_LINK_LATENCY_MS", "float", 0.0, "ms", "spmd",
   "injected DCN link latency for tests/chaos")
_k("TPUFLOW_MPMD_SYNC", "bool", False, "", "spmd",
   "force synchronous (non-overlapped) MPMD exchange")

# --- progress --------------------------------------------------------------
_k("TPUFLOW_PROGRESS_EVERY_S", "float", 1.0, "s", "progress",
   "progress-beat write throttle per rank")
_k("TPUFLOW_HANG_DETECT", "bool", True, "", "progress",
   "enable the gang hang watchdog")
_k("TPUFLOW_HANG_FLOOR_S", "float", 60.0, "s", "progress",
   "minimum no-progress window before hang escalation")
_k("TPUFLOW_HANG_COMPILE_GRACE_S", "float", 600.0, "s", "progress",
   "hang deadline while a first compile is plausible")
_k("TPUFLOW_HANG_DEADLINE_MULT", "float", 8.0, "x", "progress",
   "hang deadline as a multiple of the step-time EMA")
_k("TPUFLOW_HANG_POLL_S", "float", 5.0, "s", "progress",
   "watchdog poll interval")
_k("TPUFLOW_HANG_KILL_GRACE_S", "float", 5.0, "s", "progress",
   "SIGTERM-to-SIGKILL grace when escalating a hang")
_k("TPUFLOW_HANG_DUMP_WAIT_S", "float", 0.5, "s", "progress",
   "wait after requesting stack dumps before killing")
_k("TPUFLOW_HANG_DUMP_SIGNAL", "int", 0, "signal", "progress",
   "signal number for all-thread stack dumps (0 = SIGQUIT)")
_k("TPUFLOW_HANG_SAME_STEP_MAX", "int", 2, "count", "progress",
   "hang escalations tolerated on one step before shrinking")

# --- elastic ---------------------------------------------------------------
_k("TPUFLOW_ELASTIC_RESIZE", "bool", True, "", "elastic",
   "allow the supervisor to shrink/grow the gang")
_k("TPUFLOW_ELASTIC_RETRIES", "int", 8, "count", "elastic",
   "supervisor relaunch budget")
_k("TPUFLOW_ELASTIC_SHRINK_AFTER", "int", 2, "count", "elastic",
   "consecutive capacity failures before shrinking")
_k("TPUFLOW_ELASTIC_GROW_EVERY_S", "float", 5.0, "s", "elastic",
   "parked-capacity recheck interval (grow probe cadence)")
_k("TPUFLOW_CAPACITY_ORACLE", "str", "none", "", "elastic",
   "capacity oracle spec (none | static:N | scripted:... | gce)")
_k("TPUFLOW_CAPACITY_HINT", "int", None, "count", "elastic",
   "externally supplied available-chip hint")
_k("TPUFLOW_RETRY_BACKOFF_BASE_S", "float", 0.2, "s", "elastic",
   "retry backoff base delay")
_k("TPUFLOW_RETRY_BACKOFF_CAP_S", "float", 60.0, "s", "elastic",
   "retry backoff delay cap")
_k("TPUFLOW_RETRY_BACKOFF_JITTER", "float", 0.5, "frac", "elastic",
   "retry backoff jitter fraction")
_k("TPUFLOW_RETRY_BACKOFF_SEED", "int", None, "", "elastic",
   "deterministic backoff jitter seed (tests)")

# --- serving ---------------------------------------------------------------
_k("TPUFLOW_PAGED", "bool", False, "", "serving",
   "serve with the paged KV-cache engine")
_k("TPUFLOW_KV_PAGE_TOKENS", "int", 16, "tokens", "serving",
   "tokens per KV page (paged engine allocation granule)")
_k("TPUFLOW_SPEC_K", "int", 0, "tokens", "serving",
   "speculative draft length (0 = disabled)")
_k("TPUFLOW_PREFIX_CACHE_MB", "float", 0.0, "MB", "serving",
   "prefix KV cache budget (0 = disabled)")
_k("TPUFLOW_SERVE_LATENCY_WINDOW", "int", 1024, "count", "serving",
   "latency percentile reservoir size")
_k("TPUFLOW_TRACE_REQUESTS", "bool", True, "", "serving",
   "per-request spans in the serving scheduler")

# --- fleet -----------------------------------------------------------------
_k("TPUFLOW_FLEET_MAX_INFLIGHT", "int", None, "count", "fleet",
   "fleet-wide in-flight request cap (default: replicas * slots)")
_k("TPUFLOW_FLEET_FAILOVER", "bool", True, "", "fleet",
   "redispatch requests off dead replicas")
_k("TPUFLOW_FLEET_RESTART", "bool", True, "", "fleet",
   "restart dead replicas")
_k("TPUFLOW_FLEET_MAX_RESTARTS", "int", 16, "count", "fleet",
   "replica restart budget per fleet")
_k("TPUFLOW_FLEET_HEALTH_INTERVAL_S", "float", 1.0, "s", "fleet",
   "replica health-probe interval")
_k("TPUFLOW_FLEET_HEALTH_FAILS", "int", 3, "count", "fleet",
   "consecutive probe failures before a replica is dead")
_k("TPUFLOW_FLEET_SPAWN_TIMEOUT_S", "float", 180.0, "s", "fleet",
   "replica spawn-to-ready deadline")
_k("TPUFLOW_FLEET_REDISPATCH_MAX", "int", 3, "count", "fleet",
   "failover redispatch attempts per request")
_k("TPUFLOW_FLEET_WAIT_S", "float", 15.0, "s", "fleet",
   "request wait-for-dispatch deadline")
_k("TPUFLOW_FLEET_AUTOSCALE", "bool", False, "", "fleet",
   "enable queue-driven replica autoscaling")
_k("TPUFLOW_FLEET_MIN_REPLICAS", "int", 1, "count", "fleet",
   "autoscaler floor")
_k("TPUFLOW_FLEET_MAX_REPLICAS", "int", 8, "count", "fleet",
   "autoscaler ceiling")
_k("TPUFLOW_FLEET_SCALE_OUT_QUEUE", "float", 2.0, "x", "fleet",
   "scale out when queue depth per replica exceeds this")
_k("TPUFLOW_FLEET_SCALE_IN_OCC", "float", 0.25, "frac", "fleet",
   "scale in when occupancy drops below this")
_k("TPUFLOW_FLEET_SCALE_SUSTAIN", "int", 3, "count", "fleet",
   "consecutive breaches before the autoscaler acts")

# --- slo -------------------------------------------------------------------
_k("TPUFLOW_SLO_FILE", "path", None, "", "slo",
   "JSON file of SLO rules")
_k("TPUFLOW_SLO_P99_TTFT_MS", "float", None, "ms", "slo",
   "upper bound on p99 time-to-first-token")
_k("TPUFLOW_SLO_P99_ITL_MS", "float", None, "ms", "slo",
   "upper bound on p99 inter-token latency")
_k("TPUFLOW_SLO_INPUT_STALL_FRAC", "float", None, "frac", "slo",
   "upper bound on input-pipeline stall fraction")
_k("TPUFLOW_SLO_RESTART_RATE_PER_MIN", "float", None, "1/min", "slo",
   "upper bound on replica restart rate")
_k("TPUFLOW_SLO_DESYNC", "float", None, "count", "slo",
   "upper bound on sanitizer desync count")

# --- telemetry -------------------------------------------------------------
_k("TPUFLOW_TELEMETRY", "bool", True, "", "telemetry",
   "enable the flight recorder")
_k("TPUFLOW_TELEMETRY_FLUSH_EVERY", "int", 512, "records", "telemetry",
   "flush the record buffer every N records")
_k("TPUFLOW_PROFILE_STEPS", "str", "", "", "telemetry",
   "profiler step window spec (e.g. '10:12')")
_k("TPUFLOW_PROFILE_REQUEST", "path", "", "", "telemetry",
   "touch-file that requests an ad-hoc profile capture")
_k("TPUFLOW_PROFILE_SIGNAL", "bool", False, "", "telemetry",
   "install the signal-triggered profile capture handler")

# --- analysis --------------------------------------------------------------
_k("TPUFLOW_ANALYZE", "bool", True, "", "analysis",
   "run the pre-run static-analysis gate")
_k("TPUFLOW_STRICT_CHECK", "bool", False, "", "analysis",
   "escalate analyzer warnings at the pre-run gate to fatal")

# --- tpu -------------------------------------------------------------------
_k("TPUFLOW_TPU_LAUNCHER", "str", None, "", "tpu",
   "launch @tpu steps through the TPU VM launcher when set")
_k("TPUFLOW_TPU_PROJECT", "str", None, "", "tpu",
   "GCP project for TPU provisioning")
_k("TPUFLOW_TPU_ZONE", "str", None, "", "tpu",
   "GCE zone for TPU provisioning")
_k("TPUFLOW_TPU_TYPE", "str", None, "", "tpu",
   "accelerator type (default: the topology knob)")
_k("TPUFLOW_TPU_TOPOLOGY", "str", "v5litepod-4", "", "tpu",
   "TPU topology / accelerator shape")
_k("TPUFLOW_TPU_VERSION", "str", "tpu-ubuntu2204-base", "", "tpu",
   "TPU VM runtime version")
_k("TPUFLOW_TPU_REUSE", "str", None, "", "tpu",
   "reuse this existing TPU VM instead of provisioning")
_k("TPUFLOW_TPU_SPOT", "bool", False, "", "tpu",
   "provision spot (preemptible) TPU VMs")
_k("TPUFLOW_TPU_KEEP", "bool", False, "", "tpu",
   "keep ephemeral TPU VMs alive after the step")
_k("TPUFLOW_PACKAGE_URL", "str", None, "", "tpu",
   "pre-uploaded code package URL for TPU VM bootstrap")
_k("TPUFLOW_SPOT_MARKER_TTL_S", "float", 900.0, "s", "tpu",
   "preemption marker freshness window")
_k("TPUFLOW_SPOT_METADATA_URL", "str",
   "http://metadata.google.internal/computeMetadata/v1/instance/preempted",
   "", "tpu", "preemption metadata probe URL")

# --- conda -----------------------------------------------------------------
_k("TPUFLOW_MICROMAMBA", "path", None, "", "conda",
   "micromamba binary override")
_k("TPUFLOW_CONDA_OFFLINE", "bool", False, "", "conda",
   "resolve conda environments offline")
_k("TPUFLOW_CONDA_PKGS_DIRS", "path", None, "", "conda",
   "conda package cache directory override")
_k("TPUFLOW_WHEELHOUSE", "path", None, "", "conda",
   "directory of wheels for offline pip installs")

# --- chaos -----------------------------------------------------------------
_k("TPUFLOW_CHAOS", "str", "", "", "chaos",
   "chaos schedule spec ('' = disabled)")
_k("TPUFLOW_CHAOS_STEPS", "int", 10, "steps", "chaos",
   "seeded chaos horizon")
_k("TPUFLOW_CHAOS_NKILLS", "int", 1, "count", "chaos",
   "kills drawn from the chaos seed")
_k("TPUFLOW_CHAOS_SLOW_S", "float", 1.0, "s", "chaos",
   "injected slowdown duration")
_k("TPUFLOW_CHAOS_DIR", "path", None, "", "chaos",
   "once-only chaos ledger dir (default: run-scoped tmp)")
_k("TPUFLOW_CHAOS_FLEET", "str", "", "", "chaos",
   "fleet chaos schedule spec ('' = disabled)")
_k("TPUFLOW_CHAOS_FLEET_DISPATCHES", "int", 8, "count", "chaos",
   "seeded fleet-chaos dispatch horizon")
_k("TPUFLOW_CHAOS_FLEET_NKILLS", "int", 1, "count", "chaos",
   "replica kills drawn from the fleet-chaos seed")

# --- internal (set by the runtime, read by children — not user-facing) -----
_k("TPUFLOW_QUEUE_TS", "float", None, "s", "internal",
   "epoch timestamp of task enqueue (set by the scheduler)")
_k("TPUFLOW_STEP_ARGV", "str", None, "", "internal",
   "step argv payload for the launcher trampoline")
_k("TPUFLOW_TRIGGER_EVENTS", "str", None, "", "internal",
   "JSON trigger-event payload injected by Argo")
_k("TPUFLOW_ELASTIC_SIZE", "int", None, "count", "internal",
   "gang size granted by the elastic supervisor")
_k("TPUFLOW_ELASTIC_TOPOLOGY", "str", None, "", "internal",
   "gang topology granted by the elastic supervisor")
_k("TPUFLOW_NUMPAR_INT", "str", None, "", "internal",
   "Argo template placeholder for the num-parallel integer")
_k("TPUFLOW_REPLICA_TELEMETRY_FLOW", "str", None, "", "internal",
   "flight-recorder flow name injected into serve replicas")
_k("TPUFLOW_REPLICA_TELEMETRY_RUN", "str", None, "", "internal",
   "flight-recorder run id injected into serve replicas")

# --- online (metaflow_tpu/online/: actor-learner loop) ---------------------
_k("TPUFLOW_ONLINE_ROUNDS", "int", 4, "count", "online",
   "rollout->append->train->push rounds per `tpuflow online` run")
_k("TPUFLOW_ONLINE_ROLLOUTS", "int", 8, "count", "online",
   "rollouts the actor generates per round")
_k("TPUFLOW_ONLINE_STEPS_PER_ROUND", "int", 2, "steps", "online",
   "learner train steps per round")
_k("TPUFLOW_ONLINE_PUSH_EVERY", "int", 1, "rounds", "online",
   "push learner weights to the actor every N rounds")
_k("TPUFLOW_ONLINE_MAX_NEW_TOKENS", "int", 16, "tokens", "online",
   "decode budget per rollout")
_k("TPUFLOW_ONLINE_MAX_LAG", "int", 2, "generations", "online",
   "off-policy guard: drop rollouts older than this many weight "
   "generations")
_k("TPUFLOW_ONLINE_FRESH_GENERATIONS", "int", 0, "generations", "online",
   "ReplayReader freshness window in generations (0 = no filter)")

# --- tenancy (serving/tenancy.py + cache_router.py: multi-tenant tier) -----
_k("TPUFLOW_TENANT_WEIGHTS", "str", "", "", "tenancy",
   "per-tenant DRR weights, 'gold=4,free=1' ('' = single-tenant)")
_k("TPUFLOW_TENANT_PRIORITIES", "str", "", "", "tenancy",
   "per-tenant priority classes, 'gold=high,free=low'")
_k("TPUFLOW_TENANT_BUDGETS", "str", "", "", "tenancy",
   "per-tenant token budgets per rolling window, 'free=4096'")
_k("TPUFLOW_TENANT_BUDGET_WINDOW_S", "float", 10.0, "s", "tenancy",
   "rolling window the tenant token budgets apply over")
_k("TPUFLOW_TENANT_DEFAULT", "str", "default", "", "tenancy",
   "bucket name for requests that carry no tenant id")
_k("TPUFLOW_TENANT_QUANTUM", "int", 256, "tokens", "tenancy",
   "DRR credit quantum per round (scaled by each tenant's weight)")
_k("TPUFLOW_TENANT_FLEET_MAP", "str", "", "", "tenancy",
   "federation tenant->fleet pins, 'gold=0,free=1' (else hash spread)")
_k("TPUFLOW_CACHE_ROUTE", "bool", True, "", "tenancy",
   "cache-aware dispatch: route to the replica with the longest "
   "cached prompt prefix")
_k("TPUFLOW_CACHE_ROUTE_BLOCK", "int", 16, "tokens", "tenancy",
   "digest block size for radix-cache replicas (paged replicas "
   "publish at their page size)")
_k("TPUFLOW_CACHE_ROUTE_DIGESTS", "int", 512, "count", "tenancy",
   "max prefix digests a replica publishes through /healthz")
_k("TPUFLOW_CACHE_ROUTE_MIN_TOKENS", "int", 32, "tokens", "tenancy",
   "cached-prefix score below this is treated as cold (load wins)")
_k("TPUFLOW_SLO_TENANT_P99_TTFT_MS", "float", None, "ms", "tenancy",
   "per-tenant upper bound on p99 time-to-first-token (one rule per "
   "live tenant)")


# ---------------------------------------------------------------------------
# deadline-ordering lattice
# ---------------------------------------------------------------------------

class Ordering(object):
    """One edge of the deadline partial order: ``lo`` must be <= ``hi``.

    ``skip_if_zero`` skips the check when either side is <= 0 (the
    0-means-disabled convention shared by the deadline knobs)."""

    __slots__ = ("lo", "hi", "reason", "skip_if_zero")

    def __init__(self, lo, hi, reason, skip_if_zero=False):
        assert lo in KNOBS and hi in KNOBS, (lo, hi)
        self.lo = lo
        self.hi = hi
        self.reason = reason
        self.skip_if_zero = skip_if_zero


#: unset knobs that inherit another knob's effective value
INHERITS = {
    "TPUFLOW_MPMD_SEND_TIMEOUT_S": "TPUFLOW_MPMD_RECV_TIMEOUT_S",
}

ORDERING = (
    Ordering("TPUFLOW_MPMD_RECV_TIMEOUT_S", "TPUFLOW_HANG_FLOOR_S",
             "a recv timeout above the hang floor lets the watchdog kill "
             "a gang that is merely backpressured — routine stalls become "
             "relaunch storms"),
    Ordering("TPUFLOW_MPMD_SEND_TIMEOUT_S", "TPUFLOW_HANG_FLOOR_S",
             "a send timeout above the hang floor lets the watchdog "
             "escalate before the sender can observe the slow link"),
    Ordering("TPUFLOW_MPMD_CONNECT_TIMEOUT_S", "TPUFLOW_MPMD_RECV_TIMEOUT_S",
             "connect must give up before the first recv deadline or the "
             "stage blames the payload for a link that never came up"),
    Ordering("TPUFLOW_PROGRESS_EVERY_S", "TPUFLOW_HANG_FLOOR_S",
             "beats throttled slower than the hang floor look like hangs "
             "to the watchdog even while the step is advancing"),
    Ordering("TPUFLOW_HANG_POLL_S", "TPUFLOW_HANG_FLOOR_S",
             "a poll interval above the floor cannot observe the floor"),
    Ordering("TPUFLOW_HANG_DUMP_WAIT_S", "TPUFLOW_HANG_KILL_GRACE_S",
             "the stack-dump wait must fit inside the kill grace or dumps "
             "are truncated by SIGKILL"),
    Ordering("TPUFLOW_STORAGE_TIMEOUT_S", "TPUFLOW_GANG_NODE_WAIT_TIMEOUT_S",
             "a storage attempt longer than the gang-node wait makes peers "
             "give up on a node that is still (legitimately) downloading",
             skip_if_zero=True),
    Ordering("TPUFLOW_RETRY_BACKOFF_BASE_S", "TPUFLOW_RETRY_BACKOFF_CAP_S",
             "a backoff base above the cap inverts the backoff curve"),
    Ordering("TPUFLOW_ELASTIC_GROW_EVERY_S", "TPUFLOW_RETRY_BACKOFF_CAP_S",
             "parked gangs must recheck capacity at least as often as "
             "failed ones retry, or parking is strictly worse than failing"),
    Ordering("TPUFLOW_FLEET_HEALTH_INTERVAL_S", "TPUFLOW_FLEET_SPAWN_TIMEOUT_S",
             "health probes slower than the spawn deadline can declare a "
             "replica dead before ever probing it"),
    Ordering("TPUFLOW_FLEET_WAIT_S", "TPUFLOW_FLEET_SPAWN_TIMEOUT_S",
             "requests must not shed while a replacement replica is still "
             "legitimately spawning"),
    Ordering("TPUFLOW_SANITIZE_TIMEOUT", "TPUFLOW_GANG_FINALIZE_TIMEOUT",
             "a sanitizer barrier longer than the finalize deadline turns "
             "every desync probe into a finalize failure"),
)


# ---------------------------------------------------------------------------
# typed accessors
# ---------------------------------------------------------------------------

def _nearest(name):
    best, best_d = None, 3
    for cand in KNOBS:
        d = _edit_distance(name, cand, best_d)
        if d < best_d:
            best, best_d = cand, d
    return best


def _edit_distance(a, b, cap=3):
    """Levenshtein distance, capped for cheap nearest-name lookup."""
    if abs(len(a) - len(b)) >= cap:
        return cap
    prev = list(range(len(b) + 1))
    for i, ca in enumerate(a):
        cur = [i + 1]
        for j, cb in enumerate(b):
            cur.append(min(prev[j + 1] + 1, cur[j] + 1,
                           prev[j] + (ca != cb)))
        if min(cur) >= cap:
            return cap
        prev = cur
    return min(prev[-1], cap)


def _knob(name):
    try:
        return KNOBS[name]
    except KeyError:
        raise UnknownKnobError(name, _nearest(name))


def _raw(name, env):
    """The raw string value, or None when unset/empty."""
    value = (env if env is not None else os.environ).get(name)
    if value is None or value == "":
        return None
    return value


def is_set(name, env=None):
    """True when the knob has a non-empty value in the environment."""
    _knob(name)
    return _raw(name, env) is not None


def get_raw(name, env=None):
    """The raw string value ('' and unset both -> None). Prefer the
    typed accessors; this exists for pass-through/forwarding sites."""
    _knob(name)
    return _raw(name, env)


def get_str(name, env=None, fallback=_UNSET):
    knob = _knob(name)
    value = _raw(name, env)
    if value is not None:
        return value
    return knob.default if fallback is _UNSET else fallback


def get_bool(name, env=None, fallback=_UNSET):
    knob = _knob(name)
    value = _raw(name, env)
    if value is not None:
        return value.strip().lower() not in _FALSEY
    return knob.default if fallback is _UNSET else fallback


def get_int(name, env=None, fallback=_UNSET):
    knob = _knob(name)
    default = knob.default if fallback is _UNSET else fallback
    value = _raw(name, env)
    if value is None:
        return default
    try:
        return int(float(value))
    except (TypeError, ValueError):
        return default


def get_float(name, env=None, fallback=_UNSET):
    knob = _knob(name)
    default = knob.default if fallback is _UNSET else fallback
    value = _raw(name, env)
    if value is None:
        return default
    try:
        return float(value)
    except (TypeError, ValueError):
        return default


_GETTERS = {"str": get_str, "path": get_str, "bool": get_bool,
            "int": get_int, "float": get_float}


def get(name, env=None):
    """Type-dispatched read (registry decides the parse)."""
    return _GETTERS[_knob(name).ktype](name, env=env)


def items_with_prefix(prefix, env=None):
    """All set env entries under a registered dynamic prefix."""
    if prefix not in PREFIXES:
        raise UnknownKnobError(prefix)
    env = env if env is not None else os.environ
    return {k: v for k, v in env.items() if k.startswith(prefix)}


# ---------------------------------------------------------------------------
# lattice evaluation (lint time: defaults only; config-load time: live env)
# ---------------------------------------------------------------------------

class OrderingViolation(object):
    __slots__ = ("lo", "hi", "lo_value", "hi_value", "reason")

    def __init__(self, lo, hi, lo_value, hi_value, reason):
        self.lo = lo
        self.hi = hi
        self.lo_value = lo_value
        self.hi_value = hi_value
        self.reason = reason

    def render(self):
        return ("%s=%g must stay <= %s=%g: %s"
                % (self.lo, self.lo_value, self.hi, self.hi_value,
                   self.reason))


def _effective(name, env):
    value = get_float(name, env=env)
    if value is None and name in INHERITS:
        value = get_float(INHERITS[name], env=env)
    return value


def validate_env(env=None):
    """Evaluate the ordering lattice against ``env`` (default: the live
    process environment, overlaid on registry defaults). Returns the
    list of violations; empty means the deadline order holds."""
    violations = []
    for edge in ORDERING:
        lo_value = _effective(edge.lo, env)
        hi_value = _effective(edge.hi, env)
        if lo_value is None or hi_value is None:
            continue
        if edge.skip_if_zero and (lo_value <= 0 or hi_value <= 0):
            continue
        if lo_value > hi_value:
            violations.append(OrderingViolation(
                edge.lo, edge.hi, lo_value, hi_value, edge.reason))
    return violations


def validate_defaults():
    """The lattice evaluated over registry defaults alone — must always
    return [] (pinned by tests); a default drift that breaks the
    partial order is a registry bug."""
    return validate_env(env={})


# ---------------------------------------------------------------------------
# rendering (CLI + generated docs)
# ---------------------------------------------------------------------------

def by_subsystem():
    groups = {}
    for knob in KNOBS.values():
        groups.setdefault(knob.subsystem, []).append(knob)
    for knobs_ in groups.values():
        knobs_.sort(key=lambda k: k.name)
    return [(sub, groups[sub]) for sub in SUBSYSTEM_ORDER if sub in groups]


def to_json():
    return {
        "v": 1,
        "knobs": [KNOBS[name].to_dict() for name in sorted(KNOBS)],
        "prefixes": dict(PREFIXES),
        "ordering": [
            {"lo": e.lo, "hi": e.hi, "reason": e.reason,
             "skip_if_zero": e.skip_if_zero}
            for e in ORDERING
        ],
        "inherits": dict(INHERITS),
    }


def _default_str(knob):
    if knob.default is None:
        if knob.name in INHERITS:
            return "inherits " + INHERITS[knob.name]
        return "unset"
    if knob.ktype == "bool":
        return "on" if knob.default else "off"
    if isinstance(knob.default, float) and knob.default == int(knob.default):
        return str(int(knob.default))
    return str(knob.default)


def render_markdown():
    """The full registry as markdown — the exact content of
    docs/knobs.md (regenerated byte-identically, enforced by test)."""
    lines = [
        "# TPUFLOW_* knob registry",
        "",
        "Generated by `python -m metaflow_tpu knobs --markdown` from",
        "`metaflow_tpu/knobs.py` — do not edit by hand; regenerate and",
        "commit. `tests/test_contracts.py` fails when this file drifts",
        "from the registry.",
        "",
    ]
    for sub, knobs_ in by_subsystem():
        lines.append("## %s" % sub)
        lines.append("")
        lines.append("| knob | type | default | unit | description |")
        lines.append("|---|---|---|---|---|")
        for knob in knobs_:
            lines.append("| `%s` | %s | `%s` | %s | %s |" % (
                knob.name, knob.ktype, _default_str(knob),
                knob.unit or "—", knob.doc))
        lines.append("")
    lines.append("## dynamic prefixes")
    lines.append("")
    lines.append("| prefix | description |")
    lines.append("|---|---|")
    for prefix in sorted(PREFIXES):
        lines.append("| `%s*` | %s |" % (prefix, PREFIXES[prefix]))
    lines.append("")
    lines.append("## deadline ordering")
    lines.append("")
    lines.append("Each row pins `lo <= hi`; `check --deep` verifies the")
    lines.append("registry defaults and the pre-run gate verifies the live")
    lines.append("environment (warn by default, fatal under")
    lines.append("`TPUFLOW_STRICT_CHECK=1`).")
    lines.append("")
    lines.append("| lo | hi | why |")
    lines.append("|---|---|---|")
    for edge in ORDERING:
        suffix = " *(skipped when either side is 0)*" if edge.skip_if_zero \
            else ""
        lines.append("| `%s` | `%s` | %s%s |" % (
            edge.lo, edge.hi, edge.reason, suffix))
    lines.append("")
    return "\n".join(lines)


def render_json():
    return json.dumps(to_json(), indent=2, sort_keys=True) + "\n"
