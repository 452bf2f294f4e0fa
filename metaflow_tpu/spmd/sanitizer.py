"""Runtime collective sanitizer: turn "the gang hung" into a one-line
diagnosis.

The static pass (analysis/divergence.py) proves what it can before
launch; this module catches what it cannot — data-dependent rank
divergence, library code outside the AST's reach, dynamic keys. Under
``TPUFLOW_SANITIZE=1`` every rank journals a rolling signature stream of
its gang-relevant operations:

    collective ops    kind + name + mesh/logical axis names + shape hash
                      (spmd/sharding.py shard_tree/constrain,
                      training/train_step.py shard_batch)
    train steps       one entry per invocation of the jitted step
                      (make_trainer wraps the step when sanitizing)
    shared writes     checkpoint/datastore write keys
                      (training/checkpoint.py save)
    data stream       per-batch geometry of the lockstep input stream
                      (data/loader.py)

At a step barrier (every TPUFLOW_SANITIZE_EVERY wrapped steps, or an
explicit ``barrier()``), each rank publishes its window to the run
datastore under ``_telemetry/sanitize/`` and the checker rank compares
the streams: the first sequence number where ranks disagree — a psum one
rank skipped, a compile one rank alone re-traced, a checkpoint key that
differs — is named per rank in a desync report, written next to the
journals and pinned in tests/schema_validate.py::SANITIZE_REPORT_SCHEMA.
If a rank never publishes within the barrier timeout (it is blocked in
the collective the others never entered), the report names it as missing
instead of letting the gang spin silently for hours — the collective
flight-recorder pattern PyTorch/NCCL stacks ship for this failure class.

The journal entries are plain strings, hashing is host-side, and no jax
import happens here: a disabled sanitizer costs one attribute load per
hook.

Env vars:
    TPUFLOW_SANITIZE=1            enable journaling + barrier checks
    TPUFLOW_SANITIZE_EVERY        wrapped-step barrier cadence (64)
    TPUFLOW_SANITIZE_WINDOW       rolling journal entries kept (512)
    TPUFLOW_SANITIZE_TIMEOUT     barrier wait for peer streams, s (30)
"""

import hashlib
import json
import os
import threading
import time
from collections import deque

from .. import knobs, telemetry
from ..exception import TpuFlowException

REPORT_VERSION = 1
SANITIZE_PREFIX = "_telemetry/sanitize"


def enabled():
    return knobs.get_bool("TPUFLOW_SANITIZE")


def _env_int(name, default):
    try:
        return int(os.environ.get(name, str(default)))
    except ValueError:
        return default


class GangDesyncError(TpuFlowException):
    headline = "Gang ranks diverged on their collective streams"

    def __init__(self, report):
        self.report = report
        super().__init__(msg=render_report(report))


def render_report(report):
    """One-line-per-fact human rendering of a desync report."""
    lines = ["sanitizer barrier %s at %r: %s"
             % (report.get("barrier"), report.get("step"),
                report.get("status"))]
    if report.get("missing_ranks"):
        lines.append(
            "  rank(s) %s never published within the timeout — blocked "
            "in an op the other ranks never reached"
            % report["missing_ranks"])
    div = report.get("first_divergence")
    if div:
        lines.append("  first diverging op at seq %d:" % div["seq"])
        for rank, sig in sorted(div["ops"].items(), key=lambda kv: int(kv[0])):
            lines.append("    rank %s: %s" % (rank, sig or "<absent>"))
    if report.get("diverged_ranks"):
        lines.append("  diverging rank(s): %s" % report["diverged_ranks"])
    return "\n".join(lines)


def _shape_token(obj, depth=0):
    """Deterministic structural token for a value: array leaves become
    'dtype:shape', containers recurse (sorted dict keys), scalars repr.
    Works on numpy arrays, jax arrays AND tracers (both expose
    .shape/.dtype) without importing either."""
    if depth > 16:
        return "..."
    shape = getattr(obj, "shape", None)
    if shape is not None and not isinstance(obj, (str, bytes)):
        return "%s:%s" % (getattr(obj, "dtype", "?"),
                          ",".join(str(d) for d in shape))
    if isinstance(obj, dict):
        return "{%s}" % ";".join(
            "%s=%s" % (k, _shape_token(v, depth + 1))
            for k, v in sorted(obj.items(), key=lambda kv: str(kv[0])))
    if isinstance(obj, (list, tuple)):
        return "[%s]" % ";".join(_shape_token(v, depth + 1) for v in obj)
    if isinstance(obj, (int, float, bool, str)) or obj is None:
        return repr(obj)
    return type(obj).__name__


def shape_hash(obj):
    """Short stable hash of a pytree's structure+shapes+dtypes."""
    return hashlib.sha1(
        _shape_token(obj).encode("utf-8")).hexdigest()[:12]


def make_signature(kind, name, axes=(), shape=None, key=None):
    parts = [kind, name]
    if axes:
        parts.append(",".join(str(a) for a in axes))
    if shape is not None:
        parts.append(shape_hash(shape))
    if key is not None:
        parts.append(str(key))
    return "|".join(parts)


# Pinned signature vocabulary. Every first-party journal site uses one of
# these kinds, and every "collective" signature one of these names — the
# stream schema in tests/schema_validate.py pins the same sets, so a new
# collective is a deliberate two-file change, not drift. The zero.* names
# are the ZeRO sharded-update schedule (spmd/sharding.py): the grad
# reduce-scatter into the 1/N update and the param all-gather out of it,
# journaled once per trace like `constrain`.
SIG_KINDS = ("collective", "step", "compile", "write", "data")

COLLECTIVE_NAMES = (
    "shard_tree",
    "constrain",
    "shard_batch",
    "zero.reduce_scatter",
    "zero.shard",
    "zero.all_gather",
    # MPMD stage handoffs (spmd/mpmd.py StageTransport): journaled per
    # transfer with the (ring, microbatch, chunk) identity as the key,
    # so a stage desync report names the first diverging transfer
    "mpmd.send",
    "mpmd.recv",
)


def journal_collective(name, axes=(), shape=None, key=None):
    """Journal a collective signature, enforcing the pinned name registry.

    Gang-desync detection only works if every rank journals the same
    vocabulary — a typo'd or ad-hoc collective name would read as a
    divergence on some ranks and silence on others. First-party collective
    sites go through here; third parties can still call journal() raw."""
    if name not in COLLECTIVE_NAMES:
        raise ValueError(
            "unknown collective %r: pinned names are %s (add new collectives "
            "to sanitizer.COLLECTIVE_NAMES AND the stream schema in "
            "tests/schema_validate.py)" % (name, list(COLLECTIVE_NAMES)))
    journal("collective", name, axes=axes, shape=shape, key=key)


class GangSanitizer(object):
    """Per-rank signature journal + cross-rank barrier checker.

    flow_datastore: a datastore.FlowDataStore — journals and reports land
    under ``<flow>/<run>/_telemetry/sanitize/``. rank/world default to
    the gang env (MF_PARALLEL_NODE_INDEX / MF_PARALLEL_NUM_NODES); the
    checker rank (default 0) compares the streams at each barrier and
    raises GangDesyncError on divergence or timeout.
    """

    def __init__(self, flow_datastore, run_id, step_name="train",
                 rank=None, world=None, window=None, barrier_every=None,
                 timeout_s=None, checker=0, poll_s=0.05):
        self._fds = flow_datastore
        self.run_id = str(run_id)
        self.step_name = step_name
        # rank/world resolve LAZILY from the gang env when not pinned:
        # the task installs the sanitizer before the @parallel decorator
        # exports MF_PARALLEL_* (rank 0's control task sets them mid-step)
        self._rank = None if rank is None else int(rank)
        self._world = None if world is None else int(world)
        self.checker = int(checker)
        window = window or knobs.get_int("TPUFLOW_SANITIZE_WINDOW")
        self.barrier_every = (barrier_every
                              or knobs.get_int("TPUFLOW_SANITIZE_EVERY"))
        self.timeout_s = (knobs.get_float("TPUFLOW_SANITIZE_TIMEOUT")
                          if timeout_s is None else float(timeout_s))
        self.poll_s = poll_s
        self._lock = threading.Lock()
        self._seq = 0
        self._sigs = deque(maxlen=max(16, window))
        self._steps_seen = 0
        self._barriers = 0

    @property
    def rank(self):
        if self._rank is not None:
            return self._rank
        return _env_int("MF_PARALLEL_NODE_INDEX", 0)

    @property
    def world(self):
        if self._world is not None:
            return self._world
        return _env_int("MF_PARALLEL_NUM_NODES", 1)

    # ---------- journaling (the hot path) ----------

    def journal(self, kind, name, axes=(), shape=None, key=None):
        """Append one signature to the rolling journal; returns its global
        sequence number. Pure host-side string work — no device sync."""
        sig = make_signature(kind, name, axes=axes, shape=shape, key=key)
        with self._lock:
            seq = self._seq
            self._seq += 1
            self._sigs.append((seq, sig))
        return seq

    def wrap_step(self, step_fn, name="train_step"):
        """Wrap a (jitted) train step: one journal entry per invocation
        (name + INPUT shapes — arg 0 is the rank-local state tree, whose
        shapes are already pinned by the make_trainer compile signature;
        hashing it every step would cost ~the whole overhead budget) and
        a cross-rank barrier every ``barrier_every`` calls."""
        sanitizer = self

        def wrapped(*args, **kwargs):
            # arg 0 is always the state tree — never hash it, whatever
            # the calling convention; a keyword batch still counts
            sanitizer.journal("step", name,
                              shape=args[1:] + tuple(
                                  v for _k, v in sorted(kwargs.items())))
            out = step_fn(*args, **kwargs)
            sanitizer.on_step()
            return out

        wrapped.sanitizer = sanitizer
        wrapped.__name__ = getattr(step_fn, "__name__", name)
        return wrapped

    def on_step(self, step_num=None):
        """Advance the step counter; runs a barrier at the cadence."""
        with self._lock:
            self._steps_seen += 1
            due = (self.barrier_every
                   and self._steps_seen % self.barrier_every == 0)
        if due:
            self.barrier()

    # ---------- publication + cross-rank check ----------

    def _path(self, fname):
        storage = self._fds.storage
        return storage.path_join(
            self._fds.flow_name, self.run_id, SANITIZE_PREFIX, fname)

    def _stream_path(self, barrier_id, rank):
        return self._path("%s.b%06d.r%d.json"
                          % (self.step_name, barrier_id, rank))

    def _report_path(self, barrier_id):
        return self._path("desync.%s.b%06d.json"
                          % (self.step_name, barrier_id))

    def publish(self, barrier_id):
        """Persist this rank's journal window for one barrier."""
        with self._lock:
            sigs = list(self._sigs)
            count = self._seq
        payload = {
            "v": REPORT_VERSION,
            "rank": self.rank,
            "world": self.world,
            "barrier": int(barrier_id),
            "count": count,
            "window_start": sigs[0][0] if sigs else count,
            "sigs": [s for _seq, s in sigs],
            "ts": time.time(),
        }
        self._fds.storage.save_bytes(
            [(self._stream_path(barrier_id, self.rank),
              json.dumps(payload, sort_keys=True).encode("utf-8"))],
            overwrite=True)
        return payload

    def barrier(self, barrier_id=None, timeout_s=None):
        """Publish this rank's stream; on the checker rank, wait for the
        peers and compare. Raises GangDesyncError when the streams
        diverge or a rank never reports. Returns the report (checker)
        or None (other ranks)."""
        with self._lock:
            if barrier_id is None:
                barrier_id = self._barriers
            self._barriers = barrier_id + 1
        self.publish(barrier_id)
        if self.rank != self.checker or self.world <= 1:
            return None
        report = self.check(barrier_id, timeout_s=timeout_s)
        if report["status"] != "ok":
            raise GangDesyncError(report)
        return report

    def _load_stream(self, barrier_id, rank):
        storage = self._fds.storage
        try:
            with storage.load_bytes(
                    [self._stream_path(barrier_id, rank)]) as loaded:
                for _path, local, _meta in loaded:
                    if local is None:
                        return None
                    with open(local, "rb") as f:
                        return json.loads(f.read().decode("utf-8"))
        except Exception:
            return None
        return None

    def check(self, barrier_id, timeout_s=None):
        """Compare every rank's published stream for one barrier; write a
        desync report when they diverge or a rank is missing. Callable
        from any process that can reach the run datastore (the checker
        rank, a doctor CLI, a test)."""
        timeout_s = self.timeout_s if timeout_s is None else timeout_s
        deadline = time.monotonic() + timeout_s
        streams = {}
        while True:
            for rank in range(self.world):
                if rank not in streams:
                    payload = self._load_stream(barrier_id, rank)
                    if payload is not None:
                        streams[rank] = payload
            if len(streams) == self.world:
                break
            if time.monotonic() >= deadline:
                break
            time.sleep(self.poll_s)
        missing = sorted(set(range(self.world)) - set(streams))
        report = {
            "v": REPORT_VERSION,
            "run_id": self.run_id,
            "step": self.step_name,
            "barrier": int(barrier_id),
            "world": self.world,
            "status": "ok",
            "ranks_reported": sorted(streams),
            "missing_ranks": missing,
            "counts": {str(r): s["count"] for r, s in streams.items()},
            "first_divergence": None,
            "diverged_ranks": [],
            "ts": time.time(),
        }
        if missing:
            report["status"] = "timeout"
            report["diverged_ranks"] = missing
        else:
            div = _first_divergence(streams)
            if div is not None:
                report["status"] = "desync"
                report["first_divergence"] = div
                report["diverged_ranks"] = _diverged_ranks(div["ops"])
        if report["status"] != "ok":
            self._fds.storage.save_bytes(
                [(self._report_path(barrier_id),
                  json.dumps(report, sort_keys=True).encode("utf-8"))],
                overwrite=True)
            telemetry.event("sanitize.desync", data={
                "barrier": int(barrier_id),
                "status": report["status"],
                "diverged_ranks": report["diverged_ranks"],
                "seq": (report["first_divergence"] or {}).get("seq"),
            })
        else:
            telemetry.event("sanitize.barrier", data={
                "barrier": int(barrier_id),
                "count": max((s["count"] for s in streams.values()),
                             default=0),
            })
        return report


def _first_divergence(streams):
    """First sequence number where the ranks' signature streams disagree,
    as {"seq": n, "ops": {rank_str: sig_or_None}} — None when the streams
    agree over their comparable (unevicted) range."""
    def sig_at(payload, seq):
        idx = seq - payload["window_start"]
        if idx < 0:
            return "<evicted>"
        if idx >= len(payload["sigs"]):
            return None  # this rank never executed op `seq`
        return payload["sigs"][idx]

    lo = min(s["window_start"] for s in streams.values())
    hi = max(s["count"] for s in streams.values())
    for seq in range(lo, hi):
        ops = {str(r): sig_at(s, seq) for r, s in streams.items()}
        real = set(ops.values()) - {"<evicted>"}
        if len(real) > 1:
            return {"seq": seq, "ops": ops}
    return None


def _diverged_ranks(ops):
    """Ranks in the minority (or absent) at the first diverging seq."""
    votes = {}
    for rank, sig in ops.items():
        votes.setdefault(sig, []).append(int(rank))
    majority = max(votes.values(), key=len)
    return sorted(r for sig, ranks in votes.items()
                  for r in ranks if ranks is not majority)


# ---------------------------------------------------------------------------
# module-level current sanitizer: library hooks stay one attribute load
# when sanitizing is off (the overwhelmingly common case)
# ---------------------------------------------------------------------------

_active = None


def install(flow_datastore, run_id, **kwargs):
    """Install the process-wide sanitizer for this task attempt; no-op
    (returns None, clears any prior one) unless TPUFLOW_SANITIZE=1."""
    global _active
    if not enabled():
        _active = None
        return None
    _active = GangSanitizer(flow_datastore, run_id, **kwargs)
    return _active


def set_active(sanitizer):
    global _active
    _active = sanitizer
    return sanitizer


def current():
    return _active


def uninstall():
    global _active
    _active = None


def journal(kind, name, axes=(), shape=None, key=None):
    a = _active
    if a is not None:
        a.journal(kind, name, axes=axes, shape=shape, key=key)


def wrap_step(step_fn, name="train_step"):
    """Wrap a train step through the active sanitizer; identity when
    sanitizing is off."""
    a = _active
    if a is None:
        return step_fn
    return a.wrap_step(step_fn, name=name)
