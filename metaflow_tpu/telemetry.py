"""Run flight recorder: datastore-backed telemetry records.

Reference behavior: metaflow's event_logger + monitor sidecars make every
run inspectable after the fact (task.py:793-807 wraps task execution in
timers/counters). The local-JSONL port in system.py scatters records
across each worker's disk; this module is the run-scoped upgrade: every
record carries full identity (run/step/task/attempt/rank/host/pid/trace)
and is buffered per task, then persisted to the run's datastore under a
`_telemetry/` prefix — so gang-worker metrics from N hosts aggregate per
run instead of dying with the machines that produced them.

Record schema (pinned in tests/schema_validate.py):

    {"v": 1, "type": "timer|counter|gauge|event", "name": str,
     "ts": float, "run_id": str, "step": str, "task_id": str,
     "attempt": int, "rank": int, "host": str, "pid": int,
     # optional, by type:
     "ms": float, "ok": bool,        # timer
     "inc": number,                  # counter
     "value": number,                # gauge
     "step_num": int,                # training-step records
     "trace": str,                   # W3C trace id (TRACEPARENT)
     "data": {...}}                  # free-form extras

Profiler spans: `annotate(name, **attrs)` opens a
`jax.profiler.TraceAnnotation`, and every `timer` opens the same one
around what it times, so each timed block is also a span on the
profiler's clock under the name its record has. There is no switch: a
span is recorded while a profiler session is open (ProfileTrigger below,
or anyone's `jax.profiler.start_trace`) and costs one flag check when
none is. A process that has not imported JAX is never made to.
`PhaseLedger` is the same timed block with its seconds also summed by
name on the host's clock, always on (the serving loop's phases).

Crash safety: records flush in numbered part files
(`_telemetry/<step>.<task>.<attempt>.<part>.jsonl`) — a task that dies
mid-run loses at most the unflushed tail, never already-persisted parts.

Env vars:
    TPUFLOW_TELEMETRY=0            disable the recorder entirely
    TPUFLOW_TELEMETRY_FLUSH_EVERY  buffer size before an auto-flush (512)
    TPUFLOW_PROFILE_STEPS=A:B      capture a jax.profiler trace for train
                                   steps [A, B) and upload it
    TPUFLOW_PROFILE_REQUEST=path   touch this file (content: step count)
                                   to trigger a capture on a live run
    TPUFLOW_PROFILE_SIGNAL=1       SIGUSR2 triggers a capture too
"""

import io
import json
import os
import socket
import sys
import threading
import time
import zipfile

from . import knobs

RECORD_VERSION = 1
TELEMETRY_PREFIX = "_telemetry"
PROFILE_PREFIX = "_telemetry/profiles"
HANGS_PREFIX = "_telemetry/hangs"

_current = None

_trace_annotation = None


class _NoSpan(object):
    """What annotate() gives a process without JAX."""

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def set_metadata(self, **attrs):
        pass


_NO_SPAN = _NoSpan()


def annotate(name, **attrs):
    """A span on the profiler's clock: `jax.profiler.TraceAnnotation`
    with `attrs` as the span's stats (request id, slot, tokens,
    iteration), and nothing else. Writes no telemetry record. Stats known
    only inside the block go on with `span.set_metadata(**attrs)`. Where
    JAX has not been imported the span does nothing: a process without
    JAX has no profiler session to record into."""
    global _trace_annotation
    cls = _trace_annotation
    if cls is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        cls = getattr(profiler, "TraceAnnotation", None)
        if cls is None:
            return _NO_SPAN
        _trace_annotation = cls
    return cls(name, **attrs)


def _span_attrs(step_num, data):
    """A timer's step number and the primitive values of its data, as a
    span's stats; a list of primitives (one value a row of a prefill
    program) goes on joined by "|" (a comma would end the stat: the
    profiler packs a span's stats as `name#k=v,k=v#`)."""
    attrs = {} if step_num is None else {"step_num": int(step_num)}
    if not data:
        return attrs
    for k, v in data.items():
        if isinstance(v, (str, int, float, bool)):
            attrs[k] = v
        elif isinstance(v, (list, tuple)) and all(
                isinstance(x, (str, int, float, bool)) for x in v):
            attrs[k] = "|".join(str(x) for x in v)
    return attrs


class _Timer(object):
    """One timed block: a span on the profiler's clock and, where a
    recorder is given, its timer record under the same name. The record
    lands even when the block raises (ok: false) and the exception
    propagates. GeneratorExit is NOT a failure: it is how a consumer
    closes a generator-shaped span early (e.g. a single-artifact load).
    `seconds` holds the block's time once it has ended, and a `ledger`
    (PhaseLedger) is given those seconds under the block's name."""

    __slots__ = ("recorder", "name", "step_num", "data", "seconds", "ledger",
                 "_t0", "_span")

    def __init__(self, recorder, name, step_num=None, data=None,
                 ledger=None):
        self.recorder, self.name = recorder, name
        self.step_num, self.data = step_num, data
        self.seconds, self.ledger = None, ledger

    def start(self):
        """For a block that no `with` can hold (a generator's time
        between two yields): start() ... stop()."""
        return self.__enter__()

    def stop(self):
        self.__exit__(None, None, None)

    def __enter__(self):
        self._span = annotate(self.name,
                              **_span_attrs(self.step_num, self.data))
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def set(self, **attrs):
        """Stats known only inside the block: onto the span and into the
        record's data."""
        self._span.set_metadata(**_span_attrs(None, attrs))
        self.data = dict(self.data or (), **attrs)

    def __exit__(self, exc_type, exc, tb):
        self.seconds = time.perf_counter() - self._t0
        self._span.__exit__(exc_type, exc, tb)
        if self.ledger is not None:
            self.ledger.add(self.name, self.seconds)
        if self.recorder is not None:
            self.recorder.emit(
                "timer", self.name, ms=self.seconds * 1000,
                ok=exc_type is None or issubclass(exc_type, GeneratorExit),
                step_num=self.step_num, data=self.data)
        return False


class PhaseLedger(object):
    """Seconds and calls by name, on the host's clock, always on: what a
    loop's phases took since it was made. `ledger(name, **stats)` is a
    timed block (_Timer) that is the span `name` on the profiler's clock,
    exactly as `annotate(name, **stats)` is, and adds its seconds and one
    call here when it ends; with `record=True` it is also the timer
    record that `timer(name, data=stats)` writes. One thread's loop owns
    a ledger; another thread may add under names of its own (a
    collector's callback), and readers take `dict(ledger.seconds)`."""

    __slots__ = ("seconds", "calls")

    def __init__(self):
        self.seconds, self.calls = {}, {}

    def __call__(self, name, record=False, **stats):
        return _Timer(_current if record else None, name,
                      data=stats or None, ledger=self)

    def add(self, name, seconds):
        self.seconds[name] = self.seconds.get(name, 0.0) + seconds
        self.calls[name] = self.calls.get(name, 0) + 1


def _rank_from_env():
    try:
        return int(os.environ.get("MF_PARALLEL_NODE_INDEX", "0"))
    except ValueError:
        return 0


def trace_id_from_env(env=None):
    """The 32-hex trace id of the ambient W3C TRACEPARENT, or ''."""
    tp = (env or os.environ).get("TRACEPARENT", "")
    parts = tp.split("-")
    if len(parts) >= 2 and len(parts[1]) == 32:
        return parts[1]
    return ""


class FlightRecorder(object):
    """Buffered, identity-stamped telemetry sink for ONE task attempt
    (or one scheduler process), persisting to the run's datastore."""

    def __init__(self, flow_datastore, run_id, step_name, task_id,
                 attempt=0, rank=None, flush_every=None):
        self._fds = flow_datastore
        self.run_id = str(run_id)
        self.step_name = step_name
        self.task_id = str(task_id)
        self.attempt = int(attempt)
        self.rank = _rank_from_env() if rank is None else int(rank)
        self.host = socket.gethostname()
        self.pid = os.getpid()
        self.trace = trace_id_from_env()
        if flush_every is None:
            flush_every = knobs.get_int("TPUFLOW_TELEMETRY_FLUSH_EVERY")
        self._flush_every = max(1, flush_every)
        # records arrive from more than one thread (the training loop and
        # the async-checkpoint upload thread both emit through the
        # module-global recorder): buffer + part counter are lock-guarded
        self._lock = threading.Lock()
        self._buf = []
        self._part = 0
        # a broken storage backend must not turn every emit into a
        # blocking failed upload (nor grow the buffer without bound)
        self._flush_fail_until = 0.0
        self._max_buffered = max(self._flush_every * 8, 4096)
        # flush-failure visibility: failed attempts / shed records are
        # counted here and reported as telemetry.flush_failed +
        # telemetry.dropped_records on the first flush that lands again
        self._flush_failures = 0
        self._fail_buffered = 0
        self._dropped = 0
        self._dropped_reported = 0

    # ---------- emit ----------

    def emit(self, rtype, name, ms=None, ok=None, inc=None, value=None,
             step_num=None, data=None):
        rec = {
            "v": RECORD_VERSION,
            "type": rtype,
            "name": name,
            "ts": time.time(),
            "run_id": self.run_id,
            "step": self.step_name,
            "task_id": self.task_id,
            "attempt": self.attempt,
            "rank": self.rank,
            "host": self.host,
            "pid": self.pid,
        }
        if ms is not None:
            rec["ms"] = round(float(ms), 3)
        if ok is not None:
            rec["ok"] = bool(ok)
        if inc is not None:
            rec["inc"] = inc
        if value is not None:
            rec["value"] = value
        if step_num is not None:
            rec["step_num"] = int(step_num)
        if self.trace:
            rec["trace"] = self.trace
        if data:
            rec["data"] = data
        with self._lock:
            self._buf.append(rec)
            if len(self._buf) > self._max_buffered:
                # storage has been down long enough to hit the cap: shed
                # the oldest half rather than grow without bound
                shed = len(self._buf) // 2
                del self._buf[:shed]
                self._dropped += shed
            want_flush = len(self._buf) >= self._flush_every
        if want_flush:
            self.flush()
        return rec

    def timer(self, name, step_num=None, data=None):
        """Time a block: a timer record and a profiler span (_Timer)."""
        return _Timer(self, name, step_num=step_num, data=data)

    def counter(self, name, inc=1, data=None):
        self.emit("counter", name, inc=inc, data=data)

    def gauge(self, name, value, step_num=None, data=None):
        self.emit("gauge", name, value=value, step_num=step_num, data=data)

    def event(self, name, data=None):
        self.emit("event", name, data=data)

    # ---------- persistence ----------

    def _part_path(self, part):
        fname = "%s.%s.%d.%06d.jsonl" % (
            self.step_name, self.task_id, self.attempt, part)
        return self._fds.storage.path_join(
            self._fds.flow_name, self.run_id, TELEMETRY_PREFIX, fname)

    def flush(self, force=False):
        """Persist the buffered records as the next part file. Telemetry
        must never fail the work it observes: storage errors are
        swallowed, the buffer is retained, and further emit-triggered
        flushes back off for a cooldown so a dead backend cannot turn
        every record into a blocking failed upload (force=True — the
        finalization path — always tries)."""
        with self._lock:
            if not self._buf:
                return 0
            if not force and time.monotonic() < self._flush_fail_until:
                return 0
            records, self._buf = self._buf, []
            part = self._part
            self._part += 1
        payload = "\n".join(
            json.dumps(r, sort_keys=True) for r in records
        ).encode("utf-8") + b"\n"
        try:
            self._fds.storage.save_bytes(
                [(self._part_path(part), payload)], overwrite=True)
        except Exception:
            with self._lock:
                # put the records back (front) for the next attempt; the
                # part number is NOT reused — a later retry writing a
                # lower part number than an already-landed one is fine
                # (readers take every part), a clobber is not
                self._buf[:0] = records
                self._flush_fail_until = time.monotonic() + 30.0
                self._flush_failures += 1
                self._fail_buffered = len(self._buf)
            return 0
        with self._lock:
            failures, self._flush_failures = self._flush_failures, 0
            buffered, self._fail_buffered = self._fail_buffered, 0
            dropped_new = self._dropped - self._dropped_reported
            self._dropped_reported = self._dropped
        if failures:
            # first flush to land after an outage: make the outage (and
            # anything shed during it) visible in the record stream
            self.counter("telemetry.flush_failed", inc=failures,
                         data={"buffered": buffered})
        if dropped_new:
            self.gauge("telemetry.dropped_records", self._dropped,
                       data={"dropped_since_last_flush": dropped_new})
        if failures or dropped_new:
            # persist the visibility records now — the recursion is
            # bounded: the counters were just zeroed, so the inner call
            # cannot emit again (and a close() must not strand them)
            self.flush(force=force)
        return len(records)

    def close(self):
        return self.flush(force=True)

    # ---------- artifacts (profiler traces, ...) ----------

    def save_artifact(self, name, payload, prefix=PROFILE_PREFIX):
        """Persist an opaque artifact under the run's telemetry tree
        (profiles by default; hang forensics pass HANGS_PREFIX); returns
        the datastore-relative path (or None on error)."""
        path = self._fds.storage.path_join(
            self._fds.flow_name, self.run_id, prefix, name)
        try:
            self._fds.storage.save_bytes([(path, payload)], overwrite=True)
        except Exception:
            return None
        return path


# ---------------------------------------------------------------------------
# module-level current recorder: hot paths emit through these helpers and
# stay no-ops outside a run context (bench standalone, library use)
# ---------------------------------------------------------------------------


def enabled():
    return knobs.get_bool("TPUFLOW_TELEMETRY")


def init_recorder(flow_datastore, run_id, step_name, task_id, attempt=0,
                  rank=None):
    """Install the process-wide recorder for this task attempt. Returns
    None (and clears any inherited recorder) when telemetry is off."""
    global _current
    if not enabled():
        _current = None
        return None
    _current = FlightRecorder(flow_datastore, run_id, step_name, task_id,
                              attempt=attempt, rank=rank)
    return _current


def set_recorder(recorder):
    global _current
    _current = recorder
    return recorder


def current_recorder():
    return _current


def close_recorder():
    global _current
    rec, _current = _current, None
    # a capture window that never reached its stop step (loop ended
    # early, telemetry=True user never called close()) must still land:
    # stop + upload any in-flight capture before the final flush
    for trigger in list(_live_triggers):
        try:
            trigger.stop()
        except Exception:
            pass
    if rec is not None:
        rec.close()


def emit(rtype, name, **kwargs):
    if _current is not None:
        _current.emit(rtype, name, **kwargs)


def timer(name, step_num=None, data=None):
    """Time a block into the current recorder; with no recorder the
    block is still a span on the profiler's clock."""
    return _Timer(_current, name, step_num=step_num, data=data)


def counter(name, inc=1, data=None):
    if _current is not None:
        _current.counter(name, inc=inc, data=data)


def gauge(name, value, step_num=None, data=None):
    if _current is not None:
        _current.gauge(name, value, step_num=step_num, data=data)


def event(name, data=None):
    if _current is not None:
        _current.event(name, data=data)


def flush():
    if _current is not None:
        _current.flush()


# ---------------------------------------------------------------------------
# read-back: the `tpuflow metrics` CLI and tests consume persisted records
# ---------------------------------------------------------------------------


def read_run_records(flow_datastore, run_id):
    """All telemetry records persisted for a run, across every task/rank/
    host, sorted by timestamp."""
    storage = flow_datastore.storage
    prefix = storage.path_join(
        flow_datastore.flow_name, str(run_id), TELEMETRY_PREFIX)
    paths = [p for p, is_file in storage.list_content([prefix])
             if is_file and p.endswith(".jsonl")]
    records = []
    if paths:
        with storage.load_bytes(paths) as loaded:
            for _path, local, _meta in loaded:
                if local is None:
                    continue
                with open(local, "rb") as f:
                    for line in f.read().decode("utf-8").splitlines():
                        if not line.strip():
                            continue
                        try:
                            records.append(json.loads(line))
                        except ValueError:
                            continue
    records.sort(key=lambda r: r.get("ts", 0))
    return records


class TelemetryTail(object):
    """Incremental reader over a run's _telemetry/ part files.

    Part files are write-once (the recorder never rewrites a landed
    part), so a path-cursor delta over list_content is exact: each poll()
    lists the prefix, loads only paths not yet seen, and returns their
    records sorted by timestamp. This is what lets `tpuflow watch` tail a
    run that is still producing records without the full re-read
    read_run_records does on every refresh."""

    def __init__(self, flow_datastore, run_id):
        self._fds = flow_datastore
        self.run_id = str(run_id)
        self._seen = set()

    def poll(self):
        """Records from part files that appeared since the last poll()
        (all of them on the first call). [] when nothing new — including
        when the run has not written any telemetry yet."""
        storage = self._fds.storage
        prefix = storage.path_join(
            self._fds.flow_name, self.run_id, TELEMETRY_PREFIX)
        try:
            paths = [p for p, is_file in storage.list_content([prefix])
                     if is_file and p.endswith(".jsonl")]
        except Exception:
            # an in-progress run may not have created _telemetry/ yet
            return []
        new = sorted(p for p in paths if p not in self._seen)
        if not new:
            return []
        self._seen.update(new)
        records = []
        with storage.load_bytes(new) as loaded:
            for _path, local, _meta in loaded:
                if local is None:
                    continue
                with open(local, "rb") as f:
                    for line in f.read().decode("utf-8").splitlines():
                        if not line.strip():
                            continue
                        try:
                            records.append(json.loads(line))
                        except ValueError:
                            continue
        records.sort(key=lambda r: r.get("ts", 0))
        return records


def list_run_profiles(flow_datastore, run_id):
    """Datastore paths of profiler trace artifacts captured for a run."""
    storage = flow_datastore.storage
    prefix = storage.path_join(
        flow_datastore.flow_name, str(run_id), PROFILE_PREFIX)
    return [p for p, is_file in storage.list_content([prefix]) if is_file]


def list_run_hangs(flow_datastore, run_id):
    """Datastore paths of hang-forensics artifacts (stack dumps + report
    bundles the gang watchdog uploaded) captured for a run. Bundles live
    one level down (`_telemetry/hangs/<stamp>/...`), so this descends
    into each per-detection stamp directory."""
    storage = flow_datastore.storage
    prefix = storage.path_join(
        flow_datastore.flow_name, str(run_id), HANGS_PREFIX)
    paths = []
    stamps = []
    for p, is_file in storage.list_content([prefix]):
        (paths if is_file else stamps).append(p)
    if stamps:
        paths.extend(p for p, is_file in storage.list_content(stamps)
                     if is_file)
    return sorted(paths)


# ---------------------------------------------------------------------------
# on-demand jax.profiler capture
# ---------------------------------------------------------------------------


# ProfileTriggers with an IN-FLIGHT capture: registered at _start, removed
# at stop — close_recorder() drains them so a window that outlives the
# train loop (or a telemetry=True user who never calls close()) still
# stops the profiler and uploads the trace
_live_triggers = set()


def _zip_dir(root):
    buf = io.BytesIO()
    with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as zf:
        for dirpath, _dirs, files in os.walk(root):
            for name in files:
                full = os.path.join(dirpath, name)
                zf.write(full, os.path.relpath(full, root))
    return buf.getvalue()


class ProfileTrigger(object):
    """Step-window jax.profiler capture for a live training loop.

    Call `on_step(step_num)` once per train step. Capture starts when any
    trigger fires and stops `length` steps later; the trace directory is
    zipped and uploaded to the run's datastore under
    `_telemetry/profiles/`, with a `profile.captured` event linking it.

    Triggers:
      - env window: TPUFLOW_PROFILE_STEPS="start:stop" (absolute step
        numbers, capture is [start, stop))
      - file: the TPUFLOW_PROFILE_REQUEST path appears (its content, an
        integer, is the capture length; default 5 steps). The file is
        removed once the capture starts, so it can be re-touched.
      - signal: SIGUSR2 when TPUFLOW_PROFILE_SIGNAL=1 (install via
        install_signal_trigger()).
    """

    DEFAULT_LENGTH = 5

    def __init__(self, recorder=None, steps=None, request_file=None,
                 check_every=1.0):
        self._recorder = recorder
        spec = (steps if steps is not None
                else knobs.get_str("TPUFLOW_PROFILE_STEPS"))
        self._window = self._parse_window(spec)
        self._request_file = request_file or knobs.get_str(
            "TPUFLOW_PROFILE_REQUEST")
        self._check_every = check_every
        self._last_check = 0.0
        self._signal_pending = [0]
        self._active = None  # (start_step, stop_step, tmpdir)
        if knobs.get_bool("TPUFLOW_PROFILE_SIGNAL"):
            self.install_signal_trigger()

    @staticmethod
    def _parse_window(spec):
        if not spec:
            return None
        try:
            start, _, stop = spec.partition(":")
            start, stop = int(start), int(stop)
        except ValueError:
            sys.stderr.write(
                "telemetry: ignoring malformed TPUFLOW_PROFILE_STEPS=%r "
                "(want start:stop)\n" % spec)
            return None
        if stop <= start:
            return None
        return (start, stop)

    def install_signal_trigger(self, signum=None):
        import signal as _signal

        signum = signum or _signal.SIGUSR2
        pending = self._signal_pending

        def _on_signal(_s, _f):
            pending[0] = self.DEFAULT_LENGTH

        try:
            _signal.signal(signum, _on_signal)
        except ValueError:
            pass  # not the main thread: signal trigger unavailable

    def _poll_request_file(self):
        if not self._request_file:
            return 0
        now = time.monotonic()
        if now - self._last_check < self._check_every:
            return 0
        self._last_check = now
        try:
            with open(self._request_file) as f:
                content = f.read().strip()
            os.unlink(self._request_file)
        except OSError:
            return 0
        try:
            return max(1, int(content)) if content else self.DEFAULT_LENGTH
        except ValueError:
            return self.DEFAULT_LENGTH

    def on_step(self, step_num):
        """Drive the capture state machine; cheap when idle."""
        if self._active is None:
            length = 0
            if self._window and step_num >= self._window[0]:
                start, stop = self._window
                self._window = None
                if step_num < stop:
                    length = stop - step_num
            if not length and self._signal_pending[0]:
                length, self._signal_pending[0] = self._signal_pending[0], 0
            if not length:
                length = self._poll_request_file()
            if length:
                self._start(step_num, step_num + length)
        elif step_num >= self._active[1]:
            self.stop(step_num)

    def _start(self, start_step, stop_step):
        import tempfile

        import jax

        tmpdir = tempfile.mkdtemp(prefix="tpuflow_profile_")
        try:
            jax.profiler.start_trace(tmpdir)
        except Exception as ex:
            sys.stderr.write("telemetry: profiler start failed: %s\n" % ex)
            return
        self._active = (start_step, stop_step, tmpdir)
        _live_triggers.add(self)
        if self._recorder is not None:
            self._recorder.event(
                "profile.start",
                data={"start_step": start_step, "stop_step": stop_step})

    def stop(self, step_num=None):
        """Stop an in-flight capture, upload the zipped trace, link it."""
        if self._active is None:
            return None
        import shutil

        import jax

        start_step, stop_step, tmpdir = self._active
        self._active = None
        _live_triggers.discard(self)
        try:
            jax.profiler.stop_trace()
        except Exception as ex:
            sys.stderr.write("telemetry: profiler stop failed: %s\n" % ex)
            shutil.rmtree(tmpdir, ignore_errors=True)
            return None
        payload = _zip_dir(tmpdir)
        shutil.rmtree(tmpdir, ignore_errors=True)
        path = None
        if self._recorder is not None:
            name = "trace_%s_%s_a%d_s%d-%d.zip" % (
                self._recorder.step_name, self._recorder.task_id,
                self._recorder.attempt, start_step,
                stop_step if step_num is None else step_num)
            path = self._recorder.save_artifact(name, payload)
            self._recorder.event(
                "profile.captured",
                data={"artifact": path, "start_step": start_step,
                      "stop_step": stop_step, "bytes": len(payload)})
        else:
            # no run context: keep the trace on local disk
            out = os.path.abspath("tpuflow_profile_s%d-%d.zip"
                                  % (start_step, stop_step))
            with open(out, "wb") as f:
                f.write(payload)
            sys.stderr.write("telemetry: profiler trace saved to %s\n" % out)
            path = out
        return path
