"""Fleet-wide goodput ledger: account every chip-second in one set of categories.

The scheduling objective every later subsystem optimizes ("goodput over
elastic capacity") needs a measurement substrate first: the subsystems
already emit the raw signals — `train.step` timers with per-component
stall breakdowns (training/metrics.py), `serve.prefill_chunk` /
`serve.decode_step` timers (serving/scheduler.py), `data.batch_wait`
(data/loader.py), checkpoint spans (training/checkpoint.py), and
`elastic.backoff` capacity parks (elastic/supervisor.py) — but nobody
could SUM them. This module derives a per-rank interval ledger from
those streams and rolls it up into a wall-clock-reconciled breakdown.

Categories (pinned in tests/schema_validate.py::GOODPUT_CATEGORIES):

    productive_step     forward+backward compute inside a train step
    compile             XLA trace+compile (whole interval of a step that
                        grew the jit cache)
    input_stall         host blocked in next(iterator) / batch wait
    transfer_stall      MPMD stage blocked on the inter-stage transport
    update              optimizer update (diagnostic split-step mode)
    checkpoint_blocked  train loop blocked in the checkpoint snapshot
    restore_replay      recovery overhead: checkpoint restore + steps
                        re-done after an elastic resize / hang kill
    capacity_wait       parked attempts (chip-seconds the gang WOULD
                        have used while waiting for admissible capacity)
    serve_prefill       serving: chunked prefill device work
    serve_decode        serving: batched decode device work
    serve_idle          serving: scheduler span not covered by device
                        work (empty queue, admission gaps)
    actor_rollout       online loop: remote-fleet rollout batches (the
                        actor's chip-seconds; local-engine rollouts
                        already ride serve_prefill/serve_decode)
    unattributed        observed chip-time no category explains — an
                        explicit bucket, never silently dropped

Derivation model: records group into LANES keyed by
(step, task_id, attempt, rank) — one lane is one task attempt on one
rank, i.e. one chip's allocation. A lane's observed chip-time is the
span of its *work* timers (a timer's interval is [ts - ms, ts]); infra
envelopes (task.user_code, persist.*) are deliberately excluded so the
span measures chip occupancy, not host bookkeeping. Replayed work is
detected gang-level: a step record whose step_num does not exceed the
furthest step any earlier attempt of the same flow step reached is
work being re-done after a restore. Parked capacity (elastic.backoff
with waiting_for_capacity) contributes delay_s x world chip-seconds on
top of lane spans.

Reconciliation: sum(categories) must reach (1 - tolerance) of observed
chip-time; the remainder is the explicit `unattributed` bucket. The
dominant non-productive category names the run's loss verdict — the
run-level generalization of the INPUT-BOUND / PIPELINE-BOUND verdicts
`tpuflow metrics` prints per stage.

The same module renders OpenMetrics text (render_openmetrics) for the
/metrics endpoints on the replica server and fleet router, and hosts
the run-scope exporter (RunMetricsExporter) training gangs expose.
"""

import json
import threading

from . import telemetry

LEDGER_VERSION = 1
GOODPUT_PREFIX = "_telemetry/goodput"
RECONCILE_TOLERANCE = 0.05

PRODUCTIVE_STEP = "productive_step"
COMPILE = "compile"
INPUT_STALL = "input_stall"
TRANSFER_STALL = "transfer_stall"
UPDATE = "update"
CHECKPOINT_BLOCKED = "checkpoint_blocked"
RESTORE_REPLAY = "restore_replay"
CAPACITY_WAIT = "capacity_wait"
SERVE_PREFILL = "serve_prefill"
SERVE_DECODE = "serve_decode"
SERVE_IDLE = "serve_idle"
ACTOR_ROLLOUT = "actor_rollout"
UNATTRIBUTED = "unattributed"

CATEGORIES = (
    PRODUCTIVE_STEP, COMPILE, INPUT_STALL, TRANSFER_STALL, UPDATE,
    CHECKPOINT_BLOCKED, RESTORE_REPLAY, CAPACITY_WAIT,
    SERVE_PREFILL, SERVE_DECODE, SERVE_IDLE, ACTOR_ROLLOUT,
)

# chip-time spent doing the work the run exists for; everything else
# (incl. unattributed) is a loss category the verdict can name
PRODUCTIVE_CATEGORIES = (
    PRODUCTIVE_STEP, UPDATE, SERVE_PREFILL, SERVE_DECODE, ACTOR_ROLLOUT)


def _is_step_timer(rec):
    return (rec.get("type") == "timer"
            and rec.get("name", "").endswith(".step")
            and "step_num" in rec and "ms" in rec)


def _lane_key(rec):
    return (rec.get("step", ""), str(rec.get("task_id", "")),
            int(rec.get("attempt", 0)), int(rec.get("rank", 0)))


class _Lane(object):
    __slots__ = ("start", "end", "cats", "has_steps", "serve_busy",
                 "batch_wait_s", "snapshot_s", "kinds")

    def __init__(self):
        self.start = None
        self.end = None
        self.cats = {}
        self.has_steps = False
        self.serve_busy = 0.0
        self.batch_wait_s = 0.0
        self.snapshot_s = 0.0
        self.kinds = set()

    def work(self, ts, seconds):
        """Extend the lane's observed span by one work interval
        [ts - seconds, ts]."""
        t0 = ts - seconds
        self.start = t0 if self.start is None else min(self.start, t0)
        self.end = ts if self.end is None else max(self.end, ts)

    def add(self, category, seconds):
        if seconds > 0:
            self.cats[category] = self.cats.get(category, 0.0) + seconds

    @property
    def span(self):
        if self.start is None:
            return 0.0
        return max(0.0, self.end - self.start)


def derive_ledger(records, run_id=None, tolerance=RECONCILE_TOLERANCE):
    """Derive the goodput ledger from a run's telemetry records (the
    list read_run_records returns). Pure: no datastore access."""
    # pass 1 — replay horizon: the furthest step_num each flow step's
    # gang reached, per attempt. A later attempt's records at or below
    # an earlier attempt's horizon are work being re-done.
    reached = {}  # step_name -> {attempt: max step_num}
    for rec in records:
        if not _is_step_timer(rec):
            continue
        per = reached.setdefault(rec.get("step", ""), {})
        att = int(rec.get("attempt", 0))
        num = int(rec["step_num"])
        if num > per.get(att, -1):
            per[att] = num

    def _replay_horizon(step_name, attempt):
        per = reached.get(step_name, {})
        prior = [n for a, n in per.items() if a < attempt]
        return max(prior) if prior else None

    # pass 2 — attribute work timers into lanes
    lanes = {}
    parked = []
    capacity_wait_s = 0.0
    for rec in records:
        rtype = rec.get("type")
        name = rec.get("name", "")
        if rtype == "event":
            if name == "elastic.backoff":
                data = rec.get("data") or {}
                if data.get("waiting_for_capacity"):
                    delay = float(data.get("delay_s") or 0.0)
                    world = int(data.get("world") or 1)
                    parked.append({
                        "pathspec": data.get("pathspec", ""),
                        "attempt": int(data.get("attempt", 0)),
                        "delay_s": round(delay, 3),
                        "world": world,
                    })
                    capacity_wait_s += delay * max(1, world)
            continue
        if rtype != "timer" or "ms" not in rec:
            continue
        seconds = float(rec["ms"]) / 1000.0
        if seconds <= 0:
            continue
        ts = float(rec.get("ts", 0.0))
        if _is_step_timer(rec):
            lane = lanes.setdefault(_lane_key(rec), _Lane())
            lane.work(ts, seconds)
            lane.has_steps = True
            lane.kinds.add("train")
            data = rec.get("data") or {}
            horizon = _replay_horizon(rec.get("step", ""),
                                      int(rec.get("attempt", 0)))
            if horizon is not None and int(rec["step_num"]) <= horizon:
                lane.add(RESTORE_REPLAY, seconds)
            elif data.get("compile"):
                lane.add(COMPILE, seconds)
            else:
                stall = float(data.get("input_stall_ms") or 0.0) / 1000.0
                xfer = float(data.get("transfer_stall_ms") or 0.0) / 1000.0
                upd = float(data.get("optimizer_update_ms") or 0.0) / 1000.0
                lane.add(INPUT_STALL, min(stall, seconds))
                lane.add(TRANSFER_STALL, min(xfer, seconds))
                lane.add(UPDATE, min(upd, seconds))
                lane.add(PRODUCTIVE_STEP,
                         max(0.0, seconds - stall - xfer - upd))
        elif name == "serve.decode_step":
            lane = lanes.setdefault(_lane_key(rec), _Lane())
            lane.work(ts, seconds)
            lane.kinds.add("serve")
            lane.add(SERVE_DECODE, seconds)
            lane.serve_busy += seconds
        elif name == "serve.prefill_chunk":
            lane = lanes.setdefault(_lane_key(rec), _Lane())
            lane.work(ts, seconds)
            lane.kinds.add("serve")
            lane.add(SERVE_PREFILL, seconds)
            lane.serve_busy += seconds
        elif name == "data.batch_wait":
            # inside an instrumented train loop the wait already rides
            # the step record's input_stall_ms: attribute it only for
            # lanes that have no step records (resolved below)
            lane = lanes.setdefault(_lane_key(rec), _Lane())
            lane.work(ts, seconds)
            lane.kinds.add("train")
            lane.batch_wait_s += seconds
        elif name == "checkpoint.snapshot":
            lane = lanes.setdefault(_lane_key(rec), _Lane())
            lane.work(ts, seconds)
            lane.kinds.add("train")
            lane.snapshot_s += seconds
        elif name == "checkpoint.restore":
            lane = lanes.setdefault(_lane_key(rec), _Lane())
            lane.work(ts, seconds)
            lane.kinds.add("train")
            lane.add(RESTORE_REPLAY, seconds)
        elif name == "online.rollout":
            # the online ActorPool's remote-fleet batches: the fleet's
            # chip-seconds viewed from the supervisor lane (the actor
            # emits it ONLY on the remote path — a local engine's
            # rollouts already land in serve_* via the serve timers in
            # the same-process lane, and double-counting would break
            # reconciliation)
            lane = lanes.setdefault(_lane_key(rec), _Lane())
            lane.work(ts, seconds)
            lane.kinds.add("actor")
            lane.add(ACTOR_ROLLOUT, seconds)
        # any other timer (task.user_code, persist.*, distributed.*) is
        # host bookkeeping, not chip work: it extends neither the span
        # nor any category

    # pass 3 — per-lane resolution + rollup
    totals = dict.fromkeys(CATEGORIES, 0.0)
    totals[CAPACITY_WAIT] = capacity_wait_s
    lane_rows = []
    observed_s = capacity_wait_s
    wall_start = wall_end = None
    for key in sorted(lanes):
        lane = lanes[key]
        if lane.batch_wait_s and not lane.has_steps:
            lane.add(INPUT_STALL, lane.batch_wait_s)
        if lane.snapshot_s:
            # the snapshot lands INSIDE a step interval already counted
            # as productive: move it rather than double-count it
            lane.add(CHECKPOINT_BLOCKED, lane.snapshot_s)
            if lane.has_steps:
                prod = lane.cats.get(PRODUCTIVE_STEP, 0.0)
                moved = min(prod, lane.snapshot_s)
                if moved:
                    lane.cats[PRODUCTIVE_STEP] = prod - moved
        attributed = sum(lane.cats.values())
        if lane.serve_busy and not lane.has_steps:
            idle = max(0.0, lane.span - attributed)
            lane.add(SERVE_IDLE, idle)
            attributed += idle
        # a lane is occupied at least as long as its measured busy time
        # (span alone can undercount single-record lanes)
        lane_observed = max(lane.span, attributed)
        observed_s += lane_observed
        if lane.start is not None:
            wall_start = (lane.start if wall_start is None
                          else min(wall_start, lane.start))
            wall_end = (lane.end if wall_end is None
                        else max(wall_end, lane.end))
        for cat, sec in lane.cats.items():
            totals[cat] += sec
        step_name, task_id, attempt, rank = key
        kind = ("mixed" if len(lane.kinds) > 1
                else next(iter(lane.kinds), "train"))
        lane_rows.append({
            "step": step_name,
            "task_id": task_id,
            "attempt": attempt,
            "rank": rank,
            "kind": kind,
            "span_s": round(lane.span, 3),
            "observed_s": round(lane_observed, 3),
            "unattributed_s": round(lane_observed - attributed, 3),
            "categories": {c: round(s, 3)
                           for c, s in sorted(lane.cats.items()) if s > 0},
        })

    attributed_s = sum(totals.values())
    unattributed_s = max(0.0, observed_s - attributed_s)
    coverage = (attributed_s / observed_s) if observed_s > 0 else 1.0
    productive_s = sum(totals[c] for c in PRODUCTIVE_CATEGORIES)
    losses = {c: totals[c] for c in CATEGORIES
              if c not in PRODUCTIVE_CATEGORIES and totals[c] > 0}
    if unattributed_s > 0:
        losses[UNATTRIBUTED] = unattributed_s
    dominant = max(losses, key=losses.get) if losses else None
    return {
        "v": LEDGER_VERSION,
        "run_id": str(run_id) if run_id is not None else None,
        "wall_clock_s": round((wall_end - wall_start), 3)
        if wall_start is not None else 0.0,
        "observed_chip_s": round(observed_s, 3),
        "attributed_chip_s": round(attributed_s, 3),
        "unattributed_chip_s": round(unattributed_s, 3),
        "coverage": round(min(1.0, coverage), 4),
        "goodput_frac": round(productive_s / observed_s, 4)
        if observed_s > 0 else 0.0,
        "tolerance": tolerance,
        "reconciled": coverage >= (1.0 - tolerance),
        "categories": {c: round(totals[c], 3) for c in CATEGORIES},
        "dominant_loss": dominant,
        "dominant_loss_s": round(losses.get(dominant, 0.0), 3)
        if dominant else 0.0,
        "parked": parked,
        "lanes": lane_rows,
    }


# ---------------------------------------------------------------------------
# persistence: crash-safe ledger records under _telemetry/goodput/
# ---------------------------------------------------------------------------


def ledger_path(flow_datastore, run_id, name="ledger.json"):
    return flow_datastore.storage.path_join(
        flow_datastore.flow_name, str(run_id), GOODPUT_PREFIX, name)


def save_ledger(flow_datastore, run_id, ledger, name="ledger.json"):
    """Persist a derived ledger under the run's telemetry tree; returns
    the datastore-relative path (None on storage error — persisting a
    ledger must never fail the run it describes)."""
    path = ledger_path(flow_datastore, run_id, name)
    payload = json.dumps(ledger, sort_keys=True).encode("utf-8")
    try:
        flow_datastore.storage.save_bytes(
            [(path, payload)], overwrite=True)
    except Exception:
        return None
    return path


def load_ledger(flow_datastore, run_id, name="ledger.json"):
    """The persisted ledger, or None when none was saved."""
    path = ledger_path(flow_datastore, run_id, name)
    try:
        with flow_datastore.storage.load_bytes([path]) as loaded:
            for _path, local, _meta in loaded:
                if local is None:
                    return None
                with open(local, "rb") as f:
                    return json.loads(f.read().decode("utf-8"))
    except Exception:
        return None
    return None


def derive_run_ledger(flow_datastore, run_id, persist=False,
                      tolerance=RECONCILE_TOLERANCE):
    """Read a run's records, derive the ledger, optionally persist it."""
    records = telemetry.read_run_records(flow_datastore, run_id)
    ledger = derive_ledger(records, run_id=run_id, tolerance=tolerance)
    if persist:
        save_ledger(flow_datastore, run_id, ledger)
    return ledger


# ---------------------------------------------------------------------------
# OpenMetrics text format (stdlib-only writer + strict parser)
# ---------------------------------------------------------------------------

OPENMETRICS_CONTENT_TYPE = (
    "application/openmetrics-text; version=1.0.0; charset=utf-8")

_TYPES = ("gauge", "counter", "summary", "info", "unknown")


class Family(object):
    """One OpenMetrics metric family: a TYPE + HELP header and its
    samples. Counter samples get the mandatory `_total` suffix at
    render time; summary samples carry their quantile label."""

    def __init__(self, name, mtype, help_text=""):
        if mtype not in _TYPES:
            raise ValueError("bad metric type %r" % (mtype,))
        self.name = name
        self.mtype = mtype
        self.help_text = help_text
        self.samples = []  # (suffix, labels, value)

    def add(self, value, labels=None, suffix=None):
        if suffix is None:
            suffix = "_total" if self.mtype == "counter" else ""
        self.samples.append((suffix, dict(labels or {}), value))
        return self


def _escape_label(value):
    return (str(value).replace("\\", "\\\\").replace("\"", "\\\"")
            .replace("\n", "\\n"))


def _escape_help(value):
    return str(value).replace("\\", "\\\\").replace("\n", "\\n")


def _format_value(value):
    if isinstance(value, bool):
        return "1" if value else "0"
    if value is None:
        return "0"
    f = float(value)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def render_openmetrics(families):
    """Families -> OpenMetrics text (terminated by the mandatory
    `# EOF` line)."""
    lines = []
    for fam in families:
        lines.append("# TYPE %s %s" % (fam.name, fam.mtype))
        if fam.help_text:
            lines.append("# HELP %s %s"
                         % (fam.name, _escape_help(fam.help_text)))
        for suffix, labels, value in fam.samples:
            if labels:
                label_str = "{%s}" % ",".join(
                    "%s=\"%s\"" % (k, _escape_label(v))
                    for k, v in sorted(labels.items()))
            else:
                label_str = ""
            lines.append("%s%s%s %s" % (fam.name, suffix, label_str,
                                        _format_value(value)))
    lines.append("# EOF")
    return "\n".join(lines) + "\n"


def _parse_labels(text):
    """`k="v",k2="v2"` -> dict, with strict escape handling."""
    labels = {}
    i, n = 0, len(text)
    while i < n:
        j = text.index("=", i)
        key = text[i:j]
        if not key or not key.replace("_", "a").isalnum():
            raise ValueError("bad label name %r" % key)
        if j + 1 >= n or text[j + 1] != "\"":
            raise ValueError("label value must be quoted: %r" % text)
        i = j + 2
        buf = []
        while True:
            if i >= n:
                raise ValueError("unterminated label value in %r" % text)
            c = text[i]
            if c == "\\":
                if i + 1 >= n:
                    raise ValueError("dangling escape in %r" % text)
                nxt = text[i + 1]
                buf.append({"\\": "\\", "\"": "\"", "n": "\n"}.get(nxt))
                if buf[-1] is None:
                    raise ValueError("bad escape \\%s" % nxt)
                i += 2
                continue
            if c == "\"":
                i += 1
                break
            buf.append(c)
            i += 1
        labels[key] = "".join(buf)
        if i < n:
            if text[i] != ",":
                raise ValueError("expected ',' between labels in %r"
                                 % text)
            i += 1
    return labels


def _sample_family(name, labels, families):
    """Resolve which declared family a sample name belongs to, per the
    OpenMetrics suffix rules for each type."""
    if name in families:
        fam = families[name]
        if fam["type"] == "counter":
            raise ValueError(
                "counter sample %r missing _total suffix" % name)
        if fam["type"] == "summary" and "quantile" not in labels:
            raise ValueError(
                "summary sample %r missing quantile label" % name)
        if fam["type"] == "info":
            raise ValueError("info sample %r missing _info suffix" % name)
        return name
    for suffix, types in (("_total", ("counter",)),
                          ("_created", ("counter", "summary")),
                          ("_count", ("summary",)),
                          ("_sum", ("summary",)),
                          ("_info", ("info",))):
        if name.endswith(suffix):
            base = name[:-len(suffix)]
            if base in families and families[base]["type"] in types:
                return base
    raise ValueError("sample %r matches no declared family" % name)


def parse_openmetrics(text):
    """Strict OpenMetrics text parser (the test oracle for the /metrics
    endpoints). Enforces: terminal `# EOF`, declared-before-use
    families, no duplicate or interleaved families, suffix rules
    (counters end in _total, info in _info, summaries carry quantile),
    parseable sample values, non-negative counters. Returns
    {family: {"type", "help", "samples": [(name, labels, value)]}}."""
    if not text.endswith("\n"):
        raise ValueError("must end with a newline")
    lines = text.split("\n")[:-1]
    if not lines or lines[-1] != "# EOF":
        raise ValueError("missing terminal # EOF line")
    families = {}
    order = []
    current = None
    for lineno, line in enumerate(lines[:-1], 1):
        if not line:
            raise ValueError("blank line %d not allowed" % lineno)
        if line.startswith("#"):
            parts = line.split(" ", 3)
            if len(parts) < 4 or parts[0] != "#" or parts[1] not in (
                    "TYPE", "HELP", "UNIT"):
                raise ValueError("bad comment line %d: %r"
                                 % (lineno, line))
            kind, name, rest = parts[1], parts[2], parts[3]
            if kind == "TYPE":
                if rest not in _TYPES:
                    raise ValueError("bad type %r (line %d)"
                                     % (rest, lineno))
                if name in families:
                    raise ValueError("duplicate family %r (line %d)"
                                     % (name, lineno))
                families[name] = {"type": rest, "help": "",
                                  "samples": []}
                order.append(name)
                current = name
            else:
                if name not in families or name != current:
                    raise ValueError(
                        "%s for undeclared/non-current family %r "
                        "(line %d)" % (kind, name, lineno))
                if kind == "HELP":
                    families[name]["help"] = rest
            continue
        # sample line: name[{labels}] value [timestamp]
        brace = line.find("{")
        labels = {}
        if brace >= 0:
            close = line.find("}", brace)
            if close < 0:
                raise ValueError("unclosed labels (line %d)" % lineno)
            name = line[:brace]
            labels = _parse_labels(line[brace + 1:close])
            rest = line[close + 1:].strip()
        else:
            name, _, rest = line.partition(" ")
            rest = rest.strip()
        if not rest:
            raise ValueError("sample missing value (line %d)" % lineno)
        value_str = rest.split(" ")[0]
        try:
            value = float(value_str)
        except ValueError:
            raise ValueError("bad sample value %r (line %d)"
                             % (value_str, lineno))
        base = _sample_family(name, labels, families)
        if base != current:
            raise ValueError(
                "interleaved sample %r under family %r (line %d)"
                % (name, current, lineno))
        if families[base]["type"] == "counter" and value < 0:
            raise ValueError("negative counter %r (line %d)"
                             % (name, lineno))
        families[base]["samples"].append((name, labels, value))
    return families


# ---------------------------------------------------------------------------
# metric-family builders: one vocabulary, pinned in schema_validate.py
# ---------------------------------------------------------------------------


def scheduler_metric_families(stats):
    """Scheduler.stats() -> replica-scope metric families. Every value
    is read from the SAME stats dict /v1/stats serves, so the two
    surfaces cannot disagree."""
    fams = []

    def gauge(name, value, help_text="", labels=None):
        fams.append(Family(name, "gauge", help_text).add(value, labels))

    gauge("tpuflow_serve_queue_depth", stats["queue_depth"],
          "Requests waiting for a slot")
    gauge("tpuflow_serve_in_flight", stats["in_flight"],
          "Requests occupying slots")
    gauge("tpuflow_serve_slots", stats["slots"], "Decode slot capacity")
    gauge("tpuflow_serve_occupancy", stats["occupancy"],
          "Instantaneous slot occupancy")
    gauge("tpuflow_serve_mean_batch_occupancy",
          stats["mean_batch_occupancy"],
          "Mean decode-batch occupancy over all decode steps")
    gauge("tpuflow_serve_draining", bool(stats["draining"]),
          "1 while a graceful drain is in progress")
    gauge("tpuflow_serve_peak_in_flight", stats["peak_in_flight"],
          "High-water mark of concurrent requests")
    gauge("tpuflow_serve_max_context_tokens",
          stats["max_context_tokens"],
          "Largest prompt+max_new this engine admits")
    fams.append(
        Family("tpuflow_serve_requests", "counter",
               "Requests finished, by outcome")
        .add(stats["served"], {"outcome": "served"})
        .add(stats["cancelled"], {"outcome": "cancelled"}))
    fams.append(Family("tpuflow_serve_decode_steps", "counter",
                       "Batched decode steps executed")
                .add(stats["decode_steps"]))
    fams.append(Family("tpuflow_serve_steps_ahead", "counter",
                       "Decode steps launched while the step before was "
                       "still uncollected: all but the first of a loaded "
                       "loop, none on an engine that runs nothing ahead")
                .add(stats.get("steps_ahead", 0)))
    fams.append(Family("tpuflow_serve_weight_passes", "counter",
                       "Passes through the stack's weights over the decode "
                       "steps run: the steps times the model's passes (a "
                       "looped model runs its stack several times a token)")
                .add(stats.get("weight_passes", stats["decode_steps"])))
    fams.append(
        Family("tpuflow_serve_prefill", "counter",
               "Prefill programs run, the rows (slots) they carried, the "
               "prompt tokens in those rows, and how many of the programs "
               "were a decode step that took the rows along")
        .add(stats["prefill_programs"], {"count": "programs"})
        .add(stats["prefill_rows"], {"count": "rows"})
        .add(stats["prefill_tokens"], {"count": "tokens"})
        .add(stats.get("merged_steps", 0), {"count": "merged_steps"}))
    fams.append(
        Family("tpuflow_serve_admissions", "counter",
               "Requests bound to a slot, and how many of them drew their "
               "sampling keys (on the device, when a sampled token was "
               "first asked for; none under greedy traffic)")
        .add(stats.get("admitted", 0), {"count": "admitted"})
        .add(stats.get("key_schedules", 0), {"count": "key_schedules"}))
    pairs = stats.get("expert_pairs") or {}
    fams.append(
        Family("tpuflow_serve_expert_pairs", "counter",
               "(token, expert) pairs the programs of a model with latent "
               "expert layers routed, and those that fell on the experts "
               "this engine holds (a chip's share of each layer; equal "
               "where it holds them all, 0 for a model with no such layer)")
        .add(pairs.get("routed", 0), {"count": "routed"})
        .add(pairs.get("held", 0), {"count": "held"}))
    fams.append(
        Family("tpuflow_serve_attention_positions", "counter",
               "K and V positions over the decode steps run and all "
               "reading layers: those the decoding lanes' queries saw, "
               "and those the program fetched for them")
        .add(stats.get("attention_positions_needed", 0), {"count": "needed"})
        .add(stats.get("attention_positions_fetched", 0),
             {"count": "fetched"}))
    fams.append(Family("tpuflow_serve_iterations", "counter",
                       "Scheduler loop iterations")
                .add(stats["iterations"]))
    phases = (stats.get("phases") or {}).get("phase") or {}
    if phases:
        seconds = Family(
            "tpuflow_serve_phase_seconds", "counter",
            "The serving loop's seconds on the host's clock, by phase "
            "(the spans' names; phases nest: serve.iteration holds all "
            "but wait, the two *.fetch phases are the loop waiting for "
            "the device)")
        calls = Family("tpuflow_serve_phase_calls", "counter",
                       "Times the serving loop entered each phase")
        for name in sorted(phases):
            seconds.add(phases[name]["seconds"], {"phase": name})
            calls.add(phases[name]["calls"], {"phase": name})
        fams += [seconds, calls]
    collected = stats.get("gc") or {}
    if collected:
        pause = Family(
            "tpuflow_serve_gc_pause_seconds", "counter",
            "Seconds Python's collector held the process while the "
            "serving loop ran, by generation (beside the phases, not "
            "taken out of them)")
        for generation in sorted(collected):
            pause.add(collected[generation]["seconds"],
                      {"generation": generation})
        fams.append(pause)
    ttft = Family("tpuflow_serve_ttft_ms", "summary",
                  "Time to first token, rolling window")
    ttft.add(stats["p50_ttft_ms"] or 0.0, {"quantile": "0.5"})
    ttft.add(stats["p99_ttft_ms"] or 0.0, {"quantile": "0.99"})
    fams.append(ttft)
    itl = Family("tpuflow_serve_itl_ms", "summary",
                 "Inter-token latency, rolling window")
    itl.add(stats["p50_itl_ms"] or 0.0, {"quantile": "0.5"})
    itl.add(stats["p99_itl_ms"] or 0.0, {"quantile": "0.99"})
    fams.append(itl)
    state = stats.get("state_pool") or {}
    gauge("tpuflow_serve_state_pool_bytes", state.get("bytes", 0),
          "Device bytes of the recurrent-state pools (0: the model "
          "carries no recurrent state)")
    gauge("tpuflow_serve_state_pool_bytes_per_slot",
          state.get("bytes_per_slot", 0),
          "Recurrent-state bytes one slot holds, whatever its position")
    pools = stats.get("cache_pools") or {}
    for field, help_text in (
            ("bytes", "Device bytes of the cache's pools by what they hold: "
             "global (K and V as deep as max_seq_len), ring (K and V of "
             "window layers), state (recurrent state)"),
            ("bytes_per_slot", "The same, one slot's share")):
        fam = Family("tpuflow_serve_cache_pool_" + field, "gauge", help_text)
        for kind in sorted(pools):
            fam.add(pools[kind][field], {"kind": kind})
        if pools:
            fams.append(fam)
    prefix = stats.get("prefix_cache") or {}
    if prefix.get("enabled"):
        fams.append(
            Family("tpuflow_serve_prefix_lookups", "counter",
                   "Prefix-cache lookups, by result")
            .add(prefix["hits"], {"result": "hit"})
            .add(prefix["misses"], {"result": "miss"}))
        gauge("tpuflow_serve_prefix_hit_rate", prefix["hit_rate"],
              "Prefix-cache hit rate")
        gauge("tpuflow_serve_prefix_tokens_skipped_frac",
              prefix["prefill_tokens_skipped_frac"],
              "Fraction of prompt tokens served from cache")
    kv = stats.get("kv_pages") or {}
    if kv.get("enabled"):
        used = int(kv.get("pages_total", 0)) - int(kv.get("pages_free", 0))
        pages = Family("tpuflow_serve_kv_pages", "gauge",
                       "Paged-KV pool pages, by state")
        pages.add(used, {"state": "used"})
        pages.add(kv.get("pages_free", 0), {"state": "free"})
        pages.add(kv.get("shared_pages", 0), {"state": "shared"})
        pages.add(kv.get("cow_pages", 0), {"state": "cow"})
        fams.append(pages)
        gauge("tpuflow_serve_kv_occupancy", kv.get("occupancy", 0.0),
              "Paged-KV pool occupancy")
        fams.append(Family("tpuflow_serve_kv_exhausted", "counter",
                           "Admission stalls on page exhaustion")
                    .add(kv.get("exhausted", 0)))
    spec = stats.get("speculative") or {}
    if spec.get("enabled"):
        gauge("tpuflow_serve_spec_accept_rate",
              spec.get("accept_rate", 0.0),
              "Speculative-decode draft acceptance rate")
    goodput = stats.get("goodput") or {}
    if goodput:
        chip = Family("tpuflow_serve_goodput_seconds", "counter",
                      "Serving chip-seconds, by goodput category")
        chip.add(goodput.get("serve_prefill_s", 0.0),
                 {"category": SERVE_PREFILL})
        chip.add(goodput.get("serve_decode_s", 0.0),
                 {"category": SERVE_DECODE})
        chip.add(goodput.get("serve_idle_s", 0.0),
                 {"category": SERVE_IDLE})
        fams.append(chip)
    return fams


def fleet_metric_families(stats, healthz):
    """Fleet.stats()/healthz() -> router-scope metric families (the
    same dicts /v1/stats and /healthz serve)."""
    fams = []

    def gauge(name, value, help_text=""):
        fams.append(Family(name, "gauge", help_text).add(value))

    fams.append(
        Family("tpuflow_fleet_requests", "counter",
               "Fleet requests, by outcome")
        .add(stats["dispatched"], {"outcome": "dispatched"})
        .add(stats["completed"], {"outcome": "completed"})
        .add(stats["shed"], {"outcome": "shed"}))
    fams.append(Family("tpuflow_fleet_failovers", "counter",
                       "Requests retried on another replica")
                .add(stats["failovers"]))
    fams.append(Family("tpuflow_fleet_restarts", "counter",
                       "Replica processes restarted")
                .add(stats["restarts"]))
    fams.append(Family("tpuflow_fleet_prefill_handoffs", "counter",
                       "Disaggregated prefill->decode handoffs")
                .add(stats["prefill_handoffs"]))
    fams.append(Family("tpuflow_fleet_disagg_fallbacks", "counter",
                       "Disaggregated dispatches that fell back unified")
                .add(stats["disagg_fallbacks"]))
    fams.append(
        Family("tpuflow_fleet_scale_events", "counter",
               "Autoscaler actions, by direction")
        .add(stats["scale_outs"], {"direction": "out"})
        .add(stats["scale_ins"], {"direction": "in"}))
    gauge("tpuflow_fleet_inflight", stats["inflight"],
          "Requests in flight across the fleet")
    gauge("tpuflow_fleet_max_inflight", stats["max_inflight"],
          "Router admission limit")
    gauge("tpuflow_fleet_draining", bool(stats["draining"]),
          "1 while the fleet is draining")
    gauge("tpuflow_fleet_generation", stats["fleet_generation"],
          "Rollout generation of the newest replica")
    replicas = healthz.get("replicas") or []
    by_state = {}
    for rep in replicas:
        state = rep.get("state", "unknown")
        by_state[state] = by_state.get(state, 0) + 1
    reps = Family("tpuflow_fleet_replicas", "gauge",
                  "Replicas by lifecycle state")
    for state in sorted(by_state):
        reps.add(by_state[state], {"state": state})
    if not by_state:
        reps.add(0, {"state": "ready"})
    fams.append(reps)
    kv = healthz.get("kv_pages") or {}
    if kv.get("enabled"):
        used = int(kv.get("pages_total", 0)) - int(kv.get("pages_free", 0))
        pages = Family("tpuflow_fleet_kv_pages", "gauge",
                       "Fleet-wide paged-KV pages, by state")
        pages.add(used, {"state": "used"})
        pages.add(kv.get("pages_free", 0), {"state": "free"})
        pages.add(kv.get("shared_pages", 0), {"state": "shared"})
        pages.add(kv.get("cow_pages", 0), {"state": "cow"})
        fams.append(pages)
        gauge("tpuflow_fleet_kv_occupancy", kv.get("occupancy", 0.0),
              "Fleet-wide paged-KV occupancy")
    prefix = healthz.get("prefix_cache") or {}
    if prefix.get("enabled"):
        gauge("tpuflow_fleet_prefix_hit_rate",
              prefix.get("hit_rate", 0.0),
              "Mean prefix-cache hit rate over ready replicas")
    ttft = Family("tpuflow_fleet_ttft_ms", "summary",
                  "Worst ready-replica tail TTFT")
    ttft.add(healthz.get("p99_ttft_ms") or 0.0, {"quantile": "0.99"})
    fams.append(ttft)
    itl = Family("tpuflow_fleet_itl_ms", "summary",
                 "Worst ready-replica tail ITL")
    itl.add(healthz.get("p99_itl_ms") or 0.0, {"quantile": "0.99"})
    fams.append(itl)
    slo = healthz.get("slo") or {}
    gauge("tpuflow_fleet_slo_breached", bool(slo.get("breached")),
          "1 while any SLO rule is in breach")
    return fams


def ledger_metric_families(ledger):
    """Derived ledger -> run-scope metric families (the training-gang
    exporter's vocabulary)."""
    fams = []
    chip = Family("tpuflow_goodput_chip_seconds", "counter",
                  "Chip-seconds accounted, by goodput category")
    for cat in CATEGORIES:
        chip.add(ledger["categories"].get(cat, 0.0), {"category": cat})
    chip.add(ledger["unattributed_chip_s"], {"category": UNATTRIBUTED})
    fams.append(chip)
    fams.append(Family("tpuflow_goodput_coverage_ratio", "gauge",
                       "Attributed / observed chip-time")
                .add(ledger["coverage"]))
    fams.append(Family("tpuflow_goodput_fraction", "gauge",
                       "Productive chip-time / observed chip-time")
                .add(ledger["goodput_frac"]))
    fams.append(Family("tpuflow_goodput_wall_clock_seconds", "gauge",
                       "Wall-clock span of observed chip work")
                .add(ledger["wall_clock_s"]))
    lanes = Family("tpuflow_goodput_lanes", "gauge",
                   "Observed lanes (task-attempt-rank), by kind")
    by_kind = {}
    for lane in ledger["lanes"]:
        by_kind[lane["kind"]] = by_kind.get(lane["kind"], 0) + 1
    for kind in sorted(by_kind):
        lanes.add(by_kind[kind], {"kind": kind})
    if not by_kind:
        lanes.add(0, {"kind": "train"})
    fams.append(lanes)
    return fams


# ---------------------------------------------------------------------------
# run-scope exporter: a /metrics listener for training gangs
# ---------------------------------------------------------------------------


class RunMetricsExporter(object):
    """Scrape target for a training run: every GET /metrics re-derives
    the ledger from the run's persisted telemetry (records only append,
    so counter semantics hold across scrapes)."""

    def __init__(self, flow_datastore, run_id, host="127.0.0.1", port=0):
        from http.server import (BaseHTTPRequestHandler,
                                 ThreadingHTTPServer)

        exporter = self

        class _Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            server_version = "tpuflow-goodput/1"

            def log_message(self, fmt, *args):
                pass

            def do_GET(self):
                if self.path == "/metrics":
                    try:
                        body = exporter.render().encode("utf-8")
                    except Exception as ex:
                        body = json.dumps({"error": str(ex)}).encode()
                        self.send_response(500)
                        self.send_header("Content-Type",
                                         "application/json")
                        self.send_header("Content-Length",
                                         str(len(body)))
                        self.end_headers()
                        self.wfile.write(body)
                        return
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     OPENMETRICS_CONTENT_TYPE)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                body = json.dumps({"error": "not found"}).encode()
                self.send_response(404)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self._fds = flow_datastore
        self.run_id = str(run_id)
        self._httpd = ThreadingHTTPServer((host, port), _Handler)
        self._httpd.daemon_threads = True
        self._thread = None

    @property
    def host(self):
        return self._httpd.server_address[0]

    @property
    def port(self):
        return self._httpd.server_address[1]

    def render(self):
        ledger = derive_run_ledger(self._fds, self.run_id)
        return render_openmetrics(ledger_metric_families(ledger))

    def start(self):
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            name="tpuflow-goodput-exporter", daemon=True)
        self._thread.start()
        return self

    def close(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
