"""`tpuflow trace` — reassemble request trace trees from telemetry.

The serving stack stamps W3C trace context (trace id + span id) into
every `serve.request.*` / `fleet.request.*` record (scheduler.py::_tdata,
fleet.py::handle_generate), so this module can rebuild the full
queued -> dispatch -> prefill -> first_token -> decode -> finished /
failover tree for each request FROM THE PERSISTED RECORDS ALONE — no
collector, no sidecar, works after the fact on any finished or crashed
run. A request that failed over mid-stream shows up as one tree: the
victim's delivered-prefix attempt and the successor's resume attempt are
parented under the same request root because both carry the same trace
id and dispatch-derived child spans.

Also computes a TTFT critical-path decomposition per request
(router queue / replica queue / prefill / first decode) that must sum to
the measured TTFT — the decomposition the Gemma-on-TPU serving
comparison uses to attribute tail latency — and exports Chrome/Perfetto
trace-event JSON (`--perfetto out.json`; open in ui.perfetto.dev).

Train runs need no extra plumbing: `persist.*` / `checkpoint.*` /
`elastic.*` spans already tee into the recorder as timer records, so a
run with no serving requests exports those as Perfetto slices instead
(one process per step/task, one thread per rank).
"""

import json

from .. import telemetry

# event families that belong to a request's tree
_REQUEST_PREFIXES = ("serve.request.", "fleet.request.")

# a serve.prefill_chunk timer is one prefill program: it names the
# request of every row it carried (request_ids, with row_tokens and,
# where traced, spans beside it) and becomes a chunk-level child slice
# of the prefill phase of each
_CHUNK_TIMER = "serve.prefill_chunk"

# MPMD pipeline stages stamp a per-stage child span of the run
# traceparent into these (training/mpmd_trainer.py): the train-path
# analogue of a request subtree
_MPMD_TRANSFER = "mpmd.transfer"


def _data(rec):
    return rec.get("data") or {}


def _chunk_row(rec, request_id):
    """(span, tokens) of the row a prefill program carried for
    `request_id`."""
    d = _data(rec)
    row = d["request_ids"].index(request_id)
    spans = d.get("spans")
    return (spans[row] if spans else None) or None, d["row_tokens"][row]


def build_request_traces(records):
    """Group request-path records into per-request trace trees.

    Returns a list (request order of first appearance) of dicts:
      request_id, trace, root_span, events (ts-sorted),
      attempts: [{span, replica, dispatch, events, failover, finished,
                  first_token, delivered, status}]
    Works with tracing disabled too (span-less records collapse into a
    single implicit attempt), but cross-replica attribution then needs
    the spans the router stamped."""
    trees, order = {}, []
    records = sorted(records, key=lambda r: r.get("ts", 0))
    for rec in records:
        name = rec.get("name", "")
        is_chunk = name == _CHUNK_TIMER
        if not (name.startswith(_REQUEST_PREFIXES) or is_chunk):
            continue
        d = _data(rec)
        # a chunk belongs to every request its program carried
        rids = (d.get("request_ids") or ()) if is_chunk \
            else [r for r in (d.get("request_id"),) if r is not None]
        for rid in rids:
            tree = trees.get(rid)
            if tree is None:
                tree = trees[rid] = {
                    "request_id": rid, "trace": None, "root_span": None,
                    "events": [], "attempts": [], "shed": None,
                }
                order.append(rid)
            tree["events"].append(rec)
        if is_chunk or not rids:
            continue
        if d.get("trace") and not tree["trace"]:
            tree["trace"] = d["trace"]
        if name == "fleet.request.dispatch":
            if d.get("parent_span"):
                tree["root_span"] = d["parent_span"]
            tree["attempts"].append({
                "span": d.get("span"), "replica": d.get("replica"),
                "dispatch": d.get("dispatch"), "t_dispatch": rec.get("ts"),
                "events": [], "failover": None, "finished": None,
                "first_token": None, "delivered": None, "status": "open",
            })
    for tree in trees.values():
        _attach_events(tree)
    return [trees[rid] for rid in order]


def _attempt_for(tree, span):
    """The attempt a replica-side record belongs to: span match first,
    else the latest attempt (records land after their dispatch), else an
    implicit attempt for router-less single-server runs."""
    if span:
        for att in tree["attempts"]:
            if att["span"] == span:
                return att
    if tree["attempts"]:
        return tree["attempts"][-1]
    att = {"span": span, "replica": None, "dispatch": None,
           "t_dispatch": None, "events": [], "failover": None,
           "finished": None, "first_token": None, "delivered": None,
           "status": "open"}
    tree["attempts"].append(att)
    return att


def _attach_events(tree):
    for rec in tree["events"]:
        name = rec.get("name", "")
        d = _data(rec)
        if name == "fleet.request.dispatch":
            continue
        if name == "fleet.request.shed":
            tree["shed"] = rec
            continue
        span = (_chunk_row(rec, tree["request_id"])[0]
                if name == _CHUNK_TIMER else d.get("span"))
        att = _attempt_for(tree, span)
        att["events"].append(rec)
        if not tree["root_span"] and not name.startswith("fleet.") \
                and d.get("span"):
            # no router: the serve-side span IS the request root
            tree["root_span"] = d["span"]
        if name == "fleet.request.failover":
            att["failover"] = rec
            att["delivered"] = d.get("delivered")
            att["status"] = "failover"
        elif name == "serve.request.first_token":
            att["first_token"] = rec
        elif name in ("serve.request.finished",
                      "serve.request.cancelled"):
            att["finished"] = rec
            if att["status"] == "open":
                att["status"] = d.get("reason") or "finished"


def _first_named(events, name, span=None):
    for rec in events:
        if rec.get("name") != name:
            continue
        if span is not None and _data(rec).get("span") not in (None, span):
            continue
        return rec
    return None


def ttft_decomposition(tree):
    """Critical-path split of time-to-first-token for one request.

    Components are measured INDEPENDENTLY of each other (cross-event
    timestamp deltas + the scheduler's own queue_ms), so their sum
    agreeing with the measured TTFT is a real consistency check, not an
    identity:

      router_queue_ms  dispatch event -> replica queued event
      replica_queue_ms scheduler queue_ms (t_admit - t_submit)
      prefill_ms       prefill event -> first_token event
      first_decode_ms  0.0 by construction: this engine delivers the
                       first token from the FINAL PREFILL CHUNK
                       (scheduler._prefill), not from a decode step

    measured_ttft_ms is dispatch->first_token when a router was involved
    (client-perceived), else the scheduler's own ttft_ms. Returns None
    when the request never produced a first token."""
    first_tok = _first_named(tree["events"], "serve.request.first_token")
    if first_tok is None:
        return None
    span = _data(first_tok).get("span")
    queued = _first_named(tree["events"], "serve.request.queued", span)
    prefill = _first_named(tree["events"], "serve.request.prefill", span)
    dispatch = _first_named(tree["events"], "fleet.request.dispatch", span)
    if queued is None or prefill is None:
        return None
    router_queue_ms = (
        max(0.0, (queued["ts"] - dispatch["ts"]) * 1000)
        if dispatch is not None else 0.0)
    replica_queue_ms = float(_data(prefill).get(
        "queue_ms", (prefill["ts"] - queued["ts"]) * 1000))
    prefill_ms = max(0.0, (first_tok["ts"] - prefill["ts"]) * 1000)
    first_decode_ms = 0.0
    total = router_queue_ms + replica_queue_ms + prefill_ms \
        + first_decode_ms
    if dispatch is not None:
        measured = (first_tok["ts"] - dispatch["ts"]) * 1000
    else:
        measured = float(_data(first_tok).get("ttft_ms") or 0.0)
    err_pct = (abs(total - measured) / measured * 100
               if measured > 0 else 0.0)
    return {
        "request_id": tree["request_id"],
        "router_queue_ms": round(router_queue_ms, 3),
        "replica_queue_ms": round(replica_queue_ms, 3),
        "prefill_ms": round(prefill_ms, 3),
        "first_decode_ms": round(first_decode_ms, 3),
        "sum_ms": round(total, 3),
        "measured_ttft_ms": round(measured, 3),
        "err_pct": round(err_pct, 2),
    }


def build_stage_spans(records):
    """Per-stage MPMD transfer spans: one row per pipeline stage,
    aggregated over that stage's `mpmd.transfer` records (stamped with
    the stage's child span of the run traceparent). Returns a
    stage-ordered list of dicts; [] for runs without MPMD records."""
    stages = {}
    for rec in sorted(records, key=lambda r: r.get("ts", 0)):
        if rec.get("name") != _MPMD_TRANSFER:
            continue
        d = _data(rec)
        stage = int(d.get("stage", 0))
        row = stages.get(stage)
        if row is None:
            row = stages[stage] = {
                "stage": stage, "trace": d.get("trace"),
                "span": d.get("span"), "steps": 0, "stall_ms": 0.0,
                "frames_sent": 0, "frames_recv": 0,
                "bytes_sent": 0, "bytes_recv": 0,
                "t_first": rec.get("ts"), "t_last": rec.get("ts"),
            }
        row["steps"] += 1
        row["stall_ms"] += float(d.get("stall_ms") or 0.0)
        for key in ("frames_sent", "frames_recv",
                    "bytes_sent", "bytes_recv"):
            row[key] += int(d.get(key) or 0)
        row["t_last"] = rec.get("ts")
    out = [stages[s] for s in sorted(stages)]
    for row in out:
        row["stall_ms"] = round(row["stall_ms"], 3)
    return out


def render_stage_spans(spans, echo=print):
    echo("mpmd stage transfer spans:")
    for row in spans:
        line = ("  stage %d: %d step(s), stall %.1fms, "
                "%d frame(s) out / %d in, %d B out / %d B in"
                % (row["stage"], row["steps"], row["stall_ms"],
                   row["frames_sent"], row["frames_recv"],
                   row["bytes_sent"], row["bytes_recv"]))
        if row.get("span"):
            line += "  span=%s" % row["span"]
        echo(line)


# ---------------------------------------------------------------------------
# Chrome/Perfetto trace-event JSON
# ---------------------------------------------------------------------------


def _us(ts, t0):
    return round((ts - t0) * 1e6, 1)


def _slice(name, ts, dur_us, pid, tid, args=None):
    ev = {"name": name, "ph": "X", "ts": ts, "dur": max(1.0, dur_us),
          "pid": pid, "tid": tid}
    if args:
        ev["args"] = args
    return ev


def _meta(name, value, pid, tid):
    return {"name": name, "ph": "M", "ts": 0, "pid": pid, "tid": tid,
            "args": {"name": value}}


def perfetto_export(trees):
    """Trees -> Chrome trace-event JSON (one process per request, one
    thread per dispatch attempt). Entry shape is pinned as
    TRACE_RECORD_SCHEMA in tests/schema_validate.py."""
    out = []
    stamps = [r["ts"] for t in trees for r in t["events"] if "ts" in r]
    t0 = min(stamps) if stamps else 0.0
    for pid, tree in enumerate(trees, 1):
        evts = [r for r in tree["events"] if "ts" in r]
        if not evts:
            continue
        first, last = evts[0]["ts"], evts[-1]["ts"]
        out.append(_meta("process_name",
                         "request %s" % tree["request_id"], pid, 0))
        root_args = {"request_id": str(tree["request_id"])}
        if tree["trace"]:
            root_args["trace"] = tree["trace"]
        if tree["root_span"]:
            root_args["span"] = tree["root_span"]
        out.append(_slice("request %s" % tree["request_id"],
                          _us(first, t0), (last - first) * 1e6, pid, 0,
                          root_args))
        for tid, att in enumerate(tree["attempts"], 1):
            label = ("replica %s" % att["replica"]
                     if att["replica"] is not None else "serve")
            out.append(_meta("thread_name", label, pid, tid))
            a_evts = [r for r in att["events"] if "ts" in r]
            start = att["t_dispatch"] if att["t_dispatch"] is not None \
                else (a_evts[0]["ts"] if a_evts else first)
            end = a_evts[-1]["ts"] if a_evts else start
            args = {"status": att["status"]}
            if att["span"]:
                args["span"] = att["span"]
            if att["delivered"] is not None:
                args["delivered"] = att["delivered"]
            out.append(_slice("attempt %s" % (att["dispatch"] or 1),
                              _us(start, t0), (end - start) * 1e6,
                              pid, tid, args))
            out.extend(_phase_slices(att, a_evts, t0, pid, tid,
                                     tree["request_id"]))
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def _phase_slices(att, a_evts, t0, pid, tid, request_id):
    """queue / prefill / decode sub-slices + instants for one attempt."""
    out = []

    def ev(name):
        return _first_named(a_evts, name)

    queued, prefill = ev("serve.request.queued"), \
        ev("serve.request.prefill")
    first_tok, fin = att["first_token"], att["finished"]
    if queued and prefill:
        out.append(_slice("queue", _us(queued["ts"], t0),
                          (prefill["ts"] - queued["ts"]) * 1e6, pid, tid))
    if prefill and first_tok:
        out.append(_slice("prefill", _us(prefill["ts"], t0),
                          (first_tok["ts"] - prefill["ts"]) * 1e6,
                          pid, tid))
    for rec in a_evts:
        if rec.get("name") == _CHUNK_TIMER and rec.get("ms") is not None:
            out.append(_slice(
                "prefill_chunk",
                _us(rec["ts"], t0) - rec["ms"] * 1000, rec["ms"] * 1000,
                pid, tid,
                {"tokens": _chunk_row(rec, request_id)[1]}))
    if first_tok and fin:
        out.append(_slice("decode", _us(first_tok["ts"], t0),
                          (fin["ts"] - first_tok["ts"]) * 1e6, pid, tid,
                          {"new_tokens": _data(fin).get("new_tokens")}))
    if first_tok:
        out.append({"name": "first_token", "ph": "i",
                    "ts": _us(first_tok["ts"], t0), "pid": pid,
                    "tid": tid, "s": "t",
                    "args": {"ttft_ms": _data(first_tok).get("ttft_ms")}})
    if att["failover"]:
        out.append({"name": "failover", "ph": "i",
                    "ts": _us(att["failover"]["ts"], t0), "pid": pid,
                    "tid": tid, "s": "t",
                    "args": {"delivered": att["delivered"]}})
    return out


def perfetto_export_timers(records):
    """Fallback for runs with no serving requests: every timer record
    becomes a slice (process = step/task, thread = rank), so train-side
    persist.* / checkpoint.* / elastic.* spans open in Perfetto too."""
    timers = [r for r in records
              if r.get("type") == "timer" and r.get("ms") is not None]
    # MPMD transfer events render as stall slices on the stage's lane:
    # the interval the stage sat blocked on the transport, ending at the
    # record's timestamp
    transfers = [r for r in records
                 if r.get("name") == _MPMD_TRANSFER
                 and float(_data(r).get("stall_ms") or 0.0) > 0]
    out = []
    t0 = min((r["ts"] - r["ms"] / 1000.0 for r in timers), default=0.0)
    pids = {}

    def _pid(rec):
        key = "%s/%s" % (rec.get("step", "?"), rec.get("task_id", "?"))
        if key not in pids:
            pids[key] = len(pids) + 1
            out.append(_meta("process_name", key, pids[key], 0))
        return pids[key]

    for rec in timers:
        pid = _pid(rec)
        tid = int(rec.get("rank") or 0)
        out.append(_slice(rec.get("name", "span"),
                          _us(rec["ts"] - rec["ms"] / 1000.0, t0),
                          rec["ms"] * 1000, pid, tid,
                          _data(rec) or None))
    for rec in transfers:
        d = _data(rec)
        stall_ms = float(d.get("stall_ms") or 0.0)
        args = {"stage": d.get("stage"), "stall_ms": stall_ms}
        if d.get("span"):
            args["span"] = d["span"]
        out.append(_slice("mpmd.transfer_stall",
                          _us(rec["ts"] - stall_ms / 1000.0, t0),
                          stall_ms * 1000, _pid(rec),
                          int(rec.get("rank") or 0), args))
    return {"traceEvents": out, "displayTimeUnit": "ms"}


# ---------------------------------------------------------------------------
# rendering + entry point
# ---------------------------------------------------------------------------


def render_tree(tree, echo=print):
    head = "request %s" % tree["request_id"]
    if tree["trace"]:
        head += "  trace=%s" % tree["trace"][:16]
    echo(head)
    if tree["shed"] is not None:
        echo("  shed: %s" % _data(tree["shed"]).get("reason"))
    t_base = tree["events"][0]["ts"] if tree["events"] else 0.0
    for att in tree["attempts"]:
        where = ("replica %s" % att["replica"]
                 if att["replica"] is not None else "serve")
        line = "  attempt %s -> %s [%s]" % (
            att["dispatch"] or 1, where, att["status"])
        if att["status"] == "failover":
            line += " after %s token(s)" % (att["delivered"] or 0)
        elif att["finished"] is not None:
            line += ", %s token(s)" % _data(att["finished"]).get(
                "new_tokens")
        echo(line)
        for rec in att["events"]:
            name = rec.get("name", "").split(".")[-1]
            if rec.get("name") == _CHUNK_TIMER:
                name = "prefill_chunk(%s tok)" % _chunk_row(
                    rec, tree["request_id"])[1]
            echo("    +%8.1fms  %s" % ((rec["ts"] - t_base) * 1000, name))
    decomp = ttft_decomposition(tree)
    if decomp:
        echo("  ttft %.1fms = router %.1f + queue %.1f + prefill %.1f "
             "+ first_decode %.1f (sum %.1f, err %.1f%%)"
             % (decomp["measured_ttft_ms"], decomp["router_queue_ms"],
                decomp["replica_queue_ms"], decomp["prefill_ms"],
                decomp["first_decode_ms"], decomp["sum_ms"],
                decomp["err_pct"]))


def show_trace(flow_datastore, run_id, request=None, perfetto=None,
               as_json=False, echo=print):
    """CLI entry: assemble, render (or JSON-dump), optionally export.
    Returns the number of request trees rendered."""
    records = telemetry.read_run_records(flow_datastore, run_id)
    if not records:
        echo("no telemetry records for run %s" % run_id)
        return 0
    trees = build_request_traces(records)
    stage_spans = build_stage_spans(records) if request is None else []
    if request is not None:
        trees = [t for t in trees if str(t["request_id"]) == str(request)]
        if not trees:
            echo("no trace for request %s" % request)
            return 0
    if perfetto:
        doc = (perfetto_export(trees) if trees
               else perfetto_export_timers(records))
        with open(perfetto, "w") as f:
            json.dump(doc, f)
        echo("wrote %d trace events to %s"
             % (len(doc["traceEvents"]), perfetto))
    if not trees and not stage_spans:
        echo("no request traces in run %s (%d records; train-side timer "
             "spans export via --perfetto)" % (run_id, len(records)))
        return 0
    if as_json:
        payload = []
        for tree in trees:
            payload.append({
                "request_id": tree["request_id"],
                "trace": tree["trace"],
                "root_span": tree["root_span"],
                "attempts": [
                    {k: att[k] for k in ("span", "replica", "dispatch",
                                         "status", "delivered")}
                    for att in tree["attempts"]],
                "ttft": ttft_decomposition(tree),
            })
        doc = {"requests": payload}
        if stage_spans:
            doc["mpmd_stages"] = stage_spans
        echo(json.dumps(doc, indent=2, sort_keys=True))
    else:
        for tree in trees:
            render_tree(tree, echo)
        if stage_spans:
            render_stage_spans(stage_spans, echo)
    return len(trees)
