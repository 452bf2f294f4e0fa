"""Request-path distributed tracing: traceparent derivation, trace-tree
assembly from telemetry records alone, TTFT critical-path decomposition,
Perfetto export, and the acceptance e2e — a 2-replica in-process fleet
with a seeded chaos kill mid-stream yields ONE trace tree for the
request (router -> victim replica -> failover -> successor replica)."""

import http.client
import json
import os
import threading
import time

import jax
import pytest

from metaflow_tpu import telemetry, tracing
from metaflow_tpu.cmd.trace import (
    build_request_traces,
    perfetto_export,
    perfetto_export_timers,
    ttft_decomposition,
)
from metaflow_tpu.datastore import FlowDataStore, LocalStorage
from metaflow_tpu.elastic.policy import BackoffPolicy
from metaflow_tpu.models import llama
from metaflow_tpu.serving import (
    FleetConfig,
    Request,
    Scheduler,
    ServingFleet,
    ServingServer,
    SlotEngine,
)


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _post(port, payload, timeout=300):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", "/v1/generate", json.dumps(payload),
                 {"Content-Type": "application/json"})
    return conn, conn.getresponse()


class TestTraceparentHelpers:
    def test_request_traceparent_shape_and_determinism(self, monkeypatch):
        monkeypatch.delenv("TRACEPARENT", raising=False)
        tp = tracing.request_traceparent("req-1")
        assert tp == tracing.request_traceparent("req-1")
        trace_id, span_id = tracing.traceparent_ids(tp)
        assert len(trace_id) == 32 and len(span_id) == 16
        assert tp == "00-%s-%s-01" % (trace_id, span_id)
        # different request -> different trace AND span
        other = tracing.request_traceparent("req-2")
        assert tracing.traceparent_ids(other)[0] != trace_id

    def test_request_traceparent_joins_ambient_run_trace(self,
                                                         monkeypatch):
        run_tp = tracing.ensure_traceparent("run-seed")
        monkeypatch.setenv("TRACEPARENT", run_tp)
        tp = tracing.request_traceparent("req-1")
        # trace id comes from the run; span id stays request-derived
        assert tracing.traceparent_ids(tp)[0] == \
            tracing.traceparent_ids(run_tp)[0]
        monkeypatch.delenv("TRACEPARENT")
        solo = tracing.request_traceparent("req-1")
        assert tracing.traceparent_ids(solo)[1] == \
            tracing.traceparent_ids(tp)[1]

    def test_child_traceparent_same_trace_new_span(self):
        root = tracing.request_traceparent("req-9")
        c1 = tracing.child_traceparent(root, "dispatch-1")
        c2 = tracing.child_traceparent(root, "dispatch-2")
        t0, s0 = tracing.traceparent_ids(root)
        t1, s1 = tracing.traceparent_ids(c1)
        t2, s2 = tracing.traceparent_ids(c2)
        assert t0 == t1 == t2
        assert len({s0, s1, s2}) == 3
        # deterministic: the assembler can re-derive parentage
        assert c1 == tracing.child_traceparent(root, "dispatch-1")

    def test_traceparent_ids_malformed(self):
        assert tracing.traceparent_ids(None) == ("", "")
        assert tracing.traceparent_ids("") == ("", "")
        assert tracing.traceparent_ids("00-zz-1") == ("", "")
        assert tracing.traceparent_ids("00-%s" % ("a" * 32)) == ("", "")

    def test_trace_requests_enabled_env(self):
        assert tracing.trace_requests_enabled({}) is True
        assert tracing.trace_requests_enabled(
            {"TPUFLOW_TRACE_REQUESTS": "0"}) is False
        assert tracing.trace_requests_enabled(
            {"TPUFLOW_TRACE_REQUESTS": "1"}) is True


def _run_traced_requests(setup, tmp_path, n_requests=6, prefill_sleep=0.02):
    """Drive a single-server-style scheduler with traced requests and a
    live recorder; returns the persisted records."""
    cfg, params = setup
    fds = FlowDataStore("TraceTest", LocalStorage, ds_root=str(tmp_path))
    telemetry.init_recorder(fds, "1", "_serve", "trace-test")
    try:
        engine = SlotEngine(params, cfg, max_slots=2, max_seq_len=96,
                            prefill_chunk=16)
        # slow prefill so TTFT is dominated by spans the decomposition
        # measures (at tiny-model speed, emission jitter would swamp it)
        real_prefill = engine.prefill
        engine.prefill = \
            lambda plan: (time.sleep(prefill_sleep), real_prefill(plan))[1]
        sched = Scheduler(engine, max_queue=n_requests + 1)
        for i in range(n_requests):
            req = Request(list(range(1, 6 + i)), max_new_tokens=3, rng=i,
                          request_id="traced-%d" % i)
            req.traceparent = tracing.request_traceparent(req.id)
            sched.submit(req)
        sched.run_until_idle(100_000)
    finally:
        telemetry.close_recorder()
    return telemetry.read_run_records(fds, "1")


class TestTraceAssembly:
    def test_scheduler_records_carry_trace_context(self, setup, tmp_path):
        from schema_validate import validate_serving_record

        records = _run_traced_requests(setup, tmp_path)
        lifecycle = [r for r in records
                     if r["name"].startswith("serve.request.")]
        assert lifecycle
        for rec in lifecycle:
            validate_serving_record(rec)
            assert rec["data"]["trace"], rec["name"]
            assert rec["data"]["span"], rec["name"]

    def test_one_tree_per_request_with_decomposition(self, setup,
                                                     tmp_path):
        records = _run_traced_requests(setup, tmp_path)
        trees = build_request_traces(records)
        assert len(trees) == 6
        for tree in trees:
            assert tree["trace"] == tracing.traceparent_ids(
                tracing.request_traceparent(tree["request_id"]))[0]
            # no router: a single implicit attempt holds the lifecycle
            assert len(tree["attempts"]) == 1
            att = tree["attempts"][0]
            assert att["first_token"] is not None
            assert att["finished"] is not None
            d = ttft_decomposition(tree)
            assert d is not None
            assert d["first_decode_ms"] == 0.0
            assert d["measured_ttft_ms"] > 0
            # independent component measurements reconstruct the
            # measured TTFT to 5% (the slowed prefill keeps emission
            # jitter small beside the spans)
            assert d["err_pct"] <= 5.0, d

    def test_a_chunk_belongs_to_every_request_it_carried(self, setup,
                                                         tmp_path):
        """One serve.prefill_chunk record a prefill program: with two
        slots some carry two requests, and each request's tree holds
        the chunks that carried it, its own rows adding up to its
        prompt, under its own span."""
        records = _run_traced_requests(setup, tmp_path)
        chunks = [r for r in records if r["name"] == "serve.prefill_chunk"]
        assert any(r["data"]["rows"] == 2 for r in chunks)
        for rec in chunks:
            d = rec["data"]
            assert len(d["request_ids"]) == len(d["slots"]) \
                == len(d["row_tokens"]) == len(d["spans"]) == d["rows"]
            assert sum(d["row_tokens"]) == d["tokens"]
        for i, tree in enumerate(build_request_traces(records)):
            att = tree["attempts"][0]
            mine = [r for r in att["events"]
                    if r["name"] == "serve.prefill_chunk"]
            rows = [r["data"]["request_ids"].index(tree["request_id"])
                    for r in mine]
            assert sum(r["data"]["row_tokens"][row]
                       for r, row in zip(mine, rows)) == 5 + i
            assert {r["data"]["spans"][row]
                    for r, row in zip(mine, rows)} == {att["span"]}

    def test_perfetto_export_validates_and_covers_phases(self, setup,
                                                         tmp_path):
        from schema_validate import validate_perfetto_trace

        records = _run_traced_requests(setup, tmp_path, n_requests=2)
        trees = build_request_traces(records)
        doc = perfetto_export(trees)
        validate_perfetto_trace(doc)
        names = {e["name"] for e in doc["traceEvents"]}
        assert "prefill" in names and "first_token" in names
        # one pid per request, named after it
        procs = {e["args"]["name"] for e in doc["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert {"request traced-0", "request traced-1"} <= procs

    def test_perfetto_timer_fallback(self):
        from schema_validate import validate_perfetto_trace

        recs = [{"v": 1, "type": "timer", "name": "train.step", "ts": 10.0,
                 "ms": 25.0, "run_id": "1", "step": "train", "task_id": "t",
                 "attempt": 0, "rank": r, "host": "h", "pid": 1,
                 "step_num": 3} for r in (0, 1)]
        doc = perfetto_export_timers(recs)
        validate_perfetto_trace(doc)
        assert len([e for e in doc["traceEvents"] if e["ph"] == "X"]) == 2


class _FakeProc(object):
    """Popen shim around an in-process ServingServer replica (no
    send_signal, so fleet.kill_replica falls through to .kill())."""

    def __init__(self, server):
        self.server = server
        self.pid = os.getpid()
        self._rc = None

    def poll(self):
        return self._rc

    def kill(self):
        if self._rc is None:
            self._rc = -9
            self.server.close()

    def terminate(self):
        self.kill()

    def wait(self, timeout=None):
        return self._rc


def _make_spawner(setup, servers):
    cfg, params = setup
    build_lock = threading.Lock()

    def spawn(index, generation):
        with build_lock:
            eng = SlotEngine(params, cfg, max_slots=2, max_seq_len=96,
                             prefill_chunk=16)
            srv = ServingServer(Scheduler(eng), port=0).start()
        servers.append((index, generation, srv))
        return _FakeProc(srv), "127.0.0.1", srv.port

    return spawn


class TestFailoverTraceTree:
    def test_chaos_kill_yields_one_tree_across_replicas(self, setup,
                                                        tmp_path):
        """The acceptance pin: a seeded chaos kill mid-stream produces
        ONE per-request trace tree reconstructed from telemetry alone —
        router dispatch -> victim attempt (delivered prefix + failover)
        -> successor attempt (resume to finish) — all under one trace
        id, plus valid Perfetto JSON for it."""
        from schema_validate import validate_perfetto_trace

        from metaflow_tpu.devtools import chaos

        fds = FlowDataStore("TraceFleet", LocalStorage,
                            ds_root=str(tmp_path / "ds"))
        telemetry.init_recorder(fds, "1", "_serve", "fleet-trace")
        servers = []
        config = FleetConfig(
            failover=True, restart=False, health_interval_s=60.0,
            wait_s=2.0, redispatch_max=3, spawn_timeout_s=60.0,
            backoff=BackoffPolicy(base_s=0.05, cap_s=0.1, jitter=0.0,
                                  seed=0))
        fleet = ServingFleet(_make_spawner(setup, servers), 2,
                             config=config)
        fleet.start()
        try:
            # dispatch 1: pin a session so the victim is deterministic
            conn, resp = _post(fleet.port, {
                "tokens": [5, 6, 7], "max_new_tokens": 1,
                "session": "doomed"})
            victim = json.loads(resp.read())["replica"]
            conn.close()
            srv = [s for i, _g, s in servers if i == victim][-1]
            eng = srv.scheduler.engine
            real_decode = eng.decode_step
            eng.decode_step = \
                lambda: (time.sleep(0.05), real_decode())[1]
            # seeded kill: dispatch 3 kills the victim (dispatch 2 is
            # the streaming request below; dispatch 3 a trigger request)
            fleet.chaos = chaos.FleetChaosInjector(
                chaos.KillSchedule.parse("3:%d" % victim),
                ledger_dir=str(tmp_path / "chaos-ledger"))
            prompt, max_new = list(range(3, 11)), 16
            stream_result = {}

            def fire_stream():
                conn, resp = _post(fleet.port, {
                    "tokens": prompt, "max_new_tokens": max_new,
                    "stream": True, "session": "doomed",
                    "request_id": "trace-failover"})
                lines = [json.loads(l) for l in iter(resp.readline, b"")]
                conn.close()
                stream_result["status"] = resp.status
                stream_result["lines"] = lines

            t = threading.Thread(target=fire_stream)
            t.start()
            time.sleep(0.4)  # let dispatch 2 start streaming
            conn, resp = _post(fleet.port, {
                "tokens": [1, 2, 3], "max_new_tokens": 1})  # dispatch 3
            assert resp.status == 200
            conn.close()
            t.join(timeout=120)
            assert not t.is_alive()
            assert stream_result["status"] == 200
            lines = stream_result["lines"]
            assert lines[-1]["done"]
            assert [l["index"] for l in lines[:-1]] == list(range(max_new))
            assert fleet.failover_count >= 1
        finally:
            fleet.close()
            telemetry.close_recorder()

        records = telemetry.read_run_records(fds, "1")
        trees = [tr for tr in build_request_traces(records)
                 if tr["request_id"] == "trace-failover"]
        assert len(trees) == 1, "failover must NOT split the trace tree"
        tree = trees[0]
        root_tp = tracing.request_traceparent("trace-failover")
        assert tree["trace"] == tracing.traceparent_ids(root_tp)[0]
        assert tree["root_span"] == tracing.traceparent_ids(root_tp)[1]
        spanned = [a for a in tree["attempts"] if a["span"]]
        assert len(spanned) == 2, \
            "expected victim + successor dispatch attempts"
        first, second = spanned
        assert first["span"] != second["span"]
        assert first["replica"] == victim
        assert second["replica"] != victim
        # victim: delivered a prefix, then the failover event closed it
        assert first["failover"] is not None
        assert first["delivered"] and first["delivered"] > 0
        # successor: resumed and finished the SAME request
        assert second["failover"] is None
        assert second["finished"] is not None
        assert second["finished"]["data"]["reason"] == "length"
        # the chaos kill itself is on the record
        assert any(r["name"] == "chaos.replica_kill" for r in records)
        doc = perfetto_export([tree])
        validate_perfetto_trace(doc)
        # both attempts render as threads under the one request process
        tids = {e["tid"] for e in doc["traceEvents"] if e["ph"] == "X"}
        assert len(tids) >= 2
        assert any(e["ph"] == "i" and e["name"] == "failover"
                   for e in doc["traceEvents"])


class TestTraceCLI:
    def test_show_trace_writes_perfetto_and_json(self, setup, tmp_path):
        from schema_validate import validate_perfetto_trace

        from metaflow_tpu.cmd.trace import show_trace

        records = _run_traced_requests(setup, tmp_path / "ds",
                                       n_requests=2)
        assert records
        fds = FlowDataStore("TraceTest", LocalStorage,
                            ds_root=str(tmp_path / "ds"))
        out = tmp_path / "trace.json"
        lines = []
        n = show_trace(fds, "1", perfetto=str(out), echo=lines.append)
        assert n == 2
        validate_perfetto_trace(json.loads(out.read_text()))
        assert any("traced-0" in l for l in lines)
        # --request filters to one tree
        n = show_trace(fds, "1", request="traced-1", echo=lines.append)
        assert n == 1
        # --json emits machine-readable summaries with decomposition
        jlines = []
        show_trace(fds, "1", as_json=True, echo=jlines.append)
        docs = json.loads(jlines[-1])["requests"]
        assert {d["request_id"] for d in docs} == {"traced-0", "traced-1"}
        assert all(d["ttft"] is not None for d in docs)
