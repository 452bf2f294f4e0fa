"""Fleet-wide goodput ledger + OpenMetrics export: the pinned
chip-second categories, cross-subsystem ledger derivation (elastic resize
+ MPMD stage stall + serving trace reconciling to observed chip-time),
the `tpuflow goodput` CLI round-trip, the strict OpenMetrics writer/
parser pair, the pinned metric-name vocabularies, and the /metrics
endpoints on the replica server, the fleet router, and the run-scope
exporter — each cross-checked against the /v1/stats dict it renders
from."""

import http.client
import json

import pytest

import schema_validate as sv
from metaflow_tpu import goodput, telemetry
from metaflow_tpu.cmd.goodput import loss_verdict, show_goodput
from metaflow_tpu.datastore import FlowDataStore, LocalStorage


def _rec(name, rtype, ts, step="train", task_id="t0", attempt=0, rank=0,
         **kw):
    rec = {"v": 1, "type": rtype, "name": name, "ts": ts, "run_id": "1",
           "step": step, "task_id": task_id, "attempt": attempt,
           "rank": rank, "host": "h", "pid": 1}
    rec.update(kw)
    return rec


def _cross_subsystem_records():
    """The satellite fixture: an elastic 8->4 resize (kill at step 3,
    restore + replay of steps 2-3, a capacity park), an MPMD-style
    transfer stall on every steady step, a checkpoint snapshot, and a
    serving lane — every category is exercised at once.

    Hand-auditable totals (seconds of chip-time):
      attempt 0: 8 ranks x 4 steps x 10s            = 320
        step 0 is the compile                        ->  80 compile
        steps 1-3: 1s input + 0.5s transfer each     ->  24 input, 12 xfer
        rank 0 snapshot 2s (moved out of productive) ->   2 ckpt_blocked
      park while waiting for 4-chip capacity: 5s x 4 ->  20 capacity_wait
      attempt 1: 4 ranks x (3s restore + 5 steps x 10s) = 212
        steps 2-3 are at/below attempt 0's horizon   ->  80 replay (+12
                                                         restore = 92)
        steps 4-6: 1s input + 0.5s transfer each     ->  12 input, 6 xfer
      serve lane: 2 x 0.5s prefill + 10 x 0.2s decode over a 10s span
                                                     -> 1 + 2 + 7 idle
    """
    recs = []
    # attempt 0: 8-rank gang, steps 0..3, 10s dispatch-to-dispatch
    for rank in range(8):
        for num in range(4):
            end = 100.0 + 10.0 * (num + 1)
            data = ({"compile": True} if num == 0 else
                    {"input_stall_ms": 1000.0,
                     "transfer_stall_ms": 500.0})
            recs.append(_rec("train.step", "timer", end,
                             task_id="t%d" % rank, rank=rank,
                             ms=10_000.0, step_num=num, data=data))
    # rank 0 blocked 2s in the checkpoint snapshot (inside step 3)
    recs.append(_rec("checkpoint.snapshot", "timer", 135.0,
                     task_id="t0", rank=0, ms=2000.0, ok=True))
    # the kill: resize decision + a capacity park before the relaunch
    recs.append(_rec("elastic.resize", "event", 141.0, step="_control",
                     task_id="sup",
                     data={"pathspec": "F/1/train", "from_size": 8,
                           "to_size": 4, "direction": "shrink",
                           "attempt": 1, "oracle": "scripted"}))
    recs.append(_rec("elastic.backoff", "event", 142.0, step="_control",
                     task_id="sup",
                     data={"pathspec": "F/1/train",
                           "failure_class": "preemption", "attempt": 1,
                           "delay_s": 5.0, "waiting_for_capacity": True,
                           "world": 4}))
    # attempt 1: 4-rank gang restores and replays steps 2-3, then 4-6
    for rank in range(4):
        recs.append(_rec("checkpoint.restore", "timer", 203.0,
                         task_id="t1%d" % rank, attempt=1, rank=rank,
                         ms=3000.0, ok=True))
        for i, num in enumerate([2, 3, 4, 5, 6]):
            end = 203.0 + 10.0 * (i + 1)
            recs.append(_rec(
                "train.step", "timer", end, task_id="t1%d" % rank,
                attempt=1, rank=rank, ms=10_000.0, step_num=num,
                data={"input_stall_ms": 1000.0,
                      "transfer_stall_ms": 500.0}))
    # serving lane: busy 3s of a 10s span
    for i in range(2):
        recs.append(_rec("serve.prefill_chunk", "timer",
                         1000.5 + 0.5 * i, step="_serve", task_id="s0",
                         ms=500.0, ok=True))
    for i in range(10):
        recs.append(_rec("serve.decode_step", "timer",
                         1001.0 + 1.0 * i, step="_serve", task_id="s0",
                         ms=200.0, ok=True))
    # host bookkeeping that must NOT count as chip time
    recs.append(_rec("task.user_code", "timer", 300.0, ms=250_000.0,
                     ok=True))
    return recs


def _write_part(fds, run_id, records, name="train.t0.0.000000.jsonl"):
    """Land records in the run's _telemetry/ tree the way a recorder
    part-file flush would."""
    path = fds.storage.path_join(fds.flow_name, str(run_id),
                                 "_telemetry", name)
    payload = "\n".join(json.dumps(r) for r in records).encode("utf-8")
    fds.storage.save_bytes([(path, payload)], overwrite=True)


def _fds(tmp_path, flow="GoodputTest"):
    return FlowDataStore(flow, LocalStorage, ds_root=str(tmp_path))


class TestDeriveLedger:
    def test_categories_pinned(self):
        assert goodput.CATEGORIES == sv.GOODPUT_CATEGORIES
        assert goodput.UNATTRIBUTED == "unattributed"
        assert set(goodput.PRODUCTIVE_CATEGORIES) < set(goodput.CATEGORIES)

    def test_cross_subsystem_ledger_reconciles(self):
        ledger = goodput.derive_ledger(_cross_subsystem_records(),
                                       run_id="1")
        sv.validate_goodput_ledger(ledger)
        assert ledger["reconciled"]
        assert ledger["coverage"] >= 0.95
        cats = ledger["categories"]
        assert cats["compile"] == pytest.approx(80.0)
        assert cats["input_stall"] == pytest.approx(36.0)
        assert cats["transfer_stall"] == pytest.approx(18.0)
        assert cats["checkpoint_blocked"] == pytest.approx(2.0)
        assert cats["restore_replay"] == pytest.approx(92.0)
        assert cats["capacity_wait"] == pytest.approx(20.0)
        assert cats["serve_prefill"] == pytest.approx(1.0)
        assert cats["serve_decode"] == pytest.approx(2.0)
        assert cats["serve_idle"] == pytest.approx(7.0)
        # productive = steady steps minus splits minus the moved snapshot
        assert cats["productive_step"] == pytest.approx(304.0)
        # observed = 8x4x10 + 4x(3 + 5x10) + 10 serve + 20 parked
        assert ledger["observed_chip_s"] == pytest.approx(562.0)
        # recovery overhead dominates the losses, as the kill schedule
        # dictates — the verdict names it
        assert ledger["dominant_loss"] == "restore_replay"
        assert "restore" in loss_verdict(ledger)
        # the park is itemized per attempt
        assert ledger["parked"] == [
            {"pathspec": "F/1/train", "attempt": 1, "delay_s": 5.0,
             "world": 4}]

    def test_lanes_keyed_per_rank_attempt(self):
        ledger = goodput.derive_ledger(_cross_subsystem_records())
        # 8 attempt-0 lanes + 4 attempt-1 lanes + 1 serve lane; the
        # host-envelope timer (task.user_code) creates NO lane
        assert len(ledger["lanes"]) == 13
        kinds = {lane["kind"] for lane in ledger["lanes"]}
        assert kinds == {"train", "serve"}
        serve = [l for l in ledger["lanes"] if l["kind"] == "serve"]
        assert serve[0]["categories"]["serve_idle"] == pytest.approx(7.0)

    def test_host_envelopes_do_not_count(self):
        """task.user_code / persist timers are host bookkeeping: alone
        they produce an empty ledger, not phantom chip-time."""
        recs = [_rec("task.user_code", "timer", 100.0, ms=60_000.0,
                     ok=True),
                _rec("persist.artifacts", "timer", 101.0, ms=5000.0,
                     ok=True)]
        ledger = goodput.derive_ledger(recs)
        assert ledger["observed_chip_s"] == 0.0
        assert ledger["lanes"] == []
        assert ledger["reconciled"]

    def test_unattributed_bucket_and_unreconciled_exit(self, tmp_path):
        """A lane whose span dwarfs its attributable work lands in the
        explicit unattributed bucket and fails reconciliation — and the
        CLI exits non-zero on it."""
        recs = [
            _rec("train.step", "timer", 100.0, ms=10_000.0, step_num=0,
                 data={}),
            # a batch_wait 90s later extends the lane span; with step
            # records present it is NOT re-attributed (the step records
            # already carry input_stall_ms), so the gap is unattributed
            _rec("data.batch_wait", "timer", 200.0, ms=10_000.0,
                 ok=True),
        ]
        ledger = goodput.derive_ledger(recs)
        sv.validate_goodput_ledger(ledger)
        assert not ledger["reconciled"]
        assert ledger["dominant_loss"] == "unattributed"
        assert ledger["unattributed_chip_s"] == pytest.approx(100.0)
        fds = _fds(tmp_path)
        _write_part(fds, "9", recs)
        assert show_goodput(fds, "9", echo=lambda *_: None) == 1

    def test_batch_wait_attributed_without_step_records(self):
        """A pure input lane (no instrumented steps) charges its waits
        to input_stall instead of unattributed."""
        recs = [_rec("data.batch_wait", "timer", 100.0 + i, ms=1000.0,
                     ok=True) for i in range(5)]
        ledger = goodput.derive_ledger(recs)
        assert ledger["categories"]["input_stall"] == pytest.approx(5.0)
        assert ledger["reconciled"]

    def test_cli_json_roundtrip(self, tmp_path):
        fds = _fds(tmp_path)
        _write_part(fds, "1", _cross_subsystem_records())
        lines = []
        assert show_goodput(fds, "1", as_json=True,
                            echo=lines.append) == 0
        doc = json.loads("\n".join(lines))
        sv.validate_goodput_ledger(doc)
        assert doc == goodput.derive_ledger(
            telemetry.read_run_records(fds, "1"), run_id="1")
        # text mode renders every populated category + the verdict
        lines = []
        assert show_goodput(fds, "1", echo=lines.append) == 0
        text = "\n".join(lines)
        assert "restore + replayed work" in text
        assert "capacity wait" in text
        assert "verdict" in text

    def test_persist_and_load(self, tmp_path):
        fds = _fds(tmp_path)
        _write_part(fds, "1", _cross_subsystem_records())
        ledger = goodput.derive_run_ledger(fds, "1", persist=True)
        assert goodput.load_ledger(fds, "1") == ledger
        assert goodput.load_ledger(fds, "no-such-run") is None
        # the persisted document round-trips through the pinned schema
        sv.validate_goodput_ledger(goodput.load_ledger(fds, "1"))

    def test_no_records_exits_nonzero(self, tmp_path):
        assert show_goodput(_fds(tmp_path), "none",
                            echo=lambda *_: None) == 1


class TestTrainGoodputInterval:
    def test_interval_payload_schema_and_sums(self):
        from metaflow_tpu.training.metrics import TrainStepTelemetry

        tel = TrainStepTelemetry(profile=False)
        tel._intervals.extend([0.5, 0.5, 0.5])
        tel._stalls.extend([0.05, 0.05, 0.05])
        tel._update_ms.extend([20.0, 20.0, 20.0])
        tel._transfer_ms.extend([10.0, 10.0, 10.0])
        tel.compile_ms = 800.0
        interval = tel._goodput_interval()
        rec = _rec("goodput.interval", "event", 100.0, data=interval)
        sv.validate_goodput_interval_record(rec)
        cats = interval["categories"]
        assert sum(cats.values()) == pytest.approx(interval["span_s"],
                                                   abs=0.01)
        assert cats["productive_step"] == pytest.approx(1.26, abs=0.01)
        assert cats["compile"] == pytest.approx(0.8)

    def test_no_steps_no_event(self):
        from metaflow_tpu.training.metrics import TrainStepTelemetry

        assert TrainStepTelemetry(profile=False)._goodput_interval() \
            is None


class TestOpenMetricsFormat:
    def test_render_parse_roundtrip(self):
        fams = [
            goodput.Family("app_requests", "counter", "Requests served")
            .add(5, {"outcome": "ok"}).add(2, {"outcome": "err"}),
            goodput.Family("app_depth", "gauge", "Queue depth").add(3),
            goodput.Family("app_lat_ms", "summary", "Latency")
            .add(1.5, {"quantile": "0.5"}).add(9.25, {"quantile": "0.99"}),
            goodput.Family("app_note", "gauge",
                           'has "quotes" and\nnewline')
            .add(1, {"label": 'va"l\\ue\n'}),
        ]
        text = goodput.render_openmetrics(fams)
        assert text.endswith("# EOF\n")
        parsed = goodput.parse_openmetrics(text)
        assert parsed["app_requests"]["type"] == "counter"
        assert [(l["outcome"], v) for _n, l, v
                in parsed["app_requests"]["samples"]] \
            == [("ok", 5.0), ("err", 2.0)]
        assert parsed["app_depth"]["samples"] == [("app_depth", {}, 3.0)]
        assert [v for _n, _l, v in parsed["app_lat_ms"]["samples"]] \
            == [1.5, 9.25]
        assert parsed["app_note"]["samples"][0][1]["label"] \
            == 'va"l\\ue\n'

    def test_counter_samples_get_total_suffix(self):
        text = goodput.render_openmetrics(
            [goodput.Family("x_requests", "counter").add(1)])
        assert "x_requests_total 1" in text

    @pytest.mark.parametrize("bad, why", [
        ("# TYPE a gauge\na 1\n", "missing # EOF"),
        ("# TYPE a gauge\na 1\n# EOF", "missing trailing newline"),
        ("a 1\n# EOF\n", "sample before any TYPE"),
        ("# TYPE a counter\na 1\n# EOF\n", "counter without _total"),
        ("# TYPE a gauge\n# TYPE a gauge\n# EOF\n", "duplicate family"),
        ("# TYPE a gauge\n# TYPE b gauge\na 1\n# EOF\n",
         "interleaved sample"),
        ("# TYPE a counter\na_total -1\n# EOF\n", "negative counter"),
        ("# TYPE a summary\na 1\n# EOF\n", "summary missing quantile"),
        ("# TYPE a gauge\n\na 1\n# EOF\n", "blank line"),
        ("# TYPE a gauge\na zebra\n# EOF\n", "unparseable value"),
        ("# TYPE a gauge\na{k=\"v} 1\n# EOF\n", "unterminated label"),
        ("# HELP a text\n# TYPE a gauge\n# EOF\n",
         "HELP before its TYPE"),
    ])
    def test_strict_parser_rejects(self, bad, why):
        with pytest.raises(ValueError):
            goodput.parse_openmetrics(bad)
        assert why  # the parametrization is self-documenting


def _scheduler_stats():
    """A fully-featured Scheduler.stats() shape (every conditional
    block enabled) — the keys the real scheduler serves on /v1/stats."""
    return {
        "queue_depth": 2, "in_flight": 3, "slots": 4, "occupancy": 0.75,
        "mean_batch_occupancy": 0.6, "served": 11, "cancelled": 1,
        "decode_steps": 40, "prefill_programs": 9, "prefill_rows": 14,
        "prefill_tokens": 500, "iterations": 55, "draining": False,
        "attention_positions_needed": 700,
        "attention_positions_fetched": 1024,
        "p50_ttft_ms": 12.0, "p99_ttft_ms": 30.0,
        "p50_itl_ms": 3.0, "p99_itl_ms": 8.0,
        "peak_in_flight": 4, "max_context_tokens": 96,
        "prefix_cache": {"enabled": True, "hits": 6, "misses": 4,
                         "hit_rate": 0.6, "hit_tokens": 120,
                         "prompt_tokens": 200,
                         "prefill_tokens_skipped_frac": 0.6},
        "kv_pages": {"enabled": True, "pages_total": 64,
                     "pages_free": 16, "occupancy": 0.75,
                     "shared_pages": 8, "cow_pages": 2, "exhausted": 1},
        "speculative": {"enabled": True, "k": 2, "accept_rate": 0.9},
        "cache_pools": {
            "global": {"bytes": 4096, "bytes_per_slot": 1024},
            "ring": {"bytes": 2048, "bytes_per_slot": 512},
            "state": {"bytes": 512, "bytes_per_slot": 128}},
        "goodput": {"serve_prefill_s": 1.5, "serve_decode_s": 4.0,
                    "serve_idle_s": 2.5, "elapsed_s": 8.0},
        "phases": {"iterations": 55, "phase": {
            "serve.iteration": {"seconds": 6.0, "calls": 55},
            "engine.decode.fetch": {"seconds": 3.5, "calls": 40},
            "wait": {"seconds": 1.9, "calls": 90}},
            "device_wait_s": 3.5, "host_work_s": 2.5, "no_work_s": 1.9,
            "loop_s": 8.0},
        "gc": {"0": {"seconds": 0.004, "collections": 30},
               "2": {"seconds": 0.11, "collections": 1}},
    }


def _fleet_stats_healthz():
    stats = {
        "replicas": 2, "dispatched": 9, "completed": 8, "failovers": 1,
        "shed": 1, "restarts": 1, "inflight": 1, "max_inflight": 16,
        "draining": False, "fleet_generation": 2,
        "prefill_handoffs": 3, "disagg_fallbacks": 1,
        "scale_outs": 1, "scale_ins": 0,
    }
    healthz = {
        "replicas": [{"state": "ready"}, {"state": "ready"},
                     {"state": "backoff"}],
        "kv_pages": {"enabled": True, "pages_total": 128,
                     "pages_free": 100, "occupancy": 0.22,
                     "shared_pages": 4, "cow_pages": 0},
        "prefix_cache": {"enabled": True, "hit_rate": 0.4},
        "p99_ttft_ms": 25.0, "p99_itl_ms": 6.0,
        "slo": {"breached": False, "breaches": []},
    }
    return stats, healthz


class TestMetricFamilies:
    def test_scheduler_vocabulary_and_agreement(self):
        stats = _scheduler_stats()
        text = goodput.render_openmetrics(
            goodput.scheduler_metric_families(stats))
        parsed = goodput.parse_openmetrics(text)
        sv.validate_openmetrics_families(parsed,
                                         sv.OPENMETRICS_SERVE_METRICS)
        # every conditional family present when its subsystem is on
        assert set(parsed) == set(sv.OPENMETRICS_SERVE_METRICS)

        def sample(fam, **labels):
            for _n, l, v in parsed[fam]["samples"]:
                if all(l.get(k) == want for k, want in labels.items()):
                    return v
            raise AssertionError("no %s sample %r" % (fam, labels))

        assert sample("tpuflow_serve_queue_depth") == 2
        assert sample("tpuflow_serve_requests", outcome="served") == 11
        assert sample("tpuflow_serve_ttft_ms", quantile="0.99") == 30.0
        assert sample("tpuflow_serve_kv_pages", state="used") == 48
        assert sample("tpuflow_serve_cache_pool_bytes", kind="ring") == 2048
        assert sample("tpuflow_serve_attention_positions",
                      count="fetched") == 1024
        assert sample("tpuflow_serve_cache_pool_bytes_per_slot",
                      kind="global") == 1024
        assert sample("tpuflow_serve_goodput_seconds",
                      category="serve_decode") == 4.0
        assert sample("tpuflow_serve_phase_seconds",
                      phase="engine.decode.fetch") == 3.5
        assert sample("tpuflow_serve_phase_calls", phase="wait") == 90
        assert sample("tpuflow_serve_gc_pause_seconds",
                      generation="2") == 0.11

    def test_scheduler_conditional_families_absent(self):
        stats = _scheduler_stats()
        stats["prefix_cache"] = {"enabled": False}
        stats["kv_pages"] = {"enabled": False}
        stats["speculative"] = {"enabled": False}
        del stats["goodput"]
        parsed = goodput.parse_openmetrics(goodput.render_openmetrics(
            goodput.scheduler_metric_families(stats)))
        sv.validate_openmetrics_families(parsed,
                                         sv.OPENMETRICS_SERVE_METRICS)
        assert "tpuflow_serve_kv_pages" not in parsed
        assert "tpuflow_serve_prefix_hit_rate" not in parsed
        assert "tpuflow_serve_goodput_seconds" not in parsed

    def test_fleet_vocabulary_and_agreement(self):
        stats, healthz = _fleet_stats_healthz()
        parsed = goodput.parse_openmetrics(goodput.render_openmetrics(
            goodput.fleet_metric_families(stats, healthz)))
        sv.validate_openmetrics_families(parsed,
                                         sv.OPENMETRICS_FLEET_METRICS)
        assert set(parsed) == set(sv.OPENMETRICS_FLEET_METRICS)
        samples = {(_n, tuple(sorted(l.items()))): v
                   for fam in parsed.values()
                   for _n, l, v in fam["samples"]}
        assert samples[("tpuflow_fleet_requests_total",
                        (("outcome", "shed"),))] == 1
        assert samples[("tpuflow_fleet_replicas",
                        (("state", "ready"),))] == 2
        assert samples[("tpuflow_fleet_replicas",
                        (("state", "backoff"),))] == 1

    def test_ledger_vocabulary(self):
        ledger = goodput.derive_ledger(_cross_subsystem_records())
        parsed = goodput.parse_openmetrics(goodput.render_openmetrics(
            goodput.ledger_metric_families(ledger)))
        sv.validate_openmetrics_families(parsed,
                                         sv.OPENMETRICS_RUN_METRICS)
        chip = {l["category"]: v for _n, l, v
                in parsed["tpuflow_goodput_chip_seconds"]["samples"]}
        # every category bucket present, incl. the explicit remainder
        assert set(chip) == set(sv.GOODPUT_ALL_BUCKETS)
        assert sum(chip.values()) \
            == pytest.approx(ledger["observed_chip_s"], rel=1e-3)


class TestRunExporter:
    def test_scrape_parses_and_matches_ledger(self, tmp_path):
        fds = _fds(tmp_path)
        _write_part(fds, "1", _cross_subsystem_records())
        exporter = goodput.RunMetricsExporter(fds, "1").start()
        try:
            conn = http.client.HTTPConnection(exporter.host,
                                              exporter.port, timeout=30)
            conn.request("GET", "/metrics")
            resp = conn.getresponse()
            assert resp.status == 200
            assert resp.getheader("Content-Type") \
                == goodput.OPENMETRICS_CONTENT_TYPE
            parsed = goodput.parse_openmetrics(
                resp.read().decode("utf-8"))
            conn.request("GET", "/nope")
            assert conn.getresponse().status == 404
            conn.close()
        finally:
            exporter.close()
        sv.validate_openmetrics_families(parsed,
                                         sv.OPENMETRICS_RUN_METRICS)
        ledger = goodput.derive_run_ledger(fds, "1")
        cov = parsed["tpuflow_goodput_coverage_ratio"]["samples"][0][2]
        assert cov == pytest.approx(ledger["coverage"])


@pytest.fixture(scope="module")
def serve_setup():
    import jax

    from metaflow_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _get(port, path, timeout=60):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


class TestReplicaMetricsEndpoint:
    def test_metrics_agrees_with_v1_stats(self, serve_setup):
        from metaflow_tpu.serving import (Request, Scheduler,
                                          ServingServer, SlotEngine)

        cfg, params = serve_setup
        engine = SlotEngine(params, cfg, max_slots=2, max_seq_len=64,
                            prefill_chunk=16)
        sched = Scheduler(engine)
        sched.submit(Request(list(range(1, 9)), max_new_tokens=4, rng=0))
        sched.run_until_idle(100_000)
        srv = ServingServer(sched, port=0).start()
        try:
            status, headers, body = _get(srv.port, "/metrics")
            assert status == 200
            assert headers["Content-Type"] \
                == goodput.OPENMETRICS_CONTENT_TYPE
            parsed = goodput.parse_openmetrics(body.decode("utf-8"))
            _status, _h, stats_body = _get(srv.port, "/v1/stats")
            stats = json.loads(stats_body)
        finally:
            srv.close()
        sv.validate_openmetrics_families(parsed,
                                         sv.OPENMETRICS_SERVE_METRICS)

        def only(fam, **labels):
            hits = [v for _n, l, v in parsed[fam]["samples"]
                    if all(l.get(k) == want
                           for k, want in labels.items())]
            assert len(hits) == 1
            return hits[0]

        # structural agreement: both endpoints render the same stats()
        assert only("tpuflow_serve_slots") == stats["slots"]
        assert only("tpuflow_serve_requests", outcome="served") \
            == stats["served"]
        assert only("tpuflow_serve_decode_steps") \
            == stats["decode_steps"]
        for count in ("programs", "rows", "tokens"):
            assert only("tpuflow_serve_prefill", count=count) \
                == stats["prefill_" + count]
        assert only("tpuflow_serve_ttft_ms", quantile="0.99") \
            == pytest.approx(stats["p99_ttft_ms"] or 0.0)
        # the serve-side goodput tally rides the same stats dict
        gp = stats["goodput"]
        assert gp["serve_decode_s"] > 0
        assert gp["elapsed_s"] >= gp["serve_prefill_s"] \
            + gp["serve_decode_s"]
        assert only("tpuflow_serve_goodput_seconds",
                    category="serve_decode") \
            == pytest.approx(gp["serve_decode_s"])


class TestFleetMetricsEndpoint:
    def test_metrics_agrees_with_v1_stats(self, serve_setup):
        import os
        import threading

        from metaflow_tpu.elastic.policy import BackoffPolicy
        from metaflow_tpu.serving import (FleetConfig, Scheduler,
                                          ServingFleet, ServingServer,
                                          SlotEngine)

        cfg, params = serve_setup
        build_lock = threading.Lock()

        class _FakeProc(object):
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

            terminate = kill

            def wait(self, timeout=None):
                return self._rc

        def spawn(index, generation):
            with build_lock:
                eng = SlotEngine(params, cfg, max_slots=2,
                                 max_seq_len=64, prefill_chunk=16)
                srv = ServingServer(Scheduler(eng), port=0).start()
            return _FakeProc(srv), "127.0.0.1", srv.port

        config = FleetConfig(
            failover=False, restart=False, health_interval_s=60.0,
            wait_s=2.0, spawn_timeout_s=120.0,
            backoff=BackoffPolicy(base_s=0.05, cap_s=0.1, jitter=0.0,
                                  seed=0))
        fleet = ServingFleet(spawn, 1, config=config)
        fleet.start()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", fleet.port,
                                              timeout=120)
            conn.request("POST", "/v1/generate", json.dumps(
                {"tokens": list(range(1, 9)), "max_new_tokens": 3}),
                {"Content-Type": "application/json"})
            assert conn.getresponse().status == 200
            conn.close()
            status, headers, body = _get(fleet.port, "/metrics")
            assert status == 200
            assert headers["Content-Type"] \
                == goodput.OPENMETRICS_CONTENT_TYPE
            parsed = goodput.parse_openmetrics(body.decode("utf-8"))
            _s, _h, stats_body = _get(fleet.port, "/v1/stats")
            stats = json.loads(stats_body)
        finally:
            fleet.close()
        sv.validate_openmetrics_families(parsed,
                                         sv.OPENMETRICS_FLEET_METRICS)
        samples = {(n, tuple(sorted(l.items()))): v
                   for fam in parsed.values()
                   for n, l, v in fam["samples"]}
        assert samples[("tpuflow_fleet_requests_total",
                        (("outcome", "dispatched"),))] \
            == stats["dispatched"]
        assert samples[("tpuflow_fleet_requests_total",
                        (("outcome", "completed"),))] \
            == stats["completed"] >= 1
        assert samples[("tpuflow_fleet_generation", ())] \
            == stats["fleet_generation"]
        assert samples[("tpuflow_fleet_replicas",
                        (("state", "ready"),))] == 1
