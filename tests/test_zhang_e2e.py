"""Gang hang watchdog — end-to-end layer (real gangs, real wedged
ranks; named to sort last so the fast unit tiers run first).

The seeded-hang gate: a rank that sleeps forever at a step boundary
(TPUFLOW_CHAOS=step:rank:hang) keeps heartbeating but stops making
progress; the watchdog flags it off the per-rank progress beats within
the deadline, dumps all-thread stacks into `_telemetry/hangs/`, kills
the gang, and the elastic supervisor resumes from checkpoint — the
flow's own `end` step asserts the loss trajectory and token order are
EXACTLY the uninterrupted run's. Plus the false-positive guards (a
bounded `:slow` straggler and a clean watchdog-on run emit zero hang
events).
"""

import json
import os
import re
import sys


sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from metaflow_tpu import telemetry
from metaflow_tpu.datastore import FlowDataStore, LocalStorage

import jsonschema

from schema_validate import (
    HANG_REPORT_SCHEMA,
    validate_elastic_record,
)

FLOWS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "flows")

# watchdog knobs for CI: a progress deadline floor that a step of this
# flow (numpy and one small checkpoint save) stays under on a box loaded
# by five other test workers, yet short enough to wait out once; a
# first-step grace that covers an attempt's imports and first save on
# such a box (the wedge comes at step 3, after it, and never ends, so
# the floor is what catches it and the grace costs no time); 0.5s poll,
# unthrottled beats (every step stamps), short kill grace; the MPMD
# transfer deadlines under the floor, as knobs.ORDERING asks
FAST_WATCHDOG = {
    "TPUFLOW_HANG_FLOOR_S": "10",
    "TPUFLOW_HANG_POLL_S": "0.5",
    "TPUFLOW_HANG_COMPILE_GRACE_S": "120",
    "TPUFLOW_MPMD_RECV_TIMEOUT_S": "10",
    "TPUFLOW_MPMD_CONNECT_TIMEOUT_S": "10",
    "TPUFLOW_HANG_KILL_GRACE_S": "2",
    "TPUFLOW_HANG_DUMP_WAIT_S": "0.3",
    "TPUFLOW_PROGRESS_EVERY_S": "0",
    "TPUFLOW_RETRY_BACKOFF_BASE_S": "0.05",
}


def _fds(tpuflow_root):
    return FlowDataStore("HangChaosFlow", LocalStorage,
                         ds_root=tpuflow_root, blob_cache=False)


def _run_records(tpuflow_root, run_id):
    return telemetry.read_run_records(_fds(tpuflow_root), run_id)


def _run_id_of(out):
    m = re.search(r"run-id (\d+)", out)
    assert m, out
    return m.group(1)


def _load_artifact(fds, path):
    with fds.storage.load_bytes([path]) as loaded:
        for _p, local, _m in loaded:
            assert local is not None, path
            with open(local, "rb") as f:
                return f.read()


class TestSeededHangE2E:
    def test_hang_detect_forensics_kill_resume(self, run_flow,
                                               tpuflow_root, tmp_path):
        """4 ranks; rank 1 wedges at step 3 with a live heartbeat. The
        watchdog must detect the stall, upload per-rank stacks + a
        report bundle, kill the gang, and the elastic retry must finish
        the run token-exact (the flow asserts the exact trajectory)."""
        env = dict(FAST_WATCHDOG)
        env.update({
            "TPUFLOW_CHAOS": "3:1:hang",
            "TPUFLOW_CHAOS_DIR": str(tmp_path / "chaos"),
            "HANG_FLOW_RANKS": "4",
            "HANG_FLOW_STEPS": "8",
            "HANG_FLOW_SLEEP": "0.05",
        })
        proc = run_flow(
            os.path.join(FLOWS, "hang_chaos_flow.py"), "run",
            env_extra=env)
        out = proc.stdout + proc.stderr
        # the flow only prints this after its exact-replay asserts pass
        assert "hang run ok" in out, out
        assert "HANG detected" in out, out
        run_id = _run_id_of(out)

        records = _run_records(tpuflow_root, run_id)
        by_name = {}
        for r in records:
            by_name.setdefault(r.get("name"), []).append(r)

        # exactly one injected hang, exactly one detection, no kills
        hangs = by_name.get("chaos.hang", [])
        assert len(hangs) == 1, hangs
        assert hangs[0]["data"] == {"step": 3, "rank": 1, "world": 4}
        detections = by_name.get("hang.detected", [])
        assert len(detections) == 1, detections
        det = detections[0]["data"]
        assert det["laggard_rank"] == 1, det
        assert det["world"] == 4, det
        assert det["progress_age_s"] > det["deadline_s"] > 0, det
        for r in hangs + detections:
            validate_elastic_record(r)

        # the retry rode the elastic budget under the hang class
        backoffs = [r for r in by_name.get("elastic.backoff", [])
                    if r["data"]["failure_class"] == "hang"]
        assert backoffs, by_name.get("elastic.backoff")
        for r in backoffs:
            validate_elastic_record(r)

        # forensics bundle: report.json (pinned schema, laggard named)
        # plus at least the wedged rank's stack dump, whose traceback
        # shows the chaos _hang frame the rank is sleeping in
        fds = _fds(tpuflow_root)
        artifacts = telemetry.list_run_hangs(fds, run_id)
        assert det["forensics"] in artifacts, (det, artifacts)
        report = json.loads(_load_artifact(fds, det["forensics"]))
        jsonschema.validate(report, HANG_REPORT_SCHEMA,
                            cls=jsonschema.Draft202012Validator)
        assert report["laggard_rank"] == 1
        laggard_rows = [r for r in report["ranks"] if r["laggard"]]
        assert len(laggard_rows) == 1 and laggard_rows[0]["rank"] == 1
        stack_paths = [r["stacks"] for r in report["ranks"]
                       if r["stacks"]]
        assert stack_paths, report
        laggard_stacks = None
        for rel in stack_paths:
            full = [p for p in artifacts if p.endswith(rel)]
            assert full, (rel, artifacts)
            text = _load_artifact(fds, full[0]).decode(
                "utf-8", "replace")
            assert "Thread" in text or "Stack" in text, text[:400]
            if rel == laggard_rows[0]["stacks"]:
                laggard_stacks = text
        assert laggard_stacks is not None, report
        assert "_hang" in laggard_stacks, laggard_stacks[:2000]

    def test_slow_straggler_is_not_a_hang(self, run_flow, tpuflow_root,
                                          tmp_path):
        """False-positive guard: a bounded `:slow` straggler (1s delay
        under a 10s deadline floor) must NOT trip the watchdog — the run
        completes with zero hang events and one chaos.slow record."""
        env = dict(FAST_WATCHDOG)
        env.update({
            "TPUFLOW_CHAOS": "3:1:slow",
            "TPUFLOW_CHAOS_SLOW_S": "1.0",
            "TPUFLOW_CHAOS_DIR": str(tmp_path / "chaos"),
            "HANG_FLOW_RANKS": "2",
            "HANG_FLOW_STEPS": "6",
            "HANG_FLOW_SLEEP": "0.05",
        })
        proc = run_flow(
            os.path.join(FLOWS, "hang_chaos_flow.py"), "run",
            env_extra=env)
        out = proc.stdout + proc.stderr
        assert "hang run ok" in out, out
        assert "HANG detected" not in out, out
        records = _run_records(tpuflow_root, _run_id_of(out))
        by_name = {}
        for r in records:
            by_name.setdefault(r.get("name"), []).append(r)
        assert not by_name.get("hang.detected"), by_name["hang.detected"]
        slows = by_name.get("chaos.slow", [])
        assert len(slows) == 1, slows
        assert slows[0]["data"] == {"step": 3, "rank": 1, "world": 2,
                                    "delay_s": 1.0}
        validate_elastic_record(slows[0])

    def test_clean_run_zero_hang_events(self, run_flow, tpuflow_root):
        """False-positive guard: the watchdog is ON by default — a clean
        run (no chaos) must finish with zero hang events and zero
        forensics artifacts."""
        env = dict(FAST_WATCHDOG)
        env.update({
            "HANG_FLOW_RANKS": "2",
            "HANG_FLOW_STEPS": "6",
            "HANG_FLOW_SLEEP": "0.05",
        })
        proc = run_flow(
            os.path.join(FLOWS, "hang_chaos_flow.py"), "run",
            env_extra=env)
        out = proc.stdout + proc.stderr
        assert "hang run ok" in out, out
        assert "HANG detected" not in out, out
        run_id = _run_id_of(out)
        records = _run_records(tpuflow_root, run_id)
        hang_records = [r for r in records
                        if str(r.get("name", "")).startswith(
                            ("hang.", "chaos."))]
        assert not hang_records, hang_records
        assert not telemetry.list_run_hangs(_fds(tpuflow_root), run_id)
