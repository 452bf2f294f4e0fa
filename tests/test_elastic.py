"""Elastic gang supervision — unit layer.

Fast, in-process tests for the policy/oracle/supervisor/chaos pieces:
failure classification, jittered-backoff determinism, capacity oracles,
admissible-size selection + SPMD pre-relaunch validation, grow-notice
delivery, the chaos harness's once-only seeded kill schedules, the
preemption-marker freshness satellites, and the streaming loader's
epoch-boundary re-slice. The end-to-end shrink/grow scenarios (real
gangs, real SIGTERMs) live in tests/test_zelastic_e2e.py.
"""

import json
import os
import signal
import time
import types

import numpy as np
import pytest

from metaflow_tpu.data import StreamingTokenBatches, build_corpus
from metaflow_tpu.datastore import FlowDataStore
from metaflow_tpu.datastore.storage import LocalStorage
from metaflow_tpu.devtools import chaos
from metaflow_tpu.elastic.oracle import (
    GceCapacityOracle,
    ScriptedCapacityOracle,
    StaticCapacityOracle,
    oracle_from_env,
)
from metaflow_tpu.elastic.policy import (
    CLASS_GROW,
    CLASS_INFRA,
    CLASS_PREEMPTION,
    CLASS_USER,
    BackoffPolicy,
    classify_failure,
)
from metaflow_tpu.elastic.supervisor import ElasticGangSupervisor
from metaflow_tpu.exception import TaskPreempted
from metaflow_tpu.plugins.tpu import preemption
from metaflow_tpu.unbounded_foreach import UBF_CONTROL

from schema_validate import validate_elastic_record


# ---------------------------------------------------------------------------
# policy
# ---------------------------------------------------------------------------


class TestClassifyFailure:
    def test_mapping(self):
        assert classify_failure(spot_notice=True) == CLASS_PREEMPTION
        assert classify_failure(grow_notice=True) == CLASS_GROW
        # grow wins over spot: the supervisor's own notice is the cause
        assert classify_failure(spot_notice=True,
                                grow_notice=True) == CLASS_GROW
        assert classify_failure(attempt_recorded=True) == CLASS_USER
        assert classify_failure(attempt_recorded=False) == CLASS_INFRA


class TestBackoffPolicy:
    def test_seeded_schedule_replays(self):
        a = BackoffPolicy(base_s=0.5, cap_s=60, jitter=0.5, seed=7)
        b = BackoffPolicy(base_s=0.5, cap_s=60, jitter=0.5, seed=7)
        assert [a.delay(i, key="t") for i in range(6)] \
            == [b.delay(i, key="t") for i in range(6)]

    def test_exponential_with_cap_and_jitter_bounds(self):
        p = BackoffPolicy(base_s=1.0, cap_s=8.0, jitter=0.5, seed=3)
        for attempt in range(10):
            raw = min(8.0, 2.0 ** attempt)
            d = p.delay(attempt)
            assert 0.5 * raw <= d <= 1.5 * raw

    def test_different_keys_jitter_differently(self):
        p = BackoffPolicy(base_s=1.0, cap_s=60, jitter=0.5, seed=11)
        assert p.delay(3, key="a") != p.delay(3, key="b")

    def test_zero_base_disables(self):
        assert BackoffPolicy(base_s=0).delay(5) == 0.0

    def test_from_env(self, monkeypatch):
        monkeypatch.setenv("TPUFLOW_RETRY_BACKOFF_BASE_S", "2.5")
        monkeypatch.setenv("TPUFLOW_RETRY_BACKOFF_CAP_S", "10")
        monkeypatch.setenv("TPUFLOW_RETRY_BACKOFF_JITTER", "0")
        p = BackoffPolicy.from_env()
        assert p.delay(0) == 2.5 and p.delay(4) == 10.0

    def test_from_env_malformed_degrades_to_defaults(self, monkeypatch):
        # this runs inside NativeRuntime construction: a typo'd knob must
        # not kill every run of every flow before any task starts
        monkeypatch.setenv("TPUFLOW_RETRY_BACKOFF_BASE_S", "0.2s")
        monkeypatch.setenv("TPUFLOW_RETRY_BACKOFF_SEED", "not-a-seed")
        p = BackoffPolicy.from_env()
        assert p.base_s == 0.2


# ---------------------------------------------------------------------------
# oracles
# ---------------------------------------------------------------------------


class TestOracles:
    def test_static(self):
        assert StaticCapacityOracle(4).available_hosts() == 4

    def test_scripted_consult_indexed_last_sticks(self):
        o = ScriptedCapacityOracle("4,4,8")
        assert [o.available_hosts() for _ in range(5)] == [4, 4, 8, 8, 8]

    def test_scripted_time_keyed(self):
        now = [0.0]
        o = ScriptedCapacityOracle("0:8,5:4,9:8", clock=lambda: now[0])
        assert o.available_hosts() == 8
        now[0] = 5.5
        assert o.available_hosts() == 4
        now[0] = 20.0
        assert o.available_hosts() == 8

    def test_scripted_anchored_at_first_consult(self):
        now = [100.0]
        o = ScriptedCapacityOracle("+0:2,5:8", clock=lambda: now[0])
        now[0] = 500.0  # construction-to-first-consult gap is irrelevant
        assert o.available_hosts() == 2
        now[0] = 504.0
        assert o.available_hosts() == 2
        now[0] = 505.5
        assert o.available_hosts() == 8

    def test_scripted_rejects_empty(self):
        with pytest.raises(ValueError):
            ScriptedCapacityOracle("")

    def test_from_env(self, monkeypatch):
        monkeypatch.delenv("TPUFLOW_CAPACITY_ORACLE", raising=False)
        assert oracle_from_env() is None
        monkeypatch.setenv("TPUFLOW_CAPACITY_ORACLE", "static:3")
        assert oracle_from_env().available_hosts() == 3
        monkeypatch.setenv("TPUFLOW_CAPACITY_ORACLE", "scripted:2,4")
        assert oracle_from_env().available_hosts() == 2
        monkeypatch.setenv("TPUFLOW_CAPACITY_ORACLE", "gce")
        assert isinstance(oracle_from_env(), GceCapacityOracle)
        monkeypatch.setenv("TPUFLOW_CAPACITY_ORACLE", "bogus")
        with pytest.raises(ValueError):
            oracle_from_env()

    def test_gce_hint_env(self, monkeypatch):
        o = GceCapacityOracle()
        monkeypatch.delenv("TPUFLOW_CAPACITY_HINT", raising=False)
        assert o.available_hosts() is None  # unknown -> adaptive policy
        monkeypatch.setenv("TPUFLOW_CAPACITY_HINT", "16")
        assert o.available_hosts() == 16


# ---------------------------------------------------------------------------
# supervisor (with in-memory fakes)
# ---------------------------------------------------------------------------


class _FakeMetadata(object):
    def __init__(self):
        self.md = {}

    def record(self, step, task_id, field, value, attempt=None):
        tags = ["attempt_id:%d" % attempt] if attempt is not None else []
        self.md.setdefault((step, task_id), []).append(
            {"field_name": field, "value": value, "tags": tags})

    def get_task_metadata(self, flow_name, run_id, step, task_id):
        return self.md.get((step, task_id), [])


def _node(decorators=()):
    return types.SimpleNamespace(decorators=list(decorators))


def _tpu_deco(topology):
    return types.SimpleNamespace(name="tpu",
                                 attributes={"topology": topology})


class _FakeGraph(object):
    def __init__(self, nodes):
        self.nodes = nodes

    def __getitem__(self, name):
        return self.nodes[name]


def _task(step="train", task_id="2", num_parallel=8, attempt=0,
          user_retries=1, error_retries=0, elastic_size=None,
          ubf_context=UBF_CONTROL):
    return types.SimpleNamespace(
        step=step, task_id=task_id, num_parallel=num_parallel,
        attempt=attempt, user_retries=user_retries,
        error_retries=error_retries, elastic_size=elastic_size,
        ubf_context=ubf_context)


def _supervisor(nodes=None, oracle=None, resize=True, metadata=None,
                **kw):
    graph = _FakeGraph(nodes or {"train": _node()})
    flow = types.SimpleNamespace(name="F")
    sup = ElasticGangSupervisor(
        flow, graph, metadata or _FakeMetadata(), echo=lambda s: None,
        recorder=None, oracle=oracle,
        backoff=BackoffPolicy(base_s=0.0), resize_enabled=resize, **kw)
    sup.run_id = "R"
    sup._facts = {}  # skip AST extraction: fakes have no source
    return sup


class TestSupervisorSizes:
    def test_local_gang_sizes_are_divisors(self):
        sup = _supervisor()
        assert sup.admissible_sizes("train", 8) == [8, 4, 2, 1]
        assert sup.admissible_sizes("train", 6) == [6, 3, 2, 1]

    def test_tpu_gang_sizes_follow_topology_family(self):
        sup = _supervisor({"train": _node([_tpu_deco("v5p-64")])})
        # v5p family, 4 chips/host: 8 -> 4 -> 2 -> 1 hosts
        assert sup.admissible_sizes("train", 8) == [8, 4, 2, 1]
        assert sup.topology_for_size("train", 4) == "v5p-32"
        assert sup.topology_for_size("train", 8) == "v5p-64"

    def test_validate_size_rejects_off_table_host_count(self):
        sup = _supervisor({"train": _node([_tpu_deco("v5p-64")])})
        ok, _ = sup.validate_size("train", 4)
        assert ok
        ok, problems = sup.validate_size("train", 3)
        assert not ok and problems

    def test_pick_size_largest_admissible_under_capacity(self):
        sup = _supervisor()
        assert sup.pick_size(_task(num_parallel=8), capacity=5) == 4
        assert sup.pick_size(_task(num_parallel=8), capacity=8) == 8
        assert sup.pick_size(_task(num_parallel=8), capacity=0) is None


class TestSupervisorClassification:
    def _gang_md(self, md, preempted_member=None, attempt=0,
                 grow_member=None, control_ok=False):
        members = ["R/train/2", "R/train/2-node-1", "R/train/2-node-2"]
        md.record("train", "2", "control-mapper-tasks",
                  json.dumps(members))
        if preempted_member:
            md.record("train", preempted_member, "preempted", "true",
                      attempt=attempt)
        if grow_member:
            md.record("train", grow_member, "resize", "grow",
                      attempt=attempt)
        if control_ok:
            md.record("train", "2", "attempt_ok", "false", attempt=attempt)

    def test_worker_spot_marker_classifies_gang_preemption(self):
        md = _FakeMetadata()
        # control recorded its verdict (gang-worker-failed is a normal
        # exception there) — the WORKER's spot marker still wins
        self._gang_md(md, preempted_member="2-node-2", control_ok=True)
        sup = _supervisor(metadata=md)
        assert sup.classify(_task()) == CLASS_PREEMPTION

    def test_grow_marker_classifies_grow(self):
        md = _FakeMetadata()
        self._gang_md(md, grow_member="2", control_ok=True)
        sup = _supervisor(metadata=md)
        assert sup.classify(_task()) == CLASS_GROW

    def test_attempt_verdict_without_marker_is_user(self):
        md = _FakeMetadata()
        self._gang_md(md, control_ok=True)
        sup = _supervisor(metadata=md)
        assert sup.classify(_task()) == CLASS_USER

    def test_no_metadata_at_all_is_infra(self):
        sup = _supervisor(metadata=_FakeMetadata())
        assert sup.classify(_task()) == CLASS_INFRA

    def test_stale_attempt_marker_does_not_leak(self):
        # a spot marker from attempt 0 must not classify attempt 1
        md = _FakeMetadata()
        self._gang_md(md, preempted_member="2-node-1", attempt=0)
        md.record("train", "2", "attempt_ok", "false", attempt=1)
        sup = _supervisor(metadata=md)
        assert sup.classify(_task(attempt=1)) == CLASS_USER


class TestSupervisorPlanRetry:
    def _preempted(self, md, attempt=0):
        md.record("train", "2", "control-mapper-tasks",
                  json.dumps(["R/train/2", "R/train/2-node-1"]))
        md.record("train", "2-node-1", "preempted", "true",
                  attempt=attempt)
        md.record("train", "2", "attempt_ok", "false", attempt=attempt)

    def test_preemption_shrinks_to_oracle_capacity(self):
        md = _FakeMetadata()
        self._preempted(md)
        sup = _supervisor(metadata=md, oracle=StaticCapacityOracle(4))
        d = sup.plan_retry(_task(), 1, max_attempts=6)
        assert d.action == "retry"
        assert d.new_size == 4
        assert d.failure_class == CLASS_PREEMPTION
        assert not d.waiting

    def test_fixed_size_parks_until_capacity_returns(self):
        md = _FakeMetadata()
        self._preempted(md)
        sup = _supervisor(metadata=md, oracle=StaticCapacityOracle(4),
                          resize=False)
        d = sup.plan_retry(_task(), 1, max_attempts=6)
        assert d.action == "retry" and d.waiting
        # recheck: still short -> parked; capacity back -> launch
        task = _task()
        launch, _delay = sup.recheck_capacity(task)
        assert not launch
        sup._oracle = StaticCapacityOracle(8)
        launch, delay = sup.recheck_capacity(task)
        assert launch and delay == 0.0

    def test_preemption_budget_exceeds_user_budget(self):
        md = _FakeMetadata()
        self._preempted(md, attempt=1)
        sup = _supervisor(metadata=md)
        # user budget (1) is exhausted at attempt 1, but preemption rides
        # the elastic budget — capacity loss is not a user error
        d = sup.plan_retry(_task(attempt=1, user_retries=1), 1,
                           max_attempts=6)
        assert d.action == "retry"

    def test_user_error_fails_fast_at_budget(self):
        md = _FakeMetadata()
        md.record("train", "2", "attempt_ok", "false", attempt=1)
        sup = _supervisor(metadata=md)
        d = sup.plan_retry(_task(attempt=1, user_retries=1), 1,
                           max_attempts=6)
        assert d.action == "fail"

    def test_max_attempts_is_a_hard_ceiling(self):
        md = _FakeMetadata()
        self._preempted(md, attempt=5)
        sup = _supervisor(metadata=md)
        d = sup.plan_retry(_task(attempt=5), 1, max_attempts=6)
        assert d.action == "fail"

    def test_adaptive_step_down_without_oracle(self):
        sup = _supervisor(oracle=None)
        md = sup._metadata
        task = _task(user_retries=3)
        for attempt in (0, 1):
            md.record("train", "2", "control-mapper-tasks",
                      json.dumps(["R/train/2", "R/train/2-node-1"]))
            md.record("train", "2-node-1", "preempted", "true",
                      attempt=attempt)
        d0 = sup.plan_retry(_task(user_retries=3), 1, max_attempts=6)
        assert d0.new_size is None  # first preemption: same size
        task.attempt = 1
        d1 = sup.plan_retry(task, 1, max_attempts=6)
        assert d1.new_size == 4  # second consecutive: step down 8 -> 4

    def test_grow_notice_relaunches_larger(self, monkeypatch):
        md = _FakeMetadata()
        sup = _supervisor(metadata=md, oracle=StaticCapacityOracle(8))
        sup._grow_every_s = 0.0
        task = _task(elastic_size=4)
        delivered = []
        monkeypatch.setattr(preemption, "notify_resize",
                            lambda pid: delivered.append(pid))
        worker = types.SimpleNamespace(
            task=task, proc=types.SimpleNamespace(pid=12345))
        sup.note_launch(task)
        sup._gang(task).last_grow_poll = 0.0
        sup.poll_grow({12345: worker})
        assert delivered == [12345]
        # the gang then exits with the grow marker recorded
        md.record("train", "2", "resize", "grow", attempt=0)
        d = sup.plan_retry(task, 1, max_attempts=6)
        assert d.action == "retry"
        assert d.new_size == 8
        assert d.failure_class == CLASS_GROW
        assert d.delay_s == 0.0

    def test_grow_notice_that_kills_prelaunch_still_grows(self,
                                                          monkeypatch):
        # SIGTERM landed before the handler was installed: raw death, no
        # metadata — the pending grow intent still drives the relaunch
        sup = _supervisor(oracle=StaticCapacityOracle(8))
        sup._grow_every_s = 0.0
        task = _task(elastic_size=4)
        monkeypatch.setattr(preemption, "notify_resize", lambda pid: None)
        worker = types.SimpleNamespace(
            task=task, proc=types.SimpleNamespace(pid=1))
        sup.note_launch(task)
        sup._gang(task).last_grow_poll = 0.0
        sup.poll_grow({1: worker})
        d = sup.plan_retry(task, -15, max_attempts=6)
        assert d.action == "retry" and d.new_size == 8
        assert d.failure_class == CLASS_GROW


# ---------------------------------------------------------------------------
# chaos harness
# ---------------------------------------------------------------------------


class TestKillSchedule:
    def test_parse(self):
        assert chaos.KillSchedule.parse("3:1, 7:0").kills == ((3, 1),
                                                              (7, 0))

    def test_seeded_is_pure_and_bounded(self):
        a = chaos.KillSchedule.seeded(42, 10, 8, n_kills=3)
        b = chaos.KillSchedule.seeded(42, 10, 8, n_kills=3)
        assert a.kills == b.kills and len(a) == 3
        for s, r in a:
            assert 1 <= s < 10 and 0 <= r < 8
        assert a.kills != chaos.KillSchedule.seeded(43, 10, 8, 3).kills

    def test_kills_for_rank(self):
        sched = chaos.KillSchedule.parse("3:1,7:0,9:1")
        assert sched.kills_for_rank(1) == [3, 9]
        assert sched.kills_for_rank(5) == []


class TestChaosInjector:
    def test_delivers_once_per_run(self, tmp_path):
        sched = chaos.KillSchedule.parse("2:1")
        calls = []
        inj = chaos.ChaosInjector(sched, rank=1, world=4,
                                  ledger_dir=str(tmp_path),
                                  notify=calls.append)
        assert inj.on_step(1) is False
        assert inj.on_step(2) is True
        assert inj.on_step(2) is False  # once only
        # a NEW injector (the retried attempt) sees the same ledger
        inj2 = chaos.ChaosInjector(sched, rank=1, world=4,
                                   ledger_dir=str(tmp_path),
                                   notify=calls.append)
        assert inj2.on_step(2) is False
        assert calls == [os.getpid()]

    def test_other_ranks_untouched(self, tmp_path):
        sched = chaos.KillSchedule.parse("2:1")
        calls = []
        inj = chaos.ChaosInjector(sched, rank=0, world=4,
                                  ledger_dir=str(tmp_path),
                                  notify=calls.append)
        assert inj.on_step(2) is False and not calls

    def test_schedule_from_env(self, monkeypatch):
        monkeypatch.setenv(chaos.CHAOS_ENV, "3:1,5:0")
        sched = chaos.schedule_from_env(world=4)
        assert sched.kills == ((3, 1), (5, 0))
        monkeypatch.setenv(chaos.CHAOS_ENV, "42")
        monkeypatch.setenv(chaos.STEPS_ENV, "12")
        monkeypatch.setenv(chaos.NKILLS_ENV, "2")
        sched = chaos.schedule_from_env(world=4)
        assert sched.kills == chaos.KillSchedule.seeded(42, 12, 4, 2).kills
        monkeypatch.delenv(chaos.CHAOS_ENV)
        assert chaos.schedule_from_env(world=4) is None

    def test_maybe_chaos_step_noop_without_env(self, monkeypatch):
        monkeypatch.delenv(chaos.CHAOS_ENV, raising=False)
        assert chaos.maybe_chaos_step(3) is False

    def test_instrumented_train_step_ticks_chaos(self, monkeypatch,
                                                 tmp_path):
        """Any instrument_train_step-wrapped loop gets fault injection
        for free: the scheduled kill rides the REAL notice path (marker
        + SIGTERM -> TaskPreempted via the installed handler)."""
        from metaflow_tpu.training.metrics import instrument_train_step

        monkeypatch.setenv(chaos.CHAOS_ENV, "1:0")
        monkeypatch.setenv(chaos.DIR_ENV, str(tmp_path))
        monkeypatch.setenv("MF_PARALLEL_NODE_INDEX", "0")
        monkeypatch.setenv("MF_PARALLEL_NUM_NODES", "2")
        chaos._injector_cache.clear()
        handler = preemption.PreemptionHandler().install()
        calls = []
        wrapped = instrument_train_step(lambda: calls.append(1),
                                        profile=False)
        try:
            wrapped()  # step 0: no kill scheduled
            with pytest.raises(TaskPreempted):
                wrapped()  # step 1, rank 0: the scheduled reclaim
                time.sleep(0.5)
            assert handler.spot_notice
            assert len(calls) >= 1
        finally:
            handler.uninstall()
            wrapped.telemetry.close()
            chaos._injector_cache.clear()


# ---------------------------------------------------------------------------
# preemption marker satellites (freshness, kinds, cleanup)
# ---------------------------------------------------------------------------


class TestNoticeMarkers:
    def _sigterm_self(self):
        os.kill(os.getpid(), signal.SIGTERM)
        time.sleep(0.5)  # the raise happens on syscall return

    def test_fresh_spot_marker(self):
        handler = preemption.PreemptionHandler().install()
        try:
            with open(preemption._notice_marker(os.getpid()), "w") as f:
                f.write(json.dumps({"ts": time.time(), "kind": "spot"}))
            with pytest.raises(TaskPreempted):
                self._sigterm_self()
            assert handler.spot_notice and not handler.grow_notice
        finally:
            handler.uninstall()

    def test_stale_marker_reads_as_routine_teardown(self):
        # the task the notice was meant for died unhandled; a later
        # process reusing the PID must NOT read a spot reclaim
        handler = preemption.PreemptionHandler().install()
        marker = preemption._notice_marker(os.getpid())
        try:
            with open(marker, "w") as f:
                f.write(json.dumps({"ts": time.time() - 7200,
                                    "kind": "spot"}))
            with pytest.raises(TaskPreempted):
                self._sigterm_self()
            assert not handler.spot_notice
            assert not os.path.exists(marker)  # stale leftover cleaned up
        finally:
            handler.uninstall()

    def test_legacy_float_marker_still_reads_as_spot(self):
        handler = preemption.PreemptionHandler().install()
        try:
            with open(preemption._notice_marker(os.getpid()), "w") as f:
                f.write(str(time.time()))
            with pytest.raises(TaskPreempted):
                self._sigterm_self()
            assert handler.spot_notice
        finally:
            handler.uninstall()

    def test_grow_marker_sets_grow_notice(self):
        handler = preemption.PreemptionHandler().install()
        try:
            with pytest.raises(TaskPreempted) as exc_info:
                preemption.notify_resize(os.getpid())
                time.sleep(0.5)
            assert "grow" in str(exc_info.value).lower()
            assert handler.grow_notice and not handler.spot_notice
        finally:
            handler.uninstall()

    def test_uninstall_cleans_up_marker(self):
        # a notice arriving between uninstall() and process exit leaves a
        # marker a recycled PID could misread: uninstall removes it
        handler = preemption.PreemptionHandler().install()
        marker = preemption._notice_marker(os.getpid())
        with open(marker, "w") as f:
            f.write(json.dumps({"ts": time.time(), "kind": "spot"}))
        handler.uninstall()
        assert not os.path.exists(marker)

    def test_notice_to_dead_pid_cleans_its_marker(self):
        # a notice raced against process exit must not leave a FRESH
        # marker behind for a recycled PID to misread as a live notice
        import subprocess
        import sys as _sys

        proc = subprocess.Popen([_sys.executable, "-c", "pass"])
        proc.wait()
        with pytest.raises(ProcessLookupError):
            preemption.notify_resize(proc.pid)
        assert not os.path.exists(preemption._notice_marker(proc.pid))

    def test_marker_ttl_override(self):
        handler = preemption.PreemptionHandler(marker_ttl_s=1e9).install()
        try:
            with open(preemption._notice_marker(os.getpid()), "w") as f:
                f.write(json.dumps({"ts": time.time() - 7200,
                                    "kind": "spot"}))
            with pytest.raises(TaskPreempted):
                self._sigterm_self()
            assert handler.spot_notice  # huge TTL: still fresh
        finally:
            handler.uninstall()


# ---------------------------------------------------------------------------
# streaming loader: epoch-boundary re-slice across a gang resize
# ---------------------------------------------------------------------------

SEQ = 9
SHARD_TOKENS = 3 * (SEQ + 1)


@pytest.fixture()
def corpus_fds(tmp_path):
    fds = FlowDataStore("ElasticData", LocalStorage,
                        ds_root=str(tmp_path / "root"), blob_cache=False)
    data = (np.arange(12 * SHARD_TOKENS) % 251).astype(np.int32)
    build_corpus(fds, "c", data, shard_tokens=SHARD_TOKENS)
    return fds


class TestStreamingReslice:
    def _stream(self, fds, host_index, n_hosts, **kw):
        return StreamingTokenBatches(
            fds, "c", 3, SEQ, seed=5, host_index=host_index,
            n_hosts=n_hosts, **kw)

    def test_mid_epoch_reslice_is_a_hard_error(self, corpus_fds):
        src = self._stream(corpus_fds, 0, 2)
        it = iter(src)
        stamp = next(it)["data_state"]  # mid-epoch position
        dst = self._stream(corpus_fds, 0, 1)
        with pytest.raises(ValueError, match="mid-epoch"):
            dst.restore(stamp, reslice=True)
        # and without reslice, ANY geometry change is a hard error
        with pytest.raises(ValueError, match="n_hosts"):
            dst.restore(stamp)

    def test_drained_epoch_stamp_reslices_to_next_epoch(self, corpus_fds):
        src = self._stream(corpus_fds, 0, 2)
        per_epoch = src.batches_per_epoch(0)
        it = iter(src)
        stamp = None
        for _ in range(per_epoch):
            stamp = next(it)["data_state"]
        assert stamp["shard_cursor"] > 0
        # 2-host epoch 0 drained -> single host picks up at epoch 1,
        # byte-identical to a fresh single-host stream at epoch 1
        resliced = self._stream(corpus_fds, 0, 1).restore(stamp,
                                                          reslice=True)
        fresh = self._stream(corpus_fds, 0, 1)
        fresh._epoch = 1
        got = [next(iter(resliced))["tokens"].tobytes()]
        want = [next(iter(fresh))["tokens"].tobytes()]
        assert got == want

    def test_epoch_start_stamp_reslices_in_place(self, corpus_fds):
        src = self._stream(corpus_fds, 1, 2)
        stamp = src.state()  # pristine epoch-0 start
        resliced = self._stream(corpus_fds, 0, 4).restore(stamp,
                                                          reslice=True)
        assert resliced.state()["epoch"] == 0
        assert resliced.state()["n_hosts"] == 4

    def test_reslice_rejects_corrupted_epoch(self, corpus_fds):
        # the reslice path must enforce the same corrupted-stamp bounds
        # as the same-geometry path: a negative epoch would silently
        # over-deliver whole epochs of repeated tokens
        src = self._stream(corpus_fds, 0, 2)
        stamp = dict(src.state(), epoch=-2)
        dst = self._stream(corpus_fds, 0, 1, epochs=1)
        with pytest.raises(ValueError, match="epoch=-2 out of range"):
            dst.restore(stamp, reslice=True)

    def test_reslice_refuses_different_corpus_geometry(self, corpus_fds):
        src = self._stream(corpus_fds, 0, 2)
        stamp = src.state()
        other = StreamingTokenBatches(corpus_fds, "c", 4, SEQ, seed=5,
                                      host_index=0, n_hosts=1)
        with pytest.raises(ValueError, match="batch_size"):
            other.restore(stamp, reslice=True)


# ---------------------------------------------------------------------------
# pinned telemetry surface
# ---------------------------------------------------------------------------


def _base_record(rtype, name, **extra):
    rec = {"v": 1, "type": rtype, "name": name, "ts": time.time(),
           "run_id": "R", "step": "_runtime", "task_id": "scheduler",
           "attempt": 0, "rank": 0, "host": "h", "pid": 1}
    rec.update(extra)
    return rec


class TestElasticSchemas:
    def test_resize_event_pins(self):
        validate_elastic_record(_base_record(
            "event", "elastic.resize",
            data={"pathspec": "R/train/2", "from_size": 8, "to_size": 4,
                  "direction": "shrink", "attempt": 0,
                  "oracle": "static:4"}))

    def test_backoff_event_pins(self):
        validate_elastic_record(_base_record(
            "event", "elastic.backoff",
            data={"pathspec": "R/train/2", "failure_class": "preemption",
                  "attempt": 1, "delay_s": 0.4}))

    def test_goodput_gauge_pins(self):
        validate_elastic_record(_base_record(
            "gauge", "elastic.goodput", value=0.87,
            data={"pathspec": "R/train/2", "running_s": 10.0,
                  "total_s": 11.5, "attempts": 3, "resizes": 2}))

    def test_chaos_kill_pins(self):
        validate_elastic_record(_base_record(
            "event", "chaos.kill",
            data={"step": 3, "rank": 2, "world": 8}))

    def test_unknown_name_rejected(self):
        import jsonschema

        with pytest.raises(jsonschema.ValidationError):
            validate_elastic_record(_base_record("event", "elastic.bogus",
                                                 data={}))

    def test_invalid_direction_rejected(self):
        import jsonschema

        with pytest.raises(jsonschema.ValidationError):
            validate_elastic_record(_base_record(
                "event", "elastic.resize",
                data={"pathspec": "p", "from_size": 8, "to_size": 4,
                      "direction": "sideways", "attempt": 0}))
