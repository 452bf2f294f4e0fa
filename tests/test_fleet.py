"""Fault-tolerant serving fleet: router dispatch policy (least-loaded +
session affinity), load shedding, failover re-dispatch token identity
(non-streamed and mid-stream resume), supervisor restart, the pinned
fleet.* telemetry schema + `tpuflow metrics` fleet aggregation, and the
seeded chaos e2e (real replica subprocesses, real SIGKILL, rejoin after
backoff)."""

import http.client
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from metaflow_tpu.elastic.policy import BackoffPolicy
from metaflow_tpu.inference import generate
from metaflow_tpu.models import llama
from metaflow_tpu.serving import (
    FleetConfig,
    Request,
    Scheduler,
    ServingFleet,
    ServingServer,
    SlotEngine,
)

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _ref_tokens(params, cfg, tokens, max_new, seed=0, temperature=0.0):
    """Lockstep generate(): the token-identity oracle for any replica."""
    out = generate(params, jnp.asarray(tokens)[None], cfg, max_new,
                   temperature=temperature, rng=jax.random.PRNGKey(seed))
    return np.asarray(out)[0, len(tokens):].tolist()


def _post(port, payload, timeout=300):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", "/v1/generate", json.dumps(payload),
                 {"Content-Type": "application/json"})
    return conn, conn.getresponse()


def _get_json(port, path, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        return json.loads(conn.getresponse().read())
    finally:
        conn.close()


class _FakeProc(object):
    """Popen shim around an in-process ServingServer replica: poll/kill/
    terminate/wait — what ReplicaHandle needs from a process."""

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
    """In-process replica factory: one SlotEngine + ServingServer per
    spawn, wrapped in a _FakeProc so the supervisor sees a process."""
    cfg, params = setup
    build_lock = threading.Lock()

    def spawn(index, generation):
        with build_lock:  # serialize engine construction across boots
            eng = SlotEngine(params, cfg, max_slots=2, max_seq_len=96,
                             prefill_chunk=16)
            srv = ServingServer(Scheduler(eng), port=0).start()
        servers.append((index, generation, srv))
        return _FakeProc(srv), "127.0.0.1", srv.port

    return spawn


@pytest.fixture(scope="module")
def fleet_env(setup, tmp_path_factory):
    """A 2-replica in-process fleet with the flight recorder installed,
    so every fleet.* event the tests provoke lands in a datastore the
    final schema/metrics test reads back."""
    from metaflow_tpu import telemetry
    from metaflow_tpu.datastore import FlowDataStore, LocalStorage

    ds_root = str(tmp_path_factory.mktemp("fleet-telemetry"))
    fds = FlowDataStore("FleetTelemetry", LocalStorage, ds_root=ds_root)
    telemetry.init_recorder(fds, "1", "_serve", "fleet-test")
    servers = []
    config = FleetConfig(
        failover=True, restart=False, health_interval_s=60.0,
        wait_s=2.0, redispatch_max=3, spawn_timeout_s=60.0,
        backoff=BackoffPolicy(base_s=0.05, cap_s=0.1, jitter=0.0,
                              seed=0))
    fleet = ServingFleet(_make_spawner(setup, servers), 2, config=config)
    fleet.start()
    yield fleet, servers, fds
    fleet.close()
    telemetry.close_recorder()


def _server_for(servers, index):
    """The latest in-process server backing replica `index`."""
    return [srv for i, _g, srv in servers if i == index][-1]


class TestFleetRouter:
    """Tests run in definition order and share the module fleet; the
    final test closes the recorder and validates everything emitted."""

    def test_round_trip_token_identical(self, setup, fleet_env):
        cfg, params = setup
        fleet, _servers, _fds = fleet_env
        conn, resp = _post(fleet.port, {
            "tokens": list(range(1, 9)), "max_new_tokens": 5, "seed": 3})
        assert resp.status == 200
        body = json.loads(resp.read())
        conn.close()
        assert body["new_tokens"] == _ref_tokens(
            params, cfg, list(range(1, 9)), 5, seed=3)
        assert body["reason"] == "length"
        assert body["usage"] == {"prompt_tokens": 8, "new_tokens": 5}
        assert body["replica"] in (0, 1)

    def test_streaming_relay(self, setup, fleet_env):
        cfg, params = setup
        fleet, _servers, _fds = fleet_env
        conn, resp = _post(fleet.port, {
            "tokens": list(range(2, 10)), "max_new_tokens": 6,
            "stream": True})
        assert resp.status == 200
        lines = [json.loads(l) for l in iter(resp.readline, b"")]
        conn.close()
        assert [l["index"] for l in lines[:-1]] == list(range(6))
        assert lines[-1]["done"] and lines[-1]["reason"] == "length"
        assert lines[-1]["new_tokens"] == \
            [l["token"] for l in lines[:-1]]
        assert lines[-1]["new_tokens"] == _ref_tokens(
            params, cfg, list(range(2, 10)), 6)

    def test_least_loaded_dispatch(self, fleet_env):
        fleet, _servers, _fds = fleet_env
        a = fleet._pick(None, set())
        b = fleet._pick(None, set())
        try:
            # the second pick must go to the OTHER replica: a's
            # in-flight increment makes b the least-loaded
            assert {a.index, b.index} == {0, 1}
        finally:
            with fleet._lock:
                a.inflight = max(0, a.inflight - 1)
                b.inflight = max(0, b.inflight - 1)

    def test_session_affinity_beats_least_loaded(self, fleet_env):
        fleet, _servers, _fds = fleet_env
        first = fleet._pick("sess-affine", set())
        with fleet._lock:
            first.inflight = max(0, first.inflight - 1)
        # pile synthetic load onto the pinned replica: affinity (KV
        # reuse) must still win over least-loaded
        with fleet._lock:
            first.inflight += 5
        try:
            again = fleet._pick("sess-affine", set())
            assert again is first
        finally:
            with fleet._lock:
                first.inflight = max(0, first.inflight - 6)
            fleet._sessions.pop("sess-affine", None)

    def test_shed_expired_deadline_is_429(self, fleet_env):
        fleet, _servers, _fds = fleet_env
        before = fleet.shed_count
        conn, resp = _post(fleet.port, {
            "tokens": [1, 2, 3], "max_new_tokens": 4, "deadline_ms": 0})
        assert resp.status == 429
        body = json.loads(resp.read())
        conn.close()
        assert body["reason"] == "deadline"
        assert fleet.shed_count == before + 1

    def test_shed_queue_full_is_429(self, fleet_env):
        fleet, _servers, _fds = fleet_env
        saved = fleet.config.max_inflight
        fleet.config.max_inflight = 0
        try:
            conn, resp = _post(fleet.port, {
                "tokens": [1, 2, 3], "max_new_tokens": 4})
            assert resp.status == 429
            assert json.loads(resp.read())["reason"] == "queue_full"
            conn.close()
        finally:
            fleet.config.max_inflight = saved

    def test_shed_draining_is_503(self, fleet_env):
        fleet, _servers, _fds = fleet_env
        fleet._draining = True
        try:
            conn, resp = _post(fleet.port, {
                "tokens": [1, 2, 3], "max_new_tokens": 4})
            assert resp.status == 503
            assert json.loads(resp.read())["reason"] == "draining"
            conn.close()
        finally:
            fleet._draining = False

    def test_healthz_and_stats_pinned_schema(self, fleet_env):
        from schema_validate import validate_fleet_healthz

        fleet, _servers, _fds = fleet_env
        body = _get_json(fleet.port, "/healthz")
        validate_fleet_healthz(body)
        assert body["ok"] is True and body["ready"] == 2
        # the per-replica view carries the admission signals the
        # router's least-loaded policy reads
        for rep in body["replicas"]:
            assert rep["state"] == "ready"
        stats = _get_json(fleet.port, "/v1/stats")
        assert stats["dispatched"] >= 2
        assert stats["draining"] is False

    def test_mid_stream_failover_resumes_token_identical(self, setup,
                                                         fleet_env):
        """Kill the serving replica mid-stream: the client's single
        chunked stream continues on the survivor with no duplicated and
        no missing indices, and the total token sequence is exactly the
        single-engine reference (the acceptance pin)."""
        cfg, params = setup
        fleet, servers, _fds = fleet_env
        # pin a session so the victim replica is deterministic
        conn, resp = _post(fleet.port, {
            "tokens": [5, 6, 7], "max_new_tokens": 1,
            "session": "doomed"})
        victim = json.loads(resp.read())["replica"]
        conn.close()
        srv = _server_for(servers, victim)
        # slow the victim's engine so the kill lands mid-generation
        eng = srv.scheduler.engine
        real_decode = eng.decode_step
        eng.decode_step = \
            lambda: (time.sleep(0.05), real_decode())[1]
        prompt, max_new = list(range(3, 11)), 16
        conn, resp = _post(fleet.port, {
            "tokens": prompt, "max_new_tokens": max_new, "stream": True,
            "session": "doomed"})
        assert resp.status == 200
        lines = [json.loads(resp.readline()) for _ in range(3)]
        # hard-stop the victim: in-process equivalent of SIGKILL
        h = fleet.handles[victim]
        srv.close()
        h.proc._rc = -9  # the monitor now sees a dead process
        rest = [json.loads(l) for l in iter(resp.readline, b"")]
        conn.close()
        lines += rest
        assert lines[-1]["done"] and lines[-1]["reason"] == "length"
        toks = [l["token"] for l in lines[:-1]]
        assert [l["index"] for l in lines[:-1]] == list(range(max_new))
        assert toks == _ref_tokens(params, cfg, prompt, max_new)
        assert lines[-1]["new_tokens"] == toks
        assert fleet.failover_count >= 1
        # the monitor declares the replica dead and clears its session
        # pins; the next "doomed" request lands on the survivor
        deadline = time.time() + 10
        while h.state != "dead" and time.time() < deadline:
            time.sleep(0.05)
        assert h.state == "dead"  # restart=False in this fleet
        conn, resp = _post(fleet.port, {
            "tokens": [5, 6, 7], "max_new_tokens": 1,
            "session": "doomed"})
        body = json.loads(resp.read())
        conn.close()
        assert resp.status == 200 and body["replica"] != victim

    def test_failover_disabled_is_502_replica_lost(self, fleet_env):
        """TPUFLOW_FLEET_FAILOVER=0 semantics: a pre-stream replica
        loss surfaces as 502 instead of a silent re-dispatch."""
        fleet, _servers, _fds = fleet_env
        dead = [h for h in fleet.handles if h.state == "dead"][0]
        live = [h for h in fleet.handles if h.state == "ready"][0]
        # resurrect the dead handle's routing entry but point it at a
        # closed port: the relay fails instantly. The corpse must look
        # alive to the monitor too, or its next pass (every 50 ms)
        # declares it dead again before the request is routed
        dead.proc._rc = None
        dead.state = "ready"
        fleet.config.failover = False
        # force the pick to the corpse
        with fleet._lock:
            live.inflight += 10
        try:
            conn, resp = _post(fleet.port, {
                "tokens": [1, 2, 3], "max_new_tokens": 2})
            assert resp.status == 502
            assert json.loads(resp.read())["reason"] == "replica_lost"
            conn.close()
        finally:
            fleet.config.failover = True
            dead.state = "dead"
            dead.proc._rc = -9
            with fleet._lock:
                live.inflight = max(0, live.inflight - 10)

    def test_supervisor_restarts_dead_replica(self, setup):
        """A killed replica re-enters through backoff -> spawn -> ready
        and serves again (the rejoin half of the chaos acceptance)."""
        servers = []
        config = FleetConfig(
            failover=True, restart=True, max_restarts=4,
            health_interval_s=60.0, wait_s=10.0, spawn_timeout_s=60.0,
            backoff=BackoffPolicy(base_s=0.05, cap_s=0.1, jitter=0.0,
                                  seed=0))
        fleet = ServingFleet(_make_spawner(setup, servers), 1,
                             config=config)
        fleet.start()
        try:
            h = fleet.handles[0]
            gen1 = h.generation
            assert fleet.kill_replica(0)
            deadline = time.time() + 60
            while time.time() < deadline and not (
                    h.state == "ready" and h.generation > gen1):
                time.sleep(0.05)
            assert h.state == "ready" and h.generation == gen1 + 1
            assert h.restarts == 1 and fleet.restart_count == 1
            conn, resp = _post(fleet.port, {
                "tokens": [4, 5, 6], "max_new_tokens": 2})
            assert resp.status == 200
            conn.close()
        finally:
            fleet.close()

    def test_fleet_telemetry_schema_and_metrics(self, fleet_env):
        """LAST (order matters): every fleet.* record the scenarios
        above emitted validates against the pinned schema, and `tpuflow
        metrics` aggregates them into the fleet block."""
        from schema_validate import (
            FLEET_EVENT_DATA_SCHEMAS,
            validate_fleet_record,
        )

        from metaflow_tpu import telemetry
        from metaflow_tpu.cmd.metrics import aggregate

        fleet, _servers, fds = fleet_env

        def deaths():
            telemetry.flush()
            return sum(r["name"] == "fleet.replica.dead"
                       for r in telemetry.read_run_records(fds, "1"))

        # the tests above wait for a replica's state, not for its record:
        # kill the survivor here and wait until the record has landed
        before = deaths()
        live = [h for h in fleet.handles if h.state == "ready"][0]
        assert fleet.kill_replica(live.index)
        deadline = time.time() + 30
        while deaths() == before and time.time() < deadline:
            time.sleep(0.05)
        assert deaths() == before + 1
        telemetry.close_recorder()
        records = telemetry.read_run_records(fds, "1")
        fleet_recs = [r for r in records
                      if r["name"].startswith("fleet.")
                      or r["name"] == "chaos.replica_kill"]
        assert fleet_recs, "no fleet telemetry landed"
        for rec in fleet_recs:
            validate_fleet_record(rec)
        names = {r["name"] for r in fleet_recs}
        for lifecycle in FLEET_EVENT_DATA_SCHEMAS:
            if lifecycle in ("chaos.replica_kill", "fleet.scale_out",
                             "fleet.scale_in", "fleet.rollout",
                             "fleet.cache_route.hit"):
                # no chaos injector here; the autoscaler/rollout events
                # are exercised by test_disagg_fleet.py; these replicas
                # hold no prefix cache, so no dispatch can find one warm:
                # test_tenancy.py causes a hit and validates it
                continue
            assert lifecycle in names, "missing %s" % lifecycle
        assert "fleet.replicas_ready" in names
        agg = aggregate(records)
        fl = agg["fleet"]
        assert fl["failovers"] >= 1
        assert fl["dispatched"] >= 2 and fl["requests_per_replica"]
        for reason in ("deadline", "queue_full", "draining",
                       "replica_lost"):
            assert fl["shed"].get(reason, 0) >= 1, fl["shed"]
        assert fl["replica_deaths"] >= 1
        assert fl["restarts"], "restart backoff timeline missing"
        assert all(r["delay_s"] is not None for r in fl["restarts"])


@pytest.fixture()
def replica_env():
    """Environment for real replica subprocesses: repo on PYTHONPATH,
    CPU jax."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(HERE)] +
        [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
         if p])
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["TPUFLOW_TELEMETRY"] = "0"
    return env


SYNTH_CFG = {
    "vocab_size": 256, "dim": 64, "n_layers": 1, "n_heads": 4,
    "n_kv_heads": 2, "ffn_dim": 128, "max_seq_len": 128,
    "rope_llama3_scaling": False, "dtype": "float32"}


class TestFleetChaosE2E:
    def test_seeded_kill_failover_token_identical_rejoin(self, tmp_path,
                                                         replica_env):
        """The acceptance pin end to end: 2 REAL replica subprocesses,
        a seeded chaos schedule SIGKILLs one mid-trace, every request
        still completes with exactly the tokens an unkilled single
        engine produces, and the killed replica rejoins after backoff.
        """
        from metaflow_tpu.devtools import chaos
        from metaflow_tpu.serving.fleet import SubprocessReplicaSpawner

        cfg_json = json.dumps(SYNTH_CFG)
        replica_args = [
            "--synthetic-config", cfg_json, "--synthetic-seed", "7",
            "--slots", "2", "--max-seq-len", "96",
            "--prefill-chunk", "16", "--max-queue", "32",
            # keeps requests in flight long enough that the kill
            # lands mid-generation
            "--step-delay-ms", "30",
        ]
        schedule = chaos.KillSchedule.parse("3:1")  # dispatch 3 kills r1
        injector = chaos.FleetChaosInjector(
            schedule, ledger_dir=str(tmp_path / "chaos-ledger"))
        config = FleetConfig(
            failover=True, restart=True, max_restarts=4,
            health_interval_s=1.0, wait_s=60.0, spawn_timeout_s=300.0,
            redispatch_max=3,
            backoff=BackoffPolicy(base_s=0.2, cap_s=0.5, jitter=0.0,
                                  seed=0))
        spawner = SubprocessReplicaSpawner(
            replica_args, workdir=str(tmp_path), env=replica_env,
            spawn_timeout_s=300.0)
        fleet = ServingFleet(spawner, 2, config=config, chaos=injector)
        fleet.start()
        try:
            # the reference: synthetic weights are a pure function of
            # (seed, config), so the in-process engine-free lockstep
            # generate IS the unkilled single-replica run
            ref_cfg = llama.LlamaConfig(**SYNTH_CFG)
            ref_params = llama.init_params(jax.random.PRNGKey(7),
                                           ref_cfg)
            reqs = []
            for i in range(8):
                reqs.append({
                    "tokens": list(range(1 + i, 9 + i)),
                    "max_new_tokens": 6, "seed": i,
                    "stream": bool(i % 2),
                    "request_id": "chaos-%d" % i,
                })
            results = [None] * len(reqs)

            def fire(i):
                conn, resp = _post(fleet.port, reqs[i], timeout=300)
                try:
                    if reqs[i]["stream"]:
                        assert resp.status == 200
                        lines = [json.loads(l)
                                 for l in iter(resp.readline, b"")]
                        assert lines[-1]["done"]
                        assert [l["index"] for l in lines[:-1]] == \
                            list(range(len(lines) - 1))
                        results[i] = (200, lines[-1]["new_tokens"])
                    else:
                        body = json.loads(resp.read())
                        results[i] = (resp.status,
                                      body.get("new_tokens"))
                finally:
                    conn.close()

            threads = [threading.Thread(target=fire, args=(i,))
                       for i in range(len(reqs))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            assert not any(t.is_alive() for t in threads)
            for i, req in enumerate(reqs):
                status, toks = results[i]
                assert status == 200, "request %d failed: %s" % (
                    i, results[i])
                ref = _ref_tokens(ref_params, ref_cfg, req["tokens"],
                                  req["max_new_tokens"], seed=i)
                assert toks == ref, \
                    "request %d diverged after failover" % i
            # the seeded kill really happened, through the real path
            victim = fleet.handles[1]
            assert victim.restarts >= 1, "chaos kill never landed"
            # ... and the killed replica rejoins after backoff
            deadline = time.time() + 300
            while time.time() < deadline and victim.state != "ready":
                time.sleep(0.2)
            assert victim.state == "ready", "replica never rejoined"
            conn, resp = _post(fleet.port, {
                "tokens": [9, 8, 7], "max_new_tokens": 2})
            assert resp.status == 200
            conn.close()
        finally:
            fleet.close()
