"""True MPMD pipeline parallelism (spmd/mpmd.py + training/mpmd_trainer.py):
wire-frame round-trips, stage-plan validation, 2-stage loss/grad parity
over REAL loopback TCP against the single-gang interleaved schedule
(both transports share `interleaved_schedule`'s tables verbatim), the
bounded recv deadline + peer-death contract the chaos/elastic story
rests on, the per-stage transfer telemetry and its pinned schemas, the
`tpuflow metrics` MPMD section with the PIPELINE-BOUND verdict, the
flow-level pre-launch checker.

Parity tolerances: the MPMD run and the SPMD interleaved run execute
the SAME schedule tables with the same fp32 accumulation discipline, so
losses match to float rounding (atol 1e-5) and gradients to
rtol=1e-4/atol=1e-5 (reduction order differs only inside the vjp)."""

import os
import queue
import socket
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from metaflow_tpu import telemetry
from metaflow_tpu.analysis import check_mpmd_plan
from metaflow_tpu.datastore import FlowDataStore, LocalStorage
from metaflow_tpu.models import llama
from metaflow_tpu.spmd import MeshSpec, create_mesh, mpmd
from metaflow_tpu.spmd.pipeline import pipeline_train_interleaved
from metaflow_tpu.training.mpmd_trainer import make_stage_step, run_stage_steps

import schema_validate



def _free_peers(n):
    ports = []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        s.close()
    return ["127.0.0.1:%d" % p for p in ports]


def _run_stage_threads(world, stage_main, timeout=120):
    """Run one callable per stage on threads; re-raise the first error."""
    out = [None] * world
    errors = []

    def runner(d):
        try:
            out[d] = stage_main(d)
        except BaseException as exc:
            errors.append(exc)

    threads = [threading.Thread(target=runner, args=(d,), daemon=True)
               for d in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout)
    if errors:
        raise errors[0]
    assert not any(t.is_alive() for t in threads), "stage thread wedged"
    return out


# ---------------------------------------------------------------------------
# wire frames
# ---------------------------------------------------------------------------


class TestWireFrames:
    def test_roundtrip_float32(self):
        arr = np.arange(24, dtype=np.float32).reshape(2, 3, 4) * 0.5
        meta, back = mpmd.decode_frame(
            mpmd.encode_frame({"m": 3, "v": 1, "c": 7}, arr))
        assert meta == {"m": 3, "v": 1, "c": 7}
        assert back.dtype == np.float32 and back.shape == (2, 3, 4)
        np.testing.assert_array_equal(back, arr)

    def test_roundtrip_bfloat16_preserves_dtype(self):
        """The reason for raw-buffer framing: bfloat16 activations must
        cross the wire bit-exact, not via a float32 detour."""
        arr = jnp.linspace(-3, 3, 16, dtype=jnp.bfloat16).reshape(4, 4)
        meta, back = mpmd.decode_frame(mpmd.encode_frame({"m": 0}, arr))
        assert str(back.dtype) == "bfloat16"
        np.testing.assert_array_equal(back, np.asarray(arr))

    def test_truncated_frame_raises(self):
        frame = mpmd.encode_frame({"m": 0}, np.ones((4,), np.float32))
        with pytest.raises(mpmd.MPMDTransferError, match="truncated"):
            mpmd.decode_frame(frame[:-2])

    def test_bad_magic_raises(self):
        with pytest.raises(mpmd.MPMDTransferError, match="wire frame"):
            mpmd.decode_frame(b"NOPE" + b"\x00" * 32)


# ---------------------------------------------------------------------------
# stage plan
# ---------------------------------------------------------------------------


class TestPlan:
    def test_layers_partition_exactly(self):
        plan = mpmd.plan_stages(num_microbatches=4, num_virtual_stages=2,
                                num_stages=2, n_layers=8)
        assert plan.Lc == 2
        owned = [plan.layers_for_stage(d) for d in range(plan.S)]
        # chunk-major local order: stage d owns chunks d, d+S, ...
        assert owned[0] == [0, 1, 4, 5]
        assert owned[1] == [2, 3, 6, 7]
        assert sorted(sum(owned, [])) == list(range(8))
        d = plan.describe()
        assert d["num_stages"] == 2 and d["n_layers"] == 8
        assert d["n_cycles"] == int(plan.n_cycles)

    def test_invalid_plans_raise(self):
        with pytest.raises(ValueError, match="num_stages >= 2"):
            mpmd.plan_stages(4, 2, 1, 8)
        with pytest.raises(ValueError, match="chunks"):
            mpmd.plan_stages(4, 2, 2, 6)
        with pytest.raises(ValueError, match="num_microbatches"):
            mpmd.plan_stages(0, 2, 2, 8)

    def test_slice_assemble_roundtrip(self):
        plan = mpmd.plan_stages(2, 2, 2, 8)
        stack = {"w": jnp.arange(8 * 3, dtype=jnp.float32).reshape(8, 3)}
        back = mpmd.assemble_layer_grads(
            plan, [mpmd.slice_stage_params(plan, d, stack)
                   for d in range(plan.S)])
        np.testing.assert_array_equal(np.asarray(back["w"]),
                                      np.asarray(stack["w"]))


class TestCheckMpmdPlan:
    """The library checker `check --deep` drives (analysis/spmd_check.py):
    the same arithmetic plan_stages enforces, available without jax."""

    def test_valid(self):
        assert check_mpmd_plan(4, 2, 2, 8) == []
        assert check_mpmd_plan(4, 2, 2, 8, gang_size=2, n_hosts=4) == []

    def test_single_stage(self):
        assert any("num_stages >= 2" in p for p in check_mpmd_plan(4, 2, 1, 8))

    def test_layer_divisibility(self):
        assert any("chunks" in p for p in check_mpmd_plan(4, 2, 2, 6))

    def test_gang_size_mismatch(self):
        probs = check_mpmd_plan(4, 2, 2, 8, gang_size=3)
        assert any("never assemble" in p for p in probs)

    def test_stage_host_alignment(self):
        probs = check_mpmd_plan(4, 1, 2, 8, n_hosts=3)
        assert any("host boundary" in p for p in probs)
        assert check_mpmd_plan(4, 1, 2, 8, n_hosts=4) == []

    def test_bad_counts(self):
        assert any("num_microbatches" in p for p in check_mpmd_plan(0, 2, 2, 8))
        assert any("num_virtual_stages" in p
                   for p in check_mpmd_plan(4, 0, 2, 8))


# ---------------------------------------------------------------------------
# 2-stage parity vs the single-gang interleaved schedule
# ---------------------------------------------------------------------------

S, V, M = 2, 2, 4
L, D, B = 4, 8, 8


def _toy_problem():
    key = jax.random.PRNGKey(0)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    params = {"w": jax.random.normal(k1, (L, D, D), jnp.float32) * 0.3,
              "b": jax.random.normal(k2, (L, D), jnp.float32) * 0.1}
    x = jax.random.normal(k3, (B, D), jnp.float32)
    y = jax.random.normal(k4, (B, D), jnp.float32)
    head = {"scale": jnp.ones((D,), jnp.float32) * 1.1}

    def layer_fn(c, lp):
        return jnp.tanh(c @ lp["w"] + lp["b"])

    def loss_fn(out, t, h):
        return jnp.mean((out * h["scale"] - t) ** 2)

    return params, x, y, head, layer_fn, loss_fn


def _mpmd_run(plan, params, x, y, head, layer_fn, loss_fn,
              double_buffer=True, **transport_kw):
    peers = _free_peers(plan.S)
    mb = B // M
    x_mbs = x.reshape((M, mb, D))
    y_mbs = y.reshape((M, mb, D))

    def stage_main(d):
        tr = mpmd.StageTransport(d, plan.S, peers,
                                 double_buffer=double_buffer,
                                 **transport_kw)
        with tr.start():
            ex = mpmd.StageExecutor(
                plan, d, tr, layer_fn,
                loss_fn=loss_fn if d == plan.S - 1 else None,
                return_input_grad=(d == 0))
            res = ex.run(
                mpmd.slice_stage_params(plan, d, params),
                x_mbs=x_mbs if d == 0 else None,
                y_mbs=y_mbs if d == plan.S - 1 else None,
                head_params=head if d == plan.S - 1 else None)
            res["stall_ms"] = ex.last_transfer_stall_ms
        # snapshot AFTER close: it joins the sender threads, so every
        # queued frame has hit the wire and bumped the counters
        res["stats"] = tr.stats()
        return res

    return _run_stage_threads(plan.S, stage_main)


class TestTwoStageParity:
    @pytest.fixture(scope="class")
    def reference(self):
        params, x, y, head, layer_fn, loss_fn = _toy_problem()
        mesh = create_mesh(MeshSpec({"pipeline": S}), n_devices=S)
        params_sh = jax.tree.map(
            lambda p: jax.device_put(p, NamedSharding(mesh, P("pipeline"))),
            params)
        loss, grads, aux = pipeline_train_interleaved(
            layer_fn, loss_fn, params_sh, x, y, mesh, num_microbatches=M,
            num_virtual_stages=V, head_params=head, return_input_grad=True)
        return loss, grads, aux

    @pytest.mark.parametrize("double_buffer", [True, False],
                             ids=["double_buffered", "sync"])
    def test_loss_and_grad_parity(self, reference, double_buffer):
        """Same schedule tables, same dtype discipline, real TCP between
        the two stage programs — loss, every layer grad, the head grad,
        and the input cotangent all match the SPMD interleaved run."""
        ref_loss, ref_grads, ref_aux = reference
        params, x, y, head, layer_fn, loss_fn = _toy_problem()
        plan = mpmd.plan_stages(M, V, S, L)
        results = _mpmd_run(plan, params, x, y, head, layer_fn, loss_fn,
                            double_buffer=double_buffer)
        np.testing.assert_allclose(
            np.asarray(results[S - 1]["loss"]), np.asarray(ref_loss),
            rtol=1e-5, atol=1e-5)
        grads = mpmd.assemble_layer_grads(
            plan, [r["grads"] for r in results])
        for k in ("w", "b"):
            np.testing.assert_allclose(
                np.asarray(grads[k]), np.asarray(ref_grads[k]),
                rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(results[S - 1]["head_grads"]["scale"]),
            np.asarray(ref_aux["head_grads"]["scale"]),
            rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(results[0]["input_grad"].reshape(x.shape)),
            np.asarray(ref_aux["input_grad"]),
            rtol=1e-4, atol=1e-5)

    def test_transports_agree_bit_for_bit(self):
        """Double buffering changes when a frame moves, never what it
        holds: the loss and every gradient of the double-buffered run
        are the synchronous run's, bit for bit."""
        params, x, y, head, layer_fn, loss_fn = _toy_problem()
        plan = mpmd.plan_stages(M, V, S, L)
        db, sync = (_mpmd_run(plan, params, x, y, head, layer_fn, loss_fn,
                              double_buffer=flag) for flag in (True, False))
        assert float(db[S - 1]["loss"]) == float(sync[S - 1]["loss"])
        for a, b in zip(jax.tree.leaves([r["grads"] for r in db]),
                        jax.tree.leaves([r["grads"] for r in sync])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_transport_stats_account_the_exchange(self, reference):
        """Every frame sent is received by the peer; stall time is
        tracked; both rings carry traffic."""
        params, x, y, head, layer_fn, loss_fn = _toy_problem()
        plan = mpmd.plan_stages(M, V, S, L)
        results = _mpmd_run(plan, params, x, y, head, layer_fn, loss_fn)
        stats = [r["stats"] for r in results]
        assert sum(s["frames_sent"] for s in stats) == \
            sum(s["frames_recv"] for s in stats) > 0
        assert sum(s["bytes_sent"] for s in stats) == \
            sum(s["bytes_recv"] for s in stats) > 0
        for r, s in zip(results, stats):
            assert s["double_buffer"] is True
            assert s["stall_ms"] == pytest.approx(
                s["stall_send_ms"] + s["stall_recv_ms"])
            assert r["stall_ms"] >= 0


# ---------------------------------------------------------------------------
# bounded deadlines + peer death (the chaos/elastic contract)
# ---------------------------------------------------------------------------


def _paired_transports(double_buffer, recv_timeout_s):
    peers = _free_peers(2)

    def stage_main(d):
        return mpmd.StageTransport(
            d, 2, peers, double_buffer=double_buffer,
            recv_timeout_s=recv_timeout_s).start()

    return _run_stage_threads(2, stage_main)


class TestBoundedRecv:
    @pytest.mark.parametrize("double_buffer", [True, False],
                             ids=["double_buffered", "sync"])
    def test_recv_deadline_expires(self, double_buffer):
        """A silent peer (hung stage) must surface as a timeout within
        the bounded deadline — never an infinite block."""
        t0, t1 = _paired_transports(double_buffer, recv_timeout_s=0.4)
        try:
            with pytest.raises(mpmd.MPMDTransferTimeout):
                t1.recv(mpmd.CHAN_ACT)
        finally:
            t0.close()
            t1.close()

    def test_peer_death_fails_survivor_promptly(self):
        """A DEAD peer (chaos stage kill) is faster than the deadline:
        the socket EOF reaches the survivor's receiver immediately, and
        every later recv re-raises instead of wedging."""
        t0, t1 = _paired_transports(True, recv_timeout_s=30.0)
        try:
            t0.close()  # stage 0 dies mid-schedule
            import time
            deadline = time.perf_counter() + 5.0
            with pytest.raises(mpmd.MPMDTransferError):
                t1.recv(mpmd.CHAN_ACT)
            assert time.perf_counter() < deadline, \
                "survivor blocked instead of failing on peer EOF"
            with pytest.raises(mpmd.MPMDTransferError):
                t1.recv(mpmd.CHAN_ACT)  # sentinel is sticky
        finally:
            t1.close()

    def test_world_of_one_rejected(self):
        with pytest.raises(ValueError, match="world >= 2"):
            mpmd.StageTransport(0, 1, ["127.0.0.1:1"])


class TestBoundedSend:
    def test_outbound_sockets_use_send_deadline_not_connect_timeout(self):
        """Regression: `create_connection`'s 1s CONNECT timeout must not
        govern steady-state sendall — >1s of send backpressure (peer
        mid-jit-compile, full prefetch queue, real DCN latency) is
        normal operation, not peer death. Sends get their own generous
        deadline, defaulting to the recv deadline."""
        t0, t1 = _paired_transports(True, recv_timeout_s=30.0)
        try:
            for t in (t0, t1):
                assert t.send_timeout_s == pytest.approx(30.0)
                for sock in t._out.values():
                    assert sock.gettimeout() == pytest.approx(
                        t.send_timeout_s)
        finally:
            t0.close()
            t1.close()

    def test_send_queue_put_is_bounded_when_sender_dies(self):
        """The double-buffered put re-checks sender-thread health every
        beat and carries an overall deadline: a sender thread that died
        leaving the queue full raises instead of wedging the stage in a
        `put` the recv deadline can never reach."""
        t0, t1 = _paired_transports(True, recv_timeout_s=30.0)
        orig_q = None
        try:
            t0.send_timeout_s = 0.5
            # model the sender thread dying between the error check and
            # the put: a full queue that nothing drains
            dead_q = queue.Queue(maxsize=1)
            dead_q.put_nowait(("stale", {}))
            orig_q = t0._send_q[mpmd.CHAN_ACT]
            t0._send_q[mpmd.CHAN_ACT] = dead_q
            start = time.perf_counter()
            with pytest.raises(mpmd.MPMDTransferTimeout,
                               match="send queue full"):
                t0.send(mpmd.CHAN_ACT, np.zeros((2,), np.float32),
                        {"m": 0, "v": 1})
            assert time.perf_counter() - start < 5.0
        finally:
            if orig_q is not None:
                # the real sender thread still drains the ORIGINAL
                # queue: put it back so close() can hand it the stop
                # sentinel instead of burning the join timeout
                t0._send_q[mpmd.CHAN_ACT] = orig_q
            t0.close()
            t1.close()

    def test_dead_sender_error_preempts_the_put(self):
        """A recorded sender-thread error surfaces on the NEXT send even
        when the queue has room (the pre-put health check)."""
        t0, t1 = _paired_transports(True, recv_timeout_s=30.0)
        try:
            boom = mpmd.MPMDTransferError("sender thread died")
            t0._send_error[mpmd.CHAN_COT] = boom
            with pytest.raises(mpmd.MPMDTransferError,
                               match="sender thread died"):
                t0.send(mpmd.CHAN_COT, np.zeros((2,), np.float32),
                        {"m": 0, "v": 0})
        finally:
            t0.close()
            t1.close()


class TestRendezvousRobustness:
    def test_stray_connection_does_not_wedge_rendezvous(self, monkeypatch):
        """A port-scanner-style connection that never sends its hello
        must not park the acceptor past the rendezvous deadline: an
        accepted socket is BLOCKING (the listener's timeout does not
        propagate), so the hello read needs its own bound."""
        monkeypatch.setenv("TPUFLOW_MPMD_CONNECT_TIMEOUT_S", "15")
        peers = _free_peers(2)
        stray = {}
        stray_in = threading.Event()

        def _stray_dial():
            addr = mpmd._parse_addr(peers[0])
            while "sock" not in stray:
                try:
                    stray["sock"] = socket.create_connection(
                        addr, timeout=0.2)
                except OSError:
                    time.sleep(0.02)
            stray_in.set()

        threading.Thread(target=_stray_dial, daemon=True).start()

        def stage_main(d):
            if d == 1:
                # hold stage 1 back until the silent stray has reached
                # stage 0's listener, so the acceptor services the
                # hello-less socket before the real peer's dials
                assert stray_in.wait(timeout=10)
                time.sleep(0.3)
            return mpmd.StageTransport(
                d, 2, peers, recv_timeout_s=10.0).start()

        t0, t1 = _run_stage_threads(2, stage_main)
        try:
            t0.send(mpmd.CHAN_ACT, np.arange(3, dtype=np.float32),
                    {"m": 0, "v": 1})
            meta, arr = t1.recv(mpmd.CHAN_ACT)
            assert meta["m"] == 0 and arr.shape == (3,)
        finally:
            if "sock" in stray:
                stray["sock"].close()
            t0.close()
            t1.close()


class TestEnvPlumbing:
    def test_transport_from_env(self, monkeypatch):
        peers = _free_peers(2)
        monkeypatch.setenv("MF_MPMD_PEERS", ",".join(peers))
        monkeypatch.setenv("MF_PARALLEL_NUM_NODES", "2")
        ts = []
        for d in range(2):
            monkeypatch.setenv("MF_PARALLEL_NODE_INDEX", str(d))
            tr = mpmd.transport_from_env()
            assert tr.stage == d and tr.world == 2
            ts.append(tr)
        _run_stage_threads(2, lambda d: ts[d].start())
        for t in ts:
            t.close()

    def test_sync_env_switch(self, monkeypatch):
        monkeypatch.setenv("MF_MPMD_PEERS", ",".join(_free_peers(2)))
        monkeypatch.setenv("MF_PARALLEL_NUM_NODES", "2")
        monkeypatch.setenv("MF_PARALLEL_NODE_INDEX", "0")
        monkeypatch.setenv("TPUFLOW_MPMD_SYNC", "1")
        assert mpmd.transport_from_env().double_buffer is False

    def test_gang_launch_exports_peers(self):
        """The @parallel local gang launch must hand every rank the
        stage ring (one loopback address per rank) via MF_MPMD_PEERS."""
        from metaflow_tpu.plugins import parallel_decorator as pd

        src = open(pd.__file__).read()
        assert "MF_MPMD_PEERS" in src


# ---------------------------------------------------------------------------
# full-Llama 2-stage run: telemetry surface + pinned schemas + metrics CLI
# ---------------------------------------------------------------------------


class TestStageTrainerTelemetry:
    @pytest.fixture()
    def recorder(self, tpuflow_root):
        fds = FlowDataStore("MPMDTelemetryFlow", LocalStorage)
        telemetry.init_recorder(fds, "r1", "train", "7", attempt=0)
        yield fds
        telemetry.close_recorder()

    @pytest.mark.slow  # two real jit compiles (~18s); schema pins are also
    # covered by the fast TestMetricsPipelineBound/TestSanitizerVocabulary
    def test_records_validate_and_aggregate(self, recorder):
        """One real 2-stage tiny-Llama MPMD run: every mpmd.* event and
        per-stage step record validates against the pinned schemas, and
        `tpuflow metrics` aggregation produces the per-stage section."""
        cfg = llama.LlamaConfig.tiny(n_layers=4)
        plan = mpmd.plan_stages(num_microbatches=4, num_virtual_stages=2,
                                num_stages=2, n_layers=4)
        tokens = np.asarray(jax.random.randint(
            jax.random.PRNGKey(1), (8, 17), 0, cfg.vocab_size))
        peers = _free_peers(plan.S)

        def stage_main(d):
            tr = mpmd.StageTransport(d, plan.S, peers)
            with tr.start():
                out, summary = run_stage_steps(
                    cfg, plan, d, tr, tokens, num_steps=2)
            return out, summary

        results = _run_stage_threads(plan.S, stage_main)
        losses = [r[0]["loss"] for r in results]
        assert sum(x is not None for x in losses) == 1
        assert float([x for x in losses if x is not None][0]) > 0
        # the summary mean transfer stall rides the report (steps counts
        # steady-state intervals: step 0 compiled, step 1 is steady)
        for _out, summary in results:
            assert summary["steps"] >= 1
            assert "transfer_stall_ms" in summary

        records = telemetry.read_run_records(recorder, "r1")
        traces = [r for r in records if r["name"] == "mpmd.stage.trace"]
        transfers = [r for r in records if r["name"] == "mpmd.transfer"]
        assert len(traces) == plan.S
        assert len(transfers) == plan.S * 2  # one per stage per step
        for r in traces + transfers:
            schema_validate.validate_pipeline_record(r)
        assert sorted(r["data"]["stage"] for r in traces) == [0, 1]
        assert {tuple(r["data"]["layers"]) for r in traces} == \
            {(0, 2), (1, 3)}
        steps = [r for r in records if r["name"].endswith(".step")
                 and r["name"].startswith("mpmd.stage")]
        assert {r["name"] for r in steps} == \
            {"mpmd.stage0.step", "mpmd.stage1.step"}
        for r in steps:
            schema_validate.validate_train_step_record(r)
            assert "transfer_stall_ms" in r["data"]

        from metaflow_tpu.cmd import metrics as cmd_metrics

        agg = cmd_metrics.aggregate(records)
        stages = {row["stage"]: row for row in agg["mpmd"]["stages"]}
        assert sorted(stages) == [0, 1]
        for row in stages.values():
            assert row["steps"] == 2
            assert row["mean_step_ms"] > 0
            assert row["frames_sent"] > 0 and row["bytes_sent"] > 0
            assert row["double_buffer"] is True
            assert "transfer_stall_ms" in row
        assert agg["mpmd"]["plan"]["num_stages"] == 2
        assert agg["mpmd"]["bottleneck_stage"] in (0, 1)
        lines = []
        cmd_metrics.render_summary("r1", agg, echo=lines.append)
        text = "\n".join(lines)
        assert "mpmd pipeline" in text and "stage 0:" in text

    def test_pipeline_trace_pin_matches_spmd_emitter(self, recorder):
        """The single-program pipeline's `pipeline.trace` event (emitted
        once per compile by pipeline_loss_and_grads) validates against
        the pin — the schedule-config surface both pipelines share."""
        from metaflow_tpu.training.pipeline_trainer import (
            pipeline_loss_and_grads,
        )

        cfg = llama.LlamaConfig.tiny(n_layers=4)
        mesh = create_mesh(MeshSpec({"pipeline": 2}), n_devices=2)
        params = jax.tree.map(
            lambda p: p.astype(jnp.float32),
            llama.init_params(jax.random.PRNGKey(0), cfg))
        tokens = jnp.asarray(np.random.RandomState(0).randint(
            0, cfg.vocab_size, (8, 17)))
        try:
            with mesh:
                loss, _grads = pipeline_loss_and_grads(
                    params, tokens, cfg, mesh, num_microbatches=4,
                    num_virtual_stages=2)
            assert float(loss) > 0
        except NotImplementedError:
            # some jax versions lack a replication rule the shard_map
            # loop needs on CPU (test_training.py's pipeline parity
            # tests track that); the trace event fires before the loop,
            # which is all this pin needs
            pass
        telemetry.flush()
        records = telemetry.read_run_records(recorder, "r1")
        traces = [r for r in records if r["name"] == "pipeline.trace"]
        assert traces, "pipeline.trace never emitted"
        for r in traces:
            schema_validate.validate_pipeline_record(r)
            assert r["data"]["num_microbatches"] == 4
            assert r["data"]["n_layers"] == 4


class TestMetricsPipelineBound:
    def test_slowest_stage_flagged(self):
        """Synthetic per-stage records: stage 1 is 3x slower and stage 0
        stalls >=10% of its step on the wire -> the summary names stage
        1 PIPELINE-BOUND (the MPMD mirror of INPUT-BOUND)."""
        from metaflow_tpu.cmd import metrics as cmd_metrics

        def step_rec(stage, n, ms, stall):
            return {"v": 1, "type": "timer",
                    "name": "mpmd.stage%d.step" % stage, "ms": ms,
                    "ok": True, "step_num": n, "rank": stage,
                    "step": "train", "task_id": "t%d" % stage,
                    "data": {"transfer_stall_ms": stall,
                             "tokens_per_sec": 10.0}}

        def transfer_rec(stage, stall):
            return {"v": 1, "type": "event", "name": "mpmd.transfer",
                    "rank": stage, "step": "train",
                    "task_id": "t%d" % stage,
                    "data": {"stage": stage, "double_buffer": True,
                             "frames_sent": 6, "frames_recv": 6,
                             "bytes_sent": 1000, "bytes_recv": 1000,
                             "stall_ms": stall}}

        records = []
        for n in range(3):
            records.append(step_rec(0, n, 10.0, 4.0))   # 40% stalled
            records.append(step_rec(1, n, 30.0, 0.5))   # the bubble
            records.append(transfer_rec(0, 4.0))
            records.append(transfer_rec(1, 0.5))
        agg = cmd_metrics.aggregate(records)
        assert agg["mpmd"]["bottleneck_stage"] == 1
        assert agg["mpmd"]["pipeline_bound"] is True
        rows = {r["stage"]: r for r in agg["mpmd"]["stages"]}
        assert rows[0]["transfer_stall_frac"] >= 0.1
        lines = []
        cmd_metrics.render_summary("r1", agg, echo=lines.append)
        text = "\n".join(lines)
        assert "PIPELINE-BOUND" in text
        bound_lines = [l for l in lines if "PIPELINE-BOUND" in l]
        assert len(bound_lines) == 1 and "stage 1:" in bound_lines[0]

    def test_balanced_pipeline_not_flagged(self):
        from metaflow_tpu.cmd import metrics as cmd_metrics

        records = [
            {"v": 1, "type": "timer", "name": "mpmd.stage%d.step" % d,
             "ms": 10.0, "ok": True, "step_num": n, "rank": d,
             "step": "train", "task_id": "t%d" % d,
             "data": {"transfer_stall_ms": 0.2}}
            for n in range(3) for d in (0, 1)
        ]
        agg = cmd_metrics.aggregate(records)
        assert agg["mpmd"]["pipeline_bound"] is False
        lines = []
        cmd_metrics.render_summary("r1", agg, echo=lines.append)
        assert "PIPELINE-BOUND" not in "\n".join(lines)


class TestSanitizerVocabulary:
    def test_mpmd_collectives_pinned(self):
        """mpmd.send/mpmd.recv are part of the pinned collective
        vocabulary on BOTH sides of the contract (sanitizer + schema)."""
        from metaflow_tpu.spmd import sanitizer

        assert "mpmd.send" in sanitizer.COLLECTIVE_NAMES
        assert "mpmd.recv" in sanitizer.COLLECTIVE_NAMES
        assert tuple(schema_validate.SANITIZE_COLLECTIVE_NAMES) == \
            tuple(sanitizer.COLLECTIVE_NAMES)

    def test_handoffs_journaled(self):
        """With the sanitizer installed, a schedule pass journals every
        handoff with the transfer identity — the stream a desync report
        needs to name the first diverging transfer."""
        from metaflow_tpu.spmd import sanitizer

        params, x, y, head, layer_fn, loss_fn = _toy_problem()
        plan = mpmd.plan_stages(M, V, S, L)
        # journal-only: no datastore is touched until a barrier publishes
        san = sanitizer.set_active(
            sanitizer.GangSanitizer(None, "r1", rank=0, world=1))
        try:
            _mpmd_run(plan, params, x, y, head, layer_fn, loss_fn)
        finally:
            sanitizer.uninstall()
        sigs = [s for _seq, s in san._sigs]
        sends = [s for s in sigs if "|mpmd.send|" in s]
        recvs = [s for s in sigs if "|mpmd.recv|" in s]
        assert sends and recvs
        # transfer identity (chan:m:v) rides in the signature
        assert any("act:m" in s for s in sends)
        assert any("cot:m" in s for s in sends)


# ---------------------------------------------------------------------------
# demo flow: real gang launch + env plumbing (+ chaos recovery, slow)
# ---------------------------------------------------------------------------


class TestMpmdPipelineFlow:
    @pytest.mark.slow  # full flow run: scheduler fork + 2-rank gang
    def test_flow_runs_clean(self, run_flow, flows_dir):
        """The docs/training.md demo flow end to end: gang fork,
        MF_MPMD_PEERS plumbing, one loss owner, schedule-tick parity
        across stages."""
        proc = run_flow(os.path.join(flows_dir, "mpmd_pipeline_flow.py"),
                        "run")
        out = proc.stdout + proc.stderr
        assert "mpmd pipeline done" in out, out

    @pytest.mark.slow
    def test_chaos_stage_kill_recovers(self, run_flow, flows_dir,
                                       tmp_path):
        """TPUFLOW_CHAOS=1:1 kills stage 1 at its step-1 boundary —
        mid-transfer from stage 0's point of view. The survivor must
        fail promptly through the bounded recv deadline / peer EOF (not
        wedge), and the @retry gang relaunch must complete the run."""
        proc = run_flow(
            os.path.join(flows_dir, "mpmd_pipeline_flow.py"), "run",
            env_extra={
                "TPUFLOW_CHAOS": "1:1",
                "TPUFLOW_CHAOS_DIR": str(tmp_path / "chaos"),
                "MPMD_FLOW_STEPS": "3",
                "TPUFLOW_MPMD_RECV_TIMEOUT_S": "20",
            })
        out = proc.stdout + proc.stderr
        assert "mpmd pipeline done" in out, out
