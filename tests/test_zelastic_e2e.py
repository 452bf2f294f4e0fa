"""Elastic gang supervision — end-to-end layer (real gangs, real
SIGTERMs; named to sort last so the fast unit tiers run first).

The ROADMAP item 5 gate: a seeded kill-and-shrink run (8 -> 4 ranks
mid-training, driven by the chaos harness) reaches the same loss
trajectory as an uninterrupted run and is token-exact on data order; a
follow-on grow-back (4 -> 8) continues without repeating or skipping a
token. Plus: repeated-kill resilience, checkpoint restore onto a
SMALLER mesh (the model-state half of a resize), and the pinned elastic
telemetry surface from a live run.
"""

import os
import re
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from metaflow_tpu import telemetry
from metaflow_tpu.datastore import FlowDataStore, LocalStorage

from schema_validate import validate_elastic_record

FLOWS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "flows")


def _run_records(tpuflow_root, run_id):
    fds = FlowDataStore("ElasticTrainFlow", LocalStorage,
                        ds_root=tpuflow_root, blob_cache=False)
    return telemetry.read_run_records(fds, run_id)


def _run_id_of(out):
    m = re.search(r"run-id (\d+)", out)
    assert m, out
    return m.group(1)


class TestShrinkGrowE2E:
    def test_kill_shrink_grow_token_exact(self, run_flow, tpuflow_root,
                                          tmp_path):
        """8 ranks; rank 2 reclaimed at step 3; capacity oracle admits 4
        -> supervisor shrinks; when the script reports 8 again the gang
        grows back at the next checkpoint boundary. The script is indexed
        by consultation, and the flow honours a notice only at a boundary
        the attempt has itself written, so the 4-rank gang always leaves
        steps in the record however late it starts. The flow's own `end`
        step asserts the loss trajectory and token order are EXACTLY the
        uninterrupted run's."""
        proc = run_flow(
            os.path.join(FLOWS, "elastic_train_flow.py"), "run",
            env_extra={
                "TPUFLOW_CHAOS": "3:2",
                "TPUFLOW_CHAOS_DIR": str(tmp_path / "chaos"),
                "TPUFLOW_CAPACITY_ORACLE": "scripted:4,8",
                "TPUFLOW_ELASTIC_GROW_EVERY_S": "4",
                "TPUFLOW_RETRY_BACKOFF_BASE_S": "0.05",
                "ELASTIC_FLOW_RANKS": "8",
                "ELASTIC_FLOW_STEPS": "45",
                "ELASTIC_FLOW_SLEEP": "0.08",
            })
        out = proc.stdout + proc.stderr
        # the flow only prints this after its exact-replay asserts pass
        assert "elastic run ok" in out, out
        # steps were recorded at BOTH sizes, and the final gang is full
        assert "worlds=[4, 8] final_world=8" in out, out
        assert "Elastic resize (shrink): " in out, out
        assert "Elastic resize (grow): " in out, out

        # the pinned elastic telemetry surface, from the live run
        records = _run_records(tpuflow_root, _run_id_of(out))
        by_name = {}
        for r in records:
            by_name.setdefault(r.get("name"), []).append(r)
        resizes = by_name.get("elastic.resize", [])
        directions = [r["data"]["direction"] for r in resizes]
        assert "shrink" in directions and "grow" in directions, resizes
        assert by_name.get("elastic.backoff"), "no backoff event"
        assert by_name.get("chaos.kill"), "no chaos.kill event"
        kills = by_name["chaos.kill"]
        assert kills[0]["data"] == {"step": 3, "rank": 2, "world": 8}
        goodput = by_name.get("elastic.goodput", [])
        assert goodput and 0 < goodput[0]["value"] <= 1.0
        for r in (resizes + by_name["elastic.backoff"] + kills + goodput):
            validate_elastic_record(r)

    def test_repeated_kills_fixed_size(self, run_flow, tpuflow_root,
                                       tmp_path):
        """Two different ranks reclaimed in one run, no resize (capacity
        stays full): each kill costs one checkpoint interval, the ledger
        guarantees each fires exactly once across attempts, and the run
        still finishes token-exact."""
        proc = run_flow(
            os.path.join(FLOWS, "elastic_train_flow.py"), "run",
            env_extra={
                "TPUFLOW_CHAOS": "2:1,6:3",
                "TPUFLOW_CHAOS_DIR": str(tmp_path / "chaos"),
                "TPUFLOW_CAPACITY_ORACLE": "static:4",
                "TPUFLOW_RETRY_BACKOFF_BASE_S": "0.05",
                "ELASTIC_FLOW_RANKS": "4",
                "ELASTIC_FLOW_STEPS": "14",
                "ELASTIC_FLOW_SLEEP": "0.05",
            })
        out = proc.stdout + proc.stderr
        assert "elastic run ok" in out, out
        assert "final_world=4" in out, out
        records = _run_records(tpuflow_root, _run_id_of(out))
        kills = [r for r in records if r.get("name") == "chaos.kill"]
        assert sorted((r["data"]["step"], r["data"]["rank"])
                      for r in kills) == [(2, 1), (6, 3)]

    def test_seeded_schedule_replays_in_flow(self, run_flow, tpuflow_root,
                                             tmp_path):
        """TPUFLOW_CHAOS=<seed>: the kill schedule is a pure function of
        the seed — the delivered chaos.kill events match what the
        harness computes offline for the same (seed, horizon, world)."""
        from metaflow_tpu.devtools.chaos import KillSchedule

        expected = KillSchedule.seeded(42, 8, 2, n_kills=1)
        proc = run_flow(
            os.path.join(FLOWS, "elastic_train_flow.py"), "run",
            env_extra={
                "TPUFLOW_CHAOS": "42",
                "TPUFLOW_CHAOS_STEPS": "8",
                "TPUFLOW_CHAOS_DIR": str(tmp_path / "chaos"),
                "TPUFLOW_RETRY_BACKOFF_BASE_S": "0.05",
                "ELASTIC_FLOW_RANKS": "2",
                "ELASTIC_FLOW_STEPS": "8",
                "ELASTIC_FLOW_SLEEP": "0.05",
            })
        out = proc.stdout + proc.stderr
        assert "elastic run ok" in out, out
        records = _run_records(tpuflow_root, _run_id_of(out))
        kills = sorted((r["data"]["step"], r["data"]["rank"])
                       for r in records if r.get("name") == "chaos.kill")
        assert kills == sorted(expected.kills), (kills, expected.kills)


class TestReshardOntoSmallerMesh:
    def test_restore_like_half_mesh(self, tpuflow_root):
        """The model-state half of an elastic shrink: a checkpoint saved
        on an 8-device data mesh restores onto a 4-device mesh via
        restore(like=...) / reshard_like, values intact."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from metaflow_tpu.spmd import MeshSpec, create_mesh
        from metaflow_tpu.training import AsyncCheckpointManager

        fds = FlowDataStore("ElasticCkpt", LocalStorage)
        mesh8 = create_mesh(MeshSpec.dp())
        assert mesh8.devices.size == 8
        w = np.arange(64, dtype=np.float32).reshape(8, 8)
        state = {"w": jax.device_put(
            w, NamedSharding(mesh8, PartitionSpec("data")))}
        mgr = AsyncCheckpointManager(fds, name="resize")
        mgr.save(state, 3, extra={"cursor": 9})
        mgr.wait()

        mesh4 = create_mesh(MeshSpec.dp(), devices=jax.devices()[:4])
        like = {"w": jax.device_put(
            np.zeros((8, 8), np.float32),
            NamedSharding(mesh4, PartitionSpec("data")))}
        ck = AsyncCheckpointManager(fds, name="resize").restore(like=like)
        assert ck.step == 3 and ck.extra == {"cursor": 9}
        restored = ck.state["w"]
        np.testing.assert_array_equal(np.asarray(restored), w)
        assert restored.sharding.mesh.devices.size == 4

    def test_restore_zero_opt_state_half_mesh(self, tpuflow_root):
        """The optimizer-state half of an elastic shrink with the ZeRO
        sharded update on: opt state saved 1/8-sharded on 8 devices
        restores 1/4-sharded onto a 4-device mesh via restore(like=...),
        values intact (trajectory-level coverage in test_zero_update.py)."""
        import jax

        from metaflow_tpu.models import llama
        from metaflow_tpu.spmd import MeshSpec, create_mesh
        from metaflow_tpu.spmd import sharding as shd
        from metaflow_tpu.training import AsyncCheckpointManager, \
            make_trainer

        fds = FlowDataStore("ElasticZeroCkpt", LocalStorage)
        mesh8 = create_mesh(MeshSpec.dp())
        cfg = llama.LlamaConfig.tiny()
        state, _fn, _sh = make_trainer(
            jax.random.PRNGKey(0), cfg, mesh8, llama, zero=True)
        mgr = AsyncCheckpointManager(fds, name="zero-resize")
        mgr.save(state, 5)
        mgr.wait()

        mesh4 = create_mesh(MeshSpec.dp(), devices=jax.devices()[:4])
        state4, _fn4, _sh4 = make_trainer(
            jax.random.PRNGKey(1), cfg, mesh4, llama, zero=True,
            checkpoint=AsyncCheckpointManager(fds, name="zero-resize"))
        for a, b in zip(jax.tree.leaves(state["opt_state"]),
                        jax.tree.leaves(state4["opt_state"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # the restored state is 1/4-sharded on the SMALLER mesh — the
        # DP axis moved with the resize instead of replicating
        def on_data_axis(spec):
            return any(a == "data" for part in spec
                       for a in (part if isinstance(part, tuple)
                                 else (part,)))

        leaves4 = [x for x in jax.tree.leaves(state4["opt_state"])
                   if x.ndim and on_data_axis(x.sharding.spec)]
        assert leaves4, "no opt-state leaf sharded over the 4-way mesh"
        for x in leaves4:
            assert x.sharding.mesh.devices.size == 4
            assert shd.zero_spec(
                jax.sharding.PartitionSpec(), x.shape, mesh4) \
                == x.sharding.spec
