"""Sidecar framework, telemetry, tracing shim, FileCache, hybrid mesh,
data loader."""

import os

import numpy as np
import pytest


class TestSidecar:
    def test_message_roundtrip(self):
        from metaflow_tpu.sidecar import Message

        m = Message(Message.MUST_SEND, {"a": 1})
        out = Message.deserialize(m.serialize())
        assert out.kind == Message.MUST_SEND
        assert out.payload == {"a": 1}

    def test_null_sidecar(self):
        from metaflow_tpu.sidecar import Message, NullSidecar

        s = NullSidecar().start()
        assert not s.send(Message(Message.BEST_EFFORT))
        s.terminate()

    def test_lossy_send_after_death(self):
        from metaflow_tpu.sidecar import Message, Sidecar

        s = Sidecar("json.tool").start()  # exits immediately on bad input
        s._proc.kill()
        s._proc.wait()
        assert not s.send(Message(Message.MUST_SEND, {"x": 1}))


class TestTelemetry:
    def test_file_monitor_and_logger(self, tpuflow_root):
        from metaflow_tpu.system import (
            FileEventLogger,
            FileMonitor,
            read_metrics,
        )

        mon = FileMonitor(root=tpuflow_root)
        with mon.measure("compile"):
            pass
        with mon.count("tasks"):
            pass
        mon.gauge("hbm_gb", 3.5)
        records = read_metrics(root=tpuflow_root)
        kinds = {r["type"] for r in records}
        assert kinds == {"timer", "counter", "gauge"}

        logger = FileEventLogger(root=tpuflow_root)
        logger.log({"event": "x"})

    def test_task_emits_metrics(self, run_flow, flows_dir, tpuflow_root):
        from metaflow_tpu.system import read_metrics

        run_flow(os.path.join(flows_dir, "linear_flow.py"), "run")
        names = {r["name"] for r in read_metrics(root=tpuflow_root)}
        assert "metaflow.task.duration" in names
        assert "metaflow.task.start" in names


class TestTracing:
    def test_noop_by_default(self, monkeypatch):
        monkeypatch.delenv("TPUFLOW_OTEL_ENDPOINT", raising=False)
        import metaflow_tpu.tracing as tracing

        from metaflow_tpu import telemetry

        tracing._initialized = False
        with tracing.span("x") as s:
            assert s is None
        env = tracing.inject_tracing_vars({"A": "1"})
        assert env == {"A": "1"}
        # with no recorder, no endpoint and no profiler session a span
        # and an annotation are still blocks that run and propagate
        with telemetry.annotate("cmd", n=1) as span:
            span.set_metadata(done=True)
        with pytest.raises(KeyError):
            with tracing.span("x", {"a": 1}):
                raise KeyError("propagates")
        with telemetry.timer("y") as t:
            pass
        assert t.seconds >= 0


class TestFileCache:
    def test_store_load_evict(self, tmp_path):
        import hashlib

        from metaflow_tpu.client.filecache import FileCache

        # keys are the blobs' sha256 (load_key verifies content before
        # trusting a shared cache dir)
        blob1, blob2 = b"x" * 80, b"y" * 80
        key1 = hashlib.sha256(blob1).hexdigest()
        key2 = hashlib.sha256(blob2).hexdigest()

        cache = FileCache(cache_dir=str(tmp_path / "c"), max_size=400)
        cache.store_key(key1, blob1)
        assert cache.load_key(key1) == blob1
        assert cache.load_key("f" * 64) is None

        # a blob big enough to evict everything on store passes through
        big = b"z" * 200
        cache.store_key(hashlib.sha256(big).hexdigest(), big)
        assert cache.load_key(hashlib.sha256(big).hexdigest()) is None

        # corrupted entry (content != key) is evicted and treated as a miss
        import os

        poisoned = cache._path(key2)
        os.makedirs(os.path.dirname(poisoned), exist_ok=True)
        with open(poisoned, "wb") as f:
            f.write(b"not the real bytes")
        assert cache.load_key(key2) is None
        assert not os.path.exists(poisoned)

        # exceeding the cap evicts the oldest entry
        os.utime(cache._path(key1), (1, 1))  # force key1 oldest
        filler = []
        for i in range(5):
            b = ("f%d" % i).encode() * 40  # 80 bytes each
            filler.append(hashlib.sha256(b).hexdigest())
            cache.store_key(filler[-1], b)
        assert cache.load_key(key1) is None  # evicted
        assert cache.load_key(filler[-1]) is not None


class TestHybridMesh:
    def test_explicit_slices(self):
        import jax

        from metaflow_tpu.spmd import MeshSpec
        from metaflow_tpu.spmd.mesh import create_hybrid_mesh

        mesh = create_hybrid_mesh(
            MeshSpec.fsdp_tp(2), num_slices=2,
            devices=jax.devices()[:8],
        )
        assert dict(mesh.shape) == {"data": 2, "fsdp": 2, "tensor": 2}

    def test_single_slice_falls_back(self):
        from metaflow_tpu.spmd import MeshSpec
        from metaflow_tpu.spmd.mesh import create_hybrid_mesh

        mesh = create_hybrid_mesh(MeshSpec.fsdp(), num_slices=1)
        assert "fsdp" in mesh.axis_names

    def test_bad_division(self):
        import jax

        from metaflow_tpu.spmd import MeshSpec
        from metaflow_tpu.spmd.mesh import create_hybrid_mesh

        with pytest.raises(ValueError):
            create_hybrid_mesh(MeshSpec.fsdp(), num_slices=3,
                               devices=jax.devices()[:8])


class TestDataLoader:
    def test_token_batches(self):
        from metaflow_tpu.training.data import token_batches

        data = np.arange(100)
        batches = list(token_batches(data, batch_size=2, seq_len=9))
        assert all(b["tokens"].shape == (2, 10) for b in batches)
        # windows tile the stream without overlap
        flat = np.concatenate([b["tokens"].ravel() for b in batches])
        assert len(set(flat.tolist())) == len(flat)

    def test_resumable_restore_continues_exactly(self):
        """Restore from any mid-stream stamp → the remaining batches are
        bit-identical to the uninterrupted stream (no replay, no skip),
        including across the epoch boundary's reshuffle."""
        from metaflow_tpu.training.data import (STATE_KEY,
                                                ResumableTokenBatches)

        data = np.arange(300) % 89
        mk = lambda: ResumableTokenBatches(data, 3, 9, seed=7, epochs=2)
        full = list(mk())
        assert len(full) == mk().batches_per_epoch * 2
        for cut in (1, 4, len(full) - 2):  # mid-epoch-0, later, epoch-1
            ds = mk().restore(full[cut - 1][STATE_KEY])
            rest = list(ds)
            assert len(rest) == len(full) - cut
            for a, b in zip(rest, full[cut:]):
                np.testing.assert_array_equal(a["tokens"], b["tokens"])
                assert a[STATE_KEY] == b[STATE_KEY]

    def test_resumable_seed_mismatch_refused(self):
        from metaflow_tpu.training.data import ResumableTokenBatches

        ds = ResumableTokenBatches(np.arange(100), 2, 9, seed=1)
        state = next(iter(ds))["data_state"]
        import pytest

        with pytest.raises(ValueError, match="seed"):
            ResumableTokenBatches(np.arange(100), 2, 9, seed=2).restore(
                state)

    def test_stamp_survives_shard_and_prefetch(self):
        """The resume stamp rides host-side through mesh placement and
        the prefetch thread — the stamp a consumer checkpoints always
        matches the batch it just consumed, whatever the prefetch
        depth ran ahead to."""
        from metaflow_tpu.spmd import MeshSpec, create_mesh
        from metaflow_tpu.training.data import STATE_KEY, sharded_dataset

        mesh = create_mesh(MeshSpec.fsdp())
        data = np.arange(8 * 10 * 6)
        seen = []
        for batch in sharded_dataset(data, 8, 9, mesh, seed=3,
                                     prefetch_depth=3, epochs=1):
            assert batch[STATE_KEY]["cursor"] == len(seen) + 1
            seen.append(batch[STATE_KEY])
        # and sharded_dataset(state=...) resumes from a stamp
        resumed = list(sharded_dataset(data, 8, 9, mesh, state=seen[1],
                                       epochs=1))
        assert len(resumed) == len(seen) - 2
        assert resumed[0][STATE_KEY] == seen[2]

    def test_sharded_prefetch_trains(self):
        import jax

        from metaflow_tpu.models import llama
        from metaflow_tpu.spmd import MeshSpec, create_mesh
        from metaflow_tpu.training import (
            default_optimizer,
            make_trainer,
        )
        from metaflow_tpu.training.data import sharded_dataset

        cfg = llama.LlamaConfig.tiny()
        mesh = create_mesh(MeshSpec.fsdp())
        state, step_fn, _ = make_trainer(
            jax.random.PRNGKey(0), cfg, mesh, llama,
            optimizer=default_optimizer(lr=1e-2, warmup_steps=1,
                                        total_steps=10),
        )
        data = np.random.default_rng(0).integers(
            0, cfg.vocab_size, size=8 * 33 * 4
        )
        losses = []
        with mesh:
            for batch in sharded_dataset(data, 8, 32, mesh):
                state, m = step_fn(state, batch)
                losses.append(float(m["loss"]))
        assert len(losses) == 4
        assert losses[-1] < losses[0]
