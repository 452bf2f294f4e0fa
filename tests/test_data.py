"""Streaming dataset subsystem (metaflow_tpu/data/): corpus build +
manifest schema, byte-identity with the in-memory loader, exact-resume
equivalence (shard boundaries, epoch rollover), per-host disjoint
coverage and corrupted-shard handling against fake GCS, sequence
packing, data.* telemetry schema, the input-stall metric, and the
parallel reader against a one-shard-at-a-time loop."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from fake_gcs import FakeGCSServer  # noqa: E402
from schema_validate import (  # noqa: E402
    validate_data_record,
    validate_dataset_manifest,
    validate_train_step_record,
)

from metaflow_tpu.data import (  # noqa: E402
    ShardCorruptionError,
    ShardReader,
    StreamingTokenBatches,
    build_corpus,
    load_manifest,
    pack_documents,
    packed_batches,
    segment_loss_mask,
)
from metaflow_tpu.data.shards import DatasetError, decode_shard  # noqa: E402
from metaflow_tpu.datastore import FlowDataStore  # noqa: E402
from metaflow_tpu.datastore.storage import (  # noqa: E402
    GCSStorage,
    LocalStorage,
)
from metaflow_tpu.training.data import (  # noqa: E402
    STATE_KEY,
    ResumableTokenBatches,
)

SEQ = 9
W = SEQ + 1
SHARD_WINDOWS = 3
SHARD_TOKENS = SHARD_WINDOWS * W


def make_data(n_shards=7, tail_tokens=0):
    n = n_shards * SHARD_TOKENS + tail_tokens
    return (np.arange(n) % 251).astype(np.int32)


@pytest.fixture()
def local_fds(tmp_path):
    return FlowDataStore("DataFlow", LocalStorage,
                         ds_root=str(tmp_path / "root"), blob_cache=False)


@pytest.fixture()
def gcs_fds(monkeypatch):
    with FakeGCSServer() as srv:
        monkeypatch.setenv("TPUFLOW_GS_ENDPOINT", srv.endpoint)
        fds = FlowDataStore("DataFlow", GCSStorage,
                            ds_root="gs://data-bucket/root",
                            blob_cache=False)
        yield fds, srv


class TestCorpusFormat:
    def test_manifest_schema_pinned(self, local_fds):
        data = make_data(3, tail_tokens=17)
        manifest = build_corpus(local_fds, "c", data,
                                shard_tokens=SHARD_TOKENS)
        validate_dataset_manifest(manifest)
        # the loaded copy validates too (what readers actually consume)
        validate_dataset_manifest(load_manifest(local_fds, "c"))
        # an invented field fails: the surface is PINNED
        with pytest.raises(Exception):
            validate_dataset_manifest(dict(manifest, compression="zstd"))
        # cross-field invariants are enforced beyond the JSON shape
        broken = dict(manifest, total_tokens=manifest["total_tokens"] + 1)
        with pytest.raises(Exception):
            validate_dataset_manifest(broken)

    def test_shards_are_content_addressed_and_checksummed(self, local_fds):
        import hashlib

        data = make_data(2)
        manifest = build_corpus(local_fds, "c", data,
                                shard_tokens=SHARD_TOKENS)
        for i, shard in enumerate(manifest["shards"]):
            blob = dict(local_fds.ca_store.load_blobs([shard["key"]]))[
                shard["key"]]
            assert hashlib.sha256(blob).hexdigest() == shard["sha256"]
            assert shard["sha256"] == shard["key"]
            assert np.array_equal(
                np.frombuffer(blob, dtype=np.dtype(manifest["dtype"])),
                data[i * SHARD_TOKENS:(i + 1) * SHARD_TOKENS])

    def test_build_rejections(self, local_fds):
        with pytest.raises(DatasetError):
            build_corpus(local_fds, "c", np.arange(0))
        with pytest.raises(DatasetError):
            build_corpus(local_fds, "a/b", np.arange(10))
        with pytest.raises(DatasetError):
            build_corpus(local_fds, "_c", np.arange(10))
        build_corpus(local_fds, "c", np.arange(10), shard_tokens=5)
        with pytest.raises(DatasetError):
            build_corpus(local_fds, "c", np.arange(10), shard_tokens=5)
        # overwrite=True rebuilds
        build_corpus(local_fds, "c", np.arange(20), shard_tokens=5,
                     overwrite=True)
        assert load_manifest(local_fds, "c")["total_tokens"] == 20

    def test_dtype_roundtrip(self, local_fds):
        data = (np.arange(40) % 7).astype(np.uint16)
        build_corpus(local_fds, "u16", data, shard_tokens=20)
        ds = StreamingTokenBatches(local_fds, "u16", 2, SEQ, epochs=1)
        batch = next(iter(ds))
        assert batch["tokens"].dtype == np.uint16


class TestByteIdentity:
    """The acceptance criterion: the streaming loader over a multi-shard
    on-datastore corpus yields the SAME token stream as the in-memory
    loader over the concatenated array (same seed) — sequential, and
    seeded via the shared hierarchical order."""

    @pytest.mark.parametrize("seed", [None, 7, 123])
    def test_stream_matches_in_memory(self, local_fds, seed):
        data = make_data(7)
        build_corpus(local_fds, "c", data, shard_tokens=SHARD_TOKENS)
        stb = StreamingTokenBatches(local_fds, "c", 4, SEQ, seed=seed,
                                    epochs=2)
        rtb = ResumableTokenBatches(data, 4, SEQ, seed=seed, epochs=2,
                                    shard_windows=SHARD_WINDOWS)
        got = [b["tokens"] for b in stb]
        want = [b["tokens"] for b in rtb]
        assert len(got) == len(want) > 0
        for g, w in zip(got, want):
            assert g.tobytes() == w.tobytes()

    def test_sequential_matches_plain_resumable(self, local_fds):
        """seed=None needs no shard_windows bridge: both loaders walk
        windows in order."""
        data = make_data(5)
        build_corpus(local_fds, "c", data, shard_tokens=SHARD_TOKENS)
        stb = StreamingTokenBatches(local_fds, "c", 3, SEQ, epochs=1)
        rtb = ResumableTokenBatches(data, 3, SEQ, epochs=1)
        for g, w in zip(stb, rtb):
            assert g["tokens"].tobytes() == w["tokens"].tobytes()

    def test_short_last_shard(self, local_fds):
        """A corpus whose last shard is short (and still holds windows)
        streams identically to the concatenated array."""
        data = make_data(4, tail_tokens=2 * W + 3)
        build_corpus(local_fds, "c", data, shard_tokens=SHARD_TOKENS)
        stb = StreamingTokenBatches(local_fds, "c", 4, SEQ, seed=5,
                                    epochs=2, drop_last=False)
        rtb = ResumableTokenBatches(data, 4, SEQ, seed=5, epochs=2,
                                    drop_last=False,
                                    shard_windows=SHARD_WINDOWS)
        got = [b["tokens"] for b in stb]
        want = [b["tokens"] for b in rtb]
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert g.tobytes() == w.tobytes()

    @pytest.mark.parametrize("seed", [None, 0, 2, 11])
    def test_zero_window_tail_shard(self, local_fds, seed):
        """A trailing shard too short to hold even ONE window must not
        shift the shuffle: the streaming loader permutes only the shards
        that hold windows — the same shard count
        hierarchical_window_order derives from ceil(n_windows /
        shard_windows) — so the two orders stay identical."""
        data = make_data(4, tail_tokens=W - 3)  # 5th shard: 0 windows
        build_corpus(local_fds, "c", data, shard_tokens=SHARD_TOKENS)
        stb = StreamingTokenBatches(local_fds, "c", 4, SEQ, seed=seed,
                                    epochs=3)
        rtb = ResumableTokenBatches(data, 4, SEQ, seed=seed, epochs=3,
                                    shard_windows=SHARD_WINDOWS)
        got = [b["tokens"] for b in stb]
        want = [b["tokens"] for b in rtb]
        assert len(got) == len(want) > 0
        for g, w in zip(got, want):
            assert g.tobytes() == w.tobytes()


class TestExactResume:
    def _full(self, fds, **kw):
        ds = StreamingTokenBatches(fds, "c", 4, SEQ, **kw)
        return list(ds)

    def test_resume_at_every_cut(self, local_fds):
        """Checkpoint the stamp after batch k, rebuild the loader from
        the manifest, restore, and the continued stream is byte-identical
        to the uninterrupted one — for EVERY k, which sweeps cuts inside
        shards, exactly on shard boundaries, and across the epoch
        rollover (epochs=2)."""
        data = make_data(6)
        build_corpus(local_fds, "c", data, shard_tokens=SHARD_TOKENS)
        full = self._full(local_fds, seed=11, epochs=2)
        assert len(full) > 4
        for cut in range(1, len(full)):
            # the stamp survives JSON (what a checkpoint actually stores)
            stamp = json.loads(json.dumps(full[cut - 1][STATE_KEY]))
            ds2 = StreamingTokenBatches(local_fds, "c", 4, SEQ, seed=11,
                                        epochs=2).restore(stamp)
            rest = list(ds2)
            assert len(rest) == len(full) - cut
            for a, b in zip(rest, full[cut:]):
                assert a["tokens"].tobytes() == b["tokens"].tobytes()
                assert a[STATE_KEY] == b[STATE_KEY]

    def test_stamp_is_flat_ints(self, local_fds):
        build_corpus(local_fds, "c", make_data(3),
                     shard_tokens=SHARD_TOKENS)
        ds = StreamingTokenBatches(local_fds, "c", 2, SEQ, seed=1,
                                   epochs=1)
        stamp = next(iter(ds))[STATE_KEY]
        for key, value in stamp.items():
            assert value is None or isinstance(value, int), (key, value)

    def test_geometry_cross_checks(self, local_fds):
        build_corpus(local_fds, "c", make_data(4),
                     shard_tokens=SHARD_TOKENS)
        mk = lambda **kw: StreamingTokenBatches(local_fds, "c", 4, SEQ,
                                                **kw)
        stamp = next(iter(mk(seed=3, epochs=1)))[STATE_KEY]
        with pytest.raises(ValueError):  # seed
            mk(seed=4).restore(stamp)
        with pytest.raises(ValueError):  # batch geometry
            StreamingTokenBatches(local_fds, "c", 8, SEQ,
                                  seed=3).restore(stamp)
        with pytest.raises(ValueError):  # host slice
            mk(seed=3, host_index=1, n_hosts=2).restore(stamp)
        with pytest.raises(ValueError):  # drop_last
            mk(seed=3, drop_last=False).restore(stamp)
        for bad in ({"shard_cursor": 99}, {"window_cursor": 99},
                    {"epoch": -1}):
            with pytest.raises(ValueError):
                mk(seed=3, epochs=1).restore(dict(stamp, **bad))

    def test_unfillable_batch_raises_instead_of_spinning(self, local_fds):
        """An epochs=None stream whose host slice can never fill ONE
        batch must raise, not loop forever re-downloading its shards
        while next() never returns."""
        data = make_data(2)  # 6 windows total
        build_corpus(local_fds, "c", data, shard_tokens=SHARD_TOKENS)
        # batch_size > the host's windows under drop_last
        ds = StreamingTokenBatches(local_fds, "c", 7, SEQ, epochs=None)
        with pytest.raises(DatasetError, match="never yield"):
            next(iter(ds))
        # a host whose slice holds NO shards at all (n_hosts > n_shards)
        ds = StreamingTokenBatches(local_fds, "c", 1, SEQ, epochs=None,
                                   host_index=5, n_hosts=8,
                                   drop_last=False)
        with pytest.raises(DatasetError, match="never yield"):
            next(iter(ds))
        # with FINITE epochs the same geometry just yields nothing
        ds = StreamingTokenBatches(local_fds, "c", 7, SEQ, epochs=2)
        assert list(ds) == []

    def test_drop_last_in_resumable_stamp(self):
        """Satellite: a stamp from a drop_last=False in-memory stream
        must not restore into a drop_last=True one (batches_per_epoch
        differs) — the cross-check fires now that the stamp carries it."""
        data = make_data(4, tail_tokens=W)  # windows % batch != 0
        src = ResumableTokenBatches(data, 4, SEQ, seed=2, drop_last=False)
        stamp = next(iter(src))[STATE_KEY]
        assert stamp["drop_last"] == 0
        with pytest.raises(ValueError):
            ResumableTokenBatches(data, 4, SEQ, seed=2,
                                  drop_last=True).restore(stamp)
        # same drop_last restores fine
        ResumableTokenBatches(data, 4, SEQ, seed=2,
                              drop_last=False).restore(stamp)
        # and shard_windows streams don't accept global-shuffle stamps
        with pytest.raises(ValueError):
            ResumableTokenBatches(data, 4, SEQ, seed=2, drop_last=False,
                                  shard_windows=3).restore(stamp)


class TestPerHost:
    def test_disjoint_coverage(self, gcs_fds):
        """Each host of a gang reads only its slice: per-epoch shard sets
        are pairwise disjoint, their union covers every shard, and the
        combined token multiset equals the whole corpus's windows."""
        fds, _srv = gcs_fds
        data = make_data(8)
        manifest = build_corpus(fds, "c", data, shard_tokens=SHARD_TOKENS)
        n_hosts = 3
        all_shards = []
        all_tokens = []
        for h in range(n_hosts):
            ds = StreamingTokenBatches(fds, "c", 2, SEQ, seed=9, epochs=1,
                                       host_index=h, n_hosts=n_hosts,
                                       drop_last=False)
            host_shards = ds._host_order(0)
            assert not set(host_shards) & set(all_shards)
            all_shards.extend(host_shards)
            for batch in ds:
                all_tokens.append(batch["tokens"].ravel())
            # fetch accounting: this host touched only its own shards
            assert ds.reader.stats["fetches"] == len(host_shards)
        assert sorted(all_shards) == list(range(manifest["n_shards"]))
        got = np.sort(np.concatenate(all_tokens))
        want = np.sort(data[:manifest["n_shards"] * SHARD_TOKENS])
        assert np.array_equal(got, want)

    def test_gang_env_defaults(self, local_fds, monkeypatch):
        build_corpus(local_fds, "c", make_data(4),
                     shard_tokens=SHARD_TOKENS)
        monkeypatch.setenv("MF_PARALLEL_NODE_INDEX", "1")
        monkeypatch.setenv("MF_PARALLEL_NUM_NODES", "2")
        ds = StreamingTokenBatches(local_fds, "c", 2, SEQ, seed=1)
        assert ds.state()["host_index"] == 1
        assert ds.state()["n_hosts"] == 2

    def test_host_resume(self, gcs_fds):
        fds, _srv = gcs_fds
        build_corpus(fds, "c", make_data(6), shard_tokens=SHARD_TOKENS)
        mk = lambda: StreamingTokenBatches(fds, "c", 2, SEQ, seed=4,
                                           epochs=2, host_index=1,
                                           n_hosts=2)
        full = list(mk())
        cut = len(full) // 2
        rest = list(mk().restore(full[cut - 1][STATE_KEY]))
        for a, b in zip(rest, full[cut:]):
            assert a["tokens"].tobytes() == b["tokens"].tobytes()


class TestCorruption:
    def test_corrupted_shard_hard_error(self, gcs_fds):
        """A shard corrupted IN THE STORE: checksum mismatch → cache-
        bypass retry → still wrong → hard ShardCorruptionError (never a
        silently-wrong token stream)."""
        fds, _srv = gcs_fds
        data = make_data(3)
        manifest = build_corpus(fds, "c", data, shard_tokens=SHARD_TOKENS)
        victim = manifest["shards"][1]
        # overwrite the packed CAS object with valid-format garbage
        fds.storage.save_bytes(
            [(fds.ca_store.blob_path(victim["key"]),
              b"0" + b"\x07" * victim["bytes"])], overwrite=True)
        reader = ShardReader(fds, manifest)
        with pytest.raises(ShardCorruptionError):
            for _sid, _arr in reader.stream([0, 1, 2]):
                pass
        assert reader.stats["retries"] == 1

    def test_corrupted_cache_retries_and_heals(self, tmp_path):
        """A poisoned BLOB CACHE entry (local bit rot) retries once
        bypassing the cache, serves the good bytes, and heals the cache
        in place."""

        class DictCache(object):
            def __init__(self):
                self.d = {}

            def load_key(self, key):
                return self.d.get(key)

            def store_key(self, key, blob):
                self.d[key] = blob

        cache = DictCache()
        fds = FlowDataStore("DataFlow", LocalStorage,
                            ds_root=str(tmp_path / "root"),
                            blob_cache=cache)
        data = make_data(3)
        manifest = build_corpus(fds, "c", data, shard_tokens=SHARD_TOKENS)
        victim = manifest["shards"][2]["key"]
        good = cache.d[victim]
        cache.d[victim] = b"\x09" * len(good)
        reader = ShardReader(fds, manifest)
        out = {sid: arr.copy() for sid, arr in reader.stream([0, 1, 2])}
        assert reader.stats["retries"] == 1
        assert np.array_equal(out[2],
                              data[2 * SHARD_TOKENS:3 * SHARD_TOKENS])
        assert cache.d[victim] == good  # healed


class TestTelemetry:
    def _recorded(self, fds, fn):
        from metaflow_tpu import telemetry

        telemetry.init_recorder(fds, "r1", "train", "t1")
        try:
            fn()
        finally:
            telemetry.close_recorder()
        return telemetry.read_run_records(fds, "r1")

    def test_data_records_pinned_schema(self, local_fds):
        build_corpus(local_fds, "c", make_data(4),
                     shard_tokens=SHARD_TOKENS)

        def consume():
            ds = StreamingTokenBatches(local_fds, "c", 4, SEQ, seed=1,
                                       epochs=1)
            for _ in ds:
                pass

        records = self._recorded(local_fds, consume)
        data_recs = [r for r in records if r["name"].startswith("data.")]
        names = {r["name"] for r in data_recs}
        assert {"data.shard_fetch", "data.batch_wait",
                "data.readahead_occupancy"} <= names
        for rec in data_recs:
            validate_data_record(rec)
        occ = [r for r in data_recs
               if r["name"] == "data.readahead_occupancy"]
        assert all(0 <= r["value"] <= 1 for r in occ)

    def test_retry_counter_pinned(self, local_fds):
        class DictCache(object):
            def __init__(self):
                self.d = {}

            def load_key(self, key):
                return self.d.get(key)

            def store_key(self, key, blob):
                self.d[key] = blob

        cache = DictCache()
        fds = FlowDataStore("DataFlow", LocalStorage,
                            ds_root=local_fds.ds_root, blob_cache=cache)
        manifest = build_corpus(fds, "c2", make_data(2),
                                shard_tokens=SHARD_TOKENS)
        key = manifest["shards"][0]["key"]
        cache.d[key] = b"bad"

        def consume():
            reader = ShardReader(fds, manifest)
            list(reader.stream([0, 1]))

        records = self._recorded(fds, consume)
        retries = [r for r in records if r["name"] == "data.shard_retry"]
        assert len(retries) == 1
        validate_data_record(retries[0])

    def test_input_stall_metric(self, local_fds):
        """instrument_train_step stamps input_stall_ms (host wait between
        steps — the input-bound signal) onto each train.step record;
        `tpuflow metrics` aggregates it per step and flags input-bound
        runs."""
        from metaflow_tpu.cmd.metrics import aggregate
        from metaflow_tpu.training.metrics import instrument_train_step

        def step(state, batch):
            return state, {}

        def run():
            wrapped = instrument_train_step(step, tokens_per_step=40,
                                            profile=False)
            for _ in range(4):
                time.sleep(0.02)  # the "iterator" stalls the host
                wrapped(None, None)
            wrapped.telemetry.close()
            assert wrapped.telemetry.report()["input_stall_ms"] >= 15

        records = self._recorded(local_fds, run)
        steps = [r for r in records
                 if r["name"] == "train.step" and r["type"] == "timer"]
        stalls = [r["data"]["input_stall_ms"] for r in steps
                  if "input_stall_ms" in r.get("data", {})]
        assert stalls and all(s >= 15 for s in stalls)
        for rec in steps:
            validate_train_step_record(rec)
        agg = aggregate(records)
        assert agg["train"]["input_stall_ms"] >= 15
        assert agg["train"]["input_stall_frac"] > 0.5  # input-bound
        assert any("input_stall_ms" in row for row in agg["timeline"])


class TestPacking:
    def test_segments_and_padding(self):
        docs = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]
        windows = list(pack_documents(docs, seq_len=4))  # W=5
        assert len(windows) == 2
        t0, s0 = windows[0]
        assert t0.tolist() == [1, 2, 3, 4, 5]
        assert s0.tolist() == [1, 1, 1, 2, 2]
        t1, s1 = windows[1]
        assert t1.tolist() == [6, 7, 8, 9, 0]
        assert s1.tolist() == [1, 1, 1, 1, 0]

    def test_long_doc_splits_across_windows(self):
        docs = [list(range(1, 13))]  # 12 tokens, W=5
        windows = list(pack_documents(docs, seq_len=4))
        assert len(windows) == 3
        assert [t.tolist() for t, _s in windows] == [
            [1, 2, 3, 4, 5], [6, 7, 8, 9, 10], [11, 12, 0, 0, 0]]
        # continuation restarts as segment 1 of its window
        assert windows[1][1].tolist() == [1, 1, 1, 1, 1]
        assert windows[2][1].tolist() == [1, 1, 0, 0, 0]

    def test_loss_mask_semantics(self):
        segs = np.array([[1, 1, 2, 2, 0]])
        mask = segment_loss_mask(segs)
        # target i lives iff positions i,i+1 share a non-pad segment
        assert mask.tolist() == [[1.0, 0.0, 1.0, 0.0]]

    def test_packed_batches_feed_existing_loss(self):
        import jax

        from metaflow_tpu.models import llama

        cfg = llama.LlamaConfig.tiny()
        rng = np.random.default_rng(0)
        docs = [rng.integers(1, cfg.vocab_size, rng.integers(3, 40))
                for _ in range(12)]
        batches = list(packed_batches(docs, batch_size=2, seq_len=16))
        assert batches
        b = batches[0]
        assert b["inputs"].shape == b["targets"].shape == (2, 16)
        assert b["segment_ids"].shape == (2, 17)
        assert b["mask"].shape == (2, 16)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        loss = llama.loss_fn(params, b, cfg)
        assert np.isfinite(float(loss))

    def test_packing_loses_no_tokens(self):
        rng = np.random.default_rng(1)
        docs = [rng.integers(1, 100, rng.integers(1, 23))
                for _ in range(50)]
        total = sum(d.size for d in docs)
        windows = list(pack_documents(docs, seq_len=9))
        packed = np.concatenate([t for t, _s in windows])
        segs = np.concatenate([s for _t, s in windows])
        assert packed[segs > 0].size == total
        got = np.sort(packed[segs > 0])
        assert np.array_equal(got, np.sort(np.concatenate(docs)))


class TestCompose:
    def test_sharded_dataset_corpus_path(self, local_fds):
        """The streaming loader rides the existing compose chain:
        sharded_dataset(corpus=...) → shard_iterator → prefetch, stamps
        intact, and `state=` resumes it."""
        import jax  # noqa: F401  (mesh needs devices)

        from metaflow_tpu.spmd import MeshSpec, create_mesh
        from metaflow_tpu.training.data import sharded_dataset

        build_corpus(local_fds, "c", make_data(11),
                     shard_tokens=SHARD_TOKENS)
        mesh = create_mesh(MeshSpec.dp())
        corpus = StreamingTokenBatches(local_fds, "c", 8, SEQ, seed=2,
                                       epochs=1)
        seen = []
        for batch in sharded_dataset(None, 8, SEQ, mesh, corpus=corpus):
            assert batch["tokens"].shape[0] == 8
            seen.append(batch[STATE_KEY])
        assert seen
        corpus2 = StreamingTokenBatches(local_fds, "c", 8, SEQ, seed=2,
                                        epochs=1)
        resumed = list(sharded_dataset(None, 8, SEQ, mesh, corpus=corpus2,
                                       state=seen[0]))
        assert len(resumed) == len(seen) - 1
        assert resumed[0][STATE_KEY] == seen[1]

    def test_sharded_dataset_threads_drop_last(self):
        from metaflow_tpu.spmd import MeshSpec, create_mesh
        from metaflow_tpu.training.data import sharded_dataset

        data = make_data(2, tail_tokens=W)  # 7 windows, batch 4
        # a 1-device mesh: the short final batch of the drop_last=False
        # stream is NOT divisible across a multi-device data axis
        mesh = create_mesh(MeshSpec({"data": 1}), n_devices=1)
        kept = list(sharded_dataset(data, 4, SEQ, mesh, seed=1, epochs=1,
                                    drop_last=False))
        dropped = list(sharded_dataset(data, 4, SEQ, mesh, seed=1,
                                       epochs=1, drop_last=True))
        assert len(kept) == 2 and kept[-1]["tokens"].shape[0] == 3
        assert len(dropped) == 1


class TestDatasetCLI:
    def test_build_info_list_roundtrip(self, tmp_path):
        np.save(str(tmp_path / "tokens.npy"),
                (np.arange(120) % 31).astype(np.int32))
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.dirname(HERE)] +
            [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
        root = str(tmp_path / "dsroot")
        base = [sys.executable, "-m", "metaflow_tpu", "dataset"]
        common = ["--datastore", "local", "--datastore-root", root]
        proc = subprocess.run(
            base + ["build", "CliFlow", "corpus", "--input",
                    str(tmp_path / "tokens.npy"), "--shard-tokens", "50"]
            + common, env=env, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        assert "3 shard(s)" in proc.stdout
        proc = subprocess.run(
            base + ["info", "CliFlow", "corpus", "--json"] + common,
            env=env, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        manifest = json.loads(proc.stdout)
        validate_dataset_manifest(manifest)
        assert manifest["total_tokens"] == 120
        proc = subprocess.run(
            base + ["list", "CliFlow"] + common, env=env,
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        assert "corpus" in proc.stdout
        # and the CLI-built corpus streams
        fds = FlowDataStore("CliFlow", LocalStorage, ds_root=root,
                            blob_cache=False)
        ds = StreamingTokenBatches(fds, "corpus", 2, SEQ, epochs=1)
        assert sum(1 for _ in ds) == 6

    def test_build_missing_raises_clean(self, tmp_path):
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.dirname(HERE)] +
            [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
        proc = subprocess.run(
            [sys.executable, "-m", "metaflow_tpu", "dataset", "info",
             "NoFlow", "nope", "--datastore", "local",
             "--datastore-root", str(tmp_path / "empty")],
            env=env, capture_output=True, text=True, timeout=120)
        assert proc.returncode != 0
        assert "not found" in proc.stderr


class TestReaderConcurrency:
    def test_same_key_concurrent_readers(self, local_fds):
        """Two loaders streaming the same corpus concurrently (e.g. two
        gang processes on one host) each see a correct stream."""
        data = make_data(4)
        build_corpus(local_fds, "c", data, shard_tokens=SHARD_TOKENS)
        results = {}

        def consume(tag):
            ds = StreamingTokenBatches(local_fds, "c", 4, SEQ, seed=3,
                                       epochs=1)
            results[tag] = [b["tokens"].copy() for b in ds]

        threads = [threading.Thread(target=consume, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(results[0]) == len(results[1]) > 0
        for a, b in zip(results[0], results[1]):
            assert a.tobytes() == b.tobytes()

    def test_readahead_is_bounded(self, local_fds):
        """The reader never holds more than the readahead window (plus
        the one shard being handed over) in flight."""
        manifest = build_corpus(local_fds, "c", make_data(8),
                                shard_tokens=SHARD_TOKENS)
        shard_bytes = manifest["shards"][0]["bytes"]
        reader = ShardReader(local_fds, manifest,
                             readahead_bytes=2 * shard_bytes,
                             max_workers=4)
        for _sid, _arr in reader.stream(list(range(8))):
            pass
        assert reader.stats["fetches"] == 8
        assert reader.mean_occupancy() <= 1.0

    def test_parallel_reader_same_shards_and_several_in_flight(
            self, local_fds):
        """The parallel reader hands over what a one-shard-at-a-time loop
        over the same blobs decodes, in the given order, with more than
        one fetch in flight: two fetches meet at a barrier that a reader
        fetching one at a time could never pass."""
        n = 8
        manifest = build_corpus(local_fds, "c", make_data(n),
                                shard_tokens=SHARD_TOKENS)
        order = [5, 0, 7, 2, 1, 6, 3, 4]
        sequential = []
        for sid in order:
            for _k, blob in local_fds.ca_store.load_blobs(
                    [manifest["shards"][sid]["key"]]):
                sequential.append(decode_shard(manifest, sid, blob))
        shard_bytes = manifest["shards"][0]["bytes"]
        reader = ShardReader(local_fds, manifest, max_workers=4,
                             readahead_bytes=4 * shard_bytes)
        fetch, lock = reader._fetch, threading.Lock()
        meet = threading.Barrier(2)
        in_flight = {"now": 0, "peak": 0}

        def counted_fetch(shard_id):
            with lock:
                in_flight["now"] += 1
                in_flight["peak"] = max(in_flight["peak"],
                                        in_flight["now"])
            try:
                if shard_id in order[:2]:
                    meet.wait(timeout=60)
                return fetch(shard_id)
            finally:
                with lock:
                    in_flight["now"] -= 1

        reader._fetch = counted_fetch
        streamed = list(reader.stream(order))
        assert [sid for sid, _arr in streamed] == order
        for (_sid, arr), ref in zip(streamed, sequential):
            assert arr.tobytes() == ref.tobytes()
        assert reader.stats["fetches"] == n
        assert 2 <= in_flight["peak"] <= 4
        # sampled after each top-up: four shards in the window until the
        # order runs out, then 3, 2, 1
        assert reader.mean_occupancy() == pytest.approx(
            (5 * 4 + 3 + 2 + 1) / (8 * 4.0))
