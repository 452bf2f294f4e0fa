"""The BASELINE north-star path at test scale, in ONE flow: Mixtral
trained with DROPLESS expert-parallel dispatch (gmm_ep — a2a to the
expert's shard, local grouped matmul) through a RESUMABLE data stream,
preempted mid-epoch, and resumed exactly — model + optimizer moments +
schedule step + data cursor all restored from one orbax checkpoint, the
consumed token sequence asserted against an uninterrupted oracle.

(Reference intent: exact resume via per-task artifact persistence, metaflow/datastore/
task_datastore.py:880 — here the data cursor must ride the checkpoint.)
"""

import os

import numpy as np

import metaflow_tpu
from metaflow_tpu import FlowSpec, current, step

BATCH, SEQ, SEED = 8, 32, 11
TOTAL_BATCHES = 6
CRASH_AFTER = 3  # batches consumed before the simulated preemption


def _sig(tokens):
    t = np.asarray(tokens)
    return [int(t.sum()), int(t[0, 0]), int(t[-1, -1])]


class MoeCheckpointFlow(FlowSpec):
    @step
    def start(self):
        self.next(self.train)

    @metaflow_tpu.retry(times=2, minutes_between_retries=0)
    @metaflow_tpu.checkpoint
    @step
    def train(self):
        import jax

        from metaflow_tpu.models import mixtral
        from metaflow_tpu.spmd import MeshSpec, create_mesh
        from metaflow_tpu.training import (
            STATE_KEY,
            ResumableTokenBatches,
            default_optimizer,
            make_trainer,
            reshard_like,
        )
        from metaflow_tpu.training.data import prefetch, shard_iterator

        n = len(jax.devices())
        ep = min(4, n) if n >= 4 else 1
        # dropless expert parallelism when the mesh allows it; the
        # single-device fallback keeps the flow runnable anywhere
        cfg = mixtral.MixtralConfig.tiny(
            moe_dispatch="gmm_ep" if ep > 1 else "sparse")
        mesh = create_mesh(
            MeshSpec.moe(expert=ep) if ep > 1 else MeshSpec.dp())
        state, step_fn, _ = make_trainer(
            jax.random.PRNGKey(0), cfg, mesh, mixtral,
            optimizer=default_optimizer(lr=5e-3, warmup_steps=1,
                                        total_steps=50),
        )

        corpus = np.random.default_rng(3).integers(
            0, cfg.vocab_size, size=BATCH * (SEQ + 1) * TOTAL_BATCHES)
        ds = ResumableTokenBatches(corpus, BATCH, SEQ, seed=SEED,
                                   epochs=1)
        assert ds.batches_per_epoch == TOTAL_BATCHES

        # ONE checkpoint carries everything exact resume needs: full
        # train state (params + Adam moments + schedule step), the data
        # cursor, and the fixed-shape consumed-batch fingerprints
        template = {"state": state, "data_state": ds.state(),
                    "consumed": np.full((TOTAL_BATCHES, 3), -1,
                                        np.int64)}
        restored = current.checkpoint.load(like=template)
        consumed = template["consumed"]
        done = 0
        if restored is not None:
            state = reshard_like(restored["state"], state)
            ds.restore(restored["data_state"])
            consumed = np.asarray(restored["consumed"])
            done = int(restored["data_state"]["cursor"])
        self.resumed_from = done

        stream = prefetch(shard_iterator(iter(ds), mesh))
        with mesh:
            for i, batch in enumerate(stream, start=done):
                stamp = batch.pop(STATE_KEY)
                consumed[i] = _sig(jax.device_get(batch["tokens"]))
                state, m = step_fn(state, batch)
                current.checkpoint.save(
                    {"state": state, "data_state": stamp,
                     "consumed": consumed}, step=i)
                if (i + 1 == CRASH_AFTER and current.retry_count == 0
                        and not os.environ.get("NO_CRASH")):
                    raise RuntimeError("simulated preemption mid-epoch")
            self.final_loss = float(m["loss"])

        # exactness: the sequence consumed ACROSS attempts equals an
        # uninterrupted oracle stream — no replayed, no skipped batches
        oracle = [_sig(b["tokens"]) for b in ResumableTokenBatches(
            corpus, BATCH, SEQ, seed=SEED, epochs=1)]
        assert consumed.tolist() == oracle, (consumed.tolist(), oracle)
        # the optimizer schedule continued too (full-state restore):
        # step counts every applied update across attempts
        assert int(jax.device_get(state["step"])) == TOTAL_BATCHES
        self.dispatch = cfg.moe_dispatch
        self.next(self.end)

    @step
    def end(self):
        assert self.resumed_from == CRASH_AFTER, self.resumed_from
        print("moe checkpoint ok: %s resumed from %d, loss %.3f"
              % (self.dispatch, self.resumed_from, self.final_loss))


if __name__ == "__main__":
    MoeCheckpointFlow()
