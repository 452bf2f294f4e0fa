"""Gang training under the hang-fault chaos harness: a rank WEDGES
mid-training (seeded `step:rank:hang` schedule — it sleeps forever at a
step boundary while its heartbeat thread keeps ticking), the gang
watchdog flags the stall off the per-rank progress beats, dumps
all-thread stacks into `_telemetry/hangs/`, kills the gang, and the
elastic supervisor resumes from the shared checkpoint. The `end` step
replays the run single-process and asserts the interrupted run produced
the EXACT same loss trajectory and token order.

Unlike elastic_train_flow, the train step here runs through the REAL
`instrument_train_step` wrapper, so the chaos tick, the per-step
progress beats, and the adaptive hang deadline all ride the production
path rather than hand-rolled calls.

Driven by tests/test_zhang_e2e.py via env:

    HANG_FLOW_RANKS     gang size             (default 4)
    HANG_FLOW_STEPS     total train steps     (default 8)
    HANG_FLOW_SLEEP     seconds per step      (default 0.05)
    TPUFLOW_CHAOS       fault schedule, e.g. "3:1:hang" or "3:1:slow"
    TPUFLOW_HANG_*      watchdog deadlines (see docs/elasticity.md)
"""

import os
import time

import numpy as np

import metaflow_tpu
from metaflow_tpu import FlowSpec, current, step
from metaflow_tpu.decorators import make_step_decorator
from metaflow_tpu.plugins import STEP_DECORATORS

# module-scope import on purpose: flow load must finish before any
# async notice can land (see elastic_train_flow.py)
from metaflow_tpu.training.data import ResumableTokenBatches
from metaflow_tpu.training.metrics import instrument_train_step

tpu_parallel = make_step_decorator(STEP_DECORATORS["tpu_parallel"])

SEED = 23
BATCH = 4
SEQ = 8
LR = 0.05


def make_tokens():
    return ((np.arange(6000, dtype=np.int64) * 2654435761) % 65521).astype(
        np.int64)


def sgd_step(w, batch):
    """One deterministic scalar-SGD step; returns (loss, new_w, checksum).
    Pure float64 numpy — bit-identical wherever it runs."""
    x = float(batch.mean())
    loss = (w - x) ** 2
    new_w = w - LR * 2.0 * (w - x)
    return loss, new_w, int(batch.sum())


class HangChaosFlow(FlowSpec):
    @step
    def start(self):
        self.total_steps = int(os.environ.get("HANG_FLOW_STEPS", "8"))
        self.step_sleep = float(os.environ.get("HANG_FLOW_SLEEP", "0.05"))
        ranks = int(os.environ.get("HANG_FLOW_RANKS", "4"))
        self.next(self.train, num_parallel=ranks)

    @tpu_parallel(jax_distributed=False)
    @metaflow_tpu.retry(times=1, minutes_between_retries=0)
    @metaflow_tpu.checkpoint
    @step
    def train(self):
        rank = current.parallel.node_index
        world = current.parallel.num_nodes
        ckpt = current.checkpoint

        ds = ResumableTokenBatches(make_tokens(), BATCH, SEQ, seed=SEED)
        state = {"w": 0.0}
        start_step = 0
        history = []  # [step, world, checksum, loss] per completed step
        restored = None
        for s in reversed(ckpt.list()):
            saved = ckpt.load(step=s)
            if saved is not None and int(saved["attempt"]) < current.retry_count:
                restored = saved
                break
        if restored is not None:
            state["w"] = float(restored["w"])
            start_step = int(restored["step"]) + 1
            ds.restore(restored["data_state"])
            history = [list(h) for h in restored["history"]]
        self.rank = rank
        self.world = world

        # the production wrapper: chaos tick + progress beat + adaptive
        # hang deadline per call. The wrapper's own step counter starts
        # at 0 every attempt while the chaos schedule is keyed on it —
        # the ledger (one fault per (kind, step, rank) per run) is what
        # keeps a resumed attempt from replaying its fault.
        def train_step(batch):
            loss, state["w"], checksum = sgd_step(state["w"],
                                                  batch["tokens"])
            return loss, checksum

        instrumented = instrument_train_step(
            train_step, tokens_per_step=BATCH * SEQ, profile=False)

        it = iter(ds)
        i = start_step
        while i < self.total_steps:
            batch = next(it)
            loss, checksum = instrumented(batch)
            history.append([i, world, checksum, loss])
            if rank == 0:
                with current.preemption.shield():
                    ckpt.save(
                        {"w": state["w"], "step": i,
                         "attempt": current.retry_count,
                         "data_state": batch["data_state"],
                         "history": history},
                        step=i)
            time.sleep(self.step_sleep)
            i += 1
        # emits the terminal `done` progress beat: a rank idling in
        # worker reap after its last step must not read as hung
        instrumented.telemetry.close()
        self.final_w = state["w"]
        self.history = history if rank == 0 else None
        self.next(self.join)

    @step
    def join(self, inputs):
        ranks = sorted(inp.rank for inp in inputs)
        assert ranks == list(range(len(ranks))), ranks
        assert {inp.world for inp in inputs} == {len(ranks)}
        self.final_world = len(ranks)
        self.final_ws = sorted(set(float(inp.final_w) for inp in inputs))
        (self.history,) = [inp.history for inp in inputs
                           if inp.history is not None]
        self.total_steps = inputs[0].total_steps
        self.next(self.end)

    @step
    def end(self):
        # one entry per step, in order: nothing repeated, nothing skipped
        steps = [h[0] for h in self.history]
        assert steps == list(range(self.total_steps)), steps

        # replay single-process: the hung-killed-resumed run must match
        # the uninterrupted trajectory EXACTLY — same tokens, same losses
        ds = ResumableTokenBatches(make_tokens(), BATCH, SEQ, seed=SEED)
        it = iter(ds)
        w = 0.0
        for i in range(self.total_steps):
            batch = next(it)
            loss, w, checksum = sgd_step(w, batch["tokens"])
            got_step, got_world, got_checksum, got_loss = self.history[i]
            assert got_checksum == checksum, (
                "token order diverged at step %d: %r != %r"
                % (i, got_checksum, checksum))
            assert got_loss == loss, (
                "loss diverged at step %d: %r != %r" % (i, got_loss, loss))
        assert sorted(set(self.final_ws)) == [float(w)], (
            self.final_ws, w)

        print("hang run ok: world=%d steps=%d"
              % (self.final_world, self.total_steps))


if __name__ == "__main__":
    HangChaosFlow()
