"""Elastic gang training under the chaos harness: a rank is reclaimed
mid-training (seeded kill schedule), the elastic supervisor relaunches
the gang at the largest capacity-admissible size, training resumes from
the shared checkpoint with the data stream token-exact, and when the
scripted capacity oracle reports the fleet back at full size the gang
grows back at its next checkpoint boundary.

The `end` step replays the whole run single-process from scratch and
asserts the distributed, twice-resized run produced the EXACT same loss
trajectory and token order — the ROADMAP item 5 gate.

Driven by tests/test_zelastic_e2e.py via env:

    ELASTIC_FLOW_RANKS   gang size             (default 8)
    ELASTIC_FLOW_STEPS   total train steps     (default 40)
    ELASTIC_FLOW_SLEEP   seconds per step      (default 0.05)
    TPUFLOW_CHAOS        kill schedule, e.g. "3:2" (see devtools/chaos.py)
    TPUFLOW_CAPACITY_ORACLE  e.g. "scripted:4,4,4,8" (see elastic/oracle.py)
"""

import contextlib
import os
import time

import numpy as np

import metaflow_tpu
from metaflow_tpu import FlowSpec, current, step, telemetry
from metaflow_tpu.decorators import make_step_decorator
from metaflow_tpu.plugins import STEP_DECORATORS

# module-scope imports on purpose: they run during flow load, BEFORE the
# preemption handler is installed — an async notice landing mid-import
# would otherwise mangle the TaskPreempted into an ImportError. (A raw
# SIGTERM during load is a plain infra death, which the supervisor
# classifies and retries correctly.)
from metaflow_tpu.devtools.chaos import maybe_chaos_step
from metaflow_tpu.training.data import ResumableTokenBatches

# no jax.distributed: the ranks train the same global stream redundantly
# (pure-numpy SGD), which keeps the 8-process gang cheap on a CPU box
# while exercising the full elastic path — kill, teardown, classify,
# resize, checkpoint resume, token-exact data continuation, grow-back
tpu_parallel = make_step_decorator(STEP_DECORATORS["tpu_parallel"])

SEED = 17
BATCH = 4
SEQ = 8
LR = 0.05


def make_tokens():
    # deterministic pseudo-corpus; large enough that the run never wraps
    # into ambiguity (epochs roll over fine, the stamp handles it)
    return ((np.arange(6000, dtype=np.int64) * 2654435761) % 65521).astype(
        np.int64)


def sgd_step(w, batch):
    """One deterministic scalar-SGD step; returns (loss, new_w, checksum).
    Pure float64 numpy — bit-identical wherever it runs."""
    x = float(batch.mean())
    loss = (w - x) ** 2
    new_w = w - LR * 2.0 * (w - x)
    return loss, new_w, int(batch.sum())


class ElasticTrainFlow(FlowSpec):
    @step
    def start(self):
        self.total_steps = int(os.environ.get("ELASTIC_FLOW_STEPS", "40"))
        self.step_sleep = float(os.environ.get("ELASTIC_FLOW_SLEEP", "0.05"))
        ranks = int(os.environ.get("ELASTIC_FLOW_RANKS", "8"))
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
        w = 0.0
        start_step = 0
        history = []  # [step, world, checksum, loss] per completed step
        # resume only from a PRIOR attempt's checkpoint: in a real gang,
        # collectives keep ranks lockstep, but these ranks train the
        # stream redundantly — a late-starting worker must not
        # fast-forward through rank 0's in-flight saves (it would skip
        # its own scheduled chaos kill, among other things). Each save
        # stamps its attempt; loads skip same-attempt saves.
        # a notice is honoured only at a boundary THIS attempt has
        # written: the restore and the first step ride one shield, so a
        # grow notice that finds a shrunk gang still starting up waits
        # until that gang has a step of its own in the record (the
        # supervisor's head start is a clock, this is a step)
        first_boundary = contextlib.ExitStack()
        first_boundary.enter_context(current.preemption.shield())
        restored = None
        for s in reversed(ckpt.list()):
            state = ckpt.load(step=s)
            if state is not None and int(state["attempt"]) < current.retry_count:
                restored = state
                break
        if restored is not None:
            w = float(restored["w"])
            start_step = int(restored["step"]) + 1
            ds.restore(restored["data_state"])
            history = [list(h) for h in restored["history"]]
        self.rank = rank
        self.world = world

        it = iter(ds)
        i = start_step
        while i < self.total_steps:
            # chaos tick: a scheduled (step, rank) kill delivers a REAL
            # spot notice to this process, once per run
            maybe_chaos_step(i)
            # the step timer makes each rank's loop a gapless goodput
            # lane: batch fetch + sgd + (rank 0) save + the simulated
            # chip work all ride one train.step interval, so the run's
            # ledger reconciles instead of booking inter-record gaps as
            # unattributed. The chaos tick stays OUTSIDE — a kill must
            # not be mistaken for a long step.
            with telemetry.timer("train.step", step_num=i):
                batch = next(it)
                loss, w, checksum = sgd_step(w, batch["tokens"])
                history.append([i, world, checksum, loss])
                if rank == 0:
                    # rank 0 owns the shared-scope checkpoint in this
                    # local gang; the shield makes every save a clean
                    # boundary for both spot reclaims and supervisor
                    # grow notices
                    with current.preemption.shield():
                        ckpt.save(
                            {"w": w, "step": i,
                             "attempt": current.retry_count,
                             "data_state": batch["data_state"],
                             "history": history},
                            step=i)
                time.sleep(self.step_sleep)
            first_boundary.close()
            i += 1
        first_boundary.close()
        self.final_w = w
        self.history = history if rank == 0 else None
        self.next(self.join)

    @step
    def join(self, inputs):
        ranks = sorted(inp.rank for inp in inputs)
        assert ranks == list(range(len(ranks))), ranks
        # every rank of the final attempt saw the same world size, and it
        # matches the number of tasks that arrived at this join
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

        # replay the run single-process: the elastic run must match the
        # uninterrupted trajectory EXACTLY — same tokens, same losses
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

        worlds = [h[1] for h in self.history]
        print("elastic run ok: worlds=%s final_world=%d"
              % (sorted(set(worlds)), self.final_world))


if __name__ == "__main__":
    ElasticTrainFlow()
