"""ZeRO-style cross-replica weight-update sharding (spmd/sharding.py +
training/train_step.py): spec-transform units, loss-trajectory parity
sharded vs replicated, checkpoint round-trips across DP sizes and the
zero on/off switch, the optimizer/opt-state guard, the sanitizer's
pinned zero.* collective vocabulary and the split memory gauges.

Parity tolerances (measured on the 8-device CPU mesh, documented in
docs/training.md): losses zero-on vs zero-off drift <= ~1e-6 over a few
steps (reduction-order only); restore WITHOUT stepping is bit-exact;
one step after a restore drifts <= ~1.3e-6 per param element (host-numpy
restore changes reduction layouts, amplified by adamw's early-warmup
normalization) — asserted at atol=5e-6 for margin."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from metaflow_tpu import telemetry
from metaflow_tpu.datastore import FlowDataStore, LocalStorage
from metaflow_tpu.models import llama
from metaflow_tpu.spmd import MeshSpec, create_mesh, sanitizer
from metaflow_tpu.spmd import sharding as shd
from metaflow_tpu.training import (
    AsyncCheckpointManager,
    check_opt_state,
    default_optimizer,
    make_trainer,
    memory_efficient_optimizer,
    shard_batch,
)
from metaflow_tpu.training.metrics import _tree_device_bytes

import schema_validate


LOSS_ATOL = 2e-6     # zero-on vs zero-off loss drift (measured ~1e-6)
RESTORE_ATOL = 5e-6  # params one step after a restore (measured ~1.3e-6)


def _optimizer():
    return default_optimizer(lr=1e-2, warmup_steps=1, total_steps=10)


def _tokens(cfg, batch=8, seq=32, seed=1):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (batch, seq + 1), 0, cfg.vocab_size))


def _trainer(mesh, zero, checkpoint=None, optimizer=None, **kwargs):
    cfg = llama.LlamaConfig.tiny()
    state, step_fn, shardings = make_trainer(
        jax.random.PRNGKey(0), cfg, mesh, llama,
        optimizer=optimizer or _optimizer(), zero=zero,
        checkpoint=checkpoint, **kwargs)
    return cfg, state, step_fn, shardings


def _run_steps(mesh, cfg, state, step_fn, tokens, n):
    data = shard_batch({"tokens": tokens}, mesh)
    losses = []
    with mesh:
        for _ in range(n):
            state, m = step_fn(state, data)
            losses.append(float(m["loss"]))
    return state, losses


class TestZeroSpec:
    """zero_spec / zero_update_axis / zero_enabled units."""

    @pytest.fixture()
    def mesh(self):
        return create_mesh(MeshSpec.dp())  # 8 virtual CPU devices

    def test_picks_largest_divisible_dim(self, mesh):
        assert shd.zero_spec(P(), (512, 128), mesh) == P("data", None)
        # the larger dim wins even when it comes second
        assert shd.zero_spec(P(), (128, 512), mesh) == P(None, "data")

    def test_tie_breaks_to_lowest_index(self, mesh):
        assert shd.zero_spec(P(), (64, 64), mesh) == P("data", None)

    def test_non_divisible_leaf_stays_replicated(self, mesh):
        # 7 and 9 don't divide by the 8-way data axis: update replicates
        assert shd.zero_spec(P(), (7, 9), mesh) == P()

    def test_scalar_stays_replicated(self, mesh):
        assert shd.zero_spec(P(), (), mesh) == P()

    def test_leaf_already_on_dp_axis_untouched(self, mesh):
        spec = P(None, "data")
        assert shd.zero_spec(spec, (512, 128), mesh) is spec

    def test_model_parallel_axis_kept(self, mesh):
        # dim 0 is taken by another axis: the DP axis lands on dim 1
        assert (shd.zero_spec(P("fsdp", None), (512, 128), mesh)
                == P("fsdp", "data"))

    def test_update_axis_only_on_dp_meshes(self, mesh):
        assert shd.zero_update_axis(mesh) == "data"
        fsdp = create_mesh(MeshSpec.fsdp())
        assert shd.zero_update_axis(fsdp) is None

    def test_enabled_resolution(self, mesh, monkeypatch):
        fsdp = create_mesh(MeshSpec.fsdp())
        monkeypatch.delenv(shd.ZERO_ENV, raising=False)
        assert shd.zero_enabled(mesh) is False      # env default off
        monkeypatch.setenv(shd.ZERO_ENV, "1")
        assert shd.zero_enabled(mesh) is True       # env knob on
        assert shd.zero_enabled(mesh, zero=False) is False  # arg wins
        assert shd.zero_enabled(fsdp, zero=True) is False   # no DP axis

    def test_tree_specs_live_sharding_base(self, mesh):
        tree = {
            "w": jax.device_put(np.zeros((512, 128), np.float32),
                                NamedSharding(mesh, P())),
            "count": jax.device_put(np.zeros((), np.int32),
                                    NamedSharding(mesh, P())),
        }
        specs = shd.zero_tree_specs(tree, mesh)
        assert specs["w"] == P("data", None)
        assert specs["count"] == P()


class TestZeroTraining:
    def test_opt_state_sharded_params_replicated(self):
        mesh = create_mesh(MeshSpec.dp())
        dp = mesh.shape["data"]
        _cfg, state, _fn, shardings = _trainer(mesh, zero=True)
        # params stay replicated (the pure-DP rule table maps every
        # logical axis to None): the transform touches the update only
        for leaf in jax.tree.leaves(state["params"]):
            assert leaf.sharding.is_fully_replicated
        # optimizer state carries the DP axis...
        dp_specs = [
            sp for sp in jax.tree.leaves(
                jax.tree.map(lambda s: s.spec, shardings["opt_state"]),
                is_leaf=lambda x: isinstance(x, P))
            if "data" in [a for part in sp
                          for a in (part if isinstance(part, tuple)
                                    else (part,))]]
        assert dp_specs, "no opt-state leaf sharded over the data axis"
        # ...and the per-device footprint drops ~1/N (scalars/odd leaves
        # stay replicated, so the ratio is a bit under dp; gate at 3/4)
        zero_bytes = _tree_device_bytes(state["opt_state"])
        rep_bytes = _tree_device_bytes(
            jax.eval_shape(_optimizer().init, state["params"]))
        assert rep_bytes / zero_bytes >= 0.75 * dp, (rep_bytes, zero_bytes)

    def test_loss_trajectory_parity(self):
        """The sharded update changes layout, never semantics: same data,
        same seeds -> params after ONE step match to reduction-order
        noise, and the 4-step loss trajectories track at LOSS_ATOL.
        (Per-element params are NOT compared at step 4: adamw's early-
        warmup normalization chaotically amplifies 1e-8 reduction-order
        noise to ~1e-4 per element while the loss stays at 1e-6 — the
        documented parity is the trajectory, see docs/training.md.)"""
        mesh = create_mesh(MeshSpec.dp())
        cfg, s_off, f_off, _ = _trainer(mesh, zero=False)
        _, s_on, f_on, _ = _trainer(mesh, zero=True)
        tokens = _tokens(cfg)
        s_off, losses_off = _run_steps(mesh, cfg, s_off, f_off, tokens, 1)
        s_on, losses_on = _run_steps(mesh, cfg, s_on, f_on, tokens, 1)
        for a, b in zip(jax.tree.leaves(s_off["params"]),
                        jax.tree.leaves(s_on["params"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=RESTORE_ATOL, rtol=0)
        s_off, more_off = _run_steps(mesh, cfg, s_off, f_off, tokens, 3)
        s_on, more_on = _run_steps(mesh, cfg, s_on, f_on, tokens, 3)
        np.testing.assert_allclose(losses_on + more_on,
                                   losses_off + more_off,
                                   atol=LOSS_ATOL, rtol=0)


class TestZeroCheckpoint:
    """Round-trips of DP-sharded optimizer state: the elastic story."""

    def _saved(self, flow_ds, steps=2):
        """Train 2 steps under zero-on dp8, checkpoint, and return the
        continued-reference state one step later."""
        mesh8 = create_mesh(MeshSpec.dp())
        cfg, state, step_fn, _ = _trainer(mesh8, zero=True)
        tokens = _tokens(cfg)
        state, _ = _run_steps(mesh8, cfg, state, step_fn, tokens, steps)
        mgr = AsyncCheckpointManager(flow_ds, name="zero")
        mgr.save(state, steps)
        mgr.wait()
        # host snapshot BEFORE the reference step: the donated train step
        # consumes (deletes) `state`'s device buffers
        saved = jax.tree.map(lambda x: np.asarray(x), state)
        ref, _ = _run_steps(mesh8, cfg, state, step_fn, tokens, 1)
        return cfg, tokens, saved, ref

    @pytest.fixture()
    def flow_ds(self, tpuflow_root):
        return FlowDataStore("ZeroCkptFlow", LocalStorage)

    def test_restore_same_config_bit_exact(self, flow_ds):
        cfg, _tok, saved, _ref = self._saved(flow_ds)
        mesh8 = create_mesh(MeshSpec.dp())
        mgr = AsyncCheckpointManager(flow_ds, name="zero")
        _, state, _fn, _ = _trainer(mesh8, zero=True, checkpoint=mgr)
        assert mgr.last_restored.step == 2
        for a, b in zip(jax.tree.leaves(saved), jax.tree.leaves(state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("n_devices,zero", [
        (8, False),   # same mesh, sharded update switched OFF
        (4, True),    # elastic shrink 8 -> 4, still sharded
        (4, False),   # shrink AND switch off at once
    ])
    def test_restore_across_dp_and_zero(self, flow_ds, n_devices, zero):
        """A checkpoint saved under ZeRO-on dp8 restores onto a different
        DP size and/or ZeRO-off and continues the SAME trajectory: one
        step after restore matches one step of the uninterrupted run."""
        cfg, tokens, _saved, ref = self._saved(flow_ds)
        mesh = create_mesh(MeshSpec.dp(),
                           devices=jax.devices()[:n_devices])
        mgr = AsyncCheckpointManager(flow_ds, name="zero")
        _, state, step_fn, _ = _trainer(mesh, zero=zero, checkpoint=mgr)
        state, _ = _run_steps(mesh, cfg, state, step_fn, tokens, 1)
        assert int(state["step"]) == int(ref["step"])
        for a, b in zip(jax.tree.leaves(ref["params"]),
                        jax.tree.leaves(state["params"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=RESTORE_ATOL, rtol=0)

    def test_restore_under_different_optimizer_raises(self, flow_ds):
        self._saved(flow_ds)
        mesh8 = create_mesh(MeshSpec.dp())
        mgr = AsyncCheckpointManager(flow_ds, name="zero")
        with pytest.raises(ValueError, match="different optimizer"):
            _trainer(mesh8, zero=True, checkpoint=mgr,
                     optimizer=memory_efficient_optimizer())


class TestCheckOptState:
    """The make_trainer optimizer-mismatch guard (train_step.py)."""

    @pytest.fixture()
    def state(self):
        mesh = create_mesh(MeshSpec.dp())
        _cfg, state, _fn, _sh = _trainer(mesh, zero=False)
        return state

    def test_matching_optimizer_passes(self, state):
        check_opt_state(_optimizer(), state)
        # different hyperparams, same state SHAPES: shape-invisible by
        # design — the guard documents it cannot catch this
        check_opt_state(default_optimizer(lr=5e-3), state)

    def test_wrong_family_raises(self, state):
        sgd = optax.chain(optax.clip_by_global_norm(1.0), optax.sgd(1e-2))
        with pytest.raises(ValueError, match="optimizer/opt_state"):
            check_opt_state(sgd, state)

    def test_same_family_different_dtype_raises(self, state):
        bf16 = default_optimizer(mu_dtype=jnp.bfloat16)
        with pytest.raises(ValueError, match="hyperparameters"):
            check_opt_state(bf16, state)

    def test_factored_vs_adamw_raises(self, state):
        with pytest.raises(ValueError, match="optimizer/opt_state"):
            check_opt_state(memory_efficient_optimizer(), state)


class TestSanitizerZeroCollectives:
    def test_vocabulary_pinned_in_schema(self):
        """The sanitizer's signature vocabulary and the stream schema are
        the same two-file registry: adding a collective is a deliberate
        change to BOTH, never drift."""
        assert sanitizer.SIG_KINDS == schema_validate.SANITIZE_SIG_KINDS
        assert (sanitizer.COLLECTIVE_NAMES
                == schema_validate.SANITIZE_COLLECTIVE_NAMES)
        for name in ("zero.reduce_scatter", "zero.shard",
                     "zero.all_gather"):
            assert name in sanitizer.COLLECTIVE_NAMES

    def test_unknown_collective_name_raises(self):
        with pytest.raises(ValueError, match="unknown collective"):
            sanitizer.journal_collective("zero.bogus")

    def test_zero_schedule_lands_in_stream(self, tpuflow_root):
        """Building + stepping a zero trainer journals the schedule's
        collectives at trace time, the compile key carries the zero
        switch, and the published window validates against the pinned
        stream schema."""
        fds = FlowDataStore("ZeroSanFlow", LocalStorage)
        san = sanitizer.set_active(sanitizer.GangSanitizer(
            fds, "r1", rank=0, world=1))
        try:
            mesh = create_mesh(MeshSpec.dp())
            cfg, state, step_fn, _ = _trainer(mesh, zero=True)
            _run_steps(mesh, cfg, state, step_fn, _tokens(cfg), 1)
        finally:
            sanitizer.uninstall()
        sigs = [s for _seq, s in san._sigs]
        compile_sigs = [s for s in sigs if s.startswith("compile|")]
        assert compile_sigs and compile_sigs[0].endswith(";zero")
        for name in ("zero.reduce_scatter", "zero.shard",
                     "zero.all_gather"):
            assert any(s.startswith("collective|%s|" % name)
                       for s in sigs), (name, sigs)
        payload = san.publish(0)
        schema_validate.validate_sanitize_stream(payload)

    def test_replicated_step_journals_no_zero(self, tpuflow_root):
        """Zero-off traces journal NO zero.* signatures — one rank on the
        sharded schedule while another replicates is exactly the desync
        the first barrier must catch, so the streams must differ."""
        fds = FlowDataStore("ZeroSanFlow", LocalStorage)
        san = sanitizer.set_active(sanitizer.GangSanitizer(
            fds, "r2", rank=0, world=1))
        try:
            mesh = create_mesh(MeshSpec.dp())
            cfg, state, step_fn, _ = _trainer(mesh, zero=False)
            _run_steps(mesh, cfg, state, step_fn, _tokens(cfg), 1)
        finally:
            sanitizer.uninstall()
        sigs = [s for _seq, s in san._sigs]
        assert not any("zero." in s for s in sigs)
        assert not any(s.endswith(";zero") for s in sigs)


class TestZeroMetrics:
    @pytest.fixture()
    def recorder(self, tpuflow_root):
        fds = FlowDataStore("ZeroMetricsFlow", LocalStorage)
        telemetry.init_recorder(fds, "r1", "train", "7", attempt=1)
        yield fds
        telemetry.close_recorder()

    def test_memory_split_gauges(self, recorder):
        """The device-memory gauge splits into params / opt-state /
        activations; with the sharded update on, the opt-state gauge
        shows the ~1/N drop (this is where the HBM win is observable)."""
        mesh = create_mesh(MeshSpec.dp())
        dp = mesh.shape["data"]
        cfg, state, step_fn, _ = _trainer(
            mesh, zero=True, telemetry={"memory_every": 1})
        rep_bytes = _tree_device_bytes(
            jax.eval_shape(_optimizer().init, state["params"]))
        params_bytes = _tree_device_bytes(state["params"])
        opt_bytes = _tree_device_bytes(state["opt_state"])
        _run_steps(mesh, cfg, state, step_fn, _tokens(cfg), 2)
        step_fn.telemetry.close()
        records = telemetry.read_run_records(recorder, "r1")
        gauges = {}
        for r in records:
            if r.get("type") == "gauge":
                gauges.setdefault(r["name"], []).append(r["value"])
        assert gauges["train.memory.params_bytes"][0] == params_bytes
        assert gauges["train.memory.opt_state_bytes"][0] == opt_bytes
        assert rep_bytes / opt_bytes >= 0.75 * dp
        assert "train.summary.memory_opt_state_bytes" in gauges

    def test_optimizer_update_ms_in_step_records(self, recorder):
        """timed_update=True rides the update's wall time into the step
        records as optimizer_update_ms, which the pinned train-step
        schema accepts."""
        mesh = create_mesh(MeshSpec.dp())
        cfg, state, step_fn, _ = _trainer(
            mesh, zero=True, timed_update=True, telemetry=True)
        _run_steps(mesh, cfg, state, step_fn, _tokens(cfg), 3)
        step_fn.telemetry.close()
        records = telemetry.read_run_records(recorder, "r1")
        steps = [r for r in records if r.get("name") == "train.step"]
        assert steps
        timed = [r for r in steps
                 if (r.get("data") or {}).get("optimizer_update_ms")
                 is not None]
        assert timed, steps
        for r in timed:
            schema_validate.validate_train_step_record(r)
            assert r["data"]["optimizer_update_ms"] > 0
        assert step_fn.telemetry.report()["optimizer_update_ms"] > 0


class TestZeroTrainFlow:
    def test_flow_runs_clean(self, run_flow, flows_dir):
        """The docs/training.md demo flow: replicated-vs-sharded parity,
        the ~1/N opt-state footprint, and a bit-exact checkpoint
        round-trip, end to end as a real flow run."""
        proc = run_flow(os.path.join(flows_dir, "zero_train_flow.py"),
                        "run", env_extra={"ZERO_FLOW_STEPS": "2"})
        out = proc.stdout + proc.stderr
        assert "zero run ok" in out, out
        assert "opt_state_ratio=8.00" in out, out
