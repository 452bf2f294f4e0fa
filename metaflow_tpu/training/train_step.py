"""Training loop building blocks: sharded init, jitted train step.

The pjit/GSPMD path the reference delegates to user frameworks (SURVEY.md
§5.7): params and optimizer state are sharded via the model's logical axes +
the mesh's rule table; the train step donates its state buffers so the update
is in-place in HBM, and XLA inserts the gradient psum/reduce-scatter over the
data/fsdp axes.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec

from .. import device
from ..spmd import sanitizer
from ..spmd import sharding as shd


def _lr_schedule(lr, warmup_steps, total_steps):
    return optax.warmup_cosine_decay_schedule(
        init_value=0.0, peak_value=lr, warmup_steps=warmup_steps,
        decay_steps=max(total_steps, warmup_steps + 1), end_value=lr * 0.1,
    )


def default_optimizer(lr=3e-4, weight_decay=0.1, clip_norm=1.0,
                      warmup_steps=100, total_steps=10_000, b1=0.9, b2=0.95,
                      mu_dtype=jnp.float32):
    schedule = _lr_schedule(lr, warmup_steps, total_steps)
    return optax.chain(
        optax.clip_by_global_norm(clip_norm),
        optax.adamw(schedule, b1=b1, b2=b2, weight_decay=weight_decay,
                    mu_dtype=mu_dtype),
    )


def memory_efficient_optimizer(lr=3e-4, weight_decay=0.1, clip_norm=1.0,
                               warmup_steps=100, total_steps=10_000, b1=0.9):
    """Adafactor-style state: bf16 first moment + factored second moment
    (~2 bytes/param of optimizer state vs adamw's 8). On a single v5e chip
    this is what unlocks batch >16 for the ~1B bench config — optimizer
    state stops competing with activations for HBM.

    Weight decay matches default_optimizer's decoupled form (decay scaled by
    the scheduled lr, adamw-style) so switching optimizers changes memory,
    not regularization."""
    schedule = _lr_schedule(lr, warmup_steps, total_steps)
    adafactor = optax.adafactor(
        learning_rate=schedule,
        multiply_by_parameter_scale=False,
        clipping_threshold=None,
        momentum=b1,
        dtype_momentum=jnp.bfloat16,
        weight_decay_rate=None,
        eps=1e-30,
        factored=True,
    )

    # decoupled decay: adafactor's update already carries its -lr(t) sign,
    # so add -lr(t)*wd*w on top (same step-count the schedule sees)
    def init_fn(params):
        return {"inner": adafactor.init(params),
                "count": jnp.zeros((), jnp.int32)}

    def update_fn(updates, state, params=None):
        new_updates, inner = adafactor.update(updates, state["inner"], params)
        if weight_decay:
            lr = schedule(state["count"])
            new_updates = jax.tree.map(
                lambda u, p: u - lr * weight_decay * p, new_updates, params
            )
        return new_updates, {"inner": inner, "count": state["count"] + 1}

    return optax.chain(
        optax.clip_by_global_norm(clip_norm),
        optax.GradientTransformation(init_fn, update_fn),
    )


def reshard_like(tree, like):
    """Re-place a checkpoint-restored pytree onto the shardings of a
    LIVE state tree (same structure) — the resume recipe for a fresh
    process.

    orbax restores arrays with the shardings they were SAVED with, which
    a retry/resume process cannot use directly. Mesh-sharded leaves are
    device_put onto their NamedSharding; leaves whose live counterpart
    sits on a single device (optimizer step counters and other scalars
    that jit left unconstrained) are returned as HOST numpy instead —
    committing them to device 0 via device_put would poison a
    multi-device jit with 'incompatible devices', while an uncommitted
    host array lets jit place them exactly as it placed the originals.
    """
    def _place(restored, live):
        host = np.asarray(jax.device_get(restored))
        sharding = getattr(live, "sharding", None)
        if isinstance(sharding, NamedSharding):
            return jax.device_put(host, sharding)
        return host

    return jax.tree.map(_place, tree, like)


def check_opt_state(optimizer, state):
    """Guard: would `optimizer.init(state['params'])` produce this opt state?

    Using one optimizer to build the state and a different one in the step
    is silently wrong when the trees happen to line up (e.g. two adamw
    chains with different hyperparams) and a deep GSPMD crash when they do
    not. The check compares the abstract tree `optimizer.init` would build
    against the live/restored `state['opt_state']` — structure, shapes and
    dtypes — and raises a ValueError that names the mismatch. Costs one
    eval_shape (no compile, no device work)."""
    expect = jax.eval_shape(optimizer.init, state["params"])
    got = state["opt_state"]
    want_def = jax.tree.structure(expect)
    got_def = jax.tree.structure(got)
    if want_def != got_def:
        raise ValueError(
            "optimizer/opt_state mismatch: optimizer.init(params) would "
            "build tree\n  %s\nbut state['opt_state'] has tree\n  %s\n"
            "make_train_state and make_train_step must share ONE optimizer "
            "(use make_trainer, which enforces this); a restored checkpoint "
            "must have been saved with the same optimizer the trainer now "
            "uses." % (want_def, got_def))
    for path_want, path_got in zip(
            jax.tree_util.tree_leaves_with_path(expect),
            jax.tree_util.tree_leaves_with_path(got)):
        path, want = path_want
        _, have = path_got
        want_shape = tuple(want.shape)
        have_shape = tuple(getattr(have, "shape", ()))
        have_dtype = getattr(have, "dtype", None)
        if want_shape != have_shape or (
                have_dtype is not None and want.dtype != have_dtype):
            raise ValueError(
                "optimizer/opt_state mismatch at opt_state%s: optimizer."
                "init(params) would build %s%s, state has %s%s — same "
                "optimizer family but different hyperparameters (mu_dtype, "
                "factoring, ...)?" % (
                    jax.tree_util.keystr(path), want.dtype, want_shape,
                    have_dtype, have_shape))


def _opt_state_shardings(optimizer, params, param_shardings, mesh):
    """Where each leaf of `optimizer.init(params)` goes. A leaf that
    mirrors a parameter (its path ends in the parameter's, with the
    parameter's shape: a moment) is placed like the parameter; the rest
    (factored moments, counters) is small and replicated over the mesh.

    jit does not do this by itself: a moment starts as zeros, which
    depend on no input, so GSPMD leaves it replicated — a whole copy of
    every moment on every device — and a leaf that lands on one device
    comes back from the first step on the mesh, which compiles the
    whole step a second time."""
    flat_params = jax.tree_util.tree_leaves_with_path(params)
    flat_shardings = jax.tree.leaves(param_shardings)
    replicated = NamedSharding(mesh, PartitionSpec())

    def place(path, leaf):
        for (ppath, param), sharding in zip(flat_params, flat_shardings):
            if (tuple(path[-len(ppath):]) == tuple(ppath)
                    and leaf.shape == param.shape):
                return sharding
        return replicated

    return jax.tree_util.tree_map_with_path(
        place, jax.eval_shape(optimizer.init, params))


def make_train_state(rng, cfg, mesh, model, optimizer=None, rules=None,
                     zero=None):
    """Sharded init: params + optimizer state placed per the rule table.

    model: module exposing init_params(rng, cfg) and logical_axes(cfg).
    zero: ZeRO-style sharded update (spmd/sharding.py) — when enabled, the
    optimizer state is re-placed 1/N-sharded over the DP axis after init,
    so each replica holds (and updates) only its shard. None resolves from
    the TPUFLOW_ZERO env knob; a mesh without a data axis forces it off.
    Returns (state dict, shardings dict).
    """
    optimizer = optimizer or default_optimizer()
    rules = rules or shd.rules_for_mesh(mesh)
    log_axes = model.logical_axes(cfg)
    param_shardings = shd.tree_shardings(log_axes, mesh, rules)
    use_zero = shd.zero_enabled(mesh, zero)

    def init():
        params = model.init_params(rng, cfg)
        return params

    with mesh:
        params = jax.jit(init, out_shardings=param_shardings)()
        opt_state = jax.jit(
            optimizer.init,
            out_shardings=_opt_state_shardings(
                optimizer, params, param_shardings, mesh),
        )(params)
        step0 = jax.device_put(jnp.zeros((), jnp.int32),
                               NamedSharding(mesh, PartitionSpec()))
        if use_zero:
            # re-spec each live leaf over the DP axis (base = the sharding
            # GSPMD propagated, so model-parallel axes are kept) and
            # re-place. device_put, not a second compile: the replicated
            # copy is freed as each leaf lands, so peak memory never
            # exceeds the non-zero path's.
            opt_state = jax.device_put(
                opt_state, shd.zero_tree_shardings(opt_state, mesh))
    state = {"params": params, "opt_state": opt_state, "step": step0}
    shardings = {
        "params": param_shardings,
        "opt_state": jax.tree.map(lambda x: x.sharding, opt_state),
        "step": jax.tree.map(lambda x: x.sharding, state["step"]),
    }
    return state, shardings


def make_train_step(cfg, mesh, model, optimizer=None, loss_fn=None,
                    zero=None, rules=None, opt_specs=None,
                    timed_update=False, state_shardings=None):
    """Build the jitted, donated train step: (state, batch) → (state, metrics).

    WARNING: `optimizer` must be the SAME GradientTransformation the state
    was built with — a mismatch gives silently wrong updates when the state
    trees happen to line up. Use make_trainer (which shares one optimizer
    and runs check_opt_state) unless you have a reason not to.

    zero: ZeRO-style weight-update sharding. The replicated-DP update is
    rewritten as  grad reduce-scatter → 1/N-sharded optimizer update →
    param all-gather, expressed purely as sharding constraints (GSPMD
    inserts the collectives; semantics are unchanged). The all-gathered
    params feed only the RETURNED state — nothing later in the step
    consumes them — so XLA's latency-hiding scheduler can overlap the
    gather with the loss/grad-norm tail and the next step's dispatch.
    None resolves from TPUFLOW_ZERO; meshes without a data axis force off.

    opt_specs: optional pytree of PartitionSpecs for the (zero-sharded)
    optimizer state, matching make_train_state's placement. When omitted,
    the specs are re-derived at trace time from shapes with a replicated
    base — identical on pure-DP meshes; pass the live specs on mixed
    meshes to avoid a per-step reshard of model-parallel state.

    timed_update: split the step into two jits (grad, then donated update)
    with dispatch fences so the wrapper can report `last_update_ms` — the
    wall time of the optimizer update + collectives — per call. This is a
    DIAGNOSTIC mode: the fences serialize work the fused step overlaps, so
    never benchmark with it on. training/metrics.py picks the attribute up
    into the per-step telemetry record as `optimizer_update_ms`.

    state_shardings: make_train_state's `shardings`. The step then
    returns the state placed exactly as it came in: without it GSPMD may
    re-place a leaf it finds cheaper elsewhere (a factored moment), and a
    state whose placement changed compiles the step a second time and is
    not updated in place.

    `mesh` shapes the zero schedule's constraints; with zero off the step
    itself is mesh-agnostic (shardings propagate from the state)."""
    optimizer = optimizer or default_optimizer()
    loss_fn = loss_fn or model.loss_fn
    use_zero = shd.zero_enabled(mesh, zero)

    import inspect

    loss_takes_mesh = "mesh" in inspect.signature(loss_fn).parameters

    def compute_loss(params, batch):
        if loss_takes_mesh:
            return loss_fn(params, batch, cfg, mesh=mesh)
        return loss_fn(params, batch, cfg)

    if use_zero:
        zero_axis = shd.zero_update_axis(mesh)
        base_specs = shd.tree_specs(
            model.logical_axes(cfg), rules or shd.rules_for_mesh(mesh))

    @jax.named_scope("optimizer_update")
    def apply_update(params, grads, opt_state):
        """(full grads, state) -> (new params, new opt state, grad norm).

        Zero path: constraining the summed grads onto DP-sharded specs
        turns the grad all-reduce into a reduce-scatter; the optimizer
        then runs on 1/N-sized shards (params sliced locally — no
        collective, each replica already holds the full value); finally
        constraining the updated params back to their base (replicated-
        over-DP) specs emits the all-gather. grad_norm is computed from
        the scattered shards — same value, 1/N the reduction input."""
        if not use_zero:
            updates, new_opt = optimizer.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), new_opt,
                    optax.global_norm(grads))
        specs = jax.tree.map(
            lambda g, sp: shd.zero_spec(sp, g.shape, mesh, axis=zero_axis),
            grads, base_specs)
        ospecs = opt_specs
        if ospecs is None:
            ospecs = jax.tree.map(
                lambda o: shd.zero_spec(
                    jax.sharding.PartitionSpec(), o.shape, mesh,
                    axis=zero_axis),
                opt_state)
        grads = shd.zero_constrain(grads, mesh, specs, "reduce_scatter")
        params_sh = shd.zero_constrain(params, mesh, specs, "shard")
        opt_state = jax.tree.map(
            lambda o, sp: jax.lax.with_sharding_constraint(
                o, jax.sharding.NamedSharding(mesh, sp)),
            opt_state, ospecs)
        updates, new_opt = optimizer.update(grads, opt_state, params_sh)
        updates = jax.tree.map(
            lambda u, sp: jax.lax.with_sharding_constraint(
                u, jax.sharding.NamedSharding(mesh, sp)),
            updates, specs)
        new_params = optax.apply_updates(params_sh, updates)
        new_params = shd.zero_constrain(
            new_params, mesh, base_specs, "all_gather")
        new_opt = jax.tree.map(
            lambda o, sp: jax.lax.with_sharding_constraint(
                o, jax.sharding.NamedSharding(mesh, sp)),
            new_opt, ospecs)
        return new_params, new_opt, optax.global_norm(grads)

    if not timed_update:
        def step(state, batch):
            loss, grads = jax.value_and_grad(
                lambda p: compute_loss(p, batch))(state["params"])
            params, opt_state, grad_norm = apply_update(
                state["params"], grads, state["opt_state"])
            new_state = {
                "params": params,
                "opt_state": opt_state,
                "step": state["step"] + 1,
            }
            return new_state, {"loss": loss, "grad_norm": grad_norm}

        return jax.jit(
            step, donate_argnums=(0,),
            out_shardings=(None if state_shardings is None
                           else (state_shardings, None)))

    # diagnostic split: measure the update (optimizer math + zero
    # collectives) separately from the fwd/bwd. Two compiles, two fences.
    grad_fn = jax.jit(lambda params, batch: jax.value_and_grad(
        lambda p: compute_loss(p, batch))(params))

    def update(state, grads):
        params, opt_state, grad_norm = apply_update(
            state["params"], grads, state["opt_state"])
        new_state = {
            "params": params,
            "opt_state": opt_state,
            "step": state["step"] + 1,
        }
        return new_state, grad_norm

    update_fn = jax.jit(update, donate_argnums=(0, 1))

    def step(state, batch):
        import time

        loss, grads = grad_fn(state["params"], batch)
        jax.block_until_ready(grads)
        t0 = time.perf_counter()
        new_state, grad_norm = update_fn(state, grads)
        jax.block_until_ready(new_state["params"])
        step.last_update_ms = (time.perf_counter() - t0) * 1e3
        return new_state, {"loss": loss, "grad_norm": grad_norm}

    step.last_update_ms = None
    return step


def make_trainer(rng, cfg, mesh, model, optimizer=None, rules=None,
                 loss_fn=None, checkpoint=None, telemetry=None, zero=None,
                 timed_update=False):
    """One-stop builder: returns (state, train_step_fn, shardings) with a
    SINGLE shared optimizer — prefer this over calling make_train_state and
    make_train_step separately: a mismatched optimizer between the two gives
    SILENTLY WRONG updates whenever the opt-state trees happen to line up
    (same optax family, different hyperparameters) and an opaque GSPMD
    crash when they don't. make_trainer shares one optimizer and runs
    check_opt_state after build/restore, so a stale checkpoint saved under
    a different optimizer fails loudly with the mismatch named.

    zero: ZeRO-style cross-replica weight-update sharding (see
    make_train_step / docs/training.md). None resolves from the
    TPUFLOW_ZERO env knob; forced off on meshes without a data axis.

    timed_update: diagnostic split-step mode reporting per-call
    `optimizer_update_ms` through telemetry (see make_train_step).

    telemetry: truthy wraps the returned step with
    training.metrics.instrument_train_step so every call emits per-step
    wall time (+ tokens/sec and MFU when the kwargs below are given)
    through the run's flight recorder. Pass True for defaults or a dict of
    instrument_train_step kwargs, e.g.
    ``telemetry={"tokens_per_step": batch * seq, "flops_per_step": ...}``.

    checkpoint: an AsyncCheckpointManager (training/checkpoint.py). When
    it holds a complete checkpoint, the freshly-initialized state is
    replaced by the restored one re-placed onto the live shardings
    (reshard_like) — so a preempted/retried run resumes instead of
    restarting, and subsequent `checkpoint.save(state, step)` calls
    overlap their upload with the train steps that follow. The resumed
    step and the saved `extra` (e.g. the data iterator's resume stamp)
    are available afterwards as `checkpoint.last_restored` — without
    them a resumed run would silently restart its data stream."""
    device.platform()  # a trainer on a quiet CPU fallback is an error
    optimizer = optimizer or default_optimizer()
    use_zero = shd.zero_enabled(mesh, zero)
    # compile-shaping state: every rank must build the SAME mesh/program
    # (analysis/divergence.py's gang-divergent-compile class, verified at
    # runtime by the sanitizer barrier); the zero switch shapes the
    # program, so it is part of the compile key
    sanitizer.journal("compile", "make_trainer", axes=mesh.axis_names,
                      key=str(dict(mesh.shape))
                      + (";zero" if use_zero else ""))
    state, shardings = make_train_state(
        rng, cfg, mesh, model, optimizer=optimizer, rules=rules,
        zero=use_zero,
    )
    # hand the step the LIVE opt-state placement so mixed (data+model
    # parallel) meshes constrain onto exactly what make_train_state built
    # instead of re-deriving from a replicated base
    opt_specs = None
    if use_zero:
        opt_specs = jax.tree.map(
            lambda s: s.spec if isinstance(s, NamedSharding) else None,
            shardings["opt_state"])
        if any(sp is None for sp in jax.tree.leaves(
                opt_specs, is_leaf=lambda x: x is None)):
            opt_specs = None  # non-mesh placements: let trace-time derive
    step = make_train_step(cfg, mesh, model, optimizer=optimizer,
                           loss_fn=loss_fn, zero=use_zero, rules=rules,
                           opt_specs=opt_specs, timed_update=timed_update,
                           state_shardings=shardings)
    if checkpoint is not None:
        restored = checkpoint.restore(like=state)
        if restored is not None:
            state = restored.state
    check_opt_state(optimizer, state)
    if telemetry:
        from .metrics import instrument_train_step

        kwargs = telemetry if isinstance(telemetry, dict) else {}
        step = instrument_train_step(step, **kwargs)
    # sanitizer wraps OUTERMOST: the instrumentation must keep seeing the
    # raw jitted step (its jit-cache probe and cost-analysis .lower() die
    # on a plain wrapper); the .telemetry handle stays reachable
    wrapped = sanitizer.wrap_step(step)
    if wrapped is not step and hasattr(step, "telemetry"):
        wrapped.telemetry = step.telemetry
    step = wrapped
    return state, step, shardings


def make_eval_step(cfg, mesh, model, loss_fn=None):
    import inspect

    loss_fn = loss_fn or model.loss_fn
    loss_takes_mesh = "mesh" in inspect.signature(loss_fn).parameters

    def step(params, batch):
        if loss_takes_mesh:
            return loss_fn(params, batch, cfg, mesh=mesh)
        return loss_fn(params, batch, cfg)

    return jax.jit(step)


def shard_batch(batch, mesh):
    """Place a host batch onto the mesh: batch dim over data axes; the
    sequence dim over the 'sequence' axis when present AND divisible (a
    [B, S+1] token array stays batch-sharded; GSPMD reshards the sliced
    [B, S] inputs inside the step)."""
    from ..spmd.mesh import data_axes

    sanitizer.journal("collective", "shard_batch", axes=mesh.axis_names,
                      shape=batch)
    axes = data_axes(mesh)
    batch_spec = axes if axes else None
    seq_size = mesh.shape.get("sequence", 1)

    def place(x):
        if (
            seq_size > 1
            and getattr(x, "ndim", 0) >= 2
            and x.shape[1] % seq_size == 0
        ):
            spec = PartitionSpec(batch_spec, "sequence")
        else:
            spec = PartitionSpec(batch_spec)
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(place, batch)
