"""kind `train`: the program's own train step, driven in process.

Set-up builds one trainer (`make_trainer` with the factored optimizer
the traffic file states), drives it through its first steps from the
seed, reading what the comparison needs, and hands the same state and
step to the window. The window runs whole steps until its seconds are
used up. When it has closed and the program's state is freed, the
reference follows the same first steps (benchmark/ref_train.py) and
the readings are compared.
"""

import gc
import math
import time
import types

from .. import flops, harness, loadgen, ref_train, weights


def first_grad_norms(opt_state, params):
    """Every leaf's gradient norm at the first step, as the factored
    second moment kept it: at its first step the moment's decay is 0,
    so a factored leaf's row means, and an unfactored leaf's squares,
    are the clipped gradient's own."""
    import jax
    import jax.numpy as jnp

    found = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "v_row"))
        if hasattr(s, "v_row")]
    if len(found) != 1:
        raise RuntimeError("expected one factored second moment in the "
                           "optimizer state, found %d" % len(found))
    fac = found[0]

    def norm(path, p, v, v_row):
        kept = v if v.shape == p.shape else v_row
        sumsq = jnp.sum(kept.astype(jnp.float32)) * (p.size / kept.size)
        return tuple(k.key for k in path), math.sqrt(max(float(sumsq), 0.0))

    return dict(jax.tree.leaves(
        jax.tree_util.tree_map_with_path(norm, params, fac.v, fac.v_row),
        is_leaf=lambda x: isinstance(x, tuple)))


def first_moment_slices(opt_state):
    """The first rows of every leaf of the optimizer's first moment."""
    import jax

    found = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "ema"))
        if hasattr(s, "ema")]
    if len(found) != 1:
        raise RuntimeError("expected one first moment in the optimizer "
                           "state, found %d" % len(found))
    return {tuple(k.key for k in path): ref_train.moment_slice(leaf)
            for path, leaf in
            jax.tree_util.tree_leaves_with_path(found[0].ema)}


def run(ctx):
    import jax

    from metaflow_tpu import device
    from metaflow_tpu.spmd import MeshSpec, create_mesh
    from metaflow_tpu.training import (
        ResumableTokenBatches,
        make_trainer,
        memory_efficient_optimizer,
        shard_batch,
    )
    from .. import configs

    t, dims, chips = ctx.traffic, ctx.dims, ctx.cell["chips"]
    ref_train.training_family(dims)   # or there is nothing to compare with
    seq, batch = t["seq_len"], t["sequences_per_chip"] * chips
    opt = t["optimizer"]
    model, cfg = configs.program_config(ctx.config, seq)
    mesh = create_mesh(getattr(MeshSpec, t["mesh"])(*t.get("mesh_args", [])),
                       devices=ctx.devices)
    data = loadgen.zipf_corpus(dims["vocab_size"], t["corpus_tokens"],
                              t["zipf_exponent"], ctx.seed)
    stream = iter(ResumableTokenBatches(data, batch, seq, seed=ctx.seed))
    key = weights.seed_key(ctx.seed)
    # the program draws its weights through the benchmark's own function, so
    # that the reference can draw the same ones again without the program
    shim = types.SimpleNamespace(
        init_params=lambda rng, _cfg: weights.init_params(rng, dims),
        logical_axes=model.logical_axes, loss_fn=model.loss_fn)
    state, step, _ = make_trainer(
        key, cfg, mesh, shim, optimizer=memory_efficient_optimizer(
            lr=opt["lr"], weight_decay=opt["weight_decay"],
            clip_norm=opt["clip_norm"], warmup_steps=opt["warmup_steps"],
            total_steps=opt["total_steps"], b1=opt["b1"]))
    ctx.log("trainer built: %d layers, %d x %d tokens a step", dims["n_layers"],
            batch, seq)

    def feed():
        return shard_batch({"tokens": next(stream)["tokens"]}, mesh)

    # the first steps, through the window's own call and feed
    followed, got = [], {"loss": []}
    for i in range(t["followed_steps"]):
        tokens = next(stream)["tokens"]
        followed.append(tokens)
        state, metrics = step(state, shard_batch({"tokens": tokens}, mesh))
        got["loss"].append(float(metrics["loss"]))
        t0 = time.perf_counter()
        if i == 0:
            got["grad_norm"] = first_grad_norms(state["opt_state"],
                                                state["params"])
        if i == ref_train.MOMENT_AFTER_STEP - 1:
            got["moment"] = first_moment_slices(state["opt_state"])
        ctx.excluded_s += time.perf_counter() - t0
    t0 = time.perf_counter()
    got["delta_norm"] = ref_train.delta_norms(
        lambda path: _get(state["params"], path), key, dims)
    ctx.excluded_s += time.perf_counter() - t0
    ctx.log("first steps' losses %s", got["loss"])

    # ---- the window ----
    tokens_per_step = batch * seq
    compiles_before = ctx.compiles["compiles"]
    slice_ = harness.TraceSlice(ctx)
    wait_ms, step_ms, losses = [], [], []
    t_window = time.perf_counter()
    setup_s = ctx.setup_seconds(t_window)
    t_end = t_window
    while t_end - t_window < ctx.seconds:
        ta = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.loader"):
            batch_dev = feed()
        tb = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.train_step"):
            state, metrics = step(state, batch_dev)
            metrics["loss"].block_until_ready()
        t_end = time.perf_counter()
        losses.append(float(metrics["loss"]))
        wait_ms.append((tb - ta) * 1e3)
        step_ms.append((t_end - tb) * 1e3)
        slice_.boundary(t_window)
    window_s = t_end - t_window
    reduced = slice_.close(chips)
    compiled_in_window = ctx.compiles["compiles"] - compiles_before
    memory_peak = device.peak_bytes_in_use()
    n = len(losses)
    tokens_per_s = n * tokens_per_step / window_s
    ctx.log("window: %d steps in %.3f s, %.1f tokens/s", n, window_s,
            tokens_per_s)

    # ---- the comparison, once the program's state is freed ----
    del state, step, metrics, batch_dev
    gc.collect()
    jax.clear_caches()
    t0 = time.perf_counter()
    want = ref_train.follow(key, dims, opt, followed)
    ctx.log("reference followed %d steps in %.1f s", len(followed),
            time.perf_counter() - t0)
    lim = t["limits"]
    for name, value in compare(got, want).items():
        limit = lim.get(name, lim.get(name.split(".")[0]))
        if limit is None:
            ctx.log("%s %r (no limit in this cell)", name, value)
        else:
            ctx.check(name, value, limit)
    if ctx.control:
        # the reference in the program's place, in the precision below
        low = ref_train.follow(key, dims, opt, followed, lowp=True)
        for name, value in compare(low, want).items():
            ctx.control_reading(name, value)
    ctx.check("window_losses_not_finite",
              int(sum(not math.isfinite(x) for x in losses)), 0)
    ctx.check("compilations_in_window", compiled_in_window, 0)
    ctx.check("steps_in_window_short_of_1", max(0, 1 - n), 0)

    return {
        "attempted": n, "failed": 0,
        "memory_peak_bytes": memory_peak,
        "end_to_end": {"train_tokens_per_s": tokens_per_s,
                       "setup_s": setup_s},
        "run": dict(
            ctx.run_sizes(), kind="train", trace=reduced,
            seq_len=seq, sequences_per_step=batch,
            loader_wait_ms=wait_ms, step_ms=step_ms,
            tokens_per_s=tokens_per_s,
            flops_per_token=flops.train_flops_per_token(dims, seq)),
    }


def compare(got, want):
    """The numbers compared, by name: each step's loss, and by the worst
    leaf the first gradient's norm and the norm of the change."""
    out = {"loss_rel_gap.step%d" % (i + 1): abs(a - b) / abs(b)
           for i, (a, b) in enumerate(zip(got["loss"], want["loss"]))}
    for what in ("grad_norm", "delta_norm"):
        out[what + "_worst_leaf_gap"] = ref_train.worst_leaf_gap(
            got[what], want[what])
    if want.get("moment") is not None:
        out["first_moment_diff"] = ref_train.whole_diff(
            got["moment"], want["moment"])
        out["first_moment_worst_leaf_diff"] = ref_train.worst_leaf_diff(
            got["moment"], want["moment"])
    return out


def _get(tree, path):
    for part in path:
        tree = tree[part]
    return tree
