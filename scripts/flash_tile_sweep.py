"""Sweep the flash-attention kernels' tiles on the chip.

    chiprun -- python scripts/flash_tile_sweep.py alone
    chiprun -- python scripts/flash_tile_sweep.py step fwd=1024x512,dq=...
    JAX_PLATFORMS=cpu python scripts/flash_tile_sweep.py describe

`alone` times each of the three kernels by itself at one folded shape
(default the training cell's, [64, 4096, 128] bfloat16 causal) over
block_q x block_k; `step` times the cell's whole train step with
`flash_tiles` answering the named tiles (`;` between candidates);
`describe` compiles every candidate for a v5e that is described, not
attached: what Mosaic refuses it refuses there, at no chip time, and
nothing it prints is a time. What ops/attention.py `flash_tiles` answers
came from here (PERF.md section 6, PR 32). Lines go to standard output
and to chiprun_out/flash_tile_sweep.jsonl.
"""

import argparse
import importlib
import json
import math
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SIZES = (128, 256, 512, 1024, 2048)
SCORE_TILE_BYTES = 4 * 2 ** 20
OUT = os.path.join(ROOT, "chiprun_out", "flash_tile_sweep.jsonl")


def emit(**line):
    print(json.dumps(line), flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(json.dumps(line) + "\n")


def candidates(S):
    return [(bq, bk) for bq in SIZES for bk in SIZES
            if bq <= S and bk <= S and bq * bk * 4 <= SCORE_TILE_BYTES]


def kernel_fns(attn, scale, causal, blocks):
    """{kernel: jitted call of that kernel alone}. The backward's two
    kernels share an entry point; the output that is dropped takes its
    kernel with it."""
    import jax

    def bwd(pick):
        return jax.jit(lambda q, k, v, g, lse, delta: pick(
            attn.flash_block_bwd(q, k, v, g, lse, delta, scale, causal,
                                 grad_dtype=q.dtype,
                                 blocks=(blocks, blocks))))

    return {
        "fwd": jax.jit(lambda q, k, v, g, lse, delta: attn._flash_forward(
            q, k, v, causal, scale, blocks=blocks)[0]),
        "dq": bwd(lambda grads: grads[0]),
        "dkv": bwd(lambda grads: grads[1:]),
    }


def alone(args):
    import jax
    import jax.numpy as jnp

    from metaflow_tpu import device

    # by its path: `metaflow_tpu.ops.attention` the attribute is the function
    attn = importlib.import_module("metaflow_tpu.ops.attention")
    describe = args.mode == "describe"
    BH, S, D = args.shape
    scale = 1.0 / math.sqrt(D)
    causal = not args.full
    if describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        jax.config.update("jax_enable_compilation_cache", False)
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        one = SingleDeviceSharding(topo.devices[0])
        x = jax.ShapeDtypeStruct((BH, S, D), jnp.bfloat16, sharding=one)
        stat = jax.ShapeDtypeStruct((BH, S), jnp.float32, sharding=one)
        operands = (x, x, x, x, stat, stat)
    else:
        if not device.on_tpu():
            raise SystemExit("a time comes from the chip only")
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        q, k, v, g = (jax.random.normal(kx, (BH, S, D), jnp.bfloat16)
                      for kx in keys)
        out, lse = attn._flash_forward(q, k, v, causal, scale)
        delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), -1)
        operands = (q, k, v, g, lse, delta)
        emit(what="device", **device.describe())
    only = args.kernels.split(",")
    for blocks in candidates(S):
        for kernel, fn in kernel_fns(attn, scale, causal, blocks).items():
            if kernel not in only:
                continue
            line = dict(what=args.mode, kernel=kernel, shape=[BH, S, D],
                        causal=causal, block_q=blocks[0], block_k=blocks[1])
            t0 = time.perf_counter()
            try:
                if describe:
                    fn.lower(*operands).compile()
                else:
                    jax.block_until_ready(fn(*operands))
                line["compile_s"] = round(time.perf_counter() - t0, 2)
                if not describe:
                    t0 = time.perf_counter()
                    for _i in range(args.calls):
                        res = fn(*operands)
                    jax.block_until_ready(res)
                    line["ms"] = round(
                        (time.perf_counter() - t0) * 1e3 / args.calls, 3)
            except Exception as ex:  # what the compiler refuses is a result
                line["refused"] = str(ex).strip().splitlines()[-1][:300]
            emit(**line)


def parse_tiles(text):
    """'fwd=1024x512,dq=512x512,dkv=512x1024' -> {kernel: (bq, bk)}."""
    tiles = {}
    for part in text.split(","):
        kernel, _, pair = part.partition("=")
        tiles[kernel] = tuple(int(n) for n in pair.split("x"))
    return tiles


def step(args):
    """The training cell's own step (benchmark/drivers/train.py builds
    it the same way), one trainer, a new jitted step a candidate."""
    import types

    import jax

    from benchmark import configs, loadgen, weights
    from metaflow_tpu import device
    from metaflow_tpu.spmd import MeshSpec, create_mesh
    from metaflow_tpu.training import (
        ResumableTokenBatches,
        make_train_step,
        make_trainer,
        memory_efficient_optimizer,
        shard_batch,
    )
    attn = importlib.import_module("metaflow_tpu.ops.attention")
    if not device.on_tpu():
        raise SystemExit("a time comes from the chip only")
    _, cell, config, t = configs.load_cell(args.cell)
    dims = configs.dims(config)
    seq, batch = t["seq_len"], t["sequences_per_chip"] * cell["chips"]
    model, cfg = configs.program_config(config, seq)
    mesh = create_mesh(getattr(MeshSpec, t["mesh"])(*t.get("mesh_args", [])),
                       devices=jax.devices()[:cell["chips"]])
    data = loadgen.zipf_corpus(dims["vocab_size"], t["corpus_tokens"],
                               t["zipf_exponent"], args.seed)
    stream = iter(ResumableTokenBatches(data, batch, seq, seed=args.seed))
    o = t["optimizer"]
    optimizer = memory_efficient_optimizer(
        lr=o["lr"], weight_decay=o["weight_decay"], clip_norm=o["clip_norm"],
        warmup_steps=o["warmup_steps"], total_steps=o["total_steps"],
        b1=o["b1"])
    shim = types.SimpleNamespace(
        init_params=lambda rng, _cfg: weights.init_params(rng, dims),
        logical_axes=model.logical_axes, loss_fn=model.loss_fn)
    state, _step, shardings = make_trainer(
        weights.seed_key(args.seed), cfg, mesh, shim, optimizer=optimizer)
    emit(what="device", **device.describe())
    the_functions = attn.flash_tiles

    for text in args.tiles.split(";"):
        tiles = parse_tiles(text) if text != "default" else {}

        def answer(S, D, dtype, causal, kernel, tiles=tiles):
            return tiles.get(kernel) or the_functions(S, D, dtype, causal,
                                                      kernel)

        attn.flash_tiles = answer
        run = make_train_step(cfg, mesh, shim, optimizer=optimizer,
                              state_shardings=shardings)
        line = dict(what="step", cell=args.cell, tiles=text)
        t0 = time.perf_counter()
        try:
            for _i in range(2):
                state, metrics = run(state, shard_batch(
                    {"tokens": next(stream)["tokens"]}, mesh))
            metrics["loss"].block_until_ready()
            line["compile_and_2_steps_s"] = round(time.perf_counter() - t0, 2)
            ms = []
            for _i in range(args.calls):
                feed = shard_batch({"tokens": next(stream)["tokens"]}, mesh)
                t0 = time.perf_counter()
                state, metrics = run(state, feed)
                metrics["loss"].block_until_ready()
                ms.append((time.perf_counter() - t0) * 1e3)
            line["step_ms"] = round(sorted(ms)[len(ms) // 2], 2)
            line["tokens_per_s"] = round(batch * seq / line["step_ms"] * 1e3,
                                         1)
            line["loss"] = float(metrics["loss"])
        except Exception as ex:
            line["refused"] = str(ex).strip().splitlines()[-1][:300]
        emit(**line)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=["alone", "describe", "step"])
    ap.add_argument("tiles", nargs="?", default="default")
    ap.add_argument("--shape", type=int, nargs=3, default=(64, 4096, 128))
    ap.add_argument("--full", action="store_true", help="not causal")
    ap.add_argument("--kernels", default="fwd,dq,dkv")
    ap.add_argument("--cell", default="mistral-7b.train-4k")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--seed", type=int, default=3200000001)
    args = ap.parse_args()
    (step if args.mode == "step" else alone)(args)


if __name__ == "__main__":
    main()
