"""Compile a cell's programs for a TPU v5e that is described, not
attached, and print the compiler's count of device memory: what the
chip's compiler refuses, it refuses here, at no chip time. Nothing
runs; nothing printed here is a time or a result.

    JAX_PLATFORMS=cpu python benchmark/describe_compile.py <cell> [--layers N]

Run by hand (it loads the TPU's library at top level, which a test file
must never do).
"""

import argparse
import dataclasses
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def device_bytes(compiled):
    m = compiled.memory_analysis()
    return {"arguments": m.argument_size_in_bytes,
            "outputs": m.output_size_in_bytes,
            "temporaries": m.temp_size_in_bytes,
            "aliased": m.alias_size_in_bytes,
            "total": (m.argument_size_in_bytes + m.output_size_in_bytes
                      + m.temp_size_in_bytes - m.alias_size_in_bytes)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--layers", type=int)
    ap.add_argument("--slots", type=int)
    ap.add_argument("--max-seq-len", type=int)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P

    from benchmark import configs, weights

    jax.config.update("jax_enable_compilation_cache", False)
    _, cell, config, traffic = configs.load_cell(args.cell)
    if args.layers:
        config["num_hidden_layers"] = args.layers
    dims = configs.dims(config)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")

    def on(tree, sharding):
        if isinstance(sharding, jax.sharding.Sharding):
            return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=sharding), tree)
        return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=s), tree, sharding)

    abstract_params = jax.eval_shape(
        lambda: weights.init_params(jax.random.PRNGKey(0), dims))
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree.leaves(abstract_params))
    print("cell %s: %d layers, %.3f B parameters" % (
        args.cell, dims["n_layers"], n_params / 1e9))

    if traffic["kind"] == "train":
        from metaflow_tpu.spmd import MeshSpec
        from metaflow_tpu.spmd import sharding as shd
        from metaflow_tpu.training import (make_train_step,
                                           memory_efficient_optimizer)

        chips = cell["chips"]
        seq, batch = traffic["seq_len"], traffic["sequences_per_chip"] * chips
        model, cfg = configs.program_config(config, seq)
        # 'flash' by name: this process is CPU-pinned, where 'auto' means
        # XLA's dense attention; on the chip 'auto' picks the kernel
        cfg = dataclasses.replace(cfg, attention_impl="flash")
        spec = getattr(MeshSpec, traffic["mesh"])(*traffic.get("mesh_args", []))
        sizes = spec.resolved(chips)
        mesh = Mesh(np.array(topo.devices[:chips]).reshape(
            tuple(sizes.values())), tuple(sizes))
        o = traffic["optimizer"]
        optimizer = memory_efficient_optimizer(
            lr=o["lr"], weight_decay=o["weight_decay"],
            clip_norm=o["clip_norm"], warmup_steps=o["warmup_steps"],
            total_steps=o["total_steps"], b1=o["b1"])
        param_sh = shd.tree_shardings(model.logical_axes(cfg), mesh)
        params = on(abstract_params, param_sh)
        replicated = NamedSharding(mesh, P())
        on_mesh = set(mesh.devices.flat)
        init = jax.jit(optimizer.init).lower(params).compile()
        opt_state = on(
            jax.eval_shape(optimizer.init, params),
            jax.tree.map(lambda s: s if s.device_set <= on_mesh
                         else replicated, init.output_shardings))
        state = {"params": params, "opt_state": opt_state,
                 "step": jax.ShapeDtypeStruct((), jnp.int32,
                                              sharding=replicated)}
        data = tuple(a for a in ("data", "fsdp") if a in mesh.axis_names)
        tokens = jax.ShapeDtypeStruct(
            (batch, seq + 1), jnp.int32,
            sharding=NamedSharding(mesh, P(data or None)))
        step = make_train_step(cfg, mesh, model, optimizer=optimizer)
        compiled = step.lower(state, {"tokens": tokens}).compile()
        print("train step, mesh %s, %d x %d tokens: %s; Mosaic kernel: %s" % (
            dict(mesh.shape), batch, seq, device_bytes(compiled),
            "tpu_custom_call" in compiled.as_text()))
        return

    from metaflow_tpu.serving import SlotEngine

    serving = dict(config["serving"])
    if args.slots:
        serving["slots"] = args.slots
    if args.max_seq_len:
        serving["max_seq_len"] = args.max_seq_len
    _, cfg = configs.program_config(config, serving["max_seq_len"])
    one = SingleDeviceSharding(topo.devices[0])
    params = on(abstract_params, one)
    engine = SlotEngine(params, cfg, max_slots=serving["slots"],
                        max_seq_len=serving["max_seq_len"],
                        prefill_chunk=serving["prefill_chunk"])
    cache = on(jax.eval_shape(lambda: engine._cache), one)
    B = serving["slots"]
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    decode = engine._decode_greedy_fn.lower(
        params, cache, i32(B), i32(B),
        jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one)).compile()
    prefill = engine._prefill_fn.lower(
        params, cache, i32(1, serving["prefill_chunk"]), i32(),
        i32()).compile()
    kv = sum(int(np.prod(x.shape)) * x.dtype.itemsize
             for x in jax.tree.leaves(cache))
    print("slot engine, %d slots x %d positions: weights %.2f GB, KV %.2f GB"
          % (B, serving["max_seq_len"], 2 * n_params / 1e9, kv / 1e9))
    print("decode step: %s" % (device_bytes(decode),))
    print("prefill chunk of %d: %s" % (serving["prefill_chunk"],
                                       device_bytes(prefill)))


if __name__ == "__main__":
    main()
