"""Compile the main path's programs for a TPU v5e that is described, not
attached: what the chip's compiler refuses, it refuses here, at no chip
time. Nothing runs, so nothing here is a result or a time.

This is the only file that describes the chip. The topology is asked
for inside the fixture below and nowhere else: one process at a time
may load the TPU's library, the driver's test workers each import every
test file, and only the worker given this file may load it.
"""

import math
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from metaflow_tpu.models import llama
from metaflow_tpu.ops import gmm as gmm_fn
from metaflow_tpu.ops.attention import (
    flash_attention,
    flash_block_fwd,
)
from metaflow_tpu.ops.ring_attention import ring_attention
from metaflow_tpu.spmd import sharding as shd
from metaflow_tpu.training import (
    make_train_step,
    memory_efficient_optimizer,
)

HBM_BYTES = 16 * 1000 ** 3
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as ex:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % ex)
    # such a compile can be written to the persistent cache but not read
    # back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def on(tree, sharding):
    """The same shapes, placed: `sharding` is one sharding for every
    leaf or a tree of them."""
    if isinstance(sharding, jax.sharding.Sharding):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype, sharding), tree)
    return jax.tree.map(lambda x, s: sds(x.shape, x.dtype, s), tree,
                        sharding)


def compiled_with_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def device_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


# ---------------------------------------------------------------------------
# kernels alone, real widths
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize(
    "batch,seq", [(1, 2048), (1, 8192), (2, 4096)],
    ids=["2048", "8192", "mistral-7b.train-4k"])
def test_flash_attention_llama3_8b_heads(one_chip, batch, seq, grad):
    """With the tiles flash_tiles answers: one that overflows fast memory
    or that Mosaic refuses is found here. The third case is the training
    cell's own shape."""
    q = sds((batch, seq, 32, 128), BF16, one_chip)
    kv = sds((batch, seq, 8, 128), BF16, one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def fwd_bwd(q, k, v):
        return jax.grad(
            lambda *a: fwd(*a).astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)

    compiled_with_kernel(fwd_bwd if grad else fwd, q, kv, kv)


@pytest.mark.parametrize("diag", [True, False], ids=["diag", "off_diag"])
def test_flash_block_fwd(one_chip, diag):
    x = sds((32, 2048, 128), BF16, one_chip)
    compiled_with_kernel(
        lambda q, k, v: flash_block_fwd(q, k, v, 1 / math.sqrt(128), diag),
        x, x, x)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize(
    "rows,dim,ffn,groups",
    [(9216, 4096, 14336, 8), (24576, 2048, 1024, 64)],
    ids=["mixtral_8x7b", "64x1024"])
def test_gmm(one_chip, rows, dim, ffn, groups, grad):
    x = sds((rows, dim), BF16, one_chip)
    w = sds((groups, dim, ffn), BF16, one_chip)
    tiles = sds((rows // 128,), jnp.int32, one_chip)

    def fwd(x, w, tile_group, tile_active):
        return gmm_fn(x, w, tile_group, tile_active=tile_active,
                      interpret=False)

    def dx_dw(x, w, tile_group, tile_active):
        return jax.grad(
            lambda x, w: fwd(x, w, tile_group, tile_active)
            .astype(jnp.float32).sum(), (0, 1))(x, w)

    compiled_with_kernel(dx_dw if grad else fwd, x, w, tiles, tiles)


# ---------------------------------------------------------------------------
# whole programs at Llama-3-8B widths, from eval_shape
# ---------------------------------------------------------------------------

SMOKE_LAYERS = 4  # tests/flows/chip_smoke_flow.py's default depth


def smoke_cfg(**kw):
    # 'flash' by name: this process is CPU-pinned, where 'auto' means XLA
    return llama.LlamaConfig.llama3_8b(
        n_layers=SMOKE_LAYERS, max_seq_len=2048, attention_impl="flash",
        **kw)


def train_step_args(cfg, mesh, batch, seq):
    """(jitted step, abstract state, abstract batch) placed on `mesh` the
    way make_train_state places the live ones."""
    optimizer = memory_efficient_optimizer(total_steps=10)
    param_sh = shd.tree_shardings(llama.logical_axes(cfg), mesh)
    params = on(jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg)), param_sh)
    # the optimizer state's placement is whatever GSPMD propagates from
    # the parameters, as in make_train_state
    # (its counters depend on no parameter and land on the default
    # device, which here is the CPU: those are replicated instead)
    replicated = NamedSharding(mesh, P())
    chips = set(mesh.devices.flat)
    init = jax.jit(optimizer.init).lower(params).compile()
    opt_state = on(
        jax.eval_shape(optimizer.init, params),
        jax.tree.map(lambda s: s if s.device_set <= chips else replicated,
                     init.output_shardings))
    state = {"params": params, "opt_state": opt_state,
             "step": sds((), jnp.int32, replicated)}
    data = tuple(a for a in ("data", "fsdp") if a in mesh.axis_names)
    tokens = sds((batch, seq + 1), jnp.int32,
                 NamedSharding(mesh, P(data or None)))
    step = make_train_step(cfg, mesh, llama, optimizer=optimizer)
    return step, state, {"tokens": tokens}


def test_train_step_llama3_8b_widths_one_chip(topo):
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    step, state, batch = train_step_args(smoke_cfg(), mesh, 4, 2048)
    compiled = step.lower(state, batch).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert device_bytes(compiled) < HBM_BYTES


def test_train_step_llama3_8b_widths_fsdp_tp_four_chips(topo):
    """The compiler cannot partition a Mosaic kernel by itself: on a
    mesh the flash call has to sit under shard_map (ops/attention.py
    _flash_partition), or this compile is refused."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("fsdp", "tensor"))
    step, state, batch = train_step_args(smoke_cfg(), mesh, 4, 2048)
    compiled = step.lower(state, batch).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text or "reduce-scatter" in text
    assert device_bytes(compiled) < HBM_BYTES


def test_ring_flash_attention_four_chips(topo):
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("fsdp", "sequence"))
    spec = NamedSharding(mesh, P("fsdp", "sequence", None, None))
    q = sds((1, 8192, 32, 128), BF16, spec)
    kv = sds((1, 8192, 8, 128), BF16, spec)

    def fwd_bwd(q, k, v):
        return jax.grad(
            lambda *a: ring_attention(*a, mesh, impl="flash")
            .astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)

    text = compiled_with_kernel(fwd_bwd, q, kv, kv).as_text()
    assert "collective-permute" in text


def test_gmm_ep_mixtral_widths_four_chips(topo, monkeypatch):
    """Dropless expert-parallel dispatch (all-to-all in, local grouped
    matmul, all-to-all back) with the experts split four ways."""
    from metaflow_tpu.ops.moe import moe_ffn

    # this process is CPU-pinned, where the kernel would be interpreted
    # (`metaflow_tpu.ops.gmm` the attribute is the function, not the module)
    monkeypatch.setattr(sys.modules["metaflow_tpu.ops.gmm"],
                        "_default_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(1, 4, 1),
                ("fsdp", "expert", "tensor"))
    put = lambda shape, *spec: sds(shape, BF16, NamedSharding(mesh, P(*spec)))
    x = put((4, 2048, 4096), "fsdp")
    router = put((4096, 8))
    w_in = put((8, 4096, 14336), "expert", None, "tensor")
    w_out = put((8, 14336, 4096), "expert", "tensor", None)

    def fwd_bwd(x, router, w_gate, w_up, w_down):
        def loss(*a):
            out, aux = moe_ffn(*a, num_experts_per_tok=2,
                               dispatch="gmm_ep", mesh=mesh,
                               ep_buffer_factor=2.0)
            return out.astype(jnp.float32).sum() + aux
        return jax.grad(loss, (0, 2, 3, 4))(x, router, w_gate, w_up,
                                            w_down)

    compiled = compiled_with_kernel(fwd_bwd, x, router, w_in, w_in, w_out)
    assert "all-to-all" in compiled.as_text()
    assert device_bytes(compiled) < HBM_BYTES


def serve_params(cfg, one_chip):
    return on(jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg)), one_chip)


ENGINE_KW = dict(max_slots=4, max_seq_len=1024, prefill_chunk=64)


def test_slot_engine_steps_llama3_8b_widths(one_chip):
    from metaflow_tpu.serving import SlotEngine

    cfg = smoke_cfg()
    params = serve_params(cfg, one_chip)
    engine = SlotEngine(params, cfg, **ENGINE_KW)
    cache = on(engine._cache, one_chip)
    i32 = lambda *shape: sds(shape, jnp.int32, one_chip)
    decode = engine._decode_greedy_fn.lower(
        params, cache, i32(4), i32(4), sds((4,), jnp.bool_, one_chip)
    ).compile()
    prefill = engine._prefill_fn.lower(
        params, cache, i32(1, 64), i32(), i32()).compile()
    assert max(device_bytes(decode), device_bytes(prefill)) < HBM_BYTES


@pytest.mark.parametrize("family", ["llama", "jamba"])
def test_no_prefill_shape_holds_a_second_pool(one_chip, family):
    """Every shape of the one prefill program an iteration, rows of
    several slots among them, reads and writes the pools in place: its
    temporaries stay far under the smallest pool. (Two slots' chunks read
    out of the whole pool inside the chunk loop made the chip's compiler
    lay the K and V pools out anew in every layer, and a convolution
    tail written one row at a time made it copy that pool in and out:
    3.4e9 and 1.4e8 B of temporaries at the benchmark's sizes, PR 30.)"""
    from metaflow_tpu.models import jamba
    from metaflow_tpu.serving import SlotEngine

    if family == "llama":
        cfg, mod = smoke_cfg(), llama
    else:   # eight layers: one attention layer among seven Mamba layers
        cfg, mod = jamba.JambaConfig.jamba2_3b(n_layers=8), jamba
    params = on(jax.eval_shape(
        lambda: mod.init_params(jax.random.PRNGKey(0), cfg)), one_chip)
    engine = SlotEngine(params, cfg, max_slots=32, max_seq_len=1024,
                        prefill_chunk=64)
    cache = on(engine._cache, one_chip)
    pool = min(math.prod(a.shape) * a.dtype.itemsize
               for a in jax.tree.leaves(cache))
    i32 = lambda *shape: sds(shape, jnp.int32, one_chip)
    shapes = engine.prefill_shapes(2 * 64)
    assert shapes == [(1, 64), (1, 128), (2, 64)]
    for rows, width in shapes:
        compiled = engine._prefill_fn.lower(
            params, cache, i32(rows, width), i32(rows), i32(rows),
            i32(rows)).compile()
        temporaries = compiled.memory_analysis().temp_size_in_bytes
        assert temporaries < pool / 4, (rows, width, temporaries, pool)


def test_brumby_programs_hold_no_second_state_pool(one_chip):
    """The benchmark's Brumby configuration (8 layers at published
    widths, 20 slots: a float32 state pool of 5.5e9 B, no KV pool): the
    decode step holds the Pallas kernel that updates the decoding lanes'
    state in place, and neither it nor a prefill program, which reads and
    writes its rows' state in the pool by index, holds as much as ONE
    layer of that pool among its temporaries; the whole program stays
    under the chip's 16.0e9 B. Of the three prefill shapes the widest row
    and the two rows are compiled here ([1, 64] is [1, 128]'s program at
    half the width: `benchmark/describe_compile.py` compiles that one, and
    a compile of this model costs this file seven seconds of tier-1's
    limit)."""
    from metaflow_tpu.models import brumby
    from metaflow_tpu.serving import SlotEngine

    cfg = brumby.BrumbyConfig.brumby_14b(n_layers=8, max_seq_len=4096)
    params = on(jax.eval_shape(
        lambda: brumby.init_params(jax.random.PRNGKey(0), cfg)), one_chip)
    engine = SlotEngine(params, cfg, max_slots=20, max_seq_len=4096,
                        prefill_chunk=64)
    assert set(engine._cache) == {"ret_s", "ret_z"}
    cache = on(engine._cache, one_chip)
    layer = math.prod(cache["ret_s"].shape[1:]) * 4
    i32 = lambda *shape: sds(shape, jnp.int32, one_chip)
    decode = engine._decode_greedy_fn.lower(
        params, cache, i32(20), i32(20), sds((20,), jnp.bool_, one_chip)
    ).compile()
    assert "tpu_custom_call" in decode.as_text()
    programs = [decode] + [
        engine._prefill_fn.lower(params, cache, i32(rows, width), i32(rows),
                                 i32(rows), i32(rows)).compile()
        for rows, width in engine.prefill_shapes(2 * 64)[1:]]
    assert engine.prefill_shapes(2 * 64)[1:] == [(1, 128), (2, 64)]
    for compiled in programs:
        assert compiled.memory_analysis().temp_size_in_bytes < layer
        assert device_bytes(compiled) < HBM_BYTES


def test_phi4flash_programs_at_the_benchmarks_size(one_chip):
    """The benchmark's Phi-4-mini-flash configuration whole (32 layers at
    published widths, 200,064 rows, 64 slots x 4,096 positions): window
    pools 640 positions deep, one global K and V layer. The prefill
    shapes (the widest row and the two rows; [1, 64] is [1, 128]'s
    program at half the width) hold no pool among their temporaries. The
    decode step's attention is the kernel that reads the pools as stored
    (ops/decode_attention.py), so its temporaries hold nothing of the
    size of ONE layer of a K or V pool, global or ring (until PR 35 the
    chunk loop made the chip's compiler lay the global pool out anew
    once a step: 1.349e9 B); no state pool is copied, and the whole
    program stays under 12.5e9 B."""
    from metaflow_tpu.models import phi4flash
    from metaflow_tpu.serving import SlotEngine

    cfg = phi4flash.Phi4FlashConfig.phi4_mini_flash(max_seq_len=4096)
    params = on(jax.eval_shape(
        lambda: phi4flash.init_params(jax.random.PRNGKey(0), cfg)), one_chip)
    engine = SlotEngine(params, cfg, max_slots=64, max_seq_len=4096,
                        prefill_chunk=64)
    cache = on(jax.eval_shape(lambda: engine._cache), one_chip)
    nbytes = {name: math.prod(a.shape) * a.dtype.itemsize
              for name, a in cache.items()}
    assert cache["win_k"].shape == (8, 64, 640, 1280)
    assert cache["k"].shape == cache["v"].shape == (1, 64, 4096, 1280)
    i32 = lambda *shape: sds(shape, jnp.int32, one_chip)
    decode = engine._decode_greedy_fn.lower(
        params, cache, i32(64), i32(64), sds((64,), jnp.bool_, one_chip)
    ).compile()
    assert "tpu_custom_call" in decode.as_text()
    temporaries = decode.memory_analysis().temp_size_in_bytes
    assert temporaries < min(nbytes["k"], nbytes["win_k"] / 8,
                             nbytes["ssm"]) / 4, temporaries
    assert device_bytes(decode) < 12.5e9
    assert engine.prefill_shapes(2 * 64)[1:] == [(1, 128), (2, 64)]
    for rows, width in engine.prefill_shapes(2 * 64)[1:]:
        compiled = engine._prefill_fn.lower(
            params, cache, i32(rows, width), i32(rows), i32(rows),
            i32(rows)).compile()
        assert compiled.memory_analysis().temp_size_in_bytes \
            < nbytes["k"] / 16, (rows, width)
        assert device_bytes(compiled) < 12.5e9


def cell_engine(cell, one_chip, **cut):
    """(engine, the decode step's abstract arguments) of a serving cell
    at the benchmark's widths, slots and depth, two layers deep (the
    loop's body is traced once whatever the depth); `cut`: the other
    keys of the configuration that say which two."""
    from benchmark import configs, weights
    from metaflow_tpu.serving import SlotEngine

    _, _, config, _ = configs.load_cell(cell)
    config.update(cut)
    config["num_hidden_layers"], serving = 2, config["serving"]
    _, cfg = configs.program_config(config, serving["max_seq_len"])
    params = on(jax.eval_shape(lambda: weights.init_params(
        jax.random.PRNGKey(0), configs.dims(config))), one_chip)
    B = serving["slots"]
    engine = SlotEngine(params, cfg, max_slots=B,
                        max_seq_len=serving["max_seq_len"],
                        prefill_chunk=serving["prefill_chunk"])
    cache = on(jax.eval_shape(lambda: engine._cache), one_chip)
    i32 = lambda *shape: sds(shape, jnp.int32, one_chip)
    return engine, (params, cache, i32(B), i32(B),
                    sds((B,), jnp.bool_, one_chip))


CELLS_THAT_MERGE = ["mistral-7b.chat-steady", "mixtral-8x7b.batch-offline"]


@pytest.mark.parametrize("cell", CELLS_THAT_MERGE)
def test_decode_step_reads_its_pool_as_stored(one_chip, cell):
    """The chat and batch cells' decode steps: attention is the kernel,
    and no temporary is of the size of one layer of the K pool, whose
    chunks the loop it replaces sliced and copied."""
    engine, args = cell_engine(cell, one_chip)
    assert args[1]["k"].shape == (2, engine.max_slots, 1280, 1024)
    decode = engine._decode_greedy_fn.lower(*args).compile()
    assert "tpu_custom_call" in decode.as_text()
    layer = math.prod(args[1]["k"].shape[1:]) * 2
    assert decode.memory_analysis().temp_size_in_bytes < layer / 16


@pytest.mark.parametrize("cell", CELLS_THAT_MERGE)
def test_merged_step_holds_no_second_pool_and_holds_the_kernel(one_chip,
                                                               cell):
    """The same two cells' decode step with a prefill program's rows
    riding in it (the widest row and the two rows; [1, 64] is [1, 128]'s
    program at half the width, and `benchmark/describe_compile.py` has no
    part in it: PERF.md section 4 has all three), `ends` among the rows'
    arrays and the tokens of the rows that end put at their lanes (PR
    45): the lanes' attention is
    still the kernel that reads the pool as stored, and the rows' writes,
    views and chunk loop beside it make the compiler copy no pool (the
    expert layer's buffers at 192 tokens are the batch cell's 47e6 B;
    one layer of its K pool is 168e6)."""
    engine, args = cell_engine(cell, one_chip)
    assert engine.merges
    i32 = lambda *shape: sds(shape, jnp.int32, one_chip)
    layer = math.prod(args[1]["k"].shape[1:]) * 2
    shapes = engine.prefill_shapes(2 * engine.prefill_chunk)
    assert shapes == [(1, 64), (1, 128), (2, 64)]
    for rows, width in shapes[1:]:
        merged = engine._decode_greedy_fn.lower(*args, {
            "tokens": i32(rows, width), "slots": i32(rows),
            "start": i32(rows), "n_real": i32(rows),
            "ends": sds((rows,), jnp.bool_, one_chip)}).compile()
        text = merged.as_text()
        assert "tpu_custom_call" in text and "pool_attention" in text
        assert merged.memory_analysis().temp_size_in_bytes < layer / 2, \
            (rows, width)
        assert device_bytes(merged) < HBM_BYTES


# cell: (the scope and name of its state update's kernel, the pool's
# shape, what cuts the configuration to two recurrent layers)
CELLS_WITH_STATE = {
    "jamba2-3b.reason-steady": (
        "ssm_state_update", (2, 128, 16, 5120), {}),
    "nemotron-3-super.reason-steady": (
        "ssd_state_update", (2, 128, 128, 64, 128),
        {"hybrid_override_pattern": "MM"}),
}


@pytest.mark.parametrize("cell", sorted(CELLS_WITH_STATE))
def test_decode_step_updates_its_state_pool_in_place(one_chip, cell):
    """The reason and super cells' decode steps at the benchmark's widths
    and 128 slots: the one-token update of a recurrent layer is the
    Pallas call that reads and writes the decoding lanes' state where it
    lies (ops/ssm.py), named after its scope, and no temporary is of the
    size of ONE layer of the `ssm` pool, which the plain update cut out
    for every lane, selected and put back (0.55e9 B a layer in the super
    cell, 0.042e9 in the reason cell)."""
    name, shape, cut = CELLS_WITH_STATE[cell]
    engine, args = cell_engine(cell, one_chip, **cut)
    assert args[1]["ssm"].shape == shape
    assert engine.state_updates()["ssm"] == "loop"   # this process: a CPU
    decode = engine._decode_greedy_fn.lower(*args).compile()
    calls = [line for line in decode.as_text().splitlines()
             if "tpu_custom_call" in line and name in line]
    assert calls, name
    layer = math.prod(shape[1:]) * 4
    assert decode.memory_analysis().temp_size_in_bytes < layer / 2
    assert device_bytes(decode) < HBM_BYTES


def test_first_token_program_leaves_its_tokens_at_the_lanes(one_chip):
    """The first-token program of a stack whose rows take a program of
    their own (the Jamba cell at the benchmark's widths and slots, two
    layers deep): it samples the rows' tokens AND puts those of the rows
    that end at their lanes among the device's last tokens, so that the
    next decode step is launched with nothing fetched in between (PR 45).
    Its temporaries are the sampler's over two rows of logits, nothing of
    the size of a pool, and the lanes' tokens come out as they went in,
    one int32 a slot."""
    from benchmark import configs, weights
    from metaflow_tpu.serving import SlotEngine

    _, _, config, _ = configs.load_cell("jamba2-3b.reason-steady")
    config["num_hidden_layers"], serving = 2, config["serving"]
    _, cfg = configs.program_config(config, serving["max_seq_len"])
    params = jax.eval_shape(lambda: weights.init_params(
        jax.random.PRNGKey(0), configs.dims(config)))
    B = serving["slots"]
    engine = SlotEngine(params, cfg, max_slots=B,
                        max_seq_len=serving["max_seq_len"],
                        prefill_chunk=serving["prefill_chunk"])
    assert not engine.merges and engine.recurrent
    pool = min(math.prod(a.shape) * a.dtype.itemsize
               for a in jax.tree.leaves(jax.eval_shape(
                   lambda: engine._cache)) if a.ndim > 1)
    i32 = lambda *shape: sds(shape, jnp.int32, one_chip)
    f32 = lambda *shape: sds(shape, jnp.float32, one_chip)
    for rows, width in engine.prefill_shapes(2 * engine.prefill_chunk)[1:]:
        args = (f32(rows, width, cfg.vocab_size), i32(rows),
                sds((rows, 2), jnp.uint32, one_chip), f32(rows), i32(rows),
                f32(rows), i32(B), i32(rows),
                sds((rows,), jnp.bool_, one_chip))
        first = engine._first_fn.lower(*args).compile()
        assert [o.shape for o in jax.eval_shape(engine._first_fn, *args)] \
            == [(rows,), (B,)]
        assert first.memory_analysis().temp_size_in_bytes < pool / 4
        assert device_bytes(first) < 16 * rows * width * cfg.vocab_size


def test_a_looped_stack_carries_its_pool_through_every_pass(one_chip):
    """The benchmark's Ouro configuration at its widths, slots and depth,
    two layers deep and its four passes (the loop over the passes and the
    loop over the layers are each traced once whatever they count): the
    pool has passes x layers indices; the decode step, alone and with two
    rows riding in it, reads it through the kernel as stored and writes
    it in place in every pass: beside the compiler's relaid copies of the
    `wq` and `wk` stacks (whole, once a program: PERF.md section 7) the
    temporaries hold nothing of the size of ONE index of the K pool."""
    from benchmark import configs, weights
    from metaflow_tpu.serving import SlotEngine

    _, _, config, _ = configs.load_cell("ouro-2.6b.chat-short")
    config["num_hidden_layers"], serving = 2, config["serving"]
    _, cfg = configs.program_config(config, serving["max_seq_len"])
    params = on(jax.eval_shape(lambda: weights.init_params(
        jax.random.PRNGKey(0), configs.dims(config))), one_chip)
    B = serving["slots"]
    engine = SlotEngine(params, cfg, max_slots=B,
                        max_seq_len=serving["max_seq_len"],
                        prefill_chunk=serving["prefill_chunk"])
    assert engine.merges and engine.passes == 4
    cache = on(jax.eval_shape(lambda: engine._cache), one_chip)
    assert cache["k"].shape == (4 * 2, B, serving["max_seq_len"], 16 * 128)
    index = math.prod(cache["k"].shape[1:]) * 2
    relaid = 2 * math.prod(params["layers"]["wq"].shape) * 2
    i32 = lambda *shape: sds(shape, jnp.int32, one_chip)
    args = (params, cache, i32(B), i32(B), sds((B,), jnp.bool_, one_chip))
    rows = {"tokens": i32(2, 64), "slots": i32(2), "start": i32(2),
            "n_real": i32(2), "ends": sds((2,), jnp.bool_, one_chip)}
    for extra in ((), (rows,)):
        step = engine._decode_greedy_fn.lower(*args, *extra).compile()
        text = step.as_text()
        assert "tpu_custom_call" in text and "pool_attention" in text
        assert step.memory_analysis().temp_size_in_bytes \
            < relaid + index / 8, extra
        assert device_bytes(step) < HBM_BYTES


def test_paged_engine_steps_llama3_8b_widths(one_chip):
    from metaflow_tpu.serving import PagedEngine

    cfg = smoke_cfg()
    params = serve_params(cfg, one_chip)
    engine = PagedEngine(params, cfg, spec_k=0, **ENGINE_KW)
    pool = on(engine.pool.kv, one_chip)
    i32 = lambda *shape: sds(shape, jnp.int32, one_chip)
    decode = engine._decode_greedy_fn.lower(
        params, pool, i32(4), i32(4), sds((4,), jnp.bool_, one_chip),
        i32(4, engine.n_blocks)).compile()
    prefill = engine._prefill_fn.lower(
        params, pool, i32(1, 64), i32(engine.n_blocks), i32()).compile()
    assert max(device_bytes(decode), device_bytes(prefill)) < HBM_BYTES
