"""Brumby (power-retention layers alone) through the slot engine: what no
other family has. The cache holds a state pool and NO K and V; a slot
costs the same at every position and `max_seq_len` bounds rope alone; the
q and k head norms; the one-token update as the Pallas kernel
(interpreted) inside the engine's decode program; the pool's bytes in
`stats()` and `/metrics`; the published configuration. The cases every
recurrent family shares (parity with benchmark/families/brumby.py through
`SlotEngine` and `Scheduler`, padded chunks, masked lanes, a new
occupant, rows of several slots, the refusals by name) are
tests/test_jamba.py's, which runs each for both families. Tiny sizes,
float32."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import configs, reference
from benchmark.families import brumby as ref_family
from metaflow_tpu import goodput
from metaflow_tpu.cmd.serve import build_config, build_engine
from metaflow_tpu.inference import decode_forward, generate, init_kv_cache
from metaflow_tpu.inference.cache import cache_pools
from metaflow_tpu.inference.decode import family_config_class
from metaflow_tpu.models import brumby
from metaflow_tpu.ops import retention
from metaflow_tpu.serving import Request, Scheduler, SlotEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = brumby.BrumbyConfig.tiny()   # hidden 64, 3 layers, 4 / 2 heads of 16
DIMS = dict(configs.dims(dict(configs.read_json(os.path.join(
    ROOT, "benchmark", "tests", "cells", "configs", "tiny-brumby.json")),
    torch_dtype="float32")))
PUBLISHED = configs.read_json(os.path.join(
    ROOT, "benchmark", "configs", "brumby-14b-serve.json"))


def prompt(n, salt=0):
    return ((np.arange(n) * 37 + 11 + 5 * salt) % 255 + 1).astype(np.int32)


@pytest.fixture(scope="module")
def params():
    return brumby.init_params(jax.random.PRNGKey(0), CFG)


def serve(eng, slot, p, n=8):
    eng.admit(slot, p, n)
    first = None
    while first is None:
        _, first = eng.prefill_step(slot)
    out = [first]
    while len(out) < n:
        out.append(eng.decode_step()[slot])
    eng.release(slot)
    return out


# ---- a state pool and no KV pool ----

def test_the_cache_is_the_state_pool_alone(params):
    eng = SlotEngine(params, CFG, max_slots=3, max_seq_len=128,
                     prefill_chunk=16)
    D = retention.state_dim(CFG.head_dim)
    assert {k: v.shape for k, v in eng._cache.items()} == {
        "ret_s": (3, 3, 2, 16, D), "ret_z": (3, 3, 2, D)}
    assert all(v.dtype == jnp.float32 for v in eng._cache.values())
    assert all(pool.recurrent and not pool.view
               for pool, _ in cache_pools(CFG).values())
    per_slot = 3 * 2 * (16 + 1) * D * 4
    assert eng.state_pool_stats() == {"bytes": 3 * per_slot,
                                      "bytes_per_slot": per_slot}


def test_a_kv_only_engine_reports_no_state_pool():
    from metaflow_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    eng = SlotEngine(llama.init_params(jax.random.PRNGKey(0), cfg), cfg,
                     max_slots=2, max_seq_len=64, prefill_chunk=16)
    assert eng.state_pool_stats() == {"bytes": 0, "bytes_per_slot": 0}


def test_a_new_occupant_starts_from_an_empty_state(params):
    eng = SlotEngine(params, CFG, max_slots=2, max_seq_len=128,
                     prefill_chunk=16)
    first = serve(eng, 1, prompt(37))
    assert float(jnp.abs(eng._cache["ret_s"][:, 1]).max()) > 0
    eng.admit(1, prompt(5), 8)   # zeroed at admission, before any prefill
    for name in ("ret_s", "ret_z"):
        assert not np.asarray(eng._cache[name][:, 1]).any()
        assert not np.asarray(eng._cache[name][:, 0]).any()
    eng.release(1)
    assert serve(eng, 1, prompt(37)) == first


def test_max_seq_len_bounds_rope_alone(params):
    """No pool is as deep as `max_seq_len`: two engines that differ in it
    alone hold pools of one size and serve the same tokens; past the
    config's positions, where rope's table ends, both entry points
    refuse."""
    short = SlotEngine(params, CFG, max_slots=1, max_seq_len=64,
                       prefill_chunk=16)
    long = SlotEngine(params, CFG, max_slots=1, max_seq_len=256,
                      prefill_chunk=16)
    assert short.state_pool_stats() == long.state_pool_stats()
    assert serve(short, 0, prompt(37)) == serve(long, 0, prompt(37))
    assert long.fits(200, 56) and not long.fits(200, 57)
    assert long.max_context_tokens() == 256
    with pytest.raises(ValueError, match="rope's table"):
        SlotEngine(params, CFG, max_slots=1, max_seq_len=512)
    with pytest.raises(ValueError, match="rope's table"):
        generate(params, jnp.asarray(prompt(250))[None], CFG, 8)


def test_q_and_k_head_norms_are_applied(params):
    tokens = jnp.asarray(prompt(24))[None]
    forward = jax.jit(lambda p: brumby.forward(p, tokens, CFG))
    base = forward(params)
    # an uneven weight: a uniform one scales every weight of a position
    # alike and cancels in the normalised sum
    scaled = dict(params, layers=dict(
        params["layers"], q_norm=params["layers"]["q_norm"]
        * jnp.linspace(0.25, 4.0, CFG.head_dim)))
    assert float(jnp.abs(forward(scaled) - base).max()) > 1e-3
    run = jax.jit(lambda p: decode_forward(
        p, tokens, init_kv_cache(CFG, 1, 64), 0, CFG)[0])
    assert np.allclose(run(scaled), forward(scaled), atol=2e-3)
    assert float(jnp.abs(run(scaled) - run(params)).max()) > 1e-3


def test_the_seeded_gates_remember(params):
    g = jax.nn.sigmoid(params["layers"]["bg"])
    tau = -1.0 / jnp.log(g)
    assert float(tau.min()) >= 15.9 and float(tau.max()) <= 4100
    again = ref_family.gate_bias_init(jax.random.PRNGKey(3), (64,))
    assert float(jax.nn.sigmoid(again).min()) > 0.93


# ---- the kernel inside the engine's decode program ----

def test_decode_steps_with_the_kernel_are_the_plain_ones(params, monkeypatch):
    """The engine's decode program with the Pallas kernel (interpreted;
    on a TPU `update_pool` picks it by the lowering platform) in place of
    the plain update: two requests, one admitted while the other decodes,
    so that steps run with a masked lane; the same tokens and the same
    pools."""
    def run():
        eng = SlotEngine(params, CFG, max_slots=3, max_seq_len=128,
                         prefill_chunk=16)
        eng.admit(0, prompt(16, salt=1), 6)
        out = {0: [], 2: []}
        first = None
        while first is None:
            _, first = eng.prefill_step(0)
        out[0].append(first)
        out[0].append(eng.decode_step()[0])     # lanes 1 and 2 masked
        eng.admit(2, prompt(21, salt=2), 6)
        while not eng.decoding[2]:
            _, first = eng.prefill_step(2)
            out[0].append(eng.decode_step()[0])  # lane 2 mid-prefill
        out[2].append(first)
        for _ in range(3):
            for slot, tok in eng.decode_step().items():
                out[slot].append(tok)
        return out, jax.tree.map(np.asarray, eng._cache)

    want, want_cache = run()
    monkeypatch.setattr(
        retention, "_update_state_xla",
        functools.partial(retention._update_state_kernel, interpret=True))
    got, got_cache = run()
    assert got == want
    for name in want_cache:
        assert np.allclose(got_cache[name], want_cache[name], atol=1e-5)
        assert np.array_equal(got_cache[name][:, 1], want_cache[name][:, 1])


# ---- `tpuflow serve --model brumby` ----

def test_serve_builds_the_family_by_name(params):
    assert family_config_class("brumby") is brumby.BrumbyConfig
    fields = {f: getattr(CFG, f) for f in CFG.__dataclass_fields__}
    cfg = build_config({"cfg": fields}, model="brumby")
    assert cfg == CFG
    sched = Scheduler(build_engine(params, cfg, slots=2, max_seq_len=128,
                                   prefill_chunk=16)).start()
    reqs = [sched.submit(Request(prompt(n, salt=n).tolist(),
                                 max_new_tokens=6)) for n in (5, 37, 20)]
    got = [r.result(timeout=120) for r in reqs]
    stats = sched.stats()
    sched.stop()
    # the pool's two counters, in /v1/stats and on /metrics
    pool = sched.engine.state_pool_stats()
    assert stats["state_pool"] == pool and pool["bytes_per_slot"] > 0
    families = {f.name: f for f in goodput.scheduler_metric_families(stats)}
    assert families["tpuflow_serve_state_pool_bytes"].samples[0][2] \
        == pool["bytes"]
    assert families["tpuflow_serve_state_pool_bytes_per_slot"].samples[0][2] \
        == pool["bytes_per_slot"]
    assert all(r.reason == "length" for r in reqs)
    for r, out in zip(reqs, got):
        gaps = reference.served_gaps(params, r.tokens, out, DIMS, pad_to=64)
        # float32 on both sides: a served token is the reference's best
        assert float(gaps.max()) <= 1e-4


# ---- the published configuration ----

def test_the_published_configuration_is_uncut_but_for_depth():
    assert {k: PUBLISHED[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "intermediate_size", "vocab_size",
        "max_position_embeddings")} == {
            "hidden_size": 5120, "num_attention_heads": 40,
            "num_key_value_heads": 8, "head_dim": 128,
            "intermediate_size": 17408, "vocab_size": 151936,
            "max_position_embeddings": 32768}
    assert PUBLISHED["reduced"] == {"num_hidden_layers": "40 -> 8"}
    assert len(PUBLISHED["assumed"]) >= 7
    dims = configs.dims(PUBLISHED)
    module, cfg = configs.program_config(PUBLISHED, 4096)
    assert module is brumby and cfg == brumby.BrumbyConfig(
        n_layers=8, max_seq_len=4096)
    n = sum(int(np.prod(s)) for s, _ in brumby.leaf_shapes(cfg).values())
    assert round(n / 1e9, 3) == 4.199
    assert ref_family.matmul_params(dims) < n
    specs = ref_family.leaf_specs(dims)
    assert {path: shape for path, (shape, _) in specs.items()} == {
        path: shape for path, (shape, _) in brumby.leaf_shapes(cfg).items()}
    # 34.08 MB a layer and slot by the products a state needs, 34.35 MB as
    # the program lays them out; 20 slots at 8 layers
    assert 8 * dims["state_dim"] * 129 * 4 == 34_080_768
    pool = sum(int(np.prod((layers, 20) + p.shape(cfg, 4096))) * 4
               for p, layers in cache_pools(cfg).values())
    assert pool == 20 * 8 * 8 * 8320 * 129 * 4 and 5.4e9 < pool < 5.6e9
