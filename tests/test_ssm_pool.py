"""The one-token update of a recurrent layer's state in place in the
pool (ops/ssm.py, `ssd_update_pool` and `selective_update_pool`): the
two Pallas kernels, interpreted on XLA:CPU, against `ssd_step` and
`selective_step` on one layer of a pool of several; the slot engine's
decode step with the kernel in place of the plain update; and what the
engine says of the path a decode step takes."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import configs, weights
from metaflow_tpu.inference.decode import state_updates
from metaflow_tpu.models import brumby, jamba, llama
from metaflow_tpu.ops import ssm
from metaflow_tpu.ops.decode_attention import live_lanes
from metaflow_tpu.serving import Request, Scheduler, SlotEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS, LANES, LAYER = 3, 6, 1
MASKS = {"some": [1, 0, 1, 1, 0, 0], "every": [1] * LANES,
         "none": [0] * LANES}


def draw(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype)


def mamba2_case(keys):
    """(kernel, plain step, pool, the step's operands) at 16 heads of 16
    channels in 4 groups, 128 columns."""
    H, P, N, G = 16, 16, 128, 4
    return ssm._ssd_pool_kernel, ssm.ssd_step, \
        draw(keys[0], (LAYERS, LANES, H, P, N)), (
            draw(keys[1], (LANES, H, P), jnp.bfloat16),
            jax.nn.softplus(draw(keys[2], (LANES, H))),
            -jnp.exp(draw(keys[3], (H,))),
            draw(keys[4], (LANES, G, N), jnp.bfloat16),
            draw(keys[5], (LANES, G, N), jnp.bfloat16),
            1.5 + draw(keys[6], (H,)))


def mamba1_case(keys):
    """The same of Mamba-1 at 16 columns and 256 channels."""
    N, Di = 16, 256
    return ssm._selective_pool_kernel, ssm.selective_step, \
        draw(keys[0], (LAYERS, LANES, N, Di)), (
            draw(keys[1], (LANES, Di), jnp.bfloat16),
            jax.nn.softplus(draw(keys[2], (LANES, Di))),
            -jnp.exp(draw(keys[3], (N, Di))),
            draw(keys[4], (LANES, N), jnp.bfloat16),
            draw(keys[5], (LANES, N), jnp.bfloat16),
            1.5 + draw(keys[6], (Di,)))


@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("case", [mamba2_case, mamba1_case],
                         ids=["mamba2", "mamba1"])
def test_the_kernel_updates_the_decoding_lanes_alone(case, mask):
    """y and the new state of the lanes that decode are the plain
    step's to float32 tolerance; the lanes that do not decode and every
    other layer of the pool are bit for bit what they were."""
    kernel, step, pool, operands = case(
        jax.random.split(jax.random.PRNGKey(7), 7))
    valid = jnp.asarray(MASKS[mask], bool)
    lanes = live_lanes(valid) + (valid,)
    y, new = jax.jit(lambda pool: kernel(
        pool, jnp.int32(LAYER), *operands, *lanes, interpret=True))(pool)
    want_y, want = step(pool[LAYER], *operands, valid)
    live = np.asarray(valid)
    # y is a sum of 128 (16) float32 products of order 1 to 10, taken in
    # another order: a few of float32's 6e-8 times their sum of 200
    assert np.allclose(np.asarray(y)[live], np.asarray(want_y)[live],
                       rtol=1e-5, atol=5e-5)
    assert np.allclose(np.asarray(new[LAYER])[live], np.asarray(want)[live],
                       rtol=1e-6, atol=1e-6)
    assert np.array_equal(np.asarray(new[LAYER])[~live],
                          np.asarray(pool[LAYER])[~live])
    for other in set(range(LAYERS)) - {LAYER}:
        assert np.array_equal(new[other], pool[other])


@pytest.mark.parametrize("update", [ssm.ssd_update_pool,
                                    ssm.selective_update_pool],
                         ids=["mamba2", "mamba1"])
def test_off_the_chip_the_pool_form_is_the_plain_step(update):
    """On XLA:CPU (and at sizes that are no whole tiles anywhere)
    `*_update_pool` is the plain step on the layer cut out and put
    back."""
    case = mamba2_case if update is ssm.ssd_update_pool else mamba1_case
    _, step, pool, operands = case(jax.random.split(jax.random.PRNGKey(3), 7))
    valid = jnp.asarray(MASKS["some"], bool)
    y, new = jax.jit(lambda pool: update(
        pool, LAYER, *operands, live_lanes(valid) + (valid,)))(pool)
    want_y, want = jax.jit(step)(pool[LAYER], *operands, valid)
    live = np.asarray(valid)
    assert np.allclose(y, want_y, rtol=1e-5, atol=1e-5)
    assert np.allclose(new[LAYER], want, rtol=1e-6, atol=1e-6)
    assert np.array_equal(np.asarray(new[LAYER])[~live],
                          np.asarray(pool[LAYER])[~live])
    assert np.array_equal(new[0], pool[0])


# ---- the kernels inside the engine's decode program ----

def prompt(n, salt=0):
    return ((np.arange(n) * 37 + 11 + 5 * salt) % 255 + 1).astype(np.int32)


def tiny_jamba():
    cfg = jamba.JambaConfig.tiny()
    return cfg, jamba.init_params(jax.random.PRNGKey(0), cfg)


def tiny_nemotron():
    config = dict(configs.read_json(os.path.join(
        ROOT, "benchmark", "tests", "cells", "configs",
        "tiny-nemotron-h.json")), torch_dtype="float32")
    cfg = configs.program_config(config, 128)[1]
    return cfg, jax.jit(lambda k: weights.init_params(
        k, configs.dims(config)))(weights.seed_key(5))


@pytest.mark.parametrize("model, plain, kernel", [
    (tiny_jamba, "_selective_pool_xla", ssm._selective_pool_kernel),
    (tiny_nemotron, "_ssd_pool_xla", ssm._ssd_pool_kernel),
], ids=["jamba", "nemotron_h"])
def test_decode_steps_with_the_kernel_are_the_plain_ones(model, plain,
                                                         kernel, monkeypatch):
    """The engine's decode program with the Pallas kernel (interpreted;
    on a TPU `*_update_pool` picks it by the lowering platform) in place
    of the plain update: two requests, one admitted while the other
    decodes, so that steps run with masked lanes; the same tokens, the
    same pools to float32 tolerance, and a lane that never decoded bit
    for bit."""
    cfg, params = model()

    def run():
        eng = SlotEngine(params, cfg, max_slots=3, max_seq_len=128,
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
    calls = []

    def interpreted(*args):
        calls.append(1)
        return kernel(*args, interpret=True)

    monkeypatch.setattr(ssm, plain, interpreted)
    got, got_cache = run()
    assert calls   # the decode step took the pool's form
    assert got == want
    for name in ("ssm", "conv"):
        assert np.allclose(got_cache[name], want_cache[name], atol=1e-5)
        assert np.array_equal(got_cache[name][:, 1], want_cache[name][:, 1])


def test_the_engine_says_how_a_decode_step_updates_each_state_pool():
    """`state_updates`: by the shapes and the platform. The benchmark's
    widths are whole tiles; off the chip, with `kernel=False` (a mesh)
    and at a width that is no whole tiles every pool is the loop's; the
    small pools beside the state always are. The scheduler hands it
    on."""
    cfg, params = tiny_jamba()
    eng = SlotEngine(params, cfg, max_slots=2, max_seq_len=64,
                     prefill_chunk=16)
    assert eng.state_updates() == {"conv": "loop", "ssm": "loop"}
    sched = Scheduler(eng).start()
    sched.submit(Request(prompt(5).tolist(), max_new_tokens=3)).result(
        timeout=120)
    assert sched.stats()["state_updates"] == eng.state_updates()
    sched.stop()
    real = {"conv": jax.ShapeDtypeStruct((6, 128, 3, 5120), jnp.bfloat16),
            "ssm": jax.ShapeDtypeStruct((6, 128, 16, 5120), jnp.float32),
            "k": None, "v": None}
    assert state_updates(cfg, real) == {"conv": "loop", "ssm": "kernel"}
    assert state_updates(cfg, real, kernel=False) \
        == {"conv": "loop", "ssm": "loop"}
    ragged = jax.ShapeDtypeStruct((6, 128, 16, 5000), jnp.float32)
    assert state_updates(cfg, dict(real, ssm=ragged)) \
        == {"conv": "loop", "ssm": "loop"}
    assert state_updates(llama.LlamaConfig.tiny(), {}) == {}
    # retention's pools: the benchmark's Brumby state, and a ragged one
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    cfg = brumby.BrumbyConfig.tiny()
    assert state_updates(cfg, {"ret_s": f32(8, 20, 8, 128, 8320),
                               "ret_z": f32(8, 20, 8, 8320)}) \
        == {"ret_s": "kernel", "ret_z": "loop"}
    assert state_updates(cfg, {"ret_s": f32(2, 3, 2, 16, 136),
                               "ret_z": f32(2, 3, 2, 136)}) \
        == {"ret_s": "loop", "ret_z": "loop"}
