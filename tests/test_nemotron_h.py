"""Nemotron-H through the slot engine: Mamba-2 layers (ops/ssm.py's
one-token update and chunk form), latent expert layers under a sigmoid
router with a selection bias of which this chip holds a share
(ops/moe.py: `route`, `held`, `exact`), attention layers without a
feed-forward, each layer one of the three. Parity with the family's
plain float32 reference (benchmark/families/nemotron_h.py) on seeded
weights, the share tied to the whole layer, Mixtral's expert layer
bit for bit what it was, and the refusals a recurrent model gets. Tiny
sizes, float32 unless said, no subprocess."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import configs, reference, weights
from benchmark.families import nemotron_h as ref_family
from metaflow_tpu.cmd.serve import build_config, build_engine, \
    build_prefix_cache
from metaflow_tpu.exception import TpuFlowException
from metaflow_tpu.inference import decode_forward, init_kv_cache
from metaflow_tpu.inference.cache import MOE_PAIRS, is_recurrent, \
    layer_kinds, recurrent_pools
from metaflow_tpu.inference.decode import family, merges
from metaflow_tpu.models import jamba, mixtral, nemotron_h
from metaflow_tpu.ops import moe, ssm
from metaflow_tpu.serving import PagedEngine, RadixPrefixCache, Request, \
    Scheduler, SlotEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# cells/configs/tiny-nemotron-h.json: MEM*EME, 8 routed experts of which
# experts 2-5 are held, top 3
CONFIG = dict(configs.read_json(os.path.join(
    ROOT, "benchmark", "tests", "cells", "configs",
    "tiny-nemotron-h.json")), torch_dtype="float32")
DIMS = configs.dims(CONFIG)
CFG = configs.program_config(CONFIG, 128)[1]


def prompt(n, salt=0):
    return ((np.arange(n) * 37 + 11 + 5 * salt) % 255 + 1).astype(np.int32)


@pytest.fixture(scope="module")
def params():
    """The benchmark's seeded weights: a selection bias that is not
    zero, decays that are neither 0 nor 1, a drawn convolution bias."""
    return jax.jit(lambda k: weights.init_params(k, DIMS))(
        weights.seed_key(5))


@pytest.fixture(scope="module")
def engine(params):
    """Three slots, chunks of 16; every test leaves its slots released."""
    return SlotEngine(params, CFG, max_slots=3, max_seq_len=128,
                      prefill_chunk=16)


def close(got, want, tol=1e-4):
    """Float32 on both sides: rounding only, 1e-4 of the largest entry."""
    return float(jnp.abs(got - want).max()) < tol * max(
        1.0, float(jnp.abs(want).max()))


def prefill(eng, slot):
    first = None
    while first is None:
        _, first = eng.prefill_step(slot)
    return first


# ---- ops/ssm.py: Mamba-2 ----

def _ssd_inputs(B=2, T=21, H=8, P=4, G=2, N=16, seed=0):
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)
    return dict(S=f(B, H, P, N), x=f(B, T, H, P),
                dt=jax.nn.softplus(f(B, T, H)), A=-jnp.exp(f(H)),
                Bm=f(B, T, G, N), Cm=f(B, T, G, N), D=f(H))


def _written_out(i, valid=None):
    """The recurrence of the docstring, a position and a head at a
    time, in numpy."""
    S = np.array(i["S"], np.float64)
    B_, T, H, P = i["x"].shape
    per = H // i["Bm"].shape[2]
    ys = np.zeros((B_, T, H, P))
    for b in range(B_):
        for t in range(T):
            if valid is not None and not valid[b, t]:
                continue
            for h in range(H):
                dt, g = float(i["dt"][b, t, h]), h // per
                S[b, h] = np.exp(dt * float(i["A"][h])) * S[b, h] + dt * \
                    np.outer(i["x"][b, t, h], i["Bm"][b, t, g])
                ys[b, t, h] = S[b, h] @ np.asarray(i["Cm"][b, t, g]) \
                    + float(i["D"][h]) * np.asarray(i["x"][b, t, h])
    return ys, S


@pytest.mark.parametrize("chunk", [128, 8, 5])
def test_one_token_update_chunk_form_and_plain_scan_agree(chunk):
    """A row in one chunk, in chunks that divide it, and in chunks that
    do not (the tail padded with positions that are not valid)."""
    i = _ssd_inputs()
    want_y, want_S = _written_out(i)
    S, ys = i["S"], []
    for t in range(i["x"].shape[1]):
        y, S = ssm.ssd_step(S, i["x"][:, t], i["dt"][:, t], i["A"],
                            i["Bm"][:, t], i["Cm"][:, t], i["D"])
        ys.append(y)
    assert close(jnp.stack(ys, 1), want_y) and close(S, want_S)
    y, S = ssm.ssd_chunk(i["S"], i["x"], i["dt"], i["A"], i["Bm"], i["Cm"],
                         i["D"], chunk=chunk)
    assert close(y, want_y) and close(S, want_S)


@pytest.mark.parametrize("n_valid", [(21, 9), (0, 21), (1, 0)])
def test_the_state_passes_through_what_is_not_valid(n_valid):
    i = _ssd_inputs(seed=1)
    valid = np.arange(21)[None] < np.asarray(n_valid)[:, None]
    want_y, want_S = _written_out(i, valid)
    y, S = ssm.ssd_chunk(i["S"], i["x"], i["dt"], i["A"], i["Bm"], i["Cm"],
                         i["D"], jnp.asarray(valid), chunk=8)
    assert close(S, want_S)
    assert close(jnp.where(valid[..., None, None], y, 0.0), want_y)
    # one token: a lane that is not valid holds its state
    _, S1 = ssm.ssd_step(i["S"], i["x"][:, 0], i["dt"][:, 0], i["A"],
                         i["Bm"][:, 0], i["Cm"][:, 0], i["D"],
                         jnp.asarray([True, False]))
    assert bool(jnp.array_equal(S1[1], i["S"][1]))
    assert not bool(jnp.array_equal(S1[0], i["S"][0]))


# ---- ops/moe.py: the router's forms, a share, two-matrix experts ----

def test_sigmoid_bias_routing_is_the_equations_and_the_bias_moves_picks():
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(2, 9, 16)), jnp.float32)
    w = jnp.asarray(r.normal(size=(16, 12)) / 4, jnp.float32)
    bias = jnp.asarray(r.normal(size=12) * 0.5, jnp.float32)
    weights_, idx = moe.route(x, w, 4, "sigmoid_bias", bias=bias, scale=2.5)
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64)
                              @ np.asarray(w, np.float64))))
    want_idx = np.argsort(-(s + np.asarray(bias)), -1)[..., :4]
    assert np.array_equal(np.sort(idx, -1), np.sort(want_idx, -1))
    picked = np.take_along_axis(s, np.asarray(idx), -1)
    assert close(weights_,
                 2.5 * picked / (picked.sum(-1, keepdims=True) + 1e-20))
    assert close(weights_.sum(-1), jnp.full((2, 9), 2.5))
    # the bias moves the choice and never the weights' source
    _, plain = moe.route(x, w, 4, "sigmoid_bias", scale=2.5)
    assert not np.array_equal(np.sort(plain, -1), np.sort(idx, -1))
    # today's form under its new name
    got = moe.route(x, w, 4, "softmax_top_k")
    logits = jnp.einsum("bse,en->bsn", x, w)
    want = moe.top_k_router(logits, 12, 4)
    assert all(bool(jnp.array_equal(a, b)) for a, b in zip(got, want))
    with pytest.raises(ValueError, match="router form"):
        moe.route(x, w, 4, "softmax_all")


def _uncut():
    """The tiny model's sizes with every routed expert held, and an
    expert layer's leaves at those sizes."""
    d = dict(DIMS, n_experts_held=DIMS["n_experts"], first_held_expert=0)
    specs = {path[1]: spec for path, spec in ref_family.leaf_specs(d).items()
             if path[0] == "moe_layers"}
    keys = jax.random.split(jax.random.PRNGKey(3), len(specs))
    lp = {}
    for key, (name, (shape, init)) in zip(keys, sorted(specs.items())):
        shape = shape[1:]   # one layer
        lp[name] = (jnp.ones(shape) if init is None
                    else init(key, shape) if callable(init)
                    else jax.random.normal(key, shape) * init ** -0.5)
    return d, lp


def test_four_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """Each of four chips holds two of the eight experts, routes over
    all eight and computes its own experts' part; every chip computes
    the shared expert alike. The parts sum to what the uncut reference
    gives for the whole layer, the shared expert counted once; and the
    reference given a share gives that share."""
    d, lp = _uncut()
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 13, d["dim"]))
    whole = jnp.stack([ref_family.moe_mixer(lp, row, d) for row in h])
    shared = moe.relu2(h @ lp["shared_up"]) @ lp["shared_down"]
    total, routed, held = shared, 0, 0
    for first in (0, 2, 4, 6):
        cfg = configs.program_config(dict(
            CONFIG, n_routed_experts=2, first_held_expert=first), 128)[1]
        share = dict(lp, w_up=lp["w_up"][first:first + 2],
                     w_down=lp["w_down"][first:first + 2])
        out, pairs = nemotron_h.latent_moe(cfg, share, h)
        total = total + (out - shared)
        routed, held = int(pairs[0]), held + int(pairs[1])
        mine = dict(d, n_experts_held=2, first_held_expert=first)
        assert close(out, jnp.stack(
            [ref_family.moe_mixer(share, row, mine) for row in h]))
    assert close(total, whole)
    # every pair fell on exactly one chip's experts
    assert routed == held == 2 * 13 * d["experts_per_tok"]


def test_tokens_that_are_not_valid_are_sent_nowhere_and_counted_nowhere():
    _, lp = _uncut()
    lp = dict(lp, w_up=lp["w_up"][2:6], w_down=lp["w_down"][2:6])
    h = jax.random.normal(jax.random.PRNGKey(6), (3, 5, DIMS["dim"]))
    valid = jnp.asarray(np.arange(5)[None] < np.asarray([5, 2, 0])[:, None])
    out, pairs = nemotron_h.latent_moe(CFG, lp, h, valid)
    full, every = nemotron_h.latent_moe(CFG, lp, h)
    assert close(jnp.where(valid[..., None], out, 0.0),
                 jnp.where(valid[..., None], full, 0.0))
    assert int(pairs[0]) == 7 * 3 and int(every[0]) == 15 * 3
    assert 0 < int(pairs[1]) < int(every[1]) < int(every[0])


def _sparse_before(tokens, weights_, idx, w_gate, w_up, w_down, N, k, C):
    """`_sparse_dispatch_ffn` as it stood before this file's PR, with
    its capacity C: what Mixtral's calls are held to, bit for bit."""
    T, E = tokens.shape
    e_flat, w_flat = idx.reshape(T * k), weights_.reshape(T * k)
    pos = jnp.cumsum(jax.nn.one_hot(e_flat, N, dtype=jnp.int32), axis=0) - 1
    pos_flat = jnp.take_along_axis(pos, e_flat[:, None], axis=1)[:, 0]
    keep = pos_flat < C
    safe_pos = jnp.where(keep, pos_flat, C)
    t_flat = jnp.arange(T * k) // k
    x_buf = jnp.zeros((N, C, E), tokens.dtype).at[e_flat, safe_pos].add(
        tokens[t_flat], mode="drop")
    f32 = dict(preferred_element_type=jnp.float32)
    gate = jax.nn.silu(jnp.einsum("nce,nef->ncf", x_buf, w_gate, **f32))
    up = jnp.einsum("nce,nef->ncf", x_buf, w_up, **f32)
    y_buf = jnp.einsum("ncf,nfe->nce", (gate * up).astype(tokens.dtype),
                       w_down, **f32).astype(tokens.dtype)
    y_slots = jnp.where(keep[:, None], y_buf[e_flat, safe_pos], 0) \
        * w_flat[:, None]
    return y_slots.reshape(T, k, E).sum(axis=1)


@pytest.mark.parametrize("dtype,factor", [("float32", None),
                                          ("bfloat16", 1.0)])
def test_mixtrals_expert_layer_is_bit_for_bit_what_it_was(dtype, factor):
    cfg = mixtral.MixtralConfig.tiny(dtype=dtype)
    lp = jax.tree.map(lambda a: a[0], mixtral.init_params(
        jax.random.PRNGKey(0), cfg)["layers"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.dim)).astype(
        dtype)
    got, _ = moe.moe_ffn(x, lp["router"], lp["w_gate"], lp["w_up"],
                         lp["w_down"], 2, capacity_factor=factor)
    tokens = x.reshape(48, cfg.dim)
    logits = jnp.einsum("te,en->tn", tokens.astype(jnp.float32),
                        lp["router"].astype(jnp.float32))
    w, idx = moe.top_k_router(logits, 4, 2, dtype=x.dtype)
    want = _sparse_before(tokens, w, idx, lp["w_gate"], lp["w_up"],
                          lp["w_down"], 4, 2,
                          moe.expert_capacity(48, 4, 2, factor))
    assert bool(jnp.array_equal(got.reshape(48, -1), want))
    # the new arguments at their no-op values change no bit either, and
    # `exact` turns the capacity's drops into the lossless result
    same, _ = moe.moe_ffn(x, lp["router"], lp["w_gate"], lp["w_up"],
                          lp["w_down"], 2, capacity_factor=factor,
                          held=(4, 0), valid=jnp.ones((2, 24), bool))
    assert bool(jnp.array_equal(same, got))
    exact, _ = moe.moe_ffn(x, lp["router"], lp["w_gate"], lp["w_up"],
                           lp["w_down"], 2, capacity_factor=0.5, exact=True)
    lossless, _ = moe.moe_ffn(x, lp["router"], lp["w_gate"], lp["w_up"],
                              lp["w_down"], 2)
    # (the same mathematics inside a `cond`: the last bit may differ)
    assert close(exact.astype(jnp.float32), lossless.astype(jnp.float32),
                 1e-6 if dtype == "float32" else 1e-2)
    dropped, _ = moe.moe_ffn(x, lp["router"], lp["w_gate"], lp["w_up"],
                             lp["w_down"], 2, capacity_factor=0.5)
    assert not close(dropped.astype(jnp.float32),
                     lossless.astype(jnp.float32), 1e-2)


def test_sparse_and_dense_agree_on_a_share_of_two_matrix_experts():
    _, lp = _uncut()
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 11, DIMS["moe_latent"]))
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 11, DIMS["dim"]))
    routing = moe.route(h, lp["router"], 3, "sigmoid_bias",
                        bias=lp["router_bias"])
    valid = jnp.asarray(np.arange(11)[None] < np.asarray([11, 4])[:, None])
    kw = dict(num_experts_per_tok=3, activation=moe.relu2, routing=routing,
              held=(8, 2), valid=valid)
    args = (u, None, None, lp["w_up"][2:6], lp["w_down"][2:6])
    dense, _ = moe.moe_ffn(*args, dispatch="dense", **kw)
    for factor, exact in ((None, False), (1.0, True)):
        sparse, _ = moe.moe_ffn(*args, capacity_factor=factor, exact=exact,
                                **kw)
        assert close(sparse, dense)
    assert not bool(jnp.any(dense[1, 4:]))
    with pytest.raises(ValueError, match="'sparse' or 'dense'"):
        moe.moe_ffn(*args, dispatch="gmm", **kw)


# ---- parity with the plain reference, through the cache ----

def test_chunks_then_steps_through_the_cache_match_the_reference(params):
    """Prefill in chunks (the chunk form, the experts at a row's
    tokens), then a token at a time (the one-token update), each row at
    its own cursor: the LOGITS are the reference's full forward pass and
    the model's own. Float32 on both sides, so the tolerance is
    rounding: 1e-4 of the largest logit."""
    chunks = (7, 33)   # shorter than a chunk of the form's 8; 4 and a part
    tokens = np.stack([prompt(48), prompt(48, salt=3)])
    want = jnp.stack([reference.logits(params, t, DIMS) for t in tokens])
    cache = init_kv_cache(CFG, 2, 64)
    run = jax.jit(lambda toks, cache, pos: decode_forward(
        params, toks, cache, pos, CFG))
    got, at = [], 0
    for n in chunks:
        logits, cache = run(jnp.asarray(tokens[:, at:at + n]), cache, at)
        got.append(logits)
        at += n
    for t in range(at, 48):
        logits, cache = run(jnp.asarray(tokens[:, t:t + 1]), cache,
                            jnp.full((2,), t))
        got.append(logits)
    got = jnp.concatenate(got, axis=1)
    assert close(got, want)
    assert close(got, nemotron_h.forward(params, jnp.asarray(tokens), CFG))
    # 3 expert layers x 2 rows x 48 positions x 3 picks, and a share
    routed, held = (int(n) for n in cache[MOE_PAIRS])
    assert routed == 3 * 2 * 48 * 3 and 0 < held < routed


@pytest.mark.parametrize("dtype,limit", [
    # rounding only: a served token is the reference's best, or ties it
    ("float32", 1e-4),
    # bfloat16 keeps 8 bits: logits of size 2-4 carry errors of a few
    # hundredths, and a pick near a tie that flips moves one expert's
    # part; the e4m3 control below reads ten times this
    ("bfloat16", 0.25)])
def test_engine_serves_the_references_logits(params, dtype, limit):
    """Through `SlotEngine`: lanes admitted at different positions (one
    decodes while the other prefills, uneven prompts, padded rows), then
    a released slot re-used by a new occupant; judged by where each
    served token's reference logit lies, not by the tokens."""
    cfg = configs.program_config(dict(CONFIG, torch_dtype=dtype), 128)[1]
    dims = dict(DIMS, dtype=dtype)
    cast = jax.tree.map(lambda a: a.astype(dtype), params)
    eng = SlotEngine(cast, cfg, max_slots=2, max_seq_len=128,
                     prefill_chunk=16)
    first, second, third = prompt(37), prompt(21, salt=2), prompt(9, salt=4)
    eng.admit(1, first, 14)
    served = {1: [prefill(eng, 1)], 0: []}
    for _ in range(3):
        served[1].append(eng.decode_step()[1])
    eng.admit(0, second, 8)
    while len(served[0]) < 8 or len(served[1]) < 14:
        if not served[0]:   # still prefilling
            _, tok = eng.prefill_step(0)
            if tok is not None:
                served[0].append(tok)
        for slot, tok in eng.decode_step().items():
            if len(served[slot]) < (14 if slot else 8):
                served[slot].append(tok)
    eng.release(0)
    eng.release(1)
    eng.admit(1, third, 6)      # a new occupant: an empty state
    again = [prefill(eng, 1)]
    while len(again) < 6:
        again.append(eng.decode_step()[1])
    eng.release(1)
    worst = 0.0
    for p, out in ((first, served[1]), (second, served[0]), (third, again)):
        gaps = reference.served_gaps(cast, p.tolist(), out, dims, pad_to=64)
        assert gaps.shape == (len(out),)
        worst = max(worst, float(gaps.max()))
    assert worst <= limit
    if dtype == "float32":
        # the comparison sees an altered token
        wrong = [(again[0] + 1) % 256] + again[1:]
        assert float(reference.served_gaps(
            cast, third.tolist(), wrong, dims, pad_to=64)[0]) > limit
    else:
        # the control: the reference with 8-bit operands, in the
        # program's place, fails the bfloat16 program's limit
        control = reference.served_gaps(cast, first.tolist(), served[1],
                                        dims, pad_to=64, control=True)
        assert float(control.max()) > limit
    # the engine's counters: what the device counted since it was made
    assert 0 < eng.expert_pairs["held"] < eng.expert_pairs["routed"]


def test_through_the_scheduler_and_its_stats(engine):
    sched = Scheduler(engine).start()
    try:
        reqs = [sched.submit(Request(prompt(n, salt=n).tolist(),
                                     max_new_tokens=6, temperature=0.0,
                                     eos_id=None, rng=0))
                for n in (5, 30, 17, 40)]
        for r in reqs:
            assert len(r.result(timeout=120)) == 6 and r.reason == "length"
        pairs = sched.stats()["expert_pairs"]
    finally:
        sched.stop()
    assert 0 < pairs["held"] < pairs["routed"]
    assert not engine.active.any()


# ---- the family in the tables ----

def test_family_pools_and_plan():
    fam = family(CFG)
    assert fam.name == "nemotron_h" and fam.module is nemotron_h
    assert fam.ffn is None and not fam.rope
    assert layer_kinds(CFG) == ("mamba2", "ffn", "mamba2", "attention",
                                "ffn", "mamba2", "ffn")
    assert is_recurrent(CFG) and recurrent_pools(CFG) == ["conv", "ssm"]
    # a recurrent kind keeps the two programs of an iteration
    assert not merges(CFG, None, "chunked")
    assert type(build_config({"cfg": {"dim": CFG.dim}},
                             model="nemotron_h")) is type(CFG)
    cache = jax.eval_shape(lambda: init_kv_cache(CFG, 2, 32))
    shapes = {name: (leaf.shape, str(leaf.dtype))
              for name, leaf in cache.items()}
    assert shapes == {
        "k": ((1, 2, 32, 32), "float32"), "v": ((1, 2, 32, 32), "float32"),
        "conv": ((3, 2, 3, 64 + 2 * 2 * 16), "float32"),
        "ssm": ((3, 2, 8, 8, 16), "float32"),
        MOE_PAIRS: ((2,), "uint32")}
    axes = nemotron_h.logical_axes(CFG)
    tree = jax.eval_shape(lambda: nemotron_h.init_params(
        jax.random.PRNGKey(0), CFG))
    assert jax.tree.structure(tree) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    assert tree["moe_layers"]["w_up"].shape == (3, 4, 32, 48)
    assert tree["moe_layers"]["router"].shape == (3, 64, 8)
    # the published pattern: 40 Mamba-2, 40 expert and 8 attention layers
    kinds = nemotron_h.NemotronHConfig().layer_kinds
    assert [kinds.count(k) for k in ("mamba2", "ffn", "attention")] == \
        [40, 40, 8]
    # the benchmark's cut, the first 11: one traced body a run
    plan = jamba.layer_plan(kinds[:11])
    assert sum(r * sum(n for _, _, n in runs) for r, runs, _ in plan) == 11
    with pytest.raises(ValueError, match="M .Mamba-2."):
        nemotron_h.NemotronHConfig.tiny(pattern="ME-")
    with pytest.raises(ValueError, match="experts_held"):
        nemotron_h.NemotronHConfig.tiny(experts_held=(6, 4))


def test_tpuflow_serve_takes_every_family_by_name():
    """`--model`'s choices are the families' names (it had fallen two
    behind the table)."""
    from metaflow_tpu.__main__ import main as cli
    from metaflow_tpu.inference.decode import FAMILIES

    option = next(p for p in cli.commands["serve"].params
                  if p.name == "model")
    assert sorted(option.type.choices) == sorted(
        f.name for f in FAMILIES.values())


def _kv(n):
    shape = (1, n, 2, 16)   # refused before any shape is read
    return {"k": np.zeros(shape, np.float32), "v": np.zeros(shape, np.float32)}


REFUSALS = {
    "seed_prefix": lambda e, p: e.seed_prefix(0, _kv(4)),
    "extract_kv": lambda e, p: e.extract_kv(0, 4),
    "admit_prefilled": lambda e, p: e.admit_prefilled(
        0, prompt(8), 1, _kv(8), 4),
    "kv_token_bytes": lambda e, p: e.kv_token_bytes(),
    "build_prefix_cache": lambda e, p: build_prefix_cache(e, 1),
    "scheduler_prefix_cache": lambda e, p: Scheduler(
        e, prefix_cache=RadixPrefixCache(1 << 20)),
    "paged_engine": lambda e, p: PagedEngine(p, e.cfg, max_slots=2,
                                             max_seq_len=64),
    "build_engine_paged": lambda e, p: build_engine(
        p, e.cfg, slots=2, max_seq_len=64, paged=True),
    "disagg_prefill_only": lambda e, p: Scheduler(e).submit(
        Request([1, 2, 3], max_new_tokens=2, prefill_only=True)),
    "disagg_prefilled": lambda e, p: Scheduler(e).submit(
        Request([1, 2, 3], max_new_tokens=2,
                prefilled={"first": 1, "kv": _kv(3)})),
}


@pytest.mark.parametrize("entry", sorted(REFUSALS))
def test_kv_only_entry_points_refuse_the_model_by_name(params, engine,
                                                       entry):
    with pytest.raises(TpuFlowException,
                       match="nemotron_h.*mamba2.*recurrent state"):
        REFUSALS[entry](engine, params)
    assert not engine.active.any()


SCOPES = ("decode_layers", "attn_qkv", "kv_cache_update", "decode_attention",
          "attn_out", "ffn", "ssd_in_proj", "ssd_conv", "ssd_gate_norm",
          "ssd_out_proj", "moe_router", "moe_latent_down", "moe_dispatch",
          "moe_experts", "moe_combine", "moe_latent_up", "moe_shared_expert")


@pytest.mark.parametrize("program,own,other", [
    ("decode", "ssd_state_update", "ssd_chunk"),
    ("prefill", "ssd_chunk", "ssd_state_update")])
def test_programs_keep_their_names_and_hold_every_scope(engine, program,
                                                        own, other):
    import re

    cache = jax.eval_shape(lambda: engine._cache)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    if program == "decode":
        lowered = engine._decode_greedy_fn.lower(
            engine.params, cache, i32(3), i32(3),
            jax.ShapeDtypeStruct((3,), jnp.bool_))
    else:   # five arguments, as benchmark/describe_compile.py calls it
        lowered = engine._prefill_fn.lower(engine.params, cache, i32(1, 16),
                                           i32(), i32())
    text = lowered.as_text(debug_info=True)
    assert "module @jit__%s" % ("decode_greedy" if program == "decode"
                                else "prefill") in text
    for scope in SCOPES + (own,):
        assert re.search(r'["/(]%s["/)]' % scope, text), scope
    assert not re.search(r'["/(]%s["/)]' % other, text)
    # no Mamba-1 scope, and no feed-forward after the mixers: `ffn`
    # holds the expert layers alone
    assert not re.search(r'["/(]ssm_\w+["/)]', text)
