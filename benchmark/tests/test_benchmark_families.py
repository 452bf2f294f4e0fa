"""A model family is found by the name its configuration gives
(benchmark/families/): the moved code is held to what the parent commit
computed, the benchmark's tree to the program's, and a family that is
not in the tree is served from a directory of its own with no file of
benchmark/ edited."""

import hashlib
import json
import os
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import (configs, families, flops, kernel_costs, ref_train,
                       reference, weights)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
# recorded on the parent commit (PR 25) before anything moved, by the
# same lines as `digest`, `TOKENS` and the tests below; the operation
# counts of the configurations added since, on PR 37's tree
with open(os.path.join(HERE, "data", "parent_digests.json")) as f:
    PARENT = json.load(f)
BENCH = configs.read_json(os.path.join(ROOT, "BENCHMARK.json"))
TOKENS = (np.arange(32) * 37 + 11) % 512


def digest(a):
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()


def tiny_dims(name):
    return configs.dims(configs.read_json(os.path.join(
        HERE, "cells", "configs", name + ".json")))


def committed_dims(entry):
    return configs.dims(configs.read_json(os.path.join(ROOT, entry["file"])))


# ---- (a) seeded weights, reference and control are the parent's, bit for bit

@pytest.mark.parametrize("name", sorted(PARENT["weights"]))
def test_seeded_weights_are_the_parents(name):
    dims = tiny_dims(name)
    params = jax.jit(lambda k: weights.init_params(k, dims))(
        weights.seed_key(7))
    got = {"/".join(k.key for k in path): digest(leaf.astype("float32"))
           for path, leaf in jax.tree_util.tree_leaves_with_path(params)}
    assert got == PARENT["weights"][name]


@pytest.mark.parametrize("name,lowp", [
    (n, l) for n in sorted(PARENT["logits"]) for l in (False, True)])
def test_reference_and_control_logits_are_the_parents(name, lowp):
    dims = tiny_dims(name)
    params = jax.jit(lambda k: weights.init_params(k, dims))(
        weights.seed_key(7))
    got = digest(reference.logits(params, TOKENS, dims, lowp=lowp))
    assert got == PARENT["logits"][name]["lowp" if lowp else "plain"]


# ---- (b) the benchmark's tree is the program's ----

@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_benchmark_tree_is_the_programs(entry):
    config = configs.read_json(os.path.join(ROOT, entry["file"]))
    dims = configs.dims(config)
    model, cfg = configs.program_config(config, 256)
    ours = jax.eval_shape(
        lambda k: weights.init_params(k, dims), weights.seed_key(1))
    theirs = jax.eval_shape(
        lambda k: model.init_params(k, cfg), jax.random.PRNGKey(1))
    shapes = lambda tree: {
        tuple(k.key for k in path): (leaf.shape, leaf.dtype)
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}
    assert shapes(ours) == shapes(theirs)
    assert set(shapes(ours)) == set(weights.leaf_specs(dims))


# ---- (d) operations from shapes are the parent's ----

@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_operation_counts_are_the_parents(entry):
    """The record is PR 25's for the three configurations it had, and
    the tree's at PR 37 for those that came since; a family with no
    training reference (no `train_flops_per_token`: README, "Adding a
    family") is recorded with none."""
    dims = committed_dims(entry)
    family = families.load(dims["family"])
    assert {
        "train_flops_per_token_4096": flops.train_flops_per_token(dims, 4096)
        if hasattr(family, "train_flops_per_token") else None,
        "matmul_params_active": flops.matmul_params(dims),
        "matmul_params_all": flops.matmul_params(dims, active_only=False),
    } == PARENT["flops"][entry["name"]]


# ---- (c) a family that is not in the tree ----

PROBE = '''
"""A probe family: a stack of gated mixers with a decay of their own
initialiser, a stack of plain feed-forward blocks of other leaves, and a
head tied to the embedding."""
import jax
import jax.numpy as jnp

from benchmark.reference import F32, mm, rms_norm


def dims(config):
    return {"dim": config["width"], "n_mixers": config["mixers"],
            "n_blocks": config["blocks"], "state": config["state"],
            "vocab_size": config["vocab"], "norm_eps": 1e-5,
            "dtype": config["stored_as"]}


def program_config(d, max_seq_len):
    raise NotImplementedError("the program runs no probe")


def decay_init(key, shape):
    # the same for every seed, and neither a draw nor ones
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[-1] + 1, dtype=F32)),
                            shape)


def leaf_specs(d):
    D, S, V = d["dim"], d["state"], d["vocab_size"]
    return {
        ("embed",): ((V, D), D),
        ("mixers", "norm"): ((d["n_mixers"], D), None),
        ("mixers", "w_in"): ((d["n_mixers"], D, S), D),
        ("mixers", "decay"): ((d["n_mixers"], S), decay_init),
        ("mixers", "w_out"): ((d["n_mixers"], S, D), S),
        ("blocks", "norm"): ((d["n_blocks"], D), None),
        ("blocks", "w"): ((d["n_blocks"], D, D), D),
        ("final_norm",): ((D,), None),
    }


def logits(params, tokens, d, lowp=False):
    x = params["embed"][jnp.asarray(tokens)].astype(F32)
    for i in range(d["n_mixers"]):
        p = jax.tree.map(lambda a: a[i], params["mixers"])
        h = mm(rms_norm(x, p["norm"], d["norm_eps"]), p["w_in"], lowp)
        gate = jnp.exp(-jnp.exp(-p["decay"].astype(F32)))
        h = jax.lax.associative_scan(
            lambda a, b: (a[0] * b[0], b[0] * a[1] + b[1]),
            (jnp.broadcast_to(gate, h.shape), h))[1]   # causal, by position
        x = x + mm(h, p["w_out"], lowp)
    for i in range(d["n_blocks"]):
        p = jax.tree.map(lambda a: a[i], params["blocks"])
        x = x + mm(jax.nn.silu(rms_norm(x, p["norm"], d["norm_eps"])),
                   p["w"], lowp)
    return mm(rms_norm(x, params["final_norm"], d["norm_eps"]),
              params["embed"].T, lowp)


def matmul_params(d, active_only=True):
    return (d["n_mixers"] * 2 * d["dim"] * d["state"]
            + d["n_blocks"] * d["dim"] ** 2 + d["dim"] * d["vocab_size"])


def train_flops_per_token(d, seq_len):
    return 6 * matmul_params(d)
'''
PROBE_CONFIG = {"family": "probe", "width": 64, "mixers": 3, "blocks": 2,
                "state": 16, "vocab": 256, "stored_as": "bfloat16"}


@pytest.fixture
def probe(tmp_path, monkeypatch):
    """The probe family's file in a directory outside the tree, put on
    the package's search path; nothing under benchmark/ is written."""
    (tmp_path / "probe.py").write_text(textwrap.dedent(PROBE))
    monkeypatch.setattr(families, "__path__",
                        list(families.__path__) + [str(tmp_path)])
    yield configs.dims(dict(PROBE_CONFIG))
    sys.modules.pop("benchmark.families.probe", None)


def test_probe_family_sizes_and_operations(probe):
    assert probe["family"] == "probe" and probe["n_mixers"] == 3
    assert not os.path.exists(os.path.join(
        os.path.dirname(families.__file__), "probe.py"))
    want = 3 * 2 * 64 * 16 + 2 * 64 * 64 + 64 * 256
    assert flops.matmul_params(probe) == want
    assert flops.train_flops_per_token(probe, 128) == 6 * want
    with pytest.raises(NotImplementedError):
        configs.program_config(dict(PROBE_CONFIG), 128)
    # no `layer` and `head`: no training reference, said in one line
    with pytest.raises(NotImplementedError, match="no training reference"):
        ref_train.training_family(probe)


def test_probe_family_tree_has_two_groups_and_a_tied_head(probe):
    tree = jax.jit(lambda k: weights.init_params(k, probe))(
        weights.seed_key(2 ** 31 + 5))
    assert set(tree) == {"embed", "mixers", "blocks", "final_norm"}
    assert set(tree["mixers"]) == {"norm", "w_in", "decay", "w_out"}
    assert set(tree["blocks"]) == {"norm", "w"}
    assert all(leaf.dtype == jnp.bfloat16 for leaf in jax.tree.leaves(tree))
    assert tree["mixers"]["w_in"].shape == (3, 64, 16)


@pytest.mark.parametrize("path,kind", [(("mixers", "w_in"), "fan-in"),
                                       (("mixers", "norm"), "ones"),
                                       (("mixers", "decay"), "its own")])
def test_make_leaf_takes_each_kind_of_init(probe, path, kind):
    make = jax.jit(lambda k: weights.make_leaf(k, probe, path))
    a, b = make(weights.seed_key(1)), make(weights.seed_key(2))
    shape, _ = weights.leaf_specs(probe)[path]
    assert a.shape == tuple(shape) and a.dtype == jnp.bfloat16
    a32 = np.asarray(a.astype(jnp.float32))
    if kind == "fan-in":
        assert not np.array_equal(a32, np.asarray(b.astype(jnp.float32)))
        assert 0.8 < a32.std() * 64 ** 0.5 < 1.2
    elif kind == "ones":
        assert np.array_equal(a32, np.ones(shape, np.float32))
    else:
        want = np.log(np.arange(1, 17, dtype=np.float32))
        assert np.allclose(a32, np.broadcast_to(want, shape), rtol=1e-2)
        assert bool(jnp.array_equal(a, b))


def test_probe_family_is_served_by_the_comparison(probe):
    params = jax.jit(lambda k: weights.init_params(k, probe))(
        weights.seed_key(3))
    prompt = list(range(5, 25))
    # the reference's own greedy continuation: every gap is 0
    seq = list(prompt)
    for _ in range(6):
        padded = np.zeros(32, np.int32)
        padded[:len(seq)] = seq
        seq.append(int(jnp.argmax(
            reference.logits(params, padded, probe)[len(seq) - 1])))
    served = seq[len(prompt):]
    gaps = reference.served_gaps(params, prompt, served, probe, pad_to=32)
    assert gaps.shape == (6,) and float(gaps.max()) == 0.0
    # a token altered where it is produced shows
    wrong = [(served[0] + 1) % probe["vocab_size"]] + served[1:]
    assert float(reference.served_gaps(params, prompt, wrong, probe,
                                       pad_to=32)[0]) > 0.0
    # and the control is read through the same family, in its lower precision
    low = reference.served_gaps(params, prompt, served, probe, pad_to=32,
                                control=True)
    assert low.shape == (6,) and bool(np.all(low >= 0.0))
    plain = reference.logits(params, np.asarray(seq + [0] * 6), probe)
    lowp = reference.logits(params, np.asarray(seq + [0] * 6), probe,
                            lowp=True)
    assert float(jnp.abs(plain - lowp).max()) > 1e-3


def test_an_unknown_family_is_an_import_error():
    with pytest.raises(ImportError):
        configs.dims({"family": "no_such_family"})


# ---- the kernels' costs, against PERF.md's hand counts (PR 25) ----

def by_name(name):
    return committed_dims(next(c for c in BENCH["configs"]
                               if c["name"] == name))


PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("what,cost,ms,hand_pct,bound_by", [
    # the whole pool, 40 slots x 1280 positions at 64 KiB each, in 5.954 ms
    ("decode_attention", lambda: kernel_costs.decode_attention(
        by_name("mistral-7b-serve"), 40 * 1280), 5.954, 69, "bandwidth"),
    # every expert of four layers, 11.3 GB, in 14.927 ms
    ("moe_experts", lambda: kernel_costs.moe_experts(
        by_name("mixtral-8x7b-serve"), 64), 14.927, 92, "bandwidth"),
    # 2 x 4096 tokens through 7 layers, forward and backward, in 368.992 ms
    ("flash_attention", lambda: kernel_costs.flash_attention_train(
        by_name("mistral-7b-train"), 2, 4096), 368.992, 8, "compute"),
])
def test_kernel_costs_give_the_hand_counts(what, cost, ms, hand_pct, bound_by):
    cost = cost()
    assert kernel_costs.bound(cost, PEAK)[1] == bound_by
    assert round(kernel_costs.roofline_pct(cost, ms * 1e-3, PEAK)) == hand_pct


def test_kernel_costs_count_what_is_needed_and_no_more():
    serve = by_name("mistral-7b-serve")
    assert kernel_costs.kv_bytes_per_position(serve) == 64 * 1024
    ops, nbytes = kernel_costs.decode_attention(serve, 1000)
    assert nbytes == 1000 * 64 * 1024
    assert ops == 16 * 4 * 32 * 128 * 1000
    moe = by_name("mixtral-8x7b-serve")
    one = kernel_costs.moe_experts(moe, 1)   # one token reaches two experts
    assert one[1] == pytest.approx(4 * 2 * 3 * 4096 * 14336 * 2)
    every = kernel_costs.moe_experts(moe, 64)
    assert every[1] == pytest.approx(4 * 8 * 3 * 4096 * 14336 * 2, rel=1e-6)
    assert every[1] <= 4 * 8 * 3 * 4096 * 14336 * 2
    train = by_name("mistral-7b-train")
    ops, _ = kernel_costs.flash_attention_train(train, 2, 4096)
    assert ops == 7 * flops.attention_flops(train, 2, 4096, backward=True)
    # two chips take half the time for the same cost
    assert kernel_costs.roofline_pct((ops, 0), 1.0, PEAK, chips=2) \
        == pytest.approx(kernel_costs.roofline_pct((ops, 0), 1.0, PEAK) / 2)


# ---- the roofline readers, on the trace made by hand ----

def test_roofline_readers_divide_cost_by_the_traces_time(monkeypatch):
    from test_span_readings import by_hand

    from benchmark import harness, span_readings

    monkeypatch.setattr(span_readings, "trace", lambda run: by_hand())
    dims = by_name("mixtral-8x7b-serve")
    names = ["kernels.decode_attention_roofline.batch",
             "kernels.moe_experts_roofline.batch",
             "kernels.flash_attention_roofline.train"]
    bench = {"per_layer": [m for m in BENCH["per_layer"]
                           if m["name"] in names]}
    assert len(bench["per_layer"]) == 3
    run = {"trace": {}, "dims": dims, "chips": 1, "peak": PEAK, "slots": 64,
           "max_seq_len": 1280, "prefill_chunk": 64,
           "counters": {"decode_steps": 10}, "decode_tokens": 640,
           "kv_positions_read": 250000, "seq_len": 4096,
           "sequences_per_step": 2}
    got = {}
    for cell in ("mixtral-8x7b.batch-offline", "mistral-7b.train-4k"):
        got.update(harness.read_layer_metrics(bench, cell, set(), run))
    # the hand-made trace: 30 ns under decode_attention and 20 ns under
    # moe_experts an execution; no flash_attention scope, so no number
    assert set(got) == set(names[:2])
    assert got[names[0]]["value"] == pytest.approx(kernel_costs.roofline_pct(
        kernel_costs.decode_attention(dims, 25000), 30e-9, PEAK))
    assert got[names[1]]["value"] == pytest.approx(kernel_costs.roofline_pct(
        kernel_costs.moe_experts(dims, 64), 20e-9, PEAK))
    assert got[names[0]]["unit"] == "%"
    # off the chip there is no row of peaks: nothing is reported, never 0
    run["peak"] = None
    assert harness.read_layer_metrics(
        bench, "mixtral-8x7b.batch-offline", set(), run) == {}
