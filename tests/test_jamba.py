"""The families that carry recurrent state through the slot engine,
Jamba (Mamba-1 layers with attention layers among them), Brumby
(power-retention layers alone: a state pool and no KV pool) and
Phi-4-mini-flash (Mamba-1 layers beside window attention in rings, one
global K and V pool that several layers read, gated memory units): parity
with the plain reference, and the five properties a recurrent-state pool
needs that a KV pool got for free: a padded chunk leaves the state after
its last real token, masked lanes hold their state, a new occupant
starts from an empty state, the state carries from chunk to chunk, and
what treats a KV range as a prefix refuses the model by name. Every such
case runs for every such family (`fam`); what only Jamba has (the layer
pattern, ops/ssm.py) follows, what only Brumby has is
tests/test_brumby.py and what only Phi-4-mini-flash has
tests/test_phi4flash.py. Tiny sizes, float32 unless said."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import configs, reference
from benchmark.families import jamba as ref_family
from metaflow_tpu.models import brumby
from metaflow_tpu.cmd.serve import build_config, build_engine, \
    build_prefix_cache
from metaflow_tpu.exception import TpuFlowException
from metaflow_tpu.inference import decode_forward, generate, init_kv_cache
from metaflow_tpu.inference.cache import is_recurrent, layer_kinds, \
    recurrent_pools, ring_pools
from metaflow_tpu.inference.decode import family
from metaflow_tpu.models import jamba, llama, mixtral, phi4flash
from metaflow_tpu.ops import ssm
from metaflow_tpu.serving import PagedEngine, RadixPrefixCache, Request, \
    Scheduler, SlotEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = jamba.JambaConfig.tiny()   # hidden 64, 8 layers, attention at 2 and 6
NEW = 8


class Fam(object):
    """One recurrent family at its tiny size: the program's module and
    config, and the reference's sizes of the same model, from the
    benchmark's file."""

    def __init__(self, name, module, cfg_class):
        self.name, self.module, self.tiny = name, module, cfg_class.tiny
        self.cfg = cfg_class.tiny()
        self.dims = dict(configs.dims(dict(configs.read_json(os.path.join(
            ROOT, "benchmark", "tests", "cells", "configs",
            "tiny-%s.json" % name)), torch_dtype="float32")))
        self.state = recurrent_pools(self.cfg)   # the pools' names
        self.kv = "attention" in layer_kinds(self.cfg)

    def init_params(self):
        p = self.module.init_params(jax.random.PRNGKey(0), self.cfg)
        if "mamba_layers" in p:
            # a drawn convolution bias, so that an empty tail is not a
            # fixed point
            p["mamba_layers"]["conv_b"] = 0.5 * jax.random.normal(
                jax.random.PRNGKey(1), p["mamba_layers"]["conv_b"].shape)
        return p


FAMS = {"jamba": Fam("jamba", jamba, jamba.JambaConfig),
        "brumby": Fam("brumby", brumby, brumby.BrumbyConfig),
        "phi4flash": Fam("phi4flash", phi4flash, phi4flash.Phi4FlashConfig)}
DIMS = FAMS["jamba"].dims
LENGTHS = (5, 16, 37, 50)   # a padded chunk, a whole one, 2 + a padded, 3 + 2


def prompt(n, salt=0):
    return ((np.arange(n) * 37 + 11 + 5 * salt) % 255 + 1).astype(np.int32)


@pytest.fixture(scope="module", params=sorted(FAMS))
def fam(request):
    return FAMS[request.param]


# the cases that say of Phi-4-mini-flash what tests/test_phi4flash.py
# says already (its parity runs the forward, chunks, rows of two slots, a
# padded row, masked lanes and steps against the reference at once, its
# served requests go through the scheduler, a new occupant reuses a slot)
# run for the other two: tier-1 has little time to spare
only_state_families = pytest.mark.parametrize(
    "fam", ["brumby", "jamba"], indirect=True)


@pytest.fixture(scope="module")
def params(fam):
    return fam.init_params()


@pytest.fixture(scope="module")
def engine(fam, params):
    """Three slots, chunks of 16; every test leaves its slots released."""
    return SlotEngine(params, fam.cfg, max_slots=3, max_seq_len=128,
                      prefill_chunk=16)


@pytest.fixture(scope="module")
def alone(fam, params):
    """The tokens a prompt emits alone, unpadded, in one lockstep call
    (one compile a prompt length: the tests share four, LENGTHS)."""
    run = jax.jit(lambda p, toks: generate(p, toks, fam.cfg, NEW,
                                           max_seq_len=128))

    def tokens(p):
        assert len(p) in LENGTHS
        return np.asarray(run(params, jnp.asarray(p)[None])[0, len(p):]
                          ).tolist()
    return tokens


def close(got, want):
    """Float32 rounding: 1e-5 of the largest entry (a retention state
    sums tens of positions and holds entries of tens), or of one."""
    return float(jnp.abs(got - want).max()) < 1e-5 * max(
        1.0, float(jnp.abs(want).max()))


def prefill(eng, slot):
    first = None
    while first is None:
        _, first = eng.prefill_step(slot)
    return first


def serve(eng, slot, p, n=NEW):
    """One request alone in `slot`, to its end; the slot is released."""
    eng.admit(slot, p, n)
    out = [prefill(eng, slot)]
    while len(out) < n:
        out.append(eng.decode_step()[slot])
    eng.release(slot)
    return out


# ---- parity with the plain reference ----

@only_state_families
def test_forward_matches_the_reference(fam, params):
    tokens = prompt(48)
    want = reference.logits(params, tokens, fam.dims)
    got = fam.module.forward(params, jnp.asarray(tokens)[None], fam.cfg)[0]
    # float32 on both sides: rounding only
    assert float(jnp.abs(want - got).max()) < 1e-4 * float(jnp.abs(want).max())


@only_state_families
@pytest.mark.parametrize("chunks", [(16, 16, 8), (7, 33)])
def test_chunks_then_steps_through_the_cache_match_the_reference(
        fam, params, chunks):
    """Prefill in chunks, then a token at a time, each row at its own
    cursor: the logits are the reference's full forward pass. Float32 on
    both sides, so the tolerance is rounding: 1e-4 of the largest logit."""
    tokens = np.stack([prompt(48), prompt(48, salt=3)])
    want = jnp.stack([reference.logits(params, t, fam.dims) for t in tokens])
    cache = init_kv_cache(fam.cfg, 2, 64)
    run = jax.jit(lambda toks, cache, pos: decode_forward(
        params, toks, cache, pos, fam.cfg))
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
    assert float(jnp.abs(want - got).max()) < 1e-4 * float(jnp.abs(want).max())


@only_state_families
@pytest.mark.parametrize("dtype,limit", [
    # rounding only: a served token is the reference's best, or ties it
    ("float32", 1e-4),
    # bfloat16 keeps 8 bits: logits of size 2-4 carry errors of a few
    # hundredths, so a served token may lie that far below the best
    ("bfloat16", 0.15)])
def test_engine_serves_the_references_tokens(fam, params, dtype, limit):
    cfg = fam.tiny(dtype=dtype)
    cast = jax.tree.map(lambda a: a.astype(dtype), params)
    eng = SlotEngine(cast, cfg, max_slots=2, max_seq_len=128,
                     prefill_chunk=16)
    p = prompt(37)
    served = serve(eng, 1, p, n=12)
    gaps = reference.served_gaps(cast, p.tolist(), served,
                                 dict(fam.dims, dtype=dtype), pad_to=64)
    assert gaps.shape == (12,) and float(gaps.max()) <= limit
    # the comparison sees an altered token
    wrong = [(served[0] + 1) % 256] + served[1:]
    assert float(reference.served_gaps(
        cast, p.tolist(), wrong, dict(fam.dims, dtype=dtype),
        pad_to=64)[0]) > limit


# ---- (a) a padded chunk, (d) the state carried from chunk to chunk ----

@only_state_families
@pytest.mark.parametrize("n", LENGTHS)
def test_padded_last_chunk_gives_the_unpadded_tokens(engine, alone, n):
    assert serve(engine, 0, prompt(n)) == alone(prompt(n))


@only_state_families
def test_state_after_a_padded_chunk_is_the_state_after_its_last_token(
        fam, params, engine):
    p = prompt(37)   # chunks of 16, 16 and 5 padded to 16
    engine.admit(2, p, NEW)
    prefill(engine, 2)
    _, want = decode_forward(params, jnp.asarray(p)[None],
                             init_kv_cache(fam.cfg, 1, 128), 0, fam.cfg)
    for name in fam.state:
        got = engine._cache[name][:, 2]
        assert close(got, want[name][:, 0]), name
        assert float(jnp.abs(got).max()) > 0
    engine.release(2)


# ---- (b) masked lanes, (c) the state reset ----

@only_state_families
def test_a_request_admitted_while_another_decodes(engine, alone):
    """The second rides through decode steps as a masked lane between
    its prefill chunks; the first decodes beside the second's chunks."""
    a, b = prompt(16, salt=1), prompt(50, salt=2)
    engine.admit(0, a, NEW)
    out = {0: [prefill(engine, 0)], 1: []}
    out[0].append(engine.decode_step()[0])
    engine.admit(1, b, NEW)
    while not engine.decoding[1]:
        _, first = engine.prefill_step(1)
        if first is not None:
            out[1].append(first)
        for slot, tok in engine.decode_step().items():
            if len(out[slot]) < NEW:
                out[slot].append(tok)
    while min(len(v) for v in out.values()) < NEW:
        for slot, tok in engine.decode_step().items():
            if len(out[slot]) < NEW:
                out[slot].append(tok)
    engine.release(0)
    engine.release(1)
    assert out[0] == alone(a) and out[1] == alone(b)


def test_a_free_slots_state_is_held_through_decode_steps(fam, engine):
    serve(engine, 2, prompt(16, salt=7))   # what a released slot leaves
    before = {name: np.asarray(engine._cache[name][:, 2])
              for name in fam.state}
    serve(engine, 0, prompt(16))
    for name in fam.state:
        assert np.abs(before[name]).max() > 0
        assert np.array_equal(np.asarray(engine._cache[name][:, 2]),
                              before[name]), name


@only_state_families
def test_a_request_that_reuses_a_released_slot(fam, engine, alone):
    serve(engine, 1, prompt(50, salt=4))
    for name in fam.state:
        assert float(jnp.abs(engine._cache[name][:, 1]).max()) > 0
    assert serve(engine, 1, prompt(5, salt=1)) == alone(prompt(5, salt=1))


@only_state_families
def test_through_the_scheduler_each_request_emits_what_it_emits_alone(
        fam, params, alone):
    eng = build_engine(params, fam.cfg, slots=2, max_seq_len=128,
                       prefill_chunk=16)
    sched = Scheduler(eng).start()
    prompts = [prompt(n, salt=n) for n in LENGTHS]   # four over two slots
    reqs = [sched.submit(Request(p.tolist(), max_new_tokens=NEW,
                                 temperature=0.0, eos_id=None, rng=0))
            for p in prompts]
    got = [r.result(timeout=120) for r in reqs]
    sched.stop()
    assert got == [alone(p) for p in prompts]
    assert eng.compile_counts()["reset_state"] == 1


def test_a_step_in_flight_leaves_every_state_as_it_was(fam, params, engine,
                                                       alone):
    """One decode step in flight (PR 45), for every family that carries
    recurrent state: each request emits what it emits alone, every launch
    but the first is made before the step before it is collected, and an
    `eos`, learnt a step late, costs one more step of its lane, whose
    update lands in a state that the slot's next occupant finds reset
    (`engine.state.reset` queues behind that step in program order)."""
    sched = Scheduler(engine)
    first, second = prompt(37, salt=3), prompt(50, salt=4)
    want = alone(first)
    at = next(i for i in range(2, NEW - 1) if want[i] not in want[:i])
    ends = sched.submit(Request(first.tolist(), max_new_tokens=NEW,
                                eos_id=want[at]))
    other = sched.submit(Request(second.tolist(), max_new_tokens=NEW))
    for _ in range(200):
        if ends.reason is not None:
            break
        sched.step()
    assert ends.reason == "eos" and ends.generated == want[:at + 1]
    assert ends.slot in sched._in_flight[-1][0]   # one more step of it
    late = sched.submit(Request(prompt(16, salt=5).tolist(),
                                max_new_tokens=NEW))
    sched.run_until_idle(10_000)
    assert late.slot == ends.slot and len(ends.generated) == at + 1
    assert other.generated == alone(second)
    assert late.generated == alone(prompt(16, salt=5))
    stats = sched.stats()
    assert stats["steps_ahead"] == stats["decode_steps"] - 1 > 0
    assert not sched._in_flight and not engine._decodes
    assert engine.free_slots() == list(range(engine.max_slots))


@only_state_families
def test_chunk_sizes_16_and_64_agree(fam, params, engine, alone):
    wide = SlotEngine(params, fam.cfg, max_slots=1, max_seq_len=128,
                      prefill_chunk=64)
    for n in (37, 50):
        p = prompt(n, salt=6)
        assert serve(wide, 0, p) == serve(engine, 0, p) == alone(p)


# ---- one prefill program an iteration: rows of several slots (PR 30) ----

@only_state_families
@pytest.mark.parametrize("lengths,rows", [
    # two slots a program: 16 ends on the chunk's edge, 37 mid-chunk
    ((37, 16), [(16, 16), (21,)]),
    # a lone slot takes rows of 2 x chunk, then what is left
    ((50,), [(32,), (18,)]),
    # three admitted in one iteration, two rows a program, round robin
    ((5, 16, 37), [(5, 16), (32,), (5,)]),
])
def test_uneven_prompts_admitted_together_emit_what_they_emit_alone(
        engine, alone, monkeypatch, lengths, rows):
    eng = engine   # three slots, chunks of 16, every slot released
    sched = Scheduler(eng)
    plans, real = [], eng.collect_prefill
    monkeypatch.setattr(
        eng, "collect_prefill", lambda: plans.append(real()) or plans[-1])
    prompts = [prompt(n, salt=n) for n in lengths]
    reqs = [sched.submit(Request(p.tolist(), max_new_tokens=NEW))
            for p in prompts]
    sched.run_until_idle(10_000)
    assert [r.generated for r in reqs] == [alone(p) for p in prompts]
    assert [tuple(n for n, _ in plan) for plan in plans] == rows
    assert sched.prefill_programs == len(rows)
    assert sched.prefill_tokens == sum(lengths)


@only_state_families
def test_state_after_rows_of_several_slots_is_the_one_slot_paths(
        fam, params, engine):
    """Two prompts prefilled as rows of one program (a padded row beside
    a whole one, then a lone wider row): each slot's recurrent state, and
    its K and V where the family caches them, are those of the prompt
    run alone through the cache."""
    prompts = {0: prompt(37, salt=1), 2: prompt(50, salt=2)}
    for slot, p in prompts.items():
        engine.admit(slot, p, NEW)
    for _ in range(2):
        assert engine.prefill([(2, 16), (0, 16)]) == [(16, None)] * 2
    (n0, first0), (n2, _) = engine.prefill([(0, 16), (2, 16)])
    assert (n0, n2) == (5, 16) and first0 is not None       # 0 ends
    assert engine.prefill([(2, 32)])[0][0] == 2             # 2 ends
    assert engine.decoding[0] and engine.decoding[2]
    for slot, p in prompts.items():
        _, want = decode_forward(params, jnp.asarray(p)[None],
                                 init_kv_cache(fam.cfg, 1, 128), 0, fam.cfg)
        for name in fam.state:
            got = engine._cache[name][:, slot]
            assert close(got, want[name][:, 0]), name
            assert float(jnp.abs(got).max()) > 0
        for name in ("k", "v") if fam.kv else ():
            got = engine._cache[name][:, slot, :len(p)]
            assert float(jnp.abs(
                got - want[name][:, 0, :len(p)]).max()) < 1e-5, name
        engine.release(slot)


def test_rows_with_nothing_real_leave_every_slot_as_it_was(fam, engine):
    """What compiles the programs before a request: rows that hold
    nothing real. With requests in flight every slot's recurrent state,
    and the K and V a slot can see, stay bit for bit."""
    engine.admit(0, prompt(37, salt=3), NEW)   # mid-prefill, 16 of 37 in
    engine.prefill_step(0)
    engine.admit(1, prompt(16, salt=4), NEW)   # decoding
    prefill(engine, 1)
    engine.decode_step()
    seen = {slot: int(engine.pos[slot]) for slot in range(3)}
    before = jax.tree.map(np.asarray, engine._cache)
    engine.warm_prefill(2 * engine.prefill_chunk)
    after = jax.tree.map(np.asarray, engine._cache)
    for name in fam.state:
        assert np.array_equal(after[name], before[name]), name
    for name in ("k", "v") if fam.kv else ():
        for slot, n in seen.items():
            assert np.array_equal(after[name][:, slot, :n],
                                  before[name][:, slot, :n]), (name, slot)
    assert engine.prefill_shapes(2 * engine.prefill_chunk) == [
        (1, 16), (1, 32), (2, 16)]
    engine.release(0)
    engine.release(1)


def test_twenty_prompt_lengths_compile_nothing(fam, params):
    eng = SlotEngine(params, fam.cfg, max_slots=3, max_seq_len=128,
                     prefill_chunk=16)
    sched = Scheduler(eng)
    built = eng.compile_counts()
    # a first-token program a shape of the logits: [rows, width, vocab],
    # or with a tail layer [rows, 1, vocab] whatever the width
    assert built["prefill"] == 3 and built["first_token"] == (
        2 if getattr(fam.cfg, "tail_layer", None) is not None else 3)
    lengths = [1, 2, 5, 15, 16, 17, 20, 31, 32, 33, 40, 47, 48, 49, 63, 64,
               65, 80, 96, 100]
    reqs = [sched.submit(Request(prompt(n, salt=n).tolist(),
                                 max_new_tokens=2)) for n in lengths]
    sched.run_until_idle(10_000)
    assert all(r.reason == "length" for r in reqs)
    assert eng.compile_counts() == dict(
        built, decode_greedy=1, reset_state=1)


# ---- (e) what treats a KV range as a prefix refuses the model by name ----

def _kv(n):
    shape = (2, n, 1, 16)   # refused before any shape is read
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
def test_kv_only_entry_points_refuse_the_model_by_name(fam, params, engine,
                                                       entry):
    with pytest.raises(TpuFlowException,
                       match="%s.*recurrent state" % fam.name):
        REFUSALS[entry](engine, params)
    assert not engine.active.any()


def test_no_budget_builds_no_prefix_cache_and_refuses_nothing(engine,
                                                              monkeypatch):
    monkeypatch.delenv("TPUFLOW_PREFIX_CACHE_MB", raising=False)
    assert build_prefix_cache(engine) is None


# ---- the family table ----

@pytest.mark.parametrize("cfg,name,recurrent", [
    (llama.LlamaConfig.tiny(), "llama", False),
    (mixtral.MixtralConfig.tiny(), "mixtral", False),
    (CFG, "jamba", True),
    (brumby.BrumbyConfig.tiny(), "brumby", True),
    (phi4flash.Phi4FlashConfig.tiny(), "phi4flash", True)])
def test_family_is_picked_by_the_configs_class(cfg, name, recurrent):
    fam = family(cfg)
    assert fam.name == name and fam.module.__name__.endswith(name)
    assert is_recurrent(cfg) is recurrent
    assert len(layer_kinds(cfg)) == cfg.n_layers
    assert type(build_config({"cfg": {"dim": cfg.dim}}, model=name)) \
        is type(cfg)
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 2, 32))
    assert set(cache) == {"llama": {"k", "v"}, "mixtral": {"k", "v"},
                          "jamba": {"k", "v", "conv", "ssm"},
                          "brumby": {"ret_s", "ret_z"},
                          "phi4flash": {"k", "v", "win_k", "win_v", "conv",
                                        "ssm"}}[name]
    assert set(ring_pools(cfg)) == set(cache) & {"win_k", "win_v"}
    assert set(recurrent_pools(cfg)) == \
        set(cache) - {"k", "v", "win_k", "win_v"}
    assert all(leaf.shape[1] == 2 for leaf in cache.values())
    axes = fam.module.logical_axes(cfg)
    shapes = jax.eval_shape(lambda: fam.module.init_params(
        jax.random.PRNGKey(0), cfg))
    assert jax.tree.structure(shapes) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))


def test_an_unknown_family_is_refused_by_name():
    with pytest.raises(TpuFlowException,
                       match="brumby, jamba, llama, mixtral"):
        build_config({"cfg": {}}, model="gpt")
    with pytest.raises(TpuFlowException, match="no model family"):
        family(object())


def test_the_layer_pattern_comes_from_period_and_offset():
    big = jamba.JambaConfig()
    kinds = big.layer_kinds
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [7, 21]
    order = []
    jamba.scan_layers(CFG.layer_kinds,
                      lambda kind, i, c: order.append(kind) or c, 0)
    assert order == ["mamba", "attention", "mamba"]   # one body a run
    n = sum(int(np.prod(s)) for s, _ in jamba.leaf_shapes(big).values())
    published = configs.read_json(os.path.join(
        ROOT, "benchmark", "configs", "jamba2-3b-serve.json"))
    assert n == 3_029_337_472 and ref_family.matmul_params(
        configs.dims(published)) < n
    assert configs.program_config(published, 2560)[1] == jamba.JambaConfig(
        max_seq_len=2560)


PLANS = {
    # the four patterns that are one period over and over: one segment
    "llama": (("attention",) * 32, [(32, [("attention", 0, 1)])]),
    "brumby": (("retention",) * 8, [(8, [("retention", 0, 1)])]),
    "jamba2-3b": (jamba.JambaConfig().layer_kinds, [
        (2, [("mamba", 0, 7), ("attention", 0, 1), ("mamba", 7, 6)])]),
    "jamba-tiny": (CFG.layer_kinds, [
        (2, [("mamba", 0, 2), ("attention", 0, 1), ("mamba", 2, 1)])]),
    "no-repeat": (("mamba", "attention", "mamba"), [
        (1, [("mamba", 0, 1), ("attention", 0, 1), ("mamba", 1, 1)])]),
    # patterns with no period of their own: repeated segments
    "phi4-mini-flash": (phi4flash.Phi4FlashConfig().layer_kinds, [
        (8, [("mamba", 0, 1), ("window", 0, 1)]),
        (1, [("mamba", 0, 1), ("full", 0, 1)]),
        (7, [("gmu", 0, 1), ("cross", 0, 1)])]),
    "phi4flash-tiny": (phi4flash.Phi4FlashConfig.tiny().layer_kinds, [
        (2, [("mamba", 0, 1), ("window", 0, 1)]),
        (1, [("mamba", 0, 1), ("full", 0, 1), ("gmu", 0, 1),
             ("cross", 0, 1)])]),
    "runs-then-period": (("a",) * 5 + ("b", "c") * 3 + ("a",), [
        (5, [("a", 0, 1)]), (3, [("b", 0, 1), ("c", 0, 1)]),
        (1, [("a", 0, 1)])]),
}


@pytest.mark.parametrize("name", sorted(PLANS))
def test_layer_plan_cuts_a_pattern_into_repeated_segments(name):
    kinds, want = PLANS[name]
    plan = jamba.layer_plan(tuple(kinds))
    assert [(repeats, runs) for repeats, runs, _ in plan] == want
    # walked, every layer comes once, in order, at its index in its kind
    walked = []
    jamba.scan_layers(
        kinds, lambda kind, i, c: walked.append(kind) or c + 1, 0)
    assert walked == [kind for _, runs, _ in plan for kind, _, _ in runs]
    seen, count = [], {}
    for kind in kinds:
        seen.append((kind, count.get(kind, 0)))
        count[kind] = count.get(kind, 0) + 1
    steps = []

    def body(kind, i, carry):
        jax.debug.callback(lambda i, kind=kind: steps.append((kind, int(i))),
                           i, ordered=True)
        return carry

    jax.block_until_ready(jamba.scan_layers(kinds, body, jnp.zeros(())))
    jax.effects_barrier()
    assert steps == seen
    # a part of the model's layers counts on from what came before it
    steps.clear()
    cut = len(kinds) // 2
    jax.block_until_ready(jamba.scan_layers(
        kinds[cut:], body, jnp.zeros(()),
        start={k: kinds[:cut].count(k) for k in set(kinds)}))
    jax.effects_barrier()
    assert steps == seen[cut:]


# ---- Llama and Mixtral emit what they emitted before the family table ----

# recorded on the parent commit (PR 26) by these lines: greedy in slot 0,
# temperature 0.8 / top-k 20 / top-p 0.9 in slot 1, both decoding together
BEFORE = {
    "llama": [[502, 312, 403, 265, 302, 270, 28, 180, 41, 358],
              [77, 432, 34, 338, 35, 338, 457, 440, 102, 290]],
    "mixtral": [[145, 134, 455, 145, 184, 399, 427, 147, 501, 402],
                [21, 386, 501, 203, 470, 475, 149, 341, 473, 67]],
}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_llama_and_mixtral_emit_the_tokens_they_emitted_before(name):
    mod = {"llama": llama, "mixtral": mixtral}[name]
    cfg = (llama.LlamaConfig if name == "llama"
           else mixtral.MixtralConfig).tiny()
    eng = SlotEngine(mod.init_params(jax.random.PRNGKey(0), cfg), cfg,
                     max_slots=2, max_seq_len=128, prefill_chunk=16)
    prompts = [((np.arange(n) * 37 + 11) % cfg.vocab_size).astype(np.int32)
               for n in (21, 40)]
    for slot, (p, temp) in enumerate(zip(prompts, (0.0, 0.8))):
        eng.admit(slot, p, 10, temperature=temp, top_k=20, top_p=0.9, rng=7)
    out = [[prefill(eng, 0)], [prefill(eng, 1)]]
    for _ in range(9):
        for slot, tok in eng.decode_step().items():
            out[slot].append(tok)
    assert out == BEFORE[name]
    assert set(eng._cache) == {"k", "v"} and not eng.recurrent


# ---- ops/ssm.py ----

def _ssm_inputs(B=2, T=12, Di=8, N=4, seed=0):
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)
    return dict(h=f(B, N, Di), u=f(B, T, Di),
                delta=jax.nn.softplus(f(B, T, Di)), A=-jnp.exp(f(N, Di)),
                Bm=f(B, T, N), Cm=f(B, T, N), D=f(Di))


def test_selective_scan_is_the_recurrence_written_out():
    x = _ssm_inputs()
    y, h = ssm.selective_scan(x["h"], x["u"], x["delta"], x["A"], x["Bm"],
                              x["Cm"], x["D"])
    want_h, want_y = np.asarray(x["h"], np.float64), []
    for t in range(12):
        d, u = np.asarray(x["delta"][:, t]), np.asarray(x["u"][:, t])
        want_h = np.exp(d[:, None] * np.asarray(x["A"])[None]) * want_h \
            + (d * u)[:, None] * np.asarray(x["Bm"][:, t])[:, :, None]
        want_y.append((want_h * np.asarray(x["Cm"][:, t])[:, :, None]).sum(1)
                      + np.asarray(x["D"]) * u)
    assert np.allclose(h, want_h, atol=1e-4)
    assert np.allclose(y, np.stack(want_y, 1), atol=1e-4)


@pytest.mark.parametrize("n_valid", [(12, 12), (5, 9), (0, 12), (1, 0)])
def test_state_and_tail_pass_through_what_is_not_valid(n_valid):
    x = _ssm_inputs(seed=1)
    valid = jnp.arange(12)[None] < jnp.asarray(n_valid)[:, None]
    _, h = ssm.selective_scan(x["h"], x["u"], x["delta"], x["A"], x["Bm"],
                              x["Cm"], x["D"], valid)
    w, b = jnp.ones((4, 8)) * 0.25, jnp.zeros(8)
    tail = x["u"][:, :3] * 2
    _, new_tail = ssm.causal_conv(x["u"], tail, w, b, valid)
    for row, n in enumerate(n_valid):
        one = {k: v[row:row + 1, :n] for k, v in x.items()
               if k in ("u", "delta", "Bm", "Cm")}
        _, want = ssm.selective_scan(x["h"][row:row + 1], one["u"],
                                     one["delta"], x["A"], one["Bm"],
                                     one["Cm"], x["D"]) if n else (
                                         None, x["h"][row:row + 1])
        assert np.allclose(h[row], want[0], atol=1e-6)
        window = jnp.concatenate([tail[row], x["u"][row, :n]])
        assert np.array_equal(new_tail[row], window[-3:])


def test_one_token_is_a_chunk_of_one_and_conv_chunks_are_the_whole():
    x = _ssm_inputs(T=1, seed=2)
    valid = jnp.asarray([[True], [False]])
    y, h = ssm.selective_step(x["h"], x["u"][:, 0], x["delta"][:, 0], x["A"],
                              x["Bm"][:, 0], x["Cm"][:, 0], x["D"],
                              valid[:, 0])
    y2, h2 = ssm.selective_scan(x["h"], x["u"], x["delta"], x["A"], x["Bm"],
                                x["Cm"], x["D"], valid)
    assert np.allclose(y, y2[:, 0]) and np.allclose(h, h2)
    assert np.array_equal(h[1], x["h"][1]) and not np.allclose(h[0], x["h"][0])
    u = _ssm_inputs(T=20, seed=3)["u"]
    w = jnp.asarray(np.random.default_rng(4).normal(size=(4, 8)), jnp.float32)
    b = jnp.arange(8.0)
    zero = jnp.zeros((2, 3, 8))
    whole, _ = ssm.causal_conv(u, zero, w, b)
    first, tail = ssm.causal_conv(u[:, :13], zero, w, b)
    tail1 = tail
    steps = []
    for t in range(13, 20):   # then a token at a time, one row masked
        out, tail = ssm.causal_conv(u[:, t:t + 1], tail, w, b, valid)
        steps.append(out)
    got = jnp.concatenate([first] + steps, axis=1)
    assert np.allclose(got[0], whole[0], atol=1e-5)
    assert np.array_equal(tail[1], tail1[1])
    # by hand: out_t = b + sum_k w[k] * u[t - 3 + k]
    assert np.allclose(whole[0, 5], b + sum(w[k] * u[0, 2 + k]
                                            for k in range(4)), atol=1e-5)


# ---- scope names and program names, as the benchmark's readers find them ----

SCOPES = {
    "jamba": (("decode_layers", "attn_qkv", "kv_cache_update",
               "decode_attention", "attn_out", "ffn", "ssm_in_proj",
               "ssm_conv", "ssm_x_proj", "ssm_out_proj"),
              {"decode": "ssm_state_update", "prefill": "ssm_scan"}),
    "brumby": (("decode_layers", "retention_qkvg", "retention_out", "ffn"),
               {"decode": "retention_update", "prefill": "retention_chunk"}),
    "phi4flash": (("decode_layers", "attn_qkv", "kv_cache_update",
                   "window_attention", "decode_attention", "cross_attention",
                   "diff_combine", "gmu", "attn_out", "ffn", "ssm_in_proj",
                   "ssm_conv", "ssm_x_proj", "ssm_out_proj"),
                  {"decode": "ssm_state_update", "prefill": "ssm_scan"}),
}


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_programs_keep_their_names_and_hold_every_scope(fam, engine,
                                                        program):
    import re

    scopes, by_program = SCOPES[fam.name]
    scopes += (by_program[program],)

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
    for scope in scopes:
        assert re.search(r'["/(]%s["/)]' % scope, text), scope
    other = by_program["prefill" if program == "decode" else "decode"]
    assert not re.search(r'["/(]%s["/)]' % other, text)
