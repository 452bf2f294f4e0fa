"""Ouro (a stack that is run `passes` times over the same weights)
through `decode_forward`, `SlotEngine` and `Scheduler`: what no other
family has. A pool has passes x layers indices and pass t of layer i
lives at t * layers + i; the layer loop goes round and the model's norm
closes every pass; a block norms its sublayers' outputs (sandwich norms);
an exit gate reads every pass's normed stream. Held against
benchmark/families/ouro.py, whose reference has no cache at all (every
pass a full causal forward), on logits and not tokens. Tiny sizes (2
layers, 3 passes, so that an index mistake between pass and layer cannot
cancel), float32."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import configs, reference
from benchmark.families import ouro as ref_family
from metaflow_tpu import goodput
from metaflow_tpu.cmd.serve import build_config
from metaflow_tpu.exception import TpuFlowException
from metaflow_tpu.inference import decode_forward, generate, init_kv_cache
from metaflow_tpu.inference.cache import cache_pools, stack_passes
from metaflow_tpu.inference.decode import (attention_reads, family,
                                           family_config_class, merges,
                                           pool_read)
from metaflow_tpu.models import llama, ouro
from metaflow_tpu.serving import (PagedEngine, PagePool, Request, Scheduler,
                                  SlotEngine)
from metaflow_tpu.serving.disagg import decode_handoff, encode_handoff

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = ouro.OuroConfig.tiny()   # hidden 128, 2 layers x 3 passes, 4 heads
L, PASSES = CFG.n_layers, CFG.passes
DIMS = dict(configs.dims(dict(configs.read_json(os.path.join(
    ROOT, "benchmark", "tests", "cells", "configs", "tiny-ouro.json")),
    torch_dtype="float32")))
PUBLISHED = configs.read_json(os.path.join(
    ROOT, "benchmark", "configs", "ouro-2.6b-serve.json"))
CHUNK = 16
# past 2 * DECODE_CHUNK positions: an engine that deep reads its pools in
# the chunk loop and its stack merges (`pool_read`); no argument says so
DEEP = 640
# float32 on both sides, the program's products at the backend's default
# precision and the reference's at `highest`, through passes x layers = 6
# blocks whose sandwich norms rescale every sublayer's output to unit
# size: rounding only, a few 1e-6 of logits of size 4; an index mistake
# between pass and layer moves them by 0.1 and more
TOL = dict(rtol=2e-4, atol=2e-4)


def prompt(n, salt=0):
    return ((np.arange(n) * 37 + 11 + 5 * salt) % 511 + 1).astype(np.int32)


@pytest.fixture(scope="module")
def params():
    """Seeded weights with norm weights that are not ones, so that a norm
    left out or misplaced shows."""
    tree = ouro.init_params(jax.random.PRNGKey(0), CFG)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 8))
    uneven = lambda a: a * jax.random.uniform(next(keys), a.shape, a.dtype,
                                              0.5, 1.5)
    layers = dict(tree["layers"])
    for name in ("attn_norm", "attn_post_norm", "ffn_norm", "ffn_post_norm"):
        layers[name] = uneven(layers[name])
    return dict(tree, layers=layers, final_norm=uneven(tree["final_norm"]))


def ref_logits(params, tokens):
    return np.asarray(reference.logits(params, np.asarray(tokens), DIMS))


# ---- the pool's index is pass and layer ----

def test_the_pool_has_passes_times_layers_indices():
    cache = init_kv_cache(CFG, 3, 64)
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (PASSES * L, 3, 64, CFG.n_kv_heads * CFG.head_dim),
        "v": (PASSES * L, 3, 64, CFG.n_kv_heads * CFG.head_dim)}
    assert stack_passes(CFG) == PASSES and merges(CFG)
    assert {n: c for n, (_, c) in cache_pools(CFG).items()} == {
        "k": PASSES * L, "v": PASSES * L}
    # a decode step reads every layer's pool once a pass
    assert attention_reads(CFG, cache)[0][0] == PASSES * L
    plain = llama.LlamaConfig.tiny()
    assert stack_passes(plain) == 1
    assert init_kv_cache(plain, 3, 64)["k"].shape[0] == plain.n_layers


def test_prefill_then_decode_is_the_reference_without_a_cache(params):
    """Prefill, then token by token through the pool at t * L + i, against
    a reference in which every pass is a full causal forward: logits."""
    tokens = prompt(40)
    want = ref_logits(params, tokens)
    step = jax.jit(lambda p, t, c, pos: decode_forward(
        p, t, c, pos, CFG, attn_impl="chunked"))
    cache = init_kv_cache(CFG, 1, 64)
    P = 23
    got, cache = step(params, jnp.asarray(tokens[:P])[None], cache, 0)
    outs = [np.asarray(got[0])]
    for t in range(P, len(tokens)):
        got, cache = step(params, jnp.asarray(tokens[t:t + 1])[None], cache,
                          jnp.asarray([t]))
        outs.append(np.asarray(got[0]))
    np.testing.assert_allclose(np.concatenate(outs), want, **TOL)
    dense = decode_forward(params, jnp.asarray(tokens)[None],
                           init_kv_cache(CFG, 1, 64), 0, CFG)[0]
    np.testing.assert_allclose(np.asarray(dense[0]), want, **TOL)


def test_pass_t_reads_pass_ts_keys_and_values(params):
    """Zeroing pool index 1 * L + i after the prefill changes the next
    step's logits, for every layer i of the second pass; so does every
    other index, and there is none past passes * L."""
    tokens = prompt(20)
    cache = decode_forward(params, jnp.asarray(tokens)[None],
                           init_kv_cache(CFG, 1, 32), 0, CFG)[1]
    nxt = lambda c: np.asarray(decode_forward(
        params, jnp.asarray([[7]]), c, jnp.asarray([20]), CFG,
        attn_impl="chunked")[0])
    base = nxt(cache)
    for index in range(PASSES * L):
        cut = {n: a.at[index, :, :20].set(0) for n, a in cache.items()}
        assert np.abs(nxt(cut) - base).max() > 1e-3, index
    assert cache["k"].shape[0] == PASSES * L   # no index L * passes


# ---- through the slot engine, rows in a merged step beside a lane ----

def test_rows_of_two_slots_ride_beside_a_decoding_lane(params):
    """`merges(cfg)` holds: a lane decodes while two slots' rows ride in
    its steps, a masked lane writing at each row's first position at
    every pass's pool index (PR 39's trap); every request's logits are
    the reference's and the pool is the two-program path's."""
    eng = SlotEngine(params, CFG, max_slots=3, max_seq_len=DEEP,
                     prefill_chunk=CHUNK)
    assert eng.merges and eng.passes == PASSES
    prompts = [prompt(21), prompt(37, 1), prompt(30, 2)]
    made = {s: [] for s in range(3)}

    def iterate(plan):
        if plan:
            eng.stage_rows(plan)
        for slot, tok in eng.decode_step().items():
            made[slot].append(tok)
        for (slot, _), (_, tok) in zip(plan, eng.row_results):
            if tok is not None:
                made[slot].append(tok)

    eng.admit(0, prompts[0], 12)
    iterate([(0, 2 * CHUNK)])                  # a row alone, no lane
    eng.admit(1, prompts[1], 12)
    eng.admit(2, prompts[2], 12)
    merged_beside_a_lane = 0
    while not (eng.decoding[1] and eng.decoding[2]):
        merged_beside_a_lane += bool(eng.decoding[0])
        iterate([(s, CHUNK) for s in (1, 2) if not eng.decoding[s]])
    assert merged_beside_a_lane >= 2
    while min(len(made[s]) for s in range(3)) < 6:
        iterate([])
    assert eng._prefill_fn._cache_size() == 0   # no prefill program ran
    for slot in range(3):
        # each served token is the reference's best at its position, or
        # within rounding of it (greedy, float32)
        seq = np.concatenate([prompts[slot], made[slot]]).astype(np.int32)
        ref = ref_logits(params, seq)[len(prompts[slot]) - 1:-1]
        gap = ref.max(-1) - ref[np.arange(len(made[slot])), made[slot]]
        assert gap.max() < 1e-3, (slot, gap)
    # the pool, at every pass's index, is what decode_forward writes for
    # the same tokens with no engine, no rows and no masked lane
    for slot in range(3):
        seq = np.concatenate([prompts[slot], made[slot]])[:eng.pos[slot]]
        alone = decode_forward(params, jnp.asarray(seq)[None],
                               init_kv_cache(CFG, 1, 96), 0, CFG)[1]
        for name in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(eng._cache[name])[:, slot, :len(seq)],
                np.asarray(alone[name])[:, 0, :len(seq)],
                rtol=1e-4, atol=1e-4)


def test_the_scheduler_serves_generates_tokens_and_counts_passes(
        params, tmp_path):
    from metaflow_tpu import telemetry
    from metaflow_tpu.datastore import FlowDataStore, LocalStorage

    eng = SlotEngine(params, CFG, max_slots=3, max_seq_len=DEEP,
                     prefill_chunk=CHUNK)
    fds = FlowDataStore("ServeLoop", LocalStorage, ds_root=str(tmp_path))
    telemetry.init_recorder(fds, "1", "_serve", "loop-test")
    try:
        sched = Scheduler(eng)
        reqs = [sched.submit(Request(prompt(n, i).tolist(),
                                     max_new_tokens=7, rng=i))
                for i, n in enumerate((33, 9, 50))]
        sched.run_until_idle(10_000)
    finally:
        telemetry.close_recorder()
    for req in reqs:
        want = np.asarray(generate(
            params, jnp.asarray(req.tokens)[None], CFG, 7))[0]
        want = want[len(req.tokens):]
        assert req.reason == "length" and req.generated == want.tolist()
    stats = sched.stats()
    assert stats["merged_steps"] > 0
    assert stats["weight_passes"] == PASSES * stats["decode_steps"] > 0
    text = goodput.render_openmetrics(
        goodput.scheduler_metric_families(stats))
    assert "tpuflow_serve_weight_passes_total %d" % stats["weight_passes"] \
        in text
    # the span (and its timer record) says the passes; a position is
    # counted once a reading layer and pass
    steps = [r["data"] for r in telemetry.read_run_records(fds, "1")
             if r["name"] == "serve.decode_step" and r.get("data")]
    assert len(steps) == stats["decode_steps"] == stats["steps_ahead"] + 1
    assert all(d["passes"] == PASSES for d in steps)
    assert stats["attention_positions_needed"] % (PASSES * L) == 0


def test_a_kv_range_carries_every_pass(params):
    """extract_kv, the handoff frame, seed_prefix and admit_prefilled take
    the pool's whole leading axis: a prefix seeded from another slot's
    range decodes the tokens a local prefill gives."""
    eng = SlotEngine(params, CFG, max_slots=2, max_seq_len=DEEP,
                     prefill_chunk=CHUNK)
    p = prompt(40)
    eng.admit(0, p, 6)
    first = None
    while first is None:
        _, first = eng.prefill_step(0)
    local = [first] + [eng.decode_step()[0] for _ in range(5)]
    kv = eng.extract_kv(0, len(p))
    assert kv["k"].shape == (PASSES * L, len(p), CFG.n_kv_heads,
                             CFG.head_dim)
    assert eng.kv_token_bytes() == 2 * PASSES * L * CFG.n_kv_heads \
        * CFG.head_dim * 4
    meta, wire = decode_handoff(encode_handoff({"first": int(first)}, kv))
    assert meta["first"] == first and wire["k"].shape == kv["k"].shape
    eng.admit_prefilled(1, p, first, wire, 6)
    handed = [first] + [eng.decode_step()[1] for _ in range(5)]
    assert handed == local
    eng.release(1)
    eng.admit(1, p, 6)
    eng.seed_prefix(1, {n: a[:, :24] for n, a in kv.items()})
    first = None
    while first is None:
        _, first = eng.prefill_step(1)
    assert [first] + [eng.decode_step()[1] for _ in range(5)] == local


# ---- what refuses, by the mechanism and not by a name ----

def test_what_lays_k_and_v_out_by_layers_refuses_a_looped_stack(params):
    with pytest.raises(TpuFlowException, match="run 3 times"):
        PagedEngine(params, CFG, max_slots=2, max_seq_len=64)
    with pytest.raises(TpuFlowException, match="passes"):
        PagePool(CFG, 8, 16)
    with pytest.raises(TpuFlowException, match="per-lane exit is not built"):
        ouro.OuroConfig.tiny(exit_threshold=0.9)
    with pytest.raises(ValueError, match="per-lane exit"):
        configs.dims(dict(PUBLISHED, early_exit_threshold=0.5))
    with pytest.raises(ValueError):
        configs.dims(dict(PUBLISHED, tie_word_embeddings=True))
    with pytest.raises(ValueError, match="sliding-window"):
        configs.dims(dict(PUBLISHED, sliding_window=4096))


def test_only_a_stack_of_attention_layers_goes_round():
    """What a recurrent state or a ring is from pass to pass is not
    defined: a config that declares `passes` beside any other kind of
    layer is refused where the passes are read, before a pool is built."""
    import dataclasses

    from metaflow_tpu.models import jamba
    looped = dataclasses.make_dataclass(
        "Looped", [("passes", int, 2)], bases=(jamba.JambaConfig,),
        frozen=True)
    cfg = looped(**dataclasses.asdict(jamba.JambaConfig.tiny()))
    for reads in (stack_passes, cache_pools,
                  lambda c: init_kv_cache(c, 2, 64)):
        with pytest.raises(TpuFlowException, match="attention layers alone"):
            reads(cfg)
    assert stack_passes(jamba.JambaConfig.tiny()) == 1


# ---- how a pool is read: a pool the decode kernel can block is chunked ----

@pytest.mark.parametrize("head_dim, depth, mesh, whole_pool, picks", [
    (128, 512, None, True, "chunked"),    # the published head and depth
    (128, 64, None, True, "chunked"),
    (32, 512, None, True, "dense"),       # no whole lanes: the kernel refuses
    (32, 640, None, True, "chunked"),     # past 2 * DECODE_CHUNK, as before
    (128, 512, "a mesh", True, "dense"),  # the kernel is one chip's
    (128, 512, None, False, "dense"),     # a depth alone, as generate() asks
])
def test_auto_attention_follows_what_the_kernel_takes(head_dim, depth, mesh,
                                                      whole_pool, picks):
    """A pool no deeper than 2 * DECODE_CHUNK was served dense whatever
    its shapes, so a 512-deep pool had no merged step and no kernel: the
    rule asks what the decode step's attention would be, where the caller
    is a decode step of whole pools (the slot engine; `generate()` and
    the paged engine, whose programs are never the kernel's, give a depth
    alone)."""
    cfg = ouro.OuroConfig.tiny(head_dim=head_dim, max_seq_len=1024,
                               dtype="bfloat16")
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 2, depth))
    picked = pool_read(depth, cfg, cache, mesh) if whole_pool \
        else pool_read(depth)
    assert picked == picks
    assert merges(cfg, mesh, picks) == (picks == "chunked")


# ---- one pass and no post norms is llama, bit for bit ----

def test_one_pass_without_post_norms_is_llamas_program(params):
    """With `passes=1` and the two post norms' leaves absent the loop adds
    nothing: the same lowered program as llama's but for the module's
    name, and the same bits out, prefill and decode step."""
    one = ouro.OuroConfig.tiny(passes=1)
    plain = llama.LlamaConfig.tiny(
        n_kv_heads=one.n_kv_heads, dim=one.dim, n_heads=one.n_heads,
        ffn_dim=one.ffn_dim, vocab_size=one.vocab_size, norm_eps=one.norm_eps,
        rope_theta=one.rope_theta, max_seq_len=one.max_seq_len)
    assert plain.head_dim == one.head_dim
    bare = {k: v for k, v in params.items() if not k.startswith("exit_gate")}
    bare["layers"] = {k: v for k, v in params["layers"].items()
                      if not k.endswith("post_norm")}
    tokens = jnp.asarray(prompt(24))[None]

    def programs(cfg):
        prefill = jax.jit(lambda p, c: decode_forward(
            p, tokens, c, 0, cfg, attn_impl="chunked"))
        step = jax.jit(lambda p, c: decode_forward(
            p, tokens[:, :1], c, jnp.asarray([24]), cfg,
            attn_impl="chunked"))
        return prefill, step

    cache = init_kv_cache(one, 1, 64)
    assert cache["k"].shape == init_kv_cache(plain, 1, 64)["k"].shape
    for mine, theirs in zip(programs(one), programs(plain)):
        text = lambda f: f.lower(bare, cache).as_text()
        assert text(mine) == text(theirs)
        for a, b in zip(jax.tree.leaves(mine(bare, cache)),
                        jax.tree.leaves(theirs(bare, cache))):
            assert np.array_equal(np.asarray(a), np.asarray(b))


# ---- the exit gate ----

def test_the_exit_cdf_is_the_references_and_ends_at_one(params):
    tokens = prompt(24)
    want = np.asarray(ref_family.exit_cdf(params, tokens, DIMS))
    _, _, got = decode_forward(params, jnp.asarray(tokens)[None],
                               init_kv_cache(CFG, 1, 32), 0, CFG, exits=True)
    assert got.shape == (PASSES, 1, 24)
    np.testing.assert_allclose(np.asarray(got[:, 0]), want, rtol=1e-4,
                               atol=1e-5)
    assert np.all(np.asarray(got[-1]) == 1.0)
    assert np.all(np.diff(np.asarray(got[:, 0]), axis=0) >= 0)
    assert 0 < float(got[0].min()) and float(got[PASSES - 2].max()) < 1
    _, whole = ouro.forward(params, jnp.asarray(tokens)[None], CFG,
                            exits=True)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(got),
                               rtol=1e-4, atol=1e-5)


# ---- the published configuration and `tpuflow serve --model ouro` ----

def test_the_published_configuration_maps_onto_the_program():
    d = configs.dims(PUBLISHED)
    assert (d["n_layers"], d["passes"], d["dim"], d["n_heads"],
            d["n_kv_heads"], d["head_dim"], d["ffn_dim"],
            d["vocab_size"]) == (48, 4, 2048, 16, 16, 128, 5632, 49152)
    assert PUBLISHED["reduced"] == {}
    module, cfg = configs.program_config(PUBLISHED, 512)
    assert module is ouro and family(cfg).name == "ouro" and merges(cfg)
    assert family_config_class("ouro") is ouro.OuroConfig
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 10, 512))
    assert cache["k"].shape == (4 * 48, 10, 512, 16 * 128)
    per_position = 2 * cache["k"].shape[0] * cache["k"].shape[3] * 2
    assert per_position == 1_572_864
    shapes = jax.eval_shape(lambda: ouro.init_params(
        jax.random.PRNGKey(0), cfg))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == 2_667_974_657
    assert ref_family.matmul_params(d, active_only=False) \
        == 48 * 51_380_224 + 2048 + 2048 * 49152
    assert ref_family.matmul_params(d) \
        == 4 * (48 * 51_380_224 + 2048) + 2048 * 49152
    # `tpuflow serve --model ouro --config-json ...`: dataclass fields
    served = build_config(None, config_json='{"n_layers": 2, "passes": 3, '
                          '"dim": 128, "n_heads": 4, "n_kv_heads": 4, '
                          '"head_dim": 32, "ffn_dim": 256}', model="ouro")
    assert isinstance(served, ouro.OuroConfig) and served.passes == 3
