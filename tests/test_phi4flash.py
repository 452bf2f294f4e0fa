"""What only Phi-4-mini-flash has (models/phi4flash.py through the slot
engine): window layers whose K and V live in a ring, ONE global K and V
pool that the layers after it read again, gated memory units over the
loop's carry, and a prefill program whose cross-decoder and head see each
row's last real position alone. Parity with the plain reference
(benchmark/families/phi4flash.py) on logits, with rings that wrap; each
mechanism left out is told apart; the ring's invariant by itself. What
the family shares with Jamba and Brumby (the recurrent-state pool, the
refusals by name, the scopes) is tests/test_jamba.py's, which runs for it
too. Tiny sizes, float32."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import configs, reference
from benchmark.families import phi4flash as ref
from metaflow_tpu import goodput
from metaflow_tpu.cmd.serve import build_config, build_engine
from metaflow_tpu.inference import decode_forward, init_kv_cache
from metaflow_tpu.inference.cache import _write_layer
from metaflow_tpu.inference.decode import _visible
from metaflow_tpu.models import phi4flash
from metaflow_tpu.serving import Request, Scheduler, SlotEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = phi4flash.Phi4FlashConfig.tiny()   # window 8; [m, w] x 2, [m, f], [g, c]
FILE = configs.read_json(os.path.join(
    ROOT, "benchmark", "tests", "cells", "configs", "tiny-phi4flash.json"))
DIMS = configs.dims(dict(FILE, torch_dtype="float32"))
CHUNK = 16            # rows of up to 32: the rings are 8 + 32 = 40 deep
A, B = 75, 45         # two requests, both longer than a ring
PAD = 80              # one shape for every call of the reference


def tokens(n, salt=0):
    return ((np.arange(n) * 37 + 11 + 5 * salt) % 255 + 1).astype(np.int32)


def ref_logits(params, toks):
    """The reference's logits of `toks`, run at one shape: what pads
    the sequence follows it, and every mask is causal."""
    padded = np.zeros(PAD, np.int32)
    padded[:len(toks)] = toks
    return reference.logits(params, padded, DIMS)[:len(toks)]


@pytest.fixture(scope="module")
def params():
    """The benchmark's seeded leaves: every bias and lambda vector is
    drawn, so none of them is a fixed point."""
    from benchmark import weights

    return jax.jit(lambda k: weights.init_params(k, DIMS))(
        weights.seed_key(33))


@pytest.fixture(scope="module")
def wants(params):
    """The reference's logits over the two whole sequences."""
    return {n: ref_logits(params, tokens(n, salt=n)) for n in (A, B)}


def close(got, want, scale):
    """Float32 on both sides, so the tolerance is rounding: 1e-4 of the
    largest logit (some 4: sums of a few hundred products of float32)."""
    return float(jnp.abs(got - want).max()) < 1e-4 * scale


def test_prefill_then_decode_through_the_engine_is_the_references_forward(
        params, wants):
    """Two requests through one SlotEngine: prompts prefilled as rows of
    two slots in one program (a whole row beside a padded one), then as a
    lone wider row; then teacher-forced decode steps, every lane at its
    own cursor, a free slot beside them as a masked lane, a third request
    prefilled into it (a padded row) while the others decode past the
    rings' depth. Every logit read (each prompt's last position, from the
    prefill program's tail; every decoded position) is the reference's
    full forward at that position."""
    eng = SlotEngine(params, CFG, max_slots=3, max_seq_len=128,
                     prefill_chunk=CHUNK)
    assert eng._cache["win_k"].shape == (2, 3, 8 + 2 * CHUNK, 32)
    assert eng._cache["k"].shape == (1, 3, 128, 32)
    seq = {0: tokens(A, salt=A), 2: tokens(B, salt=B)}
    prompt_len = {0: 50, 2: 21}
    scale = float(jnp.abs(wants[A]).max())
    tails, real = [], eng._prefill_fn

    def prefill_fn(*args):
        logits, cache = real(*args)
        tails.append(logits)
        return logits, cache

    eng._prefill_fn = prefill_fn
    for slot, n in prompt_len.items():
        eng.admit(slot, seq[slot][:n], 4)
    # rows of two slots; slot 2's second row holds 5 real tokens of 16
    assert eng.prefill([(0, CHUNK), (2, CHUNK)]) == [(16, None)] * 2
    (n0, f0), (n2, f2) = eng.prefill([(0, CHUNK), (2, CHUNK)])
    assert (n0, n2) == (16, 5) and f0 is None and f2 is not None
    assert tails[-1].shape == (2, 1, 256)       # one position a row
    assert close(tails[-1][1, 0], wants[B][20], scale)
    assert f2 == int(jnp.argmax(wants[B][20]))
    # a lone row of two chunks, 18 real tokens of 32
    (n0, f0), = eng.prefill([(0, 2 * CHUNK)])
    assert n0 == 18 and close(tails[-1][0, 0], wants[A][49], scale)

    step = jax.jit(lambda cache, tok, pos, mask: decode_forward(
        params, tok[:, None], cache, pos, CFG, attn_impl=eng.attn_impl,
        valid=mask[:, None]))
    pos = np.array([50, 0, 21], np.int32)
    mask = np.array([True, False, True])
    third = tokens(30, salt=9)
    for t in range(A - 50):
        if t == 4:    # a new occupant of the free slot, prefilled beside
            eng.admit(1, third, 4)
            eng.pos[:] = pos * mask   # the engine's cursors, for its rows
            assert eng.prefill([(1, 2 * CHUNK)])[0][0] == 30
            assert close(tails[-1][0, 0], ref_logits(params, third)[29],
                         scale)
        mask[2] = pos[2] < B
        tok = np.array([seq[0][pos[0]], 0, seq[2][min(pos[2], B - 1)]],
                       np.int32)
        logits, eng._cache = step(eng._cache, jnp.asarray(tok),
                                  jnp.asarray(pos), jnp.asarray(mask))
        assert close(logits[0, 0], wants[A][pos[0]], scale), t
        if mask[2]:
            assert close(logits[2, 0], wants[B][pos[2]], scale), t
        pos += mask
    assert pos[0] == A > 40 and pos[2] == B > 40    # both rings wrapped


def test_served_through_build_engine_and_scheduler(params):
    """`tpuflow serve --model phi4flash`'s path: the family by name, the
    engine from `build_engine`, requests through the scheduler, rows of
    two slots among its programs: each request's tokens are the
    reference's. (That each is what it emits alone in one lockstep
    `generate` is tests/test_jamba.py's, for this family too.)"""
    assert build_config({"cfg": {"dim": 64}}, model="phi4flash") == \
        phi4flash.Phi4FlashConfig(dim=64)
    eng = build_engine(params, CFG, slots=2, max_seq_len=128,
                       prefill_chunk=CHUNK)
    sched = Scheduler(eng).start()
    prompts = [tokens(n, salt=n) for n in (37, 21, 50)]
    reqs = [sched.submit(Request(p.tolist(), max_new_tokens=12,
                                 temperature=0.0, eos_id=None, rng=0))
            for p in prompts]
    got = [r.result(timeout=120) for r in reqs]
    stats = sched.stats()
    sched.stop()
    for p, served in zip(prompts, got):
        gaps = reference.served_gaps(params, p.tolist(), served, DIMS,
                                     pad_to=PAD)
        assert float(gaps.max()) <= 1e-4    # rounding: the best, or a tie
    assert stats["prefill_rows"] > stats["prefill_programs"]
    pools = eng.pool_stats()
    assert stats["cache_pools"] == pools
    families = {f.name: f for f in goodput.scheduler_metric_families(stats)}
    assert {s[1]["kind"]: s[2] for s in
            families["tpuflow_serve_cache_pool_bytes"].samples} == {
                kind: entry["bytes"] for kind, entry in pools.items()}
    assert pools["global"]["bytes"] == 2 * 1 * 2 * 128 * 32 * 4
    assert pools["ring"]["bytes_per_slot"] == 2 * 2 * 40 * 32 * 4
    assert pools["state"] == eng.state_pool_stats() and \
        pools["state"]["bytes"] == 3 * 2 * (3 + 16) * 128 * 4
    # a pool 32 wide is no shape of the kernel's and max_seq_len 128 is
    # the dense form: every lane's whole pool is fetched, two rings of 40
    # and the global pool twice (the full layer and the cross layer)
    steps = stats["decode_steps"]
    assert stats["attention_positions_fetched"] == \
        steps * 2 * (2 * 40 + 2 * 128)
    assert 0 < stats["attention_positions_needed"] \
        < stats["attention_positions_fetched"]
    assert {s[1]["count"]: s[2] for s in
            families["tpuflow_serve_attention_positions"].samples} == {
                "needed": stats["attention_positions_needed"],
                "fetched": stats["attention_positions_fetched"]}


def test_the_prefill_tail_changes_no_logit_and_no_cache(params):
    """With `last` the layers from the full layer's attention on run for
    one position a row: the logits there and the whole cache are those
    of the program that runs every layer over every position."""
    toks = jnp.asarray(np.stack([tokens(24), tokens(24, salt=3)]))
    last = jnp.asarray([23, 9])
    run = jax.jit(lambda last: decode_forward(
        params, toks, init_kv_cache(CFG, 2, 64, row=24), jnp.zeros(2, int),
        CFG, last=last))
    full, cache_full = run(None)
    tail, cache_tail = run(last)
    assert tail.shape == (2, 1, 256)
    assert float(jnp.abs(tail[:, 0] - full[jnp.arange(2), last]).max()) < 1e-5
    for name in cache_full:
        # two compiled programs: float32 rounding apart, no more
        assert np.allclose(cache_tail[name], cache_full[name],
                           atol=1e-5), name


@pytest.fixture(scope="module")
def whole(params):
    """One sequence of PAD tokens: the program's logits, through the
    cache in one program and as `models/phi4flash.forward` runs whole
    sequences."""
    toks = tokens(PAD, salt=7)
    got, _ = jax.jit(lambda t: decode_forward(
        params, t, init_kv_cache(CFG, 1, PAD), 0, CFG))(jnp.asarray(toks)[None])
    return toks, got[0], phi4flash.forward(params, jnp.asarray(toks)[None],
                                           CFG)[0]


def knocked_out(params, toks, knock):
    """The reference's logits with one mechanism left out, walked a
    block at a time as `ref.logits` walks them (and through its compiled
    blocks)."""
    blocks, top = ref._jitted(tuple(sorted(DIMS.items())), False)
    x = params["embed"][jnp.asarray(toks)].astype(jnp.float32)
    T = x.shape[0]
    memory = jnp.zeros((T, DIMS["d_inner"]))
    k = jnp.zeros((T, DIMS["n_kv_heads"], DIMS["head_dim"]))
    v = jnp.zeros((T, DIMS["n_kv_heads"] // 2, 2 * DIMS["head_dim"]))
    seen = {}
    for i, kind in enumerate(ref.layer_kinds(DIMS)):
        at = seen.get(kind, 0)
        seen[kind] = at + 1
        p = jax.tree.map(lambda a: a[at], params[kind + "_layers"])
        # a window layer that sees every position is the full layer's block
        run = "full" if (kind, knock) == ("window", "window") else kind
        x, m, k2, v2 = blocks[run](p, x, memory, k, v,
                                   0.8 - 0.6 * np.exp(-0.3 * i))
        if i == DIMS["n_layers"] // 2 and knock != "memory":
            memory = m
        if kind == "full" and knock != "shared":
            k, v = k2, v2
    return top(x, {"final_norm": params["final_norm"],
                   "final_norm_b": params["final_norm_b"]}, params["embed"],
               jnp.zeros((T, DIMS["vocab_size"])))


def test_the_forward_and_the_cache_path_are_the_reference(params, whole):
    toks, through_cache, forward = whole
    want = knocked_out(params, toks, None)
    assert np.allclose(want, ref_logits(params, toks), atol=1e-6)
    scale = float(jnp.abs(want).max())
    assert close(through_cache, want, scale) and close(forward, want, scale)


@pytest.mark.parametrize("knock", ["window", "shared", "memory"])
def test_each_mechanism_left_out_is_told_apart(params, whole, knock):
    """A reference whose window layers see everything, whose cross
    layers read no K and V of the full layer, or whose gated memory units
    read no memory differs from the program by hundreds of tolerances:
    the parity holds the program to each mechanism."""
    toks, got, _ = whole
    assert float(jnp.abs(got - knocked_out(params, toks, knock)).max()) \
        > 0.05 * float(jnp.abs(got).max())


@pytest.mark.parametrize("impl", ["dense", "chunked"])
def test_the_differential_loop_is_four_plain_calls(impl):
    """One streamed (or dense) pass over K and V, two key heads over one
    value head of twice the size, against the form it stands for: each
    of a pair's two maps a plain causal softmax of its own over the
    window, with V repeated for it."""
    from metaflow_tpu.inference.decode import (_cached_attention,
                                               _chunked_cached_attention)
    from metaflow_tpu.ops import diff_attention

    Bn, T, S, KV, Hd, H, W = 2, 3, 70, 4, 8, 8, 20
    r = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)
    q, ck, cv = f(Bn, T, H, Hd), f(Bn, S, KV, Hd), f(Bn, S, KV // 2, 2 * Hd)
    pos = jnp.asarray([5, S - T])
    qm = diff_attention.pair_major(q, KV)
    if impl == "dense":
        got = _cached_attention(qm, ck, cv, pos, window=W)
    else:
        fold = lambda a: a.reshape(1, Bn, S, -1)
        got = _chunked_cached_attention(qm, fold(ck), fold(cv), pos, 0,
                                        chunk=32, window=W, v_head_dim=2 * Hd)
    q_pos = np.asarray(pos)[:, None] + np.arange(T)[None]
    idx = np.arange(S)[None, None]
    mask = (idx <= q_pos[..., None]) & (idx > q_pos[..., None] - W)
    for p in range(H // 2):          # a query pair, its two maps
        for j in range(2):
            c = p // 2               # its KV pair
            logits = np.einsum("btd,bsd->bts", q[:, :, 2 * p + j],
                               ck[:, :, 2 * c + j]) / np.sqrt(Hd)
            probs = np.asarray(jax.nn.softmax(
                jnp.where(mask, logits, -np.inf), -1))
            want = np.einsum("bts,bsd->btd", probs, cv[:, :, c])
            # pair-major: (KV pair, j, query pair of the group)
            at = c * 4 + j * 2 + p % 2
            assert np.allclose(got[:, :, at], want, atol=2e-5), (p, j)


# ---- the decode step's attention kernel (ops/decode_attention.py) ----

@pytest.mark.parametrize("kind", ["global", "ring"])
def test_the_decode_kernel_reads_a_differential_pool_as_the_loop_does(kind):
    """Key heads of 64 under value heads of 128, float32 out for the
    subtraction, interpreted on XLA:CPU against the chunk loop on the
    same pools: the global pool read at index 0 by a traced index, and a
    window of 24 over a ring of 48 with lanes whose ring has wrapped
    (positions 100 and 48), is about to (47) and has not (0, 20), a lane
    that does not decode among them. A pair's two queries lie in the two
    halves of one row against the pair's 128 lanes of K as stored."""
    from metaflow_tpu.inference.decode import _chunked_cached_attention
    from metaflow_tpu.ops import decode_attention as da
    from metaflow_tpu.ops import diff_attention

    ring = kind == "ring"
    Bn, H, KV, Hd, S, block = 6, 8, 4, 64, (48 if ring else 96), 16
    kw = dict(v_head_dim=2 * Hd, window=24 if ring else None, ring=ring,
              dtype=jnp.float32)
    r = np.random.default_rng(1)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.bfloat16)
    q = diff_attention.pair_major(f(Bn, 1, H, Hd), KV)
    pk, pv = f(2, Bn, S, KV * Hd), f(2, Bn, S, KV * Hd)
    pos = jnp.asarray([100, 0, 47, 48, 20, 7] if ring
                      else [95, 0, 15, 16, 17, 40])
    valid = jnp.asarray([True, True, True, True, True, False])
    assert da.applies(q, pk, pv, 2 * Hd)
    layer = jnp.int32(1 if ring else 0)
    got = jax.jit(lambda q, pk, pv, layer: da.attend(
        q, pk, pv, pos, layer, *da.live_lanes(valid), valid, block=block,
        interpret=True, **kw))(q, pk, pv, layer)
    want = _chunked_cached_attention(q, pk, pv, pos, layer, chunk=block,
                                     **kw)
    assert got.shape == (Bn, 1, H, 2 * Hd) and got.dtype == jnp.float32
    assert np.allclose(got[:5], want[:5], atol=2e-2, rtol=2e-2)
    assert (np.asarray(got[5]) == 0).all()
    depth = np.where(valid, np.minimum(np.asarray(pos) + 1, S), 0)
    assert np.asarray(da.fetched_positions(depth, block, S)).tolist() == (
        [48, 16, 48, 48, 32, 0] if ring else [96, 16, 16, 32, 32, 0])


def test_a_decode_step_through_the_kernel_is_the_loops(monkeypatch):
    """One decode step of the whole stack at widths the kernel takes
    (key heads of 64, a pool 128 lanes wide), the kernel interpreted in
    the platform's place: window layers over rings that have wrapped and
    one that has not, the full layer, and the cross layer that reads
    index 0 of a pool it does not write, a masked lane among the live
    ones. Logits and pools of the lanes that decode are the chunk
    loop's; a prefill program's rows keep the loop."""
    from metaflow_tpu.inference import decode
    from metaflow_tpu.ops import decode_attention as da

    cfg = phi4flash.Phi4FlashConfig.tiny(dim=256, n_heads=4, n_kv_heads=2)
    assert (cfg.head_dim, cfg.v_head_dim) == (64, 128)
    params = phi4flash.init_params(jax.random.PRNGKey(0), cfg)
    cache = init_kv_cache(cfg, 4, 64, row=16)
    assert cache["win_k"].shape == (2, 4, 24, 128)
    keys = jax.random.split(jax.random.PRNGKey(1), len(cache))
    cache = {name: jax.random.normal(key, cache[name].shape,
                                     cache[name].dtype)
             for key, name in zip(keys, sorted(cache))}
    tok = jnp.asarray([[3], [5], [7], [9]])
    pos, mask = jnp.asarray([40, 63, 5, 30]), jnp.asarray(
        [True, True, True, False])
    step = lambda: jax.jit(lambda cache: decode_forward(
        params, tok, cache, pos, cfg, attn_impl="chunked",
        valid=mask[:, None]))(cache)
    want, want_cache = step()

    calls, kernel = [], da.attend

    def attend(*args, **kw):
        calls.append(kw["ring"])
        return kernel(*args, interpret=True, **kw)

    monkeypatch.setattr(decode.decode_attention, "attend", attend)
    # the Mamba layers' state update is a kernel of the platform's too
    monkeypatch.setattr(decode.ssm, "_selective_pool_kernel",
                        functools.partial(decode.ssm._selective_pool_kernel,
                                          interpret=True))
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    got, got_cache = step()
    assert sorted(calls) == [False, False, True]   # one a traced body
    # the counter: two rings of 24 and a global pool of 64 read twice, in
    # blocks of 24 and 64; the masked lane fetches nothing
    depth = np.where(mask, np.asarray(pos) + 1, 0)
    reads = decode.attention_reads(cfg, cache)
    assert [r[3:] for r in reads] == [(64, "kernel")] * 2 + [(24, "kernel")]
    assert decode.attention_positions(reads, depth) == (
        2 * (8 + 8 + 6) + 2 * (41 + 64 + 6), 2 * 3 * 24 + 2 * 3 * 64)
    assert decode.attention_positions(
        decode.attention_reads(cfg, cache, kernel=False), depth) == (
        2 * (8 + 8 + 6) + 2 * (41 + 64 + 6), 2 * 4 * 24 + 2 * 4 * 64)
    assert np.allclose(got[:3], want[:3], atol=1e-4, rtol=1e-4)
    for name in cache:   # the masked lane writes what it likes at its cursor
        assert np.allclose(got_cache[name][:, :3], want_cache[name][:, :3],
                           atol=1e-4, rtol=1e-4), name
    # rows of a prefill program (`slots`) and several positions a row
    del calls[:]
    jax.jit(lambda cache: decode_forward(
        params, jnp.tile(tok[:2], (1, 8)), cache, pos[:2], cfg,
        attn_impl="chunked", slots=jnp.asarray([2, 0]),
        last=jnp.asarray([7, 3])))(cache)
    assert not calls


# ---- the ring ----

@pytest.mark.parametrize("window,ring", [(8, 40), (8, 9), (5, 64)])
def test_a_rings_index_is_the_one_position_a_query_can_mean(window, ring):
    """Of the positions a query at q sees, (q - window, q] from 0 on,
    each lies on its own index p % ring, and no other index is seen."""
    for q in (0, 3, window - 1, ring - 1, ring, 3 * ring + 2):
        seen = np.asarray(_visible(jnp.arange(ring), jnp.asarray(q),
                                   window, ring))
        want = np.zeros(ring, bool)
        want[[p % ring for p in range(max(0, q - window + 1), q + 1)]] = True
        assert np.array_equal(seen, want), q


def test_garbage_in_a_ring_is_overwritten_before_it_is_seen():
    """The engine's invariant for a ring of window + widest row, with no
    mask on its writes and no reset: rows padded to the program's width,
    a masked lane's write at its cursor and a new occupant over the last
    one's keys. Every key a query sees holds its own position's value."""
    window, row, S = 8, 16, 8 + 16
    pool = jnp.full((1, 2, S, 1), -7.0)      # what an earlier occupant left
    r = np.random.default_rng(1)
    cursor, junk = np.zeros(2, int), 1000.0
    for _ in range(60):
        n_real = r.integers(0, row + 1, size=2)
        width = row if r.random() < 0.7 else 1     # a chunk, or a step
        n_real = np.minimum(n_real, width)
        # a position's value is the position; padding writes junk
        new = np.where(np.arange(width)[None] < n_real[:, None],
                       cursor[:, None] + np.arange(width)[None], junk)
        junk += 1
        pool = _write_layer(pool, jnp.asarray(new, jnp.float32)[..., None,
                                                              None],
                            jnp.asarray(cursor), 0, ring=True)
        for b in range(2):
            for t in range(n_real[b]):
                q = cursor[b] + t
                seen = np.asarray(_visible(jnp.arange(S), jnp.asarray(q),
                                           window, S))
                held = np.asarray(pool[0, b, :, 0])[seen]
                assert sorted(held) == list(
                    range(max(0, q - window + 1), q + 1)), (b, q)
        cursor += n_real


def test_a_new_occupant_starts_from_an_empty_state_and_a_blind_ring(params):
    """A slot that a long request left (rings full of its keys, a Mamba
    state, K and V in the global pool) serves a short prompt as a slot
    that held nothing does."""
    eng = SlotEngine(params, CFG, max_slots=2, max_seq_len=128,
                     prefill_chunk=CHUNK)

    def serve(slot, p, n):
        eng.admit(slot, p, n)
        out = []
        while not out:
            first = eng.prefill_step(slot)[1]
            out += [] if first is None else [first]
        while len(out) < n:
            out.append(eng.decode_step()[slot])
        eng.release(slot)
        return out

    serve(0, tokens(60, salt=1), 20)     # 80 positions: the rings wrapped
    assert float(jnp.abs(eng._cache["win_k"][:, 0]).min()) > 0
    short = tokens(5, salt=2)
    assert serve(0, short, 6) == serve(1, short, 6)


def test_a_budget_wider_than_the_ring_allows_is_refused(params):
    eng = SlotEngine(params, CFG, max_slots=2, max_seq_len=128,
                     prefill_chunk=CHUNK)
    assert eng.prefill_shapes(2 * CHUNK) == [(1, 16), (1, 32), (2, 16)]
    with pytest.raises(ValueError, match="wider than the 32"):
        Scheduler(eng, prefill_budget=3 * CHUNK)


def test_the_published_configuration_is_uncut():
    published = configs.read_json(os.path.join(
        ROOT, "benchmark", "configs", "phi4-mini-flash-serve.json"))
    assert published["reduced"] == {}
    cfg = configs.program_config(published, 4096)[1]
    assert cfg == phi4flash.Phi4FlashConfig(max_seq_len=4096)
    kinds = cfg.layer_kinds
    assert [kinds.count(k) for k in ("mamba", "window", "full", "cross",
                                     "gmu")] == [9, 8, 1, 7, 7]
    assert kinds[16] == "mamba" and kinds[17] == "full" and \
        cfg.memory_layer == 8 and cfg.tail_layer == 17
    n = sum(int(np.prod(s)) for s, _ in phi4flash.leaf_shapes(cfg).values())
    d = configs.dims(published)
    assert 3.852e9 < n < 3.854e9 and ref.matmul_params(d) < n
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 64, 4096, row=128))
    assert cache["win_k"].shape == (8, 64, 640, 1280)
    assert cache["k"].shape == cache["v"].shape == (1, 64, 4096, 1280)
