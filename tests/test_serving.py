"""Continuous-batching serving engine: token identity with lockstep
generate, mid-flight slot admission/reclaim (no lockstep), cancellation/
deadline/backpressure, SIGTERM drain, the HTTP API with streaming, and
the pinned serving telemetry schema."""

import http.client
import json
import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from metaflow_tpu.inference import generate
from metaflow_tpu.models import llama, mixtral
from metaflow_tpu.serving import (
    CapacityError,
    QueueFullError,
    Request,
    Scheduler,
    ServingServer,
    SlotEngine,
)


# past 2 * DECODE_CHUNK positions: an engine that deep reads its pools in
# the chunk loop, as a deployment's does, and a stack of attention layers
# merges (inference/decode.py, `pool_read`); no argument says so
DEEP = 640


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def engine(setup):
    """ONE engine for the module: its compiled programs are shared;
    every test drains its requests, so slots come back free. Warmed so
    latency-sensitive tests (deadline) never race a compile."""
    cfg, params = setup
    eng = SlotEngine(params, cfg, max_slots=4, max_seq_len=128,
                     prefill_chunk=16)
    warm = Scheduler(eng)
    warm.submit(Request(list(range(1, 20)), max_new_tokens=2,
                        temperature=0.5))
    warm.run_until_idle(10_000)
    return eng


def _ref_tokens(params, cfg, req):
    """What single-request lockstep generate() emits for this request,
    trimmed at eos the way the engine reports it."""
    out = generate(params, jnp.asarray(req.tokens)[None], cfg,
                   req.max_new_tokens, temperature=req.temperature,
                   top_k=req.top_k, top_p=req.top_p, eos_id=req.eos_id,
                   rng=jax.random.PRNGKey(req.rng))
    new = np.asarray(out)[0, len(req.tokens):].tolist()
    if req.eos_id is not None and req.eos_id in new:
        new = new[:new.index(req.eos_id) + 1]
    return new


class TestTokenIdentity:
    def test_greedy_identical_to_generate(self, setup, engine):
        """Any request through the engine == single-request generate,
        bit-exact, across prompt lengths spanning 1..several prefill
        chunks while slots interleave (the acceptance pin)."""
        cfg, params = setup
        sched = Scheduler(engine)
        rng = np.random.default_rng(0)
        reqs = []
        for i, plen in enumerate([3, 16, 17, 40, 90, 7, 33, 64]):
            toks = rng.integers(0, cfg.vocab_size, plen).tolist()
            reqs.append(sched.submit(Request(
                toks, max_new_tokens=int(rng.integers(1, 12)), rng=i)))
        sched.run_until_idle(max_iterations=10_000)
        for req in reqs:
            assert req.reason == "length"
            assert req.generated == _ref_tokens(params, cfg, req), \
                "slot output diverged from lockstep generate"

    def test_sampled_identical_to_generate(self, setup, engine):
        """Same rng policy as generate (request_step_keys mirrors its
        split sequence) -> the sampled path is token-identical too."""
        cfg, params = setup
        sched = Scheduler(engine)
        reqs = []
        for i, (tk, tp) in enumerate([(None, None), (20, None),
                                      (None, 0.9), (20, 0.9)]):
            toks = list(range(5 + i, 25 + i))
            reqs.append(sched.submit(Request(
                toks, max_new_tokens=6, temperature=0.8, top_k=tk,
                top_p=tp, rng=100 + i)))
        sched.run_until_idle(max_iterations=10_000)
        for req in reqs:
            assert req.generated == _ref_tokens(params, cfg, req)

    def test_chunked_attn_identical_with_per_slot_positions(self, setup):
        """The flash-decode path under a per-slot position VECTOR (its
        traced trip count runs to the deepest slot; shallower slots mask
        the extra chunks) — token-identical to dense lockstep."""
        cfg, params = setup
        eng = SlotEngine(params, cfg, max_slots=3, max_seq_len=DEEP,
                         prefill_chunk=16)
        assert eng.attn_impl == "chunked"
        sched = Scheduler(eng)
        rng = np.random.default_rng(2)
        reqs = []
        for i, plen in enumerate([90, 5, 33]):  # very different depths
            toks = rng.integers(0, cfg.vocab_size, plen).tolist()
            reqs.append(sched.submit(Request(toks, max_new_tokens=8,
                                             rng=i)))
        sched.run_until_idle(max_iterations=10_000)
        for req in reqs:
            assert req.generated == _ref_tokens(params, cfg, req)

    def test_eos_frees_slot_early(self, setup, engine):
        cfg, params = setup
        # whatever greedy emits first becomes the eos id: the request
        # must finish at 1 generated token, not max_new
        probe = Scheduler(engine)
        r0 = probe.submit(Request(list(range(1, 9)), max_new_tokens=1))
        probe.run_until_idle(10_000)
        eos = r0.generated[0]
        sched = Scheduler(engine)
        req = sched.submit(Request(list(range(1, 9)), max_new_tokens=10,
                                   eos_id=eos))
        sched.run_until_idle(10_000)
        assert req.reason == "eos"
        assert req.generated == [eos]
        assert engine.free_slots() == list(range(engine.max_slots))

    def test_one_compile_per_program(self, engine):
        """The engine's compiled-program budget: prompt-length diversity
        must not grow the jit caches past the bucket count."""
        counts = engine.compile_counts()
        assert counts["decode_greedy"] <= 1
        assert counts["decode_sampled"] <= 1
        # prefill chunk buckets: powers of two up to prefill_chunk
        assert counts["prefill"] <= 3


# ---- one prefill program an iteration (PR 30) ----

CHUNK = 16          # prefill_chunk of the engines below; the budget is 2
FAMILIES = {"llama": (llama, llama.LlamaConfig),
            "mixtral": (mixtral, mixtral.MixtralConfig)}


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def family_engine(request):
    """(cfg, params, engine, programs) of a tiny model of each family:
    three slots, chunks of 16, float32; `programs` counts the
    executions of the engine's prefill program."""
    mod, config = FAMILIES[request.param]
    cfg = config.tiny()
    params = mod.init_params(jax.random.PRNGKey(0), cfg)
    eng = SlotEngine(params, cfg, max_slots=3, max_seq_len=128,
                     prefill_chunk=CHUNK)
    programs = _count_calls(eng, "_prefill_fn")
    return cfg, params, eng, programs


def _count_calls(obj, name):
    """Wrap obj.<name> so that calls[0] counts its calls."""
    real, calls = getattr(obj, name), [0]

    def counted(*args, **kw):
        calls[0] += 1
        return real(*args, **kw)

    counted._cache_size = real._cache_size
    setattr(obj, name, counted)
    return calls


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).tolist() for n in lengths]


class TestOnePrefillProgramAnIteration:
    @pytest.mark.parametrize("lengths,rows", [
        # two slots a program; 21 ends mid-chunk (5 real of 16) beside a
        # full row of the other
        ((21, 40), [(16, 16), (5, 16), (8,)]),
        # a lone slot takes a row of 2 x chunk, then what is left
        ((70,), [(32,), (32,), (6,)]),
        # three admitted in one iteration, two rows a program, round
        # robin; 32 and 16 end exactly on a chunk's edge
        ((32, 16, 9), [(16, 16), (16, 9)]),
    ])
    def test_uneven_prompts_admitted_together_emit_generates_tokens(
            self, family_engine, lengths, rows):
        cfg, params, eng, programs = family_engine
        sched = Scheduler(eng)
        plans = []
        real = eng.collect_prefill
        eng.collect_prefill = lambda: plans.append(real()) or plans[-1]
        try:
            reqs = [sched.submit(Request(p, max_new_tokens=6, rng=i))
                    for i, p in enumerate(_prompts(cfg, lengths))]
            sched.run_until_idle(10_000)
        finally:
            eng.collect_prefill = real
        for req in reqs:
            assert req.generated == _ref_tokens(params, cfg, req)
        # the tokens each program's rows consumed, program by program
        assert [tuple(n for n, _ in plan) for plan in plans] == rows

    def test_at_most_the_budget_and_one_program_an_iteration(
            self, family_engine):
        cfg, params, eng, programs = family_engine
        sched = Scheduler(eng)
        lengths = (90, 3, 40, 17, 64, 33)
        reqs = [sched.submit(Request(p, max_new_tokens=3, rng=i))
                for i, p in enumerate(_prompts(cfg, lengths, seed=1))]
        seen, ran_before = [], programs[0]
        while sched.pending():
            before = (programs[0], sched.prefill_tokens)
            sched.step()
            ran, tokens = (programs[0] - before[0],
                           sched.prefill_tokens - before[1])
            assert ran <= 1 and tokens <= sched.prefill_budget
            assert (ran == 0) == (tokens == 0)
            seen.append(tokens)
        # the budget is used whole where prompts are long enough
        assert max(seen) == sched.prefill_budget == 2 * CHUNK
        stats = sched.stats()
        assert stats["prefill_programs"] == programs[0] - ran_before > 0
        assert stats["prefill_tokens"] == sum(lengths)
        assert stats["prefill_programs"] <= stats["prefill_rows"] \
            <= 2 * stats["prefill_programs"]
        assert all(r.reason == "length" for r in reqs)

    def test_a_long_prompt_cannot_starve_a_short_one(self, family_engine):
        cfg, params, eng, _ = family_engine
        sched = Scheduler(eng)
        long_a, long_b, short = [
            sched.submit(Request(p, max_new_tokens=2, rng=i))
            for i, p in enumerate(_prompts(cfg, (100, 100, 9), seed=2))]
        for _ in range(2):   # three prefilling slots, two rows a program
            sched.step()
        assert short.generated and not long_a.generated \
            and not long_b.generated
        sched.run_until_idle(10_000)

    def test_no_slot_twice_in_a_program(self, family_engine):
        cfg, params, eng, programs = family_engine
        eng.admit(0, list(range(1, 40)), 2)
        before = programs[0]
        with pytest.raises(ValueError, match="distinct slots"):
            eng.prefill([(0, CHUNK), (0, CHUNK)])
        with pytest.raises(ValueError, match="not prefilling"):
            eng.prefill([(0, CHUNK), (1, CHUNK)])
        assert programs[0] == before and eng._prefill_cursor[0] == 0
        eng.release(0)

    def test_twenty_prompt_lengths_compile_nothing(self, family_engine):
        """Every prefill shape is compiled when the scheduler is built,
        none by a request."""
        cfg, params, eng, _ = family_engine
        sched = Scheduler(eng)
        built = eng.compile_counts()
        assert eng.prefill_shapes(sched.prefill_budget) == [
            (1, CHUNK), (1, 2 * CHUNK), (2, CHUNK)]
        assert built["prefill"] == built["first_token"] == 3
        lengths = [1, 2, 5, 15, 16, 17, 20, 31, 32, 33, 40, 47, 48, 49,
                   63, 64, 65, 80, 96, 100]
        reqs = [sched.submit(Request(p, max_new_tokens=2, rng=i))
                for i, p in enumerate(_prompts(cfg, lengths, seed=3))]
        sched.run_until_idle(10_000)
        assert all(r.reason == "length" for r in reqs)
        after = eng.compile_counts()
        assert (after["prefill"], after["first_token"]) == (3, 3)
        assert after["decode_greedy"] == 1 and after["decode_sampled"] == 0
        # a scheduler with a wider budget asks for its own, bounded, set
        assert eng.prefill_shapes(4 * CHUNK) == [
            (1, 16), (1, 32), (1, 48), (1, 64), (2, 16), (2, 32), (3, 16)]
        assert eng.prefill_shapes(1) == [(1, CHUNK)]


# ---- the prefill rows ride in the decode step (PR 39) ----

PROGRAMS = ("_decode_greedy_fn", "_decode_sampled_fn", "_prefill_fn",
            "_first_fn")


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def merging_engine(request):
    """(cfg, params, engine, calls) of a tiny model of each family whose
    stack merges (attention layers, one chip, the chunk loop): three
    slots, chunks of 16, float32; `calls[name][0]` counts the executions
    of each of the engine's programs."""
    mod, config = FAMILIES[request.param]
    cfg = config.tiny()
    params = mod.init_params(jax.random.PRNGKey(0), cfg)
    eng = SlotEngine(params, cfg, max_slots=3, max_seq_len=DEEP,
                     prefill_chunk=CHUNK)
    assert eng.merges
    return cfg, params, eng, {name: _count_calls(eng, name)
                              for name in PROGRAMS}


def _ran(calls):
    return {name: count[0] for name, count in calls.items()}


def _pool_at_real_positions(eng):
    """{(pool, slot): its K or V at every position before the slot's
    cursor}, of the slots that hold a request."""
    return {(name, slot): np.asarray(eng._cache[name])[:, slot,
                                                       :eng.pos[slot]]
            for name in ("k", "v") for slot in range(eng.max_slots)
            if eng.active[slot]}


class TestRowsRideInTheDecodeStep:
    def test_admitted_while_others_decode_emit_generates_tokens(
            self, merging_engine):
        """Requests that arrive while others decode prefill inside those
        others' decode steps, ONE program an iteration, and every request
        emits generate()'s tokens; no prefill or first-token program runs
        at all."""
        cfg, params, eng, calls = merging_engine
        sched = Scheduler(eng)
        before = _ran(calls)
        prompts = _prompts(cfg, (40, 21, 70, 9, 33, 16), seed=4)
        reqs = [sched.submit(Request(p, max_new_tokens=9, rng=i))
                for i, p in enumerate(prompts[:2])]
        merged_with_lanes = 0
        while sched.pending():
            if sched.iteration in (3, 5, 8, 9) and len(reqs) < len(prompts):
                reqs.append(sched.submit(Request(
                    prompts[len(reqs)], max_new_tokens=9, rng=len(reqs))))
            ran, steps = _ran(calls), sched.merged_steps
            decoding = int(eng.decoding.sum())
            sched.step()
            assert sum(_ran(calls).values()) - sum(ran.values()) <= 1
            merged_with_lanes += bool(
                sched.merged_steps - steps and decoding)
        assert len(reqs) == len(prompts) and merged_with_lanes >= 4
        for req in reqs:
            assert req.reason == "length"
            assert req.generated == _ref_tokens(params, cfg, req)
        after = _ran(calls)
        assert after["_prefill_fn"] == before["_prefill_fn"]
        assert after["_first_fn"] == before["_first_fn"]
        stats = sched.stats()
        assert stats["merged_steps"] == stats["prefill_programs"] > 0
        # every iteration launches a step but the last, which collects
        # the one in flight; every launch but the first is made ahead
        assert stats["decode_steps"] == after["_decode_greedy_fn"] \
            - before["_decode_greedy_fn"] == stats["iterations"] - 1 \
            == stats["steps_ahead"] + 1
        assert stats["prefill_tokens"] == sum(map(len, prompts))

    def test_the_pool_is_the_two_program_paths_at_every_real_position(
            self, merging_engine):
        """The same admissions through merged steps and, on an engine of
        the same weights, through a prefill program and a decode step an
        iteration: once both have made the same tokens, K and V of every
        slot agree at every real position."""
        cfg, params, eng, _ = merging_engine
        two = SlotEngine(params, cfg, max_slots=3, max_seq_len=DEEP,
                         prefill_chunk=CHUNK)
        prompts = _prompts(cfg, (21, 50, 37), seed=5)
        made = {e: {s: [] for s in range(3)} for e in (eng, two)}

        def iterate(e, plan):
            """One iteration: a request's first token comes before its
            decode step's on the two-program path, where a row that ends
            decodes in the same iteration."""
            def firsts(results):
                for (slot, _), (_, tok) in zip(plan, results):
                    if tok is not None:
                        made[e][slot].append(tok)

            if plan and e is two:
                firsts(e.prefill(plan))
            elif plan:
                e.stage_rows(plan)
            for slot, tok in e.decode_step().items():
                made[e][slot].append(tok)
            if e is eng:
                firsts(e.row_results)

        for e in (eng, two):
            e.admit(0, prompts[0], 12)
            iterate(e, [(0, 2 * CHUNK)])            # a row alone, no lane
            e.admit(1, prompts[1], 12)
            e.admit(2, prompts[2], 12)
            while not (e.decoding[1] and e.decoding[2]):
                iterate(e, [(s, CHUNK) for s in (1, 2)
                            if not e.decoding[s]])
            while min(len(made[e][s]) for s in range(3)) < 6:
                iterate(e, [])
        assert not two.merges or two._decode_greedy_fn._cache_size() == 1
        for slot in range(3):
            n = min(len(made[eng][slot]), len(made[two][slot]))
            assert n >= 6
            assert made[eng][slot][:n] == made[two][slot][:n]
        mine, theirs = (_pool_at_real_positions(e) for e in (eng, two))
        for key in mine:
            n = min(mine[key].shape[1], theirs[key].shape[1])
            assert n >= len(prompts[key[1]]) + 5
            np.testing.assert_allclose(mine[key][:, :n], theirs[key][:, :n],
                                       rtol=1e-5, atol=1e-5)
        for e in (eng, two):
            for slot in range(3):
                e.release(slot)

    def test_a_rows_write_wins_over_its_slots_masked_lane(
            self, merging_engine):
        """A slot mid-prefill is also a masked lane of the step, and its
        lane writes K and V at its cursor: the position its row's first
        token is written to in the same execution. The row's must be what
        stays."""
        from metaflow_tpu.inference import decode_forward, init_kv_cache

        cfg, params, _, _ = merging_engine
        cache = init_kv_cache(cfg, 3, 64)
        start, W = 5, CHUNK
        row = jnp.asarray(_prompts(cfg, (W,), seed=6))
        # lane 1 is the row's slot, masked, with its cursor at the row's
        # start and a token that is not the row's first
        tok = jnp.asarray([[7], [int(row[0, 0]) + 1], [9]])
        pos = jnp.asarray([3, start, 0])
        valid = jnp.asarray([[True], [False], [False]])
        slots, starts = jnp.asarray([1]), jnp.asarray([start])
        kw = dict(attn_impl="chunked")
        logits, merged = decode_forward(
            params, tok, cache, pos, cfg, valid=valid,
            rows=(row, slots, starts, jnp.asarray([W - 1])), **kw)
        lane_logits, two = decode_forward(params, tok, cache, pos, cfg,
                                          valid=valid, **kw)
        row_logits, two = decode_forward(params, row, two, starts, cfg,
                                         slots=slots, **kw)
        assert logits.shape == (3 + 1, 1, cfg.vocab_size)
        np.testing.assert_allclose(logits[0], lane_logits[0], rtol=1e-4,
                                   atol=1e-4)
        np.testing.assert_allclose(logits[3, 0], row_logits[0, -1],
                                   rtol=1e-4, atol=1e-4)
        for name in ("k", "v"):
            for slot, upto in ((0, 4), (1, start + W)):
                np.testing.assert_allclose(
                    merged[name][:, slot, :upto], two[name][:, slot, :upto],
                    rtol=1e-5, atol=1e-5)
            # and not the lane's: the two differ at that position
            lanes_only = decode_forward(params, tok, cache, pos, cfg,
                                        valid=valid, **kw)[1]
            assert not np.allclose(lanes_only[name][:, 1, start],
                                   merged[name][:, 1, start])

    def test_twenty_prompt_lengths_compile_nothing(self, merging_engine):
        """The merged step's shapes are compiled when the scheduler is
        built, in place of the prefill and first-token programs, none by
        a request."""
        cfg, params, eng, _ = merging_engine
        sched = Scheduler(eng)
        built = eng.compile_counts()
        # the three shapes of `prefill_shapes`, and the decode-only step
        # that earlier tests of the module ran
        assert built["decode_greedy"] == 4
        assert built["prefill"] == built["first_token"] == 0
        lengths = [1, 2, 5, 15, 16, 17, 20, 31, 32, 33, 40, 47, 48, 49,
                   63, 64, 65, 80, 96, 100]
        reqs = [sched.submit(Request(p, max_new_tokens=2, rng=i))
                for i, p in enumerate(_prompts(cfg, lengths, seed=3))]
        sched.run_until_idle(10_000)
        assert all(r.reason == "length" for r in reqs)
        assert eng.compile_counts() == built
        for req in reqs[3::10]:
            assert req.generated == _ref_tokens(params, cfg, req)

    def test_a_row_alone_with_no_lane_decoding(self, merging_engine):
        cfg, params, eng, calls = merging_engine
        sched = Scheduler(eng)
        before = _ran(calls)
        req = sched.submit(Request(_prompts(cfg, (37,), seed=7)[0],
                                   max_new_tokens=4))
        held = []
        while sched.pending():
            sched.step()
            held.append(sched._recent[-1][2:4])   # (lanes, rows)
        # 32 + 5 tokens with no lane decoding, then three decode steps
        # launched (the first token came from the second program's row,
        # and its lane decodes in the very next launch), then the
        # iteration that collects the last of them
        assert held == [(0, 1), (0, 1), (1, 0), (1, 0), (1, 0), (0, 0)]
        assert req.generated == _ref_tokens(params, cfg, req)
        assert _ran(calls)["_decode_greedy_fn"] \
            - before["_decode_greedy_fn"] == len(held) - 1 \
            == sched.stats()["decode_steps"]
        assert sched.stats()["merged_steps"] == 2

    @pytest.mark.parametrize("first,then", [(0.8, 0.0), (0.0, 0.8)],
                             ids=["sampled_lanes_greedy_rows",
                                  "greedy_lanes_sampled_rows"])
    def test_sampled_and_greedy_requests_side_by_side(
            self, merging_engine, first, then):
        """A sampled lane or a sampled row's first token takes the
        sampled step for that execution; each request's tokens are
        generate()'s with its own knobs and keys."""
        cfg, params, eng, calls = merging_engine
        sched = Scheduler(eng)
        prompts = _prompts(cfg, (12, 40), seed=8)
        early = sched.submit(Request(prompts[0], max_new_tokens=14,
                                     temperature=first, top_k=20, rng=11))
        for _ in range(3):
            sched.step()
        assert early.state == "decode"
        before = _ran(calls)
        late = [sched.submit(Request(p, max_new_tokens=5, temperature=then,
                                     top_p=0.9, rng=12 + i))
                for i, p in enumerate(prompts[1:])]
        sched.run_until_idle(10_000)
        for req in [early] + late:
            assert req.generated == _ref_tokens(params, cfg, req)
        after = _ran(calls)
        assert after["_decode_sampled_fn"] > before["_decode_sampled_fn"]
        assert after["_first_fn"] == before["_first_fn"]
        assert eng.compile_counts()["decode_sampled"] <= 4

    def test_a_stack_that_does_not_merge_keeps_its_two_programs(
            self, family_engine):
        cfg, params, eng, _ = family_engine   # dense attention: no merge
        assert not eng.merges
        eng.admit(0, list(range(1, 20)), 2)
        with pytest.raises(ValueError, match="does not merge"):
            eng.stage_rows([(0, CHUNK)])
        eng.release(0)


# ---- sampling keys drawn at first use, not at admit (PR 42) ----

@pytest.fixture(scope="module")
def chunked_engine(setup):
    """A tiny Llama engine whose stack merges (the chunk loop)."""
    cfg, params = setup
    eng = SlotEngine(params, cfg, max_slots=3, max_seq_len=DEEP,
                     prefill_chunk=CHUNK)
    assert eng.merges
    return eng


def _refuse_keys(monkeypatch):
    """Any draw of a key schedule fails, by either of its names."""
    from metaflow_tpu.serving import engine as engine_module

    def refuse(*args, **kw):
        raise AssertionError("a greedy request drew sampling keys")

    monkeypatch.setattr(engine_module, "request_step_keys", refuse)
    monkeypatch.setattr(jax.random, "split", refuse)


class TestKeySchedules:
    """A slot's key schedule is drawn the first time a key of it is asked
    for, and nothing asks for a greedy request's: `key_schedules` and the
    span `engine.admit.keys` count the draws."""

    @pytest.mark.parametrize("case", [
        "greedy-merged", "greedy-two-programs", "sampled-row-merged",
        "sampled-row-two-programs", "sampled-admit-prefilled-merged",
        "sampled-admit-prefilled-two-programs"])
    def test_keys_are_drawn_when_a_sampled_token_is_first_asked_for(
            self, setup, engine, chunked_engine, monkeypatch, case):
        cfg, params = setup
        eng = chunked_engine if case.endswith("merged") else engine
        assert eng.merges == case.endswith("merged")
        drawn, sched = eng.key_schedules, Scheduler(eng)
        prompts = _prompts(cfg, (12, 23, 40), seed=42)
        if case.startswith("greedy"):
            # admitted, prefilled and decoded to their ends with no draw
            _refuse_keys(monkeypatch)
            reqs = [sched.submit(Request(p, max_new_tokens=3 + 4 * i,
                                         rng=i))
                    for i, p in enumerate(prompts)]
            sched.run_until_idle(10_000)
            monkeypatch.undo()
            sampled = []
        else:
            knobs = dict(max_new_tokens=6, temperature=0.8, top_k=20,
                         rng=77)
            if "admit-prefilled" in case:
                # prefilled elsewhere: the handoff's own admission draws
                # a schedule for the first token, this one for the rest
                pre = sched.submit(Request(prompts[2], prefill_only=True,
                                           **knobs))
                sched.run_until_idle(10_000)
                assert eng.key_schedules == drawn + 1
                drawn, sched = drawn + 1, Scheduler(eng)
                knobs["prefilled"] = pre.handoff
            # greedy lanes decode; a sampled request joins them in a step
            reqs = [sched.submit(Request(p, max_new_tokens=16, rng=i))
                    for i, p in enumerate(prompts[:2])]
            while not all(r.state == "decode" for r in reqs):
                sched.step()
            assert eng.key_schedules == drawn
            sampled = [sched.submit(Request(prompts[2], **knobs))]
            lanes = []
            while sampled[0].state != "finished":
                sched.step()
                lanes.append(sched._recent[-1][2])
            assert max(lanes) == 3   # beside the greedy lanes, one step
            reqs += sampled
            sched.run_until_idle(10_000)
        for req in reqs:
            assert req.reason == "length"
            assert req.generated == _ref_tokens(params, cfg, req)
        assert eng.key_schedules - drawn == len(sampled) \
            == sched.stats()["key_schedules"] - drawn
        assert sched.stats()["admitted"] == len(reqs)
        # the span moves with the draw: once a SAMPLED request
        assert sched.phases.calls.get("engine.admit.keys", 0) \
            == len(sampled)


class TestContinuousBatching:
    def test_mid_flight_admission_no_lockstep(self, setup, engine):
        """More requests than slots, mixed lengths: later requests must
        be ADMITTED while earlier ones are still decoding — i.e. some
        admission happens after some finish, with others in flight."""
        cfg, params = setup
        sched = Scheduler(engine)
        rng = np.random.default_rng(1)
        reqs = []
        for i in range(12):
            plen = int(rng.integers(3, 40))
            n = 3 if i % 3 else 20
            reqs.append(sched.submit(Request(
                rng.integers(0, cfg.vocab_size, plen).tolist(),
                max_new_tokens=n, rng=i)))
        sched.run_until_idle(max_iterations=10_000)
        admits = [r.admit_iteration for r in reqs]
        finishes = [r.finish_iteration for r in reqs]
        assert all(r.reason == "length" for r in reqs)
        # lockstep would admit everything before anything finishes (or
        # in non-overlapping waves); continuous batching refills slots
        # mid-flight: some admission strictly between the first and the
        # last finish
        assert max(admits) > min(finishes)
        assert max(admits) < max(finishes)
        # and outputs still match lockstep generate exactly
        for req in reqs[:4]:
            assert req.generated == _ref_tokens(params, cfg, req)

    def test_occupancy_tracked(self, engine):
        sched = Scheduler(engine)
        for i in range(6):
            sched.submit(Request(list(range(1, 10)), max_new_tokens=8,
                                 rng=i))
        sched.run_until_idle(10_000)
        stats = sched.stats()
        assert stats["decode_steps"] > 0
        assert 0.0 < stats["mean_batch_occupancy"] <= 1.0


class TestCancellationDeadlines:
    def test_cancel_in_flight_frees_slot(self, setup, engine):
        cfg, params = setup
        sched = Scheduler(engine)
        victim = sched.submit(Request(list(range(1, 20)),
                                      max_new_tokens=100, rng=0))
        other = sched.submit(Request(list(range(1, 10)),
                                     max_new_tokens=4, rng=1))
        # a few iterations: both admitted and decoding
        for _ in range(6):
            sched.step()
        assert victim.state in ("prefill", "decode")
        sched.cancel(victim.id)
        sched.run_until_idle(10_000)
        assert victim.reason == "cancelled"
        assert other.reason == "length"
        assert engine.free_slots() == list(range(engine.max_slots))

    def test_deadline_frees_slot(self, engine):
        sched = Scheduler(engine)
        req = sched.submit(Request(list(range(1, 20)),
                                   max_new_tokens=100,
                                   deadline=time.time() + 3600))
        # let it get properly in flight (deterministic on any box), then
        # expire the deadline mid-generation
        t0 = time.time()
        while not req.generated and time.time() - t0 < 60:
            sched.step()
        assert req.generated, "request never started decoding"
        req.deadline = time.time() - 0.001
        while req.reason is None and time.time() - t0 < 60:
            sched.step()
        assert req.reason == "deadline"
        assert len(req.generated) < 100  # cut off mid-generation
        assert engine.free_slots() == list(range(engine.max_slots))

    def test_queued_request_cancel(self, engine):
        """Cancelling a request that never reached a slot."""
        sched = Scheduler(engine)
        reqs = [sched.submit(Request(list(range(1, 10)),
                                     max_new_tokens=30, rng=i))
                for i in range(engine.max_slots + 2)]
        last = reqs[-1]
        sched.cancel(last.id)
        sched.run_until_idle(10_000)
        assert last.reason == "cancelled"
        assert last.generated == []
        assert all(r.reason == "length" for r in reqs[:-1])

    def test_cancel_mid_prefill_frees_slot_once(self, engine):
        """Cancel while the prompt is still prefilling (no token out
        yet): the slot comes back exactly once and no masked lane
        leaks into later decode batches."""
        sched = Scheduler(engine)
        victim = sched.submit(Request(list(range(1, 61)),
                                      max_new_tokens=50, rng=0))
        sched.step()  # admit + first prefill chunks (budget < prompt)
        assert victim.state == "prefill"
        sched.cancel(victim.id)
        sched.run_until_idle(10_000)
        assert victim.reason == "cancelled"
        assert victim.generated == []
        assert engine.free_slots() == list(range(engine.max_slots))
        assert engine.occupancy() == 0.0
        # the stream got exactly one terminal sentinel
        assert list(victim.stream(timeout=1)) == []
        assert victim.out.qsize() == 0

    def test_deadline_expires_mid_prefill(self, engine):
        sched = Scheduler(engine)
        req = sched.submit(Request(list(range(1, 61)),
                                   max_new_tokens=50,
                                   deadline=time.time() + 3600))
        sched.step()
        assert req.state == "prefill"
        req.deadline = time.time() - 0.001
        sched.run_until_idle(10_000)
        assert req.reason == "deadline"
        assert req.generated == []
        assert engine.free_slots() == list(range(engine.max_slots))
        assert engine.occupancy() == 0.0

    def test_cancel_between_reap_and_admit(self, engine):
        """The reap->admit race: a request cancelled (or expired) after
        _reap scanned the queue but before _admit pops it must finish
        WITHOUT taking a slot. Calling _admit directly (no prior reap)
        models the race window deterministically."""
        sched = Scheduler(engine)
        victim = sched.submit(Request(list(range(1, 10)),
                                      max_new_tokens=5))
        expired = sched.submit(Request(list(range(1, 10)),
                                       max_new_tokens=5,
                                       deadline=time.time() - 1))
        survivor = sched.submit(Request(list(range(1, 10)),
                                        max_new_tokens=2, rng=1))
        victim.cancel()  # flag set; _reap has NOT seen it
        admitted = sched._admit()
        assert admitted == 1, "only the survivor may take a slot"
        assert victim.reason == "cancelled" and victim.slot is None
        assert expired.reason == "deadline" and expired.slot is None
        sched.run_until_idle(10_000)
        assert survivor.reason == "length"
        assert engine.free_slots() == list(range(engine.max_slots))
        # each corpse's stream carries exactly one terminal sentinel
        for corpse in (victim, expired):
            assert list(corpse.stream(timeout=1)) == []
            assert corpse.out.qsize() == 0

    def test_finish_idempotent_single_release(self, engine):
        """Finishing the same request twice (cancel racing a deadline)
        must release its slot exactly once — a second release would
        free the slot's NEXT occupant mid-generation."""
        sched = Scheduler(engine)
        a = sched.submit(Request(list(range(1, 20)),
                                 max_new_tokens=100, rng=0))
        for _ in range(4):
            sched.step()
        assert a.state in ("prefill", "decode")
        sched._finish(a, "cancelled")
        # the freed slot is immediately re-admitted to b ...
        b = sched.submit(Request(list(range(1, 10)),
                                 max_new_tokens=30, rng=1))
        sched.step()
        assert b.slot is not None
        # ... so the racing second finish must be a no-op
        sched._finish(a, "deadline")
        assert a.reason == "cancelled"  # first terminal reason wins
        sched.run_until_idle(10_000)
        assert b.reason == "length"
        assert engine.free_slots() == list(range(engine.max_slots))
        # a's stream: tokens delivered before the cancel, then EXACTLY
        # one terminal sentinel (a second would confuse a reader
        # blocked on the stream of a reused Request object)
        drained = []
        while not a.out.empty():
            drained.append(a.out.get())
        assert drained.count(None) == 1 and drained[-1] is None

    def test_backpressure(self, engine):
        sched = Scheduler(engine, max_queue=2)
        sched.submit(Request([1, 2, 3], max_new_tokens=2))
        sched.submit(Request([1, 2, 3], max_new_tokens=2))
        with pytest.raises(QueueFullError):
            sched.submit(Request([1, 2, 3], max_new_tokens=2))
        sched.run_until_idle(10_000)

    def test_oversized_request_rejected_not_served(self, engine):
        # admission-time capacity check: a request that can NEVER fit
        # is rejected AT SUBMIT (CapacityError -> HTTP 413), before it
        # ever queues or reaches a slot
        sched = Scheduler(engine)
        with pytest.raises(CapacityError):
            sched.submit(Request(list(range(1, 50)),
                                 max_new_tokens=500))  # > max_seq_len
        assert sched.pending() == 0
        assert engine.free_slots() == list(range(engine.max_slots))


def _post(port, payload, timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", "/v1/generate", json.dumps(payload),
                 {"Content-Type": "application/json"})
    return conn, conn.getresponse()


class TestHTTPServer:
    @pytest.fixture()
    def server(self, engine):
        srv = ServingServer(Scheduler(engine), port=0).start()
        yield srv
        srv.close()

    def test_round_trip(self, setup, server):
        cfg, params = setup
        conn, resp = _post(server.port, {
            "tokens": list(range(1, 9)), "max_new_tokens": 5, "seed": 3})
        assert resp.status == 200
        body = json.loads(resp.read())
        req = Request(list(range(1, 9)), max_new_tokens=5, rng=3)
        assert body["new_tokens"] == _ref_tokens(params, cfg, req)
        assert body["reason"] == "length"
        assert body["usage"] == {"prompt_tokens": 8, "new_tokens": 5}
        conn.close()

    def test_streaming(self, server):
        conn, resp = _post(server.port, {
            "tokens": list(range(1, 9)), "max_new_tokens": 6,
            "stream": True})
        assert resp.status == 200
        lines = [json.loads(l) for l in iter(resp.readline, b"")]
        assert [l["index"] for l in lines[:-1]] == list(range(6))
        assert lines[-1]["done"] and lines[-1]["reason"] == "length"
        assert lines[-1]["new_tokens"] == [l["token"] for l in lines[:-1]]
        conn.close()

    def test_healthz_stats_and_errors(self, server):
        from schema_validate import validate_healthz

        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=30)
        conn.request("GET", "/healthz")
        body = json.loads(conn.getresponse().read())
        # /healthz is the probe surface both a load balancer and the
        # fleet router key on: shape pinned in schema_validate.py
        validate_healthz(body)
        assert body["ok"] is True and body["draining"] is False
        assert body["slots"] == 4
        assert body["queue_depth"] == 0 and body["in_flight"] == 0
        conn.request("GET", "/v1/stats")
        stats = json.loads(conn.getresponse().read())
        assert stats["slots"] == 4
        conn.request("POST", "/v1/generate", json.dumps({"tokens": []}))
        assert conn.getresponse().status == 400
        conn.close()

    def test_streamed_rejection_is_413(self, server):
        """An oversized request must be refused BEFORE streaming starts
        — 413 (admission capacity check) with Retry-After, not 200 with
        the error buried in the tail."""
        conn, resp = _post(server.port, {
            "tokens": list(range(1, 60)), "max_new_tokens": 500,
            "stream": True})
        assert resp.status == 413
        assert resp.getheader("Retry-After") is not None
        assert "error" in json.loads(resp.read())
        conn.close()

    def test_sigterm_drains_in_flight(self, setup, engine):
        """SIGTERM mid-generation: the in-flight stream runs to
        completion, new work is refused, the listener closes."""
        srv = ServingServer(Scheduler(engine), port=0)
        old = {sig: signal.getsignal(sig)
               for sig in (signal.SIGTERM, signal.SIGINT)}
        try:
            srv.install_signal_handlers()
            srv.start()
            conn, resp = _post(srv.port, {
                "tokens": list(range(1, 20)), "max_new_tokens": 40,
                "stream": True})
            first = json.loads(resp.readline())
            assert first["index"] == 0
            os.kill(os.getpid(), signal.SIGTERM)
            lines = [json.loads(l) for l in iter(resp.readline, b"")]
            assert lines[-1]["done"] and lines[-1]["reason"] == "length"
            assert len(lines[-1]["new_tokens"]) == 40  # all 40 arrived
            conn.close()
            # the listener is gone (or refusing) after the drain
            deadline = time.time() + 30
            refused = False
            while time.time() < deadline and not refused:
                try:
                    c2 = http.client.HTTPConnection(
                        "127.0.0.1", srv.port, timeout=2)
                    c2.request("GET", "/healthz")
                    body = json.loads(c2.getresponse().read())
                    assert body["draining"] is True
                    c2.close()
                    time.sleep(0.05)
                except (ConnectionRefusedError, OSError):
                    refused = True
            assert refused
        finally:
            for sig, handler in old.items():
                signal.signal(sig, handler)


class TestServingTelemetry:
    def test_lifecycle_records_match_pinned_schema(self, engine,
                                                   tmp_path):
        """Every serve.* record the scheduler emits validates against
        the pinned schema, and the full lifecycle is present."""
        from schema_validate import (
            SERVING_EVENT_DATA_SCHEMAS,
            validate_serving_record,
        )

        from metaflow_tpu import telemetry
        from metaflow_tpu.datastore import FlowDataStore, LocalStorage

        fds = FlowDataStore("ServeTelemetry", LocalStorage,
                            ds_root=str(tmp_path))
        telemetry.init_recorder(fds, "1", "_serve", "server-test")
        try:
            sched = Scheduler(engine)
            reqs = [sched.submit(Request(list(range(1, 20)),
                                         max_new_tokens=6, rng=i))
                    for i in range(6)]
            victim = sched.submit(Request(list(range(1, 9)),
                                          max_new_tokens=100))
            for _ in range(4):
                sched.step()
            sched.cancel(victim.id)
            sched.run_until_idle(10_000)
            assert all(r.reason == "length" for r in reqs)
        finally:
            telemetry.close_recorder()
        records = telemetry.read_run_records(fds, "1")
        serve = [r for r in records if r["name"].startswith("serve.")]
        assert serve, "no serving telemetry landed"
        for rec in serve:
            validate_serving_record(rec)
        names = {r["name"] for r in serve}
        for lifecycle in SERVING_EVENT_DATA_SCHEMAS:
            if lifecycle.startswith("serve.prefix."):
                # prefix-cache events need an armed cache; pinned in
                # test_prefix_serving.py
                continue
            if lifecycle.startswith("serve.kv."):
                # page-pool events need a paged engine; pinned in
                # test_paged_serving.py
                continue
            if lifecycle.startswith("serve.tenant."):
                # tenant events need tenancy; pinned in test_tenancy.py
                continue
            assert lifecycle in names, "missing %s" % lifecycle
        assert "serve.batch_occupancy" in names
        assert "serve.decode_step" in names
        # TTFT rides the first_token + finished events
        firsts = [r for r in serve
                  if r["name"] == "serve.request.first_token"]
        assert all(r["data"]["ttft_ms"] >= 0 for r in firsts)


class TestPhaseLedger:
    """The serving loop's own account of its time: stats()["phases"],
    ["slow_iterations"], ["gc"]."""

    INNER = ("serve.reap", "serve.admit", "serve.prefill_chunk",
             "serve.decode_step", "serve.deliver",
             "engine.first_token.fetch")
    NESTED = {"serve.prefill_chunk": ("engine.admit.keys",
                                      "engine.prefill.dispatch"),
              "serve.decode_step": ("engine.decode.upload",
                                    "engine.decode.dispatch",
                                    "engine.decode.fetch")}

    def test_the_ledger_accounts_for_the_loop_as_the_counters_do(
            self, engine, tmp_path):
        from metaflow_tpu import telemetry
        from metaflow_tpu.datastore import FlowDataStore, LocalStorage

        fds = FlowDataStore("ServePhases", LocalStorage,
                            ds_root=str(tmp_path))
        telemetry.init_recorder(fds, "1", "_serve", "phases-test")
        try:
            sched = Scheduler(engine)
            assert engine.phases is sched.phases
            drawn = engine.key_schedules
            # every other request sampled: those draw their keys, once
            # each, when their first token is asked for
            reqs = [sched.submit(Request(list(range(1, 9 + 7 * i)),
                                         max_new_tokens=6, rng=i,
                                         temperature=0.7 * (i % 2)))
                    for i in range(6)]
            sched.run_until_idle(10_000)
            assert all(r.reason == "length" for r in reqs)
        finally:
            telemetry.close_recorder()
        stats = sched.stats()
        phases = stats["phases"]
        took = {n: p["seconds"] for n, p in phases["phase"].items()}
        calls = {n: p["calls"] for n, p in phases["phase"].items()}
        # the phases nest: none sums to more than what holds it
        assert sum(took[n] for n in self.INNER) <= took["serve.iteration"]
        for outer, inner in self.NESTED.items():
            assert sum(took[n] for n in inner) <= took[outer]
        assert calls["serve.iteration"] == calls["serve.reap"] \
            == calls["serve.admit"] == phases["iterations"] \
            == stats["iterations"]
        # a step is launched in one iteration and collected in the next:
        # an iteration with no lane left to launch (the last, at least)
        # holds a span with a fetch and no launch
        assert calls["serve.decode_step"] > calls["serve.deliver"] \
            == calls["engine.decode.dispatch"] \
            == calls["engine.decode.fetch"] == stats["decode_steps"] \
            > stats["steps_ahead"] > 0
        assert calls["serve.prefill_chunk"] \
            == calls["engine.prefill.dispatch"] == stats["prefill_programs"]
        assert calls["engine.first_token.fetch"] <= len(reqs)
        assert calls["engine.admit.keys"] == len(reqs) // 2 \
            == stats["key_schedules"] - drawn
        assert stats["admitted"] == len(reqs)
        assert engine.launches >= stats["decode_steps"] \
            + stats["prefill_programs"]
        # no thread ran: no sleep, no loop time, no collector's callback
        assert "wait" not in took and phases["loop_s"] is None
        assert phases["no_work_s"] == 0 and stats["gc"] == {}
        assert phases["device_wait_s"] == pytest.approx(
            took["engine.decode.fetch"] + took["engine.first_token.fetch"],
            abs=1e-5)
        assert phases["device_wait_s"] + phases["host_work_s"] \
            == pytest.approx(took["serve.iteration"], abs=1e-5)
        # what the parent computed: the two timers' seconds, summed, and
        # the steps counted (a record rounds its milliseconds to three
        # places)
        records = telemetry.read_run_records(fds, "1")
        for name, busy in (("serve.decode_step", sched.busy_decode_s),
                           ("serve.prefill_chunk", sched.busy_prefill_s)):
            mine = [r for r in records if r["name"] == name]
            count = len(mine)
            assert count == calls[name] > 0
            assert busy * 1e3 == pytest.approx(
                sum(r["ms"] for r in mine), abs=1e-3 * count)
        assert stats["goodput"]["serve_decode_s"] == round(
            sched.busy_decode_s, 3)
        steps = [r["data"] for r in records
                 if r["name"] == "serve.decode_step" and r.get("data")]
        assert len(steps) == stats["decode_steps"]
        assert sum(d["positions_needed"] for d in steps) \
            == stats["attention_positions_needed"]
        assert sum(d["positions_fetched"] for d in steps) \
            == stats["attention_positions_fetched"]
        # one pass through the weights a step, for every stack that
        # declares no `passes`
        assert all(d["passes"] == 1 for d in steps)
        assert stats["weight_passes"] == stats["decode_steps"]

    def test_the_slowest_iterations_phase_by_phase(self, engine):
        sched = Scheduler(engine)
        reqs = [sched.submit(Request(list(range(1, 30)), max_new_tokens=4,
                                     rng=i)) for i in range(3)]
        n = sched.run_until_idle(10_000)
        slow = sched.stats()["slow_iterations"]
        assert len(slow) == min(3, n)
        assert [s["ms"] for s in slow] \
            == sorted((s["ms"] for s in slow), reverse=True)
        recent = list(sched._recent)
        assert [r[0] for r in recent] == list(range(n))
        assert slow[0]["ms"] == pytest.approx(max(
            r[1]["serve.iteration"] for r in recent) * 1e3, abs=1e-3)
        for s in slow:
            assert s["phase_ms"]["serve.iteration"] == s["ms"]
            assert sum(s["phase_ms"].get(p, 0.0) for p in self.INNER) \
                <= s["ms"] + 1e-3
            assert 0 <= s["lanes"] <= engine.max_slots and s["gc_ms"] == {}
        assert sum(r[2] for r in recent) == 3 * len(reqs)   # lanes
        assert sum(r[4] for r in recent) == 29 * len(reqs)  # prompt tokens
        assert sum(r[5] for r in recent) == len(reqs)       # admitted

    @pytest.mark.parametrize("end", ["stop", "drain"])
    def test_the_collector_is_watched_while_the_loop_runs_only(
            self, engine, end):
        import gc

        alone = Scheduler(engine)
        want = alone.submit(Request(list(range(1, 20)), max_new_tokens=6))
        alone.run_until_idle(10_000)
        found = list(gc.callbacks)
        sched = Scheduler(engine).start()
        try:
            assert gc.callbacks == found + [sched._on_gc]
            req = sched.submit(Request(list(range(1, 20)),
                                       max_new_tokens=6))
            # the same tokens with the callback installed as without
            assert req.result(timeout=120) == want.generated
            gc.collect()
            stats = sched.stats()
        finally:
            assert getattr(sched, end)() in (None, True)
        assert gc.callbacks == found
        assert stats["gc"]["2"]["collections"] >= 1
        assert stats["gc"]["2"]["seconds"] > 0
        phases = stats["phases"]
        assert phases["phase"]["wait"]["calls"] >= 1
        assert phases["device_wait_s"] + phases["host_work_s"] \
            + phases["no_work_s"] <= phases["loop_s"]


class TestServeCommand:
    def test_train_checkpoint_serve_end_to_end(self, run_flow,
                                               tpuflow_root, tmp_path):
        """The full path behind `tpuflow serve FLOW/RUN`: a flow
        checkpoints trained weights, serve() resolves the run, loads the
        checkpoint, builds the engine, and answers HTTP with the exact
        tokens lockstep generate() gives for those weights."""
        import textwrap

        from metaflow_tpu import telemetry
        from metaflow_tpu.cmd.serve import serve
        from metaflow_tpu.inference import load_run_checkpoint

        flow = tmp_path / "ckpt_serve_flow.py"
        flow.write_text(textwrap.dedent("""
            import metaflow_tpu
            from metaflow_tpu import FlowSpec, current, step

            class CkptServeFlow(FlowSpec):
                @metaflow_tpu.checkpoint
                @step
                def start(self):
                    import jax
                    from metaflow_tpu.models import llama
                    cfg = llama.LlamaConfig.tiny()
                    params = llama.init_params(jax.random.PRNGKey(7),
                                               cfg)
                    current.checkpoint.save({"params": params}, step=0)
                    self.next(self.end)

                @step
                def end(self):
                    pass

            if __name__ == "__main__":
                CkptServeFlow()
        """))
        run_flow(str(flow), "run")
        cfg_json = json.dumps({
            "vocab_size": 512, "dim": 128, "n_layers": 2, "n_heads": 4,
            "n_kv_heads": 2, "ffn_dim": 256, "max_seq_len": 256,
            "rope_llama3_scaling": False, "dtype": "float32"})
        srv = serve("CkptServeFlow", config_json=cfg_json, port=0,
                    slots=2, max_seq_len=64, block=False,
                    echo=lambda *a, **k: None)
        try:
            conn, resp = _post(srv.port, {
                "tokens": list(range(1, 9)), "max_new_tokens": 4})
            assert resp.status == 200
            body = json.loads(resp.read())
            conn.close()
            restored = load_run_checkpoint("CkptServeFlow")
            cfg = llama.LlamaConfig.tiny()
            ref = generate(restored["params"],
                           jnp.asarray([list(range(1, 9))]), cfg, 4,
                           rng=jax.random.PRNGKey(0))
            assert body["new_tokens"] == \
                np.asarray(ref)[0, 8:].tolist()
        finally:
            srv.close()
            telemetry.close_recorder()

    def test_build_config_validation(self):
        from metaflow_tpu.cmd.serve import build_config, extract_params
        from metaflow_tpu.exception import TpuFlowException

        cfg = build_config({"cfg": {"dim": 64, "n_layers": 1}})
        assert cfg.dim == 64 and cfg.n_layers == 1
        with pytest.raises(TpuFlowException, match="no model config"):
            build_config({"params": {}})
        with pytest.raises(TpuFlowException, match="unknown"):
            build_config({}, config_json='{"not_a_field": 1}')
        params = {"embed": 1}
        assert extract_params({"params": params}) is params
        assert extract_params(params) is params

    @pytest.mark.parametrize("entry", ["tpuflow serve", "the replica's"])
    def test_attn_impl_is_no_option_of_a_server(self, entry, capsys):
        """`--attn-impl dense` made a server run two programs an
        iteration where the shapes ask for one; how an engine reads its
        pools is `pool_read`'s answer, and neither entry point, nor
        `build_engine`, `serve`, `serve_fleet` or `SlotEngine`, takes the
        word."""
        import inspect

        from metaflow_tpu.cmd import serve as cmd

        if entry == "tpuflow serve":
            from click.testing import CliRunner

            from metaflow_tpu.__main__ import main as cli

            result = CliRunner().invoke(
                cli, ["serve", "SomeFlow/1", "--attn-impl", "dense"])
            assert result.exit_code == 2
            assert "No such option" in result.output
            assert "--attn-impl" in result.output
        else:
            from metaflow_tpu.serving import replica

            with pytest.raises(SystemExit) as refused:
                replica.build_parser().parse_args(["--attn-impl", "dense"])
            assert refused.value.code == 2
            assert "--attn-impl" in capsys.readouterr().err
        for fn in (cmd.build_engine, cmd.serve, cmd.serve_fleet,
                   SlotEngine.__init__):
            assert "attn_impl" not in inspect.signature(fn).parameters

    def test_build_engine_shards_by_model_family(self):
        """--mesh with a Mixtral checkpoint must use the Mixtral rule
        tree (router/expert axes), not the Llama table."""
        from metaflow_tpu.cmd.serve import build_engine
        from metaflow_tpu.models import mixtral

        cfg = mixtral.MixtralConfig.tiny()
        params = mixtral.init_params(jax.random.PRNGKey(0), cfg)
        eng = build_engine(params, cfg, slots=2, max_seq_len=64,
                           mesh_spec="dp")
        assert eng.mesh is not None
