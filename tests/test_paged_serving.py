"""Paged KV cache + speculative decoding: the ISSUE-16 acceptance
pins. Token identity (greedy AND sampled) for the paged engine vs the
slot engine vs lockstep generate() across page-boundary crossings;
zero-copy prefix sharing with page refcount asserts; zero leaked pages
after every terminal path (finish/cancel/deadline/drain/shutdown);
page-exhaustion backpressure with head-of-line FIFO waits + recovery
and the pinned serve.kv.* telemetry; copy-on-write on a shared partial
tail page; the HTTP 413 capacity surface on a paged server; and the
speculative-decode contracts (greedy accept-all bit-exactness,
accept-rate accounting, draft-disagreement exactness, default
prompt-lookup drafter identity, sampled fallback)."""

import http.client
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from metaflow_tpu.inference import generate
from metaflow_tpu.models import llama
from metaflow_tpu.serving import (
    CapacityError,
    PagedEngine,
    PagedPrefixIndex,
    Request,
    Scheduler,
    ServingServer,
    SlotEngine,
)
from metaflow_tpu.serving.paged import ngram_draft

HERE = os.path.dirname(os.path.abspath(__file__))

PTOK = 16  # page size everywhere here: boundaries land on multiples


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def engine(setup):
    """ONE paged engine for the module (compiled programs shared);
    every test drains, so slots and pages come back free. Default pool
    = the slot engine's HBM shape (max_slots * blocks-per-seq)."""
    cfg, params = setup
    eng = PagedEngine(params, cfg, max_slots=4, max_seq_len=128,
                      prefill_chunk=16, page_tokens=PTOK, spec_k=0)
    warm = Scheduler(eng)
    warm.submit(Request(list(range(1, 20)), max_new_tokens=2,
                        temperature=0.5))
    warm.run_until_idle(10_000)
    return eng


def _ref_tokens(params, cfg, req):
    """Single-request lockstep generate() for this request — the shared
    ground truth the slot engine is already pinned to."""
    out = generate(params, jnp.asarray(req.tokens)[None], cfg,
                   req.max_new_tokens, temperature=req.temperature,
                   top_k=req.top_k, top_p=req.top_p, eos_id=req.eos_id,
                   rng=jax.random.PRNGKey(req.rng))
    new = np.asarray(out)[0, len(req.tokens):].tolist()
    if req.eos_id is not None and req.eos_id in new:
        new = new[:new.index(req.eos_id) + 1]
    return new


def _assert_pool_free(eng):
    assert eng.pool.free_pages() == eng.pool.usable_pages, \
        "leaked KV pages: %s" % (eng.pool.stats(),)


class TestPagedTokenIdentity:
    def test_greedy_identity_at_page_boundaries(self, setup, engine):
        """Prompt lengths straddling every page-boundary case (one
        under, exact, one over, multi-page) with generation lengths
        that cross page edges mid-decode: paged output == slot-engine
        output == generate(), token for token."""
        cfg, params = setup
        rng = np.random.default_rng(0)
        cases = [(PTOK - 1, 3), (PTOK, 4), (PTOK + 1, 4),
                 (2 * PTOK - 2, 6), (3 * PTOK, 9), (7, 2 * PTOK + 3),
                 (90, 8), (33, PTOK)]
        traces = [(rng.integers(0, cfg.vocab_size, plen).tolist(), n)
                  for plen, n in cases]

        def run(eng):
            sched = Scheduler(eng)
            reqs = [sched.submit(Request(list(p), max_new_tokens=n,
                                         rng=i))
                    for i, (p, n) in enumerate(traces)]
            sched.run_until_idle(10_000)
            return reqs

        paged = run(engine)
        slot_eng = SlotEngine(params, cfg, max_slots=4, max_seq_len=128,
                              prefill_chunk=16)
        slotted = run(slot_eng)
        for pr, sr in zip(paged, slotted):
            assert pr.reason == "length"
            ref = _ref_tokens(params, cfg, pr)
            assert pr.generated == ref, \
                "paged output diverged from lockstep generate"
            assert sr.generated == ref, \
                "slot output diverged from lockstep generate"
        _assert_pool_free(engine)

    def test_sampled_identity_at_page_boundaries(self, setup, engine):
        """The sampled path (temperature / top-k / top-p) shares
        generate()'s rng split sequence, so paged sampling is
        token-identical too — including decodes that cross a page
        boundary mid-stream."""
        cfg, params = setup
        sched = Scheduler(engine)
        reqs = []
        for i, (tk, tp) in enumerate([(None, None), (20, None),
                                      (None, 0.9), (20, 0.9)]):
            toks = list(range(3 + i, 3 + i + PTOK - 2))
            reqs.append(sched.submit(Request(
                toks, max_new_tokens=PTOK, temperature=0.8, top_k=tk,
                top_p=tp, rng=100 + i)))
        sched.run_until_idle(10_000)
        for req in reqs:
            assert req.generated == _ref_tokens(params, cfg, req)
        _assert_pool_free(engine)


class TestPagedKeySchedules:
    """The paged engine takes the slot engine's key schedules
    (engine.KeySchedules): drawn when a sampled token is first asked
    for, never for a greedy request."""

    @pytest.mark.parametrize("case", ["greedy", "sampled-beside-greedy",
                                      "sampled-admit-prefilled"])
    def test_keys_are_drawn_when_a_sampled_token_is_first_asked_for(
            self, setup, engine, monkeypatch, case):
        from metaflow_tpu.serving import engine as engine_module

        cfg, params = setup
        drawn, sched = engine.key_schedules, Scheduler(engine)
        prompts = [list(range(2 + i, 2 + i + n))
                   for i, n in enumerate((PTOK - 3, PTOK + 5, 2 * PTOK))]
        if case == "greedy":
            def refuse(*args, **kw):
                raise AssertionError("a greedy request drew sampling keys")

            monkeypatch.setattr(engine_module, "request_step_keys", refuse)
            monkeypatch.setattr(jax.random, "split", refuse)
            reqs = [sched.submit(Request(p, max_new_tokens=3 + 4 * i,
                                         rng=i))
                    for i, p in enumerate(prompts)]
            sched.run_until_idle(10_000)
            monkeypatch.undo()
            sampled = []
        else:
            knobs = dict(max_new_tokens=6, temperature=0.8, top_p=0.9,
                         rng=77)
            if case == "sampled-admit-prefilled":
                pre = sched.submit(Request(prompts[2], prefill_only=True,
                                           **knobs))
                sched.run_until_idle(10_000)
                assert engine.key_schedules == drawn + 1
                drawn, sched = drawn + 1, Scheduler(engine)
                knobs["prefilled"] = pre.handoff
            reqs = [sched.submit(Request(p, max_new_tokens=PTOK, rng=i))
                    for i, p in enumerate(prompts[:2])]
            while not all(r.state == "decode" for r in reqs):
                sched.step()
            assert engine.key_schedules == drawn
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
        assert engine.key_schedules - drawn == len(sampled) \
            == sched.stats()["key_schedules"] - drawn
        assert sched.phases.calls.get("engine.admit.keys", 0) \
            == len(sampled)
        _assert_pool_free(engine)


class TestZeroCopySharing:
    @pytest.fixture()
    def shared(self, setup):
        """A fresh engine + page-granular prefix index per test: the
        index holds page refs across requests, so pool accounting must
        start clean."""
        cfg, params = setup
        eng = PagedEngine(params, cfg, max_slots=4, max_seq_len=128,
                          prefill_chunk=16, page_tokens=PTOK, spec_k=0)
        return eng, PagedPrefixIndex(eng.pool)

    def test_prefix_hit_is_zero_copy(self, setup, shared):
        """A page-aligned prefix hit attaches the producer's device
        pages to the consumer's block table: refcounts go 1 (index) ->
        2 (index + slot) -> 1, shared_pages_attached grows, and NOT ONE
        KV byte is copied."""
        cfg, params = setup
        eng, cache = shared
        system = list(range(2, 2 + 2 * PTOK))   # exactly 2 full pages
        sched = Scheduler(eng, prefix_cache=cache)
        cold = sched.submit(Request(system + [60, 61, 62],
                                    max_new_tokens=6, rng=0))
        sched.run_until_idle(10_000)
        assert cache.registered_pages() >= 2

        h = cache.match(system + [70, 71, 72])
        pids = list(h.pages)
        cache.release(h)
        assert len(pids) == 2
        assert all(eng.pool.refs[p] == 1 for p in pids)  # index only

        copied0 = eng.kv_bytes_copied
        attached0 = eng.shared_pages_attached
        warm = sched.submit(Request(system + [70, 71, 72],
                                    max_new_tokens=6, rng=1))
        while warm.state != "decode":
            sched.step()
        # mid-flight: index ref + the consumer slot's ref, same pages
        assert all(eng.pool.refs[p] == 2 for p in pids)
        assert list(eng.block_tables[warm.slot, :2]) == pids
        sched.run_until_idle(10_000)
        assert all(eng.pool.refs[p] == 1 for p in pids)
        assert eng.kv_bytes_copied == copied0, \
            "a zero-copy hit moved KV bytes"
        assert eng.shared_pages_attached == attached0 + 2
        assert sched.prefix_hits >= 1
        # the hit changed WHERE prefill started, never what it computed
        assert warm.generated == _ref_tokens(params, cfg, warm)
        cache.clear()
        _assert_pool_free(eng)

    def test_partial_tail_shares_via_cow(self, setup, shared):
        """A prefix ending mid-page is shared through ONE copy-on-write
        page copy (the only bytes a hit can move), the producer's
        cached tail stays valid for later hits, and outputs match the
        cold run."""
        cfg, params = setup
        eng, cache = shared
        prefix = list(range(2, 2 + PTOK + PTOK // 2))  # 1 page + half
        tails = [[90, 91, 92, 93], [95, 96, 97, 98]]
        sched = Scheduler(eng, prefix_cache=cache)
        refs = []
        for i, tail in enumerate(tails):
            r = sched.submit(Request(prefix + tail, max_new_tokens=5,
                                     rng=i))
            sched.run_until_idle(10_000)
            refs.append(r)
        cow0 = eng.cow_pages
        # third request: full-page + partial-tail hit -> exactly one CoW
        again = sched.submit(Request(prefix + tails[0],
                                     max_new_tokens=5, rng=0))
        sched.run_until_idle(10_000)
        assert eng.cow_pages == cow0 + 1, eng.kv_stats()
        assert eng.cow_bytes > 0
        assert again.generated == refs[0].generated \
            == _ref_tokens(params, cfg, refs[0])
        cache.clear()
        _assert_pool_free(eng)

    def test_no_pages_leak_on_any_terminal_path(self, setup, shared):
        """cancel / deadline / drain / shutdown: each path must return
        the FULL page reservation; after cache.clear() the pool is
        byte-for-byte free."""
        eng, cache = shared
        prompt = list(range(1, 40))

        # cancel mid-flight
        sched = Scheduler(eng, prefix_cache=cache)
        victim = sched.submit(Request(prompt, max_new_tokens=80, rng=0))
        for _ in range(6):
            sched.step()
        assert victim.state in ("prefill", "decode")
        sched.cancel(victim.id)
        sched.run_until_idle(10_000)
        assert victim.reason == "cancelled"

        # deadline expiry mid-flight
        sched = Scheduler(eng, prefix_cache=cache)
        req = sched.submit(Request(prompt, max_new_tokens=80,
                                   deadline=time.time() + 3600))
        t0 = time.time()
        while not req.generated and time.time() - t0 < 60:
            sched.step()
        req.deadline = time.time() - 0.001
        while req.reason is None and time.time() - t0 < 60:
            sched.step()
        assert req.reason == "deadline"

        # graceful drain with work in flight (threaded loop)
        sched = Scheduler(eng, prefix_cache=cache).start()
        drained = sched.submit(Request(prompt, max_new_tokens=12, rng=1))
        assert sched.drain(timeout=60)
        assert drained.reason == "length"

        # hard shutdown with work in flight
        sched = Scheduler(eng, prefix_cache=cache).start()
        corpse = sched.submit(Request(prompt, max_new_tokens=50, rng=2))
        killed = sched.submit(Request(prompt, max_new_tokens=80, rng=3))
        sched.stop()
        assert killed.reason in ("shutdown", "length")
        assert corpse.reason in ("shutdown", "length")

        assert eng.free_slots() == list(range(eng.max_slots))
        free = eng.pool.free_pages()
        assert free == eng.pool.usable_pages - cache.registered_pages(),\
            "terminal paths leaked pages: %s" % (eng.pool.stats(),)
        cache.clear()
        _assert_pool_free(eng)


class TestExhaustionBackpressure:
    @pytest.fixture()
    def small(self, setup):
        """4 usable pages = two 2-page requests in flight; the third
        hits pool exhaustion, not a slot limit (slots > possible
        residents)."""
        cfg, params = setup
        return PagedEngine(params, cfg, max_slots=4, max_seq_len=128,
                           prefill_chunk=16, page_tokens=PTOK,
                           spec_k=0, total_pages=5)

    def test_exhaustion_blocks_then_recovers(self, setup, small, tmp_path):
        """Pool exhaustion is BACKPRESSURE: the head request waits (no
        reorder — later arrivals may not jump it), serve.kv.exhausted
        fires once per blocked episode, and when pages free up
        admission resumes and every request completes."""
        from schema_validate import validate_serving_record

        from metaflow_tpu import telemetry
        from metaflow_tpu.datastore import FlowDataStore, LocalStorage

        cfg, params = setup
        big = list(range(1, PTOK + 1))      # + PTOK new = 2 pages
        little = list(range(1, PTOK // 2))  # + 8 new   = 1 page
        fds = FlowDataStore("PagedExhaust", LocalStorage,
                            ds_root=str(tmp_path))
        telemetry.init_recorder(fds, "1", "_serve", "paged-test")
        try:
            sched = Scheduler(small)
            a = sched.submit(Request(list(big), max_new_tokens=PTOK,
                                     rng=0))
            b = sched.submit(Request(list(little), max_new_tokens=8,
                                     rng=1))
            c = sched.submit(Request(list(big), max_new_tokens=PTOK,
                                     rng=2))
            d = sched.submit(Request(list(little), max_new_tokens=8,
                                     rng=3))
            for _ in range(4):
                sched.step()
            st = sched.stats()
            # a(2) + b(1) of 4 pages in flight; head c needs 2 > 1 free
            assert st["in_flight"] == 2, st      # pool-capped, not slot
            assert st["queue_depth"] == 2
            assert sched.kv_exhausted >= 1
            assert c.state == "queued"
            # HEAD-OF-LINE: d WOULD fit in the 1 free page right now,
            # but it may not jump the blocked head
            assert small.can_admit(len(d.tokens), d.max_new_tokens)
            assert d.state == "queued"
            sched.run_until_idle(10_000)
            assert all(r.reason == "length" for r in (a, b, c, d))
            assert sched.stats()["kv_pages"]["exhausted"] \
                == sched.kv_exhausted
        finally:
            telemetry.close_recorder()
        _assert_pool_free(small)
        records = telemetry.read_run_records(fds, "1")
        kv = [r for r in records if r["name"].startswith("serve.kv.")]
        for rec in kv:
            validate_serving_record(rec)
        names = [r["name"] for r in kv]
        assert names.count("serve.kv.exhausted") == sched.kv_exhausted
        assert "serve.kv.page_alloc" in names
        assert "serve.kv.page_free" in names

    def test_never_fits_is_capacity_error_not_backpressure(self, small):
        """A request larger than the WHOLE pool can never be admitted:
        CapacityError at submit (413), the queue untouched."""
        assert small.fits(PTOK, PTOK)
        assert not small.fits(3 * PTOK, 3 * PTOK)  # > 4 usable pages
        sched = Scheduler(small)
        with pytest.raises(CapacityError):
            sched.submit(Request(list(range(1, 3 * PTOK)),
                                 max_new_tokens=3 * PTOK))
        assert sched.pending() == 0
        _assert_pool_free(small)
        assert sched.max_context_tokens() \
            == small.pool.usable_pages * PTOK


class TestInFlightAtEqualKVBytes:
    def test_short_requests_pack_past_the_slot_count(self, setup):
        """A pool of exactly the slot engine's KV footprint (slots x
        max_seq_len tokens) under a burst of short requests: the slot
        engine's ceiling is `slots` by construction (a slot reserves a
        whole max_seq_len row), the paged engine reserves
        ceil(need / page) pages a request and holds more lanes in the
        same bytes."""
        cfg, params = setup
        slots, max_seq_len = 4, 128
        eng = PagedEngine(params, cfg, max_slots=2 * slots,
                          max_seq_len=max_seq_len, prefill_chunk=32,
                          page_tokens=PTOK, spec_k=0,
                          total_pages=slots * (max_seq_len // PTOK) + 1)
        assert eng.pool.usable_pages * PTOK == slots * max_seq_len
        rng = np.random.default_rng(5)
        sched = Scheduler(eng, max_queue=4 * slots + 1)
        reqs = [sched.submit(Request(
            rng.integers(1, cfg.vocab_size, PTOK).tolist(),
            max_new_tokens=8, rng=i)) for i in range(4 * slots)]
        sched.run_until_idle(100_000)
        assert all(len(r.generated) == 8 for r in reqs)
        # a request is 2 pages, the pool 32: the lanes run out first
        assert sched.stats()["peak_in_flight"] == 2 * slots
        _assert_pool_free(eng)


class TestPagedSharedTelemetry:
    def test_page_shared_event_and_schema(self, setup, tmp_path):
        """serve.kv.page_shared rides every zero-copy attach and every
        serve.kv.* record validates against the pinned schema — the
        paged counterpart of the slot engine's lifecycle pin."""
        from schema_validate import validate_serving_record

        from metaflow_tpu import telemetry
        from metaflow_tpu.datastore import FlowDataStore, LocalStorage

        cfg, params = setup
        eng = PagedEngine(params, cfg, max_slots=2, max_seq_len=128,
                          prefill_chunk=16, page_tokens=PTOK, spec_k=0)
        cache = PagedPrefixIndex(eng.pool)
        fds = FlowDataStore("PagedShare", LocalStorage,
                            ds_root=str(tmp_path))
        telemetry.init_recorder(fds, "1", "_serve", "paged-test")
        try:
            sched = Scheduler(eng, prefix_cache=cache)
            system = list(range(2, 2 + 2 * PTOK))
            for i in range(3):
                sched.submit(Request(system + [60 + i],
                                     max_new_tokens=4, rng=i))
                sched.run_until_idle(10_000)
        finally:
            telemetry.close_recorder()
        records = telemetry.read_run_records(fds, "1")
        kv = [r for r in records if r["name"].startswith("serve.kv.")]
        assert kv
        for rec in kv:
            validate_serving_record(rec)
        shares = [r for r in kv if r["name"] == "serve.kv.page_shared"]
        assert len(shares) >= 2          # both post-seed requests hit
        assert all(r["data"]["tokens"] >= 2 * PTOK for r in shares)
        gauges = {r["name"] for r in records
                  if r.get("type") == "gauge"}
        assert "serve.kv.page_occupancy" in gauges
        assert "serve.kv.cow_pages" in gauges
        cache.clear()
        _assert_pool_free(eng)


class TestPagedPhases:
    def test_the_paged_engine_goes_through_the_schedulers_ledger(
            self, engine):
        """One ledger holds the whole iteration whichever engine runs
        it: the paged engine's engine.* phases and its launch count."""
        before, drawn = engine.launches, engine.key_schedules
        sched = Scheduler(engine)
        assert engine.phases is sched.phases
        # the last request sampled: it alone draws its keys
        reqs = [sched.submit(Request(list(range(1, 12 + 9 * i)),
                                     max_new_tokens=5, rng=i,
                                     temperature=0.7 * (i == 2)))
                for i in range(3)]
        sched.run_until_idle(10_000)
        assert all(r.reason == "length" for r in reqs)
        calls, took = sched.phases.calls, sched.phases.seconds
        assert calls["engine.decode.dispatch"] \
            == calls["engine.decode.fetch"] == sched.decode_steps
        # one slot and one chunk an execution: a program of the
        # scheduler's is one or more of this engine's
        assert calls["engine.prefill.dispatch"] >= sched.prefill_programs
        assert calls["engine.first_token.fetch"] == len(reqs)
        assert calls["engine.admit.keys"] == 1 \
            == engine.key_schedules - drawn \
            == sched.stats()["key_schedules"] - drawn
        assert engine.launches - before == calls["engine.decode.dispatch"] \
            + calls["engine.prefill.dispatch"]
        assert took["engine.decode.dispatch"] + took["engine.decode.fetch"] \
            <= took["serve.decode_step"] == sched.busy_decode_s
        _assert_pool_free(engine)


class TestPagedHTTP:
    def test_capacity_413_and_kv_healthz(self, setup, engine):
        """The paged capacity check surfaces as HTTP 413 + Retry-After,
        and /healthz + /v1/stats carry the kv_pages block."""
        from schema_validate import validate_healthz

        srv = ServingServer(Scheduler(engine), port=0).start()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                              timeout=60)
            conn.request("POST", "/v1/generate", json.dumps({
                "tokens": list(range(1, 60)), "max_new_tokens": 500}))
            resp = conn.getresponse()
            assert resp.status == 413
            assert resp.getheader("Retry-After") is not None
            resp.read()
            conn.request("GET", "/healthz")
            body = json.loads(conn.getresponse().read())
            validate_healthz(body)
            assert body["kv_pages"]["enabled"] is True
            assert body["max_context_tokens"] == 128
            conn.request("GET", "/v1/stats")
            stats = json.loads(conn.getresponse().read())
            assert stats["kv_pages"]["pages_total"] \
                == engine.pool.usable_pages
            assert stats["speculative"] == engine.spec_stats()
            conn.close()
        finally:
            srv.close()
        _assert_pool_free(engine)


class TestNothingAhead:
    """The paged engine runs no step ahead (`runs_ahead` false: a burst's
    acceptance is read off its tokens): through the same loop its launch
    computes the step and its collect hands it out in the same iteration,
    so it serves as it did before the loop kept a step in flight."""

    @pytest.mark.parametrize("spec_k", [0, 3])
    def test_steps_ahead_reads_zero_and_tokens_are_generates(
            self, setup, engine, spec_k):
        cfg, params = setup
        eng = engine if spec_k == 0 else PagedEngine(
            params, cfg, max_slots=4, max_seq_len=128, prefill_chunk=16,
            page_tokens=PTOK, spec_k=spec_k)
        assert eng.runs_ahead is False
        sched = Scheduler(eng)
        rng = np.random.default_rng(11)
        reqs = [sched.submit(Request(
            rng.integers(0, cfg.vocab_size, n).tolist(), max_new_tokens=m,
            rng=i)) for i, (n, m) in enumerate(
                [(5, 9), (16, 1), (33, 12), (40, 7), (17, 10), (3, 6)])]
        delivered = []
        while sched.pending():
            before = sched.decode_steps
            sched.step()
            assert not sched._in_flight and sched._prefilled is None
            # a step's tokens are delivered in the iteration that ran it
            delivered.append((sched.decode_steps - before,
                              sched._recent[-1][2]))
        assert all((launched == 1) == (lanes > 0)
                   for launched, lanes in delivered)
        for req in reqs:
            assert req.reason == "length"
            assert req.generated == _ref_tokens(params, cfg, req)
        stats = sched.stats()
        assert stats["steps_ahead"] == 0 < stats["decode_steps"]
        assert sched.phases.calls["serve.decode_step"] \
            == sched.phases.calls["serve.deliver"] == stats["decode_steps"]
        _assert_pool_free(eng)


class TestSpeculativeDecoding:
    @pytest.fixture(scope="class")
    def spec_engine(self, setup):
        cfg, params = setup
        eng = PagedEngine(params, cfg, max_slots=4, max_seq_len=128,
                          prefill_chunk=16, page_tokens=PTOK, spec_k=3)
        warm = Scheduler(eng)
        warm.submit(Request(list(range(1, 20)), max_new_tokens=2))
        warm.run_until_idle(10_000)
        return eng

    def _run(self, eng, traces, **kw):
        sched = Scheduler(eng)
        reqs = [sched.submit(Request(list(p), max_new_tokens=n, rng=i,
                                     **kw))
                for i, (p, n) in enumerate(traces)]
        sched.run_until_idle(10_000)
        return reqs

    def test_oracle_drafts_accept_all_bit_exact(self, setup, spec_engine):
        """Drafts replayed from the target model's own greedy outputs:
        every draft token verifies, multi-token steps dominate, and the
        output is STILL bit-exact with generate() — acceptance is exact
        token identity, never 'close enough'."""
        cfg, params = setup
        rng = np.random.default_rng(7)
        traces = [(rng.integers(0, cfg.vocab_size,
                                int(rng.integers(4, 30))).tolist(), 18)
                  for _ in range(4)]
        refs = [list(p) + _ref_tokens(
            params, cfg, Request(list(p), max_new_tokens=n, rng=i))
            for i, (p, n) in enumerate(traces)]

        def oracle(context, k):
            for r in refs:
                n = len(context)
                if len(r) > n and r[:n] == context:
                    out = r[n:n + k]
                    return out + [0] * (k - len(out))
            return [0] * k

        old = spec_engine.draft_fn
        spec_engine.draft_fn = oracle
        p0, a0 = spec_engine.spec_proposed, spec_engine.spec_accepted
        steps0 = spec_engine.spec_steps
        try:
            reqs = self._run(spec_engine, traces)
        finally:
            spec_engine.draft_fn = old
        for req, ref in zip(reqs, refs):
            assert req.generated == ref[len(req.tokens):], \
                "spec decode diverged from greedy generate"
        proposed = spec_engine.spec_proposed - p0
        accepted = spec_engine.spec_accepted - a0
        steps = spec_engine.spec_steps - steps0
        assert steps > 0 and proposed == steps * 4 * 3 \
            or proposed > 0   # k=3 per decoding slot per step
        assert accepted / proposed >= 0.8, (accepted, proposed)
        # accept-all means ~k+1 tokens per verify step: far fewer steps
        # than tokens generated
        total = sum(len(r.generated) for r in reqs)
        assert steps < total
        _assert_pool_free(spec_engine)

    def test_garbage_drafts_still_exact(self, setup, spec_engine):
        """An adversarial drafter (always wrong) costs speed, never
        correctness: acceptance goes ~0 and the output is byte-equal to
        plain greedy."""
        cfg, params = setup
        traces = [(list(range(5, 30)), 12), (list(range(2, 9)), 10)]

        bad = cfg.vocab_size - 1

        old = spec_engine.draft_fn
        p0, a0 = spec_engine.spec_proposed, spec_engine.spec_accepted
        spec_engine.draft_fn = lambda context, k: [bad] * k
        try:
            reqs = self._run(spec_engine, traces)
        finally:
            spec_engine.draft_fn = old
        for req in reqs:
            assert req.generated == _ref_tokens(params, cfg, req)
        proposed = spec_engine.spec_proposed - p0
        accepted = spec_engine.spec_accepted - a0
        assert proposed > 0
        # a draft can still collide with the argmax by luck; "almost
        # nothing accepted" is the contract
        assert accepted / proposed < 0.5
        _assert_pool_free(spec_engine)

    def test_default_ngram_drafter_identity(self, setup, spec_engine):
        """The stock prompt-lookup drafter on a REPETITIVE prompt (its
        favorable case): tokens identical to generate(), accounting
        consistent."""
        cfg, params = setup
        base = [5, 9, 11, 5, 9, 11, 5, 9, 11, 5, 9]
        reqs = self._run(spec_engine, [(base, 14), (base[1:], 10)])
        for req in reqs:
            assert req.generated == _ref_tokens(params, cfg, req)
        ss = spec_engine.spec_stats()
        assert ss["enabled"] and ss["k"] == 3
        assert 0 <= ss["accepted"] <= ss["proposed"]
        assert ss["accept_rate"] == round(
            ss["accepted"] / max(1, ss["proposed"]), 4)
        _assert_pool_free(spec_engine)

    def test_sampled_requests_fall_back_to_exact_sampling(
            self, setup, spec_engine):
        """spec_k > 0 with sampled requests in the batch: the engine
        falls back to the plain fused step, so sampled outputs keep the
        generate() rng contract on a mixed greedy+sampled trace."""
        cfg, params = setup
        sched = Scheduler(spec_engine)
        mixed = [
            Request(list(range(4, 24)), max_new_tokens=8, rng=0),
            Request(list(range(6, 26)), max_new_tokens=8,
                    temperature=0.8, top_k=20, rng=1),
            Request(list(range(8, 28)), max_new_tokens=8,
                    temperature=0.7, top_p=0.9, rng=2),
        ]
        for r in mixed:
            sched.submit(r)
        sched.run_until_idle(10_000)
        for req in mixed:
            assert req.generated == _ref_tokens(params, cfg, req)
        _assert_pool_free(spec_engine)

    def test_ngram_draft_shapes(self):
        """The drafter contract _spec_decode_step relies on: EXACTLY k
        ints for any context."""
        for ctx in ([1], [1, 2, 3, 1, 2, 3, 1], list(range(50))):
            for k in (1, 3, 4):
                d = ngram_draft(ctx, k)
                assert len(d) == k
                assert all(isinstance(t, int) for t in d)
