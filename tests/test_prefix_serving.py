"""Radix prefix cache + disaggregated handoff: tree mechanics (match/
split/dedup/LRU-evict/pin accounting), the engine extract/seed KV
roundtrip, cache-hit token identity vs a cold run (greedy AND sampled —
the acceptance pin), cancellation mid-prefill releasing the prefix pin,
the prefill-only -> KV-frame -> decode identity chain, Retry-After on
replica 429/503 sheds, the prefix blocks in /healthz + /v1/stats, and
the pinned serve.prefix.* telemetry schema through `tpuflow metrics`."""

import http.client
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from metaflow_tpu.inference import generate
from metaflow_tpu.models import llama
from metaflow_tpu.serving import (
    RadixPrefixCache,
    Request,
    Scheduler,
    ServingServer,
    SlotEngine,
    decode_handoff,
    encode_handoff,
)
from metaflow_tpu.serving.server import retry_after_hint

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def engine(setup):
    """One engine for the module (compiled programs are shared); every
    test drains its requests so slots come back free."""
    cfg, params = setup
    eng = SlotEngine(params, cfg, max_slots=4, max_seq_len=128,
                     prefill_chunk=16)
    warm = Scheduler(eng)
    warm.submit(Request(list(range(1, 20)), max_new_tokens=2,
                        temperature=0.5))
    warm.run_until_idle(10_000)
    return eng


def _ref_tokens(params, cfg, req):
    """Lockstep generate(): the token-identity oracle."""
    out = generate(params, jnp.asarray(req.tokens)[None], cfg,
                   req.max_new_tokens, temperature=req.temperature,
                   top_k=req.top_k, top_p=req.top_p, eos_id=req.eos_id,
                   rng=jax.random.PRNGKey(req.rng))
    new = np.asarray(out)[0, len(req.tokens):].tolist()
    if req.eos_id is not None and req.eos_id in new:
        new = new[:new.index(req.eos_id) + 1]
    return new


# ---------------------------------------------------------------------------
# Radix tree mechanics (synthetic KV: a pure function of the token value,
# so bitwise checks survive splits and re-reads)
# ---------------------------------------------------------------------------

def _kv(tokens):
    t = np.asarray(list(tokens), np.float32)
    k = np.broadcast_to(t[None, :, None, None], (2, t.size, 2, 4)).copy()
    return {"k": k, "v": k + 0.5}
    # 128 bytes per token (k + v, float32 2x2x4)


class TestRadixTree:
    def test_match_miss_hit_and_pin_accounting(self):
        c = RadixPrefixCache(1 << 20)
        assert c.match([1, 2, 3]) is None
        c.insert([1, 2, 3, 4], _kv([1, 2, 3, 4]))
        assert c.match([7, 8]) is None
        h = c.match([1, 2, 3, 4, 9])
        assert h is not None and h.length == 4
        kv = h.kv()
        np.testing.assert_array_equal(kv["k"], _kv([1, 2, 3, 4])["k"])
        np.testing.assert_array_equal(kv["v"], _kv([1, 2, 3, 4])["v"])
        # a mid-edge partial match pins too, at the matched length
        h2 = c.match([1, 2, 5])
        assert h2.length == 2
        np.testing.assert_array_equal(h2.kv()["k"], _kv([1, 2])["k"])
        assert c.pinned_nodes() >= 1
        c.release(h)
        c.release(h)  # idempotent per handle
        c.release(h2)
        assert c.pinned_nodes() == 0

    def test_split_keeps_pins_and_captured_views_valid(self):
        c = RadixPrefixCache(1 << 20)
        c.insert(list(range(10, 20)), _kv(range(10, 20)))
        h = c.match(list(range(10, 20)))
        assert h.length == 10
        # this insert splits the pinned edge at depth 3: the pinned node
        # OBJECT must stay the suffix and the handle's views must still
        # read the original bytes
        c.insert([10, 11, 12, 99, 98], _kv([10, 11, 12, 99, 98]))
        np.testing.assert_array_equal(h.kv()["k"],
                                      _kv(range(10, 20))["k"])
        assert c.pinned_nodes() >= 1
        c.release(h)
        assert c.pinned_nodes() == 0
        # both branches match correctly post-split
        h2 = c.match([10, 11, 12, 99, 98, 1])
        assert h2.length == 5
        np.testing.assert_array_equal(h2.kv()["v"],
                                      _kv([10, 11, 12, 99, 98])["v"])
        c.release(h2)

    def test_shared_prefix_is_deduplicated(self):
        c = RadixPrefixCache(1 << 20)
        c.insert(list(range(1, 9)), _kv(range(1, 9)))
        s0 = c.stats()
        assert s0["cached_tokens"] == 8 and s0["cached_bytes"] == 8 * 128
        # 6 shared tokens + 2 novel: only the novel suffix adds bytes
        c.insert(list(range(1, 7)) + [90, 91],
                 _kv(list(range(1, 7)) + [90, 91]))
        s1 = c.stats()
        assert s1["cached_tokens"] == 10
        assert s1["cached_bytes"] == 10 * 128

    def test_lru_evicts_unpinned_leaves_only(self):
        c = RadixPrefixCache(8 * 128)  # budget: exactly 8 tokens
        a = list(range(1, 9))
        c.insert(a, _kv(a))
        h = c.match(a)  # pin A
        b = list(range(50, 58))
        c.insert(b, _kv(b))  # over budget; A is pinned -> B evicts
        s = c.stats()
        assert s["evictions"] == 1 and s["cached_tokens"] == 8
        assert c.match(b) is None
        np.testing.assert_array_equal(h.kv()["k"], _kv(a)["k"])
        c.release(h)
        # unpinned now: the LRU sweep may take A for the next insert
        cc = list(range(60, 68))
        c.insert(cc, _kv(cc))
        assert c.match(a) is None
        h3 = c.match(cc)
        assert h3 is not None and h3.length == 8
        c.release(h3)
        assert c.stats()["evicted_tokens"] >= 16

    def test_insert_validates_kv_length(self):
        c = RadixPrefixCache(1 << 20)
        with pytest.raises(ValueError):
            c.insert([1, 2, 3], _kv([1, 2]))
        with pytest.raises(ValueError):
            RadixPrefixCache(0)

    def test_from_env_is_opt_in(self, monkeypatch):
        monkeypatch.delenv("TPUFLOW_PREFIX_CACHE_MB", raising=False)
        assert RadixPrefixCache.from_env() is None
        monkeypatch.setenv("TPUFLOW_PREFIX_CACHE_MB", "0")
        assert RadixPrefixCache.from_env() is None
        monkeypatch.setenv("TPUFLOW_PREFIX_CACHE_MB", "2")
        c = RadixPrefixCache.from_env()
        assert c is not None and c.max_bytes == 2 << 20


# ---------------------------------------------------------------------------
# Engine KV roundtrip: extract_kv is bitwise what seed_prefix needs
# ---------------------------------------------------------------------------

class TestEngineKVRoundtrip:
    def test_extract_then_seed_resumes_at_boundary(self, setup, engine):
        cfg, params = setup
        prompt = list(range(3, 43))
        slot = engine.free_slots()[0]
        engine.admit(slot, prompt, 4)
        first = None
        while first is None:
            _consumed, first = engine.prefill_step(slot)
        kv = engine.extract_kv(slot, len(prompt))
        assert kv["k"].shape == kv["v"].shape
        assert kv["k"].shape[1] == len(prompt)
        assert engine.kv_token_bytes() == \
            kv["k"].nbytes // len(prompt) * 2
        engine.release(slot)
        # seed a fresh slot with all-but-one cached position: the single
        # remaining prefill chunk must produce the same first token
        slot2 = engine.free_slots()[0]
        engine.admit(slot2, prompt, 4)
        engine.seed_prefix(slot2, {"k": kv["k"][:, :-1],
                                   "v": kv["v"][:, :-1]})
        consumed, first2 = engine.prefill_step(slot2)
        assert consumed == 1
        assert first2 == first
        engine.release(slot2)

    def test_a_seeded_slot_rides_a_program_beside_an_unseeded_one(
            self, engine):
        """The rows of one prefill program start where each slot's
        cursor is: a slot seeded from a cached range and a slot that
        starts at 0 give the first tokens they give alone."""
        prompts = [list(range(3, 43)), list(range(7, 30))]
        alone, kv = [], None
        for p in prompts:
            slot = engine.free_slots()[0]
            engine.admit(slot, p, 4)
            first = None
            while first is None:
                _, first = engine.prefill_step(slot)
            alone.append(first)
            kv = kv or engine.extract_kv(slot, 17)
            engine.release(slot)
        a, b = engine.free_slots()[:2]
        engine.admit(a, prompts[0], 4)
        engine.seed_prefix(a, kv)
        engine.admit(b, prompts[1], 4)
        consumed, firsts = [0, 0], [None, None]
        while None in firsts:
            plan = [(s, 16) for s, f in zip((a, b), firsts) if f is None]
            rows = [i for i, f in enumerate(firsts) if f is None]
            for i, (n, first) in zip(rows, engine.prefill(plan)):
                consumed[i] += n
                firsts[i] = first
        assert consumed == [40 - 17, 23] and firsts == alone
        engine.release(a)
        engine.release(b)

    def test_seed_rejects_full_prompt_and_started_slots(self, engine):
        prompt = list(range(5, 25))
        slot = engine.free_slots()[0]
        engine.admit(slot, prompt, 2)
        _, _ = engine.prefill_step(slot)
        kv = engine.extract_kv(slot, 8)
        with pytest.raises(ValueError):
            engine.seed_prefix(slot, kv)  # already started prefill
        engine.release(slot)
        slot2 = engine.free_slots()[0]
        engine.admit(slot2, [1, 2, 3], 2)
        with pytest.raises(ValueError):
            # seed length must leave >= 1 token to prefill
            engine.seed_prefix(slot2, engine.extract_kv(slot2, 3))
        engine.release(slot2)


# ---------------------------------------------------------------------------
# Token identity: a cache hit changes WHERE prefill starts, never what
# it computes (the acceptance pin — greedy and sampled)
# ---------------------------------------------------------------------------

SYSTEM = list(range(2, 42))  # a 40-token shared system prompt
TAILS = [[50 + i, 60 + i, 70 + i, 80 + i] for i in range(4)]


def _run_trace(engine, specs, cache):
    sched = Scheduler(engine, prefix_cache=cache)
    outs = []
    for spec in specs:
        req = Request(**spec)
        sched.submit(req)
        sched.run_until_idle(50_000)
        outs.append(req.result(timeout=10))
    return outs, sched


class TestPrefixTokenIdentity:
    def test_greedy_hits_identical_to_cold_and_generate(self, setup,
                                                        engine):
        cfg, params = setup
        specs = [dict(tokens=SYSTEM + tail, max_new_tokens=6, rng=i)
                 for i, tail in enumerate(TAILS)]
        cold, _ = _run_trace(engine, specs, None)
        warm, sched = _run_trace(engine, specs,
                                 RadixPrefixCache(64 << 20))
        assert warm == cold
        for spec, out in zip(specs, cold):
            assert out == _ref_tokens(params, cfg, Request(**spec))
        stats = sched.prefix_stats()
        assert stats["hits"] >= len(TAILS) - 1
        assert stats["prefill_tokens_skipped_frac"] > 0.5
        assert sched.prefix_cache.pinned_nodes() == 0

    def test_sampled_hits_identical_to_cold(self, setup, engine):
        specs = [dict(tokens=SYSTEM + tail, max_new_tokens=6,
                      temperature=0.8, top_k=tk, top_p=tp, rng=100 + i)
                 for i, (tail, (tk, tp)) in enumerate(zip(
                     TAILS, [(None, None), (20, None), (None, 0.9),
                             (20, 0.9)]))]
        cold, _ = _run_trace(engine, specs, None)
        warm, sched = _run_trace(engine, specs,
                                 RadixPrefixCache(64 << 20))
        assert warm == cold
        assert sched.prefix_hits >= len(TAILS) - 1

    def test_concurrent_hits_across_interleaved_slots(self, setup,
                                                      engine):
        """After one request warms the cache, a burst admitted into
        every slot in the SAME iteration all hit and all match cold."""
        specs = [dict(tokens=SYSTEM + tail, max_new_tokens=5, rng=7 + i)
                 for i, tail in enumerate(TAILS)]
        cold, _ = _run_trace(engine, specs, None)
        cache = RadixPrefixCache(64 << 20)
        sched = Scheduler(engine, prefix_cache=cache)
        sched.submit(Request(tokens=SYSTEM + [99], max_new_tokens=1))
        sched.run_until_idle(50_000)
        reqs = [sched.submit(Request(**s)) for s in specs]
        sched.run_until_idle(50_000)
        assert [r.generated for r in reqs] == cold
        assert sched.prefix_hits >= len(TAILS)
        assert cache.pinned_nodes() == 0


    def test_shared_system_prompt_skips_nine_tenths_of_prefill(
            self, engine):
        """One cold request seeds a 72-token system prompt; sixteen more
        share it and differ in a 4-token tail: each starts its prefill at
        the match boundary, counted by the scheduler's own counters over
        the requests after the seed."""
        rng = np.random.default_rng(0)
        system = rng.integers(1, 200, 72).tolist()
        sched = Scheduler(engine, max_queue=32,
                          prefix_cache=RadixPrefixCache(64 << 20))
        sched.submit(Request(system + [207, 208, 209, 210],
                             max_new_tokens=4))
        sched.run_until_idle(50_000)
        hit0, prompt0 = sched.prefix_hit_tokens, sched.prefix_prompt_tokens
        assert (hit0, prompt0) == (0, 76)
        reqs = [sched.submit(Request(
            system + [211 + i] + rng.integers(1, 200, 3).tolist(),
            max_new_tokens=4, rng=i)) for i in range(16)]
        sched.run_until_idle(50_000)
        assert all(len(r.generated) == 4 for r in reqs)
        assert sched.prefix_prompt_tokens - prompt0 == 16 * 76
        assert sched.prefix_hit_tokens - hit0 == 16 * 72
        assert sched.prefix_cache.pinned_nodes() == 0


# ---------------------------------------------------------------------------
# Cancellation mid-prefill releases the prefix pin (no leaked refs)
# ---------------------------------------------------------------------------

class TestCancellationReleasesPin:
    def test_cancel_mid_prefill_drops_pin(self, engine):
        cache = RadixPrefixCache(64 << 20)
        # prefill_budget=1 -> one 16-token chunk per iteration, so a
        # 90-token prompt seeded at 40 stays in prefill for 3+ steps
        sched = Scheduler(engine, prefix_cache=cache, prefill_budget=1)
        warm = Request(SYSTEM + [99], max_new_tokens=1)
        sched.submit(warm)
        sched.run_until_idle(50_000)
        assert cache.stats()["cached_tokens"] >= len(SYSTEM)
        victim = Request(SYSTEM + list(range(200, 250)),
                         max_new_tokens=4)
        sched.submit(victim)
        sched.step()
        assert victim.state == "prefill"
        assert victim._prefix_handle is not None
        assert cache.pinned_nodes() >= 1
        assert sched.cancel(victim.id)
        sched.step()
        assert victim.reason == "cancelled"
        assert victim._prefix_handle is None
        assert cache.pinned_nodes() == 0
        sched.run_until_idle(50_000)
        assert len(engine.free_slots()) == engine.max_slots
        # the pin never blocked eviction: the cached prefix is intact
        # and the next request still hits
        again = Request(SYSTEM + [111], max_new_tokens=2)
        sched.submit(again)
        sched.run_until_idle(50_000)
        assert sched.prefix_hits >= 2

    def test_queued_cancel_never_takes_a_pin(self, engine):
        cache = RadixPrefixCache(64 << 20)
        sched = Scheduler(engine, prefix_cache=cache)
        req = Request(SYSTEM + [7], max_new_tokens=4)
        sched.submit(req)
        req.cancel()  # cancelled while still queued: reaped, not seeded
        sched.run_until_idle(50_000)
        assert req.reason == "cancelled"
        assert cache.pinned_nodes() == 0


# ---------------------------------------------------------------------------
# Disaggregated handoff: prefill-only -> wire frame -> decode identity
# ---------------------------------------------------------------------------

class TestPrefillHandoff:
    def test_handoff_chain_token_identical(self, setup, engine):
        prompt = list(range(3, 43))
        cold, _ = _run_trace(engine, [dict(
            tokens=prompt, max_new_tokens=6, temperature=0.7, rng=5)],
            None)
        psched = Scheduler(engine)
        preq = Request(prompt, max_new_tokens=6, temperature=0.7, rng=5,
                       prefill_only=True)
        psched.submit(preq)
        psched.run_until_idle(50_000)
        assert preq.reason == "prefilled" and preq.state == "finished"
        assert preq.generated == cold[0][:1]
        frame = encode_handoff(
            {"first": preq.handoff["first"], "note": "x"},
            preq.handoff["kv"])
        meta, kv = decode_handoff(frame)
        assert meta["note"] == "x"
        assert kv["k"].dtype == preq.handoff["kv"]["k"].dtype
        np.testing.assert_array_equal(
            np.asarray(kv["k"]), np.asarray(preq.handoff["kv"]["k"]))
        np.testing.assert_array_equal(
            np.asarray(kv["v"]), np.asarray(preq.handoff["kv"]["v"]))
        dsched = Scheduler(engine)
        dreq = Request(prompt, max_new_tokens=6, temperature=0.7, rng=5,
                       prefilled={"first": int(meta["first"]), "kv": kv})
        dsched.submit(dreq)
        dsched.run_until_idle(50_000)
        assert dreq.result(timeout=10) == cold[0]

    def test_frame_rejects_garbage(self):
        with pytest.raises(ValueError):
            decode_handoff(b"not a frame")
        frame = encode_handoff({"first": 1}, _kv([1, 2, 3]))
        with pytest.raises(ValueError):
            decode_handoff(frame[:-8])  # truncated payload


# ---------------------------------------------------------------------------
# Retry-After on replica sheds + the prefix blocks in healthz/stats
# ---------------------------------------------------------------------------

def _http(port, method, path, body=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request(method, path,
                     json.dumps(body) if body is not None else None,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), \
            json.loads(resp.read() or b"null")
    finally:
        conn.close()


class TestServerRetryAfter:
    def test_retry_after_hint_is_clamped_pressure(self):
        assert retry_after_hint(0, 4) == 1
        assert retry_after_hint(7, 2) == 4
        assert retry_after_hint(10_000, 1) == 60
        assert retry_after_hint(5, 0) == 5  # zero capacity clamps to 1

    def test_shed_responses_carry_retry_after(self, engine):
        cache = RadixPrefixCache(4 << 20)
        sched = Scheduler(engine, max_queue=0, prefix_cache=cache)
        srv = ServingServer(sched, port=0).start()
        try:
            st, headers, body = _http(srv.port, "POST", "/v1/generate",
                                      {"tokens": [1, 2, 3],
                                       "max_new_tokens": 2})
            assert st == 429 and "error" in body
            assert 1 <= int(headers["Retry-After"]) <= 60
            sched._draining = True
            try:
                st, headers, body = _http(
                    srv.port, "POST", "/v1/generate",
                    {"tokens": [1, 2, 3], "max_new_tokens": 2})
                assert st == 503 and "error" in body
                assert 1 <= int(headers["Retry-After"]) <= 60
            finally:
                sched._draining = False
            # /v1/prefill sheds through the same path
            st, headers, _ = _http(srv.port, "POST", "/v1/prefill",
                                   {"tokens": [1, 2, 3],
                                    "max_new_tokens": 2})
            assert st == 429 and "Retry-After" in headers
        finally:
            srv.close()

    def test_healthz_and_stats_carry_prefix_block(self, engine):
        from schema_validate import validate_healthz

        cache = RadixPrefixCache(4 << 20)
        sched = Scheduler(engine, prefix_cache=cache)
        srv = ServingServer(sched, port=0, role="decode").start()
        try:
            st, _, hz = _http(srv.port, "GET", "/healthz")
            assert st == 200
            validate_healthz(hz)
            assert hz["role"] == "decode"
            assert hz["prefix_cache"]["enabled"] is True
            st, _, stats = _http(srv.port, "GET", "/v1/stats")
            pc = stats["prefix_cache"]
            assert pc["enabled"] and "hit_rate" in pc
            assert "prefill_tokens_skipped_frac" in pc
        finally:
            srv.close()

    def test_role_is_validated(self, engine):
        with pytest.raises(ValueError):
            ServingServer(Scheduler(engine), port=0, role="router")


# ---------------------------------------------------------------------------
# Pinned serve.prefix.* telemetry, end to end through `tpuflow metrics`
# ---------------------------------------------------------------------------

class TestPrefixTelemetry:
    def test_prefix_events_match_pinned_schema(self, setup, engine,
                                               tmp_path):
        from schema_validate import validate_serving_record

        from metaflow_tpu import telemetry
        from metaflow_tpu.cmd.metrics import aggregate
        from metaflow_tpu.datastore import FlowDataStore, LocalStorage

        # size the budget from MEASURED bytes so the third (disjoint)
        # prompt forces an LRU eviction regardless of the KV dtype
        probe = RadixPrefixCache(1 << 30)
        _run_trace(engine, [dict(tokens=SYSTEM + TAILS[0],
                                 max_new_tokens=1)], probe)
        bytes_one = probe.stats()["cached_bytes"]
        assert bytes_one > 0
        fds = FlowDataStore("PrefixTelemetry", LocalStorage,
                            ds_root=str(tmp_path))
        telemetry.init_recorder(fds, "1", "_serve", "prefix-test")
        try:
            cache = RadixPrefixCache(int(bytes_one * 1.5))
            specs = [
                dict(tokens=SYSTEM + TAILS[0], max_new_tokens=2),  # miss
                dict(tokens=SYSTEM + TAILS[1], max_new_tokens=2),  # hit
                dict(tokens=list(range(300, 340)),
                     max_new_tokens=2),                    # miss + evict
            ]
            _run_trace(engine, specs, cache)
            assert cache.stats()["evictions"] >= 1
        finally:
            telemetry.close_recorder()
        records = telemetry.read_run_records(fds, "1")
        prefix = [r for r in records
                  if r["name"].startswith("serve.prefix.")]
        names = {r["name"] for r in prefix}
        assert {"serve.prefix.hit", "serve.prefix.miss",
                "serve.prefix.evict"} <= names
        for rec in prefix:
            validate_serving_record(rec)
        agg = aggregate(records)
        pc = agg["prefix_cache"]
        assert pc["hits"] >= 1 and pc["misses"] >= 2
        assert pc["evictions"] >= 1
        assert 0 < pc["prefill_tokens_skipped_frac"] < 1
