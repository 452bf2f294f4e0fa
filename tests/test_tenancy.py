"""Cache-aware multi-tenant routing tier: TenancyConfig/TenantQueues
DRR fair share + strict priority, TokenBudgets rolling windows, the
CacheRouter digest scoring + least-loaded fallback, scheduler-level
tenant admission (budget Retry-After, priority shed, the pinned
high-priority first-token gate under low-priority saturation, counted
in scheduler iterations),
the fleet router's tenant-scoped Retry-After (the bugfix: a throttled
tenant must NOT inherit the global capacity hint), cache-aware
dispatch end to end with the pinned serve.tenant.* / fleet.cache_route.*
telemetry schemas, and the federation front tier (pins, hash spread,
fleet failover, zero shed during one fleet's rolling reload)."""

import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import pytest

from metaflow_tpu.models import llama
from metaflow_tpu.serving import (
    Request,
    Scheduler,
    ServingFleet,
    SlotEngine,
    TenantThrottledError,
)
from metaflow_tpu.serving.cache_router import CacheRouter, PromptChains
from metaflow_tpu.serving.fleet import FleetConfig
from metaflow_tpu.serving.prefix_cache import (
    RadixPrefixCache,
    route_digest_chain,
)
from metaflow_tpu.serving.tenancy import (
    FederationRouter,
    TenancyConfig,
    TenantQueues,
    TokenBudgets,
)
from test_fleet import (
    _FakeProc,
    _get_json,
    _post,
    _ref_tokens,
    _server_for,
)

import os


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _req(tokens, max_new=4, tenant=None):
    return Request(tokens, max_new_tokens=max_new, tenant=tenant)


def _eventually(predicate, timeout_s=10.0):
    """A router counts a request after it has written the response, so
    a client that has read the response may look a moment early."""
    deadline = time.monotonic() + timeout_s
    while not predicate() and time.monotonic() < deadline:
        time.sleep(0.01)
    return predicate()


# ---------------------------------------------------------------------------
# TenancyConfig
# ---------------------------------------------------------------------------


class TestTenancyConfig:
    def test_empty_config_is_single_tenant(self):
        cfg = TenancyConfig()
        assert not cfg.enabled()
        assert cfg.weight("anyone") == 1.0
        assert cfg.priority_name("anyone") == "normal"
        assert cfg.budget("anyone") is None

    def test_parsing_and_malformed_entries_dropped(self):
        cfg = TenancyConfig(
            weights={"gold": "4", "free": "1", "bad": "x", "neg": "-2"},
            priorities={"gold": "high", "bulk": "low", "odd": "zzz"},
            budgets={"free": "100", "junk": "lots"})
        assert cfg.enabled()
        assert cfg.weights == {"gold": 4.0, "free": 1.0}
        assert cfg.priority_name("gold") == "high"
        assert cfg.priority_name("bulk") == "low"
        assert cfg.priority_name("odd") == "normal"   # malformed dropped
        assert cfg.budget("free") == 100
        assert cfg.budget("junk") is None
        assert set(cfg.known_tenants()) == {
            "gold", "free", "bulk"}

    def test_share_is_weight_proportional(self):
        cfg = TenancyConfig(weights={"a": 3, "b": 1})
        assert cfg.share("a", 64) == 48
        assert cfg.share("b", 64) == 16
        # an unknown tenant joins the pool with weight 1, never below 1
        assert cfg.share("c", 2) >= 1

    def test_low_priority_share_leaves_headroom(self):
        cfg = TenancyConfig(weights={"gold": 4, "bulk": 1},
                            priorities={"gold": "high", "bulk": "low"})
        assert cfg.low_priority_share(20) == 4   # 20 * 1/5
        # no high tenant configured -> full capacity for everyone
        flat = TenancyConfig(weights={"a": 1, "b": 1})
        assert flat.low_priority_share(20) == 20


# ---------------------------------------------------------------------------
# TenantQueues: FIFO identity, DRR fair share, strict priority, shed
# ---------------------------------------------------------------------------


class TestTenantQueues:
    def test_single_tenant_is_plain_fifo(self):
        q = TenantQueues(TenancyConfig())
        reqs = [_req(list(range(1, 5)), tenant=None) for _ in range(6)]
        for r in reqs:
            q.append(r)
        assert len(q) == 6
        assert q[0] is reqs[0]          # peek == next pop
        assert [q.popleft() for _ in range(6)] == reqs
        assert not q

    def test_drr_token_share_tracks_weights(self):
        """Weights 3:1 -> admitted TOKEN share converges to 3:1, and
        order within a tenant stays FIFO."""
        cfg = TenancyConfig(weights={"a": 3, "b": 1}, quantum=8)
        q = TenantQueues(cfg)
        a = [_req(list(range(1, 13)), max_new=4, tenant="a")
             for _ in range(40)]
        b = [_req(list(range(1, 13)), max_new=4, tenant="b")
             for _ in range(40)]
        for ra, rb in zip(a, b):
            q.append(ra)
            q.append(rb)
        popped = [q.popleft() for _ in range(32)]
        tok = {"a": 0, "b": 0}
        for r in popped:
            tok[r.tenant] += len(r.tokens) + r.max_new_tokens
        share = tok["a"] / float(tok["a"] + tok["b"])
        assert 0.65 <= share <= 0.85, tok   # expected 0.75
        # FIFO within each tenant
        assert [r for r in popped if r.tenant == "a"] == \
            a[:sum(1 for r in popped if r.tenant == "a")]
        assert [r for r in popped if r.tenant == "b"] == \
            b[:sum(1 for r in popped if r.tenant == "b")]

    def test_strict_priority_tiers_preempt_drr(self):
        cfg = TenancyConfig(priorities={"gold": "high", "bulk": "low"})
        q = TenantQueues(cfg)
        lows = [_req([1, 2, 3], tenant="bulk") for _ in range(3)]
        for r in lows:
            q.append(r)
        highs = [_req([4, 5, 6], tenant="gold") for _ in range(2)]
        for r in highs:
            q.append(r)
        # every high-priority request drains before ANY low one,
        # despite the lows being queued first
        order = [q.popleft() for _ in range(5)]
        assert order == highs + lows

    def test_appendleft_requeue_keeps_head_position(self):
        cfg = TenancyConfig(weights={"a": 1, "b": 1})
        q = TenantQueues(cfg)
        first, second = (_req([1, 2], tenant="a"),
                         _req([3, 4], tenant="a"))
        q.append(first)
        q.append(second)
        head = q.popleft()
        assert head is first
        q.appendleft(head)      # page-exhaustion backpressure path
        assert q[0] is first
        assert q.popleft() is first

    def test_shed_lowest_priority_evicts_newest_of_worst_tier(self):
        cfg = TenancyConfig(
            priorities={"gold": "high", "std": "normal", "bulk": "low"})
        q = TenantQueues(cfg)
        old_low = _req([1], tenant="bulk")
        new_low = _req([2], tenant="bulk")
        std = _req([3], tenant="std")
        for r in (old_low, std, new_low):
            q.append(r)
        victim = q.shed_lowest_priority(
            below_tier=cfg.priority("gold"))
        assert victim is new_low    # newest request of the WORST tier
        assert len(q) == 2
        # nothing below normal left except old_low; a normal-tier
        # arrival can only evict the low tier, never a peer
        assert q.shed_lowest_priority(
            below_tier=cfg.priority("std")) is old_low
        assert q.shed_lowest_priority(
            below_tier=cfg.priority("std")) is None


# ---------------------------------------------------------------------------
# TokenBudgets
# ---------------------------------------------------------------------------


class TestTokenBudgets:
    def test_admit_then_charge_and_window_reset(self):
        cfg = TenancyConfig(budgets={"t": 10}, budget_window_s=5.0)
        b = TokenBudgets(cfg)
        now = time.monotonic()
        assert b.charge("t", 8, now=now) == 0.0     # 0 < 10: admit
        # admit-then-charge: spent 8 < 10 still admits (overshoot ok)
        assert b.charge("t", 8, now=now + 0.1) == 0.0
        wait = b.charge("t", 1, now=now + 1.0)      # spent 16 >= 10
        assert 0.1 <= wait <= 5.0
        # the refusal counts down to the tenant's OWN window reset
        assert wait == pytest.approx(
            5.0 - (now + 1.0 - b._window_start), abs=0.05)
        # window rolls over: spend resets
        assert b.charge("t", 8, now=now + 6.0) == 0.0
        assert b.spent("t") == 8

    def test_unbudgeted_tenant_is_never_throttled(self):
        b = TokenBudgets(TenancyConfig(budgets={"other": 1}))
        for _ in range(50):
            assert b.charge("free", 10 ** 6) == 0.0


# ---------------------------------------------------------------------------
# CacheRouter: digest chains + scoring
# ---------------------------------------------------------------------------


class TestCacheRouter:
    def test_digest_chain_prefix_property(self):
        tokens = list(range(2, 66))                 # 64 tokens
        chain = route_digest_chain(tokens, 16)
        assert len(chain) == 4
        # the chain of a prefix IS a prefix of the chain
        assert route_digest_chain(tokens[:32], 16) == chain[:2]
        # divergence after block k changes every later digest
        other = list(tokens)
        other[40] += 1
        ochain = route_digest_chain(other, 16)
        assert ochain[:2] == chain[:2]
        assert ochain[2:] != chain[2:]

    def test_score_counts_leading_cached_blocks(self):
        router = CacheRouter(enabled=True, block=16, min_score_tokens=32)
        tokens = list(range(2, 66))
        chain = route_digest_chain(tokens, 16)
        chains = router.chains(tokens)
        stats = {"prefix_cache": {"route_block": 16,
                                  "digests": chain[:3]}}
        assert router.score(chains, stats) == 48
        # a replica publishing at its own (different) block size is
        # scored against a chain recomputed at THAT block
        stats8 = {"prefix_cache": {"route_block": 8,
                                   "digests":
                                   route_digest_chain(tokens, 8)[:5]}}
        assert router.score(chains, stats8) == 40

    def test_sub_threshold_match_is_cold(self):
        router = CacheRouter(enabled=True, block=16, min_score_tokens=32)
        tokens = list(range(2, 66))
        chain = route_digest_chain(tokens, 16)
        one_block = {"prefix_cache": {"route_block": 16,
                                      "digests": chain[:1]}}
        # 16 matched tokens < 32-token floor: accidental overlap must
        # not override load balancing
        assert router.score(router.chains(tokens), one_block) == 0

    def test_disabled_empty_and_malformed_score_zero(self):
        tokens = list(range(2, 66))
        off = CacheRouter(enabled=False, block=16, min_score_tokens=32)
        assert off.score(off.chains(tokens), {"prefix_cache": {
            "route_block": 16,
            "digests": route_digest_chain(tokens, 16)}}) == 0
        on = CacheRouter(enabled=True, block=16, min_score_tokens=32)
        assert on.score(on.chains(tokens), None) == 0
        assert on.score(on.chains(tokens), {}) == 0
        assert on.score(None, {"prefix_cache": {}}) == 0
        # malformed prompt: chain degrades to [] and the replica 400s it
        assert PromptChains(["not", "tokens"]).chain(16) == []
        assert PromptChains([1, 2, 3]).chain(0) == []


# ---------------------------------------------------------------------------
# Scheduler-level tenancy: budget throttle, priority shed, TTFT gate
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine1(setup):
    """A ONE-slot engine: with a single slot the service order IS the
    admission order, which makes the priority-vs-FIFO TTFT comparison
    deterministic. Warmed so no trial ever pays a compile."""
    cfg, params = setup
    eng = SlotEngine(params, cfg, max_slots=1, max_seq_len=96,
                     prefill_chunk=16)
    warm = Scheduler(eng, tenancy=TenancyConfig())
    warm.submit(Request(list(range(1, 25)), max_new_tokens=2))
    warm.run_until_idle(10_000)
    return eng


class TestSchedulerTenancy:
    def test_budget_throttle_carries_tenant_retry_after(self, engine1):
        tcfg = TenancyConfig(budgets={"bulk": 60}, budget_window_s=30.0)
        sched = Scheduler(engine1, tenancy=tcfg)
        sched.submit(_req(list(range(1, 29)), max_new=4, tenant="bulk"))
        sched.submit(_req(list(range(1, 29)), max_new=4, tenant="bulk"))
        with pytest.raises(TenantThrottledError) as exc:
            sched.submit(_req(list(range(1, 29)), max_new=4,
                              tenant="bulk"))
        assert exc.value.tenant == "bulk"
        assert exc.value.reason == "budget"
        # the wait is the tenant's own window reset, never more
        assert 0.0 < exc.value.retry_after_s <= 30.0
        # untagged (single-tenant) traffic is never throttled
        sched.submit(_req(list(range(1, 29)), max_new=2))
        sched.run_until_idle(10_000)

    def test_priority_shed_evicts_newest_low_request(self, engine1):
        """Queue FULL (untagged traffic fills it past any per-tenant
        share) + one queued low-priority request: a high-priority
        arrival evicts the low request instead of being turned away."""
        tcfg = TenancyConfig(priorities={"gold": "high", "bulk": "low"})
        sched = Scheduler(engine1, max_queue=3, tenancy=tcfg)
        untagged = [_req([1, 2, 3, int(i)], max_new=2)
                    for i in range(4, 6)]
        low = _req([7, 7, 7], max_new=2, tenant="bulk")
        for r in untagged + [low]:
            sched.submit(r)
        gold = _req([9, 9, 9], max_new=2, tenant="gold")
        sched.submit(gold)     # full: evicts the worst tier's newest
        assert low.reason == "shed"
        assert low.state in ("finished", "cancelled")
        # the high tier then drains FIRST; untagged keeps FIFO order
        assert sched._queue.popleft() is gold
        assert sched._queue.popleft() is untagged[0]
        assert sched._queue.popleft() is untagged[1]
        # and the share guard still throttles a tenant flooding past
        # its own slice of the queue
        sched2 = Scheduler(engine1, max_queue=4, tenancy=tcfg)
        sched2.submit(_req([1, 2], max_new=2, tenant="bulk"))
        sched2.submit(_req([1, 2], max_new=2, tenant="bulk"))
        with pytest.raises(TenantThrottledError) as exc:
            sched2.submit(_req([1, 2], max_new=2, tenant="bulk"))
        assert exc.value.reason == "queue_share"
        assert exc.value.retry_after_s >= 1

    def test_high_priority_p99_ttft_gate_under_saturation(self, engine1):
        """THE acceptance pin: while a low-priority tenant saturates
        the queue, the high-priority tenant's first token comes after
        as many scheduler iterations as with the queue to itself —
        strict-priority DRR admits it next, so contention adds
        queue-pick time only. The FIFO counterfactual (no tenancy)
        shows the gate is not vacuous."""
        tcfg = TenancyConfig(weights={"gold": 4, "bulk": 1},
                             priorities={"gold": "high", "bulk": "low"})
        high_prompt = list(range(2, 34))       # 32 tokens, 2 chunks
        flood_prompt = list(range(40, 64))     # 24 tokens

        def trial(flood, tenancy):
            """Scheduler iterations from submit to the high request's
            first token, behind `flood` earlier-queued low requests."""
            sched = Scheduler(engine1, tenancy=tenancy)
            lows = [Request(flood_prompt, max_new_tokens=4,
                            tenant="bulk" if tenancy.enabled() else None)
                    for _ in range(flood)]
            for r in lows:
                sched.submit(r)
            high = Request(high_prompt, max_new_tokens=2,
                           tenant="gold" if tenancy.enabled() else None)
            sched.submit(high)
            while high.t_first is None:
                sched.step()
                assert sched.iteration < 10_000
            waited = sched.iteration
            sched.run_until_idle(100_000)
            assert sched.stats()["iterations"] == sched.iteration
            if flood and tenancy.enabled():
                # served before every one of the earlier-queued lows
                assert high.t_first < min(r.t_first for r in lows)
            return waited

        solo = trial(0, tcfg)
        assert trial(8, tcfg) == solo
        # counterfactual: FIFO (tenancy off) makes the same request
        # wait behind the whole flood
        fifo = trial(8, TenancyConfig())
        assert fifo > 3 * solo, \
            "FIFO first token after %d iterations should dwarf solo %d" % (
                fifo, solo)


# ---------------------------------------------------------------------------
# Fleet: tenant-scoped Retry-After, cache-aware dispatch, pinned schemas
# ---------------------------------------------------------------------------

_MT_ENV = {
    "TPUFLOW_TENANT_PRIORITIES": "gold=high,bulk=low",
    "TPUFLOW_TENANT_WEIGHTS": "gold=4,bulk=1",
    "TPUFLOW_TENANT_BUDGETS": "bulk=90",
    # long window: the fixture boots engines and runs several tests
    # before the throttle assertion — the window must not roll over
    "TPUFLOW_TENANT_BUDGET_WINDOW_S": "600",
    "TPUFLOW_CACHE_ROUTE": "1",
}


def _make_cached_spawner(setup, servers):
    """In-process replica factory with a radix prefix cache, so the
    replicas publish route digests for the cache-aware dispatch tests."""
    cfg, params = setup
    build_lock = threading.Lock()

    def spawn(index, generation):
        with build_lock:
            eng = SlotEngine(params, cfg, max_slots=2, max_seq_len=96,
                             prefill_chunk=16)
            from metaflow_tpu.serving import ServingServer
            srv = ServingServer(
                Scheduler(eng, prefix_cache=RadixPrefixCache(8 << 20)),
                port=0).start()
        servers.append((index, generation, srv))
        return _FakeProc(srv), "127.0.0.1", srv.port

    return spawn


@pytest.fixture(scope="module")
def mt_fleet(setup, tmp_path_factory):
    """A 2-replica in-process fleet with tenancy + cache routing on and
    the flight recorder installed: the tests below provoke tenant
    admission, budget throttles and cache-affine dispatch, and the
    final test validates everything emitted against the pinned
    schemas."""
    from metaflow_tpu import telemetry
    from metaflow_tpu.datastore import FlowDataStore, LocalStorage

    saved = {k: os.environ.get(k) for k in _MT_ENV}
    os.environ.update(_MT_ENV)
    ds_root = str(tmp_path_factory.mktemp("tenancy-telemetry"))
    fds = FlowDataStore("TenancyTelemetry", LocalStorage, ds_root=ds_root)
    telemetry.init_recorder(fds, "1", "_serve", "tenancy-test")
    servers = []
    config = FleetConfig(failover=True, restart=False,
                         health_interval_s=0.3, wait_s=2.0,
                         spawn_timeout_s=60.0)
    fleet = ServingFleet(_make_cached_spawner(setup, servers), 2,
                         config=config)
    fleet.start()
    try:
        yield fleet, servers, fds
    finally:
        fleet.close()
        telemetry.close_recorder()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


class TestFleetTenancy:
    """Tests run in definition order and share the module fleet; the
    final test closes the recorder and validates everything emitted."""

    def test_cache_aware_dispatch_prefers_warm_replica(self, setup,
                                                       mt_fleet):
        cfg, params = setup
        fleet, _servers, _fds = mt_fleet
        prompt = list(range(2, 34))     # 32 tokens = 2 digest blocks
        conn, resp = _post(fleet.port, {
            "tokens": prompt, "max_new_tokens": 4, "seed": 5,
            "tenant": "gold"})
        assert resp.status == 200
        first = json.loads(resp.read())
        conn.close()
        assert first["new_tokens"] == _ref_tokens(params, cfg, prompt,
                                                  4, seed=5)
        # wait for the warm replica's digests to ride a health probe
        time.sleep(3 * fleet.config.health_interval_s + 0.3)
        conn, resp = _post(fleet.port, {
            "tokens": prompt, "max_new_tokens": 4, "seed": 5,
            "tenant": "gold"})
        assert resp.status == 200
        second = json.loads(resp.read())
        conn.close()
        # token identity is unconditional; the warm replica wins the pick
        assert second["new_tokens"] == first["new_tokens"]
        assert second["replica"] == first["replica"]
        stats = _get_json(fleet.port, "/v1/stats")
        assert stats["cache_route"]["hits"] >= 1
        assert stats["cache_route"]["misses"] >= 1   # the cold first ask

    def test_pick_scores_beat_load_and_all_cold_falls_back(self, mt_fleet):
        fleet, _servers, _fds = mt_fleet
        prompt = list(range(2, 66))
        chain = route_digest_chain(prompt, 16)
        handles = sorted(fleet.handles, key=lambda h: h.index)

        def inject_and_pick():
            # replica B warm (full chain), replica A one block (cold:
            # under the 32-token floor) but less loaded
            with fleet._lock:
                handles[0].last_stats = dict(
                    handles[0].last_stats or {}, queue_depth=0,
                    prefix_cache={"route_block": 16,
                                  "digests": chain[:1]})
                handles[1].last_stats = dict(
                    handles[1].last_stats or {}, queue_depth=5,
                    prefix_cache={"route_block": 16, "digests": chain})
            h = fleet._pick(None, set(),
                            chains=fleet.cache_router.chains(prompt))
            with fleet._lock:
                h.inflight -= 1     # undo the pick's reservation
            return h

        # a health probe may overwrite the injected stats in the tiny
        # window before _pick reads them; retry bounds that race
        for _ in range(3):
            h = inject_and_pick()
            if h.index == handles[1].index:
                break
        assert h.index == handles[1].index
        # an unseen prompt is all-cold: bit-identical least-loaded order
        cold = fleet._pick(None, set(),
                           chains=fleet.cache_router.chains(
                               list(range(70, 90))))
        with fleet._lock:
            cold.inflight -= 1
        assert cold.index == handles[0].index
        # let real probes replace the injected stats before later tests
        time.sleep(2 * fleet.config.health_interval_s + 0.2)

    def test_budget_throttle_uses_tenant_window_not_global_hint(
            self, mt_fleet):
        """THE bugfix pin: a budget-throttled tenant's Retry-After is
        its own window reset (tens of seconds here), not the fleet's
        capacity-pressure hint (~1s on an idle fleet)."""
        fleet, servers, _fds = mt_fleet
        prompt = list(range(100, 144))      # cost 44 + 4 = 48 tokens
        statuses, bulk_replicas = [], []
        for i in range(2):                  # 48, then 96 > 90 budget
            if i:
                # let the first ask's digests ride a health probe, so
                # the second lands cache-affine on the SAME replica —
                # concentrating the tenant's replica-level spend there
                time.sleep(3 * fleet.config.health_interval_s + 0.3)
            conn, resp = _post(fleet.port, {
                "tokens": prompt, "max_new_tokens": 4, "seed": 1,
                "tenant": "bulk"})
            statuses.append(resp.status)
            bulk_replicas.append(json.loads(resp.read())["replica"])
            conn.close()
        assert statuses == [200, 200]       # admit-then-charge
        assert bulk_replicas[0] == bulk_replicas[1]
        conn, resp = _post(fleet.port, {
            "tokens": prompt, "max_new_tokens": 4, "seed": 1,
            "tenant": "bulk"})
        assert resp.status == 429
        retry_after = int(resp.getheader("Retry-After"))
        body = json.loads(resp.read())
        conn.close()
        assert body["reason"] == "tenant_budget"
        assert body["tenant"] == "bulk"     # sheds echo the tenant
        global_hint = fleet._retry_after()
        assert retry_after > global_hint, \
            "tenant Retry-After %ds must not be the global hint %ds" % (
                retry_after, global_hint)
        # the hint counts down the tenant's OWN 600s window
        assert 30 <= retry_after <= 601
        # the replica-level scheduler enforces the same budget with the
        # same tenant-scoped hint (its own bucket saw both admits):
        # hit the warm replica's server directly, bypassing the router
        warm_srv = _server_for(servers, bulk_replicas[0])
        conn, resp = _post(warm_srv.port, {
            "tokens": prompt, "max_new_tokens": 4, "seed": 1,
            "tenant": "bulk"})
        assert resp.status == 429
        replica_body = json.loads(resp.read())
        conn.close()
        assert replica_body["reason"] == "budget"
        assert replica_body["tenant"] == "bulk"
        assert int(resp.getheader("Retry-After")) >= 30
        # an unbudgeted high-priority tenant sails through
        conn, resp = _post(fleet.port, {
            "tokens": prompt, "max_new_tokens": 4, "seed": 1,
            "tenant": "gold"})
        assert resp.status == 200
        resp.read()
        conn.close()

    def test_healthz_and_stats_tenant_rollup(self, mt_fleet):
        from schema_validate import validate_fleet_healthz

        fleet, _servers, _fds = mt_fleet
        hz = _get_json(fleet.port, "/healthz")
        validate_fleet_healthz(hz)
        assert hz["tenants"]["enabled"] is True
        gold = hz["tenants"]["tenants"]["gold"]
        bulk = hz["tenants"]["tenants"]["bulk"]
        assert gold["priority"] == "high" and gold["weight"] == 4.0
        assert gold["forwarded"] >= 3 and gold["shed"] == 0
        assert gold["p99_ttft_ms"] > 0
        assert bulk["priority"] == "low" and bulk["shed"] >= 1

    def test_tenant_telemetry_schema_and_metrics(self, mt_fleet):
        """LAST (order matters): every serve.tenant.* and
        fleet.cache_route.* record emitted above validates against the
        pinned schemas, and `tpuflow metrics` aggregates them into the
        tenants + routing blocks."""
        from schema_validate import (
            validate_fleet_record,
            validate_serving_record,
        )

        from metaflow_tpu import telemetry
        from metaflow_tpu.cmd.metrics import aggregate

        _fleet, _servers, fds = mt_fleet
        telemetry.close_recorder()
        records = telemetry.read_run_records(fds, "1")
        tenant_recs = [r for r in records
                       if r["name"].startswith("serve.tenant.")]
        route_recs = [r for r in records
                      if r["name"].startswith("fleet.cache_route.")]
        assert tenant_recs and route_recs
        for rec in tenant_recs:
            validate_serving_record(rec)
        for rec in route_recs:
            validate_fleet_record(rec)
        names = {r["name"] for r in tenant_recs}
        assert {"serve.tenant.admitted",
                "serve.tenant.throttled"} <= names
        assert {"fleet.cache_route.hit", "fleet.cache_route.miss"} <= {
            r["name"] for r in route_recs}
        agg = aggregate(records)
        tenants = agg["tenants"]
        assert tenants["gold"]["admitted"] >= 3
        assert tenants["gold"]["ttft_p99_ms"] > 0
        assert tenants["bulk"]["throttled"] >= 1
        assert tenants["bulk"]["throttles"].get("budget", 0) >= 1
        routing = agg["cache_route"]
        assert routing["hits"] >= 1 and routing["misses"] >= 1
        assert 0 < routing["routed_tokens_frac"] <= 1
        assert 0 < routing["warm_rate"] < 1


# ---------------------------------------------------------------------------
# Federation: pins, hash spread, failover, zero shed during a rollout
# ---------------------------------------------------------------------------


@pytest.fixture()
def federation(setup, monkeypatch):
    """Two single-replica in-process fleets behind one FederationRouter,
    tenants pinned one per fleet."""
    from metaflow_tpu.serving import ServingServer

    monkeypatch.setenv("TPUFLOW_TENANT_FLEET_MAP", "alpha=0,beta=1")
    monkeypatch.setenv("TPUFLOW_TENANT_WEIGHTS", "alpha=1,beta=1")
    # the module fleet's tenancy env must not leak into this topology
    for var in ("TPUFLOW_CACHE_ROUTE", "TPUFLOW_TENANT_PRIORITIES",
                "TPUFLOW_TENANT_BUDGETS",
                "TPUFLOW_TENANT_BUDGET_WINDOW_S"):
        monkeypatch.delenv(var, raising=False)
    cfg, params = setup
    build_lock = threading.Lock()
    fleets = []

    def make_spawner():
        def spawn(index, generation):
            with build_lock:
                eng = SlotEngine(params, cfg, max_slots=2,
                                 max_seq_len=96, prefill_chunk=16)
                srv = ServingServer(Scheduler(eng), port=0).start()
            return _FakeProc(srv), "127.0.0.1", srv.port
        return spawn

    config = FleetConfig(failover=True, restart=False,
                         health_interval_s=0.3, wait_s=2.0,
                         spawn_timeout_s=60.0)
    for _ in range(2):
        fleet = ServingFleet(make_spawner(), 1, config=config)
        fleet.start()
        fleets.append(fleet)
    front = FederationRouter(
        ["http://127.0.0.1:%d" % f.port for f in fleets],
        poll_interval_s=0.2).start()
    try:
        yield front, fleets
    finally:
        front.close()
        for f in fleets:
            f.close()


class TestFederation:
    def test_pins_and_stable_hash_spread(self, federation):
        front, _fleets = federation
        assert front.preferred_fleet("alpha") == 0
        assert front.preferred_fleet("beta") == 1
        # unpinned tenants spread stably: same answer across restarts
        # (sha1, not PYTHONHASHSEED-dependent hash())
        spread = front.preferred_fleet("zeta")
        assert spread in (0, 1)
        again = FederationRouter(["http://x", "http://y"])
        assert again.preferred_fleet("zeta") == spread
        hz = _get_json(front.port, "/healthz")
        assert hz["ok"] is True
        assert len(hz["fleets"]) == 2
        assert hz["tenants"] == {"alpha": 0, "beta": 1}

    def test_forward_to_pinned_fleet_token_identical(self, setup,
                                                     federation):
        cfg, params = setup
        front, fleets = federation
        prompt = list(range(3, 19))
        for tenant in ("alpha", "beta"):
            conn, resp = _post(front.port, {
                "tokens": prompt, "max_new_tokens": 4, "seed": 2,
                "tenant": tenant})
            assert resp.status == 200
            body = json.loads(resp.read())
            conn.close()
            assert body["new_tokens"] == _ref_tokens(
                params, cfg, prompt, 4, seed=2)
        stats = _get_json(front.port, "/v1/stats")
        assert stats["forwarded"] >= 2 and stats["shed"] == 0
        # each pinned tenant landed on its own fleet
        assert _eventually(lambda: all(f.completed >= 1 for f in fleets))

    def test_draining_fleet_fails_over_not_sheds(self, federation):
        front, fleets = federation
        done_before = fleets[1].completed
        fleets[0]._draining = True
        try:
            conn, resp = _post(front.port, {
                "tokens": list(range(3, 11)), "max_new_tokens": 3,
                "seed": 7, "tenant": "alpha"})    # pinned to fleet 0
            assert resp.status == 200
            resp.read()
            conn.close()
        finally:
            fleets[0]._draining = False
        # the draining fleet 503s (or was already demoted by a poll);
        # either way the sibling serves and nothing is shed
        assert _eventually(
            lambda: fleets[1].completed == done_before + 1)
        assert front.shed == 0

    def test_zero_shed_during_one_fleet_rolling_reload(self, setup,
                                                       federation):
        """THE federation acceptance pin: tenant alpha keeps getting
        200s through the front while its pinned fleet rolls every
        replica to a new generation."""
        cfg, params = setup
        front, fleets = federation
        prompt = list(range(5, 21))
        expected = _ref_tokens(params, cfg, prompt, 3, seed=9)
        gen0 = fleets[0].fleet_generation
        rollout = threading.Thread(
            target=fleets[0].rolling_reload, daemon=True)
        rollout.start()
        served = 0
        deadline = time.monotonic() + 30.0
        while (rollout.is_alive() or served == 0) \
                and time.monotonic() < deadline:
            conn, resp = _post(front.port, {
                "tokens": prompt, "max_new_tokens": 3, "seed": 9,
                "tenant": "alpha"})
            assert resp.status == 200, \
                "shed during rolling reload: %d" % resp.status
            body = json.loads(resp.read())
            conn.close()
            assert body["new_tokens"] == expected
            served += 1
        rollout.join(timeout=30)
        assert not rollout.is_alive()
        assert fleets[0].fleet_generation == gen0 + 1
        assert served >= 1
        assert front.shed == 0
        hz = _get_json(front.port, "/healthz")
        assert hz["ok"] is True


# ---------------------------------------------------------------------------
# Cache-aware against least-loaded dispatch on one multi-tenant trace
# ---------------------------------------------------------------------------


class TestRoutePolicies:
    SYS_TOKENS = 48     # three route-digest blocks at the default 16
    TENANTS, PER_TENANT, MAX_NEW = 3, 4, 4

    def _run_pass(self, setup, cache_route):
        """A fresh 2-replica in-process fleet under one dispatch policy:
        seed every tenant's system prompt (an idle fleet sends each seed
        to replica 0 under either policy), hand the router the replicas'
        digests, then one concurrent burst a tenant. A burst's requests
        are held at the replicas' doors until the router has dispatched
        all of them, so every pick sees the ones before it in flight.
        Returns (prefill tokens the replicas' caches skipped, outputs)."""
        servers = []
        fleet = ServingFleet(
            _make_cached_spawner(setup, servers), 2,
            config=FleetConfig(failover=True, restart=False,
                               health_interval_s=600.0, wait_s=2.0,
                               spawn_timeout_s=60.0))
        fleet.cache_router = CacheRouter(enabled=cache_route)
        fleet.start()
        try:
            prompts = [list(range(2 + t * self.SYS_TOKENS,
                                  2 + (t + 1) * self.SYS_TOKENS))
                       for t in range(self.TENANTS)]

            def ask(tenant, tail, seed):
                conn, resp = _post(fleet.port, {
                    "tokens": prompts[tenant] + tail,
                    "max_new_tokens": self.MAX_NEW, "seed": seed,
                    "tenant": "tenant%d" % tenant})
                try:
                    assert resp.status == 200
                    return json.loads(resp.read())["new_tokens"]
                finally:
                    conn.close()

            for t in range(self.TENANTS):
                ask(t, [240, 241, 242, 243], 1000 + t)
            for h in fleet.handles:
                h.last_stats = fleet._probe(h)
            door = threading.Barrier(self.PER_TENANT)
            for _i, _g, srv in servers:
                def held(req, submit=srv.scheduler.submit):
                    door.wait(timeout=120)
                    return submit(req)
                srv.scheduler.submit = held
            hit0 = sum(srv.scheduler.prefix_hit_tokens
                       for _i, _g, srv in servers)
            outs = []
            with ThreadPoolExecutor(self.PER_TENANT) as pool:
                for t in range(self.TENANTS):
                    # map drains a burst before the next tenant's begins
                    outs.extend(pool.map(
                        lambda i, t=t: ask(
                            t, [200 + 10 * i + t, 221, 222, 223],
                            t * self.PER_TENANT + i),
                        range(self.PER_TENANT)))
            skipped = sum(srv.scheduler.prefix_hit_tokens
                          for _i, _g, srv in servers) - hit0
            return skipped, outs
        finally:
            fleet.close()

    def test_cache_aware_skips_more_prefill_than_least_loaded(
            self, setup, monkeypatch):
        """Least-loaded dispatch spreads a tenant's burst over both
        replicas by the in-flight count, so the cold one pays the
        tenant's prefill again; cache-aware dispatch sends the whole
        burst to the replica whose radix tree holds the prefix. Routing
        changes WHERE prefill runs, never what it computes."""
        for key in _MT_ENV:     # the module fleet's budgets and shares
            monkeypatch.delenv(key, raising=False)
        aware, aware_outs = self._run_pass(setup, True)
        spread, spread_outs = self._run_pass(setup, False)
        n = self.TENANTS * self.PER_TENANT
        assert aware == n * self.SYS_TOKENS
        # two of a burst's four land on the replica the seed warmed; the
        # first to reach the other one finds it cold
        assert n // 2 * self.SYS_TOKENS <= spread \
            <= self.TENANTS * (self.PER_TENANT - 1) * self.SYS_TOKENS
        assert aware_outs == spread_outs
