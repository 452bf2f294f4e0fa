"""Request scheduler for the continuous-batching engine.

The engine owns the device; this module owns time: a bounded request
queue with backpressure, per-iteration admission into free slots, a
token-budget prefill/decode interleave (long prompts prefill in chunks
between decode steps instead of stalling every active request), per
request deadlines and cancellation, and graceful drain for SIGTERM.

One scheduler iteration (`step()`):

  1. reap  — cancelled/deadline-expired requests free their slot NOW
  2. admit — free slots refill from the queue head (FIFO)
  3. prefill — up to `prefill_budget` prompt tokens in ONE engine
     program (engine.launch_prefill): the budget's k whole chunks go to
     up to k prefilling slots, a row each, round-robin, or to a lone slot
     as one row of k chunks (`_prefill_plan`). The program is dispatched
     and not waited for. Its shapes are compiled when the scheduler is
     built (engine.warm_prefill); prefill_programs / prefill_rows /
     prefill_tokens count how often it engages
  4. launch — ONE fused jitted step for every decoding slot is
     dispatched (engine.launch_decode), the slots whose prompt ended in
     3 among them; nothing is waited for
  5. collect — the step launched by the iteration BEFORE is fetched
     (engine.decode_step, the one place the loop waits for the
     device) and its tokens delivered; eos / max_new_tokens finishes a
     request and releases its slot (a later iteration's admit refills
     it — no lockstep). Then the first tokens of 3's program are fetched
     (engine.collect_prefill) and delivered (TTFT)

ONE DECODE STEP IN FLIGHT. The device runs step n while the host
delivers step n-1, reaps, admits, plans, uploads and dispatches step n+1,
so an iteration lasts the longer of the device's work and the host's, not
their sum. What makes it possible is that everything a launch needs is
known without the tokens of the step in flight: positions, key cursors,
which rows end their prompt (they decode from the very next launch, their
first token put at their lane on the device), and which lanes have their
last token launched (max_new_tokens; they ride the next launch masked).
Only `eos` is learnt late: that lane runs one more step, whose token is
dropped at delivery (the stream is token for token what it was; the
write lies past the request's last position). A slot freed by step n's
tokens is refilled for launch n+2, and a request that arrives while step
n runs rides n+2 where launch n+1 is already made: up to a step later
than before. A request that is cancelled or expires with a token in
flight loses it. `drain`, `stop` and `run_until_idle` collect the step in
flight before they return. steps_ahead counts the launches made while
another was uncollected (all but the first of a loaded loop). An engine
that cannot launch without the last step's tokens (`runs_ahead` false:
the paged engine, whose speculative bursts accept by them) computes a
step in its launch and hands it out in its collect, which then follows in
the same iteration, 3's first tokens before 4: the loop is the same,
steps_ahead stays 0.

Where the engine's stack merges (SlotEngine.merges: attention layers
throughout, one chip, pools that the engine's shapes say are read in the
chunk loop, `SlotEngine.attn_impl`: Llama, Mistral, Mixtral), 3 only
STAGES the plan's rows (engine.stage_rows, still under
serve.prefill_chunk) and 4's decode step takes them along: ONE execution
an iteration reads every weight once, where the two programs of an
iteration that prefills each read them all. The step then runs with no
lane decoding too, and when it is collected the rows' first tokens are
delivered after the lanes' tokens; a request whose prompt ends in the
step decodes from the next launch on (on the two-program path it decodes
in the same iteration's).
merged_steps counts the decode steps that carried rows; each is one of
decode_steps and one of prefill_programs. A stack with recurrent layers,
rings or a tail layer (Jamba, Brumby, Phi-4-mini-flash), the paged
engine and a mesh keep the two programs.

Telemetry rides the module-level flight-recorder helpers (no-ops
outside a run context). The request lifecycle event schema is pinned in
tests/schema_validate.py::SERVING_EVENT_DATA_SCHEMAS:

  serve.request.queued / prefill / first_token / finished / cancelled

plus serve.batch_occupancy + serve.queue_depth gauges and the
serve.decode_step / serve.prefill_chunk timers.

Every boundary of an iteration goes through ONE phase ledger
(telemetry.PhaseLedger, made here and shared with the engine): a phase is
a span on the profiler's clock (recorded while a profiler session is
open, a flag check otherwise) and, always, its seconds and one call under
the same name on the host's clock: serve.iteration around step(), inside
it serve.reap, serve.admit, serve.prefill_chunk (one a prefill program:
rows, tokens, and request_ids, slots, row_tokens a row) and
serve.decode_step (the two timers; the step holds this iteration's launch
and the fetch of the one before, and says the lanes, prefill_rows and
prefill_tokens of the LAUNCH), serve.deliver; the engine's engine.*
phases inside admit and the two timers, engine.first_token.fetch after
serve.deliver; `wait` around the loop's sleep
when an iteration found nothing to do (docs/observability.md has the
table). stats()["phases"] sums them, stats()["slow_iterations"] keeps the
slowest of the last SLOW_WINDOW iterations phase by phase, stats()["gc"]
the collector's pauses while the loop's thread runs.
"""

import gc
import heapq
import itertools
import os
import threading
import time
from collections import deque

from .. import knobs, telemetry
from .. import tracing
from .engine import refuse_recurrent
from .paged import PageExhaustedError
from .tenancy import TenancyConfig, TenantQueues, TokenBudgets

_request_ids = itertools.count(1)

# iterations whose phases are kept for stats()["slow_iterations"]: about a
# minute of a loaded server (an iteration is 15-30 ms), one small tuple each
SLOW_WINDOW = 4096
SLOW_SHOWN = 3
# inside these phases the loop waits for the device; the loop's sleep
FETCH_PHASES = ("engine.decode.fetch", "engine.first_token.fetch")
ITERATION, WAIT = "serve.iteration", "wait"


def _grown(now, before):
    """{key: now - before} over the keys whose value moved."""
    return {k: v - before.get(k, 0.0) for k, v in now.items()
            if v != before.get(k)}


def _pctl(values, q):
    """Nearest-rank percentile of an unsorted sequence; 0.0 when empty."""
    if not values:
        return 0.0
    ordered = sorted(values)
    idx = min(len(ordered) - 1, int(q * (len(ordered) - 1) + 0.5))
    return round(float(ordered[idx]), 3)


class QueueFullError(Exception):
    """Backpressure: the request queue is at capacity."""


class DrainingError(Exception):
    """The scheduler is draining (SIGTERM) and admits no new requests."""


class CapacityError(Exception):
    """The request can NEVER be served by this engine (prompt +
    max_new_tokens exceeds max_seq_len or the whole page pool) — a
    permanent 413 at admission time, not backpressure. Queueing it
    would only fail later, mid-decode or at admit."""


class TenantThrottledError(Exception):
    """Per-tenant admission control rejected the request (token budget
    exhausted or queue share exceeded). Carries the TENANT-scoped
    Retry-After — a throttled low-priority tenant must not inherit the
    global capacity hint."""

    def __init__(self, message, tenant, reason, retry_after_s):
        super(TenantThrottledError, self).__init__(message)
        self.tenant = tenant
        self.reason = reason          # "budget" | "queue_share"
        self.retry_after_s = float(retry_after_s)


class Request(object):
    """One generation request: prompt tokens in, a stream of generated
    tokens out (thread-safe queue the HTTP layer consumes)."""

    def __init__(self, tokens, max_new_tokens, temperature=0.0, top_k=None,
                 top_p=None, eos_id=None, rng=0, deadline=None,
                 request_id=None, traceparent=None, prefill_only=False,
                 prefilled=None, tenant=None):
        self.id = str(request_id) if request_id is not None \
            else "req-%d" % next(_request_ids)
        # multi-tenancy: None == untagged (single-tenant traffic) — no
        # per-tenant bookkeeping, no serve.tenant.* telemetry
        self.tenant = str(tenant) if tenant else None
        # W3C trace context for this request (minted by the fleet router
        # or the HTTP server; None = untraced). Stamped into every
        # serve.request.* telemetry record.
        self.traceparent = traceparent
        self.tokens = [int(t) for t in tokens]
        if not self.tokens:
            raise ValueError("empty prompt")
        self.max_new_tokens = int(max_new_tokens)
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        self.temperature = float(temperature)
        self.top_k = top_k
        self.top_p = top_p
        self.eos_id = eos_id
        self.rng = rng
        self.deadline = deadline  # absolute time.time(), or None
        self.generated = []
        self.token_times = []
        self.state = "new"   # queued|prefill|decode|finished|cancelled
        self.reason = None   # eos|length|cancelled|deadline|shutdown
        self.slot = None
        self.out = None      # created on submit
        self.t_submit = None
        self.t_admit = None
        self.t_first = None
        self.t_done = None
        self.admit_iteration = None
        self.finish_iteration = None
        # disaggregation: a prefill-only request stops after its first
        # token and parks {"first", "kv"} in `handoff`; a `prefilled`
        # request carries that dict in and enters decode directly
        self.prefill_only = bool(prefill_only)
        self.prefilled = prefilled
        self.handoff = None
        self._prefix_handle = None   # pinned prefix-cache match
        # prompt tokens no program was launched for yet: at 0 the prompt
        # has ended as far as planning goes, though the first token may
        # still be in flight (state stays "prefill" until it is delivered)
        self._prompt_left = len(self.tokens)
        self._cancelled = threading.Event()

    def cancel(self):
        self._cancelled.set()

    @property
    def cancelled(self):
        return self._cancelled.is_set()

    def stream(self, timeout=None):
        """Yield generated token ids as they land; raises TimeoutError if
        the engine stalls past `timeout` between tokens. Terminates when
        the request finishes (self.reason says why)."""
        import queue as _q

        while True:
            try:
                item = self.out.get(timeout=timeout)
            except _q.Empty:
                raise TimeoutError(
                    "request %s: no token within %.1fs" % (self.id, timeout))
            if item is None:  # terminal sentinel; reason is already set
                return
            yield item

    def result(self, timeout=None):
        """Block until finished; returns the generated token list."""
        for _ in self.stream(timeout=timeout):
            pass
        return list(self.generated)


class Scheduler(object):
    def __init__(self, engine, max_queue=64, prefill_budget=None,
                 prefix_cache=None, tenancy=None):
        self.engine = engine
        self.max_queue = int(max_queue)
        # multi-tenancy: per-tenant DRR queues + budgets (tenancy.py).
        # An empty config (the default) makes every surface below
        # degrade to the exact single-FIFO behavior it replaced.
        self.tenancy = (TenancyConfig.from_env() if tenancy is None
                        else tenancy)
        self._budgets = TokenBudgets(self.tenancy)
        self._tenant_counts = {}     # tenant -> counts dict
        self._tenant_ttft = {}       # tenant -> rolling TTFT window
        # optional RadixPrefixCache: admit seeds the longest cached
        # prefix into the slot, prefill resumes at the boundary, and a
        # finished prefill inserts the slot's KV back for the next hit
        if prefix_cache is not None:
            refuse_recurrent(engine.cfg, "a prefix cache")
        self.prefix_cache = prefix_cache
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_hit_tokens = 0
        self.prefix_prompt_tokens = 0
        # per-iteration prefill token budget: enough to land one chunk
        # per free slot by default, so admission keeps pace with decode
        # without ever stalling active slots behind one long prompt
        self.prefill_budget = int(engine.prefill_chunk * 2
                                  if prefill_budget is None
                                  else prefill_budget)
        if self.prefill_budget < 1:
            raise ValueError("prefill_budget must be >= 1, got %d"
                             % self.prefill_budget)
        # every prefill program this budget can call is compiled now,
        # before a request is admitted (engine.prefill_shapes)
        engine.warm_prefill(self.prefill_budget)
        self._queue = TenantQueues(self.tenancy)
        self._slots = {}          # slot index -> Request
        self._cond = threading.Condition()
        self._draining = False
        self._stopped = False
        self._thread = None
        self.iteration = 0
        self._prefill_rr = 0      # round-robin cursor over prefill slots
        # paged-engine plumbing (duck-typed: the slot engine has none of
        # these surfaces and every branch degrades to the old behavior)
        self._paged = hasattr(engine, "kv_stats")
        self.kv_exhausted = 0      # admission stalls on page exhaustion
        self._exhausted_blocked = False
        # stats
        self.served = 0
        self.cancelled_count = 0
        self.decode_steps = 0
        # how often one prefill program engages: calls of engine.prefill
        # (one device program each on the slot engine), the rows they
        # carried and the prompt tokens in those rows
        self.prefill_programs = 0
        self.prefill_rows = 0
        self.prefill_tokens = 0
        # the decode steps that carried a prefill program's rows (an
        # engine whose stack merges, engine.stage_rows): each is one of
        # decode_steps AND one of prefill_programs, ONE execution
        self.merged_steps = 0
        self._rows_staged = None   # (requests, slots, tokens) of them
        # one decode step in flight: where the engine can launch a step
        # without the last one's tokens (`runs_ahead`), an iteration
        # launches its step and THEN collects and delivers the one before,
        # so that the device has its next program queued while the host
        # reaps, admits, plans, uploads and delivers. `_in_flight` holds
        # what each uncollected launch carried, oldest first: ({slot:
        # request} of its lanes, the rows staged into it or None);
        # `_prefilled` the rows of a prefill program whose first tokens
        # are not collected yet. steps_ahead counts the launches made
        # while another was uncollected: all but the first of a loaded
        # loop, none for an engine that cannot run ahead
        self._ahead = 1 if getattr(engine, "runs_ahead", False) else 0
        self._in_flight = deque()
        self._prefilled = None
        self.steps_ahead = 0
        # the requests bound to a slot; the engine's `key_schedules`
        # beside it says how many of them drew their sampling keys (none
        # under greedy traffic: engine.KeySchedules)
        self.admitted = 0
        # how many times a decode step goes through the stack's weights
        # (a looped model's `passes`; 1 for every other)
        self._passes = getattr(engine, "passes", 1)
        # over the decode steps run: the K and V positions the decoding
        # lanes' queries saw, over all reading layers, and those the
        # program fetched for them (engine.attention_positions)
        self.attention_positions_needed = 0
        self.attention_positions_fetched = 0
        self.delivered_tokens = 0
        self.peak_in_flight = 0
        self._occupancy_sum = 0.0
        # the phase ledger (module docstring), shared with the engine so
        # that one ledger holds the whole iteration. busy_prefill_s and
        # busy_decode_s (stats()["goodput"], /metrics) are its
        # serve.prefill_chunk and serve.decode_step seconds: the HOST's
        # time around the two engine calls, not device time. A decode step
        # waits for its tokens, so busy_decode_s includes whatever the
        # device still had queued before the step; a prefill program
        # returns once dispatched unless a row ends its prompt (which
        # fetches the first tokens), so busy_prefill_s is dispatch time
        # for every other program (PERF.md, PR 23). "Is the chip waiting
        # for the host" is stats()["phases"]: host_work_s against
        # device_wait_s, and no_work_s for an idle server
        self.phases = engine.phases = telemetry.PhaseLedger()
        self._gc = telemetry.PhaseLedger()   # pauses, by generation
        self._gc_t0 = self._gc_span = None
        self._recent = deque(maxlen=SLOW_WINDOW)
        self._t_started = time.perf_counter()
        self._t_loop = None
        # rolling latency windows for /v1/stats and /healthz percentiles:
        # bounded so a long-lived server reports RECENT tail latency, not
        # an all-time blend that a morning incident pollutes forever
        window = knobs.get_int("TPUFLOW_SERVE_LATENCY_WINDOW")
        self._ttft_window = deque(maxlen=max(1, window))
        self._itl_window = deque(maxlen=max(1, window * 4))

    @property
    def busy_prefill_s(self):
        return self.phases.seconds.get("serve.prefill_chunk", 0.0)

    @property
    def busy_decode_s(self):
        return self.phases.seconds.get("serve.decode_step", 0.0)

    # ---------- intake ----------

    def submit(self, request):
        """Enqueue a request; raises QueueFullError (backpressure),
        DrainingError (shutdown in progress), or CapacityError (the
        request can never fit this engine — reject NOW instead of
        failing after it reaches a slot)."""
        import queue as _q

        if request.prefill_only or request.prefilled is not None:
            refuse_recurrent(self.engine.cfg, "disaggregated prefill/decode "
                             "(the KV handoff of serving/disagg.py)")
        fits = getattr(self.engine, "fits", None)
        if fits is not None and not fits(len(request.tokens),
                                         request.max_new_tokens):
            raise CapacityError(
                "prompt (%d) + max_new_tokens (%d) can never fit this "
                "engine (max context %d tokens)"
                % (len(request.tokens), request.max_new_tokens,
                   self.max_context_tokens()))
        tenant = request.tenant
        with self._cond:
            if self._draining or self._stopped:
                raise DrainingError("scheduler is draining")
            if tenant is not None and self.tenancy.enabled():
                self._tenant_admission_locked(request, tenant)
            if len(self._queue) >= self.max_queue:
                # a higher-priority tenant may evict the newest queued
                # request of a lower tier instead of being turned away
                if not self._priority_shed_locked(request):
                    raise QueueFullError(
                        "queue full (%d requests)" % len(self._queue))
            request.out = _q.Queue()
            request.state = "queued"
            request.t_submit = time.time()
            self._queue.append(request)
            depth = len(self._queue)
            tdepth = (self._queue.tenant_depth(tenant)
                      if tenant is not None else 0)
            self._cond.notify_all()
        telemetry.event("serve.request.queued", data=self._tdata(request, {
            "request_id": request.id, "queue_depth": depth,
            "prompt_tokens": len(request.tokens),
            "max_new_tokens": request.max_new_tokens}))
        telemetry.gauge("serve.queue_depth", depth)
        if tenant is not None:
            telemetry.gauge("serve.tenant.queue_depth", tdepth,
                            data={"tenant": tenant})
        return request

    # ---------- multi-tenant admission ----------

    def _counts_for(self, tenant):
        counts = self._tenant_counts.get(tenant)
        if counts is None:
            counts = self._tenant_counts[tenant] = {
                "admitted": 0, "throttled": 0, "shed": 0,
                "prompt_tokens": 0, "generated_tokens": 0}
        return counts

    def _tenant_admission_locked(self, request, tenant):
        """Budget + queue-share checks; raises TenantThrottledError
        with the tenant's OWN Retry-After."""
        share = self.tenancy.share(tenant, self.max_queue)
        if self._queue.tenant_depth(tenant) >= share:
            # back off on the tenant's queue drain rate, not global
            # pressure: its share of slots drains its share of queue
            slots = max(1, self.tenancy.share(
                tenant, self.engine.max_slots))
            wait = min(60, max(1, -(-share // slots)))
            self._throttle(request, tenant, "queue_share", wait)
        cost = len(request.tokens) + request.max_new_tokens
        wait = self._budgets.charge(tenant, cost)
        if wait > 0:
            self._throttle(request, tenant, "budget", wait)

    def _throttle(self, request, tenant, reason, retry_after_s):
        self._counts_for(tenant)["throttled"] += 1
        telemetry.event("serve.tenant.throttled", data=self._tdata(
            request, {"request_id": request.id, "tenant": tenant,
                      "reason": reason,
                      "retry_after_s": round(float(retry_after_s), 3)}))
        raise TenantThrottledError(
            "tenant %s throttled (%s); retry in %.1fs"
            % (tenant, reason, retry_after_s),
            tenant=tenant, reason=reason, retry_after_s=retry_after_s)

    def _priority_shed_locked(self, request):
        """Queue full: a strictly higher-priority submission evicts the
        newest queued request of the worst lower tier. Returns True
        when a slot was freed."""
        if request.tenant is None or not self.tenancy.enabled():
            return False
        victim = self._queue.shed_lowest_priority(
            below_tier=self.tenancy.priority(request.tenant))
        if victim is None:
            return False
        vtenant = victim.tenant or self.tenancy.default_tenant
        self._counts_for(vtenant)["shed"] += 1
        telemetry.event("serve.tenant.shed", data=self._tdata(victim, {
            "request_id": victim.id, "tenant": vtenant,
            "reason": "priority"}))
        self._finish(victim, "shed")
        return True

    def cancel(self, request_id):
        """Flag a queued or in-flight request; the next iteration reaps
        it. Returns True if the id was found."""
        with self._cond:
            for req in list(self._queue) + list(self._slots.values()):
                if req.id == request_id:
                    req.cancel()
                    self._cond.notify_all()
                    return True
        return False

    # ---------- lifecycle helpers ----------

    @staticmethod
    def _tdata(req, data):
        """Stamp the request's trace context into an event payload so the
        trace assembler (cmd/trace.py) can join records across replicas.
        `span` is the dispatch-attempt span the router forwarded — two
        attempts of one request share `trace` but differ in `span`."""
        trace_id, span_id = tracing.traceparent_ids(
            getattr(req, "traceparent", None))
        if trace_id:
            data["trace"] = trace_id
            data["span"] = span_id
        return data

    def _finish(self, req, reason):
        if req.state in ("finished", "cancelled"):
            # terminal already: finishing twice would release a slot
            # that may hold the NEXT occupant, and put a second None
            # sentinel into the stream
            return
        if req.slot is not None:
            if self._paged:
                before = self.engine.pool.free_pages()
                self.engine.release(req.slot)
                freed = self.engine.pool.free_pages() - before
                telemetry.event("serve.kv.page_free", data=self._tdata(
                    req, {"request_id": req.id, "slot": req.slot,
                          "pages": int(freed),
                          "free_pages": self.engine.pool.free_pages()}))
            else:
                self.engine.release(req.slot)
            del self._slots[req.slot]
        if req._prefix_handle is not None:
            # every terminal path drops the pin — including cancel /
            # deadline / shutdown mid-prefill, so no eviction-blocking
            # refs leak from requests that never finished prefill
            self.prefix_cache.release(req._prefix_handle)
            req._prefix_handle = None
        req.reason = reason
        req.t_done = time.time()
        req.finish_iteration = self.iteration
        ok = reason in ("eos", "length", "prefilled")
        req.state = "finished" if ok else "cancelled"
        name = ("serve.request.finished" if ok
                else "serve.request.cancelled")
        data = {"request_id": req.id, "reason": reason,
                "new_tokens": len(req.generated)}
        if req.tenant is not None:
            data["tenant"] = req.tenant
        if req.slot is not None:
            data["slot"] = req.slot
        if req.t_first is not None and req.t_submit is not None:
            data["ttft_ms"] = round((req.t_first - req.t_submit) * 1000, 3)
        if req.t_submit is not None:
            data["total_ms"] = round((req.t_done - req.t_submit) * 1000, 3)
        telemetry.event(name, data=self._tdata(req, data))
        if ok:
            self.served += 1
        else:
            self.cancelled_count += 1
        if req.tenant is not None and req.generated:
            self._counts_for(req.tenant)["generated_tokens"] += len(
                req.generated)
        req.out.put(None)

    def _deliver(self, req, token):
        now = time.time()
        prev = req.token_times[-1] if req.token_times else None
        req.generated.append(token)
        req.token_times.append(now)
        if req.t_first is None:
            req.t_first = now
            ttft_ms = (now - req.t_submit) * 1000
            self._ttft_window.append(ttft_ms)
            if req.tenant is not None:
                window = self._tenant_ttft.get(req.tenant)
                if window is None:
                    window = self._tenant_ttft[req.tenant] = deque(
                        maxlen=self._ttft_window.maxlen)
                window.append(ttft_ms)
            data = {"request_id": req.id, "slot": req.slot,
                    "ttft_ms": round(ttft_ms, 3)}
            if req.tenant is not None:
                data["tenant"] = req.tenant
            telemetry.event("serve.request.first_token",
                            data=self._tdata(req, data))
        elif prev is not None:
            self._itl_window.append((now - prev) * 1000)
        req.out.put(token)
        self.delivered_tokens += 1
        if req.eos_id is not None and token == req.eos_id:
            self._finish(req, "eos")
        elif len(req.generated) >= req.max_new_tokens:
            self._finish(req, "length")

    def _reap(self, now):
        for slot, req in list(self._slots.items()):
            if req.cancelled:
                self._finish(req, "cancelled")
            elif req.deadline is not None and now > req.deadline:
                self._finish(req, "deadline")
        with self._cond:
            queued = list(self._queue)
        for req in queued:
            expired = (req.deadline is not None and now > req.deadline)
            if req.cancelled or expired:
                with self._cond:
                    try:
                        self._queue.remove(req)
                    except ValueError:
                        continue
                self._finish(req, "cancelled" if req.cancelled
                             else "deadline")

    def max_context_tokens(self):
        """The largest prompt+max_new this engine can ever hold."""
        mct = getattr(self.engine, "max_context_tokens", None)
        return int(mct() if mct is not None else self.engine.max_seq_len)

    def _kv_exhausted(self, req):
        """Admission blocked on page exhaustion: head-of-line waits
        (FIFO order is preserved — backpressure, not rejection). The
        event fires once per blocked EPISODE, not per spin."""
        if self._exhausted_blocked:
            return
        self._exhausted_blocked = True
        self.kv_exhausted += 1
        telemetry.event("serve.kv.exhausted", data=self._tdata(req, {
            "request_id": req.id,
            "needed_pages": self.engine._pages_needed(
                len(req.tokens), req.max_new_tokens),
            "free_pages": self.engine.pool.free_pages(),
            "queue_depth": len(self._queue)}))

    def _admit(self):
        free = self.engine.free_slots()
        admitted = 0
        can_admit = getattr(self.engine, "can_admit", None)
        for slot in free:
            req = None
            while req is None:
                with self._cond:
                    if not self._queue:
                        return admitted
                    head = self._queue[0]
                    blocked = (
                        can_admit is not None
                        and not head.cancelled
                        and not can_admit(len(head.tokens),
                                          head.max_new_tokens))
                    if blocked:
                        self._kv_exhausted(head)
                        return admitted
                    req = self._queue.popleft()
                # the reap->admit race: a request cancelled (or expired)
                # after _reap scanned the queue but before this pop must
                # finish HERE, without ever taking the slot — admitting
                # it would spend a prefill chunk on a corpse and free
                # the slot a second time one iteration later
                now = time.time()
                expired = (req.deadline is not None and now > req.deadline)
                if req.cancelled or expired:
                    self._finish(req, "cancelled" if req.cancelled
                                 else "deadline")
                    req = None
            try:
                if req.prefilled is not None:
                    # disaggregation decode side: KV arrived with the
                    # request; seed it and skip prefill entirely
                    self.engine.admit_prefilled(
                        slot, req.tokens, req.prefilled["first"],
                        req.prefilled["kv"], req.max_new_tokens,
                        temperature=req.temperature, top_k=req.top_k,
                        top_p=req.top_p, rng=req.rng)
                else:
                    # a prefill-only request ends with its first token:
                    # its lane never decodes
                    self.engine.admit(
                        slot, req.tokens,
                        1 if req.prefill_only else req.max_new_tokens,
                        temperature=req.temperature, top_k=req.top_k,
                        top_p=req.top_p, rng=req.rng)
            except PageExhaustedError:
                # backstop: can_admit raced a concurrent alloc (e.g. a
                # prefix-index insert). Requeue at the HEAD — this is
                # backpressure, FIFO order holds, next tick retries.
                with self._cond:
                    self._queue.appendleft(req)
                self._kv_exhausted(req)
                return admitted
            except ValueError as ex:
                # oversized request: reject it, keep serving
                req.reason = "rejected"
                req.state = "cancelled"
                req.error = str(ex)
                telemetry.event("serve.request.cancelled",
                                data=self._tdata(req, {
                                    "request_id": req.id,
                                    "reason": "rejected"}))
                self.cancelled_count += 1
                req.out.put(None)
                continue
            bind = getattr(self.engine, "bind_slot_context", None)
            if bind is not None:
                bind(slot, self._tdata(req, {"request_id": req.id}))
            req.slot = slot
            req.state = "prefill"
            req.t_admit = time.time()
            req.admit_iteration = self.iteration
            self._slots[slot] = req
            admitted += 1
            self.admitted += 1
            self.peak_in_flight = max(self.peak_in_flight,
                                      len(self._slots))
            if self._paged:
                # a successful admit ends any exhaustion episode
                self._exhausted_blocked = False
                telemetry.event("serve.kv.page_alloc", data=self._tdata(
                    req, {"request_id": req.id, "slot": slot,
                          "pages": int(self.engine._n_pages[slot]),
                          "free_pages": self.engine.pool.free_pages()}))
            telemetry.event("serve.request.prefill", data=self._tdata(req, {
                "request_id": req.id, "slot": slot,
                "queue_ms": round((req.t_admit - req.t_submit) * 1000, 3)}))
            if req.tenant is not None:
                counts = self._counts_for(req.tenant)
                counts["admitted"] += 1
                counts["prompt_tokens"] += len(req.tokens)
                telemetry.event("serve.tenant.admitted",
                                data=self._tdata(req, {
                                    "request_id": req.id,
                                    "tenant": req.tenant,
                                    "prompt_tokens": len(req.tokens),
                                    "queue_ms": round(
                                        (req.t_admit - req.t_submit)
                                        * 1000, 3)}))
                telemetry.gauge(
                    "serve.tenant.queue_depth",
                    self._queue.tenant_depth(req.tenant),
                    data={"tenant": req.tenant})
            if req.prefilled is not None:
                # already past prefill: emit the first token now so the
                # stream carries ALL tokens and eos/length still apply
                req.state = "decode"
                self._deliver(req, int(req.prefilled["first"]))
            elif self.prefix_cache is not None:
                self._seed_from_cache(req, slot)
        return admitted

    def _seed_from_cache(self, req, slot):
        # match prompt[:-1]: at least one token must prefill so the
        # final chunk's logits exist for first-token sampling
        self.prefix_prompt_tokens += len(req.tokens)
        handle = self.prefix_cache.match(req.tokens[:-1])
        if handle is None:
            self.prefix_misses += 1
            telemetry.event("serve.prefix.miss", data=self._tdata(req, {
                "request_id": req.id,
                "prompt_tokens": len(req.tokens)}))
            return
        if hasattr(handle, "pages"):
            # paged engine + paged index: ZERO-COPY attach — the slot's
            # block table repoints at the shared pages (one device copy
            # only for a partially-filled tail page, CoW)
            self.engine.seed_pages(slot, handle)
            telemetry.event("serve.kv.page_shared", data=self._tdata(
                req, {"request_id": req.id, "slot": slot,
                      "pages": len(handle.pages)
                      + (1 if handle.partial is not None else 0),
                      "tokens": handle.length}))
        else:
            self.engine.seed_prefix(slot, handle.kv())
        req._prefix_handle = handle
        req._prompt_left -= handle.length
        self.prefix_hits += 1
        self.prefix_hit_tokens += handle.length
        telemetry.event("serve.prefix.hit", data=self._tdata(req, {
            "request_id": req.id, "matched_tokens": handle.length,
            "prompt_tokens": len(req.tokens)}))

    def _prefill_plan(self):
        """Which prefilling slots get how many of this iteration's
        `prefill_budget` tokens: [(slot, most_tokens), ...] for ONE
        engine program. The budget holds k whole chunks (at least one);
        up to k slots get a row each, taken round-robin so that one long
        prompt cannot starve the others, and each row may take the
        k // rows chunks that keep rows x width inside the budget: a
        lone prefilling slot gets one row of the whole budget."""
        slots = [s for s, r in sorted(self._slots.items())
                 if r.state == "prefill" and r._prompt_left]
        if not slots:
            return []
        chunk = self.engine.prefill_chunk
        k = max(1, self.prefill_budget // chunk)
        rows = min(k, len(slots))
        at = self._prefill_rr % len(slots)
        self._prefill_rr = at + rows
        return [(slots[(at + j) % len(slots)], k // rows * chunk)
                for j in range(rows)]

    def _prefill(self):
        plan = self._prefill_plan()
        if not plan:
            return 0
        slots = [slot for slot, _ in plan]
        reqs = [self._slots[slot] for slot in slots]
        # the program's attribution comes from the ENGINE's slot binding
        # (bind_slot_context at admit): device work is stamped by the
        # layer that performed it
        ctxs = [self.engine.slot_context(slot)
                or self._tdata(req, {"request_id": req.id})
                for slot, req in zip(slots, reqs)]
        data = {"rows": len(plan), "slots": slots,
                "request_ids": [c["request_id"] for c in ctxs]}
        if any(c.get("span") for c in ctxs):
            data["spans"] = [c.get("span") or "" for c in ctxs]
        merged = getattr(self.engine, "merges", False)
        with self.phases("serve.prefill_chunk", record=True,
                         **data) as chunk:
            if merged:   # staged: this iteration's decode step takes them
                consumed = self.engine.stage_rows(plan)
                self._rows_staged = (reqs, slots, sum(consumed))
            else:        # dispatched: its first tokens are collected later
                consumed = self.engine.launch_prefill(plan)
                self._prefilled = (reqs, slots)
            chunk.set(tokens=sum(consumed), row_tokens=consumed)
        for req, n in zip(reqs, consumed):
            req._prompt_left -= n
        self.prefill_programs += 1
        self.prefill_rows += len(plan)
        self.prefill_tokens += sum(consumed)
        if not self._ahead:   # before the decode step that follows them
            self._collect_prefill()
        return len(plan)

    def _collect_prefill(self):
        """Fetch and hand out the first tokens of the prefill program
        launched this iteration, if there is one."""
        launched, self._prefilled = self._prefilled, None
        if launched is not None:
            self._first_tokens(*launched, self.engine.collect_prefill())

    def _first_tokens(self, reqs, slots, results):
        """Hand out the first token of every row that ended its prompt
        (not to a request that was cancelled or expired, and left its
        slot, with its row in flight)."""
        for req, slot, (_, first) in zip(reqs, slots, results):
            if first is not None and self._slots.get(slot) is req:
                self._prefill_done(req, slot, first)

    def _prefill_done(self, req, slot, first):
        """The final prefill chunk landed: populate the prefix cache,
        drop the request's pin, and either enter decode or (prefill-only
        mode) park the KV handoff and finish."""
        kv = None
        paged_insert = (self.prefix_cache is not None
                        and hasattr(self.prefix_cache, "insert_pages")
                        and hasattr(self.engine, "slot_prefix_pages"))
        if req.prefill_only or (self.prefix_cache is not None
                                and not paged_insert):
            kv = self.engine.extract_kv(slot, len(req.tokens))
        if paged_insert:
            # paged path: register the slot's OWN pages with the index
            # (it takes its own refs) — no KV bytes move
            full, tail = self.engine.slot_prefix_pages(
                slot, len(req.tokens))
            self.prefix_cache.insert_pages(req.tokens, full, tail)
            if req._prefix_handle is not None:
                self.prefix_cache.release(req._prefix_handle)
                req._prefix_handle = None
        elif self.prefix_cache is not None:
            self.prefix_cache.insert(req.tokens, kv)
            if req._prefix_handle is not None:
                self.prefix_cache.release(req._prefix_handle)
                req._prefix_handle = None
        if req.prefill_only:
            now = time.time()
            req.generated.append(int(first))
            req.token_times.append(now)
            req.t_first = now
            self._ttft_window.append((now - req.t_submit) * 1000)
            telemetry.event("serve.request.first_token",
                            data=self._tdata(req, {
                                "request_id": req.id, "slot": req.slot,
                                "ttft_ms": round(
                                    (now - req.t_submit) * 1000, 3)}))
            req.handoff = {"first": int(first), "kv": kv}
            req.out.put(int(first))
            self._finish(req, "prefilled")
            return
        req.state = "decode"
        self._deliver(req, first)

    def _decode(self):
        """This iteration's decode step: launch it, then collect and
        deliver the step in flight before it (the same step, where the
        engine cannot run ahead). Returns (the lanes that decode in the
        launch, whether a step was collected). The rows `_prefill` staged
        ride in the launch (with no lane decoding it runs for them alone);
        when it is collected their requests' first tokens are delivered
        after the lanes' tokens, and a request whose prompt ends in it
        decodes from the launch after its own, made before that."""
        staged, self._rows_staged = self._rows_staged, None
        if not (self._in_flight or staged or self.engine.decoding.any()):
            return 0, False
        with self.phases("serve.decode_step", record=True) as step:
            lanes = self._launch(step, staged)
            collected = None
            if len(self._in_flight) > (self._ahead if lanes is not None
                                       else 0):
                requests, rows = self._in_flight.popleft()
                collected = self.engine.decode_step()
        if collected is not None:
            self._deliver_step(requests, collected, rows)
        return len(lanes or ()), collected is not None

    def _launch(self, step, staged):
        """Launch one decode step, under the open `serve.decode_step`
        span `step`; returns the slots that decode in it, None where the
        engine had nothing to launch."""
        reqs, slots, row_tokens = staged or ((), (), 0)
        stats = {"prefill_rows": len(reqs), "prefill_tokens": row_tokens,
                 "passes": self._passes}
        positions = getattr(self.engine, "attention_positions", None)
        if positions is not None:   # from the cursors, before they move
            needed, fetched = positions()
            stats.update(positions_needed=needed, positions_fetched=fetched)
        lanes = self.engine.launch_decode()
        if lanes is None:
            return None
        step.set(active=len(lanes), **stats)
        self.attention_positions_needed += stats.get("positions_needed", 0)
        self.attention_positions_fetched += stats.get("positions_fetched", 0)
        self.steps_ahead += bool(self._in_flight)
        self._in_flight.append((
            {slot: self._slots[slot] for slot in lanes
             if slot in self._slots}, staged and (reqs, slots)))
        self.decode_steps += 1
        self.merged_steps += staged is not None
        self._occupancy_sum += self.engine.occupancy()
        telemetry.gauge("serve.batch_occupancy", self.engine.occupancy())
        if self._paged:
            ks = self.engine.kv_stats()
            telemetry.gauge("serve.kv.page_occupancy", ks["occupancy"])
            telemetry.gauge("serve.kv.cow_pages", ks["cow_pages"])
            ss = self.engine.spec_stats()
            if ss["enabled"]:
                telemetry.gauge("serve.spec.accept_rate",
                                ss["accept_rate"])
        return lanes

    def _deliver_step(self, requests, tokens, rows):
        """Deliver a collected step's tokens: `requests` is {slot:
        request} as its launch bound them, `rows` the (requests, slots)
        staged into it. The token of a lane whose request has left its
        slot since (cancelled, expired, or ended by an `eos` that the
        step before delivered) is dropped."""
        with self.phases("serve.deliver") as span:
            delivered = 0
            for slot, toks in tokens.items():
                req = requests.get(slot)
                if req is None or self._slots.get(slot) is not req:
                    continue
                # speculative decode emits up to spec_k+1 tokens per slot
                # per step; eos/length inside the burst stops delivery of
                # the remainder (the engine over-generated, the stream
                # must not)
                for token in (toks if isinstance(toks, list) else [toks]):
                    if req.state != "decode":
                        break
                    self._deliver(req, token)
                    delivered += 1
            span.set(tokens=delivered)
        if rows is not None:
            self._first_tokens(*rows, self.engine.row_results)

    def _flush(self):
        """Collect whatever is still in flight and deliver what has a
        request to go to: the device is quiet and the engine's launches
        are all collected when this returns."""
        while self._in_flight:
            requests, rows = self._in_flight.popleft()
            self._deliver_step(requests, self.engine.decode_step(), rows)
        self._collect_prefill()

    # ---------- the loop ----------

    def step(self):
        """One scheduler iteration; returns True if any work was done."""
        phases = self.phases
        before, collected = dict(phases.seconds), dict(self._gc.seconds)
        prefilled, delivered = self.prefill_tokens, self.delivered_tokens
        with phases(ITERATION, iteration=self.iteration) as span:
            with phases("serve.reap"):
                self._reap(time.time())
            with phases("serve.admit") as admit:
                admitted = self._admit()
                admit.set(admitted=admitted)
            rows = self._prefill()
            lanes, fetched = self._decode()
            self._collect_prefill()
            tokens = self.prefill_tokens - prefilled
            span.set(lanes=lanes, prefill_rows=rows, prefill_tokens=tokens,
                     admitted=admitted,
                     delivered=self.delivered_tokens - delivered)
        # (iteration, seconds by the phases that ran in it, lanes, prefill
        # rows and tokens, admitted, the collector's seconds by generation)
        self._recent.append((
            self.iteration, _grown(phases.seconds, before), lanes, rows,
            tokens, admitted, _grown(self._gc.seconds, collected)))
        self.iteration += 1
        return bool(admitted or rows or lanes or fetched)

    def pending(self):
        with self._cond:
            return len(self._queue) + len(self._slots)

    def run_until_idle(self, max_iterations=None):
        """Drive step() until queue and slots are empty (bench/tests —
        no thread)."""
        n = 0
        while self.pending():
            self.step()
            n += 1
            if max_iterations is not None and n >= max_iterations:
                raise RuntimeError(
                    "scheduler did not go idle in %d iterations"
                    % max_iterations)
        self._flush()   # an `eos` leaves one step in flight behind it
        return n

    def _on_gc(self, phase, info):
        """gc.callbacks entry while the loop's thread runs: a
        collection's pause, on whatever thread it ran, by generation;
        one of generation 2 is also the span runtime.gc. A collection
        overlaps the phases: it is counted beside them."""
        generation = info["generation"]
        if phase == "start":
            if generation == 2:
                self._gc_span = telemetry.annotate("runtime.gc",
                                                   generation=2)
                self._gc_span.__enter__()
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self._gc.add(generation, time.perf_counter() - self._gc_t0)
            self._gc_t0 = None
            if self._gc_span is not None:
                self._gc_span.__exit__(None, None, None)
                self._gc_span = None

    def _loop(self):
        self._t_loop = time.perf_counter()
        gc.callbacks.append(self._on_gc)
        try:
            self._serve()
        finally:
            gc.callbacks.remove(self._on_gc)

    def _serve(self):
        while True:
            with self._cond:
                if self._stopped:
                    break
                if self._draining and not self._queue and not self._slots:
                    break
            if not self.step():
                with self._cond:
                    if (self._stopped
                            or (self._draining and not self._queue
                                and not self._slots)):
                        break
                    with self.phases(WAIT):
                        self._cond.wait(timeout=0.02)
        # loop exit: anything still queued/in-flight dies with "shutdown"
        with self._cond:
            leftovers = list(self._queue) + list(self._slots.values())
            self._queue.clear()
        for req in leftovers:
            self._finish(req, "shutdown")
        self._flush()   # its tokens have no request left to go to

    def start(self):
        if self._thread is not None:
            raise RuntimeError("scheduler already started")
        self._thread = threading.Thread(target=self._loop,
                                        name="tpuflow-serve-scheduler",
                                        daemon=True)
        self._thread.start()
        return self

    def drain(self, timeout=None):
        """Graceful shutdown (SIGTERM): stop admitting NEW submissions,
        finish everything already accepted, then stop the loop. Returns
        True once the loop has exited."""
        with self._cond:
            self._draining = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            return not self._thread.is_alive()
        # no thread: drive synchronously
        self.run_until_idle()
        return True

    def stop(self):
        """Hard stop: in-flight and queued requests finish as
        'shutdown'."""
        with self._cond:
            self._stopped = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10)

    def stats(self):
        with self._cond:
            depth = len(self._queue)
            in_flight = len(self._slots)
            tenant_depths = self._queue.depths()
        return {
            "tenancy": self.tenant_stats(tenant_depths),
            "queue_depth": depth,
            "in_flight": in_flight,
            "slots": self.engine.max_slots,
            "occupancy": self.engine.occupancy(),
            "mean_batch_occupancy": (
                round(self._occupancy_sum / self.decode_steps, 4)
                if self.decode_steps else 0.0),
            "served": self.served,
            "cancelled": self.cancelled_count,
            "decode_steps": self.decode_steps,
            "steps_ahead": self.steps_ahead,
            "weight_passes": self.decode_steps * self._passes,
            "prefill_programs": self.prefill_programs,
            "prefill_rows": self.prefill_rows,
            "prefill_tokens": self.prefill_tokens,
            "merged_steps": self.merged_steps,
            "admitted": self.admitted,
            "key_schedules": getattr(self.engine, "key_schedules", 0),
            "expert_pairs": dict(getattr(self.engine, "expert_pairs", None)
                                 or {"routed": 0, "held": 0}),
            "iterations": self.iteration,
            "draining": self._draining,
            # rolling-window tail latency (the SLO monitor's poll surface)
            "p50_ttft_ms": _pctl(list(self._ttft_window), 0.50),
            "p99_ttft_ms": _pctl(list(self._ttft_window), 0.99),
            "p50_itl_ms": _pctl(list(self._itl_window), 0.50),
            "p99_itl_ms": _pctl(list(self._itl_window), 0.99),
            "peak_in_flight": self.peak_in_flight,
            "max_context_tokens": self.max_context_tokens(),
            "prefix_cache": self.prefix_stats(),
            "kv_pages": self.kv_pages_stats(),
            "state_pool": self.state_pool_stats(),
            "cache_pools": self.cache_pool_stats(),
            "state_updates": self.state_updates(),
            "attention_positions_needed": self.attention_positions_needed,
            "attention_positions_fetched": self.attention_positions_fetched,
            "speculative": (self.engine.spec_stats() if self._paged
                            else {"enabled": False}),
            "goodput": self.goodput_stats(),
            "phases": self.phase_stats(),
            "slow_iterations": self.slow_iterations(),
            "gc": self.gc_stats(),
        }

    def gc_stats(self):
        """By generation, the seconds Python's collector held the process
        and its collections while the loop's thread ran (`_on_gc`)."""
        return {str(generation): {
            "seconds": round(seconds, 6),
            "collections": self._gc.calls.get(generation, 0)}
            for generation, seconds in sorted(dict(self._gc.seconds).items())}

    def phase_stats(self):
        """The loop's life on the host's clock, phase by phase (the
        ledger's seconds and calls), and cut three ways: device_wait_s,
        inside the two fetch phases, where the loop waits for the chip;
        host_work_s, the rest of serve.iteration, where the chip has
        nothing queued by this loop unless an earlier program still
        runs; no_work_s, the loop's sleep with nothing to do. The three
        add up to serve.iteration plus wait, which is loop_s (the
        thread's life so far) less the loop's own few lines."""
        seconds, calls = dict(self.phases.seconds), self.phases.calls
        fetch = sum(seconds.get(name, 0.0) for name in FETCH_PHASES)
        return {
            "iterations": self.iteration,
            "phase": {name: {"seconds": round(s, 6),
                             "calls": calls.get(name, 0)}
                      for name, s in sorted(seconds.items())},
            "device_wait_s": round(fetch, 6),
            "host_work_s": round(seconds.get(ITERATION, 0.0) - fetch, 6),
            "no_work_s": round(seconds.get(WAIT, 0.0), 6),
            "loop_s": (None if self._t_loop is None else
                       round(time.perf_counter() - self._t_loop, 6)),
        }

    def slow_iterations(self):
        """The slowest of the last SLOW_WINDOW iterations, each with what
        it held and its milliseconds phase by phase: where a stall that
        comes once a minute lay, and whether a collection ran in it."""
        ms = lambda d: {str(k): round(v * 1e3, 3) for k, v in d.items()}
        slowest = heapq.nlargest(SLOW_SHOWN, list(self._recent),
                                 key=lambda it: it[1].get(ITERATION, 0.0))
        return [{"iteration": n, "ms": round(took.get(ITERATION, 0.0) * 1e3,
                                             3),
                 "phase_ms": ms(took), "lanes": lanes, "prefill_rows": rows,
                 "prefill_tokens": tokens, "admitted": admitted,
                 "gc_ms": ms(collected)}
                for n, took, lanes, rows, tokens, admitted, collected
                in slowest]

    def tenant_stats(self, tenant_depths=None):
        """Per-tenant admission/latency rollup for /v1/stats and the
        `tpuflow metrics`/`watch` tenant sections."""
        if tenant_depths is None:
            with self._cond:
                tenant_depths = self._queue.depths()
        tenants = {}
        # the default bucket holds UNTAGGED requests — it only shows up
        # here if a tagged tenant actually uses that name
        names = (set(self._tenant_counts)
                 | set(self.tenancy.known_tenants())
                 | (set(tenant_depths)
                    - {self.tenancy.default_tenant}))
        for t in sorted(names):
            counts = self._tenant_counts.get(t) or {
                "admitted": 0, "throttled": 0, "shed": 0,
                "prompt_tokens": 0, "generated_tokens": 0}
            window = list(self._tenant_ttft.get(t, ()))
            tenants[t] = {
                "queued": tenant_depths.get(t, 0),
                "admitted": counts["admitted"],
                "throttled": counts["throttled"],
                "shed": counts["shed"],
                "prompt_tokens": counts["prompt_tokens"],
                "generated_tokens": counts["generated_tokens"],
                "priority": self.tenancy.priority_name(t),
                "weight": self.tenancy.weight(t),
                "p50_ttft_ms": _pctl(window, 0.50),
                "p99_ttft_ms": _pctl(window, 0.99),
            }
        return {"enabled": self.tenancy.enabled(), "tenants": tenants}

    def goodput_stats(self):
        """The scheduler's life split in the goodput categories
        (metaflow_tpu/goodput.py): the host's seconds around the prefill
        and decode engine calls (busy_prefill_s, busy_decode_s: dispatch
        and wait, not device time) and the remainder as idle. Whether
        the chip waits for the host is phase_stats()'s to say."""
        elapsed = max(0.0, time.perf_counter() - self._t_started)
        busy = self.busy_prefill_s + self.busy_decode_s
        return {
            "serve_prefill_s": round(self.busy_prefill_s, 3),
            "serve_decode_s": round(self.busy_decode_s, 3),
            "serve_idle_s": round(max(0.0, elapsed - busy), 3),
            "elapsed_s": round(elapsed, 3),
        }

    def state_pool_stats(self):
        """The engine's recurrent-state pools (bytes, bytes a slot);
        zeros for an engine that keeps none."""
        stats = getattr(self.engine, "state_pool_stats", None)
        return stats() if stats is not None else {
            "bytes": 0, "bytes_per_slot": 0}

    def state_updates(self):
        """How the engine's decode step updates each recurrent pool
        (`SlotEngine.state_updates`: "kernel" or "loop"); empty for an
        engine that keeps none or does not say."""
        updates = getattr(self.engine, "state_updates", None)
        return updates() if updates is not None else {}

    def cache_pool_stats(self):
        """The engine's pools by what they hold (`SlotEngine.pool_stats`:
        `global`, `ring`, `state`; bytes, bytes a slot); zeros for an
        engine that does not say."""
        stats = getattr(self.engine, "pool_stats", None)
        return stats() if stats is not None else {
            what: {"bytes": 0, "bytes_per_slot": 0}
            for what in ("global", "ring", "state")}

    def kv_pages_stats(self):
        """Page-pool health for /v1/stats and /healthz; {"enabled":
        False} on the slot engine so the schema stays total."""
        if not self._paged:
            return {"enabled": False}
        out = self.engine.kv_stats()
        out["exhausted"] = self.kv_exhausted
        return out

    def prefix_stats(self):
        """Prefix-cache effectiveness for /v1/stats and /healthz.
        `prefill_tokens_skipped_frac` is the FLOPs-skip proxy: prefill
        cost is linear in tokens at fixed model size, so the fraction of
        prompt tokens served from cache IS the fraction of prefill FLOPs
        never spent (the ROADMAP >=90% gate measures this)."""
        out = {
            "enabled": self.prefix_cache is not None,
            "hits": self.prefix_hits,
            "misses": self.prefix_misses,
            "hit_rate": round(
                self.prefix_hits
                / max(1, self.prefix_hits + self.prefix_misses), 4),
            "hit_tokens": self.prefix_hit_tokens,
            "prompt_tokens": self.prefix_prompt_tokens,
            "prefill_tokens_skipped_frac": round(
                self.prefix_hit_tokens
                / max(1, self.prefix_prompt_tokens), 4),
        }
        if self.prefix_cache is not None:
            out.update(self.prefix_cache.stats())
            # cache-aware routing summary: the compact digest set the
            # fleet router scores dispatch against (cache_router.py).
            # Rides the stats/healthz channel — no new wire protocol.
            block = self.route_block()
            out["route_block"] = block
            out["digests"] = self.prefix_cache.route_digests(
                block,
                limit=knobs.get_int("TPUFLOW_CACHE_ROUTE_DIGESTS"))
        return out

    def route_block(self):
        """The digest block size this replica publishes: a paged index
        digests at page granularity (its keys ARE page-chain digests),
        the radix cache at the configured routing block."""
        if self.prefix_cache is None:
            return 0
        return int(getattr(self.prefix_cache, "page_tokens", 0)
                   or knobs.get_int("TPUFLOW_CACHE_ROUTE_BLOCK"))
