"""One decode step in flight (PR 45): the serving loop launches step n+1
before it collects step n. Over a stack whose rows ride in the decode step,
one whose rows take a prefill program of their own and one that carries
recurrent state: the served tokens are `generate()`'s, greedy and sampled;
a row that ends its prompt decodes in the very next launch;
`admit_prefilled`, `eos`, cancel and a deadline with a token in flight;
`run_until_idle`, `drain` and `stop`. A file of its own beside
tests/test_serving.py (whose helpers it shares) so that the two run on
two workers."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from metaflow_tpu import telemetry
from metaflow_tpu.exception import TpuFlowException
from metaflow_tpu.inference import generate
from metaflow_tpu.models import jamba
from metaflow_tpu.serving import Request, Scheduler, SlotEngine
from test_serving import CHUNK, _prompts, _ref_tokens, setup  # noqa: F401

AHEAD = ("merging", "two-programs", "recurrent")


@pytest.fixture(scope="module", params=AHEAD)
def ahead_engine(request, setup):
    """(cfg, params, engine) of a tiny stack of each kind the loop runs a
    step ahead of: one whose rows ride in the decode step (Llama, the
    chunk loop), one whose rows take a prefill program of their own
    (Llama, dense attention) and one that carries recurrent state (Jamba:
    a prefill program, a state pool reset at admission). Three slots,
    chunks of 16; every test leaves its slots released."""
    kind = request.param
    if kind == "recurrent":
        cfg = jamba.JambaConfig.tiny()
        params = jamba.init_params(jax.random.PRNGKey(0), cfg)
    else:
        cfg, params = setup
    # the read is the shapes' to say: past 2 * DECODE_CHUNK positions the
    # chunk loop, and a stack of attention layers then merges
    eng = SlotEngine(params, cfg, max_slots=3,
                     max_seq_len=640 if kind == "merging" else 128,
                     prefill_chunk=CHUNK)
    assert eng.runs_ahead and eng.merges == (kind == "merging") \
        and eng.recurrent == (kind == "recurrent")
    return cfg, params, eng


class _Spans(telemetry.PhaseLedger):
    """A ledger that also keeps every span's name and stats, in order."""

    def __init__(self):
        super().__init__()
        self.seen = []

    def __call__(self, name, record=False, **stats):
        self.seen.append((name, stats))
        return super().__call__(name, record=record, **stats)


def _watched(eng, **kw):
    """A scheduler over `eng` whose every span is kept."""
    sched = Scheduler(eng, **kw)
    sched.phases = eng.phases = _Spans()
    return sched


def _step_until(sched, done, most=2_000):
    """Step the loop until `done()`; a loop that never gets there fails
    the test and does not hang it."""
    for _ in range(most):
        if done():
            return
        sched.step()
    raise AssertionError("not there in %d iterations" % most)


def _quiet(sched):
    """Nothing launched is left uncollected, by the loop or the engine."""
    eng = sched.engine
    return not sched._in_flight and sched._prefilled is None \
        and not eng._decodes and not eng._prefills


def _an_eos(params, cfg, tokens, new):
    """(an eos id, the index at which generate() first emits it) for
    `tokens`: a token past the second that no earlier one equals."""
    ref = np.asarray(generate(params, jnp.asarray(tokens)[None], cfg, new,
                              rng=jax.random.PRNGKey(0)))[0, len(tokens):]
    at = next(i for i in range(2, new - 2) if ref[i] not in ref[:i])
    return int(ref[at]), at


class TestOneStepInFlight:
    """The loop launches step n+1 before it collects step n: the served
    tokens are what they were, and what the host learns only from the
    tokens (`eos`, nothing else) costs one step whose token is dropped."""

    @pytest.mark.parametrize("temperature", [0.0, 0.8],
                             ids=["greedy", "sampled"])
    def test_tokens_are_generates_and_every_launch_but_the_first_is_ahead(
            self, ahead_engine, temperature):
        cfg, params, eng = ahead_engine
        sched = _watched(eng)
        # more requests than slots, so slots are refilled; prompts of one
        # token, on a chunk's edge and of several programs; one request
        # of a single token, which never decodes
        lengths = (40, 16, 1, 21, 70, 33)
        new = (12, 9, 14, 1, 10, 11)
        reqs = [sched.submit(Request(p, max_new_tokens=n, rng=i,
                                     temperature=temperature, top_k=20))
                for i, (p, n) in enumerate(zip(
                    _prompts(cfg, lengths, seed=9), new))]
        for _ in range(2_000):
            if not sched.pending():
                break
            launched = sched.decode_steps
            sched.step()
            if sched.decode_steps > launched:   # exactly one in flight
                assert len(sched._in_flight) == len(eng._decodes) == 1
        assert _quiet(sched)
        for req in reqs:
            assert req.reason == "length"
            assert req.generated == _ref_tokens(params, cfg, req)
        stats = sched.stats()
        assert stats["steps_ahead"] == stats["decode_steps"] - 1 > 10
        # the spans say the same: every dispatch but the first is made
        # with a launch uncollected, and every fetch but the last waits
        # for the launch BEFORE the newest
        order = [(n, st) for n, st in sched.phases.seen
                 if n in ("engine.decode.dispatch", "engine.decode.fetch")]
        assert [st["ahead"] for n, st in order
                if n.endswith("dispatch")] == [0] + [1] * stats["steps_ahead"]
        uncollected, at_fetch = [], []
        for name, st in order:
            if name.endswith("dispatch"):
                uncollected.append(st["launch"])
            else:
                at_fetch.append(len(uncollected))
                assert st["awaits"] == uncollected.pop(0)
        assert at_fetch == [2] * stats["steps_ahead"] + [1]

    def test_a_row_that_ends_in_the_step_in_flight_decodes_in_the_next(
            self, ahead_engine):
        """A prompt that ends in a launch decodes from the very next one,
        before its first token is fetched: from the first token on, every
        iteration delivers one more (no step's wait between the first
        token and the second)."""
        cfg, params, eng = ahead_engine
        sched = Scheduler(eng)
        req = sched.submit(Request(_prompts(cfg, (20,), seed=10)[0],
                                   max_new_tokens=5))
        sched.step()   # the one row, 20 of 32 tokens, ends the prompt
        slot = req.slot
        assert eng.decoding[slot] and len(sched._in_flight) == 1
        if eng.merges:   # its first token is in flight with the step
            assert req.state == "prefill" and not req.generated
            assert eng.pos[slot] == 20
        else:            # fetched behind the decode step's launch, which
            # carries the lane already
            assert req.state == "decode" and len(req.generated) == 1
            assert eng.pos[slot] == 21
        assert sched._prefill_plan() == []   # and no row is planned again
        seen = []
        while sched.pending() and len(seen) < 100:
            sched.step()
            seen.append(len(req.generated))
        first = len(req.generated) - len(seen)
        assert seen == list(range(first + 1, 6)) and first in (0, 1)
        assert req.generated == _ref_tokens(params, cfg, req)
        assert sched.stats()["decode_steps"] == 4 + eng.merges

    @pytest.mark.parametrize("temperature", [0.0, 0.8],
                             ids=["greedy", "sampled"])
    def test_admit_prefilled_joins_with_a_step_in_flight(
            self, ahead_engine, temperature):
        """A request prefilled elsewhere is admitted while a step runs:
        its first token is patched into its lane on the device, behind
        that step, and it decodes from the next launch on."""
        cfg, params, eng = ahead_engine
        prompts = _prompts(cfg, (23, 40), seed=11)
        knobs = dict(max_new_tokens=7, temperature=temperature, top_k=20,
                     rng=5)
        if eng.recurrent:   # a KV range is not a prefix of its state
            with pytest.raises(TpuFlowException, match="recurrent"):
                Scheduler(eng).submit(Request(prompts[1], prefill_only=True,
                                              **knobs))
            return
        pre = Scheduler(eng)
        done = pre.submit(Request(prompts[1], prefill_only=True, **knobs))
        pre.run_until_idle(10_000)
        assert done.reason == "prefilled" and _quiet(pre)
        assert sum(it[2] for it in pre._recent) == 0   # never a lane
        sched = Scheduler(eng)
        other = sched.submit(Request(prompts[0], max_new_tokens=12, rng=1))
        _step_until(sched, lambda: len(other.generated) >= 2)
        assert len(sched._in_flight) == 1
        handed = sched.submit(Request(prompts[1], prefilled=done.handoff,
                                      **knobs))
        sched.step()
        assert handed.state == "decode" and len(handed.generated) == 1
        assert len(sched._in_flight[-1][0]) == 2   # both lanes launched
        sched.run_until_idle(10_000)
        for req in (other, handed):
            assert req.generated == _ref_tokens(params, cfg, req)

    def test_eos_costs_one_step_whose_token_is_dropped(self, ahead_engine):
        cfg, params, eng = ahead_engine
        prompts = _prompts(cfg, (19, 30, 12), seed=12)
        eos, at = _an_eos(params, cfg, prompts[0], 12)
        sched = Scheduler(eng)
        ends = sched.submit(Request(prompts[0], max_new_tokens=12,
                                    eos_id=eos))
        other = sched.submit(Request(prompts[1], max_new_tokens=14, rng=1))
        _step_until(sched, lambda: ends.reason is not None)
        # the stream ends with the eos, token for token generate()'s, and
        # its lane is in the launch already made; the slot is free
        assert ends.reason == "eos" and len(ends.generated) == at + 1
        assert ends.generated == _ref_tokens(params, cfg, ends)
        assert ends.slot in sched._in_flight[-1][0]
        assert ends.slot in eng.free_slots()
        # the next occupant of that slot, admitted behind the step in
        # flight, starts from nothing of it (a recurrent state is reset)
        after = sched.submit(Request(prompts[2], max_new_tokens=6, rng=2))
        delivered = len(ends.generated)
        sched.run_until_idle(10_000)
        assert after.slot == ends.slot and len(ends.generated) == delivered
        for req in (other, after):
            assert req.reason == "length"
            assert req.generated == _ref_tokens(params, cfg, req)
        # one lane-step more was launched than tokens were delivered
        lane_steps = sum(it[2] for it in sched._recent)
        decoded = sum(len(r.generated) - 1 for r in (ends, other, after))
        assert lane_steps == decoded + 1 and _quiet(sched)

    @pytest.mark.parametrize("how", ["cancel", "deadline"])
    def test_a_request_that_leaves_drops_its_token_in_flight(
            self, ahead_engine, how):
        cfg, params, eng = ahead_engine
        prompts = _prompts(cfg, (25, 18, 9), seed=13)
        sched = Scheduler(eng)
        victim = sched.submit(Request(prompts[0], max_new_tokens=40,
                                      deadline=time.time() + 3600))
        other = sched.submit(Request(prompts[1], max_new_tokens=12, rng=1))
        _step_until(sched, lambda: len(victim.generated) >= 3)
        had, slot = len(victim.generated), victim.slot
        assert slot in sched._in_flight[-1][0]   # a token of it in flight
        if how == "cancel":
            sched.cancel(victim.id)
        else:
            victim.deadline = time.time() - 0.001
        late = sched.submit(Request(prompts[2], max_new_tokens=5, rng=2))
        sched.step()   # reaped, its slot refilled, that token dropped
        assert victim.reason == ("cancelled" if how == "cancel"
                                 else "deadline")
        assert len(victim.generated) == had and late.slot == slot
        sched.run_until_idle(10_000)
        assert len(victim.generated) == had
        assert list(victim.stream(timeout=1)) == victim.generated
        for req in (other, late):
            assert req.generated == _ref_tokens(params, cfg, req)
        assert _quiet(sched)
        assert eng.free_slots() == list(range(eng.max_slots))

    @pytest.mark.parametrize("how", ["run_until_idle", "drain",
                                     "drain-thread", "stop"])
    def test_the_step_in_flight_is_collected_before_the_loop_returns(
            self, ahead_engine, how):
        cfg, params, eng = ahead_engine
        tokens = _prompts(cfg, (19,), seed=12)[0]
        eos, at = _an_eos(params, cfg, tokens, 12)
        sched = Scheduler(eng)
        if how in ("drain-thread", "stop"):
            sched.start()
        # an eos leaves a launch behind the last delivery; `stop` meets
        # a request in mid-flight
        req = sched.submit(Request(tokens, eos_id=eos, max_new_tokens=(
            100 if how == "stop" else 12)))
        if how == "run_until_idle":
            sched.run_until_idle(10_000)
        elif how == "drain":
            assert sched.drain() is True
        elif how == "drain-thread":
            assert req.result(timeout=120) and sched.drain(timeout=60)
        else:
            req.eos_id = None
            for _ in req.stream(timeout=120):
                break   # decoding, a step in flight
            sched.stop()
            assert req.reason == "shutdown" and 0 < len(req.generated) < 100
        if how != "stop":
            assert req.reason == "eos" and len(req.generated) == at + 1
        assert _quiet(sched)
        assert eng.free_slots() == list(range(eng.max_slots))
        # the engine serves the next loop as if nothing had been ahead
        again = Scheduler(eng)
        req = again.submit(Request(tokens, max_new_tokens=4))
        again.run_until_idle(10_000)
        assert req.generated == _ref_tokens(params, cfg, req)
