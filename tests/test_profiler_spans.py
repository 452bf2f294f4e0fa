"""The program's own marks in a profiler capture: host spans on the
profiler's clock (telemetry.annotate and every timer) and scope names in
the compiled programs (jax.named_scope, a Pallas kernel's name).

One profiler session is opened for the whole module (the `captured`
fixture): a Scheduler on its thread serves a handful of requests, a
timer runs with a recorder current, a tiny train step runs; the same
work runs once more with no session open, for the comparison.
"""

import dataclasses
import gc
import glob
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from metaflow_tpu import telemetry
from metaflow_tpu.models import llama, mixtral
from metaflow_tpu.serving import Request, Scheduler, SlotEngine
from metaflow_tpu.serving.paged import PagedEngine
from metaflow_tpu.spmd import MeshSpec, create_mesh
from metaflow_tpu.training import make_trainer, shard_batch

PROMPTS = [list(range(1, 12)), list(range(5, 35)), list(range(3, 22)),
           list(range(7, 16))]
DECODE_SCOPES = ("decode_layers", "attn_qkv", "kv_cache_update",
                 "decode_attention", "attn_out", "ffn")
MOE_SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")


SAMPLED = 2   # of PROMPTS: the one request that draws sampling keys


def _serve(sched):
    reqs = [sched.submit(Request(p, max_new_tokens=5, rng=n,
                                 temperature=0.8 * (n == SAMPLED)))
            for n, p in enumerate(PROMPTS)]
    return reqs, [r.result(timeout=120) for r in reqs]


def _events(profile):
    """[(line key, name, start, end, stats)] of the host plane."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for n, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(("serve.", "engine.", "unit.",
                                      "train.", "data.", "runtime.",
                                      "wait")):
                    out.append((n, e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    from metaflow_tpu.datastore import FlowDataStore, LocalStorage

    tmp = tmp_path_factory.mktemp("spans")
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    engine = SlotEngine(params, cfg, max_slots=2, max_seq_len=64,
                        prefill_chunk=8)
    sched = Scheduler(engine).start()
    mesh = create_mesh(MeshSpec.dp(), devices=jax.devices()[:1])
    state, step, _ = make_trainer(jax.random.PRNGKey(0), cfg, mesh, llama,
                                  telemetry=True)
    batch = shard_batch({"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (2, 33), 0, cfg.vocab_size)}, mesh)
    out = {}
    try:
        _serve(sched)   # compiles every program
        state, _ = step(state, batch)
        saved = jax.tree.map(jnp.copy, state)
        # ---- no session open ----
        out["plain_requests"], out["plain_tokens"] = _serve(sched)
        _, m = step(saved, batch)
        out["plain_loss"] = float(m["loss"])
        # ---- one session for the whole module ----
        fds = FlowDataStore("Spans", LocalStorage, ds_root=str(tmp / "ds"))
        telemetry.init_recorder(fds, "1", "_serve", "spans-test")
        jax.profiler.start_trace(str(tmp / "trace"))
        try:
            out["requests"], out["tokens"] = _serve(sched)
            gc.collect()   # one of generation 2, while the loop runs
            out["gc"] = sched.stats()["gc"]
            with telemetry.timer("unit.timed", step_num=3,
                                 data={"k": "v"}) as timed:
                pass
            out["timed_s"] = timed.seconds
            _, m = step(state, batch)
            out["loss"] = float(m["loss"])
        finally:
            jax.profiler.stop_trace()
            telemetry.close_recorder()
        out["records"] = telemetry.read_run_records(fds, "1")
    finally:
        sched.stop()
    path = sorted(glob.glob(os.path.join(
        str(tmp / "trace"), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out["events"] = _events(jax.profiler.ProfileData.from_file(path))
    return out


def _inside(outer, inner):
    return outer[2] <= inner[2] and inner[3] <= outer[3]


class TestSchedulerSpans:
    def test_iteration_spans_enclose_the_boundaries_on_one_line(
            self, captured):
        events = captured["events"]
        lines = {e[0] for e in events if e[1] == "serve.iteration"}
        assert len(lines) == 1, "the scheduler's spans lie on one line"
        line = [e for e in events if e[0] in lines]
        names = {e[1] for e in line}
        assert {"serve.iteration", "serve.reap", "serve.admit",
                "serve.prefill_chunk", "engine.prefill.dispatch",
                "engine.first_token.fetch", "serve.decode_step",
                "engine.decode.upload", "engine.decode.dispatch",
                "engine.decode.fetch", "serve.deliver"} <= names
        iterations = [e for e in line if e[1] == "serve.iteration"]
        assert all("iteration" in e[4] for e in iterations)

        def parents(name, of):
            """Every `name` span lies inside exactly one `of` span."""
            for e in (x for x in line if x[1] == name):
                assert sum(_inside(p, e) for p in line
                           if p[1] == of) == 1, (name, of)

        for name in ("serve.reap", "serve.admit", "serve.prefill_chunk",
                     "serve.decode_step", "serve.deliver"):
            parents(name, "serve.iteration")
        parents("engine.prefill.dispatch", "serve.prefill_chunk")
        # the first tokens are fetched after the decode step is launched
        # and the step before it delivered: under the iteration alone
        parents("engine.first_token.fetch", "serve.iteration")
        chunks = [e for e in line if e[1] == "serve.prefill_chunk"]
        assert not any(_inside(c, f) for c in chunks for f in line
                       if f[1] == "engine.first_token.fetch")
        for name in ("engine.decode.upload", "engine.decode.dispatch",
                     "engine.decode.fetch"):
            parents(name, "serve.decode_step")
        # one decode step in flight: a step's span holds its launch and
        # THEN the fetch of the launch before, and every launch but the
        # first of a busy stretch is made with one uncollected
        steps = [e for e in line if e[1] == "serve.decode_step"]
        for step in steps:
            inner = sorted((e for e in line if e is not step
                            and _inside(step, e)
                            and e[1] in ("engine.decode.dispatch",
                                         "engine.decode.fetch")),
                           key=lambda e: e[2])
            assert [e[1] for e in inner] in (
                ["engine.decode.dispatch"], ["engine.decode.fetch"],
                ["engine.decode.dispatch", "engine.decode.fetch"])
            if len(inner) == 2:
                assert inner[0][4]["ahead"] == 1
                assert inner[1][4]["awaits"] < inner[0][4]["launch"]
        dispatched = [e[4]["ahead"] for e in line
                      if e[1] == "engine.decode.dispatch"]
        assert dispatched[0] == 0 and sum(dispatched) >= len(dispatched) - 2
        # deliver is a collected step's fan-out, after the engine call
        deliver = [e for e in line if e[1] == "serve.deliver"]
        assert len(deliver) == len(dispatched) <= len(steps)
        assert not any(_inside(s, d) for s in steps for d in deliver)
        assert all(d[4]["tokens"] >= 1 for d in deliver)
        assert sum(e[4]["admitted"] for e in line
                   if e[1] == "serve.admit") == len(PROMPTS)
        # a schedule is drawn when a sampled token is first asked for,
        # in the program that ends the prompt: once a SAMPLED request,
        # never inside serve.admit, and not at all for a greedy one
        parents("engine.admit.keys", "serve.prefill_chunk")
        keys = [e for e in line if e[1] == "engine.admit.keys"]
        assert len(keys) == 1
        assert not any(_inside(a, keys[0]) for a in line
                       if a[1] == "serve.admit")
        # nothing of the loop's or the engine's lies between iterations:
        # a reader takes every such span for the host at work
        for e in line:
            if e[1].startswith(("serve.", "engine.")) \
                    and e[1] != "serve.iteration":
                assert any(_inside(i, e) for i in iterations), e[1]
        assert any(e[1] == "wait" for e in line)

    def test_the_capture_holds_the_spans_the_docs_table_names(
            self, captured):
        """docs/observability.md's span table against the capture:
        every serving span it names is there (the state's reset needs a
        model that has one)."""
        docs = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "docs", "observability.md")
        with open(docs) as f:
            table = f.read().split("| Span | Around | Stats |")[1]
        rows = table.split("\n\n")[0].splitlines()[2:]
        named = {n for row in rows
                 for n in re.findall(r"`([\w.]+)`", row.split("|")[1])
                 if n.startswith(("serve.", "engine.", "runtime."))
                 or n == "wait"}
        assert {"engine.admit.keys", "runtime.gc", "wait",
                "serve.iteration"} <= named
        assert named - {"engine.state.reset"} \
            <= {e[1] for e in captured["events"]}
        collections = [e for e in captured["events"]
                       if e[1] == "runtime.gc"]
        assert collections and all(e[4]["generation"] == 2
                                   for e in collections)
        assert captured["gc"]["2"]["collections"] >= 1
        assert captured["gc"]["2"]["seconds"] > 0

    def test_an_iteration_and_a_step_say_what_they_held(self, captured):
        events = captured["events"]
        iterations = [e for e in events if e[1] == "serve.iteration"]
        assert all({"iteration", "lanes", "prefill_rows", "prefill_tokens",
                    "admitted", "delivered"} <= set(e[4])
                   for e in iterations)
        assert sum(e[4]["admitted"] for e in iterations) == len(PROMPTS)
        assert sum(e[4]["prefill_tokens"] for e in iterations) \
            == sum(len(p) for p in PROMPTS)
        # five tokens a request: the first from its prefill
        assert sum(e[4]["delivered"] for e in iterations) \
            == 5 * len(PROMPTS)
        # a step's span says what its LAUNCH held; the span of an
        # iteration that only collects the step in flight says nothing
        steps = [e for e in events if e[1] == "serve.decode_step"
                 and "active" in e[4]]
        assert sum(e[4]["active"] for e in steps) == 4 * len(PROMPTS)
        assert sorted(e[4]["lanes"] for e in iterations
                      if e[4]["lanes"]) \
            == sorted(e[4]["active"] for e in steps)
        assert all(0 < e[4]["positions_needed"] <= e[4]["positions_fetched"]
                   for e in steps)

    def test_every_fetch_awaits_a_launch_already_made(self, captured):
        """The engine numbers its launches: a dispatch span carries
        `launch`, strictly increasing; a fetch span `awaits` the number
        of a dispatch span that opened before it."""
        line = sorted((e for e in captured["events"]
                       if e[1].startswith("engine.")), key=lambda e: e[2])
        made, awaited = [], 0
        for e in line:
            if e[1] in ("engine.prefill.dispatch",
                        "engine.decode.dispatch"):
                assert not made or e[4]["launch"] > made[-1]
                made.append(e[4]["launch"])
            elif e[1] in ("engine.first_token.fetch",
                          "engine.decode.fetch"):
                assert e[4]["awaits"] in made
                awaited += 1
            else:
                assert "launch" not in e[4] and "awaits" not in e[4]
        assert made == list(range(made[0], made[0] + len(made)))
        assert awaited >= 4 * len(PROMPTS) // 2

    def test_every_chunk_names_its_request_and_slot(self, captured):
        """One span a prefill program: it names the request, slot and
        tokens of every row it carried (a value a row, joined by "|")."""
        chunks = [e for e in captured["events"]
                  if e[1] == "serve.prefill_chunk"]
        assert chunks
        assert all({"request_ids", "slots", "row_tokens", "rows",
                    "tokens"} <= set(e[4]) for e in chunks)
        rows = []   # (request id, slot, tokens) of every row
        for e in chunks:
            mine = list(zip(e[4]["request_ids"].split("|"),
                            map(int, str(e[4]["slots"]).split("|")),
                            map(int, str(e[4]["row_tokens"]).split("|"))))
            assert len(mine) == e[4]["rows"]
            assert sum(r[2] for r in mine) == e[4]["tokens"]
            rows += mine
        for req in captured["requests"]:
            mine = [r for r in rows if r[0] == req.id]
            assert sum(r[2] for r in mine) == len(req.tokens)
            assert {r[1] for r in mine} == {req.slot}


@pytest.fixture(scope="module")
def captured_merged(tmp_path_factory):
    """The events of a session over a scheduler whose engine merges (a
    stack of attention layers, the chunk loop): the prefill rows ride in
    the decode step."""
    tmp = tmp_path_factory.mktemp("merged")
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    # past 2 * DECODE_CHUNK positions: the chunk loop, by the shapes
    engine = SlotEngine(params, cfg, max_slots=2, max_seq_len=640,
                        prefill_chunk=8)
    assert engine.merges
    sched = Scheduler(engine).start()
    out = {}
    try:
        _, out["plain_tokens"] = _serve(sched)   # compiles every program
        before = sched.stats()
        jax.profiler.start_trace(str(tmp / "trace"))
        try:
            out["requests"], out["tokens"] = _serve(sched)
        finally:
            jax.profiler.stop_trace()
        after = sched.stats()
    finally:
        sched.stop()
    out["stats"] = {k: after[k] - before[k] for k in (
        "decode_steps", "merged_steps", "prefill_programs", "prefill_rows",
        "prefill_tokens")}
    path = sorted(glob.glob(os.path.join(
        str(tmp / "trace"), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out["events"] = _events(jax.profiler.ProfileData.from_file(path))
    return out


class TestMergedIteration:
    """An iteration whose prefill rows ride in its decode step: the
    spans, what ties a launch to its fetch, and the stats that say so."""

    def test_one_launch_an_iteration_and_the_step_says_its_rows(
            self, captured_merged):
        events, stats = captured_merged["events"], captured_merged["stats"]
        names = {e[1] for e in events}
        # no program of the rows' own: nothing dispatches or awaits one
        assert not names & {"engine.prefill.dispatch",
                            "engine.first_token.fetch"}
        iterations = [e for e in events if e[1] == "serve.iteration"]
        chunks = [e for e in events if e[1] == "serve.prefill_chunk"]
        # a step's span says what its LAUNCH held; one that only collects
        # the step in flight (the last of a busy stretch) says nothing
        spans = [e for e in events if e[1] == "serve.decode_step"]
        steps = [e for e in spans if e[4]]
        assert chunks and len(spans) > len(steps) and all(
            {"prefill_rows", "prefill_tokens", "active", "positions_needed",
             "positions_fetched", "passes"} <= set(e[4]) for e in steps)
        merged = [e for e in steps if e[4]["prefill_rows"]]
        assert len(merged) == len(chunks) == stats["merged_steps"] \
            == stats["prefill_programs"]
        assert len(steps) == stats["decode_steps"]
        assert sum(e[4]["prefill_rows"] for e in steps) \
            == sum(e[4]["rows"] for e in chunks) == stats["prefill_rows"]
        assert sum(e[4]["prefill_tokens"] for e in steps) \
            == sum(e[4]["tokens"] for e in chunks) \
            == sum(len(p) for p in PROMPTS) == stats["prefill_tokens"]
        # a step with rows and no lane is a step all the same
        assert any(e[4]["active"] == 0 for e in merged)
        # the one sampled request's keys are drawn in the merged step
        # whose row ends its prompt, before that step's program is called
        keys = [e for e in events if e[1] == "engine.admit.keys"]
        assert len(keys) == 1 and sum(
            _inside(step, keys[0]) for step in merged) == 1
        for it in iterations:
            inside = [e for e in events if e is not it and _inside(it, e)]
            chunk = [e for e in inside if e[1] == "serve.prefill_chunk"]
            step = [e for e in inside if e[1] == "serve.decode_step"]
            assert len(chunk) <= 1 and len(step) <= 1
            launches = [e for e in inside
                        if e[1] == "engine.decode.dispatch"]
            assert len(launches) == len([e for e in step if e[4]])
            if chunk:   # the rows are staged, then ONE program carries them
                assert chunk[0][3] <= step[0][2]
                assert step[0][4]["prefill_rows"] == chunk[0][4]["rows"] \
                    == it[4]["prefill_rows"]
                assert step[0][4]["prefill_tokens"] \
                    == chunk[0][4]["tokens"] == it[4]["prefill_tokens"]
                assert _inside(step[0], launches[0])
            elif step and step[0][4]:
                assert step[0][4]["prefill_rows"] == 0

    def test_the_fetch_awaits_the_oldest_launch(self, captured_merged):
        """One step in flight: every launch is fetched once, in order,
        and where `ahead` says so the next launch was dispatched before
        that fetch."""
        line = sorted((e for e in captured_merged["events"]
                       if e[1] in ("engine.decode.dispatch",
                                   "engine.decode.fetch")),
                      key=lambda e: e[2])
        assert line and len(line) % 2 == 0
        made = [e[4]["launch"] for e in line
                if e[1] == "engine.decode.dispatch"]
        assert made == list(range(made[0], made[0] + len(made)))
        assert [e[4]["awaits"] for e in line
                if e[1] == "engine.decode.fetch"] == made
        uncollected, ahead = 0, 0
        for e in line:
            if e[1] == "engine.decode.dispatch":
                assert e[4]["ahead"] == min(uncollected, 1)
                ahead += e[4]["ahead"]
                uncollected += 1
            else:
                uncollected -= 1
            assert 0 <= uncollected <= 2
        assert ahead >= len(made) - 3   # all but a busy stretch's first

    def test_same_tokens_with_and_without_a_session(self, captured_merged):
        assert captured_merged["plain_tokens"] == captured_merged["tokens"]
        assert all(len(t) == 5 for t in captured_merged["tokens"])

    def test_the_merged_program_is_the_decode_program(self):
        """Its executions are `jit__decode_greedy` (the readers find the
        decode program by that name), and it holds every scope the
        decode-only program and the prefill program hold."""
        engine = _engine(llama, "chunked")
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        B = engine.max_slots
        text = engine._decode_greedy_fn.lower(
            engine.params, jax.eval_shape(lambda: engine._cache), i32(B),
            i32(B), jax.ShapeDtypeStruct((B,), jnp.bool_),
            {"tokens": i32(2, 8), "slots": i32(2), "start": i32(2),
             "n_real": i32(2),
             "ends": jax.ShapeDtypeStruct((2,), jnp.bool_)}
        ).as_text(debug_info=True)
        assert "module @jit__decode_greedy" in text
        for scope in DECODE_SCOPES:
            assert _has(text, scope), scope


class TestNoSession:
    def test_same_run_without_a_session_and_same_tokens(self, captured):
        """Generated tokens are bit-identical with and without a session
        open; the run with none passed in the fixture."""
        assert captured["plain_tokens"] == captured["tokens"]
        assert all(len(t) == 5 for t in captured["tokens"])

    def test_train_loss_identical_with_and_without_a_session(
            self, captured):
        assert captured["plain_loss"] == captured["loss"]
        steps = [e for e in captured["events"] if e[1] == "train.step"]
        assert steps and "step_num" in steps[-1][4]

    def test_annotate_never_imports_jax(self):
        code = (
            "import sys\n"
            "from metaflow_tpu import telemetry, tracing\n"
            "with telemetry.annotate('a', n=1) as span:\n"
            "    span.set_metadata(x=2)\n"
            "with telemetry.timer('b', step_num=1, data={'k': 1}) as t:\n"
            "    t.set(tokens=3)\n"
            "with tracing.span('c', {'step': 1}):\n"
            "    pass\n"
            "phases = telemetry.PhaseLedger()\n"
            "with phases('d', record=True, n=1) as p:\n"
            "    p.set(x=2)\n"
            "assert phases.calls == {'d': 1} and p.seconds >= 0\n"
            "assert t.seconds >= 0\n"
            "assert 'jax' not in sys.modules, 'a span imported JAX'\n")
        env = dict(os.environ, PYTHONPATH=os.pathsep.join(
            [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
            + [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
               if p]))
        done = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=120)
        assert done.returncode == 0, done.stderr


class TestTimerIsBoth:
    def test_record_as_before_and_the_span_as_well(self, captured):
        recs = [r for r in captured["records"] if r["name"] == "unit.timed"]
        assert len(recs) == 1
        rec = recs[0]
        assert rec["type"] == "timer" and rec["ok"] is True
        assert rec["step_num"] == 3 and rec["data"] == {"k": "v"}
        assert rec["ms"] == pytest.approx(captured["timed_s"] * 1e3,
                                          abs=1e-3)
        spans = [e for e in captured["events"] if e[1] == "unit.timed"]
        assert len(spans) == 1
        assert spans[0][4]["step_num"] == 3 and spans[0][4]["k"] == "v"

    def test_the_scheduler_timers_still_write_their_records(self, captured):
        names = {r["name"] for r in captured["records"]}
        assert {"serve.prefill_chunk", "serve.decode_step"} <= names
        chunk = [r for r in captured["records"]
                 if r["name"] == "serve.prefill_chunk"][0]
        assert {"request_ids", "slots", "row_tokens", "rows",
                "tokens"} <= set(chunk["data"])
        assert len(chunk["data"]["request_ids"]) == chunk["data"]["rows"]
        # the finer spans are spans only: no record, no schema change
        assert not names & {"serve.iteration", "serve.reap", "serve.admit",
                            "serve.deliver", "engine.decode.fetch",
                            "engine.prefill.dispatch"}


def _has(text, scope):
    """A name on some operation's name stack in the lowered text:
    `"jit(step)/jvp(layers)/while/..."`, `"attn_qkv/dot_general"`."""
    return re.search(r'["/(]%s["/)]' % re.escape(scope), text) is not None


def _engine(model, attn_impl, paged=False):
    """An engine of abstract weights that reads its pools as `attn_impl`
    says: the slot engine's by its depth (`pool_read`: the chunk loop
    past 2 * DECODE_CHUNK positions), the paged engine's by its
    argument."""
    cfg = model.LlamaConfig.tiny() if model is llama \
        else model.MixtralConfig.tiny()
    params = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), cfg))
    if paged:
        return PagedEngine(params, cfg, max_slots=2, max_seq_len=64,
                           prefill_chunk=8, page_tokens=8,
                           attn_impl=attn_impl)
    engine = SlotEngine(params, cfg, max_slots=2, prefill_chunk=8,
                        max_seq_len=640 if attn_impl == "chunked" else 64)
    assert engine.attn_impl == attn_impl
    return engine


def _decode_text(engine):
    B = engine.max_slots
    i32 = jax.ShapeDtypeStruct((B,), jnp.int32)
    args = [engine.params,
            jax.eval_shape(lambda: engine.pool.kv) if hasattr(engine, "pool")
            else jax.eval_shape(lambda: engine._cache),
            i32, i32, jax.ShapeDtypeStruct((B,), jnp.bool_)]
    if hasattr(engine, "pool"):
        args.append(jax.eval_shape(lambda: jnp.asarray(engine.block_tables)))
    return engine._decode_greedy_fn.lower(*args).as_text(debug_info=True)


class TestScopeNames:
    @pytest.mark.parametrize("model,attn_impl,paged", [
        (llama, "dense", False), (llama, "chunked", False),
        (mixtral, "dense", False), (mixtral, "chunked", False),
        (llama, "dense", True), (llama, "chunked", True)])
    def test_decode_program_holds_every_scope(self, model, attn_impl, paged):
        text = _decode_text(_engine(model, attn_impl, paged))
        assert "module @jit__decode_greedy" in text
        scopes = DECODE_SCOPES + (MOE_SCOPES if model is mixtral else ())
        for scope in scopes:
            assert _has(text, scope), scope

    def test_prefill_program_and_pinned_program_names(self):
        engine = _engine(llama, "dense")
        cache = jax.eval_shape(lambda: engine._cache)
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        text = engine._prefill_fn.lower(
            engine.params, cache, jax.ShapeDtypeStruct((1, 8), jnp.int32),
            i32, i32).as_text(debug_info=True)
        assert "module @jit__prefill" in text
        for scope in DECODE_SCOPES:
            assert _has(text, scope), scope
        # the readers find a program's executions by these names
        for fn, name in ((engine._decode_sampled_fn, "_decode_sampled"),
                         (engine._decode_greedy_fn, "_decode_greedy"),
                         (engine._prefill_fn, "_prefill"),
                         (engine._first_fn, "_first_token")):
            assert fn.__name__ == name

    @pytest.mark.parametrize("model,scopes", [
        (llama, ("layers", "attention", "flash_attention", "flash_fwd",
                 "flash_bwd_dq", "flash_bwd_dkv", "ffn", "loss",
                 "optimizer_update")),
        (mixtral, ("layers", "attention", "ffn", "loss", "optimizer_update")
         + MOE_SCOPES)])
    def test_train_step_holds_every_scope(self, model, scopes):
        cfg = model.LlamaConfig.tiny() if model is llama \
            else model.MixtralConfig.tiny()
        if model is llama:
            # the kernel, interpreted: 'auto' is XLA attention on the CPU
            cfg = dataclasses.replace(cfg, attention_impl="flash_interpret",
                                      max_seq_len=128)
        mesh = create_mesh(MeshSpec.dp(),
                           devices=jax.devices()[:1])
        state, step, _ = make_trainer(jax.random.PRNGKey(0), cfg, mesh,
                                      model)
        seq = 128 if model is llama else 32
        tokens = jax.ShapeDtypeStruct((2, seq + 1), jnp.int32)
        text = step.lower(state, {"tokens": tokens}).as_text(
            debug_info=True)
        assert "module @jit_step" in text
        for scope in scopes:
            assert _has(text, scope), scope
