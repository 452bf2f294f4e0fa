"""benchmark/span_readings.py on traces whose answers are known: one
made by hand, small enough to add up on paper, and two cut from this
PR's chip runs (`python -m benchmark.span_readings <xplane> --record`),
read back against numbers computed from the cut by other means."""

import json
import os

import pytest

from benchmark import configs
from benchmark import span_readings as sr

HERE = os.path.dirname(os.path.abspath(__file__))


def cut(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


# ---- a trace made by hand (times in ns) ----

D = "jit(_decode_greedy)/decode_layers/while/body/closed_call/"


def by_hand():
    scopes = {"1": {
        "%while.1": "jit(_decode_greedy)/decode_layers/while",
        "%att": D + "decode_attention/decode_attention/while/body/mul",
        "%upd": D + "kv_cache_update/vmap()/scatter",
        "%exp": D + "ffn/moe_experts/nce,nef->ncf",
        "%head": "jit(_decode_greedy)/btd,dv->btv"}}
    step = [("%while.1 = while(...)", 0, 90), ("%att = fusion(...)", 0, 30),
            ("%upd = fusion(...)", 30, 60), ("%exp = fusion(...)", 60, 80),
            ("%head = fusion(...)", 90, 100)]
    ops = [("%x = copy(...)", 0, 10), ("%x = copy(...)", 240, 250)]
    for at in (10, 130):
        ops += [(n, at + s, at + e) for n, s, e in step]
    device = {"XLA Modules": [("jit_x(9)", 0, 10),
                              ("jit__decode_greedy(1)", 10, 110),
                              ("jit__decode_greedy(1)", 130, 230),
                              ("jit_x(9)", 240, 250)],
              "XLA Ops": ops}
    spans = [("serve.iteration", 5, 125, {"iteration": 0}),
             ("serve.decode_step", 8, 112, {}),
             ("engine.decode.fetch", 12, 111, {}),
             ("serve.deliver", 113, 120, {"tokens": 2}),
             ("$frame.py:1 something", 113, 119, {}),   # the tracer's own
             ("serve.iteration", 126, 245, {"iteration": 1}),
             ("serve.admit", 126, 129, {"admitted": 1}),
             ("serve.decode_step", 129, 232, {}),
             ("engine.decode.dispatch", 129, 131, {}),
             ("engine.decode.fetch", 131, 231, {}),
             ("serve.deliver", 233, 240, {"tokens": 2})]
    host = {"python3#0": [("bench.send", 0, 250)],   # another thread
            "python3#1": spans}
    return sr.Trace([("/device:TPU:0", device), ("/host:CPU", host)], scopes)


def test_by_hand_executions_scopes_and_own_times():
    t = by_hand()
    assert t.whole(sr.DECODE_PROGRAMS) == [1, 2]
    assert sr.execution_ms(t, sr.DECODE_PROGRAMS) == pytest.approx(100e-6)
    assert sr.execution_ms(t, sr.PREFILL_PROGRAMS) is None
    assert sr.scope_ms(t, sr.DECODE_PROGRAMS, ("decode_attention",)) \
        == pytest.approx(30e-6)
    # the cache's write, 30, and the while's own time, 90 - 80
    assert sr.scope_ms(t, sr.DECODE_PROGRAMS, ("kv_cache_update",),
                       rest_of="decode_layers", inner=sr.DECODE_INNER) \
        == pytest.approx(40e-6)
    assert sr.scope_ms(t, sr.DECODE_PROGRAMS, ("moe_experts",)) \
        == pytest.approx(20e-6)
    # no operation under it: nothing to read, not zero
    assert sr.scope_ms(t, sr.DECODE_PROGRAMS, ("moe_dispatch",)) is None
    assert sr.scope_ms(t, sr.TRAIN_PROGRAMS, ("flash_attention",)) is None
    assert sr.kernel_calls(t, sr.TRAIN_PROGRAMS, "flash_fwd") is None
    shares = sr.scope_shares(t)["jit__decode_greedy"]
    assert shares == pytest.approx({
        "decode_attention": 0.3, "kv_cache_update": 0.3, "moe_experts": 0.2,
        "decode_layers": 0.1, "other": 0.1})


def test_by_hand_idle_by_the_schedulers_span():
    t = by_hand()
    assert t.idle == [(110, 130), (230, 240)]
    assert [s[0] for s in t.spans].count("serve.iteration") == 2
    assert not any(s[0].startswith(("$", "bench.")) for s in t.spans)
    by_span, iterations = sr.idle_by_span(t)
    assert len(iterations) == 2
    assert by_span == pytest.approx({
        "engine.decode.fetch": 2, "serve.decode_step": 2,
        "serve.iteration": 7, "serve.deliver": 14, "no span": 1,
        "serve.admit": 3, "engine.decode.dispatch": 1})
    assert sum(by_span.values()) == 30
    # all but the fetches' 2 and the 1 outside any span, over 2 iterations
    assert sr.host_gap_ms_per_iter(t) == pytest.approx(27e-6 / 2)


def test_a_name_on_a_path_is_a_whole_component():
    path = ("jit(step)/transpose(jvp(layers))/while/body/closed_call/"
            "checkpoint/attention/flash_attention/flash_fwd/pallas_call:")
    for scope in ("layers", "attention", "flash_attention", "flash_fwd"):
        assert sr.under(path, scope)
    for scope in ("decode_layers", "decode_attention", "flash", "loss"):
        assert not sr.under(path, scope)
    assert sr.program_of("jit__prefill(123)") == ("jit__prefill", "123")


# ---- cuts of this PR's chip runs ----

def test_train_cut_flash_attention_and_kernel_calls():
    recorded = cut("train_scoped_trace_planes.json")
    t = sr.recorded(recorded)
    assert len(t.executions) == 3 and t.whole(sr.TRAIN_PROGRAMS) == [1]
    lo, hi = t.executions[1][2], t.executions[1][3]
    # by other means: the kernels' events of the middle step, which
    # hold nothing nested, summed straight from the cut
    kernels = [(recorded["names"][i], d) for i, s, d in recorded["ops"]
               if lo <= s and s + d <= hi
               and recorded["names"][i].startswith(("%flash_fwd",
                                                    "%flash_bwd"))]
    assert len(kernels) == 28   # 7 layers: forward twice, dq, dkv
    assert sum(n.startswith("%flash_fwd") for n, _ in kernels) == 14
    assert sr.kernel_calls(t, sr.TRAIN_PROGRAMS, "flash_fwd") == 14
    under_scope = sr.scope_ms(t, sr.TRAIN_PROGRAMS, ("flash_attention",))
    in_kernels = sum(d for _, d in kernels) * 1e-6
    assert in_kernels == pytest.approx(361.852366)
    # the scope also holds the backward's row sums and casts
    assert under_scope == pytest.approx(368.992515)
    assert in_kernels < under_scope < 1.03 * in_kernels
    assert sr.execution_ms(t, sr.TRAIN_PROGRAMS) == pytest.approx(1052.701895)
    shares = sr.scope_shares(t)["jit_step"]
    assert sum(shares.values()) == pytest.approx(1.0)
    assert shares["other"] < 0.02
    assert sr.host_gap_ms_per_iter(t) is None   # no scheduler in a trainer


def test_serve_cut_programs_scopes_and_host_gaps():
    """Three decode steps of the expert cell with the chunks between
    them; the first and the last execution of the cut are left out."""
    recorded = cut("serve_trace_planes.json")
    t = sr.recorded(recorded)
    assert len(t.whole(sr.DECODE_PROGRAMS)) == 3
    assert len(t.whole(sr.PREFILL_PROGRAMS)) == 4
    assert sr.execution_ms(t, sr.DECODE_PROGRAMS) == pytest.approx(63.475766)
    assert sr.execution_ms(t, sr.PREFILL_PROGRAMS) == pytest.approx(
        16.745852)
    # by other means: the operations of the decode executions that hold
    # nothing nested, summed by a substring of their scope path
    decode = [m for m in recorded["modules"]
              if m[0].startswith("jit__decode_greedy")]
    scopes = recorded["scopes"][sr.program_of(decode[0][0])[1]]
    ops = sorted(((s, s + d, recorded["names"][i])
                  for i, s, d in recorded["ops"]),
                 key=lambda o: (o[0], -o[1]))   # an outer one first
    sums = {"/decode_attention/": 0, "/moe_experts/": 0}
    for _, lo, hi in decode:
        inside = [o for o in ops if lo <= o[0] and o[1] <= hi]
        for k, (start, end, name) in enumerate(inside):
            if k + 1 < len(inside) and inside[k + 1][0] < end:
                continue   # holds the next operation: not a leaf
            for part in sums:
                if part in (scopes.get(name) or ""):
                    sums[part] += end - start
    attention = sr.scope_ms(t, sr.DECODE_PROGRAMS, ("decode_attention",))
    experts = sr.scope_ms(t, sr.DECODE_PROGRAMS, ("moe_experts",))
    assert attention == pytest.approx(sums["/decode_attention/"] * 1e-6 / 3)
    assert experts == pytest.approx(sums["/moe_experts/"] * 1e-6 / 3)
    assert attention == pytest.approx(33.772735)
    assert experts == pytest.approx(14.927314)
    assert sr.scope_ms(t, sr.DECODE_PROGRAMS, ("kv_cache_update",),
                       rest_of="decode_layers", inner=sr.DECODE_INNER) \
        == pytest.approx(9.786342)
    assert sr.scope_ms(t, sr.DECODE_PROGRAMS,
                       ("moe_dispatch", "moe_router", "moe_combine")) \
        == pytest.approx(0.2504603)
    shares = sr.scope_shares(t)
    assert shares["jit__decode_greedy"]["other"] < 0.1
    assert shares["jit__prefill"]["moe_experts"] > 0.85
    # the host: two iterations lie whole inside the cut
    by_span, iterations = sr.idle_by_span(t)
    assert len(iterations) == 2
    idle = sum(end - start for start, end in t.idle)
    assert sum(by_span.values()) == pytest.approx(idle)
    assert by_span["engine.decode.fetch"] == pytest.approx(5238122)
    working = idle - by_span["no span"] - by_span["engine.decode.fetch"] \
        - by_span.get("engine.first_token.fetch", 0)
    assert sr.host_gap_ms_per_iter(t) == pytest.approx(working * 1e-6 / 2)
    assert sr.host_gap_ms_per_iter(t) == pytest.approx(2.6779545)
    chunks = [s for s in t.spans if s[0] == "serve.prefill_chunk"]
    assert chunks and all({"request_id", "slot", "tokens"} <= set(s[3])
                          for s in chunks)


def test_pr23s_recorded_trace_holds_no_scope_and_reads_none():
    with open(os.path.join(HERE, "data", "train_trace_planes.json")) as f:
        planes = [(name, {line: [tuple(e) for e in events]
                          for line, events in lines.items()})
                  for name, lines in json.load(f)["planes"]]
    t = sr.Trace(planes, {})
    assert len(t.ops) > 1000 and t.idle
    assert sr.scope_ms(t, sr.TRAIN_PROGRAMS, ("flash_attention",)) is None
    assert sr.kernel_calls(t, sr.TRAIN_PROGRAMS, "flash_fwd") is None
    assert sr.host_gap_ms_per_iter(t) is None
    assert set(sr.scope_shares(t).get("jit_step", {"other": 1})) == {"other"}
    assert list(sr.commentary(t)) == []


def test_every_new_metric_has_its_file_and_the_other_way_round():
    with open(os.path.join(configs.ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    files = {f[:-3] for f in os.listdir(
        os.path.join(configs.HERE, "layer_metrics")) if f.endswith(".py")}
    assert files == set(entries)
    # PR 24's fourteen, which name a reading of span_readings, and the ten
    # kernel times of PRs 27, 31 and 33, which call it from a `read` of
    # their own; a share of a roofline divides one of them by a cost
    # (benchmark/kernel_costs.py) and has a file of its own kind
    new = {n for n in files
           if n.startswith(("kernels.", "engine.decode_device_ms.",
                            "engine.prefill_chunk_device_ms.",
                            "scheduler.host_gap_ms_per_iter."))
           and "_roofline" not in n}
    assert len(new) == 24
    for name in new:
        with open(os.path.join(configs.HERE, "layer_metrics",
                               name + ".py")) as f:
            text = f.read()
        assert ("from benchmark.span_readings import" in text
                or "from benchmark import span_readings" in text), name
        assert entries[name]["workloads"]
        assert entries[name]["layer"] in ("kernels", "engine step",
                                          "scheduler")
