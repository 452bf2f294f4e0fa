"""A stack that is run several times over the same weights (the tiny
Ouro of cells/configs/tiny-ouro.json: 2 layers, 3 passes, a K and V pool
of 6 indices) through the open-loop serving driver end to end, from a
cell declared beside cells/ (cells/BENCHMARK.json is never edited;
cells_loop/BENCHMARK.json names cells/'s configuration and traffic
files); the lower-precision control comes out as not correct; and the
four `.loop` readers read what a trace holds and nothing where it holds
no `loop_pass` scope."""

import os

import pytest

from benchmark import configs, harness, kernel_costs, run, span_readings

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELLS = os.path.join(HERE, "cells_loop", "BENCHMARK.json")
CELL = "tiny-ouro.chat"
NEW = ["kernels.decode_attention_roofline.loop",
       "engine.loop_pass_device_ms.loop",
       "engine.weight_stream_roofline.loop",
       "engine.weight_passes_per_step.loop"]


def run_tiny(seed=7, seconds=1.5, trace=0, **kw):
    return run.run_cell(CELL, seed, seconds, trace, require_tpu=False,
                        benchmark_path=CELLS, **kw)


def test_serve_open_end_to_end():
    result = run_tiny(seed=2 ** 31 + 11, seconds=2.0)
    assert result["correct"], result
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"]["itl_p95_ms"]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0


def test_traced_run_reads_the_spans_and_nothing_of_the_device_off_the_chip():
    """Off the chip the trace has no device plane and the run no row of
    peaks: the readers of device time return nothing and raise nothing;
    the span's `passes` is read all the same."""
    result = run_tiny(seconds=6.0, trace=1)
    assert result["correct"], result
    assert result["metrics"]["engine.decode_step_ms.chat"]["value"] > 0
    assert result["metrics"]["engine.weight_passes_per_step.loop"] == {
        "value": 3.0, "unit": "passes"}
    assert not set(NEW[:3]) & set(result["metrics"])


def test_lower_precision_control_fails_the_serving_limits():
    result = run_tiny(seconds=1.0, control=True)
    limits = configs.read_json(os.path.join(
        HERE, "cells", "traffic", "chat-tiny-loop.json"))["limits"]
    assert result["correct"]
    for name in ("served_logit_gap_mean", "served_logit_gap"):
        assert result["checks"][name] < limits[name] < result["control"][name]


# ---- the readers, on a trace made by hand ----

D = "jit(_decode_greedy)/decode_layers/while/body/loop_pass/"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def by_hand():
    """Two decode executions of 100 ns between two others: under
    `loop_pass` the layers' 70 ns and the closing norm's 8, outside it
    the outer loop's own 7 and the head's 10."""
    L = D + "while/body/closed_call/"
    scopes = {"1": {
        "%while.1": "jit(_decode_greedy)/decode_layers/while",
        "%while.2": D + "while",
        "%attn": L + "decode_attention/pool_attention",
        "%write": L + "kv_cache_update/scatter",
        "%ffn": L + "ffn/dot_general",
        "%norm": D + "loop_norm/mul",
        "%head": "jit(_decode_greedy)/btd,dv->btv"}}
    step = [("%while.1 = while(...)", 0, 90),
            ("%while.2 = while(...)", 5, 75),
            ("%attn = custom-call(...)", 5, 25),
            ("%write = fusion(...)", 25, 35), ("%ffn = fusion(...)", 35, 70),
            ("%norm = fusion(...)", 75, 83), ("%head = fusion(...)", 90, 100)]
    ops = [("%x = copy(...)", 0, 10), ("%x = copy(...)", 400, 410)]
    modules = [("jit_x(9)", 0, 10), ("jit_x(9)", 400, 410)]
    for at in (10, 130):
        ops += [(n, at + s, at + e) for n, s, e in step]
        modules.append(("jit__decode_greedy(1)", at, at + 100))
    spans = [("serve.iteration", 5, 395, {"iteration": 0}),
             ("serve.decode_step", 8, 120, {"active": 7, "passes": 4}),
             ("serve.decode_step", 125, 240, {"active": 8, "passes": 4})]
    return span_readings.Trace(
        [("/device:TPU:0", {"XLA Modules": sorted(modules, key=lambda m: m[1]),
                            "XLA Ops": ops}),
         ("/host:CPU", {"python3#0": spans})], scopes)


def real_dims():
    return configs.dims(configs.read_json(os.path.join(
        ROOT, "benchmark", "configs", "ouro-2.6b-serve.json")))


def reader(name):
    bench = {"per_layer": [{"name": name, "unit": "x",
                            "moves": "itl_p90_ms", "workloads": [CELL]}]}
    return lambda run_: harness.read_layer_metrics(
        bench, CELL, set(), run_).get(name, {}).get("value")


def a_run():
    return {"trace": {}, "dims": real_dims(), "chips": 1, "peak": PEAK,
            "slots": 10, "max_seq_len": 512, "prefill_chunk": 64,
            "counters": {"decode_steps": 10}, "decode_tokens": 70,
            "kv_positions_read": 17500}


def test_readers_divide_the_need_by_the_traces_time(monkeypatch):
    monkeypatch.setattr(span_readings, "trace", lambda run_: by_hand())
    run_ = a_run()
    # 1,750 positions a step at 4 x 48 indices of 2 x 16 x 128 x 2 B: K
    # and V of a position are 1,572,864 B, over the scope's 20 ns
    nbytes = 1750 * 1_572_864
    ops = 1750 * 192 * 4 * 16 * 128
    assert reader(NEW[0])(run_) == pytest.approx(
        kernel_costs.roofline_pct((ops, nbytes), 20e-9, PEAK))
    # under `loop_pass`: the inner loop's 70 and the norm's 8, over 4
    assert reader(NEW[1])(run_) == pytest.approx(78e-6 / 4)
    # the stack's matrices once a pass and the head's: 4 x 48 x
    # 51,380,224 + 2048 x 49,152 parameters in bfloat16, over an
    # execution's 100 less the pool's 20 + 10
    params = 4 * 48 * 51_380_224 + 2048 * 49_152
    assert reader(NEW[2])(run_) == pytest.approx(kernel_costs.roofline_pct(
        (2 * 7 * params, 2 * params), 70e-9, PEAK))
    assert reader(NEW[3])(run_) == 4.0
    # off the chip there is no row of peaks: no share, never 0
    run_["peak"] = None
    assert reader(NEW[0])(run_) is None and reader(NEW[2])(run_) is None
    assert reader(NEW[1])(run_) == pytest.approx(78e-6 / 4)


def test_readers_find_nothing_in_a_program_with_one_pass(monkeypatch):
    """The parent's programs, and every other family's: no `loop_pass`
    scope, no `passes` on the span."""
    from test_span_readings import by_hand as kv_only

    monkeypatch.setattr(span_readings, "trace", lambda run_: kv_only())
    got = [reader(name)(a_run()) for name in NEW]
    assert got[1:] == [None] * 3
    # the attention share needs no new scope: it reads `decode_attention`
    assert got[0] is None or got[0] > 0
