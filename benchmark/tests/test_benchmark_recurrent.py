"""A model with recurrent state (the tiny Jamba of
cells/configs/tiny-jamba.json) through the open-loop serving driver end
to end, from a directory of its own beside cells/ (cells/BENCHMARK.json
is never edited); the lower-precision control comes out as not correct;
and the readers of the state-space kernels divide what the kernel needs
by what the trace measured."""

import os

import pytest

from benchmark import configs, harness, kernel_costs, run, span_readings

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELLS = os.path.join(HERE, "cells_recurrent", "BENCHMARK.json")
CELL = "tiny-jamba.reason"
NEW = ["kernels.ssm_state_update_ms.reason",
       "kernels.ssm_state_update_roofline.reason",
       "kernels.ssm_scan_ms.reason", "kernels.ssm_scan_roofline.reason",
       "kernels.state_pool_ms.reason"]


def run_tiny(seed=7, seconds=1.5, trace=0, **kw):
    return run.run_cell(CELL, seed, seconds, trace, require_tpu=False,
                        benchmark_path=CELLS, **kw)


def test_the_two_tiny_configuration_files_are_one():
    read = lambda d: configs.read_json(os.path.join(
        HERE, d, "configs", "tiny-jamba.json"))
    assert read("cells") == read("cells_recurrent")


def test_serve_open_end_to_end():
    result = run_tiny(seed=2 ** 31 + 11, seconds=2.0)
    assert result["correct"], result
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"]["itl_p90_ms"]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0


def test_traced_run_reads_what_it_finds_and_nothing_off_the_chip():
    """Off the chip the trace has no device plane and the run no row of
    peaks: every new reader returns nothing and raises nothing."""
    result = run_tiny(seconds=6.0, trace=1)
    assert result["correct"], result
    assert result["metrics"]["scheduler.queue_wait_p75_ms"]["value"] >= 0
    assert result["metrics"]["engine.decode_step_ms.chat"]["value"] > 0
    assert not set(NEW) & set(result["metrics"])


def test_lower_precision_control_fails_the_serving_limits():
    result = run_tiny(seconds=1.0, control=True)
    limits = configs.read_json(os.path.join(
        HERE, "cells_recurrent", "traffic", "reason-tiny.json"))["limits"]
    assert result["correct"]
    for name in ("served_logit_gap_mean", "served_logit_gap"):
        assert result["checks"][name] < limits[name] < result["control"][name]


def test_a_state_advanced_over_the_padding_is_not_correct(monkeypatch):
    """The engine's own fault, where it is produced: a prefill that
    calls every position of its bucket real."""
    from metaflow_tpu.serving import SlotEngine

    real = SlotEngine.__init__

    def broken(self, *args, **kw):
        real(self, *args, **kw)
        fn = self._prefill_fn
        self._prefill_fn = lambda p, c, chunk, slot, start, n: fn(
            p, c, chunk, slot, start)

    monkeypatch.setattr(SlotEngine, "__init__", broken)
    assert run_tiny(seconds=1.0)["correct"] is False


# ---- the readers, on a trace made by hand ----

D = "jit(_decode_greedy)/decode_layers/while/body/closed_call/"
P = "jit(_prefill)/decode_layers/while/body/closed_call/"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def by_hand():
    scopes = {
        "1": {"%while.1": "jit(_decode_greedy)/decode_layers/while",
              "%upd": D + "ssm_state_update/mul",
              "%conv": D + "ssm_conv/add",
              "%att": D + "decode_attention/decode_attention/while/body/mul",
              "%carry": D + "dynamic_update_slice",
              "%head": "jit(_decode_greedy)/btd,vd->btv"},
        "2": {"%scan": P + "ssm_scan/while/body/mul",
              "%in": P + "ssm_in_proj/dot_general"}}
    step = [("%while.1 = while(...)", 0, 90), ("%upd = fusion(...)", 0, 40),
            ("%conv = fusion(...)", 40, 50), ("%att = fusion(...)", 50, 60),
            ("%carry = fusion(...)", 60, 75), ("%head = fusion(...)", 90, 100)]
    chunk = [("%scan = fusion(...)", 0, 25), ("%in = fusion(...)", 25, 40)]
    ops = [("%x = copy(...)", 0, 10), ("%x = copy(...)", 400, 410)]
    modules = [("jit_x(9)", 0, 10), ("jit_x(9)", 400, 410)]
    for at in (10, 130):
        ops += [(n, at + s, at + e) for n, s, e in step]
        modules.append(("jit__decode_greedy(1)", at, at + 100))
    for at in (250, 300):
        ops += [(n, at + s, at + e) for n, s, e in chunk]
        modules.append(("jit__prefill(2)", at, at + 40))
    spans = [("serve.iteration", 5, 395, {"iteration": 0}),
             ("serve.prefill_chunk", 245, 295, {"tokens": 64, "slot": 1}),
             ("serve.prefill_chunk", 296, 345, {"tokens": 16, "slot": 1})]
    return span_readings.Trace(
        [("/device:TPU:0", {"XLA Modules": sorted(modules, key=lambda m: m[1]),
                            "XLA Ops": ops}),
         ("/host:CPU", {"python3#0": spans})], scopes)


def real_dims():
    return configs.dims(configs.read_json(os.path.join(
        ROOT, "benchmark", "configs", "jamba2-3b-serve.json")))


def reader(name):
    bench = {"per_layer": [{"name": name, "unit": "x", "moves": "itl_p90_ms",
                            "workloads": [CELL]}]}
    return lambda run_: harness.read_layer_metrics(
        bench, CELL, set(), run_).get(name, {}).get("value")


def test_readers_divide_the_kernels_need_by_the_traces_time(monkeypatch):
    monkeypatch.setattr(span_readings, "trace", lambda run_: by_hand())
    dims = real_dims()
    run_ = {"trace": {}, "dims": dims, "chips": 1, "peak": PEAK, "slots": 128,
            "max_seq_len": 2560, "prefill_chunk": 64,
            "counters": {"decode_steps": 10}, "decode_tokens": 900,
            "kv_positions_read": 1}
    assert reader(NEW[0])(run_) == pytest.approx(40e-6)
    assert reader(NEW[2])(run_) == pytest.approx(25e-6)
    # under decode_layers and no inner scope: %while's own 15 and %carry's 15
    assert reader(NEW[4])(run_) == pytest.approx(30e-6)
    # 90 lanes a step: per lane and layer the state read and written once
    state = 26 * 16 * 5120 * 4
    ops, nbytes = 26 * 90 * 6 * 16 * 5120, 90 * (2 * state
                                                 + 26 * 4 * (3 * 5120 + 32))
    assert reader(NEW[1])(run_) == pytest.approx(
        kernel_costs.roofline_pct((ops, nbytes), 40e-9, PEAK))
    # a chunk's real tokens: the mean of the spans' 64 and 16
    ops, nbytes = 26 * 40 * 6 * 16 * 5120, 2 * state + 40 * 26 * 4 * (
        3 * 5120 + 32)
    assert reader(NEW[3])(run_) == pytest.approx(
        kernel_costs.roofline_pct((ops, nbytes), 25e-9, PEAK))
    # off the chip there is no row of peaks: no share, never 0
    run_["peak"] = None
    assert reader(NEW[1])(run_) is None and reader(NEW[3])(run_) is None
    assert reader(NEW[0])(run_) == pytest.approx(40e-6)


def test_readers_find_nothing_in_a_program_without_the_scopes(monkeypatch):
    from test_span_readings import by_hand as kv_only

    monkeypatch.setattr(span_readings, "trace", lambda run_: kv_only())
    run_ = {"trace": {}, "dims": real_dims(), "chips": 1, "peak": PEAK,
            "slots": 64, "max_seq_len": 1280, "prefill_chunk": 64,
            "counters": {"decode_steps": 10}, "decode_tokens": 640,
            "kv_positions_read": 1}
    assert [reader(name)(run_) for name in NEW] == [None] * 5


def test_the_state_of_a_full_pool_is_what_the_configuration_says():
    dims = real_dims()
    assert (dims["n_attn_layers"], dims["n_mamba_layers"]) == (2, 26)
    state = dims["n_mamba_layers"] * dims["d_state"] * dims["d_inner"] * 4
    assert state == 8_519_680          # 8.52 MB a slot
    assert 128 * state == pytest.approx(1.09e9, rel=0.01)
