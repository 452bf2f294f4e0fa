"""A model of power-retention layers alone (the tiny Brumby of
cells/configs/tiny-brumby.json: a state pool and no KV pool) through the
closed-loop serving driver end to end, from a cell declared beside
cells/ (cells/BENCHMARK.json is never edited; cells_retention/
BENCHMARK.json names cells/'s configuration and traffic files); the
lower-precision control comes out as not correct; and
the readers of the retention kernels divide what the kernel needs by
what the trace measured."""

import os

import pytest

from benchmark import configs, harness, kernel_costs, run, span_readings

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELLS = os.path.join(HERE, "cells_retention", "BENCHMARK.json")
CELL = "tiny-brumby.gen"
NEW = ["kernels.retention_update_ms.gen",
       "kernels.retention_update_roofline.gen",
       "kernels.retention_chunk_ms.gen",
       "kernels.retention_chunk_roofline.gen",
       "kernels.state_pool_ms.gen"]


def run_tiny(seed=7, seconds=1.5, trace=0, **kw):
    return run.run_cell(CELL, seed, seconds, trace, require_tpu=False,
                        benchmark_path=CELLS, **kw)


def test_serve_closed_end_to_end():
    result = run_tiny(seed=2 ** 31 + 11, seconds=2.0)
    assert result["correct"], result
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0


def test_traced_run_reads_what_it_finds_and_nothing_off_the_chip():
    """Off the chip the trace has no device plane and the run no row of
    peaks: every new reader returns nothing and raises nothing."""
    result = run_tiny(seconds=6.0, trace=1)
    assert result["correct"], result
    assert result["metrics"]["scheduler.occupancy_pct.batch"]["value"] > 0
    assert result["metrics"]["engine.decode_step_ms.batch"]["value"] > 0
    assert not set(NEW) & set(result["metrics"])


def test_lower_precision_control_fails_the_serving_limits():
    result = run_tiny(seconds=1.0, control=True)
    limits = configs.read_json(os.path.join(
        HERE, "cells", "traffic", "gen-tiny.json"))["limits"]
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
              "%upd": D + "retention_update/retention_update",
              "%phi": D + "retention_update/mul",
              "%qkvg": D + "retention_qkvg/dot_general",
              "%carry": D + "dynamic_update_slice",
              "%head": "jit(_decode_greedy)/btd,dv->btv"},
        "2": {"%chunk": P + "retention_chunk/btkgd,bked->btkge/dot_general",
              "%out": P + "retention_out/dot_general"}}
    step = [("%while.1 = while(...)", 0, 90),
            ("%upd = custom-call(...)", 0, 30),
            ("%phi = fusion(...)", 30, 40), ("%qkvg = fusion(...)", 40, 60),
            ("%carry = fusion(...)", 60, 75), ("%head = fusion(...)", 90, 100)]
    chunk = [("%chunk = fusion(...)", 0, 25), ("%out = fusion(...)", 25, 40)]
    ops = [("%x = copy(...)", 0, 10), ("%x = copy(...)", 400, 410)]
    modules = [("jit_x(9)", 0, 10), ("jit_x(9)", 400, 410)]
    for at in (10, 130):
        ops += [(n, at + s, at + e) for n, s, e in step]
        modules.append(("jit__decode_greedy(1)", at, at + 100))
    for at in (250, 300):
        ops += [(n, at + s, at + e) for n, s, e in chunk]
        modules.append(("jit__prefill(2)", at, at + 40))
    spans = [("serve.iteration", 5, 395, {"iteration": 0}),
             ("serve.prefill_chunk", 245, 295, {"tokens": 128, "rows": 2}),
             ("serve.prefill_chunk", 296, 345, {"tokens": 32, "rows": 1})]
    return span_readings.Trace(
        [("/device:TPU:0", {"XLA Modules": sorted(modules, key=lambda m: m[1]),
                            "XLA Ops": ops}),
         ("/host:CPU", {"python3#0": spans})], scopes)


def real_dims():
    return configs.dims(configs.read_json(os.path.join(
        ROOT, "benchmark", "configs", "brumby-14b-serve.json")))


def reader(name):
    bench = {"per_layer": [{"name": name, "unit": "x",
                            "moves": "serve_tokens_per_s",
                            "workloads": [CELL]}]}
    return lambda run_: harness.read_layer_metrics(
        bench, CELL, set(), run_).get(name, {}).get("value")


def test_readers_divide_the_kernels_need_by_the_traces_time(monkeypatch):
    monkeypatch.setattr(span_readings, "trace", lambda run_: by_hand())
    run_ = {"trace": {}, "dims": real_dims(), "chips": 1, "peak": PEAK,
            "slots": 20, "max_seq_len": 4096, "prefill_chunk": 64,
            "counters": {"decode_steps": 10}, "decode_tokens": 190,
            "kv_positions_read": 1}
    assert reader(NEW[0])(run_) == pytest.approx(40e-6)   # %upd and %phi
    assert reader(NEW[2])(run_) == pytest.approx(25e-6)
    # under decode_layers and no inner scope: %while's own 15 and %carry's 15
    assert reader(NEW[4])(run_) == pytest.approx(30e-6)
    # 19 lanes a step: per lane, layer and KV head S and z read and
    # written once, 8,256 products of a head's symmetric square
    elements = 8 * 19 * 8 * 8256 * 129
    assert reader(NEW[1])(run_) == pytest.approx(kernel_costs.roofline_pct(
        (elements * 13, elements * 8), 40e-9, PEAK))
    # a program's rows and real tokens: the means of the spans' (2, 128)
    # and (1, 32); each row reads and writes a state of its own
    state = 8 * 8256 * 129
    ops = 8 * 80 * (2 * state * 6 + 2 * 40 * 128 * 80 / 1.5)
    nbytes = 8 * (1.5 * state * 8 + 80 * 96 * 128 * 2)
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
            "slots": 20, "max_seq_len": 4096, "prefill_chunk": 64,
            "counters": {"decode_steps": 10}, "decode_tokens": 190,
            "kv_positions_read": 1}
    assert [reader(name)(run_) for name in NEW] == [None] * 5


def test_the_state_of_a_full_pool_is_what_the_configuration_says():
    dims = real_dims()
    assert dims["state_dim"] == 8256
    state = dims["n_kv_heads"] * dims["state_dim"] * (dims["head_dim"] + 1) * 4
    assert state == 34_080_768         # 34.08 MB a layer and slot
    assert 20 * 8 * state == pytest.approx(5.45e9, rel=0.01)
    # what the program lays out: 65 whole rows of 128 lanes
    from metaflow_tpu.ops import retention
    assert retention.state_dim(dims["head_dim"]) == 8320
