"""A model of Mamba-2 layers, latent expert layers of which the chip
holds a share, and attention layers (the tiny Nemotron-H of
cells/configs/tiny-nemotron-h.json: MEM*EME, experts 2-5 of 8 held)
through the open-loop serving driver end to end, from a cell declared
beside cells/ (cells/BENCHMARK.json is never edited;
cells_super/BENCHMARK.json names cells/'s configuration and traffic
files); the lower-precision control comes out as not correct; and the
eight `.super` readers divide what the kernels need by what a trace
measured, and read nothing where the trace holds none of their scopes."""

import os

import pytest

from benchmark import configs, harness, kernel_costs, run, span_readings

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELLS = os.path.join(HERE, "cells_super", "BENCHMARK.json")
CELL = "tiny-nemotron-h.reason"
NEW = ["kernels.ssd_state_update_ms.super",
       "kernels.ssd_state_update_roofline.super",
       "kernels.ssd_chunk_ms.super", "kernels.ssd_chunk_roofline.super",
       "kernels.latent_moe_experts_ms.super",
       "kernels.latent_moe_experts_roofline.super",
       "kernels.moe_dispatch_ms.super", "kernels.latent_moe_other_ms.super"]


def run_tiny(seed=7, seconds=1.5, trace=0, **kw):
    return run.run_cell(CELL, seed, seconds, trace, require_tpu=False,
                        benchmark_path=CELLS, **kw)


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
    assert result["metrics"]["engine.decode_step_ms.chat"]["value"] > 0
    assert not set(NEW) & set(result["metrics"])


def test_lower_precision_control_fails_the_serving_limits():
    result = run_tiny(seconds=1.0, control=True)
    limits = configs.read_json(os.path.join(
        HERE, "cells", "traffic", "reason-tiny-super.json"))["limits"]
    assert result["correct"]
    for name in ("served_logit_gap_mean", "served_logit_gap"):
        assert result["checks"][name] < limits[name] < result["control"][name]


# ---- the readers, on a trace made by hand ----

D = "jit(_decode_greedy)/decode_layers/while/body/closed_call/"
P = "jit(_prefill)/decode_layers/while/body/closed_call/"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def by_hand():
    """Two decode executions of 100 ns and two prefill executions of 60
    between two others: in a decode step 30 ns under `ssd_state_update`,
    25 under `moe_experts` (inside the `cond` that picks the buffers'
    depth), 6 + 4 under `moe_dispatch` and `moe_combine`, 3 + 2 + 2 + 8
    under the router, the latent projections and the shared expert; in a
    prefill program 20 under `ssd_chunk`."""
    E = D + "ffn/cond/branch_1_fun/"
    scopes = {"1": {
        "%while.1": "jit(_decode_greedy)/decode_layers/while",
        "%state": D + "ssd_state_update/mul",
        "%conv": D + "ssd_conv/add",
        "%experts": E + "moe_experts/nce,nef->ncf/dot_general",
        "%scatter": E + "moe_dispatch/scatter-add",
        "%gather": E + "moe_combine/gather",
        "%router": D + "ffn/moe_router/dot_general",
        "%down": D + "ffn/moe_latent_down/dot_general",
        "%up": D + "ffn/moe_latent_up/dot_general",
        "%shared": D + "ffn/moe_shared_expert/dot_general",
        "%head": "jit(_decode_greedy)/btd,dv->btv"},
        "2": {"%while.1": "jit(_prefill)/decode_layers/while",
              "%chunk": P + "ssd_chunk/dot_general",
              "%proj": P + "ssd_in_proj/dot_general"}}
    step = [("%while.1 = while(...)", 0, 90),
            ("%state = fusion(...)", 0, 30), ("%conv = fusion(...)", 30, 35),
            ("%router = fusion(...)", 35, 38), ("%down = fusion(...)", 38, 40),
            ("%scatter = fusion(...)", 40, 46),
            ("%experts = fusion(...)", 46, 71),
            ("%gather = fusion(...)", 71, 75), ("%up = fusion(...)", 75, 77),
            ("%shared = fusion(...)", 77, 85),
            ("%head = fusion(...)", 90, 100)]
    chunk = [("%while.1 = while(...)", 0, 60), ("%proj = fusion(...)", 0, 30),
             ("%chunk = fusion(...)", 30, 50)]
    ops = [("%x = copy(...)", 0, 10), ("%x = copy(...)", 600, 610)]
    modules = [("jit_x(9)", 0, 10), ("jit_x(9)", 600, 610)]
    for at in (10, 200):
        ops += [(n, at + s, at + e) for n, s, e in chunk]
        modules.append(("jit__prefill(2)", at, at + 60))
        ops += [(n, at + 70 + s, at + 70 + e) for n, s, e in step]
        modules.append(("jit__decode_greedy(1)", at + 70, at + 170))
    spans = [("serve.iteration", 5, 395, {"iteration": 0}),
             ("serve.prefill_chunk", 8, 60, {"rows": 2, "tokens": 100}),
             ("serve.prefill_chunk", 190, 250, {"rows": 1, "tokens": 50})]
    return span_readings.Trace(
        [("/device:TPU:0", {"XLA Modules": sorted(modules, key=lambda m: m[1]),
                            "XLA Ops": sorted(ops, key=lambda o: o[1])}),
         ("/host:CPU", {"python3#0": spans})], scopes)


def real_dims():
    return configs.dims(configs.read_json(os.path.join(
        ROOT, "benchmark", "configs", "nemotron-3-super-serve.json")))


def reader(name):
    bench = {"per_layer": [{"name": name, "unit": "x",
                            "moves": "itl_p90_ms", "workloads": [CELL]}]}
    return lambda run_: harness.read_layer_metrics(
        bench, CELL, set(), run_).get(name, {}).get("value")


def a_run():
    return {"trace": {}, "dims": real_dims(), "chips": 1, "peak": PEAK,
            "slots": 128, "max_seq_len": 2560, "prefill_chunk": 64,
            "counters": {"decode_steps": 10}, "decode_tokens": 920,
            "kv_positions_read": 0}


def test_readers_divide_the_need_by_the_traces_time(monkeypatch):
    monkeypatch.setattr(span_readings, "trace", lambda run_: by_hand())
    run_ = a_run()
    got = {name: reader(name)(run_) for name in NEW}
    assert got[NEW[0]] == pytest.approx(30e-6)
    assert got[NEW[2]] == pytest.approx(20e-6)
    assert got[NEW[4]] == pytest.approx(25e-6)
    assert got[NEW[6]] == pytest.approx(10e-6)
    assert got[NEW[7]] == pytest.approx(15e-6)
    # 92 lanes x 5 layers: the state [128, 64, 128] float32 read and
    # written, 4,194,304 B each way, and x, y, B, C and dt beside it
    state = 128 * 64 * 128
    nbytes = 92 * 5 * 4 * (2 * state + 2 * 8192 + 2 * 8 * 128 + 128)
    assert got[NEW[1]] == pytest.approx(kernel_costs.roofline_pct(
        (92 * 5 * 5 * state, nbytes), 30e-9, PEAK))
    # the programs carried 1.5 rows and 75 real tokens on average
    rows, tokens, L = 1.5, 75, 50
    nbytes = 5 * 4 * (rows * 2 * state + tokens * (
        2 * 8192 + 2 * 8 * 128 + 128))
    ops = 5 * rows * (8 * L * L * 128 + 128 * L * L * 64
                      + 4 * L * 128 * 64 * 128)
    assert got[NEW[3]] == pytest.approx(kernel_costs.roofline_pct(
        (ops, nbytes), 20e-9, PEAK))
    # 92 tokens reach 128 x (1 - (1 - 22/512)^92) = 125.7 of the 128 held
    # experts of each of 5 layers, two matrices of 1024 x 2688 in
    # bfloat16; a quarter of a token's 22 pairs falls here
    reached = 128 * (1 - (1 - 22 / 512) ** 92)
    assert reached == pytest.approx(125.7, abs=0.1)
    per_expert = 2 * 1024 * 2688
    assert got[NEW[5]] == pytest.approx(kernel_costs.roofline_pct(
        (5 * 2 * 92 * 22 * 0.25 * per_expert, 5 * reached * per_expert * 2),
        25e-9, PEAK))
    # off the chip there is no row of peaks: no share, never 0
    run_["peak"] = None
    assert all(reader(n)(run_) is None for n in (NEW[1], NEW[3], NEW[5]))
    assert reader(NEW[0])(run_) == pytest.approx(30e-6)


def test_readers_find_nothing_in_another_familys_program(monkeypatch):
    """The parent's programs, and every other family's: none of the
    `ssd_*` scopes; a Mixtral step's `moe_experts` is another metric's."""
    from test_span_readings import by_hand as kv_only

    monkeypatch.setattr(span_readings, "trace", lambda run_: kv_only())
    run_ = a_run()
    for name in NEW[:4] + NEW[7:]:
        assert reader(name)(run_) is None, name


def test_no_share_can_pass_100_at_the_peaks():
    """What a reader counts is the least the kernel can move: at the
    chip's peaks the least time is the share's whole."""
    import importlib.util

    def cost(name, fn, *args):
        path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
        spec = importlib.util.spec_from_file_location("m", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return getattr(mod, fn)(real_dims(), *args)

    for c in (cost(NEW[1], "state_update_cost", 92.0),
              cost(NEW[3], "chunk_cost", 1.5, 75.0),
              cost(NEW[5], "experts_cost", 92.0)):
        least, which = kernel_costs.bound(c, PEAK)
        assert which == "bandwidth"
        assert kernel_costs.roofline_pct(c, least, PEAK) == pytest.approx(100)
