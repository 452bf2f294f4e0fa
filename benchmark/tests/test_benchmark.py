"""The harness end to end at tiny sizes, its arithmetic, and the two
proofs the comparison rests on: the lower-precision control comes out
as not correct, and so does a run whose timed path is broken."""

import glob
import json
import os
import statistics

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import (configs, loadgen, ref_train, reference, run, stats,
                       trace_reduce, weights)

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = os.path.join(HERE, "cells", "BENCHMARK.json")
ROOT = os.path.dirname(os.path.dirname(HERE))


def run_tiny(cell, seed=7, seconds=1.5, trace=0, **kw):
    return run.run_cell(cell, seed, seconds, trace, require_tpu=False,
                        benchmark_path=CELLS, **kw)


def tiny(name, dtype="float32"):
    config = configs.read_json(os.path.join(HERE, "cells", "configs",
                                            name + ".json"))
    config["torch_dtype"] = dtype
    return config


# ---- the reference against the program's forward pass ----

# every tiny configuration in the directory: a new family's is tested by
# arriving
TINY = sorted(os.path.basename(p)[:-len(".json")] for p in glob.glob(
    os.path.join(HERE, "cells", "configs", "*.json")))


@pytest.mark.parametrize("name", TINY)
def test_reference_matches_program_forward(name):
    config = tiny(name)
    dims = configs.dims(config)
    model, cfg = configs.program_config(config, 64)
    params = jax.jit(lambda k: weights.init_params(k, dims))(
        weights.seed_key(3))
    tokens = np.random.default_rng(0).integers(1, dims["vocab_size"], 48)
    want = reference.logits(params, tokens, dims)
    got = model.forward(params, jnp.asarray(tokens)[None], cfg)[0]
    # float32 on both sides: rounding only
    assert float(jnp.abs(want - got).max()) < 1e-4 * float(jnp.abs(want).max())


@pytest.mark.parametrize("name", TINY)
def test_weights_any_seed_and_leaf_by_leaf(name):
    dims = configs.dims(tiny(name, "bfloat16"))
    key = weights.seed_key(2 ** 31 + 77)
    tree = jax.jit(lambda k: weights.init_params(k, dims))(key)
    other = jax.jit(lambda k: weights.init_params(k, dims))(
        weights.seed_key(77))
    drawn = 0
    for path, (shape, init) in weights.leaf_specs(dims).items():
        leaf, theirs = tree, other
        for part in path:
            leaf, theirs = leaf[part], theirs[part]
        again = jax.jit(lambda k: weights.make_leaf(k, dims, path))(key)
        assert leaf.dtype == jnp.bfloat16 and leaf.shape == tuple(shape)
        assert bool(jnp.array_equal(leaf, again)), path
        if init is not None:
            drawn += 1
            assert not bool(jnp.array_equal(leaf, theirs)), path
    assert drawn


# ---- traffic is a pure function of the seed ----

def test_traffic_is_a_pure_function_of_the_seed():
    traffic = configs.read_json(os.path.join(ROOT, "benchmark", "traffic",
                                             "chat-steady.json"))
    a = loadgen.requests(traffic, 32768, 11, 200)
    b = loadgen.requests(traffic, 32768, 11, 200)
    c = loadgen.requests(traffic, 32768, 2 ** 31 + 5, 200)
    assert a == b and a != c
    sizes = lambda reqs: sorted((len(t), n) for t, n in reqs)
    assert sizes(a) == sizes(c)  # the same work, in another order
    p, o = traffic["prompt_tokens"], traffic["output_tokens"]
    assert all(p["min"] <= len(t) <= p["max"] and o["min"] <= n <= o["max"]
               for t, n in a)
    assert 200 <= statistics.median(len(t) for t, _ in a) <= 300
    due_a, due_c = loadgen.arrivals(8.0, 200, 11), loadgen.arrivals(8.0, 200, 12)
    assert due_a == loadgen.arrivals(8.0, 200, 11) and due_a != due_c
    assert due_a == sorted(due_a)
    assert abs(due_a[-1] - due_c[-1]) < 1e-9       # the same gaps, permuted
    assert 0.9 * 200 / 8.0 < due_a[-1] < 200 / 8.0


# ---- sample arithmetic ----

def test_percentile_needs_ten_samples_beyond_it():
    assert stats.percentile(list(range(199)), 95) is None
    assert stats.percentile(list(range(200)), 95) == pytest.approx(189.05)
    assert stats.percentile(list(range(1001)), 50) == 500
    assert stats.percentile([], 95) is None
    values = [10.0, 10.1, 10.2, 10.3, 10.4, 10.5]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx((q3 - q1) / 10.25)


# ---- the trace reduction, on a recorded trace ----

def test_trace_reduce_on_the_recorded_trace():
    with open(os.path.join(HERE, "data", "train_trace_planes.json")) as f:
        recorded = json.load(f)
    planes = [(name, {line: [tuple(e) for e in events]
                      for line, events in lines.items()})
              for name, lines in recorded["planes"]]
    out = trace_reduce.reduce(planes, window_s=1.0, n_devices=1)
    assert out["n_device_planes"] == 1
    assert out["busy_s"] == pytest.approx(recorded["expect"]["busy_s"])
    assert out["window_s"] == pytest.approx(recorded["expect"]["window_s"])
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["device_ops"][0][0] == recorded["expect"]["top_op"]
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    assert any(name.startswith("bench.") for name, _ in out["idle_gaps"])


def test_trace_reduce_names_gaps_and_ignores_custom_planes():
    planes = [
        ("/device:CUSTOM:Megascale Trace", {}),
        ("/device:TPU:0", {"XLA Ops": [("a", 0, 100), ("b", 100, 200),
                                       ("a", 1000, 1500)],
                           "Steps": [("0", 0, 1500)]}),
        ("/host:CPU", {"python3": [("bench.loader", 150, 900),
                                   ("x", 0, 2000)]}),
    ]
    out = trace_reduce.reduce(planes, window_s=9.0, n_devices=1)
    assert out["busy_s"] == pytest.approx(700e-9)
    assert out["window_s"] == pytest.approx(1500e-9)
    assert out["device_ops"] == [["a", pytest.approx(600e-9)],
                                 ["b", pytest.approx(100e-9)]]
    assert out["idle_gaps"] == [["bench.loader", pytest.approx(800e-9)]]


# ---- every driver end to end ----

@pytest.mark.parametrize("cell,metric", [
    ("tiny.train", "train_tokens_per_s"),
    ("tiny.chat", "itl_p95_ms"),
    ("tiny-moe.batch", "serve_tokens_per_s"),
])
def test_driver_end_to_end(cell, metric):
    result = run_tiny(cell, seconds=2.0)
    assert result["correct"], result
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"][metric]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0
    # the compared numbers, each with its limit, come last in the line
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    assert {"compilations_in_window"} < set(result["compared"])
    assert all(c["ok"] and c["value"] <= c["limit"]
               for c in result["compared"].values())
    json.dumps(result)


@pytest.mark.parametrize("cell,metric,sizes", [
    ("tiny.train", "train_step.step_ms", {"seq_len", "sequences_per_step"}),
    ("tiny.chat", "scheduler.queue_wait_p75_ms",
     {"slots", "max_seq_len", "prefill_chunk"}),
    ("tiny-moe.batch", "scheduler.occupancy_pct.batch",
     {"slots", "max_seq_len", "prefill_chunk"}),
])
def test_traced_run_reports_per_layer_metrics(cell, metric, sizes,
                                              monkeypatch):
    from benchmark import harness

    handed = {}
    real = harness.read_layer_metrics

    def reading(bench, name, moved, run_):
        handed.update(run_)
        return real(bench, name, moved, run_)

    monkeypatch.setattr(harness, "read_layer_metrics", reading)
    result = run_tiny(cell, seconds=6.0, trace=1)
    assert result["correct"], result
    assert result["metrics"][metric]["value"] > 0
    assert "setup_s" not in result["metrics"]
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    # what a roofline reader divides by, in every kind of run; off the
    # TPU there is no row of peaks and so no share of one
    assert sizes | {"dims", "chips", "peak"} <= set(handed)
    assert handed["dims"]["family"] and handed["peak"] is None
    assert not any("_roofline" in name for name in result["metrics"])
    if "slots" in sizes:
        steps = handed["counters"]["decode_steps"]
        assert 0 < handed["decode_tokens"] <= steps * handed["slots"]
        assert handed["decode_tokens"] < handed["kv_positions_read"] \
            <= handed["decode_tokens"] * handed["max_seq_len"]


def test_training_cell_on_four_virtual_devices():
    assert len(jax.devices()) >= 4
    result = run_tiny("tiny.train-x4", seconds=1.0)
    assert result["correct"], result


def test_the_command_refuses_to_run_off_the_tpu(capsys):
    with pytest.raises(SystemExit) as err:
        run.main(["--workload", "mistral-7b.train-4k", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert err.value.code not in (0, None)
    assert '"correct"' not in capsys.readouterr().out


# ---- the control comes out as not correct ----

def test_lower_precision_control_fails_the_training_limits():
    result = run_tiny("tiny.train", seconds=0.5, control=True)
    limits = configs.read_json(os.path.join(
        HERE, "cells", "traffic", "train-tiny.json"))["limits"]
    assert result["correct"]
    name = "first_moment_diff"
    assert result["checks"][name] < limits[name] < result["control"][name]
    # room on both sides
    assert 2 * result["checks"][name] < limits[name]
    assert 2 * limits[name] < result["control"][name]


@pytest.mark.parametrize("cell,traffic", [("tiny.chat", "chat-tiny"),
                                          ("tiny-moe.batch", "batch-tiny")])
def test_lower_precision_control_fails_the_serving_limit(cell, traffic):
    result = run_tiny(cell, seconds=1.0, control=True)
    limits = configs.read_json(os.path.join(
        HERE, "cells", "traffic", traffic + ".json"))["limits"]
    assert result["correct"]
    # the mean over the served tokens is the number that separates them;
    # the widest gap is held against an altered token
    # (which requests a short window finishes depends on timing, so the
    # room on both sides is the chip's to show, at the cell's own size)
    name = "served_logit_gap_mean"
    assert result["checks"][name] < limits[name] < result["control"][name]
    assert result["checks"]["served_logit_gap"] <= limits["served_logit_gap"]


# ---- a broken timed path comes out as not correct ----

def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    import metaflow_tpu.training as training

    real = training.make_trainer

    def broken_trainer(*args, **kw):
        state, step, shardings = real(*args, **kw)

        def unchanged(state, batch):
            _, metrics = step(jax.tree.map(jnp.copy, state), batch)
            return state, metrics
        return state, unchanged, shardings

    monkeypatch.setattr(training, "make_trainer", broken_trainer)
    result = run_tiny("tiny.train", seconds=0.5)
    assert result["correct"] is False


def test_a_served_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    from metaflow_tpu.serving import SlotEngine

    real = SlotEngine.decode_step

    def altered(self):
        return {slot: (tok + 1) % self.cfg.vocab_size
                for slot, tok in real(self).items()}

    monkeypatch.setattr(SlotEngine, "decode_step", altered)
    result = run_tiny("tiny.chat", seconds=1.0)
    assert result["correct"] is False


# ---- the committed benchmark is whole ----

def test_benchmark_json_names_files_that_exist():
    bench = configs.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and all(m["bound"] <= 0.1 for m in e2e.values())
    for cell in bench["workloads"]:
        _, _, config, traffic = configs.load_cell(cell["name"])
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "drivers", traffic["kind"] + ".py"))
        configs.dims(config)
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py")), m["name"]
        assert m["moves"] in e2e
        assert set(m.get("workloads", [])) <= cells
