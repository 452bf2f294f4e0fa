"""Benchmark: Llama training throughput (tokens/sec/chip) on real hardware.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
The reference (Netflix/metaflow) publishes no numbers, so vs_baseline is
reported against BENCH_BASELINE when set (else 1.0). Nothing here has
been measured on the chip yet (ROADMAP S1).

Also measures step-launch p50 latency of the orchestration layer when
BENCH_MODE=launch (the reference's only quantified metric family).
"""

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def bench_tokens_per_sec():
    import jax
    import jax.numpy as jnp

    from metaflow_tpu.models import llama
    from metaflow_tpu.spmd import MeshSpec, create_mesh
    from metaflow_tpu.training import (
        default_optimizer,
        make_trainer,
        memory_efficient_optimizer,
        shard_batch,
    )

    n_devices = len(jax.devices())
    on_tpu = jax.default_backend() == "tpu"

    # env-overridable knobs so perf sweeps don't need code edits
    opt_kind = os.environ.get("BENCH_OPT", "factored" if on_tpu else "adamw")
    remat_policy = os.environ.get("BENCH_REMAT_POLICY", "") or None
    loss_chunk = int(os.environ.get("BENCH_LOSS_CHUNK", "256"))

    if on_tpu:
        cfg = llama.LlamaConfig.bench_1b(
            attention_impl="flash" if n_devices == 1 else "auto",
            remat_policy=remat_policy,
            loss_chunk=loss_chunk,
        )
        # chunked CE + factored optimizer state move the HBM ceiling well
        # past the old batch-16 limit (adamw fp32 state + full fp32 logits)
        batch = int(os.environ.get("BENCH_BATCH", "32"))
        seq = int(os.environ.get("BENCH_SEQ", "2048"))
        steps = 10
    else:  # CPU smoke fallback
        cfg = llama.LlamaConfig.tiny()
        batch, seq = 4, 128
        steps = 3

    if opt_kind == "factored":
        optimizer = memory_efficient_optimizer(total_steps=1000)
    elif opt_kind == "adamw":
        optimizer = default_optimizer(total_steps=1000)
    else:
        raise SystemExit("BENCH_OPT must be 'factored' or 'adamw', got %r"
                         % opt_kind)

    mesh = create_mesh(MeshSpec.fsdp() if n_devices > 1 else MeshSpec.dp())
    state, step, _ = make_trainer(
        jax.random.PRNGKey(0), cfg, mesh, llama, optimizer=optimizer,
    )
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size
    )
    data = shard_batch({"tokens": tokens}, mesh)

    with mesh:
        # compile + warmup
        state, m = step(state, data)
        jax.block_until_ready(m["loss"])
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step(state, data)
        jax.block_until_ready(m["loss"])
        dt = time.perf_counter() - t0

    tokens_per_step = batch * seq
    tps_per_chip = tokens_per_step * steps / dt / n_devices
    mfu = _mfu(tps_per_chip, state["params"], cfg, seq,
               jax.devices()[0].device_kind)
    return {
        "metric": "llama_%s_train_tokens_per_sec_per_chip"
        % ("1b_bf16" if on_tpu else "tiny_cpu"),
        "value": round(tps_per_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": _vs_baseline(tps_per_chip),
        "extra": {
            "n_devices": n_devices,
            "backend": jax.default_backend(),
            "params": llama.num_params(state["params"]),
            "batch": batch,
            "seq": seq,
            "optimizer": opt_kind,
            "loss": float(m["loss"]),
            "remat_policy": remat_policy,
            "loss_chunk": loss_chunk,
            # make_trainer resolves the ZeRO sharded update from
            # TPUFLOW_ZERO; record the knob so sweeps are attributable
            "zero_update": os.environ.get("TPUFLOW_ZERO", "0"),
            **mfu,
        },
    }


def _chip_tables():
    """(peak bf16 TFLOP/s, HBM GB/s) published-spec tables — the single
    source of truth lives in training/metrics.py (imported lazily: the
    CPU-by-design modes pin the CPU before anything imports jax)."""
    from metaflow_tpu.training.metrics import TPU_HBM_GBPS, TPU_PEAK_TFLOPS

    return TPU_PEAK_TFLOPS, TPU_HBM_GBPS


def _mfu(tps_per_chip, params, cfg, seq, device_kind):
    """Model FLOPs utilization for a train step (fwd+bwd = 3x fwd).

    FLOPs/token = 6*N_params + 12*L*D*S (the causal-attention score/value
    matmuls, PaLM appendix B convention — embedding lookups excluded by
    counting only matmul params is the usual MaxText/nanoGPT-style math;
    we count ALL params incl. embeddings, which slightly OVERstates FLOPs
    and therefore overstates MFU by <2% at 32k vocab; noted for honesty).
    """
    from metaflow_tpu.models import llama

    n_params = llama.num_params(params)
    flops_per_token = 6.0 * n_params + 12.0 * cfg.n_layers * cfg.dim * seq
    achieved = tps_per_chip * flops_per_token / 1e12
    kind = (device_kind or "").lower()
    peak_table, _hbm = _chip_tables()
    peak = next((tf for sub, tf in peak_table if sub in kind), None)
    out = {
        "device_kind": device_kind,
        "model_tflops_per_chip": round(achieved, 2),
    }
    if peak:
        out["peak_tflops"] = peak
        out["mfu"] = round(achieved / peak, 4)
    return out


def _append_history(result):
    """Persist every measurement AT MEASUREMENT TIME. BENCH_HISTORY=0
    disables the append (hermetic test subprocesses must not dirty the
    history)."""
    if os.environ.get("BENCH_HISTORY") == "0":
        return
    here = os.path.dirname(os.path.abspath(__file__))
    entry = {"ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
             **result}
    with open(os.path.join(here, "BENCH_HISTORY.jsonl"), "a") as f:
        f.write(json.dumps(entry) + "\n")


def _interleaved_reps(pass_a, pass_b, reps):
    """Run two zero-arg passes ALTERNATELY `reps` times each and return
    (a_runs, b_runs). Interleaving exposes both sides to the same slice
    of host drift (thermal, page cache, background load) instead of
    measuring side A on a cold machine and side B on a hot one."""
    a_runs, b_runs = [], []
    for _ in range(reps):
        a_runs.append(pass_a())
        b_runs.append(pass_b())
    return a_runs, b_runs


def _median_run(runs, key=None):
    """The median element of `runs` ordered by `key` (identity by
    default). Median, not min: min-of-N rewards whichever side got the
    single luckiest pass — the round-13 serving gates flaked on exactly
    that — while the median is robust to a one-off slow OR fast rep."""
    runs = sorted(runs, key=key or (lambda r: r))
    return runs[len(runs) // 2]


def bench_decode():
    """Autoregressive decode throughput (tokens/s/chip): jitted
    prefill+scan generation from metaflow_tpu.inference on the bench
    model (KV-cache resident in HBM)."""
    import jax

    from metaflow_tpu.inference import make_generator
    from metaflow_tpu.models import llama

    on_tpu = jax.default_backend() == "tpu"
    # flash-decode (chunked online-softmax over only the filled prefix)
    # is the long-context serving path; BENCH_DECODE_ATTN=dense compares
    # against the whole-cache einsum
    attn_impl = os.environ.get("BENCH_DECODE_ATTN", "chunked")
    if on_tpu:
        cfg = llama.LlamaConfig.bench_1b(attention_impl="xla", remat=False)
        batch = int(os.environ.get("BENCH_DECODE_BATCH", "8"))
        prompt_len, new_tokens = 128, 256
    else:
        cfg = llama.LlamaConfig.tiny()
        batch, prompt_len, new_tokens = 2, 16, 16

    from metaflow_tpu.spmd import MeshSpec, batch_sharding, create_mesh

    n_devices = len(jax.devices())
    # data-parallel decode over every chip: the per-chip division below
    # is only honest when the work is actually spread (contrast a bare
    # jit, which would pin everything to one device)
    mesh = create_mesh(MeshSpec.dp())
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    if batch % n_devices:
        batch = max(n_devices, batch - batch % n_devices)
    prompt = jax.device_put(
        jax.random.randint(
            jax.random.PRNGKey(1), (batch, prompt_len), 0, cfg.vocab_size),
        batch_sharding(mesh),
    )
    gen = make_generator(cfg, max_new_tokens=new_tokens,
                         attn_impl=attn_impl)
    with mesh:
        out = gen(params, prompt, jax.random.PRNGKey(2))  # compile+warmup
        jax.block_until_ready(out)
        reps = 3
        t0 = time.perf_counter()
        for i in range(reps):
            out = gen(params, prompt, jax.random.PRNGKey(3 + i))
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
    tps = batch * new_tokens * reps / dt / n_devices
    return {
        "metric": "llama_%s_decode_tokens_per_sec_per_chip"
        % ("1b_bf16" if on_tpu else "tiny_cpu"),
        "value": round(tps, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": _vs_baseline(tps),
        "extra": {
            "backend": jax.default_backend(),
            "n_devices": n_devices,
            "batch": batch,
            "prompt_len": prompt_len,
            "new_tokens": new_tokens,
            "attn_impl": attn_impl,
            "params": llama.num_params(params),
        },
    }


def bench_hlo_estimate():
    """XLA cost-model MFU ESTIMATE for the 886M on-chip train config —
    an estimate, not a measurement: lower the EXACT bench train step (bench_1b, bf16,
    batch×seq from the same env knobs) fully abstractly (eval_shape —
    no parameters materialize), compile, and read XLA's cost analysis
    (flops + bytes accessed) off the optimized module. An aggregate
    roofline against published v5e constants (197 bf16 TFLOP/s, 819
    GB/s HBM) then gives the cost-model step time
    max(F/peak, B/bw) and the MFU that implies.

    CLEARLY LABELED AN ESTIMATE: the module is CPU-optimized (fusion
    differs from TPU, so bytes-accessed is pessimistic) and a roofline
    assumes perfect compute/transfer overlap — this bounds what the
    hardware model allows; it is NOT a measurement and is never
    appended as a backend:"tpu" entry."""
    import jax
    import jax.numpy as jnp

    from metaflow_tpu.models import llama
    from metaflow_tpu.spmd import MeshSpec, create_mesh
    from metaflow_tpu.training import (make_train_step,
                                       memory_efficient_optimizer)

    from metaflow_tpu.training import default_optimizer

    cfg = llama.LlamaConfig.bench_1b(
        attention_impl="xla",  # the pallas kernel doesn't lower on CPU;
        # flash-attn FLOPs are identical, bytes differ (noted in caveats)
        remat_policy=os.environ.get("BENCH_REMAT_POLICY", "") or None,
        loss_chunk=int(os.environ.get("BENCH_LOSS_CHUNK", "256")),
    )
    batch = int(os.environ.get("BENCH_BATCH", "32"))
    seq = int(os.environ.get("BENCH_SEQ", "2048"))
    # same knob the measuring bench honors ('factored' is the on-chip
    # default) — the estimate must be for the EXACT swept config
    opt_kind = os.environ.get("BENCH_OPT", "factored")
    optimizer = (memory_efficient_optimizer(total_steps=1000)
                 if opt_kind == "factored"
                 else default_optimizer(total_steps=1000))
    mesh = create_mesh(MeshSpec.dp())

    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params_s = jax.eval_shape(lambda k: llama.init_params(k, cfg), key)
    opt_s = jax.eval_shape(optimizer.init, params_s)
    state_s = {"params": params_s, "opt_state": opt_s,
               "step": jax.ShapeDtypeStruct((), jnp.int32)}
    batch_s = {"tokens": jax.ShapeDtypeStruct((batch, seq + 1),
                                              jnp.int32)}
    step = make_train_step(cfg, mesh, llama, optimizer=optimizer)
    t0 = time.perf_counter()
    compiled = step.lower(state_s, batch_s).compile()
    compile_s = time.perf_counter() - t0
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    if "bytes accessed" not in cost:
        # a silently-missing bytes figure would zero the bandwidth term
        # and unconditionally report compute_bound — the exact actionable
        # verdict this mode exists to produce
        raise SystemExit(
            "XLA cost_analysis did not report 'bytes accessed' "
            "(keys: %s) — cannot form the roofline" % sorted(cost))
    bytes_accessed = float(cost["bytes accessed"])

    chip = os.environ.get("BENCH_TARGET_CHIP", "v5e")
    peak_table, hbm_table = _chip_tables()
    peak = next((tf for sub, tf in peak_table if sub in chip), None)
    hbm = next((bw for sub, bw in hbm_table if sub in chip), None)
    if peak is None or hbm is None:
        raise SystemExit("no roofline constants for BENCH_TARGET_CHIP=%r"
                         % chip)
    peak *= 1e12
    hbm_bw = hbm * 1e9
    tokens_per_step = batch * seq
    n_params = sum(int(s.size) for s in jax.tree.leaves(params_s))
    # the COMPUTE term uses the analytic PaLM-convention count (_mfu):
    # XLA:CPU rewrites large matmuls into oneDNN custom calls whose
    # flops the cost analysis does NOT count (observed 12x undercount),
    # so the HLO flops figure is reported but never used for the bound
    analytic_flops = (6.0 * n_params
                      + 12.0 * cfg.n_layers * cfg.dim * seq) \
        * tokens_per_step
    t_compute = analytic_flops / peak
    t_bytes = bytes_accessed / hbm_bw
    t_step = max(t_compute, t_bytes)
    tps_bound = tokens_per_step / t_step
    mfu_at_bound = t_compute / t_step

    return {
        "metric": "llama_1b_train_tokens_per_sec_roofline_bound",
        "value": round(tps_bound, 1),
        "unit": "tokens/s/chip (cost-model upper bound)",
        "vs_baseline": 1.0,
        "estimate": True,
        "extra": {
            "method": "analytic_flops + xla_cost_analysis_bytes, "
                      "aggregate roofline",
            "hardware_model": "%s: %.0f bf16 TFLOP/s, %.0f GB/s HBM"
            % (chip, peak / 1e12, hbm_bw / 1e9),
            "optimizer": opt_kind,
            "bound_kind": ("hbm_bandwidth_bound" if t_bytes > t_compute
                           else "compute_bound"),
            "mfu_at_bound": round(mfu_at_bound, 4),
            "analytic_flops_per_step": analytic_flops,
            "hlo_flops_per_step_unused": flops,
            "hlo_bytes_per_step": bytes_accessed,
            "roofline_step_seconds": round(t_step, 4),
            "batch": batch,
            "seq": seq,
            "n_params": n_params,
            "compile_seconds": round(compile_s, 1),
            "caveats": "ESTIMATE, not a measurement: CPU-optimized HLO "
                       "(TPU fusion differs; bytes approximate and "
                       "custom-call reads may be uncounted), xla "
                       "attention (flash kernel bytes would be lower), "
                       "perfect-overlap roofline. bound_kind is the "
                       "actionable output: compute_bound means the "
                       "measured-MFU gap is scheduling/fusion overhead, "
                       "not an HBM wall",
        },
    }


def _gmm_blocks():
    import importlib

    # metaflow_tpu.ops re-exports a `gmm` FUNCTION; fetch the module
    _g = importlib.import_module("metaflow_tpu.ops.gmm")
    return [_g.BLOCK_S, _g.BLOCK_F, _g.BLOCK_D]


def bench_moe():
    """Mixtral-style MoE train-step throughput (tokens/s/chip), dispatch
    selectable via BENCH_MOE_DISPATCH (sparse | gmm | gmm_ep | dense) —
    the on-chip comparison of the capacity-bucketed vs dropless paths.
    gmm_ep runs on an expert-axis mesh (size min(experts, devices); 1 on
    the single bench chip, where it measures the a2a+local-gmm machinery
    itself); BENCH_MOE_EP_FACTOR bounds its a2a buffers (default exact)."""
    import jax

    from metaflow_tpu.models import mixtral
    from metaflow_tpu.spmd import MeshSpec, create_mesh
    from metaflow_tpu.training import (make_trainer,
                                       memory_efficient_optimizer,
                                       shard_batch)

    on_tpu = jax.default_backend() == "tpu"
    dispatch = os.environ.get("BENCH_MOE_DISPATCH", "gmm")
    dropless = dispatch in ("gmm", "gmm_ep")
    ep_factor = os.environ.get("BENCH_MOE_EP_FACTOR")
    if on_tpu:
        cfg = mixtral.MixtralConfig(
            vocab_size=32_000, dim=1024, n_layers=8, n_heads=16,
            n_kv_heads=4, ffn_dim=2048, n_experts=8, experts_per_tok=2,
            dtype="bfloat16", moe_dispatch=dispatch,
            capacity_factor=None if dropless else 1.25,
            ep_buffer_factor=float(ep_factor) if ep_factor else None,
        )
        batch, seq, steps = 16, 1024, 8
    else:
        cfg = mixtral.MixtralConfig.tiny(
            moe_dispatch=dispatch,
            capacity_factor=None if dropless else 1.25,
            ep_buffer_factor=float(ep_factor) if ep_factor else None,
        )
        batch, seq, steps = 4, 128, 2

    if dispatch == "gmm_ep":
        ep = min(cfg.n_experts, len(jax.devices()))
        if ep > 1:
            mesh = create_mesh(MeshSpec.moe(expert=ep))
        else:
            # single chip: MeshSpec canonicalization drops size-1 axes,
            # but gmm_ep needs the 'expert' axis to exist — build the
            # degenerate mesh directly (a2a become no-ops; the bench
            # measures the dispatch machinery + local gmm)
            import numpy as _np
            from jax.sharding import Mesh

            mesh = Mesh(_np.asarray(jax.devices()[:1]), ("expert",))
    else:
        mesh = create_mesh(MeshSpec.dp() if len(jax.devices()) == 1
                           else MeshSpec.fsdp())
    state, step, _ = make_trainer(
        jax.random.PRNGKey(0), cfg, mesh, mixtral,
        optimizer=memory_efficient_optimizer(total_steps=1000),
    )
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size)
    data = shard_batch({"tokens": tokens}, mesh)
    with mesh:
        state, m = step(state, data)
        jax.block_until_ready(m["loss"])
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step(state, data)
        jax.block_until_ready(m["loss"])
        dt = time.perf_counter() - t0
    n_devices = len(jax.devices())
    tps = batch * seq * steps / dt / n_devices
    return {
        "metric": "mixtral_%s_moe_%s_train_tokens_per_sec_per_chip"
        % ("8x1b" if on_tpu else "tiny_cpu", dispatch),
        "value": round(tps, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": _vs_baseline(tps),
        "extra": {
            "backend": jax.default_backend(),
            "n_devices": n_devices,
            "dispatch": dispatch,
            # MXU tile sizes (env-swept on-chip via TPUFLOW_GMM_BLOCK_*)
            "gmm_blocks": _gmm_blocks() if dispatch.startswith("gmm")
            else None,
            "params": mixtral.num_params(state["params"]),
            "batch": batch,
            "seq": seq,
            "loss": float(m["loss"]),
        },
    }


def _serve_trace(rng, n_requests, prompt_range, short_new, long_new,
                 long_every=4):
    """A mixed-length request trace with a heavy output-length tail —
    the traffic shape continuous batching exists for: most requests want
    a few tokens, every `long_every`-th wants many, and lockstep pads
    EVERY sequence of a batch to the longest member on both axes."""
    trace = []
    for i in range(n_requests):
        p = int(rng.integers(*prompt_range))
        n = int(rng.integers(*long_new)) if i % long_every == 0 \
            else int(rng.integers(*short_new))
        trace.append((rng.integers(0, 1 << 30, p), n))
    return trace


def bench_serve():
    """Continuous-batching vs lockstep serving throughput on a
    mixed-length request trace. The headline is the ENGINE's useful
    tokens/sec; extra carries the lockstep rate off the SAME trace and
    the speedup (acceptance floor: >= 1.5x), plus per-token latency
    p50/p99 and mean batch occupancy as submetrics.

    Lockstep baseline: the strongest single-compiled-program batch
    server the repo had — make_generator (prompt-bucket padding, so it
    does NOT pay global-max prompt padding) over arrival-order groups of
    `slots` requests, max_new fixed at the trace max (a compiled
    program's static knob). Both paths run greedy and fully warmed; the
    engine's wins come from per-slot admission/eviction, not compile
    asymmetry."""
    import jax
    import numpy as np

    from metaflow_tpu.inference import make_generator
    from metaflow_tpu.models import llama
    from metaflow_tpu.serving import Request, Scheduler, SlotEngine

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = llama.LlamaConfig.bench_1b(attention_impl="xla", remat=False)
        slots = int(os.environ.get("BENCH_SERVE_SLOTS", "16"))
        n_requests, prompt_range = 64, (16, 192)
        short_new, long_new = (8, 32), (128, 256)
        max_seq_len = 512
    else:
        # bigger than tiny: at tiny scale every path is DISPATCH-bound
        # on CPU and the comparison measures python overhead, not
        # batching policy; at dim 256 x 4 layers a decode step is
        # compute-dominated (the regime serving actually runs in)
        cfg = llama.LlamaConfig.tiny(
            vocab_size=1024, dim=256, n_layers=4, n_heads=8,
            n_kv_heads=4, ffn_dim=512)
        slots = int(os.environ.get("BENCH_SERVE_SLOTS", "8"))
        n_requests, prompt_range = 48, (4, 48)
        short_new, long_new = (4, 12), (40, 48)
        max_seq_len = 128
    rng = np.random.default_rng(0)
    trace = [(np.asarray(p) % cfg.vocab_size, n)
             for p, n in _serve_trace(rng, n_requests, prompt_range,
                                      short_new, long_new)]
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    max_new = max(n for _p, n in trace)
    useful_tokens = sum(n for _p, n in trace)

    # ---- lockstep: arrival-order groups, one generate per group ----
    gen = make_generator(cfg, max_new_tokens=max_new,
                         max_seq_len=max_seq_len)

    def lockstep_pass():
        t0 = time.perf_counter()
        for g in range(0, len(trace), slots):
            group = trace[g:g + slots]
            pmax = max(len(p) for p, _n in group)
            batch = np.zeros((len(group), pmax), np.int32)
            for i, (p, _n) in enumerate(group):
                batch[i, :len(p)] = p  # lockstep pads to the group max
            out = gen(params, batch, jax.random.PRNGKey(g))
            jax.block_until_ready(out)
        return time.perf_counter() - t0

    # ---- continuous batching: same trace through the slot engine ----
    # ONE engine: its three jitted programs compile once and serve every
    # pass (slots drain back to free between passes)
    engine = SlotEngine(params, cfg, max_slots=slots,
                        max_seq_len=max_seq_len, prefill_chunk=32)

    def engine_pass():
        sched = Scheduler(engine, max_queue=n_requests + 1)
        reqs = [Request(p.tolist(), max_new_tokens=n, rng=i)
                for i, (p, n) in enumerate(trace)]
        t0 = time.perf_counter()
        for r in reqs:
            sched.submit(r)
        sched.run_until_idle(max_iterations=100_000)
        return time.perf_counter() - t0, reqs, sched

    # Both sides warm, then INTERLEAVED reps with the MEDIAN per side:
    # alternating passes expose both paths to the same slice of host
    # drift (thermal, page cache, background load), and the median is
    # robust to a one-off slow rep in either direction — min-of-N would
    # reward whichever side got the single luckiest pass. The 1.5x gate
    # assumes this methodology.
    reps = max(3, int(os.environ.get("BENCH_SERVE_REPS", "3")))
    lockstep_pass()  # warm every group's prompt bucket
    engine_pass()    # warm the three compiled programs
    lockstep_dts, engine_runs = _interleaved_reps(lockstep_pass,
                                                  engine_pass, reps)
    lockstep_dt = _median_run(lockstep_dts)
    lockstep_tps = useful_tokens / lockstep_dt
    serve_dt, reqs, sched = _median_run(engine_runs,
                                        key=lambda r: r[0])
    for dt_i, reqs_i, _s in engine_runs:
        gen_i = sum(len(r.generated) for r in reqs_i)
        assert gen_i == useful_tokens, (gen_i, useful_tokens)
    generated = sum(len(r.generated) for r in reqs)
    serve_tps = generated / serve_dt

    ttft = [(r.t_first - r.t_submit) * 1000 for r in reqs]
    gaps = []
    for r in reqs:
        gaps.extend((b - a) * 1000 for a, b in zip(r.token_times,
                                                   r.token_times[1:]))
    gaps.sort()
    p50 = gaps[len(gaps) // 2] if gaps else 0.0
    p99 = gaps[min(len(gaps) - 1, int(len(gaps) * 0.99))] if gaps else 0.0
    occupancy = sched.stats()["mean_batch_occupancy"]

    # ---- request-tracing overhead: same trace through the SAME engine,
    # a live flight recorder on BOTH sides so the delta isolates what
    # TPUFLOW_TRACE_REQUESTS=0 turns off (traceparent derivation + per-
    # event trace/span stamping), not telemetry I/O itself. Interleaved
    # pairs so host drift cancels; MEDIAN-of-3 each side (min-of-N lets
    # one lucky traced pass mask real overhead, or one lucky plain pass
    # inflate it — the <=2% gate flaked on exactly that). ----
    import tempfile

    from metaflow_tpu import telemetry, tracing
    from metaflow_tpu.cmd.trace import (
        build_request_traces,
        ttft_decomposition,
    )
    from metaflow_tpu.datastore import FlowDataStore, LocalStorage

    def timed_pass(traced):
        sched = Scheduler(engine, max_queue=n_requests + 1)
        reqs = [Request(p.tolist(), max_new_tokens=n, rng=i)
                for i, (p, n) in enumerate(trace)]
        if traced:
            for r in reqs:
                r.traceparent = tracing.request_traceparent(r.id)
        t0 = time.perf_counter()
        for r in reqs:
            sched.submit(r)
        sched.run_until_idle(max_iterations=100_000)
        return time.perf_counter() - t0

    with tempfile.TemporaryDirectory() as troot:
        fds = FlowDataStore("ServeBench", LocalStorage, ds_root=troot)
        telemetry.init_recorder(fds, "bench", "_serve", "bench")
        try:
            plain_dts, traced_dts = _interleaved_reps(
                lambda: timed_pass(False), lambda: timed_pass(True),
                reps)
        finally:
            telemetry.close_recorder()
        records = telemetry.read_run_records(fds, "bench")
    plain_dt = _median_run(plain_dts)
    traced_dt = _median_run(traced_dts)
    tracing_overhead_pct = max(
        0.0, (traced_dt - plain_dt) / plain_dt * 100) if plain_dt else 0.0

    # TTFT decomposition consistency off the traced passes' own records:
    # the components are independent measurements, so median |err| is a
    # real check that the trace tree reconstructs the request path
    errs = sorted(abs(d["err_pct"]) for d in
                  (ttft_decomposition(t)
                   for t in build_request_traces(records))
                  if d is not None and d["measured_ttft_ms"] > 0)
    decomp_err_pct = errs[len(errs) // 2] if errs else 0.0

    # ---- radix prefix cache: shared-system-prompt trace through the
    # SAME engine with a fresh Scheduler + cache. One cold request seeds
    # the system prefix; every later request shares it and differs only
    # in a short user tail, so its prefill should start at the match
    # boundary. The FLOPs proxy is hit_tokens/prompt_tokens over the
    # POST-seed requests (counter deltas exclude the unavoidable cold
    # miss). Acceptance floor: >= 0.9. ----
    from metaflow_tpu.serving import RadixPrefixCache

    sys_prefix = rng.integers(1, cfg.vocab_size, 72).tolist()
    cache = RadixPrefixCache(64 << 20)
    psched = Scheduler(engine, max_queue=n_requests + 1,
                       prefix_cache=cache)
    seed_req = Request(sys_prefix + [7, 8, 9, 10], max_new_tokens=4)
    psched.submit(seed_req)
    psched.run_until_idle(max_iterations=100_000)
    hit0, prompt0 = psched.prefix_hit_tokens, psched.prefix_prompt_tokens
    warm_reqs = [Request(sys_prefix
                         + rng.integers(1, cfg.vocab_size, 4).tolist(),
                         max_new_tokens=4, rng=i)
                 for i in range(16)]
    for r in warm_reqs:
        psched.submit(r)
    psched.run_until_idle(max_iterations=100_000)
    prefix_skipped_frac = (
        (psched.prefix_hit_tokens - hit0)
        / max(1, psched.prefix_prompt_tokens - prompt0))

    # ---- rolling upgrade under load: a 2-replica in-process fleet
    # serves a trace WHILE rolling_reload surges/drains each replica;
    # acceptance: zero requests shed (the rollout never sheds — it
    # spawns the replacement before draining the old). ----
    rollout_shed = _bench_rollout_shed(cfg, params)

    # ---- paged KV: in-flight concurrency at EQUAL HBM. The paged pool
    # holds exactly the slot engine's KV bytes (slots x max_seq_len
    # tokens), but requests reserve only the pages they need, so short
    # requests pack past the slot count. Acceptance floor: >= 1.5x. ----
    inflight_ratio = _bench_paged_inflight(cfg, params, slots,
                                           max_seq_len)

    # ---- speculative decoding: greedy tok/s with a k-token draft +
    # one fused verify step vs plain one-token greedy on the SAME paged
    # engine. Replay drafts (the plain pass's own outputs) pin the
    # high-acceptance regime — random-weight outputs have no n-gram
    # structure for the default prompt-lookup drafter to exploit, so
    # self-drafting here would measure draft quality, not the verify
    # machinery. Token identity is asserted, so the speedup is free.
    # Acceptance floor: >= 1.5x. ----
    spec_ratio, spec_accept = _bench_spec_decode(cfg, params)

    return {
        "metric": "serve_tokens_per_s",
        "value": round(serve_tps, 1),
        "unit": "useful generated tokens/s (continuous batching; "
                "median of %d interleaved reps vs lockstep)" % reps,
        "vs_baseline": _vs_baseline(serve_tps),
        "extra": {
            "backend": jax.default_backend(),
            "n_devices": len(jax.devices()),
            "slots": slots,
            "requests": n_requests,
            "useful_tokens": useful_tokens,
            "lockstep_tokens_per_s": round(lockstep_tps, 1),
            "speedup_vs_lockstep": round(serve_tps / lockstep_tps, 2),
            "ttft_p50_ms": round(sorted(ttft)[len(ttft) // 2], 1),
            "decode_steps": sched.stats()["decode_steps"],
            "params": llama.num_params(params),
        },
        "submetrics": [
            {"metric": "serve_p50_ms", "value": round(p50, 2),
             "unit": "ms/token (inter-token latency p50)"},
            {"metric": "serve_p99_ms", "value": round(p99, 2),
             "unit": "ms/token (inter-token latency p99)"},
            {"metric": "serve_batch_occupancy",
             "value": round(occupancy, 4),
             "unit": "mean fraction of slots active per decode step"},
            {"metric": "serve_tracing_overhead_pct",
             "value": round(tracing_overhead_pct, 2),
             "unit": "%% tok/s cost of request tracing vs "
                     "TPUFLOW_TRACE_REQUESTS=0 (median of %d "
                     "interleaved reps; gate: <= 2.0)" % reps},
            {"metric": "serve_ttft_decomp_err_pct",
             "value": round(decomp_err_pct, 2),
             "unit": "median |TTFT decomposition sum - measured| % "
                     "(gate: <= 5.0)"},
            {"metric": "prefix_prefill_flops_skipped_frac",
             "value": round(prefix_skipped_frac, 4),
             "unit": "fraction of post-seed prompt tokens whose "
                     "prefill the radix cache skipped (gate: >= 0.9)"},
            {"metric": "rollout_shed_requests",
             "value": rollout_shed,
             "unit": "requests shed during a rolling upgrade under "
                     "load (gate: == 0)"},
            {"metric": "paged_max_inflight_ratio",
             "value": round(inflight_ratio, 2),
             "unit": "paged peak in-flight / slot-engine slots at "
                     "equal KV HBM (gate: >= 1.5)"},
            {"metric": "spec_accept_rate",
             "value": round(spec_accept, 4),
             "unit": "draft tokens accepted / proposed (replay "
                     "drafts; gate: >= 0.8)"},
            {"metric": "spec_greedy_tokens_per_s_ratio",
             "value": round(spec_ratio, 2),
             "unit": "greedy tok/s with spec decode vs plain greedy, "
                     "same engine, token-identical (gate: >= 1.5)"},
        ],
    }


def _bench_paged_inflight(cfg, params, slots, max_seq_len):
    """Max in-flight at equal HBM: a paged pool sized to EXACTLY the
    slot engine's KV footprint (slots x max_seq_len tokens) serving a
    burst of short requests. The slot engine's in-flight ceiling is
    `slots` by construction (each slot reserves a full max_seq_len
    row); the paged engine reserves ceil(need/page) pages per request,
    so its scheduler packs more lanes into the same bytes. Returns
    peak_in_flight / slots (gate: >= 1.5)."""
    import numpy as np

    from metaflow_tpu.serving import PagedEngine, Request, Scheduler

    ptok = 16
    engine = PagedEngine(
        params, cfg, max_slots=2 * slots, max_seq_len=max_seq_len,
        prefill_chunk=32, page_tokens=ptok, spec_k=0,
        total_pages=slots * (max_seq_len // ptok) + 1)
    assert engine.pool.usable_pages * ptok == slots * max_seq_len
    rng = np.random.default_rng(5)
    sched = Scheduler(engine, max_queue=4 * slots + 1)
    reqs = [Request(rng.integers(1, cfg.vocab_size, ptok).tolist(),
                    max_new_tokens=8, rng=i)
            for i in range(4 * slots)]
    for r in reqs:
        sched.submit(r)
    sched.run_until_idle(max_iterations=100_000)
    assert all(len(r.generated) == 8 for r in reqs)
    assert engine.pool.free_pages() == engine.pool.usable_pages, \
        "paged bench leaked pages"
    return sched.peak_in_flight / slots


def _bench_spec_decode(cfg, params):
    """Speculative-decode speedup on a decode-heavy greedy trace: the
    plain pass records every request's exact greedy output, then the
    spec pass re-serves the SAME trace drafting from those recordings
    (k=4) and verifying in one fused step. Outputs are asserted
    token-identical, so the ratio is pure serving speed. Timing is
    interleaved median-of-reps like the other serving gates — this was
    the last min-of-2 measurement left and it flaked the same way the
    round-13 gates did. Returns (tok/s ratio, accept rate)."""
    import numpy as np

    from metaflow_tpu.serving import PagedEngine, Request, Scheduler
    from metaflow_tpu.serving.paged import ngram_draft

    rng = np.random.default_rng(3)
    trace = [(rng.integers(1, cfg.vocab_size,
                           int(rng.integers(4, 32))).tolist(),
              int(rng.integers(32, 48))) for _ in range(24)]
    refs = []

    def replay_draft(context, k):
        for r in refs:
            n = len(context)
            if len(r) > n and r[:n] == context:
                out = r[n:n + k]
                return out + [0] * (k - len(out))
        return ngram_draft(context, k)

    spec_k = 4
    engine = PagedEngine(params, cfg, max_slots=8, max_seq_len=128,
                         prefill_chunk=32, page_tokens=16,
                         spec_k=spec_k, draft_fn=replay_draft)

    def serve_pass(spec):
        engine.spec_k = spec_k if spec else 0
        sched = Scheduler(engine, max_queue=len(trace) + 1)
        reqs = [Request(list(p), max_new_tokens=n, rng=i)
                for i, (p, n) in enumerate(trace)]
        t0 = time.perf_counter()
        for r in reqs:
            sched.submit(r)
        sched.run_until_idle(max_iterations=100_000)
        return time.perf_counter() - t0, reqs

    serve_pass(False)
    serve_pass(True)  # warm both program sets (plain + spec verify)
    # a recording pass (untimed) populates the replay draft source so
    # every TIMED spec pass drafts from the true greedy outputs
    _dt, plain_reqs = serve_pass(False)
    refs[:] = [list(p) + list(r.generated)
               for (p, _n), r in zip(trace, plain_reqs)]
    engine.spec_proposed = engine.spec_accepted = engine.spec_steps = 0
    reps = max(3, int(os.environ.get("BENCH_SERVE_REPS", "3")))
    plain_runs, spec_runs = _interleaved_reps(
        lambda: serve_pass(False), lambda: serve_pass(True), reps)
    plain_dt, _reqs = _median_run(plain_runs, key=lambda r: r[0])
    spec_dt, _reqs = _median_run(spec_runs, key=lambda r: r[0])
    # EVERY rep must match the recorded greedy outputs, not just the
    # median one — a divergent-but-fast pass must fail, not hide
    for _dt_i, reqs_i in plain_runs + spec_runs:
        for r0, r1 in zip(plain_reqs, reqs_i):
            assert r0.generated == r1.generated, \
                "spec decode diverged from plain greedy"
    return plain_dt / spec_dt, engine.spec_stats()["accept_rate"]


def _inproc_fleet(params, cfg, replicas=2):
    """An in-process ServingFleet: each 'replica' is a SlotEngine behind
    a real ServingServer on loopback, wrapped in a Popen-shaped shim so
    the fleet supervisor drives the REAL health/failover/reload paths
    without subprocess spawn cost. Shared by the rolling-upgrade shed
    gate and the online weight-push gate."""
    import threading

    from metaflow_tpu.elastic.policy import BackoffPolicy
    from metaflow_tpu.serving import (
        FleetConfig,
        Scheduler,
        ServingFleet,
        ServingServer,
        SlotEngine,
    )

    class _Proc(object):
        def __init__(self, server):
            self.server, self.pid, self._rc = server, os.getpid(), None

        def poll(self):
            return self._rc

        def kill(self):
            if self._rc is None:
                self._rc = -9
                self.server.close()

        terminate = kill

        def wait(self, timeout=None):
            return self._rc

    build_lock = threading.Lock()

    def spawner(index, generation):
        with build_lock:
            eng = SlotEngine(params, cfg, max_slots=4, max_seq_len=128,
                             prefill_chunk=32)
            srv = ServingServer(Scheduler(eng), port=0).start()
        return _Proc(srv), "127.0.0.1", srv.port

    config = FleetConfig(
        failover=True, restart=False, health_interval_s=0.2, wait_s=5.0,
        redispatch_max=3, spawn_timeout_s=120.0,
        backoff=BackoffPolicy(base_s=0.05, cap_s=0.1, jitter=0.0,
                              seed=0))
    fleet = ServingFleet(spawner, replicas, config=config)
    fleet.start()
    return fleet


def _bench_rollout_shed(cfg, params):
    """Zero-shed rolling upgrade: an in-process 2-replica fleet serves a
    mixed trace concurrently with rolling_reload; returns the fleet's
    shed counter delta (gate: 0)."""
    import http.client
    import json as json_mod
    import threading

    import numpy as np

    fleet = _inproc_fleet(params, cfg)
    try:
        rng = np.random.default_rng(7)
        trace = [rng.integers(1, cfg.vocab_size, 12).tolist()
                 for _ in range(16)]
        errors = []

        def fire(tokens, i):
            try:
                conn = http.client.HTTPConnection(
                    "127.0.0.1", fleet.port, timeout=120)
                conn.request(
                    "POST", "/v1/generate",
                    json_mod.dumps({"tokens": tokens,
                                    "max_new_tokens": 4,
                                    "request_id": "ro-%d" % i}),
                    {"Content-Type": "application/json"})
                resp = conn.getresponse()
                body = resp.read()
                conn.close()
                if resp.status != 200:
                    errors.append((resp.status, body[:128]))
            except Exception as ex:  # noqa: BLE001 — counted as shed
                errors.append(repr(ex))

        threads = [threading.Thread(target=fire, args=(t, i))
                   for i, t in enumerate(trace)]
        shed0 = fleet.shed_count
        for t in threads[:8]:
            t.start()
        rollout = fleet.rolling_reload()
        for t in threads[8:]:
            t.start()
        for t in threads:
            t.join()
        assert rollout["replaced"] == 2, rollout
        assert not errors, errors[:3]
        # shed over the whole window (the rollout's own delta is a
        # subset of it)
        return int(fleet.shed_count - shed0)
    finally:
        fleet.close()


def _bench_online_push_shed(cfg, params):
    """The online loop's weight-push path under load: an in-process
    2-replica fleet decodes an ActorPool rollout batch WHILE
    make_fleet_push rolls it onto the next generation. Returns the
    fleet's shed delta (gate: 0 — a push must never cost rollouts)
    after asserting every rollout completed and the pool observed the
    bumped generation."""
    import threading

    import numpy as np

    from metaflow_tpu.online import ActorPool, make_fleet_push

    fleet = _inproc_fleet(params, cfg)
    try:
        rng = np.random.default_rng(11)
        prompts = [rng.integers(1, cfg.vocab_size, 8).tolist()
                   for _ in range(12)]
        actor = ActorPool(fleet=fleet, max_new_tokens=4,
                          request_timeout_s=120.0, http_workers=4)
        push = make_fleet_push(fleet)
        holder = {}

        def roll():
            try:
                holder["rollouts"] = actor.rollout_batch(prompts,
                                                         round_index=0)
            except Exception as exc:  # rejoined below
                holder["error"] = exc

        shed0 = fleet.shed_count
        thread = threading.Thread(target=roll)
        thread.start()
        info = push(None, 0)
        thread.join()
        if "error" in holder:
            raise holder["error"]
        rollouts = holder["rollouts"]
        assert len(rollouts) == len(prompts), len(rollouts)
        assert all(len(r.completion) == 4 for r in rollouts), \
            "rollout lost tokens across the reload"
        assert actor.generation == 1, actor.generation
        assert info["shed_requests"] == 0, info
        return int(fleet.shed_count - shed0)
    finally:
        fleet.close()


def bench_online():
    """BENCH_MODE=online: loop goodput of the Podracer online loop —
    learner tokens/s with the actor collecting CONCURRENTLY vs the
    serial generate-then-train baseline, same model/rounds/steps
    (gate: >= 1.3x).

    CPU by design, and on a 1-core box compute cannot overlap compute —
    so the actor is PACED: every rollout batch is padded to a
    wall-clock floor with a GIL-releasing sleep, emulating the
    round-trip latency of a REMOTE serving fleet (whose decode burns no
    learner-host cycles). The gate therefore measures the loop's
    overlap MACHINERY — prefetch thread, generation handoff, replay
    append/read, idempotent publish — not host parallelism the box
    doesn't have. The floor is calibrated to one measured UNPACED
    serial round (decode + train + replay overhead) — the wall a real
    remote round-trip must cover for the learner to hide it — so the
    ceiling is ~2x and anything under
    1.3x means the loop serialized somewhere. Interleaved median-of-
    reps like every other serving gate.

    Submetric: online_push_shed_requests — the fleet-backed weight push
    (rolling_reload through make_fleet_push) under a live rollout
    batch; gate == 0."""
    import math
    import tempfile

    import jax
    import numpy as np

    from metaflow_tpu.datastore import FlowDataStore, LocalStorage
    from metaflow_tpu.models import llama
    from metaflow_tpu.online import (
        ActorPool,
        OnlineLoop,
        PromptSampler,
        ReplayReader,
        ReplayWriter,
    )
    from metaflow_tpu.serving import Scheduler, SlotEngine
    from metaflow_tpu.spmd import MeshSpec, create_mesh
    from metaflow_tpu.training import (
        default_optimizer,
        make_trainer,
        shard_batch,
    )

    rounds = int(os.environ.get("BENCH_ONLINE_ROUNDS", "6"))
    reps = max(3, int(os.environ.get("BENCH_ONLINE_REPS", "3")))
    rollouts, batch_size = 8, 8
    prompt_len, max_new = 8, 8
    seq_len = 16  # window = 17 tokens; 8 rollouts/round -> 8 windows
    cfg = llama.LlamaConfig.tiny(vocab_size=256)
    mesh = create_mesh(MeshSpec.dp())

    def snapshot(st):
        # the jitted step donates its state: the actor serves COPIES
        return jax.tree_util.tree_map(np.asarray,
                                      jax.device_get(st["params"]))

    # ONE trainer and ONE engine serve every rep: a fresh make_trainer/
    # SlotEngine per run would recompile all jitted programs and the
    # rep would time XLA compilation, not the loop
    state0, step_fn, _sh = make_trainer(
        jax.random.PRNGKey(0), cfg, mesh, llama,
        optimizer=default_optimizer(lr=1e-2, warmup_steps=1,
                                    total_steps=1000))
    state_np = jax.tree_util.tree_map(np.asarray,
                                      jax.device_get(state0))
    params0 = state_np["params"]

    def fresh_state():
        # re-materialize device buffers (each run's steps donate them)
        return jax.tree_util.tree_map(jax.device_put, state_np)

    def learner_step(st, tokens):
        batch = shard_batch({"tokens": tokens}, mesh)
        with mesh:
            st, metrics = step_fn(st, batch)
        return st, float(metrics["loss"])

    class _PacedActor(ActorPool):
        floor_s = 0.0

        def rollout_batch(self, prompts, round_index=0):
            t0 = time.perf_counter()
            out = super(_PacedActor, self).rollout_batch(
                prompts, round_index=round_index)
            left = self.floor_s - (time.perf_counter() - t0)
            if left > 0:
                time.sleep(left)  # the emulated remote round-trip
            return out

    engine = SlotEngine(dict(params0), cfg, max_slots=rollouts,
                        max_seq_len=prompt_len + max_new + 8)
    scheduler = Scheduler(engine)
    sampler = PromptSampler(cfg.vocab_size, prompt_len, seed=0)

    # ---- calibrate: warm-measure one train step and one (unpaced)
    # rollout batch so the round shape tracks THIS host's speeds ----
    tokens = np.ones((batch_size, seq_len + 1), np.int32)
    step_dts, decode_dts = [], []
    state = fresh_state()
    for _ in range(2):  # compile + settle (first warm step still pays
        state, _ = learner_step(state, tokens)  # one-time XLA costs)
    for _ in range(5):
        t0 = time.perf_counter()
        state, _ = learner_step(state, tokens)
        step_dts.append(time.perf_counter() - t0)
    step_s = _median_run(step_dts)
    cal_actor = _PacedActor(scheduler=scheduler, max_new_tokens=max_new)
    cal_actor.rollout_batch(sampler.batch(0, rollouts))  # compile
    for _ in range(3):
        t0 = time.perf_counter()
        cal_actor.rollout_batch(sampler.batch(0, rollouts))
        decode_dts.append(time.perf_counter() - t0)
    decode_s = _median_run(decode_dts)
    # a round's learner work: long enough that sleep dominates host
    # jitter AND decode's non-overlappable compute stays well under it
    steps_per_round = max(2, int(math.ceil(2.0 * decode_s / step_s)),
                          int(math.ceil(0.3 / step_s)))

    run_counter = [0]

    def run_loop(concurrent, troot, floor_s):
        run_counter[0] += 1
        tag = "replay-%d" % run_counter[0]
        fds = FlowDataStore("OnlineBench", LocalStorage, ds_root=troot)
        engine.params = dict(params0)  # every rep starts identical
        actor = _PacedActor(scheduler=scheduler,
                            max_new_tokens=max_new)
        actor.floor_s = floor_s
        writer = ReplayWriter(fds, tag, seq_len,
                              windows_per_shard=batch_size)
        reader = ReplayReader(fds, tag, batch_size, seq_len, seed=0)
        loop = OnlineLoop(actor, writer, reader, sampler, learner_step,
                          fresh_state(), snapshot, rounds=rounds,
                          rollouts=rollouts,
                          steps_per_round=steps_per_round,
                          push_every=1, max_lag=2,
                          concurrent=concurrent)
        t0 = time.perf_counter()
        summary = loop.run()
        dt = time.perf_counter() - t0
        assert summary["dropped_stale"] == 0, summary
        assert summary["shed_requests"] == 0, summary
        assert summary["generation"] == rounds, summary
        return summary["steps"] * batch_size * seq_len / dt, dt

    with tempfile.TemporaryDirectory() as troot:
        # the warm pass (floor 0) doubles as the floor calibration: one
        # UNPACED serial round = decode + train + replay epoch overhead,
        # which is exactly the wall a remote fleet's rollout round-trip
        # must cover for the learner to hide it — so pace to that
        _tps, warm_dt = run_loop(False, troot, 0.0)
        floor_s = warm_dt / rounds
        serial_runs, overlap_runs = _interleaved_reps(
            lambda: run_loop(False, troot, floor_s),
            lambda: run_loop(True, troot, floor_s), reps)
    serial_tps = _median_run(serial_runs, key=lambda r: r[0])[0]
    overlap_tps = _median_run(overlap_runs, key=lambda r: r[0])[0]
    ratio = overlap_tps / serial_tps

    params = snapshot(state)
    return {
        "metric": "online_loop_goodput_x",
        "value": round(ratio, 2),
        "unit": "learner tokens/s, concurrent actor vs serial baseline "
                "(paced actor emulates remote fleet latency; median of "
                "%d interleaved reps; gate: >= 1.3)" % reps,
        "vs_baseline": _vs_baseline(ratio),
        "extra": {
            "backend": jax.default_backend(),
            "rounds": rounds,
            "rollouts_per_round": rollouts,
            "steps_per_round": steps_per_round,
            "batch": batch_size,
            "seq_len": seq_len,
            "pace_floor_ms": round(floor_s * 1000, 1),
            "train_step_ms": round(step_s * 1000, 1),
            "decode_batch_ms": round(decode_s * 1000, 1),
            "serial_tokens_per_s": round(serial_tps, 1),
            "concurrent_tokens_per_s": round(overlap_tps, 1),
        },
        "submetrics": [
            _submetric(lambda: {
                "metric": "online_push_shed_requests",
                "value": _bench_online_push_shed(cfg, params),
                "unit": "rollouts shed by a weight push under load "
                        "(rolling_reload via make_fleet_push; "
                        "gate: == 0)"}),
        ],
    }


def bench_step_launch():
    """p50 latency from scheduler queue → task attempt marker (the reference
    instruments this via metaflow_profile from_start markers).

    BENCH_DAEMON=1 measures launches through the persistent scheduler
    daemon (metaflow_tpu/daemon.py): runs fork from a warm interpreter
    instead of paying the cold start."""
    import contextlib
    import subprocess
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__))
    flow = os.path.join(here, "tests", "flows", "linear_flow.py")
    use_daemon = os.environ.get("BENCH_DAEMON") == "1"
    latencies = []
    with tempfile.TemporaryDirectory() as root, contextlib.ExitStack() as st:
        env = dict(os.environ)
        env["TPUFLOW_DATASTORE_SYSROOT_LOCAL"] = root
        env["PYTHONPATH"] = here
        if use_daemon:
            env["TPUFLOW_DAEMON_SOCKET"] = os.path.join(root, "d.sock")
            daemon = subprocess.Popen(
                [sys.executable, "-m", "metaflow_tpu.daemon", "start"],
                env=env, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL,
            )
            st.callback(daemon.terminate)
            deadline = time.time() + 30
            while not os.path.exists(env["TPUFLOW_DAEMON_SOCKET"]):
                if time.time() > deadline:
                    raise SystemExit("daemon never came up")
                time.sleep(0.1)
            # prefer the NATIVE thin client (no client interpreter boot);
            # BENCH_NATIVE=0 forces the pure-Python client
            native = None
            if os.environ.get("BENCH_NATIVE", "1") == "1":
                from metaflow_tpu.native import build_launch_client

                native = build_launch_client(
                    out=os.path.join(root, "tpuflow-launch"))
            if native:
                cmd = [native, flow, "run"]
            else:
                cmd = [sys.executable, "-m", "metaflow_tpu.daemon", "run",
                       flow, "run"]
        else:
            native = None
            cmd = [sys.executable, flow, "run"]
        for _ in range(5):
            t0 = time.perf_counter()
            subprocess.run(cmd, env=env, capture_output=True, check=True)
            # 3 tasks per run → per-task latency
            latencies.append((time.perf_counter() - t0) / 3)
    p50 = statistics.median(latencies)
    suffix = ""
    if use_daemon:
        suffix = "_daemon_native" if native else "_daemon"
    return {
        "metric": "step_launch_p50%s" % suffix,
        "value": round(p50 * 1000, 1),
        "unit": "ms",
        "vs_baseline": 1.0,
    }


def bench_data_path():
    """gsop engine throughput vs a loopback fake GCS server: measures the
    client machinery's ceiling (HTTP framing, threading, pwrite fan-in) —
    the real-NIC number is this capped by wire bandwidth. The reference
    ships the harness without stored numbers; we store ours."""
    import contextlib
    import tempfile

    from metaflow_tpu.gsop import GSClient

    # the fake server gets its OWN processes: a pre-forked SO_REUSEPORT
    # cluster (state shared via tmpfs) so the measured ceiling is the
    # gsop ENGINE, not one server process's GIL (round-2 verdict weak #5)
    server, endpoint, server_workers = _fake_gcs_server()

    n_objects, obj_mb = 8, 32
    blob = os.urandom(obj_mb << 20)
    # tmpfs destinations: measure the engine, not this box's disk (the
    # on-disk number is disk-bound at ~180 MB/s here)
    tmp_root = "/dev/shm" if os.path.isdir("/dev/shm") else None
    with contextlib.ExitStack() as stack:
        stack.callback(server.terminate)
        tmp = stack.enter_context(tempfile.TemporaryDirectory(dir=tmp_root))
        client = GSClient(endpoint=endpoint)

        srcs = []
        for i in range(n_objects):
            path = os.path.join(tmp, "src-%d" % i)
            with open(path, "wb") as f:
                f.write(blob)
            srcs.append(("obj-%d" % i, path))
        t0 = time.perf_counter()
        client.put_many("bench", srcs)
        put_dt = time.perf_counter() - t0

        pairs = [("obj-%d" % i, os.path.join(tmp, "dst-%d" % i))
                 for i in range(n_objects)]
        total_mb = n_objects * obj_mb
        client.get_many("bench", pairs)  # warmup: allocator + page cache
        rates = []
        for _ in range(3):  # median: shared-box noise
            t0 = time.perf_counter()
            client.get_many("bench", pairs)
            rates.append(total_mb / (time.perf_counter() - t0))
        get_mbps = statistics.median(rates)
        return {
            "metric": "gsop_get_many_throughput",
            "value": round(get_mbps, 1),
            "unit": "MB/s",
            "vs_baseline": _vs_baseline(get_mbps),
            "extra": {
                "put_mb_per_s": round(total_mb / put_dt, 1),
                "objects": n_objects,
                "object_mb": obj_mb,
                "transport": "loopback_fake_gcs_cluster",
                "server_workers": server_workers,
            },
        }


def _fake_gcs_server(latency_ms=0.0):
    """Start the loopback fake-GCS cluster; returns
    (popen, endpoint, n_workers) — the single source of truth for the
    worker count reported in bench extras. latency_ms injects a
    per-request delay (modeling object-store RTT) for benches that
    measure latency-hiding machinery."""
    import subprocess

    here = os.path.dirname(os.path.abspath(__file__))
    server_workers = int(os.environ.get("BENCH_GCS_WORKERS",
                                        min(8, max(4, os.cpu_count() or 4))))
    cmd = [sys.executable, os.path.join(here, "tests", "fake_gcs.py"),
           "--workers", str(server_workers)]
    if latency_ms:
        cmd += ["--latency-ms", str(latency_ms)]
    server = subprocess.Popen(
        cmd,
        stdout=subprocess.PIPE, text=True,
    )
    endpoint = server.stdout.readline().strip()
    if not endpoint.startswith("http://127.0.0.1:"):
        server.terminate()
        raise SystemExit(
            "fake GCS server failed to start (got %r) — refusing to fall "
            "back to the real GCS endpoint" % endpoint
        )
    return server, endpoint, server_workers


def bench_data_stream():
    """Datastore→host token throughput of the streaming dataset reader
    (metaflow_tpu/data/): a sharded corpus on the loopback fake GCS,
    consumed by the bounded-readahead parallel ShardReader vs a naive
    sequential one-shard-at-a-time loop over the same blobs. The
    headline is the PARALLEL tokens/sec; extra carries the sequential
    rate and the speedup (acceptance floor: ≥2x) plus readahead-window
    occupancy and checksum-verify accounting as submetrics."""
    import contextlib

    import numpy as np

    from metaflow_tpu.data import ShardReader, build_corpus
    from metaflow_tpu.data.shards import decode_shard
    from metaflow_tpu.datastore import FlowDataStore, GCSStorage

    n_shards = int(os.environ.get("BENCH_DATA_SHARDS", "64"))
    shard_tokens = int(os.environ.get("BENCH_DATA_SHARD_TOKENS",
                                      str(256 * 1024)))  # 1 MiB int32
    # loopback has no request latency for readahead to hide, so inject a
    # modest object-store RTT into the fake server (per request; served
    # concurrently, so the parallel reader overlaps it exactly like real
    # network waits). 10 ms is conservative for GCS first-byte latency.
    latency_ms = float(os.environ.get("BENCH_DATA_LATENCY_MS", "10"))
    total_tokens = n_shards * shard_tokens
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 32_000, total_tokens, dtype=np.int32)

    server, endpoint, _workers = _fake_gcs_server(latency_ms=latency_ms)
    with contextlib.ExitStack() as stack:
        stack.callback(server.terminate)
        os.environ["TPUFLOW_GS_ENDPOINT"] = endpoint
        stack.callback(os.environ.pop, "TPUFLOW_GS_ENDPOINT", None)
        # blob cache off on BOTH paths: measure datastore→host, not a
        # second pass over this box's disk cache
        fds = FlowDataStore("BenchData", GCSStorage,
                            ds_root="gs://bench-data/root",
                            blob_cache=False)
        manifest = build_corpus(fds, "bench", tokens,
                                shard_tokens=shard_tokens)
        order = list(range(n_shards))

        def sequential_pass():
            """The pre-subsystem baseline: fetch and decode one shard at
            a time, nothing in flight behind the consumer."""
            t0 = time.perf_counter()
            consumed = 0
            for sid in order:
                for _k, blob in fds.ca_store.load_blobs(
                        [manifest["shards"][sid]["key"]]):
                    consumed += decode_shard(manifest, sid, blob).size
            assert consumed == total_tokens
            return total_tokens / (time.perf_counter() - t0)

        def parallel_pass():
            reader = ShardReader(fds, manifest, max_workers=8,
                                 readahead_bytes=16 << 20)
            t0 = time.perf_counter()
            consumed = 0
            for _sid, arr in reader.stream(order):
                consumed += arr.size
            assert consumed == total_tokens
            return total_tokens / (time.perf_counter() - t0), reader

        sequential_pass()  # warmup: server allocators + conn pools
        seq_tps = max(sequential_pass() for _ in range(2))
        par = [parallel_pass() for _ in range(2)]
        par_tps, reader = max(par, key=lambda r: r[0])
        occupancy = reader.mean_occupancy()
        mb = total_tokens * 4 / 2**20
        return {
            "metric": "data_tokens_per_s",
            "value": round(par_tps, 1),
            "unit": "tokens/s datastore->host (parallel shard reader)",
            "vs_baseline": _vs_baseline(par_tps),
            "extra": {
                "sequential_tokens_per_s": round(seq_tps, 1),
                "speedup_vs_sequential": round(par_tps / seq_tps, 2),
                "shards": n_shards,
                "shard_tokens": shard_tokens,
                "corpus_mb": round(mb, 1),
                "readahead_mb": 16,
                "workers": 8,
                "checksum_verified_fetches": reader.stats["fetches"],
                "injected_latency_ms_per_request": latency_ms,
                "transport": "loopback_fake_gcs_cluster"
                             "+injected_rtt",
            },
            "submetrics": [
                {"metric": "data_readahead_occupancy",
                 "value": round(occupancy, 4),
                 "unit": "mean readahead-window fill fraction"},
                {"metric": "data_parallel_mb_per_s",
                 "value": round(par_tps * 4 / 2**20, 1),
                 "unit": "MB/s datastore->host"},
            ] + ([] if os.environ.get("BENCH_DATA_GSOP") == "0"
                 else [_submetric(bench_data_path)]),
        }


def bench_artifact_persist():
    """Pipelined vs serial artifact persist (8×32 MB artifacts) against
    the loopback fake GCS: measures the TaskDataStore.save_artifacts path
    end to end — serialize (D2H + pack + sha256) overlapped with upload
    vs the old serialize-everything-then-upload sequence. The headline
    number is the PIPELINED rate; extra carries the serial rate and the
    speedup (acceptance floor: ≥1.5×)."""
    import contextlib

    import numpy as np

    from metaflow_tpu.datastore import FlowDataStore, GCSStorage

    n_objects, obj_mb = 8, 32
    total_mb = n_objects * obj_mb
    rng = np.random.default_rng(0)
    # distinct incompressible arrays: dedup must not collapse the set
    base = [rng.integers(0, 255, obj_mb << 20, dtype=np.uint8)
            for _ in range(n_objects)]
    salt = [0]

    def fresh_artifacts():
        # content-addressing skips the PUT for bytes the store has seen:
        # every measured run must persist NEVER-SEEN content or it would
        # time 8 exists-checks instead of 256 MB of upload
        salt[0] += 1
        return [("a%d" % i, arr ^ np.uint8(salt[0]))
                for i, arr in enumerate(base)]

    server, endpoint, _workers = _fake_gcs_server()
    with contextlib.ExitStack() as stack:
        stack.callback(server.terminate)
        os.environ["TPUFLOW_GS_ENDPOINT"] = endpoint
        stack.callback(os.environ.pop, "TPUFLOW_GS_ENDPOINT", None)
        # blob cache off: measure the persist path, not this disk
        fds = FlowDataStore("BenchPersist", GCSStorage,
                            ds_root="gs://bench-persist/root",
                            blob_cache=False)

        def run(task_id, pipelined):
            arts = fresh_artifacts()
            ds = fds.get_task_datastore("1", "persist", task_id, attempt=0,
                                        mode="w")
            ds.init_task()
            t0 = time.perf_counter()
            ds.save_artifacts(arts, pipelined=pipelined)
            return time.perf_counter() - t0

        run("warm", False)  # warmup: server allocators, conn pools
        serial_dt = min(run("s%d" % i, False) for i in range(2))
        pipe_dt = min(run("p%d" % i, True) for i in range(2))
        pipe_rate = total_mb / pipe_dt
        return {
            "metric": "artifact_persist_mb_per_s",
            "value": round(pipe_rate, 1),
            "unit": "MB/s",
            "vs_baseline": _vs_baseline(pipe_rate),
            "extra": {
                "serial_mb_per_s": round(total_mb / serial_dt, 1),
                "speedup_vs_serial": round(serial_dt / pipe_dt, 2),
                "objects": n_objects,
                "object_mb": obj_mb,
                "transport": "loopback_fake_gcs_cluster",
            },
        }


def bench_ckpt_overlap():
    """Async checkpoint overlap: how much of a checkpoint's wall-clock the
    train loop gets back. ckpt_overlap_ratio = 1 − save()_visible / sync,
    where sync is the full serialize+upload wall-clock (save + wait) and
    save()_visible is the time the async save blocks the caller (host
    snapshot only). Between save() and wait() the bench keeps running
    jitted train-step stand-ins and reports how many completed inside the
    upload window — proof the overlap is real compute, not idle time.
    Acceptance: visible < 10% of sync."""
    import contextlib

    import numpy as np

    from metaflow_tpu.datastore import FlowDataStore, GCSStorage
    from metaflow_tpu.training import AsyncCheckpointManager

    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    state = {
        "params": {"w%d" % i: rng.standard_normal((1024, 1024))
                   .astype(np.float32) for i in range(16)},
        "step": 123,
    }  # 16 × 4 MB = 64 MB
    state_mb = sum(v.nbytes for v in state["params"].values()) >> 20

    # train-step stand-in: a jitted matmul chain, sized to a few ms
    @jax.jit
    def fake_step(x):
        for _ in range(4):
            x = jnp.tanh(x @ x)
        return x

    x0 = jnp.asarray(rng.standard_normal((512, 512)).astype(np.float32))
    fake_step(x0).block_until_ready()  # compile

    server, endpoint, _workers = _fake_gcs_server()
    with contextlib.ExitStack() as stack:
        stack.callback(server.terminate)
        os.environ["TPUFLOW_GS_ENDPOINT"] = endpoint
        stack.callback(os.environ.pop, "TPUFLOW_GS_ENDPOINT", None)
        fds = FlowDataStore("BenchCkpt", GCSStorage,
                            ds_root="gs://bench-ckpt/root",
                            blob_cache=False)
        mgr = AsyncCheckpointManager(fds, name="bench")
        # warmup (step 0): conn pool + allocator
        mgr.save(state, 0)
        mgr.wait()
        sync_dt = []
        vis_dt = []
        overlapped_steps = []
        for i in range(1, 4):
            # distinct step content each round so upload really happens
            state["params"]["w0"] = state["params"]["w0"] + np.float32(i)
            t0 = time.perf_counter()
            mgr.save(state, i)
            vis = time.perf_counter() - t0
            # the train loop continues while the upload is in flight
            steps = 0
            while not mgr.done():
                fake_step(x0).block_until_ready()
                steps += 1
            sync_dt.append(time.perf_counter() - t0)
            vis_dt.append(vis)
            overlapped_steps.append(steps)
        sync = statistics.median(sync_dt)
        visible = statistics.median(vis_dt)
        ratio = max(0.0, 1.0 - visible / sync) if sync > 0 else 0.0
        return {
            "metric": "ckpt_overlap_ratio",
            "value": round(ratio, 4),
            "unit": "fraction of checkpoint wall-clock overlapped",
            "vs_baseline": 1.0,
            "extra": {
                "sync_save_s": round(sync, 4),
                "async_visible_s": round(visible, 4),
                "visible_fraction": round(visible / sync, 4) if sync else None,
                "train_steps_during_upload": overlapped_steps,
                "state_mb": state_mb,
                "transport": "loopback_fake_gcs_cluster",
            },
        }


def bench_elastic_goodput():
    """Goodput (useful train steps / wall-clock) under a kill schedule
    and a scripted capacity hole: the elastic supervisor's
    resize-and-continue vs the fixed-size retry baseline, which can only
    park until the hole closes (admission control applies to both — a
    gang cannot relaunch onto capacity that is not there).

    Scenario (time-keyed ScriptedCapacityOracle): the fleet starts full,
    drops to HALF capacity around the chaos kill, and recovers
    BENCH_ELASTIC_HOLE_S seconds later. Both runs complete the same
    number of useful train steps on the exact same token order (the
    flow's `end` step asserts it); only the wall-clock differs. Grow-back
    is disabled for the measurement so each run's step count is the
    clean numerator.

    Both runs' telemetry additionally feeds the goodput ledger
    (metaflow_tpu/goodput.py), derived here BEFORE each run's tempdir is
    destroyed: both ledgers must reconcile (attributed >= 95% of
    observed chip-time), the elastic run must book restore_replay (the
    scheduled kill forces a checkpoint restore), and the fixed run must
    book capacity_wait (it cannot resize, so the scripted hole parks it
    at delay_s x world chip-seconds a tick — the elastic run instead
    shrinks through the hole, which is the whole point)."""
    import subprocess
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__))
    flow = os.path.join(here, "tests", "flows", "elastic_train_flow.py")
    ranks = int(os.environ.get("BENCH_ELASTIC_RANKS", "4"))
    steps = int(os.environ.get("BENCH_ELASTIC_STEPS", "30"))
    sleep = os.environ.get("BENCH_ELASTIC_SLEEP", "0.05")
    hole_s = float(os.environ.get("BENCH_ELASTIC_HOLE_S", "10"))
    half = max(1, ranks // 2)
    kill_step = 3

    def run_once(resize):
        with tempfile.TemporaryDirectory() as root:
            env = dict(os.environ)
            env.update({
                "TPUFLOW_DATASTORE_SYSROOT_LOCAL": root,
                "TPUFLOW_CLIENT_CACHE": os.path.join(root, "cache"),
                "PYTHONPATH": here,
                "JAX_PLATFORMS": "cpu",
                "TPUFLOW_CHAOS": "%d:1" % kill_step,
                "TPUFLOW_CHAOS_DIR": os.path.join(root, "chaos"),
                # "+" anchors the timeline at the FIRST consult = the
                # post-kill retry decision: a capacity hole of exactly
                # hole_s seconds starting at the failure, regardless of
                # how long imports/steps ran before the kill
                "TPUFLOW_CAPACITY_ORACLE": "scripted:+0:%d,%g:%d"
                                           % (half, hole_s, ranks),
                "TPUFLOW_ELASTIC_RESIZE": "1" if resize else "0",
                # no grow-back mid-measurement: both runs finish at one
                # size so goodput = steps / wall is directly comparable
                "TPUFLOW_ELASTIC_GROW_EVERY_S": "3600",
                "TPUFLOW_RETRY_BACKOFF_BASE_S": "0.1",
                "TPUFLOW_RETRY_BACKOFF_SEED": "0",
                "ELASTIC_FLOW_RANKS": str(ranks),
                "ELASTIC_FLOW_STEPS": str(steps),
                "ELASTIC_FLOW_SLEEP": str(sleep),
            })
            t0 = time.perf_counter()
            proc = subprocess.run([sys.executable, flow, "run"], env=env,
                                  capture_output=True, text=True)
            wall = time.perf_counter() - t0
            out = proc.stdout + proc.stderr
            if proc.returncode != 0 or "elastic run ok" not in out:
                raise SystemExit(
                    "elastic bench flow failed (resize=%s):\n%s"
                    % (resize, out[-2000:]))
            # derive the goodput ledger NOW — the tempdir (and with it
            # the run's _telemetry/) is gone once this block exits
            from metaflow_tpu import goodput
            from metaflow_tpu.datastore import FlowDataStore, LocalStorage

            fds = FlowDataStore("ElasticTrainFlow", LocalStorage,
                                ds_root=root)
            run_ids = sorted(fds.list_runs())
            if not run_ids:
                raise SystemExit(
                    "elastic bench flow left no runs in %s" % root)
            ledger = goodput.derive_run_ledger(fds, run_ids[-1])
            return steps / wall, wall, ledger

    elastic_goodput, elastic_wall, ledger = run_once(True)
    fixed_goodput, fixed_wall, fixed_ledger = run_once(False)
    ratio = elastic_goodput / fixed_goodput

    # chip-second accounting gates: every kill in the schedule must be
    # visible in the ledgers, and each ledger must explain its run
    cats = ledger["categories"]
    fixed_cats = fixed_ledger["categories"]
    for label, led in (("elastic", ledger), ("fixed", fixed_ledger)):
        if not led["reconciled"]:
            raise SystemExit(
                "%s goodput ledger failed reconciliation: coverage "
                "%.3f < %.3f (unattributed %.1fs of %.1fs observed)"
                % (label, led["coverage"], 1.0 - led["tolerance"],
                   led["unattributed_chip_s"], led["observed_chip_s"]))
    if cats["restore_replay"] <= 0:
        raise SystemExit(
            "kill at step %d produced no restore_replay chip-time: %r"
            % (kill_step, cats))
    if fixed_cats["capacity_wait"] <= 0:
        raise SystemExit(
            "capacity hole (%gs) parked the fixed-size gang but booked "
            "no capacity_wait chip-time: %r" % (hole_s, fixed_cats))
    return {
        "metric": "elastic_goodput_ratio",
        "value": round(ratio, 2),
        "unit": "x (elastic vs fixed-size retry, same kill + capacity "
                "hole)",
        "vs_baseline": _vs_baseline(ratio),
        "extra": {
            "ranks": ranks,
            "shrink_to": half,
            "useful_steps": steps,
            "kill_step": kill_step,
            "capacity_hole_s": hole_s,
            "elastic_wall_s": round(elastic_wall, 2),
            "fixed_wall_s": round(fixed_wall, 2),
            "ledger_dominant_loss": ledger["dominant_loss"],
            "ledger_goodput_frac": ledger["goodput_frac"],
        },
        "submetrics": [
            {"metric": "elastic_goodput_steps_per_s",
             "value": round(elastic_goodput, 3),
             "unit": "useful train steps/s (resize-and-continue)"},
            {"metric": "fixed_goodput_steps_per_s",
             "value": round(fixed_goodput, 3),
             "unit": "useful train steps/s (park until capacity "
                     "returns)"},
            {"metric": "elastic_ledger_coverage",
             "value": round(min(ledger["coverage"],
                                fixed_ledger["coverage"]), 4),
             "unit": "attributed / observed chip-seconds, worse of the "
                     "two runs' goodput ledgers (gate: >= 0.95)"},
            {"metric": "elastic_ledger_restore_replay_s",
             "value": round(cats["restore_replay"], 3),
             "unit": "chip-seconds restoring + replaying after the "
                     "scheduled kill, elastic run (gate: > 0)"},
            {"metric": "fixed_ledger_capacity_wait_s",
             "value": round(fixed_cats["capacity_wait"], 3),
             "unit": "delay_s x world chip-seconds the fixed-size gang "
                     "parked on the scripted hole (gate: > 0)"},
        ],
    }


def bench_hang_recovery():
    """Time-to-recovery under one seeded wedge (TPUFLOW_CHAOS hang
    fault): the gang watchdog's detect → forensics → kill → elastic
    retry pipeline vs the undetected baseline, whose only escape is the
    bounded gang worker wait (TPUFLOW_GANG_NODE_WAIT_TIMEOUT_S — the
    stand-in for however long an operator takes to notice a run that
    stopped making progress). Both runs finish the same token-exact
    trajectory (the flow's `end` step asserts it); only the wall-clock
    to get there differs. Gate: detected must be >= 1.2x faster."""
    import subprocess
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__))
    flow = os.path.join(here, "tests", "flows", "hang_chaos_flow.py")
    ranks = int(os.environ.get("BENCH_HANG_RANKS", "2"))
    steps = int(os.environ.get("BENCH_HANG_STEPS", "6"))
    sleep = os.environ.get("BENCH_HANG_SLEEP", "0.05")
    # the undetected baseline's only bound on the wedge
    wait_s = float(os.environ.get("BENCH_HANG_WAIT_S", "12"))

    def run_once(detect):
        with tempfile.TemporaryDirectory() as root:
            env = dict(os.environ)
            env.update({
                "TPUFLOW_DATASTORE_SYSROOT_LOCAL": root,
                "TPUFLOW_CLIENT_CACHE": os.path.join(root, "cache"),
                "PYTHONPATH": here,
                "JAX_PLATFORMS": "cpu",
                "TPUFLOW_CHAOS": "3:1:hang",
                "TPUFLOW_CHAOS_DIR": os.path.join(root, "chaos"),
                "TPUFLOW_RETRY_BACKOFF_BASE_S": "0.05",
                "TPUFLOW_RETRY_BACKOFF_SEED": "0",
                "HANG_FLOW_RANKS": str(ranks),
                "HANG_FLOW_STEPS": str(steps),
                "HANG_FLOW_SLEEP": str(sleep),
            })
            if detect:
                env.update({
                    "TPUFLOW_HANG_DETECT": "1",
                    "TPUFLOW_HANG_FLOOR_S": "2",
                    "TPUFLOW_HANG_POLL_S": "0.5",
                    "TPUFLOW_HANG_COMPILE_GRACE_S": "3",
                    "TPUFLOW_HANG_KILL_GRACE_S": "1",
                    "TPUFLOW_HANG_DUMP_WAIT_S": "0.3",
                    "TPUFLOW_PROGRESS_EVERY_S": "0",
                })
            else:
                env.update({
                    "TPUFLOW_HANG_DETECT": "0",
                    "TPUFLOW_GANG_NODE_WAIT_TIMEOUT_S": "%g" % wait_s,
                })
            t0 = time.perf_counter()
            proc = subprocess.run([sys.executable, flow, "run"], env=env,
                                  capture_output=True, text=True)
            wall = time.perf_counter() - t0
            out = proc.stdout + proc.stderr
            if proc.returncode != 0 or "hang run ok" not in out:
                raise SystemExit(
                    "hang bench flow failed (detect=%s):\n%s"
                    % (detect, out[-2000:]))
            return wall

    detected_wall = run_once(True)
    undetected_wall = run_once(False)
    ratio = undetected_wall / detected_wall
    return {
        "metric": "hang_recovery_ratio",
        "value": round(ratio, 2),
        "unit": "x (watchdog kill-to-recover vs undetected bounded-wait "
                "baseline, same seeded wedge)",
        "vs_baseline": _vs_baseline(ratio),
        "extra": {
            "ranks": ranks,
            "useful_steps": steps,
            "hang_step": 3,
            "undetected_wait_s": wait_s,
        },
        "submetrics": [
            {"metric": "hang_detected_wall_s",
             "value": round(detected_wall, 2),
             "unit": "s to token-exact completion (watchdog on)"},
            {"metric": "hang_undetected_wall_s",
             "value": round(undetected_wall, 2),
             "unit": "s to token-exact completion (bounded wait only)"},
        ],
    }


def _fleet_replica_env(here):
    """Env for fleet replica subprocesses of the CPU-by-design fleet and
    route benches. The replicas share the one placed compile cache
    (device.setup_compile_cache) down to the smallest program: the first
    boot pays the compiles once, so a mid-trace RESTART costs ~2s instead
    of ~5 and the comparison measures the supervisor's recovery policy,
    not XLA:CPU compile time."""
    from metaflow_tpu import device

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [here] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p])
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = device.setup_compile_cache()
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env.setdefault("TPUFLOW_TELEMETRY", "0")
    return env


def bench_fleet_goodput():
    """Fleet-router metrics, CPU by design (subprocess replicas on a
    device-emulation step delay — sleep in the replica's step loop models
    a device-bound decode the way the elastic bench models train steps;
    processes don't contend for the one host core while sleeping, so
    replica scaling is honest even on a 1-core box).

    Two gates off the SAME synthetic-weight replica binary:
      * scaling: 1 -> 2 replica useful tok/s ratio (floor: >= 1.8x) on
        a saturating closed-loop trace — the router's dispatch overhead
        and least-loaded policy must not eat the second replica.
      * goodput under chaos (the headline): a seeded mid-trace replica
        kill (FleetChaosInjector through the REAL process-death path),
        failover+restart ON vs OFF (floor: >= 1.5x). With failover the
        victim's in-flight requests re-dispatch to the survivor
        token-identically and the supervisor restarts the corpse; with
        both disabled the same kill strands those requests (502) and
        halves capacity for the rest of the trace."""
    import contextlib
    import http.client
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    from metaflow_tpu.devtools import chaos
    from metaflow_tpu.elastic.policy import BackoffPolicy
    from metaflow_tpu.serving import (FleetConfig, ServingFleet,
                                      SubprocessReplicaSpawner)

    here = os.path.dirname(os.path.abspath(__file__))
    synth = {"vocab_size": 256, "dim": 64, "n_layers": 1, "n_heads": 4,
             "n_kv_heads": 2, "ffn_dim": 128, "max_seq_len": 128,
             "rope_llama3_scaling": False, "dtype": "float32"}
    slots = int(os.environ.get("BENCH_FLEET_SLOTS", "4"))
    step_delay_ms = float(os.environ.get("BENCH_FLEET_STEP_DELAY_MS", "30"))
    n_requests = int(os.environ.get("BENCH_FLEET_REQUESTS", "128"))
    max_new = 24
    kill_dispatch = max(2, n_requests // 5)  # ~20% into the trace
    env = _fleet_replica_env(here)
    replica_args = [
        "--synthetic-config", json.dumps(synth), "--synthetic-seed", "7",
        "--slots", str(slots), "--max-seq-len", "96",
        "--prefill-chunk", "16", "--max-queue", str(2 * n_requests),
        "--step-delay-ms", str(step_delay_ms),
    ]

    def ask(port, i):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
        try:
            conn.request(
                "POST", "/v1/generate",
                json.dumps({"tokens": [1 + (i % 40), 2, 3, 4, 5, 6, 7, 8],
                            "max_new_tokens": max_new, "seed": i}),
                {"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = resp.read()
            if resp.status != 200:
                return 0
            return len(json.loads(body)["new_tokens"])
        except (OSError, ValueError):
            return 0
        finally:
            conn.close()

    def run_trace(n_replicas, failover, restart, kill=False):
        """Boot a fresh fleet, push the closed-loop trace through it
        with a saturating client pool (2x every replica's slots, so
        each replica always has a backlog), return (tok/s, completed,
        wall)."""
        with contextlib.ExitStack() as stack:
            tmp = stack.enter_context(
                tempfile.TemporaryDirectory(prefix="bench-fleet-"))
            injector = None
            if kill:
                injector = chaos.FleetChaosInjector(
                    chaos.KillSchedule.parse("%d:0" % kill_dispatch),
                    os.path.join(tmp, "ledger"))
            config = FleetConfig(
                failover=failover, restart=restart,
                spawn_timeout_s=600.0, wait_s=60.0,
                backoff=BackoffPolicy(base_s=0.2, cap_s=0.5, jitter=0.0,
                                      seed=0))
            fleet = ServingFleet(
                SubprocessReplicaSpawner(replica_args, workdir=tmp,
                                         env=env, spawn_timeout_s=600.0),
                n_replicas, config=config, chaos=injector)
            fleet.start()
            stack.callback(fleet.close)
            t0 = time.perf_counter()
            with ThreadPoolExecutor(
                    max_workers=2 * n_replicas * slots) as pool:
                tokens = sum(pool.map(
                    lambda i: ask(fleet.port, i), range(n_requests)))
            wall = time.perf_counter() - t0
            return tokens / wall, tokens, wall

    one_tps, one_tok, _ = run_trace(1, failover=True, restart=True)
    assert one_tok == n_requests * max_new, (one_tok, "1-replica drop")
    two_tps, two_tok, _ = run_trace(2, failover=True, restart=True)
    assert two_tok == n_requests * max_new, (two_tok, "2-replica drop")
    scaling = two_tps / one_tps

    ft_tps, ft_tok, ft_wall = run_trace(
        2, failover=True, restart=True, kill=True)
    assert ft_tok == n_requests * max_new, (
        ft_tok, "failover must complete every request across the kill")
    nf_tps, nf_tok, nf_wall = run_trace(
        2, failover=False, restart=False, kill=True)
    assert nf_tok < n_requests * max_new, (
        nf_tok, "the kill must strand work when failover is off")
    goodput_ratio = ft_tps / nf_tps

    return {
        "metric": "fleet_goodput_ratio",
        "value": round(goodput_ratio, 2),
        "unit": "x (failover+restart vs disabled, same seeded replica "
                "kill)",
        "vs_baseline": _vs_baseline(goodput_ratio),
        "extra": {
            "replicas": 2,
            "slots_per_replica": slots,
            "requests": n_requests,
            "max_new_tokens": max_new,
            "useful_tokens": n_requests * max_new,
            "step_delay_ms": step_delay_ms,
            "kill_dispatch": kill_dispatch,
            "scaling_1_to_2_replicas": round(scaling, 2),
            "one_replica_tokens_per_s": round(one_tps, 1),
            "two_replica_tokens_per_s": round(two_tps, 1),
            "failover_tokens_per_s": round(ft_tps, 1),
            "failover_completed_tokens": ft_tok,
            "no_failover_tokens_per_s": round(nf_tps, 1),
            "no_failover_completed_tokens": nf_tok,
            "failover_wall_s": round(ft_wall, 2),
            "no_failover_wall_s": round(nf_wall, 2),
            "gate_scaling": 1.8,
            "gate_goodput": 1.5,
        },
        "submetrics": [
            {"metric": "fleet_scaling_1_to_2", "value": round(scaling, 2),
             "unit": "x useful tok/s, 2 replicas vs 1 (same trace)"},
            {"metric": "fleet_failover_tokens_per_s",
             "value": round(ft_tps, 1),
             "unit": "useful tok/s under seeded kill (failover on)"},
            {"metric": "fleet_no_failover_tokens_per_s",
             "value": round(nf_tps, 1),
             "unit": "useful tok/s under seeded kill (failover off)"},
        ],
    }


def bench_route():
    """BENCH_MODE=route: cache-aware multi-tenant routing, CPU by
    design (same subprocess-replica shape as the fleet bench — the
    metric is a ROUTER POLICY comparison, no chip involved).

    A multi-tenant trace — 6 tenants, each with its own disjoint
    96-token system prompt, arriving as one concurrent burst per
    tenant — is pushed through the SAME 3-replica prefix-cached fleet
    twice per rep: cache-aware dispatch ON (TPUFLOW_CACHE_ROUTE=1, the
    default) vs pure least-loaded (=0). A concurrent burst is exactly
    where least-loaded is pessimal: the in-flight counter spreads the
    burst across every replica, so each replica pays the tenant's cold
    prefill, while cache-aware dispatch sends the whole burst to the
    replica whose radix tree already holds the prefix. The metric is
    the ratio of aggregate prefill FLOPs skipped (sum of
    replica-reported prefix-cache hit tokens — prefill cost is linear
    in tokens at fixed model size), gated >= 1.5x, with responses
    token-identical across the two policies (routing changes WHERE
    prefill runs, never what it computes). Reps interleave ON/OFF so
    both sides see the same slice of host drift."""
    import contextlib
    import http.client
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    from metaflow_tpu.elastic.policy import BackoffPolicy
    from metaflow_tpu.serving import (FleetConfig, ServingFleet,
                                      SubprocessReplicaSpawner)

    here = os.path.dirname(os.path.abspath(__file__))
    synth = {"vocab_size": 256, "dim": 64, "n_layers": 1, "n_heads": 4,
             "n_kv_heads": 2, "ffn_dim": 128, "max_seq_len": 160,
             "rope_llama3_scaling": False, "dtype": "float32"}
    n_replicas = 3
    slots = int(os.environ.get("BENCH_ROUTE_SLOTS", "2"))
    n_tenants = int(os.environ.get("BENCH_ROUTE_TENANTS", "6"))
    per_tenant = int(os.environ.get("BENCH_ROUTE_REQUESTS", "4"))
    reps = int(os.environ.get("BENCH_ROUTE_REPS", "3"))
    step_delay_ms = float(os.environ.get("BENCH_ROUTE_STEP_DELAY_MS",
                                         "25"))
    sys_tokens = 96   # 6 route-digest blocks at the default block=16
    max_new = 8
    env = _fleet_replica_env(here)
    replica_args = [
        "--synthetic-config", json.dumps(synth), "--synthetic-seed", "7",
        "--slots", str(slots), "--max-seq-len", "144",
        "--prefill-chunk", "16", "--max-queue", "256",
        "--step-delay-ms", str(step_delay_ms),
        "--prefix-cache-mb", "16",
    ]
    # disjoint per-tenant system prompts: tenant t owns token ids
    # [2 + t*sys_tokens, 2 + (t+1)*sys_tokens) — no shared blocks, so
    # a warm score is evidence of THIS tenant's prefix, never a
    # coincidental cross-tenant overlap
    prompts = [list(range(2 + t * sys_tokens,
                          2 + (t + 1) * sys_tokens))
               for t in range(n_tenants)]
    # the trace: one burst of per_tenant concurrent requests per
    # tenant, each with a distinct 4-token tail (same requests both
    # passes — identity is compared request-by-request)
    bursts = [[(t, prompts[t] + [200 + t, 210 + i, 220 + i, 230 + i],
                t * per_tenant + i) for i in range(per_tenant)]
              for t in range(n_tenants)]

    def ask(port, tenant, tokens, seed):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
        try:
            conn.request(
                "POST", "/v1/generate",
                json.dumps({"tokens": tokens, "max_new_tokens": max_new,
                            "seed": seed, "tenant": "tenant%d" % tenant}),
                {"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = json.loads(resp.read())
            assert resp.status == 200, (resp.status, body)
            return body["new_tokens"]
        finally:
            conn.close()

    def replica_hit_tokens(fleet):
        total = 0
        for h in fleet.handles:
            conn = http.client.HTTPConnection("127.0.0.1", h.port,
                                              timeout=30)
            try:
                conn.request("GET", "/v1/stats")
                stats = json.loads(conn.getresponse().read())
            finally:
                conn.close()
            total += int(stats["prefix_cache"]["hit_tokens"])
        return total

    def run_pass(cache_route):
        """Boot a fresh fleet with the routing policy under test, seed
        each tenant's prefix once (sequential, identical in both
        policies: an idle fleet routes every seed the same way), let
        the health poller pick up the published digests, then push one
        concurrent burst per tenant. Returns (skipped_tokens, outputs,
        stats)."""
        os.environ["TPUFLOW_CACHE_ROUTE"] = "1" if cache_route else "0"
        try:
            with contextlib.ExitStack() as stack:
                tmp = stack.enter_context(
                    tempfile.TemporaryDirectory(prefix="bench-route-"))
                config = FleetConfig(
                    failover=True, restart=True, spawn_timeout_s=600.0,
                    wait_s=60.0, health_interval_s=0.5,
                    backoff=BackoffPolicy(base_s=0.2, cap_s=0.5,
                                          jitter=0.0, seed=0))
                fleet = ServingFleet(
                    SubprocessReplicaSpawner(replica_args, workdir=tmp,
                                             env=env,
                                             spawn_timeout_s=600.0),
                    n_replicas, config=config)
                fleet.start()
                stack.callback(fleet.close)
                for t in range(n_tenants):
                    ask(fleet.port, t, prompts[t] + [240, 241, 242, 243],
                        seed=1000 + t)
                time.sleep(3 * config.health_interval_s)
                outs = []
                with ThreadPoolExecutor(max_workers=per_tenant) as pool:
                    for burst in bursts:
                        # pool.map drains the burst before the next
                        # tenant's begins: concurrency WITHIN a tenant,
                        # isolation between tenants
                        outs.extend(pool.map(
                            lambda r: ask(fleet.port, r[0], r[1], r[2]),
                            burst))
                return replica_hit_tokens(fleet), outs, fleet.stats()
        finally:
            os.environ.pop("TPUFLOW_CACHE_ROUTE", None)

    on_runs, off_runs = _interleaved_reps(
        lambda: run_pass(True), lambda: run_pass(False), reps)
    for (_s, on_outs, _st), (_s2, off_outs, _st2) in zip(on_runs,
                                                         off_runs):
        assert on_outs == off_outs, \
            "routing policy changed response tokens"
    on_med = _median_run(on_runs, key=lambda r: r[0])
    off_med = _median_run(off_runs, key=lambda r: r[0])
    on_skipped, off_skipped = on_med[0], off_med[0]
    ratio = on_skipped / max(1, off_skipped)
    route_stats = on_med[2]["cache_route"]

    return {
        "metric": "route_prefill_skip_ratio",
        "value": round(ratio, 2),
        "unit": "x aggregate prefill tokens skipped, cache-aware vs "
                "least-loaded (same multi-tenant trace)",
        "vs_baseline": _vs_baseline(ratio),
        "extra": {
            "replicas": n_replicas,
            "slots_per_replica": slots,
            "tenants": n_tenants,
            "requests_per_tenant": per_tenant,
            "system_prompt_tokens": sys_tokens,
            "max_new_tokens": max_new,
            "step_delay_ms": step_delay_ms,
            "reps": reps,
            "cache_aware_skipped_tokens": on_skipped,
            "least_loaded_skipped_tokens": off_skipped,
            "cache_route_hits": route_stats["hits"],
            "cache_route_misses": route_stats["misses"],
            "token_identical": True,
            "gate": 1.5,
        },
        "submetrics": [
            {"metric": "route_cache_aware_skipped_tokens",
             "value": on_skipped,
             "unit": "prefill tokens served from cache (routing on)"},
            {"metric": "route_least_loaded_skipped_tokens",
             "value": off_skipped,
             "unit": "prefill tokens served from cache (routing off)"},
        ],
    }


def bench_telemetry_overhead():
    """Instrumented-vs-disabled train-step overhead of the flight
    recorder (training.metrics.instrument_train_step emitting per-step
    records through an active FlightRecorder, exactly the task-context
    configuration). The headline number is the overhead in PERCENT of
    steady-state step time — acceptance: ≤2%. Runs the real bench model
    on TPU, the tiny config on CPU (where absolute step time is ~ms, the
    WORST case for fixed per-step host overhead)."""
    import tempfile

    import jax

    from metaflow_tpu import telemetry
    from metaflow_tpu.datastore import FlowDataStore, LocalStorage
    from metaflow_tpu.models import llama
    from metaflow_tpu.spmd import MeshSpec, create_mesh
    from metaflow_tpu.training import (default_optimizer,
                                       flops_per_token_dense,
                                       instrument_train_step,
                                       make_trainer,
                                       memory_efficient_optimizer,
                                       shard_batch)

    n_devices = len(jax.devices())
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = llama.LlamaConfig.bench_1b(
            loss_chunk=int(os.environ.get("BENCH_LOSS_CHUNK", "256")))
        batch = int(os.environ.get("BENCH_BATCH", "32"))
        seq = int(os.environ.get("BENCH_SEQ", "2048"))
        steps, reps = 10, 2
        optimizer = memory_efficient_optimizer(total_steps=1000)
    else:
        cfg = llama.LlamaConfig.tiny()
        batch, seq = 4, 128
        steps, reps = 20, 3
        optimizer = default_optimizer(total_steps=1000)

    mesh = create_mesh(MeshSpec.fsdp() if n_devices > 1 else MeshSpec.dp())
    state, step, _ = make_trainer(
        jax.random.PRNGKey(0), cfg, mesh, llama, optimizer=optimizer)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size)
    data = shard_batch({"tokens": tokens}, mesh)

    def loop(fn, state, n):
        state, m = fn(state, data)  # warmup (compile on first rep)
        jax.block_until_ready(m["loss"])
        t0 = time.perf_counter()
        for _ in range(n):
            state, m = fn(state, data)
        jax.block_until_ready(m["loss"])
        return (time.perf_counter() - t0) / n, state

    with mesh:
        plain_dts = []
        for _ in range(reps):
            dt, state = loop(step, state, steps)
            plain_dts.append(dt)
        plain = min(plain_dts)

        # instrumented: SAME compiled step, wrapped, with a live recorder
        # persisting to a local datastore — the full task-context path
        with tempfile.TemporaryDirectory() as root:
            fds = FlowDataStore("BenchTelemetry", LocalStorage,
                                ds_root=root)
            telemetry.init_recorder(fds, "bench", "train", "1")
            try:
                n_params = llama.num_params(state["params"])
                wrapped = instrument_train_step(
                    step,
                    tokens_per_step=batch * seq,
                    flops_per_step=flops_per_token_dense(
                        n_params, cfg.n_layers, cfg.dim, seq) * batch * seq,
                )
                instr_dts = []
                for _ in range(reps):
                    dt, state = loop(wrapped, state, steps)
                    instr_dts.append(dt)
                instr = min(instr_dts)
                wrapped.telemetry.close()
                recs = telemetry.read_run_records(fds, "bench")
                records = len(recs)
                summary = wrapped.telemetry.report()
            finally:
                telemetry.close_recorder()

    # goodput accounting rides the same records: derive the ledger +
    # render its OpenMetrics exposition and charge that analysis cost
    # against the instrumented run it describes (gate: <= 2%). This is
    # the run-scope exporter's per-scrape work, measured off-loop — the
    # per-step cost of goodput.interval emission is already inside
    # `instr` above.
    from metaflow_tpu import goodput

    t0 = time.perf_counter()
    ledger = goodput.derive_ledger(recs, run_id="bench")
    exposition = goodput.render_openmetrics(
        goodput.ledger_metric_families(ledger))
    ledger_dt = time.perf_counter() - t0
    assert exposition.endswith("# EOF\n")
    timed_s = instr * steps * reps
    ledger_pct = ledger_dt / timed_s * 100 if timed_s > 0 else 0.0

    overhead_pct = (instr - plain) / plain * 100 if plain > 0 else 0.0
    return {
        "metric": "telemetry_train_step_overhead_pct",
        "value": round(overhead_pct, 2),
        "unit": "% of step time (instrumented vs disabled)",
        "vs_baseline": 1.0,
        "extra": {
            "backend": jax.default_backend(),
            "n_devices": n_devices,
            "plain_step_ms": round(plain * 1000, 3),
            "instrumented_step_ms": round(instr * 1000, 3),
            "steps_per_rep": steps,
            "reps": reps,
            "records_emitted": records,
            "batch": batch,
            "seq": seq,
            "instrumented_summary": summary,
            "ledger_categories": {
                k: v for k, v in ledger["categories"].items() if v > 0},
        },
        "submetrics": [
            {"metric": "goodput_ledger_export_overhead_pct",
             "value": round(ledger_pct, 2),
             "unit": "% of instrumented train time to derive the "
                     "goodput ledger + render OpenMetrics (gate: <= "
                     "2.0)"},
            {"metric": "goodput_ledger_derive_ms",
             "value": round(ledger_dt * 1000, 3),
             "unit": "ms per ledger derivation + exposition render "
                     "(one run-scope /metrics scrape)"},
        ],
    }


def bench_sanitizer_overhead():
    """Sanitized-vs-disabled train-step overhead of the collective
    sanitizer (spmd/sanitizer.py: per-step signature journaling plus the
    cross-rank barrier check at its default cadence, against a live peer
    stream in the run datastore). The headline number is the overhead in
    PERCENT of steady-state step time — acceptance: ≤3%. Runs the real
    bench model on TPU, the tiny config on CPU (ms-scale steps: the
    WORST case for fixed per-step host overhead)."""
    import tempfile

    import jax

    from metaflow_tpu.datastore import FlowDataStore, LocalStorage
    from metaflow_tpu.models import llama
    from metaflow_tpu.spmd import MeshSpec, create_mesh
    from metaflow_tpu.spmd.sanitizer import GangSanitizer
    from metaflow_tpu.training import (default_optimizer, make_trainer,
                                       memory_efficient_optimizer,
                                       shard_batch)

    n_devices = len(jax.devices())
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = llama.LlamaConfig.bench_1b(
            loss_chunk=int(os.environ.get("BENCH_LOSS_CHUNK", "256")))
        batch = int(os.environ.get("BENCH_BATCH", "32"))
        seq = int(os.environ.get("BENCH_SEQ", "2048"))
        steps, reps = 10, 2
        optimizer = memory_efficient_optimizer(total_steps=1000)
    else:
        cfg = llama.LlamaConfig.tiny()
        batch, seq = 4, 128
        steps, reps = 30, 5
        optimizer = default_optimizer(total_steps=1000)

    mesh = create_mesh(MeshSpec.fsdp() if n_devices > 1 else MeshSpec.dp())
    state, step, _ = make_trainer(
        jax.random.PRNGKey(0), cfg, mesh, llama, optimizer=optimizer)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size)
    data = shard_batch({"tokens": tokens}, mesh)

    def loop(fn, state, n):
        state, m = fn(state, data)  # warmup (compile on first rep)
        jax.block_until_ready(m["loss"])
        t0 = time.perf_counter()
        for _ in range(n):
            state, m = fn(state, data)
        jax.block_until_ready(m["loss"])
        return (time.perf_counter() - t0) / n, state

    barrier_every = int(os.environ.get("TPUFLOW_SANITIZE_EVERY", "64"))
    total_calls = reps * (steps + 1)
    with mesh:
        # sanitized: SAME compiled step, wrapped, with a live datastore
        # and a lockstep PEER stream pre-published for every barrier the
        # run will hit — the checker pays its real poll+load+compare
        # cost. Plain/sanitized reps INTERLEAVE so host drift (shared CI
        # boxes) cancels instead of landing on one side.
        with tempfile.TemporaryDirectory() as root:
            fds = FlowDataStore("BenchSanitize", LocalStorage, ds_root=root)
            s0 = GangSanitizer(fds, "bench", rank=0, world=2,
                               barrier_every=barrier_every,
                               timeout_s=60, poll_s=0.001)
            s1 = GangSanitizer(fds, "bench", rank=1, world=2)
            b = 0
            for i in range(total_calls):
                s1.journal("step", "train_step", shape=(data,))
                if (i + 1) % barrier_every == 0:
                    s1.publish(b)
                    b += 1
            wrapped = s0.wrap_step(step)
            plain_dts, san_dts = [], []
            for _ in range(reps):
                dt, state = loop(step, state, steps)
                plain_dts.append(dt)
                dt, state = loop(wrapped, state, steps)
                san_dts.append(dt)
            plain = min(plain_dts)
            sanitized = min(san_dts)

    overhead_pct = (sanitized - plain) / plain * 100 if plain > 0 else 0.0
    return {
        "metric": "sanitizer_train_step_overhead_pct",
        "value": round(overhead_pct, 2),
        "unit": "% of step time (TPUFLOW_SANITIZE=1 vs off)",
        "vs_baseline": 1.0,
        "extra": {
            "backend": jax.default_backend(),
            "n_devices": n_devices,
            "plain_step_ms": round(plain * 1000, 3),
            "sanitized_step_ms": round(sanitized * 1000, 3),
            "steps_per_rep": steps,
            "reps": reps,
            "barrier_every": barrier_every,
            "barriers_run": s0._barriers,
            "journal_entries": s0._seq,
            "gate_pct": 3.0,
            "batch": batch,
            "seq": seq,
        },
    }


def _vs_baseline(value):
    base = os.environ.get("BENCH_BASELINE")
    if base:
        try:
            return round(value / float(base), 3)
        except ValueError:
            pass
    return 1.0


def bench_zero_update():
    """ZeRO-style cross-replica weight-update sharding vs the replicated
    update (TPUFLOW_ZERO, spmd/sharding.py + training/train_step.py).

    Mesh-policy + memory metric, CPU BY DESIGN: the win being gated is
    layout math — optimizer state resident per replica drops ~1/dp — and
    that is exact on the forced-host-device mesh (BENCH_ZERO_DEVICES,
    default 8). The measured tok/s comparison on this box rides as
    context; the on-chip throughput number for the sharded update is
    BENCH_MODE=train with TPUFLOW_ZERO=1 (recorded per device-kind by
    scripts/sweep_fused.py).

    Primary metric: replicated/sharded opt-state bytes per device — the
    gate asserts >= 0.75*dp (tiny-config dims all divide the DP axis, so
    the ideal is ~dp). Submetrics: tok/s both ways, loss parity drift,
    and the XLA cost-model bytes-accessed ratio for the lowered step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from metaflow_tpu.models import llama
    from metaflow_tpu.spmd import MeshSpec, create_mesh
    from metaflow_tpu.training import (default_optimizer, make_trainer,
                                       shard_batch)
    from metaflow_tpu.training.metrics import _tree_device_bytes

    steps = int(os.environ.get("BENCH_ZERO_STEPS", "6"))
    batch = int(os.environ.get("BENCH_ZERO_BATCH", "8"))
    seq = int(os.environ.get("BENCH_ZERO_SEQ", "128"))
    cfg = llama.LlamaConfig.tiny()
    mesh = create_mesh(MeshSpec.dp())
    dp = mesh.shape.get("data", 1)
    rng = jax.random.PRNGKey(0)
    tokens = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch, seq + 1))

    def run(zero):
        optimizer = default_optimizer(total_steps=1000)
        state, step, _shardings = make_trainer(
            rng, cfg, mesh, llama, optimizer=optimizer, zero=zero)
        opt_bytes = _tree_device_bytes(state["opt_state"])
        data = shard_batch({"tokens": jnp.asarray(tokens)}, mesh)
        losses = []
        with mesh:
            state, m = step(state, data)  # compile + step 0
            losses.append(float(m["loss"]))
            jax.block_until_ready(state["params"])
            t0 = time.perf_counter()
            for _ in range(steps):
                state, m = step(state, data)
                losses.append(float(m["loss"]))
            jax.block_until_ready(state["params"])
            dt = time.perf_counter() - t0
        tps = batch * seq * steps / dt
        return tps, opt_bytes, losses

    zero_tps, zero_opt_bytes, zero_losses = run(True)
    rep_tps, rep_opt_bytes, rep_losses = run(False)
    ratio = rep_opt_bytes / max(1, zero_opt_bytes)
    loss_drift = max(abs(a - b) for a, b in zip(zero_losses, rep_losses))

    def hlo_bytes_ratio():
        """XLA cost-model bytes accessed, replicated/sharded, for the
        exact lowered steps — layout evidence independent of the wall
        clock on a loaded CI box."""
        from metaflow_tpu.training import make_train_state, make_train_step

        def lower_cost(zero):
            optimizer = default_optimizer(total_steps=1000)
            state, _ = make_train_state(rng, cfg, mesh, llama,
                                        optimizer=optimizer, zero=zero)
            step = make_train_step(cfg, mesh, llama, optimizer=optimizer,
                                   zero=zero)
            data = shard_batch({"tokens": jnp.asarray(tokens)}, mesh)
            with mesh:
                cost = step.lower(state, data).compile().cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0]
            return float(cost.get("bytes accessed", 0.0))
        rep = lower_cost(False)
        sharded = lower_cost(True)
        if not sharded:
            return None
        return {
            "metric": "zero_hlo_bytes_accessed_ratio",
            "value": round(rep / sharded, 3),
            "unit": "x (replicated / sharded step, XLA cost model)",
            "extra": {"replicated_bytes": rep, "sharded_bytes": sharded},
        }

    def mfu_estimate():
        """r05-roofline-anchored MFU-uplift estimate for a real DP pod.

        Model (every input named in extra): a BENCH_ZERO_EST_DP-replica
        pod of BENCH_TARGET_CHIP chips runs the ~1B bench config at
        BENCH_ZERO_EST_TOKENS tokens per replica per step — the paper's
        strong-scaling regime, where the weight update is NOT amortized
        away by a huge per-replica batch. Anchor: the r05 hlo_estimate
        put measured throughput at BENCH_ZERO_EST_MFU of the compute
        bound, so t_step = t_compute / mfu. The replicated adamw-fp32
        update moves 28 B/param of HBM traffic (read grads+params+mu+nu,
        write params+mu+nu); ZeRO moves 28/dp + 4*(1-1/dp) (the gathered
        param shards still get written). The reduce-scatter/all-gather
        comm itself is NOT credited (no ICI table here; the all-gather
        overlaps the next fwd per the schedule, so this under-counts the
        win rather than over-counting)."""
        target = os.environ.get("BENCH_TARGET_CHIP", "v5e").lower()
        peak_table, hbm_table = _chip_tables()
        peak = next((tf for sub, tf in peak_table if sub in target), None)
        bw = next((b for sub, b in hbm_table if sub in target), None)
        if not peak or not bw:
            return None
        est_dp = int(os.environ.get("BENCH_ZERO_EST_DP", "8"))
        est_tokens = int(os.environ.get("BENCH_ZERO_EST_TOKENS", "1024"))
        est_seq = 2048
        anchor_mfu = float(os.environ.get("BENCH_ZERO_EST_MFU", "0.34"))
        bcfg = llama.LlamaConfig.bench_1b()
        abstract = jax.eval_shape(
            lambda k: llama.init_params(k, bcfg), jax.random.PRNGKey(0))
        n_params = sum(int(np.prod(l.shape))
                       for l in jax.tree.leaves(abstract))
        flops_per_token = 6.0 * n_params + 12.0 * bcfg.n_layers * bcfg.dim \
            * est_seq
        t_compute = est_tokens * flops_per_token / (peak * 1e12)
        t_step = t_compute / anchor_mfu
        t_upd_rep = 28.0 * n_params / (bw * 1e9)
        t_upd_zero = (28.0 / est_dp + 4.0 * (1.0 - 1.0 / est_dp)) \
            * n_params / (bw * 1e9)
        t_after = t_step - t_upd_rep + t_upd_zero
        ratio = t_step / t_after
        return {
            "metric": "zero_mfu_estimate_ratio",
            "value": round(ratio, 3),
            "unit": "x (r05-anchored step-time model, DP pod, "
                    "small per-replica batch)",
            "extra": {
                "target_chip": target,
                "dp": est_dp,
                "tokens_per_replica_per_step": est_tokens,
                "anchor_mfu": anchor_mfu,
                "mfu_after_estimate": round(anchor_mfu * ratio, 4),
                "n_params": n_params,
                "t_step_ms": round(t_step * 1e3, 2),
                "t_update_replicated_ms": round(t_upd_rep * 1e3, 2),
                "t_update_zero_ms": round(t_upd_zero * 1e3, 2),
                "note": "ratio -> 1.0 as tokens/replica grows (update "
                        "amortized); comm overlap not credited",
            },
        }

    return {
        "metric": "zero_opt_state_hbm_ratio",
        "value": round(ratio, 2),
        "unit": "x smaller optimizer state per replica (replicated / "
                "ZeRO-sharded update)",
        "vs_baseline": 1.0,
        "extra": {
            "dp": dp,
            "gate": round(0.75 * dp, 2),
            "zero_opt_state_bytes_per_device": zero_opt_bytes,
            "replicated_opt_state_bytes_per_device": rep_opt_bytes,
            "zero_tokens_per_s": round(zero_tps, 1),
            "replicated_tokens_per_s": round(rep_tps, 1),
            "tokens_per_s_ratio": round(zero_tps / rep_tps, 3),
            "loss_parity_max_abs_diff": loss_drift,
            "steps": steps,
            "batch": batch,
            "seq": seq,
            "backend": jax.default_backend(),
            "devices": jax.device_count(),
        },
        "submetrics": [_submetric(mfu_estimate)] + (
            # the cost-model comparison pays two extra AOT compiles;
            # BENCH_ZERO_HLO=0 lets the CI gate skip it
            [_submetric(hlo_bytes_ratio)]
            if os.environ.get("BENCH_ZERO_HLO", "1") == "1" else []),
    }


def bench_mpmd_overlap():
    """Double-buffered MPMD stage transport vs synchronous
    send-then-compute (BENCH_MODE=mpmd; spmd/mpmd.py +
    training/mpmd_trainer.py).

    Transport-policy metric, CPU BY DESIGN: the win being gated is
    overlap — with a modeled DCN link latency injected per frame
    (TPUFLOW_MPMD_LINK_LATENCY_MS), the double-buffered transport pays
    it on sender/receiver threads while the stage computes, the sync
    baseline pays it inline on the critical path. Both runs are the
    SAME 2-stage interleaved schedule over the same tiny Llama, so the
    per-step transfer-stall delta is pure transport policy.

    Primary metric: fraction of the sync baseline's per-step SEND-path
    stall (serialize + modeled link + sendall — the transfer wall-clock
    a stage itself pays; recv waits conflate wire time with peer
    compute and are reported as context, not gated) that the
    double-buffered transport hides — the gate asserts >= 0.5.
    Context: per-mode step wall time, total transfer-stall fraction,
    loss parity across modes."""
    import threading

    import numpy as np

    from metaflow_tpu.models import llama
    from metaflow_tpu.spmd import mpmd
    from metaflow_tpu.training.mpmd_trainer import make_stage_step

    steps = int(os.environ.get("BENCH_MPMD_STEPS", "3"))
    batch = int(os.environ.get("BENCH_MPMD_BATCH", "8"))
    seq = int(os.environ.get("BENCH_MPMD_SEQ", "128"))
    latency_ms = float(os.environ.get("BENCH_MPMD_LATENCY_MS", "2.0"))
    n_layers = int(os.environ.get("BENCH_MPMD_LAYERS", "4"))
    cfg = llama.LlamaConfig.tiny(n_layers=n_layers)
    plan = mpmd.plan_stages(
        num_microbatches=4, num_virtual_stages=2, num_stages=2,
        n_layers=n_layers)
    import jax
    import jax.numpy as jnp
    params = jax.tree.map(
        lambda p: p.astype(jnp.float32),
        llama.init_params(jax.random.PRNGKey(0), cfg))
    tokens = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch, seq + 1))

    def free_port():
        import socket
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def run(double_buffer):
        peers = ["127.0.0.1:%d" % free_port() for _ in range(plan.S)]
        out = [None] * plan.S
        errs = []

        def stage_main(d):
            try:
                transport = mpmd.StageTransport(
                    d, plan.S, peers, double_buffer=double_buffer,
                    link_latency_ms=latency_ms)
                with transport.start():
                    step = make_stage_step(cfg, plan, d, transport,
                                           seq_len=seq + 1)
                    res = step(params, tokens)  # compile + fill
                    s0 = transport.stats()
                    t0 = time.perf_counter()
                    for _ in range(steps):
                        res = step(params, tokens)
                    dt = time.perf_counter() - t0
                    s1 = transport.stats()
                out[d] = {
                    "step_ms": dt * 1e3 / steps,
                    "stall_ms": (s1["stall_ms"] - s0["stall_ms"]) / steps,
                    "send_stall_ms": (s1["stall_send_ms"]
                                      - s0["stall_send_ms"]) / steps,
                    "frames": (s1["frames_sent"] + s1["frames_recv"]
                               - s0["frames_sent"] - s0["frames_recv"])
                    / steps,
                    "loss": None if res["loss"] is None
                    else float(res["loss"]),
                }
            except BaseException as ex:  # surface thread death loudly
                errs.append(ex)

        threads = [threading.Thread(target=stage_main, args=(d,))
                   for d in range(plan.S)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errs:
            raise errs[0]
        return {
            "step_ms": max(r["step_ms"] for r in out),
            "stall_ms": sum(r["stall_ms"] for r in out),
            "send_stall_ms": sum(r["send_stall_ms"] for r in out),
            "frames_per_step": sum(r["frames"] for r in out),
            "loss": next(r["loss"] for r in out if r["loss"] is not None),
            "per_stage_stall_ms": [round(r["stall_ms"], 3) for r in out],
        }

    sync = run(False)
    db = run(True)
    hidden = 1.0 - db["send_stall_ms"] / max(1e-9, sync["send_stall_ms"])
    return {
        "metric": "mpmd_transfer_stall_hidden_frac",
        "value": round(hidden, 4),
        "unit": "fraction of sync-baseline per-step send-path transfer "
                "stall hidden by the double-buffered transport",
        "vs_baseline": 0.0,
        "extra": {
            "gate": 0.5,
            "link_latency_ms": latency_ms,
            "plan": plan.describe(),
            "steps": steps,
            "batch": batch,
            "seq": seq,
            "sync_step_ms": round(sync["step_ms"], 3),
            "db_step_ms": round(db["step_ms"], 3),
            "sync_send_stall_ms_per_step": round(sync["send_stall_ms"], 3),
            "db_send_stall_ms_per_step": round(db["send_stall_ms"], 3),
            "sync_stall_ms_per_step": round(sync["stall_ms"], 3),
            "db_stall_ms_per_step": round(db["stall_ms"], 3),
            "sync_stall_frac": round(
                sync["stall_ms"] / max(1e-9, sync["step_ms"]), 4),
            "db_stall_frac": round(
                db["stall_ms"] / max(1e-9, db["step_ms"]), 4),
            "sync_per_stage_stall_ms": sync["per_stage_stall_ms"],
            "db_per_stage_stall_ms": db["per_stage_stall_ms"],
            "frames_per_step": sync["frames_per_step"],
            "loss_parity_abs_diff": abs(sync["loss"] - db["loss"]),
            "backend": jax.default_backend(),
        },
    }


_submetric_errors = []


def _submetric(fn):
    """Run a secondary bench. Its failure is recorded in the artifact
    next to the primary metric, and the run then exits non-zero."""
    try:
        return fn()
    except (Exception, SystemExit) as ex:  # SystemExit: raise SystemExit paths
        err = {"metric": getattr(fn, "__name__", "submetric"),
               "error": "%s: %s" % (type(ex).__name__, ex)}
        _submetric_errors.append(err)
        return err


def _pin_cpu(host_devices=None):
    """Modes that are CPU by design (policies, host and IO paths, layout
    arithmetic on virtual devices) pin the CPU here, in this process,
    before JAX is imported; their subprocesses inherit the pin."""
    assert "jax" not in sys.modules, "pin the CPU before importing jax"
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["JAX_PLATFORM_NAME"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if host_devices and "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=%s"
            % host_devices).strip()


def _persist_with_overlap():
    result = bench_artifact_persist()
    result["submetrics"] = [_submetric(bench_ckpt_overlap)]
    return result


def _train_with_submetrics():
    result = bench_tokens_per_sec()
    # the launch-latency and data-path numbers ride INSIDE the train
    # entry (history gets one line per run, not five). They are host and
    # IO metrics whose flows run as subprocesses: this process holds the
    # chip by now, so the children are pinned to the CPU
    if os.environ.get("BENCH_SUBMETRICS", "1") == "1":
        os.environ["BENCH_DAEMON"] = os.environ.get("BENCH_DAEMON", "1")
        os.environ["JAX_PLATFORMS"] = "cpu"
        result["submetrics"] = [
            _submetric(bench_step_launch),
            _submetric(bench_data_path),
            _submetric(bench_artifact_persist),
            _submetric(bench_ckpt_overlap),
        ]
    return result


# what needs no chip, and says so: never a fallback
_CPU_MODES = {
    "launch": bench_step_launch,
    "data": bench_data_stream,
    "gsop": bench_data_path,
    "elastic": bench_elastic_goodput,
    "hang": bench_hang_recovery,
    "fleet": bench_fleet_goodput,
    "route": bench_route,
    "persist": _persist_with_overlap,
    "zero": bench_zero_update,
    "mpmd": bench_mpmd_overlap,
    "online": bench_online,
    "hlo_estimate": bench_hlo_estimate,
}
# what times a device: raises where there is no TPU, unless the caller
# pinned the CPU by name (the tests do; the result then says "cpu")
_DEVICE_MODES = {
    "train": _train_with_submetrics,
    "decode": bench_decode,
    "moe": bench_moe,
    "telemetry": bench_telemetry_overhead,
    "serve": bench_serve,
    "sanitize": bench_sanitizer_overhead,
}


if __name__ == "__main__":
    from metaflow_tpu import device

    mode = os.environ.get("BENCH_MODE", "train")
    if mode in _CPU_MODES:
        _pin_cpu(os.environ.get("BENCH_ZERO_DEVICES", "8")
                 if mode == "zero" else None)
        result = _CPU_MODES[mode]()
    elif mode in _DEVICE_MODES:
        device.setup_compile_cache()
        device.platform()
        result = _DEVICE_MODES[mode]()
    else:
        sys.exit("bench: unknown BENCH_MODE %r (one of: %s)"
                 % (mode, ", ".join(sorted({**_CPU_MODES,
                                            **_DEVICE_MODES}))))
    _append_history(result)
    print(json.dumps(result))
    if _submetric_errors:
        sys.exit("bench: %d submetric(s) failed: %s"
                 % (len(_submetric_errors), _submetric_errors))
