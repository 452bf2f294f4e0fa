"""The train -> checkpoint -> reference half of chip_smoke.py.

A linear flow, so one task owns the chip at a time. `train` builds the
trainer on a one-device mesh at Llama-3-8B widths (depth is the only
cut), takes a few steps from ResumableTokenBatches, checks that the
loss is finite and falls and that the step program holds the flash
kernel, and saves params + config through `current.checkpoint`.
`reference` loads that checkpoint the way `tpuflow serve` does and
writes down what `inference.generate()` answers to a handful of
prompts: chip_smoke.py then asks the server the same questions.

Every fact chip_smoke.py reads is one stdout line `CHIP_SMOKE <json>`.
`--size tiny` is the CPU rehearsal (LlamaConfig.tiny()).
"""

import json

import metaflow_tpu
from metaflow_tpu import FlowSpec, Parameter, current, step

MARK = "CHIP_SMOKE "
GIB = float(1 << 30)
# (prompt length, max_new_tokens): below, at and across the 64-token
# prefill chunk, so chunked prefill and mixed-length batching both run
REQUESTS = ((9, 12), (64, 8), (70, 16), (150, 10), (301, 16))
NEAR_TIE_TOP_K = 4


def say(**facts):
    print(MARK + json.dumps(facts), flush=True)


def smoke_config(size, layers):
    from metaflow_tpu.models.llama import LlamaConfig

    if size == "tiny":
        return LlamaConfig.tiny(max_seq_len=1024)
    if size != "8b":
        raise ValueError("--size is '8b' or 'tiny', got %r" % (size,))
    return LlamaConfig.llama3_8b(n_layers=int(layers), max_seq_len=2048)


def param_counts(cfg):
    """(embedding + head, one layer) parameter counts, from the shapes
    models/llama.py:init_params builds."""
    D, F, V = cfg.dim, cfg.ffn_dim, cfg.vocab_size
    kv = cfg.n_kv_heads * cfg.head_dim
    ends = 2 * V * D + D
    layer = 2 * D * D + 2 * D * kv + 3 * D * F + 2 * D
    return ends, layer


def train_budget(cfg, batch, seq):
    """The arithmetic behind the depth: bytes by cause, from shapes."""
    ends, layer = param_counts(cfg)
    n = ends + cfg.n_layers * layer
    # bf16 weights + bf16 gradients + bf16 first moment (the factored
    # second moment is rows + columns, under a thousandth of that)
    state = 6 * n
    # the largest leaf's update is formed in f32 next to its bf16 source
    transient = 4 * cfg.vocab_size * cfg.dim
    # remat keeps one [B, S, D] per layer; the chunked loss one
    # [B, chunk, vocab] f32 block forward and backward
    acts = (cfg.n_layers + 4) * batch * seq * cfg.dim * 2 \
        + 2 * batch * cfg.loss_chunk * cfg.vocab_size * 4
    return {"params": n, "params_ends": ends, "params_per_layer": layer,
            "state_gib": state / GIB, "transient_gib": transient / GIB,
            "activations_gib": acts / GIB,
            "total_gib": (state + transient + acts) / GIB}


def zipf_corpus(vocab, n_tokens, seed):
    """A seeded corpus whose unigram distribution is steep enough for a
    few steps to lower the loss."""
    import numpy as np

    rng = np.random.default_rng(seed)
    return np.minimum(rng.zipf(1.3, n_tokens) - 1, vocab - 1).astype(
        np.int32)


class ChipSmokeFlow(FlowSpec):
    size = Parameter("size", default="8b", help="'8b' or 'tiny'")
    seed = Parameter("seed", default=0, type=int)
    layers = Parameter("layers", default=4, type=int,
                       help="depth at --size 8b (see train_budget)")
    batch = Parameter("batch", default=4, type=int)
    seq = Parameter("seq", default=2048, type=int)
    steps = Parameter("steps", default=6, type=int)
    serve_seq = Parameter("serve_seq", default=1024, type=int,
                          help="KV positions per request, here and in "
                               "the server chip_smoke.py starts")

    @step
    def start(self):
        self.next(self.train)

    @metaflow_tpu.checkpoint
    @step
    def train(self):
        import dataclasses
        import time

        import jax
        import numpy as np

        from metaflow_tpu import device
        from metaflow_tpu.models import llama
        from metaflow_tpu.spmd import MeshSpec, create_mesh
        from metaflow_tpu.training import (
            ResumableTokenBatches,
            make_trainer,
            memory_efficient_optimizer,
            shard_batch,
        )
        from metaflow_tpu.training.data import STATE_KEY
        from metaflow_tpu.training.metrics import hbm_gbps, peak_tflops

        dev = device.describe()
        say(phase="device", device=dev)
        if self.size != "tiny" and device.platform() != "tpu":
            raise RuntimeError(
                "full-width smoke needs the TPU, found %s" % (dev,))
        cfg = smoke_config(self.size, self.layers)
        seq = min(int(self.seq), cfg.max_seq_len)
        batch, steps = int(self.batch), int(self.steps)
        say(phase="train_budget", layers=cfg.n_layers, batch=batch,
            seq=seq, **train_budget(cfg, batch, seq))
        say(phase="chip_row", kind=dev["kind"],
            peak_tflops=peak_tflops(dev["kind"]),
            hbm_gbps=hbm_gbps(dev["kind"]))

        mesh = create_mesh(MeshSpec.dp(), n_devices=1)
        t0 = time.perf_counter()
        state, train_step, _ = make_trainer(
            jax.random.PRNGKey(int(self.seed)), cfg, mesh, llama,
            optimizer=memory_efficient_optimizer(
                lr=3e-4, warmup_steps=1, total_steps=steps))
        jax.block_until_ready(state)
        init_s = time.perf_counter() - t0

        stream = iter(ResumableTokenBatches(
            zipf_corpus(cfg.vocab_size, (steps + 1) * batch * (seq + 1),
                        int(self.seed)),
            batch, seq, seed=int(self.seed)))

        def next_batch():
            b = next(stream)
            return (shard_batch({"tokens": b["tokens"]}, mesh),
                    b[STATE_KEY])

        first, stamp = next_batch()
        t0 = time.perf_counter()
        text = train_step.lower(state, first).compile().as_text()
        compile_s = time.perf_counter() - t0
        kernel_calls = text.count("tpu_custom_call")
        if dev["platform"] == "tpu" and not kernel_calls:
            raise AssertionError(
                "the train step holds no tpu_custom_call: flash "
                "attention was traded for XLA attention")

        losses, step_s = [], []
        batch_now = first
        for i in range(steps):
            t0 = time.perf_counter()
            state, metrics = train_step(state, batch_now)
            losses.append(float(metrics["loss"]))  # blocks on the step
            step_s.append(time.perf_counter() - t0)
            if i + 1 < steps:
                batch_now, stamp = next_batch()
        if not np.all(np.isfinite(losses)):
            raise AssertionError("loss is not finite: %s" % (losses,))
        if not losses[-1] < losses[0]:
            raise AssertionError("loss did not fall: %s" % (losses,))

        t0 = time.perf_counter()
        current.checkpoint.save(
            {"params": state["params"], "cfg": dataclasses.asdict(cfg),
             "data_state": stamp}, step=steps)
        save_s = time.perf_counter() - t0
        say(phase="train", device=dev, losses=losses,
            kernel_calls=kernel_calls,
            peak_bytes_in_use=device.peak_bytes_in_use(),
            memory_stats=jax.local_devices()[0].memory_stats(),
            init_s=init_s, compile_s=compile_s, first_step_s=step_s[0],
            step_s=step_s[1:], save_s=save_s,
            tokens_per_step=batch * seq)
        self.losses = losses
        self.next(self.reference)

    @step
    def reference(self):
        """What generate() answers, and how close its runners-up were:
        a server whose bf16 sums run in another order may flip a near
        tie, and chip_smoke.py needs the margins to tell that from a
        fault."""
        import functools
        import time

        import jax
        import jax.numpy as jnp
        import numpy as np

        from metaflow_tpu import device
        from metaflow_tpu.cmd.serve import build_config, extract_params
        from metaflow_tpu.inference import (
            decode_forward,
            generate,
            init_kv_cache,
            load_run_checkpoint,
        )

        t0 = time.perf_counter()
        restored = load_run_checkpoint(current.flow_name,
                                       run_id=current.run_id,
                                       step_name="train")
        cfg = build_config(restored)
        params = jax.device_put(extract_params(restored))
        jax.block_until_ready(params)
        load_s = time.perf_counter() - t0

        serve_seq = min(int(self.serve_seq), cfg.max_seq_len)
        rng = np.random.default_rng(int(self.seed) + 1)
        max_new = max(n for _, n in REQUESTS)
        bucket = 1
        while bucket < max(p for p, _ in REQUESTS):
            bucket *= 2

        gen = jax.jit(functools.partial(
            generate, cfg=cfg, max_new_tokens=max_new,
            max_seq_len=serve_seq))

        @jax.jit
        def runners_up(params, seq_tokens):
            cache = init_kv_cache(cfg, 1, serve_seq)
            logits, _ = decode_forward(params, seq_tokens, cache, 0, cfg)
            return jax.lax.top_k(logits[0], NEAR_TIE_TOP_K)

        t0 = time.perf_counter()
        out = []
        for plen, n_new in REQUESTS:
            prompt = rng.integers(1, cfg.vocab_size, plen).tolist()
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :plen] = prompt
            toks = np.asarray(gen(params, jnp.asarray(padded),
                                  prompt_len=jnp.int32(plen)))
            new = toks[0, bucket:bucket + n_new].tolist()
            # teacher-forced pass over [prompt | new]: row plen-1+i
            # holds the logits that chose new[i]
            seq_tokens = np.zeros((1, bucket + max_new), np.int32)
            seq_tokens[0, :plen + n_new] = prompt + new
            vals, ids = runners_up(params, jnp.asarray(seq_tokens))
            rows = slice(plen - 1, plen - 1 + n_new)
            out.append({"prompt": prompt, "max_new_tokens": n_new,
                        "new_tokens": new,
                        "top_ids": np.asarray(ids)[rows].tolist(),
                        "top_logits": np.asarray(
                            vals, np.float32)[rows].tolist()})
        say(phase="reference", device=device.describe(), requests=out,
            serve_seq=serve_seq, load_s=load_s,
            generate_s=time.perf_counter() - t0,
            peak_bytes_in_use=device.peak_bytes_in_use())
        self.next(self.end)

    @step
    def end(self):
        print("chip smoke flow: loss %.4f -> %.4f"
              % (self.losses[0], self.losses[-1]))


if __name__ == "__main__":
    ChipSmokeFlow()
