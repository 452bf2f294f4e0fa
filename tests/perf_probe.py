"""One-off perf probes for the bench config's building blocks.

Answers "where do the cycles go" piecewise: pure matmul ceiling at the
layer shapes, flash attention, one transformer layer, the lm_head
projection. Each probe runs N chained iterations INSIDE one jit (a
fori_loop whose carry feeds the next iteration) — independent dispatches
can overlap and give nonsense timings, a data-dependent chain cannot.
Not a test; run manually:

    python tests/perf_probe.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

PEAK = 197e12  # v5e bf16
N = 20


def timed_chain(make_body, init, flops_per_iter, name):
    """make_body() -> f(carry) -> carry; times N on-device iterations."""
    body = make_body()

    @jax.jit
    def run(c):
        return jax.lax.fori_loop(0, N, lambda _, c: body(c), c)

    out = run(init)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = run(init)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / N
    print("%-28s %8.2f ms   %5.1f%% of peak"
          % (name, dt * 1e3, 100 * flops_per_iter / dt / PEAK))


def main():
    B, S, D, F, V = 32, 2048, 2048, 5632, 32_000
    H, KV, Hd = 16, 8, 128
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (B * S, D), jnp.bfloat16)

    # 1. the dominant matmul pair (up then down projection)
    w_up = jax.random.normal(key, (D, F), jnp.bfloat16) * 0.02
    w_down = jax.random.normal(key, (F, D), jnp.bfloat16) * 0.02
    timed_chain(
        lambda: (lambda c: (c @ w_up) @ w_down),
        x, 2 * 2 * B * S * D * F, "matmul up+down 65k,2048,5632",
    )

    w_sq = jax.random.normal(key, (D, D), jnp.bfloat16) * 0.02
    timed_chain(
        lambda: (lambda c: c @ w_sq),
        x, 2 * B * S * D * D, "matmul 65k x 2048 x 2048",
    )

    # 2. flash attention at bench shapes (carry q; k/v closed over)
    from metaflow_tpu.ops.attention import attention

    q = jax.random.normal(key, (B, S, H, Hd), jnp.bfloat16)
    k = jax.random.normal(key, (B, S, KV, Hd), jnp.bfloat16)
    v = jax.random.normal(key, (B, S, KV, Hd), jnp.bfloat16)
    att_flops = 2 * 2 * B * H * S * S * Hd / 2  # QK^T + AV, causal half
    for impl in ("flash", "xla"):
        timed_chain(
            lambda impl=impl: (
                lambda c: attention(c, k, v, causal=True, impl=impl)
            ),
            q, att_flops, "attention fwd %s" % impl,
        )

    # attention fwd+bwd: carry q through its own gradient
    def bwd_body(impl):
        g = jax.grad(lambda q: attention(
            q, k, v, causal=True, impl=impl).sum().astype(jnp.float32))
        return lambda c: g(c).astype(jnp.bfloat16)

    for impl in ("flash", "xla"):
        timed_chain(
            lambda impl=impl: bwd_body(impl),
            q, 3.5 * att_flops, "attention fwd+bwd %s" % impl,
        )

    # 3. one full layer fwd (matmuls + rope + norms + attention)
    from metaflow_tpu.models import llama

    cfg = llama.LlamaConfig.bench_1b(attention_impl="flash")
    params = jax.jit(lambda r: llama.init_params(r, cfg))(jax.random.PRNGKey(1))
    lp1 = jax.tree.map(lambda a: a[0], params["layers"])
    cos, sin = llama.rope_frequencies(cfg.head_dim, S, cfg.rope_theta,
                                      dtype=jnp.bfloat16,
                                      llama3_scaling=False)
    xb = jax.random.normal(key, (B, S, D), jnp.bfloat16)
    layer_mm_flops = 2 * B * S * (D * (H + 2 * KV) * Hd + H * Hd * D
                                  + 3 * D * F)
    timed_chain(
        lambda: (lambda c: llama._layer(cfg, cos, sin, c, lp1)),
        xb, layer_mm_flops + att_flops, "one layer fwd",
    )

    # 4. lm_head projection; sum over vocab feeds the carry so the full
    # matmul must execute
    lm = jax.random.normal(key, (D, V), jnp.bfloat16) * 0.02
    timed_chain(
        lambda: (lambda c: c + (jnp.einsum(
            "bd,dv->bv", c, lm, preferred_element_type=jnp.float32,
        ).sum(axis=1, keepdims=True) * 1e-30).astype(jnp.bfloat16)),
        x, 2 * B * S * D * V, "lm_head 65k x 2048 x 32k",
    )


if __name__ == "__main__":
    main()
