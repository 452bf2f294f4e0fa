"""Llama-family transformer, TPU-first.

Pure-JAX pytree parameters with a parallel tree of *logical axis* annotations
(metaflow_tpu.spmd.sharding) — pjit/GSPMD shards the whole model from a
rule table; no framework indirection between the math and the mesh.

Covers the BASELINE.json targets: Llama-3-8B (dense, GQA, RoPE-500k) and the
scaled-down variants used for single-chip benchmarking. The layer stack is a
lax.scan over a stacked-parameters pytree — one compiled layer body,
layer-count-independent compile time.
"""

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp

from ..ops.attention import attention
from ..ops.norms import rms_norm
from ..ops.rope import apply_rope, rope_frequencies


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128_256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14_336
    max_seq_len: int = 8192
    rope_theta: float = 500_000.0
    rope_llama3_scaling: bool = True
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    attention_impl: str = "auto"
    remat: bool = True
    # remat policy: None = recompute everything; "dots" = save matmul
    # outputs (less recompute, more memory)
    remat_policy: str = None
    # cross-entropy chunk length (tokens): the [B, S, vocab] fp32 logits are
    # the single biggest activation (batch 16 × 2048 × 32k fp32 = 4.2 GB on
    # one v5e); chunking the loss over the sequence bounds that to
    # [B, chunk, vocab] fwd AND bwd (per-chunk remat). 0 = unchunked.
    loss_chunk: int = 256

    @property
    def head_dim(self):
        return self.dim // self.n_heads

    # ---- standard sizes ----

    @staticmethod
    def llama3_8b(**kw):
        return replace(LlamaConfig(), **kw)

    @staticmethod
    def llama3_1b(**kw):
        """Llama-3.2-1B-shaped."""
        return replace(
            LlamaConfig(
                dim=2048, n_layers=16, n_heads=32, n_kv_heads=8,
                ffn_dim=8192,
            ),
            **kw,
        )

    @staticmethod
    def tiny(**kw):
        """Test-sized config (CPU-runnable)."""
        return replace(
            LlamaConfig(
                vocab_size=512, dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
                ffn_dim=256, max_seq_len=256, rope_llama3_scaling=False,
                dtype="float32",
            ),
            **kw,
        )


def param_dtype(cfg):
    return jnp.dtype(cfg.dtype)


def init_params(rng, cfg):
    """Initialize the parameter pytree. Per-layer tensors are stacked on a
    leading 'layers' axis (consumed by lax.scan in forward)."""
    dt = param_dtype(cfg)
    k_embed, k_layers, k_out = jax.random.split(rng, 3)

    def norm_init(*shape):
        return jnp.ones(shape, dt)

    def dense_init(key, fan_in, *shape):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dt)

    L, D, F = cfg.n_layers, cfg.dim, cfg.ffn_dim
    H, KV, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = jax.random.split(k_layers, 7)

    params = {
        "embed": dense_init(k_embed, D, cfg.vocab_size, D),
        "layers": {
            "attn_norm": norm_init(L, D),
            "wq": dense_init(keys[0], D, L, D, H * Hd),
            "wk": dense_init(keys[1], D, L, D, KV * Hd),
            "wv": dense_init(keys[2], D, L, D, KV * Hd),
            "wo": dense_init(keys[3], H * Hd, L, H * Hd, D),
            "ffn_norm": norm_init(L, D),
            "w_gate": dense_init(keys[4], D, L, D, F),
            "w_up": dense_init(keys[5], D, L, D, F),
            "w_down": dense_init(keys[6], F, L, F, D),
        },
        "final_norm": norm_init(D),
        "lm_head": dense_init(k_out, D, D, cfg.vocab_size),
    }
    return params


def logical_axes(cfg):
    """Logical axis names for every parameter (same tree structure)."""
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn_norm": ("layers", "embed"),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "ffn_norm": ("layers", "embed"),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        },
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def _layer(cfg, cos, sin, x, layer_params, mesh=None):
    """One transformer block; x: [B, S, D]."""
    x = _attention_block(cfg, cos, sin, x, layer_params, mesh)
    with jax.named_scope("ffn"):
        h = rms_norm(x, layer_params["ffn_norm"], cfg.norm_eps)
        gate = jax.nn.silu(h @ layer_params["w_gate"])
        up = h @ layer_params["w_up"]
        return x + (gate * up) @ layer_params["w_down"]


@jax.named_scope("attention")
def _attention_block(cfg, cos, sin, x, layer_params, mesh):
    """The attention half of a block, its residual included."""
    B, S, D = x.shape
    H, KV, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    h = rms_norm(x, layer_params["attn_norm"], cfg.norm_eps)
    q = (h @ layer_params["wq"]).reshape(B, S, H, Hd)
    k = (h @ layer_params["wk"]).reshape(B, S, KV, Hd)
    v = (h @ layer_params["wv"]).reshape(B, S, KV, Hd)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    if cfg.attention_impl in ("ring", "ulysses"):
        # context parallelism over the 'sequence' mesh axis: 'ring'
        # rotates KV blocks (ops/ring_attention.py, O(S/n) residency);
        # 'ulysses' re-shards seq->heads with all-to-alls and runs
        # full-sequence attention per head group
        # (ops/ulysses_attention.py, unsharded inner kernel)
        if mesh is None or "sequence" not in mesh.axis_names:
            raise ValueError(
                "attention_impl=%r needs a mesh with a 'sequence' axis "
                "passed to forward/loss_fn" % cfg.attention_impl
            )
        if cfg.attention_impl == "ring":
            from ..ops.ring_attention import ring_attention

            attn = ring_attention(q, k, v, mesh, causal=True)
        else:
            from ..ops.ulysses_attention import ulysses_attention

            attn = ulysses_attention(q, k, v, mesh, causal=True)
    else:
        attn = attention(q, k, v, causal=True, impl=cfg.attention_impl,
                         mesh=mesh)
    # named for remat_policy='attn_out': saving this tensor across the layer
    # checkpoint boundary means the backward pass never re-runs the
    # attention forward (the flash custom_vjp already recomputes its own
    # blockwise internals from the saved LSE — re-running the kernel on top
    # of that is pure waste)
    from jax.ad_checkpoint import checkpoint_name

    attn = checkpoint_name(attn, "attn_out")
    return x + attn.reshape(B, S, H * Hd) @ layer_params["wo"]


def hidden_states(params, tokens, cfg, mesh=None):
    """tokens: [B, S] int32 → final-norm hidden states [B, S, D] (model
    dtype). The lm_head projection is deliberately separate so the loss can
    chunk it (see loss_fn)."""
    dt = param_dtype(cfg)
    x = params["embed"][tokens].astype(dt)
    cos, sin = rope_frequencies(
        cfg.head_dim, tokens.shape[1], cfg.rope_theta, dtype=dt,
        llama3_scaling=cfg.rope_llama3_scaling,
    )

    layer_fn = lambda x, lp: (_layer(cfg, cos, sin, x, lp, mesh=mesh), None)
    if cfg.remat:
        policy = None
        if cfg.remat_policy == "dots":
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        elif cfg.remat_policy == "attn_out":
            # costs L x [B,S,D] bf16 of HBM, saves a full attention forward
            # per layer in the backward pass
            policy = jax.checkpoint_policies.save_only_these_names(
                "attn_out"
            )
        layer_fn = jax.checkpoint(layer_fn, policy=policy)
    with jax.named_scope("layers"):
        x, _ = jax.lax.scan(layer_fn, x, params["layers"])

    return rms_norm(x, params["final_norm"], cfg.norm_eps)


def forward(params, tokens, cfg, mesh=None):
    """tokens: [B, S] int32 → logits [B, S, vocab] (float32).

    `mesh` is only needed for the sequence-parallel attention impls ('ring'/'ulysses')."""
    x = hidden_states(params, tokens, cfg, mesh=mesh)
    return jnp.einsum(
        "bsd,dv->bsv", x, params["lm_head"],
        preferred_element_type=jnp.float32,
    )


def _ce_sums(x, lm_head, targets, mask):
    """Summed cross-entropy + token count for one [B, C, D] hidden chunk.
    fp32 logits live only inside this function."""
    logits = jnp.einsum(
        "bcd,dv->bcv", x, lm_head, preferred_element_type=jnp.float32
    )
    lse = jax.nn.logsumexp(logits, axis=-1)
    tl = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - tl
    if mask is None:
        return jnp.sum(nll), jnp.asarray(nll.size, jnp.float32)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask), jnp.sum(mask)


def loss_fn(params, batch, cfg, mesh=None):
    """Next-token cross-entropy; batch: {'tokens': [B, S+1]} or
    {'inputs': [B,S], 'targets': [B,S]} (+ optional 'mask').

    When cfg.loss_chunk divides the sequence, the head projection +
    log-softmax run as a rematerialized lax.scan over sequence chunks, so
    peak activation memory is [B, chunk, vocab] fp32 instead of the full
    [B, S, vocab] in BOTH the forward and backward pass."""
    if "tokens" in batch:
        inputs = batch["tokens"][:, :-1]
        targets = batch["tokens"][:, 1:]
    else:
        inputs, targets = batch["inputs"], batch["targets"]
    x = hidden_states(params, inputs, cfg, mesh=mesh)
    return _loss_from_hidden(x, params["lm_head"], targets,
                             batch.get("mask"), cfg.loss_chunk)


@jax.named_scope("loss")
def _loss_from_hidden(x, lm_head, targets, mask, chunk):
    B, S, D = x.shape
    if chunk and S % chunk:
        # snap to the largest divisor of S that fits the requested bound so
        # an off-size sequence never silently reverts to full-logit memory
        chunk = next((c for c in range(min(chunk, S), 0, -1) if S % c == 0))
        if chunk < 32:
            chunk = 0  # degenerate chunking would be slower than the memory win
    if not chunk or S == chunk:
        loss_sum, count = _ce_sums(x, lm_head, targets, mask)
        return loss_sum / jnp.maximum(count, 1)

    n = S // chunk
    xs = jnp.moveaxis(x.reshape(B, n, chunk, D), 1, 0)
    ts = jnp.moveaxis(targets.reshape(B, n, chunk), 1, 0)
    ms = None if mask is None else jnp.moveaxis(mask.reshape(B, n, chunk), 1, 0)

    @jax.checkpoint
    def body(carry, sl):
        loss_sum, count = carry
        s, c = _ce_sums(sl["x"], lm_head, sl["t"], sl.get("m"))
        return (loss_sum + s, count + c), None

    sl = {"x": xs, "t": ts}
    if ms is not None:
        sl["m"] = ms
    (loss_sum, count), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), sl
    )
    return loss_sum / jnp.maximum(count, 1)


def num_params(params):
    return sum(int(x.size) for x in jax.tree.leaves(params))
