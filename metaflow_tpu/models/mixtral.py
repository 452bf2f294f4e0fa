"""Mixtral-style MoE transformer (BASELINE target: Mixtral-8x7B EP).

Same pure-pytree design as models/llama.py; the FFN is a top-2-of-N MoE
(ops/moe.py) whose expert dimension carries the 'expert' logical axis — on a
MeshSpec.moe mesh the experts are sharded across chips and dispatch becomes
an all-to-all.
"""

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp

from ..ops.attention import attention
from ..ops.moe import moe_ffn
from ..ops.norms import rms_norm
from ..ops.rope import apply_rope, rope_frequencies


@dataclass(frozen=True)
class MixtralConfig:
    vocab_size: int = 32_000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14_336
    n_experts: int = 8
    experts_per_tok: int = 2
    max_seq_len: int = 32_768
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    attention_impl: str = "auto"
    remat: bool = True
    router_aux_coef: float = 0.02
    # sparse = capacity-bucketed expert-parallel dispatch (ops/moe.py);
    # gmm = dropless grouped-matmul, single-shard experts;
    # gmm_ep = dropless composed with expert parallelism (a2a + local
    # gmm, bounded by ep_buffer_factor);
    # dense = the O(num_experts × tokens) oracle, debugging only
    moe_dispatch: str = "sparse"
    capacity_factor: float = 2.0
    ep_buffer_factor: float = None  # gmm_ep only; None = exact/dropless

    @property
    def head_dim(self):
        return self.dim // self.n_heads

    @staticmethod
    def mixtral_8x7b(**kw):
        return replace(MixtralConfig(), **kw)

    @staticmethod
    def tiny(**kw):
        return replace(
            MixtralConfig(
                vocab_size=512, dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
                ffn_dim=256, n_experts=4, experts_per_tok=2, max_seq_len=256,
                dtype="float32",
            ),
            **kw,
        )


def param_dtype(cfg):
    return jnp.dtype(cfg.dtype)


def init_params(rng, cfg):
    dt = param_dtype(cfg)
    k_embed, k_layers, k_out = jax.random.split(rng, 3)

    def dense(key, fan_in, *shape):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dt)

    L, D, F = cfg.n_layers, cfg.dim, cfg.ffn_dim
    H, KV, Hd, N = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.n_experts
    keys = jax.random.split(k_layers, 8)

    return {
        "embed": dense(k_embed, D, cfg.vocab_size, D),
        "layers": {
            "attn_norm": jnp.ones((L, D), dt),
            "wq": dense(keys[0], D, L, D, H * Hd),
            "wk": dense(keys[1], D, L, D, KV * Hd),
            "wv": dense(keys[2], D, L, D, KV * Hd),
            "wo": dense(keys[3], H * Hd, L, H * Hd, D),
            "ffn_norm": jnp.ones((L, D), dt),
            "router": dense(keys[4], D, L, D, N),
            "w_gate": dense(keys[5], D, L, N, D, F),
            "w_up": dense(keys[6], D, L, N, D, F),
            "w_down": dense(keys[7], F, L, N, F, D),
        },
        "final_norm": jnp.ones((D,), dt),
        "lm_head": dense(k_out, D, D, cfg.vocab_size),
    }


def logical_axes(cfg):
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn_norm": ("layers", "embed"),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "ffn_norm": ("layers", "embed"),
            "router": ("layers", "embed", None),
            "w_gate": ("layers", "expert", "embed", "mlp"),
            "w_up": ("layers", "expert", "embed", "mlp"),
            "w_down": ("layers", "expert", "mlp", "embed"),
        },
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def _layer(cfg, cos, sin, carry, layer_params, mesh=None):
    x, aux_sum = carry
    x = _attention_block(cfg, cos, sin, x, layer_params, mesh)
    with jax.named_scope("ffn"):
        moe_out, aux = _moe_block(cfg, x, layer_params, mesh)
    return (x + moe_out, aux_sum + aux), None


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
    attn = attention(q, k, v, causal=True, impl=cfg.attention_impl,
                     mesh=mesh)
    return x + attn.reshape(B, S, H * Hd) @ layer_params["wo"]


def _moe_block(cfg, x, layer_params, mesh):
    h = rms_norm(x, layer_params["ffn_norm"], cfg.norm_eps)
    return moe_ffn(
        h,
        layer_params["router"],
        layer_params["w_gate"],
        layer_params["w_up"],
        layer_params["w_down"],
        num_experts_per_tok=cfg.experts_per_tok,
        # gmm/gmm_ep are dropless: the capacity knob does not apply
        capacity_factor=(None if cfg.moe_dispatch in ("gmm", "gmm_ep")
                         else cfg.capacity_factor),
        dispatch=cfg.moe_dispatch,
        mesh=mesh,
        ep_buffer_factor=(cfg.ep_buffer_factor
                          if cfg.moe_dispatch == "gmm_ep" else None),
    )


def forward(params, tokens, cfg, return_aux=False, mesh=None):
    dt = param_dtype(cfg)
    x = params["embed"][tokens].astype(dt)
    cos, sin = rope_frequencies(cfg.head_dim, tokens.shape[1], cfg.rope_theta,
                                dtype=dt)

    layer_fn = lambda carry, lp: _layer(cfg, cos, sin, carry, lp, mesh=mesh)
    if cfg.remat:
        layer_fn = jax.checkpoint(layer_fn)
    with jax.named_scope("layers"):
        (x, aux), _ = jax.lax.scan(
            layer_fn, (x, jnp.zeros((), jnp.float32)), params["layers"]
        )

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    if return_aux:
        return logits, aux / cfg.n_layers
    return logits


def loss_fn(params, batch, cfg, mesh=None):
    if "tokens" in batch:
        inputs = batch["tokens"][:, :-1]
        targets = batch["tokens"][:, 1:]
    else:
        inputs, targets = batch["inputs"], batch["targets"]
    logits, aux = forward(params, inputs, cfg, return_aux=True, mesh=mesh)
    with jax.named_scope("loss"):
        logps = jax.nn.log_softmax(logits, axis=-1)
        token_lp = jnp.take_along_axis(
            logps, targets[..., None], axis=-1)[..., 0]
        ce = -jnp.mean(token_lp)
        return ce + cfg.router_aux_coef * aux


def num_params(params):
    return sum(int(x.size) for x in jax.tree.leaves(params))
