"""Brumby-style decoder (manifestai/Brumby-14B-Base): the Qwen3-14B block
with its softmax attention replaced by gated power retention of degree 2
(arXiv:2507.04239), no attention layer at all.

Block: x += retention(rms_norm(x)) Wo; x += swiglu(rms_norm(x)). The
retention layer projects q (H heads), k and v (KV heads) and one gate a
KV head, norms q and k per head (RMS, a weight a head size), rotates
them (rope, half-split), and mixes values with the weights
exp(sum log g) (q . k)^2 / head size, normalised by their sum
(ops/retention.py: the attention form inside a chunk, a carried state
[KV, head size, D] between chunks, so the cost of a token does not grow
with its position).

The same pure-pytree design as models/llama.py: one stack `layers` on a
leading layer axis, an untied `lm_head`. Serving goes through
inference/decode.py (the kind of layer is "retention"); `forward` here
runs whole sequences from an empty state, a chunk at a time.
"""

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp

from ..ops import retention
from ..ops.norms import rms_norm
from ..ops.rope import apply_rope, rope_frequencies

# positions a chunk of `forward`: the attention form's [T, T] weights
# stay small and the state carries between chunks
FORWARD_CHUNK = 128


@dataclass(frozen=True)
class BrumbyConfig:
    vocab_size: int = 151_936
    dim: int = 5120
    n_layers: int = 40
    n_heads: int = 40
    n_kv_heads: int = 8
    head_dim: int = 128
    ffn_dim: int = 17_408
    max_seq_len: int = 32_768
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-6
    # added to the sum of a position's weights before the division
    retention_eps: float = 1e-6
    dtype: str = "bfloat16"

    @property
    def layer_kinds(self):
        """The kind of every layer, in the model's order."""
        return ("retention",) * self.n_layers

    @staticmethod
    def brumby_14b(**kw):
        return replace(BrumbyConfig(), **kw)

    @staticmethod
    def tiny(**kw):
        """Test-sized config (CPU-runnable): a state of [2, 16, 144] a
        layer and slot."""
        return replace(
            BrumbyConfig(
                vocab_size=256, dim=64, n_layers=3, n_heads=4, n_kv_heads=2,
                head_dim=16, ffn_dim=128, max_seq_len=256, dtype="float32",
            ),
            **kw,
        )


def param_dtype(cfg):
    return jnp.dtype(cfg.dtype)


def leaf_shapes(cfg):
    """{leaf path: (shape, fan_in or None)}: None is a leaf with an
    initial value of its own (`init_params`)."""
    L, D, F, V = cfg.n_layers, cfg.dim, cfg.ffn_dim, cfg.vocab_size
    H, KV, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        ("embed",): ((V, D), D),
        ("layers", "attn_norm"): ((L, D), None),
        ("layers", "wq"): ((L, D, H * Hd), D),
        ("layers", "wk"): ((L, D, KV * Hd), D),
        ("layers", "wv"): ((L, D, KV * Hd), D),
        ("layers", "wg"): ((L, D, KV), D),
        ("layers", "bg"): ((L, KV), None),
        ("layers", "q_norm"): ((L, Hd), None),
        ("layers", "k_norm"): ((L, Hd), None),
        ("layers", "wo"): ((L, H * Hd, D), H * Hd),
        ("layers", "ffn_norm"): ((L, D), None),
        ("layers", "w_gate"): ((L, D, F), D),
        ("layers", "w_up"): ((L, D, F), D),
        ("layers", "w_down"): ((L, F, D), F),
        ("final_norm",): ((D,), None),
        ("lm_head",): ((D, V), D),
    }


def gate_bias_init(key, shape):
    """The logit of g = exp(-1 / tau) for a memory tau drawn log-uniform
    in [16, 4096] positions: a gate that a trained model would hold near
    one, so that the state carries what lies far back."""
    tau = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                     jnp.log(16.0), jnp.log(4096.0)))
    return -jnp.log(jnp.expm1(1.0 / tau))


def init_params(rng, cfg):
    """The parameter pytree. Matrices N(0, 1/fan_in); norm weights ones;
    the gate's bias `gate_bias_init`."""
    shapes = leaf_shapes(cfg)
    tree = {}
    for key, (path, (shape, fan_in)) in zip(
            jax.random.split(rng, len(shapes)), shapes.items()):
        if fan_in is not None:
            leaf = jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
        elif path[-1] == "bg":
            leaf = gate_bias_init(key, shape)
        else:
            leaf = jnp.ones(shape, jnp.float32)
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = leaf.astype(param_dtype(cfg))
    return tree


def logical_axes(cfg):
    """Logical axis names for every parameter (same tree structure): the
    gate rides the KV heads."""
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn_norm": ("layers", "embed"),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wg": ("layers", "embed", "kv_heads"),
            "bg": ("layers", "kv_heads"),
            "q_norm": ("layers", None),
            "k_norm": ("layers", None),
            "wo": ("layers", "heads", "embed"),
            "ffn_norm": ("layers", "embed"),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        },
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def log_gate(lp, h):
    """log g of every KV head, float32: log_sigmoid(h Wg + bg)."""
    return jax.nn.log_sigmoid((h @ lp["wg"]).astype(jnp.float32)
                              + lp["bg"].astype(jnp.float32))


def _layer(cfg, cos, sin, x, lp):
    B, S, _ = x.shape
    H, KV, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q = rms_norm((h @ lp["wq"]).reshape(B, S, H, Hd), lp["q_norm"],
                 cfg.norm_eps)
    k = rms_norm((h @ lp["wk"]).reshape(B, S, KV, Hd), lp["k_norm"],
                 cfg.norm_eps)
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    v = (h @ lp["wv"]).reshape(B, S, KV, Hd)
    log_g = log_gate(lp, h)

    def one_chunk(state, at):
        y, S_, z = retention.chunk(*state, *at, cfg.retention_eps)
        return (S_, z), y

    # [B, S, ...] -> [chunks, B, T, ...]; S is a multiple of T (forward)
    T = min(S, FORWARD_CHUNK)
    cut = lambda a: jnp.moveaxis(
        a.reshape((B, S // T, T) + a.shape[2:]), 1, 0)
    D = retention.state_dim(Hd)
    state = (jnp.zeros((B, KV, Hd, D), jnp.float32),
             jnp.zeros((B, KV, D), jnp.float32))
    _, y = jax.lax.scan(one_chunk, state, tuple(map(cut, (q, k, v, log_g))))
    y = jnp.moveaxis(y, 0, 1).reshape(B, S, H * Hd).astype(x.dtype)
    x = x + y @ lp["wo"]
    h = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    return x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) \
        @ lp["w_down"]


def forward(params, tokens, cfg, mesh=None):
    """tokens: [B, S] int32 -> logits [B, S, vocab] (float32): whole
    sequences from an empty state. S is padded to whole chunks (what
    follows a position never reaches it)."""
    del mesh
    B, S = tokens.shape
    pad = -S % min(S, FORWARD_CHUNK)
    tokens = jnp.pad(tokens, ((0, 0), (0, pad)))
    cos, sin = rope_frequencies(cfg.head_dim, S + pad, cfg.rope_theta,
                                dtype=param_dtype(cfg))
    x = params["embed"][tokens].astype(param_dtype(cfg))

    def body(x, lp):
        return _layer(cfg, cos, sin, x, lp), None

    with jax.named_scope("layers"):
        x, _ = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x[:, :S], params["final_norm"], cfg.norm_eps)
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                      preferred_element_type=jnp.float32)
