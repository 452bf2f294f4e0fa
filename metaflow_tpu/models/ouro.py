"""Ouro-style looped decoder (ByteDance/Ouro-2.6B; "Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741): ONE stack of
layers that a token goes through `passes` times with the same weights.

With h_0 the embedding and t = 0 .. passes - 1 the pass:

    for layer i, in pass t:                  # weights of layer i, whatever t
        a = Attn_i(rms(x; attn_norm_i))      # causal, rope on q and k, plain
                                             # multi-head or grouped-query
        x = x + rms(a; attn_post_norm_i)     # sandwich: the sublayer's output
        m = SwiGLU_i(rms(x; ffn_norm_i))     #   is normed before it joins
        x = x + rms(m; ffn_post_norm_i)      #   the residual
    h_{t+1} = rms(x; final_norm)             # the model's norm closes EVERY
                                             #   pass; the next starts from it
    lambda_t = sigmoid(w_gate . h_{t+1} + b_gate)       # the exit gate
    logits = h_passes @ lm_head

The exit distribution is p_t = lambda_t prod_{j<t} (1 - lambda_j), the
rest on the last pass; a token leaves at the first pass whose CDF reaches
`exit_threshold`. At the published threshold of 1 only the last pass's
does, so every token runs every pass: that is what is built. A threshold
below 1 is refused (nothing here guesses what a token that left early
writes into the later passes' K and V).

The same pure-pytree design as models/llama.py: the llama tree plus the
two post norms in `layers` and the gate's `exit_gate_w` [dim] and
`exit_gate_b` []. Serving goes through inference/decode.py (every layer
is of kind "attention"; a pass's K and V live at pool index
`t * n_layers + i`: pass t attends to pass t's K and V only); `forward`
here runs whole sequences with no cache, each pass a full causal forward.
"""

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp

from ..exception import TpuFlowException
from ..ops.attention import attention
from ..ops.norms import rms_norm
from ..ops.rope import apply_rope, rope_frequencies


@dataclass(frozen=True)
class OuroConfig:
    vocab_size: int = 49_152
    dim: int = 2048
    n_layers: int = 48
    n_heads: int = 16
    n_kv_heads: int = 16
    head_dim: int = 128
    ffn_dim: int = 5632
    max_seq_len: int = 65_536
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-6
    # how many times a token goes through the stack (`total_ut_steps`)
    passes: int = 4
    # a token leaves at the first pass whose exit CDF reaches this
    exit_threshold: float = 1.0
    dtype: str = "bfloat16"
    attention_impl: str = "auto"

    def __post_init__(self):
        if self.passes < 1:
            raise TpuFlowException("passes must be >= 1, got %r"
                                   % (self.passes,))
        if self.exit_threshold < 1.0:
            raise TpuFlowException(
                "exit_threshold %r < 1: per-lane exit is not built (every "
                "token runs all %d passes; what a token that left early "
                "writes into the later passes' K and V is not defined here)"
                % (self.exit_threshold, self.passes))

    @staticmethod
    def ouro_2_6b(**kw):
        return replace(OuroConfig(), **kw)

    @staticmethod
    def tiny(**kw):
        """Test-sized config (CPU-runnable): 2 layers, 3 passes, so that
        an index mistake between pass and layer cannot cancel."""
        return replace(
            OuroConfig(
                vocab_size=512, dim=128, n_layers=2, n_heads=4, n_kv_heads=4,
                head_dim=32, ffn_dim=256, max_seq_len=256, passes=3,
                dtype="float32",
            ),
            **kw,
        )


def param_dtype(cfg):
    return jnp.dtype(cfg.dtype)


def leaf_shapes(cfg):
    """{leaf path: (shape, fan_in or None)}: None is a norm weight or the
    gate's bias, whose values are `init_params`'s to say."""
    L, D, F, V = cfg.n_layers, cfg.dim, cfg.ffn_dim, cfg.vocab_size
    H, KV, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        ("embed",): ((V, D), D),
        ("layers", "attn_norm"): ((L, D), None),
        ("layers", "wq"): ((L, D, H * Hd), D),
        ("layers", "wk"): ((L, D, KV * Hd), D),
        ("layers", "wv"): ((L, D, KV * Hd), D),
        ("layers", "wo"): ((L, H * Hd, D), H * Hd),
        ("layers", "attn_post_norm"): ((L, D), None),
        ("layers", "ffn_norm"): ((L, D), None),
        ("layers", "w_gate"): ((L, D, F), D),
        ("layers", "w_up"): ((L, D, F), D),
        ("layers", "w_down"): ((L, F, D), F),
        ("layers", "ffn_post_norm"): ((L, D), None),
        ("final_norm",): ((D,), None),
        ("exit_gate_w",): ((D,), D),
        ("exit_gate_b",): ((), None),
        ("lm_head",): ((D, V), D),
    }


def init_params(rng, cfg):
    """The parameter pytree. Matrices and the gate's weight N(0,
    1/fan_in); the gate's bias N(0, 1); norm weights ones, but for the
    two post norms: their gain is the size of what a sublayer adds to
    the stream, and (2 n_layers) ** -0.5 (the residual scaling of GPT-2's
    initialisation) keeps a stack that is run again and again over its
    own output from being a chaotic map."""
    shapes = leaf_shapes(cfg)
    tree = {}
    for key, (path, (shape, fan_in)) in zip(
            jax.random.split(rng, len(shapes)), shapes.items()):
        if fan_in is not None:
            leaf = jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
        elif path[-1] == "exit_gate_b":
            leaf = jax.random.normal(key, shape, jnp.float32)
        elif path[-1].endswith("post_norm"):
            leaf = jnp.full(shape, (2 * cfg.n_layers) ** -0.5, jnp.float32)
        else:
            leaf = jnp.ones(shape, jnp.float32)
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = leaf.astype(param_dtype(cfg))
    return tree


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
            "attn_post_norm": ("layers", "embed"),
            "ffn_norm": ("layers", "embed"),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
            "ffn_post_norm": ("layers", "embed"),
        },
        "final_norm": ("embed",),
        "exit_gate_w": ("embed",),
        "exit_gate_b": (),
        "lm_head": ("embed", "vocab"),
    }


def exit_gate(params, h):
    """lambda of one pass from its normed stream h [..., dim]: float32
    [...]."""
    return jax.nn.sigmoid(
        jnp.einsum("...d,d->...", h, params["exit_gate_w"],
                   preferred_element_type=jnp.float32)
        + params["exit_gate_b"].astype(jnp.float32))


def exit_cdf(gates):
    """gates [passes, ...], lambda_t of every pass -> the CDF of the exit
    distribution after each pass, [passes, ...]: p_t = lambda_t prod_{j<t}
    (1 - lambda_j), what is left on the last pass, so the last is 1."""
    stay = jnp.cumprod(1.0 - gates, axis=0)
    return jnp.concatenate([1.0 - stay[:-1], jnp.ones_like(stay[:1])])


def _layer(cfg, cos, sin, x, lp, mesh):
    B, S, _ = x.shape
    H, KV, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q = apply_rope((h @ lp["wq"]).reshape(B, S, H, Hd), cos, sin)
    k = apply_rope((h @ lp["wk"]).reshape(B, S, KV, Hd), cos, sin)
    v = (h @ lp["wv"]).reshape(B, S, KV, Hd)
    a = attention(q, k, v, causal=True, impl=cfg.attention_impl, mesh=mesh)
    x = x + rms_norm(a.reshape(B, S, H * Hd) @ lp["wo"],
                     lp["attn_post_norm"], cfg.norm_eps)
    h = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    m = (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]
    return x + rms_norm(m, lp["ffn_post_norm"], cfg.norm_eps)


def forward(params, tokens, cfg, mesh=None, exits=False):
    """tokens: [B, S] int32 -> logits [B, S, vocab] (float32): whole
    sequences with no cache, every pass a full causal forward over the
    stack. With `exits` also the exit CDF after each pass, [passes, B, S]
    float32."""
    dt = param_dtype(cfg)
    x = params["embed"][tokens].astype(dt)
    cos, sin = rope_frequencies(cfg.head_dim, tokens.shape[1],
                                cfg.rope_theta, dtype=dt)

    def one_pass(x, _):
        with jax.named_scope("layers"):
            x, _ = jax.lax.scan(
                lambda x, lp: (_layer(cfg, cos, sin, x, lp, mesh), None),
                x, params["layers"])
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, exit_gate(params, x)

    x, gates = jax.lax.scan(one_pass, x, None, length=cfg.passes)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    return (logits, exit_cdf(gates)) if exits else logits
