"""Phi-4-mini-flash-reasoning (`model_type: phi4flash`; the architecture
is SambaY, arXiv:2507.06607): a self-decoder of Mamba-1 layers and
differential attention over a sliding window, ONE full-attention layer,
and a cross-decoder whose attention layers read that one layer's K and V
again and whose other layers are gated memory units over the last Mamba
layer's output. No positional embedding; LayerNorms with weight and
bias; a SwiGLU MLP after every mixer; a head tied to the embedding.

Block i: x += mixer_i(LN(x)); x += swiglu(LN(x)). With L layers and
half = L // 2 the mixer is, by the layer's index:

  i % mb_per_layer == 0, i <= half   "mamba": the Mamba-1 mixer of
        models/jamba.py without Jamba's inner norms; the last of them
        (index `memory_layer` among the Mamba layers) also hands the
        recurrence's output, before its gate, on as the MEMORY
  i odd, i < half                    "window": differential attention,
        a query sees itself and the `sliding_window` - 1 positions
        before it
  i == half + 1                      "full": differential attention,
        causal, every position; its K and V are the model's only global
        cache
  i odd, i >= half + 3               "cross": q alone is projected; K
        and V are the full layer's, read again; the same differential
        form
  i % mb_per_layer == 0, i > half    "gmu": out = (silu(h W1) * m) W2,
        m the memory at the same position; no state, no cache

Differential attention is ops/diff_attention.py: 2 * n_kv_heads // 2
key heads of `head_dim` under n_kv_heads // 2 value heads of twice that,
lam0(i) = 0.8 - 0.6 exp(-0.3 i) from the layer's index in the model.

The same pure-pytree design as models/jamba.py, one stack per kind of
layer (`mamba_layers`, `window_layers`, `full_layers`, `cross_layers`,
`gmu_layers`), each in the order its layers occur. Serving goes through
inference/decode.py, where nothing past the full layer's K and V at a
position is read by a later position, so a prompt's rows run the
cross-decoder for the position whose logits are read alone
(`tail_layer`); `forward` here runs every layer over every position.
"""

import math
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp

from ..ops import diff_attention
from ..ops.attention import attention
from ..ops.norms import layer_norm
from .jamba import _init_leaf, layer_at, mamba_mixer, scan_layers


@dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200_064
    dim: int = 2560
    n_layers: int = 32
    n_heads: int = 40
    n_kv_heads: int = 20
    ffn_dim: int = 10_240
    sliding_window: int = 512
    mb_per_layer: int = 2
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 160
    mamba_expand: int = 2
    max_seq_len: int = 262_144
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"

    @property
    def head_dim(self):
        return self.dim // self.n_heads

    @property
    def v_head_dim(self):
        """A pair's two key heads share one value head of twice their
        size."""
        return 2 * self.head_dim

    @property
    def d_inner(self):
        return self.mamba_expand * self.dim

    @property
    def layer_kinds(self):
        """The kind of every layer, in the model's order."""
        half = self.n_layers // 2
        kinds = []
        for i in range(self.n_layers):
            if i % self.mb_per_layer == 0:
                kinds.append("mamba" if i <= half else "gmu")
            else:
                kinds.append("window" if i < half else
                             "full" if i == half + 1 else "cross")
        return tuple(kinds)

    @property
    def memory_layer(self):
        """Which of the Mamba layers (its index among them) hands its
        recurrence's output on as the memory: the last."""
        return self.layer_kinds.count("mamba") - 1

    @property
    def tail_layer(self):
        """The layer from whose attention on a position's output is read
        by no later position: the full layer. A prompt's rows need it,
        and every layer after it, for the position whose logits are
        read alone; its K and V they need at every position."""
        return self.layer_kinds.index("full")

    @property
    def lambda_init(self):
        """{kind: lam0 of each of its layers}, from the layer's index in
        the model."""
        out = {}
        for i, kind in enumerate(self.layer_kinds):
            if kind in ("window", "full", "cross"):
                out.setdefault(kind, []).append(
                    0.8 - 0.6 * math.exp(-0.3 * i))
        return {kind: tuple(v) for kind, v in out.items()}

    @staticmethod
    def phi4_mini_flash(**kw):
        return replace(Phi4FlashConfig(), **kw)

    @staticmethod
    def tiny(**kw):
        """Test-sized config (CPU-runnable): [mamba, window] x 2, [mamba,
        full], [gmu, cross], a window of 8."""
        return replace(
            Phi4FlashConfig(
                vocab_size=256, dim=64, n_layers=8, n_heads=8, n_kv_heads=4,
                ffn_dim=128, sliding_window=8, mamba_dt_rank=8,
                max_seq_len=256, dtype="float32",
            ),
            **kw,
        )


def param_dtype(cfg):
    return jnp.dtype(cfg.dtype)


def leaf_shapes(cfg):
    """{leaf path: (shape, fan_in or None)}: every leaf's shape, and the
    fan-in of the matrices drawn N(0, 1/fan_in); None is a leaf with an
    initial value of its own (`init_params`). A norm `x` has its bias
    `x_b` beside it; a projection `wq` its bias `bq`."""
    D, F, V = cfg.dim, cfg.ffn_dim, cfg.vocab_size
    H, KV, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Di, N, K, R = (cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv,
                   cfg.mamba_dt_rank)
    count = cfg.layer_kinds.count
    shapes = {("embed",): ((V, D), D), ("final_norm",): ((D,), None),
              ("final_norm_b",): ((D,), None)}

    def stack(name, L, mixer_norm, leaves):
        for norm in (mixer_norm, "ffn_norm"):
            shapes[(name, norm)] = ((L, D), None)
            shapes[(name, norm + "_b")] = ((L, D), None)
        shapes.update({
            (name, "w_gate"): ((L, D, F), D),
            (name, "w_up"): ((L, D, F), D),
            (name, "w_down"): ((L, F, D), F)})
        shapes.update({(name, leaf): ((L,) + shape, fan_in)
                       for leaf, (shape, fan_in) in leaves.items()})

    q = {"wq": ((D, H * Hd), D), "bq": ((H * Hd,), None),
         "wo": ((H * Hd, D), H * Hd), "bo": ((D,), None),
         "lambda_q1": ((Hd,), None), "lambda_k1": ((Hd,), None),
         "lambda_q2": ((Hd,), None), "lambda_k2": ((Hd,), None),
         "subln": ((2 * Hd,), None)}
    kv = {"wk": ((D, KV * Hd), D), "bk": ((KV * Hd,), None),
          "wv": ((D, KV * Hd), D), "bv": ((KV * Hd,), None)}
    stack("mamba_layers", count("mamba"), "ssm_norm", {
        "in_proj": ((D, 2 * Di), D), "conv_w": ((K, Di), K),
        "conv_b": ((Di,), None), "x_proj": ((Di, R + 2 * N), Di),
        "dt_proj": ((R, Di), R), "dt_bias": ((Di,), None),
        "A_log": ((Di, N), None), "D": ((Di,), None),
        "out_proj": ((Di, D), Di)})
    stack("window_layers", count("window"), "attn_norm", dict(q, **kv))
    stack("full_layers", count("full"), "attn_norm", dict(q, **kv))
    stack("cross_layers", count("cross"), "attn_norm", q)
    stack("gmu_layers", count("gmu"), "gmu_norm", {
        "w_in": ((D, Di), D), "w_out": ((Di, D), Di)})
    return shapes


def init_params(rng, cfg):
    """The parameter pytree. Matrices N(0, 1/fan_in); norm weights, the
    sub-norm and D ones; every bias zeros; the four lambda vectors a
    layer N(0, 0.1); dt_bias and A_log as models/jamba.py draws them."""
    shapes = leaf_shapes(cfg)
    tree = {}
    for key, (path, (shape, fan_in)) in zip(
            jax.random.split(rng, len(shapes)), shapes.items()):
        name = path[-1]
        if name.startswith("lambda_"):
            leaf = 0.1 * jax.random.normal(key, shape, jnp.float32)
        elif fan_in is None and (name.endswith("_b") or name[0] == "b"):
            leaf = jnp.zeros(shape, jnp.float32)
        else:
            leaf = _init_leaf(key, name, shape, fan_in)
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[name] = leaf.astype(param_dtype(cfg))
    return tree


def logical_axes(cfg):
    """Logical axis names for every parameter (same tree structure):
    d_inner rides the 'mlp' axis, like the MLP's hidden width."""
    axes_of = {
        "w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
        "w_down": ("mlp", "embed"),
        "wq": ("embed", "heads"), "bq": ("heads",),
        "wk": ("embed", "kv_heads"), "bk": ("kv_heads",),
        "wv": ("embed", "kv_heads"), "bv": ("kv_heads",),
        "wo": ("heads", "embed"), "bo": ("embed",),
        "in_proj": ("embed", "mlp"), "conv_w": (None, "mlp"),
        "conv_b": ("mlp",), "x_proj": ("mlp", None),
        "dt_proj": (None, "mlp"), "dt_bias": ("mlp",),
        "A_log": ("mlp", None), "D": ("mlp",),
        "out_proj": ("mlp", "embed"),
        "w_in": ("embed", "mlp"), "w_out": ("mlp", "embed"),
    }
    tree = {"embed": ("vocab", "embed"), "final_norm": ("embed",),
            "final_norm_b": ("embed",)}
    for path, (shape, _) in leaf_shapes(cfg).items():
        if len(path) == 2:
            stack, name = path
            if "norm" in name:
                axes = ("embed",)
            else:   # the lambda vectors and the sub-norm: a head's size
                axes = axes_of.get(name, (None,))
            tree.setdefault(stack, {})[name] = ("layers",) + axes
    return tree


# ---- the blocks, over whole sequences ----

def _norm(cfg, x, lp, name):
    return layer_norm(x, lp[name], lp[name + "_b"], cfg.norm_eps)


@jax.named_scope("ffn")
def mlp(cfg, x, lp):
    h = _norm(cfg, x, lp, "ffn_norm")
    return x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) \
        @ lp["w_down"]


def gated_memory_unit(lp, h, memory):
    """(silu(h W1) * m) W2 of normed h [B, T, D] and the memory m
    [B, T, Di] at the same positions."""
    return (jax.nn.silu(h @ lp["w_in"]) * memory) @ lp["w_out"]


def _diff_attention(cfg, lp, h, k, v, lam0, window):
    """h [B, S, D] normed -> the layer's attention output [B, S, D]; k
    [B, S, KV, Hd] and v [B, S, KV // 2, 2 Hd] are this layer's own or
    the full layer's."""
    B, S, _ = h.shape
    H, KV, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (h @ lp["wq"] + lp["bq"]).reshape(B, S, H, Hd)
    out = attention(diff_attention.pair_major(q, KV), k, v, causal=True,
                    scale=1.0 / math.sqrt(Hd), window=window)
    y = diff_attention.combine(out, KV, lp, lam0, cfg.norm_eps, h.dtype)
    return y.reshape(B, S, H * Hd) @ lp["wo"] + lp["bo"]


def _kv(cfg, lp, h):
    B, S, _ = h.shape
    k = (h @ lp["wk"] + lp["bk"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = (h @ lp["wv"] + lp["bv"]).reshape(B, S, cfg.n_kv_heads // 2,
                                          cfg.v_head_dim)
    return k, v


def forward(params, tokens, cfg, mesh=None):
    """tokens: [B, S] int32 -> logits [B, S, vocab] (float32): whole
    sequences from an empty state, every layer over every position."""
    x = params["embed"][tokens].astype(param_dtype(cfg))
    B, S = tokens.shape
    stacks = {kind: params[kind + "_layers"] for kind in set(cfg.layer_kinds)}
    lam0 = {kind: jnp.asarray(v, jnp.float32)
            for kind, v in cfg.lambda_init.items()}

    def body(kind, i, carry):
        x, memory, shared = carry
        lp = layer_at(stacks[kind], i)
        if kind == "mamba":
            tail = jnp.zeros((B, cfg.mamba_d_conv - 1, cfg.d_inner), x.dtype)
            h0 = jnp.zeros((B, cfg.mamba_d_state, cfg.d_inner), jnp.float32)
            out, _, _, y = mamba_mixer(cfg, lp, _norm(cfg, x, lp, "ssm_norm"),
                                       tail, h0)
            memory = jnp.where(i == cfg.memory_layer, y.astype(x.dtype),
                               memory)
        elif kind == "gmu":
            out = gated_memory_unit(lp, _norm(cfg, x, lp, "gmu_norm"), memory)
        else:
            h = _norm(cfg, x, lp, "attn_norm")
            if kind != "cross":
                k, v = _kv(cfg, lp, h)
                if kind == "full":
                    shared = (k, v)
            else:
                k, v = shared
            out = _diff_attention(
                cfg, lp, h, k, v, lam0[kind][i],
                cfg.sliding_window if kind == "window" else None)
        return mlp(cfg, x + out, lp), memory, shared

    H, KV = cfg.n_heads, cfg.n_kv_heads
    carry = (x, jnp.zeros((B, S, cfg.d_inner), x.dtype),
             (jnp.zeros((B, S, KV, cfg.head_dim), x.dtype),
              jnp.zeros((B, S, KV // 2, cfg.v_head_dim), x.dtype)))
    with jax.named_scope("layers"):
        x, _, _ = scan_layers(cfg.layer_kinds, body, carry)
    x = layer_norm(x, params["final_norm"], params["final_norm_b"],
                   cfg.norm_eps)
    return jnp.einsum("bsd,vd->bsv", x, params["embed"],
                      preferred_element_type=jnp.float32)
