"""Nemotron-H-style hybrid decoder (NVIDIA Nemotron-3-Super-120B-A12B,
`model_type: nemotron_h`): Mamba-2 layers, a few attention layers and
latent mixture-of-experts layers, each layer ONE of the three, in the
order a pattern string lists (`M`, `*`, `E`).

Per sequence, x [T, D], eps `norm_eps`:

  layer i (kind = pattern[i]):  x = x + mixer_i(rms_norm(x, norm_i))
  after the last layer:         rms_norm(x, final_norm) @ lm_head   (untied)

  M, Mamba-2 (H heads of P channels, N state columns, G groups of heads,
              d_inner = H * P, conv_dim = d_inner + 2 * G * N):
    z, xBC, dt = split(x @ in_proj, [d_inner, conv_dim, H])        no bias
    xBC = silu(causal_depthwise_conv1d(xBC, conv_w) + conv_b)
    xs [H, P], B [G, N], C [G, N] = split(xBC); head h reads group h // (H/G)
    dt = softplus(dt + dt_bias)  (no clamp);  A = -exp(A_log)  [H]
    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] xs_t[h] B_t[g]^T,
             S_0 = 0, [P, N] float32
    y_t[h] = S_t[h] C_t[g] + D[h] xs_t[h]
    y = y * silu(z); y = rms_norm over each group of d_inner / G channels,
        weight [d_inner];  out = y @ out_proj                      no bias

  *, attention: grouped-query, causal, scale head_dim ** -0.5, no bias,
     no rotary embedding.

  E, latent mixture of experts (n routed, top k, latent width R, expert
     width F, one shared expert of width Fs):
    s = sigmoid(x @ W_r) in float32, [n]
    chosen = top_k(s + b);  w = s[chosen]; w = w / (sum(w) + 1e-20);
    w = routed_scale * w
    u = x @ latent_down [D, R]
    r = sum over e in chosen and held of w_e relu(u @ W1_e)**2 @ W2_e
    out = r @ latent_up [R, D] + relu(x @ Ws1)**2 @ Ws2

A share of the experts (`experts_held = (first, count)`): the router
keeps its n outputs and its k picks over all of them, the weights are
normalised over all k, and only the picks that fall on the experts whose
leaves are here add to r; what the absent ones would have added is left
out, and that partial result goes on (one of the chips that share each
layer, without its exchange). `latent_moe` also counts the pairs it
routed and those that fell on held experts.

The same pure-pytree design as models/jamba.py, one stack per kind of
layer (`mamba2_layers`, `attn_layers`, `moe_layers`), walked in the
pattern's order by `jamba.scan_layers`. The multi-token-prediction
module of the published model (one more `*E` pair that drafts the token
after next) is not built: the main model's logits do not depend on it.

`conv_w` is [d_conv, conv_dim] (`conv_w[k]` multiplies the input
d_conv-1-k positions back; published [conv_dim, 1, d_conv]), as in
models/jamba.py.
"""

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp

from ..ops import moe, ssm
from ..ops.attention import attention
from ..ops.norms import rms_norm
from .jamba import layer_at, scan_layers

# an expert's buffer holds this many times its even share of a step's
# pairs (22 rows at 128 tokens, 22 picks of 512); a step that sends some
# expert more runs with lossless buffers (ops/moe.py, `exact`): no pair
# is ever dropped, and under routing near balance no step pays for
# buffers as deep as its tokens
CAPACITY_FACTOR = 4.0

KINDS = {"M": "mamba2", "*": "attention", "E": "ffn"}
STACKS = {"mamba2": "mamba2_layers", "attention": "attn_layers",
          "ffn": "moe_layers"}


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131_072
    dim: int = 4096
    pattern: str = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                    "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    n_routed_experts: int = 512
    # (first, count): the experts whose leaves are here; None: all
    experts_held: tuple = None
    experts_per_tok: int = 22
    moe_latent: int = 1024
    expert_dim: int = 2688
    shared_expert_dim: int = 5376
    routed_scale: float = 5.0
    max_seq_len: int = 262_144
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    attention_impl: str = "auto"

    def __post_init__(self):
        unknown = set(self.pattern) - set(KINDS)
        if unknown:
            raise ValueError(
                "a layer pattern is made of M (Mamba-2), * (attention) and "
                "E (experts); %r has %s" % (self.pattern, sorted(unknown)))
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.n_routed_experts:
            raise ValueError("experts_held %r lies outside the %d routed"
                             % (self.experts_held, self.n_routed_experts))

    @property
    def n_layers(self):
        return len(self.pattern)

    @property
    def layer_kinds(self):
        """The kind of every layer, in the model's order."""
        return tuple(KINDS[c] for c in self.pattern)

    @property
    def d_inner(self):
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.n_groups * self.ssm_state

    @property
    def held(self):
        """(first, count) of the experts whose leaves are here."""
        return self.experts_held or (0, self.n_routed_experts)

    @staticmethod
    def nemotron_3_super(**kw):
        return replace(NemotronHConfig(), **kw)

    @staticmethod
    def tiny(**kw):
        """Test-sized config (CPU-runnable): every kind of layer, two
        groups of heads, more experts than a token picks."""
        return replace(
            NemotronHConfig(
                vocab_size=256, dim=64, pattern="MEM*EME", n_heads=4,
                n_kv_heads=2, head_dim=16, mamba_heads=8, mamba_head_dim=8,
                ssm_state=16, n_groups=2, chunk_size=8, n_routed_experts=8,
                experts_per_tok=3, moe_latent=32, expert_dim=48,
                shared_expert_dim=96, routed_scale=2.5, max_seq_len=256,
                dtype="float32",
            ),
            **kw,
        )


def param_dtype(cfg):
    return jnp.dtype(cfg.dtype)


def leaf_shapes(cfg):
    """{leaf path: (shape, fan_in or None)}: every leaf's shape, and the
    fan-in of the matrices drawn N(0, 1/fan_in); None is a leaf with an
    initial value of its own (`init_params`)."""
    D, V = cfg.dim, cfg.vocab_size
    H, KV, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Di, Mh, K = cfg.d_inner, cfg.mamba_heads, cfg.conv_kernel
    R, F, Fs = cfg.moe_latent, cfg.expert_dim, cfg.shared_expert_dim
    kinds = cfg.layer_kinds
    Lm, La, Le = (kinds.count(k) for k in ("mamba2", "attention", "ffn"))
    held = cfg.held[1]
    return {
        ("embed",): ((V, D), D),
        ("final_norm",): ((D,), None),
        ("lm_head",): ((D, V), D),
        ("mamba2_layers", "ssm_norm"): ((Lm, D), None),
        ("mamba2_layers", "in_proj"): ((Lm, D, Di + cfg.conv_dim + Mh), D),
        ("mamba2_layers", "conv_w"): ((Lm, K, cfg.conv_dim), K),
        ("mamba2_layers", "conv_b"): ((Lm, cfg.conv_dim), None),
        ("mamba2_layers", "dt_bias"): ((Lm, Mh), None),
        ("mamba2_layers", "A_log"): ((Lm, Mh), None),
        ("mamba2_layers", "D"): ((Lm, Mh), None),
        ("mamba2_layers", "gate_norm"): ((Lm, Di), None),
        ("mamba2_layers", "out_proj"): ((Lm, Di, D), Di),
        ("attn_layers", "attn_norm"): ((La, D), None),
        ("attn_layers", "wq"): ((La, D, H * Hd), D),
        ("attn_layers", "wk"): ((La, D, KV * Hd), D),
        ("attn_layers", "wv"): ((La, D, KV * Hd), D),
        ("attn_layers", "wo"): ((La, H * Hd, D), H * Hd),
        ("moe_layers", "ffn_norm"): ((Le, D), None),
        ("moe_layers", "router"): ((Le, D, cfg.n_routed_experts), D),
        ("moe_layers", "router_bias"): ((Le, cfg.n_routed_experts), None),
        ("moe_layers", "latent_down"): ((Le, D, R), D),
        ("moe_layers", "latent_up"): ((Le, R, D), R),
        ("moe_layers", "w_up"): ((Le, held, R, F), R),
        ("moe_layers", "w_down"): ((Le, held, F, R), F),
        ("moe_layers", "shared_up"): ((Le, D, Fs), D),
        ("moe_layers", "shared_down"): ((Le, Fs, D), Fs),
    }


def _init_leaf(key, name, shape, fan_in):
    if fan_in is not None:
        return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
    if name == "dt_bias":   # inverse softplus of a log-uniform step
        step = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        return step + jnp.log(-jnp.expm1(-step))
    if name == "A_log":     # A uniform in [1, 16], a scalar a head
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1., 16.))
    zeros = name in ("conv_b", "router_bias")
    return (jnp.zeros if zeros else jnp.ones)(shape, jnp.float32)


def init_params(rng, cfg):
    """The parameter pytree. Matrices N(0, 1/fan_in); norms and D ones;
    conv_b and the router's selection bias zeros; dt_bias the inverse
    softplus of a log-uniform step in [1e-3, 1e-1] and A uniform in
    [1, 16], as Mamba-2 initialises them. The head is not tied."""
    shapes = leaf_shapes(cfg)
    tree = {}
    for key, (path, (shape, fan_in)) in zip(
            jax.random.split(rng, len(shapes)), shapes.items()):
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = _init_leaf(key, path[-1], shape, fan_in).astype(
            param_dtype(cfg))
    return tree


def logical_axes(cfg):
    """Logical axis names for every parameter (same tree structure):
    d_inner and the experts' widths ride the 'mlp' axis, the experts
    the 'expert' axis."""
    return {
        "embed": ("vocab", "embed"),
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
        "mamba2_layers": {
            "ssm_norm": ("layers", "embed"),
            "in_proj": ("layers", "embed", "mlp"),
            "conv_w": ("layers", None, "mlp"),
            "conv_b": ("layers", "mlp"),
            "dt_bias": ("layers", None),
            "A_log": ("layers", None),
            "D": ("layers", None),
            "gate_norm": ("layers", "mlp"),
            "out_proj": ("layers", "mlp", "embed"),
        },
        "attn_layers": {
            "attn_norm": ("layers", "embed"),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
        },
        "moe_layers": {
            "ffn_norm": ("layers", "embed"),
            "router": ("layers", "embed", None),
            "router_bias": ("layers", None),
            "latent_down": ("layers", "embed", None),
            "latent_up": ("layers", None, "embed"),
            "w_up": ("layers", "expert", None, "mlp"),
            "w_down": ("layers", "expert", "mlp", None),
            "shared_up": ("layers", "embed", "mlp"),
            "shared_down": ("layers", "mlp", "embed"),
        },
    }


def moe_leaves(stack, i):
    """Layer i's leaves of the stack of expert layers. The routed
    experts' two matrices are FUNCTIONS that cut the layer out of the
    stack (ops/moe.py takes either): the expert layer runs one of two
    branches of a `cond`, and a layer cut out here would be the `cond`'s
    operand, which the compiler materialises: 1.4 GB copied a layer and
    step at the published sizes, where a branch that cuts for itself
    reads the stack in place."""
    big = ("w_up", "w_down")
    lp = layer_at({k: v for k, v in stack.items() if k not in big}, i)
    for name in big:
        lp[name] = lambda name=name: jax.lax.dynamic_index_in_dim(
            stack[name], i, 0, keepdims=False)
    return lp


# ---- the blocks ----

def mamba2_mixer(cfg, lp, x, conv_tail, S, valid=None, at=None):
    """The Mamba-2 mixer over T new positions of normed input x
    [B, T, D], continuing from (conv_tail [B, K-1, conv_dim], S
    [B, H, P, N] float32). Matmuls in the model's dtype; convolution,
    softplus, the recurrence and the gated norm in float32. One position
    is the one-token update, several the chunk form (ops/ssm.py).
    Returns (out [B, T, D], conv tail, S): tail and state after the last
    valid position. at: None, or (layer, lanes) in a decode step of a
    whole pool: S is then the pool [layers, B, H, P, N], whose layer
    `layer` (traced) is updated in place for the lanes that decode
    (`ssm.ssd_update_pool`), and the pool is what comes back."""
    B, T, _ = x.shape
    H, P, N, G = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state,
                  cfg.n_groups)
    Di = cfg.d_inner
    with jax.named_scope("ssd_in_proj"):
        zxbcdt = x @ lp["in_proj"]
        z = zxbcdt[..., :Di]
        xBC = zxbcdt[..., Di:Di + cfg.conv_dim]
        dt = zxbcdt[..., Di + cfg.conv_dim:]
    with jax.named_scope("ssd_conv"):
        conv, conv_tail = ssm.causal_conv(xBC, conv_tail, lp["conv_w"],
                                          lp["conv_b"], valid)
        xBC = jax.nn.silu(conv).astype(x.dtype)
        xs = xBC[..., :Di].reshape(B, T, H, P)
        Bm = xBC[..., Di:Di + G * N].reshape(B, T, G, N)
        Cm = xBC[..., Di + G * N:].reshape(B, T, G, N)
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + lp["dt_bias"].astype(jnp.float32))
        A = -jnp.exp(lp["A_log"].astype(jnp.float32))
    if T == 1:
        with jax.named_scope("ssd_state_update"):
            step = (xs[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], lp["D"])
            if at is None:
                y, S = ssm.ssd_step(
                    S, *step, None if valid is None else valid[:, 0])
            else:
                y, S = ssm.ssd_update_pool(S, at[0], *step, at[1])
            y = y[:, None]
    else:
        with jax.named_scope("ssd_chunk"):
            y, S = ssm.ssd_chunk(S, xs, dt, A, Bm, Cm, lp["D"], valid,
                                 chunk=cfg.chunk_size)
    with jax.named_scope("ssd_gate_norm"):
        y = y.reshape(B, T, G, Di // G) \
            * jax.nn.silu(z.astype(jnp.float32)).reshape(B, T, G, Di // G)
        y = y * jax.lax.rsqrt(
            jnp.mean(y * y, axis=-1, keepdims=True) + cfg.norm_eps)
        y = (y.reshape(B, T, Di)
             * lp["gate_norm"].astype(jnp.float32)).astype(x.dtype)
    with jax.named_scope("ssd_out_proj"):
        return y @ lp["out_proj"], conv_tail, S


def latent_moe(cfg, lp, h, valid=None):
    """The expert layer's addend for normed input h [B, T, D], and the
    pairs it made: (out [B, T, D], [pairs routed, pairs that fell on
    held experts] uint32). The router reads h and picks over every
    routed expert; the experts whose leaves are here work in the latent
    width (`w_up`, `w_down`: arrays, or `moe_leaves`' functions); the
    shared expert reads h. A token that is not `valid` (a
    lane that holds no decoding request, a row's padding) is sent to no
    expert and counts for nothing; its output is garbage nobody reads."""
    k = cfg.experts_per_tok
    first, count = cfg.held
    weights, idx = moe.route(h, lp["router"], k, "sigmoid_bias",
                             bias=lp["router_bias"], scale=cfg.routed_scale,
                             dtype=jnp.float32)
    with jax.named_scope("moe_router"):
        real = jnp.ones(h.shape[:2], bool) if valid is None else valid
        here = (idx >= first) & (idx < first + count) & real[..., None]
        pairs = jnp.stack([k * jnp.sum(real), jnp.sum(here)]).astype(
            jnp.uint32)
    with jax.named_scope("moe_latent_down"):
        u = h @ lp["latent_down"]
    r, _ = moe.moe_ffn(
        u, None, None, lp["w_up"], lp["w_down"], num_experts_per_tok=k,
        capacity_factor=CAPACITY_FACTOR, activation=moe.relu2,
        routing=(weights, idx),
        held=(cfg.n_routed_experts, first), valid=valid, exact=True)
    with jax.named_scope("moe_latent_up"):
        out = r.astype(h.dtype) @ lp["latent_up"]
    with jax.named_scope("moe_shared_expert"):
        out = out + moe.relu2(h @ lp["shared_up"]) @ lp["shared_down"]
    return out, pairs


def _attention_layer(cfg, x, lp, mesh):
    B, S, _ = x.shape
    H, KV, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q = (h @ lp["wq"]).reshape(B, S, H, Hd)
    k = (h @ lp["wk"]).reshape(B, S, KV, Hd)
    v = (h @ lp["wv"]).reshape(B, S, KV, Hd)
    attn = attention(q, k, v, causal=True, impl=cfg.attention_impl,
                     mesh=mesh)
    return x + attn.reshape(B, S, H * Hd) @ lp["wo"]


def _mamba2_layer(cfg, x, lp):
    B = x.shape[0]
    tail = jnp.zeros((B, cfg.conv_kernel - 1, cfg.conv_dim), x.dtype)
    S0 = jnp.zeros((B, cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state),
                   jnp.float32)
    out, _, _ = mamba2_mixer(
        cfg, lp, rms_norm(x, lp["ssm_norm"], cfg.norm_eps), tail, S0)
    return x + out


@jax.named_scope("ffn")
def _moe_layer(cfg, x, lp):
    out, _ = latent_moe(cfg, lp, rms_norm(x, lp["ffn_norm"], cfg.norm_eps))
    return x + out


def forward(params, tokens, cfg, mesh=None):
    """tokens: [B, S] int32 -> logits [B, S, vocab] (float32): whole
    sequences from an empty state."""
    x = params["embed"][tokens].astype(param_dtype(cfg))

    def body(kind, i, x):
        if kind == "ffn":
            return _moe_layer(cfg, x, moe_leaves(params[STACKS[kind]], i))
        lp = layer_at(params[STACKS[kind]], i)
        if kind == "attention":
            return _attention_layer(cfg, x, lp, mesh)
        return _mamba2_layer(cfg, x, lp)

    with jax.named_scope("layers"):
        x = scan_layers(cfg.layer_kinds, body, x)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                      preferred_element_type=jnp.float32)
