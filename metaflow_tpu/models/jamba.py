"""Jamba-style hybrid decoder (AI21 Jamba2-3B): Mamba-1 state-space
layers with an attention layer every `attn_layer_period`, a SwiGLU MLP
after every mixer, a head tied to the embedding.

Block i: x += mixer_i(rms_norm(x)); x += swiglu(rms_norm(x)). The mixer
is multi-query attention WITHOUT a positional embedding where
i % attn_layer_period == attn_layer_offset, else a Mamba-1 selective
state-space mixer with Jamba's three inner RMS norms (on dt, B and C).
The pattern is computed from period and offset, never listed.

The same pure-pytree design as models/llama.py, with TWO stacks, one per
kind of layer: `attn_layers` (the Llama block's leaves) and
`mamba_layers`, each stacked on a leading layer axis in the order its
layers occur. `scan_layers` walks them in the model's order with one
compiled body per run of a kind inside a period (`layer_plan`: this
model's pattern is one period twice over, its one-segment case), so
compile time does not grow with depth.

Leaves are stored as published except where the chip's tiling wants
the channels minor: `conv_w` is [d_conv, d_inner] (`conv_w[k]`
multiplies the input d_conv-1-k positions back; published
[d_inner, 1, d_conv]). `A_log` stays [d_inner, d_state].
"""

import functools
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp

from ..ops import ssm
from ..ops.attention import attention
from ..ops.norms import rms_norm


@dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65_536
    dim: int = 2560
    n_layers: int = 28
    n_heads: int = 20
    n_kv_heads: int = 1
    ffn_dim: int = 8192
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 160
    mamba_expand: int = 2
    max_seq_len: int = 262_144
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    attention_impl: str = "auto"

    @property
    def head_dim(self):
        return self.dim // self.n_heads

    @property
    def d_inner(self):
        return self.mamba_expand * self.dim

    @property
    def layer_kinds(self):
        """The kind of every layer, in the model's order."""
        return tuple(
            "attention"
            if i % self.attn_layer_period == self.attn_layer_offset
            else "mamba" for i in range(self.n_layers))

    @staticmethod
    def jamba2_3b(**kw):
        return replace(JambaConfig(), **kw)

    @staticmethod
    def tiny(**kw):
        """Test-sized config (CPU-runnable): two periods of four."""
        return replace(
            JambaConfig(
                vocab_size=256, dim=64, n_layers=8, n_heads=4, n_kv_heads=1,
                ffn_dim=128, attn_layer_period=4, attn_layer_offset=2,
                mamba_d_state=16, mamba_d_conv=4, mamba_dt_rank=8,
                max_seq_len=256, dtype="float32",
            ),
            **kw,
        )


def param_dtype(cfg):
    return jnp.dtype(cfg.dtype)


def leaf_shapes(cfg):
    """{leaf path: (shape, fan_in or None)}: every leaf's shape, and the
    fan-in of the matrices drawn N(0, 1/fan_in); None is a leaf with an
    initial value of its own (`init_params`)."""
    D, F, V = cfg.dim, cfg.ffn_dim, cfg.vocab_size
    H, KV, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Di, N, K, R = (cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv,
                   cfg.mamba_dt_rank)
    La = cfg.layer_kinds.count("attention")
    Lm = cfg.n_layers - La
    shapes = {("embed",): ((V, D), D), ("final_norm",): ((D,), None)}
    for stack, L in (("attn_layers", La), ("mamba_layers", Lm)):
        shapes.update({
            (stack, "ffn_norm"): ((L, D), None),
            (stack, "w_gate"): ((L, D, F), D),
            (stack, "w_up"): ((L, D, F), D),
            (stack, "w_down"): ((L, F, D), F),
        })
    shapes.update({
        ("attn_layers", "attn_norm"): ((La, D), None),
        ("attn_layers", "wq"): ((La, D, H * Hd), D),
        ("attn_layers", "wk"): ((La, D, KV * Hd), D),
        ("attn_layers", "wv"): ((La, D, KV * Hd), D),
        ("attn_layers", "wo"): ((La, H * Hd, D), H * Hd),
        ("mamba_layers", "ssm_norm"): ((Lm, D), None),
        ("mamba_layers", "in_proj"): ((Lm, D, 2 * Di), D),
        ("mamba_layers", "conv_w"): ((Lm, K, Di), K),
        ("mamba_layers", "conv_b"): ((Lm, Di), None),
        ("mamba_layers", "x_proj"): ((Lm, Di, R + 2 * N), Di),
        ("mamba_layers", "dt_norm"): ((Lm, R), None),
        ("mamba_layers", "b_norm"): ((Lm, N), None),
        ("mamba_layers", "c_norm"): ((Lm, N), None),
        ("mamba_layers", "dt_proj"): ((Lm, R, Di), R),
        ("mamba_layers", "dt_bias"): ((Lm, Di), None),
        ("mamba_layers", "A_log"): ((Lm, Di, N), None),
        ("mamba_layers", "D"): ((Lm, Di), None),
        ("mamba_layers", "out_proj"): ((Lm, Di, D), Di),
    })
    return shapes


def _init_leaf(key, name, shape, fan_in):
    if fan_in is not None:
        return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
    if name == "dt_bias":   # inverse softplus of a log-uniform step
        step = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        return step + jnp.log(-jnp.expm1(-step))
    if name == "A_log":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)), shape)
    return (jnp.zeros if name == "conv_b" else jnp.ones)(shape, jnp.float32)


def init_params(rng, cfg):
    """The parameter pytree. Matrices N(0, 1/fan_in); norms and D ones;
    conv_b zeros; dt_bias the inverse softplus of a log-uniform step in
    [1e-3, 1e-1] and A_log = log(1..d_state) as the Mamba paper
    initialises them. The head is tied: there is no `lm_head`."""
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
    d_inner rides the 'mlp' axis, like the MLP's hidden width."""
    mlp = {"ffn_norm": ("layers", "embed"),
           "w_gate": ("layers", "embed", "mlp"),
           "w_up": ("layers", "embed", "mlp"),
           "w_down": ("layers", "mlp", "embed")}
    return {
        "embed": ("vocab", "embed"),
        "attn_layers": dict(mlp, **{
            "attn_norm": ("layers", "embed"),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
        }),
        "mamba_layers": dict(mlp, **{
            "ssm_norm": ("layers", "embed"),
            "in_proj": ("layers", "embed", "mlp"),
            "conv_w": ("layers", None, "mlp"),
            "conv_b": ("layers", "mlp"),
            "x_proj": ("layers", "mlp", None),
            "dt_norm": ("layers", None),
            "b_norm": ("layers", None),
            "c_norm": ("layers", None),
            "dt_proj": ("layers", None, "mlp"),
            "dt_bias": ("layers", "mlp"),
            "A_log": ("layers", "mlp", None),
            "D": ("layers", "mlp"),
            "out_proj": ("layers", "mlp", "embed"),
        }),
        "final_norm": ("embed",),
    }


# ---- walking a stack of several kinds ----

@functools.lru_cache(maxsize=None)
def layer_plan(kinds):
    """The pattern of layer kinds (a tuple) as a list of repeated segments,
    [(repeats, runs, layers of each kind a period)]: a segment is a period
    that repeats `repeats` times, cut into runs of one kind, [(kind, index
    of the run's first layer among the period's layers of that kind,
    length)]. Of all ways to cut the pattern the one with the fewest runs
    (each is one traced body), then the fewest segments, then the
    shortest periods. A pattern that is one period over and over is one
    segment (Jamba2-3B: 2 x [mamba x 7, attention, mamba x 6]; a stack of
    one kind: n x [that kind]); one with no period of its own is several
    (Phi-4-mini-flash: 8 x [mamba, window], [mamba, full], 7 x [gmu,
    cross])."""
    n = len(kinds)

    def runs_of(period):
        runs, seen = [], {}
        for kind in period:
            if runs and runs[-1][0] == kind:
                runs[-1][2] += 1
            else:
                runs.append([kind, seen.get(kind, 0), 1])
            seen[kind] = seen.get(kind, 0) + 1
        return [tuple(r) for r in runs], seen

    # best[s]: (runs, segments, periods' lengths) and the plan of kinds[s:]
    best = {n: ((0, 0, 0), [])}
    for s in range(n - 1, -1, -1):
        for p in range(1, n - s + 1):
            runs, seen = runs_of(kinds[s:s + p])
            r = 1
            while True:
                cost, plan = best[s + r * p]
                cost = (cost[0] + len(runs), cost[1] + 1, cost[2] + p)
                if s not in best or cost < best[s][0]:
                    best[s] = (cost, [(r, runs, seen)] + plan)
                if kinds[s + r * p:s + (r + 1) * p] != kinds[s:s + p]:
                    break
                r += 1
    return best[0][1]


def scan_layers(kinds, body, carry, start=None):
    """Run `body(kind, i, carry) -> carry` for every layer in order; `i`
    is the layer's (traced) index within the stack of its kind, counted
    from `start[kind]` (a part of a model's layers: how many of each
    kind came before it). One traced body per run of the plan, whatever
    the depth."""
    first_of = dict(start or {})
    for repeats, runs, per_period in layer_plan(tuple(kinds)):

        def period(r, carry, runs=runs, per_period=per_period,
                   first_of=dict(first_of)):
            for kind, first, length in runs:
                base = r * per_period[kind] + (first_of.get(kind, 0) + first)
                if length == 1:
                    carry = body(kind, base, carry)
                else:
                    carry = jax.lax.fori_loop(
                        0, length,
                        lambda j, c, kind=kind, base=base: body(
                            kind, base + j, c),
                        carry)
            return carry

        carry = (period(0, carry) if repeats == 1
                 else jax.lax.fori_loop(0, repeats, period, carry))
        for kind, count in per_period.items():
            first_of[kind] = first_of.get(kind, 0) + repeats * count
    return carry


def layer_at(stack, i):
    """Layer i's leaves of a stacked tree."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        stack)


# ---- the blocks ----

def mamba_mixer(cfg, lp, x, conv_tail, h, valid=None, at=None):
    """The Mamba-1 mixer over T new positions of normed input x
    [B, T, D], continuing from (conv_tail [B, K-1, Di], h [B, N, Di]
    float32). Matmuls in the model's dtype; convolution, softplus and
    the recurrence in float32. The inner RMS norms on dt, B and C are
    Jamba's addition: applied where the layer's leaves hold them.
    Returns (out [B, T, D], conv tail, h, y): tail and state after the
    last valid position (ops/ssm.py), and the recurrence's output y
    [B, T, Di] in float32 before the gate, which a model with gated
    memory units hands on. at: None, or (layer, lanes) in a decode step
    of a whole pool: h is then the pool [layers, B, N, Di], whose layer
    `layer` (traced) is updated in place for the lanes that decode
    (`ssm.selective_update_pool`), and the pool is what comes back."""
    T = x.shape[1]
    Di, N, R = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    with jax.named_scope("ssm_in_proj"):
        uz = x @ lp["in_proj"]
        u, z = uz[..., :Di], uz[..., Di:]
    with jax.named_scope("ssm_conv"):
        conv, conv_tail = ssm.causal_conv(u, conv_tail, lp["conv_w"],
                                          lp["conv_b"], valid)
        u = jax.nn.silu(conv).astype(x.dtype)
    with jax.named_scope("ssm_x_proj"):
        dbc = u @ lp["x_proj"]
        inner = (lambda a, name: rms_norm(a, lp[name], cfg.norm_eps)) \
            if "dt_norm" in lp else (lambda a, name: a)
        dt = inner(dbc[..., :R], "dt_norm")
        Bm = inner(dbc[..., R:R + N], "b_norm")
        Cm = inner(dbc[..., R + N:], "c_norm")
        delta = jax.nn.softplus((dt @ lp["dt_proj"]).astype(jnp.float32)
                                + lp["dt_bias"].astype(jnp.float32))
        A = -jnp.exp(lp["A_log"].astype(jnp.float32)).T
    if T == 1:
        with jax.named_scope("ssm_state_update"):
            step = (u[:, 0], delta[:, 0], A, Bm[:, 0], Cm[:, 0], lp["D"])
            if at is None:
                y, h = ssm.selective_step(
                    h, *step, None if valid is None else valid[:, 0])
            else:
                y, h = ssm.selective_update_pool(h, at[0], *step, at[1])
            y = y[:, None]
    else:
        with jax.named_scope("ssm_scan"):
            y, h = ssm.selective_scan(h, u, delta, A, Bm, Cm, lp["D"], valid)
    with jax.named_scope("ssm_out_proj"):
        gated = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
        return gated @ lp["out_proj"], conv_tail, h, y


@jax.named_scope("ffn")
def mlp(cfg, x, lp):
    h = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    return x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) \
        @ lp["w_down"]


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


def _mamba_layer(cfg, x, lp):
    B = x.shape[0]
    tail = jnp.zeros((B, cfg.mamba_d_conv - 1, cfg.d_inner), x.dtype)
    h0 = jnp.zeros((B, cfg.mamba_d_state, cfg.d_inner), jnp.float32)
    out, _, _, _ = mamba_mixer(
        cfg, lp, rms_norm(x, lp["ssm_norm"], cfg.norm_eps), tail, h0)
    return x + out


def forward(params, tokens, cfg, mesh=None):
    """tokens: [B, S] int32 -> logits [B, S, vocab] (float32): whole
    sequences from an empty state."""
    x = params["embed"][tokens].astype(param_dtype(cfg))

    def body(kind, i, x):
        if kind == "attention":
            lp = layer_at(params["attn_layers"], i)
            return mlp(cfg, _attention_layer(cfg, x, lp, mesh), lp)
        lp = layer_at(params["mamba_layers"], i)
        return mlp(cfg, _mamba_layer(cfg, x, lp), lp)

    with jax.named_scope("layers"):
        x = scan_layers(cfg.layer_kinds, body, x)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return jnp.einsum("bsd,vd->bsv", x, params["embed"],
                      preferred_element_type=jnp.float32)
