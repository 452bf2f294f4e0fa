"""Jamba (AI21-Jamba2-3B, `model_type: jamba`) through `models/jamba.py`,
written from these equations (per sequence, x [T, D], eps as published):

  block i:  x = x + mixer_i(rms_norm(x, input norm))
            x = x + swiglu(rms_norm(x, pre-feed-forward norm))
  mixer_i is attention where i % attn_layer_period == attn_layer_offset,
  else Mamba; after the last block rms_norm(x, final norm) @ embed.T
  (`tie_word_embeddings`: the tree has no `lm_head`).

  Mamba-1 mixer (d_inner = expand * D):
    u, z = split(x @ in_proj)                                  no bias
    u = silu(causal_depthwise_conv1d(u, conv_w) + conv_b)
    dt, B, C = split(u @ x_proj, [dt_rank, d_state, d_state])  no bias
    dt, B, C = rms_norm(dt), rms_norm(B), rms_norm(C)
    delta = softplus(dt @ dt_proj + dt_bias);  A = -exp(A_log)
    h_t = exp(delta_t[:, None] * A) * h_{t-1}
          + (delta_t * u_t)[:, None] * B_t[None, :],   h_0 = 0
    y_t = h_t @ C_t + D * u_t;  out = (y * silu(z)) @ out_proj

  Attention: multi-query, causal, scale head_dim ** -0.5, no positional
  embedding, no bias, no window.

Float32 at `highest`, the recurrence a plain `lax.scan` over time, one
layer of weights upcast at a time. Nothing of the program is imported
outside `program_config`.

Departures, each beside its line below: the three inner norms are
Jamba's addition to Mamba-1; the order of the layer types is not in the
published config and follows from period and offset by the family's
convention (`assumed` in the configuration file); `conv_w` is stored
[d_conv, d_inner], the program's layout, where the checkpoint has
[d_inner, 1, d_conv]; the recurrence's state is held [d_state, d_inner],
the equation's transpose, so that the chip's tiles are full;
`num_experts: 1` makes every feed-forward the
dense MLP, so `expert_layer_period`/`offset` select nothing.

`reference.served_gaps` pads every request to one shape with tokens
after the served ones. A causal recurrence, like a causal mask, never
lets a position see what follows it: the padding changes no logit that
is read.
"""

import functools

import jax
import jax.numpy as jnp

from ..reference import F32, attention, mm, rms_norm, swiglu


def dims(config):
    """The sizes the benchmark's own code reads, from the published
    keys; it raises on what the program cannot run."""
    for key, want in (("num_experts", 1), ("tie_word_embeddings", True),
                      ("mamba_proj_bias", False), ("mamba_conv_bias", True),
                      ("sliding_window", None), ("hidden_act", "silu")):
        if config[key] != want:
            raise ValueError("the program runs a jamba model with %s = %r "
                             "only; this configuration states %r"
                             % (key, want, config[key]))
    d = {
        "dim": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "ffn_dim": config["intermediate_size"],
        "vocab_size": config["vocab_size"],
        "norm_eps": float(config["rms_norm_eps"]),
        "dtype": config["torch_dtype"],
        "attn_layer_period": config["attn_layer_period"],
        "attn_layer_offset": config["attn_layer_offset"],
        "d_state": config["mamba_d_state"],
        "d_conv": config["mamba_d_conv"],
        "dt_rank": config["mamba_dt_rank"],
        "expand": config["mamba_expand"],
    }
    d["d_inner"] = d["expand"] * d["dim"]
    d["n_attn_layers"] = layer_kinds(d).count("attention")
    d["n_mamba_layers"] = d["n_layers"] - d["n_attn_layers"]
    return d


def layer_kinds(d):
    # not in the published config: the jamba family's convention
    return ["attention" if i % d["attn_layer_period"] == d["attn_layer_offset"]
            else "mamba" for i in range(d["n_layers"])]


def program_config(d, max_seq_len):
    from metaflow_tpu.models import jamba

    return jamba, jamba.JambaConfig(
        vocab_size=d["vocab_size"], dim=d["dim"], n_layers=d["n_layers"],
        n_heads=d["n_heads"], n_kv_heads=d["n_kv_heads"],
        ffn_dim=d["ffn_dim"], attn_layer_period=d["attn_layer_period"],
        attn_layer_offset=d["attn_layer_offset"],
        mamba_d_state=d["d_state"], mamba_d_conv=d["d_conv"],
        mamba_dt_rank=d["dt_rank"], mamba_expand=d["expand"],
        max_seq_len=int(max_seq_len), norm_eps=d["norm_eps"],
        dtype=d["dtype"])


def dt_bias_init(key, shape):
    """The inverse softplus of a step drawn log-uniform in [1e-3, 1e-1]
    (the Mamba paper's initialisation of the step size)."""
    step = jnp.exp(jax.random.uniform(key, shape, F32, jnp.log(1e-3),
                                      jnp.log(1e-1)))
    return step + jnp.log(-jnp.expm1(-step))


def a_log_init(key, shape):
    """log of a uniform draw in [1, 16], the range of the Mamba paper's
    A = 1..16 (drawn, so that the leaf depends on the seed)."""
    return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))


def leaf_specs(d):
    """Two stacks, one per kind of layer, each in the order its layers
    occur; the feed-forward leaves are in both. Stored in the
    configuration's dtype (`make_leaf` casts), upcast where used."""
    D, F, V = d["dim"], d["ffn_dim"], d["vocab_size"]
    H, KV, Hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    Di, N, K, R = d["d_inner"], d["d_state"], d["d_conv"], d["dt_rank"]
    La, Lm = d["n_attn_layers"], d["n_mamba_layers"]
    specs = {("embed",): ((V, D), D), ("final_norm",): ((D,), None)}
    for stack, L in (("attn_layers", La), ("mamba_layers", Lm)):
        specs.update({
            (stack, "ffn_norm"): ((L, D), None),
            (stack, "w_gate"): ((L, D, F), D),
            (stack, "w_up"): ((L, D, F), D),
            (stack, "w_down"): ((L, F, D), F),
        })
    specs.update({
        ("attn_layers", "attn_norm"): ((La, D), None),
        ("attn_layers", "wq"): ((La, D, H * Hd), D),
        ("attn_layers", "wk"): ((La, D, KV * Hd), D),
        ("attn_layers", "wv"): ((La, D, KV * Hd), D),
        ("attn_layers", "wo"): ((La, H * Hd, D), H * Hd),
        ("mamba_layers", "ssm_norm"): ((Lm, D), None),
        ("mamba_layers", "in_proj"): ((Lm, D, 2 * Di), D),
        ("mamba_layers", "conv_w"): ((Lm, K, Di), K),
        ("mamba_layers", "conv_b"): ((Lm, Di), K),
        ("mamba_layers", "x_proj"): ((Lm, Di, R + 2 * N), Di),
        ("mamba_layers", "dt_norm"): ((Lm, R), None),
        ("mamba_layers", "b_norm"): ((Lm, N), None),
        ("mamba_layers", "c_norm"): ((Lm, N), None),
        ("mamba_layers", "dt_proj"): ((Lm, R, Di), R),
        ("mamba_layers", "dt_bias"): ((Lm, Di), dt_bias_init),
        ("mamba_layers", "A_log"): ((Lm, Di, N), a_log_init),
        ("mamba_layers", "D"): ((Lm, Di), None),
        ("mamba_layers", "out_proj"): ((Lm, Di, D), Di),
    })
    return specs


# ---- the plain reference ----

def mamba_mixer(p, x, d, lowp=False):
    """x: [T, D] float32, already normed; p: this layer's weights as
    stored."""
    T = x.shape[0]
    Di, N, K, R = d["d_inner"], d["d_state"], d["d_conv"], d["dt_rank"]
    uz = mm(x, p["in_proj"], lowp)
    u, z = uz[:, :Di], uz[:, Di:]
    # conv_w[k] multiplies the input K-1-k positions back (the program's
    # layout of the checkpoint's [d_inner, 1, d_conv])
    padded = jnp.pad(u, ((K - 1, 0), (0, 0)))
    u = jax.nn.silu(p["conv_b"].astype(F32) + sum(
        padded[k:k + T] * p["conv_w"][k].astype(F32) for k in range(K)))
    dbc = mm(u, p["x_proj"], lowp)
    # the three inner norms are Jamba's addition to Mamba-1
    dt = rms_norm(dbc[:, :R], p["dt_norm"], d["norm_eps"])
    B = rms_norm(dbc[:, R:R + N], p["b_norm"], d["norm_eps"])
    C = rms_norm(dbc[:, R + N:], p["c_norm"], d["norm_eps"])
    delta = jax.nn.softplus(mm(dt, p["dt_proj"], lowp)
                            + p["dt_bias"].astype(F32))
    # the state is held transposed, [d_state, d_inner], so that the
    # chip's 8 x 128 tiles are full: the same numbers, eight times fewer
    # tiles a step
    A = -jnp.exp(p["A_log"].astype(F32)).T                  # [N, Di]

    def step(h, at):
        delta_t, u_t, B_t, C_t = at
        h = jnp.exp(delta_t[None, :] * A) * h \
            + (delta_t * u_t)[None, :] * B_t[:, None]
        return h, jnp.sum(h * C_t[:, None], 0)

    _, y = jax.lax.scan(step, jnp.zeros((N, Di), F32), (delta, u, B, C))
    y = y + p["D"].astype(F32) * u
    return mm(y * jax.nn.silu(z), p["out_proj"], lowp)


def attention_mixer(p, x, d, lowp=False):
    """x: [T, D] float32, already normed. No positional embedding."""
    T = x.shape[0]
    H, KV, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    q = mm(x, p["wq"], lowp).reshape(T, H, hd)
    k = mm(x, p["wk"], lowp).reshape(T, KV, hd)
    v = mm(x, p["wv"], lowp).reshape(T, KV, hd)
    return mm(attention(q, k, v, lowp), p["wo"], lowp)


def block(kind, p, x, d, lowp=False):
    if kind == "attention":
        x = x + attention_mixer(p, rms_norm(x, p["attn_norm"], d["norm_eps"]),
                                d, lowp)
    else:
        x = x + mamba_mixer(p, rms_norm(x, p["ssm_norm"], d["norm_eps"]),
                            d, lowp)
    h = rms_norm(x, p["ffn_norm"], d["norm_eps"])
    return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"], lowp)


@functools.lru_cache(maxsize=None)
def _jitted(dims_items, lowp):
    d = dict(dims_items)
    blocks = {kind: jax.jit(functools.partial(block, kind, d=d, lowp=lowp))
              for kind in ("attention", "mamba")}
    # the head is tied to the embedding
    top = jax.jit(lambda x, norm, embed: mm(
        rms_norm(x, norm, d["norm_eps"]), embed.T, lowp))
    return blocks, top


def logits(params, tokens, d, lowp=False):
    """Float32 logits [T, vocab] of one sequence of tokens, walking the
    two stacks in the model's order, one layer upcast at a time."""
    blocks, top = _jitted(tuple(sorted(d.items())), lowp)
    stacks = {"attention": params["attn_layers"],
              "mamba": params["mamba_layers"]}
    seen = {"attention": 0, "mamba": 0}
    x = params["embed"][jnp.asarray(tokens)].astype(F32)
    for kind in layer_kinds(d):
        i = seen[kind]
        x = blocks[kind](jax.tree.map(lambda a: a[i], stacks[kind]), x)
        seen[kind] += 1
    return top(x, params["final_norm"], params["embed"])


# ---- operations from shapes ----

def matmul_params(d, active_only=True):
    """Matmul parameters a token meets: the projections of every mixer,
    every MLP, and the tied head; the convolution, the norms and the
    recurrence are not matrix products and are not counted."""
    D, Di, N, R = d["dim"], d["d_inner"], d["d_state"], d["dt_rank"]
    mlp = 3 * D * d["ffn_dim"]
    mamba = D * 2 * Di + Di * (R + 2 * N) + R * Di + Di * D
    attn = D * d["head_dim"] * (2 * d["n_heads"] + 2 * d["n_kv_heads"])
    return (d["n_mamba_layers"] * (mamba + mlp)
            + d["n_attn_layers"] * (attn + mlp) + D * d["vocab_size"])
