"""Nemotron-H (NVIDIA-Nemotron-3-Super-120B-A12B, `model_type:
nemotron_h`) through `models/nemotron_h.py`, written from these
equations (per sequence, x [T, D], eps `norm_eps`):

  layer i (kind = hybrid_override_pattern[i]):
      x = x + mixer_i(rms_norm(x, norm_i))       one mixer a layer, no more
  after the last layer:  rms_norm(x, norm_f) @ lm_head             (untied)

  M, Mamba-2 (H heads, P = mamba_head_dim, N = ssm_state_size, G groups,
              d_inner = H * P, conv_dim = d_inner + 2 * G * N):
    z, xBC, dt = split(x @ in_proj, [d_inner, conv_dim, H])        no bias
    xBC = silu(causal_depthwise_conv1d(xBC, conv_w) + conv_b)
    xs [H, P], B [G, N], C [G, N] = split(xBC, [d_inner, G * N, G * N])
        head h reads group h // (H / G)
    dt = softplus(dt + dt_bias)   (time_step_limit (0, inf): no clamp)
    A = -exp(A_log)   [H]
    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] xs_t[h][:, None] B_t[g][None, :]
             S_0 = 0, [P, N]
    y_t[h] = S_t[h] @ C_t[g] + D[h] xs_t[h]
    y = y * silu(z);  y = rms_norm over each group of d_inner / G
        channels, weight [d_inner];  out = y @ out_proj            no bias

  *, attention: grouped-query, causal, scale head_dim ** -0.5, no bias.

  E, latent mixture of experts (n routed, top k, latent R, expert width F):
    s = sigmoid(x @ W_r), [n]
    chosen = top_k(s + b)      b = e_score_correction_bias
                               (n_group = topk_group = 1: no group limit)
    w = s[chosen]; w = w / (sum(w) + 1e-20); w = routed_scaling_factor * w
    u = x @ W_down [D, R]
    r = sum over e in chosen and held of  w_e relu(u @ W1_e)**2 @ W2_e
    out = r @ W_up [R, D] + relu(x @ Ws1)**2 @ Ws2

Float32 at `highest`, the recurrence a plain `lax.scan` over time, the
expert layer one expert at a time (a layer's held experts upcast at once
would be gigabytes), one layer of weights upcast at a time. Nothing of
the program is imported outside `program_config`.

Departures, each beside its line below: no rotary embedding in the
attention layers (the family's published modelling code applies none;
`rope_theta` and `partial_rotary_factor` are unused: `assumed` in the
configuration file); `conv_w` is stored [d_conv, conv_dim], the
program's layout, where the checkpoint has [conv_dim, 1, d_conv]; the
multi-token-prediction module (`num_nextn_predict_layers`,
`mtp_hybrid_override_pattern`) drafts tokens and is not part of the main
model's logits: its weights are not made; **a share of the experts**:
the configuration's `n_routed_experts` is the count held here (experts
`first_held_expert` .. + count - 1 of `n_routed_experts_published`): the
router keeps its published width, picks and normalises over all of it,
and only the picks on held experts add to r, here exactly as in the
program; what the absent experts would have added is left out, and that
partial result goes on to the next layer.

`reference.served_gaps` pads every request to one shape with tokens
after the served ones. A causal recurrence, like a causal mask, never
lets a position see what follows it: the padding changes no logit that
is read.
"""

import functools

import jax
import jax.numpy as jnp

from ..reference import F32, attention, mm, rms_norm

KINDS = {"M": "mamba2", "*": "attention", "E": "moe"}
STACKS = {"mamba2": "mamba2_layers", "attention": "attn_layers",
          "moe": "moe_layers"}


def dims(config):
    """The sizes the benchmark's own code reads, from the published
    keys; it raises on what the program cannot run."""
    for key, want in (
            ("tie_word_embeddings", False), ("mamba_proj_bias", False),
            ("use_conv_bias", True), ("use_bias", False),
            ("attention_bias", False), ("mlp_bias", False),
            ("mlp_hidden_act", "relu2"), ("mamba_hidden_act", "silu"),
            ("n_shared_experts", 1), ("norm_topk_prob", True),
            ("n_group", 1), ("topk_group", 1), ("sliding_window", None)):
        if config[key] != want:
            raise ValueError("the program runs a nemotron_h model with %s = "
                             "%r only; this configuration states %r"
                             % (key, want, config[key]))
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != config["num_hidden_layers"] or set(pattern) - set(KINDS):
        raise ValueError(
            "hybrid_override_pattern %r: %d layers of M, * and E are wanted"
            % (pattern, config["num_hidden_layers"]))
    d = {
        "dim": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "pattern": pattern,
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "vocab_size": config["vocab_size"],
        "norm_eps": float(config["norm_eps"]),
        "dtype": config["torch_dtype"],
        "mamba_heads": config["mamba_num_heads"],
        "mamba_head_dim": config["mamba_head_dim"],
        "ssm_state": config["ssm_state_size"],
        "n_groups": config["n_groups"],
        "d_conv": config["conv_kernel"],
        "chunk_size": config["chunk_size"],
        # the experts whose leaves are here, of those the router picks
        # over: a chip's share of each layer (the module docstring)
        "n_experts_held": config["n_routed_experts"],
        "n_experts": config.get("n_routed_experts_published",
                                config["n_routed_experts"]),
        "first_held_expert": config.get("first_held_expert", 0),
        "experts_per_tok": config["num_experts_per_tok"],
        "moe_latent": config["moe_latent_size"],
        "expert_dim": config["moe_intermediate_size"],
        "shared_expert_dim": config["moe_shared_expert_intermediate_size"],
        "routed_scale": float(config["routed_scaling_factor"]),
    }
    d["d_inner"] = d["mamba_heads"] * d["mamba_head_dim"]
    if d["d_inner"] != config["expand"] * d["dim"]:
        raise ValueError("mamba_num_heads x mamba_head_dim is %d, expand x "
                         "hidden_size %d" % (d["d_inner"],
                                             config["expand"] * d["dim"]))
    d["conv_dim"] = d["d_inner"] + 2 * d["n_groups"] * d["ssm_state"]
    for kind in STACKS:
        d["n_%s_layers" % kind] = layer_kinds(d).count(kind)
    return d


def layer_kinds(d):
    return [KINDS[c] for c in d["pattern"]]


def program_config(d, max_seq_len):
    from metaflow_tpu.models import nemotron_h

    return nemotron_h, nemotron_h.NemotronHConfig(
        vocab_size=d["vocab_size"], dim=d["dim"], pattern=d["pattern"],
        n_heads=d["n_heads"], n_kv_heads=d["n_kv_heads"],
        head_dim=d["head_dim"], mamba_heads=d["mamba_heads"],
        mamba_head_dim=d["mamba_head_dim"], ssm_state=d["ssm_state"],
        n_groups=d["n_groups"], conv_kernel=d["d_conv"],
        chunk_size=d["chunk_size"], n_routed_experts=d["n_experts"],
        experts_held=(d["first_held_expert"], d["n_experts_held"]),
        experts_per_tok=d["experts_per_tok"], moe_latent=d["moe_latent"],
        expert_dim=d["expert_dim"],
        shared_expert_dim=d["shared_expert_dim"],
        routed_scale=d["routed_scale"], max_seq_len=int(max_seq_len),
        norm_eps=d["norm_eps"], dtype=d["dtype"])


def dt_bias_init(key, shape):
    """The inverse softplus of a step drawn log-uniform in
    [time_step_min, time_step_max] = [1e-3, 1e-1] (Mamba-2's
    initialisation of the step size)."""
    step = jnp.exp(jax.random.uniform(key, shape, F32, jnp.log(1e-3),
                                      jnp.log(1e-1)))
    return step + jnp.log(-jnp.expm1(-step))


def a_log_init(key, shape):
    """log of A drawn uniform in [1, 16], a scalar a head (Mamba-2's
    initialisation): with the steps above a decay lies between 0.2 and
    0.999, neither 0 nor 1."""
    return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))


def router_bias_init(key, shape):
    """A selection bias that is not zero (a trained model's is not): a
    tenth of the spread of the sigmoid scores, so that the experts chosen
    are not simply those of the largest scores."""
    return 0.1 * jax.random.normal(key, shape, F32)


def leaf_specs(d):
    """Three stacks, one per kind of layer, each in the order its layers
    occur. Stored in the configuration's dtype (`make_leaf` casts),
    upcast where used. The expert leaves hold the held experts only."""
    D, V = d["dim"], d["vocab_size"]
    H, KV, Hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    Di, Mh, K, Cd = d["d_inner"], d["mamba_heads"], d["d_conv"], d["conv_dim"]
    R, F, Fs = d["moe_latent"], d["expert_dim"], d["shared_expert_dim"]
    Lm, La, Le = (d["n_%s_layers" % k] for k in ("mamba2", "attention", "moe"))
    held, routed = d["n_experts_held"], d["n_experts"]
    return {
        ("embed",): ((V, D), D),
        ("final_norm",): ((D,), None),
        ("lm_head",): ((D, V), D),
        ("mamba2_layers", "ssm_norm"): ((Lm, D), None),
        ("mamba2_layers", "in_proj"): ((Lm, D, Di + Cd + Mh), D),
        ("mamba2_layers", "conv_w"): ((Lm, K, Cd), K),
        ("mamba2_layers", "conv_b"): ((Lm, Cd), K),
        ("mamba2_layers", "dt_bias"): ((Lm, Mh), dt_bias_init),
        ("mamba2_layers", "A_log"): ((Lm, Mh), a_log_init),
        ("mamba2_layers", "D"): ((Lm, Mh), None),
        ("mamba2_layers", "gate_norm"): ((Lm, Di), None),
        ("mamba2_layers", "out_proj"): ((Lm, Di, D), Di),
        ("attn_layers", "attn_norm"): ((La, D), None),
        ("attn_layers", "wq"): ((La, D, H * Hd), D),
        ("attn_layers", "wk"): ((La, D, KV * Hd), D),
        ("attn_layers", "wv"): ((La, D, KV * Hd), D),
        ("attn_layers", "wo"): ((La, H * Hd, D), H * Hd),
        ("moe_layers", "ffn_norm"): ((Le, D), None),
        ("moe_layers", "router"): ((Le, D, routed), D),
        ("moe_layers", "router_bias"): ((Le, routed), router_bias_init),
        ("moe_layers", "latent_down"): ((Le, D, R), D),
        ("moe_layers", "latent_up"): ((Le, R, D), R),
        ("moe_layers", "w_up"): ((Le, held, R, F), R),
        ("moe_layers", "w_down"): ((Le, held, F, R), F),
        ("moe_layers", "shared_up"): ((Le, D, Fs), D),
        ("moe_layers", "shared_down"): ((Le, Fs, D), Fs),
    }


# ---- the plain reference ----

def relu2(x):
    return jnp.square(jax.nn.relu(x))


def mamba2_mixer(p, x, d, lowp=False):
    """x: [T, D] float32, already normed; p: this layer's weights as
    stored."""
    T = x.shape[0]
    H, P, N, G = d["mamba_heads"], d["mamba_head_dim"], d["ssm_state"], \
        d["n_groups"]
    Di, Cd, K = d["d_inner"], d["conv_dim"], d["d_conv"]
    zxbcdt = mm(x, p["in_proj"], lowp)
    z, xBC, dt = zxbcdt[:, :Di], zxbcdt[:, Di:Di + Cd], zxbcdt[:, Di + Cd:]
    # conv_w[k] multiplies the input K-1-k positions back (the program's
    # layout of the checkpoint's [conv_dim, 1, d_conv])
    padded = jnp.pad(xBC, ((K - 1, 0), (0, 0)))
    xBC = jax.nn.silu(p["conv_b"].astype(F32) + sum(
        padded[k:k + T] * p["conv_w"][k].astype(F32) for k in range(K)))
    xs = xBC[:, :Di].reshape(T, H, P)
    # head h reads group h // (H / G)
    B = jnp.repeat(xBC[:, Di:Di + G * N].reshape(T, G, N), H // G, axis=1)
    C = jnp.repeat(xBC[:, Di + G * N:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))        # [T, H]
    A = -jnp.exp(p["A_log"].astype(F32))                       # [H]

    def step(S, at):
        dt_t, x_t, B_t, C_t = at
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        return S, jnp.sum(S * C_t[:, None, :], -1)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (dt, xs, B, C))
    y = y + p["D"].astype(F32)[:, None] * xs
    y = (y.reshape(T, Di) * jax.nn.silu(z)).reshape(T, G, Di // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + d["norm_eps"])
    y = y.reshape(T, Di) * p["gate_norm"].astype(F32)
    return mm(y, p["out_proj"], lowp)


def attention_mixer(p, x, d, lowp=False):
    """x: [T, D] float32, already normed. No positional embedding: the
    family's published modelling code applies none (`assumed`)."""
    T = x.shape[0]
    H, KV, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    q = mm(x, p["wq"], lowp).reshape(T, H, hd)
    k = mm(x, p["wk"], lowp).reshape(T, KV, hd)
    v = mm(x, p["wv"], lowp).reshape(T, KV, hd)
    return mm(attention(q, k, v, lowp), p["wo"], lowp)


def routing(p, x, d, lowp=False):
    """[T, held] float32: the weight with which each token takes each
    expert held here (0 where it did not choose it), from scores and a
    choice over every routed expert."""
    k, first = d["experts_per_tok"], d["first_held_expert"]
    s = jax.nn.sigmoid(mm(x, p["router"], lowp))
    _, chosen = jax.lax.top_k(s + p["router_bias"].astype(F32), k)
    w = jnp.take_along_axis(s, chosen, -1)
    w = d["routed_scale"] * w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    weight = jnp.sum(jax.nn.one_hot(chosen, d["n_experts"], dtype=F32)
                     * w[..., None], axis=-2)
    # a share of the experts: the picks on absent experts add nothing
    return weight[:, first:first + d["n_experts_held"]]


def moe_mixer(p, x, d, lowp=False):
    """x: [T, D] float32, already normed: the routed experts held here,
    one at a time in the latent width, and the shared expert."""
    weight = routing(p, x, d, lowp)
    u = mm(x, p["latent_down"], lowp)

    def one(acc, ew):
        w1, w2, w = ew
        return acc + w[:, None] * mm(relu2(mm(u, w1, lowp)), w2, lowp), None

    r, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (p["w_up"], p["w_down"], weight.T))
    shared = mm(relu2(mm(x, p["shared_up"], lowp)), p["shared_down"], lowp)
    return mm(r, p["latent_up"], lowp) + shared


MIXERS = {"mamba2": (mamba2_mixer, "ssm_norm"),
          "attention": (attention_mixer, "attn_norm"),
          "moe": (moe_mixer, "ffn_norm")}


def block(kind, p, x, d, lowp=False):
    mixer, norm = MIXERS[kind]
    return x + mixer(p, rms_norm(x, p[norm], d["norm_eps"]), d, lowp)


@functools.lru_cache(maxsize=None)
def _jitted(dims_items, lowp):
    d = dict(dims_items)
    blocks = {kind: jax.jit(functools.partial(block, kind, d=d, lowp=lowp))
              for kind in MIXERS}
    top = jax.jit(lambda x, norm, head: mm(
        rms_norm(x, norm, d["norm_eps"]), head, lowp))
    return blocks, top


def logits(params, tokens, d, lowp=False):
    """Float32 logits [T, vocab] of one sequence of tokens, walking the
    three stacks in the pattern's order, one layer upcast at a time."""
    blocks, top = _jitted(tuple(sorted(d.items())), lowp)
    seen = dict.fromkeys(STACKS, 0)
    x = params["embed"][jnp.asarray(tokens)].astype(F32)
    for kind in layer_kinds(d):
        i = seen[kind]
        x = blocks[kind](jax.tree.map(lambda a: a[i], params[STACKS[kind]]),
                         x)
        seen[kind] += 1
    return top(x, params["final_norm"], params["lm_head"])


# ---- operations from shapes ----

def matmul_params(d, active_only=True):
    """Matmul parameters a token meets on this chip: the projections of
    every mixer, the router, the latent projections, the shared expert,
    two matrices an expert (of the experts_per_tok a token picks over
    all routed experts, held / routed fall here on average; without
    `active_only`, every held expert), and the head; the convolution,
    the norms and the recurrence are not matrix products."""
    D, Di = d["dim"], d["d_inner"]
    R, F, Fs = d["moe_latent"], d["expert_dim"], d["shared_expert_dim"]
    mamba = D * (Di + d["conv_dim"] + d["mamba_heads"]) + Di * D
    attn = D * d["head_dim"] * (2 * d["n_heads"] + 2 * d["n_kv_heads"])
    experts = (d["experts_per_tok"] * d["n_experts_held"] / d["n_experts"]
               if active_only else d["n_experts_held"])
    moe = D * d["n_experts"] + 2 * D * R + 2 * D * Fs + experts * 2 * R * F
    return (d["n_mamba2_layers"] * mamba + d["n_attention_layers"] * attn
            + d["n_moe_layers"] * moe + D * d["vocab_size"])
