"""Phi-4-mini-flash-reasoning (`model_type: phi4flash`; the architecture
is SambaY, arXiv:2507.06607, "Decoder-Hybrid-Decoder Architecture for
Efficient Reasoning with Long Generation") through `models/phi4flash.py`,
written from these equations (per sequence, x [T, D]; L layers, half =
L // 2; LN is a LayerNorm with weight and bias, eps `layer_norm_eps`;
NO positional embedding):

  block i:  x = x + mixer_i(LN(x));  x = x + (silu(h Wg) * (h Wu)) Wd,
            h = LN(x);  after the last block LN(x) @ embed.T (tied).

  i even, i <= half    Mamba-1 (d_inner = expand * D, no inner norms):
        u, z = split(h W_in);  u = silu(conv1d(u) + b)
        dt, B, C = split(u W_x);  delta = softplus(dt W_dt + b_dt)
        A = -exp(A_log);  s_t = exp(delta_t A) s_{t-1} + (delta_t u_t) B_t^T
        m_t = s_t C_t + D u_t;  out = (m * silu(z)) W_out
        layer `half` also hands m, before the gate, on as the MEMORY
  i odd, i < half      differential attention over a WINDOW: a query
        sees itself and the `sliding_window` - 1 positions before it
  i == half + 1        differential attention, causal, FULL; its K and V
        are the model's only global cache
  i odd, i >= half + 3 CROSS attention: q = h Wq + bq only; K and V are
        layer half + 1's, read again; the same differential form
  i even, i > half     gated memory unit: out = (silu(h W1) * m) W2, m
        the memory at the same position

  differential attention (heads in interleaved pairs, P query pairs, G
  KV pairs, pair p reads KV pair p // (P // G)):
    q = (h Wq + bq) -> [T, P, 2, hd];  k -> [T, G, 2, hd]
    v -> [T, G, 2, hd] read as [T, G, 2 hd]
    a_j = softmax(q[:, p, j] k[:, p // (P // G), j]^T / sqrt(hd) + mask)
          v[:, p // (P // G)]       j = 0, 1: the SAME v for both
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(i)
    lam0(i) = 0.8 - 0.6 exp(-0.3 i)
    y_p = rms_norm(a_0 - lam a_1, subln weight [2 hd], eps) * (1 - lam0(i))
    out = concat_p(y_p) Wo + bo

The published `config.json` omits the Mamba sizes, the head size and the
attention biases. Assumed, each beside its line below and in the
configuration file's `assumed`: `mamba_d_state` 16, `mamba_d_conv` 4,
`mamba_expand` 2, `mamba_dt_rank` ceil(D / 16) (the config class's
defaults); `head_dim` D / heads; the window counts the query itself;
biases on q/k/v/o, on the convolution and on dt, none elsewhere in
Mamba; the memory is the scan's output with D u, before the gate;
bfloat16 weights, a float32 state.

Float32 at `highest`, every layer over every position (no shortcut for a
prompt's last position, no ring, no cache), Mamba a plain `lax.scan` over
time, one pair's two [T, T] softmaxes at a time, one layer of weights
upcast at a time, the tied head a block of positions at a time into a
donated buffer (`head`). Nothing of the program is
imported outside `program_config`. No `layer`/`head`: no training
reference (the scan has no backward pass, ROADMAP M6b).

`reference.served_gaps` pads every request to one shape with tokens
after the served ones; the recurrence and both masks are causal, so the
padding changes no logit that is read.
"""

import functools
import math

import jax
import jax.numpy as jnp

from ..reference import F32, mm, swiglu
from .jamba import a_log_init, dt_bias_init

HEAD_BLOCKS = 8      # the head's product, this many blocks of positions


def dims(config):
    """The sizes the benchmark's own code reads, from the published
    keys and the file's `assumed_sizes`; it raises on what the program
    cannot run."""
    for key, want in (("tie_word_embeddings", True), ("hidden_act", "silu"),
                      ("mlp_bias", False), ("lm_head_bias", False),
                      ("mb_per_layer", 2)):
        if config[key] != want:
            raise ValueError("the program runs a phi4flash model with %s = "
                             "%r only; this configuration states %r"
                             % (key, want, config[key]))
    sizes = config["assumed_sizes"]
    d = {
        "dim": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        # assumed: the config has no head size
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "ffn_dim": config["intermediate_size"],
        "vocab_size": config["vocab_size"],
        "norm_eps": float(config["layer_norm_eps"]),
        "window": config["sliding_window"],
        "max_positions": config["max_position_embeddings"],
        "dtype": config["torch_dtype"],
        # assumed: the config class's defaults
        "d_state": sizes["mamba_d_state"],
        "d_conv": sizes["mamba_d_conv"],
        "expand": sizes["mamba_expand"],
        "dt_rank": sizes["mamba_dt_rank"],
    }
    d["d_inner"] = d["expand"] * d["dim"]
    kinds = layer_kinds(d)
    for kind in ("mamba", "window", "full", "cross", "gmu"):
        d["n_%s_layers" % kind] = kinds.count(kind)
    return d


def layer_kinds(d):
    """The kind of every layer (`mb_per_layer` 2: the even layers are
    Mamba's up to the middle one, gated memory units after it)."""
    half = d["n_layers"] // 2
    return [("mamba" if i <= half else "gmu") if i % 2 == 0 else
            "window" if i < half else "full" if i == half + 1 else "cross"
            for i in range(d["n_layers"])]


def program_config(d, max_seq_len):
    from metaflow_tpu.models import phi4flash

    return phi4flash, phi4flash.Phi4FlashConfig(
        vocab_size=d["vocab_size"], dim=d["dim"], n_layers=d["n_layers"],
        n_heads=d["n_heads"], n_kv_heads=d["n_kv_heads"],
        ffn_dim=d["ffn_dim"], sliding_window=d["window"],
        mamba_d_state=d["d_state"], mamba_d_conv=d["d_conv"],
        mamba_dt_rank=d["dt_rank"], mamba_expand=d["expand"],
        max_seq_len=int(max_seq_len), norm_eps=d["norm_eps"],
        dtype=d["dtype"])


def tenth_normal(key, shape):
    """N(0, 0.1): the lambda vectors (the differential transformer's
    initialisation) and every bias, so that none of them is a fixed
    point the comparison cannot see."""
    return 0.1 * jax.random.normal(key, shape, F32)


def leaf_specs(d):
    """Five stacks, one per kind of layer, each in the order its layers
    occur; the feed-forward leaves and the two LayerNorms are in all.
    Stored in the configuration's dtype (`make_leaf` casts), upcast
    where used."""
    D, F, V = d["dim"], d["ffn_dim"], d["vocab_size"]
    H, KV, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    Di, N, K, R = d["d_inner"], d["d_state"], d["d_conv"], d["dt_rank"]
    specs = {("embed",): ((V, D), D), ("final_norm",): ((D,), None),
             ("final_norm_b",): ((D,), tenth_normal)}

    def stack(name, L, mixer_norm, leaves):
        for norm in (mixer_norm, "ffn_norm"):
            specs[(name, norm)] = ((L, D), None)
            specs[(name, norm + "_b")] = ((L, D), tenth_normal)
        specs.update({(name, "w_gate"): ((L, D, F), D),
                      (name, "w_up"): ((L, D, F), D),
                      (name, "w_down"): ((L, F, D), F)})
        specs.update({(name, leaf): ((L,) + shape, init)
                      for leaf, (shape, init) in leaves.items()})

    # assumed: biases on q, k, v and o (the config has keys for the
    # MLP's and the head's only, both false)
    q = {"wq": ((D, H * hd), D), "bq": ((H * hd,), tenth_normal),
         "wo": ((H * hd, D), H * hd), "bo": ((D,), tenth_normal),
         "lambda_q1": ((hd,), tenth_normal),
         "lambda_k1": ((hd,), tenth_normal),
         "lambda_q2": ((hd,), tenth_normal),
         "lambda_k2": ((hd,), tenth_normal),
         "subln": ((2 * hd,), None)}
    kv = {"wk": ((D, KV * hd), D), "bk": ((KV * hd,), tenth_normal),
          "wv": ((D, KV * hd), D), "bv": ((KV * hd,), tenth_normal)}
    stack("mamba_layers", d["n_mamba_layers"], "ssm_norm", {
        "in_proj": ((D, 2 * Di), D), "conv_w": ((K, Di), K),
        # assumed: biases on the convolution and on dt, none elsewhere
        "conv_b": ((Di,), K), "x_proj": ((Di, R + 2 * N), Di),
        "dt_proj": ((R, Di), R), "dt_bias": ((Di,), dt_bias_init),
        "A_log": ((Di, N), a_log_init), "D": ((Di,), None),
        "out_proj": ((Di, D), Di)})
    stack("window_layers", d["n_window_layers"], "attn_norm", dict(q, **kv))
    stack("full_layers", d["n_full_layers"], "attn_norm", dict(q, **kv))
    stack("cross_layers", d["n_cross_layers"], "attn_norm", q)
    stack("gmu_layers", d["n_gmu_layers"], "gmu_norm", {
        "w_in": ((D, Di), D), "w_out": ((Di, D), Di)})
    return specs


# ---- the plain reference ----

def layer_norm(x, p, name, d):
    x = x.astype(F32)
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + d["norm_eps"]) \
        * p[name].astype(F32) + p[name + "_b"].astype(F32)


def mamba_mixer(p, x, d, lowp=False):
    """x: [T, D] float32, already normed. Returns (out [T, D], m [T,
    d_inner]: the scan's output with D u, before the gate)."""
    T = x.shape[0]
    Di, N, K, R = d["d_inner"], d["d_state"], d["d_conv"], d["dt_rank"]
    uz = mm(x, p["in_proj"], lowp)
    u, z = uz[:, :Di], uz[:, Di:]
    # conv_w[k] multiplies the input K-1-k positions back (the program's
    # layout of the checkpoint's [d_inner, 1, d_conv])
    padded = jnp.pad(u, ((K - 1, 0), (0, 0)))
    u = jax.nn.silu(p["conv_b"].astype(F32) + sum(
        padded[k:k + T] * p["conv_w"][k].astype(F32) for k in range(K)))
    dbc = mm(u, p["x_proj"], lowp)   # Mamba-1: no norm on dt, B or C
    dt, B, C = dbc[:, :R], dbc[:, R:R + N], dbc[:, R + N:]
    delta = jax.nn.softplus(mm(dt, p["dt_proj"], lowp)
                            + p["dt_bias"].astype(F32))
    # the state is held transposed, [d_state, d_inner], as the program's
    A = -jnp.exp(p["A_log"].astype(F32)).T                  # [N, Di]

    def step(s, at):
        delta_t, u_t, B_t, C_t = at
        s = jnp.exp(delta_t[None, :] * A) * s \
            + (delta_t * u_t)[None, :] * B_t[:, None]
        return s, jnp.sum(s * C_t[:, None], 0)

    # assumed: a float32 state
    _, m = jax.lax.scan(step, jnp.zeros((N, Di), F32), (delta, u, B, C))
    m = m + p["D"].astype(F32) * u
    return mm(m * jax.nn.silu(z), p["out_proj"], lowp), m


def keys_values(p, x, d, lowp=False):
    """K [T, KV, hd] and V [T, KV // 2, 2 hd] of normed x."""
    T = x.shape[0]
    KV, hd = d["n_kv_heads"], d["head_dim"]
    k = (mm(x, p["wk"], lowp) + p["bk"].astype(F32)).reshape(T, KV, hd)
    v = (mm(x, p["wv"], lowp) + p["bv"].astype(F32)).reshape(
        T, KV // 2, 2 * hd)
    return k, v


def differential_attention(p, x, k, v, lam0, window, d, lowp=False):
    """x: [T, D] float32, already normed; k, v: this layer's own or the
    full layer's; window: None, or how many positions a query sees."""
    T = x.shape[0]
    H, KV, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    P, G = H // 2, KV // 2
    q = (mm(x, p["wq"], lowp) + p["bq"].astype(F32)).reshape(T, P, 2, hd)
    at = jnp.arange(T)
    mask = at[None, :] <= at[:, None]
    if window is not None:
        # assumed: the window counts the query itself
        mask &= at[None, :] > at[:, None] - window
    f32 = lambda name: p[name].astype(F32)
    lam = (jnp.exp(jnp.sum(f32("lambda_q1") * f32("lambda_k1")))
           - jnp.exp(jnp.sum(f32("lambda_q2") * f32("lambda_k2"))) + lam0)

    def pair(qkv):
        qp, kp, vp = qkv   # [T, 2, hd], [T, 2, hd], [T, 2 hd]
        maps = []
        for j in range(2):
            scores = mm(qp[:, j], kp[:, j].T, lowp) * (hd ** -0.5)
            scores = jnp.where(mask, scores, -jnp.inf)
            maps.append(mm(jax.nn.softmax(scores, -1), vp, lowp))
        diff = maps[0] - lam * maps[1]
        rms = jax.lax.rsqrt(jnp.mean(diff * diff, -1, keepdims=True)
                            + d["norm_eps"])
        return diff * rms * f32("subln") * (1.0 - lam0)

    kv_of = jnp.arange(P) // (P // G)
    y = jax.lax.map(pair, (q.transpose(1, 0, 2, 3),
                           k.reshape(T, G, 2, hd).transpose(1, 0, 2, 3)[kv_of],
                           v.transpose(1, 0, 2)[kv_of]))   # [P, T, 2 hd]
    return mm(y.transpose(1, 0, 2).reshape(T, H * hd), p["wo"], lowp) \
        + p["bo"].astype(F32)


def block(kind, p, x, memory, k, v, lam0, d, lowp=False):
    """One block of `kind` on one sequence; returns (x, memory, k, v):
    the memory and the full layer's K and V as they stand after it."""
    if kind == "mamba":
        out, memory = mamba_mixer(p, layer_norm(x, p, "ssm_norm", d), d, lowp)
    elif kind == "gmu":
        h = layer_norm(x, p, "gmu_norm", d)
        out = mm(jax.nn.silu(mm(h, p["w_in"], lowp)) * memory, p["w_out"],
                 lowp)
    else:
        h = layer_norm(x, p, "attn_norm", d)
        own = (k, v) if kind == "cross" else keys_values(p, h, d, lowp)
        if kind == "full":
            k, v = own
        out = differential_attention(
            p, h, own[0], own[1], lam0, d["window"] if kind == "window"
            else None, d, lowp)
    x = x + out
    h = layer_norm(x, p, "ffn_norm", d)
    return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"], lowp), \
        memory, k, v


def head(x, p, embed, out, d, lowp=False):
    """The tied head a block of positions at a time, written into `out`,
    a [T, vocab] float32 buffer the caller made (and, off the CPU,
    donates). Compiled for the described chip at [4096, 200064] the
    program then holds one block of logits among its temporaries and
    nothing else (0.41 GB; 0.45 with `lowp`), the embedding never whole
    in float32: a block of the vocabulary at a time made the loop carry
    the buffer vocabulary-major and copy it in and out (3.3 GB), which
    the control, that holds two sets of logits, had no room for."""
    x = layer_norm(x, p, "final_norm", d)
    T = x.shape[0]
    rows = -(-T // HEAD_BLOCKS)

    def one(i, out):
        # the last block is clamped and says some positions again
        start = jnp.minimum(i * rows, T - rows)
        blk = jax.lax.dynamic_slice_in_dim(x, start, rows, 0)
        return jax.lax.dynamic_update_slice_in_dim(
            out, mm(blk, embed.T, lowp), start, 0)

    return jax.lax.fori_loop(0, HEAD_BLOCKS, one, out)


@functools.lru_cache(maxsize=None)
def _jitted(dims_items, lowp):
    d = dict(dims_items)
    blocks = {kind: jax.jit(functools.partial(block, kind, d=d, lowp=lowp))
              for kind in ("mamba", "window", "full", "cross", "gmu")}
    top = jax.jit(lambda x, p, embed, out: head(x, p, embed, out, d, lowp),
                  donate_argnums=() if jax.default_backend() == "cpu"
                  else (3,))
    return blocks, top


def logits(params, tokens, d, lowp=False):
    """Float32 logits [T, vocab] of one sequence of tokens, walking the
    five stacks in the model's order, one layer upcast at a time."""
    blocks, top = _jitted(tuple(sorted(d.items())), lowp)
    x = params["embed"][jnp.asarray(tokens)].astype(F32)
    T = x.shape[0]
    memory = jnp.zeros((T, d["d_inner"]), F32)
    k = jnp.zeros((T, d["n_kv_heads"], d["head_dim"]), F32)
    v = jnp.zeros((T, d["n_kv_heads"] // 2, 2 * d["head_dim"]), F32)
    seen = {}
    for i, kind in enumerate(layer_kinds(d)):
        at = seen.get(kind, 0)
        seen[kind] = at + 1
        x, m, k, v = blocks[kind](
            jax.tree.map(lambda a: a[at], params[kind + "_layers"]), x,
            memory, k, v, 0.8 - 0.6 * math.exp(-0.3 * i))
        if i == d["n_layers"] // 2:   # the middle layer's, Mamba's last
            memory = m
    return top(x, {"final_norm": params["final_norm"],
                   "final_norm_b": params["final_norm_b"]}, params["embed"],
               jnp.zeros((T, d["vocab_size"]), F32))


# ---- operations from shapes ----

def matmul_params(d, active_only=True):
    """Matmul parameters a token meets: the projections of every mixer,
    every MLP, and the tied head; the convolution, the norms, the
    recurrence and attention's products with the cache are not
    parameters and are not counted."""
    D, Di, N, R = d["dim"], d["d_inner"], d["d_state"], d["dt_rank"]
    hd = d["head_dim"]
    mlp = 3 * D * d["ffn_dim"]
    mamba = D * 2 * Di + Di * (R + 2 * N) + R * Di + Di * D
    q_o = 2 * D * d["n_heads"] * hd
    k_v = 2 * D * d["n_kv_heads"] * hd
    return (d["n_mamba_layers"] * (mamba + mlp)
            + (d["n_window_layers"] + d["n_full_layers"]) * (q_o + k_v + mlp)
            + d["n_cross_layers"] * (q_o + mlp)
            + d["n_gmu_layers"] * (2 * D * Di + mlp) + D * d["vocab_size"])
