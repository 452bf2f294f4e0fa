"""Brumby (manifestai/Brumby-14B-Base, `model_type: brumby`) through
`models/brumby.py`: Qwen3-14B's block with its softmax attention replaced
by gated power retention of degree 2 (arXiv:2507.04239, "Scaling Context
Requires Rethinking Attention"). Written from these equations, per
sequence, x [T, D], in the ATTENTION form: no expanded feature map, no
state, nothing the program's recurrence shares.

  block:  h = rms_norm(x, input norm)
          q_t = rope(rms_norm_head(h_t Wq))  [H, hd]
          k_t = rope(rms_norm_head(h_t Wk))  [KV, hd]      v_t = h_t Wv
          log g_t = log_sigmoid(h_t Wg + bg)  [KV], float32
          a_ts = exp(sum_{l=s+1..t} log g_l) * (q_t . k_s / sqrt(hd))^2
                 for s <= t, a query head against its group's KV head
          y_t = sum_s a_ts v_s / (sum_s a_ts + eps)
          x = x + concat_heads(y) Wo
          x = x + swiglu(rms_norm(x, post-attention norm))
  after the last block rms_norm(x, final norm) @ lm_head (untied).

The published `config.json` has no key for the retention layer. What the
paper fixes: the power, the gate, the normalising sum, the symmetric
expansion the program's state uses. Assumed, each beside its line below
and in the configuration file's `assumed`: degree 2; the gate's
projection (Wg hidden x KV heads with a bias, one gate a KV head,
log_sigmoid); the q and k head norms and rope kept from the Qwen3-14B
block the model was initialised from; the scale 1 / sqrt(hd) inside the
square; eps 1e-6; bfloat16 weights.

Float32 at `highest`, one layer of weights upcast at a time, the head in
blocks of the vocabulary (151,936 columns in float32 are 3.1 GB whole).
Nothing of the program is imported outside `program_config`.

`reference.served_gaps` pads every request to one shape with tokens
after the served ones; the weights a_ts are causal, so the padding
changes no logit that is read.
"""

import functools

import jax
import jax.numpy as jnp

from ..reference import F32, mm, rms_norm, rope, swiglu

DEGREE = 2           # assumed: the power of the released model
RETENTION_EPS = 1e-6  # assumed: added to the normalising sum
HEAD_BLOCKS = 8      # the head's product, this many blocks of the vocabulary


def dims(config):
    """The sizes the benchmark's own code reads, from the published
    keys; it raises on what the program cannot run."""
    for key, want in (("attention_bias", False), ("hidden_act", "silu"),
                      ("rope_scaling", None), ("sliding_window", None),
                      ("use_sliding_window", False),
                      ("tie_word_embeddings", False)):
        if config[key] != want:
            raise ValueError("the program runs a brumby model with %s = %r "
                             "only; this configuration states %r"
                             % (key, want, config[key]))
    d = {
        "dim": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "ffn_dim": config["intermediate_size"],
        "vocab_size": config["vocab_size"],
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "max_positions": config["max_position_embeddings"],
        "dtype": config["torch_dtype"],
    }
    # what a state holds: the distinct products of a head's symmetric
    # square (the roofline's need counts these, not the program's layout)
    d["state_dim"] = d["head_dim"] * (d["head_dim"] + 1) // 2
    return d


def program_config(d, max_seq_len):
    from metaflow_tpu.models import brumby

    return brumby, brumby.BrumbyConfig(
        vocab_size=d["vocab_size"], dim=d["dim"], n_layers=d["n_layers"],
        n_heads=d["n_heads"], n_kv_heads=d["n_kv_heads"],
        head_dim=d["head_dim"], ffn_dim=d["ffn_dim"],
        max_seq_len=int(max_seq_len), rope_theta=d["rope_theta"],
        norm_eps=d["norm_eps"], retention_eps=RETENTION_EPS,
        dtype=d["dtype"])


def gate_bias_init(key, shape):
    """The logit of g = exp(-1 / tau), tau drawn log-uniform in
    [16, 4096] positions: the gate of a trained model sits near one, and
    a seeded bias of zero would forget in two tokens."""
    tau = jnp.exp(jax.random.uniform(key, shape, F32, jnp.log(16.0),
                                     jnp.log(4096.0)))
    return -jnp.log(jnp.expm1(1.0 / tau))


def leaf_specs(d):
    """One stack `layers`; stored in the configuration's dtype
    (`make_leaf` casts), upcast where used."""
    L, D, F, V = d["n_layers"], d["dim"], d["ffn_dim"], d["vocab_size"]
    H, KV, Hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    return {
        ("embed",): ((V, D), D),
        ("layers", "attn_norm"): ((L, D), None),
        ("layers", "wq"): ((L, D, H * Hd), D),
        ("layers", "wk"): ((L, D, KV * Hd), D),
        ("layers", "wv"): ((L, D, KV * Hd), D),
        # assumed: one gate a KV head, projected from the normed input
        ("layers", "wg"): ((L, D, KV), D),
        ("layers", "bg"): ((L, KV), gate_bias_init),
        # assumed: kept from the Qwen3-14B block
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


# ---- the plain reference ----

def retention(q, k, v, log_g, lowp):
    """Causal power retention, grouped-query; q: [T, H, hd], k and v:
    [T, KV, hd], log_g: [T, KV]. One group of query heads at a time, so
    that the [T, T] weights of all heads never exist together."""
    T, H, hd = q.shape
    KV = k.shape[1]
    q = q.reshape(T, KV, H // KV, hd).transpose(1, 2, 0, 3)  # [KV, G, T, hd]
    k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
    c = jnp.cumsum(log_g, axis=0).T                          # [KV, T]
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]

    @jax.checkpoint
    def group(qkvc):
        qg, kg, vg, cg = qkvc
        # assumed: the scale sits inside the power (it cancels in y but
        # for eps)
        scores = mm(qg, kg.T[None], lowp) * (hd ** -0.5)
        decay = jnp.where(causal, cg[:, None] - cg[None, :], -jnp.inf)
        a = scores ** DEGREE * jnp.exp(decay)[None]
        return mm(a, vg[None], lowp) / (
            jnp.sum(a, -1, keepdims=True) + RETENTION_EPS)

    out = jax.lax.map(group, (q, k, v, c))  # [KV, G, T, hd]
    return out.transpose(2, 0, 1, 3).reshape(T, H * hd)


def layer(p, x, d, lowp=False):
    """One block on one sequence; x: [T, D] float32, p: this layer's
    weights as stored."""
    T = x.shape[0]
    H, KV, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    pos = jnp.arange(T)
    h = rms_norm(x, p["attn_norm"], d["norm_eps"])
    # assumed: an RMS norm a head on q and k, then rope, as in Qwen3-14B
    q = rms_norm(mm(h, p["wq"], lowp).reshape(T, H, hd), p["q_norm"],
                 d["norm_eps"])
    k = rms_norm(mm(h, p["wk"], lowp).reshape(T, KV, hd), p["k_norm"],
                 d["norm_eps"])
    q, k = rope(q, pos, d["rope_theta"]), rope(k, pos, d["rope_theta"])
    v = mm(h, p["wv"], lowp).reshape(T, KV, hd)
    # assumed: log_sigmoid of a biased projection, kept in float32
    log_g = jax.nn.log_sigmoid(mm(h, p["wg"], lowp) + p["bg"].astype(F32))
    x = x + mm(retention(q, k, v, log_g, lowp), p["wo"], lowp)
    h = rms_norm(x, p["ffn_norm"], d["norm_eps"])
    return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"], lowp)


def head(x, final_norm, lm_head, d, lowp=False):
    """The head a block of the vocabulary at a time, so that lm_head is
    never upcast whole."""
    x = rms_norm(x, final_norm, d["norm_eps"])
    V = lm_head.shape[1]
    edges = [V * i // HEAD_BLOCKS for i in range(HEAD_BLOCKS + 1)]
    return jnp.concatenate(
        [mm(x, lm_head[:, a:b], lowp) for a, b in zip(edges, edges[1:])
         if b > a], axis=1)


@functools.lru_cache(maxsize=None)
def _jitted(dims_items, lowp):
    d = dict(dims_items)
    return (jax.jit(lambda p, x: layer(p, x, d, lowp)),
            jax.jit(lambda x, n, w: head(x, n, w, d, lowp)))


def logits(params, tokens, d, lowp=False):
    """Float32 logits [T, vocab] of one sequence of tokens, one layer
    upcast at a time."""
    block, top = _jitted(tuple(sorted(d.items())), lowp)
    x = params["embed"][jnp.asarray(tokens)].astype(F32)
    for i in range(d["n_layers"]):
        x = block(jax.tree.map(lambda a: a[i], params["layers"]), x)
    return top(x, params["final_norm"], params["lm_head"])


# ---- operations from shapes ----

def matmul_params(d, active_only=True):
    """Matmul parameters a token meets: every layer's projections (the
    gate's among them), its MLP, and `lm_head`; the embedding is a
    lookup, and retention's products grow with the state, not with a
    parameter: `layer_metrics/kernels.retention_*` count them."""
    attn = d["dim"] * (d["head_dim"] * (2 * d["n_heads"]
                                        + 2 * d["n_kv_heads"])
                       + d["n_kv_heads"])
    return (d["n_layers"] * (attn + 3 * d["dim"] * d["ffn_dim"])
            + d["dim"] * d["vocab_size"])
