"""The dense decoder the program runs through `models/llama.py`
(Mistral-7B: RMS-norm, rotary embedding in the half-split convention,
grouped-query causal attention, SwiGLU), written from the published
description. Nothing of the program is imported outside
`program_config`."""

import functools

import jax
import jax.numpy as jnp

from ..reference import F32, attention, mm, rms_norm, rope, swiglu


def dims(config):
    """The sizes the benchmark's own code reads, from the published
    keys of a configuration file."""
    d = {
        "dim": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config.get(
            "head_dim",
            config["hidden_size"] // config["num_attention_heads"]),
        "ffn_dim": config["intermediate_size"],
        "vocab_size": config["vocab_size"],
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "dtype": config["torch_dtype"],
    }
    if d["head_dim"] * d["n_heads"] != d["dim"]:
        raise ValueError("the program derives head_dim as hidden_size / "
                         "heads; this configuration states another")
    if config.get("sliding_window"):
        raise ValueError("the program has no sliding-window attention")
    return d


def program_fields(d, max_seq_len):
    """The dataclass fields the program's decoders share."""
    return dict(vocab_size=d["vocab_size"], dim=d["dim"],
                n_layers=d["n_layers"], n_heads=d["n_heads"],
                n_kv_heads=d["n_kv_heads"], ffn_dim=d["ffn_dim"],
                max_seq_len=int(max_seq_len), rope_theta=d["rope_theta"],
                norm_eps=d["norm_eps"], dtype=d["dtype"])


def program_config(d, max_seq_len):
    """The program's module and its own configuration object: its
    dataclass fields only, no program file is touched."""
    from metaflow_tpu.models import llama

    return llama, llama.LlamaConfig(rope_llama3_scaling=False,
                                    **program_fields(d, max_seq_len))


def leaf_specs(dims):
    """{leaf path: (shape, fan_in or None for a norm weight)}; layer
    leaves carry the leading layer axis."""
    L, D, F, V = (dims["n_layers"], dims["dim"], dims["ffn_dim"],
                  dims["vocab_size"])
    H, KV, Hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    return {
        ("embed",): ((V, D), D),
        ("layers", "attn_norm"): ((L, D), None),
        ("layers", "wq"): ((L, D, H * Hd), D),
        ("layers", "wk"): ((L, D, KV * Hd), D),
        ("layers", "wv"): ((L, D, KV * Hd), D),
        ("layers", "wo"): ((L, H * Hd, D), H * Hd),
        ("layers", "ffn_norm"): ((L, D), None),
        ("layers", "w_gate"): ((L, D, F), D),
        ("layers", "w_up"): ((L, D, F), D),
        ("layers", "w_down"): ((L, F, D), F),
        ("final_norm",): ((D,), None),
        ("lm_head",): ((D, V), D),
    }


# ---- the plain reference ----

def attention_half(p, x, dims, lowp=False):
    """The first half of a block on one sequence, residual included;
    x: [T, D] float32, p: this layer's weights as stored."""
    T = x.shape[0]
    H, KV, hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    pos = jnp.arange(T)
    h = rms_norm(x, p["attn_norm"], dims["norm_eps"])
    q = rope(mm(h, p["wq"], lowp).reshape(T, H, hd), pos, dims["rope_theta"])
    k = rope(mm(h, p["wk"], lowp).reshape(T, KV, hd), pos,
             dims["rope_theta"])
    v = mm(h, p["wv"], lowp).reshape(T, KV, hd)
    return x + mm(attention(q, k, v, lowp), p["wo"], lowp)


def layer(p, x, dims, lowp=False):
    """One block on one sequence; x: [T, D] float32."""
    x = attention_half(p, x, dims, lowp)
    h = rms_norm(x, p["ffn_norm"], dims["norm_eps"])
    return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"], lowp)


def head(x, final_norm, lm_head, dims, lowp=False):
    return mm(rms_norm(x, final_norm, dims["norm_eps"]), lm_head, lowp)


def stacked_logits(layer_fn, params, tokens, dims, lowp):
    """Float32 logits [T, vocab] of one sequence through one stack of
    `layer_fn` blocks, layer by layer: one layer of weights upcast at a
    time."""
    block, top = _jitted(layer_fn, tuple(sorted(dims.items())), lowp)
    x = params["embed"][jnp.asarray(tokens)].astype(F32)
    for i in range(dims["n_layers"]):
        x = block(jax.tree.map(lambda a: a[i], params["layers"]), x)
    return top(x, params["final_norm"], params["lm_head"])


@functools.lru_cache(maxsize=None)
def _jitted(layer_fn, dims_items, lowp):
    dims = dict(dims_items)
    return (jax.jit(lambda p, x: layer_fn(p, x, dims, lowp)),
            jax.jit(lambda x, n, w: head(x, n, w, dims, lowp)))


def logits(params, tokens, dims, lowp=False):
    """Float32 logits [T, vocab] of one sequence of tokens."""
    return stacked_logits(layer, params, tokens, dims, lowp)


# ---- operations from shapes ----

def attention_params(dims):
    """Parameters of one layer's four attention projections."""
    return dims["dim"] * dims["head_dim"] * (
        2 * dims["n_heads"] + 2 * dims["n_kv_heads"])


def matmul_params(dims, active_only=True):
    """Matmul parameters a token meets: every layer and `lm_head`; the
    embedding is a lookup and is not counted."""
    per_layer = attention_params(dims) + 3 * dims["dim"] * dims["ffn_dim"]
    return (dims["n_layers"] * per_layer
            + dims["dim"] * dims["vocab_size"])


def attention_train_flops_per_token(dims, seq_len):
    """Causal attention's two matmuls (scores and values), forward and
    backward, for one token of a sequence of `seq_len`: 12*L*S*d for the
    full square, halved because the mask leaves half of it."""
    return (12 * dims["n_layers"] * seq_len * dims["n_heads"]
            * dims["head_dim"]) // 2


def train_flops_per_token(dims, seq_len):
    """Forward and backward for one token: 6 per matmul parameter, and
    causal attention."""
    return (6 * matmul_params(dims)
            + attention_train_flops_per_token(dims, seq_len))
