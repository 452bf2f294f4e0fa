"""Ouro (ByteDance/Ouro-2.6B, `model_type: ouro`; "Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741) through
`models/ouro.py`: ONE stack of dense blocks that a token goes through
`total_ut_steps` times with the same weights, written from the published
description. Nothing of the program is imported outside
`program_config`.

With h_0 the embedding and t = 0 .. passes - 1 the pass:

    for layer i, in pass t:                  # weights of layer i, whatever t
        a = Attn_i(rms(x; attn_norm_i))      # causal, rope (half-split) on q
                                             # and k, plain multi-head
        x = x + rms(a; attn_post_norm_i)     # sandwich norms
        m = SwiGLU_i(rms(x; ffn_norm_i))
        x = x + rms(m; ffn_post_norm_i)
    h_{t+1} = rms(x; final_norm)             # closes every pass
    lambda_t = sigmoid(w_gate . h_{t+1} + b_gate)
    logits = h_passes @ lm_head

The reference has NO cache: each pass is a full causal forward over the
whole sequence, so pass t's attention can only see pass t's keys and
values, and a program that prefills and then decodes through a pool
indexed by pass and layer has to agree with it. Each assumption (what
the published `config.json` has no key for) is a sentence beside its
line and under `assumed` in the configuration file.
"""

import functools

import jax
import jax.numpy as jnp

from ..reference import F32, attention, mm, rms_norm, rope, swiglu
from . import llama


def dims(config):
    """The sizes the benchmark's own code reads, from the published
    keys; raises on what the program cannot run."""
    d = llama.dims(config)   # raises on `sliding_window`
    d["passes"] = int(config["total_ut_steps"])
    d["exit_threshold"] = float(config["early_exit_threshold"])
    if d["exit_threshold"] < 1.0:
        raise ValueError(
            "early_exit_threshold %r < 1: the program runs every token "
            "through every pass (per-lane exit is not built)"
            % (config["early_exit_threshold"],))
    if config.get("tie_word_embeddings"):
        raise ValueError("the program's Ouro has a head of its own")
    return d


def program_config(d, max_seq_len):
    """The program's module and its own configuration object: its
    dataclass fields only."""
    from metaflow_tpu.models import ouro

    return ouro, ouro.OuroConfig(
        head_dim=d["head_dim"], passes=d["passes"],
        exit_threshold=d["exit_threshold"],
        **llama.program_fields(d, max_seq_len))


def post_norm_init(n_layers):
    """The seeded gain of a post norm: (2 L) ** -0.5, the residual
    scaling by one over the root of the stack's residual sublayers that
    GPT-2's initialisation uses, times a seeded factor in [0.5, 1.5]. A
    post norm's gain is the size of what its sublayer adds to the stream
    whatever the sublayer computed, so with unit gains every layer of a
    pass swamps the unit stream the pass starts from, and the stack, run
    again and again over its own output with random weights, is a chaotic
    map: bfloat16 against float32 then diverges as a wrong program would
    (PERF.md section 2 gives both readings). At this gain a pass's 96
    sublayers add up to about what the stream carries, as a trained looped
    model's passes refine one latent."""
    def init(key, shape):
        return (2 * n_layers) ** -0.5 * jax.random.uniform(
            key, shape, jnp.float32, 0.5, 1.5)

    return init


def leaf_specs(dims):
    """The dense tree (families/llama.py) plus the two post norms of a
    block and the exit gate."""
    L, D = dims["n_layers"], dims["dim"]
    specs = llama.leaf_specs(dims)
    specs[("layers", "attn_post_norm")] = ((L, D), post_norm_init(L))
    specs[("layers", "ffn_post_norm")] = ((L, D), post_norm_init(L))
    # assumed: the gate is Linear(hidden, 1) with a bias (the bias a
    # seeded N(0, 1) scalar, so that the gate is not symmetric about 1/2)
    specs[("exit_gate_w",)] = ((D,), D)
    specs[("exit_gate_b",)] = ((), 1)
    return specs


# ---- the plain reference ----

def block(p, x, dims, lowp=False):
    """One block on one sequence; x: [T, D] float32. (Not `layer`: the
    family exports no `layer` and `head`, so the training driver says
    that it has no training reference.)"""
    T = x.shape[0]
    H, KV, hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    eps, pos = dims["norm_eps"], jnp.arange(T)
    h = rms_norm(x, p["attn_norm"], eps)
    # assumed: no bias on q, k, v, o (no `attention_bias` key) and no q
    # and k head norms
    q = rope(mm(h, p["wq"], lowp).reshape(T, H, hd), pos, dims["rope_theta"])
    k = rope(mm(h, p["wk"], lowp).reshape(T, KV, hd), pos,
             dims["rope_theta"])
    v = mm(h, p["wv"], lowp).reshape(T, KV, hd)
    a = mm(attention(q, k, v, lowp), p["wo"], lowp)
    # assumed: four norms a block, the second and fourth on the
    # sublayer's OUTPUT before it joins the residual (sandwich norms)
    x = x + rms_norm(a, p["attn_post_norm"], eps)
    m = swiglu(rms_norm(x, p["ffn_norm"], eps), p["w_gate"], p["w_up"],
               p["w_down"], lowp)
    return x + rms_norm(m, p["ffn_post_norm"], eps)


def close_pass(x, final_norm, gate_w, gate_b, dims, lowp=False):
    """What ends a pass: the model's norm (assumed: after EVERY pass, and
    the normed stream is what the next pass starts from) and the exit
    gate's lambda off it, [T]."""
    h = rms_norm(x, final_norm, dims["norm_eps"])
    return h, jax.nn.sigmoid(mm(h, gate_w.astype(F32)[:, None], lowp)[:, 0]
                             + gate_b.astype(F32))


@functools.lru_cache(maxsize=None)
def _jitted(dims_items, lowp):
    dims = dict(dims_items)
    return (jax.jit(lambda p, x: block(p, x, dims, lowp)),
            jax.jit(lambda x, n, w, b: close_pass(x, n, w, b, dims, lowp)),
            jax.jit(lambda h, w: mm(h, w, lowp)))


def passes(params, tokens, dims, lowp=False):
    """(h_passes [T, D], lambda of every pass [passes, T]): the whole
    sequence through the stack `passes` times, a layer of weights upcast
    at a time; no cache, so pass t sees pass t's K and V alone."""
    one_block, close, _ = _jitted(tuple(sorted(dims.items())), lowp)
    x = params["embed"][jnp.asarray(tokens)].astype(F32)
    gates = []
    for _ in range(dims["passes"]):
        for i in range(dims["n_layers"]):
            x = one_block(jax.tree.map(lambda a: a[i], params["layers"]), x)
        x, gate = close(x, params["final_norm"], params["exit_gate_w"],
                        params["exit_gate_b"])
        gates.append(gate)
    return x, jnp.stack(gates)


def logits(params, tokens, dims, lowp=False):
    """Float32 logits [T, vocab] of one sequence of tokens. With the
    published threshold of 1 the exit CDF reaches it at the last pass
    only, so the logits are the last pass's."""
    h, _ = passes(params, tokens, dims, lowp)
    return _jitted(tuple(sorted(dims.items())), lowp)[2](
        h, params["lm_head"])


def exit_cdf(params, tokens, dims):
    """The CDF of the exit distribution after each pass, [passes, T]:
    p_t = lambda_t prod_{j<t} (1 - lambda_j), the rest on the last."""
    _, gates = passes(params, tokens, dims)
    stay = jnp.cumprod(1.0 - gates, axis=0)
    return jnp.concatenate([1.0 - stay[:-1], jnp.ones_like(stay[:1])])


# ---- operations from shapes ----

def matmul_params(dims, active_only=True):
    """Matmul parameters a token meets: the stack `passes` times (the
    same matrices, met once a pass), `lm_head` and the gate's vector
    once a pass; with `active_only` false, every one the model holds,
    each once."""
    per_layer = (llama.attention_params(dims)
                 + 3 * dims["dim"] * dims["ffn_dim"])
    times = dims["passes"] if active_only else 1
    return (times * (dims["n_layers"] * per_layer + dims["dim"])
            + dims["dim"] * dims["vocab_size"])


# no `train_flops_per_token`, `head`: the loss over the exit distribution
# has no reference here (benchmark/ref_train.py takes one pass through one
# stack), so the training driver refuses the family
