"""Mixtral through `models/mixtral.py`: the dense decoder's attention
half (families/llama.py) and, in place of its SwiGLU, a router whose
top-k logits are softmaxed over a plain loop over every expert
(`reference.moe`). It exports no `layer` and `head`: the training
reference has not learned the expert layer."""

from ..reference import moe, rms_norm
from . import llama


def dims(config):
    d = llama.dims(config)
    d["n_experts"] = config["num_local_experts"]
    d["experts_per_tok"] = config["num_experts_per_tok"]
    return d


def program_config(d, max_seq_len):
    from metaflow_tpu.models import mixtral

    return mixtral, mixtral.MixtralConfig(
        n_experts=d["n_experts"], experts_per_tok=d["experts_per_tok"],
        **llama.program_fields(d, max_seq_len))


def leaf_specs(dims):
    """The dense tree with an expert axis after the layer axis of the
    three feed-forward leaves, and the router."""
    L, D, N = dims["n_layers"], dims["dim"], dims["n_experts"]
    specs = llama.leaf_specs(dims)
    for name in ("w_gate", "w_up", "w_down"):
        shape, fan_in = specs[("layers", name)]
        specs[("layers", name)] = ((L, N) + shape[1:], fan_in)
    specs[("layers", "router")] = ((L, D, N), D)
    return specs


def _layer(p, x, dims, lowp=False):
    x = llama.attention_half(p, x, dims, lowp)
    h = rms_norm(x, p["ffn_norm"], dims["norm_eps"])
    return x + moe(h, p, dims["experts_per_tok"], lowp)


def logits(params, tokens, dims, lowp=False):
    return llama.stacked_logits(_layer, params, tokens, dims, lowp)


def matmul_params(dims, active_only=True):
    """As the dense count, with the experts a token is routed to (or,
    without `active_only`, all of them) and the router's columns."""
    d, experts = dims["dim"], dims["n_experts"]
    per_tok = dims["experts_per_tok"] if active_only else experts
    per_layer = (llama.attention_params(dims)
                 + per_tok * 3 * d * dims["ffn_dim"] + d * experts)
    return dims["n_layers"] * per_layer + d * dims["vocab_size"]


def train_flops_per_token(dims, seq_len):
    return (6 * matmul_params(dims)
            + llama.attention_train_flops_per_token(dims, seq_len))
