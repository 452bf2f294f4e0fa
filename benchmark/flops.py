"""Operations the algorithm needs, from shapes alone.

`dims` is the dictionary `configs.dims()` makes of a configuration file.
A multiply-add counts as two operations. Recomputed operations (remat,
the flash kernel's backward recomputation) are never counted.
"""


def matmul_params_per_layer(dims, active_only=True):
    """Parameters of one layer that a token is multiplied by."""
    d, hd = dims["dim"], dims["head_dim"]
    attn = d * hd * (2 * dims["n_heads"] + 2 * dims["n_kv_heads"])
    ffn = 3 * d * dims["ffn_dim"]
    experts = dims.get("n_experts", 0)
    if experts:
        per_tok = dims["experts_per_tok"] if active_only else experts
        ffn = per_tok * ffn + d * experts  # the router's columns
    return attn + ffn


def matmul_params(dims, active_only=True):
    """Matmul parameters a token meets: every layer and `lm_head`; the
    embedding is a lookup and is not counted."""
    return (dims["n_layers"] * matmul_params_per_layer(dims, active_only)
            + dims["dim"] * dims["vocab_size"])


def train_flops_per_token(dims, seq_len):
    """Forward and backward for one token of a sequence of `seq_len`:
    6 per matmul parameter, and causal attention's two matmuls
    (scores and values), 12*L*S*d for the full square, halved because
    the mask leaves half of it."""
    attn = 12 * dims["n_layers"] * seq_len * dims["n_heads"] * dims["head_dim"]
    return 6 * matmul_params(dims) + attn // 2


def attention_flops(dims, batch, seq_len, backward):
    """One layer's causal attention over `batch` sequences: forward is
    4*S*S*H*hd operations for the full square, halved for the mask;
    backward is twice the forward."""
    fwd = 4 * batch * seq_len * seq_len * dims["n_heads"] * dims["head_dim"] // 2
    return fwd * (3 if backward else 1)
