"""Operations the algorithm needs, from shapes alone.

`dims` is the dictionary `configs.dims()` makes of a configuration file;
what a token meets in a layer is its family's to count
(benchmark/families/). A multiply-add counts as two operations.
Recomputed operations (remat, the flash kernel's backward recomputation)
are never counted.
"""

from . import families


def matmul_params(dims, active_only=True):
    """Matmul parameters a token meets; with `active_only` false, every
    one the model holds."""
    return families.load(dims["family"]).matmul_params(dims, active_only)


def train_flops_per_token(dims, seq_len):
    """Forward and backward for one token of a sequence of `seq_len`."""
    return families.load(dims["family"]).train_flops_per_token(dims, seq_len)


def attention_flops(dims, batch, seq_len, backward):
    """One layer's causal attention over `batch` sequences: forward is
    4*S*S*H*hd operations for the full square, halved for the mask;
    backward is twice the forward."""
    fwd = 4 * batch * seq_len * seq_len * dims["n_heads"] * dims["head_dim"] // 2
    return fwd * (3 if backward else 1)
