"""What one call of a kernel needs, from the configuration's sizes and
the run's own counts alone: (operations, bytes moved to or from device
memory). A multiply-add counts as two operations; what a kernel computes
again (a backward pass's recomputation) and what it reads beyond its need
(padding, slots that hold nothing) are never counted, so a share of a
roofline says how near the kernel came to the least time the chip could
take for the work, and cannot pass 100 %.

A reader in `layer_metrics/` takes the sizes from `run` (`dims`, `chips`,
`peak`, and a serving run's `slots`, `max_seq_len`, `prefill_chunk`,
`decode_tokens`, `kv_positions_read`), the kernel's device time from the
trace (`span_readings.scope_ms`), and divides: `roofline_pct`. A new
family's kernels bring cost functions of their own in their reader's
file; nothing here is edited for them.
"""

from . import flops

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def roofline_pct(cost, seconds, peak, chips=1):
    """The least time `chips` chips could take for `cost`, the larger of
    operations over the compute peak and bytes over the bandwidth peak,
    as a share of the `seconds` measured."""
    least, _ = bound(cost, peak, chips)
    return 100.0 * least / seconds


def bound(cost, peak, chips=1):
    """(least seconds, which peak sets them: "compute" or "bandwidth")."""
    ops, nbytes = cost
    compute = ops / (chips * peak["bf16_flops_per_s"])
    bandwidth = nbytes / (chips * peak["hbm_bytes_per_s"])
    return max(compute, bandwidth), \
        "compute" if compute >= bandwidth else "bandwidth"


def kv_bytes_per_position(dims):
    """K and V of one cached position, all layers, as stored."""
    return (dims["n_layers"] * 2 * dims["n_kv_heads"] * dims["head_dim"]
            * ITEMSIZE[dims["dtype"]])


def decode_attention(dims, kv_positions):
    """One decode step's attention in every layer, where the step's
    queries attend to `kv_positions` cached positions in all (each
    slot's filled length, summed over the slots that hold a request): K
    and V of each are read once; scores and values are 2 * heads * head
    size multiply-adds a position. The queries and the output, one
    position a slot, are left out."""
    ops = (dims["n_layers"] * 4 * dims["n_heads"] * dims["head_dim"]
           * kv_positions)
    return ops, kv_bytes_per_position(dims) * kv_positions


def moe_experts(dims, tokens):
    """The expert matrices of every layer over one step of `tokens`
    tokens: each expert that a token reaches is read once, the experts
    reached counted as uniform routing would have it (every one, from a
    few dozen tokens on); three products of dim x ffn_dim for each of a
    token's experts. Activations are left out."""
    experts, top_k = dims["n_experts"], dims["experts_per_tok"]
    per_expert = 3 * dims["dim"] * dims["ffn_dim"]
    reached = experts * (1.0 - (1.0 - top_k / experts) ** tokens)
    ops = dims["n_layers"] * 2 * tokens * top_k * per_expert
    return ops, (dims["n_layers"] * reached * per_expert
                 * ITEMSIZE[dims["dtype"]])


def flash_attention_train(dims, sequences, seq_len):
    """Causal attention of every layer over one train step, forward and
    backward (`flops.attention_flops`; the backward kernel's second
    forward is not counted). The forward reads q, k, v and writes the
    output; the backward reads those four and the output's gradient and
    writes three gradients; the row statistics are left out."""
    ops = dims["n_layers"] * flops.attention_flops(
        dims, sequences, seq_len, backward=True)
    rows = sequences * seq_len * dims["head_dim"] * ITEMSIZE[dims["dtype"]]
    q, kv = rows * dims["n_heads"], rows * dims["n_kv_heads"]
    return ops, dims["n_layers"] * ((2 * q + 2 * kv) + (5 * q + 4 * kv))
