"""Share of its roofline at which `moe_experts` ran in the decode program
of a latent expert layer of which this chip holds a share: what the held
experts' matrices need for a step's tokens (a step's mean over the
window, `decode_tokens` over `decode_steps`) over the scope's device time
per execution in the traced slice."""
from benchmark import kernel_costs, span_readings


def experts_cost(dims, tokens):
    """(operations, bytes) of every expert layer over one step of
    `tokens` tokens: each held expert that a token reaches is read once,
    two matrices of latent x expert width, the experts reached counted as
    uniform routing over ALL routed experts would have it (a held expert
    is missed by a token with probability 1 - k / routed); two products
    for each pair that uniform routing puts on a held expert (k x held /
    routed of a token's k). Activations and the buffers' padding are left
    out."""
    held, routed, k = (dims["n_experts_held"], dims["n_experts"],
                       dims["experts_per_tok"])
    per_expert = 2 * dims["moe_latent"] * dims["expert_dim"]
    reached = held * (1.0 - (1.0 - k / routed) ** tokens)
    layers = dims["n_moe_layers"]
    ops = layers * 2 * tokens * k * held / routed * per_expert
    return ops, (layers * reached * per_expert
                 * kernel_costs.ITEMSIZE[dims["dtype"]])


def read(run):
    ms = span_readings.scope_ms(span_readings.trace(run),
                                span_readings.DECODE_PROGRAMS,
                                ("moe_experts",))
    steps = run["counters"]["decode_steps"]
    if ms is None or not run.get("peak") or not steps:
        return None
    tokens = run["decode_tokens"] / steps
    cost = experts_cost(run["dims"], tokens)
    print("[roofline] moe_experts (latent, a share): %.1f tokens a step, "
          "%.3f GB, %.1f GFLOP in %.3f ms, bound by %s"
          % (tokens, cost[1] / 1e9, cost[0] / 1e9, ms,
             kernel_costs.bound(cost, run["peak"])[1]), flush=True)
    return kernel_costs.roofline_pct(cost, ms * 1e-3, run["peak"])
