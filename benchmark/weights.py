"""Weights from the seed, made on the device in the type they are
served in. The layout (names, stacked layers) is the program's
interface; the values are the benchmark's: N(0, 1/fan_in) matrices and
unit norm weights. Each leaf has a key of its own, so the reference can
make one leaf again without the rest.
"""

import jax
import jax.numpy as jnp


def leaf_specs(dims):
    """{leaf path: (shape, fan_in or None for a norm weight)}; layer
    leaves carry the leading layer axis."""
    L, D, F, V = (dims["n_layers"], dims["dim"], dims["ffn_dim"],
                  dims["vocab_size"])
    H, KV, Hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    N = dims.get("n_experts", 0)
    ex = (N,) if N else ()
    specs = {
        ("embed",): ((V, D), D),
        ("layers", "attn_norm"): ((L, D), None),
        ("layers", "wq"): ((L, D, H * Hd), D),
        ("layers", "wk"): ((L, D, KV * Hd), D),
        ("layers", "wv"): ((L, D, KV * Hd), D),
        ("layers", "wo"): ((L, H * Hd, D), H * Hd),
        ("layers", "ffn_norm"): ((L, D), None),
        ("layers", "w_gate"): ((L,) + ex + (D, F), D),
        ("layers", "w_up"): ((L,) + ex + (D, F), D),
        ("layers", "w_down"): ((L,) + ex + (F, D), F),
        ("final_norm",): ((D,), None),
        ("lm_head",): ((D, V), D),
    }
    if N:
        specs[("layers", "router")] = ((L, D, N), D)
    return specs


def seed_key(seed):
    """A key for any whole number: seeds run past 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


def make_leaf(key, dims, path):
    """One leaf, traced: matrices are drawn one slice of the two last
    axes at a time, so the float32 draw of a stacked expert leaf never
    exists whole."""
    dtype = jnp.dtype(dims["dtype"])
    specs = leaf_specs(dims)
    shape, fan_in = specs[path]
    if fan_in is None:
        return jnp.ones(shape, dtype)
    key = jax.random.fold_in(key, sorted(specs).index(path))
    lead = shape[:-2]
    n = 1
    for s in lead:
        n *= s

    def draw(k):
        return (jax.random.normal(k, shape[-2:], jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    if not lead:
        return draw(key)
    return jax.lax.map(draw, jax.random.split(key, n)).reshape(shape)


def init_params(key, dims):
    """The whole tree, traced; call it under one jit."""
    tree = {}
    for path in leaf_specs(dims):
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = make_leaf(key, dims, path)
    return tree
