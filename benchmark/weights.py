"""Weights from the seed, made on the device in the type they are
served in. The layout (names, stacked layers) is the program's
interface and the family's to state (benchmark/families/); the values
are the benchmark's: N(0, 1/fan_in) matrices, unit norm weights, and
what a family draws with an initialiser of its own. Each leaf has a key
of its own, so the reference can make one leaf again without the rest.
"""

import jax
import jax.numpy as jnp

from . import families


def leaf_specs(dims):
    """{leaf path: (shape, init)} of the family's tree; a path may open
    any number of groups, and layer leaves carry their leading layer
    axis. `init` is a fan-in (N(0, 1/fan_in)), None (ones), or a
    function (key, shape) -> float32 array of the family's own."""
    return families.load(dims["family"]).leaf_specs(dims)


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
    shape, init = specs[path]
    if init is None:
        return jnp.ones(shape, dtype)
    key = jax.random.fold_in(key, sorted(specs).index(path))
    if callable(init):
        return init(key, shape).astype(dtype)
    lead = shape[:-2]
    n = 1
    for s in lead:
        n *= s

    def draw(k):
        return (jax.random.normal(k, shape[-2:], jnp.float32)
                * (init ** -0.5)).astype(dtype)

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
