"""What a model's cache holds, and how a pool of it is written, cut and
reset: the ONE module that knows the cache's format.

The cache is one tree of pools, each `[passes x layers of a kind, slots]
+ its shape a slot`, and beside them at most one leaf that is no pool
(`MOE_PAIRS`). Which pools a kind of layer carries, their shape a slot
and their dtype are that kind's declaration (`POOLS`), and
`init_kv_cache`, which builds the tree from it, says what each holds. The
block (inference/decode.py) writes its new positions and cuts its rows
through `_write_layer`, `_slot_rows`, `_layer_rows` and their inverses;
the slot engine (serving/engine.py) jits `seed`, `extract` and `reset`
for a prefix's K and V and a new occupant's state. This module reads a
config (models/) and a state's size (ops/retention.py) and imports
nothing of inference/decode.py or serving/: both import from here.

Sharding: a KV pool carries the same logical axes as activations
([passes x layers of its kind, batch, seq or a ring's depth, kv_heads *
head_dim], heads major in the folded axis): under a mesh, batch rides the
data/fsdp axes and kv_heads the tensor axis, so decode parallelizes with
the exact rule table training uses (spmd/sharding.py); XLA keeps the
per-step all-gathers on ICI.
"""

import collections

import jax
import jax.numpy as jnp

from ..exception import TpuFlowException
from ..models import llama
from ..ops import retention

# A pool a kind of layer carries. shape: (cfg, max_seq_len, widest row=None)
# -> its shape a slot (the pool is [passes x layers of the kind, slots] +
# that: `passes` of them where the config runs its stack several times,
# pass t's of layer i at index t * layers + i);
# dtype: None for the cache's; recurrent: what it holds is carried from
# position to position (nothing there is overwritten before it is seen,
# so it is masked by `valid`, zeroed for a new occupant, and no KV range
# stands for it); view: a prefill program cuts its rows out once and puts
# them back (small states, and K and V of one layer that several read),
# else a layer reads and writes its rows in place; ring: K and V of the
# last positions alone, position p at index p % depth (a window layer's).
Pool = collections.namedtuple("Pool", "shape dtype recurrent view ring",
                              defaults=(False,))


def _kv_width(cfg):
    """K and V of a position, heads folded: V's heads may be fewer and
    wider than K's (`v_head_dim`), never of another width in all."""
    return cfg.n_kv_heads * cfg.head_dim


def _v_head_dim(cfg):
    """How wide a head of V is: a key head's size, or what the config
    says (differential attention: a pair's two key heads share one value
    head of twice the size)."""
    return getattr(cfg, "v_head_dim", cfg.head_dim)


def _ring_depth(cfg, seq, row):
    """How deep a window layer's pool is: the window and the widest row
    one program writes (`_write_layer` has the derivation), or without a
    bound on the row the whole sequence, where nothing ever wraps."""
    return seq if row is None else cfg.sliding_window + row


_kv_pool = Pool(lambda cfg, seq, row=None: (seq, _kv_width(cfg)),
                None, False, False)
_ring_pool = Pool(
    lambda cfg, seq, row=None: (_ring_depth(cfg, seq, row), _kv_width(cfg)),
    None, False, False, ring=True)
# one layer's K and V that the layers after it read again: a row's view
# of it (a few MB at 4,096 positions) is cut out once a program, not once
# a reading layer
_shared_kv_pool = _kv_pool._replace(view=True)
POOLS = {
    "attention": {"k": _kv_pool, "v": _kv_pool},
    "window": {"win_k": _ring_pool, "win_v": _ring_pool},
    "full": {"k": _shared_kv_pool, "v": _shared_kv_pool},
    "cross": {},   # reads the full layer's k and v, writes nothing
    "gmu": {},     # reads the memory the layer loop carries, nothing else
    "ffn": {},     # a feed-forward alone: no mixer, nothing cached
    "mamba": {
        "conv": Pool(
            lambda cfg, seq, row=None: (cfg.mamba_d_conv - 1, cfg.d_inner),
            None, True, True),
        "ssm": Pool(
            lambda cfg, seq, row=None: (cfg.mamba_d_state, cfg.d_inner),
            jnp.float32, True, True),
    },
    # Mamba-2: the tail over x, B and C together, the state a head
    # ([128, 64, 128] float32 at the published sizes: 4.19 MB a layer
    # and slot, whole tiles)
    "mamba2": {
        "conv": Pool(
            lambda cfg, seq, row=None: (cfg.conv_kernel - 1, cfg.conv_dim),
            None, True, True),
        "ssm": Pool(
            lambda cfg, seq, row=None: (
                cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state),
            jnp.float32, True, True),
    },
    # [KV, Hd, D] with D = 8,320 at a head size of 128 (the 8,256 products
    # of a symmetric square and 64 zeros: whole lanes, ops/retention.py)
    "retention": {
        "ret_s": Pool(lambda cfg, seq, row=None: (
            cfg.n_kv_heads, cfg.head_dim, retention.state_dim(cfg.head_dim)),
            jnp.float32, True, False),
        "ret_z": Pool(lambda cfg, seq, row=None: (
            cfg.n_kv_heads, retention.state_dim(cfg.head_dim)),
            jnp.float32, True, False),
    },
}

# The one leaf of the cache that is no pool: [pairs routed, pairs that
# fell on held experts] since the cache was made, uint32 (it wraps; a
# reader takes differences), summed over the expert layers of kind `ffn`
MOE_PAIRS = "moe_pairs"


def layer_kinds(cfg):
    """The kind of every layer in the model's order, a key of `POOLS`:
    "attention" (K and V cached), "mamba" and "mamba2" (a convolution
    tail and a state carried), "retention" (a state and its normaliser
    carried), "ffn" (a feed-forward alone, nothing cached),
    "window" (K and V of the last positions in a ring), "full" (K and V
    cached, for itself and the layers after it), "cross" (another
    layer's K and V read again) or "gmu" (an earlier layer's output of
    the same program, nothing cached)."""
    return getattr(cfg, "layer_kinds", None) or ("attention",) * cfg.n_layers


def stack_passes(cfg):
    """How many times a token goes through the model's stack, over the
    same weights (the config's `passes`; 1 where it declares none). Pass
    t of layer i has pool index t * layers + i: each pass keeps K and V
    of its own. Only a stack of `attention` layers goes round: what a
    recurrent state, a ring or a pool that other layers read again is
    from pass to pass is not defined."""
    passes = getattr(cfg, "passes", 1)
    if passes > 1 and set(layer_kinds(cfg)) != {"attention"}:
        raise TpuFlowException(
            "a stack that is run %d times over the same weights is built "
            "for attention layers alone, not for %s"
            % (passes, sorted(set(layer_kinds(cfg)) - {"attention"})))
    return passes


def cache_pools(cfg):
    """{pool name: (its Pool, how many indices it has: the layers that
    carry it, times the config's passes)} of the model's cache, from what
    each kind of layer present declares."""
    kinds = layer_kinds(cfg)
    return {name: (pool, stack_passes(cfg) * kinds.count(kind))
            for kind in sorted(set(kinds))
            for name, pool in POOLS[kind].items()}


def recurrent_pools(cfg):
    """The names of the pools that hold recurrent state."""
    return sorted(name for name, (pool, _) in cache_pools(cfg).items()
                  if pool.recurrent)


def ring_pools(cfg):
    """The names of the pools that are rings: K and V of the kinds of
    layer whose queries see a window."""
    return sorted(name for name, (pool, _) in cache_pools(cfg).items()
                  if pool.ring)


def is_recurrent(cfg):
    """Whether some layer carries a state that a KV range does not
    hold: such a model's prefix is not its cached K and V."""
    return bool(recurrent_pools(cfg))


def init_kv_cache(cfg, batch_size, max_seq_len, dtype=None, row=None):
    """The static cache, one tree of the pools the model's kinds of
    layer declare (`POOLS`), each [passes x layers of the kind, batch] +
    its shape a slot (pass t of layer i at index t * layers + i; one pass
    for every config that declares no `passes`): `k` and `v` [passes x
    attention layers, batch, max_seq, kv_heads * head_dim] (for a model
    with ONE full-attention layer that others read again, that layer's
    alone); for window layers `win_k` and
    `win_v` [.., sliding_window + row, kv_heads * head_dim], a ring
    (`row`: the most positions one program writes into a row; None: no
    bound, and the pool is max_seq deep); for Mamba layers `conv` [..,
    d_conv-1, d_inner] (the convolution's tail) and `ssm` [.., d_state,
    d_inner] in float32; for retention layers `ret_s` [.., kv_heads,
    head_dim, D] and `ret_z` [.., kv_heads, D] in float32. A stack with
    no attention layer has no `k` and `v`. Every pool has the batch on
    axis 1. A model with expert layers of kind `ffn` also gets
    `moe_pairs` (`MOE_PAIRS`), two counters and no pool.

    The pools are read and written a layer at a time in place
    (`_decode_layer`): heads and head size are folded into one minor
    axis (heads major), so that the indexed write and the chunk reads
    meet rows of whole lanes whatever the number of KV heads, and a
    single KV head (multi-query) leaves no axis of 1 for the chip's
    tiling to pad or to lay out anew on the way in and out."""
    dt = jnp.dtype(dtype) if dtype is not None else llama.param_dtype(cfg)
    cache = {name: jnp.zeros(
                 (layers, batch_size) + pool.shape(cfg, max_seq_len, row),
                 pool.dtype or dt)
             for name, (pool, layers) in cache_pools(cfg).items()}
    if "ffn" in layer_kinds(cfg):
        cache[MOE_PAIRS] = jnp.zeros((2,), jnp.uint32)
    return cache


def _write_layer(pool, new, pos, layer, slots=None, ring=False):
    """new [B, T, KV, Hd] into pool [layers, B, S, KV * Hd] at `layer`,
    every batch row at its own cursor (or all at a scalar `pos`); with
    `slots` ([B] distinct), row b of `new` into row slots[b] of the pool.

    A row of a prefill program is padded to the program's width, so its
    last positions may lie past the pool's edge: those land on the last
    position, which is past the row's cursor like every padded position
    and so overwritten before it is seen (a clamped block write would
    shift the real positions instead).

    With `ring` the pool is a window layer's, S = window + the widest
    row a program writes, and position p lands on index p % S. The
    engine's invariant, "garbage is overwritten before it is seen",
    holds there too. A program that writes positions c .. c + T - 1 of a
    row (T <= S - window) overwrites what stood at c + t - S, and the
    earliest position any query from c on still sees is c - window + 1 >
    c + t - S: nothing a live query needs is lost, whether position c + t
    is real or pads the row. What a padded position (or a masked lane's
    write at its cursor c) leaves at index (c' + x) % S, x < T, for the
    row's next cursor c', a later query q >= c' takes for position c' +
    x - S (`_visible`: the one position of (q - S, q] on that index)
    until position c' + x itself is written over it, and c' + x - S <= q
    - window: outside the window. So a ring needs no mask on its writes
    and no reset for a new occupant, whose queries at q < S take every
    index past q for a position before 0."""
    new = new.reshape(new.shape[:2] + (-1,))
    B, T = new.shape[:2]
    if jnp.ndim(pos) == 0:
        if not ring:
            return jax.lax.dynamic_update_slice(
                pool, new[None], (layer, 0, pos, 0))
        pos = jnp.full((B,), pos)
    rows = jnp.arange(B) if slots is None else slots
    at = pos[:, None] + jnp.arange(T)[None]
    at = at % pool.shape[2] if ring else jnp.minimum(at, pool.shape[2] - 1)
    return pool.at[layer, rows[:, None], at].set(
        new, mode="promise_in_bounds")


def _slot_rows(pool, slots, layer=None):
    """Rows `slots` ([R], traced) of a pool [layers, B, ...], each cut
    out of its own slot, of every layer or of `layer` alone: a pool
    [layers or 1, R, ...] that holds just those rows."""
    size = (pool.shape[0] if layer is None else 1, 1) + pool.shape[2:]
    rest = (0,) * (pool.ndim - 2)
    return jnp.concatenate([
        jax.lax.dynamic_slice(
            pool, (0 if layer is None else layer, slots[r]) + rest, size)
        for r in range(slots.shape[0])], axis=1)


def _put_slot_rows(pool, rows, slots, layer=None):
    """`_slot_rows(pool, slots, layer)` back into the pool, in place."""
    rest = (0,) * (pool.ndim - 2)
    for r in range(slots.shape[0]):
        pool = jax.lax.dynamic_update_slice(
            pool, rows[:, r:r + 1],
            (0 if layer is None else layer, slots[r]) + rest)
    return pool


def _layer_rows(pool, layer, slots):
    """Layer `layer` of a pool [layers, B, ...]: the whole batch, or the
    rows `slots` names, [R, ...]."""
    if slots is None:
        return jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
    return _slot_rows(pool, slots, layer)[0]


def _put_layer_rows(pool, rows, layer, slots):
    """`_layer_rows(pool, layer, slots)` back into the pool, in place."""
    if slots is None:
        return jax.lax.dynamic_update_index_in_dim(pool, rows, layer, 0)
    return _put_slot_rows(pool, rows[None], slots, layer)


def seed(cache, k, v, slot):
    """Write a [layers, T, kv_heads, head_dim] KV range into one slot's
    cache view starting at position 0; slot is TRACED so compiles are
    bounded by the T bucket, not the pool size. The pools fold heads and
    head size into one axis (init_kv_cache); the host's contract keeps
    them apart."""
    fold = lambda a: a.reshape(a.shape[0], 1, a.shape[1], -1)
    return dict(cache, **{
        name: jax.lax.dynamic_update_slice(cache[name], fold(new),
                                           (0, slot, 0, 0))
        for name, new in (("k", k), ("v", v))})


def extract(cfg, cache, slot, T):
    """The first T positions of one slot's view, (k, v) each [layers, T,
    kv_heads, head_dim]; T is STATIC (callers pass a power-of-two bucket
    and trim on host)."""
    L, width = cache["k"].shape[0], cache["k"].shape[3]
    return tuple(
        jax.lax.dynamic_slice(
            cache[name], (0, slot, 0, 0), (L, 1, T, width)
        ).reshape(L, T, cfg.n_kv_heads, cfg.head_dim)
        for name in ("k", "v"))


def reset(cache, slot, names):
    """The cache with slot `slot` (traced) of the pools `names` zeroed: a
    new occupant starts from an empty recurrent state; its K and V need
    no reset (overwritten before they are seen)."""
    cache = dict(cache)
    for name in names:
        arr = cache[name]
        cache[name] = jax.lax.dynamic_update_slice_in_dim(
            arr, jnp.zeros(arr.shape[:1] + (1,) + arr.shape[2:], arr.dtype),
            slot, axis=1)
    return cache


def kv_position_bytes(cache):
    """Bytes that K and V of one position take, over every index of the
    pools `k` and `v` (the layers that cache them, times the passes)."""
    k = cache["k"]
    layers, _, _, width = k.shape   # width: kv_heads * head_dim
    return 2 * layers * width * k.dtype.itemsize
