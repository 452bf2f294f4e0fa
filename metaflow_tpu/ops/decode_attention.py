"""One new position a lane attending over a K and V pool as stored: the
decode step's attention as ONE Pallas call.

The pools are `[layers, B, S, KV * Hd]` (inference/cache.py,
`init_kv_cache`: heads folded into the minor axis, heads major), and the
kernel's operands are those pools whole, read only: no slice, reshape or
transpose of a pool happens outside it, so there is nothing for the
chip's compiler to lay out anew. The layer, each lane's depth and
position, the lanes that decode (first, in order) and their number are
scalar-prefetch arguments. The grid is (lane, block of positions); a K
and V block's index is clamped to the lane's last needed block, so the
steps past a lane's depth, and past the last lane that decodes, fetch
nothing (their block is the one already in fast memory) and compute
nothing. The online softmax's running max, sum and accumulator stay in
fast memory across a lane's blocks and the lane's output is written
once.

The arithmetic is `inference/decode.py`'s `_streamed_attention`'s: both
products on the cache's dtype with float32 accumulation; logits, mask,
max, sum and accumulator in float32; a block's probabilities rounded to
V's dtype for the second product, the running sum taken before the
rounding. The mask is `visible` (causal; a window; a ring's positions),
from indices computed in the kernel.

Heads. A value head and the key heads that share it are one step of the
kernel's loop over heads: `Wk` lanes of K (one key head's `Hd`; for
differential attention a pair's two key heads side by side, 2 Hd), `Dv`
lanes of V, and the rows of one matrix product: the G query heads of
each of those key heads. Where several key heads share a value head, a
query of key head p of the pair lies in lanes p Hd .. (p + 1) Hd of a
`Wk`-wide row with zeros beside it, so the row contracts against the
pair's lanes of K as stored: the zeros add exact zeros, and no lane is
sliced at half a register.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF

F32 = jnp.float32
LANES = 128
# `decode_block`: the most bytes of K (or of V) one grid step fetches
BLOCK_BYTES = 2 * 1024 * 1024
# a K and a V block of that size, each double-buffered, beside q, the
# output and the accumulators
KERNEL_VMEM_BYTES = 32 * 1024 * 1024


def visible(key_idx, q_pos, window=None, ring=None):
    """Which keys a query sees. key_idx: [S] indices into a layer's
    pool; q_pos: the queries' absolute positions, broadcastable against
    it. Causal: index i holds position i, seen iff i <= q. With
    `window`, only where it also lies after q - window. With `ring`
    (the pool's depth R; position p is held at index p % R), index r
    holds, as far as query q is concerned, the one position of (q - R, q]
    that falls on it, q - (q - r) % R: a later one cannot be meant, an
    earlier one has been overwritten. What was never written (a
    position before 0: a new occupant's ring still holds the last one's
    keys) is not seen either."""
    if ring is None:
        key_pos = key_idx
        seen = key_pos <= q_pos
    else:
        key_pos = q_pos - (q_pos - key_idx) % ring
        seen = key_pos >= 0
    if window is not None:
        seen &= key_pos > q_pos - window
    return seen


def sublanes(dtype):
    """Rows of one tile of the chip's layout: 8 of 4 bytes, 16 of 2."""
    return 32 // jnp.dtype(dtype).itemsize


def decode_block(depth, width, dtype):
    """The block of positions the kernel walks a pool [.., depth, width]
    of `dtype` in: a function of the shape, from a sweep on the chip
    (`scripts/decode_block_sweep.py`; PERF.md section 6, PR 35). The
    largest divisor of the depth whose K block is at most `BLOCK_BYTES`,
    in whole vector registers of 128 positions where the depth has such
    a divisor, else in whole tiles. A grid step costs half a microsecond
    beside its fetch, so at every shape swept the largest block that
    divides the pool won, up to the point where the positions a lane
    reads past its own depth (half a block on average) cost more than
    the steps saved: 512 of 4,096 at 2,560 B a position, all 640 of a
    ring, 640 of 1,280 at 2,048 B, all 2,560 at 256 B. A divisor,
    because what lies past the edge of a block that overhangs the pool
    is not zeros. None where the depth has no such divisor: the caller
    keeps the chunk loop."""
    itemsize, sub = jnp.dtype(dtype).itemsize, sublanes(dtype)
    most = min(depth, max(sub, BLOCK_BYTES // (width * itemsize)))
    for unit in (LANES, sub):
        fits = [b for b in range(unit, most + 1, unit) if depth % b == 0]
        if fits:
            return max(fits)
    return None


def fetched_positions(depth, block, pool_depth):
    """How many positions the kernel fetches for a lane `depth` deep
    (a numpy array of the lanes' depths; 0 for a lane that does not
    decode): whole blocks, never past the pool."""
    return np.minimum(-(-np.asarray(depth) // block) * block, pool_depth)


def live_lanes(valid):
    """(the lanes in the kernel's order: those that decode first, each
    group in order; how many decode) of a [B] mask."""
    return (jnp.argsort(~valid, stable=True).astype(jnp.int32),
            valid.sum(dtype=jnp.int32))


def applies(q, cache_k, cache_v, v_head_dim=None):
    """Whether the kernel takes these shapes: one new position a lane,
    pools and queries of one dtype, a value head and its key heads in
    whole lanes, a depth `decode_block` can walk."""
    B, T, H, Hd = q.shape
    Dv = v_head_dim or Hd
    if T != 1 or not (q.dtype == cache_k.dtype == cache_v.dtype):
        return False
    if cache_k.shape != cache_v.shape or cache_k.shape[3] % Hd \
            or cache_v.shape[3] % Dv:
        return False
    KV, NV = cache_k.shape[3] // Hd, cache_v.shape[3] // Dv
    if KV % NV or H % KV or (KV // NV * Hd) % LANES or Dv % LANES:
        return False
    return decode_block(cache_k.shape[2], cache_k.shape[3],
                        cache_k.dtype) is not None


def _lane(i, lanes_ref, n_ref):
    """The lane of grid row i: the i-th that decodes; past them the
    last one that does (lane 0 of the order where none does)."""
    return lanes_ref[jnp.minimum(i, jnp.maximum(n_ref[0] - 1, 0))]


def _last_block(lane, depth_ref, block):
    """The last block of positions a lane needs."""
    return jnp.maximum(pl.cdiv(depth_ref[lane], block) - 1, 0)


def _kernel(lanes_ref, n_ref, layer_ref, depth_ref, pos_ref, q_ref, k_ref,
            v_ref, o_ref, m_ref, l_ref, acc_ref, *, block, scale, window,
            ring, heads, wk, dv):
    """Grid step (i, j): block j of the i-th decoding lane. q_ref:
    [heads, R, wk]; k_ref, v_ref: [block, heads * wk], [block, heads *
    dv]; o_ref: [heads, R, dv]; m_ref, l_ref: [heads, R, 1] and acc_ref:
    [heads, R, dv], float32, carried over the lane's blocks."""
    del layer_ref
    i, j = pl.program_id(0), pl.program_id(1)
    lane = _lane(i, lanes_ref, n_ref)
    last = _last_block(lane, depth_ref, block)
    q_ref, o_ref = q_ref.at[0], o_ref.at[0]
    k_ref, v_ref = k_ref.at[0, 0], v_ref.at[0, 0]

    @pl.when((i < n_ref[0]) & (j <= last))
    def _():
        @pl.when(j == 0)
        def _():
            m_ref[...] = jnp.full(m_ref.shape, NEG_INF, F32)
            l_ref[...] = jnp.zeros(l_ref.shape, F32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

        key_idx = j * block + jax.lax.broadcasted_iota(
            jnp.int32, (1, block), 1)
        seen = visible(key_idx, pos_ref[lane], window, ring)
        for h in range(heads):
            k = k_ref[:, h * wk:(h + 1) * wk]
            v = v_ref[:, h * dv:(h + 1) * dv]
            logits = jax.lax.dot_general(
                q_ref[h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=F32) * scale
            logits = jnp.where(seen, logits, NEG_INF)
            m = m_ref[h]
            m_new = jnp.maximum(m, logits.max(axis=-1, keepdims=True))
            p = jnp.exp(logits - m_new)
            corr = jnp.exp(m - m_new)
            m_ref[h] = m_new
            l_ref[h] = l_ref[h] * corr + p.sum(axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=F32)

        @pl.when(j == last)
        def _():
            o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def attend(q, cache_k, cache_v, pos, layer, lanes, n, valid, *,
           v_head_dim=None, window=None, ring=False, dtype=None,
           block=None, interpret=False):
    """q [B, 1, H, Hd] at positions pos [B] over layer `layer` (traced)
    of the pools cache_k, cache_v [layers, B, S, KV * Hd] (V's heads
    `v_head_dim` wide; with `ring` the pool is a window layer's ring),
    for the lanes `valid` ([B] bool) names; `lanes`, `n`:
    `live_lanes(valid)`. Returns [B, 1, H, Dv] in `dtype` (None: q's),
    zeros for a lane that does not decode; such a lane's K and V are not
    fetched. The shapes are those `applies` takes; `block` (a divisor of
    S) is for the sweep and the tests, `decode_block` answers it."""
    B, _, H, Hd = q.shape
    S, W = cache_k.shape[2:]
    Dv = v_head_dim or Hd
    KV, NV = W // Hd, cache_v.shape[3] // Dv
    P, G = KV // NV, H // KV
    wk, rows = P * Hd, P * G
    block = block or decode_block(S, W, cache_k.dtype)
    # the G queries of key head p of value head n: row p * G + g of that
    # value head, in lanes p * Hd .. (p + 1) * Hd of a row wk wide
    qw = q.reshape(B, NV, P, G, 1, Hd)
    if P > 1:
        qw = qw * jnp.eye(P, dtype=q.dtype)[:, None, :, None]
    R = -(-rows // sublanes(q.dtype)) * sublanes(q.dtype)
    qw = jnp.pad(qw.reshape(B, NV, rows, wk),
                 ((0, 0), (0, 0), (0, R - rows), (0, 0)))
    depth = jnp.where(valid, pos + 1, 0).astype(jnp.int32)
    if ring:
        depth = jnp.minimum(depth, S)
    out_dtype = jnp.dtype(dtype or q.dtype)

    def lane_block(i, j, lanes_ref, n_ref, layer_ref, depth_ref, pos_ref):
        return _lane(i, lanes_ref, n_ref), 0, 0, 0

    def pool_block(i, j, lanes_ref, n_ref, layer_ref, depth_ref, pos_ref):
        lane = _lane(i, lanes_ref, n_ref)
        last = _last_block(lane, depth_ref, block)
        # past the lanes that decode: the last one's last block, again
        at = jnp.where(i < n_ref[0], jnp.minimum(j, last), last)
        return layer_ref[0], lane, at, 0

    out = pl.pallas_call(
        functools.partial(
            _kernel, block=block, scale=1.0 / math.sqrt(Hd), window=window,
            ring=S if ring else None, heads=NV, wk=wk, dv=Dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B, S // block),
            in_specs=[
                pl.BlockSpec((1, NV, R, wk), lane_block),
                pl.BlockSpec((1, 1, block, W), pool_block),
                pl.BlockSpec((1, 1, block, NV * Dv), pool_block),
            ],
            out_specs=pl.BlockSpec((1, NV, R, Dv), lane_block),
            scratch_shapes=[
                pltpu.VMEM((NV, R, 1), F32),
                pltpu.VMEM((NV, R, 1), F32),
                pltpu.VMEM((NV, R, Dv), F32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, NV, R, Dv), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=KERNEL_VMEM_BYTES),
        # no scope's name: a profile's readers find an operation by the
        # names on its path, the kernel's among them
        name="pool_attention",
        interpret=interpret,
    )(lanes, jnp.reshape(n, (1,)), jnp.reshape(layer, (1,)).astype(jnp.int32),
      depth, pos.astype(jnp.int32), qw, cache_k, cache_v)
    # a lane that did not decode left its block unwritten
    out = jnp.where(valid[:, None, None, None], out[:, :, :rows], 0)
    return out.reshape(B, 1, H, Dv)
