"""Power retention of degree 2 with a gate (arXiv:2507.04239, "Scaling
Context Requires Rethinking Attention"), from a carried state.

Per KV head, with query heads in that head's group, Hd the head size:

  a_ts = exp(sum_{l=s+1..t} log g_l) * (q_t . k_s)^2 / Hd      s <= t
  y_t  = sum_s a_ts v_s / (sum_s a_ts + eps)

which is a recurrence over a state of fixed size: with `phi(u)` the
symmetric square of u / Hd^(1/4), so that phi(a) . phi(b) = (a . b)^2 / Hd,

  S_t = g_t S_{t-1} + v_t phi(k_t)^T      [Hd, D]
  z_t = g_t z_{t-1} + phi(k_t)            [D]
  y_t = S_t phi(q_t) / (z_t . phi(q_t) + eps)

`step` is that recurrence for one token and `chunk` the same thing for T
tokens at once (the attention form inside the chunk, plus what the state
before it adds), both continuing from a carried (S, z) and returning what
the next call needs. Both take `valid`, as ops/ssm.py does: a mask whose
true positions LEAD each row; a position that is not valid has g = 1 and
adds nothing, so the state after a chunk is the state after its last real
token, and a decode step's lanes that hold no request keep their state
bit for bit.

**The state's layout.** S is held [Hd, D] with the expanded axis D minor
(the transpose of the equation's phi(k) v^T), float32. The Hd (Hd + 1) / 2
distinct products u_i u_j (i <= j) are laid out in Hd / 2 + 1 segments of
Hd, so that D = (Hd / 2 + 1) Hd is a whole number of the chip's 128 lanes
at Hd = 128 (8,320: the 8,256 products and 64 zeros): segment i holds
u_i u_j at j >= i, and at j < i the products of row Hd - i,
u_{Hd-i} u_{Hd-i+j}; off-diagonal products carry sqrt 2, every product
1 / sqrt Hd, the pads 0.

**The one-token update of a whole pool** (`update_pool`): on a TPU a
Pallas kernel reads each decoding lane's [Hd, D] state of each KV head
once, scales it, adds v phi(k)^T, writes it to the same buffer
(`input_output_aliases`), and takes the group's query heads' products
while the tile is in fast memory; the outer product is never built in
device memory, a lane that holds no request is not read (its index is not
in the scalar-prefetched list of lanes), and no other row of the pool
moves. Elsewhere (a CPU-pinned process) the same numbers come from plain
`jax.numpy` on that layer of the pool. The choice is the lowering
platform's (`jax.lax.platform_dependent`), so a compile for a described
chip holds the kernel.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
# the kernel holds a lane's whole [Hd, D] state of one KV head in fast
# memory, in and out, each double-buffered: 4 x 4.26 MB at Hd = 128
KERNEL_VMEM_BYTES = 40 * 1024 * 1024


def state_dim(head_dim):
    """D: the expanded axis of a state, (Hd / 2 + 1) Hd."""
    if head_dim % 2:
        raise ValueError("power retention's state layout needs an even "
                         "head size, got %d" % head_dim)
    return (head_dim // 2 + 1) * head_dim


@functools.lru_cache(maxsize=None)
def _phi_tables(head_dim):
    """(first index, second index, weight) of every entry of phi, [D]
    each: phi(u)[d] = weight[d] * u[first[d]] * u[second[d]]."""
    Hd = head_dim
    i = np.arange(Hd // 2 + 1)[:, None]
    j = np.arange(Hd)[None, :]
    upper = j >= i
    first = np.where(upper, i, (Hd - i) % Hd)
    second = np.where(upper, j, (Hd - i + j) % Hd)
    weight = np.where(first == second, 1.0, math.sqrt(2.0)) / math.sqrt(Hd)
    # below the diagonal segment 0 has no row to pair with, and segment
    # Hd / 2 would hold its own row again
    weight = np.where(upper | ((i > 0) & (i < Hd // 2)), weight, 0.0)
    return (first.reshape(-1), second.reshape(-1),
            weight.reshape(-1).astype(np.float32))


def phi(u):
    """The symmetric square of u / Hd^(1/4): [..., Hd] -> [..., D] in
    float32, phi(a) . phi(b) = (a . b)^2 / Hd. Each factor is picked by a
    product with a constant 0/1 matrix (a gather over the minor axis is
    slow on the chip; the product is exact for a bfloat16 u, and float32
    is asked its full precision)."""
    first, second, weight = _phi_tables(u.shape[-1])
    precision = (jax.lax.Precision.HIGHEST if u.dtype == jnp.float32
                 else None)
    pick = lambda index: jnp.matmul(
        u, jax.nn.one_hot(index, u.shape[-1], dtype=u.dtype, axis=0),
        precision=precision, preferred_element_type=F32)
    return pick(first) * pick(second) * weight


def _grouped(q, n_kv_heads):
    """[..., H, Hd] -> [..., KV, G, Hd]: heads k*G .. k*G+G-1 share KV
    head k."""
    return q.reshape(q.shape[:-2] + (n_kv_heads, -1, q.shape[-1]))


def step(S, z, q, k, v, log_g, eps, valid=None):
    """One token from a carried state. S: [B, KV, Hd, D] and z:
    [B, KV, D] float32; q: [B, H, Hd]; k, v: [B, KV, Hd]; log_g:
    [B, KV] float32 (<= 0); valid: [B] or None. Returns (y [B, H, Hd]
    float32, S', z'), the state unchanged in the rows that are not
    valid: `update_pool` on pools of one layer."""
    y, S, z = update_pool(S[None], z[None], 0, q, k, v, log_g, eps, valid)
    return y, S[0], z[0]


def _step_terms(q, k, log_g, valid):
    """(g [B, KV], phi(k) [B, KV, D], phi(q) [B, KV, G, D]) of one
    token, g = 1 and phi(k) = 0 where the row is not valid."""
    g, pk = jnp.exp(log_g.astype(F32)), phi(k)
    if valid is not None:
        g = jnp.where(valid[:, None], g, 1.0)
        pk = jnp.where(valid[:, None, None], pk, 0.0)
    return g, pk, phi(_grouped(q, k.shape[-2]))


def chunk(S, z, q, k, v, log_g, eps, valid=None):
    """T tokens from a carried state: the attention form inside the
    chunk plus exp(b_t) phi(q_t)^T S from before it, b the chunk's
    running sum of log g. q: [B, T, H, Hd]; k, v: [B, T, KV, Hd]; log_g:
    [B, T, KV] float32; valid: [B, T] or None. Returns (y [B, T, H, Hd]
    float32, S', z') after the last valid position.

    The products over the expanded axis take phi(q) and the weighted
    phi(k) in the activations' dtype (bfloat16 on the chip, as attention
    takes its probabilities) with float32 accumulation; the state itself
    stays float32."""
    B, T, H, Hd = q.shape
    KV = k.shape[2]
    log_g = log_g.astype(F32)
    if valid is not None:
        log_g = jnp.where(valid[..., None], log_g, 0.0)
    b = jnp.cumsum(log_g, axis=1).transpose(0, 2, 1)           # [B, KV, T]
    qg = _grouped(q, KV)                                       # [B,T,KV,G,Hd]
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, k,
                        preferred_element_type=F32)
    seen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]    # [t, s]
    if valid is not None:
        seen = seen[None] & valid[:, None, :]                  # [B, t, s]
        seen = seen[:, None, None]
    decay = jnp.where(seen, b[:, :, None, :, None] - b[:, :, None, None, :],
                      -jnp.inf)
    a = jnp.square(scores) * (jnp.exp(decay) / Hd)             # [B,KV,G,T,T]
    num = jnp.einsum("bkgts,bske->btkge", a.astype(v.dtype), v,
                     preferred_element_type=F32)
    den = a.sum(-1).transpose(0, 3, 1, 2)                      # [B,T,KV,G]
    # what the state before the chunk adds
    before = jnp.exp(b).transpose(0, 2, 1)[..., None]          # [B,T,KV,1]
    pq = phi(qg).astype(q.dtype)                               # [B,T,KV,G,D]
    num += before[..., None] * jnp.einsum(
        "btkgd,bked->btkge", pq, S, preferred_element_type=F32)
    den += before * jnp.einsum("btkgd,bkd->btkg", pq, z,
                               preferred_element_type=F32)
    y = num / (den[..., None] + eps)
    # the state after the chunk's last valid position
    left = jnp.exp(b[:, :, -1:] - b).transpose(0, 2, 1)        # [B, T, KV]
    if valid is not None:
        left = jnp.where(valid[..., None], left, 0.0)
    pk = phi(k) * left[..., None]                              # [B,T,KV,D]
    whole = jnp.exp(b[:, :, -1])                               # [B, KV]
    S = whole[..., None, None] * S + jnp.einsum(
        "btke,btkd->bked", v, pk.astype(v.dtype), preferred_element_type=F32)
    z = whole[..., None] * z + pk.sum(1)
    return y.reshape(B, T, H, Hd), S, z


# ---- the one-token update of a whole pool, in place ----

def _update_kernel(lanes_ref, meta_ref, s_ref, pkq_ref, vb_ref, gb_ref,
                   o_ref, num_ref, *, group, seg):
    """One decoding lane's state of one KV head: S' = g S + v phi(k)^T
    written where S was read, and S' phi(q) of the group's query heads.
    s_ref / o_ref: [Hd, D]; pkq_ref: [rows, D], rows 0 .. group-1 phi(q)
    of the group's heads and row `group` phi(k); vb_ref: [Hd, seg], v
    along the sublanes; gb_ref: [8, seg], g everywhere; num_ref:
    [Hd, seg], head h's products in lane h."""
    del lanes_ref
    n = meta_ref[1]
    Hd, D = s_ref.shape[-2:]
    s_ref, o_ref = s_ref.at[0, 0, 0], o_ref.at[0, 0, 0]
    pkq_ref, vb_ref, num_ref = pkq_ref.at[0, 0], vb_ref.at[0, 0], \
        num_ref.at[0, 0]

    @pl.when(pl.program_id(0) < n)
    def _():
        g = gb_ref[0, 0]
        lane = jax.lax.broadcasted_iota(jnp.int32, (8, num_ref.shape[-1]), 1)

        def eight_rows(r, _):
            rows = pl.ds(pl.multiple_of(r * 8, 8), 8)
            vb = vb_ref[rows, :]
            acc = [jnp.zeros((8, seg), F32)] * group
            for at in range(0, D, seg):
                cols = pl.ds(at, seg)
                new = g * s_ref[rows, cols] \
                    + vb * pkq_ref[group:group + 1, cols]
                o_ref[rows, cols] = new
                acc = [a + new * pkq_ref[h:h + 1, cols]
                       for h, a in enumerate(acc)]
            out = jnp.zeros(lane.shape, F32)
            for h, a in enumerate(acc):
                out = jnp.where(lane == h, a.sum(axis=1, keepdims=True), out)
            num_ref[rows, :] = out

        jax.lax.fori_loop(0, Hd // 8, eight_rows, None)

    # nothing decodes: the one block that was fetched goes back as it came
    @pl.when(n == 0)
    def _():
        o_ref[...] = s_ref[...]


def _update_state_kernel(pool, layer, g, pk, pq, v, valid, interpret=False):
    """`_update_state_xla` as one Pallas call over the lanes that are
    valid ([B] bool), the pool aliased to the output."""
    _, B, KV, Hd, D = pool.shape
    G = pq.shape[2]
    rows = -(-(G + 1) // 8) * 8
    # the chip's 128 lanes at a time; a size that has no whole lanes (a
    # test's, interpreted) goes as one piece
    seg = 128 if D % 128 == 0 else D
    # the valid lanes first, in order; the grid's steps past them fetch
    # nothing (their blocks are the last valid step's) and compute nothing
    lanes = jnp.argsort(~valid, stable=True).astype(jnp.int32)
    n = valid.sum(dtype=jnp.int32)
    meta = jnp.stack([jnp.asarray(layer, jnp.int32), n])
    pkq = jnp.concatenate(
        [pq, pk[:, :, None], jnp.zeros((B, KV, rows - G - 1, D), F32)],
        axis=2)
    vb = jnp.broadcast_to(v.astype(F32)[..., None], (B, KV, Hd, seg))
    gb = jnp.broadcast_to(g[..., None, None], (B, KV, 8, seg))

    def at(i, h, lanes_ref, meta_ref):
        live = i < meta_ref[1]
        last = jnp.maximum(meta_ref[1] - 1, 0)
        return lanes_ref[jnp.minimum(i, last)], jnp.where(live, h, KV - 1)

    def pool_block(i, h, lanes_ref, meta_ref):
        return (meta_ref[0],) + at(i, h, lanes_ref, meta_ref) + (0, 0)

    def lane_block(i, h, lanes_ref, meta_ref):
        return at(i, h, lanes_ref, meta_ref) + (0, 0)

    pool, num = pl.pallas_call(
        functools.partial(_update_kernel, group=G, seg=seg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, KV),
            in_specs=[
                pl.BlockSpec((1, 1, 1, Hd, D), pool_block),
                pl.BlockSpec((1, 1, rows, D), lane_block),
                pl.BlockSpec((1, 1, Hd, seg), lane_block),
                pl.BlockSpec((1, 1, 8, seg), lane_block),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, 1, Hd, D), pool_block),
                pl.BlockSpec((1, 1, Hd, seg), lane_block),
            ]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((B, KV, Hd, seg), F32)],
        # operand 2 counts the two scalar-prefetch arguments
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=KERNEL_VMEM_BYTES),
        name="retention_update",
        interpret=interpret,
    )(lanes, meta, pool, pkq, vb, gb)
    # a lane that did not decode left its block of `num` unwritten
    num = jnp.where(valid[:, None, None, None], num[..., :G], 0.0)
    return pool, num.transpose(0, 1, 3, 2)


def _update_state_xla(pool, layer, g, pk, pq, v, valid):
    """Layer `layer` of pool [layers, B, KV, Hd, D]: S' = g S + v phi(k)^T
    written back, and S' phi(q): (pool, [B, KV, G, Hd])."""
    del valid   # g = 1 and phi(k) = 0 there: S' is S bit for bit
    S = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
    S = g[..., None, None] * S + v.astype(F32)[..., :, None] * pk[..., None, :]
    num = jnp.einsum("bked,bkgd->bkge", S, pq)
    return jax.lax.dynamic_update_index_in_dim(pool, S, layer, 0), num


def whole_tiles(pool_s):
    """Whether the kernel takes a pool of this shape: its blocks, a KV
    head's [Hd, D], are whole tiles of the chip."""
    return pool_s.shape[-2] % 8 == 0 and pool_s.shape[-1] % 128 == 0


def update_pool(pool_s, pool_z, layer, q, k, v, log_g, eps, valid=None):
    """`step` for every slot of a pool at once, layer `layer` (traced) of
    pool_s [layers, B, KV, Hd, D] and pool_z [layers, B, KV, D] updated
    in place; q: [B, H, Hd]; k, v: [B, KV, Hd]; log_g: [B, KV]; valid:
    [B] or None. Returns (y [B, H, Hd] float32, pool_s, pool_z)."""
    g, pk, pq = _step_terms(q, k, log_g, valid)
    z = g[..., None] * jax.lax.dynamic_index_in_dim(
        pool_z, layer, 0, keepdims=False) + pk
    pool_z = jax.lax.dynamic_update_index_in_dim(pool_z, z, layer, 0)
    den = jnp.einsum("bkd,bkgd->bkg", z, pq)
    if valid is None:
        valid = jnp.ones(q.shape[:1], bool)
    args = (pool_s, jnp.asarray(layer, jnp.int32), g, pk, pq, v, valid)
    if whole_tiles(pool_s):
        pool_s, num = jax.lax.platform_dependent(
            *args, tpu=_update_state_kernel, default=_update_state_xla)
    else:
        pool_s, num = _update_state_xla(*args)
    y = num / (den[..., None] + eps)
    return y.reshape(q.shape), pool_s, pool_z
