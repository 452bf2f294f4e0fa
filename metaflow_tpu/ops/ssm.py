"""The selective state-space recurrences of a Mamba-1 and of a Mamba-2
mixer, and the short causal convolution before either, from a carried
state.

A sequence is processed a chunk at a time (a prompt's prefill chunks,
then one token a decode step), so both ops start from what the chunks
before left behind and return what the next one needs:

  - the convolution's tail: the last `d_conv - 1` inputs, [B, K-1, Di];
  - the recurrence's state h, [B, N, Di] in float32 (d_state N on the
    second-minor axis and the channels Di on the minor one: a float32
    tile is 8 x 128, so [.., 16, 5120] is stored without padding where
    [.., 5120, 16] would take eight times its size).

Both take `valid`, a [B, T] mask whose true positions LEAD each row (a
chunk padded to its bucket; a decode step's lanes that hold no decoding
request, with T = 1): where a position is not valid the state and the
tail pass through, so the state after a chunk is the state after its
last real token. The outputs at such positions are garbage the caller
never reads.

The step and chunk functions are plain `jax.numpy`: the recurrence is a
`lax.scan` over the chunk's positions (at most a prefill chunk, 64)
whose body is elementwise over [B, N, Di]; one token is the body alone
(`selective_step`, `ssd_step`). The recurrence is float32 whatever the
model's dtype.

**The one-token update of a whole pool** (`selective_update_pool`,
`ssd_update_pool`; ops/retention.py's `update_pool` is the same thing
for its layer): a decode step of the whole pool does not cut a layer's
state out of the pool for every lane, select and put it back. On a TPU
one Pallas call a layer walks the lanes that decode (scalar-prefetched:
those first, and how many), fetches one lane's state of that layer as
stored, computes the plain step's float32 arithmetic on it, and writes
it where it was read (`input_output_aliases`); a lane that holds no
decoding request is neither read nor written, and with none decoding
the pool comes back as it went. Elsewhere (a CPU-pinned process, a state
that is no whole 8 x 128 tiles) the same numbers come from the plain
step on that layer of the pool. The choice is the lowering platform's
(`jax.lax.platform_dependent`), so a compile for a described chip holds
the kernel. Every other caller (a prefill program's rows, `generate()`,
a mesh, the models' own forwards) calls the plain functions, which are
also what the kernels are tested against.

Mamba-2 (`ssd_step`, `ssd_chunk`) has ONE scalar decay a head where
Mamba-1 has one a channel and state column, and B and C shared by the
heads of a group: its state is [B, H, P, N] float32 (H heads of P
channels, N columns: 128 x 64 x 128 = 4.19 MB a layer and sequence at
the published sizes, whole 8 x 128 tiles), and because the decay between
two positions is a scalar a head, a row's positions need no scan: within
a chunk the outputs are the matrix products `C B^T` under the decay mask
times the inputs, plus the carried state's term (the "state-space
duality" form). Both are float32 at `highest`: the products are a
hundredth of the mixer's projections, and the carried state is never
rounded on its way through a chunk.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
# the Mamba-2 kernel holds a lane's whole [H, P, N] state of one layer in
# fast memory, in and out, each double-buffered: 4 x 4.19 MB at the
# published sizes
KERNEL_VMEM_BYTES = 40 * 1024 * 1024


def causal_conv(u, tail, w, b, valid=None):
    """Depthwise causal convolution of width K = w.shape[0] over
    u [B, T, Di], continuing from `tail` [B, K-1, Di]. `w[k]` multiplies
    the input K-1-k positions back (`w[K-1]` the current one).

    Returns (float32 [B, T, Di] before the activation, the new tail in
    the tail's dtype: the K-1 inputs that precede the first position
    that is not valid)."""
    K, T = w.shape[0], u.shape[1]
    window = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    out = b.astype(F32) + sum(
        window[:, k:k + T].astype(F32) * w[k].astype(F32) for k in range(K))
    if valid is None:
        new_tail = window[:, T:]
    elif T == 1:   # a decode step: shift the rows that are valid, no gather
        new_tail = jnp.where(valid[:, :, None], window[:, 1:], window[:, :-1])
    else:
        n = jnp.sum(valid, axis=-1, dtype=jnp.int32)
        new_tail = jax.vmap(
            lambda row, at: jax.lax.dynamic_slice_in_dim(row, at, K - 1, 0)
        )(window, n)
    return out, new_tail.astype(tail.dtype)


def selective_step(h, u, delta, A, Bm, Cm, D, valid=None):
    """One position: h' = exp(delta * A) * h + (delta * u) B,
    y = C . h' + D * u.

    h: [B, N, Di] float32; u, delta: [B, Di]; A: [N, Di] (negative);
    Bm, Cm: [B, N]; D: [Di]; valid: [B] or None. Returns (y [B, Di]
    float32, h'), h' = h in the rows that are not valid."""
    u, delta = u.astype(F32), delta.astype(F32)
    decay = jnp.exp(delta[:, None, :] * A[None])
    new = decay * h + (delta * u)[:, None, :] * Bm.astype(F32)[:, :, None]
    y = jnp.sum(new * Cm.astype(F32)[:, :, None], axis=1) + D.astype(F32) * u
    if valid is not None:
        new = jnp.where(valid[:, None, None], new, h)
    return y, new


def selective_scan(h, u, delta, A, Bm, Cm, D, valid=None):
    """`selective_step` over the T positions of a chunk, in order. (The
    loop is not unrolled: eight positions an iteration halved a
    micro-benchmark of this function alone and made the prefill program
    0.9 ms a chunk slower on the chip, PERF.md, PR 27.)

    u, delta: [B, T, Di]; Bm, Cm: [B, T, N]; valid: [B, T] or None.
    Returns (y [B, T, Di] float32, the state after the last valid
    position)."""
    by_time = lambda a: jnp.swapaxes(a, 0, 1)
    xs = tuple(map(by_time, (u, delta, Bm, Cm)))
    if valid is not None:
        xs += (by_time(valid),)

    def step(h, x):
        y, h = selective_step(h, x[0], x[1], A, x[2], x[3], D,
                              x[4] if valid is not None else None)
        return h, y

    h, ys = jax.lax.scan(step, h, xs)
    return by_time(ys), h


# ---- Mamba-2 ----

HIGHEST = jax.lax.Precision.HIGHEST


def _per_head(a, heads):
    """[.., G, N] of the groups -> [.., H, N]: head h reads group
    h // (H / G)."""
    return jnp.repeat(a, heads // a.shape[-2], axis=-2)


def ssd_step(S, x, dt, A, Bm, Cm, D, valid=None):
    """One position of a Mamba-2 recurrence:
    S' = exp(dt * A) * S + (dt * x) B^T,  y = S' C + D * x, a head at a
    time.

    S: [B, H, P, N] float32; x: [B, H, P]; dt: [B, H] (after softplus);
    A, D: [H] (A negative); Bm, Cm: [B, G, N], head h reading group
    h // (H / G); valid: [B] or None. Returns (y [B, H, P] float32, S'),
    S' = S in the rows that are not valid."""
    H = S.shape[1]
    x, dt = x.astype(F32), dt.astype(F32)
    Bh, Ch = _per_head(Bm.astype(F32), H), _per_head(Cm.astype(F32), H)
    decay = jnp.exp(dt * A.astype(F32))
    new = decay[..., None, None] * S \
        + (dt[..., None] * x)[..., None] * Bh[:, :, None, :]
    y = jnp.sum(new * Ch[:, :, None, :], axis=-1) \
        + D.astype(F32)[:, None] * x
    if valid is not None:
        new = jnp.where(valid[:, None, None, None], new, S)
    return y, new


def ssd_chunk(S, x, dt, A, Bm, Cm, D, valid=None, chunk=128):
    """`ssd_step` over the T positions of a row without a scan over
    them: the row is cut into chunks of at most `chunk` positions, and
    within a chunk, with a_t = dt_t * A and L_ts = exp(a_{s+1} + .. +
    a_t) for s <= t (0 above the diagonal),

      y_t = sum_s (C_t . B_s) L_ts dt_s x_s  +  exp(a_1 + .. + a_t) S C_t
            + D x_t
      S'  = exp(a_1 + .. + a_T) S + sum_s L_Ts (dt_s x_s) B_s^T

    which are matrix products; the chunks of a longer row follow one
    another in a `lax.scan` that carries S. A position that is not valid
    gets dt = 0: its decay is 1 and it adds nothing, so the state passes
    through it (its output is garbage that nobody reads).

    x: [B, T, H, P]; dt: [B, T, H]; Bm, Cm: [B, T, G, N]; valid: [B, T]
    or None. Returns (y [B, T, H, P] float32, the state after the last
    valid position)."""
    B_, T, H, P = x.shape
    G = Bm.shape[2]
    x, dt = x.astype(F32), dt.astype(F32)
    Bm, Cm = Bm.astype(F32), Cm.astype(F32)
    if valid is not None:
        dt = jnp.where(valid[..., None], dt, 0.0)
    Q = min(T, chunk)
    pad = -T % Q
    if pad:   # positions that are not valid: the state passes through
        x, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (
            a.ndim - 2)) for a in (x, dt, Bm, Cm))
    A, D = A.astype(F32), D.astype(F32)
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def one(S, xs):
        x, dt, Bm, Cm = xs            # [B, Q, ..]
        cum = jnp.cumsum(dt * A, axis=1)                      # [B, Q, H]
        # L[b, h, t, s] = exp(cum_t - cum_s), s <= t: never above 1
        by_head = jnp.swapaxes(cum, 1, 2)                     # [B, H, Q]
        diff = by_head[..., :, None] - by_head[..., None, :]
        L = jnp.exp(jnp.where(causal, diff, -jnp.inf))
        scores = jnp.einsum("btgn,bsgn->bgts", Cm, Bm, precision=HIGHEST)
        # the heads of a group share its scores
        W = (scores[:, :, None] * L.reshape(B_, G, H // G, Q, Q)
             ).reshape(B_, H, Q, Q)
        dx = dt[..., None] * x                                # [B, Q, H, P]
        y = jnp.einsum("bhts,bshp->bthp", W, dx, precision=HIGHEST)
        Ch, Bh = _per_head(Cm, H), _per_head(Bm, H)           # [B, Q, H, N]
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "bhpn,bthn->bthp", S, Ch, precision=HIGHEST)
        y = y + D[:, None] * x
        to_end = jnp.exp(cum[:, -1:, :] - cum)                # [B, Q, H]
        S = jnp.exp(cum[:, -1])[..., None, None] * S + jnp.einsum(
            "bshp,bshn->bhpn", to_end[..., None] * dx, Bh, precision=HIGHEST)
        return S, y

    if T + pad == Q:
        S, y = one(S, (x, dt, Bm, Cm))
    else:
        by_chunk = lambda a: jnp.swapaxes(
            a.reshape((B_, -1, Q) + a.shape[2:]), 0, 1)
        S, ys = jax.lax.scan(one, S, tuple(map(by_chunk, (x, dt, Bm, Cm))))
        y = jnp.swapaxes(ys, 0, 1).reshape((B_, T + pad) + ys.shape[3:])
    return y[:, :T], S


# ---- the one-token update of a whole pool, in place ----
#
# A decode step of the whole pool (inference/decode.py, `_layers`) hands
# these the pool [layers, B, ...] as stored, the layer (traced) and
# `lanes`, what `ops/decode_attention.py` `live_lanes` says of the step
# once a program: (the lanes that decode first, how many they are) and
# which they are ([B] bool). Each returns (y, pool): `ssd_step` /
# `selective_step` of layer `layer`, the state of the lanes that decode
# written where it was read and nothing else of the pool touched.

def whole_tiles(pool):
    """Whether the kernels take a pool of this shape: a lane's state of
    one layer is whole tiles of the chip (its last two axes 8 x 128
    float32 at a time), what the blocks are cut from, and a Mamba-2
    pool's heads [layers, B, H, P, N] fill whole lanes, where the
    kernel lays out a head's sums."""
    return (pool.dtype == F32 and pool.shape[-2] % 8 == 0
            and pool.shape[-1] % 128 == 0
            and (pool.ndim == 4 or pool.shape[2] % 128 == 0))


def _pool_call(kernel, name, pool, layer, lanes, operands, y_shape,
               interpret, weights=(), scalars=()):
    """One Pallas call over the lanes that decode: grid step i fetches
    lane lanes[i]'s block of layer `layer` of the pool and of every array
    of `operands` ([B, rows, lanes] each; those at the indices `scalars`
    into scalar memory) and the whole of every array of `weights`, and
    writes the pool's block where it was read (the pool is aliased to
    the output) and lane lanes[i]'s block of y [B, *y_shape]. The grid
    is as long as the lanes that decode are many, so a lane that does
    not decode is never fetched and its block of y never written; with
    none the one step that runs hands its block back as it came (the
    kernels' `live`)."""
    order, n, _ = lanes
    rest = (0,) * (pool.ndim - 2)
    pool_block = pl.BlockSpec((1, 1) + pool.shape[2:],
                              lambda i, order, meta: (meta[0], order[i]) + rest)
    lane_block = lambda a, **kw: pl.BlockSpec(
        (1,) + a.shape[1:], lambda i, order, meta: (order[i], 0, 0), **kw)
    y = jax.ShapeDtypeStruct(pool.shape[1:2] + y_shape, F32)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(jnp.maximum(n, 1),),
            in_specs=[pool_block] + [
                lane_block(a, **(dict(memory_space=pltpu.SMEM)
                                 if at in scalars else {}))
                for at, a in enumerate(operands)] + [
                pl.BlockSpec(w.shape, lambda i, order, meta: (0, 0))
                for w in weights],
            out_specs=[pool_block, lane_block(y)]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype), y],
        # operand 2 counts the two scalar-prefetch arguments
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=KERNEL_VMEM_BYTES),
        name=name,
        interpret=interpret,
    )(order, jnp.stack([jnp.asarray(layer, jnp.int32), n]), pool,
      *operands, *weights)


def _live(meta_ref, s_ref, o_ref, update):
    """`update()` where a lane decodes; where none does, the one block
    that was fetched goes back as it came."""
    pl.when(meta_ref[1] > 0)(update)

    @pl.when(meta_ref[1] == 0)
    def _():
        o_ref[...] = s_ref[...]


def _ssd_kernel(order_ref, meta_ref, s_ref, dxt_ref, decay_ref, b_ref, c_ref,
                o_ref, y_ref):
    """One decoding lane's state of one Mamba-2 layer, a head's [P, N] at
    a time: S' = decay S + (dt x) B^T written where S was read, and S' C.
    s_ref / o_ref: [H, P, N]; dxt_ref: [P, H], dt x with the heads along
    the lanes; decay_ref: [1, H] in scalar memory; b_ref, c_ref: [G, N];
    y_ref: [P, H], head h's sums in lane h. The sum over a head's columns
    is a product with ones on the matrix unit, at `highest`: 77 % of the
    state's bandwidth where the sum across the lanes of every tile read
    66 %, and a tile of 8 rows at a time 32 % (PERF.md, PR 46)."""
    del order_ref
    H, P, N = s_ref.shape[-3:]
    G = b_ref.shape[-2]
    s, o = s_ref.at[0, 0], o_ref.at[0, 0]
    dxt_ref, decay_ref, b_ref, c_ref, y_ref = (
        r.at[0] for r in (dxt_ref, decay_ref, b_ref, c_ref, y_ref))

    def update():
        lane = jax.lax.broadcasted_iota(jnp.int32, (P, H), 1)
        ones = jnp.ones((N, H), F32)
        y = jnp.zeros((P, H), F32)
        for h in range(H):
            g = h // (H // G)
            new = decay_ref[0, h] * s[h] \
                + dxt_ref[:, h:h + 1] * b_ref[g:g + 1, :]
            o[h] = new
            sums = jnp.dot(new * c_ref[g:g + 1, :], ones, precision=HIGHEST,
                           preferred_element_type=F32)
            y = jnp.where(lane == h, sums, y)
        y_ref[...] = y

    _live(meta_ref, s_ref, o_ref, update)


def _ssd_pool_kernel(pool, layer, x, dt, A, Bm, Cm, D, *lanes,
                     interpret=False):
    x, dt = x.astype(F32), dt.astype(F32)
    pool, y = _pool_call(
        _ssd_kernel, "ssd_state_update", pool, layer, lanes,
        [jnp.swapaxes(dt[..., None] * x, 1, 2),
         jnp.exp(dt * A.astype(F32))[:, None, :],
         Bm.astype(F32), Cm.astype(F32)], (x.shape[2], x.shape[1]),
        interpret,
        scalars=(1,))
    # a lane that did not decode left its block of y unwritten
    y = jnp.where(lanes[2][:, None, None], jnp.swapaxes(y, 1, 2), 0.0)
    return y + D.astype(F32)[:, None] * x, pool


def _ssd_pool_xla(pool, layer, x, dt, A, Bm, Cm, D, *lanes):
    S = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
    y, S = ssd_step(S, x, dt, A, Bm, Cm, D, lanes[2])
    return y, jax.lax.dynamic_update_index_in_dim(pool, S, layer, 0)


def ssd_update_pool(pool, layer, x, dt, A, Bm, Cm, D, lanes):
    """`ssd_step` of layer `layer` (traced) of pool [layers, B, H, P, N],
    in place. On a TPU, where the kernel takes the shape (`whole_tiles`),
    one Pallas call over the lanes that decode: a grid step reads one
    lane's [H, P, N] once, as stored, and writes it where it was read;
    the lanes that do not decode are neither read nor written. Elsewhere
    the same numbers from `ssd_step` on that layer, cut out and put back.
    Returns (y [B, H, P] float32, pool)."""
    args = (pool, jnp.asarray(layer, jnp.int32), x, dt, A, Bm, Cm, D) + lanes
    if not whole_tiles(pool):
        return _ssd_pool_xla(*args)
    return jax.lax.platform_dependent(
        *args, tpu=_ssd_pool_kernel, default=_ssd_pool_xla)


def _selective_kernel(order_ref, meta_ref, h_ref, delta_ref, du_ref, bc_ref,
                      a_ref, o_ref, y_ref):
    """One decoding lane's state of one Mamba-1 layer, 128 channels at a
    time: h' = exp(delta A) h + (delta u) B written where h was read, and
    C . h'. h_ref / o_ref: [N, Di]; delta_ref, du_ref (delta u), y_ref:
    [Di / 128, 128], 128 channels a row; bc_ref: [2 N, 128], B and then C
    down the sublanes, the same in every lane; a_ref: [N, Di]."""
    del order_ref
    N, Di = h_ref.shape[-2:]
    h, o = h_ref.at[0, 0], o_ref.at[0, 0]
    delta_ref, du_ref, bc_ref, y_ref = (
        r.at[0] for r in (delta_ref, du_ref, bc_ref, y_ref))

    def update():
        Bb, Cb = bc_ref[0:N, :], bc_ref[N:2 * N, :]
        for c in range(Di // 128):
            cols = pl.ds(c * 128, 128)
            new = jnp.exp(delta_ref[pl.ds(c, 1), :] * a_ref[:, cols]) \
                * h[:, cols] + du_ref[pl.ds(c, 1), :] * Bb
            o[:, cols] = new
            y_ref[pl.ds(c, 1), :] = jnp.sum(new * Cb, axis=0, keepdims=True)

    _live(meta_ref, h_ref, o_ref, update)


def _selective_pool_kernel(pool, layer, u, delta, A, Bm, Cm, D, *lanes,
                           interpret=False):
    _, B, N, Di = pool.shape
    u, delta = u.astype(F32), delta.astype(F32)
    by_tile = lambda a: a.reshape(B, Di // 128, 128)
    bc = jnp.concatenate([Bm.astype(F32), Cm.astype(F32)], axis=1)
    pool, y = _pool_call(
        _selective_kernel, "ssm_state_update", pool, layer, lanes,
        [by_tile(delta), by_tile(delta * u),
         jnp.broadcast_to(bc[:, :, None], (B, 2 * N, 128))],
        (Di // 128, 128), interpret, weights=[A])
    y = jnp.where(lanes[2][:, None], y.reshape(B, Di), 0.0)
    return y + D.astype(F32) * u, pool


def _selective_pool_xla(pool, layer, u, delta, A, Bm, Cm, D, *lanes):
    h = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
    y, h = selective_step(h, u, delta, A, Bm, Cm, D, lanes[2])
    return y, jax.lax.dynamic_update_index_in_dim(pool, h, layer, 0)


def selective_update_pool(pool, layer, u, delta, A, Bm, Cm, D, lanes):
    """`selective_step` of layer `layer` (traced) of pool
    [layers, B, N, Di], in place: `ssd_update_pool`'s choice and shape,
    a grid step one decoding lane's [N, Di]. Returns (y [B, Di] float32,
    pool)."""
    args = (pool, jnp.asarray(layer, jnp.int32), u, delta, A, Bm, Cm, D) \
        + lanes
    if not whole_tiles(pool):
        return _selective_pool_xla(*args)
    return jax.lax.platform_dependent(
        *args, tpu=_selective_pool_kernel, default=_selective_pool_xla)
