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

Plain `jax.numpy`: the recurrence is a `lax.scan` over the chunk's
positions (at most a prefill chunk, 64) whose body is elementwise over
[B, N, Di]; one token is the body alone. The recurrence is float32
whatever the model's dtype.

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

F32 = jnp.float32


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
