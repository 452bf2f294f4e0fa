"""The selective state-space recurrence of a Mamba-1 mixer, and the short
causal convolution before it, from a carried state.

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
