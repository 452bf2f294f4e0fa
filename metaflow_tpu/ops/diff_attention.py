"""Differential attention (arXiv:2410.05258, as SambaY uses it): heads
come in interleaved pairs, a pair's two softmax maps read ONE value head
twice the size of a key head, and the pair's output is their difference.

  q [.., P pairs, 2, Hd], k [.., G pairs, 2, Hd], v [.., G, 2 Hd]; query
  pair p reads KV pair p // (P // G)
  a_j = softmax(q[p, j] k[p // (P // G), j]^T / sqrt(Hd) + mask) v[..]
  lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0
  y_p = rms_norm(a_0 - lam a_1, subln weight [2 Hd]) * (1 - lam0)

Attention itself is whatever the caller runs (ops/attention.py over a
whole sequence, inference/decode.py over a cache), as grouped-query
attention with 2 G key heads under G value heads: `pair_major` puts the
query heads in the order in which consecutive key heads share a value
head and every key head's queries are consecutive, and `combine` takes
the outputs back out of that order.
"""

import jax.numpy as jnp

from .norms import rms_norm


def pair_major(q, n_kv_heads):
    """q [B, T, H, Hd] with heads (KV pair c, query pair of the group,
    j) -> the same heads in the order (c, j, query pair): head c * 2 + j
    of K then meets its H // n_kv_heads queries as one group, and the 2
    * H // n_kv_heads groups of KV pair c lie side by side over value
    head c."""
    B, T, H, Hd = q.shape
    q = q.reshape(B, T, n_kv_heads // 2, H // n_kv_heads, 2, Hd)
    return q.swapaxes(3, 4).reshape(B, T, H, Hd)


def combine(out, n_kv_heads, lp, lam0, eps, dtype):
    """out [B, T, H, 2 Hd], the two maps' reads of the value heads in
    `pair_major`'s order -> y [B, T, H // 2, 2 Hd] in the published order
    of the pairs. lp holds lambda_q1, lambda_k1, lambda_q2, lambda_k2
    ([Hd]) and subln ([2 Hd]); lam0 is the layer's constant. Float32
    throughout; the result is cast to `dtype`."""
    B, T, H, Dv = out.shape
    f32 = lambda name: lp[name].astype(jnp.float32)
    lam = (jnp.exp(jnp.sum(f32("lambda_q1") * f32("lambda_k1")))
           - jnp.exp(jnp.sum(f32("lambda_q2") * f32("lambda_k2"))) + lam0)
    # (KV pair, j, query pair of the group): the pair's two maps are the
    # two halves of axis 3
    maps = out.astype(jnp.float32).reshape(
        B, T, n_kv_heads // 2, 2, H // n_kv_heads, Dv)
    diff = (maps[:, :, :, 0] - lam * maps[:, :, :, 1]).reshape(
        B, T, H // 2, Dv)
    return (rms_norm(diff, lp["subln"], eps) * (1.0 - lam0)).astype(dtype)
