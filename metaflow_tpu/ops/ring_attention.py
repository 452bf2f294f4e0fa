"""Ring attention: causal attention over a sequence-sharded mesh axis.

Long-context path (SURVEY.md §5.7): the sequence is sharded across the
'sequence' mesh axis; each device holds a [B, S/N, H, D] shard of q/k/v. K/V
blocks rotate around the ring via lax.ppermute while each device accumulates
blockwise attention with an online softmax — compute overlaps the collective,
total memory stays O(S/N), and the ppermute hops ride neighbouring ICI links.

Two inner-block implementations:
- 'flash' (default on TPU): the pallas flash kernels (ops/attention.py) run
  each ring step's block unnormalized, emitting online-softmax stats that
  the ring combiner merges — no S_local x S_local score tensor ever exists.
  The backward is a second ring pass: dk/dv accumulators travel WITH their
  rotating k/v blocks and arrive home after N hops (the standard ring-flash
  backward), with all blockwise probabilities made exact by the global LSE.
- 'xla': einsum blocks (materializes per-hop scores; CPU/debug fallback).

Ring-causal masking is static per branch: a hop's source shard is either
entirely before my shard (full attention), my own shard (diagonal causal
mask), or after it (skipped) — lax.switch picks the branch, so the pallas
kernels compile once per variant with no dynamic offsets.

Use inside shard_map (ring_attention_sharded builds it for a mesh).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import knobs

from .attention import (
    MIN_BLOCK,
    _broadcast_gqa,
    _fold_heads,
    _unfold_heads,
    auto_impl,
    blocks_aligned,
    flash_block_bwd,
    flash_block_fwd,
)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# xla inner block (fallback / debug)
# ---------------------------------------------------------------------------


def _block_attn(q, k, v, scale, q_offset, k_offset, causal):
    """One blockwise attention contribution + online-softmax stats.

    q: [B, Sq, H, D]; k/v: [B, Sk, Hkv, D] — GQA broadcast happens HERE,
    after the ring hop, so ppermute only ever moves kv-head-width blocks.
    Returns (unnormalized out [B,Sq,H,D] in f32, m [B,H,Sq], l [B,H,Sq]).
    """
    H = q.shape[2]
    if k.shape[2] != H:
        reps = H // k.shape[2]
        k = jnp.repeat(k, reps, axis=2)
        v = jnp.repeat(v, reps, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        Sq, Sk = q.shape[1], k.shape[1]
        q_pos = q_offset + jnp.arange(Sq)[:, None]
        k_pos = k_offset + jnp.arange(Sk)[None, :]
        s = jnp.where((q_pos >= k_pos)[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)  # [B,H,Sq]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out, m, l


def _ring_attention_local_xla(q, k, v, axis_name, causal=True, scale=None):
    """Body run per-device under shard_map (einsum inner block)."""
    B, S_local, H, D = q.shape
    scale = scale or (1.0 / math.sqrt(D))
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    q_offset = my_idx * S_local

    # derive the accumulators from q so they carry q's varying-axes (vma)
    # annotation — a plain jnp.zeros would be 'unvarying' and fail the scan
    # carry type check under shard_map
    zero_q = q.astype(jnp.float32) * 0.0
    acc = zero_q
    m_run = zero_q[..., 0].transpose(0, 2, 1) + NEG_INF
    l_run = zero_q[..., 0].transpose(0, 2, 1)

    def step(carry, r):
        acc, m_run, l_run, k_cur, v_cur = carry
        # k block currently held came from device (my_idx - r) mod N
        src = (my_idx - r) % axis_size
        k_offset = src * S_local
        out_b, m_b, l_b = _block_attn(
            q, k_cur, v_cur, scale, q_offset, k_offset, causal
        )
        m_new = jnp.maximum(m_run, m_b)
        c_run = jnp.exp(m_run - m_new)
        c_b = jnp.exp(m_b - m_new)
        l_new = l_run * c_run + l_b * c_b
        acc = acc * c_run.transpose(0, 2, 1)[..., None] + \
            out_b * c_b.transpose(0, 2, 1)[..., None]
        # rotate k/v to the next device (overlaps with next iteration's
        # compute under XLA latency hiding)
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (acc, m_new, l_new, k_nxt, v_nxt), None

    (acc, m_run, l_run, _, _), _ = jax.lax.scan(
        step, (acc, m_run, l_run, k, v), jnp.arange(axis_size)
    )
    out = acc / l_run.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# pallas flash inner block with a ring backward pass
# ---------------------------------------------------------------------------


def _ring_branch_index(src, my_idx):
    """0 = diagonal (own shard: causal mask), 1 = full (earlier shard),
    2 = skip (later shard contributes nothing under causality)."""
    return jnp.where(src == my_idx, 0, jnp.where(src < my_idx, 1, 2))


def _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale, interpret):
    B, S, H, D = q.shape
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    qf = _fold_heads(q)  # [BH, S, D]

    zero = qf.astype(jnp.float32) * 0.0
    acc = zero
    m_run = zero[..., 0] + NEG_INF  # [BH, S]
    l_run = zero[..., 0]

    def step(carry, r):
        acc, m_run, l_run, k_cur, v_cur = carry
        src = (my_idx - r) % axis_size
        kb = _fold_heads(_broadcast_gqa(k_cur, H))
        vb = _fold_heads(_broadcast_gqa(v_cur, H))

        def diag(_):
            return flash_block_fwd(qf, kb, vb, scale, True, interpret)

        def full(_):
            return flash_block_fwd(qf, kb, vb, scale, False, interpret)

        def skip(_):
            return acc * 0.0, m_run * 0.0 + NEG_INF, l_run * 0.0

        if causal:
            acc_b, m_b, l_b = jax.lax.switch(
                _ring_branch_index(src, my_idx), [diag, full, skip], None
            )
        else:
            acc_b, m_b, l_b = full(None)

        m_new = jnp.maximum(m_run, m_b)
        c_run = jnp.exp(m_run - m_new)
        c_b = jnp.exp(m_b - m_new)
        l_new = l_run * c_run + l_b * c_b
        acc = acc * c_run[..., None] + acc_b * c_b[..., None]
        p = [(i, (i + 1) % axis_size) for i in range(axis_size)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, p)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, p)
        return (acc, m_new, l_new, k_nxt, v_nxt), None

    (acc, m_run, l_run, _, _), _ = jax.lax.scan(
        step, (acc, m_run, l_run, k, v), jnp.arange(axis_size)
    )
    out = (acc / l_run[..., None]).astype(q.dtype)  # [BH, S, D]
    lse = m_run + jnp.log(l_run)  # [BH, S]
    return _unfold_heads(out, B, H), lse


def _reduce_gqa_grad(d_folded, B, H, Hkv):
    """[B*H, S, D] broadcast-head grads -> [B, S, Hkv, D] by summing the
    repeated query heads back onto their kv head."""
    BH, S, D = d_folded.shape
    reps = H // Hkv
    d = d_folded.reshape(B, Hkv, reps, S, D).sum(axis=2)  # [B, Hkv, S, D]
    return d.transpose(0, 2, 1, 3)  # [B, S, Hkv, D]


def _ring_flash_bwd_impl(q, k, v, out, lse, g, axis_name, causal, scale,
                         interpret):
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)

    qf = _fold_heads(q)
    gf = _fold_heads(g).astype(jnp.float32)
    of = _fold_heads(out).astype(jnp.float32)
    delta = jnp.sum(gf * of, axis=-1)  # [BH, S]

    dq = qf.astype(jnp.float32) * 0.0
    dk_acc = k.astype(jnp.float32) * 0.0  # travels with k_cur
    dv_acc = v.astype(jnp.float32) * 0.0

    def step(carry, r):
        dq, dk_acc, dv_acc, k_cur, v_cur = carry
        src = (my_idx - r) % axis_size
        kb = _fold_heads(_broadcast_gqa(k_cur, H))
        vb = _fold_heads(_broadcast_gqa(v_cur, H))

        def diag(_):
            return flash_block_bwd(qf, kb, vb, gf, lse, delta, scale, True,
                                   interpret)

        def full(_):
            return flash_block_bwd(qf, kb, vb, gf, lse, delta, scale, False,
                                   interpret)

        def skip(_):
            z = dq * 0.0
            return z, z, z

        if causal:
            dq_b, dk_b, dv_b = jax.lax.switch(
                _ring_branch_index(src, my_idx), [diag, full, skip], None
            )
        else:
            dq_b, dk_b, dv_b = full(None)

        dq = dq + dq_b
        # this hop's dk/dv belong to the kv block currently held: accumulate
        # into the buffers that rotate WITH the block — after N hops every
        # block is home carrying its full gradient
        dk_acc = dk_acc + _reduce_gqa_grad(dk_b, B, H, Hkv)
        dv_acc = dv_acc + _reduce_gqa_grad(dv_b, B, H, Hkv)
        p = [(i, (i + 1) % axis_size) for i in range(axis_size)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, p)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, p)
        dk_nxt = jax.lax.ppermute(dk_acc, axis_name, p)
        dv_nxt = jax.lax.ppermute(dv_acc, axis_name, p)
        return (dq, dk_nxt, dv_nxt, k_nxt, v_nxt), None

    (dq, dk_acc, dv_acc, _, _), _ = jax.lax.scan(
        step, (dq, dk_acc, dv_acc, k, v), jnp.arange(axis_size)
    )
    return (
        _unfold_heads(dq, B, H).astype(q.dtype),
        dk_acc.astype(k.dtype),
        dv_acc.astype(v.dtype),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_flash(q, k, v, axis_name, causal, scale, interpret):
    out, _lse = _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale,
                                     interpret)
    return out


def _ring_flash_fwd(q, k, v, axis_name, causal, scale, interpret):
    out, lse = _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale,
                                    interpret)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(axis_name, causal, scale, interpret, res, g):
    q, k, v, out, lse = res
    return _ring_flash_bwd_impl(q, k, v, out, lse, g, axis_name, causal,
                                scale, interpret)


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def _ring_attention_local_flash(q, k, v, axis_name, causal=True, scale=None,
                                interpret=False):
    scale = scale or (1.0 / math.sqrt(q.shape[-1]))
    return _ring_flash(q, k, v, axis_name, causal, scale, interpret)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


def _resolve_impl(impl, S_local, D, dtype):
    if impl == "auto":
        impl = knobs.get_str("TPUFLOW_RING_IMPL")
    # same predicate flash_block_fwd/bwd enforce — single source of truth
    aligned = blocks_aligned(S_local, D, dtype)
    if impl == "auto":
        impl = auto_impl(aligned, "ring_attention", (S_local,))
    if impl in ("flash", "flash_interpret") and not aligned:
        # an explicitly requested flash impl must not silently drop the
        # unaligned tail (grid floor-division would leave rows unwritten)
        raise ValueError(
            "ring flash attention needs the per-device sequence shard "
            "(%d) to be a multiple of the kernels' %d-row tile floor; use "
            "impl='xla' or pad the sequence" % (S_local, MIN_BLOCK)
        )
    return impl


def ring_attention_sharded(mesh, axis_name="sequence", causal=True,
                           scale=None, impl="auto"):
    """Build a sharded ring-attention fn for [B, S, H, D] inputs with S split
    over `axis_name` (batch over data axes when present).

    impl: 'auto' | 'flash' | 'flash_interpret' | 'xla' (or env
    TPUFLOW_RING_IMPL). 'flash' needs the per-device sequence shard to be
    a multiple of the pallas tile floor (MIN_BLOCK, 128).
    """
    from .attention import shard_map_novma

    batch_axes = tuple(a for a in ("data", "fsdp") if a in mesh.axis_names)
    spec = P(batch_axes or None, axis_name, None, None)

    def dispatch(q, k, v):
        S_local = q.shape[1]
        chosen = _resolve_impl(impl, S_local, q.shape[3], q.dtype)
        if chosen in ("flash", "flash_interpret"):
            return _ring_attention_local_flash(
                q, k, v, axis_name, causal=causal, scale=scale,
                interpret=(chosen == "flash_interpret"),
            )
        return _ring_attention_local_xla(
            q, k, v, axis_name, causal=causal, scale=scale
        )

    return shard_map_novma(dispatch, mesh, in_specs=(spec, spec, spec),
                           out_specs=spec)


def ring_attention(q, k, v, mesh, axis_name="sequence", causal=True,
                   scale=None, impl="auto"):
    return ring_attention_sharded(mesh, axis_name, causal, scale, impl)(
        q, k, v
    )
