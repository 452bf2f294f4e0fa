"""Attention: XLA reference path + pallas TPU flash-attention forward.

The flash kernel follows the standard online-softmax blockwise algorithm
(grid over [batch*heads, q blocks]; inner fori_loop over k blocks with
running max/denominator). A custom_vjp recomputes attention blockwise with
the saved LSE on the backward pass, so the S×S score matrix is never
materialized in HBM in either direction.

Public entry: `attention(q, k, v, causal=..., impl='auto')` with GQA support
(num kv heads may divide num q heads).
"""

import functools
import math
import warnings

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl

from .. import device, knobs

# 128 is the MXU tile floor; the defaults are overridable for tuning
# sweeps (bench) and odd shapes. Combinations where one block size
# divides the other keep the causal live-block arithmetic exact.
BLOCK_Q = knobs.get_int("TPUFLOW_FLASH_BLOCK_Q")
BLOCK_K = knobs.get_int("TPUFLOW_FLASH_BLOCK_K")
NEG_INF = -1e30


def _broadcast_gqa(k, num_q_heads):
    """[B, S, Hkv, D] -> [B, S, Hq, D] by repeating kv heads."""
    num_kv = k.shape[-2]
    if num_kv == num_q_heads:
        return k
    reps = num_q_heads // num_kv
    return jnp.repeat(k, reps, axis=-2)


def shard_map_novma(fn, mesh, in_specs, out_specs):
    """shard_map with check_vma=False — pallas_call inside shard_map
    trips the vma checker's dynamic_slice rule; sharding correctness is
    still enforced by the in/out specs. Shared by the sequence-parallel
    attention variants (ring_attention.py, ulysses_attention.py)."""
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def reference_attention(q, k, v, causal=True, scale=None):
    """XLA attention: [B, S, H, D] layout. Materializes S×S scores — fine for
    moderate sequence lengths; XLA fuses mask+softmax into the matmuls."""
    B, Sq, H, D = q.shape
    k = _broadcast_gqa(k, H)
    v = _broadcast_gqa(v, H)
    scale = scale or (1.0 / math.sqrt(D))
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        Sk = k.shape[1]
        mask = jnp.tril(jnp.ones((Sq, Sk), dtype=bool), k=Sk - Sq)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# ---------------------------------------------------------------------------
# pallas flash forward
# ---------------------------------------------------------------------------


def _online_softmax_loop(q, k_ref, v_ref, qi, causal, block_k, seq_len,
                         scale):
    """The flash online-softmax inner loop shared by the normalized
    (single-device) and unnormalized (ring block) forward kernels.

    q: [block_q, D] in the INPUT dtype (bf16) — every MXU dot keeps bf16
    operands with f32 accumulation (the fp32 MXU path on TPU is several
    times slower, and the XLA reference computes the same bf16×bf16→f32
    contraction). The scale is applied to the f32 scores, not to q, so no
    precision is lost to a bf16 pre-scale. Returns (m, l, acc) in f32."""
    block_q, D = q.shape
    m = jnp.full((block_q, 1), NEG_INF, dtype=jnp.float32)
    l = jnp.zeros((block_q, 1), dtype=jnp.float32)
    acc = jnp.zeros((block_q, D), dtype=jnp.float32)

    if causal:
        # only k blocks at or before the diagonal contribute
        num_kb_live = (qi * block_q) // block_k + pl.cdiv(block_q, block_k)
    else:
        num_kb_live = seq_len // block_k

    def body(kb, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0
            )
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m - m_new)
        l = l * correction + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * correction + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        return m_new, l, acc

    return jax.lax.fori_loop(0, num_kb_live, body, (m, l, acc))


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal, scale,
                      block_k, seq_len):
    # blocks carry a leading size-1 (batch*head) dim:
    # q_ref: [1, BLOCK_Q, D]; k_ref/v_ref: [1, S, D]
    qi = pl.program_id(1)
    q = q_ref[0]
    block_q = q.shape[0]
    m, l, acc = _online_softmax_loop(q, k_ref, v_ref, qi, causal, block_k,
                                     seq_len, scale)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    # lse layout is [1, 8, S]: sublane dim padded to the fp32 tile minimum,
    # each q-block program writes its sequence slice (row 0 is the payload)
    lse_ref[0, :, pl.ds(qi * block_q, block_q)] = jnp.broadcast_to(
        (m + jnp.log(l)).reshape(1, -1), (8, block_q)
    )


@jax.named_scope("flash_attention")
def _flash_forward(q, k, v, causal, scale, interpret=False):
    """q,k,v: [BH, S, D] (heads folded into batch). Returns (out, lse).
    Block sizes come from the module-level BLOCK_Q/BLOCK_K (env-tunable);
    flash_attention validates them before any kernel runs."""
    BH, S, D = q.shape
    block_q = min(BLOCK_Q, S)
    block_k = min(BLOCK_K, S)
    grid = (BH, S // block_q)

    kernel = functools.partial(
        _flash_fwd_kernel,
        causal=causal,
        scale=scale,
        block_k=block_k,
        seq_len=S,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 8, S), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 8, S), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out, lse[:, 0, :]


def _fold_heads(x):
    # [B, S, H, D] -> [B*H, S, D]
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def _unfold_heads(x, B, H):
    BH, S, D = x.shape
    return x.reshape(B, H, S, D).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_attention(q, k, v, causal, scale, interpret):
    out, _ = _flash_forward(q, k, v, causal, scale, interpret)
    return out


def _flash_attention_fwd(q, k, v, causal, scale, interpret):
    out, lse = _flash_forward(q, k, v, causal, scale, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                         dq_ref, *, causal, scale, block_k, seq_len):
    """dq for one q block: iterate k blocks (≤ diagonal when causal)."""
    qi = pl.program_id(1)
    q = q_ref[0]
    g = g_ref[0]
    block_q, D = q.shape
    lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q)]
    delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q)]

    if causal:
        num_kb = (qi * block_q) // block_k + pl.cdiv(block_q, block_k)
    else:
        num_kb = seq_len // block_k

    def body(kb, dq):
        # all MXU dots take bf16 operands with f32 accumulation; softmax
        # statistics and ds stay f32 on the VPU (see _online_softmax_loop)
        k = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                            s.shape, 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                            s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jnp.dot(g, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        return dq + jnp.dot(ds.astype(k.dtype), k,
                            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(
        0, num_kb, body, jnp.zeros((block_q, D), jnp.float32)
    )
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, causal, scale, block_q,
                          seq_len):
    """dk/dv for one k block: iterate q blocks (≥ diagonal when causal)."""
    ki = pl.program_id(1)
    k = k_ref[0]
    v = v_ref[0]
    block_k, D = k.shape
    num_qb = seq_len // block_q
    first_qb = (ki * block_k) // block_q if causal else 0

    def body(qb, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qb * block_q, block_q), :]
        g = g_ref[0, pl.ds(qb * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.ds(qb * block_q, block_q)]
        delta = delta_ref[0, 0, pl.ds(qb * block_q, block_q)]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                            s.shape, 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                            s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        pb = p.astype(g.dtype)
        dv = dv + jnp.dot(pb.T, g, preferred_element_type=jnp.float32)
        dp = jnp.dot(g, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None]) * scale).astype(q.dtype)
        dk = dk + jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
        return dk, dv

    dk, dv = jax.lax.fori_loop(
        first_qb, num_qb, body,
        (jnp.zeros((block_k, D), jnp.float32),
         jnp.zeros((block_k, D), jnp.float32)),
    )
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


@jax.named_scope("flash_attention")
def _flash_backward_pallas(q, k, v, g, out, lse, causal, scale, interpret):
    """Pallas backward via the shared blockwise kernels (flash_block_bwd):
    dq grid over q blocks, dk/dv grid over k blocks."""
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # [BH, S]
    dq, dk, dv = flash_block_bwd(q, k, v, g, lse, delta, scale, causal,
                                 interpret)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _flash_attention_bwd(causal, scale, interpret, res, g):
    q, k, v, out, lse = res
    return _flash_backward_pallas(q, k, v, g, out, lse, causal, scale,
                                  interpret)


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def flash_attention(q, k, v, causal=True, scale=None, interpret=False):
    """Pallas flash attention; q,k,v: [B, S, H, D] (kv heads may be fewer).

    Requires S to be a multiple of the 128 block size (the `attention`
    dispatcher takes the XLA path otherwise)."""
    B, S, H, D = q.shape
    block_q, block_k = _check_blocks(S)
    k = _broadcast_gqa(k, H)
    v = _broadcast_gqa(v, H)
    scale = scale or (1.0 / math.sqrt(D))
    qf, kf, vf = _fold_heads(q), _fold_heads(k), _fold_heads(v)
    out = _flash_attention(qf, kf, vf, causal, scale, interpret)
    return _unfold_heads(out, B, H)


# ---------------------------------------------------------------------------
# blockwise building blocks for ring attention (ops/ring_attention.py)
# ---------------------------------------------------------------------------


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying `like`'s varying-axes (vma) annotation —
    required for pallas_call outputs under shard_map with check_vma."""
    vma = jax.typeof(like).vma
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _flash_block_fwd_kernel(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, *,
                            causal, scale, block_k, seq_len):
    """Flash forward WITHOUT final normalization, emitting the online-softmax
    stats (m, l) — the ring combiner merges contributions across ring hops.
    causal=True means the same-offset diagonal mask (q and k blocks are the
    same sequence shard); causal=False means every k position contributes."""
    qi = pl.program_id(1)
    q = q_ref[0]
    block_q = q.shape[0]
    m, l, acc = _online_softmax_loop(q, k_ref, v_ref, qi, causal, block_k,
                                     seq_len, scale)
    acc_ref[0] = acc
    m_ref[0, :, pl.ds(qi * block_q, block_q)] = jnp.broadcast_to(
        m.reshape(1, -1), (8, block_q)
    )
    l_ref[0, :, pl.ds(qi * block_q, block_q)] = jnp.broadcast_to(
        l.reshape(1, -1), (8, block_q)
    )


def blocks_aligned(S):
    """True when seq len S satisfies the flash-kernel contract with the
    effective block sizes: S divisible by both blocks (a fori_loop bound
    of seq_len // block_k silently drops the k tail otherwise) and mutual
    block divisibility (the causal live-block count is exact only then).
    Single source of truth for both the kernels and the auto-dispatchers
    here and in ring_attention."""
    bq, bk = min(BLOCK_Q, S), min(BLOCK_K, S)
    return (S % bq == 0 and S % bk == 0
            and (bq % bk == 0 or bk % bq == 0))


def _check_blocks(S):
    """Effective (block_q, block_k) for seq len S; raises on a
    blocks_aligned violation — raising beats returning wrong attention
    output with no error. The decision is blocks_aligned itself (one
    predicate for dispatchers and kernels); only the message is derived
    here."""
    block_q = min(BLOCK_Q, S)
    block_k = min(BLOCK_K, S)
    if not blocks_aligned(S):
        if S % block_q or S % block_k:
            raise ValueError(
                "flash block kernels require seq len divisible by the "
                "%d/%d block sizes (got %d); use the xla impl or pad the "
                "sequence" % (BLOCK_Q, BLOCK_K, S)
            )
        raise ValueError(
            "flash attention block sizes must divide one another (got "
            "q=%d, k=%d via TPUFLOW_FLASH_BLOCK_Q/K)" % (block_q, block_k)
        )
    return block_q, block_k


@jax.named_scope("flash_attention")
def flash_block_fwd(q, k, v, scale, causal_diag, interpret=False):
    """One ring step's unnormalized contribution.

    q, k, v: [BH, S, D] (heads folded). Returns (acc f32 [BH,S,D],
    m f32 [BH,S], l f32 [BH,S])."""
    BH, S, D = q.shape
    block_q, block_k = _check_blocks(S)
    acc, m, l = pl.pallas_call(
        functools.partial(
            _flash_block_fwd_kernel,
            causal=causal_diag,
            scale=scale,
            block_k=block_k,
            seq_len=S,
        ),
        grid=(BH, S // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 8, S), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 8, S), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            _sds((BH, S, D), jnp.float32, q),
            _sds((BH, 8, S), jnp.float32, q),
            _sds((BH, 8, S), jnp.float32, q),
        ],
        interpret=interpret,
        name="flash_block_fwd",
    )(q, k, v)
    return acc, m[:, 0, :], l[:, 0, :]


@jax.named_scope("flash_attention")
def flash_block_bwd(q, k, v, g, lse, delta, scale, causal_diag,
                    interpret=False):
    """One ring step's gradient contribution given the GLOBAL lse/delta.

    Same kernels as the single-device flash backward — the global stats make
    each blockwise p exact, so contributions just sum across ring hops.
    Returns (dq, dk, dv) in f32, shapes [BH, S, D]."""
    BH, S, D = q.shape
    block_q, block_k = _check_blocks(S)
    lse_t = jnp.broadcast_to(lse[:, None, :], (BH, 8, S))
    delta_t = jnp.broadcast_to(delta[:, None, :], (BH, 8, S))
    stats_spec = pl.BlockSpec((1, 8, S), lambda b, i: (b, 0, 0))
    full_spec = pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0))

    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, causal=causal_diag, scale=scale,
            block_k=block_k, seq_len=S,
        ),
        grid=(BH, S // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            full_spec,
            full_spec,
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            stats_spec,
            stats_spec,
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
        out_shape=_sds((BH, S, D), jnp.float32, q),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, g, lse_t, delta_t)

    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, causal=causal_diag, scale=scale,
            block_q=block_q, seq_len=S,
        ),
        grid=(BH, S // block_k),
        in_specs=[
            full_spec,
            pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0)),
            full_spec,
            stats_spec,
            stats_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            _sds((BH, S, D), jnp.float32, q),
            _sds((BH, S, D), jnp.float32, q),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, g, lse_t, delta_t)
    return dq, dk, dv


def auto_impl(aligned, what, shape):
    """What impl='auto' means, here and in ring_attention: the Pallas
    kernel on a TPU whenever the shapes tile, XLA attention in a
    CPU-pinned process (the kernel would only run interpreted there).
    On a TPU a shape that does not tile takes the XLA path too, and
    says so once. Any other backend is device.platform()'s error."""
    if not device.on_tpu():
        return "xla"
    if aligned:
        return "flash"
    # shown once per message by the default warning filter
    warnings.warn(
        "%s: shape %s does not tile for the flash kernel (sequence "
        "blocks %d/%d, head size a multiple of 128, batch and heads "
        "dividing the mesh); using XLA attention"
        % (what, shape, BLOCK_Q, BLOCK_K), RuntimeWarning, stacklevel=3)
    return "xla"


def _flash_partition(mesh, q, k):
    """How the flash kernel is split over a multi-device mesh: the
    compiler cannot partition a Mosaic kernel by itself, so it runs
    under shard_map, batch over the data axes and heads over 'tensor'.
    Returns the PartitionSpec for [B, S, H, D], None for no mesh or a
    one-device mesh, False when batch or heads do not divide."""
    if mesh is None or mesh.size == 1:
        return None
    from jax.sharding import PartitionSpec

    batch_axes = tuple(a for a in ("data", "fsdp")
                       if mesh.shape.get(a, 1) > 1)
    n_batch = math.prod(mesh.shape[a] for a in batch_axes)
    n_heads = mesh.shape.get("tensor", 1)
    if q.shape[0] % n_batch or q.shape[2] % n_heads \
            or k.shape[2] % n_heads:
        return False
    return PartitionSpec(batch_axes or None, None,
                         "tensor" if n_heads > 1 else None, None)


def attention(q, k, v, causal=True, scale=None, impl="auto", mesh=None):
    """Dispatch: pallas flash on TPU when shapes tile cleanly, XLA
    where they do not, where the process is CPU-pinned, or by name.
    `mesh`: the mesh the caller's arrays are sharded over, if any —
    the kernel then runs per shard (see _flash_partition)."""
    spec = _flash_partition(mesh, q, k)
    if impl == "auto":
        S, D = q.shape[1], q.shape[3]
        impl = auto_impl(
            blocks_aligned(S) and D % 128 == 0 and spec is not False,
            "attention", tuple(q.shape))
    if impl in ("flash", "flash_interpret"):
        def kernel(q, k, v):
            return flash_attention(q, k, v, causal=causal, scale=scale,
                                   interpret=impl == "flash_interpret")

        if spec is None:
            return kernel(q, k, v)
        if spec is False:
            raise ValueError(
                "flash attention over mesh %s needs batch %d and heads "
                "%d/%d to divide its data and tensor axes"
                % (dict(mesh.shape), q.shape[0], q.shape[2], k.shape[2]))
        return shard_map_novma(kernel, mesh, (spec, spec, spec), spec)(
            q, k, v)
    return reference_attention(q, k, v, causal=causal, scale=scale)
