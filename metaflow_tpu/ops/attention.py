"""Attention: XLA reference path + pallas TPU flash attention.

The flash kernels follow the standard online-softmax blockwise algorithm
(grid over [batch*heads, outer blocks]; inner fori_loops over the other
operand's blocks with running max/denominator, the causal mask applied
only in the blocks that touch the diagonal). A custom_vjp recomputes
attention blockwise with the saved LSE on the backward pass, so the S×S
score matrix is never materialized in HBM in either direction. The
blocks' sizes are `flash_tiles`' answer for the shape; there is no knob.

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
from jax.experimental.pallas import tpu as pltpu

from .. import device

NEG_INF = -1e30
# the MXU's edge, and the lane width every tile is a multiple of
MIN_BLOCK = 128
# a @ b.T as one contraction over the last axis of both: no transpose
_NT = (((1,), (1,)), ((), ()))
# what a kernel may take of fast memory: K and V (or q and g) of one head
# whole, twice buffered, beside a few float32 score tiles of up to 4 MB
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 2 ** 20)
KERNELS = ("fwd", "dq", "dkv")


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


def reference_attention(q, k, v, causal=True, scale=None, window=None):
    """XLA attention: [B, S, H, D] layout. Materializes S×S scores — fine for
    moderate sequence lengths; XLA fuses mask+softmax into the matmuls.
    `window` (causal only): a query sees itself and the window - 1
    positions before it. V's heads may be fewer and wider than K's."""
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
        if window is not None:
            mask = jnp.triu(mask, k=Sk - Sq - window + 1)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# ---------------------------------------------------------------------------
# pallas flash kernels
# ---------------------------------------------------------------------------


def flash_tiles(S, D, dtype, causal, kernel):
    """(block_q, block_k) of one of the three kernels ('fwd', 'dq',
    'dkv'), from what the kernel can observe: the sequence length, the
    head size, the operands' dtype, whether the call is causal.

    A grid program holds its outer tile (the query block in 'fwd' and
    'dq', the key block in 'dkv') and walks the inner tiles in a loop
    whose every step pays for itself only on a score tile of a few
    hundred rows and columns: swept on a v5e at bfloat16 heads of 128
    (scripts/flash_tile_sweep.py; PERF.md section 6, PR 32), tiles of
    128 take three to four times the time of tiles of 512, and the
    inner tile counts for more than the outer. A larger tile does more
    per step and, under a causal mask, more in vain: a tile of t rows
    on the diagonal computes t / S over the need. So the forward takes
    512 x 512 everywhere, and the two backward kernels, whose steps
    hold three and four products where the forward's holds two, a
    square tile that grows to 1024 once what it computes past the
    diagonal is an eighth of the need or less.

    D and dtype are what the caller observes too and nothing measured
    so far moves on them (the score tile is float32 [block_q, block_k]
    whatever they are). Every answer keeps the kernels' contract: each
    block divides S and one block divides the other (`blocks_aligned`);
    a sequence shorter than a tile is one tile."""
    if kernel not in KERNELS:
        raise ValueError("flash_tiles: kernel %r is none of %s"
                         % (kernel, KERNELS))
    del D, dtype
    tile = 512
    if kernel != "fwd" and (not causal or S >= 8 * 1024):
        tile = 1024
    tile = _fit(tile, S)
    return tile, tile


def _fit(block, S):
    """The largest power-of-two multiple of MIN_BLOCK that is at most
    `block` and divides S; all of S where it is shorter than MIN_BLOCK.
    Where S is no multiple of MIN_BLOCK the answer is MIN_BLOCK, and
    blocks_aligned says no."""
    if S <= MIN_BLOCK:
        return S
    while block > MIN_BLOCK and S % block:
        block //= 2
    return block


def _rows(i, block):
    """Rows [i * block, (i + 1) * block) of a resident operand."""
    return pl.ds(pl.multiple_of(i * block, block), block)


def _mask_after(s, q_axis, q0, k0):
    """NEG_INF where the key lies after the query. Queries run along
    `q_axis` of the score tile from position q0, keys along the other
    from k0."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _walk(tiles, init, clear, diagonal):
    """The inner loop of a kernel, split in two: `tiles(False)` is the
    loop body over the tiles in the range `clear`, which lie wholly
    before the diagonal and need no mask (no iota, no compare, no
    select), `tiles(True)` the body over the range `diagonal` of tiles
    that touch it (None where the call is not causal)."""
    carry = jax.lax.fori_loop(*clear, tiles(False), init)
    if diagonal is not None:
        carry = jax.lax.fori_loop(*diagonal, tiles(True), carry)
    return carry


def _key_ranges(qi, block_q, block_k, seq_len, causal):
    """(clear, diagonal) ranges of key tiles for query block qi."""
    if not causal:
        return (0, seq_len // block_k), None
    first = (qi * block_q) // block_k
    return (0, first), (first, first + pl.cdiv(block_q, block_k))


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

    def tiles(masked):
        def body(kb, carry):
            m, l, acc = carry
            k = k_ref[0, _rows(kb, block_k), :]
            v = v_ref[0, _rows(kb, block_k), :]
            s = jax.lax.dot_general(
                q, k, _NT, preferred_element_type=jnp.float32) * scale
            if masked:
                # a row's first live tile holds a key it sees (the
                # diagonal's own), so m is finite before any tile that
                # masks the whole row
                s = _mask_after(s, 0, qi * block_q, kb * block_k)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            correction = jnp.exp(m - m_new)
            l = l * correction + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * correction + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32
            )
            return m_new, l, acc
        return body

    return _walk(tiles, (m, l, acc),
                 *_key_ranges(qi, block_q, block_k, seq_len, causal))


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal, scale,
                      block_k, seq_len):
    # blocks carry a leading size-1 (batch*head) dim:
    # q_ref: [1, block_q, D]; k_ref/v_ref: [1, S, D]
    qi = pl.program_id(1)
    q = q_ref[0]
    block_q = q.shape[0]
    m, l, acc = _online_softmax_loop(q, k_ref, v_ref, qi, causal, block_k,
                                     seq_len, scale)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    # lse layout is [1, 8, S]: sublane dim padded to the fp32 tile minimum,
    # each q-block program writes its sequence slice (row 0 is the payload)
    lse_ref[0, :, _rows(qi, block_q)] = jnp.broadcast_to(
        (m + jnp.log(l)).reshape(1, -1), (8, block_q)
    )


@jax.named_scope("flash_attention")
def _flash_forward(q, k, v, causal, scale, interpret=False, blocks=None):
    """q,k,v: [BH, S, D] (heads folded into batch). Returns (out, lse).
    `blocks`: (block_q, block_k), flash_tiles' answer where None."""
    BH, S, D = q.shape
    block_q, block_k = _kernel_blocks(q, causal, "fwd", blocks)
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
        compiler_params=_COMPILER_PARAMS,
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
    # the statistics lie along lanes; as columns once a program
    lse = lse_ref[0, 0, _rows(qi, block_q)][:, None]
    delta = delta_ref[0, 0, _rows(qi, block_q)][:, None]

    def tiles(masked):
        def body(kb, dq):
            # all MXU dots take bf16 operands with f32 accumulation; softmax
            # statistics and ds stay f32 on the VPU (see _online_softmax_loop)
            k = k_ref[0, _rows(kb, block_k), :]
            v = v_ref[0, _rows(kb, block_k), :]
            s = jax.lax.dot_general(
                q, k, _NT, preferred_element_type=jnp.float32) * scale
            if masked:
                s = _mask_after(s, 0, qi * block_q, kb * block_k)
            p = jnp.exp(s - lse)
            dp = jax.lax.dot_general(
                g, v, _NT, preferred_element_type=jnp.float32)
            ds = p * (dp - delta)
            return dq + jnp.dot(ds.astype(k.dtype), k,
                                preferred_element_type=jnp.float32)
        return body

    dq = _walk(tiles, jnp.zeros((block_q, D), jnp.float32),
               *_key_ranges(qi, block_q, block_k, seq_len, causal))
    # ds's factor `scale`, once a program on [block_q, D]
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, causal, scale, block_q,
                          seq_len):
    """dk/dv for one k block: iterate q blocks (≥ diagonal when causal).
    The scores are computed transposed, [block_k, block_q]: the
    statistics then broadcast along sublanes as they lie, and p.T @ g
    and ds.T @ q are plain products."""
    ki = pl.program_id(1)
    k = k_ref[0]
    v = v_ref[0]
    block_k, D = k.shape

    def tiles(masked):
        def body(qb, carry):
            dk, dv = carry
            q = q_ref[0, _rows(qb, block_q), :]
            g = g_ref[0, _rows(qb, block_q), :]
            lse = lse_ref[0, 0:1, _rows(qb, block_q)]
            delta = delta_ref[0, 0:1, _rows(qb, block_q)]
            s = jax.lax.dot_general(
                k, q, _NT, preferred_element_type=jnp.float32) * scale
            if masked:
                s = _mask_after(s, 1, qb * block_q, ki * block_k)
            p = jnp.exp(s - lse)
            dv = dv + jnp.dot(p.astype(g.dtype), g,
                              preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                v, g, _NT, preferred_element_type=jnp.float32)
            ds = p * (dp - delta)
            dk = dk + jnp.dot(ds.astype(q.dtype), q,
                              preferred_element_type=jnp.float32)
            return dk, dv
        return body

    num_qb = seq_len // block_q
    if causal:
        # q tiles from the first that sees this k block; those that
        # start at or after its end see all of it
        clear_from = pl.cdiv((ki + 1) * block_k, block_q)
        ranges = (clear_from, num_qb), ((ki * block_k) // block_q,
                                        clear_from)
    else:
        ranges = (0, num_qb), None
    dk, dv = _walk(tiles, (jnp.zeros((block_k, D), jnp.float32),
                           jnp.zeros((block_k, D), jnp.float32)), *ranges)
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


@jax.named_scope("flash_attention")
def _flash_backward_pallas(q, k, v, g, out, lse, causal, scale, interpret):
    """Pallas backward via the shared blockwise kernels (flash_block_bwd):
    dq grid over q blocks, dk/dv grid over k blocks. The gradients leave
    the kernels in the operands' dtype: nothing sums them afterwards."""
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # [BH, S]
    return flash_block_bwd(q, k, v, g, lse, delta, scale, causal, interpret,
                           grad_dtype=q.dtype)


def _flash_attention_bwd(causal, scale, interpret, res, g):
    q, k, v, out, lse = res
    return _flash_backward_pallas(q, k, v, g, out, lse, causal, scale,
                                  interpret)


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def flash_attention(q, k, v, causal=True, scale=None, interpret=False):
    """Pallas flash attention; q,k,v: [B, S, H, D] (kv heads may be fewer).

    Requires S to tile (`blocks_aligned`; the `attention` dispatcher
    takes the XLA path otherwise)."""
    B, S, H, D = q.shape
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
    m_ref[0, :, _rows(qi, block_q)] = jnp.broadcast_to(
        m.reshape(1, -1), (8, block_q)
    )
    l_ref[0, :, _rows(qi, block_q)] = jnp.broadcast_to(
        l.reshape(1, -1), (8, block_q)
    )


def _aligned(S, block_q, block_k):
    return (S % block_q == 0 and S % block_k == 0
            and (block_q % block_k == 0 or block_k % block_q == 0))


def blocks_aligned(S, D=128, dtype=jnp.bfloat16):
    """True when seq len S satisfies the flash-kernel contract with the
    tiles flash_tiles gives each kernel: S divisible by both blocks (a
    fori_loop bound of seq_len // block_k silently drops the k tail
    otherwise) and mutual block divisibility (the causal live-block
    count is exact only then). Single source of truth for both the
    kernels and the auto-dispatchers here and in ring_attention."""
    return all(_aligned(S, *flash_tiles(S, D, dtype, causal, kernel))
               for kernel in KERNELS for causal in (True, False))


def _kernel_blocks(q, causal, kernel, blocks=None):
    """(block_q, block_k) for `kernel` over q [BH, S, D]: `blocks` where
    given (a test's, a sweep's), else flash_tiles' answer. Raises where
    they break the contract for S — raising beats returning wrong
    attention output with no error."""
    _, S, D = q.shape
    block_q, block_k = blocks or flash_tiles(S, D, q.dtype, causal, kernel)
    if not _aligned(S, block_q, block_k):
        if S % block_q or S % block_k:
            raise ValueError(
                "flash block kernels require seq len divisible by the "
                "%d/%d block sizes (got %d); use the xla impl or pad the "
                "sequence" % (block_q, block_k, S)
            )
        raise ValueError(
            "flash attention block sizes must divide one another (got "
            "q=%d, k=%d)" % (block_q, block_k)
        )
    return block_q, block_k


@jax.named_scope("flash_attention")
def flash_block_fwd(q, k, v, scale, causal_diag, interpret=False,
                    blocks=None):
    """One ring step's unnormalized contribution.

    q, k, v: [BH, S, D] (heads folded). Returns (acc f32 [BH,S,D],
    m f32 [BH,S], l f32 [BH,S])."""
    BH, S, D = q.shape
    block_q, block_k = _kernel_blocks(q, causal_diag, "fwd", blocks)
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
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_block_fwd",
    )(q, k, v)
    return acc, m[:, 0, :], l[:, 0, :]


@jax.named_scope("flash_attention")
def flash_block_bwd(q, k, v, g, lse, delta, scale, causal_diag,
                    interpret=False, grad_dtype=jnp.float32, blocks=None):
    """One ring step's gradient contribution given the GLOBAL lse/delta.

    Same kernels as the single-device flash backward — the global stats make
    each blockwise p exact, so contributions just sum across ring hops.
    Returns (dq, dk, dv) in `grad_dtype` (f32 for the ring, which sums
    them over hops), shapes [BH, S, D]. `blocks`: ((block_q, block_k)
    of the dq kernel, the same of the dkv kernel)."""
    BH, S, D = q.shape
    dq_blocks, dkv_blocks = blocks or (None, None)
    lse_t = jnp.broadcast_to(lse[:, None, :], (BH, 8, S))
    delta_t = jnp.broadcast_to(delta[:, None, :], (BH, 8, S))
    stats_spec = pl.BlockSpec((1, 8, S), lambda b, i: (b, 0, 0))
    full_spec = pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0))

    block_q, block_k = _kernel_blocks(q, causal_diag, "dq", dq_blocks)
    q_spec = pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, causal=causal_diag, scale=scale,
            block_k=block_k, seq_len=S,
        ),
        grid=(BH, S // block_q),
        in_specs=[q_spec, full_spec, full_spec, q_spec, stats_spec,
                  stats_spec],
        out_specs=q_spec,
        out_shape=_sds((BH, S, D), grad_dtype, q),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, g, lse_t, delta_t)

    block_q, block_k = _kernel_blocks(q, causal_diag, "dkv", dkv_blocks)
    k_spec = pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, causal=causal_diag, scale=scale,
            block_q=block_q, seq_len=S,
        ),
        grid=(BH, S // block_k),
        in_specs=[full_spec, k_spec, k_spec, full_spec, stats_spec,
                  stats_spec],
        out_specs=[k_spec, k_spec],
        out_shape=[
            _sds((BH, S, D), grad_dtype, q),
            _sds((BH, S, D), grad_dtype, q),
        ],
        compiler_params=_COMPILER_PARAMS,
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
        "%s: shape %s does not tile for the flash kernel (sequence a "
        "multiple of %d, head size a multiple of 128, batch and heads "
        "dividing the mesh); using XLA attention"
        % (what, shape, MIN_BLOCK), RuntimeWarning, stacklevel=3)
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


def attention(q, k, v, causal=True, scale=None, impl="auto", mesh=None,
              window=None):
    """Dispatch: pallas flash on TPU when shapes tile cleanly, XLA
    where they do not, where the process is CPU-pinned, or by name.
    `mesh`: the mesh the caller's arrays are sharded over, if any —
    the kernel then runs per shard (see _flash_partition). `window`: a
    query sees itself and the window - 1 positions before it; the flash
    kernels have no window, so it is the XLA path's alone (ROADMAP M5)."""
    if window is not None:
        if impl not in ("auto", "xla") or not causal:
            raise ValueError("a window needs causal attention on the XLA "
                             "path, got impl=%r causal=%r" % (impl, causal))
        return reference_attention(q, k, v, causal=True, scale=scale,
                                   window=window)
    spec = _flash_partition(mesh, q, k)
    if impl == "auto":
        S, D = q.shape[1], q.shape[3]
        impl = auto_impl(
            blocks_aligned(S, D, q.dtype) and D % 128 == 0
            and spec is not False,
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
