"""Autoregressive decoding with a KV cache (and, for a model with
state-space layers, a recurrent state beside it).

The serving-side counterpart of models/llama.py: prefill runs the prompt
through the stack once and fills a static-shape KV cache; each decode step
appends one position via lax.dynamic_update_slice and attends over the
cache with a position mask. Everything is shape-static and jittable —
the whole generate loop is ONE compiled program (prefill + lax.scan over
steps), which is what keeps the MXU fed on TPU instead of relaunching a
kernel per token.

The reference framework has no inference engine (it orchestrates user
frameworks); this is part of the training/serving substrate the TPU
rebuild provides natively (SURVEY.md §5.7).

Attention over the cache (`decode_attention`) is grouped-query as stored:
the G = H // KV query heads that share a KV head are the rows of one
matrix product against that head's keys and values, in the cache's own
dtype with float32 accumulation. K and V are never repeated to H heads
and never widened; with a bfloat16 cache the probabilities of a block are
rounded to bfloat16 for the product with V, as the training kernel rounds
them (ops/attention.py). Two forms of the same arithmetic read the pools
(`_pool_attention` picks by what it can observe): the decode step of a
whole pool on a TPU is ONE Pallas call a layer (ops/decode_attention.py)
whose operands are the pools as stored, each lane read to its own depth
and no lane that does not decode; every other program (a prefill
program's rows, `generate()`, the paged engine, a process held to the
CPU) is the chunk loop `_streamed_attention`, whose one trip count runs
to the deepest row's depth. These, or for a shallow pool the kernel does
not take every lane's whole pool at once ("dense"): `pool_read` decides
once, from the shapes, and no option reaches it.

Model families: `family(cfg)` is the ONE place a family is picked, a
table keyed by the config's class (the feed-forward half of a block,
whether attention rotates its queries and keys, where `params` holds the
stack of each kind of layer). There is ONE layer loop (`_layers`): the
stacks are walked in the model's order (models/jamba.py, `scan_layers`; a
stack of one kind, Llama or Mixtral, is its one-run case, a stack of
several kinds, Jamba's Mamba-1 layers with an attention layer every so
many, holds one stack per kind). A layer is a mixer and then the family's
feed-forward, or where the family's `ffn` is None (Nemotron-H) one of the
two alone: its Mamba-2 and attention layers end at their residual, and
the kind `ffn` is a feed-forward with no mixer and no pool. The loop's
carry is the activations and the WHOLE cache: the pools are never a
scanned input or output, which would slice every layer out and write
every layer back into a second buffer. A layer's weights are sliced out
of their stack by the loop's index (`layer_at`), and the layer writes its
new positions into, and reads its chunks out of, its own index of the
donated pools in place. What those pools are for each kind of layer (K
and V, a ring of the last positions, a convolution's tail and a state,
one layer's K and V that later layers read again, nothing at all), and
how a pool is written and cut, is the cache's own business
(inference/cache.py: `POOLS`, `init_kv_cache`, `_write_layer`,
`_slot_rows`; this module knows no pool's axes but through them and the
attention reads); an attention kind says here which pools it reads,
whether it writes them and whether its queries see a window
(`ATTENTION`). Unlike a KV position, a recurrent state has no garbage
that is overwritten before it is seen: `valid` tells those layers which
of the new positions are real. The batch is the whole pool (a decode
step) or, with `slots`, a few distinct rows of it (the slot engine's
prefill program): K and V of those rows are then written in the pool by
index and a layer's views of them read out of it; a small recurrent state
(Mamba's: 0.3 MB a layer and slot) is cut out of its pools before the
loop and put back after it, a large one (power retention's: 34 MB a layer
and slot) is read and written in its pool by row index a layer at a time,
and its one-token update touches only the lanes that decode; no other row
is touched and no pool is copied. For a stack of attention layers the
batch may be both at once (`rows`, `merges`): a decode step's lanes and a
prefill program's rows as one batch of tokens, each at its own position,
for everything that multiplies by a weight, taken apart only for what
mixes positions (`_merged_layer`): one execution then reads every weight
once where the slot engine's two programs of an iteration read it twice.

A stack may be run several times over the same weights (a looped model:
the config declares `passes`, models/ouro.py). "Layer i" is then an index
into the weights only: a pool has `passes x layers` indices, pass t of
layer i reads and writes index t * layers + i (each pass attends to its
own K and V), the layer loop goes round once a pass inside one traced
body, and the model's norm closes every pass. Where a layer's leaves hold
`attn_post_norm` / `ffn_post_norm` the sublayer's output is normed before
it joins the residual (sandwich norms: `_sandwich`). `stack_passes`,
`cache_pools` (inference/cache.py) and `attention_reads` are where the
config's `passes` is read; every other config has one pass and traces
what it traced.

Scope names (jax.named_scope: metadata only, stable across recompiles;
benchmark/span_readings.py sums device time under them): the layer scan
is `decode_layers`, and inside a layer `attn_qkv`, `kv_cache_update`,
`decode_attention`, `attn_out` and `ffn` (ops/moe.py adds `moe_*` inside
`ffn`); inside a Mamba layer `ssm_in_proj`, `ssm_conv`, `ssm_x_proj`,
`ssm_scan` (a chunk) or `ssm_state_update` (one token), `ssm_out_proj`;
inside a Mamba-2 layer `ssd_in_proj`, `ssd_conv`, `ssd_chunk` (a row's
positions) or `ssd_state_update` (one token), `ssd_gate_norm`,
`ssd_out_proj`; inside a latent expert layer (under `ffn`) `moe_router`,
`moe_latent_down`, `moe_dispatch`, `moe_experts`, `moe_combine`,
`moe_latent_up`, `moe_shared_expert`; inside a retention layer
`retention_qkvg` (the projections, the head norms, rope and the gate),
`retention_chunk` (a chunk) or `retention_update` (one token),
`retention_out`; where a model has them, `window_attention` (a window
layer's ring reads and attention) and `cross_attention` (a layer's read
of another layer's K and V) beside `decode_attention`, `diff_combine` (a
differential pair's lambda, subtraction and sub-norm), `gmu` (a gated
memory unit), and in a prefill program whose last layers see one position
a row `cross_decoder` around those; in a stack run several times
`loop_pass` around one pass (inside `decode_layers`, the layers' scopes
inside it), `loop_norm` around the norm that closes it and `exit_gate`
around the gate. The pool's write sits under `kv_cache_update`, its chunk
reads under the attention kind's scope, a state pool's read and
write-back under the `ssm_*`, `ssd_*` or `retention_*` scope that needs
them: what lies under `decode_layers` and under none of those is the
loop's own cost (its counter, the residual stream), and anything the
compiler still moves without being asked.
"""

import collections
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..exception import TpuFlowException
from ..models import (
    brumby,
    jamba,
    llama,
    mixtral,
    nemotron_h,
    ouro,
    phi4flash,
)
from ..ops import (
    decode_attention,
    diff_attention,
    layer_norm,
    retention,
    rms_norm,
    ssm,
)
from ..ops.attention import NEG_INF
from ..ops.decode_attention import visible as _visible
from ..ops.moe import moe_ffn
from ..ops.rope import apply_rope, rope_frequencies
from .cache import (MOE_PAIRS, _layer_rows, _put_layer_rows, _put_slot_rows,
                    _slot_rows, _v_head_dim, _write_layer, cache_pools,
                    init_kv_cache, layer_kinds, stack_passes)

# name: what `tpuflow serve --model` takes; module: init_params,
# logical_axes, forward; ffn: the feed-forward half of a block,
# (cfg, h, lp, mesh) -> the residual's addend, or None where no mixer is
# followed by one (a layer is then a mixer or, the kind `ffn`, a
# feed-forward alone); rope: whether attention
# rotates q and k; stacks: kind of layer -> the key of `params` that
# holds that kind's stacked leaves. The kinds of layer come from the
# config (`layer_kinds`; a config without it is attention throughout).
Family = collections.namedtuple("Family", "name module ffn rope stacks")


def _dense_ffn(cfg, h, lp, mesh):
    return (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]


def _moe_ffn(cfg, h, lp, mesh):
    """Mixtral: token-choice MoE FFN."""
    dispatch = getattr(cfg, "moe_dispatch", "sparse")
    if dispatch in ("gmm", "gmm_ep"):
        # gmm's block-aligned padding is sized for training batches;
        # a per-token decode step would pad ~8 rows to experts×128.
        # sparse with no capacity is lossless — identical outputs.
        dispatch = "sparse"
    moe_out, _aux = moe_ffn(
        h, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"],
        num_experts_per_tok=cfg.experts_per_tok,
        capacity_factor=None,  # decode batches are tiny: lossless
        dispatch=dispatch,
        mesh=mesh,
    )
    return moe_out


FAMILIES = {
    llama.LlamaConfig: Family("llama", llama, _dense_ffn, True,
                              {"attention": "layers"}),
    mixtral.MixtralConfig: Family("mixtral", mixtral, _moe_ffn, True,
                                  {"attention": "layers"}),
    jamba.JambaConfig: Family("jamba", jamba, _dense_ffn, False,
                              {"attention": "attn_layers",
                               "mamba": "mamba_layers"}),
    brumby.BrumbyConfig: Family("brumby", brumby, _dense_ffn, True,
                                {"retention": "layers"}),
    ouro.OuroConfig: Family("ouro", ouro, _dense_ffn, True,
                            {"attention": "layers"}),
    phi4flash.Phi4FlashConfig: Family(
        "phi4flash", phi4flash, _dense_ffn, False,
        {kind: kind + "_layers"
         for kind in ("mamba", "window", "full", "cross", "gmu")}),
    nemotron_h.NemotronHConfig: Family("nemotron_h", nemotron_h, None, False,
                                       nemotron_h.STACKS),
}

# An attention kind of layer. k, v: the pools it reads; writes: whether
# it first writes its own K and V of the new positions there, at its own
# index (a layer that does not projects no K and V and reads index 0 of
# another kind's pool: one layer's, read by every such layer); window:
# whether a query sees only itself and the `cfg.sliding_window` - 1
# positions before it (its pool is then a ring); scope: what the reads
# and the attention stand under.
Attention = collections.namedtuple("Attention", "k v writes window scope")
ATTENTION = {
    "attention": Attention("k", "v", True, False, "decode_attention"),
    "window": Attention("win_k", "win_v", True, True, "window_attention"),
    "full": Attention("k", "v", True, False, "decode_attention"),
    "cross": Attention("k", "v", False, False, "cross_attention"),
}

# A kind of layer that carries a convolution's tail and a state (the
# pools `conv` and `ssm`). mixer: (cfg, lp, normed x, tail, state, valid,
# at) -> (out, tail, state) and, Mamba-1's, the recurrence's output
# before its gate; with `at` (layer, lanes), in a decode step of the
# whole pool, `state` is the pool itself, read and written at that layer
# for the lanes that decode. conv, step, chunk: the scopes that the
# tail's, and the state's read and write-back stand under (one token; a
# row's positions).
Recurrence = collections.namedtuple("Recurrence", "mixer conv step chunk")
RECURRENCES = {
    "mamba": Recurrence(jamba.mamba_mixer, "ssm_conv", "ssm_state_update",
                        "ssm_scan"),
    "mamba2": Recurrence(nemotron_h.mamba2_mixer, "ssd_conv",
                         "ssd_state_update", "ssd_chunk"),
}


def family(cfg):
    """The family of a model config: the one place it is picked."""
    try:
        return FAMILIES[type(cfg)]
    except KeyError:
        raise TpuFlowException(
            "no model family for a %s (families: %s)" % (
                type(cfg).__name__,
                ", ".join(sorted(f.name for f in FAMILIES.values()))))


def family_config_class(name):
    """The config dataclass of the family `--model` names."""
    for config_cls, fam in FAMILIES.items():
        if fam.name == name:
            return config_cls
    raise TpuFlowException("unknown model family %r (families: %s)" % (
        name, ", ".join(sorted(f.name for f in FAMILIES.values()))))


def _query_positions(pos, T):
    """Absolute query positions for T new tokens at offset `pos`.

    pos is either a traced SCALAR (the whole batch decodes in lockstep —
    generate()) or a traced [B] VECTOR (every batch row sits at its own
    offset — the continuous-batching slot engine). Returns [T] or [B, T];
    both shapes flow through apply_rope and the attention masks."""
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        return pos + jnp.arange(T)
    return pos[:, None] + jnp.arange(T)[None, :]


def _mask_positions(q_positions):
    """[T] or [B, T] query positions -> broadcastable [*, 1, 1, T, 1] for
    the grouped [B, KV, G, T, S] logits layout (one mask for the whole
    group of query heads that share a KV head)."""
    if q_positions.ndim == 1:
        return q_positions[None, None, None, :, None]
    return q_positions[:, None, None, :, None]


def _group_queries(q, n_kv_heads):
    """[B, T, H, Hd] -> [B, KV, G, T, Hd]: the G = H // KV query heads
    that share KV head k (heads k*G .. k*G+G-1, the order jnp.repeat
    gives) become rows of ONE [G*T, Hd] matrix against that head's keys,
    so K and V are contracted as stored and never repeated. G = 1 is
    plain multi-head attention through the same code."""
    B, T, H, Hd = q.shape
    q = q.reshape(B, T, n_kv_heads, H // n_kv_heads, Hd)
    return q.transpose(0, 2, 3, 1, 4)


def _ungroup(out, dtype):
    """[B, KV, G, T, Hd] -> [B, T, H, Hd], the inverse of _group_queries."""
    B, KV, G, T, Hd = out.shape
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, T, KV * G, Hd)
    return out.astype(dtype)


def _value_groups(a, kv_heads):
    """[B, KV, G, ...] -> [B, kv_heads, KV // kv_heads * G, ...]: the
    groups of consecutive key heads that share one value head, side by
    side over it (differential attention: a pair's two score maps read
    one value head; `ops/diff_attention.py`). The same array where K and
    V have as many heads."""
    if a.shape[1] == kv_heads:
        return a
    return a.reshape((a.shape[0], kv_heads, -1) + a.shape[3:])


def _cached_attention(q, cache_k, cache_v, pos, window=None, ring=False,
                      scope="decode_attention", dtype=None):
    """q: [B, T, H, Hd] at absolute positions pos..pos+T-1; cache_k:
    [B, S, KV, Hd], cache_v: [B, S, KV or fewer, Dv]. Keys at index i
    are visible to query t iff i <= pos + t (unfilled cache slots fall
    outside by construction), and as `_visible` says of a window and,
    with `ring`, of a pool that is a window layer's ring. pos: traced
    scalar, or [B] vector for per-slot offsets.
    Returns [B, T, H, Dv] in `dtype` (None: q's), under `scope`.

    Dense: touches the WHOLE [S] cache every step — fine at moderate
    max_seq, bandwidth-bound for long-context serving (use 'chunked').
    The same grouped contraction as _streamed_attention: both products
    batched over (B, KV) on the cache's dtype with float32 accumulation,
    softmax in float32, probabilities rounded to V's dtype."""
    with jax.named_scope(scope):
        T, Hd = q.shape[1], q.shape[3]
        scale = 1.0 / math.sqrt(Hd)
        qg = _group_queries(q, cache_k.shape[2])
        logits = jnp.einsum("bkgtd,bskd->bkgts", qg, cache_k,
                            preferred_element_type=jnp.float32) * scale
        key_idx = jnp.arange(cache_k.shape[1])
        q_pos = _mask_positions(_query_positions(pos, T))
        seen = _visible(key_idx, q_pos, window,
                        cache_k.shape[1] if ring else None)
        logits = jnp.where(seen, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(cache_v.dtype)
        out = jnp.einsum("bkgts,bskd->bkgtd",
                         _value_groups(probs, cache_v.shape[2]), cache_v,
                         preferred_element_type=jnp.float32)
        return _ungroup(out, dtype or q.dtype)


# KV-chunk size of the chunk loop (`_streamed_attention`), and the pivot
# of `pool_read`. Why 256: a choice, and a constant until a chip has
# swept it on the reads that use it (a prefill program's rows,
# `generate()`, the paged engine, every run on a CPU). The decode step's
# kernel takes no notice of it: its block is `decode_block`'s answer.
DECODE_CHUNK = 256


def _streamed_attention(q, pos, chunk, n_chunks, fetch, window=None,
                        ring=None, scope="decode_attention", dtype=None):
    """Online-softmax attention over KV streamed in `chunk`-sized blocks
    (the flash-decode accumulation shared by the contiguous-cache and
    paged-cache paths; only HOW a block is fetched differs), under
    `scope`.

    fetch(i) -> (k_blk [B, chunk, KV, Hd], v_blk [B, chunk, KV or fewer,
    Dv], key_idx [chunk]): the i-th KV block and the indices of the pool
    it holds (a position's own, or in a ring its place). Keys are
    visible as `_visible` says (causal; a window; a ring's positions)
    AND where key_idx >= i * chunk — that term masks a clamped edge
    block's re-read of earlier keys (a paged fetch never re-reads, so the
    term is a no-op there).

    A block is contracted as fetched, in the cache's dtype: the query
    heads of a group are rows of one matrix product per (slot, KV head)
    (_group_queries), accumulated in float32. Where V has fewer heads
    than K (differential attention: a pair's two key heads over one
    value head of twice the size), the score maps of the key heads that
    share a value head are rows of ONE product with it
    (`_value_groups`), so each K and V chunk is read once for both maps.
    Logits, mask, running max and sum and the accumulator are float32;
    the block's probabilities are rounded to V's dtype for the second
    product (as the training kernel does, ops/attention.py), the running
    sum is taken before the rounding. Returns [B, T, H, Dv] in `dtype`
    (None: q's)."""
    with jax.named_scope(scope):
        T, Hd = q.shape[1], q.shape[3]
        scale = 1.0 / math.sqrt(Hd)
        q_pos = _mask_positions(_query_positions(pos, T))
        # heads from a block's shape, without fetching one
        k_shape, v_shape, _ = jax.eval_shape(fetch, 0)
        qg = _group_queries(q, k_shape.shape[2])
        over_v = lambda a: _value_groups(a, v_shape.shape[2])

        def body(i, carry):
            m, l, acc = carry
            k_blk, v_blk, key_idx = fetch(i)
            logits = jnp.einsum("bkgtd,bckd->bkgtc", qg, k_blk,
                                preferred_element_type=jnp.float32) * scale
            visible = _visible(key_idx, q_pos, window, ring) \
                & (key_idx >= i * chunk)
            logits = jnp.where(visible, logits, NEG_INF)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * over_v(corr)[..., None] + jnp.einsum(
                "bkgtc,bckd->bkgtd", over_v(p.astype(v_blk.dtype)), v_blk,
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new

        m0 = jnp.full(qg.shape[:-1], NEG_INF, jnp.float32)
        l0 = jnp.zeros(qg.shape[:-1], jnp.float32)
        acc0 = jnp.zeros(over_v(l0).shape + v_shape.shape[3:], jnp.float32)
        _, l, acc = jax.lax.fori_loop(0, n_chunks, body, (m0, l0, acc0))
        return _ungroup(acc / over_v(l)[..., None], dtype or q.dtype)


def _chunked_cached_attention(q, cache_k, cache_v, pos, layer,
                              chunk=DECODE_CHUNK, window=None, ring=False,
                              v_head_dim=None, **kw):
    """Flash-decode: the same attention reading ONLY the filled prefix
    of layer `layer` (a traced index) of the pools cache_k/v
    [layers, B, S, KV * Hd]; the chunks are read straight out of that
    layer of them, so the layer's view is never copied. V's heads are
    `v_head_dim` wide (None: as wide as K's). With `ring` the pool is a
    window layer's ring: once a slot has passed its depth every index
    holds a position, and all of it is read.

    KV chunks stream through an online-softmax accumulation
    (lax.fori_loop with a TRACED trip count ceil((pos+T)/chunk), lowered
    to a while_loop) — per emitted token the HBM traffic is O(filled),
    not O(S), which is what long-context serving needs. Numerics
    follow the dense path: the same grouped products accumulated in
    float32, the same masking, probabilities rounded to V's dtype per
    chunk instead of once; the edge chunk's clamped slice re-reads
    earlier keys, masked out by the `key >= chunk start` term."""
    B, T, _, Hd = q.shape
    Smax = cache_k.shape[2]
    chunk = min(chunk, Smax)
    # traced trip count; with per-slot [B] positions the loop runs to the
    # DEEPEST slot's fill (shallower slots just mask the extra chunks)
    filled = jnp.max(jnp.asarray(pos)) + T
    if ring:
        filled = jnp.minimum(filled, Smax)
    n_chunks = (filled + chunk - 1) // chunk

    def fetch(i):
        start = jnp.minimum(i * chunk, Smax - chunk)
        at, size = (layer, 0, start, 0), (1, B, chunk, cache_k.shape[3])
        k_blk = jax.lax.dynamic_slice(cache_k, at, size).reshape(
            B, chunk, -1, Hd)
        v_blk = jax.lax.dynamic_slice(cache_v, at, size).reshape(
            B, chunk, -1, v_head_dim or Hd)
        return k_blk, v_blk, start + jnp.arange(chunk)

    return _streamed_attention(q, pos, chunk, n_chunks, fetch, window=window,
                               ring=Smax if ring else None, **kw)


def _pool_attention(q, cache_k, cache_v, pos, layer, lanes=None,
                    v_head_dim=None, window=None, ring=False,
                    scope="decode_attention", dtype=None):
    """The attention of one block over layer `layer` of its pools, by
    what the call can observe. One new position a lane of the whole pool
    (`lanes`: `_decode_lanes`' answer, None for any other program) at
    shapes the kernel takes (`ops/decode_attention.py`, `applies`): on a
    TPU one Pallas call that reads the pools as stored, each lane to its
    own depth, and no lane that does not decode; the choice is the
    lowering platform's (`jax.lax.platform_dependent`), so a compile for
    a described chip holds the kernel and XLA:CPU the loop. Everything
    else (a prefill program's rows, `generate()`'s lockstep batch, sizes
    with no whole tiles) is `_chunked_cached_attention`, in chunks of
    `DECODE_CHUNK`, which the kernel ignores. Under `scope` either
    way."""
    kw = dict(v_head_dim=v_head_dim, window=window, ring=ring, dtype=dtype)
    loop = functools.partial(_chunked_cached_attention, scope=scope, **kw)
    if lanes is None or not decode_attention.applies(q, cache_k, cache_v,
                                                     v_head_dim):
        return loop(q, cache_k, cache_v, pos, layer)
    with jax.named_scope(scope):
        return jax.lax.platform_dependent(
            q, cache_k, cache_v, pos, jnp.asarray(layer, jnp.int32), *lanes,
            tpu=functools.partial(decode_attention.attend, **kw),
            default=lambda q, k, v, pos, layer, *lanes: loop(
                q, k, v, pos, layer))


def _decode_lanes(pos, valid, T, mesh):
    """What the decode step's attention kernel prefetches, once a
    program: (the lanes that decode first, how many they are, which they
    are), or None for a program that is no decode step of a whole pool
    on one chip (several new positions a row, one position for the whole
    batch, a mesh)."""
    if T != 1 or jnp.ndim(pos) != 1 or mesh is not None:
        return None
    valid = jnp.ones(pos.shape, bool) if valid is None else valid[:, 0]
    return decode_attention.live_lanes(valid) + (valid,)


def attention_reads(cfg, cache, attn_impl="chunked", kernel=True):
    """What a decode step's attention reads, by the shapes alone: for
    every kind of layer that reads a pool (reads a step: the reading
    layers, times the config's passes; the pool's depth; the most
    positions a query sees there; how many positions are fetched at a
    time; how). cache: the pools (their shapes alone are
    read). How: "kernel" where `kernel` (the decode step runs on a TPU
    with no mesh) and the shapes are the kernel's
    (`ops/decode_attention.py`): each decoding lane in blocks of
    `decode_block`; "loop": every lane of the pool to the deepest one's
    depth in chunks of `DECODE_CHUNK`; "dense": every lane's whole
    pool."""
    kinds = layer_kinds(cfg)
    reads = []
    for kind in sorted(set(kinds) & set(ATTENTION)):
        a = ATTENTION[kind]
        pool_k, pool_v = cache[a.k], cache[a.v]
        B, S, width = pool_k.shape[1:]
        q = jax.ShapeDtypeStruct((B, 1, cfg.n_heads, cfg.head_dim),
                                 llama.param_dtype(cfg))
        if attn_impl != "chunked":
            unit, how = S, "dense"
        elif kernel and decode_attention.applies(q, pool_k, pool_v,
                                                 _v_head_dim(cfg)):
            unit, how = decode_attention.decode_block(
                S, width, pool_k.dtype), "kernel"
        else:
            unit, how = min(DECODE_CHUNK, S), "loop"
        # a stack run several times reads each layer's pool once a pass
        reads.append((stack_passes(cfg) * kinds.count(kind), S,
                      cfg.sliding_window if a.window else S, unit, how))
    return reads


def pool_read(depth, cfg=None, cache=None, mesh=None):
    """How a program reads K and V pools `depth` positions deep, the ONE
    rule, by the shapes: "chunked" past twice `DECODE_CHUNK` positions (a
    choice: no chip has timed where the two cross), and, for a decode
    step of the whole pools `cache` (the slot engine's; `generate()` and
    the paged engine give a depth alone, their programs are never the
    kernel's), at ANY depth where every attention read of the step is
    the kernel's (one chip, shapes `ops/decode_attention.py` takes): it
    reads each decoding lane to its own depth and no other lane, never
    more than the dense read of every lane's whole pool, and only a
    chunked stack lets the prefill rows ride in the step (`merges`).
    Else "dense"."""
    if depth > 2 * DECODE_CHUNK:
        return "chunked"
    if cache is None:
        return "dense"
    reads = attention_reads(cfg, cache, "chunked", kernel=mesh is None)
    return "chunked" if reads and all(
        how == "kernel" for *_, how in reads) else "dense"


def state_updates(cfg, cache, kernel=True):
    """How a decode step of the whole pool updates each recurrent pool,
    by the shapes alone: "kernel" where `kernel` (the step runs on a TPU
    with no mesh) and a lane's state of one layer is whole tiles of the
    chip: one Pallas call a layer that reads and writes the decoding
    lanes' state where it lies and no other lane (ops/ssm.py,
    ops/retention.py); "loop": the layer of every lane, cut out of the
    pool, updated and put back. cache: the pools (their shapes alone are
    read). A pool that is small beside its layer's state (a
    convolution's tail, retention's normaliser) is always the latter."""
    takes = {"ssm": ssm.whole_tiles, "ret_s": retention.whole_tiles}
    return {
        name: "kernel" if kernel and name in takes
        and takes[name](cache[name]) else "loop"
        for name, (pool, _) in cache_pools(cfg).items() if pool.recurrent}


def attention_positions(reads, depth):
    """(needed, fetched) of one decode step: how many K and V positions
    its queries see, over every layer of `reads` (`attention_reads`),
    and how many the program fetches for them. depth: [B] numpy, a
    decoding lane's new position + 1 and 0 for a lane that does not
    decode. The kernel fetches each lane's depth in whole blocks, a ring
    capped at its depth, and nothing for a lane that does not decode."""
    needed = fetched = 0
    for layers, S, sees, unit, how in reads:
        needed += layers * int(np.minimum(depth, sees).sum())
        held = np.minimum(depth, S)
        if how == "kernel":
            fetched += layers * int(
                decode_attention.fetched_positions(held, unit, S).sum())
        else:   # every lane, to the deepest one's depth or the pool's
            deepest = S if how == "dense" else int(held.max())
            fetched += layers * len(depth) * min(
                -(-deepest // unit) * unit, S)
    return needed, fetched


def _norm(cfg, x, lp, name):
    """The norm `name` of a layer's (or the model's) leaves: an RMS norm,
    or where a bias `<name>_b` stands beside the weight a LayerNorm."""
    if name + "_b" in lp:
        return layer_norm(x, lp[name], lp[name + "_b"], cfg.norm_eps)
    return rms_norm(x, lp[name], cfg.norm_eps)


def _linear(h, lp, w, b):
    """h @ lp[w], plus the bias lp[b] where the leaves hold one."""
    out = h @ lp[w]
    return out + lp[b] if b in lp else out


@jax.named_scope("attn_qkv")
def _attn_qkv(cfg, cos, sin, pos, x, lp):
    """The pre-attention half of a block: attn-norm, QKV projections,
    the q and k head norms where the layer has them, and (where the
    family rotates them) rope at the absolute positions `pos` implies;
    without rope `cos` and `sin` are None. Shared verbatim by the
    contiguous-cache layer below and the paged-cache layer
    (serving/paged.py) so both paths stay numerically identical."""
    return _project_qkv(cfg, cos, sin, pos, _norm(cfg, x, lp, "attn_norm"),
                        lp)


def _project_qkv(cfg, cos, sin, pos, h, lp):
    """q, k and v of the normed input h [B, T, dim]: what the layer's
    leaves hold. k and v are None for a layer that projects none (it
    reads another layer's); v's heads are `v_head_dim` wide where the
    config says so; a differential layer (it holds the lambda vectors)
    gets its query heads in `diff_attention.pair_major`'s order."""
    B, T, _ = h.shape
    H, KV, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _linear(h, lp, "wq", "bq").reshape(B, T, H, Hd)
    k = v = None
    if "wk" in lp:
        k = _linear(h, lp, "wk", "bk").reshape(B, T, KV, Hd)
        v = _linear(h, lp, "wv", "bv").reshape(B, T, -1, _v_head_dim(cfg))
    if "q_norm" in lp:   # an RMS norm a head, one weight a head size
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    if cos is not None:
        positions = _query_positions(pos, T)
        q = apply_rope(q, cos, sin, positions=positions)
        k = apply_rope(k, cos, sin, positions=positions)
    if "lambda_q1" in lp:
        q = diff_attention.pair_major(q, KV)
    return q, k, v


def _block_ffn(cfg, x, attn, lp, mesh=None):
    """The post-attention half of a block: output projection, residual,
    and the family's feed-forward (dense or MoE). Shared by the
    contiguous and paged cache paths."""
    B, T, _ = x.shape
    with jax.named_scope("attn_out"):
        x = x + _sandwich(cfg, _linear(
            attn.reshape(B, T, cfg.n_heads * cfg.head_dim), lp, "wo", "bo"),
            lp, "attn_post_norm")
    return _ffn(cfg, x, lp, mesh)


def _ffn(cfg, x, lp, mesh):
    """x after the feed-forward that follows a mixer in the same layer:
    the family's, or x itself where its mixers are followed by none."""
    ffn = family(cfg).ffn
    if ffn is None:
        return x
    with jax.named_scope("ffn"):
        h = _norm(cfg, x, lp, "ffn_norm")
        return x + _sandwich(cfg, ffn(cfg, h, lp, mesh), lp, "ffn_post_norm")


def _sandwich(cfg, out, lp, name):
    """A sublayer's output on its way into the residual: as it is, or
    where the layer's leaves hold a post norm `name` (sandwich norms)
    normed first."""
    return _norm(cfg, out, lp, name) if name in lp else out


def _at(a, last):
    """Position last[b] of row b of a [B, T, ...]: [B, 1, ...]."""
    return a[jnp.arange(a.shape[0]), last][:, None]


def _decode_layer(cfg, kind, cos, sin, pos, x, layer_params, cache, layer,
                  mesh=None, attn_impl="dense", slots=None, last=None,
                  lanes=None):
    """One attention block of `kind` (`ATTENTION`) over T new tokens,
    reading and (a kind that writes) extending index `layer` (traced;
    pass and layer: `_layers`) of its pools [passes x layers, B, S,
    KV * Hd], written and read in place: every row of the pool, or with
    `slots` the rows it names (a
    pool the loop holds as a view holds just the batch already). The
    feed-forward half is the family's. With `last` ([B]) K and V of all
    T positions are written and the rest of the block, from the queries
    on, runs for position last[b] of each row alone: x comes back
    [B, 1, dim]. `lanes`: `_decode_lanes`' answer for the program.
    Returns (x, cache)."""
    lp, a = layer_params, ATTENTION[kind]
    cache_k, cache_v = cache[a.k], cache[a.v]
    if cache_pools(cfg)[a.k][0].view:
        slots = None
    q, k, v = _attn_qkv(cfg, cos, sin, pos, x, lp)

    if a.writes:
        with jax.named_scope("kv_cache_update"):
            cache_k = _write_layer(cache_k, k.astype(cache_k.dtype), pos,
                                   layer, slots, ring=a.window)
            cache_v = _write_layer(cache_v, v.astype(cache_v.dtype), pos,
                                   layer, slots, ring=a.window)
        cache = dict(cache, **{a.k: cache_k, a.v: cache_v})
    if last is not None:
        x, q, pos = _at(x, last), _at(q, last), pos + last

    # a layer that writes none reads the ONE layer its pools hold
    read_k, read_v, at = cache_k, cache_v, layer if a.writes else 0
    if slots is not None:
        # the rows' views of this layer, cut out once a layer (a pool of
        # one layer that holds the batch): a few MB a row, where a chunk
        # loop that read two slots' chunks out of the whole pool made the
        # compiler lay the pool out anew in every layer (PERF.md, PR 30)
        with jax.named_scope(a.scope):
            read_k = _slot_rows(cache_k, slots, at)
            read_v = _slot_rows(cache_v, slots, at)
        at = 0
    differential = "lambda_q1" in lp
    # a differential layer's two maps are subtracted before anything
    # rounds them to the model's dtype
    kw = dict(window=cfg.sliding_window if a.window else None, ring=a.window,
              scope=a.scope, dtype=jnp.float32 if differential else None)
    if attn_impl == "chunked":
        attn = _pool_attention(
            q, read_k, read_v, pos, at, lanes, v_head_dim=_v_head_dim(cfg),
            **kw)
    else:
        view = lambda pool, hd: pool[at].reshape(pool.shape[1:3] + (-1, hd))
        attn = _cached_attention(
            q, view(read_k, cfg.head_dim), view(read_v, _v_head_dim(cfg)),
            pos, **kw)
    if differential:
        with jax.named_scope("diff_combine"):
            lam0 = jnp.asarray(cfg.lambda_init[kind], jnp.float32)[layer]
            attn = diff_attention.combine(attn, cfg.n_kv_heads, lp, lam0,
                                          cfg.norm_eps, x.dtype)
    x = _block_ffn(cfg, x, attn, lp, mesh=mesh)
    return x, cache


def _merged_layer(cfg, cos, sin, pos, x, layer_params, cache, layer, lanes,
                  rows, mesh=None):
    """One block of a stack of `attention` layers, whose K and V live at
    index `layer` of the pools (pass and layer: `_layers`), over a decode
    step's lanes AND a prefill program's rows as one batch, so that the
    layer's weights are read once for both: x [B + R * W, 1, dim] holds the
    lanes' one new token each, at their cursors `pos` [B], and after them
    the rows' tokens row by row; rows = (slots [R] distinct, start [R],
    W): row r is W tokens of slot slots[r] from position start[r].
    Everything that multiplies by a weight (the norms, q, k and v with
    rope at each token's own position, the output projection, the
    feed-forward) runs once over all of them. What mixes positions takes
    them apart, each part on the path it has in a program of its own: the
    lanes write K and V at their cursors and read through
    `_pool_attention` with `lanes` (on a TPU the kernel that reads the
    pool as stored), the rows write at their slots and read their slots'
    views in the chunk loop, all in place in the carried pool.

    The rows' writes come AFTER the lanes'. A slot that is mid-prefill is
    also a masked lane of the step, whose write lands at its cursor: past
    everything real, so overwritten before it is seen, but in a merged
    program its row may be writing that very position in the same
    execution, and the row's K and V are the real ones.
    Returns (x, cache)."""
    lp, a = layer_params, ATTENTION["attention"]
    slots, start, W = rows
    B, R = pos.shape[0], slots.shape[0]
    at = jnp.concatenate([pos, _query_positions(start, W).reshape(-1)])
    q, k, v = _attn_qkv(cfg, cos, sin, at, x, lp)
    of_lanes = lambda t: t[:B]
    of_rows = lambda t: t[B:].reshape((R, W) + t.shape[2:])
    cache_k, cache_v = cache[a.k], cache[a.v]
    with jax.named_scope("kv_cache_update"):
        write = lambda pool, new: _write_layer(
            _write_layer(pool, of_lanes(new), pos, layer),
            of_rows(new), start, layer, slots)
        cache_k = write(cache_k, k.astype(cache_k.dtype))
        cache_v = write(cache_v, v.astype(cache_v.dtype))
    kw = dict(v_head_dim=_v_head_dim(cfg), scope=a.scope)
    attn = _pool_attention(of_lanes(q), cache_k, cache_v, pos, layer, lanes,
                           **kw)
    with jax.named_scope(a.scope):   # as `_decode_layer` reads its rows
        read_k = _slot_rows(cache_k, slots, layer)
        read_v = _slot_rows(cache_v, slots, layer)
    attn_rows = _pool_attention(of_rows(q), read_k, read_v, start, 0, **kw)
    attn = jnp.concatenate(
        [attn, attn_rows.reshape((R * W, 1) + attn_rows.shape[2:])])
    x = _block_ffn(cfg, x, attn, lp, mesh=mesh)
    return x, dict(cache, **{a.k: cache_k, a.v: cache_v})


def _mamba_layer(cfg, kind, x, lp, conv, state, valid, at=None):
    """One Mamba block of `kind` (`RECURRENCES`) over T new tokens from
    this layer's carried (conv [B, K-1, channels], state), or with `at`
    (layer, lanes) from that layer of the whole pool `state`, updated in
    place; the last of the four returned is, for Mamba-1, the
    recurrence's output before its gate, [B, T, Di] float32 (else
    None)."""
    out, conv, state, *y = RECURRENCES[kind].mixer(
        cfg, lp, _norm(cfg, x, lp, "ssm_norm"), conv, state, valid, at)
    return _ffn(cfg, x + out, lp, None), conv, state, y[0] if y else None


def _ffn_layer(cfg, x, lp, cache, valid):
    """One layer that is a feed-forward alone (a latent mixture of
    experts, models/nemotron_h.py), and the cache with the pairs it made
    counted where the cache counts them."""
    with jax.named_scope("ffn"):
        out, pairs = nemotron_h.latent_moe(
            cfg, lp, _norm(cfg, x, lp, "ffn_norm"), valid)
    if MOE_PAIRS in cache:
        cache = dict(cache, **{MOE_PAIRS: cache[MOE_PAIRS] + pairs})
    return x + out, cache


def _gmu_layer(cfg, x, lp, memory):
    """One gated-memory-unit block: out = (silu(h W1) * m) W2 with m
    the memory at the same positions, [B, T, Di]."""
    with jax.named_scope("gmu"):
        out = phi4flash.gated_memory_unit(
            lp, _norm(cfg, x, lp, "gmu_norm"), memory)
    return _ffn(cfg, x + out, lp, None)


def _retention_layer(cfg, cos, sin, pos, x, lp, cache, layer, valid, slots):
    """One power-retention block over T new tokens, reading and writing
    layer `layer` (a traced index) of the state pools `ret_s`
    [layers, B, KV, Hd, D] and `ret_z` in place: one token updates the
    lanes that are valid and reads no other (ops/retention.py,
    `update_pool`); a chunk reads its rows' state out of the pool by
    index (every row, or the rows `slots` names) and writes it back to
    the same rows, this layer's 34 MB a row and no more."""
    B, T, _ = x.shape
    with jax.named_scope("retention_qkvg"):
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q, k, v = _project_qkv(cfg, cos, sin, pos, h, lp)
        log_g = brumby.log_gate(lp, h)
    pool_s, pool_z = cache["ret_s"], cache["ret_z"]
    if T == 1:
        with jax.named_scope("retention_update"):
            y, pool_s, pool_z = retention.update_pool(
                pool_s, pool_z, layer, q[:, 0], k[:, 0], v[:, 0],
                log_g[:, 0], cfg.retention_eps,
                None if valid is None else valid[:, 0])
    else:
        with jax.named_scope("retention_chunk"):
            y, S, z = retention.chunk(
                _layer_rows(pool_s, layer, slots),
                _layer_rows(pool_z, layer, slots),
                q, k, v, log_g, cfg.retention_eps, valid)
            pool_s = _put_layer_rows(pool_s, S, layer, slots)
            pool_z = _put_layer_rows(pool_z, z, layer, slots)
    with jax.named_scope("retention_out"):
        x = x + y.reshape(B, T, -1).astype(x.dtype) @ lp["wo"]
    return _ffn(cfg, x, lp, None), dict(cache, ret_s=pool_s, ret_z=pool_z)


def _layers(cfg, params, x, cache, pos, valid, mesh, attn_impl, slots,
            last=None, rows=None, exits=False):
    """The layer loop of every family: the activations, the whole cache
    and (a model with gated memory units) the memory are its carry, and
    layer i of a kind reads its weights out of that kind's stack and
    reads and writes index i of that kind's pools (of the rows `slots`
    names, where the batch is not the whole pool). A config that declares
    `passes` goes round its stack of attention layers that many times
    over the same weights (`stack_passes`; every other config once, and
    nothing here is traced for it that was not), and pass t of layer i
    reads and writes pool index t * layers + i: one traced body whatever
    the passes, the model's norm
    applied to the carry at the end of every pass (scope `loop_pass`
    around a pass, `loop_norm` around the norm that closes it), so that x
    comes back normed; with `exits` the exit gate reads each pass's
    normed carry (scope `exit_gate`) and the third value returned is
    lambda of every pass, [passes, batch, T] float32 (else None). With
    `last` the layers
    before the config's `tail_layer` run over every position, that layer
    writes K and V of every position, and from its queries on the loop
    runs for position last[b] of each row alone (scope `cross_decoder`):
    x comes back [B, 1, dim]. With `rows` (slots, start, W) the batch is a
    decode step's lanes followed by a prefill program's rows, and every
    layer is a `_merged_layer` (`merges` says for which stacks)."""
    fam = family(cfg)
    kinds = layer_kinds(cfg)
    passes = stack_passes(cfg)
    # rope's table is as long as the KV pool is deep; a stack that caches
    # no K and V is bound by the config's positions alone
    cos, sin = rope_frequencies(
        cfg.head_dim,
        cache["k"].shape[2] if "k" in cache else cfg.max_seq_len,
        cfg.rope_theta, dtype=llama.param_dtype(cfg),
        llama3_scaling=getattr(cfg, "rope_llama3_scaling", False),
    ) if fam.rope else (None, None)
    # a decode step of the whole pool: what its attention kernel
    # prefetches, once a program and not once a layer
    lanes = None if slots is not None or last is not None else \
        _decode_lanes(pos, valid, x.shape[1], mesh)

    def layers(pos, valid, last=None, t=None):
        """The loop's body for new tokens at `pos`, of which `valid`
        are real; with `last`, the body of the layer that narrows; with
        `t` (traced), the body of pass t of a stack run several times."""

        def body(kind, i, carry):
            x, cache, memory = carry
            if kind == "ffn":
                lp = nemotron_h.moe_leaves(params[fam.stacks[kind]], i)
                return _ffn_layer(cfg, x, lp, cache, valid) + (memory,)
            lp = jamba.layer_at(params[fam.stacks[kind]], i)
            # where the layer's K and V live in their pools
            index = i if t is None else t * len(kinds) + i
            if rows is not None:
                return _merged_layer(cfg, cos, sin, pos, x, lp, cache, index,
                                     lanes, rows, mesh) + (memory,)
            if kind in ATTENTION:
                x, cache = _decode_layer(
                    cfg, kind, cos, sin, pos, x, lp, cache, index, mesh=mesh,
                    attn_impl=attn_impl, slots=slots, last=last,
                    lanes=lanes)
                if last is not None and memory is not None:
                    memory = _at(memory, last)
                return x, cache, memory
            if kind == "retention":
                return _retention_layer(cfg, cos, sin, pos, x, lp, cache, i,
                                        valid, slots) + (memory,)
            if kind == "gmu":
                return _gmu_layer(cfg, x, lp, memory), cache, memory
            # the layer's tail and state are read out of the pools and
            # written back under the scope of the op that uses them, so
            # that a scope's device time holds the pool's traffic that its
            # kernel needs. In a decode step of the whole pool the state
            # is not cut out: the mixer gets the pool, the layer and the
            # lanes that decode, and its one-token update (under the
            # scope `step`) reads and writes those lanes' state of this
            # layer where it lies (ops/ssm.py, `ssd_update_pool`,
            # `selective_update_pool`)
            cache = dict(cache)
            scopes = RECURRENCES[kind]
            conv_scope = jax.named_scope(scopes.conv)
            state_scope = jax.named_scope(
                scopes.step if x.shape[1] == 1 else scopes.chunk)
            at = lambda a: jax.lax.dynamic_index_in_dim(a, i, 0,
                                                        keepdims=False)
            put = jax.lax.dynamic_update_index_in_dim
            with conv_scope:
                conv = at(cache["conv"])
            if lanes is not None:
                x, conv, cache["ssm"], y = _mamba_layer(
                    cfg, kind, x, lp, conv, cache["ssm"], valid, (i, lanes))
            else:
                with state_scope:
                    state = at(cache["ssm"])
                x, conv, state, y = _mamba_layer(cfg, kind, x, lp, conv,
                                                 state, valid)
                with state_scope:
                    cache["ssm"] = put(cache["ssm"], state, i, 0)
            with conv_scope:
                cache["conv"] = put(cache["conv"], conv, i, 0)
            if memory is not None:
                # the config marks which Mamba layer's output the gated
                # memory units after it read
                memory = jnp.where(i == cfg.memory_layer,
                                   y.astype(memory.dtype), memory)
            return x, cache, memory

        return body

    # K and V are read and written in their pools, rows `slots` of them,
    # and so is a large recurrent state (power retention's, 34 MB a layer
    # and slot: a layer's rows by index). A small one is a `view` pool
    # (Mamba's: 8.5 MB a slot over all 26 layers, and a slot's convolution
    # tail lies inside a tile of the chip's layout, where one row cannot
    # be written in place: compiled for the chip, the whole pool was laid
    # out anew on the way in and out): the rows' part of all its layers
    # is cut out once, carried through the loop as a pool that holds just
    # the batch, and put back after it. So is ONE layer's K and V that
    # several layers read (10 MB a row at 4,096 positions): cut out once,
    # not once a reading layer
    pools = {} if slots is None else {
        name: cache[name] for name, (pool, _) in cache_pools(cfg).items()
        if pool.view}
    memory = jnp.zeros(x.shape[:2] + (cfg.d_inner,), x.dtype) \
        if "gmu" in kinds else None
    with jax.named_scope("decode_layers"):
        cache = dict(cache, **{name: _slot_rows(pool, slots)
                               for name, pool in pools.items()})
        carry, gates = (x, cache, memory), None
        if passes > 1:
            def one_pass(t, state):
                (x, cache, memory), gates = state
                with jax.named_scope("loop_pass"):
                    x, cache, memory = jamba.scan_layers(
                        kinds, layers(pos, valid, t=t), (x, cache, memory))
                    with jax.named_scope("loop_norm"):
                        x = _norm(cfg, x, params, "final_norm")
                    if gates is not None:
                        with jax.named_scope("exit_gate"):
                            gates = gates.at[t].set(
                                fam.module.exit_gate(params, x))
                return (x, cache, memory), gates

            if exits:
                gates = jnp.zeros((passes,) + x.shape[:2], jnp.float32)
            carry, gates = jax.lax.fori_loop(0, passes, one_pass,
                                             (carry, gates))
        elif last is None:
            carry = jamba.scan_layers(kinds, layers(pos, valid), carry)
        else:
            cut = cfg.tail_layer
            before = collections.Counter(kinds[:cut + 1])
            carry = jamba.scan_layers(kinds[:cut], layers(pos, valid), carry)
            with jax.named_scope("cross_decoder"):
                carry = layers(pos, valid, last)(
                    kinds[cut], before[kinds[cut]] - 1, carry)
                carry = jamba.scan_layers(
                    kinds[cut + 1:], layers(pos + last, None), carry,
                    start=before)
        x, cache, _ = carry
        for name, pool in pools.items():
            cache[name] = _put_slot_rows(pool, cache[name], slots)
    return x, cache, gates


def merges(cfg, mesh=None, attn_impl="chunked"):
    """Whether a prefill program's rows can ride in the decode step of
    this stack (`decode_forward`'s `rows`): every layer caches K and V
    and nothing else (`attention`), so that a row's tokens need nothing
    of each other but their K and V in the pool; one chip; attention in
    the chunk loop. A recurrent layer (a scan or a chunk form over a
    row's positions, and a state to hold), a ring, a tail layer or a
    gated memory keeps the two programs."""
    return (mesh is None and attn_impl == "chunked"
            and set(layer_kinds(cfg)) == {"attention"})


def decode_forward(params, tokens, cache, pos, cfg, mesh=None,
                   attn_impl="dense", valid=None, slots=None, last=None,
                   rows=None, exits=False):
    """Forward over T new tokens at absolute position `pos` (a traced
    scalar, or a traced [B] vector when every batch row decodes at its
    own offset — the continuous-batching engine), reading and extending
    the cache. Works for every family of `FAMILIES`.

    tokens: [B, T] (T static: the prompt length for prefill, 1 per decode
    step). valid: None (every position is real) or a [B, T] mask whose
    true positions lead each row: a recurrent state (inference/cache.py,
    `recurrent_pools`) passes through the positions that are not valid.
    K and V need no mask (what is written there is overwritten before it
    is seen).
    slots: None (row b of the batch is row b of the cache) or a traced
    [B] vector of DISTINCT rows of a cache that holds more: row b of the
    batch reads and extends row slots[b] in place and no other row is
    touched (the slot engine's prefill program; `pos` is then a vector).
    last: None, or for a model whose config marks a `tail_layer` a [B]
    vector: only position last[b] of row b is read, so the layers from
    the tail layer's attention on, and the head, run for that position
    alone (the cache is extended by all T all the same).
    rows: None, or for a stack that `merges`, beside a decode step of the
    whole pool (tokens [B, 1], `pos` [B], no `slots`), a prefill
    program's rows to take along: (tokens [R, W], slots [R] distinct,
    start [R], last [R]). Row r extends row slots[r] of the cache by its
    W tokens from position start[r], as a program of its own with
    `slots` would, in the same pass over the weights (`_merged_layer`),
    and the head runs for position last[r] of it alone.
    exits: for a config whose stack is run several times with an exit
    gate after each pass (`passes`), also return the CDF of the exit
    distribution after every pass, [passes, B, T] float32 (with `rows`
    [passes, B + R * W, 1]: the lanes, then the rows' tokens); it ends at
    1, and with the threshold of 1 that such a config must state it
    decides nothing: every token runs every pass.
    Returns (logits [B, T, vocab] fp32, or [B, 1, vocab] with `last`, or
    [B + R, 1, vocab] with `rows`: the lanes', then the rows'; updated
    cache), and with `exits` the CDF after them."""
    dtype = llama.param_dtype(cfg)
    x = params["embed"][tokens].astype(dtype)
    if rows is not None:
        row_tokens, row_slots, row_start, row_last = rows
        x = jnp.concatenate(
            [x, params["embed"][row_tokens.reshape(-1, 1)].astype(dtype)])
        rows = (row_slots, row_start, row_tokens.shape[1])
    x, cache, gates = _layers(cfg, params, x, cache, pos, valid, mesh,
                              attn_impl, slots, last, rows, exits)
    if rows is not None:   # the lanes, and each row's one position
        B = tokens.shape[0]
        x = jnp.concatenate([x[:B], _at(
            x[B:].reshape(row_tokens.shape + x.shape[2:]), row_last)])
    if stack_passes(cfg) == 1:   # else the last pass's norm has closed it
        x = _norm(cfg, x, params, "final_norm")
        if exits:
            gates = family(cfg).module.exit_gate(params, x)[None]
    if "lm_head" in params:
        logits = jnp.einsum("btd,dv->btv", x, params["lm_head"],
                            preferred_element_type=jnp.float32)
    else:   # a head tied to the embedding
        logits = jnp.einsum("btd,vd->btv", x, params["embed"],
                            preferred_element_type=jnp.float32)
    if exits:
        return logits, cache, family(cfg).module.exit_cdf(gates)
    return logits, cache


def _sample(logits, temperature, rng, top_k=None, top_p=None):
    """logits: [B, vocab] fp32 → [B] int32.

    top_k keeps the k highest-logit tokens; top_p keeps the smallest
    nucleus whose probability mass reaches p (the highest-probability
    token always survives). Both compose (top_k filters first)."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k is not None and top_k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, NEG_INF, logits)
    if top_p is not None and top_p < 1.0:
        order = jnp.argsort(-logits, axis=-1)
        sorted_logits = jnp.take_along_axis(logits, order, axis=-1)
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        # EXCLUSIVE cumulative mass: a token is kept while the mass
        # before it is < p, so the top token always survives
        before = jnp.cumsum(probs, axis=-1) - probs
        drop_sorted = before >= top_p
        drop = jnp.zeros_like(drop_sorted).at[
            jnp.arange(logits.shape[0])[:, None], order].set(drop_sorted)
        logits = jnp.where(drop, NEG_INF, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def generate(params, prompt_tokens, cfg, max_new_tokens, temperature=0.0,
             rng=None, eos_id=None, max_seq_len=None, mesh=None,
             attn_impl="auto", top_k=None, top_p=None, prompt_len=None):
    """Generate max_new_tokens continuations of prompt_tokens [B, P].

    Pure jax (jit-friendly; max_new_tokens/temperature/eos_id/top_k/
    top_p/attn_impl must be static under jit). Returns
    [B, P + max_new_tokens] int32; once a sequence emits eos_id its tail
    is padded with eos_id.

    attn_impl: 'dense' (whole-cache masked attention), 'chunked'
    (flash-decode: online softmax over only the filled prefix — the
    long-context serving path), or 'auto', what `pool_read` answers for
    the cache's depth. This selector is the reference's: generate() is
    what both engines are tested against and 'dense' is the tests'
    oracle; no server takes it (an engine reads as `pool_read` says of
    its shapes).

    prompt_len: None when prompt_tokens is exactly the prompt. A TRACED
    scalar when prompt_tokens is right-PADDED to a longer static shape
    (the pad-to-bucket serving path): prefill runs over the padded
    length, the first token samples from the logits at prompt_len - 1,
    and decode starts writing at prompt_len — causal masking keeps the
    pad positions invisible until they are overwritten (and a recurrent
    state is held over them, decode_forward's `valid`), so the output is
    token-identical to the unpadded call. Positions [prompt_len, P) of
    the returned array still hold the pad ids (callers slice them out).
    """
    if rng is None:
        rng = jax.random.PRNGKey(0)
    B, P = prompt_tokens.shape
    total = P + max_new_tokens
    if max_seq_len is not None and max_seq_len < total:
        # dynamic_update_slice clamps out-of-range writes, which would
        # silently overwrite live cache slots instead of failing
        raise ValueError(
            "max_seq_len=%d < prompt_len (%d) + max_new_tokens (%d); "
            "the KV cache cannot hold the generation" %
            (max_seq_len, P, max_new_tokens))
    cache = init_kv_cache(cfg, B, max_seq_len or total)
    if "k" not in cache and total > cfg.max_seq_len:
        # nothing is cached by position: rope's table is all that ends
        raise ValueError(
            "prompt_len (%d) + max_new_tokens (%d) passes the config's "
            "max_seq_len (%d), where rope's table ends"
            % (P, max_new_tokens, cfg.max_seq_len))
    if attn_impl not in ("auto", "dense", "chunked"):
        # a typo'd impl must not silently select dense
        raise ValueError("attn_impl must be 'auto', 'dense' or "
                         "'chunked', got %r" % (attn_impl,))
    if attn_impl == "auto":
        attn_impl = pool_read(max_seq_len or total)

    valid = None if prompt_len is None else jnp.broadcast_to(
        jnp.arange(P) < prompt_len, (B, P))
    logits, cache = decode_forward(params, prompt_tokens, cache, 0, cfg,
                                   mesh=mesh, attn_impl=attn_impl,
                                   valid=valid)
    if prompt_len is None:
        last = logits[:, -1]
        start_pos = jnp.int32(P)
    else:
        start_pos = jnp.asarray(prompt_len, jnp.int32)
        last = jax.lax.dynamic_index_in_dim(logits, start_pos - 1, axis=1,
                                            keepdims=False)
    rng, step_rng = jax.random.split(rng)
    tok = _sample(last, temperature, step_rng, top_k, top_p)
    done = (tok == eos_id) if eos_id is not None else None

    def step(carry, step_rng):
        cache, tok, pos, done = carry
        logits, cache = decode_forward(params, tok[:, None], cache, pos,
                                       cfg, mesh=mesh, attn_impl=attn_impl)
        nxt = _sample(logits[:, 0], temperature, step_rng, top_k, top_p)
        if done is not None:
            nxt = jnp.where(done, eos_id, nxt)
            done = done | (nxt == eos_id)
        return (cache, nxt, pos + 1, done), nxt

    if max_new_tokens > 1:
        (cache, _, _, _), rest = jax.lax.scan(
            step, (cache, tok, start_pos, done),
            jax.random.split(rng, max_new_tokens - 1),
        )
        new_tokens = jnp.concatenate([tok[:, None], rest.T], axis=1)
    else:
        new_tokens = tok[:, None]
    return jnp.concatenate([prompt_tokens.astype(jnp.int32), new_tokens],
                           axis=1)


def bucket_length(n, minimum=16, maximum=None):
    """The smallest power-of-two >= n, floored at `minimum` — the shared
    prompt-length bucketing policy of make_generator and the serving
    engine, so both compile once per bucket instead of once per distinct
    prompt length. `maximum` (e.g. the KV-cache depth) caps the bucket;
    n must still fit."""
    if n < 0:
        raise ValueError("length must be >= 0, got %d" % n)
    b = max(1, int(minimum))
    while b < n:
        b *= 2
    if maximum is not None:
        b = min(b, int(maximum))
        if b < n:
            raise ValueError(
                "prompt length %d exceeds the bucket cap %d" % (n, maximum))
    return b


def pad_to_bucket(tokens, bucket=None, pad_id=0, minimum=16):
    """Right-pad [B, P] prompt tokens to `bucket` (default: the
    power-of-two bucket of P). Returns (padded [B, bucket], P)."""
    tokens = jnp.asarray(tokens)
    B, P = tokens.shape
    if bucket is None:
        bucket = bucket_length(P, minimum=minimum)
    if bucket < P:
        raise ValueError("bucket %d < prompt length %d" % (bucket, P))
    if bucket == P:
        return tokens, P
    pad = jnp.full((B, bucket - P), pad_id, tokens.dtype)
    return jnp.concatenate([tokens, pad], axis=1), P


def make_generator(cfg, max_new_tokens, temperature=0.0, eos_id=None,
                   max_seq_len=None, attn_impl="auto", top_k=None,
                   top_p=None, pad_id=0, min_bucket=16):
    """A jitted (params, prompt_tokens, rng) -> tokens generator with the
    static knobs baked in — compile once per prompt-length BUCKET, serve
    many.

    Prompts are right-padded to power-of-two buckets (bucket_length, >=
    min_bucket) and the true length rides along as a traced scalar, so
    serving traffic with arbitrary prompt lengths triggers one compile
    per (batch, bucket) instead of the silent recompile-per-length the
    naive jit had. Outputs are token-identical to generate() on the
    unpadded prompt. `gen.cache_size()` exposes the underlying jit cache
    entry count (== compiles) for tests and capacity planning."""

    @functools.partial(jax.jit, static_argnames=())
    def run(params, padded_prompt, prompt_len, rng):
        return generate(params, padded_prompt, cfg, max_new_tokens,
                        temperature=temperature, rng=rng, eos_id=eos_id,
                        max_seq_len=max_seq_len, attn_impl=attn_impl,
                        top_k=top_k, top_p=top_p, prompt_len=prompt_len)

    def gen(params, prompt_tokens, rng):
        prompt_tokens = jnp.asarray(prompt_tokens)
        B, P = prompt_tokens.shape
        cap = max_seq_len - max_new_tokens if max_seq_len else None
        bucket = bucket_length(P, minimum=min_bucket, maximum=cap)
        padded, _ = pad_to_bucket(prompt_tokens, bucket, pad_id=pad_id)
        out = run(params, padded, jnp.int32(P), rng)
        if bucket == P:
            return out
        # drop the pad gap: [prompt | pad | new] -> [prompt | new]
        return jnp.concatenate([out[:, :P], out[:, bucket:]], axis=1)

    gen.cache_size = run._cache_size
    return gen
