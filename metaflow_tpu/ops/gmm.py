"""Grouped matrix multiply: the dropless-MoE kernel (megablocks pattern).

`y[i] = x[i] @ w[g(i)]` where rows of x are grouped (sorted + padded so
every `block_s`-row tile belongs to exactly ONE group). The pallas TPU
kernel streams row tiles through the MXU with the group's weight tile
selected per grid step via a scalar-prefetched tile→group table — no
`[groups, tokens]` one-hot, no capacity drops: compute scales with the
actual token count (plus ≤ groups·block_s rows of zero padding).

Backward: dx is the same kernel with transposed weights; dw accumulates
per-tile outer products into the group's weight-grad block, exploiting
the sorted layout (tiles of one group are consecutive, so the output
block is revisited across consecutive grid steps — the pallas TPU
accumulation idiom).

The reference delegates MoE entirely to user frameworks (SURVEY.md §5.7);
this is this repo's scalable-dispatch fast path alongside the
capacity-bucketed one in ops/moe.py.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import device, knobs

# default kernel tiles; env-overridable (TPUFLOW_* layering) so the
# on-chip MFU sweep can tune MXU block sizes without code edits —
# BLOCK_S is also the padding quantum of the grouped layout, so a run
# must use ONE consistent value end to end
BLOCK_S = knobs.get_int("TPUFLOW_GMM_BLOCK_S")
BLOCK_F = knobs.get_int("TPUFLOW_GMM_BLOCK_F")
BLOCK_D = knobs.get_int("TPUFLOW_GMM_BLOCK_D")


def _default_interpret():
    """Compiled on a TPU; interpreted only in a CPU-pinned process (any
    other backend is device.platform()'s error)."""
    return not device.on_tpu()


# ---------------------------------------------------------------------------
# grouped layout: sort slots by group, pad each group to a BLOCK_S multiple
# ---------------------------------------------------------------------------


def make_group_layout(group_ids, num_groups, block_s=BLOCK_S,
                      row_valid=None):
    """Static-shape grouped layout for `gmm`.

    group_ids: [n] int32 — the group of each row.
    row_valid: optional [n] int32/bool — rows marked 0 are PADDING the
      caller was forced to carry at static shape (e.g. gmm_ep's
      unwritten all-to-all buffer slots). They still get layout
      positions (AFTER their group's valid rows) but never mark a tile
      active, so the kernels skip their compute; their gathered outputs
      come from zeroed tiles. Without this, padding rows masquerade as
      real rows of their group and re-inflate the skipped work.
    Returns dict with:
      dest        [n]        destination row of each input row
      tile_group  [n_tiles]  group id of every block_s-row tile
      tile_active [n_tiles]  1 iff the tile holds >= 1 (valid) row
      padded_len             static total rows (multiple of block_s)

    Every group's rows land contiguously at a block_s-aligned offset, so
    each tile belongs to exactly one group; rows past a group's count are
    zero padding (they multiply into zeros and accumulate nothing).
    """
    n = group_ids.shape[0]
    counts = jnp.bincount(group_ids, length=num_groups)
    if row_valid is None:
        valid = jnp.ones((n,), jnp.int32)
        counts_valid = counts
    else:
        valid = row_valid.astype(jnp.int32)
        counts_valid = jnp.bincount(group_ids, weights=valid,
                                    length=num_groups).astype(jnp.int32)
    padded = ((counts + block_s - 1) // block_s) * block_s
    ends = jnp.cumsum(padded)
    offsets = ends - padded
    # rank of each row within its group via a stable argsort — O(n log
    # n), no [n, groups] one-hot materialized. Sort key puts each
    # group's VALID rows first (arrival-stable within each class) so
    # valid rows form a prefix and tile_active is a per-group prefix
    # predicate
    order = jnp.argsort(group_ids * 2 + (1 - valid), stable=True)
    excl = jnp.cumsum(counts) - counts  # rows in earlier groups
    rank = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32)
        - excl[group_ids[order]].astype(jnp.int32)
    )
    dest = offsets[group_ids] + rank

    # static upper bound on total padded rows
    padded_len = -(-n // block_s) * block_s + num_groups * block_s
    n_tiles = padded_len // block_s
    tile_start = jnp.arange(n_tiles, dtype=jnp.int32) * block_s
    # tile t belongs to the first group whose padded range ends past it;
    # tiles beyond every group clamp to the last group — they hold only
    # zero rows, so the extra matmuls produce zeros
    tile_group = jnp.minimum(
        jnp.searchsorted(ends, tile_start, side="right"),
        num_groups - 1,
    ).astype(jnp.int32)
    # a tile is ACTIVE iff it holds at least one VALID row: valid rows
    # of group g occupy the prefix [offset_g, offset_g+counts_valid_g).
    # The kernels skip the MXU work of inactive tiles — this keeps the
    # padded static layout's compute proportional to the ACTUAL row
    # count (the dropless point; for gmm_ep's exact mode the worst-case
    # a2a buffers are mostly invalid rows, so skipping approaches a
    # P-fold FLOPs saving on a balanced P-way expert mesh)
    tile_active = (
        tile_start < (offsets + counts_valid)[tile_group]
    ).astype(jnp.int32)
    return {"dest": dest, "tile_group": tile_group,
            "tile_active": tile_active, "padded_len": padded_len}


def scatter_rows(rows, layout):
    """[n, D] → padded [padded_len, D] grouped layout (zeros elsewhere)."""
    out = jnp.zeros((layout["padded_len"], rows.shape[1]), rows.dtype)
    return out.at[layout["dest"]].set(rows)


def gather_rows(padded, layout):
    return padded[layout["dest"]]


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _gmm_fwd_kernel(tg_ref, ta_ref, x_ref, w_ref, y_ref):
    i = pl.program_id(0)

    # inactive tiles hold only zero padding: skip their MXU work (the
    # output block must still be WRITTEN — on hardware it is otherwise
    # uninitialized memory, not zeros). != 0 / == 0 are TOTAL: a block
    # left unwritten by non-exhaustive branches would be garbage HBM
    @pl.when(ta_ref[i] != 0)
    def _():
        y_ref[...] = jnp.dot(
            x_ref[...], w_ref[0],
            preferred_element_type=jnp.float32,
        ).astype(y_ref.dtype)

    @pl.when(ta_ref[i] == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)


def _gmm_call(x, w, tile_group, tile_active, block_s, block_f, interpret):
    S, D = x.shape
    G, Dw, F = w.shape
    assert D == Dw, (D, Dw)
    block_f = min(block_f, F)
    if S % block_s or F % block_f:
        raise ValueError(
            "gmm needs S %% block_s == 0 and F %% block_f == 0 "
            "(S=%d bs=%d, F=%d bf=%d)" % (S, block_s, F, block_f))
    grid = (S // block_s, F // block_f)
    return pl.pallas_call(
        _gmm_fwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_s, D), lambda i, j, tg, ta: (i, 0)),
                pl.BlockSpec((1, D, block_f),
                             lambda i, j, tg, ta: (tg[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((block_s, block_f),
                                   lambda i, j, tg, ta: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((S, F), x.dtype),
        interpret=interpret,
    )(tile_group, tile_active, x, w)


def _gmm_dw_kernel(tg_ref, ta_ref, x_ref, dy_ref, dw_ref):
    i = pl.program_id(2)
    first_of_group = jnp.logical_or(
        i == 0, tg_ref[i] != tg_ref[jnp.maximum(i - 1, 0)]
    )
    active = ta_ref[i] != 0
    # a group's real rows are a PREFIX of its tiles, so its first tile
    # is active whenever the group has any rows (empty groups own no
    # tiles and are masked by `visited` downstream): initialize on the
    # first (necessarily active) tile, accumulate on later active ones,
    # and skip the MXU entirely for padding tiles — the revisited block
    # persists untouched across skipped grid steps

    @pl.when(active)
    def _():
        tile = jnp.dot(
            x_ref[...].T, dy_ref[...], preferred_element_type=jnp.float32
        ).astype(dw_ref.dtype)

        @pl.when(first_of_group)
        def _():
            dw_ref[0] = tile

        @pl.when(jnp.logical_not(first_of_group))
        def _():
            dw_ref[0] = dw_ref[0] + tile


def _gmm_dw_call(x, dy, tile_group, tile_active, num_groups, block_s,
                 block_d, block_f, interpret):
    S, D = x.shape
    _, F = dy.shape
    block_d = min(block_d, D)
    block_f = min(block_f, F)
    if D % block_d or F % block_f:
        raise ValueError(
            "gmm dw needs D %% block_d == 0 and F %% block_f == 0 "
            "(D=%d bd=%d, F=%d bf=%d)" % (D, block_d, F, block_f))
    # i (row tiles) INNERMOST: for a fixed (d, f) the output block
    # dw[tg[i], d, f] is revisited across the consecutive i of one group
    grid = (D // block_d, F // block_f, S // block_s)
    return pl.pallas_call(
        _gmm_dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_s, block_d),
                             lambda d, f, i, tg, ta: (i, d)),
                pl.BlockSpec((block_s, block_f),
                             lambda d, f, i, tg, ta: (i, f)),
            ],
            out_specs=pl.BlockSpec((1, block_d, block_f),
                                   lambda d, f, i, tg, ta: (tg[i], d, f)),
        ),
        out_shape=jax.ShapeDtypeStruct((num_groups, D, F), jnp.float32),
        interpret=interpret,
    )(tile_group, tile_active, x, dy)


# ---------------------------------------------------------------------------
# public op with custom vjp
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _gmm_prim(x, w, tile_group, tile_active, block_s, block_f, interpret):
    """custom_vjp primal — all args resolved/positional (custom_vjp
    cannot bind keyword-only params); the public gmm() wrapper below is
    the only caller."""
    return _gmm_call(x, w, tile_group, tile_active, block_s, block_f,
                     interpret)


def gmm(x, w, tile_group, *, tile_active=None, block_s=BLOCK_S,
        block_f=BLOCK_F, interpret=None):
    """y[i·bs:(i+1)·bs] = x[i·bs:(i+1)·bs] @ w[tile_group[i]].

    x: [S, D] grouped+padded rows (S % block_s == 0 — make_group_layout);
    w: [G, D, F]; tile_group: [S // block_s] int32;
    tile_active: [S // block_s] int32 (make_group_layout's
    `tile_active`) — tiles marked 0 hold only zero padding and SKIP
    their MXU work in forward, dx and dw (compute stays proportional to
    real rows, the dropless point). None = treat every tile as active.

    tile_active/block_s/block_f/interpret are KEYWORD-ONLY: tile_active
    was inserted before block_s at one point, so a stale positional
    caller `gmm(x, w, tg, 64)` meaning block_s=64 would silently pass 64
    as the tile mask — keyword-only turns that into an immediate
    TypeError instead.
    """
    if tile_active is None:
        tile_active = jnp.ones_like(tile_group)
    if interpret is None:
        interpret = _default_interpret()
    _check_bwd_blocks(w, block_f)
    return _gmm_prim(x, w, tile_group, tile_active, block_s, block_f,
                     interpret)


def _check_bwd_blocks(w, block_f):
    """The backward pass tiles D as a feature dim (dx) and as a reduced
    dim (dw); misconfigured shapes must fail at forward time, not when
    gradients are first taken."""
    D = w.shape[1]
    if D % min(block_f, D):
        raise ValueError(
            "gmm needs D %% min(block_f, D) == 0 (D=%d, block_f=%d): the "
            "dx backward kernel tiles D with that block" % (D, block_f))
    if D % min(BLOCK_D, D):
        raise ValueError(
            "gmm needs D %% min(%d, D) == 0 (D=%d): the dw backward "
            "kernel tiles D with that block" % (BLOCK_D, D))


def _gmm_fwd(x, w, tile_group, tile_active, block_s, block_f, interpret):
    if tile_active is None:
        tile_active = jnp.ones_like(tile_group)
    if interpret is None:
        interpret = _default_interpret()
    # under jax.grad custom_vjp routes HERE, not through the primal — the
    # misconfigured-D fail-fast must fire in the differentiated case too
    _check_bwd_blocks(w, block_f)
    y = _gmm_call(x, w, tile_group, tile_active, block_s, block_f,
                  interpret)
    return y, (x, w, tile_group, tile_active)


def _gmm_bwd(block_s, block_f, interpret, residuals, dy):
    x, w, tile_group, tile_active = residuals
    if interpret is None:
        interpret = _default_interpret()
    # dx: the same grouped matmul against w^T
    dx = _gmm_call(
        dy, jnp.swapaxes(w, 1, 2), tile_group, tile_active, block_s,
        min(block_f, w.shape[1]), interpret,
    ).astype(x.dtype)
    dw = _gmm_dw_call(
        x, dy, tile_group, tile_active, w.shape[0], block_s,
        min(BLOCK_D, w.shape[1]), block_f, interpret,
    )
    # a group whose tiles were all SKIPPED (zero real rows — including
    # the trailing clamped tiles assigned to the last group) never
    # writes its dw block — on real TPU that block is uninitialized
    # memory, not zeros (interpret mode hides this). Mask to groups
    # with at least one ACTIVE tile. where, not multiply: the unvisited
    # block may be NaN-filled (interpret) or arbitrary bits (hardware)
    visited = jnp.zeros((w.shape[0],), jnp.int32).at[tile_group].max(
        tile_active)
    dw = jnp.where(visited.astype(bool)[:, None, None], dw, 0) \
        .astype(w.dtype)
    return dx, dw, None, None


_gmm_prim.defvjp(_gmm_fwd, _gmm_bwd)


def gmm_reference(x, w, tile_group, block_s=BLOCK_S):
    """XLA oracle: one-hot tile→group selection (tests only)."""
    S, D = x.shape
    tiles = x.reshape(S // block_s, block_s, D)
    w_per_tile = w[tile_group]  # [n_tiles, D, F]
    y = jnp.einsum("tbd,tdf->tbf", tiles, w_per_tile,
                   preferred_element_type=jnp.float32)
    return y.reshape(S, w.shape[-1]).astype(x.dtype)
