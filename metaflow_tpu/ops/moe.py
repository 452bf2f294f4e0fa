"""Mixture-of-Experts: top-k router + capacity-bucketed sparse dispatch.

Expert-parallel path (SURVEY.md §5.7, Mixtral target): the reference
delegates MoE entirely to user frameworks (its training substrate is the
rank/world-size env shim, /root/reference/metaflow/plugins/frameworks/
pytorch.py:11-46), so an efficient TPU dispatch is this repo's job.

Two dispatch strategies, numerically equivalent modulo capacity drops:

``sparse`` (default) — capacity-bucketed dispatch, the GShard/Switch
    pattern: top-k → position-in-expert (cumsum over a static slot order)
    → scatter into static ``[experts, capacity, embed]`` buffers → local
    expert matmuls → gather-combine. Compute and memory scale with
    ``k × tokens × capacity_factor``, NOT ``num_experts × tokens``.
    Sharded on the 'expert' mesh axis the scatter/gather become the
    all-to-all boundary (XLA inserts it; we pin the buffer sharding so
    the expert matmuls stay local).

``dense`` — reference oracle: every expert sees every token via one-hot
    einsums. O(num_experts × tokens) FLOPs; kept for equivalence tests
    and tiny-scale debugging only.

``gmm`` — DROPLESS dispatch via the pallas grouped-matmul kernel
    (ops/gmm.py, megablocks pattern): slots sort into expert-contiguous
    tiles and each tile multiplies its expert's weights directly on the
    MXU. Exact top-k semantics (no capacity, no drops) at
    O(k × tokens + experts·block) FLOPs. Single-shard experts (dense/
    tensor-parallel meshes).

``gmm_ep`` — dropless dispatch COMPOSED with expert parallelism
    (shard_map over the 'expert' mesh axis): each expert-axis member
    routes a 1/P token slice, all-to-alls slots to the shard owning
    their expert, runs the LOCAL grouped matmul over its n/P experts,
    and all-to-alls results back. Static shapes force a per-(src,dst)
    send budget: ``ep_buffer_factor=None`` (default) sizes it at the
    worst case — bit-equivalent to the dense oracle, truly dropless,
    but each shard's gmm is padded to the full slot count (weights and
    grads still shard P ways); a finite factor sizes buffers at
    ``factor·slots/P`` for real P-fold FLOPs scaling with
    shard-overflow drops only under routing imbalance (the aux loss
    pushes toward balance).

Scope names (jax.named_scope, metadata only): routing is `moe_router`,
and `sparse`, `dense` and `gmm` mark their gather/bucket step
`moe_dispatch`, the three expert matmuls `moe_experts` and the weighted
scatter back `moe_combine`, so a device trace splits the layer the same
way whichever of them ran (benchmark/span_readings.py). `gmm_ep` marks
only its router; its collectives are for the PR that measures them.

Capacity semantics are identical in the sparse and dense paths: an
expert accepts its first ``capacity`` tokens in token order; the rest
are dropped (their combine weight becomes 0 and the residual stream
passes through). The gmm path has no capacity — it is exactly dropless.
With ``exact`` the sparse path is dropless too, at a capacity's cost:
the step counts its own pairs an expert, and only a step in which some
expert is sent more than its capacity runs again with buffers as deep
as the step has tokens (`lax.cond`: one branch executes). With many
small experts and a decode step of a hundred tokens that is the
difference between 128 experts x 128 rows and 128 x 22.

Routers (`route`): ``softmax_top_k`` (Mixtral: the top-k logits,
softmaxed over those k) and ``sigmoid_bias`` (sigmoid scores, the top-k
of scores plus a selection bias, the chosen scores normalised over the k
and scaled). A caller whose router reads something other than what the
experts read (a latent expert layer) routes itself and hands `moe_ffn`
the ``routing``.

A share of the experts (``held``): the router keeps its published width
and picks over all of it, the leaves hold experts `first .. first +
count - 1` only, and the picks that fall on other experts add nothing,
here as on the chip that would hold this share: no buffer, row or
operation stands for an absent expert. Experts are three matrices
(SwiGLU: `act(x w_gate) * (x w_up)`, then `w_down`) or, with no
`w_gate`, two (`act(x w_up)`, then `w_down`).
"""

import math

import jax
import jax.numpy as jnp


@jax.named_scope("moe_router")
def top_k_router(logits, num_experts, k, dtype=jnp.float32):
    """logits: [tokens, experts] → (weights [tokens, k], idx [tokens, k]).

    Softmax over the selected k (Mixtral convention)."""
    gate_logits, idx = jax.lax.top_k(logits, k)
    weights = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    return weights.astype(dtype), idx


def relu2(x):
    """relu(x) ** 2, the activation of a two-matrix expert."""
    return jnp.square(jax.nn.relu(x))


@jax.named_scope("moe_router")
def route(x, router_w, k, form="softmax_top_k", bias=None, scale=1.0,
          dtype=None):
    """x [.., E] through the router [E, experts] -> (weights [.., k] in
    `dtype` (x's), idx [.., k]); logits and scores in float32.

    softmax_top_k: the top-k logits, softmaxed over those k.
    sigmoid_bias: s = sigmoid(logits); the k largest of s + bias are
    chosen (the bias moves the choice only); their s, normalised over
    the k (+ 1e-20) and times `scale`, are the weights."""
    logits = jnp.einsum("...e,en->...n", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    dtype = dtype or x.dtype
    if form == "softmax_top_k":
        return top_k_router(logits, router_w.shape[1], k, dtype=dtype)
    if form != "sigmoid_bias":
        raise ValueError("router form must be 'softmax_top_k' or "
                         "'sigmoid_bias', got %r" % (form,))
    scores = jax.nn.sigmoid(logits)
    picked = scores if bias is None else scores + bias.astype(jnp.float32)
    _, idx = jax.lax.top_k(picked, k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = scale * (w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20))
    return w.astype(dtype), idx


def expert_capacity(num_tokens, num_experts, k, capacity_factor):
    """Static per-expert token budget.

    capacity_factor=None means lossless: capacity = num_tokens (the worst
    case — every token routes a slot to the same expert), which makes the
    sparse path bit-equivalent to dense dispatch without capacity."""
    if capacity_factor is None:
        return num_tokens
    cap = int(math.ceil(capacity_factor * num_tokens * k / num_experts))
    return max(1, min(cap, num_tokens))


def _active_mesh():
    """The mesh from an enclosing `with mesh:` block, if any."""
    from jax._src.mesh import thread_resources

    mesh = thread_resources.env.physical_mesh
    return None if mesh.empty else mesh


def _constrain_expert_axis(x, mesh):
    """Pin buffer axis 0 to the 'expert' mesh axis so the scatter/gather is
    the single all-to-all boundary and expert matmuls stay chip-local."""
    if mesh is None or "expert" not in mesh.axis_names:
        return x
    from jax.sharding import NamedSharding, PartitionSpec

    spec = PartitionSpec("expert", *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def moe_ffn(x, router_w, w_gate, w_up, w_down, num_experts_per_tok=2,
            capacity_factor=None, activation=jax.nn.silu, dispatch="sparse",
            mesh=None, ep_buffer_factor=None, routing=None, held=None,
            valid=None, exact=False):
    """Token-choice MoE feed-forward.

    x:        [B, S, E]
    router_w: [E, num_experts]
    w_gate/w_up: [num_experts, E, F]; w_down: [num_experts, F, E];
              w_gate None: experts of two matrices. Each may be a
              function that gives the array ('sparse' and 'dense'): a
              caller whose leaves are one layer of a stack hands the
              cutting in, so that under `exact` each branch of the
              `cond` cuts the layer out where it multiplies by it. An
              array cut outside is the `cond`'s operand, and the
              compiler then copies the layer (1.4 GB for 128 experts of
              1024 x 2688) on the way in
    routing:  (weights [B, S, k], idx [B, S, k]) from `route`, where the
              caller has routed (router_w is then not read, and the
              auxiliary loss returned is 0)
    held:     None (the leaves hold every expert), or (experts routed
              over, first): the leaves hold experts first .. first +
              len(w_up) - 1 of that many, and a pick outside them
              adds nothing ('sparse' and 'dense')
    valid:    None or [B, S] bool: a token that is not valid (a lane
              that holds no decoding request, a row's padding) is sent
              to no expert, and its output is 0 ('sparse' and 'dense')
    exact:    'sparse' with a capacity_factor: no pair is dropped; a
              step that overflows some expert's capacity runs with
              lossless buffers instead
    mesh:     pass the device mesh explicitly so the sparse path can pin
              its expert buffers to the 'expert' axis even when the step
              is traced outside a `with mesh:` block; falls back to the
              ambient mesh context when omitted.
    ep_buffer_factor: 'gmm_ep' only — per-(src,dst) all-to-all budget as
              a multiple of the balanced share. None = exact worst case
              (dropless); ~1-2 trades shard-overflow drops under extreme
              imbalance for P-fold FLOPs scaling.

    Returns (out [B, S, E], aux_loss scalar).
    """
    B, S, E = x.shape
    num_experts = held[0] if held is not None else router_w.shape[1]
    k = num_experts_per_tok
    if dispatch not in ("sparse", "dense") and (
            routing is not None or held is not None or valid is not None
            or w_gate is None):
        raise ValueError(
            "dispatch=%r routes for itself over experts of three matrices "
            "that are all here; routing, held, valid and two-matrix "
            "experts take 'sparse' or 'dense'" % (dispatch,))

    if dispatch == "gmm_ep":
        # routing happens per token-slice INSIDE the shard_map; branch
        # before the full-batch router below
        if capacity_factor is not None:
            raise ValueError(
                "dispatch='gmm_ep' is dropless — capacity_factor must be "
                "None (bound memory with ep_buffer_factor instead)")
        active = mesh if mesh is not None else _active_mesh()
        if active is None or "expert" not in active.axis_names:
            raise ValueError(
                "dispatch='gmm_ep' needs a mesh with an 'expert' axis "
                "(use dispatch='gmm' for single-shard experts)")
        return _gmm_ep_dispatch_ffn(
            x, router_w, w_gate, w_up, w_down, num_experts, k, activation,
            active, ep_buffer_factor,
        )
    if ep_buffer_factor is not None:
        raise ValueError("ep_buffer_factor only applies to dispatch='gmm_ep'")
    tokens = x.reshape(B * S, E)

    if routing is not None:
        weights, idx = (a.reshape(B * S, k) for a in routing)
        one_hot, aux = None, jnp.zeros((), jnp.float32)
    else:
        with jax.named_scope("moe_router"):
            router_logits = jnp.einsum(
                "te,en->tn", tokens.astype(jnp.float32),
                router_w.astype(jnp.float32)
            )
            weights, idx = top_k_router(router_logits, num_experts, k,
                                        dtype=x.dtype)
            one_hot = jax.nn.one_hot(idx, num_experts, dtype=x.dtype)  # [t,k,n]
            aux = _load_balancing_loss(router_logits, one_hot)
    first = held[1] if held is not None else 0
    if valid is not None:
        valid = valid.reshape(B * S)

    if dispatch == "sparse":
        out = _sparse_dispatch_ffn(
            tokens, weights, idx, w_gate, w_up, w_down, num_experts, k,
            capacity_factor, activation,
            mesh if mesh is not None else _active_mesh(),
            first=first, valid=valid, exact=exact,
        )
    elif dispatch == "dense":
        out = _dense_dispatch_ffn(
            tokens, weights, idx, one_hot, w_gate, w_up, w_down, num_experts,
            k, capacity_factor, activation, first=first, valid=valid,
        )
    elif dispatch == "gmm":
        if capacity_factor is not None:
            raise ValueError(
                "dispatch='gmm' is dropless — capacity_factor must be None"
            )
        active = mesh if mesh is not None else _active_mesh()
        if active is not None and "expert" in active.axis_names:
            # silently all-gathering every expert's weights (and fp32
            # grads) onto every chip would defeat the expert axis the
            # user asked for — the capacity path is the EP story
            raise ValueError(
                "dispatch='gmm' runs experts single-shard; on an "
                "expert-parallel mesh use dispatch='gmm_ep' (dropless) "
                "or 'sparse' (capacity-bucketed)"
            )
        out = _gmm_dispatch_ffn(
            tokens, weights, idx, w_gate, w_up, w_down, num_experts, k,
            activation,
        )
    else:
        raise ValueError("dispatch must be 'sparse', 'dense', 'gmm' or "
                         "'gmm_ep', got %r" % (dispatch,))
    return out.reshape(B, S, E), aux


def _leaf(w):
    """An expert leaf: the array, or what the function gives."""
    return w() if callable(w) else w


def _experts(x_buf, w_gate, w_up, w_down, activation, dtype):
    """The experts' matrices over their buffers x_buf [n, rows, E]: three
    (act(x w_gate) * (x w_up), then w_down) or, with no w_gate, two."""
    product = lambda w: jnp.einsum("nce,nef->ncf", x_buf, _leaf(w),
                                   preferred_element_type=jnp.float32)
    if w_gate is None:
        hidden = activation(product(w_up))
    else:
        hidden = activation(product(w_gate)) * product(w_up)
    return jnp.einsum("ncf,nfe->nce", hidden.astype(dtype), _leaf(w_down),
                      preferred_element_type=jnp.float32)


def _n_experts(w_up):
    """How many experts the leaves hold, without cutting one out."""
    return (jax.eval_shape(w_up) if callable(w_up) else w_up).shape[0]


def _sparse_dispatch_ffn(tokens, weights, idx, w_gate, w_up, w_down,
                         num_experts, k, capacity_factor, activation, mesh,
                         first=0, valid=None, exact=False):
    """Capacity-bucketed dispatch: O(k·T·capacity_factor) expert FLOPs.

    Slot order is token-major (slot t·k+j precedes t'·k+j' iff t<t' or
    (t==t', j<j')); since top-k indices are distinct per token, each token
    holds at most one slot per expert, so per-expert arrival order equals
    token order — the same drop decisions as the dense oracle's
    token-axis cumsum.

    The leaves hold N = w_up.shape[0] experts, `first` .. first + N - 1
    of the `num_experts` routed over. A slot whose expert is not among
    them, or whose token is not `valid`, goes nowhere: it takes no place
    in any buffer and adds nothing."""
    T, E = tokens.shape
    N = _n_experts(w_up)
    C = expert_capacity(T, num_experts, k, capacity_factor)

    with jax.named_scope("moe_dispatch"):
        e_flat = idx.reshape(T * k)                  # expert id per slot
        w_flat = weights.reshape(T * k)              # combine weight per slot
        here = None
        if N != num_experts or valid is not None:
            e_flat = e_flat - first
            here = (e_flat >= 0) & (e_flat < N)
            if valid is not None:
                here = here & jnp.repeat(valid, k)
            e_flat = jnp.where(here, e_flat, N)      # N: no expert here
        slot_one_hot = jax.nn.one_hot(e_flat, N, dtype=jnp.int32)  # [T*k, N]
        # 0-based arrival position of each slot within its expert
        pos = jnp.cumsum(slot_one_hot, axis=0) - 1   # [T*k, N]
        at = e_flat if here is None else jnp.minimum(e_flat, N - 1)
        pos_flat = jnp.take_along_axis(pos, at[:, None], axis=1)[:, 0]

    def run(C):
        with jax.named_scope("moe_dispatch"):
            keep = pos_flat < C
            if here is not None:
                keep = keep & here
            # dropped slots scatter out of range; mode="drop" discards
            # them with static shapes (positions are unique per expert,
            # so add == set)
            safe_pos = jnp.where(keep, pos_flat, C)
            t_flat = jnp.arange(T * k) // k          # owning token per slot
            x_buf = jnp.zeros((N, C, E), tokens.dtype).at[
                e_flat, safe_pos].add(tokens[t_flat], mode="drop")
            x_buf = _constrain_expert_axis(x_buf, mesh)  # all-to-all in

        with jax.named_scope("moe_experts"):
            y_buf = _experts(x_buf, w_gate, w_up, w_down, activation,
                             tokens.dtype)
            y_buf = _constrain_expert_axis(y_buf.astype(tokens.dtype), mesh)

        # combine: gather each slot's expert output back (all-to-all
        # boundary out); out-of-range gathers clamp but are zeroed by the
        # keep mask
        with jax.named_scope("moe_combine"):
            y_slots = y_buf[e_flat, safe_pos]            # [T*k, E]
            y_slots = jnp.where(keep[:, None], y_slots, 0) * w_flat[:, None]
            return y_slots.reshape(T, k, E).sum(axis=1)

    if not exact or C >= T:
        return run(C)
    # the step's own counts: only an expert that is sent more than C
    # tokens makes the step pay for buffers as deep as its tokens
    fits = jnp.max(jnp.sum(slot_one_hot, axis=0)) <= C
    return jax.lax.cond(fits, lambda: run(C), lambda: run(T))


def _gmm_dispatch_ffn(tokens, weights, idx, w_gate, w_up, w_down,
                      num_experts, k, activation):
    """Dropless dispatch through the pallas grouped matmul: sort slots
    into expert-contiguous 128-row tiles, run the three expert matmuls as
    gmm, gather-combine. Exact top-k output (bit-comparable to the dense
    oracle without capacity)."""
    from .gmm import gather_rows, gmm, make_group_layout, scatter_rows

    T, E = tokens.shape
    with jax.named_scope("moe_dispatch"):
        e_flat = idx.reshape(T * k)
        w_flat = weights.reshape(T * k)
        t_flat = jnp.arange(T * k) // k

        layout = make_group_layout(e_flat, num_experts)
        x_pad = scatter_rows(tokens[t_flat], layout)
        tg, ta = layout["tile_group"], layout["tile_active"]
    with jax.named_scope("moe_experts"):
        gate = activation(gmm(x_pad, w_gate, tg, tile_active=ta))
        up = gmm(x_pad, w_up, tg, tile_active=ta)
        y_pad = gmm((gate * up).astype(tokens.dtype), w_down, tg,
                    tile_active=ta)
    with jax.named_scope("moe_combine"):
        y_slots = gather_rows(y_pad, layout) * w_flat[:, None]
        return y_slots.reshape(T, k, E).sum(axis=1)


def _gmm_ep_dispatch_ffn(x, router_w, w_gate, w_up, w_down, num_experts, k,
                         activation, mesh, ep_buffer_factor):
    """Dropless grouped-matmul dispatch composed with expert parallelism.

    shard_map over the WHOLE mesh: batch rides its usual ('data','fsdp')
    axes, expert weights live split on 'expert' (and their mlp dim on
    'tensor'). Per expert-axis member, over its static 1/P token slice:

      route → bucket slots by destination shard → all_to_all in →
      local gmm over this shard's n/P experts → psum partial mlp
      contractions over 'tensor' → all_to_all back → weighted combine →
      all_gather token slices.

    The per-(src,dst) buffer is the static-shape price of dropless EP on
    TPU (XLA cannot ship dynamic row counts): exact worst case when
    ep_buffer_factor is None, `ceil(factor·slots/P)` otherwise. The
    reference delegates all of MoE to user frameworks
    (/root/reference/metaflow/plugins/frameworks/pytorch.py:11-46); this
    composition is the repo's own per-chip-efficiency path for the
    Mixtral target.
    """
    import math as _math

    from jax.sharding import PartitionSpec as P

    from .attention import shard_map_novma
    from .gmm import BLOCK_S, gather_rows, gmm, make_group_layout, \
        scatter_rows

    axes = set(mesh.axis_names)
    ep = mesh.shape["expert"]
    if num_experts % ep:
        raise ValueError(
            "gmm_ep needs num_experts %% expert-axis size == 0 "
            "(experts=%d, expert axis=%d)" % (num_experts, ep))
    n_local = num_experts // ep
    batch_axes = tuple(a for a in ("data", "fsdp") if a in axes)
    tensor = "tensor" if "tensor" in axes else None
    token_axes = batch_axes + ("expert",)

    B, S, E = x.shape
    batch_shards = 1
    for a in batch_axes:
        batch_shards *= mesh.shape[a]
    if B % batch_shards:
        raise ValueError("gmm_ep: batch %d not divisible by batch shards %d"
                         % (B, batch_shards))
    T_block = (B // batch_shards) * S   # tokens per batch-shard block
    if T_block % ep:
        raise ValueError(
            "gmm_ep: per-shard token count %d not divisible by the "
            "expert axis (%d) — each member routes a 1/P token slice"
            % (T_block, ep))
    T_slice = T_block // ep
    slots = T_slice * k
    if ep_buffer_factor is None:
        c_send = slots                  # worst case: every slot, one dst
    else:
        c_send = min(slots, int(_math.ceil(ep_buffer_factor * slots / ep)))
        c_send = max(1, c_send)

    def per_member(xb, rw, wg, wu, wd):
        Bb, Sb, Eb = xb.shape
        tok_all = xb.reshape(Bb * Sb, Eb)
        p = jax.lax.axis_index("expert")
        tok = jax.lax.dynamic_slice_in_dim(tok_all, p * T_slice, T_slice, 0)

        logits = jnp.einsum("te,en->tn", tok.astype(jnp.float32),
                            rw.astype(jnp.float32))
        weights, idx = top_k_router(logits, num_experts, k, dtype=xb.dtype)
        sel = jax.nn.one_hot(idx, num_experts, dtype=xb.dtype)
        # aux: pmean the per-slice ingredients over every token-sharding
        # axis, THEN combine — sum(mean·mean) is not mean(sum·sum)
        probs = jax.nn.softmax(logits, axis=-1)
        fraction = jax.lax.pmean(jnp.mean(sel.sum(axis=1), axis=0),
                                 token_axes)
        prob_mean = jax.lax.pmean(jnp.mean(probs, axis=0), token_axes)
        aux = num_experts * jnp.sum(fraction * prob_mean)

        e_flat = idx.reshape(slots)
        w_flat = weights.reshape(slots)
        t_flat = jnp.arange(slots) // k
        dst = e_flat // n_local
        # arrival position of each slot within its destination block
        pos = jnp.cumsum(jax.nn.one_hot(dst, ep, dtype=jnp.int32),
                         axis=0) - 1
        pos_flat = jnp.take_along_axis(pos, dst[:, None], axis=1)[:, 0]
        keep = pos_flat < c_send        # exact mode: always true
        safe_pos = jnp.where(keep, pos_flat, c_send)

        send_x = jnp.zeros((ep, c_send, Eb), xb.dtype).at[
            dst, safe_pos].add(tok[t_flat], mode="drop")
        # local expert id AND a validity flag ride with each row:
        # unwritten buffer slots must not masquerade as expert-0 rows,
        # or the grouped layout would mark their tiles active and the
        # kernels would burn the full worst-case MXU work on padding
        send_le = jnp.zeros((ep, c_send), jnp.int32).at[dst, safe_pos].set(
            e_flat % n_local, mode="drop")
        send_ok = jnp.zeros((ep, c_send), jnp.int32).at[dst, safe_pos].set(
            1, mode="drop")

        # [P, C, ·] tiled all_to_all = (member, block) grid transpose:
        # recv[src] is what src addressed to this member
        recv_x = jax.lax.all_to_all(send_x, "expert", 0, 0, tiled=True)
        recv_le = jax.lax.all_to_all(send_le, "expert", 0, 0, tiled=True)
        recv_ok = jax.lax.all_to_all(send_ok, "expert", 0, 0, tiled=True)

        rows = recv_x.reshape(ep * c_send, Eb)
        layout = make_group_layout(recv_le.reshape(ep * c_send), n_local,
                                   block_s=BLOCK_S,
                                   row_valid=recv_ok.reshape(ep * c_send))
        x_pad = scatter_rows(rows, layout)
        tg, ta = layout["tile_group"], layout["tile_active"]
        gate = activation(gmm(x_pad, wg, tg, tile_active=ta))
        up = gmm(x_pad, wu, tg, tile_active=ta)
        y_pad = gmm((gate * up).astype(xb.dtype), wd, tg,
                    tile_active=ta)
        # invalid rows gathered from skipped tiles read zeros, exactly
        # what their (zero) data would have produced
        y_rows = gather_rows(y_pad, layout)
        if tensor:                      # w_down contracted a sharded mlp dim
            y_rows = jax.lax.psum(y_rows, tensor)

        y_back = jax.lax.all_to_all(
            y_rows.reshape(ep, c_send, Eb), "expert", 0, 0, tiled=True)
        y_slots = y_back[dst, safe_pos]
        y_slots = jnp.where(keep[:, None], y_slots, 0) * w_flat[:, None]
        y_slice = y_slots.reshape(T_slice, k, Eb).sum(axis=1)
        y_full = jax.lax.all_gather(y_slice, "expert", axis=0, tiled=True)
        return y_full.reshape(Bb, Sb, Eb), aux

    batch_spec = batch_axes if len(batch_axes) > 1 else \
        (batch_axes[0] if batch_axes else None)
    out, aux = shard_map_novma(
        per_member, mesh,
        in_specs=(P(batch_spec, None, None), P(None, None),
                  P("expert", None, tensor), P("expert", None, tensor),
                  P("expert", tensor, None)),
        out_specs=(P(batch_spec, None, None), P()),
    )(x, router_w, w_gate, w_up, w_down)
    return out, aux


def _dense_dispatch_ffn(tokens, weights, idx, one_hot, w_gate, w_up, w_down,
                        num_experts, k, capacity_factor, activation,
                        first=0, valid=None):
    """Reference oracle: every expert whose leaves are here (`first` ..
    first + w_up.shape[0] - 1) sees every token (one-hot einsums)."""
    T, E = tokens.shape
    N = _n_experts(w_up)
    with jax.named_scope("moe_dispatch"):
        if one_hot is None:   # the caller routed
            one_hot = jax.nn.one_hot(idx, num_experts, dtype=tokens.dtype)
        if N != num_experts:
            one_hot = one_hot[..., first:first + N]
        if valid is not None:
            one_hot = one_hot * valid[:, None, None].astype(one_hot.dtype)
        # combine matrix: [tokens, experts], rows sum to 1 over selected
        # experts
        combine = jnp.einsum("tkn,tk->tn", one_hot, weights)

        if capacity_factor is not None:
            C = expert_capacity(T, num_experts, k, capacity_factor)
            # count capacity from the ROUTING mask (one_hot), not
            # `combine > 0`: a top-k slot whose softmax weight underflowed
            # to exactly 0 still occupies a capacity slot in the sparse
            # path, and the oracle must make identical drop decisions
            dispatch_mask = jnp.sum(one_hot, axis=1) > 0  # [t, n]
            # 1-based arrival position in token order
            position_in_expert = (jnp.cumsum(dispatch_mask, axis=0)
                                  * dispatch_mask)
            combine = jnp.where(position_in_expert <= C, combine, 0.0)

        # [n, t, E]: per-expert token batch
        h = jnp.einsum("te,tn->nte", tokens, combine != 0)
    with jax.named_scope("moe_experts"):
        expert_out = _experts(h, w_gate, w_up, w_down, activation,
                              tokens.dtype)
    with jax.named_scope("moe_combine"):
        return jnp.einsum("nte,tn->te", expert_out.astype(tokens.dtype),
                          combine)


def _load_balancing_loss(router_logits, one_hot):
    """Switch-style auxiliary loss: num_experts * Σ fraction_i * prob_i."""
    num_experts = router_logits.shape[-1]
    probs = jax.nn.softmax(router_logits, axis=-1)
    fraction = jnp.mean(one_hot.sum(axis=1), axis=0)
    prob_mean = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(fraction * prob_mean)
