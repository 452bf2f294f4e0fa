"""Pipeline parallelism via shard_map over the 'pipeline' mesh axis.

GPipe-style schedule (SURVEY.md §5.7 "pipeline via shard_map"): the layer
stack is split into S contiguous stages (the stacked-layer pytree's leading
axis is sharded over 'pipeline'); M microbatches stream through, activations
hop stage→stage with lax.ppermute over neighbouring ICI links. Total ticks =
M + S - 1; bubble fraction = (S-1)/(M+S-1).

MPMD-style per-stage programs (PAPERS.md: MPMD pipeline parallelism) are a
later optimization — this single-SPMD-program formulation lets XLA overlap
the ppermute with stage compute already.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def _as_varying(z, axis_name):
    """Mark z as varying over the pipeline axis inside shard_map — a
    no-op if it already is. (zeros_like(params) inherits the params'
    annotation, hence the check.)"""
    if axis_name in jax.typeof(z).vma:
        return z
    return jax.lax.pcast(z, (axis_name,), to="varying")


def _shard_map(fn, mesh, in_specs, out_specs, manual_axes=None):
    """shard_map manual ONLY over `manual_axes` (default: every mesh axis).

    On a composed mesh (e.g. pipeline × fsdp) the schedule stays manual
    over 'pipeline' while the remaining axes are left to GSPMD — the
    body's arrays stay global over those axes, so an outer batch sharding
    (fsdp/data) or ZeRO param sharding composes with the pipeline without
    the schedule code knowing about it."""
    kwargs = {}
    if (manual_axes is not None
            and set(manual_axes) != set(mesh.axis_names)):
        kwargs["axis_names"] = frozenset(manual_axes)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def pipeline_apply(layer_fn, stage_params, x, mesh, num_microbatches,
                   axis_name="pipeline"):
    """Run x through all pipeline stages.

    layer_fn: (carry, layer_params) -> carry, applied per layer via scan
        inside each stage.
    stage_params: pytree whose leaves have leading dim n_layers, SHARDED on
        `axis_name` (n_layers % n_stages == 0).
    x: [B, ...] global batch (replicated across the pipeline axis);
        B % num_microbatches == 0.
    Returns y with x's shape.
    """
    n_stages = mesh.shape[axis_name]
    if num_microbatches < 1:
        raise ValueError("num_microbatches must be >= 1")

    def local(x_local, params_local):
        stage = jax.lax.axis_index(axis_name)
        B = x_local.shape[0]
        mb_size = B // num_microbatches
        microbatches = x_local.reshape((num_microbatches, mb_size)
                                       + x_local.shape[1:])

        def run_stage(act):
            out, _ = jax.lax.scan(
                lambda c, lp: (layer_fn(c, lp), None), act, params_local
            )
            return out

        perm_fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        n_ticks = num_microbatches + n_stages - 1
        # mark the carries as varying over the pipeline axis (their values
        # genuinely differ per stage once the loop runs)
        outputs = jax.lax.pcast(
            jnp.zeros_like(microbatches), (axis_name,), to="varying"
        )
        buf = jax.lax.pcast(
            jnp.zeros((mb_size,) + x_local.shape[1:], x_local.dtype),
            (axis_name,), to="varying",
        )

        def tick(t, carry):
            buf, outputs = carry
            # stage 0 ingests microbatch t (when available)
            mb_idx = jnp.clip(t, 0, num_microbatches - 1)
            incoming = microbatches[mb_idx]
            buf = jnp.where(stage == 0,
                            jnp.where(t < num_microbatches, incoming, buf),
                            buf)
            buf = run_stage(buf)
            # last stage emits microbatch t - (S-1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, num_microbatches - 1)
            emit = jnp.logical_and(stage == n_stages - 1,
                                   t >= n_stages - 1)
            outputs = jnp.where(
                emit,
                outputs.at[out_idx].set(buf),
                outputs,
            )
            # hand activations to the next stage
            buf = jax.lax.ppermute(buf, axis_name, perm_fwd)
            return buf, outputs

        buf, outputs = jax.lax.fori_loop(0, n_ticks, tick, (buf, outputs))
        y_local = outputs.reshape(x_local.shape)
        # every stage returns a buffer; only the last stage's is real —
        # broadcast it so the output is replicated over the pipeline axis
        last = jax.lax.psum(
            jnp.where(stage == n_stages - 1, 1.0, 0.0) * 0 + (
                y_local * (stage == n_stages - 1)
            ),
            axis_name,
        )
        return last

    # params sharded over pipeline axis on the leading (layers) dim;
    # x replicated; output replicated
    param_specs = jax.tree.map(lambda _: P(axis_name), stage_params)
    fn = _shard_map(
        local, mesh,
        in_specs=(P(), param_specs),
        out_specs=P(),
        manual_axes=(axis_name,),
    )
    return fn(x, stage_params)


def pipelined_forward(model_layer_fn, params_layers, x, mesh,
                      num_microbatches=4, axis_name="pipeline"):
    """Convenience wrapper matching models' stacked-layer params."""
    return pipeline_apply(
        model_layer_fn, params_layers, x, mesh, num_microbatches, axis_name
    )


def _degenerate_train(layer_fn, loss_fn, stage_params, x, y, M,
                      head_params=None, return_input_grad=False):
    """S == 1: no pipeline — one microbatched scan, differentiated
    directly. The single implementation behind both schedules' degenerate
    paths."""

    def full_loss(layers, head, xx):
        mbs = xx.reshape((M, xx.shape[0] // M) + xx.shape[1:])
        ybs = y.reshape((M, y.shape[0] // M) + y.shape[1:])

        def body(acc, mb_yb):
            mb, yb = mb_yb
            out, _ = jax.lax.scan(
                lambda c, lp: (layer_fn(c, lp), None), mb, layers
            )
            out = out.astype(jnp.float32)
            val = (loss_fn(out, yb, head) if head is not None
                   else loss_fn(out, yb))
            return acc + val, None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                (mbs, ybs))
        return total / M

    if head_params is None and not return_input_grad:
        return jax.value_and_grad(
            lambda p: full_loss(p, None, x)
        )(stage_params)
    loss, (lg, hg, dx) = jax.value_and_grad(
        full_loss, argnums=(0, 1, 2)
    )(stage_params, head_params, x)
    return loss, lg, {
        "head_grads": hg if head_params is not None else None,
        "input_grad": dx if return_input_grad else None,
    }


def pipeline_train_1f1b(layer_fn, loss_fn, stage_params, x, y, mesh,
                        num_microbatches, axis_name="pipeline"):
    """1F1B training schedule: loss + per-stage parameter gradients.

    Unlike differentiating through the GPipe loop (which holds every
    microbatch's activations until the flush), the one-forward-one-backward
    schedule starts each microbatch's backward as soon as the last stage
    finishes its forward, so live activation memory is bounded by the
    pipeline DEPTH (≈2S in-flight stage inputs), independent of the
    microbatch count M. Backward recomputes the stage forward from the
    saved stage input (activation checkpointing), the standard
    remat-in-pipeline trade.

    Lockstep formulation (one SPMD program): each cycle c has an F slot and
    a B slot. Stage i forwards microbatch c-i and backwards microbatch
    c-(2S-2-i); activations hop i→i+1 and cotangents hop i→i-1 via
    lax.ppermute each cycle. Total cycles M + 2(S-1); bubble matches
    non-interleaved 1F1B.

    layer_fn: (carry, layer_params) -> carry (scanned over the stage's
        local layers).
    loss_fn: (stage_output, targets) -> scalar mean loss (applied by the
        last stage per microbatch).
    stage_params: pytree, leaves stacked [n_layers, ...], sharded on
        `axis_name`.
    x: [B, ...] inputs, y: [B, ...] targets, both replicated over the
        pipeline axis; B % num_microbatches == 0.
    Returns (mean_loss, param_grads) with param_grads sharded like
    stage_params.
    """
    n_stages = dict(mesh.shape).get(axis_name, 1)
    M = num_microbatches
    if M < 1:
        raise ValueError("num_microbatches must be >= 1")

    if n_stages == 1:
        # degenerate pipeline: plain microbatched loss/grad, no collectives
        # (size-1 mesh axes are dropped by MeshSpec)
        return _degenerate_train(layer_fn, loss_fn, stage_params, x, y, M)

    def local(x_local, y_local, params_local):
        stage = jax.lax.axis_index(axis_name)
        S = n_stages
        B = x_local.shape[0]
        mb_size = B // M
        mbs = x_local.reshape((M, mb_size) + x_local.shape[1:])
        ybs = y_local.reshape((M, mb_size) + y_local.shape[1:])

        def run_stage(act, params):
            out, _ = jax.lax.scan(
                lambda c, lp: (layer_fn(c, lp), None), act, params
            )
            return out

        L = min(M, 2 * (S - 1) + 1) if S > 1 else 1  # live-input slots
        perm_fwd = [(i, (i + 1) % S) for i in range(S)]
        perm_bwd = [(i, (i - 1) % S) for i in range(S)]

        var = functools.partial(_as_varying, axis_name=axis_name)

        act_shape = (mb_size,) + x_local.shape[1:]
        state = dict(
            saved=var(jnp.zeros((L,) + act_shape, x_local.dtype)),
            fwd_buf=var(jnp.zeros(act_shape, x_local.dtype)),
            grad_buf=var(jnp.zeros(act_shape, jnp.float32)),
            pgrads=jax.tree.map(
                lambda p: var(jnp.zeros_like(p, jnp.float32)), params_local
            ),
            loss=var(jnp.zeros((), jnp.float32)),
        )

        def cycle(c, state):
            # ---- F slot: stage forwards microbatch c - stage ----
            m_f = c - stage
            f_active = jnp.logical_and(m_f >= 0, m_f < M)
            m_f_idx = jnp.clip(m_f, 0, M - 1)
            a_in = jnp.where(stage == 0, mbs[m_f_idx], state["fwd_buf"])
            slot = jnp.mod(m_f_idx, L)
            saved = jnp.where(
                f_active,
                state["saved"].at[slot].set(a_in),
                state["saved"],
            )
            a_out = run_stage(a_in, params_local)
            fwd_buf = jax.lax.ppermute(a_out, axis_name, perm_fwd)

            # ---- B slot: stage backwards microbatch c - (2S-2-stage) ----
            m_b = c - (2 * S - 2 - stage)
            b_active = jnp.logical_and(m_b >= 0, m_b < M)
            m_b_idx = jnp.clip(m_b, 0, M - 1)
            a_saved = saved[jnp.mod(m_b_idx, L)]
            out, pullback = jax.vjp(
                lambda a, p: run_stage(a, p), a_saved, params_local
            )
            # cotangent source: the last stage seeds from the loss, every
            # other stage consumes the cotangent arriving from stage+1
            loss_val, dloss_dout = jax.value_and_grad(loss_fn)(
                out.astype(jnp.float32), ybs[m_b_idx]
            )
            cot = jnp.where(
                stage == S - 1,
                dloss_dout.astype(out.dtype),
                state["grad_buf"].astype(out.dtype),
            )
            da, dp = pullback(cot)
            pgrads = jax.tree.map(
                lambda acc, g: acc
                + jnp.where(b_active, g.astype(jnp.float32), 0.0),
                state["pgrads"],
                dp,
            )
            loss = state["loss"] + jnp.where(
                jnp.logical_and(b_active, stage == S - 1), loss_val, 0.0
            )
            grad_buf = jax.lax.ppermute(
                da.astype(jnp.float32), axis_name, perm_bwd
            )
            return dict(saved=saved, fwd_buf=fwd_buf, grad_buf=grad_buf,
                        pgrads=pgrads, loss=loss)

        n_cycles = M + 2 * (S - 1)
        state = jax.lax.fori_loop(0, n_cycles, cycle, state)
        # only the last stage accumulated loss; share it with every stage
        mean_loss = jax.lax.psum(state["loss"], axis_name) / M
        pgrads = jax.tree.map(lambda g: g / M, state["pgrads"])
        return mean_loss, pgrads

    param_specs = jax.tree.map(lambda _: P(axis_name), stage_params)
    fn = _shard_map(
        local, mesh,
        in_specs=(P(), P(), param_specs),
        out_specs=(P(), param_specs),
        manual_axes=(axis_name,),
    )
    return fn(x, y, stage_params)


# ---------------------------------------------------------------------------
# Interleaved 1F1B: virtual stages (SURVEY.md §5.7; bubble-cutting half of
# the pipeline feature the reference delegates to its training substrate).
#
# Each device holds V model CHUNKS instead of one contiguous stage: chunk v
# (of V*S total) lives on device v % S, so a microbatch visits dev 0..S-1
# V times. Per-cycle work shrinks to layers/(V*S) and the pipeline
# fill/drain bubble shrinks ~V-fold relative to plain 1F1B at equal M.
#
# Formulation: a host-side STATIC scheduler (list scheduling with dataflow
# + transport + in-flight-memory constraints) emits per-(device, cycle)
# instruction tables; a single lockstep SPMD loop executes them. All
# activation hops are nearest-neighbour ppermutes (+1 ring forward, -1
# ring backward) — chunk v's successor chunk v+1 is always on the next
# device — so the schedule's communication rides ICI regardless of depth.
# ---------------------------------------------------------------------------


class _Slots(object):
    """Slot allocator for one device's buffer: alloc(c) returns a slot
    free at cycle c (growing the buffer if none), free(slot, at) releases
    it for reuse from cycle `at` on."""

    def __init__(self):
        self.free_at = []

    def alloc(self, c):
        for i, f in enumerate(self.free_at):
            if f is not None and f <= c:
                self.free_at[i] = None  # in use
                return i
        self.free_at.append(None)
        return len(self.free_at) - 1

    def free(self, slot, at):
        self.free_at[slot] = at

    def __len__(self):
        return max(1, len(self.free_at))


def interleaved_schedule(M, V, S):
    """Static interleaved-1F1B timetable: ONE op (forward, backward, or
    idle) per device per cycle, backward-priority — warmup naturally runs
    forwards, steady state alternates F/B, drain runs backwards, exactly
    the 1F1B shape; a cycle costs one CHUNK of compute (layers/(V*S)), so
    the fill/drain bubble shrinks ~V-fold vs plain 1F1B.

    Returns a dict of int32 [S, n_cycles] instruction tables:
      f_on/f_j/f_m/f_in/f_rslot/f_save  — forward op (chunk j = local
          virtual stage, microbatch m, read from input vs recv slot,
          saved-activation slot to write)
      fstore — recv slot to store the activation arriving this cycle (-1)
      b_on/b_j/b_m/b_last/b_save/b_rslot — backward op (recompute from
          saved slot; cotangent seeded from the loss on the last chunk,
          else read from a recv slot)
      bstore — recv slot to store the cotangent arriving this cycle (-1)
    plus buffer sizes (n_saved/n_recv_f/n_recv_b) and n_cycles.
    """
    VS = V * S
    INF = 1 << 30
    fc, bc = {}, {}        # (m, v) -> cycle scheduled
    saved_slot = {}        # (m, v) -> slot holding chunk v's input
    act_slot = {}          # (m, v) -> recv slot where chunk v's input lands
    cot_slot = {}          # (m, v) -> recv slot where chunk v's cotangent lands
    saved = [_Slots() for _ in range(S)]
    recv_f = [_Slots() for _ in range(S)]
    recv_b = [_Slots() for _ in range(S)]
    inflight = [0] * S
    # bounded activation memory — the 1F1B point: enough for the V chunks
    # of a full warmup plus the per-device pipeline skew, independent of M
    cap = V * S + 2 * (S - 1)
    cols = {k: [[] for _ in range(S)] for k in (
        "f_on", "f_j", "f_m", "f_in", "f_rslot", "f_save", "fstore",
        "b_on", "b_j", "b_m", "b_last", "b_save", "b_rslot", "bstore")}

    def idle_f(row):
        for k in ("f_on", "f_j", "f_m", "f_in"):
            row[k].append(0)
        row["f_rslot"].append(-1)
        row["f_save"].append(0)

    def idle_b(row):
        for k in ("b_on", "b_j", "b_m", "b_last"):
            row[k].append(0)
        row["b_save"].append(0)
        row["b_rslot"].append(-1)

    c = 0
    limit = 4 * VS * (M + 2 * VS) + 64
    while len(bc) < M * VS:
        if c > limit:
            raise RuntimeError(
                "interleaved_schedule failed to converge (M=%d V=%d S=%d)"
                % (M, V, S))
        stores_f = [(-1)] * S  # arrival-store directives decided this cycle
        stores_b = [(-1)] * S
        for d in range(S):
            row = {k: cols[k][d] for k in cols}
            # ---- backward first: drain deep chunks as soon as possible ----
            best = None
            for j in range(V):
                v = d + j * S
                for m in range(M):
                    if (m, v) in bc or (m, v) not in fc:
                        continue
                    if fc[(m, v)] > c - 1:
                        continue
                    if v < VS - 1 and bc.get((m, v + 1), INF) > c - 1:
                        continue
                    key = (m // S, -v, m % S)
                    if best is None or key < best[0]:
                        best = (key, m, v)
            if best is not None:
                _, m, v = best
                bc[(m, v)] = c
                inflight[d] -= 1
                s = saved_slot[(m, v)]
                saved[d].free(s, c + 1)  # reusable from the next cycle
                rslot = -1
                if v < VS - 1:
                    rslot = cot_slot[(m, v)]
                    recv_b[d].free(rslot, c)
                if v > 0:
                    dst = (d - 1) % S
                    slot = recv_b[dst].alloc(c)
                    cot_slot[(m, v - 1)] = slot
                    stores_b[dst] = slot
                row["b_on"].append(1)
                row["b_j"].append(v // S)
                row["b_m"].append(m)
                row["b_last"].append(1 if v == VS - 1 else 0)
                row["b_save"].append(s)
                row["b_rslot"].append(rslot)
                idle_f(row)
                continue
            idle_b(row)

            # ---- no backward ready: forward (depth-first priority) ----
            pick = None
            if inflight[d] < cap:
                best = None
                for j in range(V):
                    v = d + j * S
                    for m in range(M):
                        if (m, v) in fc:
                            continue
                        if v > 0 and fc.get((m, v - 1), INF) > c - 1:
                            continue
                        key = (m // S, j, m % S)
                        if best is None or key < best[0]:
                            best = (key, m, v)
                if best is not None:
                    pick = (best[1], best[2])
            if pick is not None:
                m, v = pick
                fc[(m, v)] = c
                inflight[d] += 1
                s = saved[d].alloc(c)
                saved_slot[(m, v)] = s
                rslot = -1
                if v > 0:
                    rslot = act_slot[(m, v)]
                    recv_f[d].free(rslot, c)  # read precedes this cycle's store
                if v < VS - 1:
                    dst = (d + 1) % S
                    slot = recv_f[dst].alloc(c)
                    act_slot[(m, v + 1)] = slot
                    stores_f[dst] = slot
                row["f_on"].append(1)
                row["f_j"].append(v // S)
                row["f_m"].append(m)
                row["f_in"].append(1 if v == 0 else 0)
                row["f_rslot"].append(rslot)
                row["f_save"].append(s)
            else:
                idle_f(row)
        for d in range(S):
            cols["fstore"][d].append(stores_f[d])
            cols["bstore"][d].append(stores_b[d])
        c += 1

    tables = {k: np.asarray(cols[k], dtype=np.int32) for k in cols}
    tables["n_cycles"] = c
    tables["n_saved"] = max(len(s) for s in saved)
    tables["n_recv_f"] = max(len(s) for s in recv_f)
    tables["n_recv_b"] = max(len(s) for s in recv_b)
    return tables


def pipeline_train_interleaved(layer_fn, loss_fn, stage_params, x, y, mesh,
                               num_microbatches, num_virtual_stages=2,
                               axis_name="pipeline", head_params=None,
                               return_input_grad=False):
    """Interleaved 1F1B: V virtual stages per device cut the pipeline
    bubble ~V-fold (each fill/drain tick now costs layers/(V*S) instead of
    layers/S of compute).

    Same contract as pipeline_train_1f1b — layers stacked on the leading
    axis in NATURAL order, loss_fn applied by the final chunk — plus
    `num_virtual_stages`. n_layers must divide evenly into V*S chunks.
    Backward recomputes each chunk forward from its saved input
    (remat-in-pipeline); gradients are returned in natural layer order.

    Training a FULL model through the pipeline needs two more gradient
    paths, both optional:
      head_params: replicated pytree consumed by the loss —
          loss_fn(out, targets, head_params) — e.g. final norm + unembed.
          Their gradients accumulate on the last-chunk device and psum
          across the axis.
      return_input_grad=True: also return dL/dx (the cotangent leaving
          chunk 0's backward, collected per microbatch) so the caller can
          chain into the embedding lookup's scatter-add transpose.
    With either option the result is (loss, stage_grads, aux) where
    aux = {"head_grads": ..., "input_grad": ...} (absent entries None);
    otherwise (loss, stage_grads) exactly as before.

    The instruction tables come from `interleaved_schedule`; the loop
    body executes one (possibly inactive) F slot and one B slot per
    cycle, with both transport rings running every cycle so the SPMD
    program stays identical across devices.
    """
    S = dict(mesh.shape).get(axis_name, 1)
    V = int(num_virtual_stages)
    M = int(num_microbatches)
    extras = head_params is not None or return_input_grad
    if V < 1:
        raise ValueError("num_virtual_stages must be >= 1")
    if S == 1:
        # no pipeline at all: differentiate everything directly
        return _degenerate_train(layer_fn, loss_fn, stage_params, x, y, M,
                                 head_params=head_params,
                                 return_input_grad=return_input_grad)
    if V == 1 and not extras:
        # V=1 IS plain 1F1B (the table path handles it too, but the
        # dedicated implementation is simpler — keep the old contract)
        return pipeline_train_1f1b(layer_fn, loss_fn, stage_params, x, y,
                                   mesh, M, axis_name)
    L = jax.tree.leaves(stage_params)[0].shape[0]
    VS = V * S
    if L % VS:
        raise ValueError(
            "n_layers=%d must divide into num_virtual_stages*num_stages=%d "
            "chunks" % (L, VS))
    Lc = L // VS

    # natural layer order -> device-major chunk order: device d holds
    # chunks d, d+S, ..., so the leading-axis shard P(axis_name) lands
    # each device's V chunks contiguously
    perm = np.array(
        [(j * S + d) * Lc + k
         for d in range(S) for j in range(V) for k in range(Lc)]
    )
    inv_perm = np.argsort(perm)
    sched = interleaved_schedule(M, V, S)
    C = sched["n_cycles"]
    T = {k: jnp.asarray(sched[k]) for k in (
        "f_on", "f_j", "f_m", "f_in", "f_rslot", "f_save", "fstore",
        "b_on", "b_j", "b_m", "b_last", "b_save", "b_rslot", "bstore")}

    perm_fwd = [(i, (i + 1) % S) for i in range(S)]
    perm_bwd = [(i, (i - 1) % S) for i in range(S)]

    def local(x_local, y_local, params_local, head_local):
        stage = jax.lax.axis_index(axis_name)
        mb_size = x_local.shape[0] // M
        mbs = x_local.reshape((M, mb_size) + x_local.shape[1:])
        ybs = y_local.reshape((M, mb_size) + y_local.shape[1:])
        params_v = jax.tree.map(
            lambda p: p.reshape((V, Lc) + p.shape[1:]), params_local
        )

        def chunk_fwd(act, j, pv):
            pj = jax.tree.map(
                lambda p: jax.lax.dynamic_index_in_dim(p, j, 0,
                                                       keepdims=False), pv
            )
            out, _ = jax.lax.scan(
                lambda c, lp: (layer_fn(c, lp), None), act, pj
            )
            return out

        var = functools.partial(_as_varying, axis_name=axis_name)
        # head params arrive replicated (P() spec = unvarying): grad'ing
        # an UNVARYING value inside a switch branch makes jax insert a
        # backward psum — a collective only the branch-taking devices
        # would execute (deadlock). Mark them varying; the manual psum
        # after the loop does the cross-device reduction instead.
        head_v = (None if head_local is None
                  else jax.tree.map(var, head_local))

        act_shape = (mb_size,) + x_local.shape[1:]
        state = dict(
            saved=var(jnp.zeros((sched["n_saved"],) + act_shape,
                                x_local.dtype)),
            recv_f=var(jnp.zeros((sched["n_recv_f"],) + act_shape,
                                 x_local.dtype)),
            recv_b=var(jnp.zeros((sched["n_recv_b"],) + act_shape,
                                 jnp.float32)),
            pgrads=jax.tree.map(
                lambda p: var(jnp.zeros_like(p, jnp.float32)), params_v
            ),
            loss=var(jnp.zeros((), jnp.float32)),
        )
        if head_local is not None:
            state["hgrads"] = jax.tree.map(
                lambda p: var(jnp.zeros_like(p, jnp.float32)), head_v
            )
        if return_input_grad:
            state["dx"] = var(jnp.zeros((M,) + act_shape, jnp.float32))

        zero_act = var(jnp.zeros(act_shape, x_local.dtype))
        zero_cot = var(jnp.zeros(act_shape, jnp.float32))

        def cycle(c, st):
            # one op per cycle: 0 = idle, 1 = forward, 2 = MID-chunk
            # backward (cotangent from the ring, no loss), 3 = LAST-chunk
            # backward (loss + optional head grads — the head's fwd+bwd
            # is only ever paid where its result is real). The branches
            # hold no collectives (layer-internal collectives run over
            # OTHER mesh axes, where same-pipeline-coordinate devices
            # take the same branch), so only the selected branch's chunk
            # of compute is paid; both transport rings run unconditionally
            # after it to keep devices in lockstep.
            op = (T["f_on"][stage, c] + 2 * T["b_on"][stage, c]
                  + T["b_last"][stage, c])

            def carried(st):
                # everything a branch may update (recv buffers are
                # handled outside, after the transport rings)
                out = dict(saved=st["saved"], pgrads=st["pgrads"],
                           loss=st["loss"])
                for k in ("hgrads", "dx"):
                    if k in st:
                        out[k] = st[k]
                return out

            def do_idle(st):
                return zero_act, zero_cot, carried(st)

            def do_fwd(st):
                a_in = jnp.where(
                    T["f_in"][stage, c] > 0,
                    mbs[T["f_m"][stage, c]],
                    st["recv_f"][jnp.clip(T["f_rslot"][stage, c], 0)],
                )
                saved = st["saved"].at[T["f_save"][stage, c]].set(a_in)
                a_out = chunk_fwd(a_in, T["f_j"][stage, c], params_v)
                upd = carried(st)
                upd["saved"] = saved
                return a_out, zero_cot, upd

            def _bwd_common(st, out, pullback, cot, b_j, b_m):
                da, dp = pullback(cot.astype(out.dtype))
                # dp is zero outside chunk b_j (gradients flow only
                # through the dynamically selected chunk), so a full-tree
                # add accumulates correctly without a scatter
                upd = carried(st)
                upd["pgrads"] = jax.tree.map(
                    lambda acc, g: acc + g.astype(jnp.float32),
                    st["pgrads"], dp,
                )
                if return_input_grad:
                    # chunk 0's input cotangent IS dL/d(embedded input)
                    # for this microbatch (local virtual stage 0 on the
                    # first pipeline device)
                    is_c0 = jnp.logical_and(stage == 0, b_j == 0)
                    upd["dx"] = jnp.where(
                        is_c0,
                        st["dx"].at[b_m].set(da.astype(jnp.float32)),
                        st["dx"],
                    )
                return zero_act, da.astype(jnp.float32), upd

            def _chunk_vjp(st):
                # recompute the chunk forward from its saved input
                # (remat-in-pipeline); shared by both backward ops
                b_j = T["b_j"][stage, c]
                a_sv = st["saved"][T["b_save"][stage, c]]
                out, pullback = jax.vjp(
                    lambda a, pv: chunk_fwd(a, b_j, pv), a_sv, params_v
                )
                return out, pullback, b_j

            def do_bwd_mid(st):
                out, pullback, b_j = _chunk_vjp(st)
                cot = st["recv_b"][jnp.clip(T["b_rslot"][stage, c], 0)]
                return _bwd_common(st, out, pullback, cot, b_j,
                                   T["b_m"][stage, c])

            def do_bwd_last(st):
                out, pullback, b_j = _chunk_vjp(st)
                b_m = T["b_m"][stage, c]
                if head_local is None:
                    loss_val, dldout = jax.value_and_grad(loss_fn)(
                        out.astype(jnp.float32), ybs[b_m]
                    )
                    dhead = None
                else:
                    loss_val, (dldout, dhead) = jax.value_and_grad(
                        loss_fn, argnums=(0, 2)
                    )(out.astype(jnp.float32), ybs[b_m], head_v)
                send_f, send_b, upd = _bwd_common(
                    st, out, pullback, dldout, b_j, b_m
                )
                upd["loss"] = st["loss"] + loss_val
                if dhead is not None:
                    # last-chunk ops all run on one device; the psum
                    # after the loop spreads the sum
                    upd["hgrads"] = jax.tree.map(
                        lambda acc, g: acc + g.astype(jnp.float32),
                        st["hgrads"], dhead,
                    )
                return send_f, send_b, upd

            send_f, send_b, upd = jax.lax.switch(
                op, [do_idle, do_fwd, do_bwd_mid, do_bwd_last], st
            )
            saved, pgrads, loss = upd["saved"], upd["pgrads"], upd["loss"]

            arriving_f = jax.lax.ppermute(send_f, axis_name, perm_fwd)
            fstore = T["fstore"][stage, c]
            recv_f = jnp.where(
                fstore >= 0,
                st["recv_f"].at[jnp.clip(fstore, 0)].set(arriving_f),
                st["recv_f"],
            )
            arriving_b = jax.lax.ppermute(send_b, axis_name, perm_bwd)
            bstore = T["bstore"][stage, c]
            recv_b = jnp.where(
                bstore >= 0,
                st["recv_b"].at[jnp.clip(bstore, 0)].set(arriving_b),
                st["recv_b"],
            )
            new = dict(saved=saved, recv_f=recv_f, recv_b=recv_b,
                       pgrads=pgrads, loss=loss)
            for k in ("hgrads", "dx"):
                if k in upd:
                    new[k] = upd[k]
            return new

        st = jax.lax.fori_loop(0, C, cycle, state)
        mean_loss = jax.lax.psum(st["loss"], axis_name) / M
        grads = jax.tree.map(
            lambda g: (g / M).reshape((V * Lc,) + g.shape[2:]),
            st["pgrads"],
        )
        out = (mean_loss, grads)
        if head_local is not None:
            # accumulated only on the last-chunk device; zeros elsewhere
            out += (jax.tree.map(
                lambda g: jax.lax.psum(g, axis_name) / M, st["hgrads"]),)
        if return_input_grad:
            dx = jax.lax.psum(st["dx"], axis_name) / M
            out += (dx.reshape(x_local.shape),)
        return out

    param_specs = jax.tree.map(lambda _: P(axis_name), stage_params)
    out_specs = (P(), param_specs)
    if head_params is not None:
        out_specs += (jax.tree.map(lambda _: P(), head_params),)
    if return_input_grad:
        out_specs += (P(),)
    fn = _shard_map(
        local, mesh,
        in_specs=(P(), P(), param_specs,
                  jax.tree.map(lambda _: P(), head_params)),
        out_specs=out_specs,
        manual_axes=(axis_name,),
    )
    params_re = jax.tree.map(lambda p: p[perm], stage_params)
    results = fn(x, y, params_re, head_params)
    loss, grads_re = results[0], results[1]
    grads = jax.tree.map(lambda g: g[inv_perm], grads_re)
    if not extras:
        return loss, grads
    idx = 2
    hg = None
    if head_params is not None:
        hg = results[idx]
        idx += 1
    dx = results[idx] if return_input_grad else None
    return loss, grads, {"head_grads": hg, "input_grad": dx}
