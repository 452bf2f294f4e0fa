"""Slot-based continuous-batching decode engine.

The lockstep generate() path compiles ONE program per (batch, prompt
bucket) and forces every sequence in a batch to start and finish
together — real traffic with mixed prompt/output lengths leaves most of
the MXU idle padding to the slowest request. This engine is the
Orca/vLLM-lineage fix, shaped for TPUs: scheduling happens in Python,
but every device step is one of a FIXED set of jitted programs, so the
compiled-program residency that TPUs reward is preserved.

Layout: a pool of B slots shares one static cache, a tree of the pools
the model's kinds of layer declare, each [layers of the kind, B] + its
shape a slot (inference/cache.py, `init_kv_cache`: the one module that
knows the format, and whose `seed`, `extract` and `reset` this engine
jits). It is the carry of decode_forward's layer loop, donated and
updated in place.
Each slot holds at most one in-flight request and carries host-side
state (pos, sampling knobs, per-token rng keys: drawn when a sampled
token is first asked for, `KeySchedules`). Three compiled programs
cover everything:

  - prefill: ONE execution an iteration writes the next prompt tokens of
    up to `rows` slots into the cache. tokens [R, W], slots [R],
    start [R], n_real [R]: row r is slot slots[r]'s next tokens from its
    cursor, padded to the width W. The rows are distinct slots (a
    recurrent state and causal attention make a slot's second row depend
    on its first); a lone prefilling slot gets one wider row instead. K and V
    are written into, and read out of, the pool in place at the rows'
    slots (inference/decode.py, `slots`); no slot's view is cut out and
    written back. The shapes are a bounded set, `prefill_shapes(budget)`
    ([1, chunk], [1, 2 chunk], [2, chunk] with the scheduler's default
    budget of two chunks: the weights are read once whatever the width,
    so there are no narrower buckets), and `warm_prefill` compiles all
    of them before a request is admitted
  - decode: advance ALL slots one token in one fused call — per-slot
    positions (vector-pos decode_forward), cache writes at each lane's
    own cursor, per-slot slot-masked sampling (greedy/temperature/
    top-k/top-p as traced per-slot arrays, so one program serves every
    sampling-config mix); on a TPU its attention reads the pools as
    stored, each decoding lane to its own depth and no other lane
    (ops/decode_attention.py), and `attention_positions()` says from the
    host's cursors how much of what it fetches the queries see
  - first-token: sample, for every row of a prefill program that ends
    its prompt, the token its logits imply, and put it at the row's lane
    among the device's last tokens; fetched in one wait, later. Where
    the config marks a tail layer (nothing past that layer's K and V at
    a position is read by a later one), the prefill program runs that
    layer's attention, the layers after it and the head for each row's
    last real position alone, and its logits are [R, 1, vocab]

Where every layer of the stack is an attention layer (Llama, Mistral,
Mixtral), on one chip and with the chunk loop (`merges`,
inference/decode.py; `attn_impl`, what `pool_read` there answers for
this engine's shapes: a fact, no option), an iteration that prefills is
ONE execution, of the decode program: `stage_rows(plan)` builds the rows
that `prefill` would have run, and the next `launch_decode` takes them
along, the lanes and the rows' tokens one batch for everything that
multiplies by a weight, so that every weight is read once where two
programs read it twice. The rows' first tokens come back with the lanes'
tokens in the one fetch (`row_results`), and the program leaves the
token of a row that ends (`ends`, host-known) at its lane; with no lane
decoding the same program runs for the rows alone, and the prefill and
first-token programs are never compiled (`warm_prefill` warms the decode
step's three row shapes in their place). A stack with a recurrent layer,
a ring, a tail layer or a gated memory keeps prefill then decode, two
programs an iteration: its rows have more to take apart than K and V,
and its kinds join one at a time (ROADMAP S9b).

Every program has a LAUNCH and a COLLECT (`launch_decode` /
`decode_step`, `launch_prefill` / `collect_prefill`; `decode_step` with
nothing in flight and `prefill` are the two in a row). A launch uploads
what is dirty, dispatches and replays the program's masked advance on the host's mirrors (`pos`,
the key cursors, which rows now decode, which lanes have their last token
launched: all known without the tokens), and waits for nothing; a collect
is the one blocking fetch of the OLDEST launch. So a scheduler may launch
step n+1 while step n runs and fetch n's tokens after (`runs_ahead`), and
the device always has its next program queued. What the host learns only
from the tokens decides nothing that launches: the lanes' last tokens live
on the device alone (`_d_tok`). `seed_prefix`, `extract_kv` and the state reset of `admit` queue
behind the step in flight in program order; `extract_kv` then waits for
it.

Slots never wait for each other: a finished slot is released and can be
refilled while its neighbors keep decoding. Free/prefilling slots ride
through the fused decode step as masked lanes — their writes land at
their own cursor and are overwritten (prefill rewrites the range, decode
overwrites pad garbage exactly one position before it would become
visible), so no flag tensor is needed inside the compiled program. A
window layer's ring keeps that invariant with no mask and no reset
(inference/cache.py, `_write_layer`), as long as no row is wider than
the ring allows: `prefill_row`, two chunks, the scheduler's default
budget.

A model with recurrent layers keeps a RECURRENT-STATE POOL in the same
cache tree, whatever pools its kinds of layer declare
(inference/cache.py, `POOLS`, says what each holds and how large;
Brumby's is 34 MB a layer and slot and it has NO KV pool: a slot then
costs the same at position 10 and at 30,000, and `max_seq_len` bounds
rope's table only). None of the three invariants above holds for a
recurrence, which has no garbage that is overwritten before it is seen,
so for such a model (Jamba, Brumby, Phi-4-mini-flash, Nemotron-H): (a)
the prefill program is told how many of each row's positions are real,
and the state after a row is the state after its last real token (a row
with none holds the state); (b) the fused decode step holds the state of
every lane whose mask is false; (c) admit zeroes the slot's state (one
small jitted program, host span `engine.state.reset`); (d) the state
carries from chunk to chunk of one prompt in the pool. A KV range is not
a prefix of such a model: seed_prefix, extract_kv, admit_prefilled and
kv_token_bytes refuse it (`refuse_recurrent`), as do the prefix caches,
the paged engine and the disaggregated handoff built on them.

A model whose stack is run several times over the same weights (the
config's `passes`: a looped model, models/ouro.py) keeps K and V of
every pass: the pools' leading axis is passes x layers (pass t of layer
i at index t * layers + i), a position costs `passes` times a layer
stack's K and V, and a decode step reads the weights `passes` times
(`self.passes`; the scheduler's `weight_passes`). Everything here that
takes a slot's K and V range takes the pool's whole leading axis, so
seed_prefix, extract_kv, admit_prefilled, kv_token_bytes, the host
prefix cache and the handoff frame carry passes x layers as they are;
what lays K and V out by the model's layers (the paged pool and its
engine and index) refuses such a config (`refuse_looped`).

Token identity with generate(): same forward, same sampling ops (the
per-slot sampler reproduces decode._sample row-for-row), same rng policy
(request_step_keys mirrors generate's split sequence), so a request
served through the engine emits exactly the tokens the lockstep path
would give it alone — greedy case bit-exact (pinned by
tests/test_serving.py).
"""

import functools
from collections import deque

import numpy as np

import jax
import jax.numpy as jnp

from .. import device, telemetry
from ..exception import TpuFlowException
from ..inference import cache as kv_cache
from ..inference.cache import (MOE_PAIRS, POOLS, cache_pools, init_kv_cache,
                               is_recurrent, layer_kinds, recurrent_pools,
                               ring_pools, stack_passes)
from ..inference.decode import (attention_positions, attention_reads,
                                bucket_length, decode_forward, family, merges,
                                pool_read, state_updates)
from ..ops.attention import NEG_INF


def request_step_keys(rng, max_new_tokens):
    """The per-token rng keys generate() would use: the first token
    samples with split(rng)[1], tokens 1..n-1 with
    split(split(rng)[0], n-1). Returns [max_new_tokens, 2] uint32."""
    if isinstance(rng, int):
        rng = jax.random.PRNGKey(rng)
    rng, first = jax.random.split(rng)
    if max_new_tokens > 1:
        rest = jax.random.split(rng, max_new_tokens - 1)
        return np.concatenate(
            [np.asarray(first)[None], np.asarray(rest)], axis=0)
    return np.asarray(first)[None]


_ZERO_KEY = np.zeros(2, np.uint32)
_ZERO_KEY.setflags(write=False)


class KeySchedules(object):
    """The slots' sampling keys, for SlotEngine and PagedEngine alike:
    `admit` records the request's rng and length (`_bind_keys`) and draws
    nothing; `_keys_for(slot)` draws the occupant's schedule
    (`request_step_keys`: two splits and two fetches that wait for the
    device, under the span `engine.admit.keys`, inside whatever phase
    asks) the first time one of its keys is asked for and keeps it until
    `release`. A key of an occupant at temperature 0 is the zero key,
    which `sample_slots` never reads there (it returns the argmax), so a
    greedy request's admission touches no device. `_key_cursor` counts
    every slot's tokens, drawn or not; `key_schedules` counts the
    schedules drawn (0 under greedy traffic, the admissions under
    sampled). Reads the engine's `_temp` and `phases`."""

    def _init_keys(self, slots):
        self._keys = np.zeros((slots, 2), np.uint32)  # current step key
        self._key_request = [None] * slots        # (rng, max_new_tokens)
        self._step_keys = [None] * slots          # [max_new, 2] once drawn
        self._key_cursor = np.zeros(slots, np.int32)
        self.key_schedules = 0

    def _bind_keys(self, slot, rng, max_new_tokens):
        self._key_request[slot] = (rng, max_new_tokens)
        self._step_keys[slot] = None
        self._key_cursor[slot] = 0

    def _drop_keys(self, slot):
        self._key_request[slot] = self._step_keys[slot] = None

    def _keys_for(self, slot):
        if self._temp[slot] <= 0.0:
            return _ZERO_KEY
        keys = self._step_keys[slot]
        if keys is None:
            with self.phases("engine.admit.keys"):
                keys = self._step_keys[slot] = request_step_keys(
                    *self._key_request[slot])
            self.key_schedules += 1
        cursor = int(self._key_cursor[slot])
        if cursor >= len(keys):
            raise ValueError("slot %d ran past its key schedule" % slot)
        return keys[cursor]


def refuse_recurrent(cfg, what):
    """Raise for `what`, which treats a KV range as a prefix, where the
    model also carries recurrent state: the K and V of the positions
    before a cut say nothing of a recurrent layer's state there."""
    if is_recurrent(cfg):
        raise TpuFlowException(
            "%s is not supported for a %s model: its %s layers carry "
            "recurrent state (the pools %s, per layer and slot) that no KV "
            "range holds, and a KV range is not a prefix of it" % (
                what, family(cfg).name,
                " and ".join(kind for kind in sorted(set(layer_kinds(cfg)))
                             if any(p.recurrent
                                    for p in POOLS[kind].values())),
                ", ".join(recurrent_pools(cfg))))


def refuse_looped(cfg, what):
    """Raise for `what`, which lays its K and V out by the model's
    layers, where the config runs its stack several times (`passes`):
    every pass keeps K and V of its own, so the pool has passes x layers
    indices and `what` has no pass index."""
    if stack_passes(cfg) > 1:
        raise TpuFlowException(
            "%s is not supported for a %s model whose stack is run %d "
            "times over the same weights (`passes`): each pass keeps K and "
            "V of its own, %d pool indices where it lays out %d layers; "
            "serve it through the slot engine" % (
                what, family(cfg).name, stack_passes(cfg),
                stack_passes(cfg) * cfg.n_layers, cfg.n_layers))


def sample_slots(logits, keys, temperature, top_k, top_p):
    """Per-slot sampling: [B, vocab] fp32 logits -> [B] int32, with
    TRACED per-slot knobs (temperature[B], top_k[B] int32 — vocab size
    disables, top_p[B] — 1.0 disables, keys[B, 2] uint32).

    Row-for-row identical to decode._sample with the same scalar knobs:
    same filter order (temperature scale, top_k, exclusive-mass top_p),
    same tie handling, and vmap'd categorical over per-slot keys matches
    the single-key batch-of-one call bit-for-bit."""
    B, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    is_greedy = temperature <= 0.0
    safe_t = jnp.where(is_greedy, 1.0, temperature)
    lt = logits / safe_t[:, None]
    k = jnp.clip(top_k, 1, V)
    sorted_desc = -jnp.sort(-lt, axis=-1)
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)
    lt = jnp.where((k < V)[:, None] & (lt < kth), NEG_INF, lt)
    order = jnp.argsort(-lt, axis=-1)
    sorted_logits = jnp.take_along_axis(lt, order, axis=-1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    # EXCLUSIVE cumulative mass (decode._sample): the top token survives
    before = jnp.cumsum(probs, axis=-1) - probs
    drop_sorted = before >= top_p[:, None]
    drop = jnp.zeros_like(drop_sorted).at[
        jnp.arange(B)[:, None], order].set(drop_sorted)
    lt = jnp.where((top_p < 1.0)[:, None] & drop, NEG_INF, lt)
    sampled = jax.vmap(
        lambda key, row: jax.random.categorical(key, row))(keys, lt)
    return jnp.where(is_greedy, greedy, sampled.astype(jnp.int32))


class SlotEngine(KeySchedules):
    """Fixed pool of decode slots over one shared static KV cache.

    Host-side bookkeeping (which slot holds which request, positions,
    sampling knobs) lives in numpy arrays; device work goes through the
    three jitted programs described in the module docstring. The engine
    is NOT thread-safe — exactly one scheduler loop drives it.
    """

    # the loop may launch a decode step before it collects the last one's
    # tokens: everything a launch needs is known without them
    runs_ahead = True

    def __init__(self, params, cfg, max_slots=8, max_seq_len=None,
                 prefill_chunk=64, mesh=None, cache_dtype=None, pad_id=0,
                 min_bucket=16):
        self.params = params
        self.cfg = cfg
        self.max_slots = int(max_slots)
        self.max_seq_len = int(max_seq_len or cfg.max_seq_len)
        self.prefill_chunk = int(prefill_chunk)
        if self.max_slots < 1:
            raise ValueError("max_slots must be >= 1")
        if self.prefill_chunk < 1:
            # a 0-chunk engine would admit requests and never prefill
            # them: the scheduler loop idles forever with full slots
            raise ValueError("prefill_chunk must be >= 1, got %d"
                             % self.prefill_chunk)
        self.pad_id = int(pad_id)
        self.min_bucket = min(int(min_bucket), self.prefill_chunk)
        self.mesh = mesh
        self._vocab = cfg.vocab_size

        # the widest row a prefill program may carry (the scheduler's
        # default budget): a window layer's ring is this much deeper than
        # its window
        self.prefill_row = 2 * self.prefill_chunk
        self._cache = init_kv_cache(cfg, self.max_slots, self.max_seq_len,
                                    dtype=cache_dtype, row=self.prefill_row)
        # how the programs read the pools: the shapes' answer, no option
        self.attn_impl = pool_read(self.max_seq_len, cfg, self._cache, mesh)
        # a config with a tail layer: a prefill program's logits are of
        # each row's last real position alone
        self._tail = getattr(cfg, "tail_layer", None) is not None
        if "k" not in self._cache and self.max_seq_len > cfg.max_seq_len:
            # no pool is as deep as max_seq_len: rope's table, which is
            # the config's, is all that bounds a position
            raise ValueError(
                "max_seq_len %d passes the config's %d, where rope's table "
                "ends" % (self.max_seq_len, cfg.max_seq_len))
        self._attention_reads = None   # attention_positions() fills it
        # how many times a step goes through the stack's weights (a
        # looped model; the pools then have passes x layers indices)
        self.passes = stack_passes(cfg)
        self._recurrent_pools = recurrent_pools(cfg)
        self.recurrent = bool(self._recurrent_pools)
        # a stack of attention layers on one chip: an iteration's prefill
        # rows ride in its decode step (`stage_rows`), and one execution
        # reads every weight once
        self.merges = merges(cfg, mesh, self.attn_impl)
        self._staged = None      # the rows the next decode step takes
        self.row_results = []    # what the last collected step's rows gave
        # launched and not yet collected, oldest first: the decode steps
        # (`launch_decode`) and the prefill programs (`launch_prefill`)
        self._decodes = deque()
        self._prefills = deque()
        B = self.max_slots
        # host-side per-slot state
        self.pos = np.zeros(B, np.int32)          # next cache write index
        self.active = np.zeros(B, bool)           # slot holds a request
        self.decoding = np.zeros(B, bool)         # past prefill
        # tokens the occupant may emit and those launched for it so far
        # (its first among them): a lane whose last token is in flight
        # rides the next launch masked, with no token fetched to say so
        self._max_new = np.zeros(B, np.int32)
        self._emitted = np.zeros(B, np.int32)
        self._temp = np.zeros(B, np.float32)
        self._top_k = np.full(B, self._vocab, np.int32)
        self._top_p = np.ones(B, np.float32)
        self._init_keys(B)                        # per-token rng keys
        self._slot_ctx = [None] * B               # request trace context
        self._prompt = [None] * B                 # remaining host prompt
        self._prefill_cursor = np.zeros(B, np.int32)
        # device mirrors of the decode-step inputs: steady-state decode
        # re-uploads NOTHING (the jitted step advances tok/pos on device);
        # slot membership or sampling-knob changes set _dirty and the
        # next step re-stages from the host arrays above what the host
        # knows ahead of the tokens (pos, the mask, the knobs). The lanes'
        # last tokens are the DEVICE's alone: a step writes them, a row
        # that ends its prompt leaves its first token at its slot (the
        # merged step, the first-token program), `admit_prefilled` patches
        # one lane, and the host keeps no mirror of them, which would be a
        # step stale while a step is in flight
        self._dirty = True
        self._d_tok = jnp.zeros(B, jnp.int32)
        self._d_pos = self._d_mask = None
        self._d_temp = self._d_top_k = self._d_top_p = None
        # every engine.* span goes through this ledger (a Scheduler puts
        # its own here, so that one ledger holds the whole iteration);
        # `launches` numbers the prefill and decode programs as they are
        # dispatched: the dispatch span carries `launch`, the fetch span
        # that waits for that program's result `awaits` (the first-token
        # program rides behind the prefill program whose logits it reads)
        self.phases = telemetry.PhaseLedger()
        self.launches = 0
        # a model with latent expert layers: the (token, expert) pairs
        # its programs routed and those that fell on the experts held
        # here, since the engine was made. The device counts them in the
        # cache (`MOE_PAIRS`, uint32 that wraps); every decode step's
        # fetch brings the counters along and the host adds what changed
        self.expert_pairs = {"routed": 0, "held": 0}
        self._pairs_seen = np.zeros(2, np.uint32)

        def _prefill(params, cache, tokens, slots, start, n_real=None):
            # tokens [R, W]: row r is the next tokens of slot slots[r]
            # (distinct slots) from its position start[r], of which the
            # first n_real[r] are the prompt's and the rest pad the row
            # to the program's width (None = all are real; only a
            # recurrent state asks). A lone scalar slot and start are a
            # program of one row. The pools are read and written in
            # place at those rows; no slot's view is cut out of them.
            slots, start = jnp.reshape(slots, (-1,)), jnp.reshape(start, (-1,))
            valid = None if n_real is None else (
                jnp.arange(tokens.shape[1])[None]
                < jnp.reshape(n_real, (-1, 1)))
            last = None
            if self._tail:
                last = jnp.full(slots.shape, tokens.shape[1] - 1) \
                    if n_real is None else jnp.maximum(
                        jnp.reshape(n_real, (-1,)) - 1, 0)
            return decode_forward(
                params, tokens, cache, start, cfg, mesh=mesh,
                attn_impl=self.attn_impl, valid=valid, slots=slots,
                last=last)

        def _put_first(tok, first, slots, ends):
            # a row that ends its prompt leaves its token at its slot, for
            # the lane to decode from in the next step; the other rows'
            # land past the lanes and are dropped
            return tok.at[jnp.where(ends, slots, tok.shape[0])].set(
                first, mode="drop")

        def _advance(nxt, tok, pos, mask, rows):
            # decoding lanes take the new token and move their cursor;
            # masked lanes (free / mid-prefill) hold still — the SAME
            # update runs on the host mirrors, so no download is needed
            lanes = tok.shape[0]
            tok = jnp.where(mask, nxt[:lanes], tok)
            pos = pos + mask.astype(jnp.int32)
            if rows is not None:
                tok = _put_first(tok, nxt[lanes:], rows["slots"],
                                 rows["ends"])
            return tok, pos

        def _step_logits(params, cache, tok, pos, mask, rows):
            # the decode step's logits, [B, vocab]; with the rows of a
            # prefill program riding along (`stage_rows`: tokens [R, W],
            # slots, start, n_real, and ends for `_advance`), each row's
            # logits at its last real position after them, [B + R, vocab]
            if rows is not None:
                rows = (rows["tokens"], rows["slots"], rows["start"],
                        jnp.maximum(rows["n_real"] - 1, 0))
            logits, cache = decode_forward(
                params, tok[:, None], cache, pos, cfg, mesh=mesh,
                attn_impl=self.attn_impl, valid=mask[:, None], rows=rows)
            return logits[:, 0], cache

        def _decode_sampled(params, cache, tok, pos, mask, keys, temp,
                            top_k, top_p, rows=None):
            logits, cache = _step_logits(params, cache, tok, pos, mask, rows)
            if rows is not None:   # the rows' keys and knobs, as staged
                keys, temp, top_k, top_p = (
                    jnp.concatenate([lanes, rows[name]])
                    for lanes, name in ((keys, "keys"), (temp, "temp"),
                                        (top_k, "top_k"), (top_p, "top_p")))
            nxt = sample_slots(logits, keys, temp, top_k, top_p)
            tok, pos = _advance(nxt, tok, pos, mask, rows)
            return nxt, tok, pos, cache, cache.get(MOE_PAIRS)

        def _decode_greedy(params, cache, tok, pos, mask, rows=None):
            # static fast path when every active slot is greedy: the full
            # per-slot sampler (two sorts + scatter per step) costs ~2x a
            # tiny forward on CPU; greedy traffic must not pay it
            logits, cache = _step_logits(params, cache, tok, pos, mask, rows)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            tok, pos = _advance(nxt, tok, pos, mask, rows)
            # the expert pairs' counters beside the tokens: the cache's
            # own are donated to the next step before these are fetched
            return nxt, tok, pos, cache, cache.get(MOE_PAIRS)

        def _first_token(logits, idx, keys, temp, top_k, top_p, tok, slots,
                         ends):
            # row r's token off position idx[r] of a prefill program's
            # logits [R, W, vocab] (a config with a tail layer: [R, 1,
            # vocab], idx 0), every row with its own key and knobs; and
            # the lanes' tokens with those of the rows that end put in
            last = logits[jnp.arange(logits.shape[0]), idx]
            first = sample_slots(last, keys, temp, top_k, top_p)
            return first, _put_first(tok, first, slots, ends)

        # the cache is donated: the pool's KV state is the single largest
        # buffer and every call replaces it wholesale
        self._prefill_fn = jax.jit(_prefill, donate_argnums=(1,))
        self._decode_sampled_fn = jax.jit(_decode_sampled,
                                          donate_argnums=(1,))
        self._decode_greedy_fn = jax.jit(_decode_greedy,
                                         donate_argnums=(1,))
        self._first_fn = jax.jit(_first_token)
        self._set_tok_fn = jax.jit(lambda tok, slot, token:
                                   tok.at[slot].set(token))
        # the cache's own programs (inference/cache.py)
        self._seed_fn = jax.jit(kv_cache.seed, donate_argnums=(0,))
        self._reset_state_fn = jax.jit(
            functools.partial(kv_cache.reset,
                              names=tuple(self._recurrent_pools)),
            donate_argnums=(0,))
        # no donation: the pool cache must survive an extraction
        self._extract_fn = jax.jit(functools.partial(kv_cache.extract, cfg),
                                   static_argnums=(2,))

    # ---------- pool state ----------

    def free_slots(self):
        return [i for i in range(self.max_slots) if not self.active[i]]

    def occupancy(self):
        return float(self.active.sum()) / self.max_slots

    def fits(self, prompt_len, max_new_tokens):
        """Could this request EVER be admitted? False is a permanent
        413 at submit time (the scheduler's admission capacity check),
        not backpressure. A slot holds `max_seq_len` positions: the KV
        pool's depth, or where no layer caches K and V (a slot's state
        is then the same size at every position) the length of rope's
        table."""
        return prompt_len + max_new_tokens <= self.max_seq_len

    def max_context_tokens(self):
        """The largest prompt+max_new any request may carry — the
        scalar the fleet router sheds oversized dispatches against."""
        return self.max_seq_len

    def state_pool_stats(self):
        """The recurrent-state pools' bytes on the device, in all and a
        slot (0 for a model that carries none): what a slot costs
        whatever its position, beside the KV pool's bytes a position."""
        return self.pool_stats()["state"]

    def pool_stats(self):
        """Every pool's bytes on the device by what it holds, in all and
        a slot: `global` (K and V as deep as `max_seq_len`), `ring` (K
        and V of window layers, as deep as the window and the widest
        prefill row, whatever `max_seq_len`), `state` (recurrent state:
        the same at every position). Zeros for what the model has none
        of."""
        out = {what: {"bytes": 0, "bytes_per_slot": 0}
               for what in ("global", "ring", "state")}
        rings = ring_pools(self.cfg)
        for name, (pool, _) in cache_pools(self.cfg).items():
            what = ("state" if pool.recurrent else
                    "ring" if name in rings else "global")
            out[what]["bytes"] += int(self._cache[name].nbytes)
        for entry in out.values():
            entry["bytes_per_slot"] = entry["bytes"] // self.max_slots
        return out

    def state_updates(self):
        """{recurrent pool: how a decode step updates it} ("kernel": the
        decoding lanes' state alone, where it lies; "loop": the layer of
        every lane cut out and put back), by the shapes and the platform
        (inference/decode.py, `state_updates`); empty for a model that
        carries no state."""
        return state_updates(self.cfg, self._cache,
                             kernel=self.mesh is None and device.on_tpu())

    def attention_positions(self):
        """(needed, fetched) of the decode step the cursors stand before
        (inference/decode.py, `attention_positions`): the K and V
        positions its decoding lanes' queries see over all reading
        layers, and those the program fetches for them."""
        if self._attention_reads is None:   # by the shapes: once
            self._attention_reads = attention_reads(
                self.cfg, self._cache, self.attn_impl,
                kernel=self.mesh is None and device.on_tpu())
        return attention_positions(
            self._attention_reads, np.where(self.decoding, self.pos + 1, 0))

    def compile_counts(self):
        """jit cache entries per program — each decode variant must stay
        at <= 1, prefill and first_token at the programs of
        `prefill_shapes` (all compiled by `warm_prefill`); where the rows
        ride in the decode step (`merges`) each decode variant has one
        more entry for every shape of `prefill_shapes` (the greedy ones
        compiled by `warm_prefill`) and prefill and first_token stay at
        0."""
        return {
            "prefill": self._prefill_fn._cache_size(),
            "decode_greedy": self._decode_greedy_fn._cache_size(),
            "decode_sampled": self._decode_sampled_fn._cache_size(),
            "first_token": self._first_fn._cache_size(),
            "seed_prefix": self._seed_fn._cache_size(),
            "extract_kv": self._extract_fn._cache_size(),
            "reset_state": self._reset_state_fn._cache_size(),
        }

    def kv_token_bytes(self):
        """Host bytes one cached token costs (k + v across layers) —
        the unit the prefix-cache byte budget is denominated in."""
        refuse_recurrent(self.cfg, "kv_token_bytes (a prefix cache's unit)")
        return kv_cache.kv_position_bytes(self._cache)

    # ---------- slot lifecycle ----------

    def admit(self, slot, prompt_tokens, max_new_tokens, temperature=0.0,
              top_k=None, top_p=None, rng=0):
        """Bind a request to a free slot; prefill starts with the next
        `prefill` that plans the slot. prompt_tokens: 1-D int sequence."""
        if self.active[slot]:
            raise ValueError("slot %d is busy" % slot)
        prompt = np.asarray(prompt_tokens, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if prompt.size + max_new_tokens > self.max_seq_len:
            raise ValueError(
                "prompt (%d) + max_new_tokens (%d) exceeds the engine's "
                "max_seq_len (%d)" % (prompt.size, max_new_tokens,
                                      self.max_seq_len))
        self.active[slot] = True
        self.decoding[slot] = False
        self.pos[slot] = 0
        self._prompt[slot] = prompt
        self._prefill_cursor[slot] = 0
        self._temp[slot] = float(temperature)
        self._top_k[slot] = (self._vocab if top_k is None
                             else min(int(top_k), self._vocab))
        self._top_p[slot] = 1.0 if top_p is None else float(top_p)
        self._bind_keys(slot, rng, max_new_tokens)
        self._max_new[slot] = int(max_new_tokens)
        self._emitted[slot] = 0
        self._dirty = True
        if self.recurrent:
            with self.phases("engine.state.reset", slot=int(slot)):
                self._cache = self._reset_state_fn(self._cache,
                                                   jnp.int32(slot))

    def bind_slot_context(self, slot, ctx):
        """Attach the occupant's identity/trace context ({"request_id",
        "trace", "span"} from the scheduler) to a slot. The engine is
        the system of record for slot->request binding, so engine-level
        instrumentation (the serve.prefill_chunk device timer, future
        per-slot profiling hooks) attributes device work to the request
        that bought it."""
        self._slot_ctx[slot] = dict(ctx) if ctx else None

    def slot_context(self, slot):
        """The context bound at admit time, or None for a free slot."""
        return self._slot_ctx[slot]

    def seed_prefix(self, slot, kv):
        """Copy a cached KV range ({"k": [layers, T, kv_heads,
        head_dim], "v": ...}, host arrays; `layers` is the pool's leading
        axis, passes x layers for a stack run several times: what
        `extract_kv` gave) into the slot's cache view at
        positions [0, T) and move the prefill cursor to T, so chunked
        prefill resumes at the match boundary. Must run after admit(),
        before the slot's first prefill; T must be < the slot's prompt
        length (at least one token has to prefill so final-chunk logits
        exist for first-token sampling).

        The upload pads T to a power-of-two bucket (compiles stay
        log2-bounded); pad positions hold garbage that is overwritten
        before it becomes visible — by the resumed prefill chunks up to
        the prompt end, and by the decode-step write at pos beyond it —
        the same invariant masked lanes already rely on."""
        refuse_recurrent(self.cfg, "seed_prefix (prefix-cache reuse)")
        if not self.active[slot] or self.decoding[slot]:
            raise ValueError("slot %d is not prefilling" % slot)
        if int(self._prefill_cursor[slot]) != 0:
            raise ValueError("slot %d already started prefill" % slot)
        k, v = np.asarray(kv["k"]), np.asarray(kv["v"])
        T = k.shape[1]
        prompt = self._prompt[slot]
        if not (0 < T < prompt.size):
            raise ValueError(
                "seed length %d must be in [1, prompt %d)"
                % (T, prompt.size))
        bucket = bucket_length(T, minimum=self.min_bucket,
                               maximum=self.max_seq_len)
        if bucket > T:
            pad = [(0, 0), (0, bucket - T), (0, 0), (0, 0)]
            k = np.pad(k, pad)
            v = np.pad(v, pad)
        dtype = self._cache["k"].dtype
        self._cache = self._seed_fn(
            self._cache, jnp.asarray(k, dtype), jnp.asarray(v, dtype),
            jnp.int32(slot))
        self._prefill_cursor[slot] = T
        self.pos[slot] = T
        self._dirty = True

    def extract_kv(self, slot, length):
        """The first `length` cache positions of a slot as host arrays
        ({"k": [layers, length, kv_heads, head_dim], "v": ...}; `layers`
        is the pool's leading axis: every pass's K and V of a stack run
        several times, passes x layers) — the prefix-cache insert /
        disaggregation handoff read path. The
        device slice uses a power-of-two bucket (static shape, bounded
        compiles) and trims on host."""
        refuse_recurrent(self.cfg, "extract_kv (prefix-cache insert, "
                         "disaggregated handoff)")
        if length < 1 or length > self.max_seq_len:
            raise ValueError("length %d out of range" % length)
        bucket = bucket_length(length, minimum=self.min_bucket,
                               maximum=self.max_seq_len)
        k, v = self._extract_fn(self._cache, jnp.int32(slot), bucket)
        return {"k": np.asarray(k)[:, :length],
                "v": np.asarray(v)[:, :length]}

    def admit_prefilled(self, slot, prompt_tokens, first_token, kv,
                        max_new_tokens, temperature=0.0, top_k=None,
                        top_p=None, rng=0):
        """Bind a request whose prefill ALREADY happened elsewhere (a
        dedicated prefill worker): seed the full prompt's KV, accept the
        first sampled token, and enter the decode state directly. With
        the same (prompt, knobs, rng), the continued decode emits
        exactly the tokens a local prefill would — the key schedule
        resumes at cursor 1, as after a row of `prefill` that ends its
        prompt."""
        refuse_recurrent(self.cfg, "admit_prefilled (disaggregated "
                         "handoff)")
        self.admit(slot, prompt_tokens, max_new_tokens,
                   temperature=temperature, top_k=top_k, top_p=top_p,
                   rng=rng)
        prompt = self._prompt[slot]
        k = np.asarray(kv["k"])
        if k.shape[1] != prompt.size:
            self.release(slot)
            raise ValueError("handoff kv length %d != prompt %d"
                             % (k.shape[1], prompt.size))
        bucket = bucket_length(prompt.size, minimum=self.min_bucket,
                               maximum=self.max_seq_len)
        v = np.asarray(kv["v"])
        if bucket > prompt.size:
            pad = [(0, 0), (0, bucket - prompt.size), (0, 0), (0, 0)]
            k, v = np.pad(k, pad), np.pad(v, pad)
        dtype = self._cache["k"].dtype
        self._cache = self._seed_fn(
            self._cache, jnp.asarray(k, dtype), jnp.asarray(v, dtype),
            jnp.int32(slot))
        self._prefill_cursor[slot] = prompt.size
        self.pos[slot] = prompt.size
        # one lane of the device's tokens patched, behind whatever step
        # is in flight
        self._d_tok = self._set_tok_fn(self._d_tok, jnp.int32(slot),
                                       jnp.int32(first_token))
        self._rows_done(np.asarray([slot]))

    def release(self, slot):
        """Reclaim a slot immediately; the stale cache contents stay and
        are overwritten by the next occupant's prefill (a recurrent
        state is zeroed when the next occupant is admitted)."""
        self.active[slot] = False
        self._slot_ctx[slot] = None
        self.decoding[slot] = False
        self.pos[slot] = 0  # park the masked-lane write cursor
        self._prompt[slot] = None
        self._drop_keys(slot)
        self._temp[slot] = 0.0
        self._top_k[slot] = self._vocab
        self._top_p[slot] = 1.0
        self._dirty = True

    # ---------- device work ----------

    def prefill_shapes(self, budget):
        """The (rows, width) of every prefill program that a scheduler
        with `budget` prompt tokens an iteration can call: up to
        k = budget // prefill_chunk rows (at least one, at most the
        pool's slots), r rows at every width of 1 .. k // r chunks, so
        that rows x width never passes k chunks. Three programs with the
        default budget of two chunks: [1, chunk], [1, 2 chunk],
        [2, chunk]."""
        k = max(1, int(budget) // self.prefill_chunk)
        if k * self.prefill_chunk > self.prefill_row and \
                ring_pools(self.cfg):
            raise ValueError(
                "a prefill budget of %d tokens makes rows of %d, wider than "
                "the %d that the window layers' ring leaves room for "
                "(prefill_row: two chunks)"
                % (budget, k * self.prefill_chunk, self.prefill_row))
        return [(rows, chunks * self.prefill_chunk)
                for rows in range(1, min(k, self.max_slots) + 1)
                for chunks in range(1, k // rows + 1)]

    def warm_prefill(self, budget):
        """Compile every program of `prefill_shapes(budget)`, and the
        first-token program of each, by running it on rows that hold
        nothing real, so that no request's prefill is the first call of
        a shape. Safe with requests in flight: a row with nothing real
        writes only past its slot's cursor (overwritten before it is
        seen), holds a recurrent state and ends no prompt, so the lanes'
        tokens stay as they are. Where the rows ride in the
        decode step (`merges`) the programs warmed are that step's, one
        a shape with no lane decoding, and the prefill and first-token
        programs are not compiled at all (the sampled step's shapes
        compile when a sampled request first meets them, as its
        decode-only program does)."""
        for rows, width in self.prefill_shapes(budget):
            slots = np.arange(rows, dtype=np.int32)
            none = np.zeros(rows, np.int32)
            if self.merges:
                _, _, _, self._cache, _ = self._decode_greedy_fn(
                    self.params, self._cache, self._d_tok,
                    jnp.asarray(self.pos.copy()),
                    jnp.asarray(np.zeros(self.max_slots, bool)),
                    {"tokens": np.full((rows, width), self.pad_id, np.int32),
                     "slots": slots, "start": self.pos[slots],
                     "n_real": none, "ends": np.zeros(rows, bool)})
                continue
            logits, self._cache = self._prefill_fn(
                self.params, self._cache,
                jnp.asarray(np.full((rows, width), self.pad_id, np.int32)),
                jnp.asarray(slots), jnp.asarray(self.pos[slots]),
                jnp.asarray(none))
            self._first_fn(
                logits, jnp.asarray(none),
                jnp.asarray(np.zeros((rows, 2), np.uint32)),
                jnp.asarray(self._temp[slots]),
                jnp.asarray(self._top_k[slots]),
                jnp.asarray(self._top_p[slots]),
                self._d_tok, jnp.asarray(slots),
                jnp.asarray(np.zeros(rows, bool)))

    def prefill(self, plan):
        """Run one iteration's prefill, ONE execution of the prefill
        program, and wait for its first tokens: `launch_prefill(plan)`,
        then `collect_prefill()`.

        Returns [(tokens_consumed, first_token_or_None), ...] in the
        plan's order: first_token is the request's first sampled token,
        from this program's logits, where the row ends its prompt (a
        long prompt spreads over several iterations, so that decode
        steps for the other slots interleave). The first tokens of all
        rows are fetched in one wait."""
        self.launch_prefill(plan)
        return self.collect_prefill()

    def launch_prefill(self, plan):
        """Dispatch one prefill program and wait for nothing: `plan` is
        [(slot, most_tokens), ...] over distinct prefilling slots, and
        row r of the program carries the next min(most_tokens, what is
        left) prompt tokens of its slot, padded to the program's width
        (the longest row's tokens, to a whole number of chunks). The
        first-token program rides behind it where a row ends its prompt
        and leaves that row's token at its lane on the device, so the
        slot decodes from the next `launch_decode` on, whenever the
        tokens are fetched (`collect_prefill`). Returns the tokens each
        row takes."""
        slots, start, n_real, tokens, ends = self._rows_of(plan)
        # before the program is queued: a sampled request's schedule is
        # drawn here, and its fetch would wait behind the program
        sampling = self._row_sampling(slots, ends) if ends.any() else None
        self.launches += 1
        launch = self.launches
        with self.phases("engine.prefill.dispatch", launch=launch):
            logits, self._cache = self._prefill_fn(
                self.params, self._cache, jnp.asarray(tokens),
                jnp.asarray(slots), jnp.asarray(start), jnp.asarray(n_real))
        self._advance_rows(slots, start + n_real)
        first = None
        if ends.any():
            # the rows that do not end sample too, greedily, and are not
            # read: one program whatever the rows that end
            first, self._d_tok = self._first_fn(
                logits,
                jnp.asarray(np.zeros_like(n_real) if self._tail
                            else n_real - 1),
                *map(jnp.asarray, sampling), self._d_tok,
                jnp.asarray(slots), jnp.asarray(ends))
            self._rows_done(slots[ends])
        self._prefills.append((launch, n_real, ends, first))
        return n_real.tolist()

    def collect_prefill(self):
        """[(tokens_consumed, first_token_or_None), ...] of the oldest
        prefill program launched and not yet collected, in its plan's
        order; the host waits here, once, where a row ended its prompt."""
        launch, n_real, ends, first = self._prefills.popleft()
        if first is not None:
            with self.phases("engine.first_token.fetch", awaits=launch):
                first = np.asarray(first)
        return [(int(n), int(first[r]) if ends[r] else None)
                for r, n in enumerate(n_real)]

    def _rows_of(self, plan):
        """(slots [R], start [R], n_real [R], tokens [R, W], ends [R]) of
        the rows `plan` asks for (`prefill`): row r is the next n_real[r]
        prompt tokens of slots[r] from its cursor start[r], padded to the
        width, the longest row's tokens to a whole number of chunks;
        ends[r]: the row ends its prompt."""
        slots = np.asarray([slot for slot, _ in plan], np.int32)
        if len(set(slots.tolist())) != len(plan) or not len(plan):
            # a recurrent state and causal attention make a slot's second
            # row depend on its first: one row a slot and program
            raise ValueError("a prefill program takes distinct slots, "
                             "got %r" % (slots.tolist(),))
        for slot in slots:
            if not self.active[slot] or self.decoding[slot] or \
                    self._prefill_cursor[slot] >= self._prompt[slot].size:
                raise ValueError("slot %d is not prefilling" % slot)
        start = self._prefill_cursor[slots]
        sizes = np.asarray([self._prompt[s].size for s in slots])
        n_real = np.minimum(np.asarray([most for _, most in plan]),
                            sizes - start).astype(np.int32)
        width = -(-int(n_real.max()) // self.prefill_chunk) \
            * self.prefill_chunk
        tokens = np.full((len(plan), width), self.pad_id, np.int32)
        for r, slot in enumerate(slots):
            tokens[r, :n_real[r]] = \
                self._prompt[slot][start[r]:start[r] + n_real[r]]
        return slots, start, n_real, tokens, start + n_real == sizes

    def _advance_rows(self, slots, end):
        """Move the rows' cursors to `end`."""
        self._prefill_cursor[slots] = end
        # keep pos at the prefill cursor: a mid-prefill slot rides
        # through fused decode steps as a masked lane whose write lands
        # at pos — it must fall where the NEXT chunk overwrites it, not
        # on already-written positions
        self.pos[slots] = end
        self._dirty = True

    def _row_sampling(self, slots, ends):
        """(keys [R, 2], temperature, top_k, top_p [R]) for the rows'
        first tokens: a row that ends its prompt samples with its
        request's first key (the zero key at temperature 0) and knobs;
        the others sample too, greedily, and are not read."""
        return (np.stack([self._keys_for(s) if e else _ZERO_KEY
                          for s, e in zip(slots, ends)]),
                np.where(ends, self._temp[slots], 0.0).astype(np.float32),
                self._top_k[slots], self._top_p[slots])

    def _rows_done(self, done):
        """Slots `done` ended their prompt in a program just launched,
        which leaves each one's first token at its lane on the device:
        they decode from the next launch on."""
        self.decoding[done] = True
        self._key_cursor[done] += 1
        self._dirty = True
        self._emitted_one(done)

    def _emitted_one(self, slots):
        """One more token of each of `slots` is launched: a lane that
        has its last one rides the later launches masked (its slot stays
        its request's until `release`)."""
        self._emitted[slots] += 1
        last = slots[self._emitted[slots] >= self._max_new[slots]]
        if last.size:
            self.decoding[last] = False
            self._dirty = True

    def stage_rows(self, plan):
        """`launch_prefill(plan)` for a stack whose rows ride in the
        decode step (`merges`): the rows are built (host arrays: they go
        to the device with the step's call, not one upload each), their
        slots' cursors moved, and the NEXT `launch_decode` carries them,
        one execution that reads every weight once; when that launch is
        collected `row_results` holds what `prefill` would have returned,
        and a row that ends its prompt decodes from the launch after its
        own. Returns the tokens each row takes."""
        if not self.merges:
            raise ValueError("this engine's rows take a program of their "
                             "own (prefill); its stack does not merge")
        slots, start, n_real, tokens, ends = self._rows_of(plan)
        self._advance_rows(slots, start + n_real)
        self._staged = (slots, n_real, ends, {
            "tokens": tokens, "slots": slots, "start": start,
            "n_real": n_real, "ends": ends})
        return n_real.tolist()

    def prefill_step(self, slot):
        """The one-row case of `prefill`: the next chunk of `slot`.
        Returns (tokens_consumed, first_token_or_None)."""
        return self.prefill([(slot, self.prefill_chunk)])[0]

    def launch_decode(self):
        """Dispatch one fused decode step over the WHOLE pool and wait
        for nothing. Returns the slots that decode in it (None, and
        nothing is dispatched, where no slot is in the decode state and no
        row is staged); the other slots ride through as masked lanes
        (their KV writes are overwritten before becoming visible, their
        recurrent state is held).

        With rows staged (`stage_rows`) the same program takes them
        along, under the same name, dispatch and fetch spans (with no
        lane decoding it runs for the rows alone): the rows' K and V are
        written at their slots, each row's token after its last real
        position comes back with the lanes' in the one fetch, and a row
        that ends its prompt leaves it at its lane for the next launch.
        The sampled program runs if a decoding lane or a row that ends
        its prompt is sampled.

        Steady state stays on device: tok/pos flow out of one jitted call
        and back into the next; only the per-step sampling keys upload
        (and only when a sampled slot is active). The host mirrors replay
        the same masked advance HERE, without the tokens: positions, key
        cursors, the rows that now decode and the lanes that have their
        last token launched are all known before anything is fetched, so
        the next launch can be made while this one runs. `ahead` on the
        dispatch span says whether another launch was uncollected."""
        staged, self._staged = self._staged, None
        rows = None
        if staged is not None:
            row_slots, n_real, ends, rows = staged
        decoding = np.flatnonzero(self.decoding)
        if not decoding.size and rows is None:
            return None
        if self._dirty:
            with self.phases("engine.decode.upload"):
                # copies: the mirrors move on before the program that
                # reads an upload has run, and an upload may read the
                # host's array as late as that
                self._d_pos, self._d_mask, self._d_temp, self._d_top_k, \
                    self._d_top_p = (jnp.asarray(mirror.copy()) for mirror in (
                        self.pos, self.decoding, self._temp, self._top_k,
                        self._top_p))
                self._dirty = False
        self.launches += 1
        with self.phases("engine.decode.dispatch", launch=self.launches,
                         ahead=int(bool(self._decodes))):
            if (self._temp[decoding] > 0.0).any() or (
                    rows is not None
                    and (self._temp[row_slots[ends]] > 0.0).any()):
                for i in decoding:
                    self._keys[i] = self._keys_for(i)
                if rows is not None:
                    keys, temp, top_k, top_p = self._row_sampling(
                        row_slots, ends)
                    rows = dict(rows, keys=keys, temp=temp, top_k=top_k,
                                top_p=top_p)
                out, self._d_tok, self._d_pos, self._cache, pairs = \
                    self._decode_sampled_fn(
                        self.params, self._cache, self._d_tok, self._d_pos,
                        self._d_mask, jnp.asarray(self._keys.copy()),
                        self._d_temp,
                        self._d_top_k, self._d_top_p, rows)
            else:
                out, self._d_tok, self._d_pos, self._cache, pairs = \
                    self._decode_greedy_fn(
                        self.params, self._cache, self._d_tok, self._d_pos,
                        self._d_mask, rows)
        self.pos[decoding] += 1
        self._key_cursor[decoding] += 1
        self._emitted_one(decoding)
        if rows is not None and ends.any():
            self._rows_done(row_slots[ends])
        self._decodes.append((self.launches, decoding, out, pairs,
                              None if staged is None else (n_real, ends)))
        return decoding.tolist()

    def decode_step(self):
        """{slot: token} of the OLDEST decode step launched and not yet
        collected: the one place the host waits for the device. With none
        in flight it launches one first, so a caller that keeps nothing
        ahead gets one whole fused step a call, as ever ({} where no slot
        is in the decode state and no row is staged); a loop that keeps a
        step in flight calls `launch_decode` for step n+1 and then this
        for step n. Where the step carried rows, `row_results` then holds
        [(tokens_consumed, first_token_or_None), ...] in the plan's
        order (else [])."""
        if not self._decodes and self.launch_decode() is None:
            self.row_results = []
            return {}
        launch, decoding, out, pairs, rows = self._decodes.popleft()
        with self.phases("engine.decode.fetch", awaits=launch):
            out = np.asarray(out)   # the host waits for the device here
            if pairs is not None:   # computed by now: 8 bytes more
                seen = np.asarray(pairs)
                for name, n in zip(("routed", "held"),
                                   seen - self._pairs_seen):
                    self.expert_pairs[name] += int(n)
                self._pairs_seen = seen
        self.row_results = []
        if rows is not None:   # the rows' tokens lie after the lanes'
            n_real, ends = rows
            first = out[self.max_slots:]
            self.row_results = [(int(n), int(first[r]) if ends[r] else None)
                                for r, n in enumerate(n_real)]
        return {int(i): int(out[i]) for i in decoding}
