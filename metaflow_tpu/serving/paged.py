"""Paged-KV continuous-batching engine + speculative decoding.

The slot engine (engine.py) gives every slot a private
[max_seq] stripe of one static KV block, so max_seq bounds concurrency,
short requests strand HBM, and every radix prefix hit COPIES cached KV
into the slot. This module is the vLLM-lineage fix shaped for the same
TPU constraints: keep scheduling in Python, keep every device step one
of a FIXED set of jitted programs.

Layout: a global PagePool of fixed-size KV pages
({"k": [layers, n_pages, page_tokens, kv_heads, head_dim], "v": ...})
plus a per-slot BLOCK TABLE ([B, n_blocks] int32). Block tables are
TRACED arrays, so the compiled-program set stays fixed regardless of
which pages a slot happens to hold:

  - prefill: write one prompt chunk through one slot's block-table row
    (token position p lands in page table[p // page_tokens] at offset
    p % page_tokens — a batched scatter, the paged analogue of
    engine.py's dynamic_update_slice discipline)
  - decode: advance ALL slots one token in one fused call; attention
    gathers KV back through the tables (dense gathered view at small
    depth, page-streamed online softmax — decode._streamed_attention —
    beyond it)
  - spec: verify a K-token self-drafted proposal in ONE fused call
    ([B, K+1] tokens at per-slot offsets); the host keeps the longest
    prefix of drafts the target model's own argmax agrees with, so
    greedy output is token-identical to the non-speculative path

Page 0 is a reserved SCRATCH page: free and mid-prefill slots ride
through fused steps as masked lanes whose writes land in their own
table (all zeros for a free slot → scratch) and are overwritten before
they can become visible — the same invariant engine.py relies on.

Admission is RESERVATION-based and therefore deadlock-free: admit()
allocates every page the request could ever touch
(ceil((prompt + max_new + spec_k) / page_tokens)) up front, so decode
can never strand mid-request out of memory. The concurrency win over
the slot engine is the RAGGED reservation: a slot engine charges every
request max_seq tokens of HBM; this engine charges what the request
asked for, so at equal HBM the pool admits well past B short requests.
Page exhaustion surfaces at ADMISSION (scheduler backpressure +
serve.kv.exhausted), never mid-decode.

Zero-copy prefix sharing: prefix_cache.PagedPrefixIndex registers a
finished prompt's pages under a hash chain and holds its own pool ref
per page; a later hit POINTS the new slot's block table at the same
device pages (refcount++, no KV bytes move). Only a partially-filled
tail page is copied (copy-on-write) — a shared page that would be
appended to must be private first.
"""

import os
import threading

import numpy as np

import jax
import jax.numpy as jnp

from .. import knobs, telemetry
from ..inference.decode import (
    _attn_qkv,
    _block_ffn,
    _cached_attention,
    _streamed_attention,
    bucket_length,
    pool_read,
)
from ..models import llama
from ..ops import rms_norm
from ..ops.rope import rope_frequencies
from .engine import (KeySchedules, refuse_looped, refuse_recurrent,
                     sample_slots)

DEFAULT_PAGE_TOKENS = 16


def page_tokens_from_env(default=DEFAULT_PAGE_TOKENS):
    """TPUFLOW_KV_PAGE_TOKENS: tokens per KV page (the paged engine's
    allocation granule)."""
    return max(1, knobs.get_int("TPUFLOW_KV_PAGE_TOKENS",
                                fallback=default))


def spec_k_from_env(default=0):
    """TPUFLOW_SPEC_K: speculative draft length (0 disables)."""
    return max(0, knobs.get_int("TPUFLOW_SPEC_K", fallback=default))


class PageExhaustedError(RuntimeError):
    """The page pool cannot satisfy an allocation right now. NOT a
    ValueError on purpose: the scheduler rejects ValueError admits as
    malformed, but exhaustion is backpressure — the request waits."""


class PagePool(object):
    """The global device KV page pool + host-side free list/refcounts.

    Pages are ref-counted, not owned: a slot refs every page in its
    block table, the prefix index refs every page it registers, and a
    page returns to the free list only when the LAST ref drops — which
    is exactly what makes prefix hits zero-copy-safe (eviction or slot
    release can never free a page another holder still reads).
    """

    def __init__(self, cfg, n_pages, page_tokens, dtype=None):
        if n_pages < 2:
            raise ValueError("n_pages must be >= 2 (page 0 is scratch)")
        if page_tokens < 1:
            raise ValueError("page_tokens must be >= 1")
        # pages are [layers, ...]: no pass index (serving/engine.py)
        refuse_looped(cfg, "a paged KV pool")
        dt = jnp.dtype(dtype) if dtype is not None else llama.param_dtype(cfg)
        shape = (cfg.n_layers, int(n_pages), int(page_tokens),
                 cfg.n_kv_heads, cfg.head_dim)
        self.kv = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
        self.n_pages = int(n_pages)
        self.page_tokens = int(page_tokens)
        self._lock = threading.Lock()
        self.refs = np.zeros(self.n_pages, np.int32)
        self.refs[0] = 1  # scratch: permanently held, never allocated
        self._free = list(range(self.n_pages - 1, 0, -1))
        self.alloc_count = 0       # cumulative pages handed out
        self.freed_count = 0       # cumulative pages returned

    @property
    def usable_pages(self):
        return self.n_pages - 1    # minus the scratch page

    def page_bytes(self):
        k = self.kv["k"]
        layers, _, ptok, kv_heads, head_dim = k.shape
        return 2 * layers * ptok * kv_heads * head_dim * k.dtype.itemsize

    def free_pages(self):
        with self._lock:
            return len(self._free)

    def pages_in_use(self):
        with self._lock:
            return self.usable_pages - len(self._free)

    def shared_pages(self):
        """Pages currently held by more than one owner (scratch excluded)."""
        with self._lock:
            return int((self.refs[1:] > 1).sum())

    def can_alloc(self, n):
        with self._lock:
            return len(self._free) >= n

    def alloc(self, n):
        """Take n pages (each with one ref). Raises PageExhaustedError —
        callers gate on can_alloc/can_admit, so this raising is the
        backstop, not the control flow."""
        with self._lock:
            if len(self._free) < n:
                raise PageExhaustedError(
                    "need %d pages, %d free" % (n, len(self._free)))
            pids = [self._free.pop() for _ in range(n)]
            for p in pids:
                self.refs[p] = 1
            self.alloc_count += n
            return pids

    def ref(self, pids):
        with self._lock:
            for p in pids:
                p = int(p)
                if p == 0:
                    continue
                if self.refs[p] <= 0:
                    raise RuntimeError("ref of free page %d" % p)
                self.refs[p] += 1

    def unref(self, pids):
        """Drop one ref per page; pages reaching zero return to the free
        list. Returns how many were actually freed."""
        freed = 0
        with self._lock:
            for p in pids:
                p = int(p)
                if p == 0:
                    continue
                if self.refs[p] <= 0:
                    raise RuntimeError("unref of free page %d" % p)
                self.refs[p] -= 1
                if self.refs[p] == 0:
                    self._free.append(p)
                    freed += 1
            self.freed_count += freed
        return freed

    def stats(self):
        with self._lock:
            free = len(self._free)
            shared = int((self.refs[1:] > 1).sum())
        total = self.usable_pages
        return {
            "page_tokens": self.page_tokens,
            "page_bytes": self.page_bytes(),
            "pages_total": total,
            "pages_free": free,
            "pages_in_use": total - free,
            "occupancy": round((total - free) / max(1, total), 4),
            "shared_pages": shared,
            "page_allocs": self.alloc_count,
            "page_frees": self.freed_count,
        }


def ngram_draft(context, k, max_ngram=3):
    """Prompt-lookup self-drafting (the default draft policy): find the
    most recent earlier occurrence of the longest trailing n-gram of the
    context and propose its continuation. Free — no draft model — and
    effective exactly when decode revisits earlier phrasing (templated
    output, code, retrieval-grounded answers)."""
    ctx = [int(t) for t in context]
    for ng in range(min(max_ngram, max(0, len(ctx) - 1)), 0, -1):
        tail = ctx[-ng:]
        for i in range(len(ctx) - ng - 1, -1, -1):
            if ctx[i:i + ng] == tail:
                cont = ctx[i + ng:i + ng + k]
                if cont:
                    while len(cont) < k:
                        cont.append(cont[-1])
                    return cont
    last = ctx[-1] if ctx else 0
    return [last] * k


def _paged_forward(params, tokens, pool_kv, tables, pos, cfg,
                   page_tokens, mesh=None, attn_impl="dense"):
    """decode_forward through a block table: forward T new tokens per
    row at per-row offsets `pos` [B], writing their KV into the pages
    `tables` [B, n_blocks] names and attending back through them.

    Numerics match the contiguous path: the qkv/rope and FFN halves are
    the SAME functions (decode._attn_qkv/_block_ffn), 'dense' gathers
    the table into a contiguous [B, S] view and runs the SAME
    _cached_attention, and 'chunked' streams pages through the SAME
    online-softmax accumulation (_streamed_attention)."""
    dt = llama.param_dtype(cfg)
    B, T = tokens.shape
    n_blocks = tables.shape[1]
    S = n_blocks * page_tokens
    KV, Hd = cfg.n_kv_heads, cfg.head_dim
    x = params["embed"][tokens].astype(dt)
    cos, sin = rope_frequencies(
        cfg.head_dim, S, cfg.rope_theta, dtype=dt,
        llama3_scaling=getattr(cfg, "rope_llama3_scaling", False),
    )
    abs_pos = pos[:, None] + jnp.arange(T)[None, :]          # [B, T]
    page_idx = abs_pos // page_tokens
    offs = abs_pos % page_tokens
    pids = jnp.take_along_axis(tables, page_idx, axis=1)     # [B, T]

    def layer_fn(carry, inp):
        lp, pk, pv = inp          # pk/pv: [n_pages, page_tokens, KV, Hd]
        q, k, v = _attn_qkv(cfg, cos, sin, pos, carry, lp)
        # paged cache write: token t of row b lands in page pids[b, t]
        # at offset offs[b, t] — one batched scatter per layer, the
        # block-table analogue of the vector-pos dynamic_update_slice
        with jax.named_scope("kv_cache_update"):
            pk = pk.at[pids, offs].set(k.astype(pk.dtype))
            pv = pv.at[pids, offs].set(v.astype(pv.dtype))
        if attn_impl == "chunked":
            n_chunks = (jnp.max(pos) + T + page_tokens - 1) // page_tokens

            def fetch(i):
                blk = tables[:, i]                           # [B]
                return (pk[blk], pv[blk],
                        i * page_tokens + jnp.arange(page_tokens))

            attn = _streamed_attention(q, pos, page_tokens, n_chunks,
                                       fetch)
        else:
            with jax.named_scope("decode_attention"):
                view_k = pk[tables].reshape(B, S, KV, Hd)
                view_v = pv[tables].reshape(B, S, KV, Hd)
            attn = _cached_attention(q, view_k, view_v, pos)
        out = _block_ffn(cfg, carry, attn, lp, mesh=mesh)
        return out, (pk, pv)

    with jax.named_scope("decode_layers"):
        x, (new_k, new_v) = jax.lax.scan(
            layer_fn, x, (params["layers"], pool_kv["k"], pool_kv["v"])
        )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("btd,dv->btv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    return logits, {"k": new_k, "v": new_v}


class PagedEngine(KeySchedules):
    """SlotEngine-compatible engine over a paged KV pool.

    Same API surface the scheduler drives (admit/prefill/
    decode_step/release/seed_prefix/extract_kv/admit_prefilled), plus
    the paged extensions: can_admit/fits (reservation capacity),
    seed_pages (zero-copy prefix attach), slot_prefix_pages (prefix
    registration read path), kv_stats/spec_stats.

    NOT thread-safe — exactly one scheduler loop drives it.
    """

    def __init__(self, params, cfg, max_slots=8, max_seq_len=None,
                 prefill_chunk=64, mesh=None, attn_impl="auto",
                 cache_dtype=None, pad_id=0, min_bucket=16,
                 page_tokens=None, total_pages=None, spec_k=None,
                 draft_fn=None):
        if attn_impl not in ("auto", "dense", "chunked"):
            raise ValueError("attn_impl must be 'auto', 'dense' or "
                             "'chunked', got %r" % (attn_impl,))
        # pages hold K and V only: no page table for a recurrent state
        refuse_recurrent(cfg, "the paged engine")
        # its one scan over `params["layers"]` is one pass over the stack
        refuse_looped(cfg, "the paged engine")
        self.params = params
        self.cfg = cfg
        self.max_slots = int(max_slots)
        self.max_seq_len = int(max_seq_len or cfg.max_seq_len)
        self.prefill_chunk = int(prefill_chunk)
        if self.max_slots < 1:
            raise ValueError("max_slots must be >= 1")
        if self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1, got %d"
                             % self.prefill_chunk)
        self.pad_id = int(pad_id)
        self.min_bucket = min(int(min_bucket), self.prefill_chunk)
        self.mesh = mesh
        self._vocab = cfg.vocab_size
        self.page_tokens = int(page_tokens or page_tokens_from_env())
        self.spec_k = int(spec_k_from_env() if spec_k is None else spec_k)
        if self.spec_k < 0:
            raise ValueError("spec_k must be >= 0")
        self.draft_fn = draft_fn or ngram_draft
        ptok = self.page_tokens
        # table width covers max_seq PLUS the spec margin: a verify step
        # writes up to K positions past the last accepted token, and an
        # out-of-range table index would be clamped into a LIVE page
        self.n_blocks = -(-(self.max_seq_len + self.spec_k) // ptok)
        if total_pages is None:
            # default: the slot engine's HBM shape — every slot can hold
            # a full max_seq sequence (+1 scratch page)
            total_pages = self.max_slots * self.n_blocks + 1
        self.pool = PagePool(cfg, total_pages, ptok, dtype=cache_dtype)
        if attn_impl == "auto":
            attn_impl = pool_read(self.n_blocks * ptok)
        self.attn_impl = attn_impl

        B = self.max_slots
        # host-side per-slot state (mirrors engine.py)
        self.pos = np.zeros(B, np.int32)
        self.active = np.zeros(B, bool)
        self.decoding = np.zeros(B, bool)
        self.block_tables = np.zeros((B, self.n_blocks), np.int32)
        self._n_pages = np.zeros(B, np.int32)
        self._tok = np.zeros(B, np.int32)
        self._temp = np.zeros(B, np.float32)
        self._top_k = np.full(B, self._vocab, np.int32)
        self._top_p = np.ones(B, np.float32)
        self._init_keys(B)
        self._slot_ctx = [None] * B
        self._prompt = [None] * B
        self._prefill_cursor = np.zeros(B, np.int32)
        self._max_new = np.zeros(B, np.int32)
        self._emitted = np.zeros(B, np.int32)
        self._context = [None] * B       # prompt+generated (draft source)
        self._dirty = True
        self._d_tok = self._d_pos = self._d_mask = self._d_tables = None
        self._d_temp = self._d_top_k = self._d_top_p = None
        # the engine.* spans' ledger and the launch counter, as SlotEngine
        self.phases = telemetry.PhaseLedger()
        self.launches = 0
        # what the last launch_prefill / launch_decode computed
        self._prefilled = self._stepped = None
        # counters
        self.kv_bytes_copied = 0   # host<->page copies (0 on zero-copy hits)
        self.cow_pages = 0         # partial tail pages privatized
        self.cow_bytes = 0
        self.shared_pages_attached = 0  # zero-copy pages attached to slots
        self.shared_tokens = 0     # tokens those pages carried
        self.spec_steps = 0
        self.spec_proposed = 0
        self.spec_accepted = 0

        fwd = _paged_forward

        def _prefill(params, pool_kv, chunk_tokens, table_row, start):
            logits, pool_kv = fwd(
                params, chunk_tokens, pool_kv, table_row[None],
                start[None], cfg, ptok, mesh=mesh,
                attn_impl=self.attn_impl)
            return logits, pool_kv

        def _advance(nxt, tok, pos, mask):
            tok = jnp.where(mask, nxt, tok)
            pos = pos + mask.astype(jnp.int32)
            return tok, pos

        def _decode_sampled(params, pool_kv, tok, pos, mask, tables,
                            keys, temp, top_k, top_p):
            logits, pool_kv = fwd(
                params, tok[:, None], pool_kv, tables, pos, cfg, ptok,
                mesh=mesh, attn_impl=self.attn_impl)
            nxt = sample_slots(logits[:, 0], keys, temp, top_k, top_p)
            tok, pos = _advance(nxt, tok, pos, mask)
            return nxt, tok, pos, pool_kv

        def _decode_greedy(params, pool_kv, tok, pos, mask, tables):
            logits, pool_kv = fwd(
                params, tok[:, None], pool_kv, tables, pos, cfg, ptok,
                mesh=mesh, attn_impl=self.attn_impl)
            nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            tok, pos = _advance(nxt, tok, pos, mask)
            return nxt, tok, pos, pool_kv

        def _spec_verify(params, pool_kv, toks, pos, tables):
            # toks: [B, K+1] = last emitted token + K drafts; the target
            # model scores ALL K+1 positions in one fused call and the
            # host keeps the agreeing prefix (greedy: argmax == the
            # token sequential decode would emit, so acceptance
            # preserves token identity)
            logits, pool_kv = fwd(
                params, toks, pool_kv, tables, pos, cfg, ptok,
                mesh=mesh, attn_impl=self.attn_impl)
            out = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return out, pool_kv

        def _first_token(logits, idx, key, temp, top_k, top_p):
            last = jax.lax.dynamic_index_in_dim(logits, idx, axis=1,
                                                keepdims=False)
            return sample_slots(last, key[None], temp[None], top_k[None],
                                top_p[None])[0]

        def _seed_host(pool_kv, k, v, table_row):
            # scatter a host KV range ([layers, T, kv, hd]) into the
            # slot's pages at positions [0, T) — the radix-cache /
            # disagg-handoff COPY path (zero-copy goes via seed_pages)
            T = k.shape[1]
            p_idx = jnp.arange(T) // ptok
            pids = table_row[p_idx]
            offs = jnp.arange(T) % ptok
            pk = pool_kv["k"].at[:, pids, offs].set(
                k.astype(pool_kv["k"].dtype))
            pv = pool_kv["v"].at[:, pids, offs].set(
                v.astype(pool_kv["v"].dtype))
            return {"k": pk, "v": pv}

        def _extract(pool_kv, table_row, T):
            # gather the first T positions back out (static T bucket)
            n = -(-T // ptok)
            k = pool_kv["k"][:, table_row[:n]]
            v = pool_kv["v"][:, table_row[:n]]
            L = k.shape[0]
            KV, Hd = k.shape[3], k.shape[4]
            return (k.reshape(L, n * ptok, KV, Hd)[:, :T],
                    v.reshape(L, n * ptok, KV, Hd)[:, :T])

        def _copy_page(pool_kv, src, dst):
            # copy-on-write: privatize one page before it is appended to
            L, _, T, KV, Hd = pool_kv["k"].shape
            out = {}
            for name in ("k", "v"):
                blk = jax.lax.dynamic_slice(
                    pool_kv[name], (0, src, 0, 0, 0), (L, 1, T, KV, Hd))
                out[name] = jax.lax.dynamic_update_slice(
                    pool_kv[name], blk, (0, dst, 0, 0, 0))
            return out

        self._prefill_fn = jax.jit(_prefill, donate_argnums=(1,))
        self._decode_sampled_fn = jax.jit(_decode_sampled,
                                          donate_argnums=(1,))
        self._decode_greedy_fn = jax.jit(_decode_greedy,
                                         donate_argnums=(1,))
        self._spec_fn = jax.jit(_spec_verify, donate_argnums=(1,))
        self._first_fn = jax.jit(_first_token)
        self._seed_fn = jax.jit(_seed_host, donate_argnums=(0,))
        self._extract_fn = jax.jit(_extract, static_argnums=(2,))
        self._copy_page_fn = jax.jit(_copy_page, donate_argnums=(0,))

    # ---------- pool / capacity state ----------

    def free_slots(self):
        return [i for i in range(self.max_slots) if not self.active[i]]

    def occupancy(self):
        return float(self.active.sum()) / self.max_slots

    def compile_counts(self):
        return {
            "prefill": self._prefill_fn._cache_size(),
            "decode_greedy": self._decode_greedy_fn._cache_size(),
            "decode_sampled": self._decode_sampled_fn._cache_size(),
            "spec_verify": self._spec_fn._cache_size(),
            "first_token": self._first_fn._cache_size(),
            "seed_prefix": self._seed_fn._cache_size(),
            "extract_kv": self._extract_fn._cache_size(),
            "copy_page": self._copy_page_fn._cache_size(),
        }

    def kv_token_bytes(self):
        k = self.pool.kv["k"]
        layers, _, _, kv_heads, head_dim = k.shape
        return 2 * layers * kv_heads * head_dim * k.dtype.itemsize

    def _pages_needed(self, prompt_len, max_new_tokens):
        need = prompt_len + max_new_tokens + self.spec_k
        return -(-need // self.page_tokens)

    def fits(self, prompt_len, max_new_tokens):
        """Could this request EVER be admitted (empty pool)? The
        admission-time capacity check — a False here is a permanent 413,
        not backpressure."""
        if prompt_len + max_new_tokens > self.max_seq_len:
            return False
        return (self._pages_needed(prompt_len, max_new_tokens)
                <= self.pool.usable_pages)

    def can_admit(self, prompt_len, max_new_tokens):
        """Can this request be admitted NOW (enough free pages for its
        full reservation)? A False is backpressure: the scheduler keeps
        it queued and emits serve.kv.exhausted."""
        return self.pool.can_alloc(
            self._pages_needed(prompt_len, max_new_tokens))

    def max_context_tokens(self):
        """The largest prompt+max_new any request may carry — the
        scalar the fleet router sheds oversized dispatches against."""
        return min(self.max_seq_len,
                   self.pool.usable_pages * self.page_tokens - self.spec_k)

    def kv_stats(self):
        out = {"enabled": True}
        out.update(self.pool.stats())
        out.update({
            "cow_pages": self.cow_pages,
            "cow_bytes": self.cow_bytes,
            "kv_bytes_copied": self.kv_bytes_copied,
            "shared_pages_attached": self.shared_pages_attached,
            "shared_tokens": self.shared_tokens,
            "spec_k": self.spec_k,
        })
        return out

    def spec_stats(self):
        return {
            "enabled": self.spec_k > 0,
            "k": self.spec_k,
            "steps": self.spec_steps,
            "proposed": self.spec_proposed,
            "accepted": self.spec_accepted,
            "accept_rate": round(
                self.spec_accepted / max(1, self.spec_proposed), 4),
        }

    # ---------- slot lifecycle ----------

    def admit(self, slot, prompt_tokens, max_new_tokens, temperature=0.0,
              top_k=None, top_p=None, rng=0):
        """Bind a request to a free slot and RESERVE its full page
        budget. Raises ValueError for malformed/never-fits requests and
        PageExhaustedError when the pool is momentarily out of pages
        (callers gate on can_admit)."""
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
        n_pages = self._pages_needed(prompt.size, max_new_tokens)
        if n_pages > self.pool.usable_pages:
            raise ValueError(
                "request needs %d KV pages but the pool only has %d"
                % (n_pages, self.pool.usable_pages))
        pids = self.pool.alloc(n_pages)   # may raise PageExhaustedError
        self.active[slot] = True
        self.decoding[slot] = False
        self.pos[slot] = 0
        self.block_tables[slot, :] = 0
        self.block_tables[slot, :n_pages] = pids
        self._n_pages[slot] = n_pages
        self._prompt[slot] = prompt
        self._prefill_cursor[slot] = 0
        self._temp[slot] = float(temperature)
        self._top_k[slot] = (self._vocab if top_k is None
                             else min(int(top_k), self._vocab))
        self._top_p[slot] = 1.0 if top_p is None else float(top_p)
        self._bind_keys(slot, rng, max_new_tokens)
        self._max_new[slot] = int(max_new_tokens)
        self._emitted[slot] = 0
        self._context[slot] = [int(t) for t in prompt]
        self._dirty = True

    def bind_slot_context(self, slot, ctx):
        self._slot_ctx[slot] = dict(ctx) if ctx else None

    def slot_context(self, slot):
        return self._slot_ctx[slot]

    def release(self, slot):
        """Reclaim the slot and drop its page refs. Pages the prefix
        index (or another holder) still refs survive; everything else
        returns to the free list — so every terminal path (finish,
        cancel, deadline, drain, shutdown) releases the full
        reservation."""
        n = int(self._n_pages[slot])
        if n:
            self.pool.unref(self.block_tables[slot, :n])
        self.block_tables[slot, :] = 0
        self._n_pages[slot] = 0
        self.active[slot] = False
        self._slot_ctx[slot] = None
        self.decoding[slot] = False
        self.pos[slot] = 0
        self._prompt[slot] = None
        self._drop_keys(slot)
        self._context[slot] = None
        self._temp[slot] = 0.0
        self._top_k[slot] = self._vocab
        self._top_p[slot] = 1.0
        self._dirty = True

    # ---------- prefix seeding ----------

    def seed_pages(self, slot, handle):
        """ZERO-COPY prefix attach: point the slot's block table at the
        shared pages a PagedPrefixIndex match pinned. The slot's own
        pages for those positions go straight back to the pool (the net
        reservation SHRINKS on a hit). A partially-filled tail page is
        privatized with one device page copy (copy-on-write) — the only
        KV bytes that ever move on a hit."""
        if not self.active[slot] or self.decoding[slot]:
            raise ValueError("slot %d is not prefilling" % slot)
        if int(self._prefill_cursor[slot]) != 0:
            raise ValueError("slot %d already started prefill" % slot)
        prompt = self._prompt[slot]
        if not (0 < handle.length < prompt.size):
            raise ValueError(
                "seed length %d must be in [1, prompt %d)"
                % (handle.length, prompt.size))
        n_full = len(handle.pages)
        if n_full:
            own = self.block_tables[slot, :n_full]
            self.pool.ref(handle.pages)
            self.pool.unref(own)
            self.block_tables[slot, :n_full] = handle.pages
            self.shared_pages_attached += n_full
            self.shared_tokens += n_full * self.page_tokens
        if handle.partial is not None:
            src, _ntok = handle.partial
            dst = int(self.block_tables[slot, n_full])
            self.pool.kv = self._copy_page_fn(
                self.pool.kv, jnp.int32(src), jnp.int32(dst))
            self.cow_pages += 1
            self.cow_bytes += self.pool.page_bytes()
        self._prefill_cursor[slot] = handle.length
        self.pos[slot] = handle.length
        self._dirty = True

    def slot_prefix_pages(self, slot, prompt_len):
        """The pages holding the first prompt_len cached tokens of a
        slot: (full_page_ids, tail_page_id_or_None) — what the prefix
        index registers after a finished prefill."""
        ptok = self.page_tokens
        n_full = prompt_len // ptok
        full = [int(p) for p in self.block_tables[slot, :n_full]]
        tail = None
        if prompt_len % ptok:
            tail = int(self.block_tables[slot, n_full])
        return full, tail

    def seed_prefix(self, slot, kv):
        """Host-KV copy seeding (radix-cache / compat path): upload a
        cached [layers, T, kv, hd] range into the slot's pages at
        positions [0, T). The zero-copy path is seed_pages."""
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
        self._upload_kv(slot, k, v, T)
        self._prefill_cursor[slot] = T
        self.pos[slot] = T
        self._dirty = True

    def _upload_kv(self, slot, k, v, T):
        bucket = bucket_length(T, minimum=self.min_bucket,
                               maximum=self.n_blocks * self.page_tokens)
        if bucket > T:
            pad = [(0, 0), (0, bucket - T), (0, 0), (0, 0)]
            k, v = np.pad(k, pad), np.pad(v, pad)
        dtype = self.pool.kv["k"].dtype
        self.pool.kv = self._seed_fn(
            self.pool.kv, jnp.asarray(k, dtype), jnp.asarray(v, dtype),
            jnp.asarray(self.block_tables[slot]))
        self.kv_bytes_copied += int(k.nbytes) + int(v.nbytes)

    def extract_kv(self, slot, length):
        """The first `length` cached positions of a slot as host arrays
        — the disaggregation-handoff read path (a paged prefix cache
        never needs this: it shares pages in place)."""
        if length < 1 or length > self.max_seq_len:
            raise ValueError("length %d out of range" % length)
        bucket = bucket_length(length, minimum=self.min_bucket,
                               maximum=self.n_blocks * self.page_tokens)
        k, v = self._extract_fn(
            self.pool.kv, jnp.asarray(self.block_tables[slot]), bucket)
        return {"k": np.asarray(k)[:, :length],
                "v": np.asarray(v)[:, :length]}

    def admit_prefilled(self, slot, prompt_tokens, first_token, kv,
                        max_new_tokens, temperature=0.0, top_k=None,
                        top_p=None, rng=0):
        """Bind a request prefilled ELSEWHERE (disaggregation): seed the
        full prompt KV into fresh pages and enter decode directly."""
        self.admit(slot, prompt_tokens, max_new_tokens,
                   temperature=temperature, top_k=top_k, top_p=top_p,
                   rng=rng)
        prompt = self._prompt[slot]
        k = np.asarray(kv["k"])
        if k.shape[1] != prompt.size:
            self.release(slot)
            raise ValueError("handoff kv length %d != prompt %d"
                             % (k.shape[1], prompt.size))
        self._upload_kv(slot, k, np.asarray(kv["v"]), prompt.size)
        self._prefill_cursor[slot] = prompt.size
        self.decoding[slot] = True
        self.pos[slot] = prompt.size
        self._tok[slot] = int(first_token)
        self._key_cursor[slot] = 1
        self._emitted[slot] = 1
        self._context[slot].append(int(first_token))
        self._dirty = True

    # ---------- device work ----------

    def warm_prefill(self, budget):
        """SlotEngine.warm_prefill's place in the scheduler's contract:
        this engine's chunk programs (one slot, power-of-two buckets)
        compile on first use, as they always have."""

    def prefill(self, plan):
        """SlotEngine.prefill's contract, [(slot, most_tokens), ...] ->
        [(tokens_consumed, first_token_or_None), ...], answered with
        this engine's own program: one slot and one chunk an execution,
        so a row of several chunks is several executions."""
        out = []
        for slot, most in plan:
            consumed, first = 0, None
            while consumed < most and first is None:
                n, first = self.prefill_step(slot)
                consumed += n
            out.append((consumed, first))
        return out

    # a speculative burst's acceptance, and so every slot's next position,
    # is read off the fetched tokens: this engine runs nothing ahead. In
    # the scheduler's contract (SlotEngine's launch and collect) its
    # launch computes the program whole and its collect hands that out
    runs_ahead = False

    def launch_prefill(self, plan):
        self._prefilled = self.prefill(plan)
        return [n for n, _ in self._prefilled]

    def collect_prefill(self):
        out, self._prefilled = self._prefilled, None
        return out

    def launch_decode(self):
        """The slots that decode in the step, which is computed here
        (None where there is none); `decode_step` hands it out."""
        decoding = np.flatnonzero(self.decoding).tolist()
        if not decoding:
            return None
        self._stepped = self._step(decoding)
        return decoding

    def decode_step(self):
        """One fused step over the whole pool. Returns {slot: token}
        (plain path) or {slot: [tokens]} (speculative path — up to
        spec_k+1 tokens per slot per step). The scheduler treats both
        shapes uniformly. What `launch_decode` computed, where it was
        called first."""
        if self._stepped is None and self.launch_decode() is None:
            return {}
        out, self._stepped = self._stepped, None
        return out

    def prefill_step(self, slot):
        """Write the next prompt chunk of `slot` through its block
        table: returns (tokens_consumed, first_token_or_None)."""
        if not self.active[slot] or self.decoding[slot]:
            raise ValueError("slot %d is not prefilling" % slot)
        prompt = self._prompt[slot]
        start = int(self._prefill_cursor[slot])
        end = min(start + self.prefill_chunk, prompt.size)
        chunk = prompt[start:end]
        # the pad bucket must stay inside the slot's RESERVED pages: a
        # write through a table index past n_pages would be clamped into
        # the last page and silently corrupt live positions
        bucket = bucket_length(
            chunk.size, minimum=self.min_bucket,
            maximum=min(self.prefill_chunk,
                        int(self._n_pages[slot]) * self.page_tokens
                        - start))
        if bucket > chunk.size:
            chunk = np.concatenate([
                chunk, np.full(bucket - chunk.size, self.pad_id, np.int32)])
        # before the program is queued: a sampled request's schedule is
        # drawn here, and its fetch would wait behind the program
        key = self._keys_for(slot) if end == prompt.size else None
        self.launches += 1
        launch = self.launches
        with self.phases("engine.prefill.dispatch", launch=launch):
            logits, self.pool.kv = self._prefill_fn(
                self.params, self.pool.kv, jnp.asarray(chunk)[None],
                jnp.asarray(self.block_tables[slot]), jnp.int32(start))
        self._prefill_cursor[slot] = end
        self.pos[slot] = end
        self._dirty = True
        consumed = end - start
        if end < prompt.size:
            return consumed, None
        first = self._first_fn(
            logits, jnp.int32(prompt.size - 1 - start),
            jnp.asarray(key),
            jnp.float32(self._temp[slot]), jnp.int32(self._top_k[slot]),
            jnp.float32(self._top_p[slot]))
        with self.phases("engine.first_token.fetch", awaits=launch):
            first = int(first)
        self.decoding[slot] = True
        self.pos[slot] = prompt.size
        self._tok[slot] = first
        self._key_cursor[slot] += 1
        self._emitted[slot] = 1
        self._context[slot].append(first)
        self._dirty = True
        return consumed, first

    def _stage(self):
        if self._dirty:
            with self.phases("engine.decode.upload"):
                self._d_tok = jnp.asarray(self._tok)
                self._d_pos = jnp.asarray(self.pos)
                self._d_mask = jnp.asarray(self.decoding)
                self._d_tables = jnp.asarray(self.block_tables)
                self._d_temp = jnp.asarray(self._temp)
                self._d_top_k = jnp.asarray(self._top_k)
                self._d_top_p = jnp.asarray(self._top_p)
                self._dirty = False

    def _step(self, decoding):
        """The fused step for the slots `decoding`, whole: dispatched and
        fetched (`ahead=0` on its dispatch span: nothing is ever left
        uncollected here)."""
        sampled = any(self._temp[i] > 0.0 for i in decoding)
        if self.spec_k > 0 and not sampled:
            return self._spec_decode_step(decoding)
        self._stage()
        self.launches += 1
        with self.phases("engine.decode.dispatch", launch=self.launches,
                         ahead=0):
            if sampled:
                for i in decoding:
                    self._keys[i] = self._keys_for(i)
                out, self._d_tok, self._d_pos, self.pool.kv = \
                    self._decode_sampled_fn(
                        self.params, self.pool.kv, self._d_tok,
                        self._d_pos, self._d_mask, self._d_tables,
                        jnp.asarray(self._keys), self._d_temp,
                        self._d_top_k, self._d_top_p)
            else:
                out, self._d_tok, self._d_pos, self.pool.kv = \
                    self._decode_greedy_fn(
                        self.params, self.pool.kv, self._d_tok,
                        self._d_pos, self._d_mask, self._d_tables)
        with self.phases("engine.decode.fetch", awaits=self.launches):
            out = np.asarray(out)
        tokens = {}
        for i in decoding:
            tokens[i] = int(out[i])
            self._tok[i] = out[i]
            self.pos[i] += 1
            self._key_cursor[i] += 1
            self._emitted[i] += 1
            self._context[i].append(int(out[i]))
        return tokens

    def _spec_decode_step(self, decoding):
        """Speculative decode: draft K tokens per decoding slot
        (self-drafting — prompt-lookup by default, draft_fn pluggable),
        verify all K+1 positions in ONE fused call, keep the prefix the
        target model agrees with. Greedy-only (sampled slots fall back
        to the plain step before reaching here), so acceptance is exact
        token identity: out[j] IS the token sequential greedy decode
        would emit after toks[:j+1]."""
        K = self.spec_k
        B = self.max_slots
        drafts = np.zeros((B, K), np.int32)
        for i in decoding:
            d = self.draft_fn(self._context[i], K)
            drafts[i] = np.asarray(d[:K], np.int32)
        toks = np.concatenate([self._tok[:, None], drafts], axis=1)
        self._stage()
        self.launches += 1
        with self.phases("engine.decode.dispatch", launch=self.launches,
                         ahead=0):
            out, self.pool.kv = self._spec_fn(
                self.params, self.pool.kv, jnp.asarray(toks), self._d_pos,
                self._d_tables)
        with self.phases("engine.decode.fetch", awaits=self.launches):
            out = np.asarray(out)
        tokens = {}
        for i in decoding:
            remaining = int(self._max_new[i] - self._emitted[i])
            n_acc = 0
            while n_acc < K and drafts[i, n_acc] == out[i, n_acc]:
                n_acc += 1
            n_emit = max(1, min(n_acc + 1, remaining))
            emitted = [int(t) for t in out[i, :n_emit]]
            tokens[i] = emitted
            self._tok[i] = emitted[-1]
            self.pos[i] += n_emit
            self._key_cursor[i] += n_emit
            self._emitted[i] += n_emit
            self._context[i].extend(emitted)
            self.spec_proposed += K
            self.spec_accepted += n_acc
        self.spec_steps += 1
        # pos/tok advanced HOST-side (acceptance is data-dependent):
        # restage before the next fused call
        self._dirty = True
        return tokens
