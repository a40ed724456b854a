"""Serving engine: slot-based continuous batching over prefill/decode steps.

One engine serves one model.  The KV cache is a fixed (max_slots, ...) pytree;
requests are admitted into free slots (their prefilled cache rows are
scattered into the slots), all active slots decode in lockstep, and finished
requests retire immediately so new ones can be admitted mid-stream — the vLLM
iteration-level scheduling idea, realized with jit-static shapes.

The generation path is **fused on-device**: one jitted ``jax.lax.scan``
(:attr:`ServingEngine._decode_k`) generates ``decode_block`` tokens per host
dispatch with on-device greedy sampling and per-slot active/EOS/max_new
masking, returning only a ``(K, max_slots)`` token block plus validity masks
to the host — the host syncs once per K tokens instead of once per token.
The KV cache is **donated** through the decode and insert jits
(``donate_argnums``), so decode updates the cache buffers in place instead of
copying the full ``(max_slots, max_len, ...)`` pytree every step.  Decode
attention reads only a power-of-two **horizon** slice of the cache covering
the longest live sequence plus the K-token block (the seq axis is bucketed
like prompt lengths, so jit variants stay bounded): on CPU the decode step is
memory-bound on the K/V read, and short streams in a long-``max_len`` engine
stop paying for buffer they have not filled.  Admission is **batched**: every
request admitted in one serving tick is padded to a shared length bucket,
prefilled in a single call, and scatter-inserted into its slot by one fused
``_insert_many``.

The pre-fusion driver survives as :meth:`ServingEngine.serve_stepwise` (one
host round-trip per token, per-request prefill) — the parity reference for
``tests/test_engine.py`` and the baseline leg of
``benchmarks/engine_decode.py``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.tokenizer import ByteTokenizer
from repro.kernels import ops
from repro.models.transformer import Model
from repro.serving import tracing
from repro.serving.generation import GenerationConfig
from repro.serving.kvpool import PagedCacheManager


def sample_tokens(logits, keys, temp, top_k, top_p):
    """Temperature/top-k/top-p sampling, one token per row.

    ``logits``: (B, V) float32; ``keys``: (B, 2) uint32 per-row PRNG keys
    (already folded with the stream position); ``temp``/``top_k``/``top_p``:
    (B,) per-row knobs.  Rows with ``temp <= 0`` take the greedy argmax —
    bit-identical to the plain argmax path, so mixed greedy/sampled batches
    are safe.  The filtering order is standard: temperature-scale, sort
    descending, intersect the top-k rank mask with the nucleus mask (the
    rank-0 token is always kept), then sample categorically over the
    survivors.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def one(lg, key, t, k, p):
        scaled = lg.astype(jnp.float32) / jnp.maximum(t, 1e-6)
        order = jnp.argsort(-scaled)                    # descending, stable
        sl = scaled[order]
        ranks = jnp.arange(sl.shape[-1], dtype=jnp.int32)
        keep = jnp.where(k > 0, ranks < k, True)
        probs = jax.nn.softmax(sl)
        keep &= (jnp.cumsum(probs) - probs) < p         # mass *before* token
        idx = jax.random.categorical(key, jnp.where(keep, sl, -jnp.inf))
        return order[idx].astype(jnp.int32)

    sampled = jax.vmap(one)(logits, keys, temp, top_k, top_p)
    return jnp.where(temp > 0.0, sampled, greedy)


def _fold_keys(keys, n):
    """Per-row ``fold_in``: key i is folded with stream position ``n[i]`` —
    the determinism pivot (see ``GenerationConfig``): position, never the
    dispatch step, so outputs are invariant to K/slot/replica placement."""
    return jax.vmap(jax.random.fold_in)(keys, n)


@dataclass
class Request:
    rid: int
    tokens: list[int]
    max_new: int = 32
    out_tokens: list[int] = field(default_factory=list)
    done: bool = False
    enqueued_at: float = field(default_factory=time.time)
    started_at: Optional[float] = None
    finished_at: Optional[float] = None
    on_tokens: Optional[Callable[[list[int], bool], None]] = None
    # ^ streaming hook: called once per host dispatch that appended to
    #   out_tokens — the prefill's first token at admission, then each fused
    #   decode block (so the cadence is exactly ``decode_block`` tokens).
    #   Args: the freshly appended token ids and whether the request is done.
    #   Called from the serving thread; sinks must not block.
    gen: Optional[GenerationConfig] = None
    # ^ unified generation knobs; None (the deprecation shim) synthesizes a
    #   greedy config from the legacy ``max_new`` field — bit-identical to
    #   the pre-GenerationConfig engine.


class ServingEngine:
    """Continuous-batching engine for a single model on the local device(s).

    ``decode_block`` is K, the number of tokens generated per host dispatch
    by the fused scan; K=1 degenerates to one sync per token (still fused
    sampling/masking on device).  Greedy outputs are bit-identical for every
    K (parity-tested) — K only trades host round-trips against up to K−1
    wasted lockstep steps on the final block of a stream.

    ``paged=True`` swaps the contiguous ``(max_slots, max_len, ...)`` KV
    pytree for a paged layout: block *pools* of ``page_size``-token pages,
    one per layer stack in the paged kernel's order (see
    ``Model.paged_cache_specs``), plus host-side per-slot block tables
    (:class:`PagedCacheManager`).
    Batched admission prefills the shared batch-prompt prefix once and maps
    every sibling slot's table onto the same physical pages (refcounted);
    a slot gets a private copy only when decode first appends into a shared
    page (copy-on-write, performed as one fused page-copy before the decode
    dispatch).  Greedy outputs are bit-identical to the contiguous path:
    causal attention makes the shared prefix K/V independent of what follows
    it, and reads beyond a slot's length are masked to exact zeros in both
    layouts.  ``share_prefix=False`` keeps paging but gives every slot
    private pages (the CoW machinery then never fires).
    """

    def __init__(self, model: Model, params, *, max_slots: int = 8, max_len: int = 1024,
                 decode_block: int = 8, paged: bool = False, page_size: int = 16,
                 share_prefix: bool = True,
                 eos_id: int = ByteTokenizer.eos, pad_id: int = ByteTokenizer.pad):
        self.model = model
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        self.decode_block = max(1, int(decode_block))
        self.paged = bool(paged)
        self.page_size = int(page_size)
        self.share_prefix = bool(share_prefix)
        self.eos_id = eos_id
        self.pad_id = pad_id
        if self.paged:
            self.kv = PagedCacheManager(max_slots, max_len, self.page_size)
            self.cache = model.init_paged_cache(self.kv.alloc.n_pages,
                                                self.page_size, max_slots)
        else:
            self.kv = None
            self.cache = model.init_cache(max_slots, max_len)
        self.slot_req: list[Optional[Request]] = [None] * max_slots
        self.tok = ByteTokenizer()          # engine-owned: one instance, all paths
        # telemetry: host dispatches vs device steps (benchmarks/engine_decode.py)
        self.n_decode_calls = 0             # host→device decode dispatches
        self.n_decode_steps = 0             # device decode steps they executed
        self.n_prefill_calls = 0            # admission prefill dispatches
        self.kv_pages_in_use_sum = 0        # pages mapped at each paged decode
        #   dispatch, summed over dispatches (see kv_occupancy)

        @jax.jit
        def _decode(params, tokens, cache):
            # the pre-fusion reference step: deliberately NOT donated — one
            # full-cache copy per token is part of what serve_stepwise
            # baselines (benchmarks/engine_decode.py measures against it)
            return model.decode_step(params, tokens, cache)

        self._decode = _decode

        @partial(jax.jit, static_argnums=(3,))
        def _prefill(params, tokens, lengths, max_len):
            return model.prefill(params, tokens, max_len, lengths=lengths)

        self._prefill = _prefill

        @partial(jax.jit, donate_argnums=(0,))
        def _insert_many(cache, rows, slots):
            # scatter B freshly prefilled cache rows into their slots in one
            # fused update; a slot index of max_slots marks a padding row of
            # the admission bucket and mode="drop" discards it
            def ins_axis(axis):
                def ins(dst, src):
                    src = src.astype(dst.dtype)
                    if axis == 0:
                        return dst.at[slots].set(src, mode="drop")
                    return dst.at[:, slots].set(src, mode="drop")
                return ins
            out = {}
            for key, sub in cache.items():
                # "blocks" leaves are layer-stacked: batch dim is axis 1
                axis = 1 if key == "blocks" else 0
                out[key] = jax.tree.map(ins_axis(axis), sub, rows[key])
            return out

        self._insert_many = _insert_many

        def _seq_axis(leaf) -> Optional[int]:
            # K/V cache leaves are (..., seq, kv_heads, head_dim) with
            # seq == max_len for global attention (window/ring caches and
            # recurrent states are smaller and never match) — the only
            # leaves the decode horizon may shrink
            if leaf.ndim >= 3 and leaf.shape[-3] == self.max_len:
                return leaf.ndim - 3
            return None

        @partial(jax.jit, static_argnums=(0,), static_argnames=("sample",),
                 donate_argnums=(2,))
        def _decode_k(horizon, params, cache, last_tok, active, n_out, limit,
                      keys=None, temp=None, top_k=None, top_p=None, *,
                      sample=False):
            """K decode steps fused in one dispatch.

            Device state per slot: ``last_tok`` (next input token), ``active``
            (still generating), ``n_out`` (tokens emitted so far, prefill
            first token included), ``limit`` (min(max_new, max_len−1−prompt)).
            Returns the updated cache (donated — in-place), the final active
            mask, the (K, max_slots) greedy token block and a validity mask
            (``valid[k, i]`` ⇔ slot i was active entering step k, i.e. token
            ``toks[k, i]`` belongs to its stream).  Inactive slots decode
            garbage into their own cache rows, exactly like the stepwise
            driver — admission overwrites the whole row.

            ``horizon`` (static) bounds the K/V positions attention can see:
            the scan runs on a ``[:horizon]`` slice of the seq axis and the
            slice is written back into the donated full buffer afterwards.
            The host guarantees horizon ≥ the largest live sequence length
            + K, so the restriction is exact (greedy outputs are parity-
            tested against the full-horizon stepwise path); a retired slot's
            garbage stream may run past the horizon, where its writes drop
            out of bounds — admission rebuilds the row from prefill anyway.

            ``sample`` (static) switches the on-device token choice from
            greedy argmax to :func:`sample_tokens`; ``keys`` (max_slots, 2)
            are per-slot PRNG base keys folded with the position counter
            ``n`` carried through the scan — token t of a stream is a pure
            function of (seed, t).  With ``sample=False`` the traced graph
            is exactly the greedy one (the sampling args are never touched),
            so all-greedy serving stays bit-identical to the pre-sampling
            engine.
            """
            def shrink(leaf):
                ax = _seq_axis(leaf)
                if ax is None or horizon >= self.max_len:
                    return leaf
                return jax.lax.slice_in_dim(leaf, 0, horizon, axis=ax)

            def merge(full, small):
                if full.shape == small.shape:
                    return small
                return jax.lax.dynamic_update_slice_in_dim(
                    full, small, 0, axis=full.ndim - 3)

            small = jax.tree.map(shrink, cache)

            def step(carry, _):
                sc, last, act, n = carry
                logits, sc = model.decode_step(params, last[:, None], sc)
                if sample:
                    nxt = sample_tokens(logits[:, 0], _fold_keys(keys, n),
                                        temp, top_k, top_p)
                else:
                    nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
                n = n + act.astype(jnp.int32)
                done = act & ((nxt == self.eos_id) | (n >= limit))
                last = jnp.where(act, nxt, last)
                return (sc, last, act & ~done, n), (nxt, act)

            (small, _last, act, _n), (toks, valid) = jax.lax.scan(
                step, (small, last_tok, active, n_out), None,
                length=self.decode_block)
            cache = jax.tree.map(merge, cache, small)
            return cache, act, toks, valid

        self._decode_k = _decode_k

        @partial(jax.jit, donate_argnums=(0,))
        def _insert_pages(cache, rows, slots, dst_pages):
            """Scatter freshly prefilled rows into the page pools.

            ``rows`` is a prefill cache whose K/V leaves are ``(L, B, Lp, Hk,
            hd)`` (a ``rem{j}`` layer's without the leading L) with ``Lp`` a
            page multiple; each row splits into ``Lp/page_size`` logical
            pages, packed into the pool's layout by ``ops.pack_rows`` (the
            rows move, never the pool), and lands at the physical page
            ``dst_pages[b, j]`` (sentinel ≥ n_pages drops the write —
            padding rows of the admission bucket, and shared prefix pages the
            owner row already wrote).  The rows' length scatters per slot
            into the cache's one ``len``, as in the contiguous
            ``_insert_many``.
            """
            flat = dst_pages.reshape(-1)

            # every layer of the rows holds the same prompt lengths
            lens = (rows["blocks"]["b0"]["len"][0] if "blocks" in rows
                    else rows["rem0"]["len"])
            out = {"len": cache["len"].at[slots].set(lens, mode="drop")}

            def ins(dst, src):                      # one K or V stack
                pages = ops.pack_rows(src.astype(dst.dtype), self.page_size)
                return dst.at[:, flat].set(pages, mode="drop")

            for key, sub in cache.items():
                if key == "blocks":
                    out[key] = {b: {kv: ins(sub[b][kv], rows[key][b][kv])
                                    for kv in ("k", "v")} for b in sub}
                elif key != "len":                  # a stack of one layer
                    out[key] = {kv: ins(sub[kv], rows[key][kv][None])
                                for kv in ("k", "v")}
            return out

        self._insert_pages = _insert_pages

        @partial(jax.jit, donate_argnums=(0,))
        def _fork_pages(cache, src_pages, dst_pages):
            """Copy-on-write device copy: physical page ``src[i]`` → ``dst[i]``
            in every layer's pools.  Sentinel entries (the fork list is padded
            to a size bucket) clip their read to the last real page and drop
            their write."""
            def cp(leaf):
                if leaf.ndim < 5:
                    return leaf                     # length leaf: no pages
                safe = jnp.minimum(src_pages, leaf.shape[1] - 1)
                return leaf.at[:, dst_pages].set(leaf[:, safe], mode="drop")

            return jax.tree.map(cp, cache)

        self._fork_pages = _fork_pages

        @partial(jax.jit, static_argnames=("sample",), donate_argnums=(1,))
        def _decode_k_paged(params, cache, table, last_tok, active, n_out,
                            limit, keys=None, temp=None, top_k=None,
                            top_p=None, *, sample=False):
            """Paged twin of ``_decode_k``: same fused K-step scan, same
            donated in-place cache, but attention walks ``table`` (already
            sliced host-side to the bucketed horizon's column count, which
            bounds both per-step attention cost and jit variants — the paged
            analogue of the contiguous horizon slice).  No seq-axis shrink:
            the pool is shared, the table IS the horizon.  Sampling args as
            in ``_decode_k``.
            """
            def step(carry, _):
                sc, last, act, n = carry
                logits, sc = model.decode_step(params, last[:, None], sc,
                                               table=table)
                if sample:
                    nxt = sample_tokens(logits[:, 0], _fold_keys(keys, n),
                                        temp, top_k, top_p)
                else:
                    nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
                n = n + act.astype(jnp.int32)
                done = act & ((nxt == self.eos_id) | (n >= limit))
                last = jnp.where(act, nxt, last)
                return (sc, last, act & ~done, n), (nxt, act)

            (cache, _last, act, _n), (toks, valid) = jax.lax.scan(
                step, (cache, last_tok, active, n_out), None,
                length=self.decode_block)
            return cache, act, toks, valid

        self._decode_k_paged = _decode_k_paged

        @jax.jit
        def _pick_tokens(logits, keys, n, temp, top_k, top_p):
            # one sampled token per row at stream position ``n`` — the
            # admission first-token and stepwise-driver analogue of the
            # in-scan sampling (identical fold-in, so fused/stepwise agree)
            return sample_tokens(logits, _fold_keys(keys, n), temp, top_k,
                                 top_p)

        self._pick_tokens = _pick_tokens

    # ------------------------------------------------------------------
    def _bucket_len(self, n: int) -> int:
        """Pad prompt lengths to power-of-two buckets to bound jit variants."""
        b = 16
        while b < n:
            b *= 2
        return min(b, self.max_len)

    def _bucket_count(self, n: int) -> int:
        """Pad admission batch sizes to power-of-two buckets (≤ max_slots)."""
        b = 1
        while b < n:
            b *= 2
        return min(b, self.max_slots)

    def _sibling_share_pages(self, owner: list[int], sib: list[int]) -> int:
        """How many of the owner's prompt pages a sibling admitted in the
        same batch may point at.  Page j is shareable iff the two prompts
        agree on every position of it the SIBLING will ever read unmasked —
        i.e. the common token prefix reaches ``min(len(sib), page_end)``.
        So a sibling that is a prefix of the owner (identical prompts
        included) shares even the final partial page (positions past its
        length are masked, and its first decode append CoW-forks the page);
        past a genuine divergence the floor applies."""
        common = 0
        for a, b in zip(owner, sib):
            if a != b:
                break
            common += 1
        if common == len(sib):
            return -(-common // self.page_size)
        return common // self.page_size

    def _admit_batch(self, reqs: list[Request], slots: list[int]):
        """Admit ``reqs`` into ``slots`` with ONE prefill + ONE insert: all
        prompts pad to a shared length bucket, the batch count pads to a
        power-of-two bucket (padding rows scatter out of bounds and drop).

        Paged mode pads prompts to a page multiple and builds the sharing
        plan first: the owner (first request) allocates and writes all its
        prompt pages; every sibling points its table at the owner's common-
        prefix pages (refcount bump, write dropped via sentinel) and
        allocates only the pages past the shared prefix.  The shared prefix
        K/V is therefore prefilled B times but *stored* once — causal
        attention makes each row's prefix K/V bit-identical, so which row's
        bytes land is immaterial.
        """
        B = self._bucket_count(len(reqs))
        L = self._bucket_len(max(len(r.tokens) for r in reqs))
        if self.paged:
            ps = self.page_size
            Lp = -(-L // ps) * ps           # page-multiple prompt buffer
            seqs = [r.tokens for r in reqs] + [[self.pad_id]] * (B - len(reqs))
            tokens, lengths = self.tok.pad_batch(seqs, Lp)
            dst = np.full((B, Lp // ps), self.kv.alloc.n_pages, np.int32)
            owner_pages: list[int] = []
            for idx, (req, slot) in enumerate(zip(reqs, slots)):
                n_need = -(-len(req.tokens) // ps)
                if idx == 0:
                    pages = self.kv.alloc.alloc_n(n_need)
                    owner_pages = pages
                    dst[idx, :n_need] = pages
                else:
                    n_sh = 0
                    if self.share_prefix:
                        n_sh = min(self._sibling_share_pages(reqs[0].tokens,
                                                             req.tokens),
                                   n_need, len(owner_pages))
                    pages = [self.kv.alloc.share(p) for p in owner_pages[:n_sh]]
                    priv = self.kv.alloc.alloc_n(n_need - n_sh)
                    dst[idx, n_sh:n_need] = priv
                    pages = pages + priv
                self.kv.map_slot(slot, pages)
            logits, rows = self._prefill(self.params, jnp.asarray(tokens),
                                         jnp.asarray(lengths), Lp)
            self.n_prefill_calls += 1
            slot_arr = np.full(B, self.max_slots, dtype=np.int32)
            slot_arr[: len(reqs)] = slots
            self.cache = self._insert_pages(self.cache, rows,
                                            jnp.asarray(slot_arr),
                                            jnp.asarray(dst))
        else:
            seqs = [r.tokens for r in reqs] + [[self.pad_id]] * (B - len(reqs))
            tokens, lengths = self.tok.pad_batch(seqs, L)
            slot_arr = np.full(B, self.max_slots, dtype=np.int32)
            slot_arr[: len(reqs)] = slots
            logits, rows = self._prefill(self.params, jnp.asarray(tokens),
                                         jnp.asarray(lengths), self.max_len)
            self.n_prefill_calls += 1
            self.cache = self._insert_many(self.cache, rows, jnp.asarray(slot_arr))
        gens = [self._gen_of(r) for r in reqs]
        if any(not g.greedy for g in gens):
            # sampled first token: stream position 0, same fold-in as every
            # later position — padding rows of the bucket stay greedy
            rows_n = int(logits.shape[0])
            keys = np.zeros((rows_n, 2), dtype=np.uint32)
            temp = np.zeros(rows_n, dtype=np.float32)
            top_k = np.zeros(rows_n, dtype=np.int32)
            top_p = np.ones(rows_n, dtype=np.float32)
            for j, (req, g) in enumerate(zip(reqs, gens)):
                if g.greedy:
                    continue
                keys[j] = self._base_key(req)
                temp[j] = g.temperature
                top_k[j] = g.top_k
                top_p[j] = g.top_p
            with tracing.span("engine.fetch"):
                first = np.asarray(self._pick_tokens(
                    logits[:, 0], jnp.asarray(keys),
                    jnp.zeros(rows_n, jnp.int32), jnp.asarray(temp),
                    jnp.asarray(top_k), jnp.asarray(top_p)))
        else:
            with tracing.span("engine.fetch"):
                first = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
        now = time.time()
        for req, slot, f in zip(reqs, slots, first):
            self.slot_req[slot] = req
            req.started_at = now
            req.finished_at = None      # clear stale timing on re-admission
            req.done = False
            req.out_tokens.append(int(f))
            if int(f) == self.eos_id:
                self._retire(slot)
            if req.on_tokens is not None:
                req.on_tokens([int(f)], req.done)

    def _admit_free(self, queue: list[Request]):
        """Fill every free slot from the queue (FCFS, slot-index order); an
        EOS-at-prefill retirement frees its slot for the next round."""
        while queue:
            free = [i for i, r in enumerate(self.slot_req) if r is None]
            if not free:
                return
            n = min(len(free), len(queue))
            with tracing.span("engine.admit"):
                self._admit_batch([queue.pop(0) for _ in range(n)], free[:n])

    def _retire(self, slot: int):
        req = self.slot_req[slot]
        if req is not None:
            req.done = True
            req.finished_at = time.time()
        self.slot_req[slot] = None
        if self.paged:
            # drop the slot's table references; only pages no sibling still
            # shares actually return to the free list
            self.kv.release_slot(slot)

    def _active_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.slot_req) if r is not None]

    @staticmethod
    def _gen_of(req: Request) -> GenerationConfig:
        """Effective generation config: the request's, or (deprecation shim)
        a greedy one synthesized from the legacy ``max_new`` field."""
        if req.gen is not None:
            return req.gen
        return GenerationConfig(max_new=req.max_new)

    @staticmethod
    def _base_key(req: Request) -> np.ndarray:
        """Per-request PRNG base key (cached on the request — admission to
        retirement, every driver folds the same base with the position)."""
        key = getattr(req, "_prng_base", None)
        if key is None:
            seed = ServingEngine._gen_of(req).seed
            key = np.asarray(jax.random.PRNGKey(seed), dtype=np.uint32)
            req._prng_base = key
        return key

    def _slot_state(self):
        """Host view of the device decode state, rebuilt from the requests
        each fused call — the host bookkeeping stays authoritative."""
        last = np.zeros(self.max_slots, dtype=np.int32)
        act = np.zeros(self.max_slots, dtype=bool)
        n_out = np.zeros(self.max_slots, dtype=np.int32)
        limit = np.ones(self.max_slots, dtype=np.int32)
        for i, req in enumerate(self.slot_req):
            if req is None:
                continue
            last[i] = req.out_tokens[-1]
            act[i] = True
            n_out[i] = len(req.out_tokens)
            limit[i] = min(self._gen_of(req).max_new,
                           self.max_len - 1 - len(req.tokens))
        return last, act, n_out, limit

    def _sampling_state(self):
        """Per-slot sampling arrays for the fused/stepwise dispatch; rows of
        greedy requests stay at (temp=0, key=0) and take the argmax branch
        inside :func:`sample_tokens`.  ``sample`` is False iff every live
        request is greedy — the dispatch then omits the sampling args
        entirely and runs the exact pre-sampling graph."""
        keys = np.zeros((self.max_slots, 2), dtype=np.uint32)
        temp = np.zeros(self.max_slots, dtype=np.float32)
        top_k = np.zeros(self.max_slots, dtype=np.int32)
        top_p = np.ones(self.max_slots, dtype=np.float32)
        sample = False
        for i, req in enumerate(self.slot_req):
            if req is None:
                continue
            g = self._gen_of(req)
            if g.greedy:
                continue
            sample = True
            keys[i] = self._base_key(req)
            temp[i] = g.temperature
            top_k[i] = g.top_k
            top_p[i] = g.top_p
        return sample, keys, temp, top_k, top_p

    def _prepare_paged(self, active: list[int], horizon: int,
                       offset: int = 0):
        """Page maintenance before one paged decode dispatch: grow every
        active slot's table to cover its next ``decode_block`` writes, CoW-
        fork any still-shared page in that write range (one fused device
        copy for the whole tick), and upload the table sliced to the
        horizon's column count — the slice is what bounds per-step attention
        cost, playing the role of the contiguous path's seq-axis shrink.

        ``offset`` shifts the first write position relative to the default
        ``prompt + emitted``: the speculative engine passes −1 because its
        dispatches re-feed the last emitted token (whose KV was rolled back
        or never written), so the write range starts one position earlier."""
        ps = self.page_size
        cap = self.kv.pages_per_slot * ps
        src: list[int] = []
        dst: list[int] = []
        for i in active:
            req = self.slot_req[i]
            ln = len(req.tokens) + len(req.out_tokens) + offset
            end = min(ln + self.decode_block, cap)
            self.kv.extend_slot(i, -(-end // ps))
            s, d = self.kv.fork_for_write(i, ln, end)
            src += s
            dst += d
        if src:
            # pad the fork list to a power-of-two bucket (bounds jit
            # variants); sentinel pads clip their read and drop their write
            nb = 1
            while nb < len(src):
                nb *= 2
            sentinel = self.kv.alloc.n_pages
            sa = np.full(nb, sentinel, np.int32)
            da = np.full(nb, sentinel, np.int32)
            sa[: len(src)] = src
            da[: len(dst)] = dst
            self.cache = self._fork_pages(self.cache, jnp.asarray(sa),
                                          jnp.asarray(da))
        n_cols = min(self.kv.pages_per_slot, -(-horizon // ps))
        return jnp.asarray(self.kv.table[:, :n_cols])

    def kv_occupancy(self) -> dict:
        """KV memory and dispatch telemetry for the serving plane
        (WindowReport, the metrics registry, the bench).

        Contiguous engines report the committed buffer size (every slot owns
        a full ``max_len`` row whether it uses it or not).  Paged engines
        report the pool they reserve (``pages_reserved``, ``reserved_bytes``)
        beside live and peak *mapped* bytes (distinct physical pages times
        per-page bytes summed across layers), and ``pages_in_use_sum``, the
        pages mapped at each decode dispatch summed over dispatches:
        ``pages_in_use_sum / (decode_calls * pages_reserved)`` is the share
        of the reservation that decode used.  Both report their dispatch
        counts (``decode_calls``, ``prefill_calls``).
        """
        kv_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(self.cache)
                       if leaf.ndim >= 3)
        calls = {"decode_calls": self.n_decode_calls,
                 "prefill_calls": self.n_prefill_calls}
        if not self.paged:
            return {"paged": False, "kv_bytes": kv_bytes,
                    "peak_kv_bytes": kv_bytes, **calls}
        occ = self.kv.occupancy()
        page_bytes = kv_bytes // max(occ["pages_reserved"], 1)
        occ.update(paged=True, page_bytes=page_bytes,
                   kv_bytes=occ["pages_used"] * page_bytes,
                   peak_kv_bytes=occ["peak_pages"] * page_bytes,
                   reserved_bytes=kv_bytes,
                   pages_in_use_sum=self.kv_pages_in_use_sum, **calls)
        return occ

    # ------------------------------------------------------------------
    def serve(self, requests: list[Request], greedy: bool = True) -> list[Request]:
        """Run all requests to completion with continuous batching.

        Fused driver: one batched admission per tick, then one
        ``_decode_k`` dispatch generates up to ``decode_block`` tokens for
        every active slot before the host looks at the results again.
        """
        queue = list(requests)
        with tracing.span("engine.serve", inv=requests[0].rid if requests else -1):
            while queue or self._active_slots():
                self._admit_free(queue)
                active = self._active_slots()
                if not active:
                    continue
                with tracing.span("engine.prepare"):
                    last, act, n_out, limit = self._slot_state()
                    sample, keys, temp, top_k, top_p = self._sampling_state()
                    kw = {}
                    if sample:
                        kw = dict(keys=jnp.asarray(keys), temp=jnp.asarray(temp),
                                  top_k=jnp.asarray(top_k), top_p=jnp.asarray(top_p),
                                  sample=True)
                    live = max(len(self.slot_req[i].tokens)
                               + len(self.slot_req[i].out_tokens) for i in active)
                    horizon = min(self.max_len,
                                  self._bucket_len(live + self.decode_block))
                    if self.paged:
                        table = self._prepare_paged(active, horizon)
                        self.kv_pages_in_use_sum += self.kv.alloc.pages_in_use
                with tracing.span("engine.decode"):
                    if self.paged:
                        self.cache, act_f, toks, valid = self._decode_k_paged(
                            self.params, self.cache, table, jnp.asarray(last),
                            jnp.asarray(act), jnp.asarray(n_out), jnp.asarray(limit),
                            **kw)
                    else:
                        self.cache, act_f, toks, valid = self._decode_k(
                            horizon, self.params, self.cache, jnp.asarray(last),
                            jnp.asarray(act), jnp.asarray(n_out), jnp.asarray(limit),
                            **kw)
                self.n_decode_calls += 1
                self.n_decode_steps += self.decode_block
                with tracing.span("engine.fetch"):
                    toks = np.asarray(toks)
                    valid = np.asarray(valid)
                    act_f = np.asarray(act_f)
                with tracing.span("engine.emit"):
                    for i in active:
                        req = self.slot_req[i]
                        block = [int(t) for t in toks[valid[:, i], i]]
                        req.out_tokens.extend(block)
                        if not act_f[i]:
                            self._retire(i)
                        if req.on_tokens is not None:
                            req.on_tokens(block, req.done)
        return requests

    def serve_stepwise(self, requests: list[Request]) -> list[Request]:
        """Pre-fusion reference driver: per-request prefill admission and one
        host round-trip (dispatch + argmax sync) per generated token.  Kept
        for the fused-path parity tests and as the baseline leg of
        ``benchmarks/engine_decode.py``; outputs are bit-identical to
        :meth:`serve` under greedy sampling.  Contiguous-layout only — it is
        the *reference*, and paging it would leave no fixed point to test
        against."""
        if self.paged:
            raise RuntimeError("serve_stepwise is the contiguous parity "
                               "reference; use serve() on a paged engine")
        queue = list(requests)
        while queue or self._active_slots():
            for slot in range(self.max_slots):
                if self.slot_req[slot] is None and queue:
                    self._admit_batch([queue.pop(0)], [slot])
            active = self._active_slots()
            if not active:
                continue
            # lockstep decode across all slots (inactive slots decode garbage
            # into their own slot state; they are reset at admission)
            last = np.full((self.max_slots, 1), self.pad_id, dtype=np.int32)
            for i in active:
                last[i, 0] = self.slot_req[i].out_tokens[-1]
            logits, self.cache = self._decode(self.params, jnp.asarray(last), self.cache)
            self.n_decode_calls += 1
            self.n_decode_steps += 1
            sample, keys, temp, top_k, top_p = self._sampling_state()
            if sample:
                n_arr = np.zeros(self.max_slots, dtype=np.int32)
                for i in active:
                    n_arr[i] = len(self.slot_req[i].out_tokens)
                nxt = np.asarray(self._pick_tokens(
                    logits[:, 0], jnp.asarray(keys), jnp.asarray(n_arr),
                    jnp.asarray(temp), jnp.asarray(top_k),
                    jnp.asarray(top_p)))
            else:
                nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
            for i in active:
                req = self.slot_req[i]
                req.out_tokens.append(int(nxt[i]))
                total_len = len(req.tokens) + len(req.out_tokens)
                if (int(nxt[i]) == self.eos_id
                        or len(req.out_tokens) >= self._gen_of(req).max_new
                        or total_len >= self.max_len - 1):
                    self._retire(i)
                if req.on_tokens is not None:
                    req.on_tokens([int(nxt[i])], req.done)
        return requests

    # convenience --------------------------------------------------------
    def generate_text(self, prompts: list[str], max_new: int = 32,
                      gen: Optional[GenerationConfig] = None) -> list[str]:
        """``gen`` supersedes the legacy ``max_new`` kwarg when given (the
        deprecation shim keeps ``max_new=`` callers bit-identical)."""
        if gen is not None:
            max_new = gen.max_new
        reqs = [Request(rid=i, tokens=self.tok.encode(p), max_new=max_new,
                        gen=gen)
                for i, p in enumerate(prompts)]
        self.serve(reqs)
        outs = []
        for r in reqs:
            ids = r.out_tokens
            if self.eos_id in ids:
                ids = ids[: ids.index(self.eos_id)]
            outs.append(self.tok.decode(ids))
        return outs
