"""Kernel dispatch layer: Pallas on TPU, memory-sane chunked jnp elsewhere.

Models call these entry points only.  Selection:
  * backend="pallas"  — force the Pallas kernel (tests use interpret=True);
  * backend="jnp"     — force the chunked jnp path;
  * backend=None      — Pallas iff running on TPU.

The chunked jnp fallbacks are structured exactly like the kernels (block-tiled
online softmax / chunked recurrences), so the dry-run's compiled HLO has the
same asymptotic memory behaviour the TPU kernels deliver.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


__all__ = ["flash_attention", "decode_attention", "paged_attention",
           "span_attention", "paged_span_attention", "wkv6", "rglru_scan"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pick(backend: Optional[str]) -> str:
    if backend is not None:
        return backend
    return "pallas" if _on_tpu() else "jnp"


# ---------------------------------------------------------------------------
# flash attention (prefill / train)
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                    q_block: int = 512, kv_block: int = 1024,
                    causal_skip: bool = True, backend: Optional[str] = None,
                    interpret: bool = False):
    """q: (B, Sq, H, D); k/v: (B, Skv, Hk, D) -> (B, Sq, H, D).

    ``causal_skip``: statically skip fully-masked KV blocks (halves FLOPs for
    causal attention; toggleable for the perf study).
    """
    if _pick(backend) == "pallas":
        return _flash_pallas(q, k, v, causal, window, q_block, kv_block,
                             interpret)
    return _flash_jnp(q, k, v, causal=causal, window=window,
                      q_block=q_block, kv_block=kv_block, causal_skip=causal_skip)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_pallas(q, k, v, causal, window, q_block, kv_block, interpret):
    from repro.kernels.flash_attention import flash_attention_pallas

    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  q_block=q_block, kv_block=kv_block,
                                  interpret=interpret)


def _flash_pallas_fwd(q, k, v, causal, window, q_block, kv_block, interpret):
    out = _flash_pallas(q, k, v, causal, window, q_block, kv_block, interpret)
    return out, (q, k, v)


def _flash_pallas_bwd(causal, window, q_block, kv_block, interpret, res, g):
    # the kernel has no backward pass: differentiate its chunked jnp twin
    # (same masked online softmax), recomputing the forward from q/k/v
    def twin(q, k, v):
        return _flash_jnp(q, k, v, causal=causal, window=window,
                          q_block=q_block, kv_block=kv_block, causal_skip=True)

    return jax.vjp(twin, *res)[1](g)


_flash_pallas.defvjp(_flash_pallas_fwd, _flash_pallas_bwd)


def _flash_jnp(q, k, v, *, causal, window, q_block, kv_block, causal_skip):
    B, Sq, H, D = q.shape
    Skv, Hk = k.shape[1], k.shape[2]
    G = H // Hk
    orig_sq = Sq
    qb = min(q_block, Sq)
    kb = min(kv_block, Skv)
    pad_q = (-Sq) % qb
    pad_k = (-Skv) % kb
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        Sq += pad_q
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        Skv += pad_k
    nq, nk = Sq // qb, Skv // kb
    offset = (Skv - pad_k) - (Sq - pad_q)          # align sequence ends
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    # kv laid out as (nk, B, kb, Hk, D) for scan
    k_r = jnp.moveaxis(k.reshape(B, nk, kb, Hk, D), 1, 0)
    v_r = jnp.moveaxis(v.reshape(B, nk, kb, Hk, D), 1, 0)

    def q_block_attend(qi, i):
        """qi: (B, qb, H, D) — online softmax over kv blocks."""
        q_pos = i * qb + jnp.arange(qb) + offset

        def kv_step(carry, inp):
            m, l, acc = carry
            kj, vj, j = inp
            k_pos = j * kb + jnp.arange(kb)
            kje = jnp.repeat(kj, G, axis=2)        # (B, kb, H, D)
            vje = jnp.repeat(vj, G, axis=2)
            s = jnp.einsum("bqhd,bkhd->bqhk", qi.astype(jnp.float32),
                           kje.astype(jnp.float32)) * scale
            mask = jnp.ones((qb, kb), bool)
            if causal:
                mask &= k_pos[None, :] <= q_pos[:, None]
            if window is not None:
                mask &= k_pos[None, :] > q_pos[:, None] - window
            mask &= (k_pos < Skv - pad_k)[None, :]
            s = jnp.where(mask[None, :, None, :], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=-1))
            # guard fully-masked rows
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(mask[None, :, None, :], p, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum("bqhk,bkhd->bqhd", p,
                                                     vje.astype(jnp.float32))
            return (m_new, l, acc), None

        m0 = jnp.full((B, qb, H), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, qb, H), jnp.float32)
        a0 = jnp.zeros((B, qb, H, D), jnp.float32)
        if causal and causal_skip:
            # statically restrict to kv blocks visible to this q block; the
            # restricted range is still a lax.scan (differentiable, small HLO)
            hi = min(nk, (i * qb + qb - 1 + offset) // kb + 1)
            lo = 0
            if window is not None:
                lo = max(0, (i * qb + offset - window + 1) // kb)
            hi = max(hi, lo + 1)
            (m, l, acc), _ = jax.lax.scan(
                kv_step, (m0, l0, a0),
                (k_r[lo:hi], v_r[lo:hi], jnp.arange(lo, hi)))
        else:
            (m, l, acc), _ = jax.lax.scan(
                kv_step, (m0, l0, a0), (k_r, v_r, jnp.arange(nk)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.astype(q.dtype)

    outs = [q_block_attend(q[:, i * qb:(i + 1) * qb], i) for i in range(nq)]
    out = jnp.concatenate(outs, axis=1)
    return out[:, :orig_sq]


# ---------------------------------------------------------------------------
# decode attention (one new token vs long KV)
# ---------------------------------------------------------------------------

def decode_attention(q, k, v, lengths, *, kv_block: int = 2048,
                     backend: Optional[str] = None, interpret: bool = False):
    """q: (B, 1, H, D); k/v: (B, Smax, Hk, D); lengths: (B,)."""
    if _pick(backend) == "pallas":
        from repro.kernels.decode_attention import decode_attention_pallas

        return decode_attention_pallas(q, k, v, lengths, kv_block=kv_block,
                                       interpret=interpret)
    return _decode_jnp(q, k, v, lengths)


def _decode_jnp(q, k, v, lengths):
    """Explicit max/exp/sum form: with a sequence-sharded KV cache GSPMD turns
    the reductions into small all-reduces (flash-decode semantics)."""
    B, _, H, D = q.shape
    Hk = k.shape[2]
    G = H // Hk
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    q32 = q[:, 0].astype(jnp.float32)                              # (B, H, D)
    qg = q32.reshape(B, Hk, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k.astype(jnp.float32)) * scale
    valid = (jnp.arange(k.shape[1])[None, :] < lengths[:, None])[:, None, None, :]
    s = jnp.where(valid, s, -jnp.inf)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - jax.lax.stop_gradient(m))
    p = jnp.where(valid, p, 0.0)
    num = jnp.einsum("bhgk,bkhd->bhgd", p, v.astype(jnp.float32))
    den = p.sum(axis=-1, keepdims=True)
    out = (num / jnp.maximum(den, 1e-30)).reshape(B, 1, H, D)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# paged decode attention (one new token vs a block-pooled KV cache)
# ---------------------------------------------------------------------------

def paged_attention(q, k_pool, v_pool, table, lengths, *,
                    backend: Optional[str] = None, interpret: bool = False):
    """q: (B, 1, H, D); k_pool/v_pool: (P, page, Hk, D); table: (B, n_pages)
    int32 physical page indices (entries >= P are unmapped sentinels);
    lengths: (B,) valid KV lengths.

    The Pallas kernel walks the block table with scalar-prefetch DMA (no
    contiguous copy ever materializes); the jnp fallback gathers the mapped
    pages into a (B, n_pages*page, Hk, D) view and reuses the flash-decode
    reduction — same masked-softmax semantics, so the two agree bitwise on
    the valid positions.
    """
    if _pick(backend) == "pallas":
        from repro.kernels.paged_attention import paged_attention_pallas

        return paged_attention_pallas(q, k_pool, v_pool, table, lengths,
                                      interpret=interpret)
    return _paged_jnp(q, k_pool, v_pool, table, lengths)


def _paged_jnp(q, k_pool, v_pool, table, lengths):
    P, ps = k_pool.shape[0], k_pool.shape[1]
    B, n_tab = table.shape
    safe = jnp.clip(table, 0, P - 1)                   # sentinels clip; the
    k = jnp.take(k_pool, safe, axis=0)                 # length mask hides them
    v = jnp.take(v_pool, safe, axis=0)                 # (B, n_tab, ps, Hk, D)
    k = k.reshape(B, n_tab * ps, *k.shape[3:])
    v = v.reshape(B, n_tab * ps, *v.shape[3:])
    return _decode_jnp(q, k, v, lengths)


# ---------------------------------------------------------------------------
# span decode attention (a short run of S new tokens in one dispatch —
# speculative-decode verification)
# ---------------------------------------------------------------------------

def span_attention(q, k, v, base_len, *, backend: Optional[str] = None,
                   interpret: bool = False):
    """q: (B, S, H, D); k/v: (B, Smax, Hk, D); base_len: (B,) valid KV length
    *before* the span.  Query position ``i`` attends to ``base_len + i + 1``
    keys (causal within the span; the span's own K/V must already be written
    into the buffers).

    Implemented as an unrolled loop of per-position :func:`_decode_jnp` calls
    (S is the speculation depth — single digits), so every position computes
    the *identical* masked-softmax expression as the one-token decode path and
    the two agree bitwise.  There is no Pallas variant; the TPU backend also
    takes this path (XLA fuses the unrolled positions into one dispatch).
    """
    del backend, interpret
    S = q.shape[1]
    outs = [_decode_jnp(q[:, i:i + 1], k, v, base_len + (i + 1))
            for i in range(S)]
    return jnp.concatenate(outs, axis=1)                       # (B, S, H, D)


def paged_span_attention(q, k_pool, v_pool, table, base_len, *,
                         backend: Optional[str] = None,
                         interpret: bool = False):
    """Paged variant of :func:`span_attention` — q: (B, S, H, D);
    k_pool/v_pool: (P, page, Hk, D); table: (B, n_pages) int32; base_len: (B,)
    valid KV length before the span.  One page gather serves all S positions;
    per-position masking reuses the flash-decode reduction bit-for-bit.
    """
    del backend, interpret
    P, ps = k_pool.shape[0], k_pool.shape[1]
    B, n_tab = table.shape
    safe = jnp.clip(table, 0, P - 1)
    k = jnp.take(k_pool, safe, axis=0).reshape(B, n_tab * ps,
                                               *k_pool.shape[2:])
    v = jnp.take(v_pool, safe, axis=0).reshape(B, n_tab * ps,
                                               *v_pool.shape[2:])
    S = q.shape[1]
    outs = [_decode_jnp(q[:, i:i + 1], k, v, base_len + (i + 1))
            for i in range(S)]
    return jnp.concatenate(outs, axis=1)


# ---------------------------------------------------------------------------
# RWKV6 WKV (chunked)
# ---------------------------------------------------------------------------

def wkv6(r, k, v, w, u, state=None, *, chunk: int = 32,
         backend: Optional[str] = None, interpret: bool = False):
    """RWKV6 recurrence. r/k/v/w: (B, T, H, D); u: (H, D); state: (B, H, D, D).

    Chunked: intra-chunk pair decays are exact (pairwise log-space
    differences), inter-chunk via the carried (D, D) state.
    """
    if _pick(backend) == "pallas":
        from repro.kernels.rwkv6_scan import wkv6_pallas

        return wkv6_pallas(r, k, v, w, u, state=state, chunk=chunk, interpret=interpret)
    return _wkv6_jnp(r, k, v, w, u, state, chunk)


def _wkv6_jnp(r, k, v, w, u, state, chunk):
    B, T, H, D = r.shape
    if state is None:
        state = jnp.zeros((B, H, D, D), jnp.float32)
    orig_t = T
    pad = (-T) % chunk
    if pad:
        r, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) for x in (r, k, v))
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0), (0, 0)), constant_values=1.0)
        T += pad
    n = T // chunk
    C = chunk
    r_, k_, v_, w_ = (jnp.moveaxis(x.reshape(B, n, C, H, D), 1, 0).astype(jnp.float32)
                      for x in (r, k, v, w))
    u32 = u.astype(jnp.float32)
    lw = jnp.log(jnp.clip(w_, 1e-12, 1.0))        # (n, B, C, H, D) logs ≤ 0

    def chunk_step(S, inp):
        rc, kc, vc, lwc = inp                      # (B, C, H, D)
        Lc = jnp.cumsum(lwc, axis=1)               # inclusive Σ_{j≤t} log w_j
        L_excl = Lc - lwc                          # exclusive Σ_{j<t}
        # state contribution: r_t · diag(exp(L_excl_t)) S
        q_dec = rc * jnp.exp(L_excl)
        o_state = jnp.einsum("bchd,bhde->bche", q_dec, S)
        # intra-chunk: pair decay exp(L_excl[t] − L[s]) for s < t (≤ 1, stable)
        pair = L_excl[:, :, None, :, :] - Lc[:, None, :, :, :]   # (B, C, C, H, D)
        tri = (jnp.arange(C)[:, None] > jnp.arange(C)[None, :])  # strict lower
        amp = jnp.where(tri[None, :, :, None, None], jnp.exp(pair), 0.0)
        att = jnp.einsum("bthd,btshd,bshd->bths", rc, amp, kc)
        o_intra = jnp.einsum("bths,bshe->bthe", att, vc)
        # current token via bonus u: (Σ_d r_td u_d k_td) · v_t
        o_diag = jnp.einsum("bchd,bchd,bche->bche", rc, u32 * kc, vc)
        out = o_state + o_intra + o_diag
        # state update: S' = diag(exp(L_C)) S + Σ_s diag(exp(L_C − L_s)) k_sᵀ v_s
        LC = Lc[:, -1:, :, :]                      # (B, 1, H, D)
        k_dec = kc * jnp.exp(LC - Lc)
        S = jnp.exp(LC[:, 0])[..., None] * S + jnp.einsum("bshd,bshe->bhde", k_dec, vc)
        return S, out

    state, outs = jax.lax.scan(chunk_step, state, (r_, k_, v_, lw))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, T, H, D)[:, :orig_t]
    return out.astype(r.dtype), state


# ---------------------------------------------------------------------------
# RG-LRU (chunked / associative scan)
# ---------------------------------------------------------------------------

def rglru_scan(x, a_log, state=None, *, chunk: int = 256,
               backend: Optional[str] = None, interpret: bool = False):
    """Diagonal gated linear recurrence.  x/a_log: (B, T, W); state: (B, W)."""
    if _pick(backend) == "pallas":
        from repro.kernels.rglru_scan import rglru_pallas

        return rglru_pallas(x, a_log, state=state, chunk=chunk, interpret=interpret)
    return _rglru_jnp(x, a_log, state)


def _rglru_jnp(x, a_log, state):
    B, T, W = x.shape
    if state is None:
        state = jnp.zeros((B, W), jnp.float32)
    al = a_log.astype(jnp.float32)
    gated = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * al), 1e-12)) * x.astype(jnp.float32)
    # associative scan over (a, b): (a2, b2) ∘ (a1, b1) = (a1·a2, a2·b1 + b2)
    a = jnp.exp(al)
    # fold the carried state into the first step
    gated = gated.at[:, 0].add(a[:, 0] * state)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, gated), axis=1)
    return h.astype(x.dtype), h[:, -1].astype(jnp.float32)
