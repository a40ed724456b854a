"""Attention block: GQA, RoPE/M-RoPE, QK-norm, sliding window, cross-attention,
full-sequence (train/prefill) and cached single-token decode paths.

KV caches:
  * global attention — full-length buffer (B, S_max, Hk, hd) + per-sequence
    lengths; with ``kv_seq_shard`` the sequence dim is sharded over the model
    axis and the decode softmax becomes a flash-decode partial reduction.
  * sliding-window attention — ring buffer (B, window, Hk, hd): keys are
    RoPE-rotated at write time, so ring order does not matter.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.kernels import ops
from repro.models.layers import Builder, apply_dense, apply_rope, init_dense


def init_attention(b: Builder, cfg: ModelConfig, cross: bool = False):
    d, hd = cfg.d_model, cfg.head_dim
    H, Hk = cfg.n_heads, cfg.n_kv_heads
    p = {
        "q": init_dense(b, d, H * hd, ("embed", "heads"), bias=cfg.qkv_bias and not cross,
                        bias_axes=("heads",)),
        "k": init_dense(b, d, Hk * hd, ("embed", "kv_heads"), bias=cfg.qkv_bias and not cross,
                        bias_axes=("kv_heads",)),
        "v": init_dense(b, d, Hk * hd, ("embed", "kv_heads"), bias=cfg.qkv_bias and not cross,
                        bias_axes=("kv_heads",)),
        "o": init_dense(b, H * hd, d, ("heads", "embed")),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = b.param((hd,), ("head_dim",), init="ones")
        p["k_norm"] = b.param((hd,), ("head_dim",), init="ones")
    return p


def _heads(x, n, hd):
    return x.reshape(*x.shape[:-1], n, hd)


def _qk_normalize(p, q, k, eps):
    def rms(x, scale):
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)
    if "q_norm" in p:
        q = rms(q, p["q_norm"].astype(jnp.float32))
        k = rms(k, p["k_norm"].astype(jnp.float32))
    return q, k


def attention_full(p, cfg: ModelConfig, x, positions, *, causal: bool = True,
                   window: Optional[int] = None, kv_source=None, flags=None):
    """Full-sequence attention.  x: (B, S, d); positions: (B, S) or (B, 3, S).

    ``kv_source``: encoder output for cross-attention (no RoPE, not causal).
    Returns (out, (k, v)) — the projected K/V so prefill can fill caches.
    """
    B, S, d = x.shape
    H, Hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cross = kv_source is not None
    src = kv_source if cross else x
    fl = flags or {}
    constrain0 = fl.get("constrain")
    q = _heads(apply_dense(p["q"], x), H, hd)
    k = _heads(apply_dense(p["k"], src), Hk, hd)
    v = _heads(apply_dense(p["v"], src), Hk, hd)
    if constrain0 is not None:
        # pin PRODUCTION layout (batch-sharded, seq-replicated) right at the
        # projections — otherwise the seq-sharded cache out-sharding
        # back-propagates into the matmuls and GSPMD gathers per layer
        k = constrain0(k, ("batch", None, "kv_heads", "head_dim"))
        v = constrain0(v, ("batch", None, "kv_heads", "head_dim"))
    if not cross:
        q, k = _qk_normalize(p, q, k, cfg.norm_eps)
        if cfg.rope_type != "none":
            q = apply_rope(q, positions, cfg)
            k = apply_rope(k, positions, cfg)
    fl = flags or {}
    # Pin the attention compute layout: batch-sharded, seq-REPLICATED K/V/Q.
    # Without this, a seq-sharded cache out-sharding propagates backward into
    # K/V production and GSPMD all-gathers (B, S, Hk, hd) per layer; with it,
    # writing a seq-sharded cache is a free local slice (§Perf cell 3).
    constrain = fl.get("constrain")
    if constrain is not None:
        # q additionally shards its SEQ over the model axis when the config
        # enables kv_seq_shard ("kv_seq" rule): each rank computes its own q
        # rows against the full (replicated) K/V — sequence-parallel flash
        # attention without K/V gathers (§Perf cell 3, iteration 2).
        q = constrain(q, ("batch", None, "heads", "head_dim"))
        k = constrain(k, ("batch", None, "kv_heads", "head_dim"))
        v = constrain(v, ("batch", None, "kv_heads", "head_dim"))
    out = ops.flash_attention(
        q, k, v, causal=causal and not cross, window=window,
        q_block=fl.get("q_block", 512), kv_block=fl.get("kv_block", 1024),
        causal_skip=fl.get("causal_skip", True), backend=fl.get("backend"))
    out = apply_dense(p["o"], out.reshape(B, S, H * hd))
    return out, (k, v)


def _paged_write(cache, table, positions, k_new, v_new, layer):
    """Write new K/V ``(B, S, Hk, hd)`` at ``positions`` (B, S) into layer
    ``layer`` of a paged stack through each slot's block table ``table``
    (B, n_cols); entries >= P are unmapped and the write drops.  Returns
    the two pools."""
    B, S = positions.shape
    P = cache["k"].shape[1]
    ps = ops.pool_geometry(cache["k"], k_new.shape[-1])[2]
    n_cols = table.shape[1]
    page = positions // ps
    phys = jnp.where(page < n_cols,
                     table[jnp.arange(B)[:, None], jnp.minimum(page, n_cols - 1)], P)
    flat = lambda a: a.reshape(B * S, *a.shape[2:])
    return tuple(ops.write_tokens(cache[kv], layer, flat(phys), flat(positions % ps),
                                  flat(new))
                 for kv, new in (("k", k_new), ("v", v_new)))


def attention_decode(p, cfg: ModelConfig, x, cache, *, window: Optional[int] = None,
                     kv_source_cache=None, flags=None):
    """One-token decode.  x: (B, 1, d); cache: {"k","v","len"(B,)}.

    With a ring-buffer cache (sliding window) the new KV overwrites slot
    ``len % window``.  With ``flags["kv_table"]`` k/v are the paged pools
    of a whole layer stack (``ops.pool_shape``) and ``flags["kv_layer"]``
    names this layer.  Returns (out, new_cache).
    """
    B, _, d = x.shape
    H, Hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    fl = flags or {}
    q = _heads(apply_dense(p["q"], x), H, hd)
    if kv_source_cache is not None:                     # cross-attention
        k, v, lengths = kv_source_cache["k"], kv_source_cache["v"], kv_source_cache["len"]
        out = ops.decode_attention(q, k, v, lengths, backend=fl.get("backend"))
        out = apply_dense(p["o"], out.reshape(B, 1, H * hd))
        return out, cache

    k_new = _heads(apply_dense(p["k"], x), Hk, hd)
    v_new = _heads(apply_dense(p["v"], x), Hk, hd)
    q, k_new = _qk_normalize(p, q, k_new, cfg.norm_eps)
    pos = cache["len"]                                  # (B,) current positions
    if cfg.rope_type != "none":
        if cfg.rope_type == "mrope":
            pos3 = jnp.broadcast_to(pos[:, None, None], (B, 3, 1))
            q = apply_rope(q, pos3, cfg)
            k_new = apply_rope(k_new, pos3, cfg)
        else:
            q = apply_rope(q, pos[:, None], cfg)
            k_new = apply_rope(k_new, pos[:, None], cfg)
    table = fl.get("kv_table")
    if table is not None:
        # Paged KV: cache k/v are the block pools of a whole layer stack,
        # shared by every slot; this layer writes and reads its own
        # ``[layer]`` in place.  ``table`` (B, n_cols) maps each slot's
        # logical pages to physical ones (entries >= P are unmapped — the
        # write drops, the read masks).  Pages being appended into are
        # private (the engine CoW-forks shared ones before dispatch), so no
        # two live slots ever write the same location.
        assert window is None, "paged KV cache supports global attention only"
        layer = fl["kv_layer"]
        k_buf, v_buf = _paged_write(cache, table, pos[:, None], k_new, v_new, layer)
        out = ops.paged_attention(q, k_buf, v_buf, table, pos + 1, layer,
                                  backend=fl.get("backend"))
        out = apply_dense(p["o"], out.reshape(B, 1, H * hd))
        return out, {"k": k_buf, "v": v_buf, "len": pos + 1}

    S_buf = cache["k"].shape[1]
    write_at = pos % S_buf if window is not None else pos
    bidx = jnp.arange(B)
    k_buf = cache["k"].at[bidx, write_at].set(k_new[:, 0].astype(cache["k"].dtype))
    v_buf = cache["v"].at[bidx, write_at].set(v_new[:, 0].astype(cache["v"].dtype))
    valid = jnp.minimum(pos + 1, S_buf)
    out = ops.decode_attention(q, k_buf, v_buf, valid, backend=fl.get("backend"))
    out = apply_dense(p["o"], out.reshape(B, 1, H * hd))
    new_cache = {"k": k_buf, "v": v_buf, "len": pos + 1}
    return out, new_cache


def attention_span(p, cfg: ModelConfig, x, cache, *, flags=None):
    """S-token decode in one dispatch (speculative-decode verification).

    x: (B, S, d); cache: {"k","v","len"(B,)} with ``len`` the valid length
    *before* the span.  All S new K/V entries are written first, then query
    position ``i`` attends causally to ``len + i + 1`` keys — numerically the
    write-then-masked-read order is indistinguishable from S sequential
    :func:`attention_decode` steps (future keys are masked to exact zeros).
    Global attention only (no ring buffer, no cross); a paged cache as in
    :func:`attention_decode`.  Returns (out, new_cache) with ``len``
    advanced by S.
    """
    B, S, d = x.shape
    H, Hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    fl = flags or {}
    q = _heads(apply_dense(p["q"], x), H, hd)
    k_new = _heads(apply_dense(p["k"], x), Hk, hd)
    v_new = _heads(apply_dense(p["v"], x), Hk, hd)
    q, k_new = _qk_normalize(p, q, k_new, cfg.norm_eps)
    pos = cache["len"]                                  # (B,) span base
    positions = pos[:, None] + jnp.arange(S)[None, :]   # (B, S)
    if cfg.rope_type != "none":
        if cfg.rope_type == "mrope":
            pos3 = jnp.broadcast_to(positions[:, None, :], (B, 3, S))
            q = apply_rope(q, pos3, cfg)
            k_new = apply_rope(k_new, pos3, cfg)
        else:
            q = apply_rope(q, positions, cfg)
            k_new = apply_rope(k_new, positions, cfg)
    table = fl.get("kv_table")
    if table is not None:                               # paged, as in attention_decode
        layer = fl["kv_layer"]
        k_buf, v_buf = _paged_write(cache, table, positions, k_new, v_new, layer)
        out = ops.paged_span_attention(q, k_buf, v_buf, table, pos, layer,
                                       backend=fl.get("backend"))
        out = apply_dense(p["o"], out.reshape(B, S, H * hd))
        return out, {"k": k_buf, "v": v_buf, "len": pos + S}
    bidx = jnp.arange(B)[:, None]
    k_buf = cache["k"].at[bidx, positions].set(k_new.astype(cache["k"].dtype),
                                               mode="drop")
    v_buf = cache["v"].at[bidx, positions].set(v_new.astype(cache["v"].dtype),
                                               mode="drop")
    out = ops.span_attention(q, k_buf, v_buf, pos, backend=fl.get("backend"))
    out = apply_dense(p["o"], out.reshape(B, S, H * hd))
    return out, {"k": k_buf, "v": v_buf, "len": pos + S}


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  window: Optional[int] = None, dtype=jnp.bfloat16):
    S = min(window, max_len) if window is not None else max_len
    shape = (batch, S, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "len": jnp.zeros((batch,), jnp.int32),
    }


def kv_cache_axes(window: Optional[int] = None, kv_seq_shard: bool = False):
    """Logical axes of cache leaves (for sharding the serving state).

    ``kv_seq`` maps to the model mesh axis when ShardingConfig.kv_seq_shard is
    set (flash-decode: sequence-sharded KV, partial softmax + small
    all-reduces); kv_heads are then replicated to keep the spec valid.
    """
    return {
        "k": ("batch", "kv_seq", "kv_heads", "head_dim"),
        "v": ("batch", "kv_seq", "kv_heads", "head_dim"),
        "len": ("batch",),
    }
