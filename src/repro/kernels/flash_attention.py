"""Pallas TPU flash attention (prefill/train): causal GQA with sliding-window
support.

Grid: (batch, q_heads, n_q_blocks, n_kv_blocks) with the KV dimension
innermost and ARBITRARY (sequential) — the online-softmax running state
(m, l, acc) lives in VMEM scratch and accumulates across KV blocks; the
normalized output is written on each KV block's last visit.

BlockSpecs keep one (q_block × head_dim) Q tile and one (kv_block × head_dim)
K/V tile in VMEM; tiles are 128-aligned for the MXU.  Fully-masked causal
blocks are skipped with ``pl.when`` (no MXU work issued).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            causal: bool, window: Optional[int], q_block: int, kv_block: int,
            n_kv: int, sm_scale: float, kv_valid: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    q_start = qi * q_block
    k_start = kj * kv_block

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # static-shape positions for masking
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 1)
    mask = k_pos < kv_valid
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window

    # skip blocks that cannot contain any visible key (causal/window pruning)
    def visible() -> bool:
        return True

    run = jnp.asarray(True)
    if causal:
        run = k_start <= q_start + q_block - 1
    if window is not None:
        run = jnp.logical_and(run, k_start + kv_block > q_start - window + 1)

    @pl.when(run)
    def _attend():
        q = q_ref[0, 0].astype(jnp.float32)                  # (qb, hd)
        k = k_ref[0, 0].astype(jnp.float32)                  # (kb, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(kj == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: Optional[int] = None, q_block: int = 512,
                           kv_block: int = 512, interpret: bool = False):
    """q: (B, Sq, H, D); k/v: (B, Skv, Hk, D) -> (B, Sq, H, D).

    GQA: each of the H grid rows reads KV head ``h // (H // Hk)``.
    Sequence ends are aligned (prefill semantics): q position i attends keys
    ≤ i + (Skv − Sq).
    """
    B, Sq, H, D = q.shape
    Skv, Hk = k.shape[1], k.shape[2]
    assert H % Hk == 0
    G = H // Hk
    assert Sq == Skv, "prefill kernel assumes aligned q/kv (use ops fallback otherwise)"
    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Skv)
    pad_q = (-Sq) % q_block
    pad_k = (-Skv) % kv_block
    kv_valid = Skv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    Sq_p, Skv_p = Sq + pad_q, Skv + pad_k
    # (B, H, S, D) layout: head-major so each grid cell reads one tile
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    n_q, n_kv = Sq_p // q_block, Skv_p // kv_block
    grid = (B, H, n_q, n_kv)

    kernel = functools.partial(
        _kernel, causal=causal, window=window, q_block=q_block,
        kv_block=kv_block, n_kv=n_kv, sm_scale=1.0 / math.sqrt(D),
        kv_valid=kv_valid)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q_block, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, kv_block, D), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, kv_block, D), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_block, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq_p, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)[:, :Sq]
