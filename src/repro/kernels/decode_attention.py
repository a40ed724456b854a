"""Pallas TPU decode attention: one new token per sequence vs a long KV cache.

Decode is HBM-bandwidth-bound (the whole KV cache is streamed once per step),
so the kernel's job is to keep the streaming dense and the softmax state in
VMEM: grid (batch, kv_heads, n_kv_blocks), KV innermost/sequential; running
(m, l, acc) scratch carries the online softmax across KV blocks; all G = H/Hk
query heads of a KV group ride in one (G, D) tile so GQA reuses each K/V block
G times from VMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            kv_block: int, n_kv: int, sm_scale: float):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    valid_len = len_ref[b]
    k_pos = j * kv_block + jax.lax.broadcasted_iota(jnp.int32, (1, kv_block), 1)
    mask = (k_pos < valid_len)[0]                       # (kb,)

    @pl.when(j * kv_block < valid_len)
    def _attend():
        q = q_ref[0, 0].astype(jnp.float32)             # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)             # (kb, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(mask[None, :], s, NEG_INF)        # (G, kb)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.where(mask[None, :], jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == n_kv - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def decode_attention_pallas(q, k, v, lengths, *, kv_block: int = 2048,
                            interpret: bool = False):
    """q: (B, 1, H, D); k/v: (B, Smax, Hk, D); lengths: (B,) -> (B, 1, H, D)."""
    B, _, H, D = q.shape
    Smax, Hk = k.shape[1], k.shape[2]
    G = H // Hk
    kv_block = min(kv_block, Smax)
    pad = (-Smax) % kv_block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = Smax + pad
    n_kv = Sp // kv_block
    # (B, Hk, G, D) query groups; KV as (B, Hk, S, D)
    qg = q[:, 0].reshape(B, Hk, G, D)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    grid = (B, Hk, n_kv)
    kernel = functools.partial(_kernel, kv_block=kv_block, n_kv=n_kv,
                               sm_scale=1.0 / math.sqrt(D))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),       # lengths, scalar-read
            pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, kv_block, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, kv_block, D), lambda b, h, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hk, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, kt, vt)
    return out.reshape(B, 1, H, D)
