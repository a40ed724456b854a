"""Pallas TPU paged decode attention: one new token per sequence vs a KV cache
scattered across a refcounted block pool.

Generalizes ``decode_attention_pallas``'s online-softmax loop: instead of
streaming a contiguous ``[0, Smax)`` seq axis, the KV-innermost grid dimension
walks the sequence's *block table* — grid step ``(b, h, j)`` streams physical
page ``table[b, j]`` of the pool.  The gather happens in the BlockSpec index
map via scalar prefetch (``pltpu.PrefetchScalarGridSpec``): the table is an
SMEM-resident scalar argument available before the body runs, so the DMA for
each KV tile is issued straight at its pooled address — no materialized
contiguous copy of the sequence ever exists.

Shared-prefix pages need no special handling: two sequences whose tables point
at the same physical page simply stream the same tile; CoW-forked pages are
ordinary private pages by the time attention sees them.  Sentinel table
entries (``>= n_pool_pages``: unmapped tail of a short sequence, or a retired
slot) clip to page 0 in the index map and are skipped by the ``mapped``
predicate in the body, mirroring the length mask.

The running (m, l, acc) scratch carries the softmax across pages; all
G = H/Hk query heads of a KV group ride one (G, D) tile so GQA reuses each
gathered page G times from VMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
            acc_scr, *, page_size: int, n_tab: int, n_pool: int,
            sm_scale: float):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    valid_len = len_ref[b]
    k_pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
    mask = (k_pos < valid_len)[0]                       # (ps,)
    mapped = table_ref[b, j] < n_pool                   # sentinel page → skip

    @pl.when((j * page_size < valid_len) & mapped)
    def _attend():
        q = q_ref[0, 0].astype(jnp.float32)             # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)             # (ps, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(mask[None, :], s, NEG_INF)        # (G, ps)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.where(mask[None, :], jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == n_tab - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_attention_pallas(q, k_pool, v_pool, table, lengths, *,
                           interpret: bool = False):
    """q: (B, 1, H, D); k_pool/v_pool: (P, ps, Hk, D); table: (B, n_pages)
    int32 physical page indices (>= P marks an unmapped entry);
    lengths: (B,) valid KV lengths.  -> (B, 1, H, D).
    """
    B, _, H, D = q.shape
    P, ps, Hk, _ = k_pool.shape
    G = H // Hk
    n_tab = table.shape[1]
    # (B, Hk, G, D) query groups; pool as (P, Hk, ps, D) so each grid step
    # DMA's one head-row of one physical page
    qg = q[:, 0].reshape(B, Hk, G, D)
    kt = k_pool.transpose(0, 2, 1, 3)
    vt = v_pool.transpose(0, 2, 1, 3)
    kernel = functools.partial(_kernel, page_size=ps, n_tab=n_tab,
                               n_pool=P, sm_scale=1.0 / math.sqrt(D))

    def page_map(b, h, j, table_ref, len_ref):
        # scalar-prefetched gather: clip sentinels (the body masks them out)
        return (jnp.minimum(table_ref[b, j], P - 1), h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                          # table, lengths
        grid=(B, Hk, n_tab),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, j, t, n: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, ps, D), page_map),
            pl.BlockSpec((1, 1, ps, D), page_map),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, j, t, n: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hk, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(table.astype(jnp.int32), lengths.astype(jnp.int32), qg, kt, vt)
    return out.reshape(B, 1, H, D)
