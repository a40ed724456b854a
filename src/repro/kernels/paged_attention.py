"""Pallas TPU paged decode attention: one new token per sequence vs a KV cache
scattered across a refcounted block pool.

Generalizes ``decode_attention_pallas``'s online-softmax loop: instead of
streaming a contiguous ``[0, Smax)`` seq axis, the KV-innermost grid dimension
walks the sequence's *block table* — grid step ``(b, h, j)`` streams physical
page ``table[b, j]`` of the pool.  The gather happens in the BlockSpec index
map via scalar prefetch (``pltpu.PrefetchScalarGridSpec``): the table and the
layer index are SMEM-resident scalar arguments available before the body
runs, so the DMA for each KV tile is issued straight at its pooled address —
no materialized contiguous copy of the sequence ever exists.

The pool is the whole layer stack laid out by ``ops.pool_shape``: one grid
step reads the contiguous ``(rows, width)`` block of one head of one page of
one layer, and the caller never slices a layer out or transposes the pool.
A head dim that divides 128 is packed ``R = 128 / head_dim`` positions to a
lane row (row ``i``, lanes ``[r*D, (r+1)*D)`` hold position ``i*R + r``).
The query is then block-diagonal: its row ``r*G + g`` holds query head ``g``
in lane block ``r`` and zeros elsewhere, so one matmul scores every position
of the page, row ``r*G + g`` the positions ``≡ r (mod R)``.  Each such row
keeps its own online softmax, and the R partial softmaxes of a query head
are merged after the kernel (as split-KV decoding merges its splits).  With
R = 1 the kernel is the plain one-position-per-row walk.

Shared-prefix pages need no special handling: two sequences whose tables point
at the same physical page simply stream the same tile; CoW-forked pages are
ordinary private pages by the time attention sees them.  Sentinel table
entries (``>= n_pool_pages``: unmapped tail of a short sequence, or a retired
slot) clip to the last page in the index map and are skipped by the
``mapped`` predicate in the body, mirroring the length mask.

The running (m, l, acc) scratch carries the softmax across pages; all
G = H/Hk query heads of a KV group ride one (R*G, width) tile so GQA reuses
each gathered page G times from VMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ops import pool_geometry

NEG_INF = -1e30


def _kernel(table_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
            m_scr, l_scr, acc_scr, *, page_size: int, n_pack: int, n_group: int,
            n_tab: int, n_pool: int, sm_scale: float):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    valid_len = len_ref[b]
    shape = (n_pack * n_group, page_size // n_pack)     # (R*G, rows)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    offset = sum((row >= r * n_group).astype(jnp.int32) for r in range(1, n_pack))
    k_pos = (j * page_size + jax.lax.broadcasted_iota(jnp.int32, shape, 1) * n_pack
             + offset)
    mask = k_pos < valid_len
    mapped = table_ref[b, j] < n_pool                   # sentinel page → skip

    @pl.when((j * page_size < valid_len) & mapped)
    def _attend():
        q = q_ref[0, 0].astype(jnp.float32)             # (R*G, width)
        k = k_ref[0, 0, 0].astype(jnp.float32)          # (rows, width)
        v = v_ref[0, 0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(mask, s, NEG_INF)                 # (R*G, rows)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == n_tab - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = acc_scr[...] / l
        lse_ref[0, 0] = m_scr[...] + jnp.log(l)


def paged_attention_pallas(q, k_pool, v_pool, table, lengths, layer, *,
                           interpret: bool = False):
    """q: (B, 1, H, D); k_pool/v_pool: (L, P, Hk, rows, width) stacked pools
    (``ops.pool_shape``); table: (B, n_pages) int32 physical page indices
    (>= P marks an unmapped entry); lengths: (B,) valid KV lengths; layer:
    index into the stack.  -> (B, 1, H, D).
    """
    B, _, H, D = q.shape
    _, P, Hk, rows, width = k_pool.shape
    R, Dp, ps = pool_geometry(k_pool, D)
    G = H // Hk
    n_tab = table.shape[1]
    qg = q[:, 0].reshape(B, Hk, G, D)                   # (B, Hk, G, D) groups
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, Dp - D)))
    eye = jnp.eye(R, dtype=q.dtype)                     # block-diagonal query
    qbd = (eye[:, None, :, None] * qg[:, :, None, :, None, :]).reshape(
        B, Hk, R * G, width)
    kernel = functools.partial(_kernel, page_size=ps, n_pack=R, n_group=G,
                               n_tab=n_tab, n_pool=P, sm_scale=1.0 / math.sqrt(D))

    def page_map(b, h, j, table_ref, len_ref, layer_ref):
        # scalar-prefetched gather: clip sentinels (the body masks them out)
        return (layer_ref[0], jnp.minimum(table_ref[b, j], P - 1), h, 0, 0)

    def group_map(b, h, j, *_):
        return (b, h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                          # table, lengths, layer
        grid=(B, Hk, n_tab),
        in_specs=[
            pl.BlockSpec((1, 1, R * G, width), group_map),
            pl.BlockSpec((1, 1, 1, rows, width), page_map),
            pl.BlockSpec((1, 1, 1, rows, width), page_map),
        ],
        out_specs=[pl.BlockSpec((1, 1, R * G, width), group_map),
                   pl.BlockSpec((1, 1, R * G, 1), group_map)],
        scratch_shapes=[
            pltpu.VMEM((R * G, 1), jnp.float32),
            pltpu.VMEM((R * G, 1), jnp.float32),
            pltpu.VMEM((R * G, width), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Hk, R * G, width), jnp.float32),
                   jax.ShapeDtypeStruct((B, Hk, R * G, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(table.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qbd, k_pool, v_pool)
    # row r*G + g answered query head g in lane block r over the positions
    # ≡ r (mod R): merge the R partial softmaxes by their log-sum-exps
    out = jnp.diagonal(out.reshape(B, Hk, R, G, R, Dp), axis1=2, axis2=4)
    w = jax.nn.softmax(lse.reshape(B, Hk, R, G), axis=2)        # (B, Hk, R, G)
    out = (out * w.transpose(0, 1, 3, 2)[:, :, :, None, :]).sum(axis=-1)
    return out[..., :D].reshape(B, 1, H, D).astype(q.dtype)
