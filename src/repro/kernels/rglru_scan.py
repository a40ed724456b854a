"""Pallas TPU RG-LRU scan (RecurrentGemma gated diagonal linear recurrence).

The recurrence is elementwise-diagonal, so this is a VPU/bandwidth kernel,
not an MXU one: grid (batch, n_width_blocks, n_chunks), chunks innermost and
sequential, the (1, Wb) fp32 state in VMEM scratch.  Each chunk is processed
with an in-VMEM ``fori_loop`` over its tokens — raw recurrence in fp32, no
log-space reformulation needed, exact by construction.  Chunking exists to
amortize HBM→VMEM transfers into (chunk × Wb) tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, a_ref, h0_ref, o_ref, hT_ref, h_scr, *, chunk: int, n_chunks: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        h_scr[...] = h0_ref[...]

    x = x_ref[0].astype(jnp.float32)             # (C, Wb)
    al = a_ref[0].astype(jnp.float32)
    a = jnp.exp(al)
    gated = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * al), 1e-12)) * x

    def body(t, carry):
        h, out = carry
        h = a[t] * h[0] + gated[t]
        out = jax.lax.dynamic_update_slice_in_dim(out, h[None, :], t, axis=0)
        return h[None, :], out

    h0 = h_scr[...]                              # (1, Wb)
    out0 = jnp.zeros((chunk, x.shape[1]), jnp.float32)
    hT, out = jax.lax.fori_loop(0, chunk, body, (h0, out0))
    o_ref[0] = out.astype(o_ref.dtype)
    h_scr[...] = hT

    @pl.when(c == n_chunks - 1)
    def _final():
        hT_ref[...] = hT


def rglru_pallas(x, a_log, state=None, *, chunk: int = 256, w_block: int = 512,
                 interpret: bool = False):
    """x/a_log: (B, T, W); state: (B, W) fp32.  Returns (h (B,T,W), final (B,W))."""
    B, T, W = x.shape
    if state is None:
        state = jnp.zeros((B, W), jnp.float32)
    chunk = min(chunk, T)
    pad_t = (-T) % chunk
    if pad_t:
        x = jnp.pad(x, ((0, 0), (0, pad_t), (0, 0)))
        a_log = jnp.pad(a_log, ((0, 0), (0, pad_t), (0, 0)))
    w_block = min(w_block, W)
    assert W % w_block == 0, (W, w_block)
    Tp = T + pad_t
    n_chunks = Tp // chunk
    grid = (B, W // w_block, n_chunks)
    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks)
    out, h_final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, w_block), lambda b, wj, c: (b, c, wj)),
            pl.BlockSpec((1, chunk, w_block), lambda b, wj, c: (b, c, wj)),
            pl.BlockSpec((1, w_block), lambda b, wj, c: (b, wj)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, w_block), lambda b, wj, c: (b, c, wj)),
            pl.BlockSpec((1, w_block), lambda b, wj, c: (b, wj)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Tp, W), x.dtype),
            jax.ShapeDtypeStruct((B, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, w_block), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, a_log, state)
    return out[:, :T], h_final
