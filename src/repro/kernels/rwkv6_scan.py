"""Pallas TPU chunked WKV6 scan (RWKV6 "Finch" recurrence).

TPU adaptation of the (GPU-oriented) chunked linear-attention algorithm:
grid (batch, heads, n_chunks) with chunks innermost/sequential; the (D, D)
inter-chunk state lives in fp32 VMEM scratch.  Within a chunk the recurrence
is reorganized into MXU matmuls:

    o_intra = ((r·exp(Le)) (k·exp(−L))ᵀ ⊙ tril) v  + diag-bonus term
    o_state = (r·exp(Le)) · S
    S'      = exp(LC) ⊙ S + (k·exp(LC − L))ᵀ v

where L is the inclusive per-channel cumulative log-decay and Le its
exclusive version.  Exponent *differences* are clamped at ±30 before
exponentiation — contributions beyond e⁻³⁰ are zero in fp32, so the clamp
only prevents overflow of the factored form (exact for all practical decay,
validated against the token-by-token oracle).
"""
from __future__ import annotations

import functools

import jax
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp
from jax.experimental import pallas as pl

_CLAMP = 30.0


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sT_ref, s_scr, *,
            chunk: int, n_chunks: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0]

    r = r_ref[0, 0].astype(jnp.float32)          # (C, D)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)             # (1, D) bonus
    S = s_scr[...]                               # (D, D)

    logw = jnp.log(jnp.clip(w, 1e-12, 1.0))
    L = jnp.cumsum(logw, axis=0)                 # inclusive (C, D)
    Le = L - logw                                # exclusive
    LC = L[-1:, :]                               # (1, D)

    # factored pair decays, clamped: exp(Le_t − L_s) = exp(Le_t) · exp(−L_s)
    q_dec = r * jnp.exp(jnp.clip(Le, -_CLAMP, _CLAMP))
    k_dec = k * jnp.exp(jnp.clip(-L, -_CLAMP, _CLAMP))
    att = jax.lax.dot_general(q_dec, k_dec, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)   # (C, C)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
           > jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    att = jnp.where(tri, att, 0.0)
    o_intra = jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    o_state = jax.lax.dot_general(q_dec, S, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    o_diag = ((r * u * k).sum(axis=1, keepdims=True)) * v
    o_ref[0, 0] = (o_intra + o_state + o_diag).astype(o_ref.dtype)

    k_tail = k * jnp.exp(jnp.clip(LC - L, -_CLAMP, _CLAMP))
    S_new = jnp.exp(jnp.clip(LC, -_CLAMP, 0.0)).T * S + jax.lax.dot_general(
        k_tail, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    s_scr[...] = S_new

    @pl.when(c == n_chunks - 1)
    def _final():
        sT_ref[0, 0] = S_new


def wkv6_pallas(r, k, v, w, u, state=None, *, chunk: int = 32, interpret: bool = False):
    """r/k/v/w: (B, T, H, D); u: (H, D); state: (B, H, D, D) fp32.
    Returns (out (B,T,H,D), final_state)."""
    B, T, H, D = r.shape
    if state is None:
        state = jnp.zeros((B, H, D, D), jnp.float32)
    pad = (-T) % chunk
    if pad:
        r, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) for x in (r, k, v))
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0), (0, 0)), constant_values=1.0)
    Tp = T + pad
    n_chunks = Tp // chunk
    # (B, H, T, D) layout
    rt, kt, vt, wt = (x.transpose(0, 2, 1, 3) for x in (r, k, v, w))
    grid = (B, H, n_chunks)
    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks)
    out, s_final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, D), lambda b, h, c: (h, 0)),
            pl.BlockSpec((1, 1, D, D), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, D, D), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tp, D), r.dtype),
            jax.ShapeDtypeStruct((B, H, D, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(rt, kt, vt, wt, u, state)
    return out.transpose(0, 2, 1, 3)[:, :T], s_final
