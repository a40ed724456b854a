"""Plain float32 reference of the served model, and its int8 control.

What every architecture shares lives here; what one architecture is lives in
its module ``archs/<arch>.py``, which the configuration file names by its
``"arch"`` key (``run.load_arch``).  A module gives:

* ``arch(config) -> dict``: the shapes, as plain hashable values, with what
  ``work.py`` reads (``d``, ``heads``, ``kv_heads``, ``head_dim``, ``layers``,
  ``vocab``, ``tied``, and ``matmul_params``, the matrix parameters one token
  runs through in one layer);
* ``model_config(config)``: the program's ``repro.config.ModelConfig``;
* ``abstract_params(a, dtype)``: the parameter layout its forward reads; the
  harness checks at set-up that the program's tree has the same layout, and
  ``weights.build`` fills both from the seed;
* ``forward(params, tokens, gather, a, int8)``: the whole forward from tokens
  (B, T) to the (B, n, vocab) float32 logits at positions ``gather`` (B, n),
  written from the published description with the primitives below;
* optionally ``paged_attention(a, context)`` and ``flash_attention(a, n)``,
  its own (flops, bytes) where ``work.py``'s generic counts do not describe
  its attention.

No kernel, cache, paging or batching of the program is used, and nothing of
the program is imported.  Matrix products run at ``highest`` precision, so
float32 means float32 on the TPU too.  A forward scans its layers one at a
time and casts each to float32 inside the scan, so a model whose bf16
weights fill most of the chip still fits.

The control is the same forward with int8 in place of the served bf16: every
projection's weights are quantized per output channel and its input per
token (symmetric, 127 levels), which is what an int8 serving path would do.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def quant(x, axis):
    """Symmetric int8 round trip along ``axis`` (the reduced dimension)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def dense(x, w, b=None, int8=False):
    """``x @ w + b``; with ``int8`` through the control's int8 round trips."""
    if int8:
        x = quant(x, -1)            # per token
        w = quant(w, 0)             # per output channel
    y = x @ w
    return y if b is None else y + b


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: (B, T, H, D), rotate-half convention, positions 0..T-1."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def causal_attention(q, k, v):
    """Causal softmax attention.  q: (B, T, H, D); k, v: (B, T, Hk, D), each
    K/V head shared by H/Hk query heads.  Returns (B, T, H, D)."""
    T, H, D = q.shape[1], q.shape[2], q.shape[3]
    k = jnp.repeat(k, H // k.shape[2], axis=2)
    v = jnp.repeat(v, H // v.shape[2], axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)


@partial(jax.jit, static_argnames=("forward", "a_items", "int8"))
def _forward(params, tokens, gather, forward, a_items, int8):
    return forward(params, tokens, gather, dict(a_items), int8)


def _chunks(seqs, positions, length: int, n_pos: int, rows: int):
    """Fixed-shape (rows, length) token and (rows, n_pos) position blocks, so
    every call of a configuration hits one compiled program."""
    for s in range(0, len(seqs), rows):
        idx = list(range(s, min(s + rows, len(seqs))))
        tok = np.zeros((rows, length), np.int32)
        gat = np.zeros((rows, n_pos), np.int32)
        for r, i in enumerate(idx):
            if len(seqs[i]) > length or len(positions[i]) > n_pos:
                raise ValueError("sequence longer than the reference's fixed shape")
            tok[r, : len(seqs[i])] = seqs[i]
            gat[r, : len(positions[i])] = positions[i]
        yield idx, jnp.asarray(tok), jnp.asarray(gat)


def logits(module, params, a: dict, seqs, positions, length: int, int8: bool = False,
           rows: int = 4, n_pos: int = 80):
    """Logits of the architecture ``module``'s forward for each sequence in
    ``seqs`` at ``positions`` (a list of index arrays), ``rows`` sequences at
    a time at a fixed padded ``length``.
    Returns a list of (n_i, vocab) arrays."""
    out = []
    items = tuple(sorted(a.items()))
    with jax.default_matmul_precision("highest"):
        for idx, tok, gat in _chunks(seqs, positions, length, n_pos, rows):
            lg = np.asarray(_forward(params, tok, gat, module.forward, items, int8))
            out += [lg[r, : len(positions[i])] for r, i in enumerate(idx)]
    return out


@jax.jit
def _gap_of(ref, chosen):
    """How far each chosen token's reference logit lies below the best."""
    picked = jnp.take_along_axis(ref, chosen[..., None], axis=-1)[..., 0]
    return ref.max(-1) - picked


def served_gaps(module, params, a: dict, served, length: int, int8: bool = False,
                rows: int = 4, n_pos: int = 80):
    """For each served request ``(prompt, tokens)``: per served token, how far
    its reference logit lies below the reference's best at that position
    (teacher-forced on the served tokens).  With ``int8``, the same for the
    token the int8 control puts first at each position.  Returns a list of
    float arrays."""
    seqs = [list(p) + list(t[:-1]) for p, t in served]
    pos = [np.arange(len(p) - 1, len(p) - 1 + len(t)) for p, t in served]
    items = tuple(sorted(a.items()))
    out = []
    with jax.default_matmul_precision("highest"):
        for idx, tok, gat in _chunks(seqs, pos, length, n_pos, rows):
            ref = _forward(params, tok, gat, module.forward, items, False)
            if int8:
                control = _forward(params, tok, gat, module.forward, items, True)
                chosen = control.argmax(-1).astype(jnp.int32)
            else:
                ch = np.zeros(gat.shape, np.int32)
                for r, i in enumerate(idx):
                    ch[r, : len(served[i][1])] = served[i][1]
                chosen = jnp.asarray(ch)
            gaps = np.asarray(_gap_of(ref, chosen))
            out += [gaps[r, : len(served[i][1])] for r, i in enumerate(idx)]
    return out
