"""Plain float32 reference of the served decoder, and its int8 control.

A Qwen2-style decoder written from the published description in
straightforward ``jax.numpy``: token embedding; per layer RMSNorm, Q/K/V
projections with bias, rotate-half RoPE, causal softmax attention, output
projection, residual, RMSNorm, SwiGLU MLP, residual; final RMSNorm; logits
through the untied head or the transposed embedding.  No kernel, cache,
paging or batching of the program is used, and nothing of the program is
imported.  Matrix products run at ``highest`` precision, so float32 means
float32 on the TPU too.  Layers are scanned one at a time and cast to float32
inside the scan, so a model whose bf16 weights fill most of the chip still
fits.

The control is the same forward with int8 in place of the served bf16: every
projection's weights are quantized per output channel and its input per
token (symmetric, 127 levels), which is what an int8 serving path would do.

``abstract_params`` states the parameter layout this reference reads; the
harness checks at set-up that the program's tree has the same layout, and
``weights.build`` fills both from the seed.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def arch(config: dict) -> dict:
    """The shapes this reference needs, from a configuration file."""
    hf = config["hf_config"]
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    return dict(d=d, heads=h, kv_heads=hf["num_key_value_heads"], head_dim=d // h,
                ff=hf["intermediate_size"], layers=hf["num_hidden_layers"],
                vocab=hf["vocab_size"], eps=hf["rms_norm_eps"],
                theta=hf["rope_theta"], tied=hf["tie_word_embeddings"])


def abstract_params(a: dict, dtype=jnp.bfloat16) -> dict:
    L, d, ff = a["layers"], a["d"], a["ff"]
    hq, hk = a["heads"] * a["head_dim"], a["kv_heads"] * a["head_dim"]
    vocab_padded = -(-a["vocab"] // 32) * 32
    s = lambda *shape: jax.ShapeDtypeStruct(shape, dtype)
    block = {
        "n1": {"scale": s(L, d)},
        "attn": {"q": {"w": s(L, d, hq), "b": s(L, hq)},
                 "k": {"w": s(L, d, hk), "b": s(L, hk)},
                 "v": {"w": s(L, d, hk), "b": s(L, hk)},
                 "o": {"w": s(L, hq, d)}},
        "n2": {"scale": s(L, d)},
        "mlp": {"gate": {"w": s(L, d, ff)}, "up": {"w": s(L, d, ff)},
                "down": {"w": s(L, ff, d)}},
    }
    tree = {"embed": s(vocab_padded, d), "final_norm": {"scale": s(d)},
            "blocks": {"b0": block}}
    if not a["tied"]:
        tree["lm_head"] = s(d, vocab_padded)
    return tree


def _quant(x, axis):
    """Symmetric int8 round trip along ``axis`` (the reduced dimension)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _dense(x, w, b=None, int8=False):
    if int8:
        x = _quant(x, -1)            # per token
        w = _quant(w, 0)             # per output channel
    y = x @ w
    return y if b is None else y + b


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (B, T, H, D), rotate-half convention, positions 0..T-1."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@partial(jax.jit, static_argnames=("a_items", "int8"))
def _forward(params, tokens, gather, a_items, int8):
    """tokens: (B, T) int32; gather: (B, n) positions whose logits are read.
    Returns (B, n, vocab) float32 logits."""
    a = dict(a_items)
    B, T = tokens.shape
    H, Hk, D = a["heads"], a["kv_heads"], a["head_dim"]
    f32 = lambda t: jax.tree.map(lambda v: v.astype(jnp.float32), t)
    x = params["embed"][tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def layer(x, p):
        p = f32(p)
        h = _rms(x, p["n1"]["scale"], a["eps"])
        at = p["attn"]
        q = _dense(h, at["q"]["w"], at["q"]["b"], int8).reshape(B, T, H, D)
        k = _dense(h, at["k"]["w"], at["k"]["b"], int8).reshape(B, T, Hk, D)
        v = _dense(h, at["v"]["w"], at["v"]["b"], int8).reshape(B, T, Hk, D)
        q, k = _rope(q, a["theta"]), _rope(k, a["theta"])
        k = jnp.repeat(k, H // Hk, axis=2)
        v = jnp.repeat(v, H // Hk, axis=2)
        s = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(D)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)
        x = x + _dense(o.reshape(B, T, H * D), at["o"]["w"], None, int8)
        h = _rms(x, p["n2"]["scale"], a["eps"])
        m = p["mlp"]
        g = jax.nn.silu(_dense(h, m["gate"]["w"], None, int8)) * _dense(h, m["up"]["w"], None, int8)
        return x + _dense(g, m["down"]["w"], None, int8), None

    x, _ = jax.lax.scan(layer, x, params["blocks"]["b0"])
    x = jnp.take_along_axis(x, gather[..., None], axis=1)
    x = _rms(x, params["final_norm"]["scale"].astype(jnp.float32), a["eps"])
    head = params["embed"].T if a["tied"] else params["lm_head"]
    logits = _dense(x, head.astype(jnp.float32), None, int8)
    return logits[..., : a["vocab"]]


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


def logits(params, a: dict, seqs, positions, length: int, int8: bool = False,
           rows: int = 4, n_pos: int = 80):
    """Logits of each sequence in ``seqs`` at ``positions`` (a list of index
    arrays), ``rows`` sequences at a time at a fixed padded ``length``.
    Returns a list of (n_i, vocab) arrays."""
    out = []
    items = tuple(sorted(a.items()))
    with jax.default_matmul_precision("highest"):
        for idx, tok, gat in _chunks(seqs, positions, length, n_pos, rows):
            lg = np.asarray(_forward(params, tok, gat, items, int8))
            out += [lg[r, : len(positions[i])] for r, i in enumerate(idx)]
    return out


@jax.jit
def _gap_of(ref, chosen):
    """How far each chosen token's reference logit lies below the best."""
    picked = jnp.take_along_axis(ref, chosen[..., None], axis=-1)[..., 0]
    return ref.max(-1) - picked


def served_gaps(params, a: dict, served, length: int, int8: bool = False,
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
            ref = _forward(params, tok, gat, items, False)
            if int8:
                chosen = _forward(params, tok, gat, items, True).argmax(-1).astype(jnp.int32)
            else:
                ch = np.zeros(gat.shape, np.int32)
                for r, i in enumerate(idx):
                    ch[r, : len(served[i][1])] = served[i][1]
                chosen = jnp.asarray(ch)
            gaps = np.asarray(_gap_of(ref, chosen))
            out += [gaps[r, : len(served[i][1])] for r, i in enumerate(idx)]
    return out
