"""Qwen2: the dense decoder of Qwen1.5 (``Qwen2ForCausalLM``).

Per layer: RMSNorm, Q/K/V projections with bias, rotate-half RoPE, causal
softmax attention (grouped heads where ``num_key_value_heads`` is smaller),
output projection, residual, RMSNorm, SwiGLU MLP, residual.  A final RMSNorm,
then logits through the untied head or the transposed embedding.  The head
size is ``hidden_size / num_attention_heads``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import causal_attention, dense, rms, rope


def arch(config: dict) -> dict:
    """The shapes the reference and the work counts need."""
    hf = config["hf_config"]
    d, h, hk = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"]
    ff, hd = hf["intermediate_size"], d // h
    return dict(d=d, heads=h, kv_heads=hk, head_dim=hd, ff=ff,
                layers=hf["num_hidden_layers"], vocab=hf["vocab_size"],
                eps=hf["rms_norm_eps"], theta=hf["rope_theta"],
                tied=hf["tie_word_embeddings"],
                # Q, K, V and O projections and the gate, up and down of the MLP
                matmul_params=d * h * hd + 2 * d * hk * hd + h * hd * d + 3 * d * ff)


def model_config(config: dict):
    """The program's ``ModelConfig`` of this configuration."""
    from repro.config import ModelConfig

    hf = config["hf_config"]
    return ModelConfig(
        name=config["name"], family="dense", n_layers=hf["num_hidden_layers"],
        d_model=hf["hidden_size"], n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], d_ff=hf["intermediate_size"],
        vocab_size=hf["vocab_size"], activation="swiglu", norm="rmsnorm",
        norm_eps=hf["rms_norm_eps"], qkv_bias=True, rope_theta=hf["rope_theta"],
        tie_embeddings=hf["tie_word_embeddings"], dtype=config["serving"]["dtype"],
        source=config["source"])


def abstract_params(a: dict, dtype=jnp.bfloat16) -> dict:
    """The parameter layout ``forward`` reads, layers stacked on a leading axis."""
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


def forward(params, tokens, gather, a: dict, int8: bool):
    """tokens: (B, T) int32; gather: (B, n) positions whose logits are read.
    Returns (B, n, vocab) float32 logits."""
    B, T = tokens.shape
    H, Hk, D = a["heads"], a["kv_heads"], a["head_dim"]
    f32 = lambda t: jax.tree.map(lambda v: v.astype(jnp.float32), t)
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, p):
        p = f32(p)
        h = rms(x, p["n1"]["scale"], a["eps"])
        at = p["attn"]
        q = dense(h, at["q"]["w"], at["q"]["b"], int8).reshape(B, T, H, D)
        k = dense(h, at["k"]["w"], at["k"]["b"], int8).reshape(B, T, Hk, D)
        v = dense(h, at["v"]["w"], at["v"]["b"], int8).reshape(B, T, Hk, D)
        o = causal_attention(rope(q, a["theta"]), rope(k, a["theta"]), v)
        x = x + dense(o.reshape(B, T, H * D), at["o"]["w"], None, int8)
        h = rms(x, p["n2"]["scale"], a["eps"])
        m = p["mlp"]
        g = jax.nn.silu(dense(h, m["gate"]["w"], None, int8)) * dense(h, m["up"]["w"], None, int8)
        return x + dense(g, m["down"]["w"], None, int8), None

    x, _ = jax.lax.scan(layer, x, params["blocks"]["b0"])
    x = jnp.take_along_axis(x, gather[..., None], axis=1)
    x = rms(x, params["final_norm"]["scale"].astype(jnp.float32), a["eps"])
    head = params["embed"].T if a["tied"] else params["lm_head"]
    logits = dense(x, head.astype(jnp.float32), None, int8)
    return logits[..., : a["vocab"]]
