"""A test-only architecture: a dense decoder with grouped-query attention,
a head size that is not ``hidden_size / num_attention_heads``, RMSNorm over
each head's queries and keys before RoPE, no Q/K/V bias and an untied head
(the attention of the Qwen3 family).  It lives beside the tests, not in
``archs/``, and is reached by pointing ``run.ARCH_DIR`` here: it shows that an
architecture joins the harness as new files alone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import causal_attention, dense, rms, rope


def arch(config: dict) -> dict:
    hf = config["hf_config"]
    d, h, hk = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"]
    hd, ff = hf["head_dim"], hf["intermediate_size"]
    return dict(d=d, heads=h, kv_heads=hk, head_dim=hd, ff=ff,
                layers=hf["num_hidden_layers"], vocab=hf["vocab_size"],
                eps=hf["rms_norm_eps"], theta=hf["rope_theta"],
                tied=hf["tie_word_embeddings"],
                matmul_params=d * h * hd + 2 * d * hk * hd + h * hd * d + 3 * d * ff)


def model_config(config: dict):
    from repro.config import ModelConfig

    hf = config["hf_config"]
    return ModelConfig(
        name=config["name"], family="dense", n_layers=hf["num_hidden_layers"],
        d_model=hf["hidden_size"], n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        d_ff=hf["intermediate_size"], vocab_size=hf["vocab_size"], activation="swiglu",
        norm="rmsnorm", norm_eps=hf["rms_norm_eps"], qkv_bias=False,
        qk_norm=True, rope_theta=hf["rope_theta"],
        tie_embeddings=hf["tie_word_embeddings"], dtype=config["serving"]["dtype"],
        source=config["source"])


def abstract_params(a: dict, dtype=jnp.bfloat16) -> dict:
    L, d, ff, hd = a["layers"], a["d"], a["ff"], a["head_dim"]
    hq, hk = a["heads"] * hd, a["kv_heads"] * hd
    vocab_padded = -(-a["vocab"] // 32) * 32
    s = lambda *shape: jax.ShapeDtypeStruct(shape, dtype)
    block = {
        "n1": {"scale": s(L, d)},
        "attn": {"q": {"w": s(L, d, hq)}, "k": {"w": s(L, d, hk)}, "v": {"w": s(L, d, hk)},
                 "o": {"w": s(L, hq, d)}, "q_norm": s(L, hd), "k_norm": s(L, hd)},
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
    B, T = tokens.shape
    H, Hk, D, eps = a["heads"], a["kv_heads"], a["head_dim"], a["eps"]
    f32 = lambda t: jax.tree.map(lambda v: v.astype(jnp.float32), t)
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, p):
        p = f32(p)
        h = rms(x, p["n1"]["scale"], eps)
        at = p["attn"]
        q = dense(h, at["q"]["w"], None, int8).reshape(B, T, H, D)
        k = dense(h, at["k"]["w"], None, int8).reshape(B, T, Hk, D)
        v = dense(h, at["v"]["w"], None, int8).reshape(B, T, Hk, D)
        q, k = rms(q, at["q_norm"], eps), rms(k, at["k_norm"], eps)
        o = causal_attention(rope(q, a["theta"]), rope(k, a["theta"]), v)
        x = x + dense(o.reshape(B, T, H * D), at["o"]["w"], None, int8)
        h = rms(x, p["n2"]["scale"], eps)
        m = p["mlp"]
        g = jax.nn.silu(dense(h, m["gate"]["w"], None, int8)) * dense(h, m["up"]["w"], None, int8)
        return x + dense(g, m["down"]["w"], None, int8), None

    x, _ = jax.lax.scan(layer, x, params["blocks"]["b0"])
    x = jnp.take_along_axis(x, gather[..., None], axis=1)
    x = rms(x, params["final_norm"]["scale"].astype(jnp.float32), eps)
    head = params["embed"].T if a["tied"] else params["lm_head"]
    return dense(x, head.astype(jnp.float32), None, int8)[..., : a["vocab"]]
