"""Unified model assembly for all assigned architecture families.

One ``Model`` class covers dense / MoE / RWKV6 / RG-LRU-hybrid decoders, the
VLM backbone (embedding inputs + M-RoPE) and the audio encoder-decoder:

* homogeneous layer stacks are scanned over *pattern groups* (compile-time
  O(1) in depth); a non-divisible tail (e.g. RecurrentGemma's 38 = 12×3 + 2)
  is unrolled;
* the same block code runs in full-sequence mode (train / prefill, optionally
  emitting a cache) and single-token decode mode (consuming/updating caches);
* every parameter carries logical sharding axes (see models.layers); the
  launcher turns them into PartitionSpecs for any mesh.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.config import MeshConfig, ModelConfig, ShardingConfig
from repro.kernels.ops import pool_shape
from repro.models import attention as attn_mod
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models.layers import (
    Builder,
    ParamSpec,
    apply_norm,
    init_norm,
    logical_rules,
    logical_to_pspec,
    sanitize_pspec,
    spec_tree_to_pspecs,
)

__all__ = ["Model", "StackedBuilder"]


@jax.custom_vjp
def _weight_barrier(tree):
    """Differentiable loop-invariant-hoisting fence for scanned weight groups.

    ``jax.lax.optimization_barrier`` keeps the CPU backend from hoisting (and
    materializing) an f32 copy of the whole stacked weights out of the scan
    body, but the primitive has no differentiation rule — the fence is an
    identity, so its gradient is the identity too.
    """
    return jax.lax.optimization_barrier(tree)


def _weight_barrier_fwd(tree):
    return jax.lax.optimization_barrier(tree), None


def _weight_barrier_bwd(_, ct):
    return (ct,)


_weight_barrier.defvjp(_weight_barrier_fwd, _weight_barrier_bwd)


class StackedBuilder:
    """Wraps a Builder so every parameter gets a leading (n_groups,) 'layers'
    dim — the whole pattern-group stack is created as one leaf for lax.scan."""

    def __init__(self, base: Builder, n: int):
        self._base = base
        self._n = n
        self.mode = base.mode

    def param(self, shape, axes, **kw):
        return self._base.param((self._n, *shape), ("layers", *axes), **kw)


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def _init_block(b, cfg: ModelConfig, kind: str, with_cross: bool = False):
    p = {"n1": init_norm(b, cfg.d_model, cfg.norm)}
    if kind in ("attn", "local"):
        p["attn"] = attn_mod.init_attention(b, cfg)
        if with_cross:
            p["nc"] = init_norm(b, cfg.d_model, cfg.norm)
            p["cross"] = attn_mod.init_attention(b, cfg, cross=True)
        p["n2"] = init_norm(b, cfg.d_model, cfg.norm)
        p["mlp"] = moe_mod.init_moe(b, cfg) if cfg.moe else mlp_mod.init_mlp(b, cfg)
    elif kind == "rwkv":
        p["tm"] = rwkv_mod.init_time_mix(b, cfg)
        p["n2"] = init_norm(b, cfg.d_model, cfg.norm)
        p["cm"] = rwkv_mod.init_channel_mix(b, cfg)
    elif kind == "rglru":
        p["rec"] = rglru_mod.init_rglru_block(b, cfg)
        p["n2"] = init_norm(b, cfg.d_model, cfg.norm)
        p["mlp"] = moe_mod.init_moe(b, cfg) if cfg.moe else mlp_mod.init_mlp(b, cfg)
    else:
        raise ValueError(kind)
    return p


def _mlp_or_moe(p, cfg: ModelConfig, x, flags):
    if cfg.moe:
        return moe_mod.apply_moe(p, cfg, x, dispatch=flags.get("moe_dispatch", "gather"),
                                 exact=flags.get("moe_exact", False),
                                 dp_size=flags.get("dp_size", 1),
                                 constrain=flags.get("moe_constrain"))
    return mlp_mod.apply_mlp(p, cfg, x), jnp.zeros((), jnp.float32)


def _block_full(p, cfg: ModelConfig, kind: str, x, positions, *, causal=True,
                enc_out=None, enc_positions=None, want_cache=False,
                cache_len: int = 0, flags=None):
    """Full-sequence block.  Returns (x, cache_entry_or_None, aux)."""
    flags = flags or {}
    aux = jnp.zeros((), jnp.float32)
    cache = None
    norm = lambda pn, h: apply_norm(pn, h, cfg.norm, cfg.norm_eps)
    if kind in ("attn", "local"):
        window = cfg.window if kind == "local" else None
        a, (k, v) = attn_mod.attention_full(p["attn"], cfg, norm(p["n1"], x), positions,
                                            causal=causal, window=window, flags=flags)
        x = x + a
        if "cross" in p and enc_out is not None:
            c, (ck, cv) = attn_mod.attention_full(p["cross"], cfg, norm(p["nc"], x),
                                                  positions, kv_source=enc_out, flags=flags)
            x = x + c
        h, a2 = _mlp_or_moe(p["mlp"], cfg, norm(p["n2"], x), flags)
        x = x + h
        aux = aux + a2
        if want_cache:
            cache = _fill_kv_cache(cfg, k, v, cache_len, cfg.window if kind == "local" else None)
            if "cross" in p and enc_out is not None:
                cache["cross"] = {"k": ck, "v": cv,
                                  "len": jnp.full((x.shape[0],), ck.shape[1], jnp.int32)}
    elif kind == "rwkv":
        h, (shift_tm, wkv) = rwkv_mod.time_mix_full(p["tm"], cfg, norm(p["n1"], x))
        x = x + h
        h, shift_cm = rwkv_mod.channel_mix_full(p["cm"], cfg, norm(p["n2"], x))
        x = x + h
        if want_cache:
            cache = {"shift_tm": shift_tm, "shift_cm": shift_cm, "wkv": wkv}
    elif kind == "rglru":
        h, (conv, hstate) = rglru_mod.rglru_block_full(p["rec"], cfg, norm(p["n1"], x))
        x = x + h
        h, a2 = _mlp_or_moe(p["mlp"], cfg, norm(p["n2"], x), flags)
        x = x + h
        aux = aux + a2
        if want_cache:
            cache = {"conv": conv, "h": hstate}
    else:
        raise ValueError(kind)
    return x, cache, aux


def _fill_kv_cache(cfg: ModelConfig, k, v, max_len: int, window: Optional[int]):
    """Place prefill K/V into a fixed-size (or ring) cache buffer."""
    B, S = k.shape[0], k.shape[1]
    size = min(window, max_len) if window else max_len
    buf_k = jnp.zeros((B, size, cfg.n_kv_heads, cfg.head_dim), k.dtype)
    buf_v = jnp.zeros_like(buf_k)
    if window:
        take = min(S, size)
        pos = jnp.arange(S - take, S)
        slot = pos % size
        buf_k = buf_k.at[:, slot].set(k[:, -take:])
        buf_v = buf_v.at[:, slot].set(v[:, -take:])
    else:
        buf_k = jax.lax.dynamic_update_slice_in_dim(buf_k, k[:, :size], 0, axis=1)
        buf_v = jax.lax.dynamic_update_slice_in_dim(buf_v, v[:, :size], 0, axis=1)
    return {"k": buf_k, "v": buf_v, "len": jnp.full((B,), S, jnp.int32)}


def _block_step(p, cfg: ModelConfig, kind: str, x, cache, flags=None):
    """Single-token decode.  Returns (x, new_cache)."""
    flags = flags or {}
    norm = lambda pn, h: apply_norm(pn, h, cfg.norm, cfg.norm_eps)
    if kind in ("attn", "local"):
        window = cfg.window if kind == "local" else None
        self_cache = {kk: cache[kk] for kk in ("k", "v", "len")}
        a, new_self = attn_mod.attention_decode(p["attn"], cfg, norm(p["n1"], x),
                                                self_cache, window=window, flags=flags)
        x = x + a
        new_cache = dict(new_self)
        if "cross" in p and "cross" in cache:
            c, _ = attn_mod.attention_decode(p["cross"], cfg, norm(p["nc"], x), None,
                                             kv_source_cache=cache["cross"], flags=flags)
            x = x + c
            new_cache["cross"] = cache["cross"]
        h, _ = _mlp_or_moe(p["mlp"], cfg, norm(p["n2"], x), flags)
        x = x + h
    elif kind == "rwkv":
        h, (shift_tm, wkv) = rwkv_mod.time_mix_step(p["tm"], cfg, norm(p["n1"], x),
                                                    cache["shift_tm"], cache["wkv"])
        x = x + h
        h, shift_cm = rwkv_mod.channel_mix_full(p["cm"], cfg, norm(p["n2"], x),
                                                cache["shift_cm"])
        x = x + h
        new_cache = {"shift_tm": shift_tm, "shift_cm": shift_cm, "wkv": wkv}
    elif kind == "rglru":
        h, (conv, hstate) = rglru_mod.rglru_block_step(p["rec"], cfg, norm(p["n1"], x),
                                                       cache["conv"], cache["h"])
        x = x + h
        h, _ = _mlp_or_moe(p["mlp"], cfg, norm(p["n2"], x), flags)
        x = x + h
        new_cache = {"conv": conv, "h": hstate}
    else:
        raise ValueError(kind)
    return x, new_cache


def _block_span(p, cfg: ModelConfig, kind: str, x, cache, flags=None):
    """S-token decode (speculative verification).  Mirrors :func:`_block_step`
    for global-attention blocks; other kinds have stateful recurrences that a
    parallel span cannot reproduce step-exactly, so they are rejected."""
    flags = flags or {}
    norm = lambda pn, h: apply_norm(pn, h, cfg.norm, cfg.norm_eps)
    if kind != "attn":
        raise ValueError(f"decode_span supports global-attention blocks only, "
                         f"got {kind!r}")
    self_cache = {kk: cache[kk] for kk in ("k", "v", "len")}
    a, new_cache = attn_mod.attention_span(p["attn"], cfg, norm(p["n1"], x),
                                           self_cache, flags=flags)
    x = x + a
    if "cross" in p:
        raise ValueError("decode_span does not support cross-attention")
    h, _ = _mlp_or_moe(p["mlp"], cfg, norm(p["n2"], x), flags)
    x = x + h
    return x, new_cache


# ---------------------------------------------------------------------------
# cache specs (abstract; concrete init via jnp.zeros of the same shapes)
# ---------------------------------------------------------------------------

def _block_cache_spec(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                      with_cross: bool, enc_len: int, dtype) -> dict:
    d = cfg.d_model
    if kind in ("attn", "local"):
        size = min(cfg.window, max_len) if kind == "local" and cfg.window else max_len
        spec = {
            "k": ParamSpec((batch, size, cfg.n_kv_heads, cfg.head_dim), dtype,
                           ("batch", "kv_seq", "kv_heads", "head_dim")),
            "v": ParamSpec((batch, size, cfg.n_kv_heads, cfg.head_dim), dtype,
                           ("batch", "kv_seq", "kv_heads", "head_dim")),
            "len": ParamSpec((batch,), jnp.int32, ("batch",)),
        }
        if with_cross:
            spec["cross"] = {
                "k": ParamSpec((batch, enc_len, cfg.n_kv_heads, cfg.head_dim), dtype,
                               ("batch", "seq", "kv_heads", "head_dim")),
                "v": ParamSpec((batch, enc_len, cfg.n_kv_heads, cfg.head_dim), dtype,
                               ("batch", "seq", "kv_heads", "head_dim")),
                "len": ParamSpec((batch,), jnp.int32, ("batch",)),
            }
        return spec
    if kind == "rwkv":
        H = d // cfg.rwkv_head_dim
        return {
            "shift_tm": ParamSpec((batch, d), dtype, ("batch", "embed")),
            "shift_cm": ParamSpec((batch, d), dtype, ("batch", "embed")),
            "wkv": ParamSpec((batch, H, cfg.rwkv_head_dim, cfg.rwkv_head_dim),
                             jnp.float32, ("batch", "heads", "head_dim", "head_dim")),
        }
    if kind == "rglru":
        W = cfg.lru_width or d
        return {
            "conv": ParamSpec((batch, cfg.conv_width - 1, W), dtype, ("batch", "conv", "mlp")),
            "h": ParamSpec((batch, W), jnp.float32, ("batch", "mlp")),
        }
    raise ValueError(kind)


def _stack_spec(spec, n: int):
    return jax.tree.map(
        lambda s: ParamSpec((n, *s.shape), s.dtype, ("layers", *s.logical_axes)),
        spec, is_leaf=lambda x: isinstance(x, ParamSpec))


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

@dataclass
class Model:
    cfg: ModelConfig
    shard: ShardingConfig = field(default_factory=ShardingConfig)
    mesh: Any = None          # optional jax Mesh for activation constraints

    def __post_init__(self):
        # pad the vocab to a 32-multiple so the embedding/lm_head/logits can
        # always shard over the model axis (e.g. seamless's 256206 → 256224);
        # padded columns are masked to −inf in the logits and never targeted
        self.vocab_padded = ((self.cfg.vocab_size + 31) // 32) * 32
        pat = list(self.cfg.block_pattern)
        self.pattern = pat
        if self.shard.scan_layers:
            self.n_groups = self.cfg.n_layers // len(pat)
            self.rem_kinds = self.cfg.layer_kinds()[self.n_groups * len(pat):]
        else:
            self.n_groups = 0
            self.rem_kinds = self.cfg.layer_kinds()
        self.dtype = jnp.bfloat16 if self.cfg.dtype == "bfloat16" else jnp.float32

    # ---------------- parameters ----------------
    def _build(self, b: Builder):
        cfg = self.cfg
        params: dict = {
            "embed": b.param((self.vocab_padded, cfg.d_model), ("vocab", "embed"), scale=1.0),
            "final_norm": init_norm(b, cfg.d_model, cfg.norm),
        }
        with_cross = cfg.enc_dec
        if self.n_groups > 0:
            sb = StackedBuilder(b, self.n_groups)
            params["blocks"] = {
                f"b{i}": _init_block(sb, cfg, kind, with_cross)
                for i, kind in enumerate(self.pattern)
            }
        for j, kind in enumerate(self.rem_kinds):
            params[f"rem{j}"] = _init_block(b, cfg, kind, with_cross)
        if not cfg.tie_embeddings:
            params["lm_head"] = b.param((cfg.d_model, self.vocab_padded), ("embed", "vocab"))
        if cfg.enc_dec:
            ne = cfg.n_encoder_layers
            seb = StackedBuilder(b, ne)
            params["encoder"] = {"blocks": {"b0": _init_block(seb, cfg, "attn", False)},
                                 "norm": init_norm(b, cfg.d_model, cfg.norm)}
        return params

    def init(self, key) -> dict:
        return self._build(Builder("init", key, dtype=self.dtype))

    def param_specs(self) -> dict:
        return self._build(Builder("spec", dtype=self.dtype))

    def param_pspecs(self, mesh_cfg: MeshConfig) -> dict:
        rules = logical_rules(mesh_cfg, self.cfg, self.shard)
        return spec_tree_to_pspecs(self.param_specs(), rules, mesh_cfg)

    def abstract_params(self) -> dict:
        return jax.tree.map(lambda s: s.sds(), self.param_specs(),
                            is_leaf=lambda x: isinstance(x, ParamSpec))

    def param_count(self) -> int:
        import numpy as np
        return int(sum(np.prod(s.shape) for s in jax.tree.leaves(
            self.param_specs(), is_leaf=lambda x: isinstance(x, ParamSpec))))

    # ---------------- helpers ----------------
    def _constrain(self, x, axes):
        if self.mesh is None:
            return x
        mesh_cfg = MeshConfig(shape=tuple(self.mesh.shape.values()),
                              axes=tuple(self.mesh.shape.keys()))
        rules = logical_rules(mesh_cfg, self.cfg, self.shard)
        spec = sanitize_pspec(x.shape, logical_to_pspec(axes, rules), mesh_cfg)
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    def _embed_in(self, params, tokens_or_embeds):
        if tokens_or_embeds.dtype in (jnp.int32, jnp.int64):
            x = params["embed"][tokens_or_embeds].astype(self.dtype)
        else:
            x = tokens_or_embeds.astype(self.dtype)
        return self._constrain(x, ("batch", "seq", "embed"))

    def _logits(self, params, x):
        x = apply_norm(params["final_norm"], x, self.cfg.norm, self.cfg.norm_eps)
        head = params["embed"].T if self.cfg.tie_embeddings else params["lm_head"]
        logits = (x @ head.astype(x.dtype)).astype(jnp.float32)
        if self.vocab_padded != self.cfg.vocab_size:
            pad_mask = jnp.arange(self.vocab_padded) >= self.cfg.vocab_size
            logits = jnp.where(pad_mask, -1e30, logits)
        return self._constrain(logits, ("batch", "seq", "vocab"))

    def _positions(self, B, S, offset=0):
        pos = jnp.arange(S, dtype=jnp.int32)[None, :] + offset
        pos = jnp.broadcast_to(pos, (B, S))
        if self.cfg.rope_type == "mrope":
            return jnp.broadcast_to(pos[:, None, :], (B, 3, S))   # text-only default
        return pos

    def _run_encoder(self, params, enc_embeds):
        cfg = self.cfg
        x = enc_embeds.astype(self.dtype)
        B, S = x.shape[:2]
        pos = self._positions(B, S)

        def body(h, gp):
            gp = _weight_barrier(gp)
            h, _, _ = _block_full(gp["b0"], cfg, "attn", h, pos, causal=False,
                                  flags=self._flags())
            return h, None

        x, _ = jax.lax.scan(body, x, params["encoder"]["blocks"])
        return apply_norm(params["encoder"]["norm"], x, cfg.norm, cfg.norm_eps)

    def _flags(self):
        dp_size = 1
        if self.mesh is not None:
            sizes = dict(self.mesh.shape)
            dp_size = sizes.get("pod", 1) * sizes.get("data", 1)
        return {
            "moe_dispatch": self.shard.moe_dispatch,
            "causal_skip": self.shard.causal_skip,
            "q_block": self.shard.attn_q_block,
            "kv_block": self.shard.attn_kv_block,
            "constrain": self._constrain if self.shard.pin_kv_layout else None,
            "dp_size": dp_size,
            "moe_constrain": self._constrain if self.mesh is not None else None,
        }

    # ---------------- full-sequence forward ----------------
    def forward(self, params, inputs, enc_inputs=None, positions=None):
        """inputs: tokens (B, S) int32 or embeds (B, S, d).  Returns (logits, aux)."""
        cfg = self.cfg
        x = self._embed_in(params, inputs)
        B, S = x.shape[:2]
        pos = positions if positions is not None else self._positions(B, S)
        enc_out = self._run_encoder(params, enc_inputs) if cfg.enc_dec else None
        flags = self._flags()

        def group_body(h, gp):
            # block loop-invariant hoisting of per-layer weight converts (the
            # CPU backend would otherwise materialize an f32 copy of the WHOLE
            # stacked weights; on TPU bf16 dots are native and this is free)
            gp = _weight_barrier(gp)
            aux = jnp.zeros((), jnp.float32)
            for i, kind in enumerate(self.pattern):
                h, _, a = _block_full(gp[f"b{i}"], cfg, kind, h, pos,
                                      enc_out=enc_out, flags=flags)
                aux = aux + a
            h = self._constrain(h, ("batch", "seq", "embed"))
            return h, aux

        body = group_body
        if self.shard.remat == "block":
            body = jax.checkpoint(group_body, prevent_cse=False)
        aux_total = jnp.zeros((), jnp.float32)
        if self.n_groups > 0:
            x, auxs = jax.lax.scan(body, x, params["blocks"])
            aux_total = aux_total + auxs.sum()
        for j, kind in enumerate(self.rem_kinds):
            x, _, a = _block_full(params[f"rem{j}"], cfg, kind, x, pos,
                                  enc_out=enc_out, flags=flags)
            aux_total = aux_total + a
        return self._logits(params, x), aux_total

    def loss(self, params, batch):
        """batch: {"tokens" | "embeds", "labels", optional "enc_embeds"}.
        Next-token cross-entropy (labels already shifted); -100 masks."""
        logits, aux = self.forward(params, batch.get("tokens", batch.get("embeds")),
                                   enc_inputs=batch.get("enc_embeds"),
                                   positions=batch.get("positions"))
        labels = batch["labels"]
        mask = (labels >= 0).astype(jnp.float32)
        safe = jnp.maximum(labels, 0)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        ce = (logz - gold) * mask
        return ce.sum() / jnp.maximum(mask.sum(), 1.0) + aux

    # ---------------- caches ----------------
    def cache_specs(self, batch: int, max_len: int, enc_len: int = 0) -> dict:
        cfg = self.cfg
        spec: dict = {}
        if self.n_groups > 0:
            spec["blocks"] = {
                f"b{i}": _stack_spec(
                    _block_cache_spec(cfg, kind, batch, max_len, cfg.enc_dec, enc_len,
                                      jnp.bfloat16 if self.dtype == jnp.bfloat16 else jnp.float32),
                    self.n_groups)
                for i, kind in enumerate(self.pattern)
            }
        for j, kind in enumerate(self.rem_kinds):
            dt = jnp.bfloat16 if self.dtype == jnp.bfloat16 else jnp.float32
            spec[f"rem{j}"] = _block_cache_spec(cfg, kind, batch, max_len,
                                                cfg.enc_dec, enc_len, dt)
        return spec

    def cache_pspecs(self, mesh_cfg: MeshConfig, batch: int, max_len: int, enc_len: int = 0):
        rules = logical_rules(mesh_cfg, self.cfg, self.shard)
        return spec_tree_to_pspecs(self.cache_specs(batch, max_len, enc_len), rules, mesh_cfg)

    def init_cache(self, batch: int, max_len: int, enc_len: int = 0) -> dict:
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.cache_specs(batch, max_len, enc_len),
                            is_leaf=lambda x: isinstance(x, ParamSpec))

    def abstract_cache(self, batch: int, max_len: int, enc_len: int = 0) -> dict:
        return jax.tree.map(lambda s: s.sds(), self.cache_specs(batch, max_len, enc_len),
                            is_leaf=lambda x: isinstance(x, ParamSpec))

    def paged_cache_specs(self, n_pages: int, page_size: int, batch: int) -> dict:
        """Cache specs for the paged serving layout: each attention layer
        stack's K/V become one block pool of ``n_pages`` pages shared by all
        slots, laid out by ``ops.pool_shape`` (the paged kernel's order, so
        a page of one layer is read where it lies; a ``rem{j}`` layer is a
        stack of one); the per-slot block tables live host-side in the
        engine's allocator, and one ``len`` (batch,) serves every layer.
        Only decoder-only global-attention stacks qualify — recurrent state
        and ring buffers have no paged form.
        """
        cfg = self.cfg
        assert not cfg.enc_dec and all(k == "attn" for k in cfg.layer_kinds()), \
            "paged KV cache requires a decoder-only global-attention stack"
        dt = jnp.bfloat16 if self.dtype == jnp.bfloat16 else jnp.float32

        def stack(n):
            kv = lambda: ParamSpec(
                pool_shape(n, n_pages, cfg.n_kv_heads, page_size, cfg.head_dim), dt,
                ("layers", "kv_pages", "kv_heads", "kv_page", "head_dim"))
            return {"k": kv(), "v": kv()}

        spec: dict = {"len": ParamSpec((batch,), jnp.int32, ("batch",))}
        if self.n_groups > 0:
            spec["blocks"] = {f"b{i}": stack(self.n_groups)
                              for i in range(len(self.pattern))}
        for j in range(len(self.rem_kinds)):
            spec[f"rem{j}"] = stack(1)
        return spec

    def init_paged_cache(self, n_pages: int, page_size: int, batch: int) -> dict:
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.paged_cache_specs(n_pages, page_size, batch),
                            is_leaf=lambda x: isinstance(x, ParamSpec))

    # ---------------- prefill ----------------
    def prefill(self, params, inputs, max_len: int, enc_inputs=None, lengths=None):
        """Run the full prompt, build caches.  Returns (last_logits, cache).

        ``lengths`` (B,): valid prompt lengths for right-padded batches.  With
        causal attention right-padding never contaminates the valid prefix;
        the returned logits are gathered at each sequence's last valid token
        and cache lengths are set per sequence.
        """
        cfg = self.cfg
        x = self._embed_in(params, inputs)
        B, S = x.shape[:2]
        pos = self._positions(B, S)
        enc_out = self._run_encoder(params, enc_inputs) if cfg.enc_dec else None
        flags = self._flags()
        cache: dict = {}

        if self.n_groups > 0:
            def group_body(h, gp):
                gp = _weight_barrier(gp)
                caches = {}
                for i, kind in enumerate(self.pattern):
                    h, c, _ = _block_full(gp[f"b{i}"], cfg, kind, h, pos, enc_out=enc_out,
                                          want_cache=True, cache_len=max_len, flags=flags)
                    caches[f"b{i}"] = c
                return h, caches

            x, caches = jax.lax.scan(group_body, x, params["blocks"])
            cache["blocks"] = caches
        for j, kind in enumerate(self.rem_kinds):
            x, c, _ = _block_full(params[f"rem{j}"], cfg, kind, x, pos, enc_out=enc_out,
                                  want_cache=True, cache_len=max_len, flags=flags)
            cache[f"rem{j}"] = c
        if lengths is not None:
            # right-padded variable-length prompts: valid only for pure
            # attention stacks (recurrent states would advance through pads)
            assert all(k in ("attn", "local") for k in cfg.layer_kinds()), \
                "variable-length prefill requires attention-only models"
            last = jnp.take_along_axis(x, (lengths - 1)[:, None, None]
                                       .astype(jnp.int32), axis=1)
            logits = self._logits(params, last)
            cache = jax.tree.map(
                lambda leaf: (jnp.broadcast_to(lengths.astype(leaf.dtype), leaf.shape)
                              if leaf.ndim >= 1 and leaf.dtype == jnp.int32
                              and leaf.shape[-1] == B else leaf),
                cache)
        else:
            logits = self._logits(params, x[:, -1:, :])
        return logits, cache

    # ---------------- decode ----------------
    def _paged_layers(self, block_fn, params, x, cache, flags):
        """Run every layer's ``block_fn`` over a paged cache.  Each stacked
        pool is a carry of the layer scan beside the layer index, so a layer
        writes its tokens into the whole stack in place and the paged kernel
        reads the stack at ``[layer]``: no layer's pool is sliced out of the
        stack or written back.  Every layer reads the one ``len``, which
        advances by the tokens' count after the last.  Returns (x, new_cache)."""
        cfg = self.cfg
        lens = cache["len"]

        def layer_fn(p, kind, h, pools, layer):
            h, c = block_fn(p, cfg, kind, h, {**pools, "len": lens},
                            flags={**flags, "kv_layer": layer})
            return h, {"k": c["k"], "v": c["v"]}

        new_cache: dict = {"len": lens + x.shape[1]}
        if self.n_groups > 0:
            def group_body(carry, gp):
                h, gc, layer = carry
                gp = _weight_barrier(gp)
                gc = dict(gc)
                for i, kind in enumerate(self.pattern):
                    h, gc[f"b{i}"] = layer_fn(gp[f"b{i}"], kind, h, gc[f"b{i}"], layer)
                return (h, gc, layer + 1), None

            (x, new_cache["blocks"], _), _ = jax.lax.scan(
                group_body, (x, cache["blocks"], jnp.int32(0)), params["blocks"])
        for j, kind in enumerate(self.rem_kinds):
            x, new_cache[f"rem{j}"] = layer_fn(params[f"rem{j}"], kind, x,
                                               cache[f"rem{j}"], 0)
        return x, new_cache

    def decode_step(self, params, tokens, cache, table=None):
        """tokens: (B, 1) int32 (or (B, 1, d) embeds).  Returns (logits, cache).

        ``table``: optional (B, n_cols) int32 block table switching the
        attention layers onto a paged KV cache (see ``paged_cache_specs``);
        one table serves every layer — all layers page identically.
        """
        cfg = self.cfg
        x = self._embed_in(params, tokens)
        flags = {**self._flags(), "moe_exact": True}   # no capacity drops mid-decode
        if table is not None:
            x, new_cache = self._paged_layers(_block_step, params, x, cache,
                                              {**flags, "kv_table": table})
            return self._logits(params, x), new_cache
        new_cache: dict = {}
        if self.n_groups > 0:
            def group_body(h, xs):
                gp, gc = xs
                gp = _weight_barrier(gp)
                new_gc = {}
                for i, kind in enumerate(self.pattern):
                    h, nc = _block_step(gp[f"b{i}"], cfg, kind, h, gc[f"b{i}"], flags=flags)
                    new_gc[f"b{i}"] = nc
                return h, new_gc

            x, nblocks = jax.lax.scan(group_body, x, (params["blocks"], cache["blocks"]))
            new_cache["blocks"] = nblocks
        for j, kind in enumerate(self.rem_kinds):
            x, nc = _block_step(params[f"rem{j}"], cfg, kind, x, cache[f"rem{j}"], flags=flags)
            new_cache[f"rem{j}"] = nc
        logits = self._logits(params, x)
        return logits, new_cache

    def decode_span(self, params, tokens, cache, table=None):
        """tokens: (B, S) int32 — a short run of S new tokens appended in ONE
        dispatch, returning per-position logits (B, S, V).  The speculative
        verify pass: one fused target forward scores all drafted tokens.

        Causality within the span is enforced by masking (each position sees
        only earlier keys), so the result matches S sequential
        :meth:`decode_step` calls bitwise.  Global-attention decoder-only
        models (the paged-cache constraint); ``table`` as in ``decode_step``.
        """
        cfg = self.cfg
        x = self._embed_in(params, tokens)
        flags = {**self._flags(), "moe_exact": True}
        if table is not None:
            x, new_cache = self._paged_layers(_block_span, params, x, cache,
                                              {**flags, "kv_table": table})
            return self._logits(params, x), new_cache
        new_cache: dict = {}
        if self.n_groups > 0:
            def group_body(h, xs):
                gp, gc = xs
                gp = _weight_barrier(gp)
                new_gc = {}
                for i, kind in enumerate(self.pattern):
                    h, nc = _block_span(gp[f"b{i}"], cfg, kind, h, gc[f"b{i}"],
                                        flags=flags)
                    new_gc[f"b{i}"] = nc
                return h, new_gc

            x, nblocks = jax.lax.scan(group_body, x, (params["blocks"], cache["blocks"]))
            new_cache["blocks"] = nblocks
        for j, kind in enumerate(self.rem_kinds):
            x, nc = _block_span(params[f"rem{j}"], cfg, kind, x, cache[f"rem{j}"],
                                flags=flags)
            new_cache[f"rem{j}"] = nc
        logits = self._logits(params, x)
        return logits, new_cache
