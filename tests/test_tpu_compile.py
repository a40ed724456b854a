"""Compile-only rehearsal for one chip of a described TPU v5e (``v5e:2x2``).

The TPU compiler compiles the serving path's Pallas kernels and the fused
paged decode step at real widths for a chip that is described, not attached.
That catches what interpret mode cannot: tiles not aligned to the hardware,
kernels asking for more fast memory than they may use, programs that do not
fit the device.  Nothing here runs, so it says nothing about results or
times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import ShardingConfig, get_arch
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import paged_attention_pallas

HBM_BYTES = 16 * 2**30          # one TPU v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    """Shapes of ``tree`` placed on the described chip."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)


def _dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


@pytest.mark.parametrize("arch,slots,max_len", [
    ("qwen1.5-0.5b", 8, 1024),      # the engine phase of chip_smoke.py
    ("tiny-l", 4, 512),             # the tiny pool's widest member (f32)
])
def test_paged_attention_compiles(one_chip, arch, slots, max_len):
    cfg = get_arch(arch)
    ps = 16
    cols = max_len // ps
    kv = (slots * cols, ps, cfg.n_kv_heads, cfg.head_dim)
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((slots, 1, cfg.n_heads, cfg.head_dim), _dtype(cfg)),
        jax.ShapeDtypeStruct(kv, _dtype(cfg)), jax.ShapeDtypeStruct(kv, _dtype(cfg)),
        jax.ShapeDtypeStruct((slots, cols), jnp.int32),
        jax.ShapeDtypeStruct((slots,), jnp.int32)))
    compiled = jax.jit(paged_attention_pallas).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("seq", [16, 128, 512])
def test_flash_attention_compiles(one_chip, seq):
    cfg = get_arch("qwen1.5-0.5b")
    sc = ShardingConfig()
    B, D = 8, cfg.head_dim
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((B, seq, cfg.n_heads, D), jnp.bfloat16),
        jax.ShapeDtypeStruct((B, seq, cfg.n_kv_heads, D), jnp.bfloat16),
        jax.ShapeDtypeStruct((B, seq, cfg.n_kv_heads, D), jnp.bfloat16)))
    fn = functools.partial(flash_attention_pallas, causal=True,
                           q_block=sc.attn_q_block, kv_block=sc.attn_kv_block)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_paged_decode_compiles(one_chip, monkeypatch):
    """The engine's K=8 fused paged decode for a 2-layer cut of Qwen1.5-0.5B
    at full width: the kernel is in the program and the program fits."""
    from repro.kernels import ops
    from repro.models.transformer import Model
    from repro.serving.engine import ServingEngine

    # kernel selection asks the default backend, which is the CPU here
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(get_arch("qwen1.5-0.5b"), n_layers=2)
    model = Model(cfg, ShardingConfig(remat="none"))
    slots, max_len = 8, 1024
    engine = ServingEngine(model, None, max_slots=slots, max_len=max_len,
                           decode_block=8, paged=True, page_size=16)
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32)
    args = _on(one_chip, (
        model.abstract_params(), engine.cache,
        jax.ShapeDtypeStruct((slots, max_len // 16), jnp.int32), vec,
        jax.ShapeDtypeStruct((slots,), jnp.bool_), vec, vec))
    compiled = engine._decode_k_paged.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, mem
