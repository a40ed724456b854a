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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.layout import Format
from jax.sharding import SingleDeviceSharding

from repro.config import ShardingConfig, get_arch
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ops import pool_shape
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
    kv = pool_shape(cfg.n_layers, slots * cols, cfg.n_kv_heads, ps, cfg.head_dim)
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((slots, 1, cfg.n_heads, cfg.head_dim), _dtype(cfg)),
        jax.ShapeDtypeStruct(kv, _dtype(cfg)), jax.ShapeDtypeStruct(kv, _dtype(cfg)),
        jax.ShapeDtypeStruct((slots, cols), jnp.int32),
        jax.ShapeDtypeStruct((slots,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)))
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


def _pool_movers(hlo: str, pool_dims) -> list[str]:
    """Instructions of a compiled program whose result has the dims of a KV
    pool but that are not an in-place update (``dynamic-update-slice``,
    ``scatter``, or a fusion whose root is one) or a name for a buffer that
    is there already (``parameter``, ``get-tuple-element``): copies, slices,
    relayouts and their fusions."""
    head = re.compile(r"^(?:ENTRY )?%(\S+) \(")
    inst = re.compile(r"^\s*(ROOT )?%\S+ = \w+\[([\d,]*)\]\S* ([\w-]+)\(")
    calls = re.compile(r"calls=%([\w.-]+)")
    in_place = {"dynamic-update-slice", "scatter"}
    roots, found, comp = {}, [], None
    for line in hlo.splitlines():
        if (h := head.match(line)):
            comp = h.group(1)
        elif (m := inst.match(line)):
            if m.group(1):
                roots[comp] = m.group(3)
            dims = tuple(int(d) for d in m.group(2).split(",") if d)
            if dims in pool_dims:
                c = calls.search(line)
                found.append((m.group(3), c and c.group(1), line.strip()[:160]))
    return [text for op, fused, text in found
            if op not in in_place | {"parameter", "get-tuple-element"}
            and not (op == "fusion" and roots.get(fused) in in_place)]


@pytest.mark.parametrize("slots,max_len,temp_layers", [
    (1, 2048, 1),                   # the benchmark's closed cell
    # the engine phase of chip_smoke.py: here the decode also relayouts one
    # 50 MB weight stack into temporaries, which is no part of the pool
    (8, 1024, 24),
])
def test_paged_programs_keep_the_pool_in_place(one_chip, monkeypatch, slots, max_len,
                                               temp_layers):
    """The engine of Qwen1.5-0.5B at full width and depth, page 16, compiles
    its fused decode and its admission insert with the KV pool row-major at
    the program's edge by default and no pool-sized or layer-sized copy,
    slice or relayout inside; temporaries stay under ``temp_layers``
    layers' K pool, so under one copy of the pool.  Full depth, since the
    compiler stages a pool of up to about 16 MB (a cut of 2 or 4 layers)
    whole into the chip's fast memory for each dispatch."""
    from repro.kernels import ops
    from repro.models.transformer import Model
    from repro.serving.engine import ServingEngine

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = get_arch("qwen1.5-0.5b")
    n_layers = cfg.n_layers
    model = Model(cfg, ShardingConfig(remat="none"))
    ps, prompt = 16, 256
    cols = max_len // ps
    engine = ServingEngine(model, None, max_slots=slots, max_len=max_len,
                           decode_block=8, paged=True, page_size=ps)
    P, Hk, D = engine.kv.alloc.n_pages, cfg.n_kv_heads, cfg.head_dim
    layer_pool = pool_shape(1, P, Hk, ps, D)[1:]
    layer_k_bytes = P * Hk * ps * D * 2                 # bf16
    cache = _on(one_chip, engine.cache)
    kv_rows = jax.ShapeDtypeStruct((n_layers, slots, prompt, Hk, D), jnp.bfloat16)
    rows = {"blocks": {"b0": {"k": kv_rows, "v": kv_rows, "len": jax.ShapeDtypeStruct(
        (n_layers, slots), jnp.int32)}}}
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32)
    decode = engine._decode_k_paged.lower(*_on(one_chip, (
        model.abstract_params(), engine.cache,
        jax.ShapeDtypeStruct((slots, cols), jnp.int32), vec,
        jax.ShapeDtypeStruct((slots,), jnp.bool_), vec, vec))).compile()
    insert = engine._insert_pages.lower(cache, *_on(one_chip, (
        rows, vec, jax.ShapeDtypeStruct((slots, prompt // ps), jnp.int32)))).compile()
    assert "tpu_custom_call" in decode.as_text()
    for name, compiled, pool_in, pool_out in (
            ("decode", decode, decode.input_formats[0][1], decode.output_formats[0]),
            ("insert", insert, insert.input_formats[0][0], insert.output_formats)):
        movers = _pool_movers(compiled.as_text(),
                              {(n_layers, *layer_pool), (1, *layer_pool), layer_pool})
        assert movers == [], (name, movers)
        pools = [f for f in jax.tree.leaves(
            (pool_in, pool_out), is_leaf=lambda x: isinstance(x, Format))
            if len(f.layout.major_to_minor) == 5]
        assert len(pools) == 4, (name, pools)           # K and V, in and out
        for fmt in pools:
            assert fmt.layout.major_to_minor == tuple(range(5)), (name, fmt)
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < temp_layers * layer_k_bytes, (name, temp, layer_k_bytes)
