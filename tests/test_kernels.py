"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes kernel bodies on CPU), plus the ops-layer chunked
fallbacks against the same oracles, plus hypothesis property sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.rglru_scan import rglru_pallas
from repro.kernels.rwkv6_scan import wkv6_pallas

RNG = np.random.default_rng(0)


def arr(*shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,Hk,D,causal,win,qb,kb", [
    (2, 128, 4, 2, 32, True, None, 32, 32),
    (1, 64, 8, 8, 16, True, 16, 16, 16),
    (2, 96, 4, 1, 32, True, None, 32, 16),     # MQA, uneven blocks
    (1, 128, 2, 2, 64, False, None, 64, 32),   # bidirectional (encoder)
])
def test_flash_attention_vs_oracle(dtype, B, S, H, Hk, D, causal, win, qb, kb):
    q, k, v = (arr(B, S, H, D, dtype=dtype), arr(B, S, Hk, D, dtype=dtype),
               arr(B, S, Hk, D, dtype=dtype))
    want = ref.mha_ref(q, k, v, causal=causal, window=win)
    got = flash_attention_pallas(q, k, v, causal=causal, window=win,
                                 q_block=qb, kv_block=kb, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 3), st.integers(1, 8), st.integers(0, 2), st.booleans())
def test_flash_attention_property(B, sblocks, hk_pow, causal):
    """Random (shape, GQA ratio) sweep at block granularity."""
    S = 16 * sblocks
    Hk = 2 ** hk_pow
    H = Hk * 2
    D = 16
    q, k, v = arr(B, S, H, D), arr(B, S, Hk, D), arr(B, S, Hk, D)
    want = ref.mha_ref(q, k, v, causal=causal)
    got = flash_attention_pallas(q, k, v, causal=causal, q_block=16, kv_block=16,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5, rtol=3e-5)


def test_ops_flash_matches_oracle_uneven_and_offset():
    """ops fallback covers decode-style q/kv offset the kernel does not."""
    q, k, v = arr(2, 17, 4, 8), arr(2, 33, 2, 8), arr(2, 33, 2, 8)
    want = ref.mha_ref(q, k, v, causal=True)
    got = ops.flash_attention(q, k, v, causal=True, q_block=8, kv_block=8, backend="jnp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal,win", [(True, None), (True, 8), (False, None)])
def test_ops_flash_pallas_gradient_matches_oracle(causal, win):
    """Training through the Pallas path (the tiny pool trains on the chip):
    its gradient is the oracle's gradient."""
    q, k, v = arr(2, 32, 4, 16), arr(2, 32, 2, 16), arr(2, 32, 2, 16)
    w = arr(2, 32, 4, 16)

    def loss(attn):
        return lambda q, k, v: (attn(q, k, v) * w).sum()

    got = jax.grad(loss(lambda q, k, v: ops.flash_attention(
        q, k, v, causal=causal, window=win, q_block=16, kv_block=16,
        backend="pallas", interpret=True)), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: ref.mha_ref(
        q, k, v, causal=causal, window=win)), argnums=(0, 1, 2))(q, k, v)
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w_), atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,Hk,D,kb", [
    (3, 40, 8, 2, 16, 16),
    (2, 128, 4, 4, 32, 32),
    (1, 100, 8, 1, 64, 32),
])
def test_decode_attention_vs_oracle(dtype, B, S, H, Hk, D, kb):
    q = arr(B, 1, H, D, dtype=dtype)
    k, v = arr(B, S, Hk, D, dtype=dtype), arr(B, S, Hk, D, dtype=dtype)
    lengths = jnp.asarray(RNG.integers(1, S + 1, B), jnp.int32)
    want = ref.decode_attention_ref(q, k, v, lengths)
    got = decode_attention_pallas(q, k, v, lengths, kv_block=kb, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 4), st.integers(2, 60), st.integers(0, 2))
def test_decode_attention_property(B, S, gq):
    Hk, D = 2, 16
    H = Hk * 2 ** gq
    q, k, v = arr(B, 1, H, D), arr(B, S, Hk, D), arr(B, S, Hk, D)
    lengths = jnp.asarray(RNG.integers(1, S + 1, B), jnp.int32)
    want = ref.decode_attention_ref(q, k, v, lengths)
    got = decode_attention_pallas(q, k, v, lengths, kv_block=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# paged decode attention
# ---------------------------------------------------------------------------

LAYERS, LAYER = 3, 1      # paged pools are layer stacks; the data sits at LAYER


def _stack(pages, layer=LAYER, n_layers=LAYERS):
    """``(P, page, Hk, D)`` pages -> the stacked pool the kernel reads
    (``ops.pool_shape``), the pages at ``layer`` and large garbage
    elsewhere."""
    pages = np.asarray(ops.pack_pages(jnp.asarray(pages, jnp.float32)
                                      .transpose(0, 2, 1, 3)))
    pool = np.full((n_layers, *pages.shape), 1e3, np.float32)
    pool[layer] = pages
    return pool


def _paginate(k, v, page_size, lengths, n_extra=3):
    """Scatter contiguous ``(B, S, Hk, D)`` K/V into a shuffled page pool.

    Returns ``(k_pool, v_pool, table)`` where ``table[b, j]`` is the physical
    page holding logical positions ``[j*ps, (j+1)*ps)`` of row ``b`` in layer
    ``LAYER`` of a stacked pool.  Table entries for pages entirely past
    ``lengths[b]`` are the sentinel ``P`` (matching the engine's unmapped-
    column convention), the pool carries ``n_extra`` unreferenced pages, and
    every out-of-range element (other layers included) is filled with large
    garbage so any leak through the length mask is loud.
    """
    B, S, Hk, D = k.shape
    n_tab = -(-S // page_size)
    kp = np.full((B, n_tab * page_size, Hk, D), 1e3, np.float32)
    vp = np.full_like(kp, 1e3)
    kp[:, :S] = np.asarray(k, np.float32)
    vp[:, :S] = np.asarray(v, np.float32)
    P = B * n_tab + n_extra
    phys = RNG.permutation(P)[: B * n_tab].reshape(B, n_tab)
    k_pool = np.full((P, page_size, Hk, D), 1e3, np.float32)
    v_pool = np.full_like(k_pool, 1e3)
    k_pool[phys.reshape(-1)] = kp.reshape(B * n_tab, page_size, Hk, D)
    v_pool[phys.reshape(-1)] = vp.reshape(B * n_tab, page_size, Hk, D)
    table = np.where(np.arange(n_tab)[None] * page_size < np.asarray(lengths)[:, None],
                     phys, P)
    return (jnp.asarray(_stack(k_pool), k.dtype), jnp.asarray(_stack(v_pool), v.dtype),
            jnp.asarray(table, jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("page_size", [16, 64])
@pytest.mark.parametrize("B,S,H,Hk,D", [
    (3, 40, 8, 2, 16),
    (2, 130, 4, 4, 32),
    (1, 64, 8, 1, 64),
])
def test_paged_attention_vs_oracle(dtype, page_size, B, S, H, Hk, D):
    q = arr(B, 1, H, D, dtype=dtype)
    k, v = arr(B, S, Hk, D, dtype=dtype), arr(B, S, Hk, D, dtype=dtype)
    # ragged lengths, always including one full-length row
    lengths = np.append(RNG.integers(1, S + 1, B - 1), S).astype(np.int32)
    k_pool, v_pool, table = _paginate(k, v, page_size, lengths)
    want = ref.decode_attention_ref(q, k, v, jnp.asarray(lengths))
    got = paged_attention_pallas(q, k_pool, v_pool, table, jnp.asarray(lengths),
                                 LAYER, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_paged_attention_shared_and_forked_pages():
    """Two slots alias the same physical prefix pages; slot 1's tail page is a
    CoW fork holding divergent tokens.  Each row must match the dense oracle
    on its own logical sequence — sharing is invisible to attention."""
    ps, H, Hk, D = 16, 4, 2, 16
    S = 3 * ps
    pre_k, pre_v = arr(1, 2 * ps, Hk, D), arr(1, 2 * ps, Hk, D)
    tails = [(arr(1, ps, Hk, D), arr(1, ps, Hk, D)) for _ in range(2)]
    k = jnp.concatenate([jnp.concatenate([pre_k, tk], 1) for tk, _ in tails], 0)
    v = jnp.concatenate([jnp.concatenate([pre_v, tv], 1) for _, tv in tails], 0)
    # pool: pages 0-1 = shared prefix, 2 = slot0 tail, 3 = slot1 fork, 4 = junk
    k_pool = jnp.concatenate([pre_k.reshape(2, ps, Hk, D), tails[0][0], tails[1][0],
                              jnp.full((1, ps, Hk, D), 1e3)], 0)
    v_pool = jnp.concatenate([pre_v.reshape(2, ps, Hk, D), tails[0][1], tails[1][1],
                              jnp.full((1, ps, Hk, D), 1e3)], 0)
    table = jnp.asarray([[0, 1, 2], [0, 1, 3]], jnp.int32)
    lengths = jnp.asarray([S, S - 5], jnp.int32)   # forked row mid-page
    q = arr(2, 1, H, D)
    want = ref.decode_attention_ref(q, k, v, lengths)
    got = paged_attention_pallas(q, jnp.asarray(_stack(k_pool)),
                                 jnp.asarray(_stack(v_pool)), table, lengths, LAYER,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_ops_paged_matches_oracle_jnp_fallback():
    """The ops-layer gather fallback agrees with the dense oracle (and hence
    with the kernel) on the same shuffled, sentinel-bearing table."""
    B, S, H, Hk, D, ps = 3, 70, 4, 2, 16, 16
    q, k, v = arr(B, 1, H, D), arr(B, S, Hk, D), arr(B, S, Hk, D)
    lengths = np.asarray([1, 37, 70], np.int32)
    k_pool, v_pool, table = _paginate(k, v, ps, lengths)
    want = ref.decode_attention_ref(q, k, v, jnp.asarray(lengths))
    got = ops.paged_attention(q, k_pool, v_pool, table, jnp.asarray(lengths), LAYER,
                              backend="jnp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("D", [64, 80])         # packed two to a row; padded
@pytest.mark.parametrize("layer", [0, 2, 4])
def test_paged_attention_reads_its_layer_of_the_stack(layer, D):
    """The kernel reads ``[layer]`` of a five-layer stacked pool, laid out
    as the engine stores it, exactly where the jnp gather does — first,
    middle and last layer — with sentinel table columns and one physical
    page shared by both rows."""
    L, P, ps, H, Hk = 5, 9, 16, 4, 2
    k_pool, v_pool = (ops.pack_pages(arr(L, P, Hk, ps, D)) for _ in range(2))
    assert k_pool.shape == ops.pool_shape(L, P, Hk, ps, D)
    table = jnp.asarray([[3, 7, 1, P], [3, 5, P, P]], jnp.int32)   # page 3 shared
    lengths = jnp.asarray([3 * ps - 3, ps + 9], jnp.int32)
    q = arr(2, 1, H, D)
    want = ops._paged_jnp(q, k_pool, v_pool, table, lengths, layer)
    got = paged_attention_pallas(q, k_pool, v_pool, table, lengths, layer,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
    other = ops._paged_jnp(q, k_pool, v_pool, table, lengths, (layer + 1) % L)
    assert not np.allclose(np.asarray(got), np.asarray(other), atol=1e-3)


@pytest.mark.parametrize("D", [16, 64, 80])     # eight to a row; two; padded
def test_pool_writes_land_where_reads_find_them(D):
    """``ops.write_tokens`` puts every position of a slot's pages where
    ``gather_pages`` (and so the kernel's layout) reads it, in any order;
    writes to the sentinel page drop, and no other layer or page moves."""
    L, P, ps, Hk, layer = 3, 6, 16, 2, 1
    table = np.asarray([[4, 0, 2]], np.int32)
    n = table.shape[1] * ps
    kv = arr(n + 2, Hk, D)
    order = RNG.permutation(n + 2)                 # the last two are dropped
    phys = np.append(table[0, np.arange(n) // ps], [P, P])[order]
    pos = (np.arange(n + 2) % ps)[order]
    pool = jax.jit(ops.write_tokens, static_argnums=1)(
        jnp.zeros(ops.pool_shape(L, P, Hk, ps, D)), layer, jnp.asarray(phys),
        jnp.asarray(pos), kv[order])
    got = ops.gather_pages(pool, jnp.asarray(table), layer, D)[0]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(kv[:n]))
    untouched = np.ones(pool.shape[:2], bool)
    untouched[layer, table[0]] = False
    assert not np.any(np.asarray(pool)[untouched])


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 3), st.integers(2, 70), st.sampled_from([16, 32]), st.integers(0, 2))
def test_paged_attention_property(B, S, page_size, gq):
    """Random shapes / GQA ratios / page sizes: paged gather == dense oracle."""
    Hk, D = 2, 16
    H = Hk * 2 ** gq
    q, k, v = arr(B, 1, H, D), arr(B, S, Hk, D), arr(B, S, Hk, D)
    lengths = RNG.integers(1, S + 1, B).astype(np.int32)
    k_pool, v_pool, table = _paginate(k, v, page_size, lengths)
    want = ref.decode_attention_ref(q, k, v, jnp.asarray(lengths))
    got = paged_attention_pallas(q, k_pool, v_pool, table, jnp.asarray(lengths),
                                 LAYER, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# WKV6
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,T,H,D,chunk", [
    (2, 50, 3, 8, 16),
    (1, 64, 2, 16, 32),
    (2, 33, 4, 8, 8),       # non-multiple T
])
def test_wkv6_vs_oracle(dtype, B, T, H, D, chunk):
    r, k, v = (arr(B, T, H, D, dtype=dtype) for _ in range(3))
    w = jnp.asarray(RNG.uniform(0.4, 0.999, size=(B, T, H, D)), dtype)
    u = arr(H, D, scale=0.5)
    st0 = arr(B, H, D, D, scale=0.1)
    want, s_want = ref.wkv6_ref(r, k, v, w, u, state=st0)
    got, s_got = wkv6_pallas(r, k, v, w, u, state=st0, chunk=chunk, interpret=True)
    tol = 2e-4 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(s_got), np.asarray(s_want), atol=tol, rtol=tol)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 2), st.integers(2, 40), st.floats(0.3, 0.99))
def test_wkv6_property_decay_sweep(B, T, wmin):
    H, D = 2, 8
    r, k, v = (arr(B, T, H, D) for _ in range(3))
    w = jnp.asarray(RNG.uniform(wmin, 0.999, size=(B, T, H, D)), jnp.float32)
    u = arr(H, D, scale=0.5)
    want, _ = ref.wkv6_ref(r, k, v, w, u)
    got, _ = wkv6_pallas(r, k, v, w, u, chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-4, rtol=5e-4)


def test_wkv6_ops_chunk_invariance():
    """The chunked jnp fallback must be chunk-size invariant."""
    B, T, H, D = 1, 48, 2, 8
    r, k, v = (arr(B, T, H, D) for _ in range(3))
    w = jnp.asarray(RNG.uniform(0.5, 0.99, size=(B, T, H, D)), jnp.float32)
    u = arr(H, D, scale=0.5)
    o1, s1 = ops.wkv6(r, k, v, w, u, chunk=8, backend="jnp")
    o2, s2 = ops.wkv6(r, k, v, w, u, chunk=48, backend="jnp")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# RG-LRU
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,T,W,chunk,wb", [
    (2, 33, 16, 8, 16),
    (1, 100, 64, 32, 32),
    (3, 17, 32, 256, 16),   # chunk > T
])
def test_rglru_vs_oracle(dtype, B, T, W, chunk, wb):
    x = arr(B, T, W, dtype=dtype)
    a_log = -jnp.abs(arr(B, T, W, scale=0.5)).astype(jnp.float32)
    st0 = arr(B, W)
    want, s_want = ref.rglru_ref(x, a_log, state=st0)
    got, s_got = rglru_pallas(x, a_log, state=st0, chunk=chunk, w_block=wb,
                              interpret=True)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(s_got), np.asarray(s_want), atol=tol, rtol=tol)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 3), st.integers(1, 50), st.floats(0.05, 3.0))
def test_rglru_property(B, T, decay_scale):
    W = 16
    x = arr(B, T, W)
    a_log = -jnp.abs(arr(B, T, W)) * decay_scale
    want, s_want = ref.rglru_ref(x, a_log)
    got, s_got = ops.rglru_scan(x, a_log, backend="jnp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s_got), np.asarray(s_want), atol=1e-4, rtol=1e-4)


def test_state_chaining_equals_full_run():
    """Running two halves with carried state == one full run (all kernels)."""
    B, T, H, D = 1, 32, 2, 8
    r, k, v = (arr(B, T, H, D) for _ in range(3))
    w = jnp.asarray(RNG.uniform(0.5, 0.99, size=(B, T, H, D)), jnp.float32)
    u = arr(H, D, scale=0.5)
    full, s_full = wkv6_pallas(r, k, v, w, u, chunk=8, interpret=True)
    h1, s1 = wkv6_pallas(r[:, :16], k[:, :16], v[:, :16], w[:, :16], u, chunk=8, interpret=True)
    h2, s2 = wkv6_pallas(r[:, 16:], k[:, 16:], v[:, 16:], w[:, 16:], u, state=s1,
                         chunk=8, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([h1, h2], 1)),
                               np.asarray(full), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full), atol=1e-4, rtol=1e-4)
