"""Fused serving-engine generation path: greedy parity of the K-step
scan decode + batched bucket-grouped prefill against the per-token reference
driver, donation safety of the cache-carrying jits, and the host-dispatch
accounting the fusion exists to shrink."""
import jax
import numpy as np
import pytest

from repro.config import ShardingConfig, get_arch
from repro.data.tokenizer import ByteTokenizer
from repro.kernels import ops
from repro.models.transformer import Model
from repro.serving.engine import Request, ServingEngine


@pytest.fixture(scope="module")
def tiny():
    cfg = get_arch("tiny-s")
    model = Model(cfg, ShardingConfig(remat="none"))
    return model, model.init(jax.random.PRNGKey(3))


TOK = ByteTokenizer()
MAX_LEN = 160


def _requests():
    """Mixed-retirement workload: varying prompt lengths (spanning length
    buckets), varying max_new (max_new retirement at 1, 3, …), and one prompt
    long enough to hit the max_len−1 total-length ceiling."""
    prompts = [f"query number {i} " + "abc" * (7 * i) for i in range(6)]
    prompts.append("z" * (MAX_LEN - 8))            # total-length retirement
    max_news = (3, 1, 17, 40, 8, 25, 32)
    return [Request(rid=i, tokens=TOK.encode(p), max_new=m)
            for i, (p, m) in enumerate(zip(prompts, max_news))]


@pytest.fixture(scope="module")
def eos_id(tiny):
    """An eos id the untrained model actually emits mid-stream, so the parity
    sweep exercises genuine EOS retirement (not just max_new/max_len).
    Depends only on (model, params) — probed once for the whole module."""
    model, params = tiny
    probe = ServingEngine(model, params, max_slots=4, max_len=MAX_LEN, eos_id=-1)
    reqs = _requests()
    probe.serve_stepwise(reqs)
    counts: dict[int, int] = {}
    for r in reqs:
        for t in r.out_tokens[1:]:
            counts[t] = counts.get(t, 0) + 1
    return max(counts, key=counts.get)


@pytest.mark.parametrize("slots", [1, 8])
@pytest.mark.parametrize("k", [1, 4, 8])
def test_fused_decode_parity_with_stepwise(tiny, eos_id, slots, k):
    model, params = tiny
    eos = eos_id
    ref = ServingEngine(model, params, max_slots=slots, max_len=MAX_LEN, eos_id=eos)
    r_ref = _requests()
    ref.serve_stepwise(r_ref)
    # the workload genuinely mixes retirement causes
    by_eos = [r for r in r_ref if eos in r.out_tokens]
    by_len = [r for r in r_ref if eos not in r.out_tokens]
    assert by_eos and by_len, "workload must retire by EOS and by max_new/max_len"
    assert all(r.done for r in r_ref)

    eng = ServingEngine(model, params, max_slots=slots, max_len=MAX_LEN,
                        decode_block=k, eos_id=eos)
    r_fused = _requests()
    eng.serve(r_fused)
    for a, b in zip(r_ref, r_fused):
        assert a.out_tokens == b.out_tokens, f"rid {a.rid} diverged"
        assert b.done
    # the fusion's point: K tokens per host dispatch, not one
    assert eng.n_decode_steps == eng.n_decode_calls * k
    if k > 1:
        assert eng.n_decode_calls < ref.n_decode_calls
    # batched admission: never more prefill dispatches than serving ticks
    assert eng.n_prefill_calls <= ref.n_prefill_calls


def test_generate_text_roundtrip_unchanged(tiny):
    # generate_text rides the fused path; sequential-vs-batched equality is
    # the legacy engine invariant and must survive the rewrite
    model, params = tiny
    prompts = [f"query number {i}" for i in range(5)]
    eng = ServingEngine(model, params, max_slots=2, max_len=128)
    batched = eng.generate_text(prompts, max_new=8)
    seq = []
    for p in prompts:
        e1 = ServingEngine(model, params, max_slots=1, max_len=128)
        seq.append(e1.generate_text([p], max_new=8)[0])
    assert batched == seq


def test_readmission_clears_stale_lifecycle_fields(tiny):
    model, params = tiny
    eng = ServingEngine(model, params, max_slots=2, max_len=128, eos_id=-1)
    req = Request(rid=0, tokens=TOK.encode("hello"), max_new=4)
    req.done = True                   # stale state from a failed prior attempt
    req.finished_at = 123.0
    eng.serve([req])
    assert req.done and req.finished_at != 123.0
    assert req.started_at is not None and req.finished_at >= req.started_at
    assert len(req.out_tokens) <= 4 + 1


# ---------------------------------------------------------------------------
# donation safety
# ---------------------------------------------------------------------------

def _first_kv_leaf(cache):
    return jax.tree.leaves(cache)[0]


def test_decode_k_donates_cache_in_place(tiny):
    model, params = tiny
    eng = ServingEngine(model, params, max_slots=4, max_len=128,
                        decode_block=4, eos_id=-1)
    reqs = [Request(rid=i, tokens=TOK.encode(f"donate {i}"), max_new=64)
            for i in range(4)]
    eng._admit_free(list(reqs))
    import jax.numpy as jnp

    last, act, n_out, limit = eng._slot_state()
    args = (jnp.asarray(last), jnp.asarray(act), jnp.asarray(n_out),
            jnp.asarray(limit))
    horizon = eng.max_len
    old = eng.cache
    p0 = _first_kv_leaf(old).unsafe_buffer_pointer()
    cache1, _act, _t, _v = eng._decode_k(horizon, eng.params, old, *args)
    donated = _first_kv_leaf(cache1).unsafe_buffer_pointer() == p0
    if donated:   # backend honors donation (CPU does on current jax)
        # use-after-donate must be impossible: the donated input is dead
        with pytest.raises(RuntimeError):
            _ = _first_kv_leaf(old) + 0
        # and the buffer identity stays stable across further fused steps
        cache2, *_ = eng._decode_k(horizon, eng.params, cache1, *args)
        assert _first_kv_leaf(cache2).unsafe_buffer_pointer() == p0
        eng.cache = cache2
    else:
        eng.cache = cache1
    # either way the engine state is live — no use-after-donate anywhere
    more = [Request(rid=9, tokens=TOK.encode("after"), max_new=3)]
    eng.serve(more)
    assert more[0].done


def test_insert_donates_and_engine_survives_interleaving(tiny):
    model, params = tiny
    eng = ServingEngine(model, params, max_slots=4, max_len=128, decode_block=8)
    p0 = _first_kv_leaf(eng.cache).unsafe_buffer_pointer()
    eng.serve([Request(rid=0, tokens=TOK.encode("first"), max_new=6)])
    ptr = _first_kv_leaf(eng.cache).unsafe_buffer_pointer()
    # serve again on the same engine: donated buffers were rewired, not leaked
    out = eng.generate_text(["second prompt"], max_new=6)
    assert len(out) == 1
    if ptr == p0:        # donation honored end-to-end: still the same buffer
        assert _first_kv_leaf(eng.cache).unsafe_buffer_pointer() == p0


# ---------------------------------------------------------------------------
# paged KV cache: parity, prefix sharing, CoW, donation
# ---------------------------------------------------------------------------

# a batch-prompt-style shared system prefix, long enough to span whole pages
SYS = "system: you are a terse assistant; answer every query in order. "


def _shared_requests():
    """The batch-prompting shape: every prompt opens with the same system
    prefix (several full pages at page_size=16), then diverges; retirement
    still mixes max_new sizes and a total-length ceiling."""
    prompts = [SYS + f"query number {i} " + "abc" * (5 * i) for i in range(5)]
    prompts.append(SYS)                            # prompt == the bare prefix
    prompts.append("z" * (MAX_LEN - 8))            # no shared prefix at all
    max_news = (3, 1, 17, 40, 8, 25, 32)
    return [Request(rid=i, tokens=TOK.encode(p), max_new=m)
            for i, (p, m) in enumerate(zip(prompts, max_news))]


@pytest.fixture(scope="module")
def stepwise_outputs(tiny, eos_id):
    """Greedy reference streams from the contiguous per-token driver — the
    fixed point every paged configuration must reproduce bit-for-bit."""
    model, params = tiny
    outs = {}
    for maker in (_requests, _shared_requests):
        eng = ServingEngine(model, params, max_slots=4, max_len=MAX_LEN,
                            eos_id=eos_id)
        rs = maker()
        eng.serve_stepwise(rs)
        outs[maker.__name__] = [list(r.out_tokens) for r in rs]
    return outs


@pytest.mark.parametrize("slots", [1, 8])
@pytest.mark.parametrize("k", [1, 4, 8])
@pytest.mark.parametrize("share", [True, False])
def test_paged_parity_with_contiguous(tiny, eos_id, stepwise_outputs, slots,
                                      k, share):
    """Paged serve() is bit-identical to the contiguous stepwise reference
    (and, by the fused-parity test above, to contiguous serve()) across
    K × slots × share-prefix; every page returns to the pool at drain."""
    model, params = tiny
    maker = _shared_requests if share else _requests
    eng = ServingEngine(model, params, max_slots=slots, max_len=MAX_LEN,
                        decode_block=k, eos_id=eos_id, paged=True,
                        page_size=16, share_prefix=share)
    rs = maker()
    eng.serve(rs)
    for r, want in zip(rs, stepwise_outputs[maker.__name__]):
        assert r.out_tokens == want, f"rid {r.rid} diverged"
        assert r.done
    eng.kv.alloc.check(tables=eng.kv.slot_pages)
    assert eng.kv.alloc.pages_in_use == 0          # fully drained
    if share and slots > 1:
        assert eng.kv.alloc.n_shares > 0           # sharing actually engaged


def test_paged_identical_prompts_fork_on_first_write(tiny, stepwise_outputs,
                                                     eos_id):
    """Identical prompts share ALL prompt pages (partial tail included);
    the first decode append then CoW-forks the boundary page — outputs must
    still match the contiguous reference exactly."""
    model, params = tiny

    def run(paged):
        eng = ServingEngine(model, params, max_slots=8, max_len=MAX_LEN,
                            decode_block=8, eos_id=eos_id, paged=paged,
                            page_size=16)
        rs = [Request(rid=i, tokens=TOK.encode(SYS), max_new=6 + i)
              for i in range(6)]
        (eng.serve if paged else eng.serve_stepwise)(rs)
        return [r.out_tokens for r in rs], eng

    want, _ = run(False)
    got, eng = run(True)
    assert got == want
    a = eng.kv.alloc
    assert a.n_forks > 0, "CoW never fired on a shared boundary page"
    assert a.pages_in_use == 0
    a.check(tables=eng.kv.slot_pages)


def test_paged_pool_keeps_its_row_major_layout(tiny, eos_id):
    """The pool is stored as ``ops.pool_shape`` lays it out, the paged
    kernel's order, with one length per slot, and stays row-major through
    admission, CoW fork and decode."""
    model, params = tiny
    cfg = model.cfg
    eng = ServingEngine(model, params, max_slots=8, max_len=MAX_LEN,
                        decode_block=8, eos_id=eos_id, paged=True, page_size=16)
    assert eng.cache["blocks"]["b0"]["k"].shape == ops.pool_shape(
        model.n_groups, eng.kv.alloc.n_pages, cfg.n_kv_heads, 16, cfg.head_dim)
    assert eng.cache["len"].shape == (8,)
    rs = [Request(rid=i, tokens=TOK.encode(SYS), max_new=6 + i) for i in range(6)]
    eng.serve(rs)
    assert eng.kv.alloc.n_forks > 0                  # CoW fork ran
    for leaf in jax.tree.leaves(eng.cache):
        assert leaf.format.layout.major_to_minor == tuple(range(leaf.ndim))
    for kv in ("k", "v"):
        assert np.any(np.asarray(eng.cache["blocks"]["b0"][kv]) != 0)


def test_paged_shared_admission_allocates_prompt_pages_once(tiny):
    """Admitting B siblings with one shared prompt stores the prompt pages
    ONCE: the owner allocates them, every sibling only bumps refcounts."""
    model, params = tiny
    eng = ServingEngine(model, params, max_slots=8, max_len=MAX_LEN,
                        paged=True, page_size=16, eos_id=-1)
    toks = TOK.encode(SYS)                          # identical prompts
    n_pages = -(-len(toks) // 16)
    reqs = [Request(rid=i, tokens=list(toks), max_new=4) for i in range(4)]
    eng._admit_batch(reqs, [0, 1, 2, 3])
    a = eng.kv.alloc
    assert a.n_allocs == n_pages                    # owner's pages, once
    assert a.n_shares == 3 * n_pages                # 3 siblings, all refs
    assert a.pages_in_use == n_pages                # B× tables, 1× storage
    for s in (1, 2, 3):
        assert eng.kv.slot_pages[s] == eng.kv.slot_pages[0]
    a.check(tables=eng.kv.slot_pages)

    # share_prefix=False: same workload, every slot pays full storage
    eng2 = ServingEngine(model, params, max_slots=8, max_len=MAX_LEN,
                         paged=True, page_size=16, share_prefix=False,
                         eos_id=-1)
    reqs2 = [Request(rid=i, tokens=list(toks), max_new=4) for i in range(4)]
    eng2._admit_batch(reqs2, [0, 1, 2, 3])
    assert eng2.kv.alloc.pages_in_use == 4 * n_pages
    assert eng2.kv.alloc.n_shares == 0


def test_paged_decode_donates_cache_in_place(tiny):
    model, params = tiny
    eng = ServingEngine(model, params, max_slots=4, max_len=128,
                        decode_block=4, paged=True, page_size=16, eos_id=-1)
    reqs = [Request(rid=i, tokens=TOK.encode(f"donate {i}"), max_new=64)
            for i in range(4)]
    eng._admit_free(list(reqs))
    import jax.numpy as jnp

    last, act, n_out, limit = eng._slot_state()
    args = (jnp.asarray(last), jnp.asarray(act), jnp.asarray(n_out),
            jnp.asarray(limit))
    table = eng._prepare_paged(eng._active_slots(), eng.max_len)
    old = eng.cache
    p0 = _first_kv_leaf(old).unsafe_buffer_pointer()
    cache1, _act, _t, _v = eng._decode_k_paged(eng.params, old, table, *args)
    donated = _first_kv_leaf(cache1).unsafe_buffer_pointer() == p0
    if donated:   # backend honors donation (CPU does on current jax)
        with pytest.raises(RuntimeError):
            _ = _first_kv_leaf(old) + 0             # donated input is dead
        cache2, *_ = eng._decode_k_paged(eng.params, cache1, table, *args)
        assert _first_kv_leaf(cache2).unsafe_buffer_pointer() == p0
        eng.cache = cache2
    else:
        eng.cache = cache1
    # the engine state stays live through further paged serving either way
    more = [Request(rid=9, tokens=TOK.encode("after"), max_new=3)]
    eng.serve(more)
    assert more[0].done


def test_paged_stepwise_is_refused(tiny):
    model, params = tiny
    eng = ServingEngine(model, params, max_slots=2, max_len=128, paged=True)
    with pytest.raises(RuntimeError, match="contiguous parity reference"):
        eng.serve_stepwise([Request(rid=0, tokens=TOK.encode("x"), max_new=2)])


def test_paged_kv_occupancy_reports_pool_state(tiny):
    model, params = tiny
    eng = ServingEngine(model, params, max_slots=4, max_len=128, paged=True,
                        page_size=16, eos_id=-1)
    occ0 = eng.kv_occupancy()
    assert occ0["paged"] and occ0["pages_used"] == 0 and occ0["page_bytes"] > 0
    eng.serve([Request(rid=0, tokens=TOK.encode(SYS), max_new=4)])
    occ = eng.kv_occupancy()
    assert occ["pages_used"] == 0                   # drained after retirement
    assert occ["peak_pages"] > 0
    assert occ["peak_kv_bytes"] == occ["peak_pages"] * occ["page_bytes"]
    # contiguous engines report committed bytes, no page counters
    eng_c = ServingEngine(model, params, max_slots=4, max_len=128)
    occ_c = eng_c.kv_occupancy()
    assert not occ_c["paged"] and occ_c["kv_bytes"] > 0
