"""BlockAllocator / PagedCacheManager invariants.

Property-based: hypothesis drives random alloc / share / CoW-fork / free
sequences against a model of the pool and asserts the allocator's invariants
after every step — refcounts equal table references, pages are never both
free and referenced, a fork never aliases, pages-in-use never exceeds the
pool, and freeing a retired slot returns exactly its non-shared pages.

Example-based twins of each property pin the same invariants on fixed
sequences.
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.serving.kvpool import BlockAllocator, OutOfPages, PagedCacheManager


# ---------------------------------------------------------------------------
# BlockAllocator — example tests
# ---------------------------------------------------------------------------

def test_alloc_free_roundtrip():
    a = BlockAllocator(4, 16)
    pages = a.alloc_n(4)
    assert sorted(pages) == [0, 1, 2, 3]
    assert a.pages_in_use == 4 and a.pages_free == 0
    with pytest.raises(OutOfPages):
        a.alloc()
    for p in pages:
        assert a.release(p) is True
    assert a.pages_in_use == 0 and a.n_frees == 4
    a.check()


def test_share_and_release_order():
    a = BlockAllocator(4, 16)
    p = a.alloc()
    a.share(p)
    a.share(p)
    assert a.refcount(p) == 3 and a.pages_shared == 1
    assert a.release(p) is False           # two references remain
    assert a.release(p) is False
    assert a.release(p) is True            # last reference frees
    with pytest.raises(ValueError):
        a.release(p)                       # double free is a hard error
    a.check()


def test_fork_gives_private_nonaliased_page():
    a = BlockAllocator(4, 16)
    p = a.alloc()
    a.share(p)
    q = a.fork(p)
    assert q != p                          # CoW never aliases
    assert a.refcount(p) == 1 and a.refcount(q) == 1
    assert a.n_forks == 1
    with pytest.raises(ValueError):
        a.fork(p)                          # forking a private page is a bug
    a.check()


def test_share_unreferenced_is_error():
    a = BlockAllocator(2, 16)
    with pytest.raises(ValueError):
        a.share(0)


def test_peak_tracks_high_water():
    a = BlockAllocator(8, 16)
    pages = a.alloc_n(5)
    for p in pages:
        a.release(p)
    a.alloc()
    assert a.peak_pages == 5 and a.pages_in_use == 1


# ---------------------------------------------------------------------------
# BlockAllocator — hypothesis property sweep
# ---------------------------------------------------------------------------

@settings(max_examples=60, deadline=None)
@given(st.integers(1, 12),
       st.lists(st.tuples(st.sampled_from(["alloc", "share", "fork", "free"]),
                          st.integers(0, 10**6)), max_size=60))
def test_allocator_invariants_under_random_ops(n_pages, ops):
    """Drive random op sequences against a reference model (a list of held
    references per page); the allocator must agree with the model and pass
    ``check`` after every single transition."""
    a = BlockAllocator(n_pages, 16)
    held: list[int] = []                   # one entry per outstanding reference
    for op, pick in ops:
        if op == "alloc":
            if len(set(held)) < n_pages:
                held.append(a.alloc())
            else:
                with pytest.raises(OutOfPages):
                    a.alloc()
        elif op == "share" and held:
            p = held[pick % len(held)]
            a.share(p)
            held.append(p)
        elif op == "fork" and held:
            p = held[pick % len(held)]
            if held.count(p) >= 2:
                q = a.fork(p)
                assert q not in held       # fresh page, never aliased
                held.remove(p)
                held.append(q)
            else:
                with pytest.raises(ValueError):
                    a.fork(p)
        elif op == "free" and held:
            p = held.pop(pick % len(held))
            assert a.release(p) is (p not in held)
        # allocator state == model state, every step
        assert a.pages_in_use == len(set(held))
        assert a.pages_in_use <= n_pages
        assert a.pages_shared == sum(1 for p in set(held) if held.count(p) > 1)
        for p in set(held):
            assert a.refcount(p) == held.count(p)
        a.check()


# ---------------------------------------------------------------------------
# PagedCacheManager
# ---------------------------------------------------------------------------

def test_manager_default_sizing_never_oom():
    m = PagedCacheManager(max_slots=4, max_len=100, page_size=16)
    assert m.pages_per_slot == 7           # ceil(100/16)
    assert m.alloc.n_pages == 28
    for s in range(4):                     # every slot filled to the brim
        m.map_slot(s, m.alloc.alloc_n(m.pages_per_slot))
    assert m.alloc.pages_free == 0
    m.alloc.check(tables=m.slot_pages)


def test_release_slot_returns_only_unshared_pages():
    m = PagedCacheManager(max_slots=3, max_len=64, page_size=16)
    owner = m.alloc.alloc_n(3)
    m.map_slot(0, owner)
    # sibling shares the first 2 pages, owns 1 private
    sib = [m.alloc.share(owner[0]), m.alloc.share(owner[1]), m.alloc.alloc()]
    m.map_slot(1, sib)
    # retiring the sibling frees exactly its private page
    assert m.release_slot(1) == 1
    assert (m.table[1] == m.alloc.n_pages).all()
    # now the owner's pages are all private again; retiring frees all 3
    assert m.release_slot(0) == 3
    assert m.alloc.pages_in_use == 0
    m.alloc.check(tables=m.slot_pages)


def test_extend_and_fork_for_write():
    m = PagedCacheManager(max_slots=2, max_len=64, page_size=16)
    owner = m.alloc.alloc_n(2)             # positions [0, 32)
    m.map_slot(0, owner)
    m.map_slot(1, [m.alloc.share(p) for p in owner])
    # slot 1 appends at position 30: page 1 (shared) must fork, page 0 must not
    src, dst = m.fork_for_write(1, 30, 34)
    assert src == [owner[1]] and len(dst) == 1 and dst[0] != owner[1]
    assert m.slot_pages[1][1] == dst[0] and m.table[1, 1] == dst[0]
    assert m.alloc.refcount(owner[1]) == 1  # owner keeps the original
    # growing to cover position 34 allocates exactly one fresh private page
    new = m.extend_slot(1, 3)
    assert len(new) == 1 and m.table[1, 2] == new[0]
    # idempotent: already covered
    assert m.extend_slot(1, 3) == []
    m.alloc.check(tables=m.slot_pages)
    # the write range [30, 34) is now fully private to slot 1
    assert m.fork_for_write(1, 30, 34) == ([], [])


def test_table_sentinel_marks_unmapped():
    m = PagedCacheManager(max_slots=2, max_len=64, page_size=16)
    m.map_slot(0, m.alloc.alloc_n(2))
    assert (m.table[0, 2:] == m.alloc.n_pages).all()
    assert (m.table[1] == m.alloc.n_pages).all()


@settings(max_examples=40, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 3), st.sampled_from(["admit", "grow", "retire"]),
                          st.integers(1, 96), st.integers(0, 3)), max_size=40))
def test_manager_invariants_under_slot_churn(ops):
    """Random admit(share-with)/grow/retire slot lifecycles: table references
    and refcounts must agree after every step, and the pool can never run
    out under default sizing."""
    m = PagedCacheManager(max_slots=4, max_len=96, page_size=16)
    lens = [0, 0, 0, 0]
    for slot, op, ln, other in ops:
        if op == "admit":
            if lens[slot]:
                m.release_slot(slot)
            n_need = -(-ln // 16)
            donor = m.slot_pages[other] if other != slot else []
            n_sh = min(len(donor), n_need)
            pages = [m.alloc.share(p) for p in donor[:n_sh]]
            pages += m.alloc.alloc_n(n_need - n_sh)
            m.map_slot(slot, pages)
            lens[slot] = ln
        elif op == "grow" and lens[slot]:
            end = min(lens[slot] + 8, 96)
            m.extend_slot(slot, -(-end // 16))
            m.fork_for_write(slot, lens[slot], end)
            lens[slot] = end
        elif op == "retire" and lens[slot]:
            m.release_slot(slot)
            lens[slot] = 0
        live = [p for pages in m.slot_pages for p in pages]
        assert m.alloc.pages_in_use == len(set(live)) <= m.alloc.n_pages
        m.alloc.check(tables=m.slot_pages)
        for s in range(4):
            np.testing.assert_array_equal(
                m.table[s, :len(m.slot_pages[s])], m.slot_pages[s])
            assert (m.table[s, len(m.slot_pages[s]):] == m.alloc.n_pages).all()
    # draining every slot returns the pool to empty
    for s in range(4):
        m.release_slot(s)
    assert m.alloc.pages_in_use == 0


# ---------------------------------------------------------------------------
# truncate_slot — the speculative-decode rollback primitive
# ---------------------------------------------------------------------------

def test_truncate_slot_releases_only_past_keep_point():
    m = PagedCacheManager(max_slots=2, max_len=96, page_size=16)
    m.map_slot(0, m.alloc.alloc_n(4))            # covers positions [0, 64)
    # keep 33 tokens → ceil(33/16) = 3 pages stay mapped, 1 freed
    assert m.truncate_slot(0, 33) == 1
    assert len(m.slot_pages[0]) == 3
    assert (m.table[0, 3:] == m.alloc.n_pages).all()
    # already covered: truncating to the same (or a longer) point is a no-op
    assert m.truncate_slot(0, 33) == 0
    assert m.truncate_slot(0, 48) == 0
    m.alloc.check(tables=m.slot_pages)


def test_truncate_shared_suffix_drops_reference_not_page():
    """A rejected draft suffix on a COW-shared page must only drop this
    slot's reference — the sibling keeps its KV; refcounts step down by
    exactly one."""
    m = PagedCacheManager(max_slots=2, max_len=96, page_size=16)
    owner = m.alloc.alloc_n(3)
    m.map_slot(0, owner)
    m.map_slot(1, [m.alloc.share(p) for p in owner])
    assert m.alloc.refcount(owner[2]) == 2
    # slot 1 rolls back past the last shared page: 0 pages actually freed
    assert m.truncate_slot(1, 32) == 0
    assert m.alloc.refcount(owner[2]) == 1       # owner keeps the page
    assert len(m.slot_pages[1]) == 2
    # the owner's rollback of the now-private page really frees it
    assert m.truncate_slot(0, 32) == 1
    m.alloc.check(tables=m.slot_pages)
    assert m.release_slot(0) + m.release_slot(1) == 2  # shared pair remains


def test_truncate_to_zero_empties_slot():
    m = PagedCacheManager(max_slots=1, max_len=64, page_size=16)
    m.map_slot(0, m.alloc.alloc_n(4))
    assert m.truncate_slot(0, 0) == 4
    assert m.slot_pages[0] == [] and m.alloc.pages_in_use == 0
    assert (m.table[0] == m.alloc.n_pages).all()


def test_truncate_then_extend_reuses_pool():
    """Rollback → re-grow cycles (every speculative round) must not leak:
    the free list absorbs truncated pages and hands them back on extend."""
    m = PagedCacheManager(max_slots=1, max_len=64, page_size=16)
    m.map_slot(0, m.alloc.alloc_n(2))
    for _ in range(8):
        assert m.truncate_slot(0, 16) == 1       # roll back to one page
        assert len(m.extend_slot(0, 2)) == 1     # grow to 2 pages again
        m.alloc.check(tables=m.slot_pages)
    assert m.alloc.pages_in_use == 2
    assert m.alloc.peak_pages == 2               # reuse, not fresh allocation
