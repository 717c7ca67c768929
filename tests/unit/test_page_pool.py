"""The host's page allocator (``inference/page_pool.py``), host-only: the
same suite on both shapes of pool a serving engine holds.  ``shared``: the
slots' table, pages taken as a request needs them and shared by reference.
``rings``: a window layer's, a slot's whole row taken at admission, never
shared."""
import pytest

from deepspeed_tpu.inference.page_pool import PagePool

SLOTS = 3
SHAPES = {"shared": dict(num_pages=13, pages_per_slot=4),
          "rings": dict(num_pages=1 + SLOTS * 3, pages_per_slot=3)}


@pytest.fixture(params=list(SHAPES))
def pool(request):
    kw = SHAPES[request.param]
    return PagePool(kw["num_pages"], SLOTS, kw["pages_per_slot"])


def _admit(pool, slot, epoch=0):
    """A slot's row, as the engine takes it: whole."""
    pages = pool.take(pool.table.shape[1], epoch)
    pool.table[slot] = pages
    return pages


def test_a_fresh_pool_is_all_free_and_never_hands_out_page_zero(pool):
    total = pool.num_pages - 1
    assert pool.accounting() == {"free": total, "quarantined": 0,
                                 "referenced": 0, "total": total,
                                 "balanced": True}
    assert pool.table.shape == (SLOTS, pool.table.shape[1])
    assert not pool.table.any()
    pages = pool.take(total)
    assert sorted(pages) == list(range(1, total + 1))
    with pytest.raises(IndexError):
        pool.take(1)


def test_take_share_and_drop_to_zero_frees(pool):
    pages = _admit(pool, 0)
    assert len(set(pages)) == len(pages) and pool.row(0) == pages
    assert all(pool.refcount[p] == 1 for p in pages)
    acct = pool.accounting()
    assert acct["referenced"] == len(pages) and acct["balanced"]
    pool.share(pages[0])                  # a second reader (the index's pin)
    for p in pages:
        pool.drop(p)
    assert pool.referenced() == 1 and pages[0] not in pool.free
    assert set(pages[1:]) <= set(pool.free)
    pool.drop(pages[0])
    assert pool.referenced() == 0
    assert pool.accounting()["free"] == pool.num_pages - 1


def test_a_drop_below_zero_raises(pool):
    (p,) = pool.take(1)
    pool.drop(p)
    with pytest.raises(RuntimeError, match="double-free"):
        pool.drop(p)
    assert pool.free.count(p) == 1        # and was not freed twice


def test_a_leak_is_neither_free_nor_referenced_and_comes_back_whole(pool):
    kept = _admit(pool, 0)
    leaked = _admit(pool, 1)
    other = _admit(pool, 2)
    pool.table[1] = 0
    pool.leak(1, leaked)
    pool.leak(2, other[:1])
    acct = pool.accounting()
    assert acct["quarantined"] == len(leaked) + 1 and acct["balanced"]
    assert acct["referenced"] == len(kept) + len(other) - 1
    assert not set(leaked) & set(pool.free)
    assert pool.fenced == {1: leaked, 2: other[:1]}
    # a passed canary hands back exactly the slot's own pages
    assert pool.restore(1) == leaked
    assert set(leaked) <= set(pool.free) and 1 not in pool.fenced
    assert pool.quarantined == other[:1]
    assert pool.accounting()["balanced"]
    assert pool.restore(1) == []          # nothing left to hand back


def test_stale_names_pages_of_another_epoch(pool):
    old = pool.take(2, epoch=3)
    new = pool.take(1, epoch=4)
    assert pool.stale(old + new, 4) == old
    assert pool.stale(old + new, 3) == new
    pool.drop(old[0])
    (again,) = pool.take(1, epoch=4)      # the page just freed, restamped
    assert again == old[0] and pool.stale([again], 4) == []


def test_high_water_mark_is_the_most_pages_ever_not_free(pool):
    a = pool.take(2)
    b = pool.take(3)
    assert pool.hwm == 5
    for p in a + b:
        pool.drop(p)
    pool.take(1)
    assert pool.hwm == 5 and pool.accounting()["free"] == pool.num_pages - 2


def test_a_pool_of_no_pages_is_balanced():
    assert PagePool(1, 0, 0).accounting() == {
        "free": 0, "quarantined": 0, "referenced": 0, "total": 0,
        "balanced": True}
