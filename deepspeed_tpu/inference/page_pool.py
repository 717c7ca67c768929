"""The host's allocator of one pool of pages (docs/SERVING.md "What a slot
holds").  A serving engine holds a :class:`PagePool` for each pool its cache
layout lists (``inference/cache_layout.py``): the slots' page table, whose
pages are taken as a request needs them and shared by reference (prefix
sharing, the index's own pins), and a window layer's rings, taken a ring at
a time at admission and never shared.  Physical page 0 is the pool's trash
page and is never handed out; every other page is exactly one of free,
quarantined (leaked by a fenced slot: suspect contents are accounted, never
recycled) or referenced (:meth:`PagePool.accounting`).  Pure Python over
numpy: a list pop and an array store a page.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

__all__ = ["PagePool"]


class PagePool:
    """Free list, reference counts, weight-epoch stamps and the quarantine
    account of ``num_pages`` pages, beside ``table [slots, pages_per_slot]``:
    the rows of physical pages the programs are fed (0: no page)."""

    def __init__(self, num_pages: int, slots: int, pages_per_slot: int):
        self.num_pages = int(num_pages)
        self.free: List[int] = list(range(self.num_pages - 1, 0, -1))
        # 0 = free or quarantined, >0 = held by slots and/or the prefix
        # index.  A page returns to the free list only at 0, so its contents
        # can never be recycled under a reader
        self.refcount = np.zeros((self.num_pages,), np.int64)
        # the weight epoch each page was taken under (docs/HYBRID.md)
        self.epoch = np.zeros((self.num_pages,), np.int64)
        self.quarantined: List[int] = []      # leaked-and-accounted
        # which fenced slot leaked which pages: a canary probes the slot
        # through them, and a passed one hands exactly those back
        self.fenced: Dict[int, List[int]] = {}
        self.hwm = 0                          # most pages ever not free
        self.table = np.zeros((slots, pages_per_slot), np.int32)

    def take(self, n: int, epoch: int = 0) -> List[int]:
        """Pop ``n`` free pages and take the first reference on each, stamped
        with ``epoch``: what they are about to hold the live weights made."""
        pages = [self.free.pop() for _ in range(n)]
        for p in pages:
            self.refcount[p] = 1
            self.epoch[p] = epoch
        occupied = (self.num_pages - 1) - len(self.free)
        if occupied > self.hwm:
            self.hwm = occupied
        return pages

    def share(self, p: int) -> None:
        self.refcount[p] += 1

    def drop(self, p: int) -> None:
        """Release one reference; the last one frees the page.  A negative
        count is a double-free: the pool can no longer be trusted."""
        c = int(self.refcount[p]) - 1
        if c < 0:
            raise RuntimeError(
                f"page {p} dropped below zero references — double-free "
                "(page accounting is corrupt; rebuild the engine)")
        self.refcount[p] = c
        if c == 0:
            self.free.append(p)

    def row(self, slot: int) -> List[int]:
        """The pages ``slot``'s row of the table names."""
        return [p for p in self.table[slot].tolist() if p]

    def leak(self, slot: int, pages: List[int]) -> None:
        """``slot`` is being fenced: zero the references of the pages its
        failed attempt took WITHOUT freeing them."""
        for p in pages:
            self.refcount[p] = 0
        self.quarantined.extend(pages)
        self.fenced[slot] = list(pages)

    def restore(self, slot: int) -> List[int]:
        """``slot`` passed its canary: exactly the pages it leaked go back
        to the free list."""
        pages = self.fenced.pop(slot, [])
        for p in pages:
            self.quarantined.remove(p)
        self.free.extend(pages)
        return pages

    def stale(self, pages, epoch: int) -> List[int]:
        """Those of ``pages`` that were taken under another weight epoch."""
        return [p for p in pages if self.epoch[p] != epoch]

    def referenced(self) -> int:
        return int((self.refcount[1:] > 0).sum())

    def accounting(self) -> Dict[str, Any]:
        free, quarantined = len(self.free), len(self.quarantined)
        referenced, total = self.referenced(), max(self.num_pages - 1, 0)
        return {"free": free, "quarantined": quarantined,
                "referenced": referenced, "total": total,
                "balanced": free + quarantined + referenced == total}
