"""What a slot's cache is made of, said once (docs/SERVING.md "What a slot
holds"): arithmetic over the model's config and the engine's geometry.
:class:`~.execution.MeshExecutor` builds a :class:`CacheLayout`;
:class:`~.serving.ServingEngine` reads it from there (and builds an equal
one first, to refuse what it must before anything is compiled).  It says
how each set of leaves a slot holds is addressed (pages of the slot's table,
a ring of pages in the window pool, a row a slot), how deep they are (a
page's rows a layer of the model, or of a looped model a layer of EVERY pass:
``models.transformer.cache_depth``) and from that the pools of pages to
allocate and what ``init_paged_cache`` is called with; which
mechanisms work on it (:data:`REFUSED`: mechanism x kind of cache, the kinds
``models.transformer.cache_kind``'s); and what a tick and a prompt read of
it, as the span attrs the benchmark's per-layer readers take.  The next kind
of cache is a row here and its forward in ``models/``, not the scheduler.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

from ..models.mixers import MIXERS, mixers_of
from ..models.mixers.ssm import ssm_scan_path
from ..models.transformer import (block_read_rows, cache_depth, cache_kind,
                                  cache_layers, causal_walk_steps, is_hybrid,
                                  kind_layers, layers_by_kind,
                                  paged_read_rows,
                                  ssm_scan_chunks, window_read_rows,
                                  window_ring_pages)

__all__ = ["CacheLayout", "REFUSED"]

# the kinds whose cache is more than K and V pages of one pool
_NOT_PAGES_ALONE = ("window", "latent", "state")

# mechanism -> the kinds of cache (``cache_kind``) it cannot work on: five
# the executor asks for, then the engine's three
REFUSED: Dict[str, Tuple[str, ...]] = {
    "tensor-sharded heads (tp > 1)": _NOT_PAGES_ALONE,
    "copy-on-write page snapshots (prefix_cache=True)": _NOT_PAGES_ALONE,
    "KV-page tiering": _NOT_PAGES_ALONE,
    "the int8 pool": _NOT_PAGES_ALONE,
    # per-slot factors ride a scan over one stack of equal layers
    "multi-tenant adapters": _NOT_PAGES_ALONE + ("grouped",),
    "prefix sharing (prefix_cache=True)": _NOT_PAGES_ALONE,
    "speculative decoding": _NOT_PAGES_ALONE,
    # a slot that gave its pages up is rebuilt by prefills that start behind
    # rows already in its pages, as a shared prefix's tail is
    "pages that follow a slot's length (recompute preemption)":
        _NOT_PAGES_ALONE,
}


class CacheLayout:
    """The cache of ``b_slots`` slots of ``pages_per_slot`` pages of
    ``page_size`` tokens over ``num_pages`` pages, for the model ``cfg``:
    each page ``depth`` layers deep, ``passes`` passes of the model's own
    layers (1 for any model but a looped one)."""

    def __init__(self, cfg, b_slots: int, page_size: int,
                 pages_per_slot: int, num_pages: int):
        self.cfg = cfg
        self.b_slots, self.page_size = int(b_slots), int(page_size)
        self.pages_per_slot = int(pages_per_slot)
        self.kind, self.description = cache_kind(cfg)
        # a page holds its tokens' rows of every layer of every pass
        self.passes, self.depth = cfg.loop_passes, cache_depth(cfg)
        # window layers: a ring a slot in a pool of its own, reused in place
        self.ring_pages = (window_ring_pages(cfg.window_size, self.page_size)
                           if self.kind == "window" else 0)
        self.window_pages = self.ring_pages and 1 + b_slots * self.ring_pages
        # a mixer of ``models.mixers.MIXERS``: its leaves a row a slot beside
        # the pages.  The paged leaves cover the layers with attention, the
        # state leaves those with a mixer: every layer both (a parallel
        # block), or each layer one of the two (a layer_pattern)
        self.stateful = self.kind == "state"
        self.kv_layers, self.state_layers = cache_layers(cfg)
        # the layers run by kind ("mlp": a layer that is its MLP or expert
        # layer alone and owns no cache leaf), and the leaves a kind's own
        self.layers_by_kind = layers_by_kind(cfg)
        self.leaves_by_kind = {
            kind: (MIXERS[kind].pool_keys if kind in MIXERS
                   else () if kind == "mlp"
                   else ("latent",) if self.kind == "latent"
                   else tuple(n + ("_window" if kind == "window" else "")
                              for n in ("k", "v")))
            for kind in self.layers_by_kind}
        if self.stateful and not is_hybrid(cfg):    # beside attention
            self.leaves_by_kind["full"] += mixers_of(cfg)[0].pool_keys
        # a pool of pages each: ``(pages, page 0 its trash page; those a
        # slot's row of its table names)``.  The slots' table first, then
        # what is taken a whole row a slot and never shared
        self.pools: Tuple[Tuple[int, int], ...] = (
            (int(num_pages), self.pages_per_slot),)
        if self.ring_pages:
            self.pools += ((self.window_pages, self.ring_pages),)
        self.pool_kw = {"window_pages": self.window_pages or None,
                        "slots": self.b_slots}
        # K/V head rows a token row of each kind of layer stands for, over
        # the kind's layers (the kv_rows_* span attrs)
        self.kind_heads = ({k: g.kv_heads * n
                            for k, (g, n) in kind_layers(cfg).items()}
                           if self.kind == "window" else {})
        # a prompt of such a model attends within itself, gathers nothing
        # back: a latent model's, and one whose layers are walked by kind
        self.block_attends_itself = self.kind == "latent" or is_hybrid(cfg)
        # a slot's bytes of state and the passes the tick's step makes over
        # them, and the bytes one token's rows take in the paged leaves over
        # every layer and pass; the paged leaves a tick writes by row and by
        # page: the executor that made the pool and traced the tick says
        self.state_slot_bytes = self.state_passes = self.kv_token_bytes = 0
        self.kv_write_leaves = (0, 0)
        # whether the tick's read fetches a page at a time (the kernel) or
        # gathers whole steps of pairs: the executor says that too
        self.kv_read_pages = False

    # ------------------------------------------------------- mechanisms

    def allows(self, mechanism: str) -> bool:
        return self.kind not in REFUSED[mechanism]

    def refuse(self, mechanism: str, on: Any = True) -> None:
        """Raise, by both names, where ``mechanism`` is asked for (``on``)
        and cannot work on this cache."""
        if on and not self.allows(mechanism):
            raise NotImplementedError(
                f"{mechanism} does not support a model with "
                f"{self.description}")

    # ------------------------------------------------ what a call reads

    def _kv_row_attrs(self, full: int, full_live: int, window: int,
                      window_live: int) -> Dict[str, int]:
        """Token rows read and live a kind of layer -> K/V head rows."""
        hf, hw = self.kind_heads["full"], self.kind_heads["window"]
        return {"kv_rows_full": full * hf, "kv_live_rows_full": full_live * hf,
                "kv_rows_window": window * hw,
                "kv_live_rows_window": window_live * hw}

    def decode_attrs(self, lengths, slots: int) -> Dict[str, Any]:
        """The ``serve.decode`` span attrs of a tick of ``slots`` slots whose
        live ones hold ``lengths`` rows, the row being written counted in.
        ``gathered_rows``: K/V rows its read covers a layer, each slot's own
        pages, in whole steps where the read gathers and to the last live
        page where it fetches a page at a time (``mesh_info()["kv_read"]``);
        ``passes`` times the model's layers read them,
        and ``kv_bytes`` is what the rows held take over all of those.
        ``kv_row_write_leaves`` / ``kv_page_write_leaves``: the paged leaves
        into which the tick stores a token's row where it lies, and those it
        merges a slot's whole page into (static a program).  Two
        kinds of layer: K/V head rows read and live a kind (a window layer
        reads the ring pages under its window), ``kv_slots_live`` the live
        slots and ``kv_slots_past_window`` those of them that hold more rows
        than the window (their rings have wrapped: a window layer reads fewer
        rows of them than a full one).
        A state a slot: the slots whose state the tick read and wrote, the
        bytes of one reading over the layers that have one
        (``state_layers``), its step's passes, the live token rows over
        the layers that have K/V (``kv_layers``), and the layers run by kind
        (``layers_by_kind``: ``"ssm:5,mlp:5,full:1"``)."""
        lengths = np.asarray(lengths, np.int64)
        rows = paged_read_rows(lengths, self.page_size, self.pages_per_slot,
                               slots, whole_steps=not self.kv_read_pages)
        attrs: Dict[str, Any] = {
            "gathered_rows": rows, "passes": self.passes,
            "kv_bytes": int(lengths.sum()) * self.kv_token_bytes,
            "kv_row_write_leaves": self.kv_write_leaves[0],
            "kv_page_write_leaves": self.kv_write_leaves[1]}
        if self.kind == "window":
            W = self.cfg.window_size
            attrs.update(self._kv_row_attrs(
                rows, int(lengths.sum()),
                window_read_rows(lengths, self.page_size, W, slots),
                int(np.minimum(lengths, W).sum())),
                kv_slots_live=len(lengths),
                kv_slots_past_window=int((lengths > W).sum()))
        if self.stateful:
            # ``state_bytes`` over the layers with a mixer, ``kv_live_rows``
            # (token rows x layers) over those with attention
            attrs.update(state_slots=len(lengths),
                         state_bytes=len(lengths) * self.state_slot_bytes,
                         state_passes=self.state_passes,
                         state_layers=self.state_layers,
                         kv_layers=self.kv_layers,
                         kv_live_rows=int(lengths.sum()) * self.kv_layers,
                         layers_by_kind=",".join(
                             f"{k}:{n}"
                             for k, n in self.layers_by_kind.items()))
        return attrs

    def tick_attrs(self, pools, page_wait: bool) -> Dict[str, int]:
        """The ``serve.tick`` span attrs: ``pages_free`` of the slots' pool
        and ``page_wait``, 1 where the tick's admission left the head of the
        queue waiting for pages with a slot free (pages, not slots, bound
        the batch).  Two kinds of layer: pages of each of the engine's
        ``pools`` (:attr:`pools`' order) that hold a request's K/V."""
        attrs = {"page_wait": int(page_wait),
                 "pages_free": len(pools[0].free)}
        if self.kind == "window":
            attrs.update(pages_full=pools[0].referenced(),
                         pages_window=pools[1].referenced())
        return attrs

    def prefill_attrs(self, bucket: int, tokens: int, shared: int
                      ) -> Dict[str, Any]:
        """The ``serve.prefill`` span attrs of a prompt's ``tokens`` real
        tokens in a block of ``bucket`` behind ``shared`` tokens of shared
        pages.  ``gathered_rows`` as a tick's, or 0 where the block attends
        within itself: then ``walk_steps``, the chunk steps its full or
        latent layers run as far as its tokens reach, beside the bucket's,
        and what a block reads of itself a kind of layer.  ``passes`` and
        ``kv_bytes`` as a tick's, over the rows the slot holds after it.  A
        state a slot: whether the call resets its slot's state and, where
        the kind's prompt runs a scan (not a convolution's tail alone), the
        scan's chunks (of the kind's own length: ``ssm_chunk``, or
        ``linear_chunk`` for delta layers) that hold a real token beside the
        bucket's, and how the bucket's program runs that scan where the kind
        has a kernel for it (``ssm_scan``: ``"kernel"`` / ``"xla"``,
        ``models.mixers.ssm.ssm_scan_path``)."""
        attrs: Dict[str, Any] = {"gathered_rows": (
            0 if self.block_attends_itself else paged_read_rows(
                [shared + tokens], self.page_size, self.pages_per_slot, 1)),
            "passes": self.passes,
            "kv_bytes": (shared + tokens) * self.kv_token_bytes}
        if self.stateful:
            chunks = ssm_scan_chunks(self.cfg, bucket, tokens)
            if chunks is not None:      # a kind whose prompt runs a scan
                attrs.update(
                    scan_chunks=chunks,
                    scan_chunks_bucket=ssm_scan_chunks(self.cfg, bucket))
            scan = ssm_scan_path(self.cfg, bucket)
            if scan is not None:        # state-space layers
                attrs.update(ssm_scan=scan)
            attrs.update(state_reset=int(shared == 0))
        if self.block_attends_itself:
            attrs.update(walk_steps=causal_walk_steps(bucket, tokens),
                         walk_steps_bucket=causal_walk_steps(bucket))
        if self.kind == "window":
            attrs.update(self._kv_row_attrs(
                block_read_rows(bucket, tokens=tokens), tokens,
                block_read_rows(bucket, self.cfg.window_size, tokens=tokens),
                tokens))
        return attrs
