"""Mesh-wide execution tier of the serving engine.

:class:`~.serving.ServingEngine` keeps scheduling — admission, page tables,
the prefix index, deadlines: pure Python over numpy — and
:class:`MeshExecutor` owns everything that touches a device: the pool (made
as its :class:`~.cache_layout.CacheLayout` says) and its
:class:`~jax.sharding.NamedSharding` placement, the decode /
bucketed-prefill / COW programs, and the device copy of the per-slot
sampling lanes.  Page-table scatter/gather, copy-on-write, sampling lanes
and the speculative draft pool all ride the sharded programs unchanged,
because they only ever see this surface.

Sharding layout (GSPMD over the ``parallel/mesh.py`` named mesh — the
same NamedSharding/PartitionSpec pattern training and ``generate()``
already use):

- **KV pool** ``[L, P, page, Hkv, hd]``: KV heads over ``'model'``
  (:func:`~..models.transformer.paged_cache_specs`), pages replicated —
  any slot on any data shard may own any page.  Per-device pool bytes
  shrink ~1/tp, which is what lets one engine's pool span a slice's HBM.
- **Attention/MLP weights**: :func:`~.engine.auto_tp_specs` over
  ``'model'`` — the exact specs ``InferenceEngine`` serves ``generate()``
  with, so serving numerics stay identical to the one-shot path.
- **Host scheduling arrays** (page tables, lengths, last tokens, lanes):
  replicated.  They are tiny per-tick scheduling state; XLA routes the
  per-axis collectives the sharded einsums need.
- **Outputs**: sampled tokens replicated, pools pinned back to their
  canonical sharding via ``out_shardings`` so placement can never drift
  across ticks (a drifted pool would silently re-shard every tick).

With ``mesh=None`` the programs are the same jits without sharding
annotations — single-chip serving is the degenerate case, not a separate
code path.  Develop and gate multi-chip on CPU via
``XLA_FLAGS=--xla_force_host_platform_device_count=N``
(:func:`~..parallel.mesh.initialize_serving_mesh`); the compiled
programs are real SPMD partitions either way (docs/SERVING.md
"Multi-chip serving").
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.mixers import (MIXERS, mixers_of, state_scan_paths,
                             state_step_paths)
from ..models.transformer import (PAGED_POOL_KEYS, STATE_POOL_KEYS,
                                  cow_copy_pool, expert_counts_shape,
                                  is_hybrid, paged_pool_cache,
                                  paged_pool_order, paged_pool_tuple,
                                  per_layer_leaves, expert_matmul_path,
                                  expert_products, expert_rows_moved,
                                  kv_read_paths, kv_write_paths)
from ..observability.program_stats import (ProgramCatalog, account,
                                           finish_sample)
from .cache_layout import CacheLayout
from .kv_tiering import extract_pool_page, inject_pool_page
from .sampling import position_keys, sample_tokens

__all__ = ["MeshExecutor", "place_params", "pool_jit", "pool_bytes"]

# process-global COW page-copy program (jax.jit caches on argument avals
# INCLUDING shardings, so every engine with the same pool
# shape/dtype/placement — notably a warm-restart replacement — shares ONE
# compile per process, and meshed/unmeshed pools each get their own
# specialization of the same jit).  The program is generic over the
# canonical pool TUPLE — a jit retraces per input pytree structure, so the
# same cached jit serves full-precision (k, v) and quantized
# (k, v, k_scale, v_scale) pools with one compile each.  No out_shardings:
# the in-place page update propagates the input pools' sharding verbatim,
# so one jit serves meshed and unmeshed pools alike.
_COW_PROG = jax.jit(cow_copy_pool, donate_argnums=(0,))

# process-global KV-tiering programs (docs/SERVING.md "KV-page tiering"),
# shared across engines for the same reason as _COW_PROG.  The extract
# half NEVER donates (a demote reads the pool and must leave it alive);
# the inject half donates the pool like the COW snapshot.
_TIER_EXTRACT_PROG = jax.jit(extract_pool_page)
_TIER_INJECT_PROG = jax.jit(inject_pool_page, donate_argnums=(0,))


def feed_lane(fed, out, slot):
    """``fed`` (a decode program's token operand) with lane ``slot`` set to
    the token a prefill program's output ``out`` leads with: how an
    admission's first token reaches the next tick without the host
    (:meth:`MeshExecutor.feed_lane`)."""
    return fed.at[slot].set(out.reshape(-1)[0].astype(fed.dtype))


# What an executor compiles ahead of time to learn how the device wants its
# state, by everything the compiled program is made from: the program that
# makes the pool, and the layouts a decode program asked for its weights
# (MeshExecutor._tick_formats).  An engine built again over the same model,
# geometry and placement (a warm restart, a fleet's members) reads them here
# and compiles nothing to find them.
_AOT: Dict[Any, Any] = {}
_AOT_MAX = 128


def _aot(key, build):
    """``build()`` once a process a ``key``."""
    if key not in _AOT:
        if len(_AOT) >= _AOT_MAX:
            _AOT.clear()
        _AOT[key] = build()
    return _AOT[key]


def pool_jit(fn, mesh, pool_specs, n_leading: int, in_shardings=None):
    """jit a pool-consuming program.  ``fn`` takes the pool as its second
    argument and returns it last, as ONE canonical tuple (so donating that
    one argument donates every pool leaf at once — payload AND scale planes
    on a quantized pool).  Each tick consumes and reproduces the pool, so it
    is donated on every backend: the pool exists once in device memory, not
    twice.  On a mesh, pin the outputs: ``n_leading`` replicated leading
    outputs (tokens/counts) followed by the pool tuple on its canonical
    shardings (``pool_specs``: one PartitionSpec per pool array) — without
    ``out_shardings`` GSPMD is free to pick a different pool placement per
    program and the donated buffers would reshard every tick.
    ``in_shardings`` (one entry an argument, ``None``: as the argument lies)
    is for the one ahead-of-time compile that asks the compiler which
    layouts it wants (``MeshExecutor._tick_formats``)."""
    kw = {} if in_shardings is None else {"in_shardings": in_shardings}
    if mesh is None:
        return jax.jit(fn, donate_argnums=(1,), **kw)
    rep = NamedSharding(mesh, P())
    pools = tuple(NamedSharding(mesh, s) for s in pool_specs)
    if n_leading == 0:   # the program returns the bare pool tuple
        return jax.jit(fn, donate_argnums=(1,), out_shardings=pools, **kw)
    return jax.jit(fn, donate_argnums=(1,),
                   out_shardings=tuple([rep] * n_leading) + (pools,), **kw)


def _named_pool_jit(prog, name: str, mesh, pool_specs, in_shardings=None):
    """``pool_jit`` for a serving program that returns (tokens, pools),
    under a stable name: the XLA module is ``jit_<name>`` in a device
    trace, a compile log and an HLO dump, whatever the Python closure that
    built it is called (docs/OBSERVABILITY.md "Device-time correlation")."""
    prog.__name__ = prog.__qualname__ = name
    return pool_jit(prog, mesh, pool_specs, 1, in_shardings)


def place_params(params, mesh):
    """Commit a param tree to its auto-TP shardings on ``mesh`` (reuses
    :func:`~.engine.auto_tp_specs` — the same Megatron-style split
    ``generate()`` runs with).  Params already committed to this mesh
    (the ``InferenceEngine.serving()`` path) pass through untouched; a
    raw host tree (standalone ``ServingEngine(..., mesh=...)``) is
    sharded here.  ``mesh=None`` or a tp=1 mesh is a no-op."""
    if mesh is None or mesh.shape.get("model", 1) == 1:
        return params
    leaves = jax.tree_util.tree_leaves(params)
    if leaves and isinstance(getattr(leaves[0], "sharding", None),
                             NamedSharding) \
            and leaves[0].sharding.mesh == mesh:
        return params
    from .engine import auto_tp_specs

    specs = auto_tp_specs(params, mesh)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
    return jax.device_put(params, shardings)


def _as_given(xs):
    """What a re-laying program computes: its ``out_shardings`` are the
    work.  One function a process, so that the jit finds by its arguments
    what an earlier engine compiled."""
    return xs


def _lies_as(leaf, fmt) -> bool:
    """Whether the device array ``leaf`` lies as ``fmt`` (a ``Format``)
    says: on that sharding, its axes in that order, tiled so.  Read field by
    field: a layout that a compiled program reports and the one an array
    reports for the same bytes need not compare equal whole."""
    have, want = leaf.format.layout, fmt.layout
    return (leaf.sharding == fmt.sharding
            and tuple(have.major_to_minor) == tuple(want.major_to_minor)
            and tuple(have.tiling or ()) == tuple(want.tiling or ()))


def pool_bytes(*pools) -> Dict[str, int]:
    """Total and per-device bytes of a (possibly sharded) pool tuple —
    EVERY pool array counts, so a quantized pool's scale planes are priced
    into ``kv_pool_bytes_*`` (the 2× capacity claim is only honest with
    the scales in the denominator).  ``per_device`` is the MAX across
    devices (capacity planning reads the worst shard); on a tp-sharded
    full-precision pool it is ~``total / tp`` (a quantized pool's
    replicated scale planes sit on every device, so the equality is
    deliberately NOT asserted there)."""
    total = sum(int(a.nbytes) for a in pools)
    per: Dict[Any, int] = {}
    try:
        for arr in pools:
            for s in arr.addressable_shards:
                per[s.device] = per.get(s.device, 0) + int(s.data.nbytes)
    except Exception:   # duck-typed arrays without shard metadata
        return {"total": total, "per_device": total}
    return {"total": total,
            "per_device": max(per.values()) if per else total}


class MeshExecutor:
    """The device half of a serving engine: paged KV pool + fixed-shape
    programs, optionally tensor-sharded over a named device mesh.

    The host half (:class:`~.serving.ServingEngine`) calls exactly four
    program entry points — :meth:`decode`, :meth:`prefill`, :meth:`cow`
    and the lane cache — and never touches a device array directly, so
    the whole fleet of programs can move between a single chip and a
    mesh without the scheduler noticing.
    """

    def __init__(self, model, params, num_pages: int, page_size: int,
                 b_slots: int, dtype=None, kv_dtype=None, mesh=None,
                 prefix_cache: bool = True, host_tier: bool = False,
                 catalog: Optional[ProgramCatalog] = None, adapters=None,
                 pages_per_slot: Optional[int] = None):
        """``params``: the model's tree (a stack a group), or a call that
        returns it and keeps no hold on it (``InferenceEngine.serving()``
        hands its tree over so: what the placement below replaces is then
        freed before the pool is made, and nothing is held twice).
        ``pages_per_slot``: the width of the decode program's page table
        (default: the pool's pages over the slots)."""
        self.model = model
        self.mesh = mesh
        # multi-tenant adapter serving (docs/SERVING.md): when an
        # AdapterRegistry rides along, EVERY decode/prefill/verify program
        # traces the per-slot LoRA factor stacks as one extra operand —
        # always present, so the inventory is bit-identical across any
        # tenant mix (adapter-less slots ride all-zero factors).  None
        # keeps today's program signatures byte-identical.
        self.adapters = adapters
        # per-program accounting (observability/program_stats.py): FLOPs
        # from lowered cost analysis at first invocation (no extra backend
        # compile), invocation counts per call, optional synced sampling.
        # None = no accounting at all (the legacy zero-instrumentation
        # path; the serving engine always passes one).
        self.catalog = catalog
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.b_slots = int(b_slots)
        cfg = model.config
        # (layers, experts) of a model whose expert layers are dropless:
        # its decode and prefill programs append the rows each expert
        # computed to the token output (split_counts); None for any other
        self.moe_shape = expert_counts_shape(cfg)
        self.pages_per_slot = int(pages_per_slot or max(
            1, (self.num_pages - 1) // self.b_slots))
        # what a slot's cache is made of (inference/cache_layout.py).  What
        # moves, shares or shards pages as K and V of some heads in ONE pool
        # refuses any other by name instead of serving a wrong answer
        self.layout = layout = CacheLayout(
            cfg, b_slots, page_size, self.pages_per_slot, num_pages)
        self.tp = 1
        if mesh is not None:
            if "model" not in mesh.axis_names:
                raise ValueError(
                    "serving mesh must carry a 'model' axis (build it with "
                    "parallel.mesh.initialize_mesh / "
                    "initialize_serving_mesh), got axes "
                    f"{tuple(mesh.axis_names)}")
            self.tp = int(mesh.shape["model"])
        layout.refuse("tensor-sharded heads (tp > 1)", self.tp > 1)
        layout.refuse("copy-on-write page snapshots (prefix_cache=True)",
                      prefix_cache)
        layout.refuse("KV-page tiering", host_tier)
        layout.refuse("the int8 pool", kv_dtype is not None)
        layout.refuse("multi-tenant adapters", adapters is not None)
        if self.tp > 1 and cfg.kv_heads % self.tp != 0:
            raise ValueError(
                f"kv_heads={cfg.kv_heads} not divisible by the mesh's "
                f"model axis ({self.tp}): the paged KV pool shards its "
                "head dim over 'model' (paged_cache_specs) — pick tp "
                "dividing kv_heads or replicate with tp=1")
        # the step the decode tick of a model with a state a slot holds, by
        # the kind of its mixer (``models.mixers.MIXERS``: chosen where the
        # tick is traced), and that step's passes over a live slot's state
        self.state_steps = state_step_paths(cfg)
        self.state_passes = max((MIXERS[kind].passes[step] for kind, step
                                 in self.state_steps.items()), default=0)
        # and the scan a prompt's program holds, for the kinds whose scan
        # has a kernel (chosen where the bucket is traced)
        self.state_scans = state_scan_paths(cfg)
        pool_kw = {"dtype": dtype, "kv_dtype": kv_dtype, **layout.pool_kw}
        specs = model.paged_cache_specs(kv_dtype=kv_dtype)
        # canonical pool tuple (models.transformer.PAGED_POOL_KEYS order):
        # (k, v) full precision, (k, v, k_scale, v_scale) quantized — every
        # program, COW/tier mover and byte gauge runs off this one tuple,
        # so the int8 layout is the SAME code path, not a parallel one
        self.kv_dtype = kv_dtype if kv_dtype is None else str(kv_dtype)

        def fresh_cache():
            return model.init_paged_cache(self.num_pages, self.page_size,
                                          **pool_kw)

        shapes = jax.eval_shape(fresh_cache)
        self._pool_keys = tuple(k for k in PAGED_POOL_KEYS if k in shapes)
        self.quantized = "k_scale" in shapes
        self._pool_specs = tuple(specs[k] for k in self._pool_keys)
        # The program that makes the pool, compiled before it runs: what it
        # reports of its results is how the device will store each leaf, so
        # the weights can be placed against the decode program while no pool
        # is in memory yet.  On a mesh the pool lives on the same device set
        # as the (sharded) params — KV heads over 'model' (scale planes
        # carry no head dim and ride replicated).
        make_pool = _aot(
            ("pool", type(model), cfg, mesh, self.num_pages, self.page_size,
             tuple(sorted((k, str(v)) for k, v in pool_kw.items()))),
            lambda: jax.jit(
                lambda: paged_pool_tuple(fresh_cache()), out_shardings=(
                    None if mesh is None else tuple(
                        NamedSharding(mesh, s) for s in self._pool_specs))
            ).lower().compile())
        self._pool_avals = tuple(
            jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=f.sharding)
            for a, f in zip(paged_pool_tuple(shapes),
                            make_pool.output_formats))
        # how the device stores a K/V leaf, where that is not row-major:
        # the paged read hands its loop the pool in that order
        # (models.transformer._pool_views); leaves of different widths may
        # be stored differently, so a pool a kind gives the order a leaf
        orders = [paged_pool_order(f) for f in make_pool.output_formats]
        self.pool_order = (dict(zip(self._pool_keys, orders))
                           if len(layout.pools) > 1 else orders[0])
        # how a decode tick lays its rows into each paged leaf, chosen where
        # it is traced from the backend, the leaf's shape and the order just
        # observed: "row" (the kernel: one row a slot stored where it lies)
        # or "page" (each slot's page gathered, merged and scattered back)
        self.kv_write = kv_write_paths(cfg, shapes, self.pool_order)
        self._decode_prog = self._build_decode()
        # The weights, placed ONCE in the form the decode program consumes
        # (docs/SERVING.md "Weight placement"), as the pool is stored the
        # way that program reads it: a leaf a layer where the forward walks
        # its layers in Python, each leaf in the layout the compiled tick
        # asks for.  Every program of the engine is compiled against this
        # placement.  ``tree`` is rebound at each step, so a tree that was
        # handed over is freed as it is replaced, before the pool exists.
        # (first onto the auto-TP shardings generate() uses; a tree already
        # committed to this mesh, InferenceEngine.serving()'s, passes through)
        tree = place_params(params() if callable(params) else params, mesh)
        tree, cut = self._per_layer(tree)
        # capture the placed tree's shape so LIVE weight updates
        # (update_params — hybrid rollout, docs/HYBRID.md) can be pinned to
        # the exact avals + shardings + layouts every program compiled
        # against: a jit caches on all three, so an update committed to the
        # captured placement is a guaranteed cache hit, never a recompile
        self._param_formats = self._tick_formats(tree)
        self.params, relaid, nbytes = self._in_formats(tree)
        del tree
        # the programs that cut and copied run behind this thread and hold
        # what they read until they end: the pool is made after them, or it
        # would be allocated beside the leaves they replace
        jax.block_until_ready(self.params)
        self.weight_placement = {"weight_leaves_split": cut,
                                 "weight_leaves_relaid": relaid,
                                 "weight_bytes_relaid": nbytes}
        leaves, self._param_treedef = jax.tree_util.tree_flatten(self.params)
        self._param_avals = [(tuple(getattr(x, "shape", ())),
                              str(getattr(x, "dtype", type(x).__name__)))
                             for x in leaves]
        # and how a tick reads each paged leaf: "pages" (the kernel: each
        # live page fetched once from where it lies) or "gather" (a step's
        # pages copied out of the pool, then attended); its queries are as
        # wide as the widest of the model's dtype and the placed weights'
        self.kv_read = kv_read_paths(
            cfg, shapes, self.pool_order, b_slots, jnp.result_type(
                cfg.dtype, *{x.dtype for x in leaves
                             if jnp.issubdtype(x.dtype, jnp.floating)}))
        # the fresh pool, committed to its placement: a jit caches on the
        # arg's committed-ness, so an UNcommitted initial pool would cost
        # each program one extra compile when the second call arrives
        # holding committed program outputs
        self.pools = tuple(jax.device_put(a, a.sharding)
                           for a in make_pool())
        self._prefill_progs: Dict[int, Any] = {}
        self._cow_prog = _COW_PROG if prefix_cache else None
        if self._cow_prog is not None:
            # pre-warm the one COW program shape with a trash-page self-copy
            # so its single compile lands at init, never during admission —
            # the zero-recompile steady state must hold from the first tick.
            # Through the entry point so the prewarm also registers the
            # program's cost in the catalog (acceptance: every inventory
            # program reports nonzero FLOPs even before a real COW).
            self.cow(0, 0)
        # KV-page tiering (docs/SERVING.md "KV-page tiering"): the device↔
        # host page movers.  Page ids are traced scalars, so each is ONE
        # program shape; both are pre-warmed on the trash page here at init
        # so a demote/promote during admission can never compile.  The
        # executor owns the move because on a mesh the host slab must be
        # placed under the pool's own sharding (heads over 'model') so each
        # shard receives exactly its head slice.
        self._extract_prog = self._inject_prog = None
        if host_tier:
            self._extract_prog = _TIER_EXTRACT_PROG
            self._inject_prog = _TIER_INJECT_PROG
            # prewarm through the entry points (trash-page round trip):
            # compiles land at init AND the catalog registers both movers
            self.inject(self.extract(0), 0)
        # constant for the engine's lifetime (the pool never reallocates):
        # health()/gauges read these per tick, so compute them once
        self.pool_bytes = pool_bytes(*self.pools)
        # of which the leaves indexed by slot (a state-space model's)
        self.state_bytes = sum(
            int(a.nbytes) for k, a in zip(self._pool_keys, self.pools)
            if k in STATE_POOL_KEYS)
        layout.state_slot_bytes = self.state_bytes // self.b_slots
        layout.state_passes = self.state_passes
        layout.kv_write_leaves = tuple(
            sum(path == by for path in self.kv_write.values())
            for by in ("row", "page"))
        layout.kv_read_pages = "pages" in (self.kv_read.get("k"),
                                           self.kv_read.get("latent"))
        # one token's rows in every paged leaf, over every layer and pass
        layout.kv_token_bytes = sum(
            int(a.nbytes) // (a.shape[1] * self.page_size)
            for k, a in zip(self._pool_keys, self.pools)
            if k not in STATE_POOL_KEYS)
        # device copy of the lane vectors, rebuilt only when a lane
        # changes (admission / retirement) — unlike lengths/last_tok the
        # lanes are constant across a request's whole decode, so the
        # per-tick call must not pay 4 host->device transfers for them
        self._lanes_device = None
        # device copy of the per-slot adapter factor stacks, same
        # invalidation contract as the lanes: constant across a request's
        # decode, rebuilt only when slot membership changes
        self._adapters_device = None
        # the one small program beside the decode and prefill programs that
        # the lookahead runs (docs/SERVING.md "Decode lookahead"): a
        # prefill's token into a lane of the next tick's token operand, on
        # the device.  Its output lies as a decode program's own does, so
        # the tick it is fed to is that program and no second compile; warmed
        # here on both operands' forms so that no admission compiles it
        self._feed_prog = jax.jit(feed_lane,
                                  out_shardings=self._token_sharding())
        counts = self.moe_shape[0] * self.moe_shape[1] if self.moe_shape else 0
        first = jax.device_put(np.zeros((1 + counts,) if counts else (),
                                        np.int32), self._token_sharding())
        self.feed_lane(self.feed_lane(np.zeros((self.b_slots,), np.int32),
                                      first, 0), first, 0)

    # ------------------------------------------------------------ programs

    def _apply_paged(self, *args, **kw):
        """``model.apply_paged`` -> ``(logits, cache, counts)``: the rows
        each expert of each layer computed where the model hands them back
        (``moe_shape``), else ``None``."""
        kw["pool_order"] = self.pool_order
        if self.moe_shape is None:
            return (*self.model.apply_paged(*args, **kw), None)
        return self.model.apply_paged(*args, expert_counts=True, **kw)

    @staticmethod
    def _with_counts(nxt, counts):
        """A program's one small output: the sampled token(s), followed by
        the expert counts where the model has any, so the host's one fetch
        of the tokens brings what the router did with it."""
        if counts is None:
            return nxt
        return jnp.concatenate([nxt.reshape(-1).astype(jnp.int32),
                                counts.reshape(-1)])

    def split_counts(self, out: np.ndarray):
        """The fetched output of :meth:`decode` or :meth:`prefill` ->
        ``(tokens, counts [L, E] or None)``."""
        if self.moe_shape is None:
            return out, None
        n = out.size - self.moe_shape[0] * self.moe_shape[1]
        return out[:n], out[n:].reshape(self.moe_shape)

    def _build_decode(self):
        apply_paged, with_counts = self._apply_paged, self._with_counts
        keys = self._pool_keys

        def prog(params, pools, page_table, lengths, last_tok, active,
                 temp, top_k, top_p, seeds, *adapters):
            # write each slot's last token at position `lengths`, read the
            # next-token logits; inactive slots write to the trash page.
            # The sampled token will sit at stream position `lengths + 1`,
            # so its lane key folds that position — the same counter
            # generate(sampling=...) and a replay/failover re-prefill
            # derive, which is what keeps sampled streams engine-
            # independent and resume-exact (docs/SERVING.md "Sampling").
            # `last_tok` has the shape of the program's own first output
            # (tokens, then an MoE model's expert counts), so a tick can be
            # fed the one before it without a fetch (ServingEngine
            # lookahead).  `adapters`: the per-slot factor pytree, one
            # trailing operand where a registry rides along, else none.
            cache = paged_pool_cache(pools, keys)
            logits, cache, counts = apply_paged(
                params, last_tok[:lengths.shape[0], None], cache, page_table,
                lengths, active[:, None],
                adapters=adapters[0] if adapters else None)
            with jax.named_scope("sample"):
                nxt = sample_tokens(logits[:, -1, :], temp, top_k, top_p,
                                    lambda: position_keys(seeds, lengths + 1))
            return with_counts(nxt, counts), paged_pool_tuple(cache)

        # kept for the jit that asks the compiler for the weights' layouts
        # (_compile_tick_formats): one function, so that jit finds it traced
        self._decode_fn = prog
        return _named_pool_jit(prog, "serve_decode", self.mesh,
                               self._pool_specs)

    def _tick_formats(self, params):
        """The ``Format`` (layout and sharding) the decode program wants
        each leaf of ``params`` in: the tick compiled ahead of time with
        ``Layout.AUTO`` on the parameter tree and every other argument as
        :meth:`decode` passes it, read off ``compiled.input_formats``.  The
        tick decides because it is bound by memory and runs thousands of
        times a window; a prefill re-lays a leaf out inside a program a
        hundred times longer if it must.  The CPU backend answers with the
        layout a leaf has.  ``None`` for a tree with a leaf that is no
        device array (a host tree rides each call's own upload, as it did).
        Found once a process for what the program is made from
        (``_aot``)."""
        leaves, treedef = jax.tree_util.tree_flatten(params)
        if not leaves or not all(isinstance(x, jax.Array) for x in leaves):
            return None
        # a leaf nobody committed goes where the programs' small operands
        # go (replicated over the mesh, else where the pool lies), once and
        # not with every call
        avals = treedef.unflatten([
            jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=(
                x.sharding if x.committed else self._token_sharding()))
            for x in leaves])
        adapters = (() if self.adapters is None else (jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            self._adapter_zero()),))
        order = (tuple(sorted(self.pool_order.items()))
                 if isinstance(self.pool_order, dict) else self.pool_order)
        key = (getattr(self.model.apply_paged, "__func__",
                       self.model.apply_paged), self.model.config, self.mesh,
               self.b_slots, self.page_size, self.pages_per_slot, order,
               tuple((a.shape, str(a.dtype), a.sharding)
                     for a in self._pool_avals),
               treedef, tuple((a.shape, str(a.dtype), a.sharding)
                              for a in jax.tree_util.tree_leaves(avals)),
               str(jax.tree_util.tree_structure(adapters)),
               tuple((a.shape, str(a.dtype))
                     for a in jax.tree_util.tree_leaves(adapters)))
        return _aot(key, lambda: self._compile_tick_formats(avals, adapters))

    def _compile_tick_formats(self, params, adapters):
        B = self.b_slots

        def vec(dtype, n=B):
            return jax.ShapeDtypeStruct((n,), dtype)

        # the slots' table, or a table a pool (full, ring)
        table = tuple(jax.ShapeDtypeStruct((B, per_slot), jnp.int32)
                      for _, per_slot in self.layout.pools)
        table = table if len(table) > 1 else table[0]
        counts = self.moe_shape[0] * self.moe_shape[1] if self.moe_shape else 0
        args = (params, self._pool_avals, table, vec(jnp.int32),
                jax.ShapeDtypeStruct((B + counts,), jnp.int32,
                                     sharding=self._token_sharding()),
                vec(jnp.bool_), vec(jnp.float32), vec(jnp.int32),
                vec(jnp.float32), vec(jnp.uint32)) + adapters
        auto = jax.tree_util.tree_map(
            lambda x: Format(Layout.AUTO, x.sharding), params)
        formats = _named_pool_jit(
            self._decode_fn, "serve_decode", self.mesh, self._pool_specs,
            (auto,) + (None,) * (len(args) - 1)).lower(*args).compile(
            ).input_formats[0][0]
        # each leaf's layout, on the sharding the leaf has
        return jax.tree_util.tree_map(
            lambda x, f: Format(f.layout, x.sharding), params, formats)

    def _per_layer(self, tree, sharding=None):
        """``per_layer_leaves`` of a tree of device arrays, each first
        committed to ``sharding`` (default: where it lies): a jit caches on
        that, so the programs that cut the tree the engine was built on are
        the ones that cut the next (:meth:`update_params` compiles
        nothing)."""
        leaves = jax.tree_util.tree_leaves(tree)
        if is_hybrid(self.model.config) and leaves and all(
                isinstance(x, jax.Array) for x in leaves):
            tree = jax.device_put(tree, sharding or jax.tree_util.tree_map(
                lambda x: x.sharding, tree))
        return per_layer_leaves(self.model.config, tree)

    def _in_formats(self, tree):
        """``(tree as the decode program reads it, leaves copied, their
        bytes)``: a leaf that lies so already where it lies (not copied, not
        even committed anew), any other committed to its sharding and, if
        its layout is another, copied into the layout, all of those by one
        program.  The same steps for the tree the engine is built on and for
        every later one, so the second finds that program compiled."""
        if self._param_formats is None:
            return tree, 0, 0
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        formats = jax.tree_util.tree_leaves(self._param_formats)
        for i, f in enumerate(formats):
            if not _lies_as(leaves[i], f):
                leaves[i] = jax.device_put(leaves[i], f.sharding)
        moved = [i for i, f in enumerate(formats)
                 if not _lies_as(leaves[i], f)]
        nbytes = sum(int(leaves[i].nbytes) for i in moved)
        if moved:
            for i, x in zip(moved, jax.jit(_as_given, out_shardings=tuple(
                    formats[i] for i in moved))(
                        tuple(leaves[i] for i in moved))):
                leaves[i] = x
        return treedef.unflatten(leaves), len(moved), nbytes

    def _build_prefill(self, s_pad: int):
        apply_paged, with_counts = self._apply_paged, self._with_counts
        keys = self._pool_keys

        # a state a slot: the program is told whose row it resets and writes
        stateful = self.layout.stateful

        def prog(params, pools, pt_row, tokens, n_real, start,
                 temp, top_k, top_p, seed, *rest):
            # tokens [1, s_pad] right-padded; only the first n_real K/V are
            # written (pads go to the trash page); the first generated token
            # samples the last REAL position's logits under the request's
            # lane ([1]-shaped traced params — greedy folds to argmax
            # in-graph, so the historical greedy contract is bit-identical).
            # The head runs over that one position (``logits_at``), not over
            # the bucket.
            # `start` is the slot position of tokens[:, 0] — 0 for a cold
            # prefill, the shared-prefix length for a tail prefill (the
            # read starts at the slot's first page, so queries attend to
            # the shared pages through the ordinary causal mask).
            # A traced scalar: every start shares ONE program per bucket.
            # `rest`: the admitted slot's factor slice, as in decode; or,
            # for a model with a state a slot, the slot (a traced scalar:
            # one program a bucket serves every slot), whose state row the
            # call resets (start 0) and leaves at the last real token.
            seq_mask = (jnp.arange(s_pad, dtype=jnp.int32) < n_real)[None, :]
            cache = paged_pool_cache(pools, keys)
            kw = ({"state_slot": rest[0][None]} if stateful else
                  {"adapters": rest[0] if rest else None})
            logits, cache, counts = apply_paged(
                params, tokens, cache, pt_row, start[None], seq_mask,
                logits_at=(n_real - 1)[None], **kw)
            # the emitted token will sit at stream position S = start +
            # n_real — the counter-based key generate(sampling=...) and
            # every replay/failover resume re-derive for the same position
            with jax.named_scope("sample"):
                lg = logits[0]                             # [1, V]
                nxt = sample_tokens(
                    lg, temp, top_k, top_p,
                    lambda: position_keys(seed, (start + n_real)[None]))[0]
            return with_counts(nxt, counts), paged_pool_tuple(cache)

        return _named_pool_jit(prog, f"serve_prefill_{s_pad}", self.mesh,
                               self._pool_specs)

    def _place_host_slabs(self, slabs):
        """Commit one host page's slab tuple to the pool's placement: on a
        mesh each ``[L, page, Hkv, hd]`` payload slab shards its head dim
        over 'model' (its pool spec minus the page axis), so a promote
        feeds each shard its own head slice; ``[L, page]`` scale slabs ride
        replicated.  Unmeshed, the numpy slabs ride the jit's default
        device_put."""
        if self.mesh is None:
            return tuple(slabs)
        return tuple(
            jax.device_put(s, NamedSharding(self.mesh,
                                            P(spec[0], *spec[2:])))
            for s, spec in zip(slabs, self._pool_specs))

    # ---------------------------------------------------------- entry points
    # Every program call site follows the one catalog protocol
    # (program_stats.account / finish_sample): register lowered cost on
    # first sight, count the dispatch, sample the synced wall time on the
    # picked invocations (docs/OBSERVABILITY.md "Per-program accounting").

    def _token_sharding(self):
        """Where a program's token output lands: replicated over the mesh
        (``pool_jit``'s ``out_shardings``), else where the pool lies."""
        if self.mesh is not None:
            return NamedSharding(self.mesh, P())
        return self._pool_avals[0].sharding

    def fed(self, last_tok):
        """The token operand of :meth:`decode` on the device: the host's
        [B_slots] vector placed as the program's own output is (room for an
        MoE model's expert counts behind it), so that feeding that output
        back is the same program and not a second compile; a device array
        as it is."""
        if isinstance(last_tok, np.ndarray):
            if self.moe_shape is not None:
                last_tok = np.concatenate([last_tok, np.zeros(
                    self.moe_shape[0] * self.moe_shape[1], last_tok.dtype)])
            last_tok = jax.device_put(last_tok, self._token_sharding())
        return last_tok

    def feed_lane(self, fed, out, slot: int):
        """``fed`` (the host's vector or a tick's device output) with lane
        ``slot`` taken from the prefill output ``out`` where it lies on the
        device: the tick launched behind an admission's prefill includes
        the new slot and no fetch stands between the two launches."""
        return self._feed_prog(self.fed(fed), out, np.int32(slot))

    def decode(self, page_table, lengths, last_tok, active, lanes,
               adapters=None):
        """One fixed-shape decode step over all slots; returns the sampled
        [B_slots] token vector (device array — the caller fetches inside
        its watchdog window; with expert counts behind it where the model
        has them, :meth:`split_counts`) and updates the pools in place.
        ``last_tok`` is the host's [B_slots] vector or the device array the
        previous call returned, as it is.  With an
        adapter registry attached, ``adapters`` is the per-slot factor
        pytree (``adapter_stacks``); ``None`` rides the cached all-zero
        stacks (base-model traffic) — the program signature never changes."""
        last_tok = self.fed(last_tok)
        # ``page_table``: the slots' table, or (full, ring) tables of a
        # model with window layers
        args = (self.params, self.pools,
                jax.tree_util.tree_map(jnp.asarray, page_table),
                jnp.asarray(lengths), last_tok, jnp.asarray(active), *lanes)
        if self.adapters is not None:
            args += (adapters if adapters is not None
                     else self._adapter_zero(),)
        t0 = account(self.catalog, "decode", self._decode_prog, args)
        nxt, self.pools = self._decode_prog(*args)
        if t0 is not None:
            finish_sample(self.catalog, "decode", nxt, t0)
        return nxt

    def prefill(self, s_pad: int, pt_row, tokens, n_real, start,
                lane_t, lane_k, lane_p, lane_s, adapter_row=None, slot=0):
        """One bucketed prefill ([1, s_pad]); returns the first sampled
        token (device scalar; a vector led by it where the model has expert
        counts, :meth:`split_counts`) and updates the pools.  Builds the bucket's
        program on first use — the bucket set IS the program inventory.
        ``adapter_row`` is the admitted slot's one-slot factor slice
        (:meth:`adapter_row`) when a registry rides along; ``slot`` the slot
        admitted, which a model with a state a slot needs to know."""
        prog = self._prefill_progs.get(s_pad)
        if prog is None:
            prog = self._prefill_progs[s_pad] = self._build_prefill(s_pad)
        # lanes ride as numpy arrays: jit device-puts them without
        # compiling the tiny list->array convert programs a jnp.asarray
        # of a Python list would cost on first use
        args = (self.params, self.pools, pt_row, tokens,
                jnp.int32(n_real), jnp.int32(start),
                np.asarray([lane_t], np.float32),
                np.asarray([lane_k], np.int32),
                np.asarray([lane_p], np.float32),
                np.asarray([lane_s], np.uint32))
        if self.adapters is not None:
            args += (adapter_row if adapter_row is not None
                     else self._adapter_zero_row(),)
        elif self.layout.stateful:
            args += (jnp.int32(slot),)
        t0 = account(self.catalog, f"prefill_{s_pad}", prog, args)
        nxt, self.pools = prog(*args)
        if t0 is not None:
            finish_sample(self.catalog, f"prefill_{s_pad}", nxt, t0)
        return nxt

    def cow(self, src: int, dst: int) -> None:
        """Snapshot physical page ``src`` onto ``dst`` across all layers
        (copy-on-write boundary page; one fixed program shape).  On a
        quantized pool the copy moves raw int8 bytes + scale rows — COW
        never round-trips through float."""
        args = (self.pools, jnp.int32(src), jnp.int32(dst))
        t0 = account(self.catalog, "cow", self._cow_prog, args)
        self.pools = self._cow_prog(*args)
        if t0 is not None:
            finish_sample(self.catalog, "cow", self.pools[0], t0)

    def extract(self, src: int):
        """Demote half of the tier move: copy physical page ``src`` to
        host, returning one numpy slab per pool array in canonical order —
        ``(hk, hv)`` of ``[L, page, Hkv, hd]`` full precision, plus the
        ``[L, page]`` scale slabs on an int8 pool (a sharded pool gathers
        the head shards into one slab).  Read-only — the pool survives."""
        args = (self.pools, jnp.int32(src))
        t0 = account(self.catalog, "tier_extract", self._extract_prog, args)
        slabs = self._extract_prog(*args)
        out = tuple(np.asarray(s) for s in slabs)
        if t0 is not None:   # the host fetch above already synced
            self.catalog.record_sync("tier_extract",
                                     time.perf_counter() - t0)
        return out

    def inject(self, slabs, dst: int) -> None:
        """Promote half of the tier move: place the host slab tuple under
        the pool's shardings and write it into physical page ``dst`` (one
        fixed program shape; pools donated like COW)."""
        placed = self._place_host_slabs(slabs)
        args = (self.pools, placed, jnp.int32(dst))
        t0 = account(self.catalog, "tier_inject", self._inject_prog, args)
        self.pools = self._inject_prog(*args)
        if t0 is not None:
            finish_sample(self.catalog, "tier_inject", self.pools[0], t0)

    def update_params(self, params):
        """Swap the LIVE param tree under every compiled program (hybrid
        rollout, docs/HYBRID.md).  Params are ordinary program arguments,
        so the swap itself is free — the work here is making it provably
        zero-recompile: the incoming tree (typically the training engine's
        live compute view) is resharded through the same
        ``place_params``/``auto_tp_specs`` path the original placement
        used, held a leaf a layer where the first one is, then committed to
        the EXACT shardings and layouts captured at build time, so the
        jitted programs see identical avals + shardings + layouts and hit
        their caches.  What callers hand in is the model's tree, a stack a
        group, or the placed tree itself.  A tree whose structure or leaf
        shapes/dtypes differ from the compiled ones is rejected loudly — it
        would silently recompile every program in the inventory."""
        placed = place_params(params, self.mesh)
        if (is_hybrid(self.model.config) and isinstance(placed, dict)
                and jax.tree_util.tree_structure(placed)
                != self._param_treedef):
            # a stack a group, as callers hold the model: cut where the
            # placed tree lies (such a model is on one device)
            placed, _ = self._per_layer(placed, jax.tree_util.tree_leaves(
                self.params)[0].sharding)
        treedef = jax.tree_util.tree_structure(placed)
        if treedef != self._param_treedef:
            raise ValueError(
                "update_params: the new param tree's structure differs "
                f"from the compiled one ({treedef} vs "
                f"{self._param_treedef}) — every program would recompile")
        leaves = jax.tree_util.tree_leaves(placed)
        for i, x in enumerate(leaves):
            aval = (tuple(getattr(x, "shape", ())),
                    str(getattr(x, "dtype", type(x).__name__)))
            if aval != self._param_avals[i]:
                raise ValueError(
                    f"update_params: leaf {i} has aval {aval}, compiled "
                    f"programs expect {self._param_avals[i]} — the swap "
                    "must be shape/dtype-identical (zero-recompile)")
        self.params = self._in_formats(placed)[0]

    def lanes(self, temp, top_k, top_p, seeds):
        """Cached device copy of the per-slot lane vectors; the engine
        invalidates on admission/retirement (lane membership changed)."""
        if self._lanes_device is None:
            self._lanes_device = (jnp.asarray(temp), jnp.asarray(top_k),
                                  jnp.asarray(top_p), jnp.asarray(seeds))
        return self._lanes_device

    def invalidate_lanes(self) -> None:
        self._lanes_device = None

    # per-slot adapter operand cache — the same contract as the sampling
    # lanes: constant across a request's decode, invalidated only when a
    # slot's adapter membership changes (admission / retirement)

    def adapter_stacks(self, host_stacks):
        """Cached device copy of the engine's per-slot adapter factor
        stacks (``AdapterRegistry.make_slot_stacks`` layout)."""
        if self._adapters_device is None:
            self._adapters_device = jax.tree_util.tree_map(
                jnp.asarray, host_stacks)
        return self._adapters_device

    def invalidate_adapters(self) -> None:
        self._adapters_device = None

    @staticmethod
    def adapter_row(host_stacks, slot: int):
        """One slot's factor slice of the host stacks, shaped for the
        [1, s_pad] prefill programs — numpy views, so slicing is free and
        every slot shares the ONE per-bucket program shape."""
        s = int(slot)
        return {"scale": host_stacks["scale"][s:s + 1],
                "factors": {k: {"A": ab["A"][:, s:s + 1],
                                "B": ab["B"][:, s:s + 1]}
                            for k, ab in host_stacks["factors"].items()}}

    def _adapter_zero(self):
        """All-zero decode stacks (base-model fallback operand)."""
        if getattr(self, "_adapter_zero_host", None) is None:
            self._adapter_zero_host = self.adapters.make_slot_stacks(
                self.b_slots)
        return jax.tree_util.tree_map(jnp.asarray, self._adapter_zero_host)

    def _adapter_zero_row(self):
        if getattr(self, "_adapter_zero_host", None) is None:
            self._adapter_zero_host = self.adapters.make_slot_stacks(
                self.b_slots)
        return self.adapter_row(self._adapter_zero_host, 0)

    # ------------------------------------------------------------- health

    def pool_alive(self) -> bool:
        dead = getattr(self.pools[0], "is_deleted", None)
        return not (dead and self.pools[0].is_deleted())

    def mesh_info(self) -> Dict[str, Any]:
        """Static facts for health()/gauges: device count and the
        non-trivial axis sizes (``{}`` / 1 device when unmeshed), and what
        the weights' placement did to the tree it was given:
        ``weight_leaves_split`` stacks cut into a leaf a layer,
        ``weight_leaves_relaid`` leaves (``weight_bytes_relaid`` bytes)
        copied into the layout the decode program asked for; all 0 for a
        tree that already lay so (a warm restart's).  ``ssm_step``,
        ``delta_step``, ``conv_step`` (each row's ``step_key`` of
        ``models.mixers.MIXERS``): the step the decode tick holds for that
        kind of mixer (the row's ``step_path``: ``"one_pass"`` or the plain
        step's name), ``None`` for a model with no such layer.  ``ssm_scan``:
        the scan a prompt's program holds for its state-space layers
        (``models.mixers.ssm.ssm_scan_path``: ``"kernel"``,
        ``ops/pallas/ssm_scan.py``, or ``"xla"``), ``None`` likewise.
        ``kv_write``: how the decode tick
        lays a token's rows into each paged leaf (``"row"`` / ``"page"``:
        ``models.transformer.kv_write_path``), ``kv_read`` how it reads each
        (``"pages"`` / ``"gather"``: ``models.transformer.kv_read_path``).
        ``expert_matmul``: how each
        program compiled so far runs its expert layers' grouped products
        (:meth:`expert_matmul`).  ``loop_passes``: how often a token
        runs the model's layers (a looped model's ``loop_passes``, else 1),
        and ``kv_bytes_per_token``: what one token's rows take in the paged
        leaves over every layer and pass.  ``cache_kind``
        (``models.transformer.cache_kind``) and how deep its leaves are:
        ``kv_layers`` layers with K/V pages, ``state_layers`` with a state row
        a slot, ``ring_pages`` pages a slot in a window layer's ring (0: no
        window layers).  ``layers_by_kind``: the layers run of each kind
        ("mlp": a layer that is its MLP or expert layer alone) and
        ``cache_leaves_by_kind`` the cache leaves a kind's layers own (none
        for "mlp").  ``state_programs``: the step or scan each program
        compiled so far holds for its mixer (:meth:`state_programs`)."""
        mesh = self.mesh
        return {"mesh_devices": 1 if mesh is None else int(mesh.size),
                "mesh_axes": {} if mesh is None else {
                    a: int(mesh.shape[a]) for a in mesh.axis_names
                    if int(mesh.shape[a]) > 1},
                **self.weight_placement,
                **{m.step_key: self.state_steps.get(m.kind)
                   for m in MIXERS.values()},
                **self.state_scans,
                "kv_write": dict(self.kv_write),
                "kv_read": dict(self.kv_read),
                "expert_matmul": self.expert_matmul(),
                "loop_passes": self.layout.passes,
                "kv_bytes_per_token": self.layout.kv_token_bytes,
                "cache_kind": self.layout.kind,
                "kv_layers": self.layout.kv_layers,
                "state_layers": self.layout.state_layers,
                "ring_pages": self.layout.ring_pages,
                "layers_by_kind": dict(self.layout.layers_by_kind),
                "cache_leaves_by_kind": {
                    k: list(v)
                    for k, v in self.layout.leaves_by_kind.items()},
                "state_programs": self.state_programs()}

    def state_programs(self) -> Dict[str, str]:
        """How each compiled program of a model with a state a slot advances
        it, by the program's name on its ``serve.launch`` span: ``decode``
        the tick's step (each row's ``step_path`` of
        ``models.mixers.MIXERS``), ``prefill_<bucket>`` the bucket's scan
        where the kind has a kernel for one (``scan_path``); ``{}`` for any
        other model."""
        cfg = self.model.config
        out = {}
        for m in mixers_of(cfg):
            out["decode"] = self.state_steps[m.kind]
            if m.scan_path:
                out.update({f"prefill_{s}": m.scan_path(cfg, s)
                            for s in sorted(self._prefill_progs)})
        return out

    def expert_matmul(self) -> Dict[str, str]:
        """How each compiled program of a model with dropless expert layers
        runs their grouped products (``"kernel"`` / ``"ragged_dot"``:
        ``models.transformer.expert_matmul_path``), by the program's name on
        its ``serve.launch`` span: ``decode``, ``prefill_<bucket>``; ``{}``
        for any other model."""
        cfg = self.model.config
        if self.moe_shape is None:
            return {}
        return {"decode": expert_matmul_path(cfg, self.b_slots, 1),
                **{f"prefill_{s}": expert_matmul_path(cfg, 1, s)
                   for s in sorted(self._prefill_progs)}}

    def expert_product_attrs(self, program: str) -> Dict[str, int]:
        """The grouped products ``program`` holds, by the way they went:
        static a program, for its span."""
        n = expert_products(self.model.config)
        kernel = n if self.expert_matmul().get(program) == "kernel" else 0
        return {"moe_kernel_products": kernel,
                "moe_ragged_products": n - kernel}

    def expert_row_attrs(self, program: str, counts: np.ndarray,
                         live_tokens: int) -> Dict[str, int]:
        """The rows ``program``'s expert layers sorted and the rows their
        way in moved in one call (``models.transformer.expert_rows_moved``),
        from the call's fetched ``counts``: host arithmetic."""
        B, S = ((self.b_slots, 1) if program == "decode"
                else (1, int(program.rsplit("_", 1)[1])))
        total, moved = expert_rows_moved(self.model.config, B, S, counts,
                                         live_tokens)
        return {"moe_sorted_rows": total, "moe_moved_rows": moved}

    # ----------------------------------------------------------- adoption

    def adopt_programs(self, old: "MeshExecutor") -> None:
        """Warm-restart/recycle path: carry the dead executor's compiled
        programs — jax.jit caches on avals INCLUDING shardings, and the
        fresh pool has the same shape/dtype/placement, so every adopted
        program is a cache hit instead of a recompile."""
        self._decode_prog = old._decode_prog
        self._prefill_progs.update(old._prefill_progs)
