"""Speculative decoding over the paged KV pool: draft k, verify in one pass.

Decode is batch-amortized but still ONE token per model traversal; a small
draft model can guess several tokens cheaply and the big target model can
*score* all of them in a single fixed-shape forward — the verify-k
multiplier vLLM/Medusa-style stacks get, rebuilt TPU-native so it lives
inside the serving engine's zero-recompile program inventory
(docs/SERVING.md "Speculative decoding"):

- **Mirrored paged pools.**  The draft model gets its OWN pool with the
  same ``(num_pages, page_size)`` geometry, indexed by the engine's SAME
  per-slot page tables: every admission prefills both pools, every COW
  snapshots both, so draft residency needs zero extra bookkeeping — page
  accounting, prefix sharing and quarantine stay exactly the engine's
  (a shared page's draft-side K/V was written by the same donor admission
  that wrote its target-side K/V).
- **Draft loop.**  Per tick the draft decodes ``k`` tokens with ``k``
  invocations of ONE ``[B_slots, 1]`` draft program, each returning the
  proposal AND its full proposal distribution ``q`` (the engine's
  per-slot :class:`~.sampling.SamplingParams` filter the draft logits
  too, so proposals stay inside the target's support).
- **Verify-k.**  One ``[B_slots, k+1]`` target ``forward_paged`` call
  writes ``[last_tok, d_1..d_k]`` and yields the k+1 target distributions
  in one traversal; standard rejection sampling runs IN-GRAPH: accept
  ``d_i`` iff ``u_i * q_i(d_i) < p_i(d_i)``, emit a correction token from
  ``normalize(max(p - q, 0))`` at the first rejection — so each slot
  emits 1..k tokens per tick and the TARGET distribution is preserved
  exactly.  (The classic *bonus* token from ``p_k`` on full acceptance is
  deliberately NOT emitted: it would sit one past the last draft-pool
  write, leaving a permanent draft-K/V gap that degrades ``q`` for the
  rest of the request — capping at k keeps the pending token's draft
  write exactly one tick behind, always.)  Greedy lanes (``temperature
  <= 0``) make every ``p`` one-hot, so acceptance degenerates to ``d_i ==
  argmax`` and the emitted stream is token-identical to non-speculative
  greedy decode (the acceptance test).
- **Counter-based keys, salted per role** — draft proposal / accept
  uniform / correction resample for the token at absolute position ``pos``
  derive from ``position_keys(seed, pos, salt=SALT_*)``.  Because every
  EMITTED token at position ``pos`` follows the same per-position
  procedure — propose from ``q(·|confirmed prefix)`` with the DRAFT key,
  accept-test with the ACCEPT key, correct with the RESAMPLE key (an
  emitted draft token's in-block predecessors were all accepted, i.e.
  they ARE the confirmed prefix) — the stream is independent of block
  alignment: replay, tick-aligned failover resume AND a
  ``max_journal_tokens``-truncated mid-block resume all re-derive the
  identical sampled stream.

Rejected positions leave draft-token K/V garbage in both pools past the
accepted length; slot-index == position causality hides it until the next
tick's writes overwrite it (and :func:`~..models.transformer.forward_paged`
trash-redirects any write past the slot's allocated pages, so a verify
block straddling the page-table end can never wrap into live pages).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..models.transformer import (PAGED_POOL_KEYS, paged_pool_cache,
                                  paged_pool_order,
                                  paged_pool_tuple)
from ..observability.program_stats import account, finish_sample
from .sampling import position_keys, sample_tokens, sampling_probs

__all__ = ["SpeculativeConfig", "SpeculativeDecoder", "layer_skip_draft",
           "perturbed_draft"]

# role salts for the counter-based key schedule: the draft proposal, the
# accept-test uniform and the correction/bonus resample at one stream
# position must draw INDEPENDENT randomness, and none may collide with the
# non-speculative sampler's unsalted position key
SALT_DRAFT = 1
SALT_ACCEPT = 2
SALT_RESAMPLE = 3


@dataclasses.dataclass
class SpeculativeConfig:
    """Draft-side configuration for a speculative :class:`ServingEngine`.

    ``draft_model``/``draft_params`` must expose the same paged contract as
    the target (``models.CausalLM``) over the SAME vocabulary; ``k`` is the
    number of draft tokens proposed per verify tick (each slot then emits
    1..k tokens per tick)."""
    draft_model: Any
    draft_params: Any
    k: int = 4

    def validate(self, target_model, max_model_len: int) -> None:
        if self.k < 1:
            raise ValueError(f"speculative k={self.k} must be >= 1")
        if not hasattr(self.draft_model, "apply_paged"):
            raise ValueError(
                "speculative draft_model needs the paged decode contract "
                "(init_paged_cache/apply_paged) — see models.CausalLM")
        dv = self.draft_model.config.vocab_size
        tv = target_model.config.vocab_size
        if dv != tv:
            raise ValueError(
                f"draft vocab {dv} != target vocab {tv}: rejection "
                "sampling compares p and q over one token space")
        if self.draft_model.config.max_seq_len < max_model_len:
            raise ValueError(
                f"draft max_seq_len {self.draft_model.config.max_seq_len} "
                f"< max_model_len {max_model_len}: the draft must reach "
                "every position the target serves")


def layer_skip_draft(model, params, num_layers: int):
    """Self-speculative draft (LayerSkip / Draft&Verify style): the draft
    IS the target's first ``num_layers`` transformer blocks plus its
    embedding/norm/head — zero extra weights loaded (the sliced layer
    stack shares the target's leaves), and on a trained checkpoint the
    early layers' argmax agrees with the full stack often enough to pay
    for the verify.  Returns ``(draft_model, draft_params)`` for
    :class:`SpeculativeConfig`."""
    cfg = model.config
    if not (0 < num_layers < cfg.num_layers):
        raise ValueError(
            f"layer_skip_draft num_layers={num_layers} must be in "
            f"(0, {cfg.num_layers}) — the draft must be a strict prefix "
            "of the target stack")
    if isinstance(params.get("layers"), (list, tuple)):
        raise NotImplementedError(
            "layer_skip_draft needs a uniform stacked layer tree "
            "(scan_layers); per-layer pyramids are not sliceable")
    from ..models import CausalLM

    draft = CausalLM(cfg, num_layers=num_layers)
    draft_params = dict(params)
    draft_params["layers"] = jax.tree_util.tree_map(
        lambda x: x[:num_layers], params["layers"])
    return draft, draft_params


def perturbed_draft(model, params, scale: float = 1e-3, seed: int = 0):
    """A noise-perturbed full copy of the target — the CPU bench stand-in
    for a distilled draft (tiny CI models are random-init, so no trained
    small model exists to draft with).  ``scale`` is relative to each
    leaf's std: small scales keep argmax agreement high (accepted length
    near k+1), larger ones exercise the rejection path."""
    from ..models import CausalLM

    draft = CausalLM(model.config)
    key_box = [jax.random.PRNGKey(seed)]

    def perturb(x):
        if not (hasattr(x, "dtype") and jnp.issubdtype(x.dtype,
                                                       jnp.floating)):
            return x
        key_box[0], sub = jax.random.split(key_box[0])
        std = jnp.std(x) + 1e-8
        return x + scale * std * jax.random.normal(sub, x.shape, x.dtype)

    return draft, jax.tree_util.tree_map(perturb, params)


class SpeculativeDecoder:
    """The draft pool + the three speculative programs, owned by a
    :class:`~.serving.ServingEngine` built with ``speculative=``.

    Program inventory (all fixed-shape, draft decode + verify compiled at
    init, draft prefills per prompt bucket like the target's):

    - draft decode ``[B_slots, 1]`` — one proposal + its ``q`` row;
    - verify ``[B_slots, k+1]`` — target scores + in-graph acceptance;
    - draft prefill ``[1, S_pad]`` per bucket — prompt K/V into the
      draft pool (emits nothing; the target prefill emits the first
      token exactly as without speculation).
    """

    def __init__(self, config: SpeculativeConfig, target_model,
                 num_pages: int, page_size: int, b_slots: int,
                 dtype=None, kv_dtype=None, mesh=None, catalog=None,
                 adapters=None, target_pool_order=None):
        from .execution import place_params, pool_bytes

        # multi-tenant adapter serving (docs/SERVING.md): the TARGET
        # verify program carries the per-slot LoRA operand (correctness —
        # acceptance compares against the tenant's true distribution);
        # the DRAFT stays adapter-free by design: rejection sampling
        # preserves the target distribution regardless of q, so an
        # adapter-less draft only costs acceptance rate, never exactness.
        self.adapters = adapters

        # per-program accounting shared with the owning engine's
        # MeshExecutor (observability/program_stats.py): draft_decode /
        # verify / draft_prefill_<bucket> rows land in the same ledger
        self.catalog = catalog
        self.config = config
        self.k = int(config.k)
        self.draft_model = config.draft_model
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.b_slots = int(b_slots)
        self._mesh = mesh
        tp = int(mesh.shape.get("model", 1)) if mesh is not None else 1
        if tp > 1 and self.draft_model.config.kv_heads % tp != 0:
            raise ValueError(
                f"draft kv_heads={self.draft_model.config.kv_heads} not "
                f"divisible by the mesh's model axis ({tp}): the draft "
                "pool shards its head dim over 'model' exactly like the "
                "target's (paged_cache_specs)")
        # the draft's weights ride the same auto-TP shardings as the
        # target's (a layer-skip draft SHARES the target's leaves, so this
        # is a no-op for it; a standalone draft tree gets sharded here)
        self.draft_params = place_params(config.draft_params, mesh)
        # captured placement for live draft-weight refreshes (hybrid
        # rollout, docs/HYBRID.md): an update committed to these shardings
        # keeps identical avals, so draft/verify never recompile
        _leaves = jax.tree_util.tree_leaves(self.draft_params)
        self._draft_treedef = jax.tree_util.tree_structure(self.draft_params)
        self._draft_avals = [(tuple(getattr(x, "shape", ())),
                              str(getattr(x, "dtype", type(x).__name__)))
                             for x in _leaves]
        self._draft_shardings = (
            jax.tree_util.tree_map(lambda x: x.sharding, self.draft_params)
            if _leaves and all(hasattr(x, "sharding") for x in _leaves)
            else None)
        # the draft pool mirrors the target's storage dtype too: a
        # quantized engine quantizes BOTH pools, so the HBM headroom the
        # int8 target pool buys isn't spent back on a full-precision draft
        self.kv_dtype = kv_dtype if kv_dtype is None else str(kv_dtype)
        cache = self.draft_model.init_paged_cache(num_pages, page_size,
                                                  dtype=dtype,
                                                  kv_dtype=kv_dtype)
        specs = self.draft_model.paged_cache_specs(kv_dtype=kv_dtype)
        self._pool_keys = tuple(k for k in PAGED_POOL_KEYS if k in cache)
        self._pool_specs = tuple(specs[k] for k in self._pool_keys)
        self._kv_spec = specs["k"]
        tspecs = target_model.paged_cache_specs(kv_dtype=kv_dtype)
        self._target_pool_specs = tuple(tspecs[k] for k in PAGED_POOL_KEYS
                                        if k in tspecs)
        if mesh is not None:
            from jax.sharding import NamedSharding

            self.dpools = tuple(
                jax.device_put(cache[k], NamedSharding(mesh, specs[k]))
                for k in self._pool_keys)
        else:
            self.dpools = tuple(
                jax.device_put(cache[k], cache[k].sharding)
                for k in self._pool_keys)
        self.pool_bytes = pool_bytes(*self.dpools)
        # each pool read in the order its device stores it
        # (MeshExecutor.pool_order; the target's is the engine's)
        self._dpool_order = paged_pool_order(self.dpools[0])
        self._target_pool_order = target_pool_order
        self._draft_prog = self._build_draft()
        self._verify_prog = self._build_verify(target_model)
        self._draft_prefill_progs: Dict[int, Any] = {}
        # rolling stats: mean accepted length = emitted / verify slot-ticks
        self.verify_slot_ticks = 0
        self.emitted_tokens = 0
        self.drafted_tokens = 0

    # ----------------------------------------------------------- programs

    def _build_draft(self):
        draft_apply = self.draft_model.apply_paged

        def prog(dparams, dpools, page_table, pos, tok, active,
                 temp, top_k, top_p, seeds):
            # write `tok` (pending at `pos`) into the draft pool, propose
            # the token at pos+1 from the draft distribution under the
            # slot's own sampling lane (salted position key)
            cache = paged_pool_cache(dpools)
            logits, cache = draft_apply(dparams, tok[:, None], cache,
                                        page_table, pos, active[:, None],
                                        pool_order=self._dpool_order)
            lg = logits[:, -1, :]
            d_tok = sample_tokens(
                lg, temp, top_k, top_p,
                lambda: position_keys(seeds, pos + 1, salt=SALT_DRAFT))
            q = sampling_probs(lg, temp, top_k, top_p)
            return d_tok, q, paged_pool_tuple(cache)

        from .execution import pool_jit

        return pool_jit(prog, self._mesh, self._pool_specs, 2)

    def _build_draft_prefill(self, s_pad: int):
        draft_apply = self.draft_model.apply_paged

        def prog(dparams, dpools, pt_row, tokens, n_real, start):
            seq_mask = (jnp.arange(s_pad, dtype=jnp.int32)
                        < n_real)[None, :]
            cache = paged_pool_cache(dpools)
            _, cache = draft_apply(dparams, tokens, cache, pt_row,
                                   start[None], seq_mask,
                                   pool_order=self._dpool_order)
            return paged_pool_tuple(cache)

        from .execution import pool_jit

        return pool_jit(prog, self._mesh, self._pool_specs, 0)

    def _build_verify(self, target_model):
        target_apply = target_model.apply_paged
        k = self.k
        with_adapters = self.adapters is not None

        def prog(params, pools, page_table, lengths, last_tok,
                 active, d_toks, d_probs, temp, top_k, top_p, seeds,
                 adapters=None):
            B = lengths.shape[0]
            V = d_probs.shape[-1]
            # one target traversal writes [last_tok, d_1..d_k] at
            # positions L..L+k and yields the k+1 next-token distributions
            tokens = jnp.concatenate([last_tok[:, None], d_toks], axis=1)
            seq_mask = jnp.broadcast_to(active[:, None], (B, k + 1))
            cache = paged_pool_cache(pools)
            logits, cache = target_apply(
                params, tokens, cache, page_table, lengths, seq_mask,
                adapters=adapters if with_adapters else None,
                pool_order=self._target_pool_order)
            rep = lambda x: jnp.repeat(x, k + 1)                 # noqa: E731
            p = sampling_probs(logits.reshape(B * (k + 1), V), rep(temp),
                               rep(top_k), rep(top_p)).reshape(B, k + 1, V)
            # ---- rejection sampling, vectorized over the k proposals.
            # accept d_i (at position L+i) iff u_i * q_i(d_i) < p_i(d_i);
            # the first rejection truncates via the cumulative product
            p_at = jnp.take_along_axis(p[:, :k], d_toks[..., None],
                                       axis=-1)[..., 0]           # [B,k]
            q_at = jnp.take_along_axis(d_probs, d_toks[..., None],
                                       axis=-1)[..., 0]
            pos_i = lengths[:, None] + 1 + jnp.arange(k,
                                                      dtype=jnp.int32)[None]
            akeys = position_keys(jnp.repeat(seeds, k),
                                  pos_i.reshape(-1), salt=SALT_ACCEPT)
            u = jax.vmap(jax.random.uniform)(akeys).reshape(B, k)
            accept = u * q_at < p_at
            n_acc = jnp.cumprod(accept.astype(jnp.int32),
                                axis=1).sum(axis=1)               # [B] 0..k
            # ---- the correction token at the first rejection: a draw
            # from normalize(max(p-q, 0)) at index n_acc (greedy lanes:
            # p one-hot, so it reduces to the exact argmax).  When every
            # proposal survives we emit d_1..d_k and NO bonus token from
            # p_k: the bonus would sit at position L+k+1, one past the
            # last draft-pool write (the draft loop writes L..L+k-1), and
            # skipping over it would leave position L+k's draft K/V a
            # permanent gap — degrading q for the rest of the request and
            # breaking resume exactness.  Capping at k keeps the pending
            # token's draft write exactly one tick behind, always.
            p_n = jnp.take_along_axis(p, n_acc[:, None, None],
                                      axis=1)[:, 0]               # [B,V]
            q_n = jnp.take_along_axis(d_probs,
                                      jnp.minimum(n_acc, k - 1)[:, None,
                                                                None],
                                      axis=1)[:, 0]
            residual = jnp.maximum(p_n - q_n, 0.0)
            rs = residual.sum(-1, keepdims=True)
            corr = jnp.where(rs > 0, residual / jnp.maximum(rs, 1e-38),
                             p_n)
            fkeys = position_keys(seeds, lengths + n_acc + 1,
                                  salt=SALT_RESAMPLE)
            sampled = jax.vmap(jax.random.categorical)(
                fkeys, jnp.log(corr + 1e-38))
            final = jnp.where(temp <= 0.0, jnp.argmax(corr, axis=-1),
                              sampled).astype(jnp.int32)
            # the column at index n_acc is the correction; on full
            # acceptance (n_acc == k) it lands in the k+1-th column,
            # which the capped n_emit below never consumes
            emitted = jnp.concatenate(
                [d_toks, jnp.zeros((B, 1), jnp.int32)], axis=1)
            emitted = emitted.at[jnp.arange(B), n_acc].set(final)
            n_emit = jnp.minimum(n_acc + 1, k).astype(jnp.int32)
            return emitted, n_emit, paged_pool_tuple(cache)

        from .execution import pool_jit

        # the verify pass consumes and reproduces the TARGET pool: its
        # output pools pin to the target's canonical shardings, same as
        # the plain decode tick's
        return pool_jit(prog, self._mesh, self._target_pool_specs, 2)

    def program_inventory(self) -> Dict[str, Any]:
        return {"k": self.k, "draft_decode": 1, "verify": 1,
                "draft_prefill_buckets": sorted(self._draft_prefill_progs)}

    def update_params(self, draft_params) -> None:
        """Swap the LIVE draft weights (hybrid rollout, docs/HYBRID.md) —
        committed to the placement captured at build time so draft/verify
        stay cache hits.  The draft pool is NOT flushed here: stale draft
        K/V can only cost acceptance rate, never correctness (the verify
        pass reads the TARGET pool), and the owning engine's
        ``update_params`` already flushed every target-side page."""
        from .execution import place_params

        placed = place_params(draft_params, self._mesh)
        # same zero-recompile guard as MeshExecutor.update_params: a
        # structurally different draft tree would silently recompile
        # draft/prefill/verify on every subsequent tick
        treedef = jax.tree_util.tree_structure(placed)
        if treedef != self._draft_treedef:
            raise ValueError(
                "update_params: the new draft tree's structure differs "
                f"from the compiled one ({treedef} vs "
                f"{self._draft_treedef}) — draft/verify would recompile")
        for i, x in enumerate(jax.tree_util.tree_leaves(placed)):
            aval = (tuple(getattr(x, "shape", ())),
                    str(getattr(x, "dtype", type(x).__name__)))
            if aval != self._draft_avals[i]:
                raise ValueError(
                    f"update_params: draft leaf {i} has aval {aval}, "
                    f"compiled programs expect {self._draft_avals[i]} — "
                    "the swap must be shape/dtype-identical")
        if self._draft_shardings is not None:
            placed = jax.device_put(placed, self._draft_shardings)
        self.draft_params = placed

    # ----------------------------------------------------------- the tick

    def pool_alive(self) -> bool:
        dead = getattr(self.dpools[0], "is_deleted", None)
        return not (dead and self.dpools[0].is_deleted())

    def prefill(self, s_pad: int, pt_row, tokens, n_real: int,
                start: int) -> None:
        """Write the prompt tail's K/V into the draft pool (same bucket,
        page-table row and ``start`` as the target prefill that just ran —
        the draft emits nothing)."""
        prog = self._draft_prefill_progs.get(s_pad)
        if prog is None:
            prog = self._draft_prefill_progs[s_pad] = \
                self._build_draft_prefill(s_pad)
        args = (self.draft_params, self.dpools, pt_row,
                tokens, jnp.int32(n_real), jnp.int32(start))
        t0 = account(self.catalog, f"draft_prefill_{s_pad}", prog, args)
        self.dpools = prog(*args)
        if t0 is not None:
            finish_sample(self.catalog, f"draft_prefill_{s_pad}",
                          self.dpools[0], t0)

    def cow(self, cow_prog, src: int, dst: int) -> None:
        """Mirror a target-pool COW snapshot in the draft pool (same
        fixed-shape program; jit re-specializes once per pool aval at
        engine init, never at admission)."""
        self.dpools = cow_prog(self.dpools, jnp.int32(src), jnp.int32(dst))

    def tick(self, target_params, pools, page_table, lengths,
             last_tok, active, temp, top_k, top_p,
             seeds, adapters=None) -> Tuple[np.ndarray, np.ndarray, Any]:
        """One speculative decode tick: k draft invocations + one verify.
        Returns ``(emitted [B, k+1], n_emit [B], pools)`` — the caller
        consumes ``emitted[b, :n_emit[b]]`` per slot (truncated by its own
        budget/eos) and the updated TARGET pool tuple.  ``adapters`` is
        the per-slot factor pytree for the verify pass when the engine
        serves tenants (the draft loop never sees it)."""
        pt = jnp.asarray(page_table)
        ln = jnp.asarray(lengths)
        act = jnp.asarray(active)
        tj, kj, pj, sj = (jnp.asarray(temp), jnp.asarray(top_k),
                          jnp.asarray(top_p), jnp.asarray(seeds))
        tok = jnp.asarray(last_tok)
        d_toks, d_probs = [], []
        for i in range(self.k):
            dargs = (self.draft_params, self.dpools, pt,
                     ln + i, tok, act, tj, kj, pj, sj)
            t0 = account(self.catalog, "draft_decode", self._draft_prog,
                         dargs)
            tok, q, self.dpools = self._draft_prog(*dargs)
            if t0 is not None:
                finish_sample(self.catalog, "draft_decode", tok, t0)
            d_toks.append(tok)
            d_probs.append(q)
        vargs = (target_params, pools, pt, ln, jnp.asarray(last_tok),
                 act, jnp.stack(d_toks, axis=1), jnp.stack(d_probs, axis=1),
                 tj, kj, pj, sj)
        if self.adapters is not None:
            vargs += (adapters,)
        t0 = account(self.catalog, "verify", self._verify_prog, vargs)
        emitted, n_emit, pools = self._verify_prog(*vargs)
        if t0 is not None:
            finish_sample(self.catalog, "verify", emitted, t0)
        n_active = int(np.asarray(active).sum())
        self.verify_slot_ticks += n_active
        self.drafted_tokens += self.k * n_active
        return np.asarray(emitted), np.asarray(n_emit), pools

    def mean_accepted_len(self) -> float:
        """Tokens emitted per verify tick per slot (1..k; > 1 means the
        draft is paying for itself)."""
        if self.verify_slot_ticks == 0:
            return 0.0
        return self.emitted_tokens / self.verify_slot_ticks

    # ---------------------------------------------------------- adoption

    def compatible(self, other: Optional["SpeculativeDecoder"]) -> bool:
        return (other is not None
                and self.draft_model is other.draft_model
                and self.k == other.k
                and self.num_pages == other.num_pages
                and self.page_size == other.page_size
                and self.b_slots == other.b_slots
                and self.kv_dtype == other.kv_dtype)

    def adopt_programs(self, old: "SpeculativeDecoder") -> None:
        """Warm-restart path: carry the dead engine's compiled speculative
        programs (jax.jit caches on avals — the fresh pool has the same
        shape/dtype, so every adopted program is a cache hit)."""
        self._draft_prog = old._draft_prog
        self._verify_prog = old._verify_prog
        self._draft_prefill_progs.update(old._draft_prefill_progs)
