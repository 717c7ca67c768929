"""Model zoo + the engine's model contract adapter.

The engine's model contract is functional: ``loss_fn(params, batch, rng)``,
``init_fn(rng) -> params``, optional ``param_specs`` (TP/SP shardings).
``CausalLM`` packages the transformer family behind that contract — it plays
the role of the reference's model-wrapping (``DeepSpeedEngine(module=...)``,
engine.py:181) without inheriting from a module class.
"""
from __future__ import annotations

import os

import jax.numpy as jnp

from .transformer import (CONFIGS, KV_QUANT_DTYPES, PAGE_SIZE,
                          TransformerConfig, cache_specs,
                          cow_copy_pool, cross_entropy_loss, forward,
                          forward_cached, forward_paged, get_config, has_moe,
                          init_cache, init_paged_cache, init_params,
                          paged_cache_specs, paged_pool_cache,
                          paged_pool_tuple, param_specs)

__all__ = ["CausalLM", "TransformerConfig", "CONFIGS", "get_config", "forward",
           "forward_cached", "forward_paged", "init_cache", "init_paged_cache",
           "cache_specs", "paged_cache_specs", "init_params", "param_specs",
           "cross_entropy_loss", "PAGE_SIZE", "cow_copy_pool",
           "paged_pool_tuple", "paged_pool_cache", "KV_QUANT_DTYPES"]


class CausalLM:
    """Causal-LM adapter: batch = {'input_ids': [B,S]} (labels default to the
    next-token shift) or {'input_ids', 'labels'[, 'positions']}."""

    def __init__(self, config="tiny", attn_impl: str = "auto", **overrides):
        self.config = get_config(config, **overrides)
        self.attn_impl = attn_impl
        self.param_specs = param_specs(self.config)

    @classmethod
    def from_hf(cls, model_or_path, dtype=None, attn_impl: str = "auto",
                checkpoint=None, mesh=None, **overrides):
        """(model, params) from an HF checkpoint — a ``from_pretrained``
        directory, a live transformers module, or (config, state_dict)
        (module_inject policies; reference replace_module checkpoint load).

        Directory paths stream shard-by-shard onto ``mesh`` (never the whole
        model on host — reference inference/engine.py:449 sd_loader path);
        ``checkpoint`` overrides the weight source (e.g. a DeepSpeed
        checkpoint json with per-mp-rank shard files) while ``model_or_path``
        still supplies the config."""
        if checkpoint is not None or (
                isinstance(model_or_path, str) and os.path.isdir(model_or_path)):
            from ..module_inject.sharded_load import load_hf_checkpoint_sharded

            hf_config = None
            if checkpoint is not None:
                if isinstance(model_or_path, str):
                    import transformers

                    hf_config = transformers.AutoConfig.from_pretrained(
                        model_or_path)
                else:
                    # a live module (or anything carrying its HF config)
                    # supplies the config — the checkpoint json's directory
                    # need not hold a config.json
                    hf_config = getattr(model_or_path, "config", None)
            cfg, params = load_hf_checkpoint_sharded(
                checkpoint or model_or_path, dtype=dtype, mesh=mesh,
                hf_config=hf_config)
        else:
            from ..module_inject import load_hf_checkpoint

            cfg, params = load_hf_checkpoint(model_or_path, dtype=dtype)
        import dataclasses

        if dtype is not None:
            # compute dtype must track the param dtype or the decode scan
            # carries mix precisions
            overrides = {"dtype": dtype, **overrides}
        if overrides:
            cfg = dataclasses.replace(cfg, **overrides)
        model = cls.__new__(cls)
        model.config = cfg
        model.attn_impl = attn_impl
        model.param_specs = param_specs(cfg)
        return model, params

    def variant(self, **fields):
        """This model with config fields replaced (same attention
        implementation): how the engine builds the model behind a compiled
        step that differs in a static field — a random-LTD keep count, the
        checkpoint policy it resolved."""
        return type(self)(self.config, attn_impl=self.attn_impl, **fields)

    def init_fn(self, rng):
        from ..utils.init_on_device import on_device_init

        return on_device_init(lambda r: init_params(self.config, r))(rng)

    def frozen_spec(self):
        """Engine frozen-parameter contract (requires_grad=False parity):
        bool pytree (True = frozen) from ``config.frozen_keywords``, or
        None when nothing is frozen.  A keyword freezes leaves whose path
        contains it as an EXACT '/'-separated segment — 'embed' freezes
        'embed' but not 'pos_embed' (substring matching would silently
        sweep in the learned position/type embeddings)."""
        keywords = self.config.frozen_keywords
        if not keywords:
            return None
        if isinstance(keywords, str):   # tuple-vs-string slip: 'embed'
            keywords = (keywords,)      # must not iterate as characters
        import jax

        from ..utils.debug import path_str

        shapes = jax.eval_shape(lambda: init_params(self.config,
                                                    jax.random.PRNGKey(0)))

        def frozen(path, _):
            name = "/" + path_str(path) + "/"
            # exact-segment match; a '/'-qualified keyword matches the
            # contiguous segment run ('layers/wq' freezes layers/wq only)
            return any("/" + k.strip("/") + "/" in name for k in keywords)

        mask = jax.tree_util.tree_map_with_path(frozen, shapes)
        if not any(jax.tree_util.tree_leaves(mask)):
            raise ValueError(
                f"frozen_keywords {tuple(keywords)} matched no parameter "
                "path — keywords match exact '/'-separated segments "
                "('embed', 'wq') or qualified runs ('layers/wq'); paths "
                "look like 'layers/wq', 'embed', 'lm_head'")
        return mask

    def _split(self, batch):
        pld_theta = None
        if isinstance(batch, dict):
            tokens = batch["input_ids"]
            labels = batch.get("labels")
            positions = batch.get("positions")
            pld_theta = batch.get("pld_theta")
        else:
            tokens, labels, positions = batch, None, None
        if labels is None:
            labels = jnp.concatenate(
                [tokens[:, 1:], jnp.full_like(tokens[:, :1], -100)], axis=1)
        return tokens, labels, positions, pld_theta

    def apply_fn(self, params, tokens, positions=None, rng=None,
                 deterministic=True, return_aux=False, pld_theta=None):
        return forward(self.config, params, tokens, positions=positions, rng=rng,
                       attn_impl=self.attn_impl, deterministic=deterministic,
                       return_aux=return_aux, pld_theta=pld_theta)

    def _loss(self, params, batch, rng, deterministic):
        tokens, labels, positions, pld_theta = self._split(batch)
        logits, aux = self.apply_fn(params, tokens, positions=positions, rng=rng,
                                    deterministic=deterministic, return_aux=True,
                                    pld_theta=None if deterministic else pld_theta)
        loss = cross_entropy_loss(logits, labels)
        if has_moe(self.config):
            loss = loss + self.config.moe_aux_loss_coef * aux["moe_aux_loss"]
        return loss

    def loss_fn(self, params, batch, rng):
        return self._loss(params, batch, rng, deterministic=False)

    def pipeline_grad_fn(self):
        """Manual fwd+bwd through the 1F1B executor (the engine routes here
        when ``config.pipeline_schedule == "1f1b"``).  Same contract as the
        engine's ``grad_of_batch``: (grads of scale*mean loss, unscaled
        per-microbatch losses)."""
        from .transformer import pipeline_1f1b_loss_and_grads

        def fn(params, scaler, batch, rng):
            tokens, labels, positions, _ = self._split(batch)
            if positions is not None:
                raise NotImplementedError(
                    "1f1b pipeline requires default positions")
            return pipeline_1f1b_loss_and_grads(
                self.config, params, tokens, labels, rng,
                attn_impl=self.attn_impl, loss_scale=scaler.loss_scale)

        return fn

    def eval_fn(self, params, batch, rng):
        return self._loss(params, batch, rng, deterministic=True)

    # -- KV-cached decode contract (used by InferenceEngine.generate and the
    #    hybrid engine): static-shape cache + single-program prefill/decode --
    def init_cache(self, batch_size, max_len, dtype=None):
        return init_cache(self.config, batch_size, max_len, dtype)

    def cache_specs(self):
        return cache_specs(self.config)

    def apply_cached(self, params, tokens, cache, positions, input_mask):
        return forward_cached(self.config, params, tokens, cache, positions,
                              input_mask)

    # -- block-paged decode contract (used by ServingEngine): one physical
    #    page pool multiplexed across decode slots via per-slot page tables.
    #    kv_dtype="int8" narrows the pool's at-rest representation (per-page
    #    scale planes ride in the cache dict); None = compute dtype --
    def init_paged_cache(self, num_pages, page_size=PAGE_SIZE, dtype=None,
                         kv_dtype=None, window_pages=None, slots=1):
        return init_paged_cache(self.config, num_pages, page_size, dtype,
                                kv_dtype=kv_dtype, window_pages=window_pages,
                                slots=slots)

    def paged_cache_specs(self, kv_dtype=None):
        return paged_cache_specs(self.config, kv_dtype=kv_dtype)

    def apply_paged(self, params, tokens, cache, page_table, start, seq_mask,
                    adapters=None, expert_counts=False, pool_order=None,
                    state_slot=None, logits_at=None):
        return forward_paged(self.config, params, tokens, cache, page_table,
                             start, seq_mask, adapters=adapters,
                             expert_counts=expert_counts,
                             pool_order=pool_order, state_slot=state_slot,
                             logits_at=logits_at)

    @property
    def param_count(self) -> int:
        return self.config.param_count
