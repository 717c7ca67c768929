"""ZeRO-Infinity parameter offload — the layer-streamed executor.

Reference mechanisms: ``runtime/swap_tensor/partitioned_param_swapper.py:36``
(parameters on NVMe, swapped in around each submodule's forward/backward) and
``runtime/zero/stage3.py:502-536`` (offload_param wiring).  The reference
drives this with per-module autograd hooks; a TPU/XLA program cannot pause
mid-graph to page weights, so the executor IS the schedule:

  - bf16 params live in per-layer NVMe files (native aio engine).
  - The train step is a Python loop over layers; each layer is ONE jitted
    program (identical shapes -> one compiled executable reused L times).
  - Forward: prefetch layer i+1 from NVMe while layer i computes; keep only
    the [B,S,D] boundary activations on device.
  - Backward: reverse loop; ``jax.vjp`` of the layer block recomputes the
    layer's internals (per-layer remat for free) and yields (dparams, dx).
  - Gradients accumulate in host RAM (fp32); the native SIMD Adam streams
    fp32 masters + moments from NVMe leaf by leaf (same pipeline as
    SwappedAdamOptimizer) and writes updated bf16 params back to NVMe.

Peak device memory = ONE layer's params + boundary activations + one layer's
grads — a model whose weights exceed HBM trains on one chip.  Peak host
memory = fp32 grads (4 B/param); masters + moments (12 B/param) stay on NVMe.

Throughput follows the host<->device link and NVMe bandwidth by construction
(the reference has the same property; its sweet spot is the same: maximize
arithmetic intensity per byte streamed).
"""
from __future__ import annotations

import math
import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ...utils.logging import log_dist
from ...parallel.mesh import BATCH_AXES, constrain_spec
from ..swap_tensor.partitioned_optimizer_swapper import TensorSwapper
from ...ops.adam.cpu_adam import DeepSpeedCPUAdam


def _bf16():
    import ml_dtypes

    return ml_dtypes.bfloat16


def _idx_key(idx, shape) -> str:
    """Normalized hashable key for a device index (tuple of slices)."""
    return ",".join(f"{s.indices(d)[0]}:{s.indices(d)[1]}"
                    for s, d in zip(idx, shape))


def _norm_slices(idx, shape):
    return tuple(slice(*s.indices(d)[:2]) for s, d in zip(idx, shape))


def _leaf_shards(mesh, spec, shape, multi: bool):
    """Per-process shard descriptors for one leaf.

    Returns ``{idx_key: (suffix, slices)}`` plus ``{suffix: weight}`` where
    weight = 1 / (#processes holding that shard) — the grad-norm correction
    so globally-summed squared norms count each distinct shard once.
    Single-process collapses to ONE full-leaf shard with suffix '' (the
    legacy file layout, byte-identical behavior)."""
    if not multi:
        full = tuple(slice(0, d) for d in shape)
        return {_idx_key(full, shape): ("", full)}, {"": 1.0}
    sharding = NamedSharding(mesh, spec)
    holders: Dict[str, set] = {}
    for dev, idx in sharding.devices_indices_map(shape).items():
        holders.setdefault(_idx_key(idx, shape), set()).add(dev.process_index)
    local = {}
    for dev, idx in sharding.addressable_devices_indices_map(shape).items():
        local.setdefault(_idx_key(idx, shape), idx)
    info, weights = {}, {}
    for n, (key, idx) in enumerate(sorted(local.items())):
        sfx = f".s{n}"
        info[key] = (sfx, _norm_slices(idx, shape))
        weights[sfx] = 1.0 / len(holders[key])
    return info, weights


class InfinityParamEngine:
    """Owns NVMe-resident params + optimizer state and the layer-streamed
    train step (engine.train_batch delegates here when
    ``zero_optimization.offload_param.device == "nvme"``)."""

    STATES = ("master", "exp_avg", "exp_avg_sq")

    def __init__(self, config, model, lr_schedule, mesh):
        if model is None or not hasattr(model, "config") or \
                not hasattr(model.config, "num_layers"):
            raise NotImplementedError(
                "offload_param needs the native transformer family "
                "(deepspeed_tpu.models.CausalLM): the layer-streamed "
                "executor must know the model's layer structure")
        cfg = model.config
        from ...models.transformer import has_moe

        if isinstance(cfg.num_experts, (tuple, list)):
            raise NotImplementedError(
                "offload_param with a PR-MoE pyramid (per-layer expert "
                "counts) is not supported: the layer stream needs uniform "
                "layer files")
        self._moe = has_moe(cfg)
        if cfg.pipeline_stages > 1:
            raise NotImplementedError(
                "offload_param composes with pipeline_stages=1 (a pipelined "
                "stage already holds only its own layers)")
        if getattr(cfg, "random_ltd", False):
            raise NotImplementedError("offload_param + random_ltd: unsupported")
        if config.progressive_layer_drop.enabled:
            raise NotImplementedError(
                "offload_param + progressive_layer_drop: unsupported")
        if config.fp16.enabled:
            raise NotImplementedError(
                "offload_param pairs with bf16 (fp16 overflow handling would "
                "need host-side loss-scale bookkeeping)")
        if config.precision != jnp.bfloat16:
            raise ValueError("offload_param requires bf16 compute (fp32 "
                             "params have no compact streaming format)")
        if not getattr(cfg, "causal", True) or \
                getattr(cfg, "type_vocab_size", 0):
            raise NotImplementedError(
                "offload_param trains causal LMs (encoder models have no "
                "next-token loss for the layer-streamed executor)")
        # Multi-host: per-host shard files — each process stores ONLY the
        # unique addressable shards of every leaf (the reference swapper is
        # per-rank by the same construction,
        # partitioned_param_swapper.py:36), so host RAM/NVMe per process
        # scales down with the process count for sharded leaves
        self._multi = jax.process_count() > 1
        # bind the host side (SIMD Adam + aio threadpool) to one NUMA node
        # BEFORE the pools spawn (threads inherit the mask); DS_TPU_NUMA_NODE
        # overrides, 'off' disables
        from ...utils.numa import bind_for_offload

        bind_for_offload()
        opt_cfg = config.optimizer
        opt_type = (opt_cfg.type if opt_cfg else "adamw").lower()
        if opt_type not in ("adam", "adamw"):
            raise NotImplementedError(
                f"offload_param runs the native CPU Adam on the host; "
                f"optimizer {opt_type!r} is not supported")

        self.cfg = cfg
        self.model = model
        self.mesh = mesh
        self.config = config
        self.lr_schedule = lr_schedule
        self.gas = config.gradient_accumulation_steps
        self.clip = config.gradient_clipping
        self.attn_impl = getattr(model, "attn_impl", "auto")
        self.step_count = 0

        p = dict(opt_cfg.params) if opt_cfg else {}
        self.adam = DeepSpeedCPUAdam(
            lr=p.get("lr", 1e-3), betas=tuple(p.get("betas", (0.9, 0.999))),
            eps=p.get("eps", 1e-8), weight_decay=p.get("weight_decay", 0.0),
            adamw_mode=bool(p.get("adam_w_mode", opt_type == "adamw")))
        # moment STORE dtypes (same memory-lean knobs as the fused device
        # optimizer's mu_dtype/nu_dtype): bf16 halves the NVMe footprint of
        # m and/or v — 14 B/param (fp32 moments) -> 10 B/param with both,
        # the difference between a 7B store fitting a ~90 GB disk or not.
        # The host Adam always steps fp32; bf16 is the at-rest format.
        self._mu16 = str(p.get("mu_dtype", "")).lower() == "bfloat16"
        self._nu16 = str(p.get("nu_dtype", "")).lower() == "bfloat16"
        zc = config.zero_config.offload_param
        nvme_path = zc.nvme_path
        if self._multi:
            # shard files are process-local; a shared filesystem must not
            # collide across hosts
            nvme_path = os.path.join(nvme_path,
                                     f"proc{jax.process_index()}")
        self.swapper = TensorSwapper(
            nvme_path, aio_threads=max(config.aio.thread_count, 1))

        self._init_param_store(config.seed)
        self._build_programs()
        total = self.param_count
        opt_bytes = 4 + (2 if self._mu16 else 4) + (2 if self._nu16 else 4)
        log_dist(
            f"ZeRO-Infinity param offload: {total:,} params "
            f"({total * 2 / 1e9:.2f} GB bf16) + optimizer state "
            f"({total * opt_bytes / 1e9:.2f} GB, moments "
            f"{'bf16' if self._mu16 else 'fp32'}/"
            f"{'bf16' if self._nu16 else 'fp32'}) on NVMe at {zc.nvme_path}; "
            f"device holds 1/{cfg.num_layers} of the layer stack at a time",
            ranks=[0])

    # ------------------------------------------------------------------
    # Param store: init on HOST (never materialize the full model on device),
    # split into stem / per-layer / head leaves, file per leaf.
    # ------------------------------------------------------------------
    def _init_param_store(self, seed: int):
        from ...models.transformer import init_params, param_specs

        cpu = jax.local_devices(backend="cpu")[0]
        with jax.default_device(cpu):
            params = init_params(self.cfg, jax.random.PRNGKey(seed))
        params = jax.tree_util.tree_map(
            lambda x: np.asarray(x, np.float32), params)

        specs = param_specs(self.cfg)
        L = self.cfg.num_layers
        self.num_layers = L
        self.layer_keys: List[str] = sorted(params["layers"].keys())
        # per-layer leaf spec = stacked spec minus the leading L dim
        self._layer_specs = {
            k: P(*tuple(specs["layers"][k])[1:]) for k in self.layer_keys}
        self._layer_shapes = {
            k: params["layers"][k].shape[1:] for k in self.layer_keys}

        self.stem_keys = [k for k in ("embed", "pos_embed", "embed_norm_scale",
                                      "embed_norm_bias") if k in params]
        self.head_keys = [k for k in ("final_norm_scale", "final_norm_bias",
                                      "lm_head", "lm_head_bias") if k in params]
        # every top-level leaf must be claimed — a silently-dropped param
        # would train a different model than the config describes
        unclaimed = set(params) - set(self.stem_keys) - set(self.head_keys) \
            - {"layers"}
        if unclaimed:
            raise NotImplementedError(
                f"offload_param: unhandled top-level param leaves "
                f"{sorted(unclaimed)} — the layer-streamed executor does not "
                "know where they belong")
        self._flat_specs = {k: specs[k] for k in
                            self.stem_keys + self.head_keys}
        self._flat_shapes = {k: params[k].shape
                             for k in self.stem_keys + self.head_keys}

        self.param_count = sum(
            int(np.prod(l.shape))
            for l in jax.tree_util.tree_leaves(params))

        # per-leaf shard descriptors (single-process: one '' full shard)
        self._flat_shards: Dict[str, Dict] = {}
        self._shard_weight: Dict[str, float] = {}
        for k in self.stem_keys + self.head_keys:
            info, w = _leaf_shards(self.mesh, self._flat_specs[k],
                                   self._flat_shapes[k], self._multi)
            self._flat_shards[k] = info
            for sfx, wt in w.items():
                self._shard_weight[f"{k}{sfx}"] = wt
        self._layer_shards: Dict[str, Dict] = {}
        self._layer_shard_weight: Dict[str, float] = {}
        for k in self.layer_keys:
            info, w = _leaf_shards(self.mesh, self._layer_specs[k],
                                   self._layer_shapes[k], self._multi)
            self._layer_shards[k] = info
            self._layer_shard_weight[k] = w
        for i in range(L):
            for k in self.layer_keys:
                for sfx, wt in self._layer_shard_weight[k].items():
                    self._shard_weight[f"layers.{i}.{k}{sfx}"] = wt

        bf16 = _bf16()
        # write every SHARD: fp32 master + zero moments (store dtype) +
        # bf16 param
        def put(name, arr32, shards):
            for sfx, slices in shards.values():
                piece = np.ascontiguousarray(arr32[slices])
                self.swapper.write(f"{name}{sfx}.master", piece)
                z = np.zeros_like(piece)
                self.swapper.write(f"{name}{sfx}.exp_avg",
                                   z.astype(bf16) if self._mu16 else z)
                self.swapper.write(f"{name}{sfx}.exp_avg_sq",
                                   z.astype(bf16) if self._nu16 else z)
                self.swapper.write(f"{name}{sfx}.param", piece.astype(bf16))
                self._leaf_names.append(f"{name}{sfx}")

        self._leaf_names: List[str] = []
        for k in self.stem_keys + self.head_keys:
            put(k, params[k], self._flat_shards[k])
        for i in range(L):
            for k in self.layer_keys:
                put(f"layers.{i}.{k}",
                    np.ascontiguousarray(params["layers"][k][i]),
                    self._layer_shards[k])

        # stem + head are touched every microbatch (the reference's
        # persistence-threshold behavior): resident bf16 device copies
        self._stem_dev = {k: self._put_flat(k) for k in self.stem_keys}
        self._head_dev = {k: self._put_flat(k) for k in self.head_keys}

        # double-buffered pinned host buffers for the layer stream
        # (keyed per shard; single-process = one '' shard per leaf)
        def shard_shape(k, slices):
            return tuple(s.stop - s.start for s in slices)

        self._layer_bufs = [
            {(k, sfx): np.empty(shard_shape(k, slices), bf16)
             for k in self.layer_keys
             for sfx, slices in self._layer_shards[k].values()}
            for _ in range(2)]
        # host fp32 gradient accumulators (allocated lazily per window)
        self._host_grads: Optional[Dict[str, np.ndarray]] = None

    def _put_flat(self, key, arr=None):
        """Global stem/head array from the process-local shard files.
        ``arr`` (single-process fast path) skips the NVMe re-read."""
        sharding = NamedSharding(self.mesh, self._flat_specs[key])
        if not self._multi:
            if arr is None:
                arr = self.swapper.read(f"{key}.param")
            return jax.device_put(arr, sharding)
        shape = self._flat_shapes[key]
        info = self._flat_shards[key]
        cache: Dict[str, np.ndarray] = {}

        def cb(idx):
            sfx = info[_idx_key(idx, shape)][0]
            if sfx not in cache:
                cache[sfx] = self.swapper.read(f"{key}{sfx}.param")
            return cache[sfx]

        return jax.make_array_from_callback(shape, sharding, cb)

    def _put_layer(self, bufs):
        # .copy(): device_put from numpy can be zero-copy on the CPU backend,
        # and these double-buffered read buffers are refilled by the next
        # aio submit — the device array must own its memory
        if not self._multi:
            return {k: jax.device_put(
                bufs[(k, "")].copy(),
                NamedSharding(self.mesh, self._layer_specs[k]))
                for k in self.layer_keys}
        out = {}
        for k in self.layer_keys:
            shape = self._layer_shapes[k]
            info = self._layer_shards[k]
            sharding = NamedSharding(self.mesh, self._layer_specs[k])
            cache: Dict[str, np.ndarray] = {}   # one copy per unique shard
            # (make_array_from_callback calls the cb per DEVICE; partially
            # replicated local shards would otherwise copy N_local times)

            def cb(idx, _i=info, _s=shape, _k=k, _c=cache):
                sfx = _i[_idx_key(idx, _s)][0]
                if sfx not in _c:
                    _c[sfx] = bufs[(_k, sfx)].copy()
                return _c[sfx]

            out[k] = jax.make_array_from_callback(shape, sharding, cb)
        return out

    # ------------------------------------------------------------------
    # The five jitted programs (each compiled once; layer programs are
    # shape-identical across layers so XLA reuses one executable).
    # ------------------------------------------------------------------
    def _build_programs(self):
        from ...models.transformer import (_attend_full, _block, _embed,
                                           _head, cross_entropy_loss)

        cfg = self.cfg
        attn_impl = self.attn_impl
        if attn_impl == "auto":
            attn_impl = "xla"
        act_spec = P(BATCH_AXES, "seq", None)
        tied = cfg.tie_embeddings
        f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda g: g.astype(jnp.float32), t)

        def positions_of(tokens):
            B, S = tokens.shape
            return jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))

        def stem_body(stem, tokens):
            return constrain_spec(
                _embed(cfg, stem, tokens, positions_of(tokens)), act_spec)

        moe = self._moe
        # single source: the SAME value is jit-baked into layer_bwd's aux
        # cotangent and read by the loss reporting in _micro_fwd_bwd /
        # eval_batch — they must never disagree
        self._aux_coef = aux_coef = cfg.moe_aux_loss_coef

        def layer_body(lp, x, rng, deterministic=False):
            B, S, _ = x.shape
            pos = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
            y, aux = _block(cfg, lp, x, pos, rng,
                            _attend_full(cfg, pos, attn_impl),
                            deterministic)[:2]
            y = constrain_spec(y, act_spec)
            # MoE: the load-balancing aux is part of the loss, so it must be
            # a layer OUTPUT for the vjp to route router gradients
            return (y, aux) if moe else y

        def head_body(head, stem, x, labels):
            # a tied head reads the stem's embedding: its gradient lands there
            hp = {**head, "embed": stem["embed"]} if tied else head
            return cross_entropy_loss(_head(cfg, hp, x), labels)

        self._stem_fwd = jax.jit(stem_body)
        self._layer_fwd = jax.jit(layer_body)
        # eval variants: deterministic blocks + loss-only head (no vjp)
        self._layer_fwd_det = jax.jit(
            lambda lp, x, rng: layer_body(lp, x, rng, deterministic=True))
        self._head_fwd = jax.jit(head_body)

        def head_vjp(head, stem, x, labels):
            if tied:
                loss, (dhead, dstem, dx) = jax.value_and_grad(
                    head_body, argnums=(0, 1, 2))(head, stem, x, labels)
            else:
                loss, (dhead, dx) = jax.value_and_grad(
                    head_body, argnums=(0, 2))(head, stem, x, labels)
                dstem = {}
            return loss, f32(dhead), f32(dstem), dx

        self._head_vjp = jax.jit(head_vjp)

        if moe:
            def layer_bwd(lp, x, rng, dy):
                (y, aux), vjp = jax.vjp(
                    lambda l, xi: layer_body(l, xi, rng), lp, x)
                # d(ce + coef*sum_l aux_l)/d(layer l) — aux cotangent = coef
                dlp, dx = vjp((dy, jnp.asarray(aux_coef, aux.dtype)))
                return f32(dlp), dx
        else:
            def layer_bwd(lp, x, rng, dy):
                y, vjp = jax.vjp(lambda l, xi: layer_body(l, xi, rng), lp, x)
                dlp, dx = vjp(dy)
                return f32(dlp), dx

        self._layer_bwd = jax.jit(layer_bwd)

        def stem_bwd(stem, tokens, dx):
            _, vjp = jax.vjp(lambda s: stem_body(s, tokens), stem)
            (dstem,) = vjp(dx)
            return f32(dstem)

        self._stem_bwd = jax.jit(stem_bwd)

    # ------------------------------------------------------------------
    # Layer streaming
    # ------------------------------------------------------------------
    def _submit_layer(self, i: int, slot: int):
        bufs = self._layer_bufs[slot]
        return [self.swapper.submit_read(f"layers.{i}.{k}{sfx}.param",
                                         out=bufs[(k, sfx)])
                for k in self.layer_keys
                for sfx, _ in self._layer_shards[k].values()], slot

    def _collect_layer(self, pending):
        handles, slot = pending
        for h, _ in handles:
            self.swapper.wait(h)
        return self._put_layer(self._layer_bufs[slot])

    # ------------------------------------------------------------------
    # Train step
    # ------------------------------------------------------------------
    def _accum(self, name: str, g) -> None:
        if self._host_grads is None:
            self._host_grads = {}
        if self._multi:
            # pull only the process-local unique shards of the global grad
            if name.startswith("layers."):
                leaf_key = name.split(".", 2)[2]
                info = self._layer_shards[leaf_key]
            else:
                info = self._flat_shards[name]
            shape = g.shape
            seen = set()
            for sh in g.addressable_shards:
                key = _idx_key(sh.index, shape)
                sfx = info[key][0]
                if sfx in seen:
                    continue          # replicated across local devices
                seen.add(sfx)
                with jax.transfer_guard("allow"):
                    arr = np.asarray(sh.data, np.float32)
                self._accum_host(f"{name}{sfx}", arr)
            return
        with jax.transfer_guard("allow"):
            arr = np.asarray(g, np.float32)
        self._accum_host(name, arr)

    def _accum_host(self, key: str, arr: np.ndarray) -> None:
        buf = self._host_grads.get(key)
        if buf is None:
            # np.asarray of a jax.Array is a read-only zero-copy view; the
            # accumulator mutates in place, so it must own writable memory
            self._host_grads[key] = np.array(arr, np.float32, order="C")
        else:
            buf += arr

    def _stream_forward(self, tokens, keys, layer_fwd, keep: bool):
        """Prefetch-pipelined forward over all layers.  ``keep`` retains the
        boundary activations (training) — eval discards them.  Returns
        ``(x_final, xs_or_None, last_layer_params, moe_aux_sum)``."""
        x = self._stem_fwd(self._stem_dev, tokens)
        xs = [x] if keep else None
        pending = self._submit_layer(0, 0)
        lp = None
        aux_sum = jnp.float32(0.0)
        for i in range(self.num_layers):
            nxt = (self._submit_layer(i + 1, (i + 1) % 2)
                   if i + 1 < self.num_layers else None)
            lp = self._collect_layer(pending)
            out = layer_fwd(lp, x, keys[i])
            if self._moe:
                x, aux = out
                aux_sum = aux_sum + aux
            else:
                x = out
            if keep:
                xs.append(x)
            pending = nxt
        return x, xs, lp, aux_sum

    @staticmethod
    def _tokens_labels(batch):
        if isinstance(batch, dict):
            tokens = batch["input_ids"]
            labels = batch.get("labels")
        else:
            tokens, labels = batch, None
        if labels is None:
            labels = jnp.concatenate(
                [tokens[:, 1:], jnp.full_like(tokens[:, :1], -100)], axis=1)
        return tokens, labels

    def _to_global(self, arr):
        """Multi-host: every process feeds the same host batch; build the
        dp-sharded global array from it.  Arrays that are already jax global
        arrays (the engine's _shard_batch path) pass through — np.asarray on
        a non-addressable array would throw."""
        if not self._multi or isinstance(arr, jax.Array):
            return arr
        a = np.asarray(arr)
        sharding = NamedSharding(self.mesh,
                                 P(BATCH_AXES, *([None] * (a.ndim - 1))))
        return jax.make_array_from_callback(a.shape, sharding,
                                            lambda idx: a[idx])

    def _micro_fwd_bwd(self, tokens, labels, rng):
        L = self.num_layers
        tokens = self._to_global(tokens)
        labels = self._to_global(labels)
        keys = jax.random.split(rng, L)
        x, xs, last_lp, aux_sum = self._stream_forward(
            tokens, keys, self._layer_fwd, keep=True)

        loss, dhead, dstem_h, dx = self._head_vjp(
            self._head_dev, self._stem_dev, xs[L], labels)
        if self._moe:
            # reported loss matches the fused engine: ce + coef*sum(aux);
            # the aux GRADIENT flows via the layer vjp's aux cotangent
            loss = loss + self._aux_coef * aux_sum
        for k, g in dhead.items():
            self._accum(k, g)
        for k, g in dstem_h.items():
            self._accum(k, g)

        bwd_slot = 0

        def submit_rev(i):
            nonlocal bwd_slot
            s, bwd_slot = bwd_slot, bwd_slot ^ 1
            return self._submit_layer(i, s)

        pending = submit_rev(L - 2) if L > 1 else None
        for i in reversed(range(L)):
            if i == L - 1:
                lp = last_lp
            else:
                lp = self._collect_layer(pending)
                pending = None
            if i > 0 and pending is None:
                pending = submit_rev(i - 1)  # prefetch under layer i's bwd
            dlp, dx = self._layer_bwd(lp, xs[i], keys[i], dx)
            for k, g in dlp.items():
                self._accum(f"layers.{i}.{k}", g)
            xs[i + 1] = None  # free the boundary activation
            del lp

        dstem = self._stem_bwd(self._stem_dev, tokens, dx)
        for k, g in dstem.items():
            self._accum(k, g)
        return loss

    def eval_batch(self, batch) -> float:
        """Forward-only layer-streamed evaluation: deterministic blocks
        (dropout off), loss-only head (no vjp), no activations kept."""
        tokens, labels = self._tokens_labels(batch)
        tokens = self._to_global(tokens)
        labels = self._to_global(labels)
        keys = jax.random.split(jax.random.PRNGKey(self.config.seed),
                                self.num_layers)
        x, _, _, aux_sum = self._stream_forward(
            tokens, keys, self._layer_fwd_det, keep=False)
        loss = self._head_fwd(self._head_dev, self._stem_dev, x, labels)
        if self._moe:
            loss = loss + self._aux_coef * aux_sum
        with jax.transfer_guard("allow"):
            return float(np.asarray(loss))

    def train_batch(self, batch) -> Tuple[Any, Dict[str, Any]]:
        """batch: device tree with leading [gas] dim ({'input_ids', optional
        'labels'}).  Returns (mean_loss, metrics)."""
        if isinstance(batch, dict):
            if "positions" in batch:
                raise NotImplementedError(
                    "offload_param: custom positions not supported")
            tokens_all = batch["input_ids"]
            labels_all = batch.get("labels")
        else:
            tokens_all, labels_all = batch, None

        self._host_grads = None
        rng = jax.random.fold_in(jax.random.PRNGKey(self.config.seed),
                                 self.step_count)
        losses = []
        for g in range(self.gas):
            tokens = tokens_all[g]
            if labels_all is not None:
                labels = labels_all[g]
            else:
                _, labels = self._tokens_labels(tokens)
            losses.append(self._micro_fwd_bwd(
                tokens, labels, jax.random.fold_in(rng, g)))

        lr = float(self.lr_schedule(self.step_count)) \
            if callable(self.lr_schedule) else float(self.lr_schedule)
        grad_norm = self._apply_adam(lr)
        self.step_count += 1
        with jax.transfer_guard("allow"):
            mean_loss = float(np.mean([np.asarray(l) for l in losses]))
        metrics = {"loss": jnp.float32(mean_loss),
                   "grad_norm": jnp.float32(grad_norm),
                   "loss_scale": jnp.float32(1.0),
                   "step_applied": jnp.bool_(True)}
        return metrics["loss"], metrics

    # ------------------------------------------------------------------
    # Host Adam over NVMe-streamed state (same read/compute/writeback
    # pipeline as SwappedAdamOptimizer, fused with the bf16 param rewrite).
    # ------------------------------------------------------------------
    def _apply_adam(self, lr: float) -> float:
        grads = self._host_grads
        assert grads is not None, "train window produced no gradients"
        inv_gas = 1.0 / self.gas
        sq = 0.0
        for name, g in grads.items():
            g *= inv_gas
            # weight corrects for shards held by several processes (weight
            # 1/#holders; single-process weights are all 1.0) so the global
            # sum counts each distinct shard exactly once
            sq += self._shard_weight.get(name, 1.0) * float(np.vdot(g, g))
        if self._multi:
            # every process must clip with the SAME global norm
            from jax.experimental import multihost_utils

            sq = float(np.sum(multihost_utils.process_allgather(
                np.float64(sq))))
        gnorm = math.sqrt(sq)
        factor = 1.0
        if self.clip and self.clip > 0 and gnorm > self.clip:
            factor = self.clip / (gnorm + 1e-6)

        bf16 = _bf16()
        step = self.step_count + 1
        for name in self._leaf_names:
            g = grads[name]
            if factor != 1.0:
                g = g * factor
            master = self.swapper.read(f"{name}.master")
            m = self.swapper.read(f"{name}.exp_avg")
            v = self.swapper.read(f"{name}.exp_avg_sq")
            # the host Adam steps fp32; bf16 is only the at-rest format
            m32 = (np.ascontiguousarray(m, np.float32) if self._mu16 else m)
            v32 = (np.ascontiguousarray(v, np.float32) if self._nu16 else v)
            out16 = np.empty(master.size, np.uint16)
            self.adam.step_flat(master.reshape(-1),
                                np.ascontiguousarray(g.reshape(-1)),
                                m32.reshape(-1), v32.reshape(-1), step=step,
                                bf16_out=out16, lr=lr)
            self.swapper.write(f"{name}.master", master)
            self.swapper.write(f"{name}.exp_avg",
                               m32.astype(bf16) if self._mu16 else m32)
            self.swapper.write(f"{name}.exp_avg_sq",
                               v32.astype(bf16) if self._nu16 else v32)
            new16 = out16.view(bf16).reshape(master.shape)
            self.swapper.write(f"{name}.param", new16)
            if name in self._stem_dev:
                self._stem_dev[name] = self._put_flat(name, new16)
            elif name in self._head_dev:
                self._head_dev[name] = self._put_flat(name, new16)
        if self._multi:
            # shard-named leaves: rebuild the global stem/head arrays from
            # the updated shard files once, after all shards stepped
            for k in self.stem_keys:
                self._stem_dev[k] = self._put_flat(k)
            for k in self.head_keys:
                self._head_dev[k] = self._put_flat(k)
        self._host_grads = None
        return gnorm

    # ------------------------------------------------------------------
    # Checkpointing — streamed leaf-by-leaf so the full 12 B/param state is
    # never resident in host RAM (the invariant the whole module exists for).
    # ------------------------------------------------------------------
    def _read_leaf_state(self, name: str):
        return (self.swapper.read(f"{name}.master"),
                self.swapper.read(f"{name}.exp_avg"),
                self.swapper.read(f"{name}.exp_avg_sq"))

    def _write_leaf_state(self, name: str, master, m, v) -> None:
        master = np.ascontiguousarray(master, np.float32)
        bf16 = _bf16()
        self.swapper.write(f"{name}.master", master)
        # checkpoint files stay fp32; the STORE keeps its at-rest dtype
        self.swapper.write(f"{name}.exp_avg", np.ascontiguousarray(
            m, bf16 if self._mu16 else np.float32))
        self.swapper.write(f"{name}.exp_avg_sq", np.ascontiguousarray(
            v, bf16 if self._nu16 else np.float32))
        # the bf16 compute params derive from the restored masters
        new16 = master.astype(_bf16())
        self.swapper.write(f"{name}.param", new16)
        if name in self._stem_dev:
            self._stem_dev[name] = self._put_flat(name, new16)
        elif name in self._head_dev:
            self._head_dev[name] = self._put_flat(name, new16)

    def _ckpt_dir(self, base: str) -> str:
        """Multi-host shard state is process-local — one subdir per host."""
        return (os.path.join(base, f"proc{jax.process_index()}")
                if self._multi else base)

    def save_state_files(self, out_dir: str) -> None:
        from ..offload import save_offload_state_files

        save_offload_state_files(self._ckpt_dir(out_dir), self._leaf_names,
                                 self._read_leaf_state, self.step_count)

    def load_state_files(self, in_dir: str) -> None:
        from ..offload import load_offload_state_files

        shapes = {n: self.swapper._shapes[f"{n}.master"]
                  for n in self._leaf_names}
        self.step_count = load_offload_state_files(
            self._ckpt_dir(in_dir), self._leaf_names, self._write_leaf_state,
            expected_shapes=shapes)
        if self._multi:
            for k in self.stem_keys:
                self._stem_dev[k] = self._put_flat(k)
            for k in self.head_keys:
                self._head_dev[k] = self._put_flat(k)

    def read_masters(self) -> Dict[str, np.ndarray]:
        return {n: self.swapper.read(f"{n}.master")
                for n in self._leaf_names}
