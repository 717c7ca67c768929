"""InferenceEngine (reference ``deepspeed/inference/engine.py:89``).

First slice: tensor-parallel jitted forward with dtype conversion and
auto-sharded params (the auto-TP analogue — ``module_inject/auto_tp.py``
discovers linear layers to shard; here :func:`auto_tp_specs` shards every
matmul-shaped weight's largest free dim over the 'model' axis).  Generation
with a paged KV cache and Pallas-fused blocks lands with the kernel-injection
milestone (module_inject/), which plugs in through the same ``apply_fn``
contract.

The reference's CUDA-graph capture/replay (engine.py:532-560) has no TPU
analogue because jit AOT-compiles the whole forward — every call IS the
captured graph.
"""
from __future__ import annotations

import threading
import weakref
from collections import OrderedDict
from typing import Any, Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .config import DeepSpeedInferenceConfig
from ..parallel.mesh import MeshLayout, initialize_mesh
from ..utils.logging import logger, log_dist


def auto_tp_specs(params: Any, mesh) -> Any:
    """Auto-TP for a param pytree (reference module_inject/auto_tp.py): shard
    each >=2D weight's largest dim over 'model'; replicate the rest."""
    tp = mesh.shape["model"]

    def spec_for(x):
        shape = getattr(x, "shape", ())
        if len(shape) < 2 or tp == 1:
            return P()
        dim = int(np.argmax(shape))
        if shape[dim] % tp != 0:
            return P()
        entries = [None] * len(shape)
        entries[dim] = "model"
        return P(*entries)

    return jax.tree_util.tree_map(spec_for, params)


class InferenceEngine:
    # bound LRU of compiled generate programs: distinct (model, shape,
    # sampling) tuples each hold a full jitted program — unbounded growth is
    # a memory leak on long-lived engines serving many shapes
    GEN_CACHE_MAX = 32
    _warned_uncached = False   # one-time fallback warning (class-wide)

    def __init__(self, model: Any = None, config: Optional[DeepSpeedInferenceConfig] = None,
                 apply_fn: Optional[Callable] = None, params: Any = None, mesh=None):
        self._config = config or DeepSpeedInferenceConfig()
        self._model = model if hasattr(model, "apply_cached") else None
        self._gen_cache: OrderedDict = OrderedDict()
        # serving() hands self.params over and takes the placed tree back:
        # one builder at a time (a fleet's members restart on own threads)
        self._handover = threading.Lock()
        if model is not None:
            apply_fn = apply_fn or getattr(model, "apply_fn", None) or getattr(
                model, "apply", None)
            params = params if params is not None else getattr(model, "params", None)
        if apply_fn is None:
            raise ValueError("InferenceEngine needs apply_fn(params, *args) "
                             "(directly or via a model adapter)")
        self.apply_fn = apply_fn

        tp = self._config.tensor_parallel.tp_size if self._config.tensor_parallel.enabled else 1
        if mesh is None:
            mesh = initialize_mesh(MeshLayout.from_world(jax.device_count(), tp=tp,
                                                         ep=self._config.moe.ep_size))
        self.mesh = mesh

        # Weight-only quantization (reference ZeRO-Inference int8 path:
        # init_inference(dtype=torch.int8)): weights stored int8/int4 at
        # rest, dequantized inside the jitted programs at use
        self._quant = self._config.weights_quantized
        if self._quant:
            if tp != 1:
                raise NotImplementedError(
                    "quantized inference is single-shard (tp=1) for "
                    "now: blockwise scales do not carry TP specs")
            if params is None:
                raise ValueError(
                    "weight quantization (dtype int8 / quant.enabled) needs "
                    "a param tree — a bare apply_fn engine has no weights "
                    "to quantize")
        if params is not None:
            if self._quant:
                from .quantization import quantize_params

                bits = self._config.quant.num_bits
                cdtype = self._config.compute_jnp_dtype
                # per-leaf quantization: peak device memory stays at the
                # loaded tree + ONE leaf's quantized copy, not the full
                # tree twice.  No donation — the caller owns `params`.
                # (Quantize-during-stream for models whose compute-dtype
                # form exceeds HBM is future loader work.)
                # one-shot init-time cast, discarded after this load —
                # never in the serving/steady path
                qleaf = jax.jit(lambda x: quantize_params(   # dslint: disable=recompile-hazard
                    x, bits=bits, compute_dtype=cdtype))
                self.params = jax.tree_util.tree_map(qleaf, params)
            else:
                dtype = self._config.jnp_dtype
                specs = auto_tp_specs(params, mesh)
                shardings = jax.tree_util.tree_map(
                    lambda s: NamedSharding(mesh, s), specs,
                    is_leaf=lambda x: isinstance(x, P))
                # one-shot init-time cast+placement.  Only the leaves that
                # are not yet in the engine's dtype go through the cast (one
                # program for all of them); a leaf already in it is placed
                # where it lies, so a tree born in the serving dtype on the
                # device is never on it twice (a 10 GB model on a 16 GB chip)
                leaves, treedef = jax.tree_util.tree_flatten(params)
                placement = treedef.flatten_up_to(shardings)
                todo = [i for i, x in enumerate(leaves)
                        if hasattr(x, "dtype") and x.dtype != dtype
                        and jnp.issubdtype(x.dtype, jnp.floating)]
                if todo:
                    done = jax.jit(   # dslint: disable=recompile-hazard
                        lambda xs: [x.astype(dtype) for x in xs],
                        out_shardings=[placement[i] for i in todo])(
                            [leaves[i] for i in todo])
                    for i, x in zip(todo, done):
                        leaves[i] = x
                self.params = treedef.unflatten(
                    [jax.device_put(x, s) for x, s in zip(leaves, placement)])
        else:
            self.params = None
        if self._quant:
            from .quantization import dequantize_params

            inner_apply = self.apply_fn
            self.apply_fn = lambda p, *a, **k: inner_apply(
                dequantize_params(p), *a, **k)
            if self._model is not None and (
                    hasattr(self._model, "apply_cached")
                    or hasattr(self._model, "apply_paged")):
                # generate()'s decode programs call model.apply_cached —
                # shim it so the cache loop reads int8 weights every step.
                # The paged serving contract (apply_paged) gets the same
                # treatment: ServingEngine's prefill/decode programs then
                # dequantize at entry, so a quantized engine serves through
                # the ordinary paged path (init_paged_cache itself never
                # touches params — the pool stays compute-dtype).  Each shim
                # installs on its own hasattr: a model exposing only one of
                # the two contracts still gets that one dequantized.
                import copy

                shim = copy.copy(self._model)
                if hasattr(self._model, "apply_cached"):
                    inner_cached = self._model.apply_cached
                    shim.apply_cached = lambda p, *a, **k: inner_cached(
                        dequantize_params(p), *a, **k)
                if hasattr(self._model, "apply_paged"):
                    inner_paged = self._model.apply_paged
                    shim.apply_paged = lambda p, *a, **k: inner_paged(
                        dequantize_params(p), *a, **k)
                self._model = shim
        # the engine's ONE forward program: per-instance by design (one
        # inference engine per process; serving routes through the
        # MeshExecutor inventory, never this)
        self._forward = jax.jit(self.apply_fn)   # dslint: disable=recompile-hazard
        log_dist(f"inference engine ready: tp={tp} dtype={self._config.dtype}"
                 + (f" quant=int{self._config.quant.num_bits}"
                    if self._quant else ""), ranks=[0])

    @property
    def model(self):
        """The wrapped model adapter (reference InferenceEngine.module)."""
        return self._model

    def serving(self, **kwargs):
        """A continuous-batching :class:`~.serving.ServingEngine` sharing
        this engine's model and (cast/sharded) params, so serving numerics
        are identical to :meth:`generate`.  On a quantized engine the
        shimmed ``apply_paged`` dequantizes at program entry, so serving
        reads the same int8/int4 weights as quantized ``generate()`` and
        stays token-identical to it.  The ``dtype`` pin below governs only
        the pool's COMPUTE dtype; pass ``kv_dtype="int8"`` to additionally
        narrow the pool's at-rest storage (docs/SERVING.md "Quantized KV
        pages") — weight quantization and KV quantization are independent
        knobs that compose in one engine.  See docs/SERVING.md."""
        if self._model is None or not hasattr(self._model, "apply_paged"):
            raise ValueError(
                "serving() needs a model with the paged decode contract "
                "(apply_paged) — see models.CausalLM")
        from .serving import ServingEngine

        kwargs.setdefault("mesh", self.mesh)
        if self._quant and kwargs.get("dtype") is None:
            # the serving KV pool's COMPUTE dtype stays the compute dtype
            # regardless of weight quantization; pin it explicitly (also
            # over an explicit dtype=None) so the pool never allocates
            # pages in the weights' storage dtype.  An explicit
            # kv_dtype="int8" kwarg still narrows the at-rest storage on
            # top of this pin — the scale rows dequantize back into the
            # pinned compute dtype inside the gather
            kwargs["dtype"] = self._config.compute_jnp_dtype
        # The tree is handed over, not shared: the executor holds the
        # weights as its decode program reads them (a leaf a layer where
        # the forward walks the layers in Python, each leaf in the layout
        # the compiled tick asks for: docs/SERVING.md "Weight placement"),
        # what it replaces is freed as it is replaced, and the placed tree,
        # which every forward reads, is this engine's from here on.
        def hand_over():
            tree, self.params = self.params, None
            return tree

        # (the executor refuses what it cannot serve before it takes the
        # tree; a failure after that, out of memory under the pool, leaves
        # this engine without weights as it leaves the process without room)
        with self._handover:
            sv = ServingEngine(self._model, hand_over, **kwargs)
            self.params = sv.params
        return sv

    def supervised_serving(self, max_restarts: int = 5, **kwargs):
        """A :class:`~.serving_supervisor.ServingSupervisor` whose engine
        factory is :meth:`serving` with these kwargs: decode-tick faults
        warm-restart a fresh KV pool (compiled programs carried over) and
        replay queue + in-flight requests token-exactly.  See
        docs/SERVING.md "Failure handling"."""
        from .serving_supervisor import ServingSupervisor

        return ServingSupervisor(lambda: self.serving(**kwargs),
                                 max_restarts=max_restarts,
                                 monitor=kwargs.get("monitor"))

    def serving_fleet(self, n_engines: int = None, coord_dir: str = None,
                      store=None, router_id: str = "router0",
                      max_restarts: int = 5, lease_s: float = None,
                      miss_limit: int = None, max_fleet_queue: int = None,
                      fleet_monitor=None, metrics_port: int = None,
                      **kwargs):
        """A :class:`~.fleet.FleetRouter` over ``n_engines`` supervised
        serving engines (each a :meth:`supervised_serving` sharing this
        engine's model/params), leased on a coordination store (``store=``
        or a ``coord_dir`` for the file backend).  Engines register
        heartbeat leases + health advertisements; the router admits by
        least-loaded engine, sheds by fleet-wide queue depth
        (``max_fleet_queue``), fails requests over on lease lapse, and
        rolls restarts one engine at a time.  ``metrics_port=0`` gives
        every member its own ephemeral /metrics endpoint.

        ``n_engines`` / ``coord_dir`` / ``lease_s`` / ``miss_limit`` left
        unset fall back to the launcher's exported contract
        (``DS_TPU_FLEET_SIZE`` / ``_COORD_DIR`` / ``_LEASE`` /
        ``_MISS_LIMIT`` — `deepspeed-tpu --fleet N ...`), then to
        2 / 5.0s / 3.  An explicit argument always wins.  See
        docs/FLEET.md."""
        import os

        from ..elasticity.coordination import FileCoordinationStore
        from .fleet import FleetMember, FleetRouter

        env = os.environ
        if n_engines is None:
            n_engines = int(env.get("DS_TPU_FLEET_SIZE", 2))
        if lease_s is None:
            lease_s = float(env.get("DS_TPU_FLEET_LEASE", 5.0))
        if miss_limit is None:
            miss_limit = int(env.get("DS_TPU_FLEET_MISS_LIMIT", 3))
        if store is None:
            coord_dir = coord_dir or env.get("DS_TPU_FLEET_COORD_DIR")
            if not coord_dir:
                raise ValueError(
                    "serving_fleet needs store= or coord_dir= (the "
                    "coordination store engines lease on; the launcher's "
                    "--fleet flags export DS_TPU_FLEET_COORD_DIR)")
            store = FileCoordinationStore(coord_dir)
        members = [
            FleetMember(f"engine{i}",
                        self.supervised_serving(max_restarts=max_restarts,
                                                **kwargs),
                        store, lease_s=lease_s, metrics_port=metrics_port)
            for i in range(int(n_engines))]
        return FleetRouter(store, members, router_id=router_id,
                           lease_s=lease_s, miss_limit=miss_limit,
                           max_fleet_queue=max_fleet_queue,
                           monitor=fleet_monitor)

    def forward(self, *args, **kwargs):
        if self.params is not None:
            return self._forward(self.params, *args, **kwargs)
        return self._forward(*args, **kwargs)

    __call__ = forward

    # ------------------------------------------------------------------
    # Generation.  Reference: InferenceEngine._generate (engine.py:621) over
    # the KV-cache workspace (csrc/transformer/inference/inference_context.h).
    # TPU redesign: static-shape prefill + a lax.scan decode loop, so one
    # generate() call compiles exactly two programs (per prompt-length
    # bucket) instead of retracing a growing sequence every token.
    # ------------------------------------------------------------------

    @staticmethod
    def _bucket(n: int) -> int:
        """Prompt-length bucket (next power of two ≥ 16) to bound recompiles."""
        b = 16
        while b < n:
            b *= 2
        return b

    def _generate_program(self, model, B, S_pad, max_new, greedy,
                          top_k=0, top_p=1.0):
        cfg = model.config

        # KV-cache length rounded up to a 128 multiple: lane-aligned cache
        # tiles keep the decode einsum on clean XLA tilings (and the bucket
        # rounding below reuses the same granularity)
        T_cache = -(-(S_pad + max_new) // 128) * 128

        def prog(params, tokens, input_mask, positions, rng, eos_id, temperature):
            cache = model.init_cache(B, T_cache, dtype=cfg.dtype)
            logits, cache = model.apply_cached(params, tokens, cache, positions,
                                               input_mask)
            lengths = input_mask.sum(-1).astype(jnp.int32)           # [B]
            last = jnp.take_along_axis(
                logits, (lengths - 1)[:, None, None], axis=1)[:, 0]  # [B,V]

            def sample(lg, key):
                if greedy:
                    return jnp.argmax(lg, axis=-1).astype(jnp.int32)
                # the shared sampling subsystem (inference/sampling.py):
                # one full sort serves top-k and top-p, temperature <= 0
                # folds to argmax in-graph (never a division by zero), and
                # top_k >= vocab / top_k == 0 disable the k-filter — the
                # ISSUE 9 edge cases, fixed once for generate() and serving
                from .sampling import sample_tokens

                return sample_tokens(
                    lg, jnp.broadcast_to(temperature, (B,)),
                    jnp.full((B,), top_k, jnp.int32),
                    jnp.full((B,), top_p, jnp.float32),
                    jax.random.split(key, B))

            def step(carry, _):
                cache, lg, pos, done, key = carry
                key, sub = jax.random.split(key)
                tok = sample(lg, sub)
                # done rows repeat eos_id verbatim (never a clamped stand-in:
                # jnp.maximum(eos_id, 0) silently emitted token 0 for done
                # rows).  With eos_token_id=None the sentinel is -1, tokens
                # are >= 0, so `done` can never become True and the sentinel
                # is never emitted.
                tok = jnp.where(done, eos_id, tok)
                done = done | (tok == eos_id)
                lg2, cache = model.apply_cached(
                    params, tok[:, None], cache, pos[:, None], ~done[:, None])
                return (cache, lg2[:, 0], pos + 1, done, key), tok

            done0 = jnp.zeros((B,), jnp.bool_)
            (_, _, _, _, _), toks = jax.lax.scan(
                step, (cache, last, lengths, done0, rng), None, length=max_new)
            return toks.T  # [B, max_new]

        return jax.jit(prog, static_argnames=())

    def _generate_lanes_program(self, model, B, S_pad, max_new):
        """The per-row RNG-lane twin of :meth:`_generate_program`
        (``generate(sampling=...)``): temperature/top-k/top-p/seed are
        TRACED per-row vectors, greedy rows fold to argmax in-graph, and
        the key for the token at stream position ``p`` of row ``b`` is
        ``fold_in(PRNGKey(seed_b), p)`` — exactly the schedule the serving
        engine's per-slot lanes use, which is what makes serving output
        token-identical to this path under the same seed/params
        (docs/SERVING.md "Sampling").  One program per (B, S_pad, max_new)
        regardless of the parameter mix."""
        from .sampling import position_keys, sample_tokens

        cfg = model.config
        T_cache = -(-(S_pad + max_new) // 128) * 128

        def prog(params, tokens, input_mask, positions, eos_id,
                 temp, top_k, top_p, seeds):
            cache = model.init_cache(B, T_cache, dtype=cfg.dtype)
            logits, cache = model.apply_cached(params, tokens, cache,
                                               positions, input_mask)
            lengths = input_mask.sum(-1).astype(jnp.int32)           # [B]
            last = jnp.take_along_axis(
                logits, (lengths - 1)[:, None, None], axis=1)[:, 0]  # [B,V]

            def step(carry, _):
                cache, lg, pos, done = carry
                # `pos` is the stream position the sampled token will
                # occupy (starts at the prompt length) — the lane counter
                tok = sample_tokens(lg, temp, top_k, top_p,
                                    position_keys(seeds, pos))
                tok = jnp.where(done, eos_id, tok)
                done = done | (tok == eos_id)
                lg2, cache = model.apply_cached(
                    params, tok[:, None], cache, pos[:, None],
                    ~done[:, None])
                return (cache, lg2[:, 0], pos + 1, done), tok

            done0 = jnp.zeros((B,), jnp.bool_)
            (_, _, _, _), toks = jax.lax.scan(
                step, (cache, last, lengths, done0), None, length=max_new)
            return toks.T  # [B, max_new]

        return jax.jit(prog, static_argnames=())

    def generate(self, input_ids, max_new_tokens: int = 32, eos_token_id: Optional[int] = None,
                 greedy: bool = True, rng: Optional[jax.Array] = None, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0,
                 attention_mask=None, model=None, params=None,
                 sampling=None):
        """KV-cached autoregressive generation under jit.

        Prompts may be right-padded ragged rows (pass ``attention_mask``); pad
        slots are written to the cache but masked from attention.  Returns the
        original ids with ``max_new_tokens`` generated tokens appended (rows
        that hit ``eos_token_id`` repeat it).

        ``sampling`` — a :class:`~.sampling.SamplingParams` (or one per
        row) switches to the per-row RNG-lane path: temperature/top-k/
        top-p/seed become TRACED vectors (any mix shares one program) and
        keys are counter-based (``fold_in(PRNGKey(seed), position)``), so
        the output is token-identical to a :class:`~.serving.ServingEngine`
        request carrying the same params — the sampled parity contract
        (docs/SERVING.md "Sampling").  Mutually exclusive with the legacy
        ``greedy``/``rng``/``temperature``/``top_k``/``top_p`` knobs.
        """
        if (model is not None and model is not self._model
                and self._quant and params is None):
            raise NotImplementedError(
                "generate(model=...) on a quantized engine needs explicit "
                "params: self.params is a QuantizedWeight tree the override "
                "model's apply_cached cannot consume (the engine's own "
                "model is shimmed to dequantize)")
        model = model or self._model
        if sampling is not None:
            if rng is not None:
                raise ValueError(
                    "generate(sampling=...) uses counter-based lane keys "
                    "derived from SamplingParams.seed — rng= would be "
                    "silently ignored; pass one or the other")
            if not greedy or temperature != 1.0 or top_k or top_p < 1.0:
                raise ValueError(
                    "generate(sampling=...) is mutually exclusive with the "
                    "legacy greedy/temperature/top_k/top_p knobs — they "
                    "would be silently ignored; put them in SamplingParams")
            if model is None or not hasattr(model, "apply_cached"):
                raise NotImplementedError(
                    "generate(sampling=...) requires a KV-cache-capable "
                    "model (apply_cached); the full-recompute fallback "
                    "has no lane path")
            return self._generate_lanes(model, input_ids, max_new_tokens,
                                        eos_token_id, sampling,
                                        attention_mask, params)
        if model is None or not hasattr(model, "apply_cached"):
            if attention_mask is not None:
                raise NotImplementedError(
                    "attention_mask requires a KV-cache-capable model "
                    "(apply_cached); the full-recompute fallback would "
                    "silently attend to pad tokens")
            if top_k or top_p < 1.0:
                raise NotImplementedError(
                    "top_k/top_p require a KV-cache-capable model "
                    "(apply_cached); the fallback would silently sample the "
                    "full distribution")
            return self._generate_uncached(input_ids, max_new_tokens, eos_token_id,
                                           greedy, rng, temperature, params=params)
        ids, toks, mpad, pos, B, S_pad = self._pad_prompt(input_ids,
                                                          attention_mask)
        prog = self._cached_program(
            model, (B, S_pad, max_new_tokens, greedy, top_k, top_p),
            lambda: self._generate_program(model, B, S_pad, max_new_tokens,
                                           greedy, top_k=top_k, top_p=top_p))
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        eos = jnp.int32(-1 if eos_token_id is None else eos_token_id)
        new = prog(
            self.params if params is None else params,
            jnp.asarray(toks), jnp.asarray(mpad), jnp.asarray(pos),
            rng, eos, jnp.float32(temperature))
        return jnp.concatenate([jnp.asarray(ids), new], axis=1)

    @staticmethod
    def _pad_prompt(input_ids, attention_mask):
        """Shared generate() host prep: right-pad the (possibly ragged)
        prompt to its pow2 bucket and derive the cumulative positions
        (pads repeat the last real index)."""
        ids = np.asarray(input_ids)
        if ids.ndim == 1:
            ids = ids[None, :]
        B, S = ids.shape
        mask = (np.ones_like(ids, dtype=bool) if attention_mask is None
                else np.asarray(attention_mask, dtype=bool))
        S_pad = InferenceEngine._bucket(S)
        toks = np.zeros((B, S_pad), ids.dtype)
        toks[:, :S] = ids
        mpad = np.zeros((B, S_pad), bool)
        mpad[:, :S] = mask
        pos = np.maximum(np.cumsum(mpad, axis=1) - 1, 0).astype(np.int32)
        return ids, toks, mpad, pos, B, S_pad

    def _cached_program(self, model, key_tail, builder):
        """LRU-cached generate program lookup.  Model identity is held by
        weakref: id(model) can be REUSED after GC and would then serve a
        stale program compiled for a different model; a weakref compares
        by referent identity while alive and can never equal a ref to a
        new object once dead — stale entries are inert and age out of the
        LRU.  (Either way the cached program's closure pins the model
        while its entry lives, so an id in a live key can never be
        recycled; eviction releases the pin.)"""
        try:
            mkey: Any = weakref.ref(model)
            hash(mkey)   # a ref hashes via its referent — an unhashable
        except TypeError:          # or weakref-less adapter falls back:
            mkey = (id(model),)    # id is safe while the entry (and its
                                   # closure pin on the model) lives
        key = (mkey,) + tuple(key_tail)
        prog = self._gen_cache.get(key)
        if prog is None:
            prog = self._gen_cache[key] = builder()
            while len(self._gen_cache) > self.GEN_CACHE_MAX:
                self._gen_cache.popitem(last=False)
        else:
            self._gen_cache.move_to_end(key)
        return prog

    def _generate_lanes(self, model, input_ids, max_new_tokens,
                        eos_token_id, sampling, attention_mask, params):
        """Host side of ``generate(sampling=...)``: normalize the per-row
        :class:`~.sampling.SamplingParams`, pad/bucket the prompt exactly
        like the legacy path, and run the lane program (cached per
        (model, B, S_pad, max_new) — the params are traced, so every
        parameter mix is a cache hit)."""
        from .sampling import SamplingParams

        ids, toks, mpad, pos, B, S_pad = self._pad_prompt(input_ids,
                                                          attention_mask)
        lanes = ([sampling] * B if isinstance(sampling, SamplingParams)
                 else list(sampling))
        if len(lanes) != B:
            raise ValueError(
                f"sampling: got {len(lanes)} SamplingParams for a batch "
                f"of {B} rows (pass one, or one per row)")
        for sp in lanes:
            sp.validate()
        prog = self._cached_program(
            model, (B, S_pad, max_new_tokens, "lanes"),
            lambda: self._generate_lanes_program(model, B, S_pad,
                                                 max_new_tokens))
        eos = jnp.int32(-1 if eos_token_id is None else eos_token_id)
        new = prog(
            self.params if params is None else params,
            jnp.asarray(toks), jnp.asarray(mpad), jnp.asarray(pos), eos,
            jnp.asarray([sp.temperature for sp in lanes], jnp.float32),
            jnp.asarray([sp.top_k for sp in lanes], jnp.int32),
            jnp.asarray([sp.top_p for sp in lanes], jnp.float32),
            jnp.asarray([sp.seed for sp in lanes], jnp.uint32))
        return jnp.concatenate([jnp.asarray(ids), new], axis=1)

    def _generate_uncached(self, input_ids, max_new_tokens: int = 32,
                           eos_token_id: Optional[int] = None, greedy: bool = True,
                           rng: Optional[jax.Array] = None, temperature: float = 1.0,
                           params=None):
        """Full-recompute fallback for arbitrary logits-returning apply_fns
        (and the parity reference for the cached path in tests).

        The forward runs on sequences RIGHT-PADDED to the ``_bucket``
        granularity, reading logits at the last real position — a growing
        ``ids`` would otherwise retrace/recompile the jitted forward EVERY
        step; padded, the whole generation compiles O(log) programs.  The
        bucketing requires a causal ``apply_fn`` (tail pads must not affect
        earlier positions' logits); the first call probes this with one
        padded-vs-unpadded logit comparison and a non-causal apply_fn drops
        back to the exact (per-step retracing) path with a warning."""
        if not InferenceEngine._warned_uncached:
            InferenceEngine._warned_uncached = True
            logger.warning(
                "generate() is using the full-recompute fallback (O(S) "
                "forward per token).  Give the model a KV cache "
                "(apply_cached — see models.CausalLM) for the single-"
                "program cached decode path.")
        ids = np.asarray(input_ids)
        if ids.ndim == 1:
            ids = ids[None, :]
        B = ids.shape[0]
        rng = rng if rng is not None else jax.random.PRNGKey(0)

        def fwd(tokens):
            logits = (self._forward(params, tokens) if params is not None
                      else self.forward(tokens))
            return logits[0] if isinstance(logits, tuple) else logits

        for _ in range(max_new_tokens):
            n = ids.shape[1]
            if getattr(self, "_uncached_causal", None) is False:
                next_logits = fwd(ids)[:, n - 1, :]
            else:
                padded = np.zeros((B, self._bucket(n)), ids.dtype)
                padded[:, :n] = ids
                next_logits = fwd(padded)[:, n - 1, :]
                if (getattr(self, "_uncached_causal", None) is None
                        and padded.shape[1] > n):
                    # one-time causality probe: tail pads must not reach
                    # position n-1 or the bucketed outputs would silently
                    # diverge from the exact ones (prefix-LM apply_fns).
                    # Only a genuinely padded step can probe — at n ==
                    # bucket(n) the two forwards would compare identical
                    # arrays and latch a vacuous True verdict
                    exact = fwd(ids)[:, n - 1, :]
                    self._uncached_causal = bool(jnp.allclose(
                        exact, next_logits, rtol=1e-4, atol=1e-5))
                    if not self._uncached_causal:
                        logger.warning(
                            "uncached generate: apply_fn is not causal "
                            "(pad tokens leak into earlier logits) — "
                            "using the exact per-step path, which "
                            "retraces every new length")
                        next_logits = exact
            if greedy or temperature <= 0:
                # temperature <= 0 folds to greedy (dividing logits by it
                # would be a silent NaN factory) — same guard the shared
                # sampling subsystem applies in-graph
                nxt = jnp.argmax(next_logits, axis=-1)
            else:
                rng, sub = jax.random.split(rng)
                nxt = jax.random.categorical(sub, next_logits / temperature, axis=-1)
            ids = np.concatenate([ids, np.asarray(nxt)[:, None].astype(ids.dtype)],
                                 axis=1)
            if eos_token_id is not None and bool((nxt == eos_token_id).all()):
                break
        return jnp.asarray(ids)
