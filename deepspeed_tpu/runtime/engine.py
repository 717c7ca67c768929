"""DeepSpeedEngine — the core training engine (reference ``runtime/engine.py:181``).

TPU-native redesign.  The reference engine wraps ``torch.nn.Module`` and
orchestrates forward/backward/step imperatively with autograd hooks; here the
engine owns a functional ``TrainState`` pytree and ONE jitted ``train_step``
whose data layout (ZeRO stage, TP specs, precision) is declared through the
sharding planner (runtime/zero/planner.py).  What the reference does in
~3,400 lines of hook orchestration, GSPMD does in the compiler:

  - grad allreduce / reduce-scatter  <- grad sharding constraints
    (engine.allreduce_gradients :1830, stage_1_and_2.reduce_* :837)
  - ZeRO-3 param fetch/release       <- param sharding + XLA all-gather
    scheduling (partitioned_param_coordinator.fetch_sub_module :250)
  - all_gather_dp_groups after step  <- params recomputed from sharded
    masters under their own sharding (stage_1_and_2.py:1751)
  - loss scaling + overflow skip     <- lax.cond select inside the step
    (fp16/loss_scaler.py)

Model contract: ``loss_fn(params, batch, rng) -> loss | (loss, aux_dict)``.
Adapters for flax modules / HF models live in ``deepspeed_tpu.models``.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from .config import DeepSpeedConfig
from .lr_schedules import get_lr_scheduler, constant_lr
from .optimizer import create_optimizer
from .fp16.loss_scaler import (LossScaleState, dynamic_loss_scale_state,
                               static_loss_scale_state, no_loss_scale_state, scale_loss,
                               grads_finite, update_scale)
from .zero.planner import plan_sharding, named_shardings, constrain, ZeroShardingPlan
from .offload import (resolve_offload_mode, apply_streamed_placement,
                      HostSteppedOffload)
from .features import (wire_compression, wire_progressive_layer_drop,
                       wire_curriculum, wire_random_ltd, wire_flops_profiler)
from ..observability.trace import get_tracer, trace_span
from ..parallel.mesh import (dp_world_size, resolve_engine_mesh,
                             BATCH_AXES, ZERO_AXES)
from ..utils.logging import logger, log_dist
from ..utils.memory import is_out_of_memory, program_bytes
from ..utils.timer import SynchronizedWallClockTimer, ThroughputTimer
from .. import comm as dist


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    """Everything the jitted step reads and writes."""

    step: jnp.ndarray                 # i32 global step
    params: Any                       # compute-precision params (fwd/bwd view)
    master_params: Any                # fp32 masters (None when compute is fp32)
    opt_state: Any
    scaler: LossScaleState
    rng: jnp.ndarray
    comm_error: Any = None            # 1-bit error-feedback buffers (per-worker)


def make_grad_accumulator(grad_of_batch, gas: int, accum_dtype=None):
    """Shared microbatch scan: accumulate ``gas`` microbatch gradients.

    run(work, scaler, window, rng) -> (summed grads, losses [gas], new_rng).
    Single source of truth for the accumulation loop (fused train step,
    NVMe grad-only step, and the 1-bit compressed region all use it).
    ``accum_dtype`` is the accumulator precision (reference config
    ``data_types.grad_accum_dtype``, runtime/config.py:867): fp32 by default;
    bf16 halves the live gradient buffer at a small accumulation-rounding
    cost (most relevant for large ``gas``)."""
    accum_dtype = accum_dtype or jnp.float32

    def run(work, scaler, window, rng):
        def micro(carry, microbatch):
            acc, r = carry
            r, sub = jax.random.split(r)
            grads, loss = grad_of_batch(work, scaler, microbatch, sub)
            acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(accum_dtype), acc, grads)
            return (acc, r), loss

        zeros = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, accum_dtype), work)
        (grads, new_rng), losses = jax.lax.scan(micro, (zeros, rng), window,
                                                length=gas)
        return grads, losses, new_rng

    return run


def _xla_options() -> Dict[str, str]:
    """Extra XLA compiler options for the train/eval step jits.

    ``DS_TPU_XLA_OPTIONS="k=v,k2=v2"`` — escape hatch for per-job compiler
    tuning (e.g. scheduler or fusion knobs) without code changes; the
    reference exposes the same class of knob via op-builder build flags.
    """
    raw = os.environ.get("DS_TPU_XLA_OPTIONS", "").strip()
    opts = {}
    for item in raw.split(","):
        if "=" in item:
            k, v = item.split("=", 1)
            opts[k.strip()] = v.strip()
    return opts


def _jit_step(fn, options: Optional[Dict[str, str]] = None, **kw):
    """``jax.jit`` of a train / grad / eval step under the compiler options
    the engine resolved for its steps (``engine.step_compile_options``)."""
    if options:
        kw["compiler_options"] = options
    return jax.jit(fn, **kw)


def _cast_tree(tree, dtype):
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def _tree_select(pred, a, b):
    return jax.tree_util.tree_map(lambda x, y: jnp.where(pred, x, y), a, b)


# The share of a device's memory (``memory_stats()["bytes_limit"]``) that a
# fused step may plan for before the engine takes the next leaner checkpoint
# policy (:meth:`DeepSpeedEngine.resolve_remat`).  Chosen on a v5e (PERF.md
# §6, PR 38), not a knob.  Its evidence is one cell, Pythia-1.4b at depth 10
# and 8,192 tokens a step: the top rung's program, 97.1% of the limit, ran
# every step of every run and beat the next rung (81.2%) by 5.4%, so the
# constant sits just above the fullest program seen to run.  The compiler's
# plan is exact for the program; the 2% left is for what the plan cannot
# see (the allocator's fragmentation, the small arrays a loop keeps between
# steps).  Tooling with a budget of its own hands that in instead
# (``compile_train_step(batch, budget_bytes=...)``: the autotuner).
REMAT_HEADROOM = 0.98


def _shape_signature(tree) -> tuple:
    """What a jitted step is compiled for: the shape and dtype of each leaf."""
    return tuple((np.shape(x), str(getattr(x, "dtype", type(x).__name__)))
                 for x in jax.tree_util.tree_leaves(tree))


class DeepSpeedEngine:
    def __init__(self, model: Any = None, loss_fn: Optional[Callable] = None,
                 init_fn: Optional[Callable] = None, params: Any = None,
                 param_specs: Any = None, config: Any = None,
                 optimizer: Optional[optax.GradientTransformation] = None,
                 lr_scheduler: Optional[Callable] = None,
                 training_data: Any = None, mesh=None, dont_change_device: bool = False):
        # -- model contract resolution --
        self.model = model
        if model is not None and loss_fn is None:
            # `model` may be an adapter object exposing (init_fn, loss_fn, param_specs)
            loss_fn = getattr(model, "loss_fn", None)
            init_fn = init_fn or getattr(model, "init_fn", None)
            param_specs = param_specs if param_specs is not None else getattr(
                model, "param_specs", None)
            if hasattr(model, "eval_fn"):
                self._eval_fn = model.eval_fn
        if loss_fn is None:
            raise ValueError("engine needs loss_fn(params, batch, rng) (directly or via model)")
        if init_fn is None and params is None:
            raise ValueError("engine needs init_fn(rng)->params or explicit params")
        self.loss_fn = loss_fn
        self._eval_fn = getattr(self, "_eval_fn", None) or loss_fn

        # -- config / mesh --
        self.config = config if isinstance(config, DeepSpeedConfig) else DeepSpeedConfig(config)
        # MiCS/hpZ both factorize the data axis; hpZ's planner divergence
        # (masters/grads on the FULL group, compute view inner-only) is
        # applied below via zero_axes
        hpz = self.config.zero_config.zero_hpz_partition_size
        mesh = resolve_engine_mesh(self.config.mesh, self.config.zero_config,
                                   mesh)
        self.mesh = mesh
        self.dp_world = dp_world_size(mesh)
        self.config.resolve_batch_triad(self.dp_world)
        dist.configure(self.config.comms_logger)

        self.compute_dtype = self.config.precision
        self.use_master_weights = self.compute_dtype != jnp.float32
        self.fp16_enabled = self.config.fp16.enabled
        self.zero_stage = self.config.zero_optimization_stage
        self.gas = self.config.gradient_accumulation_steps
        self.micro_batch_size = self.config.train_micro_batch_size_per_gpu
        self.train_batch_size = self.config.train_batch_size

        pp = self.mesh.shape.get("pipe", 1)
        if pp > 1 and model is not None and hasattr(model, "config"):
            mcfg = model.config
            stages = getattr(mcfg, "pipeline_stages", 1)
            if stages != pp:
                raise ValueError(
                    f"mesh has pipe={pp} but model.config.pipeline_stages={stages}")
            # pipeline_microbatches is DECOUPLED from gas (VERDICT r2 item 3):
            # the per-step window (gas × micro_batch × dp samples) splits into
            # M model-level microbatches; gas remains the optimizer cadence
            micro = getattr(mcfg, "pipeline_microbatches", None) or stages
            window = self.gas * self.micro_batch_size * self.dp_world
            if window % micro:
                raise ValueError(
                    f"pipeline microbatches ({micro}) must divide the "
                    f"per-step sample window gas*micro_batch*dp={window}")

        if self.config.activation_checkpointing.partition_activations:
            # satisfied structurally: saved remat residuals carry the model's
            # sharding constraints, so GSPMD already partitions them over the
            # model/seq axes (the Megatron partition_activations behavior)
            log_dist("activation_checkpointing.partition_activations: saved "
                     "residuals follow the activation shardings (structural "
                     "under GSPMD)", ranks=[0])

        # -- compression (QAT / pruning transform on the compute tree) --
        wire_compression(self, model)

        # -- lr schedule --
        if lr_scheduler is not None:
            self.lr_schedule = lr_scheduler
        elif self.config.scheduler is not None:
            self.lr_schedule = get_lr_scheduler(self.config.scheduler.type,
                                                self.config.scheduler.params)
        else:
            lr = (self.config.optimizer.params.get("lr", 1e-3)
                  if self.config.optimizer else 1e-3)
            self.lr_schedule = constant_lr(lr)

        # -- frozen parameters (reference requires_grad=False semantics:
        #    excluded from updates, grad norm and clipping; still in params
        #    + checkpoints).  The functional analogue of torch's per-tensor
        #    flag: the model exposes ``frozen_spec() -> pytree of bool``
        #    (True = frozen) matching its param tree.  LoRA
        #    (runtime/lora.py) remains the memory-optimal freezing route —
        #    this path keeps the full tree in the optimizer for API parity.
        frozen_spec = getattr(model, "frozen_spec", None)
        self._frozen_mask = frozen_spec() if callable(frozen_spec) else frozen_spec
        if self._frozen_mask is not None and not any(
                jax.tree_util.tree_leaves(self._frozen_mask)):
            self._frozen_mask = None    # nothing frozen: skip the masking

        # -- optimizer --
        self._compression = None
        if optimizer is not None:
            if self._frozen_mask is not None:
                # same contract as engine-built chains: whatever the client
                # chain emits (including weight decay), frozen leaves get a
                # zero update; grads are additionally zeroed in apply_update
                from .optimizer import zero_frozen_updates
                optimizer = optax.chain(
                    optimizer, zero_frozen_updates(self._frozen_mask))
                log_dist("client optimizer wrapped with frozen-parameter "
                         "masking (model.frozen_spec)", ranks=[0])
            self.optimizer = optimizer
        else:
            opt_cfg = self.config.optimizer
            opt_type = opt_cfg.type if opt_cfg else "adamw"
            opt_params = dict(opt_cfg.params) if opt_cfg else {}
            self.optimizer = create_optimizer(opt_type, opt_params, self.lr_schedule,
                                              self.config.gradient_clipping,
                                              frozen_mask=self._frozen_mask)
            norm_type = opt_type.lower().replace("_", "")
            if norm_type in ("onebitadam", "onebitlamb", "zerooneadam"):
                for ax in ("model", "seq", "pipe", "expert"):
                    if self.mesh.shape.get(ax, 1) > 1:
                        raise ValueError(
                            f"1-bit optimizers need a pure-DP mesh ({ax} "
                            f"axis has size {self.mesh.shape[ax]})")
            if norm_type == "zerooneadam":
                # 0/1 Adam (runtime/comm/zero_one.py): variance freeze +
                # local-step intervals — a DISTINCT algorithm from the
                # EF-sign 1-bit path (reference fp16/onebit/zoadam.py)
                if self._frozen_mask is not None:
                    raise NotImplementedError(
                        "model.frozen_spec does not compose with ZeroOneAdam "
                        "(it owns its whole optimizer state outside the "
                        "masked optax chain)")
                if self.zero_stage != 0:
                    raise ValueError(
                        "ZeroOneAdam composes with ZeRO stage 0 only (the "
                        "in-region update reads replicated masters; the "
                        "reference tutorial lists the same ZeRO "
                        "incompatibility)")
                if self.fp16_enabled:
                    raise NotImplementedError(
                        "ZeroOneAdam + fp16 loss scaling: the local-step "
                        "phase has no per-worker overflow protocol")
                if self.config.gradient_clipping:
                    raise NotImplementedError(
                        "ZeroOneAdam supports max_grad_norm=0 only "
                        "(reference zoadam.py has the same default; clipping "
                        "a locally-drifted update is undefined)")
                if self.config.zero_config.offload_optimizer is not None:
                    raise NotImplementedError(
                        "ZeroOneAdam + optimizer offload: unsupported")
                if self._compression_transform is not None:
                    raise NotImplementedError(
                        "ZeroOneAdam + compression_training: the in-region "
                        "update differentiates the raw masters and would "
                        "silently skip the QAT/pruning transform")
                self._compression = {"algo": "zo", "hyper": dict(opt_params)}
            elif norm_type in ("onebitadam", "onebitlamb"):
                # 1-bit path: error-feedback sign-compressed grad exchange
                # after freeze_step warmup (reference fp16/onebit/adam.py:308)
                self._compression = {
                    "algo": "ef",
                    "freeze_step": int(opt_params.get("freeze_step", 100))}
                if self.zero_stage > 1:
                    raise ValueError(
                        "1-bit optimizers compose with ZeRO stage <= 1 only "
                        "(stages 2/3 shard gradients; the reference has the "
                        "same restriction)")

        # -- ZeRO-Infinity parameter offload: params live on NVMe and a
        #    layer-streamed executor (runtime/zero/infinity.py) replaces the
        #    fused jitted step entirely --
        self._param_offload = None
        zpo = self.config.zero_config.offload_param
        po_dev = getattr(zpo.device, "value", zpo.device) if zpo else "none"
        if po_dev == "nvme":
            from .zero.infinity import InfinityParamEngine

            if self._compression_transform is not None:
                raise NotImplementedError(
                    "offload_param + compression_training: unsupported")
            if self._compression is not None:
                raise NotImplementedError(
                    "offload_param + 1-bit optimizers: unsupported")
            if self.config.data_efficiency.data_routing.random_ltd.enabled:
                raise NotImplementedError(
                    "offload_param + random_ltd: the layer-streamed executor "
                    "builds its programs from the base model config")
            if self.config.flops_profiler.enabled:
                raise NotImplementedError(
                    "offload_param + flops_profiler: the profiler hooks the "
                    "fused jitted step, which this path replaces")
            zoo = self.config.zero_config.offload_optimizer
            if zoo is not None and \
                    getattr(zoo.device, "value", zoo.device) != "none":
                raise NotImplementedError(
                    "offload_param already places the optimizer state on its "
                    "own NVMe path (masters + moments live beside the "
                    "params); a simultaneous offload_optimizer config would "
                    "be silently ignored — remove it")
            if self._frozen_mask is not None:
                raise NotImplementedError(
                    "model.frozen_spec does not compose with offload_param "
                    "(the layer-streamed host Adam steps every shard); use "
                    "the LoRA path (runtime/lora.py) to train adapters "
                    "against NVMe-resident frozen weights")
            self._param_offload = InfinityParamEngine(
                self.config, model, self.lr_schedule, mesh)
            self._offload = None
            self.offload_active = False
            self._offload_dev_shardings = None
            self._train_out_shardings = None
            self._compute_cast = None
            self.plan = None
            self.state = None
            self.param_count = self._param_offload.param_count
        else:
            self._init_device_state(init_fn, params, param_specs, mesh, hpz)

        # -- bookkeeping --
        self.global_steps = 0
        self.skipped_steps = 0
        self.micro_steps = 0
        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(batch_size=self.train_batch_size,
                                          steps_per_output=self.config.steps_per_print)
        self._compiled_train_step = None
        self.remat_resolution: Optional[Dict[str, Any]] = None
        self._remat_span_attrs: Optional[Dict[str, Any]] = None
        # batch shapes -> (the fused step resolved for them, its record)
        self._remat_steps: Dict[tuple, Tuple[Any, Optional[Dict]]] = {}
        self._remat_signature: Optional[tuple] = None
        self._compiled_grad_step = None
        self._compiled_eval_step = None
        self._compiled_micro_grad = None
        self._compiled_apply_step = None
        self._accum_grads = None
        self._accum_count = 0
        self._window_losses = []
        self._last_grad_norm: Optional[float] = None
        self._data_iterator = None
        # -- optional training features (runtime/features.py owns config
        #    resolution + validation for each; BEFORE the dataloader so an
        #    in-loop curriculum can drive the sampler) --
        wire_progressive_layer_drop(self)
        wire_curriculum(self)
        wire_random_ltd(self, self.model)
        wire_flops_profiler(self)
        # the checkpoint policy is the engine's to resolve where the model's
        # config names none, the loss is the model's own (the resolver builds
        # each rung's step from a model variant's ``loss_fn``: a loss the
        # caller handed in is not its to swap) and the step is the fused one
        # at a fixed token count: a step that grows (random-LTD's keep count,
        # a sequence-length curriculum), the 1F1B executor's stash and the
        # offload executors keep "nothing_saveable"
        mcfg = getattr(self.model, "config", None)
        self._remat_auto = False
        if (getattr(mcfg, "remat", False) and hasattr(self.model, "variant")
                and getattr(self.loss_fn, "__self__", None) is self.model
                and self._random_ltd is None and not self._curriculum_seqlen
                and self._offload is None
                and self._param_offload is None
                and getattr(mcfg, "pipeline_schedule", "gpipe") != "1f1b"):
            from ..models.transformer import REMAT_AUTO

            self._remat_auto = mcfg.remat_policy == REMAT_AUTO
        if self._remat_auto:
            log_dist("activation checkpointing: the model names no "
                     "remat_policy; the engine resolves one from each "
                     "compiled step's memory", ranks=[0])
        # per-program device-time accounting (docs/OBSERVABILITY.md
        # "Per-program accounting"): the fused train step registers its
        # lowered FLOPs on first run; every step counts an invocation and
        # the wall clock between step completions feeds the live
        # train/tflops_est + train/mfu_est gauges (steady-state async
        # dispatch means inter-step wall ~= device step time)
        from ..observability.program_stats import ProgramCatalog

        self.program_catalog = ProgramCatalog()
        self._step_flops: Optional[float] = None
        self._step_wall_t: Optional[float] = None
        self._step_wall_s: Optional[float] = None   # EMA of inter-step wall
        self.training_dataloader = self._build_dataloader(training_data)
        self.monitor = self._build_monitor()
        # opt-in /metrics scrape endpoint (DS_TPU_METRICS_PORT): no-op
        # without the env var, so engine init never binds a socket unasked
        from ..observability.export import maybe_start_metrics_server

        maybe_start_metrics_server(self.monitor)
        # windowed device-trace capture, env-armed (DS_TPU_DEVICE_TRACE):
        # train_batch counts the window down one unit per step
        from ..observability.device_profiler import maybe_capture_from_env

        maybe_capture_from_env()
        self._watchdog = self._build_watchdog()
        log_dist(
            f"engine ready: params={self.param_count:,} zero_stage={self.zero_stage} "
            f"dtype={self.compute_dtype.__name__} mesh={dict(mesh.shape)} "
            f"batch={self.train_batch_size} (micro={self.micro_batch_size} gas={self.gas} "
            f"dp={self.dp_world}) step_compile_options="
            f"{self.step_compile_options or 'none'}", ranks=[0])

    @property
    def step_compile_options(self) -> Dict[str, str]:
        """The compiler options the train / grad / eval steps are compiled
        with (read-only).  A rule of the plan and the accelerator, not a
        switch: where the ZeRO plan moves parameters between devices as the
        step runs (:attr:`ZeroShardingPlan.gathers_params`: stage 3 over
        ZeRO axes of more than one device) the accelerator's
        ``collective_overlap_options()``, which ask its compiler to run a
        layer's collectives behind the layers beside it (empty on every
        accelerator but the TPU); nothing anywhere else, so a stage <= 2
        step, a one-device step and a CPU step compile as they always did.
        A key of ``DS_TPU_XLA_OPTIONS`` wins over the rule's."""
        opts: Dict[str, str] = {}
        if self.plan is not None and self.plan.gathers_params:
            from ..accelerator import get_accelerator

            opts.update(get_accelerator().collective_overlap_options())
        opts.update(_xla_options())
        return opts

    def _init_device_state(self, init_fn, params, param_specs, mesh, hpz):
        """Build the device-resident TrainState: sharded init, ZeRO planning,
        optimizer state, loss scaler, offload placement."""
        # -- sharded initialization (the zero.Init analogue: params are BORN
        #    sharded; nothing ever materializes replicated, reference
        #    partition_parameters.py:681) --
        seed_rng = jax.random.PRNGKey(self.config.seed)
        if params is not None:
            shapes = jax.eval_shape(lambda: params)
            init_thunk = lambda rng: params  # noqa: E731
        else:
            shapes = jax.eval_shape(init_fn, seed_rng)
            init_thunk = init_fn
        if self._frozen_mask is not None:
            mask_td = jax.tree_util.tree_structure(self._frozen_mask)
            shapes_td = jax.tree_util.tree_structure(shapes)
            if mask_td != shapes_td:
                raise ValueError(
                    "model.frozen_spec() structure does not match the param "
                    f"tree: mask {mask_td} vs params {shapes_td}")
            n_frozen = sum(
                int(np.prod(s.shape)) for s, m in zip(
                    jax.tree_util.tree_leaves(shapes),
                    jax.tree_util.tree_leaves(self._frozen_mask)) if m)
            log_dist(f"frozen parameters: {n_frozen:,} excluded from "
                     "updates/grad-norm (model.frozen_spec)", ranks=[0])
        hier = self.config.zero_config.zero_hierarchical_dp_size
        self.plan: ZeroShardingPlan = plan_sharding(
            shapes, self.zero_stage, mesh, tp_specs=param_specs,
            persistence_threshold=self.config.zero_config.stage3_param_persistence_threshold,
            # hpZ: masters/opt/grads on the full group, compute view
            # inner-only — with 'data_outer' MINOR in the dim tuple, so that
            # stripping the outer axis yields the CONTIGUOUS inner shard
            # (outer-major would make the secondary copy a permutation of
            # the true rows; caught by the composition loss-parity test).
            # hierarchical qgZ: EVERYTHING on the full group, outer-MAJOR —
            # the 2-hop reduce lands outer-major by construction.
            zero_axes=(ZERO_AXES + ("data_outer",) if hpz > 1
                       else BATCH_AXES if hier > 1 else ZERO_AXES),
            param_zero_axes=(ZERO_AXES if hpz > 1 else None))
        self._param_shardings = named_shardings(mesh, self.plan.param_specs)
        self._master_shardings = named_shardings(mesh, self.plan.master_specs)
        self._grad_shardings = named_shardings(mesh, self.plan.grad_specs)

        # -- ZeRO++ (qwZ/qgZ): make stage-3's param-gather / grad-reduce
        #    collectives explicit with an int8 wire format --
        zcfg = self.config.zero_config
        if zcfg.zero_quantized_weights or zcfg.zero_quantized_gradients:
            if not self.use_master_weights:
                raise ValueError("ZeRO++ quantized collectives require bf16 or "
                                 "fp16 compute (fp32 has no cast step to hook)")
            from .zero.zeropp import make_zeropp_cast

            # qgZ runs int8 (not the reference's int4) by default: one ICI hop
            # on TPU vs the reference's NVLink+IB two-hop makes bandwidth
            # cheaper and convergence the scarcer resource; int4 remains
            # available in ops/quantizer for the hierarchical path.
            #
            # Region-axes selection = the ZeRO++ composition switch (see
            # make_zeropp_cast): hpZ covers only the outer hop; the
            # hierarchical knob covers both hops with a 2-hop reduce.
            if hpz > 1:
                region_axes, hier_outer = ("data_outer",), None
            elif hier > 1:
                region_axes, hier_outer = BATCH_AXES, "data_outer"
            else:
                region_axes, hier_outer = ZERO_AXES, None
            self._compute_cast = make_zeropp_cast(
                self.plan.master_specs, self.plan.param_specs, mesh,
                self.compute_dtype, region_axes,
                weight_bits=8 if zcfg.zero_quantized_weights else None,
                grad_bits=8 if zcfg.zero_quantized_gradients else None,
                hierarchical_outer=hier_outer)
            if self._compute_cast.num_quantized_leaves == 0:
                logger.warning(
                    "ZeRO++ enabled but no parameter is ZeRO-sharded (all "
                    "below stage3_param_persistence_threshold or indivisible) "
                    "— quantized collectives will not engage")
        else:
            self._compute_cast = None

        with jax.transfer_guard("allow"):
            master = jax.jit(
                lambda rng: _cast_tree(init_thunk(rng), jnp.float32),
                out_shardings=self._master_shardings)(seed_rng)
        if self.use_master_weights:
            params0 = jax.jit(lambda m: _cast_tree(m, self.compute_dtype),
                              out_shardings=self._param_shardings)(master)
        else:
            master_spec_tree = self._master_shardings
            params0 = jax.jit(lambda m: m, out_shardings=master_spec_tree)(master)
            # fp32 mode: params ARE the masters; keep one copy
            master = None

        # -- ZeRO-Offload / ZeRO-Infinity: where the fp32 optimizer state
        #    rests (runtime/offload.py owns the decision + mechanisms).
        self._offload = None
        offload_mode = resolve_offload_mode(
            self.config, mesh, use_master_weights=master is not None,
            fp16_enabled=self.fp16_enabled,
            has_compression=self._compression_transform is not None)
        if offload_mode in ("host_step", "nvme"):
            if self._frozen_mask is not None:
                raise NotImplementedError(
                    "model.frozen_spec does not compose with optimizer "
                    "offload yet (the host-stepped executor updates every "
                    "shard); drop the offload config or use the LoRA path "
                    "(runtime/lora.py) which keeps frozen weights out of "
                    "the optimizer entirely")
            self._offload = HostSteppedOffload(
                self.config, master, self._param_shardings,
                storage=("cpu" if offload_mode == "host_step" else "nvme"),
                fp16_enabled=self.fp16_enabled,
                has_compression=self._compression_transform is not None)
            master = None
            opt_state = ()
        elif self._compression is not None and \
                self._compression.get("algo") == "zo":
            # 0/1 Adam owns its whole optimizer state (ZeroOneState rides
            # the comm_error slot below); no optax state
            opt_state = ()
        else:
            opt_state = jax.jit(self.optimizer.init)(
                master if master is not None else params0)

        if self.fp16_enabled:
            f16 = self.config.fp16
            scaler = (static_loss_scale_state(f16.loss_scale) if f16.loss_scale > 0 else
                      dynamic_loss_scale_state(f16.initial_scale_power, f16.loss_scale_window,
                                               f16.min_loss_scale, f16.hysteresis))
        else:
            scaler = no_loss_scale_state()

        # Scalars/state live replicated on the WHOLE mesh so every leaf of the
        # TrainState shares one device set (jit rejects mixed device sets, and
        # checkpoint restore preserves placements).
        replicated = NamedSharding(mesh, P())
        scaler = jax.device_put(scaler, replicated)
        seed_rng = jax.device_put(seed_rng, replicated)
        step0 = jax.device_put(jnp.int32(0), replicated)
        opt_state = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, replicated)
            if hasattr(x, "shape") and not hasattr(x.sharding, "spec") else x, opt_state)

        # -- ZeRO-Offload streamed placement: optimizer state (and fp32
        #    masters) rest in pinned host memory; XLA streams the dp-shards
        #    over PCIe into the jitted step and lands them back on the host
        #    (out_shardings below), so HBM never holds optimizer state at
        #    rest (reference stage_1_and_2.py:1041-1124 CPU offload).
        self.offload_active = False
        self._offload_dev_shardings = None
        if offload_mode == "streamed":
            opt_state, master, self._offload_dev_shardings, \
                self.offload_active = apply_streamed_placement(opt_state, master)
        comm_error = None
        if self._compression is not None:
            template = master if self.use_master_weights else params0
            if self._compression.get("algo") == "zo":
                from .comm.zero_one import init_zero_one_state

                comm_error = init_zero_one_state(template, self.mesh)
            else:
                from .comm.compressed import init_error_tree

                comm_error = jax.device_put(
                    init_error_tree(template, self.mesh),
                    NamedSharding(self.mesh, P(BATCH_AXES)))
        self.state = TrainState(step=step0, params=params0, master_params=master,
                                opt_state=opt_state, scaler=scaler, rng=seed_rng,
                                comm_error=comm_error)
        # Out-shardings pin every state leaf back to where it started (host
        # for offloaded leaves); metrics come back replicated on device.
        # The matching device-kind shardings stream the offloaded leaves INTO
        # the step (XLA refuses compute on host-placed operands).
        self._train_out_shardings = (
            (jax.tree_util.tree_map(lambda x: x.sharding, self.state), replicated)
            if self.offload_active else None)
        self.param_count = sum(
            int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(shapes))

    # ------------------------------------------------------------------
    def _build_dataloader(self, training_data):
        if training_data is None:
            if self._curriculum_metric_path is not None:
                raise ValueError(
                    "a metric-driven curriculum samples THROUGH the engine "
                    "dataloader — pass training_data to initialize()")
            return None
        from .dataloader import DeepSpeedDataLoader

        sampler = None
        ds_cfg = self.config.data_efficiency.data_sampling
        if ds_cfg.enabled and ds_cfg.curriculum_learning.enabled:
            if self._curriculum_metric_path is not None:
                raise ValueError(
                    "both the legacy curriculum_learning.metric_values_path "
                    "sampler and data_efficiency.data_sampling."
                    "curriculum_learning are configured — they would fight "
                    "over the batch stream; enable exactly one")
            # multi-metric cluster-bucketed curriculum (reference
            # DeepSpeedDataSampler); per-metric values come from
            # DataAnalyzer runs, schedulers from per-metric configs
            from .data_pipeline.curriculum_scheduler import \
                CurriculumScheduler
            from .data_pipeline.data_sampler import \
                MultiMetricCurriculumSampler

            metrics = {}
            for name, mc in ds_cfg.curriculum_learning.curriculum_metrics.items():
                values = np.load(mc.metric_values_path)
                if len(values) != len(training_data):
                    raise ValueError(
                        f"curriculum metric {name!r} has {len(values)} "
                        f"values for a dataset of {len(training_data)} "
                        "samples")
                metrics[name] = {
                    "values": values,
                    "difficulty_type": mc.difficulty_type,
                    "clustering_type": mc.clustering_type,
                    "scheduler": CurriculumScheduler({
                        "curriculum_type": name,
                        "min_difficulty": mc.min_difficulty,
                        "max_difficulty": mc.max_difficulty,
                        "schedule_type": mc.schedule_type,
                        "schedule_config": mc.schedule_config}),
                }
            sampler = MultiMetricCurriculumSampler(
                metrics, batch_size=self.micro_batch_size * self.dp_world,
                seed=self.config.seed)
        elif self._curriculum_metric_path is not None:
            # metric-driven curriculum: difficulty values from a DataAnalyzer
            # run steer the in-loop sampler (reference DeepSpeedDataSampler,
            # data_sampler.py:36)
            from .data_pipeline.data_sampler import CurriculumBatchSampler

            values = np.load(self._curriculum_metric_path)
            if len(values) != len(training_data):
                raise ValueError(
                    f"curriculum metric file has {len(values)} values for a "
                    f"dataset of {len(training_data)} samples")
            sampler = CurriculumBatchSampler(
                values, batch_size=self.micro_batch_size * self.dp_world,
                curriculum=self.curriculum_scheduler, seed=self.config.seed)

        return DeepSpeedDataLoader(training_data,
                                   batch_size=self.micro_batch_size * self.dp_world,
                                   mesh=self.mesh, data_sampler=sampler)

    def _build_monitor(self):
        if not self.config.monitor_config.enabled:
            return None
        from ..monitor.monitor import MonitorMaster

        return MonitorMaster(self.config.monitor_config)

    def _build_watchdog(self):
        rc = getattr(self.config, "resilience", None)
        if rc is None or not rc.watchdog.enabled:
            return None
        from ..resilience.watchdog import HangWatchdog

        return HangWatchdog(timeout_s=rc.watchdog.timeout_s,
                            exit_code=rc.watchdog.exit_code,
                            monitor=self.monitor)

    # ------------------------------------------------------------------
    # The jitted step
    # ------------------------------------------------------------------
    def _make_scaled_grad(self, loss_fn=None):
        """grad_fn(tree, scaler, batch, sub) -> (scaled grads, loss) —
        shared by the fused train_step scan and the per-microbatch loop.
        ``loss_fn`` defaults to the engine's own; the fused step hands in the
        variant whose checkpoint policy it resolved.

        ``tree`` is what :meth:`_compute_tree` returned: normally the
        compute-precision (bf16) params — differentiating w.r.t. the bf16
        tree instead of fp32 masters keeps every backward matmul reading
        bf16 weights (measured ~20% step time on v5e: the in-graph
        fp32->bf16 cast makes XLA feed fp32 weight bytes to the bwd dots).
        The cotangents are bf16 either way, so the gradients are bit-
        identical; accumulation still happens in fp32.  With ZeRO++ the
        quantized-gather cast must stay inside the grad (its custom VJP is
        the gradient reduce-scatter), so ``tree`` is the fp32 masters."""
        loss_fn = loss_fn or self.loss_fn
        prescale = self.config.prescale_gradients
        predivide = self.config.gradient_predivide_factor
        cast_inside = self._compute_cast if self.use_master_weights else None
        frozen_mask = self._frozen_mask

        def grad_of_batch(tree, scaler, one_batch, sub):
            def scaled(t):
                p = cast_inside(t) if cast_inside is not None else t
                if frozen_mask is not None:
                    # stop_gradient lets XLA dead-code-eliminate the whole
                    # backward for frozen leaves (the reference's
                    # requires_grad=False computes no grad at all); the
                    # update-side masking in apply_update stays as the
                    # semantic contract for paths that skip this closure
                    p = jax.tree_util.tree_map(
                        lambda m, x: jax.lax.stop_gradient(x) if m else x,
                        frozen_mask, p)
                out = loss_fn(p, one_batch, sub)
                loss, _ = out if isinstance(out, tuple) else (out, {})
                return scale_loss(loss, scaler), loss

            grads, loss = jax.grad(scaled, has_aux=True)(tree)
            if prescale:
                grads = jax.tree_util.tree_map(lambda g: g / predivide, grads)
            return grads, loss

        return grad_of_batch

    def _make_compute_tree(self):
        """tree_fn(masters, step=None) -> the tree grad_of_batch
        differentiates: the bf16/fp16 compute params (cast hoisted out of the
        microbatch scan), or the masters themselves under ZeRO++ / fp32
        compute.  When compression_training is configured the QAT/pruning
        transform applies here, on the compute-precision view, gated by the
        traced step (reference init_compression wraps the matched modules;
        see deepspeed_tpu/compression/compress.py)."""
        use_master = self.use_master_weights
        compute_dtype = self.compute_dtype
        param_shardings = self._param_shardings
        compress = getattr(self, "_compression_transform", None)
        if not use_master or self._compute_cast is not None:
            if compress is not None:
                raise NotImplementedError(
                    "compression_training with fp32 compute / ZeRO++ "
                    "quantized gather is not supported yet")
            return lambda masters, step=None: masters

        def tree_fn(masters, step=None):
            # zero_params / zero_grads name the places where the ZeRO plan's
            # sharding constraints apply; the gathers and reduce-scatters
            # themselves are the partitioner's
            with jax.named_scope("zero_params"):
                work = constrain(_cast_tree(masters, compute_dtype),
                                 param_shardings)
            if compress is not None and step is not None:
                work = constrain(compress(work, step), param_shardings)
            return work

        return tree_fn

    def _make_update_body(self):
        """update(state, masters, opt_in, grads, eff_gas) -> (new_state,
        metrics): unscale, overflow-skip, optimizer update, scaler update,
        master->compute cast.  The single source of truth for step semantics
        (used by both the fused step and the fwd/bwd/step loop)."""
        use_master = self.use_master_weights
        compute_dtype = self.compute_dtype
        optimizer = self.optimizer
        param_shardings = self._param_shardings
        fp16 = self.fp16_enabled
        prescale = self.config.prescale_gradients
        predivide = self.config.gradient_predivide_factor

        frozen_mask = self._frozen_mask

        def apply_update(state: TrainState, masters, opt_in, grads, eff_gas):
            # grad_clip: unscale, overflow check and the global norm the
            # clip is computed from (the clip's own multiply is the first
            # link of the optax chain, under `optimizer`)
            with jax.named_scope("grad_clip"):
                inv = 1.0 / (state.scaler.loss_scale * eff_gas)
                if prescale:
                    inv = inv * predivide
                grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
                if frozen_mask is not None:
                    # frozen params produce no gradient in the reference
                    # (requires_grad=False): zero theirs BEFORE the overflow
                    # check, grad norm and clipping so none of the three
                    # sees them (a frozen layer's inf would otherwise skip
                    # the step)
                    grads = jax.tree_util.tree_map(
                        lambda m, g: jnp.zeros_like(g) if m else g,
                        frozen_mask, grads)
                finite = grads_finite(grads) if fp16 else jnp.bool_(True)
                grad_norm = optax.global_norm(grads)
            with jax.named_scope("optimizer"):
                updates, new_opt = optimizer.update(grads, opt_in, masters)
                new_masters = optax.apply_updates(masters, updates)
                # overflow => skip (reference DynamicLossScaler step-skip
                # semantics)
                new_masters = _tree_select(finite, new_masters, masters)
                new_opt = _tree_select(finite, new_opt, opt_in)
                new_scaler = update_scale(state.scaler, finite)
            if use_master:
                with jax.named_scope("zero_params"):
                    new_params = constrain(
                        _cast_tree(new_masters, compute_dtype),
                        param_shardings)
                new_master_out = new_masters
            else:
                new_params = new_masters
                new_master_out = None
            new_state = TrainState(step=state.step + 1, params=new_params,
                                   master_params=new_master_out, opt_state=new_opt,
                                   scaler=new_scaler, rng=state.rng)
            metrics = {"grad_norm": grad_norm,
                       "loss_scale": state.scaler.loss_scale,
                       "step_applied": finite}
            return new_state, metrics

        return apply_update

    def _stream_in(self, state: TrainState):
        """(masters, opt_in) for the step, moved device-side when offloaded."""
        masters = state.master_params if self.use_master_weights else state.params
        opt_in = state.opt_state
        if self._offload_dev_shardings is not None:
            m_sh, o_sh = self._offload_dev_shardings
            if self.use_master_weights and m_sh is not None:
                masters = jax.device_put(masters, m_sh)
            opt_in = jax.device_put(opt_in, o_sh)
        return masters, opt_in

    def _swap_ltd_variant(self, keep: int) -> None:
        """Re-point loss_fn at a model variant with the new static keep-count
        and swap in (or rebuild) the matching compiled step."""
        self._ltd_keep = keep
        active = keep < self.model.config.max_seq_len
        variant = self.model.variant(
            random_ltd=active, random_ltd_keep=int(keep) if active else 0)
        self.loss_fn = variant.loss_fn
        self._compiled_train_step = self._ltd_cache.get(keep)
        # every compiled program that closed over the old loss_fn is stale
        self._compiled_grad_step = None
        self._compiled_micro_grad = None
        log_dist(f"random-LTD: keep={keep} tokens/layer "
                 f"({'active' if active else 'full sequence'})", ranks=[0])

    # -- host-stepped offload surface (runtime/offload.py owns the state;
    #    these properties keep the engine's historical attribute names) --
    @property
    def _nvme_swapper(self):
        return self._offload.optimizer if self._offload is not None else None

    @property
    def _nvme_names(self):
        return self._offload.names if self._offload is not None else None

    def _make_grad_only_step(self):
        gas = self.gas
        accumulate = make_grad_accumulator(self._make_scaled_grad(), gas,
                                           self.config.data_types.jnp_dtype())
        prescale = self.config.prescale_gradients
        predivide = self.config.gradient_predivide_factor
        clip = self.config.gradient_clipping

        def grad_step(state: TrainState, batch):
            work = state.params  # bf16 — masters live on NVMe
            grads, losses, new_rng = accumulate(work, state.scaler, batch,
                                                state.rng)
            # mirror apply_update's normalization: gas mean, predivide
            # compensation (grad_of_batch pre-divided), then global clipping —
            # the host Adam kernel must see exactly what the optax chain would
            scale = (predivide if prescale else 1.0) / gas
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
            gnorm = optax.global_norm(grads)
            if clip and clip > 0:
                factor = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                grads = jax.tree_util.tree_map(lambda g: g * factor, grads)
            return grads, jnp.mean(losses), gnorm, new_rng

        return _jit_step(grad_step, self.step_compile_options)

    def _train_batch_nvme(self, global_batch):
        """device grads -> host NVMe Adam -> bf16 params back to device."""
        if self._compiled_grad_step is None:
            self._compiled_grad_step = self._make_grad_only_step()
        self.tput_timer.start()
        grads, loss, grad_norm, new_rng = self._compiled_grad_step(
            self.state, global_batch)
        lr = float(self.lr_schedule(self.global_steps)) \
            if callable(self.lr_schedule) else float(self.lr_schedule)
        new_params = self._offload.host_step(grads, lr)
        self.state = dataclasses.replace(
            self.state, params=new_params, step=self.state.step + 1,
            rng=new_rng)
        self.global_steps += 1
        self.micro_steps += self.gas
        self._last_grad_norm = float(grad_norm)
        loss_val = loss
        self.tput_timer.stop(sync_tree=loss_val)
        metrics = {"loss": loss_val, "grad_norm": grad_norm,
                   "loss_scale": jnp.float32(1.0),
                   "step_applied": jnp.bool_(True)}
        self._emit_monitor_events(metrics)
        if self.global_steps % self.config.steps_per_print == 0:
            self._report_progress(metrics)
        return loss_val

    def _train_batch_param_offload(self, global_batch):
        """ZeRO-Infinity param offload: the layer-streamed executor owns the
        whole step (fwd/bwd layer loop + host Adam)."""
        self.tput_timer.start()
        loss, metrics = self._param_offload.train_batch(global_batch)
        self.global_steps += 1
        self.micro_steps += self.gas
        self._last_grad_norm = float(metrics["grad_norm"])
        self.tput_timer.stop(sync_tree=loss)
        self._emit_monitor_events(metrics)
        if self.global_steps % self.config.steps_per_print == 0:
            self._report_progress(metrics)
        return loss

    def _make_train_step(self, loss_fn=None):
        gas = self.gas
        grad_specs = self._grad_shardings
        pipeline = self.mesh.shape.get("pipe", 1) > 1
        grad_of_batch = self._make_scaled_grad(loss_fn)
        compute_tree = self._make_compute_tree()
        apply_update = self._make_update_body()
        stream_in = self._stream_in

        compression = self._compression
        if compression is not None and compression.get("algo") == "zo":
            # 0/1 Adam: the region owns grads AND the update (variance
            # freeze + local steps need per-worker momentum/delta state)
            from .comm.zero_one import make_zero_one_step

            use_master = self.use_master_weights
            compute_dtype = self.compute_dtype
            param_shardings = self._param_shardings
            lr_schedule = self.lr_schedule
            template = (self.state.master_params if use_master
                        else self.state.params)
            zo_fn = make_zero_one_step(
                make_grad_accumulator(grad_of_batch, gas,
                                      self.config.data_types.jnp_dtype()),
                self.mesh, gas, compute_dtype, template,
                compression["hyper"])

            def train_step(state: TrainState, batch):
                masters = (state.master_params if use_master
                           else state.params)
                new_rng, region_rng = jax.random.split(state.rng)
                lr = jnp.float32(lr_schedule(state.step))
                new_masters, new_zo, loss, gnorm = zo_fn(
                    masters, state.scaler, batch, region_rng,
                    state.comm_error, state.step, lr)
                if use_master:
                    params = constrain(_cast_tree(new_masters, compute_dtype),
                                       param_shardings)
                    new_state = TrainState(
                        step=state.step + 1, params=params,
                        master_params=new_masters, opt_state=(),
                        scaler=state.scaler, rng=new_rng, comm_error=new_zo)
                else:
                    new_state = TrainState(
                        step=state.step + 1, params=new_masters,
                        master_params=None, opt_state=(),
                        scaler=state.scaler, rng=new_rng, comm_error=new_zo)
                metrics = {"loss": loss, "grad_norm": gnorm,
                           "loss_scale": state.scaler.loss_scale,
                           "step_applied": jnp.bool_(True)}
                return new_state, metrics

            return _jit_step(train_step, self.step_compile_options,
                             donate_argnums=(0,))

        if compression is not None:
            from .comm.compressed import make_compressed_grad_fn

            template = (self.state.master_params if self.use_master_weights
                        else self.state.params)
            comp_grad = make_compressed_grad_fn(
                make_grad_accumulator(grad_of_batch, gas,
                                      self.config.data_types.jnp_dtype()),
                self.mesh, gas,
                compression["freeze_step"], template)

            def train_step(state: TrainState, batch):
                masters, opt_in = stream_in(state)
                work = compute_tree(masters, state.step)
                new_rng, region_rng = jax.random.split(state.rng)
                grads, losses, new_error = comp_grad(
                    work, state.scaler, batch, region_rng, state.comm_error,
                    state.step)
                new_state, metrics = apply_update(state, masters, opt_in,
                                                  grads, gas)
                # overflow => the step was skipped; the error buffer must not
                # absorb the inf/NaN residual or EF poisons every later step
                new_error = _tree_select(metrics["step_applied"], new_error,
                                         state.comm_error)
                new_state = dataclasses.replace(new_state, rng=new_rng,
                                                comm_error=new_error)
                metrics["loss"] = jnp.mean(losses)
                return new_state, metrics

            if self._train_out_shardings is not None:
                return _jit_step(train_step, self.step_compile_options,
                                 donate_argnums=(0,),
                                 out_shardings=self._train_out_shardings)
            return _jit_step(train_step, self.step_compile_options,
                             donate_argnums=(0,))

        accumulate = make_grad_accumulator(grad_of_batch, gas,
                                           self.config.data_types.jnp_dtype())

        # 1F1B schedule (model config pipeline_schedule="1f1b"): the manual
        # interleaved executor produces the gradients itself — AD cannot
        # express fwd/bwd interleaving (runtime/pipe/spmd.py:pipeline_1f1b)
        manual_pipe = None
        if pipeline and getattr(getattr(self.model, "config", None),
                                "pipeline_schedule", "gpipe") == "1f1b":
            if self._compression_transform is not None:
                raise NotImplementedError(
                    "pipeline_schedule='1f1b' + compression_training: the "
                    "manual executor differentiates the raw params")
            if self.config.prescale_gradients:
                raise NotImplementedError(
                    "pipeline_schedule='1f1b' + prescale_gradients: "
                    "unsupported")
            if self.progressive_layer_drop is not None:
                raise NotImplementedError(
                    "pipeline_schedule='1f1b' + progressive_layer_drop: the "
                    "manual executor would silently drop pld_theta")
            if self._random_ltd is not None:
                raise NotImplementedError(
                    "pipeline_schedule='1f1b' + random_ltd: unsupported")
            manual_pipe = self.model.pipeline_grad_fn()

        # landing dtype for the per-step gradients (config
        # data_types.grad_accum_dtype, reference runtime/config.py:867):
        # fp32 by default; bf16 halves the live grad buffer also in the
        # gas=1 / pipeline fast paths, not just the accumulation scan
        accum_dtype = self.config.data_types.jnp_dtype() or jnp.float32

        def train_step(state: TrainState, batch):
            masters, opt_in = stream_in(state)
            work = compute_tree(masters, state.step)  # bf16 cast hoisted out of the scan

            # grad_accum: forward and backward of the gas window (the
            # model's own scopes nest under it)
            with jax.named_scope("grad_accum"):
                if pipeline:
                    # pipeline engines consume the whole gas window in ONE
                    # call: the model splits it into microbatches internally
                    # and the SPMD pipeline overlaps them across stages
                    # (reference PipelineEngine.train_batch,
                    # pipe/engine.py:286)
                    flat = jax.tree_util.tree_map(
                        lambda x: x.reshape((-1,) + x.shape[2:]), batch)
                    new_rng, sub = jax.random.split(state.rng)
                    if manual_pipe is not None:
                        grads, losses = manual_pipe(work, state.scaler, flat,
                                                    sub)
                    else:
                        grads, losses = grad_of_batch(work, state.scaler,
                                                      flat, sub)
                    grads = jax.tree_util.tree_map(
                        lambda g: g.astype(accum_dtype), grads)
                    eff_gas = 1  # loss already averages over the gas window
                elif gas == 1:
                    # no accumulation window: skip the scan and the fp32
                    # zero buffer init + add (saves ~12 bytes/param of HBM
                    # traffic)
                    new_rng, sub = jax.random.split(state.rng)
                    grads, losses = grad_of_batch(
                        work, state.scaler,
                        jax.tree_util.tree_map(lambda x: x[0], batch), sub)
                    grads = jax.tree_util.tree_map(
                        lambda g: g.astype(accum_dtype), grads)
                    eff_gas = 1
                else:
                    grads, losses, new_rng = accumulate(
                        work, state.scaler, batch, state.rng)
                    eff_gas = gas
            # ZeRO-2/3: land the accumulated grads sharded — XLA lowers the DP
            # reduction into reduce-scatter against this constraint
            with jax.named_scope("zero_grads"):
                grads = constrain(grads, grad_specs)
            new_state, metrics = apply_update(state, masters, opt_in, grads, eff_gas)
            new_state = dataclasses.replace(new_state, rng=new_rng)
            metrics["loss"] = jnp.mean(losses)
            return new_state, metrics

        if self._train_out_shardings is not None:
            return _jit_step(train_step, self.step_compile_options,
                             donate_argnums=(0,),
                             out_shardings=self._train_out_shardings)
        return _jit_step(train_step, self.step_compile_options,
                         donate_argnums=(0,))

    def _select_train_step(self, global_batch,
                           budget_bytes: Optional[int] = None) -> None:
        """Point ``_compiled_train_step`` at the fused step to run on
        ``global_batch``.  Where the checkpoint policy is the engine's to
        resolve that is one step a batch shape, each resolved from its own
        program's memory (a longer batch than the first is held to the same
        budget, not retraced under what the first one chose); else the one
        step of the model as configured."""
        if self._remat_auto:
            signature = _shape_signature(global_batch)
            if signature != self._remat_signature:
                self._remat_signature = signature
                self._compiled_train_step, self.remat_resolution = \
                    self._remat_steps.get(signature, (None, None))
        if self._compiled_train_step is not None:
            return
        if (not self._remat_auto
                or self.resolve_remat(global_batch, budget_bytes) is None):
            self._compiled_train_step = self._make_train_step()
        if self._remat_auto:
            self._remat_steps[signature] = (self._compiled_train_step,
                                            self.remat_resolution)

    def resolve_remat(self, global_batch, budget_bytes: Optional[int] = None):
        """Pick what the backward keeps from how much memory the step has.

        Walks ``REMAT_LADDER`` from the richest policy down.  Each rung is
        the model variant with that policy behind a fused step, compiled
        ahead of time for ``global_batch``'s shapes; the first whose program
        fits is kept as the step to run: ``memory_analysis()``'s arguments +
        temporaries + outputs - aliased (:func:`program_bytes`), within
        ``budget_bytes`` (default: ``REMAT_HEADROOM`` of the device's
        ``bytes_limit``).  A compile the compiler refuses as out of memory
        does not fit; the last rung is taken whatever it needs.  The common
        case compiles one program, the one ``train_batch`` then runs (the
        jit cache is shared).

        The rule reads the program's bytes and the device's limit, which
        every process of a job sees alike, so all pick the same rung.
        Returns the record also kept as ``remat_resolution``, or None where
        the policy is not the engine's to resolve, or the backend reports no
        memory limit to decide from (the CPU) and no budget was given."""
        from ..models.transformer import REMAT_LADDER

        if not self._remat_auto:
            return None
        if budget_bytes is None:
            limit = self._device_bytes_limit()
            if not limit:
                return None
            budget_bytes = int(REMAT_HEADROOM * limit)
        tried = []
        for rung in REMAT_LADDER:
            last = rung == REMAT_LADDER[-1]
            try:
                step, compiled = self._compile_remat_rung(rung, global_batch)
            except Exception as err:
                if last or not is_out_of_memory(err):
                    raise
                tried.append({"policy": rung, "bytes": None})
                continue
            mem = compiled.memory_analysis()
            tried.append({"policy": rung, "bytes": program_bytes(mem)})
            if tried[-1]["bytes"] <= budget_bytes or last:
                break
        self._compiled_train_step = step
        self.remat_resolution = {
            "policy": rung, "tried": tried, "budget_bytes": int(budget_bytes),
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes}
        rungs = ", ".join(
            f"{t['policy']} " + ("does not compile in memory"
                                 if t["bytes"] is None
                                 else f"{t['bytes'] / 2**30:.2f} GiB")
            for t in tried)
        self._remat_span_attrs = {
            "remat_policy": rung, "remat_tried": rungs,
            "remat_argument_bytes": mem.argument_size_in_bytes,
            "remat_temp_bytes": mem.temp_size_in_bytes,
            "remat_budget_bytes": int(budget_bytes)}
        self.program_catalog.annotate("train_step", **self._remat_span_attrs)
        log_dist(f"activation checkpointing: remat_policy={rung} resolved "
                 f"from the compiled step ({rungs}; may plan "
                 f"{budget_bytes / 2**30:.2f} GiB)", ranks=[0])
        return self.remat_resolution

    def _device_bytes_limit(self) -> Optional[int]:
        stats = self.mesh.local_devices[0].memory_stats() or {}
        return stats.get("bytes_limit")

    def _compile_remat_rung(self, rung: str, global_batch):
        """``(step, compiled)``: the fused step of the model variant that
        keeps ``rung``'s residuals, and its program for ``global_batch``."""
        step = self._make_train_step(
            self.model.variant(remat_policy=rung).loss_fn)
        return step, step.lower(self.state, global_batch).compile()

    def _make_eval_step(self):
        eval_fn = self._eval_fn
        compress = self._compression_transform

        def eval_step(state: TrainState, batch):
            p = state.params
            if compress is not None:
                # evaluate the same quantized/pruned view training optimizes,
                # or validation metrics overstate the compressed model
                p = compress(p, state.step)
            out = eval_fn(p, batch, state.rng)
            loss, aux = out if isinstance(out, tuple) else (out, {})
            return loss, aux

        return _jit_step(eval_step, self.step_compile_options)

    # ------------------------------------------------------------------
    # Public API (reference engine.forward/backward/step + train_batch)
    # ------------------------------------------------------------------
    def _collect_global_batch(self, batch_or_iter):
        """Accept: a full global batch [train_batch, ...]; a [gas, mb, ...]
        pre-stacked batch; or an iterator yielding gas micro-batches."""
        if hasattr(batch_or_iter, "__next__"):
            micro = [next(batch_or_iter) for _ in range(self.gas)]
            batch = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *micro)
        else:
            batch = batch_or_iter
            lead = jax.tree_util.tree_leaves(batch)[0].shape[0]
            if lead == self.gas * self.micro_batch_size * self.dp_world:
                batch = jax.tree_util.tree_map(
                    lambda x: x.reshape((self.gas, -1) + x.shape[1:]), batch)
            elif lead != self.gas:
                raise ValueError(
                    f"batch leading dim {lead} is neither train_batch_size "
                    f"({self.train_batch_size}) nor gas ({self.gas})")
        return self._shard_batch(batch)

    def _shard_batch(self, batch):
        sharding = NamedSharding(self.mesh, P(None, BATCH_AXES))

        def put(x):
            x = np.asarray(x)
            if jax.process_count() > 1:
                # Every host materializes the same GLOBAL batch (the loaders
                # are identically seeded), so each host serves its addressable
                # shards by global index — not make_array_from_process_local_data,
                # which would treat the global batch as a per-host shard.
                return jax.make_array_from_callback(x.shape, sharding,
                                                    lambda idx: x[idx])
            return jax.device_put(x, sharding)

        return jax.tree_util.tree_map(put, batch)

    def train_batch(self, data_iter=None, batch=None) -> jnp.ndarray:
        """One full optimizer step over gas micro-batches (reference
        PipelineEngine.train_batch semantics for the non-pipeline engine).

        Resilience hooks: the ``train.step`` fault-injection site fires on
        entry, and the hang watchdog (config ``resilience.watchdog``) is
        armed for the step's duration — a step wedged inside a collective
        becomes a stack report + supervisor-recyclable exit instead of a
        silent forever-hang.

        Observability: the whole call runs under a ``train.batch`` span
        (with ``train.data``/``train.step`` children in the fused path) on
        the process-global tracer — no-op when tracing is disabled
        (docs/OBSERVABILITY.md)."""
        from ..observability.device_profiler import device_trace_unit
        from ..resilience.fault_injection import SITE_TRAIN_STEP, maybe_fire

        with trace_span("train.batch", step=self.global_steps + 1):
            if self._watchdog is None:
                maybe_fire(SITE_TRAIN_STEP, step=self.global_steps + 1)
                loss = self._train_batch_impl(data_iter=data_iter,
                                              batch=batch)
            else:
                with self._watchdog.armed(
                        f"train_batch step {self.global_steps + 1}"):
                    maybe_fire(SITE_TRAIN_STEP, step=self.global_steps + 1)
                    loss = self._train_batch_impl(data_iter=data_iter,
                                                  batch=batch)
        # windowed device capture: one train step = one capture unit
        # (a global None check when no capture is armed)
        device_trace_unit()
        return loss

    def _train_batch_impl(self, data_iter=None, batch=None) -> jnp.ndarray:
        if batch is None:
            if data_iter is None:
                if self.training_dataloader is None:
                    raise ValueError("train_batch needs a batch, an iterator, or "
                                     "training_data at initialize()")
                if self._data_iterator is None:
                    from .dataloader import RepeatingLoader

                    self._data_iterator = iter(RepeatingLoader(self.training_dataloader))
                data_iter = self._data_iterator
            batch = data_iter
        with trace_span("train.data"):
            global_batch = self._collect_global_batch(batch)
        global_batch = self._inject_pld_theta(global_batch, shape=(self.gas,))
        if self._curriculum_seqlen:
            # legacy seqlen curriculum: truncate the window's sequence dim;
            # jit caches one program per distinct difficulty automatically
            # (metric-driven curricula steer the SAMPLER instead)
            diff = self.curriculum_scheduler.update_difficulty(
                self.global_steps + 1)
            ref = (global_batch["input_ids"] if isinstance(global_batch, dict)
                   and "input_ids" in global_batch
                   else jax.tree_util.tree_leaves(global_batch)[0])
            S = ref.shape[-1]
            # truncate only leaves whose trailing axis IS the sequence axis
            global_batch = jax.tree_util.tree_map(
                lambda x: x[..., :diff]
                if x.ndim >= 3 and x.shape[-1] == S else x, global_batch)
        if self._random_ltd is not None:
            keep = self._random_ltd.update_seq(self.global_steps)
            if keep != self._ltd_keep:
                self._swap_ltd_variant(keep)
        if self._param_offload is not None:
            return self._train_batch_param_offload(global_batch)
        if self._nvme_swapper is not None:
            return self._train_batch_nvme(global_batch)
        if self._remat_auto or self._compiled_train_step is None:
            self._select_train_step(global_batch)
            if self._random_ltd is not None:
                self._ltd_cache[self._ltd_keep] = self._compiled_train_step
        profiling = (self.flops_profiler is not None
                     and self.global_steps + 1 ==
                     self.config.flops_profiler.profile_step)
        if profiling:
            jax.block_until_ready(self.state.params)
            self.flops_profiler.start_profile()
        self.tput_timer.start()
        # the sync point only runs when tracing is enabled: a traced step
        # measures device time (block_until_ready on the loss), an untraced
        # one keeps its async dispatch pipelining
        with trace_span("train.step", step=self.global_steps + 1) as _sp:
            self.state, metrics = self._compiled_train_step(self.state,
                                                            global_batch)
            _sp.sync(metrics["loss"])
            if self._remat_span_attrs and get_tracer().enabled:
                # once, on the first step a tracer sees
                _sp.set(**self._remat_span_attrs)
                self._remat_span_attrs = None
        # bookkeeping (train.monitor, here and below): untraced, this part
        # overlaps the step the device is still running
        with trace_span("train.monitor"):
            self._account_step(global_batch)
        if profiling:
            from ..profiling.flops_profiler import cost_analysis_of

            float(metrics["loss"])  # device sync: the profile must hold the step
            self.flops_profiler.stop_profile()
            self.flops_profiler.attach_cost(cost_analysis_of(
                self._compiled_train_step, self.state, global_batch))
            fp = self.config.flops_profiler
            self.flops_profiler.print_model_profile(
                profile_step=fp.profile_step, module_depth=fp.module_depth,
                top_modules=fp.top_modules, detailed=fp.detailed,
                output_file=fp.output_file)
        self.global_steps += 1
        self.micro_steps += self.gas
        # the blocking reads after the step: untraced, the first of them is
        # where the host waits for the device
        with trace_span("train.fetch"):
            self._last_grad_norm = float(metrics["grad_norm"])
            if self.fp16_enabled and not bool(metrics["step_applied"]):
                self.skipped_steps += 1
                log_dist(f"step {self.global_steps}: grad overflow, step "
                         "skipped; loss scale -> "
                         f"{float(self.state.scaler.loss_scale)}", ranks=[0])
            self.tput_timer.stop(sync_tree=metrics["loss"])
        # serial with the device: the next step's launch waits for it
        with trace_span("train.monitor"):
            self._emit_monitor_events(metrics)
            if self.global_steps % self.config.steps_per_print == 0:
                self._report_progress(metrics)
        return metrics["loss"]

    def eval_batch(self, batch) -> jnp.ndarray:
        if self._param_offload is not None:
            # forward-only layer-streamed loop (same NVMe prefetch pipeline)
            return jnp.float32(self._param_offload.eval_batch(
                self._shard_batch_eval(batch)))
        if self._compiled_eval_step is None:
            self._compiled_eval_step = self._make_eval_step()
        micro = self._shard_batch_eval(batch)
        loss, _ = self._compiled_eval_step(self.state, micro)
        return loss

    def _shard_batch_eval(self, batch):
        sharding = NamedSharding(self.mesh, P(BATCH_AXES))
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(np.asarray(x), sharding), batch)

    # ------------------------------------------------------------------
    # Reference-shaped training loop: loss = engine.forward(batch);
    # engine.backward(loss); engine.step().  (reference engine.py:1708,
    # 1849, 2050.)  forward runs one fused fwd+bwd per micro-batch (same
    # total compute as train_batch — JAX has no standalone autograd tape to
    # replay later), backward banks the gradients, step applies the
    # optimizer update at the gradient-accumulation boundary.
    # ------------------------------------------------------------------
    def _inject_pld_theta(self, batch, shape=()):
        """Add the scheduled PLD theta as a batch leaf (replicated global
        array, so multi-controller jit inputs stay consistent).  ``shape`` is
        ``(gas,)`` for the accumulation window (the scan slices it to the
        scalar the model reads) and ``()`` for a single micro-batch."""
        if self.progressive_layer_drop is None:
            return batch
        if not isinstance(batch, dict):
            raise ValueError(
                "progressive_layer_drop needs dict batches ({'input_ids': ...})"
                " so the theta schedule can ride along as 'pld_theta'")
        theta = self.progressive_layer_drop.update_state(self.global_steps)
        arr = jax.device_put(np.full(shape, theta, np.float32),
                             NamedSharding(self.mesh, P()))
        return {**batch, "pld_theta": arr}

    # ------------------------------------------------------------------
    def compute_eigenvalue(self, batch, rng=None):
        """Largest Hessian eigenvalue + per-leaf Rayleigh quotients at the
        current weights (reference engine eigenvalue integration; the values
        feed MoQ-style quantization scheduling)."""
        from .eigenvalue import Eigenvalue

        ec = self.config.eigenvalue
        est = getattr(self, "_eigenvalue_estimator", None)
        if est is None:
            est = Eigenvalue(verbose=ec.verbose, max_iter=ec.max_iter,
                             tol=ec.tol, stability=ec.stability)
            self._eigenvalue_estimator = est  # caches the jitted HVP too
        # the compute-precision view: the loss mixes params with
        # cfg.dtype activations, so fp32 masters would change dtypes
        # mid-scan — differentiate what training differentiates
        params = self.state.params
        micro = self._shard_batch_eval(batch)
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        return est.compute_eigenvalue(self.loss_fn, params, micro, rng)

    # ------------------------------------------------------------------
    def lower_train_step(self, batch):
        """AOT-lower (no backend compile) the fused train step — the cheap
        host-side half of :meth:`compile_train_step`.  The autotuner's
        parallel compile-pruning lowers under a lock (global mesh state) and
        compiles the lowered programs concurrently (XLA releases the GIL)."""
        global_batch = self._collect_global_batch(batch)
        global_batch = self._inject_pld_theta(global_batch, shape=(self.gas,))
        if self._nvme_swapper is not None or self._param_offload is not None:
            raise NotImplementedError(
                "lower_train_step does not cover the NVMe grad-only / "
                "layer-streamed offload paths")
        if self._remat_auto:
            # lowering alone cannot resolve a checkpoint policy (that takes
            # the compiled program's memory): the step these shapes resolved
            # to where they have, else the "nothing_saveable" one, which is
            # not kept, so the first train_batch still resolves.  That is the
            # leanest program the resolver can fall to: it fits a budget
            # exactly where the resolved step will
            step = (self._remat_steps.get(_shape_signature(global_batch),
                                          (None, None))[0]
                    or self._make_train_step())
        else:
            if self._compiled_train_step is None:
                self._compiled_train_step = self._make_train_step()
            step = self._compiled_train_step
        return step.lower(self.state, global_batch)

    def compile_train_step(self, batch, budget_bytes: Optional[int] = None):
        """AOT-compile the fused train step for ``batch``'s shapes and return
        the ``jax.stages.Compiled`` — its ``memory_analysis()`` /
        ``cost_analysis()`` let tooling (autotuner, flops profiler) judge a
        config without executing a step.  The jit cache is shared, so the
        subsequent ``train_batch`` call does not recompile.  Where the
        model's checkpoint policy is the engine's to resolve, this is where
        it is resolved for these shapes (:meth:`resolve_remat`), within
        ``budget_bytes`` where the caller holds the program to a budget of
        its own (the autotuner), so both judge by one."""
        global_batch = self._collect_global_batch(batch)
        global_batch = self._inject_pld_theta(global_batch, shape=(self.gas,))
        if self._nvme_swapper is not None or self._param_offload is not None:
            raise NotImplementedError(
                "compile_train_step does not cover the NVMe grad-only / "
                "layer-streamed offload paths")
        if self._remat_auto or self._compiled_train_step is None:
            self._select_train_step(global_batch, budget_bytes)
        return self._compiled_train_step.lower(self.state,
                                               global_batch).compile()

    # ------------------------------------------------------------------
    def _make_micro_grad_step(self):
        grad_specs = self._grad_shardings
        grad_of_batch = self._make_scaled_grad()
        compute_tree = self._make_compute_tree()
        stream_in = self._stream_in

        accum_dtype = self.config.data_types.jnp_dtype()

        def micro_grad(state: TrainState, batch, accum):
            masters, _ = stream_in(state)
            rng, sub = jax.random.split(state.rng)
            grads, loss = grad_of_batch(compute_tree(masters, state.step), state.scaler,
                                        batch, sub)
            accum = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(accum_dtype), accum, grads)
            accum = constrain(accum, grad_specs)
            return loss, accum, rng

        return jax.jit(micro_grad, donate_argnums=(2,))

    def _make_apply_step(self):
        gas = self.gas
        apply_update = self._make_update_body()
        stream_in = self._stream_in

        def apply_step(state: TrainState, grads):
            masters, opt_in = stream_in(state)
            return apply_update(state, masters, opt_in, grads, gas)

        if self._train_out_shardings is not None:
            state_sh, rep = self._train_out_shardings
            return jax.jit(apply_step, donate_argnums=(0,),
                           out_shardings=(state_sh, rep))
        return jax.jit(apply_step, donate_argnums=(0,))

    def _zero_grad_buffer(self):
        masters = (self.state.master_params if self.use_master_weights
                   else self.state.params)
        accum_dtype = self.config.data_types.jnp_dtype()
        zeros = jax.jit(
            lambda m: jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape, accum_dtype), m),
            out_shardings=self._grad_shardings)(masters)
        return zeros

    def forward(self, batch):
        """Compute the micro-batch loss (gradients computed alongside and
        held for the matching backward())."""
        if self.mesh.shape.get("pipe", 1) > 1:
            raise RuntimeError("pipeline engines train with train_batch(); "
                               "per-microbatch forward/backward is not exposed "
                               "(reference PipelineEngine restriction)")
        if self._param_offload is not None:
            raise RuntimeError(
                "offload_param engines train with train_batch() (the layer-"
                "streamed executor owns the fwd/bwd schedule)")
        if self._compression is not None:
            raise NotImplementedError(
                "1-bit optimizers run through train_batch() (the compressed "
                "exchange spans the whole accumulation window)")
        if self._compiled_micro_grad is None:
            self._compiled_micro_grad = self._make_micro_grad_step()
        if self._accum_grads is None:
            self._accum_grads = self._zero_grad_buffer()
            self._accum_count = 0
        if self._accum_count >= self.gas:
            raise RuntimeError(
                f"forward() beyond the accumulation window: {self._accum_count} "
                f"micro-batches already banked with gas={self.gas}; call step()")
        micro = self._shard_batch_eval(batch)
        micro = self._inject_pld_theta(micro, shape=())
        if self._accum_count == 0:
            self.tput_timer.start()
        with trace_span("train.forward", micro=self._accum_count) as _sp:
            loss, self._accum_grads, rng = self._compiled_micro_grad(
                self.state, micro, self._accum_grads)
            _sp.sync(loss)
        self.state = dataclasses.replace(self.state, rng=rng)
        self._window_losses.append(loss)
        self._backward_pending = True
        return loss

    def backward(self, loss=None):
        """Bank the gradients computed by the matching forward()."""
        assert getattr(self, "_backward_pending", False), \
            "backward() without a preceding forward()"
        # the fused fwd+bwd already ran under train.forward; this span marks
        # the accumulation bookkeeping so the reference-shaped loop's
        # timeline still shows all three phases
        with trace_span("train.backward", micro=self._accum_count):
            self._backward_pending = False
            self._accum_count += 1
            self.micro_steps += 1
        return loss

    def step(self):
        """Apply the optimizer update at the gradient-accumulation boundary;
        a mid-window step() is a no-op (reference skips until boundary)."""
        assert not getattr(self, "_backward_pending", False), \
            "step() with a forward() missing its backward()"
        if self._accum_count == 0:
            raise RuntimeError("step() with no accumulated gradients")
        if self._accum_count < self.gas:
            return None
        if self._compiled_apply_step is None:
            self._compiled_apply_step = self._make_apply_step()
        with trace_span("train.step", step=self.global_steps + 1) as _sp:
            self.state, metrics = self._compiled_apply_step(self.state,
                                                            self._accum_grads)
            _sp.sync(metrics["grad_norm"])
        self._accum_grads = None
        self._accum_count = 0
        self.global_steps += 1
        self._last_grad_norm = float(metrics["grad_norm"])
        # same bookkeeping/observability stream as train_batch
        metrics["loss"] = jnp.mean(jnp.stack(self._window_losses))
        self._window_losses = []
        if self.fp16_enabled and not bool(metrics["step_applied"]):
            self.skipped_steps += 1
            log_dist(f"step {self.global_steps}: grad overflow, step skipped; "
                     f"loss scale -> {float(self.state.scaler.loss_scale)}", ranks=[0])
        self.tput_timer.stop(sync_tree=metrics["loss"])
        self._emit_monitor_events(metrics)
        if self.global_steps % self.config.steps_per_print == 0:
            self._report_progress(metrics)
        return metrics

    def is_gradient_accumulation_boundary(self) -> bool:
        """True while the accumulation window is full — i.e. the banked
        micro-batches complete a window and step() will apply the update
        (reference engine.py is_gradient_accumulation_boundary semantics:
        true when processing the window's last micro-batch)."""
        return self._accum_count > 0 and self._accum_count % self.gas == 0

    # ------------------------------------------------------------------
    def _account_step(self, global_batch) -> None:
        """Per-program accounting for the fused train step
        (docs/OBSERVABILITY.md "Per-program accounting"): register the
        compiled step's lowered FLOPs once (no backend compile — the
        lowering hits the jit trace cache for these avals), count the
        invocation, and EMA the inter-step wall clock.  At steady state
        the loop is device-bound, so the wall between step RETURNS tracks
        the device step time without adding a sync point.  NOTE: lax.scan
        bodies (scan_layers, the gas accumulation loop) are counted once
        by XLA's analysis, so the estimate UNDERCOUNTS scanned configs —
        same caveat as the flops profiler; treat mfu_est as a trend gauge,
        not the bench's certified figure."""
        now = time.perf_counter()
        if self._step_flops is None:
            # register_call owns the lower()/cost_analysis() protocol
            # (and its failure path: zeros + a warning, never a raise)
            self.program_catalog.register_call(
                "train_step", self._compiled_train_step, self.state,
                global_batch)
            self._step_flops = self.program_catalog.flops_of("train_step")
        self.program_catalog.invoke("train_step")
        if self._step_wall_t is not None:
            dt = now - self._step_wall_t
            self._step_wall_s = (dt if self._step_wall_s is None
                                 else 0.8 * self._step_wall_s + 0.2 * dt)
        self._step_wall_t = now

    def _emit_monitor_events(self, metrics):
        if self.monitor is None:
            return
        events = [("Train/Samples/train_loss", float(metrics["loss"]), self.global_steps),
                  ("Train/Samples/lr", self.get_current_lr(), self.global_steps)]
        if self.fp16_enabled:
            events.append(("Train/Samples/loss_scale",
                           float(metrics["loss_scale"]), self.global_steps))
        if self.progressive_layer_drop is not None:
            events.append(("Train/Samples/pld_theta",
                           self.progressive_layer_drop.get_theta(),
                           self.global_steps))
        if self._step_flops and self._step_wall_s:
            # live roofline gauges (docs/OBSERVABILITY.md): achieved
            # model-flops throughput from the compiled step's cost and the
            # inter-step wall EMA; mfu_est divides by the operator-stated
            # roof (DS_TPU_PEAK_TFLOPS, e.g. the bench's measured matmul
            # peak) and reads 0 until one is provided — dashboards never
            # branch on configuration
            from ..observability.program_stats import peak_flops_per_sec

            achieved = self._step_flops / self._step_wall_s
            peak = peak_flops_per_sec()
            events.append(("train/tflops_est", achieved / 1e12,
                           self.global_steps))
            events.append(("train/mfu_est",
                           achieved / peak if peak else 0.0,
                           self.global_steps))
        self.monitor.write_events(events)

    def _report_progress(self, metrics):
        log_dist(f"step={self.global_steps}, skipped={self.skipped_steps}, "
                 f"lr={self.get_current_lr():.3e}, loss={float(metrics['loss']):.4f}, "
                 f"grad_norm={float(metrics['grad_norm']):.3f}", ranks=[0])

    def get_lr(self) -> list:
        """Current learning rate(s), one per param group (reference
        engine.get_lr -> lr_scheduler.get_lr(), a list; this engine has one
        logical group).  Scalar convenience: ``get_current_lr()``."""
        return [self.get_current_lr()]

    def get_current_lr(self) -> float:
        step = self.global_steps if self.state is None else self.state.step
        return float(self.lr_schedule(step))

    @property
    def loss_scale(self) -> float:
        if self.state is None:
            return 1.0  # offload_param: bf16-only, no loss scaling
        return float(self.state.scaler.loss_scale)

    def get_global_grad_norm(self) -> Optional[float]:
        """Global gradient norm of the most recent optimizer step (None until
        the first step completes)."""
        return self._last_grad_norm

    @property
    def module(self):
        if self.state is None:
            raise NotImplementedError(
                "offload_param engines hold no device param tree; use "
                "engine._param_offload.read_masters() for the fp32 leaves")
        return self.state.params

    def get_params(self, fp32: bool = False):
        if self.state is None:
            raise NotImplementedError(
                "offload_param engines hold no device param tree; use "
                "engine._param_offload.read_masters() for the fp32 leaves")
        if fp32 and self.state.master_params is not None:
            return self.state.master_params
        return self.state.params

    # ------------------------------------------------------------------
    # Checkpointing (reference engine.py:2593-3365) — see checkpoint_engine/
    # ------------------------------------------------------------------
    def save_checkpoint(self, save_dir, tag=None, client_state=None, save_latest=True):
        """Save the full training state.  With a host-stepped offload
        optimizer active (ZeRO-Offload host RAM / ZeRO-Infinity NVMe), the
        host-resident fp32 masters + Adam moments are serialized alongside
        the orbax tree (reference swap_tensor/optimizer_utils.py)."""
        from .checkpoint_engine.orbax_engine import save_engine_checkpoint

        with trace_span("ckpt.save",
                        tag=str(tag) if tag is not None else
                        f"global_step{self.global_steps}"):
            return save_engine_checkpoint(self, save_dir, tag=tag,
                                          client_state=client_state,
                                          save_latest=save_latest)

    def wait_for_checkpoint(self):
        """Block until an in-flight async save (checkpoint.async_save) is
        durable and `latest` is published; re-raises a failed save.  No-op
        for synchronous saves (reference Nebula commit barrier).  The join
        is bounded (the engine's finalize timeout) and the hang watchdog is
        armed around it, so a wedged storage write ends in a stack report +
        restartable exit, never a hung shutdown."""
        from .checkpoint_engine.async_engine import wait_for_pending_checkpoint

        with trace_span("ckpt.finalize"):
            if self._watchdog is None:
                return wait_for_pending_checkpoint(self)
            with self._watchdog.armed("async-checkpoint finalize"):
                return wait_for_pending_checkpoint(self)

    def replica_snapshot(self) -> bytes:
        """Serialize the live train state to one host-RAM byte slab for
        the pod replica layer (elasticity/replication.py): a device→host
        copy, never a filesystem write — see checkpoint_engine/
        replica_snapshot.py for the format."""
        from .checkpoint_engine.replica_snapshot import snapshot_train_state

        return snapshot_train_state(self)

    def replica_ingest(self, payload: bytes) -> int:
        """Rebuild the train state from a replica slab (live-adoption
        path); leaves re-shard against the current mesh.  Returns the
        restored global step."""
        from .checkpoint_engine.replica_snapshot import ingest_train_state

        return ingest_train_state(self, payload)

    def load_checkpoint(self, load_dir, tag=None, load_optimizer_states=True,
                        load_lr_scheduler_states=True, load_module_only=False):
        from .checkpoint_engine.orbax_engine import load_engine_checkpoint

        with trace_span("ckpt.load",
                        tag=str(tag) if tag is not None else "latest"):
            return load_engine_checkpoint(
                self, load_dir, tag=tag,
                load_optimizer_states=load_optimizer_states,
                load_module_only=load_module_only)
