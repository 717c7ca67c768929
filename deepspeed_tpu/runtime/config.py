"""Master JSON config (the analogue of ``runtime/config.py``'s DeepSpeedConfig).

Config surface keeps the reference's key names wherever the concept survives the
TPU redesign (train_batch_size triad, fp16/bf16 blocks, zero_optimization with
stage 0-3 + offload + ZeRO++ knobs, gradient_clipping, monitor blocks,
flops_profiler, wall_clock_breakdown, …) and adds one TPU-native section:
``"mesh"`` — the parallelism layout (dp/tp/pp/ep/sp) that the reference spread
across mpu arguments, pipeline module args and expert-group setup
(utils/groups.py) instead.

Batch triad resolution/validation mirrors reference runtime/config.py
(train_batch = micro_batch × gradient_accumulation_steps × dp_world).
"""
from __future__ import annotations

import json
from enum import Enum
from pathlib import Path
from typing import Any, Dict, List, Optional, Union

from pydantic import Field, model_validator

from .config_utils import DeepSpeedConfigModel
from . import constants as C
from ..utils.logging import logger


class OffloadDeviceEnum(str, Enum):
    none = "none"
    cpu = "cpu"
    nvme = "nvme"


class DeepSpeedZeroOffloadParamConfig(DeepSpeedConfigModel):
    """zero_optimization.offload_param (reference runtime/zero/offload_config.py)."""

    device: OffloadDeviceEnum = OffloadDeviceEnum.none
    nvme_path: Optional[str] = None
    buffer_count: int = Field(5, ge=0)
    buffer_size: int = Field(100_000_000, ge=0)
    max_in_cpu: int = Field(1_000_000_000, ge=0)
    pin_memory: bool = False


class DeepSpeedZeroOffloadOptimizerConfig(DeepSpeedConfigModel):
    """zero_optimization.offload_optimizer (reference runtime/zero/offload_config.py)."""

    device: OffloadDeviceEnum = OffloadDeviceEnum.none
    nvme_path: Optional[str] = None
    buffer_count: int = Field(4, ge=0)
    pin_memory: bool = False
    pipeline_read: bool = False
    pipeline_write: bool = False
    fast_init: bool = False
    ratio: float = Field(1.0, ge=0.0, le=1.0)
    # device=cpu execution strategy (TPU-specific): True = host SIMD Adam on
    # RAM-resident state (device never holds fp32 master/m/v — the reference
    # cpu_offload semantics, required for models near HBM capacity on few
    # chips); False = state parked in pinned host memory and streamed
    # through the jitted step (cheaper per step when dp shards the state
    # thin).  None = auto: host step when the mesh has ONE data shard.
    host_step: Optional[bool] = None


class ZeroConfig(DeepSpeedConfigModel):
    """zero_optimization block (reference runtime/zero/config.py:38-283).

    On TPU, stages are realized as sharding plans over the mesh's DP axes
    (see runtime/zero/planner.py) rather than hook-driven partitioning:
      0 = replicated (plain DP), 1 = optimizer states sharded,
      2 = + gradients reduce-scattered into shards, 3 = + parameters sharded.
    """

    stage: int = Field(0, ge=0, le=3)
    contiguous_gradients: bool = True
    reduce_scatter: bool = True
    reduce_bucket_size: int = Field(500_000_000, ge=0)
    allgather_partitions: bool = True
    allgather_bucket_size: int = Field(500_000_000, ge=0)
    overlap_comm: bool = True
    round_robin_gradients: bool = False
    elastic_checkpoint: bool = False

    offload_param: Optional[DeepSpeedZeroOffloadParamConfig] = None
    offload_optimizer: Optional[DeepSpeedZeroOffloadOptimizerConfig] = None

    # stage-3 knobs.  The three prefetch / live-window sizes are kept for
    # API parity and steer nothing: the overlap of a layer's collectives
    # with the compute beside them is asked of the compiler by the engine,
    # one option set a plan that gathers parameters
    # (DeepSpeedEngine.step_compile_options; runtime/zero/planner.py), since
    # the scheduler alone left a sixth of the four-chip step waiting on the
    # wire (PERF.md section 6, PR 60).  The persistence threshold is live:
    # parameters under it stay replicated in stage 3, and their gradients'
    # small all-reduce runs inside the backward's layer loop.
    sub_group_size: int = Field(1_000_000_000, ge=0)
    stage3_max_live_parameters: int = Field(1_000_000_000, ge=0)
    stage3_max_reuse_distance: int = Field(1_000_000_000, ge=0)
    stage3_prefetch_bucket_size: int = Field(50_000_000, ge=0)
    stage3_param_persistence_threshold: int = Field(100_000, ge=0)
    stage3_gather_16bit_weights_on_model_save: bool = False

    # ZeRO++ (reference zero/config.py:38-41; partition_parameters.py:1019-1158)
    zero_hpz_partition_size: int = Field(1, ge=1)
    zero_quantized_weights: bool = False
    zero_quantized_gradients: bool = False

    # MiCS (reference zero/mics.py)
    mics_shard_size: int = Field(-1)
    mics_hierarchical_params_gather: bool = False

    # hierarchical qgZ (reference coalesced_collectives.py:31 — the 2-hop
    # intra-node -> inter-node quantized gradient reduction): inner ZeRO
    # group size (the ICI domain); grads quantize-reduce within the inner
    # group first, then across 'data_outer', moving 1/inner of the bytes
    # over the expensive links
    zero_hierarchical_dp_size: int = Field(-1)

    ignore_unused_parameters: bool = True

    @model_validator(mode="after")
    def _validate(self):
        if self.zero_quantized_weights or self.zero_quantized_gradients:
            if self.stage != 3:
                raise ValueError("ZeRO++ quantized collectives require stage 3")
        if self.mics_shard_size == 0 or self.mics_shard_size < -1:
            raise ValueError(
                f"mics_shard_size={self.mics_shard_size} invalid: must be -1 "
                "(disabled) or a positive shard-group size")
        if self.mics_shard_size > 0 and self.stage != 3:
            raise ValueError("mics_shard_size (MiCS) requires ZeRO stage 3")
        if self.mics_hierarchical_params_gather and self.mics_shard_size <= 0:
            raise ValueError(
                "mics_hierarchical_params_gather requires mics_shard_size > 0")
        if self.zero_hpz_partition_size > 1 and self.stage != 3:
            raise ValueError(
                "zero_hpz_partition_size (ZeRO++ hpZ) requires stage 3")
        if self.zero_hierarchical_dp_size > 1 and self.stage != 3:
            raise ValueError(
                "zero_hierarchical_dp_size (hierarchical qgZ) requires "
                "stage 3")
        if self.zero_hierarchical_dp_size > 1 and self.mics_shard_size > 0:
            raise ValueError(
                "zero_hierarchical_dp_size and mics_shard_size both "
                "factorize the data axis — enable one or the other")
        if self.zero_hierarchical_dp_size > 1 \
                and self.zero_hpz_partition_size > 1:
            raise ValueError(
                "zero_hierarchical_dp_size and zero_hpz_partition_size both "
                "factorize the data axis — hpZ already makes the outer hop "
                "the only explicit one; hierarchical qgZ needs masters "
                "sharded over both hops")
        return self


class FP16Config(DeepSpeedConfigModel):
    """fp16 block (reference runtime/fp16/loss_scaler.py semantics)."""

    enabled: bool = False
    auto_cast: bool = False
    loss_scale: float = Field(0.0, ge=0.0)  # 0 => dynamic
    initial_scale_power: int = Field(16, ge=0)
    loss_scale_window: int = Field(1000, ge=1)
    hysteresis: int = Field(2, ge=1)
    consecutive_hysteresis: bool = False
    min_loss_scale: float = Field(1.0, ge=0.0)


class BF16Config(DeepSpeedConfigModel):
    """bf16 block (reference runtime/bf16_optimizer.py): bf16 compute with
    fp32 master weights + fp32 grad accumulation, sharded like ZeRO-1."""

    enabled: bool = False
    # accumulate gradients in fp32 across micro-batches (reference always does)
    fp32_grad_accum: bool = True


class OptimizerConfig(DeepSpeedConfigModel):
    type: str = "adamw"
    params: Dict[str, Any] = Field(default_factory=dict)


class SchedulerConfig(DeepSpeedConfigModel):
    type: str = "WarmupLR"
    params: Dict[str, Any] = Field(default_factory=dict)


class MeshConfig(DeepSpeedConfigModel):
    """TPU-native parallelism layout — dp is inferred when left at 0."""

    dp: int = Field(0, ge=0)  # 0 => infer from device count
    tp: int = Field(1, ge=1)
    pp: int = Field(1, ge=1)
    ep: int = Field(1, ge=1)
    sp: int = Field(1, ge=1)


class ActivationCheckpointingConfig(DeepSpeedConfigModel):
    """activation_checkpointing block (reference checkpointing.py:789 configure).

    On TPU this maps to jax.checkpoint policies; partition_activations maps to
    sharding the saved residuals over the model/seq axes."""

    partition_activations: bool = False
    cpu_checkpointing: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False


class TensorBoardConfig(DeepSpeedConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"


class WandbConfig(DeepSpeedConfigModel):
    enabled: bool = False
    group: Optional[str] = None
    team: Optional[str] = None
    project: str = "deepspeed_tpu"


class CSVConfig(DeepSpeedConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"


class MonitorConfig(DeepSpeedConfigModel):
    tensorboard: TensorBoardConfig = Field(default_factory=TensorBoardConfig)
    wandb: WandbConfig = Field(default_factory=WandbConfig)
    csv_monitor: CSVConfig = Field(default_factory=CSVConfig)

    @property
    def enabled(self) -> bool:
        return self.tensorboard.enabled or self.wandb.enabled or self.csv_monitor.enabled


class FlopsProfilerConfig(DeepSpeedConfigModel):
    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


class CommsLoggerConfig(DeepSpeedConfigModel):
    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False
    prof_ops: List[str] = Field(default_factory=list)


class CheckpointConfig(DeepSpeedConfigModel):
    tag_validation: str = "Warn"  # Ignore | Warn | Fail
    load_universal: bool = False
    use_node_local_storage: bool = False
    parallel_write_pipeline: bool = False
    # Nebula-analogue async tiered save (reference nebula_checkpoint_engine):
    # save_checkpoint returns after the device->host snapshot; the storage
    # write runs in the background and `latest` is published only on commit
    async_save: bool = False


class WatchdogConfig(DeepSpeedConfigModel):
    """Hang watchdog (resilience/watchdog.py): armed around ``train_batch``
    and async-checkpoint finalization; past ``timeout_s`` it dumps an
    all-thread stack report through the monitor layer and exits
    ``exit_code`` so the elastic supervisor can recycle the process."""

    enabled: bool = False
    timeout_s: float = Field(600.0, gt=0.0)
    exit_code: int = 85   # resilience.watchdog.RC_HANG


class ResilienceConfig(DeepSpeedConfigModel):
    """``resilience`` block: checkpoint verification + hang watchdog (fault
    injection is env/test-driven via DS_TPU_FAULTS, never config)."""

    # verify manifest.json (checksums + payload listing) before any load
    verify_on_load: bool = True
    watchdog: WatchdogConfig = Field(default_factory=WatchdogConfig)


class DataTypeConfig(DeepSpeedConfigModel):
    grad_accum_dtype: Optional[str] = None


class PipelineConfig(DeepSpeedConfigModel):
    """pipeline block — schedule/microbatch knobs (engine-level; stage count
    comes from mesh.pp)."""

    pipe_partitioned: bool = True
    grad_partitioned: bool = True
    activation_checkpoint_interval: int = 0
    partition_method: str = "parameters"


class ProgressiveLayerDropConfig(DeepSpeedConfigModel):
    """``progressive_layer_drop`` block (reference runtime/config.py PLD
    keys; runtime/progressive_layer_drop.py)."""

    enabled: bool = False
    theta: float = Field(0.5, gt=0.0, le=1.0)
    gamma: float = Field(0.001, ge=0.0)


class EigenvalueConfig(DeepSpeedConfigModel):
    """``eigenvalue`` block (reference runtime/eigenvalue.py knobs; device/
    layer-name knobs are meaningless on the pytree design and not accepted)."""

    enabled: bool = False
    verbose: bool = False
    max_iter: int = Field(100, ge=1)
    tol: float = Field(1e-2, gt=0.0)
    stability: float = Field(1e-6, ge=0.0)


class CurriculumLearningLegacyConfig(DeepSpeedConfigModel):
    """Top-level ``curriculum_learning`` block (reference legacy curriculum,
    runtime/config.py ``curriculum_enabled_legacy``): the engine truncates
    the batch sequence to the scheduled difficulty."""

    enabled: bool = False
    curriculum_type: str = "seqlen"
    min_difficulty: int = 8
    max_difficulty: int = 1024
    schedule_type: str = "fixed_linear"
    schedule_config: Dict[str, Any] = Field(default_factory=dict)
    # non-seqlen curriculum types: per-sample difficulty values (a
    # DataAnalyzer ``<metric>_values.npy``) driving the in-loop sampler
    metric_values_path: Optional[str] = None


class RandomLTDConfig(DeepSpeedConfigModel):
    enabled: bool = False
    min_value: int = 128
    max_value: int = 2048
    random_ltd_schedule: Dict[str, Any] = Field(default_factory=dict)


class DataRoutingConfig(DeepSpeedConfigModel):
    enabled: bool = False
    random_ltd: RandomLTDConfig = Field(default_factory=RandomLTDConfig)


class CurriculumMetricConfig(DeepSpeedConfigModel):
    """One metric of the multi-metric curriculum (reference
    ``data_efficiency.data_sampling.curriculum_learning.curriculum_metrics``
    entries, constants.py CURRICULUM_LEARNING_METRICS)."""

    metric_values_path: str  # a DataAnalyzer `<metric>_values.npy`
    difficulty_type: str = "value"          # 'value' | 'percentile'
    clustering_type: str = "schedule_based"  # | 'single_cluster'
    min_difficulty: int = 1
    max_difficulty: int = 100
    schedule_type: str = "fixed_linear"
    schedule_config: Dict[str, Any] = Field(default_factory=dict)

    @model_validator(mode="after")
    def _validate(self):
        if self.difficulty_type not in ("value", "percentile"):
            raise ValueError(
                f"difficulty_type={self.difficulty_type!r}: 'value' or "
                "'percentile'")
        if self.clustering_type not in ("schedule_based", "single_cluster"):
            raise ValueError(
                f"clustering_type={self.clustering_type!r}: "
                "'schedule_based' or 'single_cluster'")
        return self


class CurriculumLearningConfig(DeepSpeedConfigModel):
    """Multi-metric cluster-bucketed curriculum (reference
    data_sampling/data_sampler.py:36 DeepSpeedDataSampler)."""

    enabled: bool = False
    curriculum_metrics: Dict[str, CurriculumMetricConfig] = Field(
        default_factory=dict)

    @model_validator(mode="after")
    def _validate(self):
        if self.enabled and not self.curriculum_metrics:
            raise ValueError(
                "data_sampling.curriculum_learning.enabled needs >=1 entry "
                "in curriculum_metrics")
        return self


class DataSamplingConfig(DeepSpeedConfigModel):
    enabled: bool = False
    curriculum_learning: CurriculumLearningConfig = Field(
        default_factory=CurriculumLearningConfig)

    @model_validator(mode="after")
    def _validate(self):
        if self.curriculum_learning.enabled and not self.enabled:
            raise ValueError(
                "data_sampling.curriculum_learning.enabled=true requires "
                "data_sampling.enabled=true (the engine gates on both — a "
                "silently-ignored curriculum would train uniformly)")
        return self


class DataEfficiencyConfig(DeepSpeedConfigModel):
    enabled: bool = False
    data_routing: DataRoutingConfig = Field(default_factory=DataRoutingConfig)
    data_sampling: DataSamplingConfig = Field(
        default_factory=DataSamplingConfig)


class AIOConfig(DeepSpeedConfigModel):
    block_size: int = 1048576
    queue_depth: int = 8
    thread_count: int = 1
    single_submit: bool = False
    overlap_events: bool = True


class ElasticityConfig(DeepSpeedConfigModel):
    enabled: bool = False
    max_train_batch_size: int = 2000
    micro_batch_sizes: List[int] = Field(default_factory=lambda: [2, 4, 6])
    min_gpus: int = 1
    max_gpus: int = 10000
    min_time: int = 0
    version: float = 0.1
    ignore_non_elastic_batch_info: bool = False
    prefer_larger_batch: bool = True
    num_gpus_per_node: int = Field(1, ge=1)
    model_parallel_size: int = Field(1, ge=1)


class DataTypesConfig(DeepSpeedConfigModel):
    """``data_types`` block (reference runtime/config.py:867): gradient
    accumulation precision.  None/fp32 = exact fp32 accumulation; bf16 halves
    the live gradient buffer."""

    grad_accum_dtype: Optional[str] = None

    @model_validator(mode="after")
    def _validate(self):
        if self.grad_accum_dtype not in (None, "fp32", "float32", "bf16",
                                         "bfloat16"):
            raise ValueError(
                f"data_types.grad_accum_dtype={self.grad_accum_dtype!r} "
                "must be fp32 or bf16")
        return self

    def jnp_dtype(self):
        import jax.numpy as jnp

        if self.grad_accum_dtype in ("bf16", "bfloat16"):
            return jnp.bfloat16
        return jnp.float32


class DeepSpeedConfigError(Exception):
    pass


class DeepSpeedConfig:
    """Master config object (reference runtime/config.py DeepSpeedConfig).

    Accepts a dict, a JSON file path, or None; resolves the batch-size triad
    against the mesh's data-parallel world size.
    """

    def __init__(self, config: Union[None, str, Path, Dict[str, Any]] = None,
                 dp_world_size: Optional[int] = None):
        if config is None:
            config = {}
        if isinstance(config, (str, Path)):
            with open(config, "r") as f:
                config = json.load(f)
        if not isinstance(config, dict):
            raise DeepSpeedConfigError(f"config must be dict or path, got {type(config)}")
        self._param_dict = dict(config)

        self.mesh = MeshConfig(**config.get("mesh", {}))
        self.zero_config = ZeroConfig(**config.get(C.ZERO_OPTIMIZATION, {}))
        self.fp16 = FP16Config(**config.get(C.FP16, {}))
        self.bf16 = BF16Config(**config.get(C.BF16, {}))
        if self.fp16.enabled and self.bf16.enabled:
            raise DeepSpeedConfigError("fp16 and bf16 cannot both be enabled")

        opt = config.get(C.OPTIMIZER)
        self.optimizer = OptimizerConfig(**opt) if opt is not None else None
        sched = config.get(C.SCHEDULER)
        self.scheduler = SchedulerConfig(**sched) if sched is not None else None

        self.gradient_clipping: float = float(
            config.get(C.GRADIENT_CLIPPING, C.GRADIENT_CLIPPING_DEFAULT))
        self.prescale_gradients: bool = bool(config.get(C.PRESCALE_GRADIENTS, False))
        self.gradient_predivide_factor: float = float(
            config.get(C.GRADIENT_PREDIVIDE_FACTOR, 1.0))
        self.steps_per_print: int = int(config.get(C.STEPS_PER_PRINT, C.STEPS_PER_PRINT_DEFAULT))
        self.wall_clock_breakdown: bool = bool(config.get(C.WALL_CLOCK_BREAKDOWN, False))
        self.memory_breakdown: bool = bool(config.get(C.MEMORY_BREAKDOWN, False))
        self.dump_state: bool = bool(config.get(C.DUMP_STATE, False))
        self.seed: int = int(config.get("seed", 42))

        self.activation_checkpointing = ActivationCheckpointingConfig(
            **config.get("activation_checkpointing", {}))
        self.monitor_config = MonitorConfig(**{
            k: v for k, v in config.items() if k in ("tensorboard", "wandb", "csv_monitor")})
        self.flops_profiler = FlopsProfilerConfig(**config.get("flops_profiler", {}))
        self.comms_logger = CommsLoggerConfig(**config.get("comms_logger", {}))
        self.checkpoint_config = CheckpointConfig(**config.get("checkpoint", {}))
        self.resilience = ResilienceConfig(**config.get("resilience", {}))
        self.data_types = DataTypeConfig(**config.get("data_types", {}))
        self.pipeline = PipelineConfig(**config.get("pipeline", {}))
        self.aio = AIOConfig(**config.get("aio", {}))
        self.curriculum_learning = CurriculumLearningLegacyConfig(
            **config.get("curriculum_learning", {}))
        self.data_efficiency = DataEfficiencyConfig(
            **config.get("data_efficiency", {}))
        self.elasticity = ElasticityConfig(**config.get("elasticity", {}))
        self.data_types = DataTypesConfig(**config.get("data_types", {}))
        self.progressive_layer_drop = ProgressiveLayerDropConfig(
            **config.get("progressive_layer_drop", {}))
        self.eigenvalue = EigenvalueConfig(**config.get("eigenvalue", {}))

        self.gradient_accumulation_steps: Optional[int] = config.get(
            C.GRADIENT_ACCUMULATION_STEPS)
        self.train_batch_size: Optional[int] = config.get(C.TRAIN_BATCH_SIZE)
        self.train_micro_batch_size_per_gpu: Optional[int] = config.get(
            C.TRAIN_MICRO_BATCH_SIZE_PER_GPU)

        self._reject_unimplemented_knobs()

        if dp_world_size is not None:
            self.resolve_batch_triad(dp_world_size)

    def _reject_unimplemented_knobs(self) -> None:
        """Fail fast on accepted-but-unimplemented settings.

        Schema parity with the reference means every knob parses; a knob that
        parses but does nothing is a silent lie (a user enabling offload must
        not discover at OOM time that it was inert).  Any setting listed here
        raises NotImplementedError at config time; entries are removed as the
        backing feature lands.
        """
        bad: List[str] = []
        zc = self.zero_config

        if self._param_dict.get("sparse_gradients", False):
            bad.append(
                "sparse_gradients (XLA fuses the embedding scatter-add and "
                "ZeRO/TP already shard the exchange; a variable-nnz sparse "
                "allreduce is inexpressible under static shapes — see "
                "runtime/sparse_tensor.py for the fixed-width row-sparse "
                "utility and the full position)")

        if zc.offload_param is not None and \
                zc.offload_param.device == OffloadDeviceEnum.cpu:
            bad.append("zero_optimization.offload_param.device=cpu "
                       "(use device=nvme for the layer-streamed param "
                       "offload, or offload_optimizer for state-only offload)")
        if zc.offload_param is not None and \
                zc.offload_param.device == OffloadDeviceEnum.nvme:
            if not zc.offload_param.nvme_path:
                bad.append("zero_optimization.offload_param.device=nvme "
                           "requires nvme_path")
            if zc.stage != 3:
                bad.append("zero_optimization.offload_param requires "
                           "stage=3 (reference restriction)")
        if zc.offload_optimizer is not None and \
                zc.offload_optimizer.device == OffloadDeviceEnum.nvme and \
                not zc.offload_optimizer.nvme_path:
            bad.append("zero_optimization.offload_optimizer.device=nvme "
                       "requires nvme_path")
        ac = self.activation_checkpointing
        for knob in ("cpu_checkpointing", "contiguous_memory_optimization",
                     "synchronize_checkpoint_boundary", "profile"):
            if getattr(ac, knob):
                bad.append(f"activation_checkpointing.{knob}")
        if ac.number_checkpoints is not None:
            bad.append("activation_checkpointing.number_checkpoints "
                       "(contiguous-buffer partitioning)")

        if bad:
            raise NotImplementedError(
                "config enables features this build does not implement yet: "
                + "; ".join(bad))

    # -- batch triad (reference runtime/config.py `_batch_assertion` et al.) --
    def resolve_batch_triad(self, dp_world_size: int) -> None:
        if self.elasticity.enabled:
            self._resolve_elastic_triad(dp_world_size)
            return
        tb, mb, gas = (self.train_batch_size, self.train_micro_batch_size_per_gpu,
                       self.gradient_accumulation_steps)
        if tb is not None and mb is not None and gas is not None:
            pass
        elif tb is not None and mb is not None:
            gas = tb // (mb * dp_world_size)
        elif tb is not None and gas is not None:
            mb = tb // (gas * dp_world_size)
        elif mb is not None and gas is not None:
            tb = mb * gas * dp_world_size
        elif tb is not None:
            gas = 1
            mb = tb // dp_world_size
        elif mb is not None:
            gas = 1
            tb = mb * dp_world_size
        else:
            raise DeepSpeedConfigError(
                "at least one of train_batch_size / train_micro_batch_size_per_gpu "
                "must be set")
        if gas < 1 or mb < 1 or tb != mb * gas * dp_world_size:
            raise DeepSpeedConfigError(
                f"batch triad inconsistent: train_batch_size={tb} != "
                f"micro_batch({mb}) * gas({gas}) * dp_world({dp_world_size})")
        self.train_batch_size, self.train_micro_batch_size_per_gpu = tb, mb
        self.gradient_accumulation_steps = gas

    def _resolve_elastic_triad(self, dp_world_size: int) -> None:
        """Elastic mode: the batch triad comes from the elastic plan, not the
        user's knobs (reference elasticity handling in runtime/config.py —
        explicit batch settings conflict unless ignore_non_elastic_batch_info)."""
        from ..elasticity import (ensure_immutable_elastic_config,
                                  resolve_plan_for_current_world)
        if getattr(self, "elastic_plan", None) is not None:
            return  # already resolved (engine re-calls resolve_batch_triad)
        ec = self.elasticity
        user_set = [k for k, v in (
            ("train_batch_size", self.train_batch_size),
            ("train_micro_batch_size_per_gpu", self.train_micro_batch_size_per_gpu),
            ("gradient_accumulation_steps", self.gradient_accumulation_steps),
        ) if v is not None]
        if user_set and not ec.ignore_non_elastic_batch_info:
            raise DeepSpeedConfigError(
                f"elasticity is enabled but {user_set} are also set; elastic "
                "training derives the batch triad from the plan — remove them "
                "or set elasticity.ignore_non_elastic_batch_info")
        ensure_immutable_elastic_config(ec.model_dump())
        plan = resolve_plan_for_current_world(
            ec, dp_world_size, node_size=ec.num_gpus_per_node,
            model_parallel_size=ec.model_parallel_size)
        (self.train_batch_size, self.train_micro_batch_size_per_gpu,
         self.gradient_accumulation_steps) = plan.as_triad()
        self.elastic_plan = plan

    # -- convenience accessors used by the engine --
    @property
    def zero_enabled(self) -> bool:
        return self.zero_config.stage > 0

    @property
    def zero_optimization_stage(self) -> int:
        return self.zero_config.stage

    @property
    def precision(self):
        import jax.numpy as jnp

        if self.bf16.enabled:
            return jnp.bfloat16
        if self.fp16.enabled:
            return jnp.float16
        return jnp.float32

    def to_dict(self) -> Dict[str, Any]:
        return dict(self._param_dict)

    def print_config(self) -> None:
        logger.info("DeepSpeedConfig:\n" + json.dumps(self._param_dict, indent=2, default=str))
