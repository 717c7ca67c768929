"""ZeRO as a sharding plan (TPU-native redesign of stages 0-3).

The reference implements ZeRO with flattened partitions, autograd hooks and
hand-rolled bucketed collectives (``runtime/zero/stage_1_and_2.py:90``,
``stage3.py:67``, ``partition_parameters.py``).  Under XLA/GSPMD the same
memory/communication behavior is *declared* instead of orchestrated:

  stage 0  params R | grads R (allreduce)        | opt R
  stage 1  params R | grads R (allreduce)        | opt sharded over DP
  stage 2  params R | grads sharded (→ XLA emits reduce-scatter) | opt sharded
  stage 3  params sharded (→ XLA emits per-layer all-gather, the
           fetch/release machinery of partitioned_param_coordinator.py) |
           grads sharded | opt sharded

``R`` = replicated over the DP axes (still sharded over model/seq axes by any
tensor-parallel spec the model supplies).  The planner composes the model's TP
PartitionSpec with the ZeRO axes: it picks the largest dimension whose
per-(tp)shard size divides the DP world and assigns ``('data','expert')``
there.  Params smaller than ``stage3_param_persistence_threshold`` stay
replicated in stage 3 — exactly the reference's persistent-param optimization
(parameter_offload.py:347) but with zero bookkeeping.

The prefetch-window knobs (`stage3_max_live_parameters`,
`stage3_prefetch_bucket_size`, `stage3_max_reuse_distance`) are accepted for
schema parity and validated, but NOT translated further.  The assumption this
design started from, that XLA's latency-hiding scheduler would place and
double-buffer the plan's collectives by itself, the chip refuted (PERF.md
section 6, PR 60): the layers are one ``lax.scan`` body and the scheduler
hides nothing across its iterations, and the v5e's partitioner turns most of a
layer's products into windowed einsums (a ring of collective-permutes around
the activations) whose waits, one synchronous weight all-gather and the small
replicated parameters' gradient all-reduce stood exposed for a sixth of the
four-chip step.  What asks for the overlap now is the engine, on the step's
own compile: where a plan :attr:`ZeroShardingPlan.gathers_params`, the
accelerator's ``collective_overlap_options()`` go to the compiler with the
train / grad / eval steps (``DeepSpeedEngine.step_compile_options``).  The
knobs above still steer nothing: the option set is one rule of the plan and
the accelerator, measured on the chip, not a window a user sizes.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...parallel.mesh import ZERO_AXES, axis_size


@dataclasses.dataclass(frozen=True)
class ZeroShardingPlan:
    """Per-pytree sharding specs produced by :func:`plan_sharding`."""

    param_specs: Any      # compute params (bf16/fp16) — what the fwd/bwd sees
    master_specs: Any     # fp32 master params (== param_specs sharded at stage>=1)
    grad_specs: Any       # gradient shardings (stage>=2 sharded)
    opt_specs: Any        # optimizer state per-param shardings (== master_specs)
    stage: int
    # devices a compute parameter's ZeRO axes span (1: nothing to gather)
    param_zero_size: int = 1

    @property
    def gathers_params(self) -> bool:
        """Whether a step under this plan moves parameters between devices
        as it runs: stage 3 over ZeRO axes of more than one device.  What
        the engine asks before it hands the step's compile the accelerator's
        collective-overlap options."""
        return self.stage >= 3 and self.param_zero_size > 1


def _spec_axes_in_dim(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


def _shard_dim_for(shape: Tuple[int, ...], base_spec: P, mesh: Mesh, zero_size: int,
                   used_axes: frozenset) -> Optional[int]:
    """Pick the dimension to shard over the ZeRO axes: the largest dim whose
    per-TP-shard size is divisible by the DP world and which doesn't already
    carry a DP axis."""
    best_dim, best_size = None, 0
    entries = list(base_spec) + [None] * (len(shape) - len(base_spec))
    for dim, extent in enumerate(shape):
        axes_here = _spec_axes_in_dim(entries[dim])
        if used_axes & set(axes_here):
            return None  # already ZeRO-sharded (explicit user spec) — keep it
        tp_div = int(np.prod([mesh.shape[a] for a in axes_here])) if axes_here else 1
        if extent % tp_div != 0:
            continue
        per_shard = extent // tp_div
        if per_shard % zero_size == 0 and extent > best_size:
            best_dim, best_size = dim, extent
    return best_dim


def _compose_spec(shape: Tuple[int, ...], base_spec: Optional[P], mesh: Mesh,
                  zero_axes: Tuple[str, ...],
                  preferred_dim: Optional[int] = None) -> P:
    base_spec = base_spec if base_spec is not None else P()
    zero_size = axis_size(mesh, list(zero_axes))
    if zero_size == 1:
        return base_spec
    dim = None
    if preferred_dim is not None:
        # hpZ: the compute view must shard the SAME dim the master does —
        # the quantized-gather region strips the outer axis from the master
        # spec, which only yields the param spec when the dims agree
        entries = list(base_spec) + [None] * (len(shape) - len(base_spec))
        axes_here = _spec_axes_in_dim(entries[preferred_dim])
        tp_div = (int(np.prod([mesh.shape[a] for a in axes_here]))
                  if axes_here else 1)
        if (not (set(axes_here) & set(zero_axes))  # never duplicate an axis
                and shape[preferred_dim] % tp_div == 0
                and (shape[preferred_dim] // tp_div) % zero_size == 0):
            dim = preferred_dim
    if dim is None:
        dim = _shard_dim_for(shape, base_spec, mesh, zero_size,
                             frozenset(zero_axes))
    if dim is None:
        return base_spec
    entries = list(base_spec) + [None] * (len(shape) - len(base_spec))
    existing = _spec_axes_in_dim(entries[dim])
    entries[dim] = tuple(existing) + tuple(zero_axes)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def _leaf_size(shape) -> int:
    return int(np.prod(shape)) if shape else 1


def plan_sharding(param_shapes: Any, stage: int, mesh: Mesh, tp_specs: Optional[Any] = None,
                  persistence_threshold: int = 0,
                  zero_axes: Tuple[str, ...] = ZERO_AXES,
                  param_zero_axes: Optional[Tuple[str, ...]] = None) -> ZeroShardingPlan:
    """Build the ZeRO sharding plan for a pytree of parameter ShapeDtypeStructs.

    tp_specs: optional pytree of PartitionSpec with the model's tensor/sequence
    parallel sharding (e.g. from flax ``nn.with_partitioning`` metadata); ZeRO
    axes are composed on top.

    param_zero_axes: axes for the COMPUTE params when they differ from the
    master/grad axes — the ZeRO++ hpZ secondary partition (reference
    partition_parameters.py:1019 ``zero_hpz_partition_size``): masters/opt/
    grads stay sharded over the full group while the bf16 forward view shards
    only within the inner (intra-node) group, so per-layer all-gathers ride
    the cheap links and the extra memory is params/hpz per device.
    """
    if tp_specs is None:
        tp_specs = jax.tree_util.tree_map(lambda _: P(), param_shapes)
    hpz_mode = param_zero_axes is not None and param_zero_axes != zero_axes
    param_zero_axes = param_zero_axes if param_zero_axes is not None else zero_axes

    def spec_for(shaped, base, threshold, axes, preferred_dim=None):
        shape = tuple(shaped.shape)
        if threshold and _leaf_size(shape) < threshold:
            return base if base is not None else P()
        return _compose_spec(shape, base, mesh, axes,
                             preferred_dim=preferred_dim)

    def _zero_dim_of(spec: P, axes) -> Optional[int]:
        for dim, entry in enumerate(spec):
            if set(_spec_axes_in_dim(entry)) & set(axes):
                return dim
        return None

    # stage >= 1: master/opt sharded; no size threshold (opt state is the
    # memory hog the stage exists to shard)
    master = (jax.tree_util.tree_map(
        lambda s, b: spec_for(s, b, 0, zero_axes), param_shapes, tp_specs)
        if stage >= 1 else tp_specs)
    # stage >= 3: compute params sharded, small params persist replicated.
    # Under hpZ the param spec must use the SAME dim as the master spec
    # (the secondary partition is the master shard re-gathered over the
    # outer axis only).
    params = (jax.tree_util.tree_map(
        lambda s, b, m: spec_for(
            s, b, persistence_threshold, param_zero_axes,
            preferred_dim=(_zero_dim_of(m, zero_axes) if hpz_mode else None)),
        param_shapes, tp_specs, master)
        if stage >= 3 else tp_specs)
    # stage >= 2: grads land sharded (XLA lowers the DP reduction to
    # reduce-scatter + the step's gather); stage 3 grads match param sharding
    # — except under hpZ, where the primary (full) partition owns grads/opt
    if stage >= 3:
        grads = params if param_zero_axes == zero_axes else master
    elif stage == 2:
        grads = master
    else:
        grads = tp_specs
    return ZeroShardingPlan(param_specs=params, master_specs=master, grad_specs=grads,
                            opt_specs=master, stage=stage,
                            param_zero_size=axis_size(mesh, list(param_zero_axes)))


def named_shardings(mesh: Mesh, specs: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))


def constrain(tree: Any, specs: Any) -> Any:
    """Apply with_sharding_constraint leaf-wise (inside jit)."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.lax.with_sharding_constraint(x, s), tree, specs)
