"""Device-mesh topology management (the TPU-native process-group layer).

Replaces the reference's process-group bookkeeping (``deepspeed/utils/groups.py``:
DP group :353, model-parallel :64, expert-parallel/expert-data-parallel
:113-207, node-local all-to-all :324, hpZ intra-node :428) and the pipeline
topology (``runtime/pipe/topology.py:12`` ``ProcessTopology``/:244
``PipeModelDataParallelTopology``).  Instead of building NCCL communicators per
group, we build ONE ``jax.sharding.Mesh`` whose named axes play the role of all
those groups; collectives are expressed per-axis inside pjit/shard_map programs
and XLA routes them over ICI/DCN.

Canonical axis order (outermost → innermost):

    ('pipe', 'data_outer', 'data', 'expert', 'seq', 'model')

- DP world (batch sharding) = data_outer × data × expert → spec ``BATCH_AXES``.
  ZeRO sharding uses only the *inner* axes ``ZERO_AXES = ('data','expert')``;
  'data_outer' is 1 except under MiCS (``mics_shard_size``), where ZeRO shards
  live in inner-axis groups and replicate across 'data_outer' replica groups
  (reference ``runtime/zero/mics.py``).
- expert parallelism shards the expert dimension over 'expert' only; expert
  params replicate over 'data' (the reference's *expert-data-parallel* group,
  groups.py:161).
- 'model' is innermost so tensor-parallel collectives ride nearest-neighbor ICI.
- 'pipe' is outermost: stage boundaries are the least bandwidth-hungry link.
- multi-slice (DCN) jobs put the DCN dimension on 'pipe' or 'data' by choosing
  sizes accordingly; XLA inserts hierarchical collectives automatically.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MESH_AXES = ("pipe", "data_outer", "data", "expert", "seq", "model")

# Axes over which a ZeRO/FSDP-sharded non-expert parameter is partitioned.
ZERO_AXES = ("data", "expert")
# Pure data-parallel axes (batch sharding excluding the expert dimension).
DATA_AXES = ("data_outer", "data")
# Batch (data-parallel) sharding axes.
BATCH_AXES = DATA_AXES + ("expert",)


@dataclasses.dataclass(frozen=True)
class MeshLayout:
    """Degrees of each parallelism dimension; the analogue of the reference's
    ``PipeModelDataParallelTopology`` axis sizes (topology.py:244) plus the
    expert/sequence axes from groups.py."""

    dp: int = 1  # data-parallel degree EXCLUDING expert axis
    tp: int = 1  # tensor/model parallel
    pp: int = 1  # pipeline stages
    ep: int = 1  # expert parallel
    sp: int = 1  # sequence/context parallel
    # MiCS (reference runtime/zero/mics.py): ZeRO shards live on the inner
    # ZERO_AXES ('data','expert') and replicate across 'data_outer', so the
    # shard group size is dp×ep and the number of replica groups is dp_outer.
    # Batch/grad reduction spans all of BATCH_AXES; ZERO_AXES stays inner-only.
    dp_outer: int = 1

    @property
    def world_size(self) -> int:
        return self.dp * self.dp_outer * self.tp * self.pp * self.ep * self.sp

    @property
    def dp_world_size(self) -> int:
        """Total data-parallel degree as the reference counts it (dp×ep)."""
        return self.dp * self.dp_outer * self.ep

    def axis_sizes(self) -> Tuple[int, int, int, int, int, int]:
        return (self.pp, self.dp_outer, self.dp, self.ep, self.sp, self.tp)

    @staticmethod
    def from_world(world_size: int, tp: int = 1, pp: int = 1, ep: int = 1, sp: int = 1,
                   dp: Optional[int] = None, dp_outer: int = 1) -> "MeshLayout":
        denom = tp * pp * ep * sp * dp_outer
        if dp is None:
            if world_size % denom != 0:
                raise ValueError(
                    f"world size {world_size} not divisible by "
                    f"tp*pp*ep*sp*dp_outer={denom}")
            dp = world_size // denom
        layout = MeshLayout(dp=dp, tp=tp, pp=pp, ep=ep, sp=sp, dp_outer=dp_outer)
        if layout.world_size != world_size:
            raise ValueError(
                f"mesh layout {layout} covers {layout.world_size} devices, have {world_size}")
        return layout


def build_mesh(layout: MeshLayout, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Construct the global Mesh for a layout.

    Uses ``jax.experimental.mesh_utils`` for ICI-topology-aware device
    assignment on TPU slices — a layout the topology cannot carry is an
    error there, not a silent row-major order.  The host platform (simulated
    meshes) has no physical topology, so it reshapes row-major.
    """
    if devices is None:
        devices = jax.devices()
    shape = layout.axis_sizes()
    if layout.world_size != len(devices):
        raise ValueError(f"layout needs {layout.world_size} devices, got {len(devices)}")
    if devices[0].platform == "cpu":
        dev_array = np.asarray(list(devices)).reshape(shape)
    else:
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(shape, devices=list(devices))
    return Mesh(dev_array, MESH_AXES)


# ---------------------------------------------------------------------------
# Global mesh registry (the analogue of groups.py's cached process groups).
# ---------------------------------------------------------------------------
_GLOBAL_MESH: Optional[Mesh] = None
_GLOBAL_LAYOUT: Optional[MeshLayout] = None


def initialize_mesh(layout: Optional[MeshLayout] = None,
                    devices: Optional[Sequence[jax.Device]] = None, **kwargs) -> Mesh:
    global _GLOBAL_MESH, _GLOBAL_LAYOUT
    if layout is None:
        n = len(devices) if devices is not None else jax.device_count()
        layout = MeshLayout.from_world(n, **kwargs)
    _GLOBAL_LAYOUT = layout
    _GLOBAL_MESH = build_mesh(layout, devices)
    return _GLOBAL_MESH


def initialize_serving_mesh(tp: int = 1, n_devices: Optional[int] = None,
                            dp: Optional[int] = None) -> Mesh:
    """The multi-chip serving recipe (docs/SERVING.md "Multi-chip
    serving"): install a ``('data', 'model')``-shaped global mesh over the
    first ``n_devices`` devices with the model axis = ``tp`` — the KV pool
    shards its head dim over 'model' and the remaining degree lands on
    'data'.  On CPU, force the virtual devices BEFORE jax initializes::

        XLA_FLAGS=--xla_force_host_platform_device_count=8

    and this builds the same SPMD partitions a TPU slice compiles.  The
    returned mesh is also installed as the process-global mesh, so the
    model's internal sharding constraints and the serving programs agree
    on one device set (pass it to ``init_inference(mesh=...)`` /
    ``ServingEngine(mesh=...)``)."""
    devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"n_devices={n_devices} exceeds the {len(devices)} visible "
                "device(s) — on CPU, set XLA_FLAGS="
                "--xla_force_host_platform_device_count before jax starts")
        devices = devices[:n_devices]
    layout = MeshLayout.from_world(len(devices), tp=tp, dp=dp)
    return initialize_mesh(layout, devices=devices)


def get_mesh() -> Mesh:
    if _GLOBAL_MESH is None:
        initialize_mesh()
    return _GLOBAL_MESH


def get_layout() -> MeshLayout:
    if _GLOBAL_LAYOUT is None:
        initialize_mesh()
    return _GLOBAL_LAYOUT


def reset_mesh() -> None:
    global _GLOBAL_MESH, _GLOBAL_LAYOUT
    _GLOBAL_MESH = None
    _GLOBAL_LAYOUT = None


# ---------------------------------------------------------------------------
# Spec helpers (the analogue of "which group does this tensor reduce over").
# ---------------------------------------------------------------------------

def batch_pspec(extra_leading: int = 0) -> P:
    """PartitionSpec for a [batch, ...] array sharded over the DP world."""
    return P(*([None] * extra_leading), BATCH_AXES)


def replicated_pspec() -> P:
    return P()


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def shard_map_unchecked(fn, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-axes check off — manual regions
    here wrap collectives/pallas calls the checker can't analyze."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


_IN_MANUAL_REGION = False


@contextlib.contextmanager
def manual_region():
    """Trace-time flag: model code traced inside a fully-manual shard_map
    region must skip sharding constraints (all mesh axes are manual there,
    and with_sharding_constraint on a manual axis is an error)."""
    global _IN_MANUAL_REGION
    prev, _IN_MANUAL_REGION = _IN_MANUAL_REGION, True
    try:
        yield
    finally:
        _IN_MANUAL_REGION = prev


def constrain_spec(x, spec: P):
    """``with_sharding_constraint`` against the global mesh; no-op when no
    mesh has been initialized (single-device eager tests) or while tracing
    inside a manual shard_map region."""
    if _GLOBAL_MESH is None or _IN_MANUAL_REGION:
        return x
    return jax.lax.with_sharding_constraint(x, named(_GLOBAL_MESH, spec))


def axis_size(mesh: Mesh, axis) -> int:
    if isinstance(axis, (tuple, list)):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


def dp_world_size(mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or get_mesh()
    return axis_size(mesh, BATCH_AXES)


# ---------------------------------------------------------------------------
# Coordinate bookkeeping for checkpoint naming / launcher (ProcessTopology
# parity, topology.py:12). Ranks here are *device* linear indices in mesh
# order, not process ranks — one JAX process drives many devices.
# ---------------------------------------------------------------------------

class ProcessTopology:
    """Named-axis cartesian rank mapping over arbitrary axes.

    API parity with the reference ``ProcessTopology`` (topology.py:12):
    ``get_rank(**coords)``, ``get_coord(rank)``, ``get_dim``, ``get_axis_list``.
    """

    def __init__(self, axes: Sequence[str], dims: Sequence[int]):
        assert len(axes) == len(dims)
        self.axes = tuple(axes)
        self.dims = tuple(int(d) for d in dims)

    def world_size(self) -> int:
        return int(np.prod(self.dims)) if self.dims else 1

    def get_dim(self, axis: str) -> int:
        return self.dims[self.axes.index(axis)]

    def get_rank(self, **coords) -> int:
        missing = set(self.axes) - set(coords)
        if missing:
            raise ValueError(f"missing coords for axes {missing}")
        rank = 0
        for axis, dim in zip(self.axes, self.dims):
            c = coords[axis]
            if not 0 <= c < dim:
                raise ValueError(f"coord {axis}={c} out of range [0,{dim})")
            rank = rank * dim + c
        return rank

    def get_coord(self, rank: int):
        coords = {}
        for axis, dim in zip(reversed(self.axes), reversed(self.dims)):
            coords[axis] = rank % dim
            rank //= dim
        import collections

        Coord = collections.namedtuple("Coord", self.axes)
        return Coord(**{a: coords[a] for a in self.axes})

    def get_axis_list(self, axis: str, idx: int):
        """All ranks whose coordinate on `axis` equals idx (a "group")."""
        return [r for r in range(self.world_size()) if getattr(self.get_coord(r), axis) == idx]

    def get_axis_comm_lists(self, axis: str):
        """Lists of ranks that communicate along `axis` (vary axis, fix others)."""
        others = [a for a in self.axes if a != axis]
        groups = {}
        for r in range(self.world_size()):
            coord = self.get_coord(r)
            key = tuple(getattr(coord, a) for a in others)
            groups.setdefault(key, []).append(r)
        return [sorted(v) for _, v in sorted(groups.items())]


def resolve_engine_mesh(mc, zero_cfg, mesh: Optional[Mesh] = None) -> Mesh:
    """Build (or validate) the engine's mesh from the config's parallelism
    degrees plus the ZeRO-group factorization knobs (MiCS / hpZ).

    MiCS (reference ``runtime/zero/mics.py:351``): ZeRO shards within groups
    of ``mics_shard_size`` devices, replicated across 'data_outer' replica
    groups — there via nested process groups, here via mesh factorization
    (ZERO_AXES stay inner, BATCH_AXES span both).  hpZ reuses the same
    factorization (inner group = secondary partition); the *planner*
    diverges for hpZ (masters/grads on the full group, compute view
    inner-only) — that stays the caller's concern.
    """
    from ..utils.logging import log_dist

    mics = zero_cfg.mics_shard_size
    hpz = zero_cfg.zero_hpz_partition_size
    hier = getattr(zero_cfg, "zero_hierarchical_dp_size", -1)
    actives = [k for k, v in [("mics_shard_size", mics > 0),
                              ("zero_hpz_partition_size", hpz > 1),
                              ("zero_hierarchical_dp_size", hier > 1)] if v]
    if len(actives) > 1:
        raise ValueError(
            f"{' and '.join(actives)} all factorize the data axis — "
            "enable exactly one")
    if hpz > 1:
        mics = hpz
    elif hier > 1:
        # hierarchical qgZ: same inner x outer factorization as MiCS; the
        # planner diverges (masters/params shard over BOTH axes — plain
        # ZeRO-3 semantics with a 2-level reduction topology)
        mics = hier
    if mesh is None:
        dp_outer = 1
        if mics > 0:
            # ZeRO shards over ZERO_AXES=('data','expert'), so the shard
            # group spans the expert axis too: inner data size = mics / ep.
            denom = mc.tp * mc.pp * mc.ep * mc.sp
            world = jax.device_count()
            if mc.dp is None and world % denom != 0:
                raise ValueError(
                    f"world size {world} not divisible by "
                    f"tp*pp*ep*sp={denom}")
            full_dp = mc.dp or (world // denom)
            if mics % mc.ep != 0:
                raise ValueError(
                    f"mics_shard_size={mics} must be a multiple of "
                    f"ep={mc.ep}: ZeRO shard groups span the expert axis")
            inner_dp = mics // mc.ep
            if full_dp % inner_dp != 0:
                raise ValueError(
                    f"mics_shard_size={mics} (inner data degree "
                    f"{inner_dp} after the ep={mc.ep} factor) must "
                    f"divide the DP degree {full_dp}")
            dp_outer = full_dp // inner_dp
            mics = inner_dp
        layout = MeshLayout.from_world(
            jax.device_count(), tp=mc.tp, pp=mc.pp, ep=mc.ep, sp=mc.sp,
            dp=(mics if mics > 0 else (mc.dp or None)), dp_outer=dp_outer)
        mesh = initialize_mesh(layout)
    elif mics > 0:
        # ZeRO shard group on an explicit mesh = inner data × expert
        group = mesh.shape.get("data", 1) * mesh.shape.get("expert", 1)
        if group != mics:
            raise ValueError(
                f"mics_shard_size={mics} conflicts with the explicit "
                f"mesh's ZeRO group size data×expert={group}; build the "
                f"mesh with MeshLayout(dp=mics//ep, dp_outer=...) instead")
    if mics > 0 and zero_cfg.mics_hierarchical_params_gather:
        # XLA already emits hierarchical collectives for factorized-axis
        # shardings; the knob is satisfied structurally
        log_dist("MiCS: hierarchical gather is implicit in the factorized "
                 "mesh (XLA hierarchical collectives)", ranks=[0])
    return mesh


def topology_from_mesh(mesh: Optional[Mesh] = None) -> ProcessTopology:
    mesh = mesh or get_mesh()
    return ProcessTopology(axes=mesh.axis_names, dims=[mesh.shape[a] for a in mesh.axis_names])
