"""Communication backend base (reference ``comm/backend.py:25``).

In the reference a Backend wraps an out-of-band collective library
(NCCL/oneCCL/HCCL).  On TPU the data plane is *compiled into the program*: XLA
emits collectives (psum / all-gather / reduce-scatter / all-to-all /
collective-permute) over ICI/DCN from sharding annotations or explicit ``lax``
ops inside ``shard_map``.  The Backend abstraction therefore splits into:

- a **data-plane** object (:class:`XLABackend`) whose ops are traced-context
  collectives keyed by mesh axis name (the analogue of a process group), and
- a **control-plane** (``jax.distributed`` + multihost utils) for rendezvous,
  barriers, and host-side object broadcast — see ``comm.init_distributed``.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Union

from ..parallel.mesh import BATCH_AXES

AxisName = Union[str, Sequence[str]]


class Backend:
    def __init__(self, name: str = "backend", rank: int = 0, size: int = 1):
        self.name = name
        self.initialized = False

    def is_initialized(self) -> bool:
        return self.initialized

    def init_process_group(self) -> None:
        self.initialized = True

    def destroy_process_group(self) -> None:
        self.initialized = False


class XLABackend(Backend):
    """Data-plane collectives as traced ``lax`` ops over mesh axes.

    These must be called inside a ``shard_map``(manual) region — the engine's
    hot loops run there.  For eager/control-plane variants see ``comm.comm``.
    """

    def __init__(self):
        super().__init__(name="xla")

    # Each op returns the result (functional, jax-style) instead of mutating.
    def all_reduce(self, tensor: Any, op: str = "sum", axis: AxisName = BATCH_AXES):
        import jax.lax as lax

        if op == "sum":
            return lax.psum(tensor, axis)
        if op == "max":
            return lax.pmax(tensor, axis)
        if op == "min":
            return lax.pmin(tensor, axis)
        if op in ("mean", "avg"):
            return lax.pmean(tensor, axis)
        if op == "prod":
            # XLA has no product collective; gather then reduce locally.
            import jax.numpy as jnp

            gathered = lax.all_gather(tensor, axis, axis=0, tiled=False)
            return jnp.prod(gathered, axis=0)
        raise ValueError(f"unsupported reduce op {op}")

    def all_gather(self, tensor: Any, axis: AxisName, tiled: bool = True, gather_dim: int = 0):
        import jax.lax as lax

        return lax.all_gather(tensor, axis, axis=gather_dim, tiled=tiled)

    def reduce_scatter(self, tensor: Any, axis: AxisName, scatter_dim: int = 0):
        import jax.lax as lax

        return lax.psum_scatter(tensor, axis, scatter_dimension=scatter_dim, tiled=True)

    def all_to_all(self, tensor: Any, axis: AxisName, split_dim: int = 0, concat_dim: int = 0):
        import jax.lax as lax

        return lax.all_to_all(tensor, axis, split_axis=split_dim, concat_axis=concat_dim,
                              tiled=True)

    def permute(self, tensor: Any, axis: str, perm):
        import jax.lax as lax

        return lax.ppermute(tensor, axis, perm)

    def axis_index(self, axis: AxisName):
        import jax.lax as lax

        return lax.axis_index(axis)

    def axis_size(self, axis: AxisName) -> int:
        import jax.lax as lax
        import numpy as np

        if isinstance(axis, (tuple, list)):
            return int(np.prod([lax.axis_size(a) for a in axis]))
        return lax.axis_size(axis)
