"""Shared Pallas kernel utilities (reference ``csrc/includes/``: the common
kernel layer every CUDA op includes — ``reduction_utils.h``,
``memory_access_utils.h``, ``conversion_utils.h``).

The TPU analogue is small because Mosaic handles tiling/layout, but the
conventions that DO repeat across kernels live here so they stay aligned:

  - ``NEG_INF`` — the masking constant (finite: ``-inf`` breaks the online
    softmax's ``exp(m_prev - m_new)`` rescale when a whole block is masked).
  - ``resolve_interpret()`` — kernels are compiled unless interpret mode is
    asked for by name; a host without a TPU is an error, not a reason.
  - ``pick_block()`` — largest power-of-two tile that divides the axis.
  - ``mask_to_i32()`` — masks cross the pallas_call boundary as int32 and
    are compared ``!= 0`` in-kernel: bool memref tiling is a Mosaic
    lowering hazard.
  - ``parallel_semantics()`` — CompilerParams with the leading grid axes
    'parallel' and the innermost (accumulator-carrying) axis 'arbitrary'.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Set to "1" by tests/conftest.py and by chip_smoke.py's CPU rehearsal (an
# env var so the scripts those spawn inherit it).  Nothing else sets it.
INTERPRET_ENV = "DS_TPU_PALLAS_INTERPRET"


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Whether a kernel runs in Pallas interpret mode.

    An explicit argument wins; otherwise interpret mode is on only when
    ``DS_TPU_PALLAS_INTERPRET=1`` asks for it.  It is never inferred from the
    devices present: with no TPU attached and no request, this raises rather
    than hand back a slow imitation of the kernel.  (``interpret=False`` is
    not checked, so a kernel can be AOT-compiled against a TPU topology from
    a CPU host.)"""
    if interpret is not None:
        return interpret
    if os.environ.get(INTERPRET_ENV) == "1":
        return True
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            "Pallas kernels compile for the TPU and none is attached "
            f"(backend {jax.default_backend()!r}).  Tests and rehearsals ask "
            f"for interpret mode by name: {INTERPRET_ENV}=1 or interpret=True.")
    return False


def pick_block(n: int, want: int, floor: int = 8) -> int:
    """Largest power-of-two block <= ``want`` dividing ``n`` (>= ``floor``).

    Raises NotImplementedError when no such block exists — callers fall back
    to their XLA path rather than running a ragged final tile (padded rows
    would leak through index-based masks).
    """
    b = min(want, n)
    while b > floor and n % b:
        b //= 2
    # a full-axis tile (b == n) is legal at any size (tile == array dim);
    # otherwise the tile must divide n and respect the floor
    if n % b or (b < floor and b != n):
        raise NotImplementedError(
            f"axis length {n} has no power-of-two block divisor >= {floor}; "
            "use the XLA path")
    return b


def mask_to_i32(mask) -> jax.Array:
    """Boolean mask -> int32 for crossing the pallas_call boundary."""
    return jnp.asarray(mask).astype(jnp.int32)


def parallel_semantics(n_parallel: int, n_arbitrary: int = 1):
    """CompilerParams for an n-axis grid: leading axes independent, the
    trailing axes carrying accumulator state across iterations."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_parallel
        + ("arbitrary",) * n_arbitrary)
