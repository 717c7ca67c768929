"""What more than one mixer of ``MIXERS`` uses, and the model file too."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def _scaled(x, m: float):
    """``x * m`` for one of the family's fixed multipliers, the product
    rounded once (the constant itself is not rounded to ``x``'s dtype
    first); ``x`` as it is where ``m`` is 1."""
    if m == 1.0:
        return x
    return (x.astype(jnp.float32) * m).astype(x.dtype)


def _causal_taps(w, bias, x, tail, n_real):
    """A depthwise causal convolution of ``w [K, C]`` (and ``bias [C]`` or
    None) over ``x [B,S,C]`` behind ``tail [B,K-1,C]``, in float32: ``(out
    [B,S,C] float32, the last K - 1 inputs before position n_real [B])``.
    One token a row, it is the K-term sum over the tail and the new row,
    and the tail shifted by one (or kept, ``n_real`` 0)."""
    K, S = w.shape[0], x.shape[1]
    ext = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    w = w.astype(jnp.float32)
    y = sum(ext[:, k:k + S].astype(jnp.float32) * w[k] for k in range(K))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    tail = jax.vmap(lambda e, n: jax.lax.dynamic_slice_in_dim(
        e, n, K - 1, axis=0))(ext, n_real.astype(jnp.int32))
    return y, tail


def _pallas_interpret() -> Optional[bool]:
    """``interpret`` for a Pallas kernel inside a model's program (the
    one-pass state step, the K/V row write and the tick's read by pages over
    a cache leaf, the expert layer's grouped product, which ``_mlp`` hands
    ``moe_ffn``) where a program traced now may hold one, ``None`` where it
    may not: Pallas kernels compile for the TPU (``ops/pallas/common.py``'s
    own test of the backend) and ``pallas_call`` has no partitioning rule,
    so any other backend, and a mesh of more than one device, keep the
    ``jax.numpy`` path.  Never a config field or an environment variable; a
    test that compiles for a described chip, or runs a kernel in interpret
    mode, replaces this function (every caller reads it through this
    module)."""
    from ...parallel import mesh as mesh_mod

    m = mesh_mod._GLOBAL_MESH
    if jax.default_backend() != "tpu" or (m is not None and m.size > 1):
        return None
    return False


def _slot_rows(row0, state_slot, B: int):
    """``(take, put)`` over a stacked slot-indexed leaf ``[L * slots, ...]``
    for a batch of ``B`` rows of the layer whose rows start at ``row0``:
    the block ``row0 .. row0 + B - 1`` where it lies (``state_slot`` None),
    else the rows ``state_slot [B]`` names."""
    if state_slot is None:
        def take(a):
            return jax.lax.dynamic_slice_in_dim(a, row0, B, axis=0)

        def put(a, new):
            return jax.lax.dynamic_update_slice_in_dim(
                a, new.astype(a.dtype), row0, axis=0)
    else:
        rows = row0 + state_slot

        def take(a):
            return a[rows]

        def put(a, new):
            return a.at[rows].set(new.astype(a.dtype))
    return take, put


def _slot_rows_in_place(row0, state_slot, B: int):
    """:func:`_slot_rows`, one named row (a prompt's) as a slice and an
    update where the row lies: a scatter into the leaf makes the compiler
    keep a version of it a layer."""
    if state_slot is not None and B == 1:
        return _slot_rows(row0 + state_slot[0], None, 1)
    return _slot_rows(row0, state_slot, B)
