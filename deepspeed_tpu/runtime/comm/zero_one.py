"""0/1 Adam — variance freeze + local-step intervals (arXiv 2202.06009).

Reference: ``deepspeed/runtime/fp16/onebit/zoadam.py`` (``ZeroOneAdam``).
This is a DISTINCT algorithm from 1-bit Adam (``onebit/adam.py``), with two
mechanisms the EF-sign path does not have:

  1. **Adaptive variance freeze** (the 0 in 0/1): the second moment updates
     only on an exponentially-growing interval schedule (``var_interval``
     doubles every ``var_update_scaler`` updates) and freezes entirely after
     ``var_freeze_step``.  On var-update steps gradients sync in full
     precision; on other warmup steps they sync 1-bit compressed.
  2. **Local steps** (the 1): after the variance freezes, workers stop
     synchronizing every step.  Each worker applies Adam updates against its
     LOCAL gradients; every ``local_step_interval`` steps (interval doubles
     every ``local_step_scaler`` steps, clipped at ``local_step_clipper``)
     the accumulated per-worker update is exchanged 1-bit-compressed, the
     average replaces the local speculation, and the momentum resyncs as
     ``m = -ū/Σlr`` (zoadam.py:246-262).

TPU-native formulation.  The reference lets each worker's ``p.data`` drift
between syncs — impossible for a replicated SPMD array.  Here the synced
parameters stay replicated and each worker carries a **delta** tree (its
accumulated local updates, per-worker state sharded over the data axis like
the EF error buffers); the in-region gradient evaluates at ``p + delta_w``,
which is exactly the reference's drifted ``p.data``.  At a sync step the
delta is folded into the replicated params via the compressed exchange and
zeroed.  One jitted step contains both phases under ``lax.cond`` on the
traced step counter.

Composition limits (mirroring the reference's: the 0/1 Adam tutorial lists
ZeRO incompatibility): pure-DP mesh, ZeRO stage 0, no fp16 loss scaling, no
gradient clipping (reference ``max_grad_norm`` default 0 is the only
supported value).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .compressed import (DEFAULT_BLOCK, _pad_len, compressed_mean)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ZeroOneState:
    """Everything 0/1 Adam carries across steps.

    Per-worker leaves (flat ``[w * npad]`` f32, sharded over the DP axes):
    ``exp_avg`` (momentum — diverges between syncs), ``delta`` (accumulated
    local updates), ``error`` (EF residual).  Replicated: ``exp_avg_sq``
    (param-shaped — updated only with synced gradients), ``lrs`` and the
    interval counters."""

    exp_avg: Any
    exp_avg_sq: Any
    delta: Any
    error: Any
    lrs: jnp.ndarray
    var_interval: jnp.ndarray
    var_counter: jnp.ndarray
    local_interval: jnp.ndarray
    local_counter: jnp.ndarray


def init_zero_one_state(params: Any, mesh, block: int = DEFAULT_BLOCK
                        ) -> ZeroOneState:
    """Freshly-initialized state, device_put with the right shardings."""
    from ...parallel.mesh import BATCH_AXES, axis_size

    w = axis_size(mesh, BATCH_AXES)
    perw = NamedSharding(mesh, P(BATCH_AXES))
    rep = NamedSharding(mesh, P())

    def flatw(x):
        return jax.device_put(
            jnp.zeros((w * _pad_len(x.size, block),), jnp.float32), perw)

    def repz(x):
        return jax.device_put(jnp.zeros(x.shape, jnp.float32), rep)

    scalar = lambda v, dt=jnp.int32: jax.device_put(  # noqa: E731
        jnp.asarray(v, dt), rep)
    return ZeroOneState(
        exp_avg=jax.tree_util.tree_map(flatw, params),
        exp_avg_sq=jax.tree_util.tree_map(repz, params),
        delta=jax.tree_util.tree_map(flatw, params),
        error=jax.tree_util.tree_map(flatw, params),
        lrs=scalar(0.0, jnp.float32),
        var_interval=scalar(1), var_counter=scalar(0),
        local_interval=scalar(1), local_counter=scalar(0))


def make_zero_one_step(accumulate, mesh, gas: int, compute_dtype,
                       param_template: Any, hyper: dict,
                       block: int = DEFAULT_BLOCK):
    """Build ``fn(masters, scaler, window, rng, zo_state, step, lr)`` ->
    ``(new_masters, new_zo_state, mean_loss, grad_norm)``.

    ``accumulate`` is the shared microbatch scan (grads are
    loss_scale*gas-scaled sums; this path unscales in-region since it owns
    the whole update)."""
    from ...parallel.mesh import BATCH_AXES, manual_region, shard_map_unchecked

    b1, b2 = hyper.get("betas", (0.9, 0.999))
    eps = hyper.get("eps", 1e-8)
    wd = hyper.get("weight_decay", 0.0)
    var_freeze_step = int(hyper.get("var_freeze_step", 100000))
    var_update_scaler = int(hyper.get("var_update_scaler", 16))
    local_step_scaler = int(hyper.get("local_step_scaler", 32678))
    local_step_clipper = int(hyper.get("local_step_clipper", 16))

    pads = jax.tree_util.tree_map(lambda x: _pad_len(x.size, block),
                                  param_template)

    def unflat(flat, ref):
        return flat[:ref.size].reshape(ref.shape)

    def flat(x, npad):
        return jnp.pad(x.ravel(), (0, npad - x.size))

    def region(masters, scaler, window, rng, zo: ZeroOneState, step, lr):
        count = step + 1  # reference state['step'] after its increment
        # at the warmup->frozen boundary the EF buffers switch metric
        # (gradient residual -> accumulated-momentum residual): reset once
        # (zoadam.py reinitial_error_buffer)
        first_frozen = count == var_freeze_step + 1
        error = jax.tree_util.tree_map(
            lambda e: jnp.where(first_frozen, jnp.zeros_like(e), e), zo.error)

        delta_tree = jax.tree_util.tree_map(unflat, zo.delta, masters)
        p_eff = jax.tree_util.tree_map(
            lambda p, d: (p + d).astype(compute_dtype), masters, delta_tree)
        local_grads, losses, _ = accumulate(p_eff, scaler, window, rng)
        inv = (1.0 / (scaler.loss_scale * gas)).astype(jnp.float32)
        local_grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32) * inv, local_grads)
        m_tree = jax.tree_util.tree_map(unflat, zo.exp_avg, masters)

        def pair_map(fn, *trees):
            is_pair = lambda t: isinstance(t, tuple)  # noqa: E731
            out = jax.tree_util.tree_map(fn, *trees)
            a = jax.tree_util.tree_map(lambda t: t[0], out, is_leaf=is_pair)
            b = jax.tree_util.tree_map(lambda t: t[1], out, is_leaf=is_pair)
            return a, b

        # ---------------- phase A: warmup (variance still updating) -------
        def phase_a(error):
            on_var = (count % zo.var_interval) == 0

            def full_sync():
                g = jax.tree_util.tree_map(
                    lambda x: lax.pmean(x, BATCH_AXES), local_grads)
                return g, error

            def onebit_sync():
                fg = jax.tree_util.tree_map(flat, local_grads, pads)
                means, errs = pair_map(
                    lambda f, e: compressed_mean(f, e, BATCH_AXES, block),
                    fg, error)
                g = jax.tree_util.tree_map(unflat, means, local_grads)
                return g, errs

            g, new_error = lax.cond(on_var, full_sync, onebit_sync)
            new_m = jax.tree_util.tree_map(
                lambda m, gi: b1 * m + (1.0 - b1) * gi, m_tree, g)
            new_v = jax.tree_util.tree_map(
                lambda v, gi: jnp.where(on_var,
                                        b2 * v + (1.0 - b2) * gi * gi, v),
                zo.exp_avg_sq, g)
            upd = jax.tree_util.tree_map(
                lambda m, v, p: m / (jnp.sqrt(v) + eps) + wd * p,
                new_m, new_v, masters)
            new_p = jax.tree_util.tree_map(
                lambda p, u: p - lr * u, masters, upd)
            gnorm = jnp.sqrt(sum(
                jnp.vdot(gi, gi) for gi in jax.tree_util.tree_leaves(g)))
            # exponential var-interval schedule (zoadam.py:268-272)
            vc = jnp.where(on_var, zo.var_counter + 1, zo.var_counter)
            grow = vc == var_update_scaler
            new_var_counter = jnp.where(grow, 0, vc)
            new_var_interval = jnp.where(grow, zo.var_interval * 2,
                                         zo.var_interval)
            return (new_p, new_m, new_v,
                    jax.tree_util.tree_map(jnp.zeros_like, zo.delta),
                    new_error, jnp.float32(0.0),
                    new_var_interval, new_var_counter,
                    zo.local_interval, zo.local_counter, gnorm)

        # ---------------- phase B: frozen variance, local steps -----------
        def phase_b(error):
            new_m = jax.tree_util.tree_map(
                lambda m, gi: b1 * m + (1.0 - b1) * gi, m_tree, local_grads)
            upd = jax.tree_util.tree_map(
                lambda m, v, p, d: m / (jnp.sqrt(v) + eps) + wd * (p + d),
                new_m, zo.exp_avg_sq, masters, delta_tree)
            new_delta_tree = jax.tree_util.tree_map(
                lambda d, u: d - lr * u, delta_tree, upd)
            new_lrs = zo.lrs + lr
            on_sync = (count % zo.local_interval) == 0

            def sync():
                # delta * (sqrt(v)+eps) = -Σ lr·m  (zoadam.py:248)
                buf = jax.tree_util.tree_map(
                    lambda d, v: d * (jnp.sqrt(v) + eps),
                    new_delta_tree, zo.exp_avg_sq)
                fb = jax.tree_util.tree_map(flat, buf, pads)
                means, errs = pair_map(
                    lambda f, e: compressed_mean(f, e, BATCH_AXES, block),
                    fb, error)
                buf_avg = jax.tree_util.tree_map(unflat, means, masters)
                m_sync = jax.tree_util.tree_map(
                    lambda ba: -ba / new_lrs, buf_avg)
                p_new = jax.tree_util.tree_map(
                    lambda p, ba, v: p + ba / (jnp.sqrt(v) + eps),
                    masters, buf_avg, zo.exp_avg_sq)
                zero_delta = jax.tree_util.tree_map(jnp.zeros_like, new_delta_tree)
                return p_new, m_sync, zero_delta, errs, jnp.float32(0.0)

            def local():
                return (masters, new_m, new_delta_tree, error, new_lrs)

            p_new, m_out, delta_out, err_out, lrs_out = lax.cond(
                on_sync, sync, local)
            # pmean the SQUARED sums before the sqrt so the metric stays
            # norm-like across phases (phase A reports the norm of the synced
            # gradient; mean-of-norms would jump discontinuously at
            # var_freeze_step)
            gsq = sum(jnp.vdot(gi, gi)
                      for gi in jax.tree_util.tree_leaves(local_grads))
            gnorm = jnp.sqrt(lax.pmean(gsq, BATCH_AXES))
            # local-step interval schedule (zoadam.py:284-289)
            lc = zo.local_counter + 1
            grow = lc == local_step_scaler
            new_local_counter = jnp.where(grow, 0, lc)
            new_local_interval = jnp.where(
                grow, jnp.minimum(local_step_clipper, zo.local_interval * 2),
                zo.local_interval)
            return (p_new, m_out, zo.exp_avg_sq, delta_out, err_out, lrs_out,
                    zo.var_interval, zo.var_counter,
                    new_local_interval, new_local_counter, gnorm)

        def phase_b_packed(error):
            (p_new, m_out, v_out, delta_out, err_out, lrs_out, vi, vc, li,
             lc, gnorm) = phase_b(error)
            delta_flat = jax.tree_util.tree_map(flat, delta_out, pads)
            m_flat = jax.tree_util.tree_map(flat, m_out, pads)
            return (p_new, m_flat, v_out, delta_flat, err_out, lrs_out,
                    vi, vc, li, lc, gnorm)

        def phase_a_packed(error):
            (p_new, m_out, v_out, delta_flat, err_out, lrs_out, vi, vc, li,
             lc, gnorm) = phase_a(error)
            m_flat = jax.tree_util.tree_map(flat, m_out, pads)
            return (p_new, m_flat, v_out, delta_flat, err_out, lrs_out,
                    vi, vc, li, lc, gnorm)

        (new_p, m_flat, new_v, delta_flat, new_error, new_lrs, vi, vc, li,
         lc, gnorm) = lax.cond(count <= var_freeze_step,
                               phase_a_packed, phase_b_packed, error)
        new_zo = ZeroOneState(
            exp_avg=m_flat, exp_avg_sq=new_v, delta=delta_flat,
            error=new_error, lrs=new_lrs, var_interval=vi, var_counter=vc,
            local_interval=li, local_counter=lc)
        return new_p, new_zo, lax.pmean(jnp.mean(losses), BATCH_AXES), gnorm

    rep = jax.tree_util.tree_map(lambda _: P(), param_template)
    perw = jax.tree_util.tree_map(lambda _: P(BATCH_AXES), param_template)
    repz = jax.tree_util.tree_map(lambda _: P(), param_template)
    zo_specs = ZeroOneState(
        exp_avg=perw, exp_avg_sq=repz, delta=perw, error=perw,
        lrs=P(), var_interval=P(), var_counter=P(),
        local_interval=P(), local_counter=P())
    sm = shard_map_unchecked(
        region, mesh,
        in_specs=(rep, P(), P(None, BATCH_AXES), P(), zo_specs, P(), P()),
        out_specs=(rep, zo_specs, P(), P()))

    def fn(masters, scaler, window, rng, zo_state, step, lr):
        with manual_region():
            return sm(masters, scaler, window, rng, zo_state, step, lr)

    return fn
