"""Engine-side offload wiring — ZeRO-Offload and ZeRO-Infinity placement.

Three placements for the fp32 optimizer state (masters + Adam moments),
mirroring the reference's offload matrix (``runtime/zero/stage_1_and_2.py``
cpu_offload, ``runtime/zero/stage3.py:502`` offload_optimizer/offload_param,
``runtime/swap_tensor/partitioned_optimizer_swapper.py``):

  streamed   state rests in pinned host memory; XLA streams dp-shards over
             PCIe into the ONE jitted step and lands them back on the host
             (sharding memory kinds — no torch-style hook orchestration).
  host_step  state resident in host RAM; the device runs a grad-only jitted
             step and the host applies the native SIMD Adam between steps.
  nvme       as host_step, but state lives in per-leaf files driven by the
             native aio engine with a read/compute/writeback pipeline
             (ZeRO-Infinity).

`resolve_offload_mode` owns the decision (including the reference's
``host_step`` auto heuristic); `HostSteppedOffload` owns the host/NVMe
optimizer and the device<->host exchange; `apply_streamed_placement` owns
the pinned-host placement.  The engine composes these — it holds no offload
policy of its own.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

import jax

from ..utils.logging import logger, log_dist
from ..parallel.mesh import dp_world_size


def resolve_offload_mode(config, mesh, use_master_weights: bool,
                         fp16_enabled: bool, has_compression: bool) -> str:
    """Which optimizer-state placement this config selects.

    Returns one of ``"none" | "streamed" | "host_step" | "nvme"``.

    ``device=cpu`` with ONE data shard: park-and-stream would still pull the
    FULL fp32 master/m/v into HBM inside the step, so single-shard cpu
    offload routes through the same host-step path as NVMe (state in RAM
    instead of on disk) unless ``host_step=False`` forces streaming.
    """
    zc = config.zero_config
    dev = zc.offload_optimizer.device if zc.offload_optimizer else "none"
    dev = getattr(dev, "value", dev)
    if dev == "nvme":
        return "nvme"
    if dev != "cpu":
        return "none"
    hs = zc.offload_optimizer.host_step
    if hs is not None:
        return "host_step" if bool(hs) else "streamed"
    # auto: host step only where it's BOTH needed (one data shard —
    # streaming would pull the full fp32 state into HBM inside the step)
    # and supported by the host path's preconditions; otherwise keep the
    # streamed placement, which handles fp32/fp16/any-optimizer/
    # compression and checkpointing
    opt_cfg = config.optimizer
    opt_type = (opt_cfg.type if opt_cfg else "adamw").lower()
    host_step = (dp_world_size(mesh) == 1
                 and use_master_weights
                 and not fp16_enabled
                 and not has_compression
                 and opt_type in ("adam", "adamw"))
    return "host_step" if host_step else "streamed"


def apply_streamed_placement(opt_state, master):
    """ZeRO-Offload streamed placement: move optimizer state (and fp32
    masters) to pinned host memory so HBM never holds them at rest; XLA
    streams the dp-shards over PCIe into the jitted step (reference
    stage_1_and_2.py:1041-1124 CPU offload, TPU-native form).

    Returns ``(opt_state, master, dev_shardings, active)`` where
    ``dev_shardings`` are the matching device-kind shardings that stream the
    leaves INTO the step (XLA refuses compute on host-placed operands), or
    ``None`` when the placement is a no-op (CPU backend).
    """
    if jax.devices()[0].platform == "cpu":
        # Host and "device" memory are the same RAM on the CPU backend (and
        # XLA cannot compile placement annotations on a forced multi-device
        # host mesh) — the placement would be a no-op; the code path is
        # still exercised minus memory kinds.
        logger.warning(
            "offload_optimizer.device=cpu: CPU backend — host memory IS "
            "device memory; offload placement skipped")
        return opt_state, master, None, False
    to_host = lambda x: jax.device_put(  # noqa: E731
        x, x.sharding.with_memory_kind("pinned_host"))
    opt_state = jax.tree_util.tree_map(to_host, opt_state)
    if master is not None:
        master = jax.tree_util.tree_map(to_host, master)
    to_dev = lambda x: x.sharding.with_memory_kind("device")  # noqa: E731
    dev_shardings = (
        jax.tree_util.tree_map(to_dev, master) if master is not None else None,
        jax.tree_util.tree_map(to_dev, opt_state))
    return opt_state, master, dev_shardings, True


class HostSteppedOffload:
    """Owns the host/NVMe optimizer state and the device<->host exchange for
    the grad-only train path (ZeRO-Offload host step / ZeRO-Infinity).

    Step cost = one fp32-grad download + one bf16-param upload per step
    (params bytes x6 round trip) — ~0.4s/step for a 1B model over a TPU-VM's
    local PCIe; offload throughput follows the host link, by construction.
    """

    def __init__(self, config, master, param_shardings, storage: str,
                 fp16_enabled: bool, has_compression: bool):
        if master is None:
            raise ValueError("optimizer offload requires bf16/fp16 "
                             "compute (fp32 params have no separate masters "
                             "to offload)")
        if fp16_enabled:
            raise NotImplementedError(
                "host-stepped offload currently pairs with bf16 (fp16 dynamic "
                "loss scaling would need host-side overflow handling)")
        if has_compression:
            raise NotImplementedError(
                "compression_training with host-stepped optimizer offload is "
                "not supported: the grad-only step differentiates the raw "
                "params and would silently skip the QAT/pruning transform")
        # the host Adam sweep + any aio threads inherit this affinity —
        # cross-NUMA master/moment traffic is the reference's numactl case
        from ..utils.numa import bind_for_offload

        bind_for_offload()
        opt_cfg = config.optimizer
        opt_type = (opt_cfg.type if opt_cfg else "adamw").lower()
        if opt_type not in ("adam", "adamw"):
            raise NotImplementedError(
                f"host-stepped offload runs the native CPU Adam kernel; "
                f"optimizer {opt_type!r} is not supported on the host path")
        from .swap_tensor import HostAdamOptimizer, SwappedAdamOptimizer

        self.storage = storage
        zc = config.zero_config.offload_optimizer
        p = dict(opt_cfg.params) if opt_cfg else {}
        flat, treedef = jax.tree_util.tree_flatten_with_path(master)
        self.names: List[str] = [jax.tree_util.keystr(path)
                                 for path, _ in flat]
        self.treedef = treedef
        self.param_shardings = param_shardings
        with jax.transfer_guard("allow"):
            masters_np = {n: np.asarray(x, np.float32)
                          for n, (_, x) in zip(self.names, flat)}
        adam_kw = dict(
            lr=p.get("lr", 1e-3), betas=tuple(p.get("betas", (0.9, 0.999))),
            eps=p.get("eps", 1e-8), weight_decay=p.get("weight_decay", 0.0),
            adamw_mode=bool(p.get("adam_w_mode", opt_type == "adamw")))
        if storage == "cpu":
            self.optimizer = HostAdamOptimizer(masters_np, **adam_kw)
            log_dist("ZeRO-Offload: optimizer state in host RAM "
                     f"({self.optimizer.state_bytes() / 1e9:.2f} GB), "
                     "host SIMD Adam step", ranks=[0])
        else:
            self.optimizer = SwappedAdamOptimizer(
                masters_np, zc.nvme_path,
                aio_threads=max(config.aio.thread_count,
                                config.aio.queue_depth // 2, 1),
                pipeline=bool(zc.pipeline_read or zc.pipeline_write),
                **adam_kw)
            log_dist(f"ZeRO-Infinity: optimizer state on NVMe at "
                     f"{zc.nvme_path} "
                     f"({self.optimizer.state_bytes() / 1e9:.2f} GB)",
                     ranks=[0])

    # -- per-step exchange --------------------------------------------------
    def host_step(self, grads_tree, lr: float):
        """fp32 grads (device tree) -> host Adam -> new bf16 param tree."""
        import ml_dtypes

        flat_grads = jax.tree_util.tree_leaves(grads_tree)
        with jax.transfer_guard("allow"):
            grads_np = {n: np.asarray(g, np.float32)
                        for n, g in zip(self.names, flat_grads)}
        bf16 = self.optimizer.step(grads_np, lr=lr)
        leaves = []
        shard_leaves = jax.tree_util.tree_leaves(self.param_shardings)
        for n, sh in zip(self.names, shard_leaves):
            leaves.append(jax.device_put(bf16[n].view(ml_dtypes.bfloat16), sh))
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    # -- checkpointing ------------------------------------------------------
    # Streamed leaf-by-leaf (one master/m/v triple resident at a time), so
    # checkpointing never materializes the full 12 B/param state in host RAM
    # — the same reason the reference streams swapped state to files next to
    # the torch checkpoint (``swap_tensor/optimizer_utils.py``).
    def save_state_files(self, out_dir: str) -> None:
        save_offload_state_files(out_dir, self.names,
                                 self.optimizer.read_state,
                                 int(self.optimizer.step_count))

    def load_state_files(self, in_dir: str) -> None:
        shapes = {n: self.optimizer.state_shape(n) for n in self.names}
        step = load_offload_state_files(in_dir, self.names,
                                        self.optimizer.write_state,
                                        expected_shapes=shapes)
        self.optimizer.step_count = step


def save_offload_state_files(out_dir: str, names, read_state,
                             step_count: int) -> None:
    """One .npy per (leaf, state) + meta.json, written sequentially —
    peak extra host memory is one leaf's fp32 triple."""
    import json
    import os

    os.makedirs(out_dir, exist_ok=True)
    for i, name in enumerate(names):
        master, m, v = read_state(name)
        np.save(os.path.join(out_dir, f"{i:05d}.master.npy"),
                np.asarray(master, np.float32))
        np.save(os.path.join(out_dir, f"{i:05d}.exp_avg.npy"),
                np.asarray(m, np.float32))
        np.save(os.path.join(out_dir, f"{i:05d}.exp_avg_sq.npy"),
                np.asarray(v, np.float32))
    with open(os.path.join(out_dir, "meta.json"), "w") as f:
        json.dump({"step_count": int(step_count), "names": list(names)}, f)


def load_offload_state_files(in_dir: str, names, write_state,
                             expected_shapes=None) -> int:
    """Counterpart of :func:`save_offload_state_files`; returns the saved
    step count.  Validates the leaf list against the engine's and (when
    ``expected_shapes`` maps name->shape) each leaf's shape — leaf names are
    keystr paths, so a same-architecture model of a different width would
    otherwise pass name validation and silently corrupt the swap files."""
    import json
    import os

    with open(os.path.join(in_dir, "meta.json")) as f:
        meta = json.load(f)
    if list(meta["names"]) != list(names):
        raise ValueError(
            "offload checkpoint param-tree mismatch: checkpoint has "
            f"{len(meta['names'])} leaves, engine has {len(names)}")
    for i, name in enumerate(names):
        master = np.load(os.path.join(in_dir, f"{i:05d}.master.npy"))
        if expected_shapes is not None and \
                tuple(master.shape) != tuple(expected_shapes[name]):
            raise ValueError(
                f"offload checkpoint shape mismatch at {name!r}: "
                f"checkpoint {master.shape}, engine "
                f"{tuple(expected_shapes[name])}")
        write_state(
            name, master,
            np.load(os.path.join(in_dir, f"{i:05d}.exp_avg.npy")),
            np.load(os.path.join(in_dir, f"{i:05d}.exp_avg_sq.npy")))
    return int(meta["step_count"])
