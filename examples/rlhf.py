"""RLHF actor loop: ZeRO-sharded LoRA training with fused-weight generation.

The DeepSpeed-Chat actor contract (reference ``runtime/hybrid_engine.py`` +
DeepSpeedExamples step3): one engine both *generates* rollouts and *trains*
on them, flipping modes every iteration.  Here the actor trains LoRA
adapters over a frozen base model under ZeRO-3; ``generate()`` fuses the
adapters into the base weights (one jitted ``base + A@B·scale``) and decodes
with the KV-cache program.

``--serving`` routes the rollouts through the hybrid rollout subsystem
instead (docs/HYBRID.md): batched, supervised generation through the
paged continuous-batching serving engine over the live fused weights,
with the weight-epoch flip publishing each round's update — the
production actor path.

Run (virtual 8-chip mesh):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/rlhf.py --model tiny --iters 2 [--serving]
"""
import argparse

import numpy as np

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.runtime.hybrid_engine import DeepSpeedHybridEngine
from deepspeed_tpu.runtime.lora import LoRAConfig, LoRAModel
from deepspeed_tpu.utils.compile_cache import place_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="tiny")
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--prompt_len", type=int, default=16)
    ap.add_argument("--new_tokens", type=int, default=8)
    ap.add_argument("--batch", type=int, default=None,
                    help="global rollout batch (default: dp world size)")
    ap.add_argument("--lora_rank", type=int, default=4)
    ap.add_argument("--serving", action="store_true",
                    help="rollouts through the paged serving engine "
                         "(RolloutEngine, docs/HYBRID.md) instead of "
                         "sequential generate()")
    args = ap.parse_args()
    place_compile_cache()

    base = CausalLM(args.model, max_seq_len=128)
    base_params = base.init_fn(jax.random.PRNGKey(0))
    actor_model = LoRAModel(base, base_params,
                            LoRAConfig(rank=args.lora_rank))

    engine, _, _, _ = deepspeed_tpu.initialize(model=actor_model, config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3},
        "bf16": {"enabled": True},
    })
    hybrid = DeepSpeedHybridEngine(engine)
    S = args.prompt_len + args.new_tokens
    rollout_engine = None
    if args.serving:
        # the hybrid rollout subsystem: batched rollouts through the paged
        # serving engine over the live fused weights (docs/HYBRID.md)
        rollout_engine = hybrid.rollout_engine(
            b_slots=4, max_model_len=128, rollout_seq_len=S)

    B = args.batch or engine.train_batch_size
    rng = np.random.default_rng(0)
    for it in range(args.iters):
        prompts = rng.integers(0, base.config.vocab_size,
                               (B, args.prompt_len)).astype(np.int32)
        if rollout_engine is not None:
            # 1) publish this iteration's weight epoch (fuses LoRA once)
            #    and collect the rollout batch through the serving engine
            rollout_engine.publish_weights()
            results = rollout_engine.rollout(
                prompts, max_new_tokens=args.new_tokens)
            seqs = rollout_engine.training_batch(results)["input_ids"]
            rollout_shape = (len(results), args.new_tokens)
        else:
            # 1) rollout: sequential generate with fused LoRA weights
            hybrid.fuse_lora_weight()
            rollout = np.asarray(hybrid.generate(
                prompts, max_new_tokens=args.new_tokens))
            hybrid.unfuse_lora_weight()
            rollout_shape = rollout.shape
            seqs = np.concatenate(
                [prompts, rollout[:, -args.new_tokens:]], axis=1)

        # 2) score (toy reward: prefer token diversity) and build the PPO-ish
        #    batch — a real actor would use a reward model + advantages here

        # 3) train on the rollouts (weighted LM surrogate)
        loss = hybrid.train_batch(batch={"input_ids": seqs})
        print(f"iter {it}: rollout {rollout_shape} loss {float(loss):.4f}",
              flush=True)
    if rollout_engine is not None:
        h = rollout_engine.health()
        print(f"serving rollouts: epoch {h['weight_epoch']}, "
              f"{h['rollout_tokens_total']} token(s), "
              f"{h['kv_flushed_pages_total']} stale page(s) flushed")

    hybrid.report_generate_latency()
    lora_norm = sum(float(jnp.abs(ab["B"]).sum())
                    for ab in jax.tree_util.tree_leaves(
                        engine.state.params,
                        is_leaf=lambda x: isinstance(x, dict) and "B" in x))
    print(f"done: adapters updated (sum|B| = {lora_norm:.4f} > 0)")
    assert lora_norm > 0.0, "LoRA B factors never left zero — no training"


if __name__ == "__main__":
    main()
