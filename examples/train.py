"""Minimal training loop — the reference's canonical usage shape:

    engine, optimizer, _, scheduler = deepspeed.initialize(...)
    for batch in loader:
        loss = engine.train_batch(batch)        # fused fwd+bwd+step
        # or the reference loop: engine.forward / engine.backward / engine.step

Run single-host:     python examples/train.py
Multi-host:          deepspeed-tpu --hostfile hosts examples/train.py
Simulated 4-proc:    deepspeed-tpu --simulate 4 examples/train.py
"""
import argparse
import os

import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.utils.compile_cache import place_compile_cache


def synthetic_batches(vocab, batch, seq, steps, seed=0):
    rng = np.random.default_rng(seed)
    for _ in range(steps):
        yield {"input_ids": rng.integers(0, vocab, (batch, seq)).astype(np.int32)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="llama-374m")
    ap.add_argument("--seq_len", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--ckpt_dir", default=None)
    ap = deepspeed_tpu.add_config_arguments(ap)
    args = ap.parse_args()
    place_compile_cache()

    deepspeed_tpu.init_distributed()
    model = CausalLM(args.model, max_seq_len=args.seq_len)
    engine, _, _, scheduler = deepspeed_tpu.initialize(
        args=args, model=model,
        config=args.deepspeed_config or os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "ds_config.json"))

    for step, batch in enumerate(synthetic_batches(
            model.config.vocab_size, engine.train_batch_size,
            args.seq_len, args.steps)):
        loss = engine.train_batch(batch=batch)
        if step % 5 == 0:
            print(f"step {step}  loss {float(loss):.4f}  "
                  f"lr {engine.get_lr()[0]:.2e}")
    if args.ckpt_dir:
        engine.save_checkpoint(args.ckpt_dir)
        print(f"checkpoint saved to {args.ckpt_dir}")


if __name__ == "__main__":
    main()
