"""Real-chip autotuner session: the model-based/grid tuner against hardware.

Runs a small but real space on the chip — llama-374m, ZeRO-1, micro-batch
ladder x remat policy — and writes the records + best config, exactly the
files the reference's ``autotuning_results/`` layout produces (reference
``autotuning/autotuner.py:404 tune()``).

    python tools/autotune_tpu.py [--results_dir autotuning_results]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="llama-374m")
    ap.add_argument("--seq_len", type=int, default=2048)
    ap.add_argument("--results_dir",
                    default="autotuning_results")
    ap.add_argument("--tuner_type", default="gridsearch",
                    choices=["gridsearch", "random", "model_based"])
    args = ap.parse_args()

    from deepspeed_tpu.autotuning import autotune
    from deepspeed_tpu.models import CausalLM

    base_config = {
        "train_micro_batch_size_per_gpu": 4,
        "optimizer": {"type": "adamw",
                      "params": {"lr": 1e-4, "mu_dtype": "bfloat16"}},
        "bf16": {"enabled": True},
        "steps_per_print": 10 ** 9,
        "autotuning": {
            "enabled": True,
            "tuner_type": args.tuner_type,
            "mbs_candidates": [4, 8, 16],
            "zero_stages": [1],
            "remat_policies": [None, "save_attn"],
            "start_profile_step": 2,
            "end_profile_step": 6,
            "results_dir": args.results_dir,
        },
    }

    rng = np.random.default_rng(0)

    def batch_factory(engine):
        seq = engine.autotune_seq_len or args.seq_len
        vocab = engine.model.config.vocab_size
        return {"input_ids": rng.integers(
            0, vocab, (engine.train_batch_size, seq)).astype(np.int32)}

    best, records = autotune(
        model_factory=lambda: CausalLM(args.model, max_seq_len=args.seq_len),
        base_config=base_config,
        batch_factory=batch_factory,
    )
    ok = [r for r in records if r.status == "ok"]
    print(json.dumps({
        "n_trials": len(records),
        "n_ok": len(ok),
        "best": {k: v for k, v in (best or {}).items()
                 if k in ("train_micro_batch_size_per_gpu",
                          "zero_optimization", "_remat_policy")},
        "best_metric_samples_per_sec":
            max((r.metric_val for r in ok), default=0.0),
        "results_dir": args.results_dir,
    }))
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
