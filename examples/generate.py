"""KV-cached generation (reference init_inference usage shape).

    python examples/generate.py                       # native tiny model
    python examples/generate.py --hf /path/to/hf_dir  # HF checkpoint via
                                                      # the injection policies
"""
import argparse

import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.utils.compile_cache import place_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="llama-374m")
    ap.add_argument("--hf", default=None,
                    help="HF model dir (llama/mistral/gpt2/opt/gptj/neox)")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt_len", type=int, default=64)
    ap.add_argument("--new_tokens", type=int, default=64)
    args = ap.parse_args()
    place_compile_cache()

    if args.hf:
        engine = deepspeed_tpu.init_inference(model=args.hf)
        vocab = engine.model.config.vocab_size
    else:
        import jax

        model = CausalLM(args.model, max_seq_len=args.prompt_len + args.new_tokens)
        params = model.init_fn(jax.random.PRNGKey(0))
        engine = deepspeed_tpu.init_inference(model=model, params=params)
        vocab = model.config.vocab_size

    rng = np.random.default_rng(0)
    prompt = rng.integers(0, vocab, (args.batch, args.prompt_len)).astype(np.int32)
    out = engine.generate(prompt, max_new_tokens=args.new_tokens,
                          greedy=False, temperature=0.8, top_p=0.95)
    print("generated shape:", np.asarray(out).shape)


if __name__ == "__main__":
    main()
