"""Stable-Diffusion-style denoising loop on the native diffusion family.

The reference accelerates a live ``diffusers`` pipeline by swapping its
UNet/VAE for CUDA-graph wrappers (``deepspeed.init_inference`` →
``generic_injection``, module_inject/replace_module.py:310).  Here the
models themselves are native JAX (models/diffusion.py) and the DSUNet/DSVAE
adapters keep the exact pipeline calling convention, so this example IS the
pipeline: text-free classifier-free-guidance-less DDIM over random
conditioning — small enough to run on the virtual mesh, structurally the
real thing.  With a real diffusers checkpoint, load weights via
``DSUNet.from_diffusers(pipe.unet)`` / ``load_diffusers_state_dict``.

Run:
    python examples/stable_diffusion.py --steps 10 --size 16
"""
import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.diffusers import DSUNet, DSVAE
from deepspeed_tpu.models.diffusion import TINY_UNET, TINY_VAE
from deepspeed_tpu.utils.compile_cache import place_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--size", type=int, default=16, help="latent H=W")
    ap.add_argument("--batch", type=int, default=1)
    args = ap.parse_args()
    place_compile_cache()

    unet = DSUNet(TINY_UNET, data_format="NHWC")
    vae = DSVAE(TINY_VAE, data_format="NHWC")

    rng = jax.random.PRNGKey(0)
    latents = jax.random.normal(
        rng, (args.batch, args.size, args.size, TINY_UNET.in_channels))
    ctx = jax.random.normal(jax.random.PRNGKey(1),
                            (args.batch, 8, TINY_UNET.cross_attention_dim))

    # DDIM over a uniform timestep subset
    alphas = jnp.cumprod(1.0 - jnp.linspace(1e-4, 0.02, 1000))
    ts = np.linspace(999, 0, args.steps).astype(np.int32)
    x = latents
    t0 = time.perf_counter()
    for i, t in enumerate(ts):
        eps = unet(x, int(t), ctx, return_dict=False)[0]
        a_t = alphas[int(t)]
        a_prev = alphas[int(ts[i + 1])] if i + 1 < len(ts) else jnp.float32(1.0)
        x0 = (x - jnp.sqrt(1 - a_t) * eps) / jnp.sqrt(a_t)
        x = jnp.sqrt(a_prev) * x0 + jnp.sqrt(1 - a_prev) * eps
    jax.block_until_ready(x)
    dt = time.perf_counter() - t0
    img = vae.decode(x / TINY_VAE.scaling_factor, return_dict=False)[0]
    img = np.asarray(img)
    print(f"denoised {args.steps} steps in {dt:.2f}s "
          f"({dt / args.steps * 1000:.0f} ms/step incl. first-step compile); "
          f"decoded image {img.shape}, range [{img.min():.2f}, {img.max():.2f}]")
    assert np.isfinite(img).all()


if __name__ == "__main__":
    main()
