#!/usr/bin/env python3
"""A hash of the lowered serving programs of a tiny model of each kind of
cache: a witness that a host-side change left every compiled program as it
was.  Run it on two checkouts and compare the lines:

    JAX_PLATFORMS=cpu PYTHONPATH=<checkout> python tools/program_hashes.py

One line a kind: the sha256 (12 hex digits) of the decode program's and the
16-token prefill bucket's StableHLO text (no source locations), and
``program_inventory()`` after one request; for the kinds with a mixer
(``TRAINED``) also that of the training forward, the loss and its gradients
through ``forward``.  Not pinned anywhere: any change to the model's forward
moves them."""
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.inference.serving import Request
from deepspeed_tpu.models import (CausalLM, cross_entropy_loss, forward,
                                  get_config, init_params)
from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh

F32 = dict(vocab_size=256, max_seq_len=512, dtype=jnp.float32)
MOE = dict(hidden_size=64, intermediate_size=32, num_heads=4, num_experts=8,
           moe_top_k=3, **F32)
KINDS = {
    "uniform": ("olmoe-1b-7b", dict(num_layers=2, **MOE)),
    "grouped": ("olmoe-1b-7b", dict(num_layers=3, dense_layers=1, **MOE)),
    "window": ("mimo-v2.5", dict(
        num_layers=7, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_heads=8, num_kv_heads=2,
        window_kv_heads=4, head_dim=24, v_head_dim=16, rotary_dim=8,
        window_size=16, num_experts=16, moe_experts_held=4, moe_top_k=3,
        **F32)),
    # Trinity's form: a gate on attention, rotary by kind, four norms, a
    # shared expert, a window longer than a page
    "gated_window": ("trinity-large-preview", dict(
        num_layers=8, layer_pattern=("window", "window", "full", "window") * 2,
        dense_layers=1, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_heads=8, num_kv_heads=2, head_dim=16,
        window_size=16, num_experts=16, moe_experts_held=4, moe_top_k=3,
        **F32)),
    "latent": ("kanana-2-30b-a3b", dict(
        num_layers=4, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_heads=4, head_dim=24, v_head_dim=16,
        rotary_dim=8, kv_lora_rank=32, num_experts=16, moe_experts_held=4,
        moe_top_k=3, **F32)),
    "state": ("falcon-h1-34b", dict(
        num_layers=2, hidden_size=64, intermediate_size=96, num_heads=4,
        num_kv_heads=2, head_dim=16, ssm_heads=4, ssm_head_dim=8,
        ssm_state=16, ssm_groups=2, ssm_chunk=8, **F32)),
    # Granite's form: "ssm" and "full" layers, an expert layer behind each
    "ssm_moe": ("granite-4.0-h-small", dict(
        num_layers=7, hidden_size=64, intermediate_size=32, num_heads=4,
        num_kv_heads=2, head_dim=16, ssm_heads=4, ssm_head_dim=8,
        ssm_state=16, ssm_groups=1, ssm_chunk=8, num_experts=8, moe_top_k=3,
        **F32)),
    "delta": ("olmo-hybrid-7b", dict(
        num_layers=4, hidden_size=64, intermediate_size=96, num_heads=4,
        num_kv_heads=4, head_dim=16, linear_heads=4, linear_key_dim=8,
        linear_value_dim=64, linear_chunk=8, **F32)),
    "conv": ("lfm2-8b-a1b", dict(
        num_layers=6, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_heads=4, num_kv_heads=2, head_dim=16,
        num_experts=8, moe_top_k=3, **F32)),
    # Ouro's form: one stack run loop_passes times
    "looped": ("ouro-2.6b", dict(
        num_layers=3, hidden_size=64, intermediate_size=96, num_heads=4,
        num_kv_heads=4, head_dim=16, **F32)),
    # Nemotron-H's form: every layer ONE sublayer (a mixer, attention or an
    # expert layer alone), the experts in a latent, squared ReLU
    "latent_moe": ("nemotron-3-super-120b-a12b", dict(
        num_layers=11, hidden_size=64, intermediate_size=24,
        moe_intermediate_size=24, moe_latent_size=32, num_heads=4,
        num_kv_heads=2, head_dim=16, ssm_heads=4, ssm_head_dim=8,
        ssm_state=16, ssm_groups=2, ssm_chunk=8, num_experts=16,
        moe_experts_held=4, moe_top_k=3, **F32)),
}
# the kinds whose layers run a mixer: their training forward is hashed too
TRAINED = ("state", "ssm_moe", "delta", "conv")


def sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:12]


def train_hash(cfg, params) -> str:
    """The loss of a 12-token batch and its gradients, lowered."""
    tokens = jnp.zeros((2, 12), jnp.int32)

    def loss(p):
        return cross_entropy_loss(forward(cfg, p, tokens), tokens)

    return sha(jax.jit(jax.value_and_grad(loss)).lower(params).as_text())


def main() -> None:
    mesh = initialize_mesh(MeshLayout(), devices=jax.devices()[:1])
    for kind, (name, kw) in KINDS.items():
        cfg = get_config(name, **kw)
        params = init_params(cfg, jax.random.PRNGKey(0))
        train = (f" train {train_hash(cfg, params)}" if kind in TRAINED
                 else "")
        engine = deepspeed_tpu.init_inference(
            model=CausalLM(cfg), params=params, dtype="fp32", mesh=mesh)
        sv = engine.serving(b_slots=3, page_size=8, max_model_len=96)
        sv.run([Request(rid="r", input_ids=np.arange(5, dtype=np.int32),
                        max_new_tokens=3)])
        ex, B = sv._exec, sv.b_slots
        decode = ex._decode_prog.lower(
            ex.params, ex.pools,
            jax.tree_util.tree_map(jnp.asarray, sv._tables()),
            jnp.zeros((B,), jnp.int32), ex.fed(np.zeros((B,), np.int32)),
            jnp.zeros((B,), bool), *sv._lanes_jnp()).as_text()
        one = lambda dtype: np.zeros((1,), dtype)     # noqa: E731
        prefill = ex._prefill_progs[16].lower(
            ex.params, ex.pools,
            jax.tree_util.tree_map(jnp.asarray, sv._tables(0)),
            jnp.zeros((1, 16), jnp.int32), jnp.int32(5), jnp.int32(0),
            one(np.float32), one(np.int32), one(np.float32), one(np.uint32),
            *((jnp.int32(0),) if ex.layout.stateful else ())).as_text()
        print(f"{kind:8s} decode {sha(decode)} prefill_16 {sha(prefill)}"
              f"{train} inventory {json.dumps(sv.program_inventory())}", flush=True)


if __name__ == "__main__":
    main()
