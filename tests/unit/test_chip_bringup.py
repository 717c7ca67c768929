"""What can be known about the chip path without a chip — so a kernel the
compiler would refuse, a fallback that would hide a missing TPU, or a smoke
that passes on the CPU fails here before it costs chip time."""
import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_flash_kernels_compile_for_v5e():
    """AOT: the flash forward and backward, ``interpret=False``, compiled by
    the installed libtpu for a compile-only v5e device.  One program — the
    gradient's — holds all three kernels (forward, dq sweep, dkv sweep)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:  # noqa: BLE001 — no libtpu / no such topology
        pytest.skip(f"TPU topology unavailable for AOT compile: {e}")
    # llama-740m's head shape at the smoke's sequence length: the forward
    # sweeps two K blocks and the backward four
    spec = jax.ShapeDtypeStruct(
        (1, 4096, 2, 128), jnp.bfloat16,
        sharding=SingleDeviceSharding(topo.devices[0]))

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=False).astype(jnp.float32).sum()

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        spec, spec, spec)
    assert lowered.as_text().count("tpu_custom_call") == 3
    lowered.compile()           # Mosaic accepts the kernels, or this raises


def test_interpret_mode_is_asked_for_never_inferred(monkeypatch):
    from deepspeed_tpu.ops.pallas.common import (INTERPRET_ENV,
                                                 resolve_interpret)

    assert resolve_interpret() is True          # conftest asked, by name
    assert resolve_interpret(False) is False    # explicit argument wins
    monkeypatch.delenv(INTERPRET_ENV)
    # a CPU host and no request: an error, not a slow imitation
    with pytest.raises(RuntimeError, match="none is attached"):
        resolve_interpret()
    assert resolve_interpret(True) is True


def test_explicit_pallas_raises_when_it_cannot_be_honoured():
    from deepspeed_tpu.models import forward, get_config, init_params

    cfg = get_config("tiny", dtype=jnp.float32)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    ragged = jax.ShapeDtypeStruct((2, 100), jnp.int32)   # 100 % 128 != 0

    def run(impl):
        return jax.eval_shape(
            lambda p, t: forward(cfg, p, t, attn_impl=impl), params, ragged)

    with pytest.raises(ValueError, match="pallas attention requested"):
        run("pallas")
    assert run("auto").shape == (2, 100, cfg.vocab_size)   # auto still chooses


def test_compile_cache_is_placed_from_outside_or_at_one_fixed_path(
        monkeypatch, tmp_path):
    from deepspeed_tpu.utils import compile_cache

    written = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *kv: written.append(kv))
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, str(tmp_path))
    assert compile_cache.place_compile_cache() == str(tmp_path)
    assert written == []                    # env set: no config written

    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV)
    fixed = os.path.join(REPO, ".jax_compile_cache")
    assert compile_cache.place_compile_cache() == fixed
    assert ("jax_compilation_cache_dir", fixed) in written
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_compile_cache/" in f.read().split()


def test_chip_smoke_refuses_the_cpu():
    """The plain invocation on a host without a TPU: non-zero, no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("top_k,slots", [(8, 16), (7, 1)],
                         ids=["decode-16-slots", "rows-not-a-multiple-of-8"])
def test_olmoe_paged_decode_compiles_for_v5e_with_its_experts_in_place(
        top_k, slots):
    """AOT: ``forward_paged`` of OLMoE at the published widths (depth 2),
    one decode tick, compiled by the installed libtpu.  The expert matmuls
    are the compiler's grouped-matmul kernel (``ragged-dot`` Mosaic calls),
    also where ``tokens x k`` is no multiple of 8 (unpadded, that row count
    left the kernel for a dense product the compiler then refused), and no
    op of the program cuts out or copies a layer's expert stack."""
    import re

    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from deepspeed_tpu.models import CausalLM, get_config, init_params

    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:  # noqa: BLE001 — no libtpu / no such topology
        pytest.skip(f"TPU topology unavailable for AOT compile: {e}")
    one = SingleDeviceSharding(topo.devices[0])

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    cfg = get_config("olmoe-1b-7b", num_layers=2, moe_top_k=top_k)
    model = CausalLM(cfg)
    params = jax.tree_util.tree_map(
        lambda a: S(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    pool = S((2, 1 + slots * 2, 128, 16, 128), jnp.bfloat16)   # far under a stack
    text = jax.jit(model.apply_paged).lower(
        params, S((slots, 1), jnp.int32), {"k": pool, "v": pool},
        S((slots, 2), jnp.int32), S((slots,), jnp.int32),
        S((slots, 1), jnp.bool_)).compile().as_text()
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) == 3
    expert_stack = 64 * 2048 * 1024
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]+)\]", line)
        if m and int(np.prod([int(d) for d in m.group(1).split(",")])) \
                >= expert_stack:
            assert re.search(r" (parameter|get-tuple-element|bitcast)\(",
                             line), line[:200]


@pytest.mark.parametrize(
    "name,overrides,slots,pool_order",
    [("opt-1.3b", {"activation": "relu"}, 8, (0, 1, 3, 4, 2)),
     ("olmoe-1b-7b", {}, 16, None)],
    ids=["opt-64-wide-heads-page-minor", "olmoe-128-wide-heads-row-major"])
def test_paged_decode_reads_a_bounded_pool_in_place_on_v5e(
        name, overrides, slots, pool_order):
    """AOT: one decode tick of ``forward_paged`` at the published widths
    (depth 2) and the benchmark's geometry (16 pages of 128 rows a slot),
    compiled by the installed libtpu with the pool donated.  The read's loop
    sits two computations deep, where the compiler takes an operand
    row-major in its logical shape; handed the pool in the order the device
    stores it (``pool_order``: the TPU puts the page rows of a 64-wide head
    minor-most, keeps a 128-wide one row-major) nothing is re-laid out: no
    op's result is the size of a pool leaf except the in-place page
    scatters, no gather is wider than one step's (slot, page) pairs, and
    the program's temporaries are megabytes (PERF.md, PR 25, PR 27 and
    PR 29)."""
    import re

    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from deepspeed_tpu.models import get_config, init_params
    from deepspeed_tpu.models.transformer import (forward_paged,
                                                  paged_read_pairs)

    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:  # noqa: BLE001 — no libtpu / no such topology
        pytest.skip(f"TPU topology unavailable for AOT compile: {e}")
    one = SingleDeviceSharding(topo.devices[0])

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    cfg = get_config(name, num_layers=2, **overrides)
    params = jax.tree_util.tree_map(
        lambda a: S(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    maxp, page = 16, 128
    page_elems = page * cfg.kv_heads * cfg.dims_per_head
    pool = S((2, 1 + slots * maxp, page, cfg.kv_heads, cfg.dims_per_head),
             jnp.bfloat16)

    def tick(params, k, v, tokens, table, start, mask):
        logits, cache = forward_paged(cfg, params, tokens, {"k": k, "v": v},
                                      table, start, mask,
                                      pool_order=pool_order)
        return jnp.argmax(logits[:, -1], -1), cache["k"], cache["v"]

    compiled = jax.jit(tick, donate_argnums=(1, 2)).lower(
        params, pool, pool, S((slots, 1), jnp.int32),
        S((slots, maxp), jnp.int32), S((slots,), jnp.int32),
        S((slots, 1), jnp.bool_)).compile()
    row_wide = slots * maxp * page_elems
    read = paged_read_pairs(slots, maxp) * page_elems
    # megabytes: 1.6 MB (OPT) and 2.8 MB (OLMoE) with the flat pair list
    # (PR 29; 1.3 and 2.0 with every slot read to the longest, PR 28), where
    # one re-laid-out pool leaf is gigabytes
    assert compiled.memory_analysis().temp_size_in_bytes < 8e6
    gathers = set()
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = bf16\[([\d,]+)\]", line)
        if not m:
            continue
        dims = [int(d) for d in m.group(1).split(",")]
        if sorted(dims[-3:]) != sorted([page, cfg.kv_heads,
                                        cfg.dims_per_head]):
            continue                     # not pages of K/V
        n = int(np.prod(dims))
        if " gather(" in line:
            gathers.add(n)
        if n >= row_wide:
            assert re.search(r" (parameter|get-tuple-element|bitcast|while)"
                             r"\(| scatter\(|/scatter\"", line), line[:240]
    assert read in gathers and max(gathers) == read
