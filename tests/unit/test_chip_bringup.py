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


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:  # noqa: BLE001 — no libtpu / no such topology
        pytest.skip(f"TPU topology unavailable for AOT compile: {e}")


@pytest.fixture(scope="module")
def one_v5e_chip(v5e_2x2):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_2x2.devices[0])


def _placed_as_the_executor_places(cfg, chip):
    """``(params, in_shardings entry)`` for an AOT compile of a tick: the
    shapes of ``cfg``'s bfloat16 tree held as ``MeshExecutor`` holds it (a
    leaf a layer where the forward walks the layers in Python,
    ``per_layer_leaves``), and ``Layout.AUTO`` on every leaf, which is how
    the executor asks the compiler for the layouts it then places the tree
    in (``MeshExecutor._tick_formats``): the program compiled so is the
    tick at the executor's placement."""
    from jax.experimental.layout import Format, Layout

    from deepspeed_tpu.models import init_params
    from deepspeed_tpu.models.transformer import per_layer_leaves

    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16, sharding=chip),
        jax.eval_shape(lambda: per_layer_leaves(
            cfg, init_params(cfg, jax.random.PRNGKey(0)))[0]))
    return params, jax.tree_util.tree_map(
        lambda a: Format(Layout.AUTO, chip), params)


def _materialised(text):
    """``(name, opcode, [(dims, layout), ...])`` of every bfloat16 result
    of every instruction of a compiled module that is not inside a fusion's
    body: what the program writes somewhere, a tuple's parts each."""
    import re

    bodies = set(re.findall(r" fusion\([^\n]*calls=%([\w.\-]+)", text))
    inside = None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            inside = head.group(1)
            continue
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\(", line)
        if m and inside not in bodies:
            yield m.group(1), m.group(3), [
                ([int(d) for d in dims.split(",")], layout)
                for dims, layout in re.findall(
                    r"bf16\[([\d,]+)\]\{([^}]*)\}", m.group(2))]


def test_hybrid_paged_decode_keeps_both_pools_in_place_on_v5e(one_v5e_chip):
    """AOT: ``jit_serve_decode``'s body for MiMo-V2.5 at the benchmark's cut
    (published widths, 7 layers, 16 experts held, 32 slots of 128 pages),
    compiled by the installed libtpu with the pool of each kind donated and
    handed over in the order the chip stores it (observed on the v5e, PR 30:
    the 192-wide keys page-rows minor-most, the 128-wide values row-major, the
    full layers' 4 x 128 values kept head-major), and the weights as the
    executor places them (PR 35).  No op's result is the size
    of a leaf of either pool but the in-place page scatters, the window
    layers' gathers are a step's (slot, page) pairs of a two-page ring, and
    the program's temporaries are a twentieth of one pool leaf (with the
    values of the full layers page-major they were 2 x 1 GB of copies; with
    a stack a group, 0.64 GB: one fusion cut the five window layers' ``wq``
    out of their stack and wrote them back every tick).  Nothing the
    program writes outside on-chip memory is of a ``wq`` leaf's size: every
    projection is read once, by its product."""
    import re

    import numpy as np

    from deepspeed_tpu.models import get_config
    from deepspeed_tpu.models.transformer import (forward_paged,
                                                  paged_read_pairs)

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    cfg = get_config("mimo-v2.5", num_layers=7, moe_experts_held=16,
                     vocab_size=19072)
    params, auto = _placed_as_the_executor_places(cfg, one_v5e_chip)
    assert isinstance(params["layers"]["window_moe"]["wq"], tuple)
    assert not isinstance(params["layers"]["window_moe"]["w_up"], tuple)
    slots, maxp, page = 32, 128, 128
    pages, ring = 1 + slots * maxp, 1 + slots * 2
    cache = {"k": S((2, pages, page, 4, 192), jnp.bfloat16),
             "v": S((2, pages, 4, page, 128), jnp.bfloat16),
             "k_window": S((5, ring, page, 8, 192), jnp.bfloat16),
             "v_window": S((5, ring, page, 8, 128), jnp.bfloat16)}
    page_minor = (0, 1, 3, 4, 2)
    order = {"k": page_minor, "v": None, "k_window": page_minor,
             "v_window": None}

    def tick(params, cache, tokens, tables, start, mask):
        logits, cache, counts = forward_paged(
            cfg, params, tokens, cache, tables, start, mask,
            expert_counts=True, pool_order=order)
        return jnp.argmax(logits[:, -1], -1), counts, cache

    compiled = jax.jit(tick, donate_argnums=(1,),
                       in_shardings=(auto,) + (None,) * 5).lower(
        params, cache, S((slots, 1), jnp.int32),
        (S((slots, maxp), jnp.int32), S((slots, 2), jnp.int32)),
        S((slots,), jnp.int32), S((slots, 1), jnp.bool_)).compile()
    # 0.045 GB (0.117 with a leaf a layer in the default layouts, 0.644 with
    # a stack a group: PERF.md, PR 35)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2e9
    text = compiled.as_text()
    wq = int(np.prod(params["layers"]["window_moe"]["wq"][0].shape))
    for name, opcode, results in _materialised(text):
        for dims, layout in results:
            if opcode in ("fusion", "copy") and "S(1)" not in layout:
                assert int(np.prod(dims)) != wq, (name, dims, layout)
    leaves = {(page, 4, 192): 2 * pages, (page, 4, 128): 2 * pages,
              (page, 8, 192): 5 * ring, (page, 8, 128): 5 * ring}
    gathers = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = bf16\[([\d,]+)\]", line)
        if not m:
            continue
        dims = [int(d) for d in m.group(1).split(",")]
        kind = next((k for k in leaves if sorted(dims[-3:]) == sorted(k)),
                    None)
        if kind is None or len(dims) < 4:
            continue                     # not pages of K/V
        n = int(np.prod(dims))
        if " gather(" in line:
            gathers.setdefault(kind, set()).add(n)
        if n >= leaves[kind] * int(np.prod(kind)):
            assert re.search(r" (parameter|get-tuple-element|bitcast|while)"
                             r"\(| scatter\(|/scatter\"", line), line[:240]
    for kind, sizes in gathers.items():
        pairs = paged_read_pairs(slots, maxp if kind[1] == 4 else 2)
        assert max(sizes) <= pairs * int(np.prod(kind)), kind


def test_state_decode_tick_holds_one_kernel_over_the_leaf_on_v5e(
        one_v5e_chip, monkeypatch):
    """AOT: ``jit_serve_decode``'s body for Falcon-H1 at the published widths
    (a state of 32 x 128 x 256 float32 a slot a layer; depth 2, 8 slots),
    compiled by the installed libtpu with the cache donated and the step
    chosen as on a TPU (the test answers for the backend; the kernel is
    compiled, not interpreted).  Mosaic accepts the one-pass kernel; the
    layer scan's body holds ONE ``tpu_custom_call`` over the state (and one
    that reads K/V by pages); the ``ssm_state`` leaf
    goes into it and comes out of it and NOTHING else of the program reads
    or writes a tensor of the state rows' size: no update fusion, no
    ``multiply_reduce`` over ``[B,32,128,256]``, no copy of the leaf (an
    alias the compiler could not honour would copy 2 GB a layer in the
    benchmark's cell and push it over the chip); temporaries are
    megabytes."""
    import re

    import numpy as np

    from deepspeed_tpu.models import CausalLM, get_config, init_params
    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.models.mixers import common as MX
    from deepspeed_tpu.models.mixers import ssm as SSM

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    monkeypatch.setattr(MX, "_pallas_interpret", lambda: False)
    depth, slots = 2, 8
    cfg = get_config("falcon-h1-34b", num_layers=depth, vocab_size=16384)
    assert SSM.ssm_step_path(cfg) == "one_pass"
    params = jax.tree_util.tree_map(
        lambda a: S(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    cache = jax.tree_util.tree_map(
        lambda a: S(a.shape, a.dtype), jax.eval_shape(
            lambda: CausalLM(cfg).init_paged_cache(
                1 + slots * 16, 128, dtype=jnp.bfloat16, slots=slots)))
    assert cache["ssm_state"].shape == (depth, slots, 32, 128, 256)

    def tick(params, cache, tokens, table, start, mask):
        logits, cache = T.forward_paged(cfg, params, tokens, cache, table,
                                        start, mask)
        return jnp.argmax(logits[:, -1], -1), cache

    compiled = jax.jit(tick, donate_argnums=(1,)).lower(
        params, cache, S((slots, 1), jnp.int32), S((slots, 16), jnp.int32),
        S((slots,), jnp.int32), S((slots, 1), jnp.bool_)).compile()
    # 2.3 MB with _ssm_step at this size; the kernel adds no state-sized one
    assert compiled.memory_analysis().temp_size_in_bytes < 8e6
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in ln]
    # the state's step, and since PR 52 the read of the 4 x 128 K/V leaves
    # by pages (ops/pallas/paged_read.py), once each in the scan's body
    assert len(calls) == 2
    assert sum("/ssm_step/pallas_call" in ln for ln in calls) == 1
    assert sum("/paged_read/pallas_call" in ln for ln in calls) == 1
    rows = slots * 32 * 128 * 256
    bodies = set(re.findall(r" fusion\([^\n]*calls=%([\w.\-]+)", text))
    passes = {"parameter", "get-tuple-element", "bitcast", "while", "tuple",
              "custom-call"}
    inside, state_sized = None, set()
    lines = []
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            inside = head.group(1)
            continue
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\((.*)",
                     line)
        if not m or inside in bodies:
            continue
        name, result, opcode, rest = m.groups()
        lines.append((name, opcode, rest))
        if any(int(np.prod([int(d) for d in dims.split(",")])) >= rows
               for dims in re.findall(r"f32\[([\d,]+)\]", result)):
            assert opcode in passes, line[:240]
            state_sized.add(name)
    assert state_sized
    for name, opcode, rest in lines:
        operands = set(re.findall(r"%([\w.\-]+)", rest.split("), ")[0]))
        if operands & state_sized:
            assert opcode in passes, (name, opcode)


def test_looped_decode_tick_writes_its_rows_where_they_lie_on_v5e(
        one_v5e_chip, monkeypatch):
    """AOT: ``jit_serve_decode``'s body for Ouro at the published widths (K
    and V leaves of 16 x 128 a row; depth 2 x 4 passes, 16 slots of 8 pages
    over the benchmark's 40), compiled by the installed libtpu with the pool
    donated and the K/V write chosen as on a TPU (the test answers for the
    backend; the kernel is compiled, not interpreted).  Mosaic accepts the
    row write; the layer scan's body holds its TWO ``tpu_custom_call``s (K,
    V) and the one that reads both by pages; each leaf goes into its call and
    comes out of it through both scans and NOTHING else of the program writes a tensor of a leaf's size: no page
    scatter, no copy into another order in front of the call or behind it
    (an alias the compiler could not honour would copy 4 GB a (pass, layer) in
    the benchmark's cell); temporaries are a third of one leaf."""
    import re

    import numpy as np

    from deepspeed_tpu.models import CausalLM, get_config, init_params
    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.models.mixers import common as MX

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    monkeypatch.setattr(MX, "_pallas_interpret", lambda: False)
    depth, slots, pages = 2, 16, 40
    cfg = get_config("ouro-2.6b", num_layers=depth)
    params = jax.tree_util.tree_map(
        lambda a: S(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    cache = jax.tree_util.tree_map(
        lambda a: S(a.shape, a.dtype), jax.eval_shape(
            lambda: CausalLM(cfg).init_paged_cache(pages, 128,
                                                   dtype=jnp.bfloat16)))
    assert cache["k"].shape == (4 * depth, pages, 128, 16, 128)
    assert T.kv_write_paths(cfg, cache, None) == {"k": "row", "v": "row"}

    def tick(params, cache, tokens, table, start, mask):
        logits, cache = T.forward_paged(cfg, params, tokens, cache, table,
                                        start, mask)
        return jnp.argmax(logits[:, -1], -1), cache

    compiled = jax.jit(tick, donate_argnums=(1,)).lower(
        params, cache, S((slots, 1), jnp.int32), S((slots, 8), jnp.int32),
        S((slots,), jnp.int32), S((slots, 1), jnp.bool_)).compile()
    # 52.0 MB, the page merge's program 52.1: a layer's weight slices
    # (PERF.md S19d), a third of one leaf
    assert compiled.memory_analysis().temp_size_in_bytes < 64e6
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in ln]
    # K's and V's row, and since PR 52 their read by pages
    # (ops/pallas/paged_read.py), once each in the scans' body
    assert len(calls) == 3
    assert sum("/kv_row_write/pallas_call" in ln for ln in calls) == 2
    assert sum("/paged_read/pallas_call" in ln for ln in calls) == 1
    assert T.kv_read_paths(cfg, cache, None) == {"k": "pages", "v": "pages"}
    leaf = int(np.prod(cache["k"].shape))
    passes = {"parameter", "get-tuple-element", "bitcast", "while", "tuple",
              "custom-call"}
    leaf_sized = [(name, opcode) for name, opcode, results in
                  _materialised(text)
                  if any(int(np.prod(dims)) >= leaf for dims, _ in results)]
    assert leaf_sized and {op for _, op in leaf_sized} <= passes, leaf_sized
    assert "custom-call" in {op for _, op in leaf_sized}


@pytest.mark.parametrize("width", [128, 256])
@pytest.mark.parametrize("heads", [2, 4, 8, 16, 24])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_row_write_compiles_for_every_row_its_tile_plan_takes(
        one_v5e_chip, dtype, heads, width):
    """AOT: ``kv_row_write`` alone over a donated leaf of every class of row
    ``row_block`` admits (a decode program that takes the row write has no
    other path to fall back on when Mosaic refuses its leaf, so the plan
    admits only what is compiled here).  Mosaic accepts it, and the compiler
    keeps the leaf row-major and in place: no copy in front of the call or
    behind it, no temporary."""
    from deepspeed_tpu.ops.pallas.kv_row_write import kv_row_write, row_block

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    leaf = S((12, 128, heads, width), dtype)
    assert row_block(leaf.shape, leaf.dtype) == (heads, width)
    compiled = jax.jit(
        lambda a, new, pages, rows: kv_row_write(a, new, pages, rows,
                                                 interpret=False),
        donate_argnums=(0,)).lower(
            leaf, S((16, heads, width), dtype), S((16,), jnp.int32),
            S((16,), jnp.int32)).compile()
    assert tuple(compiled.input_formats[0][0].layout.major_to_minor) == (
        0, 1, 2, 3)
    assert " copy(" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes == 0


_LATENT_TICKS = {}          # compiled once a (stored order, backend's answer)


def _latent_tick(chip, pool_order=None):
    """Kanana-2's decode tick at the benchmark's widths and geometry (depth
    3: the dense layer and two expert layers, 16 experts held, 32 slots of 64
    pages of 128 rows of 576), the latent leaf donated, compiled for
    ``chip`` with the leaf's stored order as the caller observed it
    (``pool_order``): ``(compiled, depth, slots, maxp, page, width)``."""
    from deepspeed_tpu.models import get_config, init_params
    from deepspeed_tpu.models.mixers import common as MX
    from deepspeed_tpu.models.transformer import forward_paged

    key = (pool_order, MX._pallas_interpret())
    if key in _LATENT_TICKS:
        return _LATENT_TICKS[key]

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    depth = 3
    cfg = get_config("kanana-2-30b-a3b", num_layers=depth,
                     moe_experts_held=16, vocab_size=16032)
    params = jax.tree_util.tree_map(
        lambda a: S(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    slots, maxp, page, width = 32, 64, 128, 576
    pages = 1 + slots * maxp
    cache = {"latent": S((depth, pages, page, width), jnp.bfloat16)}

    def tick(params, cache, tokens, table, start, mask):
        logits, cache, counts = forward_paged(
            cfg, params, tokens, cache, table, start, mask,
            expert_counts=True, pool_order=pool_order)
        return jnp.argmax(logits[:, -1], -1), counts, cache

    compiled = jax.jit(tick, donate_argnums=(1,)).lower(
        params, cache, S((slots, 1), jnp.int32), S((slots, maxp), jnp.int32),
        S((slots,), jnp.int32), S((slots, 1), jnp.bool_)).compile()
    _LATENT_TICKS[key] = compiled, depth, slots, maxp, page, width
    return _LATENT_TICKS[key]


def test_latent_paged_decode_keeps_its_leaf_in_place_on_v5e(one_v5e_chip):
    """AOT: ``jit_serve_decode``'s body for Kanana-2 at the benchmark's
    widths and geometry (:func:`_latent_tick`), compiled by the installed
    libtpu with the latent leaf donated.  The v5e stores ``[L, P, 128, 576]``
    with the page rows minor-most (``major_to_minor`` (0, 1, 3, 2): 576 is no
    whole number of 128 lanes), and the absorbed read takes the gathered
    pages in that order whatever the caller observed: no op's result is the
    size of the leaf but the in-place page scatters, a gather is one step's
    (slot, page) pairs, and the program's temporaries are megabytes.  With
    the products written over ``[N, page, 576]`` the compiler copied the
    whole leaf in front of every layer's read (0.68 GB of temp at depth 2:
    PERF.md, PR 32)."""
    import re

    import numpy as np

    from deepspeed_tpu.models.transformer import paged_read_pairs

    compiled, depth, slots, maxp, page, width = _latent_tick(one_v5e_chip)
    pages = 1 + slots * maxp
    layout = compiled.input_formats[0][1]["latent"].layout
    assert tuple(layout.major_to_minor) == (0, 1, 3, 2)
    assert compiled.memory_analysis().temp_size_in_bytes < 16e6
    text = compiled.as_text()
    # the scores and the sum over values reach the MXU as products
    assert "attn_latent" in text
    leaf, read = depth * pages * page * width, paged_read_pairs(slots, maxp)
    gathers = set()
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = bf16\[([\d,]+)\]", line)
        if not m:
            continue
        dims = [int(d) for d in m.group(1).split(",")]
        if sorted(dims[-2:]) != sorted([page, width]):
            continue                     # not pages of latent rows
        n = int(np.prod(dims))
        if " gather(" in line:
            gathers.add(n)
        if n >= leaf:
            assert re.search(r" (parameter|get-tuple-element|bitcast|while)"
                             r"\(| scatter\(|/scatter\"", line), line[:240]
    assert gathers and max(gathers) == read * page * width


def test_latent_tick_is_the_same_program_under_the_tpus_rule(one_v5e_chip,
                                                             monkeypatch):
    """AOT: the same tick with its paths chosen as on a TPU (the test
    answers for the backend, as the row write's own test does).  The latent
    leaf has no head axis, so it keeps the page merge: no row-write kernel.
    Handed the order the executor observes on the v5e (page rows
    minor-most) the rule reads by pages: Mosaic takes ``latent_read`` at the
    benchmark's widths, once in the dense layer's scan and once in the
    expert layers', and the program gathers no page of latent rows for its
    read any more (what is left is the merge's: a slot's one page); the leaf
    is still stored as the read's view wants it, nothing but the in-place
    scatters is its size, and the temporaries are megabytes.  Observed
    row-major (no order handed in: the caller that has not looked) the rule
    keeps the gather, and the program is, instruction for instruction, the
    one compiled where no kernel may run."""
    import re

    import numpy as np

    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.models.mixers import common as MX

    assert MX._pallas_interpret() is None        # the CPU's answer
    merge = _latent_tick(one_v5e_chip)[0]
    monkeypatch.setattr(MX, "_pallas_interpret", lambda: False)
    unseen = _latent_tick(one_v5e_chip)[0]
    assert (_without_source_locations(unseen.as_text())
            == _without_source_locations(merge.as_text()))
    on_tpu, depth, slots, maxp, page, width = _latent_tick(
        one_v5e_chip, T.LATENT_PAGE_ROWS_MINOR)
    text = on_tpu.as_text()
    assert "kv_row_write" not in text
    # (the expert layers' ragged-dot is the compiler's own Mosaic call)
    calls = [ln for ln in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in ln
             and "ragged-dot" not in ln]
    assert len(calls) == 2
    assert all("/attn_latent/kv_read/" in ln and "/latent_read/pallas_call"
               in ln for ln in calls)
    layout = on_tpu.input_formats[0][1]["latent"].layout
    assert tuple(layout.major_to_minor) == T.LATENT_PAGE_ROWS_MINOR
    assert on_tpu.memory_analysis().temp_size_in_bytes < 16e6
    leaf = depth * (1 + slots * maxp) * page * width
    gathers = set()
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = bf16\[([\d,]+)\]", line)
        if not m:
            continue
        dims = [int(d) for d in m.group(1).split(",")]
        if sorted(dims[-2:]) != sorted([page, width]):
            continue                     # not pages of latent rows
        n = int(np.prod(dims))
        if " gather(" in line:
            gathers.add(n)
        if n >= leaf:
            assert re.search(r" (parameter|get-tuple-element|bitcast|while)"
                             r"\(| scatter\(|/scatter\"", line), line[:240]
    # the merge's gather of each slot's one page, no step of 64 pairs
    assert gathers == {slots * page * width}


@pytest.mark.parametrize(
    "name,overrides,slots,pool_order",
    [("kanana-2-30b-a3b", {"num_layers": 3, "moe_experts_held": 16,
                           "vocab_size": 16032}, 32, None),
     ("opt-1.3b", {"num_layers": 2, "activation": "relu"}, 8,
      (0, 1, 3, 4, 2)),
     ("olmoe-1b-7b", {"num_layers": 2}, 16, None)],
    ids=["kanana-2", "opt-1.3b", "olmoe-1b-7b"])
def test_scanned_tick_at_the_executors_layouts_copies_no_sliced_weight(
        one_v5e_chip, name, overrides, slots, pool_order):
    """AOT: a scanned model's decode tick at the published widths, compiled
    by the installed libtpu with its weights as the executor places them: a
    stack a group (the scan's dynamic slice IS the fetch into on-chip
    memory) in the layouts the compiler asks for (PR 35).  In the default
    layouts a ``copy`` re-laid the slice of ``wq`` (Kanana's ``wkv_a`` and
    ``wkv_b``, OPT's ``wk`` and ``wv`` too) out before its product, every
    layer of every tick; at the executor's there is no ``copy`` of a
    projection's size left in the program."""
    import numpy as np

    from deepspeed_tpu.models import CausalLM, get_config
    from deepspeed_tpu.models.transformer import forward_paged

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    cfg = get_config(name, **overrides)
    params, auto = _placed_as_the_executor_places(cfg, one_v5e_chip)
    cache = jax.tree_util.tree_map(
        lambda a: S(a.shape, a.dtype), jax.eval_shape(
            lambda: CausalLM(cfg).init_paged_cache(
                1 + slots * 16, 128, dtype=jnp.bfloat16)))
    moe = cfg.num_experts != 1

    def tick(params, cache, tokens, table, start, mask):
        r = forward_paged(cfg, params, tokens, cache, table, start, mask,
                          pool_order=pool_order, expert_counts=moe)
        return (jnp.argmax(r[0][:, -1], -1),) + tuple(r[1:])

    compiled = jax.jit(tick, donate_argnums=(1,),
                       in_shardings=(auto,) + (None,) * 5).lower(
        params, cache, S((slots, 1), jnp.int32), S((slots, 16), jnp.int32),
        S((slots,), jnp.int32), S((slots, 1), jnp.bool_)).compile()
    stacks = [v for v in jax.tree_util.tree_leaves(params["layers"])
              if v.ndim == 3]
    projections = {int(np.prod(v.shape[1:])) for v in stacks}
    assert any(tuple(f.layout.major_to_minor) != (0, 1, 2)
               for f in jax.tree_util.tree_leaves(
                   compiled.input_formats[0][0]["layers"])
               if len(f.layout.major_to_minor) == 3)
    for op, opcode, results in _materialised(compiled.as_text()):
        for dims, layout in results:
            if opcode == "copy":
                assert int(np.prod(dims)) not in projections, (op, dims,
                                                               layout)


# The optimized HLO of the programs the benchmark's other configurations run
# (depth 2, MiMo at its cut's 7; metadata and the four source tables
# stripped), as the parent of PR 32 compiled them for the v5e, which are the
# parent of PR 30's for the first four: a change to the code they share with
# a new configuration either leaves these programs as they are, or says
# which instruction moved and why.  The hash is over every computation of
# the module: the installed XLA prints its four source tables (``FileNames``
# ... ``StackFrames``) AHEAD of the computations, so they are cut out of the
# text, which is not cut at them.
PROGRAMS_AT_PR_29 = {
    "opt-1.3b_decode": "65db27332cd211e1",
    "olmoe-1b-7b_decode": "99fe542b1b7b4288",
    "olmoe-1b-7b_prefill_256": "ba49df7d1cba2c84",
    "pythia_step": "08b45152c86e09db",
    "mimo-v2.5_decode": "e90810420bd2a420",
    "mimo-v2.5_prefill_256": "7636fa83161144ea",
}


def _without_source_locations(text):
    """A compiled module's text with nothing in it that names a line of
    source: two programs of the same instructions read the same."""
    import re

    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    text = re.sub(r"stack_frame_id=\d+", "", text)
    text = re.sub(r"\n(?:FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r"(?:\d+ .*\n)*", "\n", text)
    assert text.count("\n") > 1000          # the computations, not a header
    return text


def _program_hash(one_v5e_chip, program):
    """``(hash, text)`` of ``program`` (a key of ``PROGRAMS_AT_PR_29``)
    compiled for the described chip."""
    import hashlib
    import json
    import re

    from deepspeed_tpu.models import get_config, init_params
    from deepspeed_tpu.models.transformer import (cross_entropy_loss, forward,
                                                  forward_paged)

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    def shapes(cfg):
        return jax.tree_util.tree_map(
            lambda a: S(a.shape, jnp.bfloat16),
            jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))

    if program == "pythia_step":
        import dataclasses

        from benchmark.lib import system

        with open(os.path.join(REPO, "benchmark", "configs",
                               "pythia-1.4b-d10.json")) as f:
            cfg = dataclasses.replace(
                system.transformer_config(json.load(f), False), num_layers=2)

        def loss(p, toks):
            logits = forward(cfg, p, toks[:, :-1], attn_impl="xla",
                             deterministic=False, rng=jax.random.PRNGKey(0))
            return cross_entropy_loss(logits, toks[:, 1:])

        compiled = jax.jit(jax.value_and_grad(loss)).lower(
            shapes(cfg), S((4, 1025), jnp.int32)).compile()
    elif program.startswith("mimo"):
        from benchmark.lib import system
        from deepspeed_tpu.models import CausalLM

        with open(os.path.join(REPO, "benchmark", "configs",
                               "mimo-v2.5-ep16-d7.json")) as f:
            cfg = system.transformer_config(json.load(f), False)
        slots = 8
        cache = jax.tree_util.tree_map(
            lambda a: S(a.shape, a.dtype), jax.eval_shape(
                lambda: CausalLM(cfg).init_paged_cache(
                    1 + slots * 16, 128, dtype=jnp.bfloat16)))

        def tick(params, cache, tokens, table, start, mask):
            logits, cache, counts = forward_paged(
                cfg, params, tokens, cache, table, start, mask,
                expert_counts=True)
            return jnp.argmax(logits[:, -1], -1), cache, counts

        b, s = ((slots, 1) if program.endswith("decode")
                else (1, int(program.rsplit("_", 1)[1])))
        compiled = jax.jit(tick, donate_argnums=(1,)).lower(
            shapes(cfg), cache, S((b, s), jnp.int32),
            S((b, max(16, s // 128)), jnp.int32),
            S((b,), jnp.int32), S((b, s), jnp.bool_)).compile()
    else:
        name, what = program.split("_", 1)
        over, slots, order = {
            "opt-1.3b": ({"activation": "relu"}, 8, (0, 1, 3, 4, 2)),
            "olmoe-1b-7b": ({}, 16, None)}[name]
        cfg = get_config(name, num_layers=2, **over)
        moe = cfg.num_experts != 1
        pool = S((2, 1 + slots * 16, 128, cfg.kv_heads, cfg.dims_per_head),
                 jnp.bfloat16)

        def tick(params, k, v, tokens, table, start, mask):
            r = forward_paged(cfg, params, tokens, {"k": k, "v": v}, table,
                              start, mask, pool_order=order,
                              expert_counts=moe)
            return (jnp.argmax(r[0][:, -1], -1), r[1]["k"], r[1]["v"]) + (
                (r[2],) if moe else ())

        b, s = (slots, 1) if what == "decode" else (1, int(what[8:]))
        compiled = jax.jit(tick, donate_argnums=(1, 2)).lower(
            shapes(cfg), pool, pool, S((b, s), jnp.int32),
            S((b, 16), jnp.int32), S((b,), jnp.int32),
            S((b, s), jnp.bool_)).compile()
    text = _without_source_locations(compiled.as_text())
    return hashlib.sha256(text.encode()).hexdigest()[:16], text


@pytest.mark.parametrize("program", list(PROGRAMS_AT_PR_29))
def test_other_configurations_programs_are_the_parents(one_v5e_chip, program):
    assert _program_hash(one_v5e_chip, program)[0] == \
        PROGRAMS_AT_PR_29[program]


# Prefill programs under the TPU's rule (the test answers for the backend),
# at PR 48 (the kernel side re-pinned at PR 50: the sorted rows' way in and
# way back over the live rows alone), a bucket on each side of ``moe.sharded_moe.KERNEL_ROWS_AN_EXPERT``
# for each model: program -> (rows an expert a call, the path, the hash, with
# the bodies of this repo's kernels cut out).  MiMo's expert layers take a long prompt
# 2,048 tokens at a time (64 rows an expert); OLMoE's buckets are whole
# calls, and ``olmoe-1b-7b-d12.rollout-backlog`` sends a third of its prompts
# to the 512 and 1,024 buckets (64 and 128 rows an expert): the rule reaches
# them, whatever ISSUE 48 expected of "OLMoE's buckets".  Since PR 55 the
# bound is 16: OLMoE's 256 bucket (32 rows an expert) crossed to the kernel
# and is pinned anew, its 128 bucket stands AT the bound (the kernel's) and
# its 64 bucket (8 rows) under it, the parent's program still.
PREFILLS_AT_PR_48 = {
    "mimo-v2.5_prefill_256": (8, "ragged_dot",
                              PROGRAMS_AT_PR_29["mimo-v2.5_prefill_256"]),
    "mimo-v2.5_prefill_8192": (64, "kernel", "7f17f92dfbfba7ad"),
    "olmoe-1b-7b_prefill_256": (32, "kernel", "83ae1bec205ab14f"),
    "olmoe-1b-7b_prefill_512": (64, "kernel", "6a6be069caf54c58"),
    "olmoe-1b-7b_prefill_128": (16, "kernel", "f2f904b2e13c9aae"),
    "olmoe-1b-7b_prefill_64": (8, "ragged_dot", "adcfa6167c2aa145"),
}


def _holds_no_pass_over_dead_rows(text, tokens, top_k, d_model):
    """A compiled prompt program whose expert layers take ``tokens`` tokens a
    call moves its live rows alone (``moe/live_rows.py``, PR 50): no float32
    ``[tokens, top_k, d]`` value (the gather back written as float32) and
    no select over the ``[tokens x top_k, d]`` sorted rows (the pass that
    zeroed the dead ones) anywhere in it."""
    import re

    assert f"f32[{tokens},{top_k},{d_model}]" not in text
    rows = rf"\w+\[{tokens * top_k},{d_model}\]"
    assert not re.search(rf"= {rows}\S* select\(", text)
    # the way in: a buffer nobody wrote, filled by tiles under a loop
    assert "unwritten_rows" in text


@pytest.mark.parametrize("program", list(PREFILLS_AT_PR_48))
def test_a_prompts_expert_products_by_the_depth_of_its_groups(
        one_v5e_chip, monkeypatch, program):
    """AOT, the expert products chosen as on a TPU: a prefill whose calls of
    the expert layer are at or over the bound holds gate, up and down of
    every expert layer as ``ops/pallas/grouped_matmul.py`` and none as the
    compiler's ``ragged-dot``; one under it is the parent's program,
    instruction for instruction; every tick keeps the compiler's.  Since
    PR 50 the kernel side also moves its live rows alone each way (re-pinned
    there; the ``ragged_dot`` side's hashes are PR 29's still)."""
    import hashlib
    import json
    import re

    from benchmark.lib import system
    from deepspeed_tpu.models import get_config
    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.models.mixers import common as MX
    from deepspeed_tpu.moe.sharded_moe import KERNEL_ROWS_AN_EXPERT

    monkeypatch.setattr(MX, "_pallas_interpret", lambda: False)
    rows, path, pinned = PREFILLS_AT_PR_48[program]
    if program.startswith("mimo"):
        with open(os.path.join(REPO, "benchmark", "configs",
                               "mimo-v2.5-ep16-d7.json")) as f:
            cfg = system.transformer_config(json.load(f), False)
    else:
        cfg = get_config("olmoe-1b-7b", num_layers=2)
    bucket = int(program.rsplit("_", 1)[1])
    assert (bucket // T._moe_chunks(cfg, 1, bucket) * cfg.moe_top_k
            // cfg.num_experts) == rows
    assert (rows >= KERNEL_ROWS_AN_EXPERT) == (path == "kernel")
    assert T.expert_matmul_path(cfg, 1, bucket) == path
    assert T.expert_matmul_path(cfg, 16, 1) == "ragged_dot"
    digest, text = _program_hash(one_v5e_chip, program)
    kernels = [ln for ln in text.splitlines()
               if "custom_call_target=\"tpu_custom_call\"" in ln]
    held = sum("grouped_matmul" in ln for ln in kernels)
    if path == "ragged_dot":
        assert held == 0 and any("ragged" in ln for ln in kernels)
        assert digest == pinned
        return
    # gate, up and down an expert layer, once where the layers are a scan
    assert held in (T.expert_products(cfg), 3)
    assert not any("ragged" in ln for ln in kernels)
    _holds_no_pass_over_dead_rows(
        text, bucket // T._moe_chunks(cfg, 1, bucket), cfg.moe_top_k,
        cfg.hidden_size)
    # a Pallas kernel's compiled body names the files and lines of its call
    # stack (PERF.md, PR 41): the pin is of the program around the kernels,
    # their operands and layouts; the body has its own tests
    text = re.sub(r'"body":"[^"]*"', '"body":""', text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == pinned


def test_a_page_rows_minor_pool_keeps_the_page_merge_on_a_tpu(one_v5e_chip,
                                                              monkeypatch):
    """AOT: OPT's decode tick with its pool in the order the v5e stores a
    64-wide head (page rows minor-most), the K/V write chosen as on a TPU
    (the test answers for the backend).  The rule reads the stored order and
    keeps the page merge: no kernel in the program (over such a leaf the row
    write would have the whole pool copied into row-major order and back),
    and the program is still the parent of PR 30's, instruction for
    instruction."""
    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.models.mixers import common as MX

    monkeypatch.setattr(MX, "_pallas_interpret", lambda: False)
    digest, text = _program_hash(one_v5e_chip, "opt-1.3b_decode")
    assert "tpu_custom_call" not in text
    assert digest == PROGRAMS_AT_PR_29["opt-1.3b_decode"]


# What the v5e reports as ``memory_stats()["bytes_limit"]`` (my chip run,
# PR 38), and the rung its policy table names for the one-chip training cell.
V5E_BYTES_LIMIT = 16909336576
PYTHIA_CELL_RUNG = "save_matmuls"


def test_pythia_cell_resolves_the_chip_tables_rung_on_v5e(one_v5e_chip,
                                                          monkeypatch):
    """AOT: ``pythia-1.4b-d10`` at S=2,048 x mb 4 through the engine's
    resolver.  The engine cannot be built on a described device (it places
    its own state), so a small CPU engine runs the resolver and is handed,
    in place of its own rungs, the cell's step compiled by the installed
    libtpu for the v5e: the cell's model and loss under the rung's policy,
    bf16 gradients into AdamW with a bf16 first moment on float32 masters,
    the state donated.  It must stop at the rung the chip's table names,
    with the program's bytes inside the headroom, and that program holds 3
    flash custom calls a layer (forward, dq, dkv; the layers are one scan
    forward and one backward) where ``nothing_saveable`` holds 4."""
    import json

    import numpy as np
    import optax

    import deepspeed_tpu
    from benchmark.lib import system
    from deepspeed_tpu.models import CausalLM, init_params
    from deepspeed_tpu.models.transformer import REMAT_LADDER
    from deepspeed_tpu.ops.pallas import flash_attention as flash
    from deepspeed_tpu.parallel import mesh as mesh_mod
    from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh
    from deepspeed_tpu.runtime.engine import REMAT_HEADROOM

    # compile the kernel itself: this host's interpret mode is the CPU's
    monkeypatch.setattr(flash, "resolve_interpret", lambda interpret=None:
                        False)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "pythia-1.4b-d10.json")) as f:
        cfg = system.transformer_config(json.load(f), False)

    def placed(tree, dtype=None):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, dtype or a.dtype,
                                           sharding=one_v5e_chip), tree)

    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    adam = optax.adamw(1e-4, mu_dtype=jnp.bfloat16)
    state = (placed(shapes, jnp.bfloat16), placed(shapes, jnp.float32),
             placed(jax.eval_shape(adam.init, placed(shapes, jnp.float32))))
    tokens = jax.ShapeDtypeStruct((4, 2048), jnp.int32, sharding=one_v5e_chip)
    texts = {}

    def compile_rung(rung, _batch):
        model = CausalLM(cfg, attn_impl="pallas", remat_policy=rung)

        def step(state, toks):
            params, master, moments = state
            loss, grads = jax.value_and_grad(model.loss_fn)(
                params, {"input_ids": toks}, jax.random.PRNGKey(0))
            updates, moments = adam.update(grads, moments, master)
            master = optax.apply_updates(master, updates)
            params = jax.tree_util.tree_map(
                lambda m: m.astype(jnp.bfloat16), master)
            return (params, master, moments), loss

        jitted = jax.jit(step, donate_argnums=(0,))
        compiled = jitted.lower(state, tokens).compile()
        texts[rung] = compiled.as_text()
        return jitted, compiled

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=CausalLM("tiny", remat=True),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
                "bf16": {"enabled": True}, "steps_per_print": 10 ** 9},
        mesh=initialize_mesh(MeshLayout(), devices=jax.devices()[:1]))
    assert engine._remat_auto
    # the forward constrains activations on the global mesh: the CPU's
    mesh_mod.reset_mesh()
    monkeypatch.setattr(engine, "_compile_remat_rung", compile_rung)
    monkeypatch.setattr(engine, "_device_bytes_limit",
                        lambda: V5E_BYTES_LIMIT)
    got = engine.resolve_remat({"input_ids": np.zeros((1, 1, 8), np.int32)})
    assert got["policy"] == PYTHIA_CELL_RUNG
    assert [t["policy"] for t in got["tried"]] == list(
        REMAT_LADDER[:REMAT_LADDER.index(PYTHIA_CELL_RUNG) + 1])
    need = got["tried"][-1]["bytes"]
    assert need <= got["budget_bytes"] == int(
        REMAT_HEADROOM * V5E_BYTES_LIMIT), need
    # richer than the parent's program, which is why it is faster
    compile_rung("nothing_saveable", None)
    calls = {r: t.count('custom_call_target="tpu_custom_call"')
             for r, t in texts.items()}
    assert calls[PYTHIA_CELL_RUNG] == 3 and calls["nothing_saveable"] == 4


def test_collective_overlap_options_are_the_installed_compilers(v5e_2x2):
    """AOT, four described chips: the option set the engine hands a ZeRO-3
    step's compile (``TPU_Accelerator.collective_overlap_options``) is one
    the installed libtpu takes through ``compiler_options`` (it refuses a
    key it does not know, so a renamed option fails here and not on the
    chip), on a product whose weight is sharded over its contraction as the
    planner shards it and whose gradient lands sharded."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deepspeed_tpu.accelerator.tpu_accelerator import TPU_Accelerator

    mesh = Mesh(np.array(v5e_2x2.devices), ("data",))
    rows = NamedSharding(mesh, P("data"))
    x = jax.ShapeDtypeStruct((4, 256, 512), jnp.bfloat16, sharding=rows)
    w = jax.ShapeDtypeStruct((512, 512), jnp.bfloat16, sharding=rows)

    def grad(x, w):
        def loss(w):
            y = jax.lax.with_sharding_constraint(
                jnp.einsum("bsd,df->bsf", x, w), rows)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        return jax.lax.with_sharding_constraint(jax.grad(loss)(w), rows)

    options = TPU_Accelerator().collective_overlap_options()
    assert options
    jax.jit(grad, compiler_options=options).lower(x, w).compile()
    with pytest.raises(Exception, match="No such compile option"):
        jax.jit(grad, compiler_options={**options, "xla_tpu_no_such": "1"}
                ).lower(x, w).compile()


@pytest.mark.parametrize("program", ["decode", "prefill_16384"])
def test_mamba_or_attention_layers_fit_the_chip_at_the_cells_size(
        one_v5e_chip, monkeypatch, program):
    """AOT: the two programs of ``granite-4.0-h-small-ep2-d10.ragdoc-backlog``
    that decide whether its 12.5 GB of weights and cache leave room, at the
    cell's own sizes (ten layers at the published widths, 36 of 72 experts,
    32 slots of 104 pages), the weights held a leaf a layer and the cache
    donated.  The tick: one one-pass kernel a mamba layer over the
    ``ssm_state`` leaf where it lies, megabytes of temporaries.  The
    16,384-token prefill: the mixer a piece of 2,048 tokens at a time (its
    scan one kernel a piece since PR 59), the expert layer too, x pinned
    after each layer, 1.5 GB of temporaries
    (5.3 GB, and over the chip, where the compiler reads the head's one row
    out of every layer's branch outputs at the end; PERF.md, PR 47)."""
    import json
    import re

    from benchmark.lib import system
    from deepspeed_tpu.models import CausalLM, init_params
    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.models.mixers import common as MX
    from deepspeed_tpu.models.mixers import ssm as SSM

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    monkeypatch.setattr(MX, "_pallas_interpret", lambda: False)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "granite-4.0-h-small-ep2-d10.json")) as f:
        cfg = system.transformer_config(json.load(f), False)
    slots, maxp = 32, 104
    params = jax.tree_util.tree_map(
        lambda a: S(a.shape, jnp.bfloat16), jax.eval_shape(
            lambda: T.per_layer_leaves(cfg, init_params(
                cfg, jax.random.PRNGKey(0)))[0]))
    cache = jax.tree_util.tree_map(
        lambda a: S(a.shape, a.dtype), jax.eval_shape(
            lambda: CausalLM(cfg).init_paged_cache(
                1 + slots * maxp, 128, dtype=jnp.bfloat16, slots=slots)))
    assert cache["k"].shape[0] == 1 and cache["ssm_state"].shape[:2] == (
        9, slots)
    b, s = (slots, 1) if program == "decode" else (1, 16384)

    def run(params, cache, tokens, table, start, mask, slot, at):
        kw = {} if program == "decode" else {"state_slot": slot,
                                             "logits_at": at}
        logits, cache, counts = T.forward_paged(
            cfg, params, tokens, cache, table, start, mask,
            expert_counts=True, **kw)
        return jnp.argmax(logits[:, -1], -1), cache, counts

    compiled = jax.jit(run, donate_argnums=(1,)).lower(
        params, cache, S((b, s), jnp.int32), S((b, maxp), jnp.int32),
        S((b,), jnp.int32), S((b, s), jnp.bool_), S((b,), jnp.int32),
        S((b,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 12.6e9
    assert mem.alias_size_in_bytes > 2.9e9          # the cache in place
    if program == "decode":
        assert mem.temp_size_in_bytes < 0.1e9
        text = compiled.as_text()
        kernels = [ln for ln in text.splitlines()
                   if "custom_call_target=\"tpu_custom_call\"" in ln]
        # one state step a mamba layer, the attention layer's K and V rows
        # written where they lie; the rest are the grouped products, a
        # tick's few rows a group as the compiler's own (lax.ragged_dot)
        assert sum("ssm_step" in ln for ln in kernels) == 9
        assert sum("kv_write" in ln for ln in kernels) == 2
        assert sum("ragged" in ln for ln in kernels) >= 3
        assert not any("grouped_matmul" in ln for ln in kernels)
        assert T.expert_matmul_path(cfg, b, s) == "ragged_dot"
    else:
        assert mem.temp_size_in_bytes < 2.0e9
        text = compiled.as_text()
        kernels = [ln for ln in text.splitlines()
                   if "custom_call_target=\"tpu_custom_call\"" in ln]
        # a chunk of 2,048 tokens is 284 rows an expert: gate, up and down
        # of each of the ten layers are the kernel over the held groups'
        # row tiles (ops/pallas/grouped_matmul.py), none the compiler's
        assert sum("grouped_matmul" in ln for ln in kernels) == 30
        assert not any("ragged" in ln for ln in kernels)
        assert T.expert_matmul_path(cfg, b, s) == "kernel"
        # and the sorted rows around them move live rows alone: no float32
        # [2048, 10, 4096], no select over [20480, 4096]
        _holds_no_pass_over_dead_rows(text, 2048, cfg.moe_top_k,
                                      cfg.hidden_size)
        # the nine mamba layers' scan is one kernel each, a call a piece of
        # 2,048 tokens under the pieces' loop (ops/pallas/ssm_scan.py): no
        # float32 of the decays' size ([.., 256, 256, .., 128]) and none of
        # y or x dt between [position, head, dim] and another order
        assert SSM.ssm_scan_path(cfg, s) == "kernel"
        assert sum("ssm_scan" in ln for ln in kernels) == 9
        assert not re.search(r"f32\[[\d,]*256,256,[\d,]*128\]", text)
        assert not re.search(
            r"f32\[1,(2048,8192|2048,128,64|8,256,1,128,64)\]\{[^}]*\}"
            r" (copy|reshape|transpose)\(", text)


SCANS_AT_PR_59 = {
    # heads, head width, state, groups, chunk, tokens: a state-space layer
    # of a prefill program of the benchmark's two cells
    "granite-4.0-h-small-a-piece-of-2048": (128, 64, 128, 1, 256, 2048),
    "falcon-h1-34b-the-512-bucket": (32, 128, 256, 2, 128, 512),
}


@pytest.mark.parametrize("shape", list(SCANS_AT_PR_59))
def test_a_prompts_scan_compiles_at_both_published_shapes(one_v5e_chip,
                                                          shape):
    """AOT: ``ops/pallas/ssm_scan.py`` alone at the widths its tile plan
    must take (64-wide heads two a lane tile in one group; 128-wide heads in
    two groups), bfloat16 operands and a float32 state: Mosaic takes the
    blocks, and beside the kernel the program holds megabytes (the
    cumulative sum of ``dt A`` a block of heads at a time), nothing of the
    decays' size."""
    from deepspeed_tpu.ops.pallas.ssm_scan import scan_block, ssm_scan

    H, P, N, G, Q, tokens = SCANS_AT_PR_59[shape]

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    assert scan_block(H, G, P, N, Q) == 8
    compiled = jax.jit(lambda *a: ssm_scan(*a, chunk=Q, interpret=False)).lower(
        S((1, tokens, H, P), jnp.bfloat16), S((1, tokens, G, N), jnp.bfloat16),
        S((1, tokens, G, N), jnp.bfloat16), S((1, tokens, H), jnp.float32),
        S((H,), jnp.float32), S((1, H, P, N), jnp.float32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * tokens * H * 4


@pytest.mark.parametrize("program", ["decode", "prefill_1024"])
def test_delta_or_attention_layers_fit_the_chip_at_the_cells_size(
        one_v5e_chip, monkeypatch, program):
    """AOT: the two programs of ``olmo-hybrid-7b-d16.thinkrollout-backlog``
    at the cell's own sizes (sixteen layers at the published widths, 32
    slots of 16 pages), the weights held a leaf a layer and the cache
    donated.  The tick: one one-pass kernel a delta layer over the
    ``delta_state`` leaf where it lies (two heads a row, [15, 96, 384]: what
    the leaf takes is what the equations hold, 849 MB), the K/V leaves of 30
    heads head-major (row-major the compiler pads 30 heads to 32 and copies
    2 x 1.9 GB of pool a tick, over the chip), 0.2 GB of temporaries.  The
    1,024-token prefill: the chunk form, no kernel; the slot's one row of
    each state leaf sliced and updated where it lies (a scatter into
    ``delta_conv [.., 3, 11520]`` kept a padded version of the leaf a layer
    and 1.4 GB of temporaries)."""
    import json

    from benchmark.lib import system
    from deepspeed_tpu.models import CausalLM, init_params
    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.models.mixers import common as MX
    from deepspeed_tpu.models.mixers import delta as DELTA

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    monkeypatch.setattr(MX, "_pallas_interpret", lambda: False)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "olmo-hybrid-7b-d16.json")) as f:
        cfg = system.transformer_config(json.load(f), False)
    assert DELTA.delta_step_path(cfg) == "one_pass"
    slots, maxp = 32, 16
    params = jax.tree_util.tree_map(
        lambda a: S(a.shape, jnp.bfloat16), jax.eval_shape(
            lambda: T.per_layer_leaves(cfg, init_params(
                cfg, jax.random.PRNGKey(0)))[0]))
    cache = jax.tree_util.tree_map(
        lambda a: S(a.shape, a.dtype), jax.eval_shape(
            lambda: CausalLM(cfg).init_paged_cache(
                1 + slots * maxp, 128, dtype=jnp.bfloat16, slots=slots)))
    assert cache["k"].shape == (4, 513, 30, 128, 128)
    assert cache["delta_state"].shape == (12, slots, 15, 96, 384)
    b, s = (slots, 1) if program == "decode" else (1, 1024)

    def run(params, cache, tokens, table, start, mask, slot, at):
        kw = {} if program == "decode" else {"state_slot": slot,
                                             "logits_at": at}
        logits, cache = T.forward_paged(cfg, params, tokens, cache, table,
                                        start, mask, **kw)
        return jnp.argmax(logits[:, -1], -1), cache

    compiled = jax.jit(run, donate_argnums=(1,)).lower(
        params, cache, S((b, s), jnp.int32), S((b, maxp), jnp.int32),
        S((b,), jnp.int32), S((b, s), jnp.bool_), S((b,), jnp.int32),
        S((b,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 13.2e9
    assert mem.alias_size_in_bytes > 4.9e9          # the cache in place
    kernels = [ln for ln in compiled.as_text().splitlines()
               if "custom_call_target=\"tpu_custom_call\"" in ln]
    if program == "decode":
        assert mem.temp_size_in_bytes < 0.3e9
        assert sum("delta_step" in ln for ln in kernels) == 12
        # 30 heads are no row the row-write kernel's tile plan takes
        assert not any("kv_row_write" in ln for ln in kernels)
        # the read's kernel takes a head-major block of any number of heads
        # (PR 52): one call an attention layer, and no gathered copy of a
        # step's 64 pages is left in the program
        assert T.kv_read_paths(cfg, cache, None) == {"k": "pages",
                                                     "v": "pages"}
        assert sum("/paged_read/pallas_call" in ln for ln in kernels) == 4
        assert "bf16[64,30,128,128]" not in compiled.as_text()
    else:
        assert mem.temp_size_in_bytes < 0.8e9
        assert not kernels
        assert "remat_compressed" not in compiled.as_text()


@pytest.mark.parametrize("program", ["decode", "prefill_1024"])
def test_conv_or_attention_layers_fit_the_chip_at_the_cells_size(
        one_v5e_chip, monkeypatch, program):
    """AOT: the two programs of ``lfm2-8b-a1b-d14.agentturn-backlog`` at the
    cell's own sizes (fourteen layers at the published widths with all 32
    experts of each of the twelve expert layers, 128 slots of 24 pages), the
    weights held a leaf a layer and the cache donated, the K/V leaves in the
    order the TPU stores 64-wide heads (page rows minor-most, as OPT's).  The
    tick: 9.33 GB of weights and 2.43 GB of cache in place (the eleven
    layers' tails are 11.5 MB of it, one row of 4,096 a slot), no kernel of
    the conv operator's own (the three-term sum and the shift fuse); its 512
    sorted rows are 16 an expert, the grouped-product kernel's side of the
    rule since PR 55: 36 ``grouped_matmul`` calls and no ``ragged-dot``.  The
    1,024-token prefill: 4,096 sorted rows, 128 an expert, the kernel's
    too."""
    import json

    from benchmark.lib import system
    from deepspeed_tpu.models import CausalLM, init_params
    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.models.mixers import common as MX
    from deepspeed_tpu.models.mixers import conv as CONV

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    monkeypatch.setattr(MX, "_pallas_interpret", lambda: False)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "lfm2-8b-a1b-d14.json")) as f:
        cfg = system.transformer_config(json.load(f), False)
    assert CONV.conv_step_path(cfg) == "plain"
    slots, maxp = 128, 24
    params = jax.tree_util.tree_map(
        lambda a: S(a.shape, jnp.bfloat16), jax.eval_shape(
            lambda: T.per_layer_leaves(cfg, init_params(
                cfg, jax.random.PRNGKey(0)))[0]))
    cache = jax.tree_util.tree_map(
        lambda a: S(a.shape, a.dtype), jax.eval_shape(
            lambda: CausalLM(cfg).init_paged_cache(
                1 + slots * maxp, 128, dtype=jnp.bfloat16, slots=slots)))
    assert cache["k"].shape == cache["v"].shape == (3, 3073, 128, 8, 64)
    assert cache["conv_tail"].shape == (11, slots, 4096)
    b, s = (slots, 1) if program == "decode" else (1, 1024)
    order = (0, 1, 3, 4, 2)

    def run(params, cache, tokens, table, start, mask, slot, at):
        kw = {} if program == "decode" else {"state_slot": slot,
                                             "logits_at": at}
        logits, cache, counts = T.forward_paged(
            cfg, params, tokens, cache, table, start, mask,
            expert_counts=True, pool_order=order, **kw)
        return jnp.argmax(logits[:, -1], -1), cache, counts

    compiled = jax.jit(run, donate_argnums=(1,)).lower(
        params, cache, S((b, s), jnp.int32), S((b, maxp), jnp.int32),
        S((b,), jnp.int32), S((b, s), jnp.bool_), S((b,), jnp.int32),
        S((b,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert 11.7e9 < mem.argument_size_in_bytes < 11.9e9
    assert mem.alias_size_in_bytes > 2.4e9          # the cache in place
    text = compiled.as_text()
    kernels = [ln for ln in text.splitlines()
               if "custom_call_target=\"tpu_custom_call\"" in ln]
    if program == "decode":
        assert mem.temp_size_in_bytes < 0.5e9
        assert T.expert_matmul_path(cfg, slots, 1) == "kernel"
        assert T.kv_read_paths(cfg, cache, order) == {"k": "gather",
                                                      "v": "gather"}
        # gate, up and down of each of the twelve expert layers
        assert sum("grouped_matmul" in ln for ln in kernels) == 36
        assert not any("ragged" in ln for ln in kernels)
    else:
        assert mem.temp_size_in_bytes < 1.5e9
        assert T.expert_matmul_path(cfg, 1, 1024) == "kernel"
    # no copy of the tails' leaf: it is updated where it lies
    assert not [ln for ln in text.splitlines() if " copy(" in ln and (
        "bf16[11,128,4096]" in ln or "bf16[1408,4096]" in ln)]


@pytest.mark.parametrize("program", ["decode", "prefill_8192"])
def test_gated_window_layers_fit_the_chip_at_the_cells_size(
        one_v5e_chip, monkeypatch, program):
    """AOT: the two largest programs of ``trinity-large-ep16-d8.mixedctx-
    backlog`` at the cell's own sizes (eight layers at the published widths
    with 16 held experts of each of the seven expert layers, 24 slots of 80
    pages in the full layers' pool and a ring of 33 in the window layers'),
    the weights held a leaf a layer and the cache donated.  The tick: 8.29
    GB of weights and 4.5 GB of cache in place; BOTH pools are read by the
    gather: the ring through the window's lower bound, and the full pool (8
    x 128, bfloat16, row-major) because 8 heads are half a tile of 16
    sublanes, which the page kernel's block does not take (``page_block``);
    its 96 pairs of (slot, expert) are under a row an expert,
    ``ragged_dot``'s side of the rule.  The
    8,192-token prefill walks a window layer's keys in chunks of 512 inside
    the window: no array of the program is a chunk of 4,096 queries against
    8,192 keys."""
    import json

    from benchmark.lib import system
    from deepspeed_tpu.models import CausalLM, init_params
    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.models.mixers import common as MX

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    monkeypatch.setattr(MX, "_pallas_interpret", lambda: False)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "trinity-large-ep16-d8.json")) as f:
        cfg = system.transformer_config(json.load(f), False)
    slots, maxp, ring = 24, 80, 33
    assert T.window_ring_pages(cfg.window_size, 128) == ring
    params = jax.tree_util.tree_map(
        lambda a: S(a.shape, jnp.bfloat16), jax.eval_shape(
            lambda: T.per_layer_leaves(cfg, init_params(
                cfg, jax.random.PRNGKey(0)))[0]))
    cache = jax.tree_util.tree_map(
        lambda a: S(a.shape, a.dtype), jax.eval_shape(
            lambda: CausalLM(cfg).init_paged_cache(
                1 + slots * maxp, 128, dtype=jnp.bfloat16,
                window_pages=1 + slots * ring, slots=slots)))
    assert cache["k"].shape == cache["v"].shape == (2, 1921, 128, 8, 128)
    assert cache["k_window"].shape == (6, 793, 128, 8, 128)
    b, s = (slots, 1) if program == "decode" else (1, 8192)

    def run(params, cache, tokens, tables, start, mask, at):
        kw = {} if program == "decode" else {"logits_at": at}
        logits, cache, counts = T.forward_paged(
            cfg, params, tokens, cache, tables, start, mask,
            expert_counts=True, **kw)
        return jnp.argmax(logits[:, -1], -1), cache, counts

    compiled = jax.jit(run, donate_argnums=(1,)).lower(
        params, cache, S((b, s), jnp.int32),
        (S((b, maxp), jnp.int32), S((b, ring), jnp.int32)),
        S((b,), jnp.int32), S((b, s), jnp.bool_), S((b,), jnp.int32)
    ).compile()
    mem = compiled.memory_analysis()
    assert 12.7e9 < mem.argument_size_in_bytes < 12.9e9
    assert mem.alias_size_in_bytes > 4.4e9          # both pools in place
    text = compiled.as_text()
    if program == "decode":
        assert mem.temp_size_in_bytes < 0.6e9
        assert T.expert_matmul_path(cfg, slots, 1) == "ragged_dot"
        assert set(T.kv_read_paths(cfg, cache, None, slots).values()) == {
            "gather"}
        assert T.kv_write_paths(cfg, cache, None) == {
            k: "row" for k in ("k", "v", "k_window", "v_window")}
    else:
        # x pinned after each layer (12.8 GB of weights and cache are over
        # ``PIN_RESIDENT_BYTES``): 0.74 GB of temporaries, 2.2 GB unpinned
        assert mem.temp_size_in_bytes < 1.0e9
        # a chunk of the window's own length against two of keys, one KV
        # head's six query heads: what the chunks of ``window`` would hold
        assert "f32[1,1,6,4096,8192]" not in text
        assert "4096,8192]" not in text


@pytest.mark.parametrize("program", ["decode", "prefill_4096"])
def test_one_sublayer_layers_fit_the_chip_at_the_cells_size(
        one_v5e_chip, monkeypatch, program):
    """AOT: the two programs of ``nemotron-3-super-ep4-d11.subagent-backlog``
    that decide whether its 12.96 GB of weights and cache leave room, at the
    cell's own sizes (eleven one-sublayer layers at the published widths,
    128 of 512 experts in the 1,024-wide latent, 128 slots of 56 pages), the
    weights held a leaf a layer and the cache donated.  The tick: one
    one-pass kernel an M layer over the ``ssm_state`` leaf where it lies (a
    block of 8 heads inside one of the 8 groups of 16), the E layers' two
    products ``lax.ragged_dot`` (5.5 rows an expert), megabytes of
    temporaries.  The longest prompt's bucket: the M layers' scan one kernel
    each, the E layers' two products the grouped kernel a chunk of 2,048
    tokens (88 rows an expert) over latent rows, half a GB of
    temporaries."""
    import json

    from benchmark.lib import system
    from deepspeed_tpu.models import CausalLM, init_params
    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.models.mixers import common as MX
    from deepspeed_tpu.models.mixers import ssm as SSM

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    monkeypatch.setattr(MX, "_pallas_interpret", lambda: False)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "nemotron-3-super-ep4-d11.json")) as f:
        cfg = system.transformer_config(json.load(f), False)
    slots, maxp = 128, 56
    params = jax.tree_util.tree_map(
        lambda a: S(a.shape, jnp.bfloat16), jax.eval_shape(
            lambda: T.per_layer_leaves(cfg, init_params(
                cfg, jax.random.PRNGKey(0)))[0]))
    cache = jax.tree_util.tree_map(
        lambda a: S(a.shape, a.dtype), jax.eval_shape(
            lambda: CausalLM(cfg).init_paged_cache(
                1 + slots * maxp, 128, dtype=jnp.bfloat16, slots=slots)))
    # one layer of K/V pages (2 KV heads: head-major), five of state rows,
    # nothing for the five expert layers
    assert cache["k"].shape == (1, 7169, 2, 128, 128)
    assert cache["ssm_state"].shape[:2] == (5, slots)
    b, s = (slots, 1) if program == "decode" else (1, 4096)

    def run(params, cache, tokens, table, start, mask, slot, at):
        kw = {} if program == "decode" else {"state_slot": slot,
                                             "logits_at": at}
        logits, cache, counts = T.forward_paged(
            cfg, params, tokens, cache, table, start, mask,
            expert_counts=True, **kw)
        return jnp.argmax(logits[:, -1], -1), cache, counts

    compiled = jax.jit(run, donate_argnums=(1,)).lower(
        params, cache, S((b, s), jnp.int32), S((b, maxp), jnp.int32),
        S((b,), jnp.int32), S((b, s), jnp.bool_), S((b,), jnp.int32),
        S((b,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert 12.9e9 < mem.argument_size_in_bytes < 13.0e9
    assert mem.alias_size_in_bytes > 3.6e9          # the cache in place
    kernels = [ln for ln in compiled.as_text().splitlines()
               if "custom_call_target=\"tpu_custom_call\"" in ln]
    if program == "decode":
        assert mem.temp_size_in_bytes < 0.1e9
        assert SSM.ssm_step_path(cfg) == "one_pass"
        assert sum("ssm_step" in ln for ln in kernels) == 5
        assert sum("ragged" in ln for ln in kernels) >= 10
        assert not any("grouped_matmul" in ln for ln in kernels)
        assert T.expert_matmul_path(cfg, b, s) == "ragged_dot"
    else:
        assert mem.temp_size_in_bytes < 0.7e9
        assert SSM.ssm_scan_path(cfg, s) == "kernel"
        assert sum("ssm_scan" in ln for ln in kernels) == 5
        assert sum("grouped_matmul" in ln for ln in kernels) == 10
        assert not any("ragged" in ln for ln in kernels)
        assert T.expert_matmul_path(cfg, b, s) == "kernel"
