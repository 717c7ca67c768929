"""Multi-chip serving (ISSUE 10): the decode tick and the paged KV pool
tensor-sharded over a device mesh.

Covers the acceptance surface on the virtual 8-device CPU mesh:

- sharded (tp=2) serving is TOKEN-EXACT vs the unsharded engine — greedy
  and sampled lanes under the same seeds — and vs ``generate()``;
- per-device KV-pool bytes shrink 1/tp (health() + the serve/* gauges on
  the Prometheus exposition);
- the zero-recompile steady state holds with a mesh attached (0 compiles
  on the measured pass, inventory stable);
- ServingSupervisor warm restarts and ``recycle()`` ADOPT the sharded
  programs (no recompile — jit avals include shardings, and the factory
  re-creates the pool with the same NamedShardings) and replay is
  token-exact;
- the speculative draft/verify programs ride the same mesh, greedy
  speculative staying token-identical to the plain sharded engine;
- a mesh whose 'model' axis does not divide kv_heads is rejected loudly.

Compile discipline (single-core CI): one module-scoped tp=2 engine + one
shared ServingEngine shape; streams stay inside the 16-token prompt
bucket with max_new drawn from a 2-element choice set.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.inference.sampling import SamplingParams
from deepspeed_tpu.inference.serving import Request, ServingEngine
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.monitor import InMemoryMonitor
from deepspeed_tpu.parallel.mesh import initialize_serving_mesh
from deepspeed_tpu.resilience import (FaultInjector, clear_injector,
                                      install_injector)
from deepspeed_tpu.resilience.fault_injection import SITE_SERVE_DECODE
from deepspeed_tpu.utils.compile_counter import compile_counter

TP = 2
SERVE_KW = dict(b_slots=3, page_size=8, max_model_len=64)

_count = compile_counter()


@pytest.fixture(autouse=True)
def _mesh_installed():
    """Each test runs with the tp=2 serving mesh installed as the global
    mesh (the conftest autouse fixture resets it after every test; jax
    caches Mesh instances, so this re-installs the SAME mesh object the
    module-scoped engine was built on)."""
    initialize_serving_mesh(tp=TP)
    yield


@pytest.fixture(scope="module")
def sharded_engine():
    mesh = initialize_serving_mesh(tp=TP)
    model = CausalLM("tiny", dtype=jnp.float32, attn_impl="xla")
    params = model.init_fn(jax.random.PRNGKey(3))
    engine = deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32"}, params=params, mesh=mesh)
    return model, params, engine


@pytest.fixture(scope="module")
def sharded_serve(sharded_engine):
    _, _, engine = sharded_engine
    return engine.serving(monitor=InMemoryMonitor(), **SERVE_KW)


def _stream(n, seed=0, sampled=True):
    """Mixed greedy/sampled stream inside one prompt bucket."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        sp = None
        if sampled and i % 2 == 1:
            sp = SamplingParams(temperature=0.9, top_k=25, top_p=0.95,
                                seed=700 + i)
        reqs.append(Request(
            rid=i,
            input_ids=rng.integers(1, 250, int(rng.integers(3, 14))
                                   ).astype(np.int32),
            max_new_tokens=int(rng.choice((4, 6))), sampling=sp))
    return reqs


@pytest.mark.slow
def test_sharded_token_exact_vs_unsharded_and_generate(sharded_engine,
                                                       sharded_serve):
    """The acceptance gate: tp=2 outputs == tp=1 outputs == generate(),
    greedy and sampled, same seeds; and the per-device pool footprint
    shrinks 1/tp while the sharding is the documented head split."""
    model, params, engine2 = sharded_engine
    # unsharded reference on the historical default mesh (tp=1)
    initialize_serving_mesh(tp=1)
    ref_engine = deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32"}, params=params)
    ref_serve = ref_engine.serving(**SERVE_KW)
    ref = {r.rid: r.output_ids for r in ref_serve.run(_stream(6, seed=1))}
    del ref_serve

    initialize_serving_mesh(tp=TP)
    stream = _stream(6, seed=1)
    results = sharded_serve.run(_stream(6, seed=1))
    by_rid = {r.rid: r for r in results}
    assert sorted(by_rid) == sorted(r.rid for r in stream)
    for req in stream:
        np.testing.assert_array_equal(
            by_rid[req.rid].output_ids, ref[req.rid],
            err_msg=f"rid {req.rid} sharded != unsharded")
        # generate() oracle through the SAME sharded params (sampled rows
        # ride the identical counter-based lane keys)
        oracle = np.asarray(engine2.generate(
            req.input_ids[None], max_new_tokens=req.max_new_tokens,
            sampling=req.sampling or SamplingParams()))
        np.testing.assert_array_equal(
            by_rid[req.rid].output_ids, oracle[0, len(req.input_ids):],
            err_msg=f"rid {req.rid} sharded != generate()")

    h = sharded_serve.health()
    assert h["mesh_devices"] == jax.device_count()
    assert h["mesh_axes"]["model"] == TP
    assert h["kv_pool_bytes_per_device"] * TP == h["kv_pool_bytes_total"]
    spec = sharded_serve._exec.pools[0].sharding.spec
    assert tuple(spec) == (None, None, None, "model", None)


def test_zero_steady_state_compiles_on_mesh(sharded_serve):
    """Admission of a fresh mixed greedy/sampled stream into the warmed
    sharded engine compiles NOTHING and leaves the inventory bit-stable —
    the one-program-per-shape contract survives the mesh."""
    sharded_serve.run(_stream(6, seed=2))        # warm (buckets compiled)
    inv = sharded_serve.program_inventory()
    base = _count()
    results = sharded_serve.run(_stream(6, seed=3))
    assert _count() - base == 0
    assert sharded_serve.program_inventory() == inv
    assert len(results) == 6
    assert sharded_serve.page_accounting()["balanced"]


@pytest.mark.slow
def test_supervisor_warm_restart_adopts_sharded_programs(sharded_engine,
                                                         sharded_serve):
    """A decode-tick fault on the mesh warm-restarts with the compiled
    sharded programs ADOPTED (0 compiles across the faulted run), the
    replacement pool on the SAME sharding, and replay token-exact."""
    _, _, engine2 = sharded_engine
    stream = _stream(6, seed=4)
    ref = {r.rid: r.output_ids for r in sharded_serve.run(_stream(6, seed=4))}

    sup = engine2.supervised_serving(max_restarts=3, **SERVE_KW)
    sup.run(_stream(6, seed=4))                  # warm the supervised engine
    old_sharding = sup.engine._exec.pools[0].sharding
    inj = install_injector(FaultInjector())
    inj.add(site=SITE_SERVE_DECODE, kind="raise", at_call=3)
    try:
        base = _count()
        results = sup.run(_stream(6, seed=4), max_ticks=2000)
        compiles = _count() - base
    finally:
        clear_injector()
    assert sup.restarts == 1
    assert sup.restart_log[-1]["programs_reused"] is True
    assert compiles == 0, "warm restart recompiled on the mesh"
    assert sup.engine._exec.pools[0].sharding == old_sharding
    by_rid = {r.rid: r for r in results}
    for rid, out in ref.items():
        np.testing.assert_array_equal(by_rid[rid].output_ids, out,
                                      err_msg=f"rid {rid} replay diverged")
    assert any(r.replays == 1 for r in results)
    assert sup.engine.page_accounting()["balanced"]


def test_recycle_reuses_sharded_programs_and_gauges(sharded_engine):
    """Rolling-restart recycle() on a mesh: fresh pool with the same
    shardings, compiled programs adopted (0 compiles), mesh gauges on the
    Prometheus exposition, and the recycled engine still serves."""
    _, _, engine2 = sharded_engine
    monitor = InMemoryMonitor()
    sup = engine2.supervised_serving(max_restarts=2, monitor=monitor,
                                     **SERVE_KW)
    first = sup.run(_stream(4, seed=5))
    assert len(first) == 4
    old_sharding = sup.engine._exec.pools[0].sharding
    assert not sup.drain(max_ticks=500)          # idle: nothing unserved
    base = _count()
    assert sup.recycle() is True
    assert _count() - base == 0, "recycle recompiled on the mesh"
    assert sup.engine._exec.pools[0].sharding == old_sharding
    results = sup.run(_stream(4, seed=6))
    assert len(results) == 4
    h = sup.health()
    assert h["mesh_axes"] == {"data": jax.device_count() // TP, "model": TP}
    from deepspeed_tpu.observability.export import prometheus_text

    text = prometheus_text(monitor=monitor)
    assert f"dstpu_serve_mesh_devices {jax.device_count()}" in text
    assert f"dstpu_serve_mesh_axis_model {TP}" in text
    assert "dstpu_serve_kv_pool_bytes_per_device" in text


@pytest.mark.slow
def test_speculative_sharded_greedy_token_exact(sharded_engine,
                                                sharded_serve):
    """The draft pool and the draft/verify programs ride the same mesh:
    greedy speculative output is token-identical to the plain sharded
    engine, and the draft pool's per-device bytes shrink 1/tp too."""
    from deepspeed_tpu.inference.speculative import (SpeculativeConfig,
                                                     layer_skip_draft)

    model, _, engine2 = sharded_engine
    ref = {r.rid: r.output_ids
           for r in sharded_serve.run(_stream(5, seed=7, sampled=False))}
    dm, dp = layer_skip_draft(model, engine2.params, 1)
    spec = engine2.serving(
        speculative=SpeculativeConfig(draft_model=dm, draft_params=dp, k=2),
        **SERVE_KW)
    results = spec.run(_stream(5, seed=7, sampled=False))
    for r in results:
        np.testing.assert_array_equal(r.output_ids, ref[r.rid])
    h = spec.health()
    assert h["draft_pool_bytes_per_device"] > 0
    assert h["draft_pool_bytes_per_device"] \
        == spec._spec.pool_bytes["total"] // TP


def test_mesh_rejects_indivisible_kv_heads(sharded_engine):
    """tiny has kv_heads=4: a model axis of 8 cannot shard the pool's head
    dim — the executor fails loudly at engine build, not mid-decode."""
    model, params, _ = sharded_engine
    mesh = initialize_serving_mesh(tp=8)
    with pytest.raises(ValueError, match="kv_heads"):
        ServingEngine(model, params, mesh=mesh, **SERVE_KW)


@pytest.mark.slow
def test_sharded_pool_demote_promote_token_exact(sharded_engine):
    """ISSUE 11 on a mesh: the tier movers run against the SHARDED pool —
    extract gathers the head shards into one host slab, inject device_puts
    it back under the pool's own NamedSharding — and demote/promote
    cycling stays token-exact with an untiered sharded engine, ledger
    balanced."""
    _, _, engine = sharded_engine
    rng = np.random.default_rng(19)
    systems = [rng.integers(1, 250, 17).astype(np.int32) for _ in range(3)]
    tails = [rng.integers(1, 250, 3).astype(np.int32) for _ in range(9)]

    def stream():
        return [Request(rid=i,
                        input_ids=np.concatenate([systems[i % 3], tails[i]]),
                        max_new_tokens=4)
                for i in range(9)]

    ref_serve = engine.serving(b_slots=1, page_size=8, max_model_len=40,
                               num_pages=8, prefix_cache=False)
    ref = {r.rid: r.output_ids for r in ref_serve.run(stream())}
    del ref_serve
    serve = engine.serving(b_slots=1, page_size=8, max_model_len=40,
                           num_pages=8, host_tier_pages=16)
    assert serve.mesh is not None
    results = serve.run(stream())
    for r in results:
        np.testing.assert_array_equal(r.output_ids, ref[r.rid])
    assert serve.demotions > 0 and serve.promotions > 0
    acct = serve.page_accounting()
    assert acct["balanced"] and acct["demoted"] == len(serve._tier)
