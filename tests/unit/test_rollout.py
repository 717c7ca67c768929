"""Hybrid rollout subsystem (ISSUE 13): RLHF-shaped generation through the
paged serving engine over LIVE training weights.

Covers the acceptance surface:

- **handoff parity**: rollout tokens through the ServingEngine are
  token-exact vs ``generate(params=live, sampling=lane)`` on the same
  weights — greedy AND sampled — across ≥2 live weight updates with 0
  steady-state compiles and a bit-identical ``program_inventory()``
  (unsharded here; the 2-device-mesh half lives in the ``tp=2`` tests
  below);
- **weight epochs / stale KV**: a param update flushes every cached
  prefix page, COW-donor boundary page and demoted host-tier slab with
  the page-accounting ledger balanced through the flip, and the
  epoch-tag defenses (index entry stamp, host-slab stamp, per-page stamp)
  each independently refuse pre-update K/V;
- **round resilience**: a kill mid-rollout warm-restarts with the adopted
  program inventory and replays token-exactly under the same RNG lane AND
  weight epoch; the full seeded train+rollout chaos scenario is the
  pinned ``tools/chaos_soak.py --mode hybrid`` seed (multiseed marked
  ``slow``);
- satellites: LoRA fuse-once-per-flip through the rollout path, the
  training-batch handoff shape contract, rollout gauges, and the
  update-time guards (idle slots, aval mismatch).
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.inference.sampling import SamplingParams
from deepspeed_tpu.inference.serving import Request, ServingEngine
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.monitor import InMemoryMonitor
from deepspeed_tpu.parallel import mesh as mesh_mod
from deepspeed_tpu.resilience import (FaultInjector, clear_injector,
                                      install_injector)
from deepspeed_tpu.resilience.fault_injection import SITE_SERVE_DECODE
from deepspeed_tpu.rollout import RolloutEngine, RolloutRound
from deepspeed_tpu.utils.compile_counter import compile_counter

SERVE_KW = dict(b_slots=3, page_size=8, max_model_len=64)

_count = compile_counter()


@pytest.fixture(autouse=True)
def _fresh_mesh():
    mesh_mod.reset_mesh()
    yield
    mesh_mod.reset_mesh()


def _train_config():
    return {
        "train_micro_batch_size_per_gpu": 8,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 2},
    }


@pytest.fixture(scope="module")
def stack():
    """One training engine + rollout engine shared by the round tests
    (compile discipline: streams stay inside the 16-token prompt bucket)."""
    mesh_mod.reset_mesh()
    model = CausalLM("tiny", dtype=jnp.float32, attn_impl="xla",
                     max_seq_len=64)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model,
                                               config=_train_config())
    monitor = InMemoryMonitor()
    ro = RolloutEngine(engine, monitor=monitor, max_restarts=4,
                       rollout_seq_len=16, **SERVE_KW)
    return model, engine, ro, monitor


def _prompts(n=5, seed=0, lo=4, hi=12):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, int(rng.integers(lo, hi))).astype(np.int32)
            for _ in range(n)]


def _lanes(n=5):
    """Mixed greedy/sampled lane set (greedy None, greedy-by-params, hot
    temperature, nucleus, top-k)."""
    pool = [None, SamplingParams(),
            SamplingParams(temperature=0.9, top_k=25, seed=11),
            SamplingParams(temperature=1.2, top_p=0.9, seed=3),
            SamplingParams(temperature=0.7, top_k=17, top_p=0.95, seed=42)]
    return [pool[i % len(pool)] for i in range(n)]


def _batches(engine, round_seed, k=2):
    return [{"input_ids": np.random.default_rng(1000 + round_seed * 10 + i)
             .integers(0, 256, (engine.train_batch_size, 16))
             .astype(np.int32)} for i in range(k)]


def _assert_parity(ro, prompts, lanes, results, max_new):
    """Every rollout output token-identical to generate(params=live,
    sampling=lane) — the one-shot oracle over the SAME weight view."""
    for res in results:
        i = res.rid[1]
        sp = lanes[i] or SamplingParams()
        # hybrid.generate supplies params=live itself (the LoRA-fused view
        # when applicable) — the same weight view serving published
        base = np.asarray(ro.hybrid.generate(
            prompts[i][None], max_new_tokens=max_new,
            sampling=sp))[0, len(prompts[i]):]
        np.testing.assert_array_equal(res.output_ids, base)


# ------------------------------------------------ handoff parity acceptance


@pytest.mark.slow
def test_rollout_parity_and_zero_recompile_across_weight_updates(stack):
    """The tentpole acceptance: train K steps -> publish epoch -> rollout,
    twice more after a warm round — greedy + sampled token-exact vs
    generate() on the live weights, 0 compiles during the measured
    rounds, inventory bit-identical across ≥2 weight updates."""
    _, engine, ro, _ = stack
    prompts, lanes = _prompts(5, seed=0), _lanes(5)

    # warm round: serving buckets + the generate() oracle programs compile
    r1 = ro.run_round(prompts, train_batches=_batches(engine, 0),
                      max_new_tokens=6, sampling=lanes, max_ticks=2000)
    assert r1.weight_epoch == ro.serving.weight_epoch
    assert len(r1.losses) == 2 and all(np.isfinite(r1.losses))
    _assert_parity(ro, prompts, lanes, r1.results, 6)

    inventory = ro.serving.program_inventory()
    base = _count()
    measured = []
    for rnd in (1, 2):
        rr = ro.run_round(prompts, train_batches=_batches(engine, rnd),
                          max_new_tokens=6, sampling=lanes, max_ticks=2000)
        measured.append(rr)
        assert ro.serving.program_inventory() == inventory
        # parity against the round's OWN weight view, before the next
        # round trains past it.  The oracle's lane program compiled on the
        # warm round, so it is a cache hit inside the counted window.
        _assert_parity(ro, prompts, lanes, rr.results, 6)
    steady_compiles = _count() - base
    assert steady_compiles == 0, \
        f"{steady_compiles} compile(s) across 2 live weight updates"
    assert measured[1].weight_epoch == measured[0].weight_epoch + 1
    h = ro.health()
    assert h["weight_updates_total"] >= 3
    assert ro.serving.page_accounting()["balanced"]


def test_round_training_batch_and_gauges(stack):
    """The round hands back a fixed-shape {"input_ids": [B, S]} batch and
    the rollout/* gauges land on the monitor."""
    _, engine, ro, monitor = stack
    prompts, lanes = _prompts(4, seed=7), _lanes(4)
    rr = ro.run_round(prompts, train_batches=(), max_new_tokens=4,
                      sampling=lanes, max_ticks=2000)
    assert isinstance(rr, RolloutRound)
    batch = rr.train_batch["input_ids"]
    assert batch.shape == (4, 16) and batch.dtype == np.int32
    # row i = prompt i + its rollout, right-padded
    by_i = {r.rid[1]: r for r in rr.results}
    for i in range(4):
        row = np.concatenate([prompts[i], by_i[i].output_ids])[:16]
        np.testing.assert_array_equal(batch[i, :len(row)], row)
        assert (batch[i, len(row):] == 0).all()
    latest = monitor.latest_map()
    assert latest["rollout/rounds_total"] == float(ro.rounds_completed)
    assert latest["rollout/weight_epoch"] == float(ro.weight_epoch)
    assert latest["serve/weight_epoch"] == float(ro.weight_epoch)
    assert "rollout/tokens_per_sec" in latest
    assert "rollout/refresh_s" in latest
    h = ro.health()
    assert h["rollout_rounds_total"] == ro.rounds_completed
    assert h["rollout_tokens_total"] > 0
    assert h["rollout_refresh_p50_s"] > 0
    # program-stats coverage rides the serving catalog: every inventory
    # program the rollouts used reports accounting rows
    stats = h["program_stats"]
    assert "decode" in stats and stats["decode"]["invocations"] > 0


def test_midrollout_kill_replays_same_lane_and_epoch(stack):
    """A decode kill mid-rollout warm-restarts with the ADOPTED program
    inventory and replays token-exactly under the same sampling lane and
    the same weight epoch (the factory rebuilds from the published
    params)."""
    _, engine, ro, _ = stack
    prompts, lanes = _prompts(4, seed=21), _lanes(4)
    # reference round at a fresh epoch (publish without training: the
    # weight VIEW is unchanged, so the next round's outputs must match)
    ref = ro.run_round(prompts, train_batches=(), max_new_tokens=8,
                       sampling=lanes, max_ticks=2000)
    ref_by = {r.rid[1]: r.output_ids for r in ref.results}
    restarts0 = ro.supervisor.restarts
    inj = FaultInjector()
    inj.add(site=SITE_SERVE_DECODE, kind="raise", at_call=3)
    install_injector(inj)
    try:
        rr = ro.run_round(prompts, train_batches=(), max_new_tokens=8,
                          sampling=lanes, max_ticks=4000)
    finally:
        clear_injector()
    assert ro.supervisor.restarts == restarts0 + 1
    entry = ro.supervisor.restart_log[-1]
    assert entry["programs_reused"], "warm restart rebuilt the inventory"
    # the replacement engine serves the SAME epoch the killed one did
    assert ro.serving.weight_epoch == rr.weight_epoch == \
        ref.weight_epoch + 1
    for r in rr.results:
        np.testing.assert_array_equal(r.output_ids, ref_by[r.rid[1]])
    assert ro.serving.page_accounting()["balanced"]


# --------------------------------------------------- weight-epoch contract


@pytest.fixture(scope="module")
def inference_stack():
    """Standalone inference engine for the serving-only epoch tests."""
    mesh_mod.reset_mesh()
    model = CausalLM("tiny", dtype=jnp.float32, attn_impl="xla")
    params = model.init_fn(jax.random.PRNGKey(0))
    engine = deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32"}, params=params)
    return model, engine


def _shared_prefix_reqs(tag, vocab=256, sys_len=19, n=2, tail=3, seed=1):
    """Shared 19-token system prompt (2 full 8-token pages + a COW
    boundary) + unique tails."""
    rng = np.random.default_rng(seed)
    system = rng.integers(1, vocab, sys_len).astype(np.int32)
    reqs = [Request(rid=f"{tag}{i}",
                    input_ids=np.concatenate(
                        [system, rng.integers(1, vocab, tail)
                         .astype(np.int32)]),
                    max_new_tokens=4)
            for i in range(n)]
    return system, reqs


@pytest.mark.slow
def test_stale_kv_never_served_after_weight_update(inference_stack):
    """ISSUE 13 stale-KV regression: admit a shared-prefix stream (hot
    pages + COW boundary + a demoted host-tier slab), update the live
    params, re-admit the same prefix — the lookup must MISS everything
    (no shared tokens, no COW, no promotion), the ledger must balance
    through the flip, and the re-decoded output must match generate() on
    the NEW weights."""
    model, engine = inference_stack
    serve = engine.serving(host_tier_pages=4, **SERVE_KW)
    system, reqs = _shared_prefix_reqs("a", n=2)
    serve.run(reqs)
    assert serve.prefix_hits >= 1 and serve.cow_copies >= 1
    # park one full chunk on the host tier (partial entries evict first)
    for _ in range(6):
        if serve._prefix.demoted:
            break
        serve._demote_lru_entry()
    assert serve._prefix.demoted >= 1
    assert len(serve._tier) == serve._prefix.demoted
    assert serve.page_accounting()["balanced"]

    new_params = jax.jit(
        lambda p: jax.tree_util.tree_map(lambda x: x * 1.01, p))(serve.params)
    stats = serve.update_params(new_params)
    assert stats["weight_epoch"] == 1 and stats["balanced"]
    assert stats["flushed_hbm_pages"] > 0 and stats["flushed_host_slabs"] >= 1
    assert len(serve._prefix) == 0 and len(serve._tier) == 0
    acct = serve.page_accounting()
    assert acct["balanced"] and acct["demoted"] == 0 and acct["cached"] == 0

    hits0, cows0, promos0 = (serve.prefix_hits, serve.cow_copies,
                             serve.promotions)
    rng = np.random.default_rng(9)
    again = Request(rid="fresh", input_ids=np.concatenate(
        [system, rng.integers(1, 256, 3).astype(np.int32)]),
        max_new_tokens=4)
    res = serve.run([again])[0]
    # the hit MUST NOT reuse the old epoch's pages: cold admission
    assert res.shared_prefix_tokens == 0
    assert serve.prefix_hits == hits0
    assert serve.cow_copies == cows0 and serve.promotions == promos0
    base = np.asarray(engine.generate(
        again.input_ids[None], max_new_tokens=4,
        params=serve.params))[0, len(again.input_ids):]
    np.testing.assert_array_equal(res.output_ids, base)
    # and the fresh prefix re-publishes under the NEW epoch: the next
    # sharer hits again
    res2 = serve.run([Request(rid="sharer", input_ids=np.concatenate(
        [system, rng.integers(1, 256, 3).astype(np.int32)]),
        max_new_tokens=4)])[0]
    assert res2.shared_prefix_tokens > 0
    assert serve.page_accounting()["balanced"]


@pytest.mark.slow
def test_epoch_tag_defenses_refuse_stale_entries(inference_stack):
    """Defense-in-depth: even WITHOUT the flush, each epoch stamp
    independently refuses pre-update K/V — a stale index entry is a
    lookup miss, a stale host slab is a vanished buffer, and a stale
    mapped page trips the admission guard loudly."""
    model, engine = inference_stack
    serve = engine.serving(host_tier_pages=4, **SERVE_KW)
    system, reqs = _shared_prefix_reqs("t", n=1, seed=4)
    serve.run(reqs)
    assert len(serve._prefix) > 0
    # (1) index-entry stamp: flip the index epoch without flushing — every
    # entry is now from a retired epoch and must read as a miss
    serve._prefix.epoch = 99
    m = serve._prefix.lookup(
        np.concatenate([system, np.asarray([1, 2, 3], np.int32)]), limit=20)
    assert m.n_tokens == 0 and m.cow_src is None and not m.pages
    serve._prefix.epoch = 0   # restore
    # (2) host-slab stamp: a slab stored under epoch 0 vanishes when
    # fetched at epoch 1
    for _ in range(6):
        if serve._prefix.demoted:
            break
        serve._demote_lru_entry()
    key = next(iter(serve._tier.keys()))
    assert serve._tier.get(key, epoch=0) is not None
    assert serve._tier.get(key, epoch=1) is None
    assert serve._tier.epoch_of(key) == 0
    # (3) per-page stamp: a cached page stamped with another epoch trips
    # the admission guard instead of being mapped (simulates a flush hole)
    pages = serve._prefix.pages()
    assert pages
    serve._pages.epoch[pages[0]] = 77
    rng = np.random.default_rng(13)
    with pytest.raises(RuntimeError, match="weight-epoch invariant"):
        serve.run([Request(rid="stale", input_ids=np.concatenate(
            [system, rng.integers(1, 256, 3).astype(np.int32)]),
            max_new_tokens=2)])


def test_update_params_requires_idle_slots(inference_stack):
    model, engine = inference_stack
    serve = engine.serving(**SERVE_KW)
    rng = np.random.default_rng(2)
    serve.submit(Request(rid="r", input_ids=rng.integers(1, 256, 6)
                         .astype(np.int32), max_new_tokens=8))
    serve.step()   # admits + starts decoding
    assert serve._active.any()
    with pytest.raises(RuntimeError, match="in flight"):
        serve.update_params(serve.params)
    serve.run([])  # drain the slot so the shared fixture stays clean


def test_update_params_rejects_mismatched_tree(inference_stack):
    model, engine = inference_stack
    serve = engine.serving(**SERVE_KW)
    bad_dtype = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), serve.params)
    with pytest.raises(ValueError, match="aval"):
        serve.update_params(bad_dtype)
    leaves, treedef = jax.tree_util.tree_flatten(serve.params)
    with pytest.raises(ValueError, match="structure"):
        serve.update_params(leaves)   # a list, not the compiled tree


@pytest.mark.slow
def test_supervisor_carries_weight_epoch_on_restart(inference_stack):
    """A PLAIN supervised engine (factory params predate the update): a
    restart must re-publish the dead engine's live view at its epoch so
    replay decodes under the weights the stream started with."""
    model, engine = inference_stack
    sup = engine.supervised_serving(max_restarts=3, **SERVE_KW)
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, input_ids=rng.integers(1, 256, 8)
                    .astype(np.int32), max_new_tokens=6) for i in range(3)]

    new_params = jax.jit(
        lambda p: jax.tree_util.tree_map(lambda x: x * 1.02, p))(
            sup.engine.params)
    sup.engine.update_params(new_params)
    assert sup.engine.weight_epoch == 1
    copies = [Request(rid=f"c{r.rid}", input_ids=r.input_ids,
                      max_new_tokens=r.max_new_tokens) for r in reqs]
    ref = {r.rid: r.output_ids for r in sup.run(copies)}

    inj = FaultInjector()
    inj.add(site=SITE_SERVE_DECODE, kind="raise", at_call=2)
    install_injector(inj)
    try:
        results = sup.run(reqs, max_ticks=2000)
    finally:
        clear_injector()
    assert sup.restarts == 1
    # the REPLACEMENT engine serves epoch 1 (factory built at epoch 0)
    assert sup.engine.weight_epoch == 1
    for r in results:
        np.testing.assert_array_equal(r.output_ids, ref[f"c{r.rid}"])
    h = sup.health()
    assert h["weight_updates_total"] >= 2   # the update + the carry


@pytest.mark.slow
def test_speculative_draft_refresh_and_guard(inference_stack):
    """A weight flip on a speculative engine may refresh the draft too:
    the swap validates BEFORE mutating (a mismatched draft tree is
    rejected loudly, engine untouched), and greedy speculative output
    after the flip stays token-exact vs generate() on the new weights."""
    from deepspeed_tpu.inference.speculative import (SpeculativeConfig,
                                                     layer_skip_draft)

    model, engine = inference_stack
    draft_model, draft_params = layer_skip_draft(model, engine.params, 1)
    serve = engine.serving(
        speculative=SpeculativeConfig(draft_model, draft_params, k=2),
        **SERVE_KW)
    rng = np.random.default_rng(6)
    prompt = rng.integers(1, 256, 8).astype(np.int32)
    serve.run([Request(rid="warm", input_ids=prompt, max_new_tokens=4)])
    # a structurally broken draft tree is rejected with the engine intact
    bad_draft = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), serve._spec.draft_params)
    epoch0, cached0 = serve.weight_epoch, len(serve._prefix)
    with pytest.raises(ValueError, match="draft leaf"):
        serve.update_params(serve.params, draft_params=bad_draft)
    assert serve.weight_epoch == epoch0 and len(serve._prefix) == cached0
    # a valid refresh: new target + its layer-skip draft slice
    new_params = jax.jit(lambda p: jax.tree_util.tree_map(
        lambda x: x * 1.01, p))(serve.params)
    _, new_draft = layer_skip_draft(model, new_params, 1)
    stats = serve.update_params(new_params, draft_params=new_draft)
    assert stats["weight_epoch"] == epoch0 + 1
    res = serve.run([Request(rid="post", input_ids=prompt,
                             max_new_tokens=4)])[0]
    base = np.asarray(engine.generate(
        prompt[None], max_new_tokens=4,
        params=serve.params))[0, len(prompt):]
    np.testing.assert_array_equal(res.output_ids, base)
    assert serve.page_accounting()["balanced"]


# ----------------------------------------------------------- LoRA satellite


@pytest.mark.slow
def test_lora_rollout_fuses_once_per_flip():
    """The LoRA fuse-once-per-flip cache rides the rollout path: repeated
    publishes without a train step reuse the fused tree; a train step
    invalidates it exactly once."""
    from deepspeed_tpu.runtime.lora import LoRAConfig, LoRAModel

    base = CausalLM("tiny", dtype=jnp.float32, attn_impl="xla",
                    max_seq_len=64)
    base_params = base.init_fn(jax.random.PRNGKey(0))
    actor = LoRAModel(base, base_params, LoRAConfig(rank=4))
    engine, _, _, _ = deepspeed_tpu.initialize(model=actor, config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 3},
    })
    ro = RolloutEngine(engine, **SERVE_KW)
    ro.publish_weights()
    fused = ro.hybrid._fused_params
    assert fused is not None
    ro.publish_weights()           # no train step: cache hit, same tree
    assert ro.hybrid._fused_params is fused
    rng = np.random.default_rng(0)
    rr = ro.run_round([rng.integers(1, 256, 6).astype(np.int32)],
                      train_batches=[{"input_ids": np.full(
                          (engine.train_batch_size, 16), 7, np.int32)}],
                      max_new_tokens=4, max_ticks=2000)
    # the train step flipped global_steps -> publish re-fused exactly once
    assert ro.hybrid._fused_params is not fused
    assert ro.hybrid._fused_at_step == engine.global_steps
    assert len(rr.results) == 1


# -------------------------------------------------- 2-device-mesh handoff

TP = 2


@pytest.fixture(scope="module")
def sharded_stack():
    mesh_mod.reset_mesh()
    from deepspeed_tpu.parallel.mesh import initialize_serving_mesh

    mesh = initialize_serving_mesh(tp=TP, n_devices=TP)
    model = CausalLM("tiny", dtype=jnp.float32, attn_impl="xla")
    params = model.init_fn(jax.random.PRNGKey(3))
    engine = deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32"}, params=params, mesh=mesh)
    serve = engine.serving(**SERVE_KW)
    return model, engine, serve, mesh


def _mesh_stream(tag, n=5, seed=5):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        sp = (SamplingParams(temperature=0.9, top_k=25, seed=11 + i)
              if i % 2 else None)
        reqs.append(Request(rid=f"{tag}{i}",
                            input_ids=rng.integers(1, 256, 9)
                            .astype(np.int32),
                            max_new_tokens=6, sampling=sp))
    return reqs


@pytest.mark.slow
def test_mesh_weight_updates_parity_and_zero_recompile(sharded_stack):
    """The 2-device half of the parity suite: live updates reshard the
    tree through the shared place_params/auto_tp_specs path — sharded
    rollout decode stays token-exact vs generate() on the updated view,
    with 0 compiles across ≥2 updates and the per-device pool bytes
    untouched at 1/tp."""
    model, engine, serve, mesh = sharded_stack
    serve.run(_mesh_stream("w"))                     # warm
    inventory = serve.program_inventory()
    perturb = jax.jit(lambda p: jax.tree_util.tree_map(
        lambda x: x * 1.01, p))
    live = perturb(serve.params)                     # perturb compiles here
    oracle_warmed = False
    base = None
    for upd in range(3):
        if upd == 1:
            base = _count()                          # measured: updates 2+3
        serve.update_params(live)
        reqs = _mesh_stream(f"u{upd}", seed=50 + upd)
        results = {r.rid: r for r in serve.run(reqs)}
        if upd >= 1:
            assert _count() - base == 0, "sharded weight update recompiled"
        assert serve.program_inventory() == inventory
        # oracle AFTER the counted serve pass (its lane program compiles
        # once, on the warm pass)
        for req in reqs:
            sp = req.sampling or SamplingParams()
            out = np.asarray(engine.generate(
                req.input_ids[None], max_new_tokens=6, sampling=sp,
                params=serve.params))[0, len(req.input_ids):]
            np.testing.assert_array_equal(results[req.rid].output_ids, out)
        oracle_warmed = True
        live = perturb(live)
    assert serve.weight_epoch == 3
    h = serve.health()
    assert h["mesh_devices"] == TP
    assert h["kv_pool_bytes_per_device"] * TP == h["kv_pool_bytes_total"]
    # the updated params really are model-axis sharded (auto-TP path)
    leaf = jax.tree_util.tree_leaves(serve.params)[0]
    assert getattr(leaf.sharding, "mesh", None) == mesh
    assert oracle_warmed


# --------------------------------- acceptance: the chaos hybrid harness


@pytest.mark.chaos
@pytest.mark.slow
def test_hybrid_chaos_soak_deterministic_seed():
    """Pinned seed of ``tools/chaos_soak.py --mode hybrid``: seeded kills
    mid-rollout (serve.decode) and mid-train-step (train.step) across
    rounds — loss continuity vs the fault-free reference, rollout replay
    parity, the pool invariant, and the weight-epoch ladder all hold."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    os.pardir, os.pardir, "tools"))
    from chaos_soak import run_hybrid_soak

    stats = run_hybrid_soak(seed=0, verbose=False)
    assert stats["serve_restarts"] >= 1, "no mid-rollout kill landed"
    assert stats["train_kills"] >= 1, "no mid-train-step kill landed"
    assert stats["parity_checked"] == stats["rollouts_total"]
    assert stats["losses_checked"] == stats["train_steps_total"]
    assert stats["balanced"]


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_hybrid_chaos_soak_multiseed(seed):
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    os.pardir, os.pardir, "tools"))
    from chaos_soak import run_hybrid_soak

    stats = run_hybrid_soak(seed=seed, verbose=False)
    assert stats["parity_checked"] == stats["rollouts_total"]
    assert stats["balanced"]
