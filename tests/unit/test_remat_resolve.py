"""Which activations the backward keeps is the engine's to resolve (ISSUE 38).

A ``TransformerConfig`` that names no ``remat_policy`` leaves it at
``REMAT_AUTO``; the training engine then walks ``REMAT_LADDER`` from the
richest policy down and keeps the first rung whose compiled fused step fits
the device (``DeepSpeedEngine.resolve_remat``).  The CPU backend reports no
memory limit, so the budget is handed in here; the rule is the one the chip
runs.  A policy changes what is stored and what is run twice, never a value:
every rung gives ``nothing_saveable``'s loss and update to the bit.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import CausalLM, TransformerConfig, init_params
from deepspeed_tpu.models.transformer import (REMAT_AUTO, REMAT_LADDER,
                                              cross_entropy_loss, forward)
from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh
from deepspeed_tpu.runtime.engine import REMAT_HEADROOM
from deepspeed_tpu.utils.memory import program_bytes
from deepspeed_tpu.utils.compile_counter import compile_counter

S, MB = 128, 4
SIZED = dict(vocab_size=64, hidden_size=64, intermediate_size=256,
             num_layers=2, num_heads=2, max_seq_len=S, remat=True)


def _engine(stage=1, dp=1, dtype=jnp.bfloat16, mb=MB, **fields):
    model = CausalLM(TransformerConfig(**{**SIZED, "dtype": dtype, **fields}),
                     attn_impl="xla")
    config = {"train_micro_batch_size_per_gpu": mb,
              "gradient_accumulation_steps": 1,
              "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
              "zero_optimization": {"stage": stage,
                                    "stage3_param_persistence_threshold": 0},
              "steps_per_print": 10 ** 9}
    if dtype == jnp.bfloat16:
        config["bf16"] = {"enabled": True}
    layout = MeshLayout(dp=dp) if dp > 1 else MeshLayout()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=config,
        mesh=initialize_mesh(layout, devices=jax.devices()[:dp]))
    return engine


def _batch(engine, seq=S, vocab=64):
    return {"input_ids": np.random.default_rng(0).integers(
        0, vocab, (engine.train_batch_size, seq)).astype(np.int32)}


def _describe_device(engine, bytes_limit):
    """The CPU backend reports no ``bytes_limit``; a test gives one."""
    engine._device_bytes_limit = lambda: bytes_limit


def _program_bytes(compiled):
    return program_bytes(compiled.memory_analysis())


@pytest.fixture(scope="module")
def rung_bytes():
    """What each rung's fused step needs, from an engine whose config names
    the policy outright."""
    from deepspeed_tpu.parallel import mesh

    need = {}
    for rung in REMAT_LADDER:
        mesh.reset_mesh()
        engine = _engine(remat_policy=rung)
        need[rung] = _program_bytes(engine.compile_train_step(_batch(engine)))
    mesh.reset_mesh()
    assert sorted(need.values(), reverse=True) == list(need.values()), need
    return need


@pytest.mark.parametrize("fits", list(REMAT_LADDER) + [None])
def test_the_richest_rung_whose_program_fits_is_taken(rung_bytes, fits):
    """The budget shrinks from "everything fits" to "nothing does": the
    resolver stops at the first rung within it, and takes the last rung
    whatever it needs."""
    engine = _engine()
    assert engine.model.config.remat_policy == REMAT_AUTO
    budget, want = ((1024, REMAT_LADDER[-1]) if fits is None
                    else (rung_bytes[fits], fits))
    got = engine.resolve_remat(
        engine._collect_global_batch(_batch(engine)), budget_bytes=budget)
    assert got is engine.remat_resolution and got["policy"] == want
    tried = [t["policy"] for t in got["tried"]]
    assert tried == list(REMAT_LADDER[:REMAT_LADDER.index(want) + 1])
    # the bytes on record are the programs' own, rung by rung
    assert [t["bytes"] for t in got["tried"]] == [rung_bytes[r] for r in tried]
    assert (got["argument_bytes"] + got["temp_bytes"] + got["output_bytes"]
            - got["alias_bytes"]) == rung_bytes[want]
    # the step it kept is the one that runs, and the record says so
    assert np.isfinite(float(engine.train_batch(batch=_batch(engine))))
    row = engine.program_catalog.table()["train_step"]
    assert row["remat_policy"] == want and row["invocations"] == 1
    assert row["remat_budget_bytes"] == got["budget_bytes"] == budget


def test_the_default_budget_is_the_headroom_share_of_the_devices_limit(
        rung_bytes):
    """No budget given: ``REMAT_HEADROOM`` of ``bytes_limit`` (a described
    device here: the CPU reports none)."""
    engine = _engine()
    limit = int(rung_bytes["save_qkv"] / REMAT_HEADROOM) + 64

    _describe_device(engine, limit)
    got = engine.resolve_remat(engine._collect_global_batch(_batch(engine)))
    assert got["policy"] == "save_qkv"
    assert got["budget_bytes"] == int(REMAT_HEADROOM * limit)
    assert rung_bytes["save_qkv"] <= got["budget_bytes"] \
        < rung_bytes["save_matmuls"]


def test_a_compile_the_compiler_refuses_for_memory_does_not_fit(monkeypatch):
    engine = _engine()
    compile_rung = engine._compile_remat_rung

    def refuse_the_top(rung, batch):
        if rung == REMAT_LADDER[0]:
            raise RuntimeError("RESOURCE_EXHAUSTED: Ran out of memory in "
                               "memory space hbm")
        return compile_rung(rung, batch)

    monkeypatch.setattr(engine, "_compile_remat_rung", refuse_the_top)
    got = engine.resolve_remat(
        engine._collect_global_batch(_batch(engine)), budget_bytes=10 ** 12)
    assert got["policy"] == REMAT_LADDER[1]
    assert got["tried"][0] == {"policy": REMAT_LADDER[0], "bytes": None}

    def broken(rung, batch):
        raise TypeError("not a memory error")

    monkeypatch.setattr(engine, "_compile_remat_rung", broken)
    with pytest.raises(TypeError):
        engine.resolve_remat(engine._collect_global_batch(_batch(engine)),
                             budget_bytes=10 ** 12)


@pytest.mark.parametrize("policy", list(REMAT_LADDER) + ["dots_saveable"])
def test_a_named_policy_is_never_replaced(policy):
    engine = _engine(remat_policy=policy)
    assert not engine._remat_auto
    engine.resolve_remat = None      # would raise if the engine called it
    engine.compile_train_step(_batch(engine))
    engine.train_batch(batch=_batch(engine))
    assert engine.remat_resolution is None
    assert engine.model.config.remat_policy == policy
    assert "remat_policy" not in engine.program_catalog.table()["train_step"]


def test_a_loss_the_caller_handed_in_is_never_swapped_for_the_models():
    """``DeepSpeedEngine(model=CausalLM(...), loss_fn=custom)``: the resolver
    builds its steps from a model variant's own ``loss_fn``, so a loss that
    is not the model's is not its to resolve for, budget or no budget."""
    model = CausalLM(TransformerConfig(**{**SIZED, "dtype": jnp.float32}),
                     attn_impl="xla")

    def custom(params, batch, rng):
        return 3.0 * model.loss_fn(params, batch, rng) + 7.0

    config = {"train_micro_batch_size_per_gpu": MB,
              "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
              "zero_optimization": {"stage": 1}, "steps_per_print": 10 ** 9}
    mesh = initialize_mesh(MeshLayout(), devices=jax.devices()[:1])
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, loss_fn=custom, init_fn=model.init_fn,
        param_specs=model.param_specs, config=dict(config), mesh=mesh)
    assert model.config.remat_policy == REMAT_AUTO and not engine._remat_auto
    batch = _batch(engine)
    global_batch = engine._collect_global_batch(batch)
    assert engine.resolve_remat(global_batch, budget_bytes=10 ** 12) is None
    engine.compile_train_step(batch, budget_bytes=10 ** 12)
    assert engine.remat_resolution is None
    got = float(engine.train_batch(batch=batch))
    # the same weights under the model's own loss (the engine's to resolve)
    own, _, _, _ = deepspeed_tpu.initialize(model=model, config=dict(config),
                                            mesh=mesh)
    assert own._remat_auto
    own.compile_train_step(batch, budget_bytes=10 ** 12)
    assert own.remat_resolution["policy"] == REMAT_LADDER[0]
    want = float(own.train_batch(batch=batch))
    np.testing.assert_allclose(got, 3.0 * want + 7.0, rtol=1e-6)


def test_each_batch_shape_is_resolved_from_its_own_program(rung_bytes):
    """A longer batch after a short first one is held to the same budget,
    not retraced under the policy the first one took; a shape seen before
    runs the step kept for it."""
    engine = _engine()
    short, full = _batch(engine, seq=S // 4), _batch(engine)
    assert rung_bytes["save_qkv"] < rung_bytes["save_matmuls"]
    _describe_device(engine, int(rung_bytes["save_qkv"] / REMAT_HEADROOM) + 64)
    engine.train_batch(batch=short)
    assert engine.remat_resolution["policy"] == "save_matmuls"
    short_step = engine._compiled_train_step
    engine.train_batch(batch=full)
    assert engine.remat_resolution["policy"] == "save_qkv"
    assert [t["policy"] for t in engine.remat_resolution["tried"]] == \
        ["save_matmuls", "save_qkv"]
    assert engine._compiled_train_step is not short_step
    compiles = compile_counter()
    c0 = compiles()
    engine.train_batch(batch=short)
    assert engine._compiled_train_step is short_step
    assert engine.remat_resolution["policy"] == "save_matmuls"
    engine.train_batch(batch=full)
    assert engine.remat_resolution["policy"] == "save_qkv"
    assert compiles() - c0 == 0
    assert len(engine._remat_steps) == 2


@pytest.mark.parametrize("fits", ["save_matmuls", "save_attn", None])
def test_the_autotuner_and_the_engine_judge_a_candidate_by_one_budget(
        rung_bytes, fits, tmp_path):
    """A candidate that names no policy is resolved within the budget the
    autotuner then holds its program to: measured on the richest rung that
    budget takes, ``compile_oom`` only where the leanest does not fit, which
    is also what the prune path (the leanest program) says."""
    from deepspeed_tpu.autotuning.autotuner import (Autotuner,
                                                    AutotuningConfig,
                                                    MEMORY_SAFETY_MARGIN)
    from deepspeed_tpu.parallel import mesh

    engines = []

    def make_engine(overrides):
        mesh.reset_mesh()
        engines.append(_engine())
        return engines[-1]

    hbm = (1024 if fits is None
           else int(rung_bytes[fits] / MEMORY_SAFETY_MARGIN) + 64)
    tuner = Autotuner(make_engine, _batch, AutotuningConfig(
        enabled=True, hbm_bytes=hbm, start_profile_step=0,
        end_profile_step=1, results_dir=str(tmp_path)))
    rec = tuner._measure({})
    pruned, = tuner.compile_prune([{}])
    mesh.reset_mesh()
    if fits is None:
        assert rec.status == pruned.status == "compile_oom"
        assert rec.memory_bytes == pruned.memory_bytes == \
            rung_bytes["nothing_saveable"]
        return
    assert rec.status == pruned.status == "ok", (rec.error, pruned.error)
    assert engines[0].remat_resolution["policy"] == fits
    assert engines[0].remat_resolution["budget_bytes"] == \
        int(hbm * MEMORY_SAFETY_MARGIN)
    assert rec.memory_bytes == rung_bytes[fits] and rec.metric_val > 0
    assert pruned.memory_bytes == rung_bytes["nothing_saveable"]


def test_nothing_to_resolve_without_checkpointing_or_a_memory_limit():
    engine = _engine(remat=False)
    assert not engine._remat_auto
    # the CPU backend reports no limit: the unresolved step is the run one
    engine = _engine()
    assert engine._remat_auto
    assert jax.devices()[0].memory_stats() is None
    engine.train_batch(batch=_batch(engine))
    assert engine.remat_resolution is None


def test_the_top_rung_fitting_is_one_compile_and_the_first_step_none():
    engine = _engine()
    batch = _batch(engine)
    _describe_device(engine, 10 ** 12)
    compiles = compile_counter()
    global_batch = engine._collect_global_batch(batch)
    c0 = compiles()
    engine._select_train_step(global_batch)
    assert compiles() - c0 == 1
    assert engine.remat_resolution["policy"] == REMAT_LADDER[0]
    assert len(engine.remat_resolution["tried"]) == 1
    c0 = compiles()
    engine.train_batch(batch=batch)
    engine.compile_train_step(batch)
    assert compiles() - c0 == 0


def test_train_batch_resolves_on_its_first_call_and_tags_the_first_span():
    from deepspeed_tpu.observability import (Span, configure_tracer,
                                             get_tracer)

    engine = _engine()
    batch = _batch(engine)
    _describe_device(engine, 10 ** 12)
    # a lowering asked for first resolves nothing and is not kept
    assert "stablehlo" in engine.lower_train_step(batch).as_text()
    assert engine._compiled_train_step is None
    engine.train_batch(batch=batch)          # untraced: the attrs wait
    assert engine.remat_resolution["policy"] == REMAT_LADDER[0]
    get_tracer().reset()
    configure_tracer(enabled=True)
    try:
        engine.train_batch(batch=batch)
        engine.train_batch(batch=batch)
        steps = [s for s in get_tracer().recorder.snapshot()
                 if isinstance(s, Span) and s.name == "train.step"]
    finally:
        configure_tracer(enabled=False)
        get_tracer().reset()
    assert len(steps) == 2
    assert steps[0].attrs["remat_policy"] == REMAT_LADDER[0]
    assert steps[0].attrs["remat_budget_bytes"] == \
        int(REMAT_HEADROOM * 10 ** 12)
    assert steps[0].attrs["remat_temp_bytes"] == \
        engine.remat_resolution["temp_bytes"]
    assert REMAT_LADDER[0] in steps[0].attrs["remat_tried"]
    assert "remat_policy" not in steps[1].attrs


TOPOLOGIES = {
    "opt": dict(activation="relu", position="learned", tie_embeddings=True,
                norm="layernorm", attn_bias=True, mlp_bias=True),
    "pythia": dict(activation="gelu_exact", parallel_residual=True,
                   rotary_dim=8, norm="layernorm", attn_bias=True,
                   mlp_bias=True),
    "gptj": dict(activation="gelu", parallel_residual=True,
                 shared_layernorm=True, rotary_dim=8, rope_interleaved=True,
                 norm="layernorm", lm_head_bias=True),
    "moe": dict(num_experts=4, moe_top_k=2),
    "dropout": dict(activation="gelu", dropout=0.1),
}


@pytest.mark.parametrize("topology", ["opt", "pythia", "gptj"])
def test_the_unresolved_value_outside_the_engine_is_nothing_saveable(topology):
    """``forward`` under a caller's own jit: the same lowered program."""
    cfg = TransformerConfig(**{**SIZED, "dtype": jnp.float32,
                               **TOPOLOGIES[topology]})
    assert cfg.remat_policy == REMAT_AUTO
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((2, 33), jnp.int32)

    def lowered(c):
        def loss(p, toks):
            logits = forward(c, p, toks[:, :-1], attn_impl="xla",
                             deterministic=False, rng=jax.random.PRNGKey(0))
            return cross_entropy_loss(logits, toks[:, 1:])
        return jax.jit(jax.value_and_grad(loss)).lower(params,
                                                       tokens).as_text()

    named = lowered(dataclasses.replace(cfg, remat_policy="nothing_saveable"))
    assert lowered(cfg) == named
    assert lowered(dataclasses.replace(cfg, remat_policy="save_qkv")) != named


@pytest.mark.parametrize("stage,dp", [(1, 2), (3, 4)],
                         ids=["zero1-dp2", "zero3-dp4"])
@pytest.mark.parametrize("topology", list(TOPOLOGIES))
def test_every_rung_gives_nothing_saveables_step_to_the_bit(topology, stage,
                                                            dp):
    """One engine, the fused step of each rung run on a copy of the same
    state and the same batch: the loss, the gradient norm and every leaf of
    the state after the update (parameters, masters, Adam's moments: the
    first moment is the gradient times a constant) equal the last rung's."""
    engine = _engine(stage=stage, dp=dp, dtype=jnp.float32, mb=2,
                     **TOPOLOGIES[topology])
    batch = engine._collect_global_batch(_batch(engine, seq=32))
    out = {}
    for rung in REMAT_LADDER:
        step, _ = engine._compile_remat_rung(rung, batch)
        state = jax.tree_util.tree_map(jnp.copy, engine.state)
        new_state, metrics = step(state, batch)
        out[rung] = jax.tree_util.tree_map(np.asarray, (new_state, metrics))
    want = jax.tree_util.tree_leaves(out[REMAT_LADDER[-1]])
    assert np.isfinite(out[REMAT_LADDER[-1]][1]["loss"])
    for rung in REMAT_LADDER[:-1]:
        got = jax.tree_util.tree_leaves(out[rung])
        assert len(got) == len(want)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b, err_msg=rung)


@pytest.mark.parametrize("dp", [1, 4], ids=["one-device", "shard_map-dp4"])
def test_a_rung_that_keeps_the_kernels_residuals_runs_no_second_forward(dp):
    """The flash kernel's vjp names its own output and row statistics, so a
    rung that keeps them (with q, k, v, or running the projections again)
    leaves 3 kernels a layer in the gradient's program — forward, dq, dkv;
    the layers are one scan each way — where ``nothing_saveable`` and the
    unresolved value hold 4."""
    if dp > 1:
        initialize_mesh(MeshLayout(dp=dp), devices=jax.devices()[:dp])
    cfg = TransformerConfig(**{**SIZED, "hidden_size": 256, "max_seq_len": 256,
                               "dtype": jnp.float32})
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((4, 257), jnp.int32)

    def kernels(policy):
        c = dataclasses.replace(cfg, remat_policy=policy)

        def loss(p, toks):
            logits = forward(c, p, toks[:, :-1], attn_impl="pallas",
                             deterministic=False, rng=jax.random.PRNGKey(0))
            return cross_entropy_loss(logits, toks[:, 1:])
        return str(jax.make_jaxpr(jax.value_and_grad(loss))(
            params, tokens)).count("pallas_call")

    assert [kernels(r) for r in REMAT_LADDER] == [3, 3, 3, 4]
    assert kernels(REMAT_AUTO) == 4


def test_under_the_ring_a_rung_keeps_one_merged_output_a_layer():
    """The ring's per-step kernel does not name its partial output, so a
    rung keeps the layer's one merged ``attn_out`` and not one a ring step:
    every rung's gradient program holds ``nothing_saveable``'s kernels (two
    forward, step 0 and the scanned one, run again inside the backward, and
    their four backward kernels), as before the vjp named its residuals."""
    initialize_mesh(MeshLayout(sp=4, dp=2))
    cfg = TransformerConfig(**{**SIZED, "hidden_size": 128, "num_heads": 4,
                               "max_seq_len": 512, "dtype": jnp.float32})
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((2, 513), jnp.int32)

    def program(policy):
        c = dataclasses.replace(cfg, remat_policy=policy)

        def loss(p, toks):
            logits = forward(c, p, toks[:, :-1], attn_impl="ring",
                             deterministic=False, rng=jax.random.PRNGKey(0))
            return cross_entropy_loss(logits, toks[:, 1:])
        return str(jax.make_jaxpr(jax.value_and_grad(loss))(params, tokens))

    for rung in REMAT_LADDER[:-1]:
        text = program(rung)
        assert text.count("pallas_call") == 8, rung
        # kept, so named once: in the forward, not again in a recompute
        assert text.count("name=attn_out") == 1, rung
    assert program(REMAT_LADDER[-1]).count("pallas_call") == 8


def test_a_trainer_that_shares_its_device_with_generation_resolves_nothing():
    """The hybrid engine's caches and rollout pool are in no fused step's
    program: wrapped, the trainer keeps ``nothing_saveable``."""
    from deepspeed_tpu.runtime.hybrid_engine import DeepSpeedHybridEngine

    engine = _engine()
    assert engine._remat_auto
    hybrid = DeepSpeedHybridEngine(engine)
    _describe_device(engine, 10 ** 12)
    hybrid.train_batch(batch=_batch(engine))
    assert not engine._remat_auto and engine.remat_resolution is None
