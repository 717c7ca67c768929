"""ZeRO-3's collectives behind the compute beside them: the rule that hands
a step's compile the accelerator's collective-overlap options (PR 60).

The rule reads the plan and the accelerator, nothing else: stage 3 over ZeRO
axes of more than one device on the TPU accelerator gets the option set;
stage <= 2, a one-device mesh and the CPU accelerator get none; a key of
``DS_TPU_XLA_OPTIONS`` wins over the rule's.  The options themselves are
compiled by the installed libtpu in ``test_chip_bringup.py``."""
import numpy as np
import pytest

import jax

import deepspeed_tpu
from deepspeed_tpu.accelerator import get_accelerator, set_accelerator
from deepspeed_tpu.accelerator.tpu_accelerator import (
    COLLECTIVE_OVERLAP_OPTIONS, CPU_Accelerator, TPU_Accelerator)
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.parallel.mesh import MeshLayout, initialize_mesh
from deepspeed_tpu.runtime.zero.planner import plan_sharding

from .simple_model import random_batch
from .test_engine import HID, _make_engine, _train


@pytest.fixture
def tpu_accelerator():
    """The TPU accelerator answering for this process: the rule is read,
    no step is compiled under it (a CPU compile refuses an ``xla_tpu_*``
    key, which is why the CPU accelerator's set is empty)."""
    before = get_accelerator()
    set_accelerator(TPU_Accelerator())
    yield
    set_accelerator(before)


def _engine(stage, dp, **zero):
    config = {"train_micro_batch_size_per_gpu": 1,
              "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
              "bf16": {"enabled": True}, "steps_per_print": 10 ** 9,
              "zero_optimization": {"stage": stage, **zero}}
    layout = MeshLayout(dp=dp) if dp > 1 else MeshLayout()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=CausalLM("tiny"), config=config,
        mesh=initialize_mesh(layout, devices=jax.devices()[:dp]))
    return engine


def test_the_option_set_names_only_the_tpu_compilers_keys():
    assert COLLECTIVE_OVERLAP_OPTIONS
    assert all(k.startswith("xla_") for k in COLLECTIVE_OVERLAP_OPTIONS)
    assert TPU_Accelerator().collective_overlap_options() == \
        COLLECTIVE_OVERLAP_OPTIONS
    # a copy: a caller's edit is not the next engine's rule
    TPU_Accelerator().collective_overlap_options()["x"] = "y"
    assert "x" not in COLLECTIVE_OVERLAP_OPTIONS
    assert CPU_Accelerator().collective_overlap_options() == {}


@pytest.mark.parametrize("stage,dp,expected", [
    (3, 4, True), (3, 1, False), (2, 4, False), (1, 4, False), (0, 4, False)])
def test_the_rule_reads_the_plan_and_the_accelerator(tpu_accelerator, stage,
                                                     dp, expected):
    engine = _engine(stage, dp)
    assert engine.plan.gathers_params is expected
    assert engine.step_compile_options == (
        COLLECTIVE_OVERLAP_OPTIONS if expected else {})


def test_hpz_gathers_over_its_inner_axes(tpu_accelerator):
    """hpZ's compute view is sharded over the inner axes alone: 4 of the 8
    devices, which is still something to gather."""
    engine = _make_engine(stage=3, precision="bf16",
                          zero_optimization={"stage": 3,
                                             "zero_hpz_partition_size": 4})
    assert dict(engine.mesh.shape)["data_outer"] == 2
    assert engine.plan.param_zero_size == 4 and engine.plan.gathers_params
    assert engine.step_compile_options == COLLECTIVE_OVERLAP_OPTIONS


def test_the_cpu_accelerator_gets_no_option():
    engine = _engine(3, 4)
    assert engine.plan.gathers_params
    assert engine.step_compile_options == {}


def test_a_users_key_wins_key_by_key(tpu_accelerator, monkeypatch):
    ours = next(iter(COLLECTIVE_OVERLAP_OPTIONS))
    monkeypatch.setenv("DS_TPU_XLA_OPTIONS", f"{ours}=theirs,xla_other=1")
    got = _engine(3, 4).step_compile_options
    assert got == {**COLLECTIVE_OVERLAP_OPTIONS, ours: "theirs",
                   "xla_other": "1"}
    # and where the rule gives nothing the passthrough is all there is
    assert _engine(2, 4).step_compile_options == {ours: "theirs",
                                                  "xla_other": "1"}


def test_the_plan_says_what_it_gathers_over():
    shapes = {"w": jax.ShapeDtypeStruct((8, 8), np.float32)}
    mesh = initialize_mesh(MeshLayout(dp=4), devices=jax.devices()[:4])
    for stage, size, gathers in ((3, 4, True), (2, 4, False)):
        plan = plan_sharding(shapes, stage, mesh)
        assert (plan.param_zero_size, plan.gathers_params) == (size, gathers)
    one = initialize_mesh(MeshLayout(), devices=jax.devices()[:1])
    plan = plan_sharding(shapes, 3, one)
    assert (plan.param_zero_size, plan.gathers_params) == (1, False)


def test_the_ready_line_names_the_options(tpu_accelerator, caplog):
    import logging

    from deepspeed_tpu.utils.logging import logger

    logger.propagate = True
    try:
        with caplog.at_level(logging.INFO):
            _engine(3, 4)
    finally:
        logger.propagate = False
    ready = [r.getMessage() for r in caplog.records
             if "engine ready" in r.getMessage()]
    assert ready and all(k in ready[-1] for k in COLLECTIVE_OVERLAP_OPTIONS)


def test_stage3_step_under_the_rule_is_stage0s():
    """Four forced host devices: the ZeRO-3 fused step, compiled through the
    rule's wrapper, gives the losses and the updated masters of the stage-0
    step to ``test_zero_stage_loss_parity``'s tolerance."""
    engines = {stage: _make_engine(stage=stage) for stage in (0, 3)}
    assert engines[3].plan.gathers_params
    assert not engines[0].plan.gathers_params
    losses = {s: _train(e, steps=4) for s, e in engines.items()}
    np.testing.assert_allclose(losses[3], losses[0], rtol=2e-4, atol=1e-5)
    masters = {s: jax.tree_util.tree_leaves(
        e.state.master_params if e.state.master_params is not None
        else e.state.params) for s, e in engines.items()}
    for a, b in zip(masters[3], masters[0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5)
    # the same batch again through the eval step, which the rule covers too
    batch = random_batch(engines[3].train_batch_size, HID, 99)
    np.testing.assert_allclose(float(engines[3].eval_batch(batch)),
                               float(engines[0].eval_batch(batch)),
                               rtol=2e-4, atol=1e-5)
