"""The paged read's bound and the serving engine's count of it (ISSUE 27).

The paged forward reads every slot's pages up to the longest live position
of the call, ``PAGED_READ_GRANULE`` pages a step; the trip count is computed
on the device from ``start`` and ``seq_mask``.  The engine's ``gathered_rows``
span attr is the host's copy of the same formula (``paged_read_pages``).
Here: the two agree for every length, the spans of a served backlog carry
what the programs they launched were given, and the bound costs the engine
no program and the lookahead no tick.  All CPU, one tiny model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.serving import Request
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.models.transformer import (PAGED_READ_GRANULE,
                                              _paged_read_steps,
                                              paged_read_pages)
from deepspeed_tpu.observability.trace import configure_tracer

GEO = dict(b_slots=3, page_size=8, max_model_len=64)
MAXP = GEO["max_model_len"] // GEO["page_size"]


def _program_rows(start, seq_mask, page_size=GEO["page_size"], maxp=MAXP):
    """Rows of one slot the program reads, by its own trip count."""
    seq_mask = np.asarray(seq_mask)
    positions = np.asarray(start)[:, None] + np.arange(seq_mask.shape[1])
    steps = int(_paged_read_steps(jnp.asarray(positions, jnp.int32),
                                  jnp.asarray(seq_mask), page_size, maxp))
    return min(steps * PAGED_READ_GRANULE, maxp) * page_size


@pytest.mark.parametrize("maxp", [1, 5, 8])
@pytest.mark.parametrize("rows", [0, 1, 7, 8, 9, 15, 16, 17, 31, 32, 33, 63,
                                  64, 70])
def test_host_formula_is_the_programs_trip_count(rows, maxp):
    """``rows`` = the longest live position + 1 (0: no real token); a
    position past the table (a verify block's tail) reads the whole row."""
    ps = GEO["page_size"]
    # one real token at position rows - 1 of slot 1, slot 0 masked far out
    start = [40, max(rows - 1, 0)]
    mask = [[False], [rows > 0]]
    got = paged_read_pages(rows, ps, maxp) * ps
    assert got == _program_rows(start, mask, maxp=maxp)
    step = min(PAGED_READ_GRANULE, maxp) * ps
    assert got == min(max(-(-rows // step), 1) * step, maxp * ps)


@pytest.fixture(scope="module")
def engine():
    model = CausalLM("tiny", dtype=jnp.float32, attn_impl="xla")
    return deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32"},
        params=model.init_fn(jax.random.PRNGKey(3)))


def _backlog(n=8, seed=5):
    rng = np.random.default_rng(seed)
    return [Request(rid=f"b{i}", max_new_tokens=int(rng.integers(4, 24)),
                    input_ids=rng.integers(1, 200, (int(rng.integers(3, 30)),)
                                           ).astype(np.int32))
            for i in range(n)]


def test_span_rows_are_what_the_launched_programs_read(engine):
    """Every request due at once, lookahead on: each ``serve.decode`` and
    ``serve.prefill`` span's ``gathered_rows`` is what the program it stands
    for reads, reckoned by the program's own function from the inputs the
    executor was handed."""
    sv = engine.serving(lookahead=True, **GEO)
    decodes, prefills = [], []
    decode, prefill = sv._exec.decode, sv._exec.prefill

    def spy_decode(page_table, lengths, last_tok, active, *a, **kw):
        decodes.append((np.array(lengths), np.array(active)))
        return decode(page_table, lengths, last_tok, active, *a, **kw)

    def spy_prefill(s_pad, pt_row, tokens, n_real, start, *a, **kw):
        prefills.append((s_pad, int(n_real), int(start)))
        return prefill(s_pad, pt_row, tokens, n_real, start, *a, **kw)

    sv._exec.decode, sv._exec.prefill = spy_decode, spy_prefill
    tracer = configure_tracer(enabled=True, capacity=8192)
    tracer.reset()
    try:
        results = sv.run(_backlog())
        spans = tracer.recorder.snapshot()
    finally:
        configure_tracer(enabled=False)
    assert len(results) == 8 and all(r.finish_reason == "length"
                                     for r in results)
    # the bound is in the program's inputs: launching ahead needs no change
    assert sv.lookahead_launched > 0 and sv.lookahead_dropped == 0
    assert sv.program_inventory()["decode"] == 1
    assert sv._exec._decode_prog._cache_size() == 1

    ticks = [s.attrs for s in spans if s.name == "serve.decode"]
    # nothing dropped, so the ticks were consumed in the order launched
    assert len(ticks) == len(decodes) == sv._tick
    for attrs, (lengths, active) in zip(ticks, decodes):
        want = GEO["b_slots"] * _program_rows(lengths, active[:, None])
        assert attrs["gathered_rows"] == want
        assert attrs["live_rows"] == lengths[active].sum()
        assert 0 < attrs["live_rows"] <= attrs["gathered_rows"]
    # the read follows the slots: not every tick reads the same
    assert len({a["gathered_rows"] for a in ticks}) > 1
    assert max(a["gathered_rows"] for a in ticks) < (
        GEO["b_slots"] * GEO["max_model_len"])

    fills = [s.attrs for s in spans if s.name == "serve.prefill"]
    assert len(fills) == len(prefills) == 8
    for attrs, (s_pad, n_real, start) in zip(fills, prefills):
        assert (attrs["bucket"], attrs["tokens"]) == (s_pad, n_real)
        mask = (np.arange(s_pad) < n_real)[None, :]
        assert attrs["gathered_rows"] == _program_rows([start], mask)
        assert start + n_real <= attrs["gathered_rows"]
