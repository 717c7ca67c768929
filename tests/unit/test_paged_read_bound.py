"""The paged read's list of live pages and the serving engine's count of it
(ISSUE 27, ISSUE 29).

The paged forward reads one flat slot-major list of the call's live (slot,
page) pairs — every slot to its own length, nothing of a slot with no real
token — ``paged_read_pairs`` pairs a step; the list and its trip count are
computed on the device from ``start``, ``seq_mask`` and the page table
(``_paged_read_plan``).  The engine's ``gathered_rows`` span attr is the
host's copy of the same count (``paged_read_rows``).  Here: what the plan
lists, that the two agree for every length, that the spans of a served
backlog carry what the programs they launched were given, and that the read
costs the engine no program and the lookahead no tick.  All CPU, one tiny
model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.serving import Request
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.models.transformer import (_paged_read_plan,
                                              paged_read_pairs,
                                              paged_read_rows)
from deepspeed_tpu.observability.trace import configure_tracer

GEO = dict(b_slots=3, page_size=8, max_model_len=64)
MAXP = GEO["max_model_len"] // GEO["page_size"]


def _plan(start, seq_mask, page_size=GEO["page_size"], maxp=MAXP,
          table=None):
    seq_mask = np.asarray(seq_mask)
    if table is None:   # slot b's page j is physical page 1 + b*maxp + j
        table = 1 + np.arange(len(seq_mask) * maxp).reshape(-1, maxp)
    return jax.tree_util.tree_map(np.asarray, _paged_read_plan(
        jnp.asarray(table, jnp.int32), jnp.asarray(start, jnp.int32),
        jnp.asarray(seq_mask), page_size))


def _program_rows(start, seq_mask, page_size=GEO["page_size"], maxp=MAXP):
    """Rows a layer of the program reads, by its own trip count."""
    steps, slot = _plan(start, seq_mask, page_size, maxp)[:2]
    assert slot.shape[1] == paged_read_pairs(len(start), maxp)
    return int(steps) * slot.shape[1] * page_size


@pytest.mark.parametrize("maxp", [1, 5, 8])
@pytest.mark.parametrize("rows", [0, 1, 7, 8, 9, 15, 16, 17, 31, 32, 33, 63,
                                  64, 70])
def test_host_formula_is_the_programs_trip_count(rows, maxp):
    """Per-slot lengths in, rows read out.  Slot 0 holds no real token (and
    sits masked far out), slot 1 holds ``rows`` (0: no real token either; a
    position past the table, a verify block's tail, reads the whole row),
    slots 2 and 3 hold 9 and 20 rows: the host's count from the lengths is
    the program's trip count from ``start`` and ``seq_mask``."""
    ps = GEO["page_size"]
    start = [40, max(rows - 1, 0), 8, 19]
    mask = [[False], [rows > 0], [True], [True]]
    got = paged_read_rows([rows, 9, 20], ps, maxp, slots=4)
    assert got == _program_rows(start, mask, maxp=maxp)
    # an idle slot may be listed as 0 rows or left out
    assert got == paged_read_rows([0, rows, 9, 20], ps, maxp, slots=4)
    pairs = 4 * min(2, maxp)
    live = sum(min(-(-n // ps), maxp) for n in (rows, 9, 20))
    assert got == -(-live // pairs) * pairs * ps
    # every slot to its own length: the live rows, and under a step more
    assert 0 <= got - live * ps < pairs * ps


PLAN_CASES = {
    # start, mask [B,S]; page 8, 4 pages a slot
    "unequal-with-an-idle-slot-between": ([29, 5, 7, 31, 2],
                                          [[1], [0], [1], [1], [1]]),
    "page-edges": ([7, 8, 15, 16, 0], [[1], [1], [1], [1], [1]]),
    "verify-block-past-the-table": ([30, 12, 3], [[1] * 4] * 3),
    "masked-tail": ([4], [[1] * 9 + [0] * 7]),
    "no-real-token": ([21, 3, 0], [[0], [0], [0]]),
    "first-and-last-idle": ([9, 20, 9], [[0], [1], [0]]),
}


@pytest.mark.parametrize("case", list(PLAN_CASES))
def test_plan_lists_each_live_page_once_slot_major(case):
    """Every live slot's pages ``0 .. ceil((longest real position + 1) /
    page) - 1`` (at most the table row) once each, slot-major, nothing of a
    slot with no real token; past the total: no slot, the trash page, a
    limit that no row passes.  ``limit`` is the query's position within
    the pair's page."""
    ps, maxp = 8, 4
    start, mask = PLAN_CASES[case]
    mask = np.asarray(mask, bool)
    B, S = mask.shape
    table = np.random.default_rng(1).permutation(B * maxp).reshape(B, maxp) + 1
    steps, slot, pages, limit = _plan(start, mask, ps, maxp, table)
    pairs = paged_read_pairs(B, maxp)
    assert slot.shape == pages.shape == limit.shape[:2]
    assert slot.shape[1] == pairs and limit.shape[2] == S
    assert slot.size == -(-B * maxp // pairs) * pairs   # the whole table fits
    slot, pages, limit = slot.reshape(-1), pages.reshape(-1), \
        limit.reshape(-1, S)

    positions = np.asarray(start)[:, None] + np.arange(S)
    want = []
    for b in range(B):
        if mask[b].any():
            rows = positions[b][mask[b]].max() + 1
            want += [(b, j) for j in range(min(-(-rows // ps), maxp))]
    total = len(want)
    assert int(steps) == -(-total // pairs)
    assert list(slot[:total]) == [b for b, _ in want]
    assert list(pages[:total]) == [table[b, j] for b, j in want]
    for n, (b, j) in enumerate(want):
        np.testing.assert_array_equal(limit[n], positions[b] - j * ps)
    # past the total: in no slot, the trash page, nothing passes r <= limit
    assert (slot[total:] == B).all() and (pages[total:] == 0).all()
    assert (limit[total:] == -1).all()


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
        tracer.reset()      # leave no span behind for the next test's run
    assert len(results) == 8 and all(r.finish_reason == "length"
                                     for r in results)
    # the bound is in the program's inputs: launching ahead needs no change
    assert sv.lookahead_launched > 0 and sv.lookahead_dropped == 0
    # ... and no program: the inventory of the tree before the list (PR 28)
    assert sv.program_inventory() == {"decode": 1, "prefill_buckets": [16, 32],
                                      "cow": 1}
    assert sv._exec._decode_prog._cache_size() == 1

    ticks = [s.attrs for s in spans if s.name == "serve.decode"]
    # nothing dropped, so the ticks were consumed in the order launched
    assert len(ticks) == len(decodes) == sv._tick
    saved = 0     # rows under every slot read to the longest slot's pages
    for attrs, (lengths, active) in zip(ticks, decodes):
        assert attrs["gathered_rows"] == _program_rows(lengths,
                                                       active[:, None])
        assert attrs["live_rows"] == lengths[active].sum()
        assert 0 < attrs["live_rows"] <= attrs["gathered_rows"]
        saved = max(saved, GEO["b_slots"] * -(-(
            lengths[active].max() + 1) // GEO["page_size"])
            * GEO["page_size"] - attrs["gathered_rows"])
    # the read follows the slots: not every tick reads the same, and some
    # tick reads less than every slot to the longest slot's pages
    assert len({a["gathered_rows"] for a in ticks}) > 1
    assert saved > 0
    assert max(a["gathered_rows"] for a in ticks) < (
        GEO["b_slots"] * GEO["max_model_len"])

    fills = [s.attrs for s in spans if s.name == "serve.prefill"]
    assert len(fills) == len(prefills) == 8
    for attrs, (s_pad, n_real, start) in zip(fills, prefills):
        assert (attrs["bucket"], attrs["tokens"]) == (s_pad, n_real)
        mask = (np.arange(s_pad) < n_real)[None, :]
        assert attrs["gathered_rows"] == _program_rows([start], mask)
        assert start + n_real <= attrs["gathered_rows"]
