"""A prompt's causal walk (``_attention_causal_block``) and its window
layers' groups of chunks (``_attention_window_block``) bounded by how far the
block's own real tokens reach: real rows equal the masked product and the
unbounded form's bit for bit, a chunk that holds padding alone runs nothing
and comes out zero, and the host's formulas (``causal_walk_steps``,
``block_read_rows``) are the device's trip counts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import get_config
from deepspeed_tpu.models import transformer as T

C, S = 16, 64


@pytest.fixture(autouse=True)
def chunk_of_16(monkeypatch):
    monkeypatch.setattr(T, "CAUSAL_BLOCK_CHUNK", C)


def _layer(group="full_moe"):
    cfg = get_config(
        "mimo-v2.5", num_layers=7, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_heads=8, num_kv_heads=2,
        window_kv_heads=4, head_dim=24, v_head_dim=16, rotary_dim=8,
        window_size=16, num_experts=16, moe_experts_held=4, moe_top_k=3,
        vocab_size=256, dtype=jnp.float32)
    return T.layer_groups(cfg)[group][0]


def _qkv(B, seed=6, kv_heads=2):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, S, 8, 24)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, kv_heads, 24)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, kv_heads, 16)), jnp.float32)
    return q, k, v, jnp.broadcast_to(jnp.arange(S)[None], (B, S))


def _mask(counts):
    return jnp.arange(S)[None, :] < jnp.asarray(counts)[:, None]


# one row, and two rows with unequal counts (the bound is the longer's)
COUNTS = ([(n,) for n in (1, C - 1, C, C + 1, 2 * C, S - C + 1, S)]
          + [(1, C + 1), (2 * C, C - 1), (S, 1), (S - C + 1, 2 * C)])


@pytest.mark.parametrize("counts", COUNTS, ids=lambda c: "-".join(map(str, c)))
def test_bounded_walk_is_the_masked_product_on_real_rows(counts):
    g = _layer()
    q, k, v, pos = _qkv(len(counts))
    want = T._attention(g, q, k, v, pos, "xla", custom_positions=True)
    whole = T._attention_causal_block(g, q, k, v, pos)
    reach = T._block_reach(_mask(counts))
    assert int(reach) == max(counts)
    r = -(-int(reach) // C)
    got = jax.jit(lambda *a: T._attention_causal_block(g, *a))(
        q, k, v, pos, reach)
    assert np.isfinite(np.asarray(got)).all()
    for b, n in enumerate(counts):
        np.testing.assert_allclose(got[b, :n], want[b, :n], atol=2e-6)
    # every chunk the walk runs is the unbounded walk's, bit for bit, and a
    # chunk past the last real token is never run
    assert (np.asarray(got[:, :r * C]) == np.asarray(whole[:, :r * C])).all()
    assert (np.asarray(got[:, r * C:]) == 0).all()
    # the host's formula is the device's trip count: chunk i < r runs i + 1
    steps = sum(i + 1 for i in range(S // C) if i * C < int(reach))
    assert T.causal_walk_steps(S, max(counts)) == steps
    assert T.block_read_rows(S, tokens=max(counts)) == C * steps


def test_the_count_absent_is_the_whole_walk():
    g = _layer()
    q, k, v, pos = _qkv(2)
    whole = T._attention_causal_block(g, q, k, v, pos)
    full = T._attention_causal_block(
        g, q, k, v, pos, T._block_reach(jnp.ones((2, S), bool)))
    assert (np.asarray(whole) == np.asarray(full)).all()
    np.testing.assert_allclose(
        whole, T._attention(g, q, k, v, pos, "xla", custom_positions=True),
        atol=2e-6)
    n = S // C
    assert T.causal_walk_steps(S) == T.causal_walk_steps(S, S) == n * (n + 1) // 2
    assert T.block_read_rows(S) == T.block_read_rows(S, tokens=S)


def test_nothing_of_a_skipped_chunk_is_read():
    """Keys and values past the last real chunk may hold anything: no step
    touches them (a NaN there would show in every row a step folded it
    into)."""
    g = _layer()
    q, k, v, pos = _qkv(1)
    n = C + 3
    bad = jnp.arange(S)[None, :, None, None] >= 2 * C
    got = T._attention_causal_block(
        g, q, jnp.where(bad, jnp.nan, k), jnp.where(bad, jnp.nan, v), pos,
        T._block_reach(_mask((n,))))
    want = T._attention(g, q, k, v, pos, "xla", custom_positions=True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got[0, :n], want[0, :n], atol=2e-6)


@pytest.mark.parametrize("block,tokens,steps", [
    (8192, 4600, 45), (8192, 8192, 136), (8192, 6144, 78), (4096, 3000, 21),
    (4096, 2048, 10), (1024, 513, 3), (512, 300, 1), (256, 200, 1),
    (16384, 12288, 300)])
def test_host_formula_at_the_shipped_chunk(monkeypatch, block, tokens, steps):
    monkeypatch.setattr(T, "CAUSAL_BLOCK_CHUNK", 512)
    assert T.causal_walk_steps(block, tokens) == steps
    rows = 512 * steps if block >= 1024 else block
    assert T.block_read_rows(block, tokens=tokens) == rows
    # a window layer's: the groups of 16 chunks of 128 that hold a token
    groups = -(-tokens // 2048) * 2048 if block > 2048 else block
    assert T.block_read_rows(block, 128, tokens=tokens) == 2 * groups
    assert T.block_read_rows(block, 128) == 2 * block


W = 8       # a window layer: chunks of 8, taken two at a time below
WINDOW_COUNTS = [(n,) for n in (1, 2 * W - 1, 2 * W, 2 * W + 1, 4 * W,
                                S - 2 * W + 1, S)] + [(3, 2 * W + 1), (S, 1)]


@pytest.mark.parametrize("counts", WINDOW_COUNTS,
                         ids=lambda c: "-".join(map(str, c)))
def test_window_groups_past_the_last_real_token_are_not_computed(
        monkeypatch, counts):
    monkeypatch.setattr(T, "WINDOW_BLOCK_CHUNKS", 2)
    g = _layer("window_moe")
    q, k, v, pos = _qkv(len(counts), seed=5, kv_heads=4)
    sink = jnp.asarray(np.random.default_rng(5).standard_normal(8),
                       jnp.float32)
    want = T._attention(g, q, k, v, pos, "xla", custom_positions=True,
                        window=W, sink=sink)
    whole = T._attention_window_block(g, q, k, v, pos, W, sink)
    reach = T._block_reach(_mask(counts))
    got = jax.jit(lambda *a: T._attention_window_block(
        g, *a[:4], W, *a[4:]))(q, k, v, pos, sink, reach)
    for b, n in enumerate(counts):
        np.testing.assert_allclose(got[b, :n], want[b, :n], atol=2e-6)
    # groups of 2 chunks of 8: those a real token reaches are the unbounded
    # form's bit for bit, the others zero
    held = -(-max(counts) // (2 * W)) * 2 * W
    assert (np.asarray(got[:, :held]) == np.asarray(whole[:, :held])).all()
    assert (np.asarray(got[:, held:]) == 0).all()
    assert T.block_read_rows(S, W, tokens=max(counts)) == 2 * held
    # a block taken whole (no more chunks than a group) has no bound to use
    monkeypatch.setattr(T, "WINDOW_BLOCK_CHUNKS", 16)
    np.testing.assert_array_equal(
        T._attention_window_block(g, q, k, v, pos, W, sink, reach), whole)
    assert T.block_read_rows(S, W, tokens=max(counts)) == 2 * S
