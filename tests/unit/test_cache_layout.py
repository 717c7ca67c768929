"""What a slot's cache is made of, said once (``inference/cache_layout.py``):
the pools a kind of cache asks for, the whole mechanism x kind table, and
the span attrs against the host's copies of the programs' trip counts.
Host-only: no program is compiled."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.cache_layout import REFUSED, CacheLayout
from deepspeed_tpu.inference.page_pool import PagePool
from deepspeed_tpu.models import get_config
from deepspeed_tpu.models import transformer as T

PAGE, SLOTS, MAXP = 8, 3, 12
MOE = dict(hidden_size=64, intermediate_size=32, num_heads=4, vocab_size=256,
           num_experts=8, moe_top_k=3, dtype=jnp.float32)
CONFIGS = {
    "uniform": lambda: get_config("olmoe-1b-7b", num_layers=2, **MOE),
    "grouped": lambda: get_config("olmoe-1b-7b", num_layers=3,
                                  dense_layers=1, **MOE),
    "window": lambda: get_config(
        "mimo-v2.5", num_layers=7, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_heads=8, num_kv_heads=2,
        window_kv_heads=4, head_dim=24, v_head_dim=16, rotary_dim=8,
        window_size=16, num_experts=16, moe_experts_held=4, moe_top_k=3,
        vocab_size=256, max_seq_len=512, dtype=jnp.float32),
    "latent": lambda: get_config(
        "kanana-2-30b-a3b", num_layers=4, hidden_size=64,
        intermediate_size=96, moe_intermediate_size=32, num_heads=4,
        head_dim=24, v_head_dim=16, rotary_dim=8, kv_lora_rank=32,
        num_experts=16, moe_experts_held=4, moe_top_k=3, vocab_size=256,
        max_seq_len=512, dtype=jnp.float32),
    "state": lambda: get_config(
        "falcon-h1-34b", num_layers=2, hidden_size=64, intermediate_size=96,
        num_heads=4, num_kv_heads=2, head_dim=16, vocab_size=256,
        ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_groups=2, ssm_chunk=8,
        max_seq_len=512, dtype=jnp.float32),
    "looped": lambda: get_config(
        "ouro-2.6b", num_layers=3, hidden_size=64, intermediate_size=96,
        num_heads=4, num_kv_heads=4, head_dim=16, vocab_size=256,
        max_seq_len=512, dtype=jnp.float32),
}
# what each kind's refusal names the model by (tier-1 matches on these)
NAMED = {"grouped": ("leading dense layers",),
         "window": ("window", "layer_pattern"),
         "latent": ("latent",),
         "state": ("state-space layers (a state a slot)",),
         "looped": ("loop_passes",)}
# mechanism x kind, written out: True = works on that kind of cache
PAGES_ALONE = dict(uniform=True, grouped=True, window=False, latent=False,
                   state=False, looped=True)
TABLE = {
    "tensor-sharded heads (tp > 1)": PAGES_ALONE,
    "copy-on-write page snapshots (prefix_cache=True)": PAGES_ALONE,
    "KV-page tiering": PAGES_ALONE,
    "the int8 pool": PAGES_ALONE,
    "multi-tenant adapters": {**PAGES_ALONE, "grouped": False},
    "prefix sharing (prefix_cache=True)": PAGES_ALONE,
    "speculative decoding": PAGES_ALONE,
    "pages that follow a slot's length (recompute preemption)": PAGES_ALONE,
}


def layout(kind):
    return CacheLayout(CONFIGS[kind](), SLOTS, PAGE, MAXP, 1 + SLOTS * MAXP)


def test_the_table_names_every_mechanism_once():
    assert set(TABLE) == set(REFUSED)


@pytest.mark.parametrize("kind", list(CONFIGS))
@pytest.mark.parametrize("mechanism", list(TABLE))
def test_mechanism_by_kind(mechanism, kind):
    lay = layout(kind)
    assert lay.kind == kind == T.cache_kind(lay.cfg)[0]
    allowed = TABLE[mechanism][kind]
    assert lay.allows(mechanism) is allowed
    if allowed:
        lay.refuse(mechanism)       # a mechanism that works raises nothing
        return
    with pytest.raises(NotImplementedError) as e:
        lay.refuse(mechanism)
    msg = str(e.value)
    assert mechanism in msg and "does not support a model with" in msg
    assert all(name in msg for name in NAMED[kind])
    # the model layer's own refusals name the model by the same words
    with pytest.raises(NotImplementedError) as e:
        T._hybrid_refuse("the contiguous cache", lay.cfg)
    assert T.cache_kind(lay.cfg)[1] in str(e.value) and lay.description in msg


@pytest.mark.parametrize("kind", list(CONFIGS))
def test_pools_and_what_the_cache_is_made_with(kind):
    lay = layout(kind)
    assert lay.pools[0] == (1 + SLOTS * MAXP, MAXP)
    assert lay.stateful == (kind == "state")
    assert lay.allows("prefix sharing (prefix_cache=True)") == (
        kind in ("uniform", "grouped", "looped"))
    # a page's rows: a layer of the model, or of a looped model of every pass
    assert (lay.passes, lay.depth) == ((4, 12) if kind == "looped" else
                                       (1, lay.cfg.num_layers))
    if kind == "window":
        ring = T.window_ring_pages(16, PAGE)
        assert lay.ring_pages == ring == 3
        assert lay.pools[1] == (1 + SLOTS * ring, ring)
        assert lay.kind_heads == {"full": 2 * 2, "window": 4 * 5}
        pools = [PagePool(n, SLOTS, per) for n, per in lay.pools]
        pools[0].take(7), pools[1].take(6)
        assert lay.tick_attrs(pools, False) == {
            "page_wait": 0, "pages_free": SLOTS * MAXP - 7,
            "pages_full": 7, "pages_window": 6}
    else:
        assert len(lay.pools) == 1 and not lay.ring_pages
        pool = PagePool(lay.pools[0][0], SLOTS, lay.pools[0][1])
        pool.take(5)
        assert lay.tick_attrs([pool], True) == {
            "page_wait": 1, "pages_free": SLOTS * MAXP - 5}
        assert lay.window_pages == 0 and lay.kind_heads == {}
    cache = jax.eval_shape(lambda: T.init_paged_cache(
        lay.cfg, lay.pools[0][0], PAGE, **lay.pool_kw))
    if kind in ("uniform", "looped"):
        assert cache["k"].shape[0] == lay.depth
    leaves = {"window": {"k", "v", "k_window", "v_window"},
              "latent": {"latent"},
              "state": {"k", "v", "ssm_state", "ssm_conv"}}
    assert set(cache) == leaves.get(kind, {"k", "v"})
    if kind == "window":
        assert cache["k_window"].shape[1] == lay.window_pages
    if kind == "state":
        assert cache["ssm_state"].shape[1] == SLOTS


LENGTHS = [[1], [8, 9, 30], [17, 64], [96, 3, 40]]


@pytest.mark.parametrize("kind", list(CONFIGS))
@pytest.mark.parametrize("lengths", LENGTHS)
def test_decode_attrs_are_the_hosts_row_counts(kind, lengths):
    lay = layout(kind)
    lay.state_slot_bytes, lay.state_passes = 1000, 3    # the executor's
    lay.kv_token_bytes, lay.kv_write_leaves = 96, (1, 3)
    a = lay.decode_attrs(lengths, SLOTS)
    rows = T.paged_read_rows(lengths, PAGE, MAXP, SLOTS)
    assert a["gathered_rows"] == rows >= sum(lengths)
    assert a["passes"] == (4 if kind == "looped" else 1)
    assert a["kv_bytes"] == 96 * sum(lengths)
    assert (a["kv_row_write_leaves"], a["kv_page_write_leaves"]) == (1, 3)
    want = {"gathered_rows", "passes", "kv_bytes", "kv_row_write_leaves",
            "kv_page_write_leaves"}
    if kind == "window":
        want |= {"kv_rows_full", "kv_live_rows_full", "kv_rows_window",
                 "kv_live_rows_window", "kv_slots_live",
                 "kv_slots_past_window"}
        assert (a["kv_slots_live"], a["kv_slots_past_window"]) == (
            len(lengths), sum(n > 16 for n in lengths))
        assert a["kv_rows_full"] == rows * 4
        assert a["kv_live_rows_full"] == sum(lengths) * 4
        assert a["kv_rows_window"] == 20 * T.window_read_rows(
            lengths, PAGE, 16, SLOTS)
        assert a["kv_live_rows_window"] == 20 * sum(
            min(n, 16) for n in lengths)
    if kind == "state":
        want |= {"state_slots", "state_bytes", "state_passes",
                 "state_layers", "kv_layers", "kv_live_rows",
                 "layers_by_kind"}
        assert (a["state_slots"], a["state_bytes"], a["state_passes"]) == (
            len(lengths), 1000 * len(lengths), 3)
        # a parallel block: every layer has both; rows x layers with K/V
        assert a["state_layers"] == a["kv_layers"] == lay.cfg.num_layers
        assert a["kv_live_rows"] == sum(lengths) * lay.cfg.num_layers
    assert set(a) == want


@pytest.mark.parametrize("kind", list(CONFIGS))
@pytest.mark.parametrize("bucket,tokens,shared", [
    (16, 5, 0), (32, 32, 0), (32, 17, 16), (64, 41, 0)])
def test_prefill_attrs_are_the_hosts_trip_counts(monkeypatch, kind, bucket,
                                                 tokens, shared):
    monkeypatch.setattr(T, "CAUSAL_BLOCK_CHUNK", 4)     # tiny prompts walk
    monkeypatch.setattr(T, "WINDOW_BLOCK_CHUNKS", 1)
    lay = layout(kind)
    lay.kv_token_bytes = 96                             # the executor's
    a = lay.prefill_attrs(bucket, tokens, shared)
    assert a["passes"] == (4 if kind == "looped" else 1)
    assert a["kv_bytes"] == 96 * (shared + tokens)
    want = {"gathered_rows", "passes", "kv_bytes"}
    if kind in ("window", "latent"):
        want |= {"walk_steps", "walk_steps_bucket"}
        assert a["gathered_rows"] == 0
        assert a["walk_steps"] == T.causal_walk_steps(bucket, tokens)
        assert a["walk_steps_bucket"] == T.causal_walk_steps(bucket)
        assert 1 < a["walk_steps"] <= a["walk_steps_bucket"]
    else:
        assert a["gathered_rows"] == T.paged_read_rows(
            [shared + tokens], PAGE, MAXP, 1) >= shared + tokens
    if kind == "window":
        want |= {"kv_rows_full", "kv_live_rows_full", "kv_rows_window",
                 "kv_live_rows_window"}
        assert a["kv_rows_full"] == 4 * T.block_read_rows(bucket,
                                                          tokens=tokens)
        assert a["kv_rows_window"] == 20 * T.block_read_rows(
            bucket, 16, tokens=tokens)
        assert (a["kv_live_rows_full"], a["kv_live_rows_window"]) == (
            4 * tokens, 20 * tokens)
    if kind == "state":
        want |= {"scan_chunks", "scan_chunks_bucket", "state_reset"}
        assert a["scan_chunks"] == T.ssm_scan_chunks(lay.cfg, bucket, tokens)
        assert a["scan_chunks_bucket"] == bucket // 8
        assert a["state_reset"] == int(shared == 0)
        # and how the bucket's program runs the scan: no kernel off a TPU
        assert a.pop("ssm_scan") == "xla"
    assert set(a) == want
    assert all(isinstance(v, (int, np.integer)) for v in a.values())
