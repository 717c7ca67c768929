"""``benchmark/lib/reference_ouro.py`` by itself: its pieces against
arithmetic written out by hand in numpy, what each of its passes starts
from, the mutations a test can make of it, and the system against it at a
tiny size in bfloat16 with the float8 counter-reading beside."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_ouro as R

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
KW = dict(n_prompt=20, block_tokens=32, pass_prompt=21, pass_bucket=32,
          n_decode=4, page_size=16)


def _cfg(**over):
    from deepspeed_tpu.models import get_config

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ouro-2.6b.json")) as f:
        spec = json.load(f)["rehearse_transformer_config"]
    return get_config(spec["base"], **{**spec["overrides"], **over})


def _params(cfg, seed=3):
    """Seeded weights, every norm scale away from 1."""
    from deepspeed_tpu.models import init_params

    p = init_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    p["layers"] = {k: (v * jnp.asarray(
        1 + 0.2 * rng.standard_normal(v.shape), v.dtype)
        if k.endswith("norm_scale") else v) for k, v in p["layers"].items()}
    p["final_norm_scale"] = p["final_norm_scale"] + jnp.asarray(
        0.2 * rng.standard_normal(p["final_norm_scale"].shape), jnp.float32)
    return jax.tree_util.tree_map(lambda a: a.astype(cfg.dtype), p)


@pytest.fixture(scope="module")
def tiny():
    cfg = _cfg(dtype=jnp.float32)
    return cfg, _params(cfg)


def _np_rms(x, w, eps):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def test_rotary_and_attention_by_hand(tiny):
    cfg, params = tiny
    s = R.spec(cfg)
    lp = {k: np.asarray(v[1], np.float64) for k, v in
          params["layers"].items()}
    rng = np.random.default_rng(0)
    S, H, hd = 7, cfg.num_heads, cfg.dims_per_head
    u = rng.standard_normal((S, cfg.hidden_size))
    pos = np.arange(S)

    def rot(x):     # [S, H, hd]: pairs (i, i + hd/2)
        out = np.empty_like(x)
        for i in range(hd // 2):
            ang = pos * s["theta"] ** (-2 * i / hd)
            a, b = x[..., i], x[..., i + hd // 2]
            out[..., i] = a * np.cos(ang)[:, None] - b * np.sin(ang)[:, None]
            out[..., i + hd // 2] = (b * np.cos(ang)[:, None]
                                     + a * np.sin(ang)[:, None])
        return out

    q = rot((u @ lp["wq"]).reshape(S, H, hd))
    k = rot((u @ lp["wk"]).reshape(S, H, hd))
    v = (u @ lp["wv"]).reshape(S, H, hd)
    want = np.zeros((S, H, hd))
    for h in range(H):
        for t in range(S):          # causal: positions 0 .. t
            sc = q[t, h] @ k[:t + 1, h].T / np.sqrt(hd)
            p = np.exp(sc - sc.max())
            want[t, h] = (p / p.sum()) @ v[:t + 1, h]
    want = want.reshape(S, H * hd) @ lp["wo"]
    with jax.default_matmul_precision("highest"):
        got = R.attention(s, {k_: jnp.asarray(v_, jnp.float32)
                              for k_, v_ in lp.items()},
                          jnp.asarray(u, jnp.float32), jnp.arange(S))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(
        R.rotary(jnp.asarray(q, jnp.float32), jnp.zeros((S,), jnp.int32),
                 s["theta"]), q, rtol=1e-6)       # position 0 turns nothing


def test_a_block_has_four_norms_by_hand(tiny):
    cfg, params = tiny
    s = R.spec(cfg)
    lp = {k: jnp.asarray(v[2], jnp.float32)
          for k, v in params["layers"].items()}
    n = {k: np.asarray(v, np.float64) for k, v in lp.items()}
    rng = np.random.default_rng(1)
    x = rng.standard_normal((5, cfg.hidden_size))
    pos = jnp.arange(5)
    with jax.default_matmul_precision("highest"):
        a = np.asarray(R.attention(
            s, lp, jnp.asarray(_np_rms(x, n["attn_norm_scale"], s["eps"]),
                               jnp.float32), pos), np.float64)
        got = R.block(s, lp, jnp.asarray(x, jnp.float32), pos)
    h = x + _np_rms(a, n["attn_post_norm_scale"], s["eps"])
    u = _np_rms(h, n["mlp_norm_scale"], s["eps"])
    g = u @ n["w_gate"]
    m = (g / (1 + np.exp(-g)) * (u @ n["w_up"])) @ n["w_down"]
    want = h + _np_rms(m, n["mlp_post_norm_scale"], s["eps"])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_every_pass_runs_the_same_stack_from_the_normed_x(tiny):
    """Pass r + 1 is the stack over the final norm of pass r, the logits
    the head over the last pass's: the loop written out a pass at a time."""
    cfg, params = tiny
    toks = jnp.asarray(np.random.default_rng(2).integers(0, 256, (19,)))
    logits, after = R.forward(cfg, params, toks)
    assert len(after) == 4 and after[0].shape == (19, cfg.hidden_size)
    s, pos = R.spec(cfg), jnp.arange(19)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][toks].astype(jnp.float32)
        for r in range(4):
            for i in range(cfg.num_layers):
                x = R.block(s, R._f32(R._layer(params, i)), x, pos)
            x = R._rmsnorm(x, params["final_norm_scale"], s["eps"])
            np.testing.assert_allclose(after[r], x, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(logits, x @ params["lm_head"], rtol=1e-4,
                                   atol=1e-5)
    # every pass moves x: none is a copy of the one before it
    for r in range(3):
        assert R.rms_rel_err(after[r + 1], after[r]) > 0.1
    # ``rows`` reads the same numbers at those positions alone
    part, after_rows = R.forward(cfg, params, toks, rows=(3, 18))
    np.testing.assert_allclose(part, logits[[3, 18]], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(after_rows[1], after[1][[3, 18]], rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("mutate", [{"passes": 3}, {"passes": 1},
                                    {"norm_every_pass": False},
                                    {"post_norms": False}])
def test_each_mutation_is_another_model(tiny, mutate):
    cfg, params = tiny
    toks = jnp.asarray(np.random.default_rng(4).integers(0, 256, (15,)))
    want = R.reference_logits(cfg, params, toks)
    assert R.rel_err(R.reference_logits(cfg, params, toks, **mutate),
                     want) > 0.05


def test_what_is_outside_the_block_is_refused():
    from deepspeed_tpu.models import get_config

    for cfg in (get_config("tiny"), _cfg(sandwich_norm=False),
                _cfg(num_kv_heads=2), _cfg(qk_norm=True)):
        with pytest.raises(NotImplementedError, match="Ouro block only"):
            R.reference_logits(cfg, {}, jnp.zeros((3,), jnp.int32))


def test_the_system_passes_in_bfloat16_and_float8_does_not():
    """The comparison that decides the cell's ``correct``, at the tiny
    size: the system in bfloat16 within every limit; this file's side
    rounded through float8_e4m3 past at least one; and the float8 reading
    of every check several times the bfloat16 one."""
    cfg = _cfg()        # bfloat16, as the cell runs it
    params = _params(cfg)
    shipped = R.layer_checks(cfg, params, 5, **KW)
    assert all(c["rel_err"] <= c["tol"] for c in shipped.values()), shipped
    f8 = R.layer_checks(cfg, params, 5, round_to=jnp.float8_e4m3fn, **KW)
    assert any(c["rel_err"] > c["tol"] for c in f8.values()), f8
    for name in shipped:
        assert f8[name]["rel_err"] > 3 * shipped[name]["rel_err"], name
    toks = jnp.asarray(np.random.default_rng(5).integers(0, 256, (40,)))
    want = R.reference_logits(cfg, params, toks)
    from deepspeed_tpu.models.transformer import forward

    got = forward(cfg, params, toks[None])[0]
    assert R.rel_err(got, want) < 0.05
    assert R.rel_err(R.reference_logits(
        cfg, params, toks, round_to=jnp.float8_e4m3fn), want) > 0.05
