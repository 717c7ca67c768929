"""Flops profiler (reference ``deepspeed/profiling/flops_profiler/profiler.py:24``).

TPU-first redesign: the reference walks an eager module tree, monkey-patching
``torch.nn.functional`` to count MACs per call.  Under XLA the whole step is
ONE compiled program, so instead of patching Python call sites we ask the
compiler itself: ``jit(fn).lower(*args).compile().cost_analysis()`` returns
the exact flop/byte counts of the optimized HLO — including fusion, remat
recompute, and sharding effects that an eager-side count cannot see.

Two surfaces (API parity with the reference):

- ``get_model_profile(model, batch_size, seq_len, ...)`` — one-shot profile
  of a model forward: returns ``(flops, macs, params)`` like the reference's
  ``get_model_profile`` (profiler.py:1111).
- ``FlopsProfiler`` — attached by the engine; at ``profile_step`` it profiles
  the *actual jitted train step* and prints the reference-style report
  (params, fwd+bwd flops, latency, achieved TFLOPS, HBM bytes, arithmetic
  intensity).  Per-module depth tables don't exist post-fusion, so the
  breakdown reports what the hardware sees instead: compiled-program
  totals + the analytic per-component split (attention vs matmul vs other,
  derived from the model config).
"""
from __future__ import annotations

import json
import time
from typing import Any, Dict, Optional

from ...utils.logging import logger


def _number(x: float, units: Optional[str] = None, precision: int = 2) -> str:
    if units is None:
        if x >= 1e12:
            return f"{x / 1e12:.{precision}f} T"
        if x >= 1e9:
            return f"{x / 1e9:.{precision}f} G"
        if x >= 1e6:
            return f"{x / 1e6:.{precision}f} M"
        if x >= 1e3:
            return f"{x / 1e3:.{precision}f} K"
        return f"{x:.{precision}f}"
    return f"{x:.{precision}f} {units}"


number_to_string = _number  # reference naming (profiler.py:927)


def flops_to_string(flops, units=None, precision=2):
    return _number(flops, units, precision) + ("FLOPS" if units is None else "")


def params_to_string(n, units=None, precision=2):
    return _number(n, units, precision)


def macs_to_string(n, units=None, precision=2):
    return _number(n, units, precision) + ("MACs" if units is None else "")


def cost_analysis_of(jitted, *args, **kwargs) -> Dict[str, float]:
    """Exact compiled-program costs from XLA for a jitted callable.

    Returns at least ``flops`` and ``bytes accessed`` (platform-dependent keys
    are passed through).  The compile is cached by jax, so calling this on an
    already-used step is cheap.
    """
    compiled = jitted.lower(*args, **kwargs).compile()
    out = dict(compiled.cost_analysis() or {})
    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            out["temp_size_bytes"] = getattr(mem, "temp_size_in_bytes", None)
            out["argument_size_bytes"] = getattr(mem, "argument_size_in_bytes", None)
            out["output_size_bytes"] = getattr(mem, "output_size_in_bytes", None)
    except Exception:
        pass
    return out


def _param_count(params) -> int:
    import jax

    return sum(x.size for x in jax.tree_util.tree_leaves(params)
               if hasattr(x, "size"))


def get_model_profile(model, batch_size: int = 1, seq_len: int = 128,
                      warm_up: int = 1, as_string: bool = True,
                      print_profile: bool = True, detailed: bool = True,
                      output_file: Optional[str] = None):
    """Profile a model's forward (reference ``get_model_profile``).

    ``model`` is anything with ``init_fn``/``apply_fn`` (the engine's model
    contract, e.g. ``CausalLM``).  Returns ``(flops, macs, params)`` — strings
    when ``as_string`` (reference behavior), raw numbers otherwise.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    # XLA cost analysis counts a lax.scan body ONCE, not trip-count times —
    # profile the unrolled (scan_layers=False) variant so every layer is
    # visible in the HLO.  Params are identical either way (stacked [L] dim).
    cfg0 = getattr(model, "config", None)
    if cfg0 is not None and getattr(cfg0, "scan_layers", False):
        try:
            model = type(model)(cfg0, scan_layers=False)
        except Exception:
            pass
    params = model.init_fn(jax.random.PRNGKey(0))
    compute_dtype = getattr(model.config, "dtype", None)
    if compute_dtype is not None:
        # the engine runs the model in its compute dtype; profile the same
        params = jax.tree_util.tree_map(
            lambda x: x.astype(compute_dtype)
            if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
            else x, params)
    vocab = getattr(model.config, "vocab_size", 1000)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, vocab, (batch_size, seq_len)).astype(np.int32))
    jitted = jax.jit(model.apply_fn)
    ca = cost_analysis_of(jitted, params, tokens)
    flops = float(ca.get("flops", 0.0))
    macs = flops / 2.0
    nparams = _param_count(params)

    latency = None
    if warm_up >= 0:
        for _ in range(max(warm_up, 1)):
            out = jitted(params, tokens)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        out = jitted(params, tokens)
        jax.block_until_ready(out)
        latency = time.perf_counter() - t0

    if print_profile:
        lines = ["-" * 72,
                 "DeepSpeed-TPU Flops Profiler — model forward",
                 "-" * 72,
                 f"params:                 {params_to_string(nparams)}",
                 f"batch x seq:            {batch_size} x {seq_len}",
                 f"fwd flops (compiled):   {flops_to_string(flops)}",
                 f"fwd MACs:               {macs_to_string(macs)}",
                 f"fwd flops per token:    {_number(flops / (batch_size * seq_len))}"]
        if latency:
            lines.append(f"fwd latency:            {latency * 1e3:.2f} ms")
            lines.append(
                f"fwd TFLOPS achieved:    {flops / latency / 1e12:.2f}")
        if detailed:
            ba = ca.get("bytes accessed", None)
            if ba:
                lines.append(f"HBM bytes accessed:     {_number(float(ba))}B")
                lines.append(f"arithmetic intensity:   {flops / float(ba):.1f} flop/B")
        lines.append("-" * 72)
        if detailed:
            # per-module rows (reference module tree, profiler.py:273)
            try:
                det = get_detailed_profile(model, batch_size, seq_len)
                lines.append(f"{'module':<38}{'count':>6}{'flops':>12}"
                             f"{'%':>7}")
                for r in det["modules"]:
                    lines.append(f"{r['name']:<38}{r['count']:>6}"
                                 f"{_number(r['flops']):>12}{r['pct']:>6.1f}%")
                lines.append("-" * 72)
            except Exception as e:  # het/MoE configs may lack a block slice
                lines.append(f"(per-module breakdown unavailable: {e})")
        report = "\n".join(lines)
        if output_file:
            with open(output_file, "w") as f:
                f.write(report + "\n")
        else:
            logger.info("\n" + report)

    if as_string:
        return flops_to_string(flops), macs_to_string(macs), params_to_string(nparams)
    return flops, macs, nparams


def get_detailed_profile(model, batch_size: int = 1, seq_len: int = 128,
                         print_profile: bool = False):
    """Per-module breakdown (reference ``FlopsProfiler`` module tree,
    profiler.py:273/493): the reference hooks every nn.Module and counts
    MACs per call; post-fusion HLO has no module boundaries, so the TPU
    build COST-ANALYZES PER-BLOCK PROGRAMS of the same building blocks the
    model's forward composes (embed / per-layer attention core / per-layer
    MLP / full layer / lm_head) and derives the rest (projections, norms,
    residuals, loss) as measured remainders.

    Returns ``{"total": {...}, "modules": [row, ...]}`` where each row has
    ``name / flops / bytes / pct / count`` (count = L for per-layer rows).
    The ``dense_flops_per_token`` / ``attn_flops_per_token`` keys feed the
    autotuner's cost-model features.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ...models import transformer as T

    # totals must come from the UNROLLED program: XLA cost analysis counts
    # a lax.scan body once, not trip-count times (same handling as
    # get_model_profile)
    cfg0 = getattr(model, "config", None)
    if cfg0 is not None and getattr(cfg0, "scan_layers", False):
        try:
            model = type(model)(cfg0, scan_layers=False)
        except Exception as e:
            # silently keeping the scanned model would count the scan body
            # ONCE against per-layer rows multiplied by L — garbage
            # percentages and a clamped-to-zero dense coefficient that
            # would silently skew the autotuner's cost model
            raise RuntimeError(
                f"get_detailed_profile: cannot rebuild {type(model).__name__} "
                f"with scan_layers=False ({e}); per-module totals need the "
                "unrolled program") from e
    cfg = model.config
    # pin attention to the XLA path everywhere: the Pallas kernel engages
    # under 'auto' at S>=2048 and its custom-call flops are INVISIBLE to
    # cost_analysis — mixing paths would misattribute attention and could
    # push the derived dense coefficient negative
    if getattr(model, "attn_impl", "xla") != "xla":
        import copy

        model = copy.copy(model)   # never mutate the caller's model
        model.attn_impl = "xla"
    params = model.init_fn(jax.random.PRNGKey(0))
    compute_dtype = getattr(cfg, "dtype", None) or jnp.float32
    params = jax.tree_util.tree_map(
        lambda x: x.astype(compute_dtype)
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
        else x, params)
    layers = params["layers"]
    stacked = jax.tree_util.tree_leaves(layers)[0].ndim >= 1 and \
        jax.tree_util.tree_leaves(layers)[0].shape[0] == cfg.num_layers
    lp0 = (jax.tree_util.tree_map(lambda x: x[0], layers) if stacked
           else layers)
    L = cfg.num_layers
    B, S, d = batch_size, seq_len, cfg.hidden_size
    hd, nh, nkv = cfg.dims_per_head, cfg.num_heads, cfg.kv_heads
    rng = jax.random.PRNGKey(0)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = jax.random.normal(rng, (B, S, d), compute_dtype)
    q = jax.random.normal(rng, (B, S, nh, hd), compute_dtype)
    kv = jax.random.normal(rng, (B, S, nkv, hd), compute_dtype)
    tokens = jnp.zeros((B, S), jnp.int32)

    def _flops_bytes(fn, *args):
        ca = cost_analysis_of(jax.jit(fn), *args)
        return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed",
                                                         0.0))

    rows = []

    def add(name, fl, by, count=1):
        rows.append({"name": name, "flops": fl * count, "bytes": by * count,
                     "count": count})

    emb_f, emb_b = _flops_bytes(lambda e, t: jnp.take(e, t, axis=0),
                                params["embed"], tokens)
    add("embed", emb_f, emb_b)
    attn_f, attn_b = _flops_bytes(
        lambda q, k, v: T._attention(cfg, q, k, v, positions, "xla"),
        q, kv, kv)
    mlp_f, mlp_b = _flops_bytes(
        lambda lp, h: T._mlp(cfg, lp, h, rng, True)[0], lp0, x)
    blk_f, blk_b = _flops_bytes(
        lambda lp, h: T._block(cfg, lp, h, positions, rng,
                               T._attend_full(cfg, positions))[0],
        lp0, x)
    proj_f = max(blk_f - attn_f - mlp_f, 0.0)
    proj_b = max(blk_b - attn_b - mlp_b, 0.0)
    add("layer.attention_core", attn_f, attn_b, count=L)
    add("layer.qkv_out_projections+norms", proj_f, proj_b, count=L)
    add("layer.mlp", mlp_f, mlp_b, count=L)
    head_f, head_b = _flops_bytes(lambda w, h: h @ w, params["lm_head"], x)
    add("lm_head", head_f, head_b)

    total_f, total_b = _flops_bytes(model.apply_fn, params, tokens)
    accounted_f = sum(r["flops"] for r in rows)
    accounted_b = sum(r["bytes"] for r in rows)
    add("other (final norm, residuals, loss)",
        max(total_f - accounted_f, 0.0), max(total_b - accounted_b, 0.0))
    for r in rows:
        r["pct"] = round(100.0 * r["flops"] / total_f, 1) if total_f else 0.0

    ntok = B * S
    out = {
        "total": {"flops": total_f, "bytes": total_b,
                  "flops_per_token": total_f / ntok},
        "modules": rows,
        "dense_flops_per_token": max(total_f - attn_f * L, 0.0) / ntok,
        "attn_flops_per_token": attn_f * L / ntok,
        "batch_size": B, "seq_len": S,
    }
    if print_profile:
        lines = ["-" * 72,
                 "DeepSpeed-TPU Flops Profiler — per-module breakdown "
                 f"(B={B}, S={S})",
                 "-" * 72,
                 f"{'module':<38}{'count':>6}{'flops':>12}{'bytes':>12}"
                 f"{'%':>6}"]
        for r in rows:
            lines.append(f"{r['name']:<38}{r['count']:>6}"
                         f"{_number(r['flops']):>12}"
                         f"{_number(r['bytes']):>12}B{r['pct']:>5.1f}")
        lines.append(f"{'TOTAL (compiled forward)':<38}{'':>6}"
                     f"{_number(total_f):>12}{_number(total_b):>12}B"
                     f"{100.0:>5.1f}")
        lines.append("-" * 72)
        logger.info("\n" + "\n".join(lines))
    return out


class FlopsProfiler:
    """Engine-attached profiler (reference ``FlopsProfiler`` class).

    The engine calls ``start_profile()`` / ``stop_profile()`` around the
    configured ``profile_step`` and ``print_model_profile()`` after it; the
    measured program is the engine's own compiled train step.
    """

    def __init__(self, engine=None, config=None):
        self.engine = engine
        self.config = config
        self.started = False
        self._t0 = None
        self._latency = None
        self._cost: Dict[str, Any] = {}

    # -- lifecycle -------------------------------------------------------
    def start_profile(self, ignore_list=None) -> None:
        self.started = True
        self._t0 = time.perf_counter()

    def stop_profile(self) -> None:
        if self._t0 is not None:
            self._latency = time.perf_counter() - self._t0
        self.started = False

    def end_profile(self) -> None:  # reference alias
        self.stop_profile()

    def attach_cost(self, cost: Dict[str, Any]) -> None:
        """Engine hands over ``cost_analysis_of(train_step, state, batch)``."""
        self._cost = dict(cost or {})

    # -- accessors (reference API) --------------------------------------
    def get_total_flops(self, as_string: bool = False):
        f = float(self._cost.get("flops", 0.0))
        return flops_to_string(f) if as_string else f

    def get_total_macs(self, as_string: bool = False):
        m = float(self._cost.get("flops", 0.0)) / 2.0
        return macs_to_string(m) if as_string else m

    def get_total_params(self, as_string: bool = False):
        n = _param_count(self.engine.state.params) if self.engine is not None else 0
        return params_to_string(n) if as_string else n

    def get_total_duration(self, as_string: bool = False):
        d = self._latency or 0.0
        return f"{d * 1e3:.2f} ms" if as_string else d

    # -- report ----------------------------------------------------------
    def print_model_profile(self, profile_step: int = 1, module_depth: int = -1,
                            top_modules: int = 1, detailed: bool = True,
                            output_file: Optional[str] = None) -> None:
        flops = self.get_total_flops()
        dur = self.get_total_duration()
        lines = ["-" * 72,
                 f"DeepSpeed-TPU Flops Profiler — train step @ step {profile_step}",
                 "-" * 72,
                 f"params:                       {self.get_total_params(True)}",
                 f"flops per step (compiled):    {flops_to_string(flops)}",
                 f"MACs per step:                {self.get_total_macs(True)}"]
        if dur:
            lines.append(f"step latency:                 {dur * 1e3:.2f} ms")
            lines.append(f"TFLOPS achieved:              {flops / dur / 1e12:.2f}")
        if detailed:
            ba = self._cost.get("bytes accessed")
            if ba:
                lines.append(f"HBM bytes accessed:           {_number(float(ba))}B")
                lines.append(f"arithmetic intensity:         "
                             f"{flops / float(ba):.1f} flop/B")
            for k in ("temp_size_bytes", "argument_size_bytes", "output_size_bytes"):
                v = self._cost.get(k)
                if v:
                    lines.append(f"{k.replace('_', ' '):<30}{_number(float(v))}B")
            # analytic split so users can sanity-check the compiled number
            eng = self.engine
            cfg = getattr(getattr(eng, "model", None), "config", None)
            scans = []
            if cfg is not None and getattr(cfg, "scan_layers", False):
                scans.append("layer loop")
            if getattr(eng, "gas", 1) > 1:
                scans.append("grad-accumulation loop")
            if scans:
                lines.append(f"NOTE: {' and '.join(scans)} compiled as "
                             "lax.scan — XLA counts each body ONCE; trust "
                             "the analytic row for totals")
            if cfg is not None and hasattr(cfg, "param_count"):
                try:
                    bsz = eng.train_micro_batch_size_per_gpu * \
                        eng.gradient_accumulation_steps
                    S = cfg.max_seq_len
                    dense = 6.0 * cfg.param_count * bsz * S
                    attn = 12.0 * cfg.num_layers * cfg.hidden_size * S * bsz * S
                    lines.append(f"analytic model flops (6N+12LdS): "
                                 f"{flops_to_string(dense + attn)} "
                                 f"(dense {100 * dense / (dense + attn):.0f}% / "
                                 f"attn {100 * attn / (dense + attn):.0f}%)")
                except Exception:
                    pass
        lines.append("-" * 72)
        report = "\n".join(lines)
        if output_file:
            with open(output_file, "w") as f:
                f.write(report + "\n")
        else:
            logger.info("\n" + report)

    def as_dict(self) -> Dict[str, Any]:
        return {"flops": self.get_total_flops(), "macs": self.get_total_macs(),
                "params": self.get_total_params(), "duration_s": self.get_total_duration(),
                **{k: v for k, v in self._cost.items() if isinstance(v, (int, float))}}

    def to_json(self) -> str:
        return json.dumps(self.as_dict())
