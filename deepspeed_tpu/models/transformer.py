"""TPU-native causal transformer family (the framework's flagship models).

Covers the architectures the reference trains/serves through its injection
policies (``module_inject/containers``: GPT-2, GPT-J/NeoX, Bloom, OPT, Llama,
Megatron — ``replace_policy.py:21-27``) with ONE configurable pure-JAX model:

  - norm: RMSNorm (llama/neox) or LayerNorm (gpt2/opt/bloom)
  - position: rotary (llama/gptj/neox), learned (gpt2/opt), or alibi (bloom)
  - mlp: SwiGLU (llama) or GELU (gpt2/opt/bloom)
  - attention: MHA or grouped-query (GQA, llama-2-70B-style)

Design is TPU-first, not a port:
  - ``lax.scan`` over stacked per-layer params — one compiled block regardless
    of depth (compile time O(1) in layers; the MXU sees identical fused steps).
  - tensor parallelism is *declared*: ``param_specs()`` returns Megatron-style
    PartitionSpecs over the 'model' mesh axis (column-parallel QKV/up, row-
    parallel out/down) and GSPMD inserts the all-reduces the reference does by
    hand in ``module_inject/layers.py`` (LinearAllreduce/LinearLayer).
  - sequence parallelism: activations are sharding-constrained over the 'seq'
    axis; attention contracts over the full sequence so XLA gathers K/V over
    ICI (ring-attention Pallas kernel in ops/pallas upgrades this path).
  - activation checkpointing via ``jax.checkpoint`` around the scanned block
    (reference runtime/activation_checkpointing/checkpointing.py:474).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import BATCH_AXES, constrain_spec
from . import mixers
from .mixers import MIXERS, mixers_of
from .mixers import common as _common
from .mixers.common import _scaled
# what the benchmark's references and cells reach for by these names
from .mixers.conv import _conv_mixer  # noqa: F401
from .mixers.delta import (_delta_mixer, delta_state_heads,  # noqa: F401
                           delta_widths)
from .mixers.ssm import _ssm_mixer, ssm_in_width  # noqa: F401

@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: Optional[int] = None        # None => MHA
    head_dim: Optional[int] = None            # None => hidden // heads
    max_seq_len: int = 2048
    norm: str = "rmsnorm"                     # rmsnorm | layernorm
    # swiglu | gelu | gelu_exact | relu | quick_gelu | relu2 (relu(x)^2)
    activation: str = "swiglu"
    position: str = "rope"            # rope | learned | alibi | none (NoPE)
    rope_theta: float = 10000.0
    # partial rotary (GPT-J/NeoX): apply rope to the first rotary_dim dims
    rotary_dim: Optional[int] = None          # None => full head_dim
    # GPT-J convention rotates (x0,x1),(x2,x3) pairs; llama/neox rotate the
    # half-split (x[:half], x[half:])
    rope_interleaved: bool = False
    # parallel residual (GPT-J/NeoX): x + attn(norm(x)) + mlp(norm'(x));
    # shared_layernorm (GPT-J) feeds the MLP the SAME normed activations as
    # attention (one LN per block, no mlp_norm params)
    parallel_residual: bool = False
    shared_layernorm: bool = False
    lm_head_bias: bool = False                # GPT-J ties a bias to lm_head
    # encoder-family knobs (BERT): bidirectional attention, post-layernorm
    # blocks (attn -> add -> LN), LayerNorm after the embedding sum (also
    # Bloom), segment/token-type embeddings, no final norm (post-LN blocks
    # end normalized)
    causal: bool = True
    post_layernorm: bool = False
    embed_layernorm: bool = False
    type_vocab_size: int = 0
    final_norm: bool = True
    norm_eps: float = 1e-5
    # GPT-Neo: per-layer attention-type alternation — a tuple of
    # "global"/"local" per layer; "local" layers see a sliding window of
    # window_size keys (HF GPTNeoConfig attention_types/window_size).  The
    # window rides the layer scan as a per-layer scalar so layers stay
    # uniform; flash/ring paths defer to the masked XLA path.
    attention_layers: Optional[tuple] = None
    window_size: int = 256
    # softmax scale override: GPT-Neo applies NO 1/sqrt(hd) scaling
    # (modeling_gpt_neo scales by 1.0); None = the standard 1/sqrt(hd)
    attn_softmax_scale: Optional[float] = None
    # QK-norm, two more leaves a layer (q_norm_scale, k_norm_scale).  True
    # (OLMoE): the norm of ``norm`` with a learned scale over the WHOLE q and
    # k projections, before the head split and the rotary embedding.
    # ``"head"`` (LFM2's ``q_layernorm`` / ``k_layernorm``): the norm over
    # each head's own dims after the split, one scale of ``head_dim`` that
    # the heads share, before the rotary embedding
    qk_norm: Any = False                      # False | True | "head"
    # Two kinds of attention layer in one model (MiMo-V2): a tuple of
    # "full"/"window" per layer (the first ``num_layers`` entries are used:
    # a cut in depth keeps the published pattern).  A window layer sees the last
    # ``window_size`` positions and may have its own KV head count, its own
    # rotary theta and a learned sink (one logit a query head that takes
    # probability in the softmax and gives no value).  The layers are then
    # stacked by kind (:func:`layer_groups`) and run in the published order;
    # the paged cache holds a pool per kind, a window layer's a ring of
    # :func:`window_ring_pages` pages a slot.  A third kind, "ssm" (Granite
    # 4.0-H): a layer whose mixer is the state-space one ALONE, where a
    # "full" layer of the same model is attention alone (:func:`sublayers`);
    # K/V leaves then cover the attention layers and the state leaves the
    # "ssm" ones.
    layer_pattern: Optional[tuple] = None
    # Layers of ONE sublayer (Nemotron-H, ``nemotron_h``): every entry of
    # ``layer_pattern`` is then ``x + f(N(x))``, one norm and one add: "ssm"
    # the mixer alone and "full" attention alone (no MLP leaves and no second
    # norm behind either), and a further kind, "mlp", the MLP or expert
    # layer alone (no attention, no mixer, no cache leaf).  ``sublayer`` is
    # how :func:`layer_groups` says which of the two such a group's uniform
    # config is ("mix" | "mlp"), never a model's own setting.
    one_sublayer: bool = False
    sublayer: Optional[str] = None
    window_kv_heads: Optional[int] = None     # None => num_kv_heads
    window_rope_theta: Optional[float] = None  # None => rope_theta
    window_attn_sink: bool = False
    # the position rule of the window layers where it is not the model's
    # (Trinity, ``afmoe``: ``position="none"`` and ``window_position="rope"``,
    # window layers rotate q and k and full layers carry no position at all)
    window_position: Optional[str] = None     # None => position
    # a gate on attention's output (Trinity's ``gate_proj``): one more leaf a
    # layer, ``wg [d, Hq * vd]``, made from the normed input beside q, k and
    # v: ``(attn * sigmoid(h wg)) wo``
    attn_output_gate: bool = False
    # values narrower (or wider) than keys, and scaled after the projection
    v_head_dim: Optional[int] = None          # None => head_dim
    attn_value_scale: float = 1.0
    # the first ``dense_layers`` layers keep a dense MLP (of width
    # ``intermediate_size``) in a model whose other layers are expert layers:
    # the layers are then stacked by group (:func:`layer_groups`)
    dense_layers: int = 0
    # Latent attention (MLA, ``deepseek_v3``): a token's keys and values are
    # up-projections (``wkv_b``) of one ``kv_lora_rank``-wide latent, RMS-
    # normed, beside one rotated key row of ``rotary_dim`` dims that every
    # head shares.  ``head_dim`` is a query's and key's whole width, its
    # LAST ``rotary_dim`` dims the rotated ones; ``v_head_dim`` the values'.
    # The paged cache holds one leaf ``latent [L, P, page, kv_lora_rank +
    # rotary_dim]`` with no head axis; a prompt attends within itself over
    # the expanded keys and values, a decode tick reads the latent rows
    # themselves with the up-projections absorbed into the query and the
    # output (:func:`_attend_latent_paged`).  None: K and V heads.
    kv_lora_rank: Optional[int] = None
    # State-space layers beside attention (Falcon-H1, ``falcon_h1``): every
    # block runs a Mamba-2 mixer and the attention side by side on ONE normed
    # input, ``x + a * attn(n) + b * ssm(n)``, then the MLP on the sum
    # (:func:`_block`).  ``ssm_heads`` heads of ``ssm_head_dim`` channels
    # (``mamba_d_ssm`` together), each with a ``[ssm_head_dim, ssm_state]``
    # float32 state a sequence; B and C shared by ``ssm_heads / ssm_groups``
    # heads; a depthwise causal convolution of ``ssm_conv`` taps over x, B and
    # C; a prompt runs the recurrence in chunks of ``ssm_chunk`` positions
    # (``mixers.ssm._ssm_scan``), a decode token as the recurrence itself.  The
    # paged cache then holds two leaves with NO page axis beside K and V:
    # ``ssm_state [L, slots, heads, head_dim, state]`` and the convolution's
    # tail ``ssm_conv [L, slots, taps - 1, channels]``.  0 heads: no mixer.
    # Under a ``layer_pattern`` the mixer is the "ssm" layers' and theirs
    # alone; ``ssm_alone`` is how :func:`layer_groups` says so of such a
    # group's uniform config, never a model's own setting.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    ssm_alone: bool = False
    # Gated-delta-rule layers (Olmo-Hybrid, ``olmo_hybrid``; Gated DeltaNet,
    # arXiv:2412.06464): a fourth kind of ``layer_pattern`` entry, "linear",
    # whose mixer stands in attention's place.  ``linear_heads`` heads, each
    # with a ``[linear_key_dim, linear_value_dim]`` float32 MATRIX state a
    # sequence: q, k and v through a depthwise causal convolution of
    # ``linear_conv`` taps (no bias) and SiLU, q and k L2-normed by head, the
    # state decayed by ``alpha = exp(-exp(A_log) softplus(a + dt_bias))``
    # and corrected by the delta rule with the write strength ``beta =
    # sigmoid(b)`` (doubled under ``linear_neg_eigval``: eigenvalues of the
    # transition in (-1, 1)), the output RMS-normed by head and gated
    # (:func:`_delta_mixer`).  A prompt runs the recurrence in chunks of
    # ``linear_chunk`` positions (``mixers.delta._delta_scan``), a decode token
    # as the recurrence itself.  The paged cache holds ``delta_state`` and the
    # convolutions' tail ``delta_conv`` for the "linear" layers, a row a
    # slot, beside K and V pages for the "full" ones.  0 heads: no such layer.
    linear_heads: int = 0
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_conv: int = 4
    linear_chunk: int = 64
    linear_neg_eigval: bool = True
    # Gated short-convolution layers (LFM2, ``lfm2`` / ``lfm2_moe``): a fifth
    # kind of ``layer_pattern`` entry, "conv", whose operator stands in
    # attention's place: ``[B | C | u] = W_in n``, ``z = B . u``, a depthwise
    # causal convolution of ``conv_taps`` taps over z (``conv_bias``: with a
    # bias; NO activation), ``W_out (C . conv(z))`` (:func:`_conv_mixer`).  A
    # sequence's whole state is the convolution's tail, the last ``conv_taps
    # - 1`` rows of z: the paged cache holds ONE leaf ``conv_tail`` for the
    # "conv" layers, a row a slot, beside K and V pages for the "full" ones.
    # 0 taps: no such layer.
    conv_taps: int = 0
    conv_bias: bool = False
    # the family's fixed multipliers (muP), every one a constant of the
    # published config: on the embedding and the logits, on attention's
    # input, keys and output, on the mixer's input, output and the five
    # segments of its in-projection (z, x, B, C, dt), on the MLP's gate
    # and output
    embed_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attn_in_multiplier: float = 1.0
    attn_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)
    # on every sublayer's output before it is added to x (Granite's
    # ``residual_multiplier``): ``x += r * mix(n1(x));  x += r * mlp(n2(x))``
    residual_multiplier: float = 1.0
    # A looped model (Ouro, ``ouro``): the whole stack of ``num_layers``
    # layers runs ``loop_passes`` times over a token with the SAME weights,
    # the final norm after EVERY pass (the next pass starts from the normed
    # x) and the head after the last.  Every (pass, layer) keeps its own keys
    # and values, so a cache is ``loop_passes * num_layers`` layers deep
    # (:func:`cache_depth`), pass ``r``'s layer ``l`` at ``r * num_layers +
    # l``, while the weights' stack stays ``num_layers`` deep.  x is carried
    # in float32 from layer to layer (the branches read it in ``dtype``): in
    # bfloat16 the 2 x loop_passes x num_layers adds of a token each round
    # it, 384 times for Ouro-2.6B, and that alone reads 0.03-0.046 on the
    # logits against a float32 reference (PERF.md, PR 44).
    # ``sandwich_norm``: a norm after each branch as well as before it,
    # ``x += N2(attn(N1(x)));  x += N4(mlp(N3(x)))``, two more scales a layer
    # ``norm_after`` (Olmo 2's order): the norm on each branch's OUTPUT and
    # none on its input, ``x += N1(mix(x));  x += N2(mlp(x))``; the two
    # scales are the leaves ``attn_norm_scale`` / ``mlp_norm_scale``
    loop_passes: int = 1
    sandwich_norm: bool = False
    norm_after: bool = False
    tie_embeddings: bool = False
    attn_bias: bool = False
    mlp_bias: bool = False
    dropout: float = 0.0
    # MoE (reference deepspeed/moe/): num_experts > 1 makes every block's MLP
    # an expert-parallel MoE layer (scan-over-layers keeps blocks uniform).
    # PR-MoE (reference moe/layer.py:16): a TUPLE gives per-layer expert
    # counts (the pyramid; 1 = dense layer) — layers become heterogeneous,
    # so the forward drops to the per-layer loop and pipeline is unsupported.
    num_experts: Any = 1
    moe_top_k: int = 2
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    moe_min_capacity: int = 8
    moe_aux_loss_coef: float = 0.01
    moe_drop_tokens: bool = True              # False => ragged no-drop path
    # top-k gates renormalised to sum to 1 (GShard top-2, Mixtral); False
    # keeps the k largest softmax probabilities as they are (OLMoE
    # ``norm_topk_prob=false``).  Top-1 never renormalises.
    moe_norm_topk_prob: bool = True
    # added to the chosen gates' sum before it divides them (LFM2: 1e-6); 0:
    # the sum itself
    moe_norm_topk_eps: float = 0.0
    # an expert's width where it is not the dense MLP's (None =>
    # intermediate_size)
    moe_intermediate_size: Optional[int] = None
    # the router's scores: "softmax" over all experts, or "sigmoid" of each
    # logit; ``moe_select_bias`` adds a learned per-expert bias
    # (``router_bias``) to the scores for the CHOICE of the k experts and
    # not to the gates
    moe_score_func: str = "softmax"
    moe_select_bias: bool = False
    # one chip's share of the experts: the router keeps ``num_experts``
    # outputs and ``moe_top_k`` a token, the expert stacks hold
    # ``moe_experts_held`` experts from ``moe_expert_first`` on, and a pair
    # whose expert is elsewhere is computed by no one here (the dropless
    # path only).  None: every expert is here.
    moe_experts_held: Optional[int] = None
    moe_expert_first: int = 0
    # ``moe_shared_experts`` experts every token goes through beside its
    # routed ones, built as ONE gated MLP of that many expert widths
    # (``shared_w_*``, every chip's alike under expert parallelism); the
    # routed experts' gates are multiplied by ``moe_routed_scale``
    moe_shared_experts: int = 0
    moe_routed_scale: float = 1.0
    # routed experts that work in a latent narrower than the model
    # (Nemotron-H's ``moe_latent_size``): two more leaves a layer,
    # ``moe_latent_in [d, latent]`` and ``moe_latent_out [latent, d]``, the
    # expert stacks ``[E, latent, f]`` / ``[E, f, latent]``; the router and
    # the shared experts stay on the model's width: ``(sum_e gate_e
    # expert_e(n W_in)) W_out + shared(n)``.  None: experts on the width
    moe_latent_size: Optional[int] = None
    # residual MoE (PR-MoE, reference moe/layer.py use_residual): each MoE
    # layer also runs a dense MLP; outputs mix via a learned 2-way coefficient
    moe_use_residual: bool = False
    noisy_gate_policy: Optional[str] = None
    # pipeline parallelism: layers split into stages over the 'pipe' mesh
    # axis; microbatches default to the engine's gradient_accumulation_steps
    pipeline_stages: int = 1
    pipeline_microbatches: Optional[int] = None
    # "gpipe": fwd wavefront scan + AD backward (fastest span; activation
    # stash grows with microbatch count M).  "1f1b": interleaved fwd/bwd in
    # one scan (runtime/pipe/spmd.py:pipeline_1f1b) — O(P²) stash
    # independent of M, the reference TrainSchedule's memory contract.
    pipeline_schedule: str = "gpipe"
    remat: bool = True                        # activation checkpointing
    # which residuals a checkpointed layer keeps for its backward: a rung of
    # REMAT_LADDER, any jax.checkpoint_policies name, or REMAT_AUTO (below)
    remat_policy: str = "auto"
    # random-LTD (data efficiency): non-deterministic passes run each layer on
    # a random `random_ltd_keep`-token subset; dropped tokens ride the
    # residual stream (runtime/data_pipeline/data_routing/random_ltd.py)
    random_ltd: bool = False
    random_ltd_keep: int = 0
    # activation fake-quant (compression_training.activation_quantization —
    # reference basic_layer.py QuantAct): applied to the post-norm inputs of
    # attention and the MLP, dynamic range, straight-through gradient
    act_quant_bits: int = 0
    act_quant_symmetric: bool = False
    scan_layers: bool = True
    dtype: Any = jnp.bfloat16                 # compute dtype hint (engine casts)
    initializer_range: float = 0.02
    # frozen parameters (reference requires_grad=False; engine contract
    # model.frozen_spec): leaves whose '/'-joined param path contains any of
    # these as an EXACT path segment are frozen — no update (not even
    # weight decay), excluded from grad norm + clipping.  Examples:
    # ("embed",) freezes the token embedding only (NOT pos_embed/type_embed
    # — list those separately on learned-position configs); ("wq", "wk",
    # "wv", "wo") freezes all attention projections (stacked [L, ...]
    # leaves freeze whole stacks — per-layer granularity needs the LoRA
    # path, runtime/lora.py).
    frozen_keywords: Tuple[str, ...] = ()

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def dims_per_head(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def v_dims_per_head(self) -> int:
        return self.v_head_dim or self.dims_per_head

    @property
    def param_count(self) -> int:
        if is_grouped(self):
            # the parts outside the layers once, each group's layers beside
            outside = dataclasses.replace(
                self, layer_pattern=None, dense_layers=0, one_sublayer=False,
                num_layers=0).param_count
            return outside + sum(
                g.param_count - outside for g, _ in layer_groups(self).values())
        d, f, v, L = self.hidden_size, self.intermediate_size, self.vocab_size, self.num_layers
        hd, nh, nkv = self.dims_per_head, self.num_heads, self.kv_heads
        vd = self.v_dims_per_head
        attn = d * hd * nh + d * hd * nkv + d * vd * nkv + vd * nh * d
        if self.kv_lora_rank:
            r, rd = self.kv_lora_rank, self.rotary_dim
            attn = (d * hd * nh + d * (r + rd) + r
                    + r * nh * (hd - rd + vd) + vd * nh * d)
        if self.attn_bias:
            attn += nh * hd + nkv * hd + nkv * vd + d
        if self.qk_norm:
            attn += sum(qk_norm_widths(self))
        if self.window_attn_sink:
            attn += nh
        if self.attn_output_gate:
            attn += d * vd * nh
        has_attn, _, has_mlp = sublayers(self)
        if not has_attn:                # a mixer in attention's place
            attn = 0
        attn += sum(m.param_count(self) for m in mixers_of(self))
        if self.moe_intermediate_size and self.num_experts != 1:
            f = self.moe_intermediate_size
        products = 3 if self.activation == "swiglu" else 2
        mlp = products * d * f
        if self.mlp_bias:
            mlp += (2 * f if self.activation == "swiglu" else f) + d
        experts = (tuple(self.num_experts)
                   if isinstance(self.num_experts, (tuple, list))
                   else (self.num_experts,) * L)
        total_mlp = 0
        for E in experts:
            m = mlp
            if E > 1:
                # the experts held here + the router at its full width;
                # experts in a latent: their two products on its width, and
                # the projections into it and back
                dl = self.moe_latent_size or d
                m = (products * dl * f * (self.moe_experts_held or E) + d * E
                     + (2 * d * dl if self.moe_latent_size else 0))
                if self.moe_select_bias:
                    m += E
                m += mlp * self.moe_shared_experts
                if self.moe_use_residual:
                    m += mlp + 2 * d  # dense residual branch + coefficient
            total_mlp += m
        if not has_mlp:
            total_mlp = 0
        n_norms = 1 if self.sublayer else (
            (1 if self.shared_layernorm else 2)
            + (2 if self.sandwich_norm else 0))
        norms = n_norms * d * (2 if self.norm == "layernorm" else 1)
        embed = v * d * (1 if self.tie_embeddings else 2)
        if self.lm_head_bias and not self.tie_embeddings:
            embed += v
        pos = self.max_seq_len * d if self.position == "learned" else 0
        extra = 0
        if self.embed_layernorm:
            extra += d * (2 if self.norm == "layernorm" else 1)
        if self.type_vocab_size:
            extra += self.type_vocab_size * d
        final_norm = (d * (2 if self.norm == "layernorm" else 1)
                      if self.final_norm else 0)
        return (L * (attn + norms) + total_mlp + embed + pos + extra
                + final_norm)


# Nemotron-3-Super's ``hybrid_override_pattern``: M a Mamba-2 mixer, E an
# expert layer, * attention, each a layer of its own
NEMOTRON_3_SUPER_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEM*EMEMEMEME")

# -- named configs (sizes from the public model cards) --
CONFIGS: Dict[str, TransformerConfig] = {
    "gpt2-125m": TransformerConfig(
        vocab_size=50257, hidden_size=768, intermediate_size=3072, num_layers=12,
        num_heads=12, max_seq_len=1024, norm="layernorm", activation="gelu",
        position="learned", tie_embeddings=True, attn_bias=True, mlp_bias=True,
        norm_eps=1e-5),
    "gpt2-1.3b": TransformerConfig(
        vocab_size=50257, hidden_size=2048, intermediate_size=8192, num_layers=24,
        num_heads=16, max_seq_len=1024, norm="layernorm", activation="gelu",
        position="learned", tie_embeddings=True, attn_bias=True, mlp_bias=True),
    "llama2-7b": TransformerConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=11008, num_layers=32,
        num_heads=32, max_seq_len=4096),
    "llama2-13b": TransformerConfig(
        vocab_size=32000, hidden_size=5120, intermediate_size=13824, num_layers=40,
        num_heads=40, max_seq_len=4096),
    "llama2-70b": TransformerConfig(
        vocab_size=32000, hidden_size=8192, intermediate_size=28672, num_layers=80,
        num_heads=64, num_kv_heads=8, max_seq_len=4096),
    "bloom-7b": TransformerConfig(
        vocab_size=250880, hidden_size=4096, intermediate_size=16384, num_layers=30,
        num_heads=32, max_seq_len=2048, norm="layernorm", activation="gelu",
        position="alibi", attn_bias=True, mlp_bias=True, tie_embeddings=True),
    "opt-1.3b": TransformerConfig(
        vocab_size=50272, hidden_size=2048, intermediate_size=8192, num_layers=24,
        num_heads=32, max_seq_len=2048, norm="layernorm", activation="gelu",
        position="learned", attn_bias=True, mlp_bias=True, tie_embeddings=True),
    # llama-architecture sizes that train on one v5e chip (bf16 + fp32
    # Adam); ~740M is the largest whose fused-Adam peak fits without offload
    "llama-374m": TransformerConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=2816, num_layers=24,
        num_heads=16, max_seq_len=2048),
    "llama-740m": TransformerConfig(
        vocab_size=32000, hidden_size=1792, intermediate_size=4864, num_layers=16,
        num_heads=14, max_seq_len=4096),
    # allenai/OLMoE-1B-7B-0125-Instruct config.json: 64 SiLU-gated experts of
    # width 1024 (``intermediate_size``), top-8 of the full softmax not
    # renormalised, no token dropped, QK-norm, RMSNorm, full rotary, no bias
    "olmoe-1b-7b": TransformerConfig(
        vocab_size=50304, hidden_size=2048, intermediate_size=1024,
        num_layers=16, num_heads=16, max_seq_len=4096, norm_eps=1e-5,
        rope_theta=10000.0, qk_norm=True, num_experts=64, moe_top_k=8,
        moe_norm_topk_prob=False, moe_drop_tokens=False),
    # XiaomiMiMo/MiMo-V2.5 config.json (``mimo_v2``, the language model):
    # 48 layers, 9 of full attention (64 heads over 4 KV heads, theta 1e7)
    # and 39 of a 128-wide sliding window (64 over 8, theta 1e4, a learned
    # sink), keys 192 wide with rotary on the leading 64, values 128 wide
    # and scaled by 0.707; layer 0 a dense SwiGLU of width 16,384, the rest
    # 256 experts of width 2,048, sigmoid scores, 8 a token chosen on score
    # + bias, gates renormalised over the chosen
    "mimo-v2.5": TransformerConfig(
        vocab_size=152576, hidden_size=4096, intermediate_size=16384,
        moe_intermediate_size=2048,
        num_layers=48, num_heads=64, num_kv_heads=4, head_dim=192,
        v_head_dim=128, max_seq_len=1048576, norm_eps=1e-5,
        rope_theta=1e7, rotary_dim=64, attn_value_scale=0.707,
        layer_pattern=tuple("full" if i in (0, 5, 11, 17, 23, 29, 35, 41, 47)
                            else "window" for i in range(48)),
        window_size=128, window_kv_heads=8, window_rope_theta=1e4,
        window_attn_sink=True, dense_layers=1,
        num_experts=256, moe_top_k=8, moe_score_func="sigmoid",
        moe_select_bias=True, moe_norm_topk_prob=True, moe_drop_tokens=False,
        remat=False),
    # kakaocorp/kanana-2-30b-a3b-instruct-2601 config.json (``deepseek_v3``):
    # 48 layers, 32 heads of latent attention (a 512-wide normed latent and
    # one shared 64-wide rotated key row a token; keys 128 + 64, values 128,
    # no q_lora), rotary on adjacent pairs at theta 1e6; layer 0 a dense
    # SwiGLU of 6,144, the rest 128 routed experts of 768 beside 2 shared
    # (one MLP of 1,536), sigmoid scores, 6 a token chosen on score + bias,
    # gates renormalised and scaled by 2.448
    "kanana-2-30b-a3b": TransformerConfig(
        vocab_size=128256, hidden_size=2048, intermediate_size=6144,
        moe_intermediate_size=768, num_layers=48, num_heads=32, head_dim=192,
        v_head_dim=128, kv_lora_rank=512, rotary_dim=64,
        rope_interleaved=True, rope_theta=1e6, max_seq_len=32768,
        norm_eps=1e-6, dense_layers=1, num_experts=128, moe_top_k=6,
        moe_score_func="sigmoid", moe_select_bias=True,
        moe_norm_topk_prob=True, moe_routed_scale=2.448,
        moe_shared_experts=2, moe_drop_tokens=False, remat=False),
    # tiiuae/Falcon-H1-34B-Instruct config.json (``falcon_h1``): 72 blocks,
    # each a Mamba-2 mixer (32 heads of 128 channels = ``mamba_d_ssm`` 4,096,
    # state 256, 2 groups, 4 taps, chunk 128) beside grouped-query attention
    # (20 heads over 4 KV heads of 128, rotary theta 1e11) on one normed
    # input, then a SwiGLU of 21,504; fixed multipliers on every branch;
    # RMSNorm eps 1e-5, untied head over 261,120 ids
    "falcon-h1-34b": TransformerConfig(
        vocab_size=261120, hidden_size=5120, intermediate_size=21504,
        num_layers=72, num_heads=20, num_kv_heads=4, head_dim=128,
        max_seq_len=262144, norm_eps=1e-5, rope_theta=1e11,
        ssm_heads=32, ssm_head_dim=128, ssm_state=256, ssm_groups=2,
        ssm_conv=4, ssm_chunk=128,
        embed_multiplier=5.656854249492381, lm_head_multiplier=0.0078125,
        attn_in_multiplier=1.0, attn_out_multiplier=0.0375,
        key_multiplier=0.011048543456039804, ssm_in_multiplier=0.25,
        ssm_out_multiplier=0.08838834764831845,
        ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                         0.3535533905932738),
        mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
        remat=False),
    # ByteDance/Ouro-2.6B config.json (``ouro``, a looped language model):
    # ONE stack of 48 layers (16 heads of 128 over 16 KV heads, a SwiGLU of
    # 5,632, four RMSNorms a layer, eps 1e-6, full rotary theta 1e6) run
    # ``total_ut_steps`` 4 times a token, the final norm after every pass, an
    # untied head over 49,152 ids after the last; ``early_exit_threshold`` 1:
    # every token runs all four passes, and the exit gate is not built
    "ouro-2.6b": TransformerConfig(
        vocab_size=49152, hidden_size=2048, intermediate_size=5632,
        num_layers=48, num_heads=16, num_kv_heads=16, head_dim=128,
        max_seq_len=65536, norm_eps=1e-6, rope_theta=1e6,
        loop_passes=4, sandwich_norm=True, remat=False),
    # ibm-granite/granite-4.0-h-small config.json (``granitemoehybrid``,
    # 32B-A9B): 40 layers in the order 5 x mamba, attention, 4 x mamba, ... A
    # mamba layer is a Mamba-2 mixer ALONE (128 heads of 64 = 2 x hidden,
    # state 128, 1 group, 4 taps with a bias, chunk 256), an attention layer
    # grouped-query attention alone (32 heads over 8 KV heads of 128, no
    # position embedding, scores x 1/128); behind every one 72 experts of
    # 768, the 10 largest logits softmaxed, beside one shared expert of
    # 1,536; embedding x 12, every sublayer's output x 0.22, logits / 16
    # over the tied embedding; RMSNorm eps 1e-5
    "granite-4.0-h-small": TransformerConfig(
        vocab_size=100352, hidden_size=4096, intermediate_size=768,
        num_layers=40, num_heads=32, num_kv_heads=8, head_dim=128,
        max_seq_len=131072, norm_eps=1e-5, position="none",
        layer_pattern=tuple("full" if i in (5, 15, 25, 35) else "ssm"
                            for i in range(40)),
        ssm_heads=128, ssm_head_dim=64, ssm_state=128, ssm_groups=1,
        ssm_conv=4, ssm_chunk=256,
        num_experts=72, moe_top_k=10, moe_score_func="softmax",
        moe_norm_topk_prob=True, moe_shared_experts=2, moe_drop_tokens=False,
        embed_multiplier=12.0, attn_softmax_scale=0.0078125,
        residual_multiplier=0.22, lm_head_multiplier=0.0625,
        tie_embeddings=True, remat=False),
    # allenai/Olmo-Hybrid-7B config.json (``olmo_hybrid``): 32 layers, three
    # gated-delta-rule layers (30 heads, keys 96 and values 192 wide, 4 taps,
    # eigenvalues in (-1, 1)) then one of full attention (30 heads over 30 KV
    # heads of 128, QK-norm, no rotary embedding: ``rope_theta`` null), eight
    # times; the norm on each branch's output and none on its input; a
    # SwiGLU of 11,008; RMSNorm eps 1e-6, untied head over 100,352 ids
    "olmo-hybrid-7b": TransformerConfig(
        vocab_size=100352, hidden_size=3840, intermediate_size=11008,
        num_layers=32, num_heads=30, num_kv_heads=30, head_dim=128,
        max_seq_len=65536, norm_eps=1e-6, position="none", qk_norm=True,
        norm_after=True,
        layer_pattern=tuple("full" if i % 4 == 3 else "linear"
                            for i in range(32)),
        linear_heads=30, linear_key_dim=96, linear_value_dim=192,
        linear_conv=4, linear_chunk=64, linear_neg_eigval=True, remat=False),
    # LiquidAI/LFM2-8B-A1B config.json (``lfm2_moe``): 24 layers, 18 gated
    # short convolutions (3 taps, no bias) and 6 of grouped-query attention
    # (32 heads over 8 KV heads of 64, QK-norm by head, full rotary theta
    # 1e6) at 2, 6, 10, 14, 18 and 21; layers 0 and 1 a dense SwiGLU of
    # 7,168, the rest 32 experts of 1,792, sigmoid scores, 4 a token chosen
    # on score + bias, gates renormalised over the chosen (sum + 1e-6);
    # RMSNorm eps 1e-5, the head the embedding's transpose over 65,536 ids
    "lfm2-8b-a1b": TransformerConfig(
        vocab_size=65536, hidden_size=2048, intermediate_size=7168,
        moe_intermediate_size=1792, num_layers=24, num_heads=32,
        num_kv_heads=8, head_dim=64, max_seq_len=128000, norm_eps=1e-5,
        rope_theta=1e6, qk_norm="head",
        layer_pattern=tuple("full" if i in (2, 6, 10, 14, 18, 21) else "conv"
                            for i in range(24)),
        conv_taps=3, conv_bias=False, dense_layers=2,
        num_experts=32, moe_top_k=4, moe_score_func="sigmoid",
        moe_select_bias=True, moe_norm_topk_prob=True, moe_norm_topk_eps=1e-6,
        moe_drop_tokens=False, tie_embeddings=True, remat=False),
    # arcee-ai/Trinity-Large-Preview config.json (``afmoe``, 400B-A13B): 60
    # layers, three of a 4,096-wide sliding window (rotary, theta 1e4) then
    # one of full attention WITHOUT any position, fifteen times; 48 heads
    # over 8 KV heads of 128, QK-norm by head, a sigmoid gate on attention's
    # output; four RMSNorms a layer (eps 1e-5); layers 0-5 a dense SwiGLU of
    # 12,288, the rest 256 experts of 3,072 beside one shared, sigmoid
    # scores, 4 a token chosen on score + bias, gates renormalised (sum +
    # 1e-20) and scaled by 2.448; the embedding x sqrt(3,072)
    # (``mup_enabled``); untied head over 200,192 ids
    "trinity-large-preview": TransformerConfig(
        vocab_size=200192, hidden_size=3072, intermediate_size=12288,
        moe_intermediate_size=3072, num_layers=60, num_heads=48,
        num_kv_heads=8, head_dim=128, max_seq_len=262144, norm_eps=1e-5,
        rope_theta=1e4, position="none", window_position="rope",
        qk_norm="head", attn_output_gate=True, sandwich_norm=True,
        embed_multiplier=55.42562584220407,
        layer_pattern=tuple("full" if i % 4 == 3 else "window"
                            for i in range(60)),
        window_size=4096, dense_layers=6,
        num_experts=256, moe_top_k=4, moe_score_func="sigmoid",
        moe_select_bias=True, moe_norm_topk_prob=True,
        moe_norm_topk_eps=1e-20, moe_routed_scale=2.448,
        moe_shared_experts=1, moe_drop_tokens=False, remat=False),
    # nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16 config.json
    # (``nemotron_h``, 120B-A12B): 88 layers by ``hybrid_override_pattern``,
    # each ONE sublayer behind one RMSNorm (eps 1e-5): 40 Mamba-2 mixers (128
    # heads of 64, state 128, 8 groups, 4 taps with a bias, chunk 128), 8 of
    # attention (32 heads over 2 KV heads of 128, no position of any kind)
    # and 40 expert layers: 512 experts of 2,688 in a latent of 1,024
    # (squared ReLU, ungated), sigmoid scores, 22 a token chosen on score +
    # bias, gates renormalised (sum + 1e-20) and scaled by 5, beside one
    # shared expert of 5,376 on the full width; untied head over 131,072
    # ids.  The multi-token-prediction module is left out
    "nemotron-3-super-120b-a12b": TransformerConfig(
        vocab_size=131072, hidden_size=4096, intermediate_size=2688,
        moe_intermediate_size=2688, moe_latent_size=1024, num_layers=88,
        num_heads=32, num_kv_heads=2, head_dim=128, max_seq_len=262144,
        norm_eps=1e-5, position="none", activation="relu2",
        one_sublayer=True,
        layer_pattern=tuple({"M": "ssm", "E": "mlp", "*": "full"}[c]
                            for c in NEMOTRON_3_SUPER_PATTERN),
        ssm_heads=128, ssm_head_dim=64, ssm_state=128, ssm_groups=8,
        ssm_conv=4, ssm_chunk=128,
        num_experts=512, moe_top_k=22, moe_score_func="sigmoid",
        moe_select_bias=True, moe_norm_topk_prob=True,
        moe_norm_topk_eps=1e-20, moe_routed_scale=5.0,
        moe_shared_experts=2, moe_drop_tokens=False, remat=False),
    # tiny variants for tests / dryruns
    "tiny": TransformerConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, max_seq_len=128, remat=False),
    "tiny-gpt2": TransformerConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, max_seq_len=128, norm="layernorm", activation="gelu",
        position="learned", tie_embeddings=True, attn_bias=True, mlp_bias=True,
        remat=False),
    "tiny-gqa": TransformerConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=8, num_kv_heads=2, max_seq_len=128, remat=False),
    "tiny-moe": TransformerConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, max_seq_len=128, num_experts=4, moe_top_k=2, remat=False),
    # PR-MoE pyramid (reference moe/layer.py use_residual + per-layer expert
    # counts): dense first layer, 4-expert second, residual mixing
    "tiny-prmoe": TransformerConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, max_seq_len=128, num_experts=(1, 4), moe_top_k=2,
        moe_use_residual=True, scan_layers=False, remat=False),
}


def get_config(name_or_cfg, **overrides) -> TransformerConfig:
    cfg = CONFIGS[name_or_cfg] if isinstance(name_or_cfg, str) else name_or_cfg
    # a list (a JSON file's) is the tuple the frozen config holds
    overrides = {k: tuple(v) if isinstance(v, list) else v
                 for k, v in overrides.items()}
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def moe_layer_experts(cfg: TransformerConfig) -> Tuple[int, ...]:
    """Per-layer expert counts; scalar configs broadcast (PR-MoE pyramid:
    reference moe/layer.py accepts per-layer num_experts lists)."""
    if isinstance(cfg.num_experts, (tuple, list)):
        if len(cfg.num_experts) != cfg.num_layers:
            raise ValueError(
                f"num_experts tuple has {len(cfg.num_experts)} entries for "
                f"{cfg.num_layers} layers")
        return tuple(int(e) for e in cfg.num_experts)
    return (int(cfg.num_experts),) * cfg.num_layers


def has_moe(cfg: TransformerConfig) -> bool:
    return max(moe_layer_experts(cfg)) > 1


# the attention projections: what a layer with the mixer in attention's
# place (:func:`sublayers`) does not have
_ATTN_LEAVES = ("wq", "wk", "wv", "wo", "wg")
# what a layer that is its MLP alone does not have beside them, and what a
# layer that is its mixer or attention alone does not have (``sublayer``)
_MIX_NORM_LEAVES = ("attn_norm_scale", "attn_norm_bias")
_MLP_LEAVES = ("mlp_norm_scale", "mlp_norm_bias", "w_gate", "w_up", "w_in",
               "w_down", "b_gate", "b_up", "b_in", "b_down")

# The per-expert leaves of an MoE layer, ``[L, E, ...]`` in the layer stack:
# the matmul weights and (gelu experts) their per-expert biases.
_EXPERT_LEAVES = ("w_gate", "w_up", "w_in", "w_down", "b_in", "b_down")


def expert_counts_shape(cfg) -> Optional[Tuple[int, int]]:
    """``(layers, experts)`` of the counts ``forward_paged(expert_counts=
    True)`` hands back: the dropless expert layers (every layer but the
    leading dense ones) and the experts held here.  None for any other
    model (dense, capacity buffers, a per-layer pyramid)."""
    experts = getattr(cfg, "num_experts", 1)
    if isinstance(experts, int) and experts > 1 and not cfg.moe_drop_tokens:
        # of layers that are one sublayer, the "mlp" ones alone
        layers = (sum(not dense for *_, kind, dense in layer_plan(cfg)
                      if kind == "mlp") if cfg.one_sublayer
                  else cfg.num_layers - cfg.dense_layers)
        return layers, cfg.moe_experts_held or experts
    return None


def is_hybrid(cfg: TransformerConfig) -> bool:
    """Layers of more than one kind (``layer_pattern``): cache leaves of
    its own a kind, and the layers walked in their published order."""
    return cfg.layer_pattern is not None


def is_grouped(cfg: TransformerConfig) -> bool:
    """Layers that are not one uniform stack (two kinds of attention, or
    leading dense layers before expert layers): ``params["layers"]`` is
    ``{group: stack}`` (:func:`layer_groups`)."""
    return (cfg.layer_pattern is not None or cfg.dense_layers > 0
            or cfg.one_sublayer)


def is_latent(cfg: TransformerConfig) -> bool:
    """Latent attention (``kv_lora_rank``): one cache leaf with no head
    axis, and two attention paths over it."""
    return bool(cfg.kv_lora_rank)


def is_ssm(cfg: TransformerConfig) -> bool:
    """State-space layers (``ssm_heads``; ``mixers/ssm.py``)."""
    return bool(cfg.ssm_heads)


def is_delta(cfg: TransformerConfig) -> bool:
    """Gated-delta-rule layers (``linear_heads``; ``mixers/delta.py``)."""
    return bool(cfg.linear_heads)


def is_conv(cfg: TransformerConfig) -> bool:
    """Gated short-convolution layers (``conv_taps``; ``mixers/conv.py``)."""
    return bool(cfg.conv_taps)


def has_state(cfg: TransformerConfig) -> bool:
    """A state a slot (:func:`cache_kind` ``"state"``): a mixer of ``MIXERS``."""
    return bool(mixers_of(cfg))


def qk_norm_widths(cfg: TransformerConfig) -> Tuple[int, int]:
    """The widths of ``q_norm_scale`` and ``k_norm_scale``: a head's dims
    under ``qk_norm="head"``, the whole projections' otherwise."""
    hd = cfg.dims_per_head
    if cfg.qk_norm == "head":
        return hd, hd
    return cfg.num_heads * hd, cfg.kv_heads * hd


def sublayers(cfg: TransformerConfig) -> Tuple[bool, bool, bool]:
    """``(attention, mixer, mlp)``: which of the three a layer of the
    uniform stack ``cfg`` has, the first two before the third.  The one rule
    :func:`_block`, the parameters and the cache's leaves follow: a mixer
    where the stack turns one of :data:`~.mixers.MIXERS` on, attention
    unless that mixer stands in its place (any but one that may stand
    ``beside`` it, and that one too in a pattern's group of its own,
    ``ssm_alone``; both: Falcon-H1's parallel block), and the MLP or expert
    layer behind them; a layer that is ONE sublayer (``sublayer``) its
    mixer or attention and no MLP ("mix"), or the MLP and nothing before it
    ("mlp")."""
    if cfg.sublayer == "mlp":
        return False, False, True
    has = mixers_of(cfg)
    return (not (cfg.ssm_alone or any(not m.beside for m in has)), bool(has),
            cfg.sublayer != "mix")


def cache_layers(cfg: TransformerConfig) -> Tuple[int, int]:
    """``(layers with K/V pages, layers with a state row a slot)`` of a
    model's own layers, what its cache's leaves are deep: the layers with
    attention and those with a mixer (:func:`sublayers`)."""
    groups = (layer_groups(cfg).values() if is_grouped(cfg)
              else [(cfg, cfg.num_layers)])
    has = [(sublayers(g), n) for g, n in groups]
    return (sum(n for (attn, _, _), n in has if attn),
            sum(n for (_, mixer, _), n in has if mixer))


def cache_depth(cfg: TransformerConfig) -> int:
    """Layers of K/V a sequence keeps: a looped model's every (pass, layer)
    has its own, ``loop_passes * num_layers``; any other model's its layers
    with attention (:func:`cache_layers`: all of them but a pattern's "ssm"
    ones).  The weights' stack is ``num_layers`` deep either way."""
    return cfg.loop_passes * cache_layers(cfg)[0]


def window_ring_pages(window: int, page_size: int) -> int:
    """Pages of a window layer's ring, a slot: what ``window`` consecutive
    positions can span, and one being written.  Position ``p`` lives in
    ring page ``(p // page_size) % ring``."""
    return -(-window // page_size) + 1


def pool_leaf_head_major(kv_heads: int, width: int) -> bool:
    """Whether a leaf of a per-kind pool is kept ``[L, P, Hkv, page,
    width]`` and not ``[L, P, page, Hkv, width]``.  The TPU tiles an array's
    two minor-most axes 8 x 128: under fewer than 8 KV heads of whole-lane
    width the heads are a partial tile, and the compiler copies such a pool
    into the head-major order before every page scatter and back after it
    (2 x 1 GB a tick for MiMo's 4 x 128 values; PERF.md, PR 30).  Kept
    head-major from the start it is updated where it lies.  The same holds
    for heads that are not whole tiles of 8 (Olmo-Hybrid's 30 x 128: the
    compiler pads 30 to 32 and copies 2 x 1.9 GB of pool a tick, over the
    chip, PERF.md PR 51)."""
    return (kv_heads < 8 or kv_heads % 8 != 0) and width % 128 == 0


def layer_plan(cfg: TransformerConfig):
    """A grouped model's layers in the published order: ``(group, index in
    the group, kind, dense)`` each, ``group`` = ``<kind>_<dense|moe>``
    (every layer ``full`` without a ``layer_pattern``); of layers that are
    one sublayer (``one_sublayer``) a mixer's or attention's group is
    ``<kind>_only``: it has no MLP of either sort."""
    pattern = cfg.layer_pattern or ("full",) * cfg.num_layers
    if cfg.window_position not in (None, "rope", "none"):
        # a table of learned positions or ALiBi's slopes is the whole
        # model's (the embedding, the read's mask), not a kind of layer's
        raise ValueError(f"window_position={cfg.window_position!r}: "
                         "None | 'rope' | 'none'")
    if len(pattern) < cfg.num_layers:
        raise ValueError(
            f"layer_pattern has {len(pattern)} entries for "
            f"{cfg.num_layers} layers")
    if cfg.one_sublayer:
        if cfg.layer_pattern is None:
            raise ValueError("one_sublayer says what each entry of a "
                             "layer_pattern is: it takes one")
        for flag in ("sandwich_norm", "norm_after", "post_layernorm",
                     "parallel_residual", "shared_layernorm"):
            if getattr(cfg, flag):
                raise NotImplementedError(
                    "layers of one sublayer (one_sublayer) are one norm and "
                    f"one add: they do not take {flag}")
    kinds = ("full", "window", *MIXERS, *(("mlp",) if cfg.one_sublayer
                                          else ()))
    plan, seen = [], {}
    # a model cut in depth runs the first layers of the published pattern
    for i, kind in enumerate(pattern[:cfg.num_layers]):
        if kind not in kinds:
            raise ValueError(f"layer_pattern[{i}] = {kind!r}: "
                             + " | ".join(kinds))
        if kind in MIXERS and MIXERS[kind] not in mixers_of(cfg):
            raise ValueError(
                f"layer_pattern[{i}] = {kind!r} in a model with no "
                f"{MIXERS[kind].words}")
        alone = cfg.one_sublayer and kind != "mlp"
        dense = alone or i < cfg.dense_layers or not has_moe(cfg)
        group = f"{kind}_{'only' if alone else 'dense' if dense else 'moe'}"
        plan.append((group, seen.get(group, 0), kind, dense))
        seen[group] = seen.get(group, 0) + 1
    return plan


def layer_groups(cfg: TransformerConfig):
    """``{group: (the uniform config of its layers, how many)}`` of a
    grouped model, in order of first appearance: each group is a plain stack that
    :func:`init_params`, :func:`param_specs` and :func:`_block` take as they
    take any model's, with the kind's KV heads, theta, sink and MLP, and
    under a pattern the kind's one mixer (:func:`sublayers`): a kind of
    :data:`~.mixers.MIXERS` its mixer alone, any other attention alone; of
    layers that are one sublayer (``one_sublayer``) that and no MLP, or
    (kind "mlp") the MLP alone."""
    groups: Dict[str, Any] = {}
    for group, index, kind, dense in layer_plan(cfg):
        window = kind == "window"
        groups[group] = (dataclasses.replace(
            cfg, layer_pattern=None, dense_layers=0, num_layers=index + 1,
            ssm_alone=kind == "ssm", one_sublayer=False,
            sublayer=(("mlp" if kind == "mlp" else "mix")
                      if cfg.one_sublayer else None),
            # a kind's mixer in its own layers alone; without a pattern the
            # one that stands beside attention in every layer
            **{m.field: (getattr(cfg, m.field) if kind == m.kind
                         or (m.beside and cfg.layer_pattern is None) else 0)
               for m in MIXERS.values()},
            num_kv_heads=(cfg.window_kv_heads if window
                          and cfg.window_kv_heads else cfg.num_kv_heads),
            rope_theta=(cfg.window_rope_theta if window
                        and cfg.window_rope_theta else cfg.rope_theta),
            position=(cfg.window_position if window and cfg.window_position
                      else cfg.position),
            window_attn_sink=window and cfg.window_attn_sink,
            moe_intermediate_size=None if dense else cfg.moe_intermediate_size,
            num_experts=1 if dense else cfg.num_experts,
            moe_experts_held=None if dense else cfg.moe_experts_held),
            index + 1)
    return groups


def _whole_in_group(lp: Dict[str, Any], leaf: str) -> bool:
    """Whether ``leaf`` of a group's stack ``lp`` is read whole by the paged
    forward over two kinds of layer: an expert layer's per-expert stacks,
    ``[n * E, ...]`` with a layer's experts at ``l * E``."""
    return leaf in _EXPERT_LEAVES and "router" in lp


# one program a tree of stacks, shared by every engine of the process: each
# stack ``[n, ...]`` as its n layers, each an array of its own
_unstack = jax.jit(lambda stacks: jax.tree_util.tree_map(
    lambda v: tuple(v[i] for i in range(v.shape[0])), stacks))


def per_layer_leaves(cfg: TransformerConfig, params: Dict[str, Any]):
    """``(params, leaves cut)`` with a leaf a layer where the paged forward
    walks its layers in Python (:func:`is_hybrid`: a ``layer_pattern``, whose
    kinds' stacks differ in shape, so there is nothing to scan): every leaf
    of a group that is not read whole (:func:`_whole_in_group`) as a tuple
    of its layers' arrays, so that ``v[index]`` is a Python index and the
    compiler is handed no ``slice`` of a stack (a static slice that feeds a
    copy is materialised: 1 GB of ``wq`` written back to memory every tick,
    PERF.md PR 35).  The expert stacks stay whole; a scanned model's tree
    comes back as it is (there the scan's dynamic slice is the fetch), and
    so does a leaf that is already held a layer at a time or is no array.
    The containers are new, the tree handed in is not touched."""
    if not is_hybrid(cfg):
        return params, 0
    stacks = {name: {k: v for k, v in lp.items() if isinstance(v, jax.Array)
                     and not _whole_in_group(lp, k)}
              for name, lp in params["layers"].items()}
    if not any(stacks.values()):
        return params, 0
    cut = _unstack(stacks)
    return ({**params, "layers": {name: {**lp, **cut[name]} for name, lp
                                  in params["layers"].items()}},
            sum(len(lp) for lp in stacks.values()))


def layer_windows(cfg: TransformerConfig) -> Optional[jax.Array]:
    """[L] int32 of local-attention window sizes (0 = global) from
    cfg.attention_layers, or None when the config has no alternation."""
    if cfg.attention_layers is None:
        return None
    if len(cfg.attention_layers) != cfg.num_layers:
        raise ValueError(
            f"attention_layers has {len(cfg.attention_layers)} entries for "
            f"{cfg.num_layers} layers")
    return jnp.asarray([cfg.window_size if t == "local" else 0
                        for t in cfg.attention_layers], jnp.int32)


def _sm_scale(cfg: TransformerConfig, hd: int) -> float:
    return (cfg.attn_softmax_scale if cfg.attn_softmax_scale is not None
            else 1.0 / math.sqrt(hd))


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def kind_layers(cfg: TransformerConfig):
    """``{kind: (a group config of the kind, its layers in the model)}`` of a
    hybrid model: what a kind's K/V pool is shaped by (KV heads, widths,
    which every group of one kind shares) and how many layers it holds."""
    kinds: Dict[str, Any] = {}
    for name, (g, n) in layer_groups(cfg).items():
        kind = name.split("_")[0]
        kinds[kind] = (g, kinds.get(kind, (g, 0))[1] + n)
    return kinds


def layers_by_kind(cfg: TransformerConfig) -> Dict[str, int]:
    """``{kind: layers}`` of the layers run, in order of first appearance:
    a pattern's kinds (of layers that are one sublayer "mlp" is one), else
    every layer ``full``."""
    kinds: Dict[str, int] = {}
    for *_, kind, _ in layer_plan(cfg):
        kinds[kind] = kinds.get(kind, 0) + 1
    return kinds


def _check_latent(cfg: TransformerConfig) -> None:
    """What a latent-attention layer is built from, and what it leaves
    out."""
    if not (cfg.rotary_dim and cfg.position == "rope"
            and cfg.norm == "rmsnorm"
            and cfg.dims_per_head > cfg.rotary_dim):
        raise NotImplementedError(
            "latent attention (kv_lora_rank) takes RMSNorm, rotary "
            "positions and head_dim = the unrotated width + rotary_dim")
    for on, what in ((cfg.attn_bias, "attn_bias"), (cfg.qk_norm, "qk_norm"),
                     (cfg.num_kv_heads not in (None, cfg.num_heads),
                      "grouped KV heads"),
                     (cfg.attn_value_scale != 1.0, "attn_value_scale"),
                     (cfg.layer_pattern is not None, "layer_pattern"),
                     (cfg.attention_layers is not None, "attention_layers")):
        if on:
            raise NotImplementedError(
                f"latent attention (kv_lora_rank) does not take {what}")


def _own_sublayer_alone(cfg: TransformerConfig, layers: Dict[str, Any]):
    """A layer that is one sublayer (``sublayer``) keeps one norm and its
    own leaves: the others go from a stack's leaves or specs, in place."""
    for name in {"mix": _MLP_LEAVES, "mlp": _MIX_NORM_LEAVES}.get(
            cfg.sublayer, ()):
        layers.pop(name, None)


def _check_experts(cfg: TransformerConfig) -> None:
    """What an expert layer's shared experts and latent are built from."""
    if cfg.moe_shared_experts and cfg.activation not in ("swiglu", "relu2"):
        raise NotImplementedError(
            "shared experts are gated (swiglu) or squared-ReLU (relu2) "
            f"MLPs, not activation={cfg.activation!r}")
    if cfg.moe_latent_size and (cfg.moe_drop_tokens or cfg.mlp_bias
                                or cfg.moe_use_residual):
        raise NotImplementedError(
            "experts in a latent (moe_latent_size) are the dropless path's "
            "(moe_drop_tokens=False), without mlp_bias or moe_use_residual")


def _check_qk_norm(cfg: TransformerConfig) -> None:
    if cfg.qk_norm not in (True, "head"):
        raise ValueError(f"qk_norm={cfg.qk_norm!r}: False | True | 'head'")
    if cfg.norm != "rmsnorm":
        raise NotImplementedError(
            "qk_norm is an RMSNorm (OLMoE): it carries a scale and no offset")


def _check_loop(cfg: TransformerConfig) -> None:
    """What a looped model (``loop_passes`` > 1) and a block with a norm
    after each branch (``sandwich_norm``) are built from, and what they
    leave out."""
    if cfg.sandwich_norm:
        if cfg.norm != "rmsnorm":
            raise NotImplementedError(
                "sandwich_norm is four RMSNorms a layer: a scale and no "
                "offset")
        for on, what in ((cfg.post_layernorm, "post_layernorm"),
                         (cfg.parallel_residual, "parallel_residual")):
            if on:
                raise NotImplementedError(
                    f"sandwich_norm does not take {what}")
    if cfg.norm_after:
        if cfg.norm != "rmsnorm":
            raise NotImplementedError(
                "norm_after is two RMSNorms a layer: a scale and no offset")
        for on, what in ((cfg.post_layernorm, "post_layernorm"),
                         (cfg.parallel_residual, "parallel_residual"),
                         (cfg.sandwich_norm, "sandwich_norm")):
            if on:
                raise NotImplementedError(f"norm_after does not take {what}")
    if cfg.loop_passes < 1:
        raise ValueError(f"loop_passes={cfg.loop_passes} must be >= 1")
    if cfg.loop_passes > 1:
        if not cfg.final_norm:
            raise NotImplementedError(
                "a looped model (loop_passes) norms x after every pass: it "
                "takes final_norm")
        for on, what in ((is_grouped(cfg), "layer_pattern / dense_layers"),
                         (is_latent(cfg), "latent attention"),
                         (cfg.num_experts != 1, "expert layers"),
                         (cfg.attention_layers is not None,
                          "attention_layers"),
                         (cfg.pipeline_stages > 1, "pipeline_stages"),
                         (not cfg.scan_layers, "scan_layers=False")):
            if on:
                raise NotImplementedError(
                    f"a looped model (loop_passes) does not take {what}")


def init_params(cfg: TransformerConfig, rng: jax.Array) -> Dict[str, Any]:
    """Initialize fp32 params. Layer params are stacked on a leading [L] dim
    so the forward can lax.scan over them.  PR-MoE pyramid configs
    (num_experts tuple) get a LIST of per-layer dicts instead — shapes
    differ per layer, so there is nothing to scan."""
    if isinstance(cfg.num_experts, (tuple, list)):
        return _init_params_het(cfg, rng)
    mixers.check(cfg)   # a model's config, and each of its groups' in turn
    if is_grouped(cfg):
        # the parts outside the layers from a one-layer model of the first
        # group, then each group's own stack: ``params["layers"][group]``
        groups = layer_groups(cfg)
        first = next(iter(groups.values()))[0]
        params = init_params(dataclasses.replace(first, num_layers=1), rng)
        params["layers"] = {
            name: init_params(g, jax.random.fold_in(rng, i + 1))["layers"]
            for i, (name, (g, _)) in enumerate(groups.items())}
        return params
    d, f = cfg.hidden_size, cfg.intermediate_size
    hd, nh, nkv, L = cfg.dims_per_head, cfg.num_heads, cfg.kv_heads, cfg.num_layers
    vd = cfg.v_dims_per_head
    std = cfg.initializer_range
    keys = jax.random.split(rng, 18)

    def dense(key, shape, scale=std):
        return (jax.random.normal(key, shape, jnp.float32) * scale)

    layers: Dict[str, Any] = {
        "attn_norm_scale": jnp.ones((L, d)),
        "wq": dense(keys[0], (L, d, nh * hd)),
        "wk": dense(keys[1], (L, d, nkv * hd)),
        "wv": dense(keys[2], (L, d, nkv * vd)),
        # residual-path projections scaled down by sqrt(2L) (GPT-2 init)
        "wo": dense(keys[3], (L, nh * vd, d), std / math.sqrt(2 * L)),
    }
    if is_latent(cfg):
        # no K and V projections: the latent and the shared key row from
        # ``wkv_a``, the latent's own norm, and the up-projection to every
        # head's keys (their unrotated part) and values
        _check_latent(cfg)
        r, rd = cfg.kv_lora_rank, cfg.rotary_dim
        del layers["wk"], layers["wv"]
        layers["wkv_a"] = dense(keys[1], (L, d, r + rd))
        layers["kv_a_norm_scale"] = jnp.ones((L, r))
        layers["wkv_b"] = dense(keys[2], (L, r, nh * (hd - rd + vd)))
    has_attn = sublayers(cfg)[0]
    if cfg.qk_norm and has_attn:
        _check_qk_norm(cfg)
        wq, wk = qk_norm_widths(cfg)
        layers["q_norm_scale"] = jnp.ones((L, wq))
        layers["k_norm_scale"] = jnp.ones((L, wk))
    if cfg.window_attn_sink:
        # one logit a query head, of the order of a score between two
        # tokens at these weights (std^2 * d), so that it takes a real
        # share of a row's probability: a checkpoint learns it
        layers["attn_sink"] = dense(keys[16], (L, nh), std * std * d)
    if cfg.attn_output_gate:
        layers["wg"] = dense(jax.random.fold_in(rng, 19), (L, d, nh * vd))
    if not has_attn:
        for name in _ATTN_LEAVES:
            layers.pop(name, None)
    for m in mixers_of(cfg):
        layers.update(m.init(cfg, rng, dense))
    if not cfg.shared_layernorm:   # GPT-J shares the attention LN
        layers["mlp_norm_scale"] = jnp.ones((L, d))
    _check_loop(cfg)
    if cfg.sandwich_norm:
        # the norms after the branches start at 1 / sqrt(2L): the residual
        # scaling the GPT-2 init gives w_o and w_down above, which a norm
        # behind them erases.  At 1 every branch adds a unit-RMS vector to
        # a unit-RMS stream and a looped stack of random layers is an
        # expanding map: a rounding error grows 2.4 x a pass (PERF.md, PR
        # 44), which no checkpoint that runs in bfloat16 does
        post = jnp.full((L, d), 1.0 / math.sqrt(2 * L))
        layers["attn_post_norm_scale"] = post
        layers["mlp_post_norm_scale"] = post
    if cfg.norm_after:
        # the two norms stand BEHIND w_o and w_down and erase the residual
        # scaling their init carries: they start at it, as sandwich_norm's
        layers["attn_norm_scale"] = layers["mlp_norm_scale"] = jnp.full(
            (L, d), 1.0 / math.sqrt(2 * L))
    if cfg.norm == "layernorm":
        layers["attn_norm_bias"] = jnp.zeros((L, d))
        if not cfg.shared_layernorm:
            layers["mlp_norm_bias"] = jnp.zeros((L, d))
    E = cfg.num_experts
    if E > 1 and cfg.moe_intermediate_size:
        f = cfg.moe_intermediate_size
    held = cfg.moe_experts_held or E        # the expert stacks' own count
    mlp_shape = (lambda *s: (L, held) + s) if E > 1 else (lambda *s: (L,) + s)
    # the width the routed experts read and write: the model's, or a latent
    de = cfg.moe_latent_size if E > 1 and cfg.moe_latent_size else d
    if E > 1:
        _check_experts(cfg)
        # per-expert biases supported on the gelu/relu path (Megatron-DS MoE
        # experts are biased Linears); swiglu experts stay bias-free
        assert not (cfg.mlp_bias and cfg.activation == "swiglu"), \
            "swiglu MoE experts do not support mlp_bias"
        layers["router"] = dense(keys[10], (L, d, E))
        if cfg.moe_select_bias:
            # about a fifth of the spread of the scores across experts at
            # these weights (router logits have std * sqrt(d)): it moves
            # the choice for some tokens and fixes it for none.  Near the
            # top-k threshold an expert's load goes as exp(~17 x its bias
            # / the scores' spread x 0.27), a factor of two a standard
            # deviation, so independent draws would give a share of 16
            # experts a quarter more or less load from one seed to the
            # next (PERF.md, PR 30).  The values are therefore the
            # quantiles of that normal over one share's experts
            # (``moe_experts_held``, else all), laid over every share in an
            # order of its own from the seed: every share of the experts,
            # under every seed, holds the same biases.
            n = held if E % held == 0 else E
            quantiles = jax.scipy.special.ndtri(
                (jnp.arange(n, dtype=jnp.float32) + 0.5) / n)
            order = jax.vmap(lambda key: jax.random.permutation(key, n))(
                jax.random.split(keys[17], L * (E // n)))
            layers["router_bias"] = (quantiles[order].reshape(L, E)
                                     * (0.04 * std * math.sqrt(d)))
    if cfg.activation == "swiglu":
        layers["w_gate"] = dense(keys[4], mlp_shape(de, f))
        layers["w_up"] = dense(keys[5], mlp_shape(de, f))
        layers["w_down"] = dense(keys[6], mlp_shape(f, de), std / math.sqrt(2 * L))
    else:
        layers["w_in"] = dense(keys[4], mlp_shape(de, f))
        layers["w_down"] = dense(keys[6], mlp_shape(f, de), std / math.sqrt(2 * L))
    if de != d:
        lk = jax.random.split(jax.random.fold_in(rng, 20), 2)
        layers["moe_latent_in"] = dense(lk[0], (L, d, de))
        layers["moe_latent_out"] = dense(lk[1], (L, de, d))
    if E > 1 and cfg.moe_shared_experts:
        # the shared experts as one MLP of their widths together, gated
        # where the experts are
        fs = cfg.moe_shared_experts * f
        sk = jax.random.split(jax.random.fold_in(rng, 18), 3)
        if cfg.activation == "swiglu":
            layers["shared_w_gate"] = dense(sk[0], (L, d, fs))
            layers["shared_w_up"] = dense(sk[1], (L, d, fs))
        else:
            layers["shared_w_in"] = dense(sk[1], (L, d, fs))
        layers["shared_w_down"] = dense(sk[2], (L, fs, d),
                                        std / math.sqrt(2 * L))
    if E > 1 and cfg.moe_use_residual:
        # residual MoE (PR-MoE, reference moe/layer.py use_residual): a dense
        # MLP branch + learned 2-way mixing coefficient per layer
        if cfg.activation == "swiglu":
            layers["res_w_gate"] = dense(keys[11], (L, d, f))
            layers["res_w_up"] = dense(keys[12], (L, d, f))
            layers["res_w_down"] = dense(keys[13], (L, f, d),
                                         std / math.sqrt(2 * L))
        else:
            layers["res_w_in"] = dense(keys[11], (L, d, f))
            layers["res_w_down"] = dense(keys[13], (L, f, d),
                                         std / math.sqrt(2 * L))
        layers["coefficient"] = dense(keys[14], (L, d, 2))
    if cfg.attn_bias:
        layers["bq"] = jnp.zeros((L, nh * hd))
        layers["bk"] = jnp.zeros((L, nkv * hd))
        layers["bv"] = jnp.zeros((L, nkv * hd))
        layers["bo"] = jnp.zeros((L, d))
    if cfg.mlp_bias:
        if cfg.activation == "swiglu":
            layers["b_gate"] = jnp.zeros((L, f))
            layers["b_up"] = jnp.zeros((L, f))
        else:
            layers["b_in"] = jnp.zeros(mlp_shape(f))
        layers["b_down"] = jnp.zeros(mlp_shape(d))
    _own_sublayer_alone(cfg, layers)

    params: Dict[str, Any] = {
        # a block with no norm on its input (norm_after) reads the embedding
        # raw: rows of unit scale, as a trained one's are.  Drawn at std 0.02
        # every branch of the first layers works under its norm's eps (mean
        # squares under 1e-6): the block is then a quadratic map of x that
        # doubles a rounding error a layer (PERF.md, PR 51)
        "embed": dense(keys[7], (cfg.vocab_size, d),
                       1.0 if cfg.norm_after else std),
        "layers": layers,
    }
    if cfg.final_norm:
        params["final_norm_scale"] = jnp.ones((d,))
        if cfg.norm == "layernorm":
            params["final_norm_bias"] = jnp.zeros((d,))
    if cfg.position == "learned":
        params["pos_embed"] = dense(keys[8], (cfg.max_seq_len, d))
    if cfg.embed_layernorm:
        params["embed_norm_scale"] = jnp.ones((d,))
        if cfg.norm == "layernorm":
            params["embed_norm_bias"] = jnp.zeros((d,))
    if cfg.type_vocab_size:
        params["type_embed"] = dense(keys[15], (cfg.type_vocab_size, d))
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(keys[9], (d, cfg.vocab_size))
        if cfg.lm_head_bias:
            params["lm_head_bias"] = jnp.zeros((cfg.vocab_size,))
    if cfg.pipeline_stages > 1:
        from ..runtime.pipe.spmd import stage_layer_count

        lp = stage_layer_count(L, cfg.pipeline_stages)
        params["layers"] = jax.tree_util.tree_map(
            lambda a: a.reshape((cfg.pipeline_stages, lp) + a.shape[1:]),
            params["layers"])
    return params


def _init_params_het(cfg: TransformerConfig, rng: jax.Array) -> Dict[str, Any]:
    """PR-MoE pyramid init: per-layer expert counts (1 = dense layer).
    ``params['layers']`` is a list of per-layer dicts."""
    if cfg.pipeline_stages > 1:
        raise NotImplementedError(
            "per-layer num_experts (PR-MoE pyramid) + pipeline parallelism "
            "is not supported (stages need uniform layer stacks)")
    if cfg.mlp_bias or cfg.attn_bias:
        raise NotImplementedError(
            "PR-MoE pyramid configs do not support attn/mlp biases")
    if cfg.sandwich_norm or cfg.loop_passes != 1:
        raise NotImplementedError(
            "PR-MoE pyramid configs do not support sandwich_norm or "
            "loop_passes")
    d, f = cfg.hidden_size, cfg.intermediate_size
    hd, nh, nkv, L = (cfg.dims_per_head, cfg.num_heads, cfg.kv_heads,
                      cfg.num_layers)
    std = cfg.initializer_range
    experts = moe_layer_experts(cfg)
    lkeys = jax.random.split(rng, L + 1)

    def dense(key, shape, scale=std):
        return jax.random.normal(key, shape, jnp.float32) * scale

    layers = []
    for i, E in enumerate(experts):
        k = jax.random.split(lkeys[i], 10)
        lp: Dict[str, Any] = {
            "attn_norm_scale": jnp.ones((d,)),
            "wq": dense(k[0], (d, nh * hd)),
            "wk": dense(k[1], (d, nkv * hd)),
            "wv": dense(k[2], (d, nkv * hd)),
            "wo": dense(k[3], (nh * hd, d), std / math.sqrt(2 * L)),
        }
        if cfg.qk_norm:
            _check_qk_norm(cfg)
            wq, wk = qk_norm_widths(cfg)
            lp["q_norm_scale"] = jnp.ones((wq,))
            lp["k_norm_scale"] = jnp.ones((wk,))
        if not cfg.shared_layernorm:
            lp["mlp_norm_scale"] = jnp.ones((d,))
        if cfg.norm == "layernorm":
            lp["attn_norm_bias"] = jnp.zeros((d,))
            if not cfg.shared_layernorm:
                lp["mlp_norm_bias"] = jnp.zeros((d,))
        shape = (lambda *s: (E,) + s) if E > 1 else (lambda *s: s)
        if E > 1:
            lp["router"] = dense(k[7], (d, E))
        if cfg.activation == "swiglu":
            lp["w_gate"] = dense(k[4], shape(d, f))
            lp["w_up"] = dense(k[5], shape(d, f))
            lp["w_down"] = dense(k[6], shape(f, d), std / math.sqrt(2 * L))
        else:
            lp["w_in"] = dense(k[4], shape(d, f))
            lp["w_down"] = dense(k[6], shape(f, d), std / math.sqrt(2 * L))
        if E > 1 and cfg.moe_use_residual:
            if cfg.activation == "swiglu":
                lp["res_w_gate"] = dense(k[8], (d, f))
                lp["res_w_up"] = dense(jax.random.fold_in(k[8], 1), (d, f))
                lp["res_w_down"] = dense(jax.random.fold_in(k[8], 2), (f, d),
                                         std / math.sqrt(2 * L))
            else:
                lp["res_w_in"] = dense(k[8], (d, f))
                lp["res_w_down"] = dense(jax.random.fold_in(k[8], 2), (f, d),
                                         std / math.sqrt(2 * L))
            lp["coefficient"] = dense(k[9], (d, 2))
        layers.append(lp)

    keys = jax.random.split(lkeys[-1], 4)
    params: Dict[str, Any] = {
        "embed": dense(keys[0], (cfg.vocab_size, d)),
        "layers": layers,
        "final_norm_scale": jnp.ones((d,)),
    }
    if cfg.norm == "layernorm":
        params["final_norm_bias"] = jnp.zeros((d,))
    if cfg.position == "learned":
        params["pos_embed"] = dense(keys[1], (cfg.max_seq_len, d))
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(keys[2], (d, cfg.vocab_size))
        if cfg.lm_head_bias:
            params["lm_head_bias"] = jnp.zeros((cfg.vocab_size,))
    return params


def param_specs(cfg: TransformerConfig) -> Dict[str, Any]:
    """Megatron-style TP PartitionSpecs over the 'model' axis (reference
    module_inject/layers.py LinearLayer/LinearAllreduce; auto_tp.py infers the
    same split).  Column-parallel: QKV, gate/up.  Row-parallel: out, down.
    The ZeRO planner composes ('data','expert') on top of these."""
    if isinstance(cfg.num_experts, (tuple, list)):
        return _param_specs_het(cfg)
    if is_grouped(cfg):
        groups = layer_groups(cfg)
        specs = param_specs(next(iter(groups.values()))[0])
        specs["layers"] = {name: param_specs(g)["layers"]
                           for name, (g, _) in groups.items()}
        return specs
    col = P(None, None, "model")     # [L, d, f_shard]
    row = P(None, "model", None)     # [L, f_shard, d]
    rep = P(None, None)
    layers: Dict[str, Any] = {
        "attn_norm_scale": rep,
        "wq": col, "wk": col, "wv": col, "wo": row,
    }
    if is_latent(cfg):
        # the latent is every head's: its projection and norm replicated,
        # the up-projection by head like any column-parallel one
        del layers["wk"], layers["wv"]
        layers.update(wkv_a=P(None, None, None), kv_a_norm_scale=rep,
                      wkv_b=col)
    has_attn = sublayers(cfg)[0]
    if cfg.qk_norm and has_attn:
        # over the column-parallel projection; by head: every chip's heads
        # share the one scale
        by = None if cfg.qk_norm == "head" else "model"
        layers.update(q_norm_scale=P(None, by), k_norm_scale=P(None, by))
    if cfg.window_attn_sink:
        layers["attn_sink"] = P(None, "model")
    if cfg.attn_output_gate:
        layers["wg"] = col
    if not has_attn:
        for name in _ATTN_LEAVES:
            layers.pop(name, None)
    for m in mixers_of(cfg):
        layers.update(m.specs(cfg))
    if not cfg.shared_layernorm:
        layers["mlp_norm_scale"] = rep
    if cfg.sandwich_norm:
        layers.update(attn_post_norm_scale=rep, mlp_post_norm_scale=rep)
    if cfg.norm == "layernorm":
        layers["attn_norm_bias"] = rep
        if not cfg.shared_layernorm:
            layers["mlp_norm_bias"] = rep
    if cfg.num_experts > 1:
        # experts over the 'expert' axis, expert-internal TP over 'model'
        # (the reference's expert-parallel groups, utils/groups.py:113)
        mcol = P(None, "expert", None, "model")   # [L, E, d, f_shard]
        mrow = P(None, "expert", "model", None)   # [L, E, f_shard, d]
        layers["router"] = P(None, None, None)
        if cfg.moe_select_bias:
            layers["router_bias"] = P(None, None)
    else:
        mcol, mrow = col, row
    if cfg.activation == "swiglu":
        layers.update(w_gate=mcol, w_up=mcol, w_down=mrow)
    else:
        layers.update(w_in=mcol, w_down=mrow)
    if cfg.num_experts > 1 and cfg.moe_latent_size:
        # whole on every chip, as the router that stands beside them
        layers.update(moe_latent_in=P(None, None, None),
                      moe_latent_out=P(None, None, None))
    if cfg.num_experts > 1 and cfg.moe_shared_experts:
        if cfg.activation == "swiglu":
            layers.update(shared_w_gate=col, shared_w_up=col)
        else:
            layers["shared_w_in"] = col
        layers["shared_w_down"] = row
    if cfg.num_experts > 1 and cfg.moe_use_residual:
        if cfg.activation == "swiglu":
            layers.update(res_w_gate=col, res_w_up=col, res_w_down=row)
        else:
            layers.update(res_w_in=col, res_w_down=row)
        layers["coefficient"] = P(None, None, None)
    if cfg.attn_bias:
        layers.update(bq=P(None, "model"), bk=P(None, "model"), bv=P(None, "model"),
                      bo=P(None, None))
    if cfg.mlp_bias:
        if cfg.activation == "swiglu":
            layers.update(b_gate=P(None, "model"), b_up=P(None, "model"))
        elif cfg.num_experts > 1:      # per-expert biases [L, E, f]
            layers["b_in"] = P(None, "expert", "model")
        else:
            layers["b_in"] = P(None, "model")
        layers["b_down"] = (P(None, "expert", None) if cfg.num_experts > 1
                            and cfg.activation != "swiglu" else P(None, None))
    _own_sublayer_alone(cfg, layers)

    if cfg.pipeline_stages > 1:
        # stage dim rides the 'pipe' axis; each shard holds its stage's layers
        layers = {k: P("pipe", *v) for k, v in layers.items()}

    specs: Dict[str, Any] = {
        "embed": P("model", None),   # vocab-parallel embedding
        "layers": layers,
    }
    if cfg.final_norm:
        specs["final_norm_scale"] = P()
        if cfg.norm == "layernorm":
            specs["final_norm_bias"] = P()
    if cfg.position == "learned":
        specs["pos_embed"] = P(None, None)
    if cfg.embed_layernorm:
        specs["embed_norm_scale"] = P()
        if cfg.norm == "layernorm":
            specs["embed_norm_bias"] = P()
    if cfg.type_vocab_size:
        specs["type_embed"] = P(None, None)
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, "model")
        if cfg.lm_head_bias:
            specs["lm_head_bias"] = P("model")
    return specs


def _param_specs_het(cfg: TransformerConfig) -> Dict[str, Any]:
    """Per-layer spec dicts mirroring :func:`_init_params_het`."""
    col, row, rep = P(None, "model"), P("model", None), P(None)
    experts = moe_layer_experts(cfg)
    layers = []
    for E in experts:
        lp: Dict[str, Any] = {"attn_norm_scale": rep,
                              "wq": col, "wk": col, "wv": col, "wo": row}
        if cfg.qk_norm:
            by = None if cfg.qk_norm == "head" else "model"
            lp.update(q_norm_scale=P(by), k_norm_scale=P(by))
        if not cfg.shared_layernorm:
            lp["mlp_norm_scale"] = rep
        if cfg.norm == "layernorm":
            lp["attn_norm_bias"] = rep
            if not cfg.shared_layernorm:
                lp["mlp_norm_bias"] = rep
        if E > 1:
            lp["router"] = P(None, None)
            mcol = P("expert", None, "model")
            mrow = P("expert", "model", None)
        else:
            mcol, mrow = col, row
        if cfg.activation == "swiglu":
            lp.update(w_gate=mcol, w_up=mcol, w_down=mrow)
        else:
            lp.update(w_in=mcol, w_down=mrow)
        if E > 1 and cfg.moe_use_residual:
            if cfg.activation == "swiglu":
                lp.update(res_w_gate=col, res_w_up=col, res_w_down=row)
            else:
                lp.update(res_w_in=col, res_w_down=row)
            lp["coefficient"] = P(None, None)
        layers.append(lp)
    specs: Dict[str, Any] = {
        "embed": P("model", None),
        "layers": layers,
        "final_norm_scale": P(),
    }
    if cfg.norm == "layernorm":
        specs["final_norm_bias"] = P()
    if cfg.position == "learned":
        specs["pos_embed"] = P(None, None)
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, "model")
        if cfg.lm_head_bias:
            specs["lm_head_bias"] = P("model")
    return specs


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

# Layer names on the device (docs/OBSERVABILITY.md "Device-time
# correlation"): every forward below runs its parts under jax.named_scope —
# embed, norm, attn_qkv, attn, attn_out, mlp, lm_head, loss, and in the paged
# block kv_write / kv_gather.  A scope is trace-time metadata on the ops it
# encloses: it costs nothing at run time and leaves the compiled computation
# as it was, and a device trace or an HLO dump then names a fusion by the
# layer it came from instead of by a number the compiler renumbers.

def _norm(cfg, x, scale, bias=None):
    with jax.named_scope("norm"):
        x32 = x.astype(jnp.float32)
        if cfg.norm == "rmsnorm":
            var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
            out = x32 * jax.lax.rsqrt(var + cfg.norm_eps) * scale
        else:
            mean = jnp.mean(x32, axis=-1, keepdims=True)
            var = jnp.var(x32, axis=-1, keepdims=True)
            out = ((x32 - mean) * jax.lax.rsqrt(var + cfg.norm_eps) * scale
                   + bias)
        return out.astype(x.dtype)


def _embed(cfg, params, tokens, positions, token_type_ids=None):
    """tokens ``[B,S]`` -> hidden states: token embedding, learned positions
    (``positions`` index the table as they are; a caller whose positions can
    pass its end clamps them), BERT's segment embedding, and the embedding
    LayerNorm of Bloom / BERT."""
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]
        x = _scaled(x, cfg.embed_multiplier)
        if cfg.position == "learned":
            x = x + params["pos_embed"].astype(cfg.dtype)[positions]
        if "type_embed" in params:
            tt = (token_type_ids if token_type_ids is not None
                  else jnp.zeros_like(tokens))
            x = x + params["type_embed"].astype(cfg.dtype)[tt]
        if cfg.embed_layernorm:
            x = _norm(cfg, x, params["embed_norm_scale"],
                      params.get("embed_norm_bias"))
    return x


def _lm_head(cfg, params, x):
    """Final hidden states -> logits (tied or untied head, GPT-J's bias)."""
    with jax.named_scope("lm_head"):
        if cfg.tie_embeddings:
            return _scaled(x @ params["embed"].astype(cfg.dtype).T,
                           cfg.lm_head_multiplier)
        logits = x @ params["lm_head"].astype(cfg.dtype)
        if "lm_head_bias" in params:   # GPT-J ties a bias to the LM head
            logits = logits + params["lm_head_bias"].astype(cfg.dtype)
        return _scaled(logits, cfg.lm_head_multiplier)


def _off_stream(cfg, h):
    """What a branch or the head reads of x: a looped model carries x in
    float32 (:func:`_passes`) and computes in ``cfg.dtype``; any other
    model's x is what it computes in."""
    return h.astype(cfg.dtype) if cfg.loop_passes > 1 else h


def _final_norm(cfg, params, x):
    return _norm(cfg, x, params["final_norm_scale"],
                 params.get("final_norm_bias"))


def _head(cfg, params, x):
    """The last layer's output -> logits: the final norm (post-LN blocks end
    normalised and have none; a looped model's last pass has ended with it,
    :func:`_passes`), then :func:`_lm_head`."""
    if cfg.final_norm and cfg.loop_passes == 1:
        x = _final_norm(cfg, params, x)
    return _lm_head(cfg, params, _off_stream(cfg, x))


def _passes(cfg, params, run_pass, carry, xs=None):
    """``run_pass(carry, xs_r) -> (carry, ys_r)`` runs the whole stack once
    (``carry`` leads with x).  Any model but a looped one: that call, on
    ``xs`` as it is.  A looped model: ``loop_passes`` calls as ONE scan on
    the device over the leading axis of ``xs`` (what a pass has of its own: a
    contiguous cache's layers, a pool's offset), the same weights in every
    pass and the final norm on x after each."""
    if cfg.loop_passes == 1:
        return run_pass(carry, xs)

    def one(carry, xs_r):
        (x, *rest), ys_r = run_pass(carry, xs_r)
        return (_final_norm(cfg, params, x), *rest), ys_r

    # x in float32 from here to the head: every add of a branch rounds it
    carry = (carry[0].astype(jnp.float32), *carry[1:])
    return jax.lax.scan(one, carry, xs, length=cfg.loop_passes)


def _rope(q, k, positions, theta, head_dim, rotary_dim=None,
          interleaved=False):
    """Rotary embedding: full or partial (``rotary_dim`` — GPT-J/NeoX), in
    either the half-split (llama/neox) or interleaved pair (GPT-J
    rotate_every_two) convention."""
    rd = head_dim if rotary_dim is None else rotary_dim
    half = rd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,half]
    cos, sin = jnp.cos(angles), jnp.sin(angles)

    def rot(x):  # x: [B,S,H,hd]
        x_rot, x_pass = x[..., :rd], x[..., rd:]
        c = cos[:, :, None, :].astype(x.dtype)
        s = sin[:, :, None, :].astype(x.dtype)
        if interleaved:
            x1, x2 = x_rot[..., 0::2], x_rot[..., 1::2]
            r1, r2 = x1 * c - x2 * s, x2 * c + x1 * s
            out = jnp.stack([r1, r2], axis=-1).reshape(x_rot.shape)
        else:
            x1, x2 = x_rot[..., :half], x_rot[..., half:]
            out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
        return out if rd == x.shape[-1] else jnp.concatenate(
            [out, x_pass], axis=-1)

    return rot(q), rot(k)


def _alibi_slopes(num_heads: int) -> np.ndarray:
    # standard ALiBi slope schedule (power-of-2 geometric)
    closest = 2 ** math.floor(math.log2(num_heads))
    base = 2.0 ** (-8.0 / closest)
    slopes = [base ** (i + 1) for i in range(closest)]
    if closest < num_heads:
        extra_base = 2.0 ** (-4.0 / closest)
        slopes += [extra_base ** (2 * i + 1) for i in range(num_heads - closest)]
    return np.asarray(slopes, dtype=np.float32)


def _sharded_flash(mesh, spec, sm_scale, q, k, v):
    """Causal flash attention per shard via shard_map (pallas_call has no
    SPMD partitioning rule); ``spec`` carries the head-axis placement —
    P(..., 'model', ...) for the tp path, P(..., ('model','seq'), ...) for
    ulysses.  One wrapper so a kernel-signature change lands once."""
    from ..ops.pallas.flash_attention import flash_attention
    from ..parallel import mesh as mesh_mod

    fa = mesh_mod.shard_map_unchecked(
        functools.partial(flash_attention, causal=True, sm_scale=sm_scale),
        mesh, in_specs=(spec, spec, spec), out_specs=spec)
    # kernel may widen to f32; cast HERE so the tp and ulysses call sites
    # can never disagree on output dtype
    return fa(q, k, v).astype(q.dtype)


def _attention(cfg: TransformerConfig, q, k, v, positions, attn_impl: str = "xla",
               custom_positions: bool = False, window=None, sink=None):
    """q:[B,S,Hq,hd] k,v:[B,S,Hkv,hd] -> [B,S,Hq,hd], causal.

    ``window``: traced per-layer scalar (0 = global) — local layers mask
    keys older than ``window`` positions; rides the masked XLA path only.
    ``sink [Hq]``: a learned logit a head that joins each row's softmax as
    one more column and gives no value (the masked XLA path only)."""
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    # Sequence-parallel mesh: ring attention keeps queries resident and
    # rotates K/V over the 'seq' axis (ppermute over ICI) instead of letting
    # GSPMD all-gather the full sequence.  Checked BEFORE "auto" resolves so
    # any seq-sharded mesh routes through the ring by default.
    if attn_impl in ("auto", "ring", "pallas") and cfg.position != "alibi" \
            and cfg.causal and not custom_positions and window is None:
        from ..parallel import mesh as mesh_mod

        m = mesh_mod._GLOBAL_MESH
        if m is not None and m.shape["seq"] > 1:
            sp = m.shape["seq"]
            tp = m.shape["model"]
            dp = mesh_mod.axis_size(m, BATCH_AXES)
            failed = [c for c, ok in [
                (f"S={S} % sp={sp}", S % sp == 0),
                (f"Hq={Hq} % tp={tp}", Hq % tp == 0),
                (f"Hkv={Hkv} % tp={tp}", Hkv % tp == 0),
                (f"B={B} % dp={dp}", B % dp == 0)] if not ok]
            if not failed:
                from ..ops.ring_attention import ring_attention_sharded

                return checkpoint_name(ring_attention_sharded(
                    q, k, v, m, BATCH_AXES, causal=True,
                    sm_scale=_sm_scale(cfg, hd)), "attn_out")
            if attn_impl == "ring":
                raise ValueError(
                    f"ring attention requested but unsatisfiable: {failed}")
        elif attn_impl == "ring":
            raise ValueError(
                "ring attention requires an initialized mesh with a 'seq' "
                f"axis > 1 (mesh={'none' if m is None else dict(m.shape)})")
    elif attn_impl == "ring":
        raise ValueError("ring attention requires a mesh with seq > 1, "
                         "default positions, and non-alibi attention")
    if attn_impl == "ulysses":
        # DeepSpeed-Ulysses sequence parallelism, the GSPMD way (the
        # reference snapshot predates Ulysses — beyond-parity like ring):
        # re-constrain [B,S,H,hd] from sequence-sharded to head-sharded —
        # XLA lowers the resharding to the head<->sequence all-to-all the
        # paper hand-writes — run FULL-sequence flash attention per shard,
        # constrain back.  vs ring: 2 all-to-alls + local attention
        # (bandwidth ~ O(B·S·H·hd/N) per hop) instead of N ppermute hops
        # overlapped with compute; prefer ulysses when heads >> sp and the
        # mesh's all-to-all rides one ICI hop, ring when S is the scarce
        # resource or heads are few (GQA).
        from ..parallel import mesh as mesh_mod

        m = mesh_mod._GLOBAL_MESH
        if m is None or m.shape["seq"] <= 1:
            raise ValueError(
                "ulysses attention requires an initialized mesh with a "
                f"'seq' axis > 1 (mesh={'none' if m is None else dict(m.shape)})")
        sp, tp = m.shape["seq"], m.shape["model"]
        dp = mesh_mod.axis_size(m, BATCH_AXES)
        failed = [c for c, ok in [
            (f"Hq={Hq} % sp*tp={sp * tp}", Hq % (sp * tp) == 0),
            (f"Hkv={Hkv} % sp*tp={sp * tp}", Hkv % (sp * tp) == 0),
            (f"S={S} % 128", S % 128 == 0),
            (f"B={B} % dp={dp}", B % dp == 0),
            # same shard_map kernel as the tp flash path: its specs never
            # mention 'pipe', so a pipelined mesh must be rejected here
            ("pipe=1", m.shape["pipe"] == 1),
            ("causal", bool(cfg.causal)),
            ("non-alibi", cfg.position != "alibi"),
            ("default positions", not custom_positions),
            ("no window", window is None)] if not ok]
        if failed:
            raise ValueError(f"ulysses attention unsatisfiable: {failed}")
        head_spec = P(BATCH_AXES, None, ("model", "seq"), None)
        q = constrain_spec(q, head_spec)
        k = constrain_spec(k, head_spec)
        v = constrain_spec(v, head_spec)
        out = _sharded_flash(m, head_spec, _sm_scale(cfg, hd), q, k, v)
        return constrain_spec(out, P(BATCH_AXES, "seq", "model", None))
    if attn_impl in ("auto", "pallas"):
        from ..parallel import mesh as mesh_mod

        m = mesh_mod._GLOBAL_MESH
        sharded = m is not None and any(s > 1 for s in m.shape.values())
        # The flash kernel masks by row/col index, so it requires default
        # positions (packed sequences carry custom ids); bias and windows
        # are not fused.  pallas_call has no SPMD partitioning rule, so on a
        # mesh it runs per-shard via shard_map — batch over DP axes, heads
        # over 'model' — and needs the full sequence per shard (ring
        # attention, above, covers the seq-sharded case).
        checks = [("S % 128", S % 128 == 0),
                  ("causal", bool(cfg.causal)),
                  ("non-alibi", cfg.position != "alibi"),
                  ("default positions", not custom_positions),
                  ("no window", window is None)]
        if sharded:
            tp = m.shape["model"]
            dp = mesh_mod.axis_size(m, BATCH_AXES)
            checks += [("seq=1", m.shape["seq"] == 1),
                       ("pipe=1", m.shape["pipe"] == 1),
                       (f"Hq={Hq} % tp={tp}", Hq % tp == 0),
                       (f"Hkv={Hkv} % tp={tp}", Hkv % tp == 0),
                       (f"B={B} % dp={dp}", B % dp == 0)]
        failed = [c for c, ok in checks if not ok]
        if attn_impl == "pallas" and failed:
            raise ValueError(
                f"pallas attention requested but unsatisfiable: {failed} "
                f"(S={S}, mesh={'none' if m is None else dict(m.shape)})")
        # "auto": measured on v5e (B=8,H=16,hd=64, bf16, fwd + fwd‖bwd):
        #   S=1024: xla 13.9ms vs pallas 15.9ms  — xla wins
        #   S=2048: xla 32.0ms vs pallas 29.8ms  — pallas wins (B=4: +18%)
        #   S=4096: xla 50.4ms vs pallas 25.5ms  — pallas 2x
        # The flash kernel takes over once the materialized [S,S] scores
        # dominate; below that XLA's fused einsum path is faster.  The
        # choice reads the shape and the mesh only — never which device
        # answered.
        if attn_impl == "pallas" or (S >= 2048 and not failed):
            sm = _sm_scale(cfg, hd)
            if sharded:
                return _sharded_flash(m, P(BATCH_AXES, None, "model", None),
                                      sm, q, k, v)
            from ..ops.pallas.flash_attention import flash_attention

            # GQA handled in-kernel (KV-head index map), no repeat
            return flash_attention(q, k, v, causal=True, sm_scale=sm)
    if Hkv != Hq:  # GQA: repeat KV groups
        rep = Hq // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * _sm_scale(cfg, hd)
    scores = scores.astype(jnp.float32)
    if cfg.position == "alibi":
        scores = scores + _alibi_bias(cfg, positions, Hq, S, jnp.float32)
    if cfg.causal:
        causal = positions[:, None, :, None] >= positions[:, None, None, :]
        scores = jnp.where(causal, scores, -1e30)
    if window is not None:
        # sliding window (GPT-Neo local layers): key within `window` of the
        # query; window == 0 means this layer is global — mask is all-true,
        # so one uniform computation serves both layer kinds under the scan
        rel = positions[:, None, :, None] - positions[:, None, None, :]
        local_ok = (window <= 0) | (rel < window)
        scores = jnp.where(local_ok, scores, -1e30)
    probs = _softmax_with_sink(
        scores, None if sink is None
        else sink.astype(jnp.float32)[None, :, None, None]).astype(q.dtype)
    return checkpoint_name(jnp.einsum("bhqk,bkhd->bqhd", probs, v),
                           "attn_out")


def _softmax_with_sink(scores, sink):
    """Softmax over the last axis of float32 ``scores``; ``sink`` (shaped to
    broadcast against ``scores[..., :1]``, or None) is one more logit a row
    that takes its share of the probability and has no column of its own."""
    if sink is None:
        return jax.nn.softmax(scores, axis=-1)
    m = jnp.maximum(scores.max(-1, keepdims=True), sink)
    p = jnp.exp(scores - m)
    return p / (p.sum(-1, keepdims=True) + jnp.exp(sink - m))


def _alibi_bias(cfg, positions, num_heads, S, dtype):
    slopes = jnp.asarray(_alibi_slopes(num_heads))
    rel = (positions[:, None, :] - positions[:, :, None]).astype(jnp.float32)  # [B,q,k]
    return (-jnp.abs(rel)[:, None, :, :] * slopes[None, :, None, None]).astype(dtype)


def _maybe_act_quant(cfg: TransformerConfig, h):
    """Activation fake-quant at the inputs of the attention and MLP
    matmuls."""
    if not cfg.act_quant_bits:
        return h
    from ..compression.quantize import activation_fake_quant

    return activation_fake_quant(h, cfg.act_quant_bits,
                                 symmetric=cfg.act_quant_symmetric)


def _dense_mlp(cfg: TransformerConfig, lp: Dict[str, Any], h, prefix=""):
    """Plain MLP body; ``prefix="res_"`` selects the PR-MoE residual branch's
    weights (biases only exist on the unprefixed dense path)."""
    bias = cfg.mlp_bias and not prefix
    gate_mult, out_mult = cfg.mlp_multipliers
    if cfg.activation == "swiglu":
        g = checkpoint_name(h @ lp[prefix + "w_gate"], "mlp_gate")
        u = checkpoint_name(h @ lp[prefix + "w_up"], "mlp_up")
        if bias:
            g, u = g + lp["b_gate"], u + lp["b_up"]
        m = jax.nn.silu(_scaled(g, gate_mult)) * u
        m = _scaled(m @ lp[prefix + "w_down"], out_mult)
    else:
        m = checkpoint_name(h @ lp[prefix + "w_in"], "mlp_up")
        if bias:
            m = m + lp["b_in"]
        if cfg.activation == "relu":
            m = jax.nn.relu(m)
        elif cfg.activation == "relu2":        # Nemotron-H: relu(x)^2
            m = jnp.square(jax.nn.relu(m))
        elif cfg.activation == "gelu_exact":   # HF 'gelu' (erf)
            m = jax.nn.gelu(m, approximate=False)
        elif cfg.activation == "quick_gelu":   # CLIP: x * sigmoid(1.702 x)
            m = m * jax.nn.sigmoid(1.702 * m)
        else:
            m = jax.nn.gelu(m)
        m = m @ lp[prefix + "w_down"]
    if bias:
        m = m + lp["b_down"]
    return m


# Tokens of one prompt a dropless expert layer takes at a time
MOE_CHUNK_TOKENS = 2048


def _moe_chunks(cfg: TransformerConfig, B: int, S: int) -> int:
    """Calls a dropless expert layer makes of a block ``[B, S]``: a long
    prompt goes :data:`MOE_CHUNK_TOKENS` at a time, anything else whole."""
    n = S // MOE_CHUNK_TOKENS
    return n if (B == 1 and n > 1 and S % MOE_CHUNK_TOKENS == 0
                 and not cfg.moe_drop_tokens) else 1


def expert_matmul_path(cfg: TransformerConfig, B: int, S: int
                       ) -> Optional[str]:
    """How a program over a block ``[B, S]`` runs the grouped
    products of ``cfg``'s dropless expert layers: ``"kernel"``
    (``ops/pallas/grouped_matmul.py``) or ``"ragged_dot"``, as
    ``moe.sharded_moe.expert_matmul_path`` says of one call of the layer
    (:func:`_moe_chunks` of the block's tokens each); ``None`` for a model
    with no such layer.  The same function of the same static shape that
    ``moe_ffn_nodrop`` asks when the program is traced; the serving executor
    reports it (``mesh_info()["expert_matmul"]``)."""
    from ..moe.sharded_moe import expert_matmul_path as path

    if expert_counts_shape(cfg) is None:
        return None
    return path(B * S // _moe_chunks(cfg, B, S), cfg.moe_top_k,
                cfg.num_experts, cfg.moe_latent_size or cfg.hidden_size,
                cfg.moe_intermediate_size or cfg.intermediate_size,
                cfg.dtype, _common._pallas_interpret())


def expert_rows_moved(cfg: TransformerConfig, B: int, S: int, counts,
                      live_tokens: int) -> Tuple[int, int]:
    """``(sorted, moved)``: the (token, expert) rows the dropless expert
    layers of a program over a block ``[B, S]`` sort, and the rows their way
    in fills, from the rows each expert computed (``counts [layers,
    experts]``, the program's own) and the block's real tokens (its first
    ``live_tokens``).  On the ``lax.ragged_dot`` side of the rule every
    sorted row is gathered; on the kernel's (``moe/live_rows.py``) a call's
    live rows in whole tiles.  ``counts`` are summed over a prompt's chunks,
    so for a block in several chunks each further chunk that holds a real
    token is reckoned a whole tile more, the most its own rounding can add:
    exact for a block in one call, an upper bound (under a tile a chunk)
    else."""
    from ..moe.live_rows import ROWS_IN_TILE, moved_rows

    n = _moe_chunks(cfg, B, S)
    tokens = B * S // n
    rows = tokens * cfg.moe_top_k + -(tokens * cfg.moe_top_k) % 8
    total = len(counts) * n * rows
    if expert_matmul_path(cfg, B, S) != "kernel":
        return total, total
    chunks = min(n, -(-live_tokens // tokens))
    return total, sum(
        min(chunks * rows, int(moved_rows(int(r), rows))
            + (chunks - 1) * min(ROWS_IN_TILE, rows))
        for r in counts.sum(axis=1) if r)


def expert_products(cfg: TransformerConfig) -> int:
    """Grouped products a program of ``cfg`` holds: gate, up and down (in
    and down for experts that are not gated) a dropless expert layer."""
    shape = expert_counts_shape(cfg)
    return (3 if cfg.activation == "swiglu" else 2) * shape[0] if shape else 0


def _mlp(cfg: TransformerConfig, lp: Dict[str, Any], h, rng, deterministic,
         token_mask=None, expert_offset=None):
    """The MLP or expert layer of :func:`_block`: returns (output,
    moe_aux_loss, counts), ``counts`` the rows each expert computed (``[E]``
    int32; ``None`` unless the layer ran the dropless dispatch).  MoE-ness is detected from the layer's params
    (PR-MoE pyramid layers differ per depth).

    ``token_mask [B,S]`` (serving: a prompt's padding, a tick's idle slots)
    keeps masked tokens out of a dropless expert layer's groups; a dense MLP
    computes them like any other.  ``expert_offset``: the expert leaves of
    ``lp`` are the whole layer stack and this layer's experts start there
    (``moe_ffn_nodrop``)."""
    with jax.named_scope("mlp"):
        aux, counts = jnp.float32(0.0), None
        if "router" in lp:
            from ..moe.sharded_moe import MoEConfig, moe_ffn

            moe = MoEConfig(num_experts=int(lp["router"].shape[-1]),
                            top_k=cfg.moe_top_k,
                            capacity_factor=cfg.capacity_factor,
                            eval_capacity_factor=cfg.eval_capacity_factor,
                            min_capacity=cfg.moe_min_capacity,
                            noisy_gate_policy=cfg.noisy_gate_policy,
                            drop_tokens=cfg.moe_drop_tokens,
                            norm_topk_prob=cfg.moe_norm_topk_prob,
                            norm_topk_eps=cfg.moe_norm_topk_eps,
                            score_func=cfg.moe_score_func,
                            held=((cfg.moe_expert_first, cfg.moe_experts_held)
                                  if cfg.moe_experts_held else None),
                            routed_scale=cfg.moe_routed_scale)

            latent = "moe_latent_in" in lp

            def experts(hc, mask):
                u = hc
                if latent:
                    # the experts read and write the latent's rows; the
                    # router reads the model's width
                    with jax.named_scope("moe_latent_in"):
                        u = hc @ lp["moe_latent_in"]
                m, aux, counts = moe_ffn(
                    u, lp["router"], lp, moe, activation=cfg.activation,
                    deterministic=deterministic, rng=rng, token_mask=mask,
                    expert_offset=expert_offset,
                    select_bias=lp.get("router_bias"),
                    pallas_interpret=_common._pallas_interpret(),
                    router_input=hc if latent else None)
                if latent:
                    with jax.named_scope("moe_latent_out"):
                        m = m @ lp["moe_latent_out"]
                return m, aux, counts

            B, S, D = h.shape
            n = _moe_chunks(cfg, B, S)
            if n > 1:
                # a long prompt a chunk at a time: the sorted rows, their
                # products and the way back are top_k x the chunk, not
                # top_k x the prompt (8 x 16,384 rows of 4,096 are 1 GB
                # each time they are written)
                mask = (token_mask if token_mask is not None
                        else jnp.ones((B, S), bool))
                m, aux, counts = jax.lax.map(
                    lambda a: experts(a[0][None], a[1][None]),
                    (h.reshape(n, -1, D), mask.reshape(n, -1)))
                m, aux, counts = m.reshape(B, S, D), aux.mean(), counts.sum(0)
            else:
                m, aux, counts = experts(h, token_mask)
            if "shared_w_down" in lp:
                # the shared experts: every token's, beside the routed sum
                # (under a held share of the routed experts every chip
                # computes them alike)
                with jax.named_scope("mlp_shared"):
                    m = m + _dense_mlp(cfg, lp, h, prefix="shared_")
            if "coefficient" in lp:
                # residual MoE (reference moe/layer.py:16 use_residual): dense
                # branch + learned softmax mixing coefficient
                res = _dense_mlp(cfg, lp, h, prefix="res_")
                coef = jax.nn.softmax(
                    (h @ lp["coefficient"]).astype(jnp.float32), axis=-1
                ).astype(m.dtype)
                m = m * coef[..., 0:1] + res * coef[..., 1:2]
        else:
            m = _dense_mlp(cfg, lp, h)
        return m, aux, counts


def _qkv(cfg: TransformerConfig, lp: Dict[str, Any], h, positions, proj=None):
    """Post-norm activations ``h [B,S,d]`` -> ``q [B,S,Hq,hd]``, ``k
    [B,S,Hkv,hd]``, ``v [B,S,Hkv,vd]``, biased, QK-normed, rotated, the
    values scaled.  ``proj(y, name,
    hin)``, when given, adds the serving path's per-slot adapter delta
    (:func:`_adapter_proj`)."""
    B, S, _ = h.shape
    hd, nh, nkv = cfg.dims_per_head, cfg.num_heads, cfg.kv_heads
    with jax.named_scope("attn_qkv"):
        q, k, v = h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]
        if proj is not None:
            q, k, v = proj(q, "wq", h), proj(k, "wk", h), proj(v, "wv", h)
        if cfg.attn_bias:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        by_head = cfg.qk_norm == "head"
        if cfg.qk_norm and not by_head:
            # over the whole projection, before the head split
            q = _norm(cfg, q, lp["q_norm_scale"])
            k = _norm(cfg, k, lp["k_norm_scale"])
        q = q.reshape(B, S, nh, hd)
        k = k.reshape(B, S, nkv, hd)
        if by_head:     # over each head's own dims, one scale for all heads
            q = _norm(cfg, q, lp["q_norm_scale"])
            k = _norm(cfg, k, lp["k_norm_scale"])
        v = v.reshape(B, S, nkv, cfg.v_dims_per_head)
        if cfg.attn_value_scale != 1.0:
            v = v * jnp.asarray(cfg.attn_value_scale, v.dtype)
        k = _scaled(k, cfg.key_multiplier)
        if cfg.position == "rope":
            q, k = _rope(q, k, positions, cfg.rope_theta, hd,
                         rotary_dim=cfg.rotary_dim,
                         interleaved=cfg.rope_interleaved)
    return q, k, v


def _qkv_latent(cfg: TransformerConfig, lp: Dict[str, Any], h, positions):
    """Post-norm activations ``h [B,S,d]`` of a latent-attention layer ->
    ``q [B,S,Hq,hd]`` and the token's cache row ``latent [B,S,r + rd]``:
    the ``r``-wide latent RMS-normed (``kv_a_norm_scale``), then the one key
    row every head shares, rotated.  The rotary embedding takes the last
    ``rd`` dims of each query head and that row, adjacent pairs ``(2i, 2i +
    1)`` under ``rope_interleaved`` (a checkpoint loader that de-interleaves
    both, as ``deepseek_v3`` does, permutes q and k alike and leaves every
    score as it is)."""
    B, S, _ = h.shape
    hd, nh = cfg.dims_per_head, cfg.num_heads
    r, rd = cfg.kv_lora_rank, cfg.rotary_dim
    with jax.named_scope("attn_qkv"):
        q = (h @ lp["wq"]).reshape(B, S, nh, hd)
        kv = h @ lp["wkv_a"]
        c = _norm(cfg, kv[..., :r], lp["kv_a_norm_scale"])
        q_pe, k_pe = _rope(q[..., hd - rd:], kv[..., None, r:], positions,
                           cfg.rope_theta, rd,
                           interleaved=cfg.rope_interleaved)
        q = jnp.concatenate([q[..., :hd - rd], q_pe], axis=-1)
        latent = jnp.concatenate([c, k_pe[:, :, 0]], axis=-1)
    return q, latent


def _latent_up(cfg: TransformerConfig, wkv_b):
    """``wkv_b [r, Hq * (nope + vd)]`` as its two views ``(W_UK [r, Hq,
    nope], W_UV [r, Hq, vd])``: a head's keys (their unrotated part) and
    values from the latent."""
    nope = cfg.dims_per_head - cfg.rotary_dim
    w = wkv_b.reshape(cfg.kv_lora_rank, cfg.num_heads, -1)
    return w[..., :nope], w[..., nope:]


def _latent_expand(cfg: TransformerConfig, latent, wkv_b):
    """Cache rows ``latent [B,S,r + rd]`` -> every head's ``k [B,S,Hq,hd]``
    (its own unrotated part, then the shared rotated row) and ``v
    [B,S,Hq,vd]``: the expanded path, what a prompt attends over."""
    B, S, _ = latent.shape
    r, nh = cfg.kv_lora_rank, cfg.num_heads
    w_uk, w_uv = _latent_up(cfg, wkv_b)
    c = latent[..., :r]
    k_pe = jnp.broadcast_to(latent[:, :, None, r:],
                            (B, S, nh, cfg.rotary_dim))
    k = jnp.concatenate([jnp.einsum("bsr,rhn->bshn", c, w_uk), k_pe], -1)
    return k, jnp.einsum("bsr,rhv->bshv", c, w_uv)


def _attn_gate(cfg: TransformerConfig, lp: Dict[str, Any], h, proj=None):
    """Post-norm activations ``h [B,S,d]`` -> the gate on attention's output
    ``sigmoid(h wg) [B,S,Hq * vd]`` (``attn_output_gate``: made from the
    input q, k and v are made from, applied by :func:`_attn_out` in front of
    ``wo``); None for a model without one."""
    if not cfg.attn_output_gate:
        return None
    with jax.named_scope("attn_gate"):
        g = h @ lp["wg"]
        if proj is not None:
            g = proj(g, "wg", h)
        return jax.nn.sigmoid(g.astype(jnp.float32)).astype(g.dtype)


def _attn_out(cfg: TransformerConfig, lp: Dict[str, Any], attn, proj=None,
              gate=None):
    """Attention output ``[B,S,Hq,hd]`` through the output projection,
    multiplied by ``gate`` (:func:`_attn_gate`) first where there is one."""
    B, S = attn.shape[:2]
    with jax.named_scope("attn_out"):
        attn2d = attn.reshape(B, S, -1)
        if gate is not None:
            attn2d = attn2d * gate
        out = attn2d @ lp["wo"]
        if proj is not None:
            out = proj(out, "wo", attn2d)
        if cfg.attn_bias:
            out = out + lp["bo"]
    return out


def ssm_scan_chunks(cfg: TransformerConfig, block: int,
                    tokens: Optional[int] = None) -> Optional[int]:
    """Chunks of its mixer's ``chunk`` positions the scan of a block of
    ``block`` tokens runs, or those of them that hold one of its ``tokens``
    real ones (the ``scan_chunks`` span attrs of a prompt).  None for a model
    whose prompts run no scan (no state a slot, or a tail alone)."""
    for m in mixers_of(cfg):
        if m.chunk:
            return -(-(block if tokens is None else min(tokens, block))
                     // getattr(cfg, m.chunk))
    return None


def _mixer_of(cfg: TransformerConfig):
    """:func:`_block`'s ``ssm`` over a block that starts its sequences."""
    has = mixers_of(cfg)
    return functools.partial(has[0].mixer, cfg) if has else None


def _dropout(cfg: TransformerConfig, y, rng, deterministic: bool):
    """``(y, rng)``: residual dropout on a sublayer's output, and the key
    chain moved on by the split it took."""
    if not cfg.dropout or deterministic:
        return y, rng
    rng, sub = jax.random.split(rng)
    keep = jax.random.bernoulli(sub, 1 - cfg.dropout, y.shape)
    return y * keep / (1 - cfg.dropout), rng


def _block(cfg: TransformerConfig, lp: Dict[str, Any], x, positions, rng,
           attend, deterministic: bool = True, proj=None, token_mask=None,
           expert_offset=None, ssm=None):
    """One transformer layer, every residual wiring the family has:

      pre-LN (GPT-2, OPT, Llama)   x += attn(LN(x));  x += mlp(LN'(x))
      parallel (NeoX; GPT-J)       x += attn(LN(x)) + mlp(LN'(x))  (GPT-J:
                                   one LN, the MLP reads attention's input)
      post-LN (BERT)               x = LN(x + attn(x));  x = LN'(x + mlp(x))
      two mixers (Falcon-H1)       n = LN(x);  x += a attn(n) + b ssm(n);
                                   x += mlp(LN'(x))
      one mixer a layer (Granite   x += r ssm(LN(x))  or  x += r attn(LN(x));
      4.0-H, ``residual_multiplier``)                 x += r mlp(LN'(x))
      sandwich (Ouro, Trinity)     x += N2(attn(N1(x)));  x += N4(mlp(N3(x)))
      gated (``attn_output_gate``) attn(n) = (softmax(q k) v * sigmoid(n Wg)) Wo
      norm after (Olmo-Hybrid,     x += N1(mix(x));  x += N2(mlp(x)), mix the
      ``norm_after``)              delta mixer or attention by the layer's kind
      one sublayer (Nemotron-H,    x += mix(N(x))  or  x += mlp(N(x)): one
      ``sublayer``)                norm and one add, by the layer's kind

    What attention reads, and where K/V go, is the caller's:
    ``attend(q, k, v) -> (out [B,S,Hq,hd], state)`` is handed the layer's
    projections (a latent-attention layer's: its queries, the tokens' cache
    rows and its up-projection) and returns whatever it keeps (:func:`_attend_full`: nothing;
    :func:`_attend_cached`: the layer's cache buffers; :func:`_attend_paged`:
    the page pool).  ``proj``, ``token_mask`` and ``expert_offset`` are the
    serving path's (:func:`_qkv`, :func:`_attn_out`, :func:`_mlp`).  A layer
    with a state-space mixer is handed ``ssm(lp, h) -> (out [B,S,d], kept)``,
    the mixer over the same normed input with whatever state it continues
    and keeps (:func:`_ssm_mixer`); ``state`` is then ``(attend's, the
    mixer's)``.  Which of the two a layer has is :func:`sublayers`' rule: a
    layer with no attention takes no ``attend`` (None), and ``state`` is
    ``(None, the mixer's)``; a layer that is its MLP alone takes neither and
    keeps nothing (``state`` None), one that is its mixer or attention
    alone ends at that add.

    Returns ``(x, moe_aux_loss, expert_counts, state)``."""
    post = cfg.post_layernorm
    has_attn, has_mixer, has_mlp = sublayers(cfg)
    if not (has_attn or has_mixer):
        return (*_block_mlp(cfg, lp, x, x, rng, deterministic, token_mask,
                            expert_offset), None)
    h = x if post or cfg.norm_after else _norm(
        cfg, x, lp["attn_norm_scale"], lp.get("attn_norm_bias"))
    h = _maybe_act_quant(cfg, _off_stream(cfg, h))
    if has_mixer:
        # the mixers read ONE normed input, each through its own multiplier
        side, ssm_kept = ssm(lp, h)
    if has_attn:
        if has_mixer:
            h = _scaled(h, cfg.attn_in_multiplier)
        if is_latent(cfg):
            # latent attention: ``attend`` is handed the token's cache row
            # in place of k, and the layer's up-projection in place of v
            q, k = _qkv_latent(cfg, lp, h, positions)
            v = lp["wkv_b"]
        else:
            q, k, v = _qkv(cfg, lp, h, positions, proj)
            # named so "save_matmuls" can pin the projection outputs
            # (post-rope, so the attention backward starts from exactly
            # these tensors)
            q = checkpoint_name(q, "q_proj")
            k = checkpoint_name(k, "k_proj")
            v = checkpoint_name(v, "v_proj")
        # the attention's output is named "attn_out" where it is made
        # (:func:`_attention`; the flash kernel names its own output inside
        # its vjp, in the layout its backward reads), so a remat policy can
        # keep it
        gate = _attn_gate(cfg, lp, h, proj)
        attn, state = attend(q, k, v)
        attn = _attn_out(cfg, lp, attn, proj, gate)
        if cfg.sandwich_norm:
            attn = _norm(cfg, attn, lp["attn_post_norm_scale"])
        attn, rng = _dropout(cfg, attn, rng, deterministic)
        if has_mixer:
            attn = (_scaled(attn, cfg.attn_out_multiplier)
                    + _scaled(side, cfg.ssm_out_multiplier))
            state = (state, ssm_kept)
    else:
        attn = _scaled(side, cfg.ssm_out_multiplier)
        state = (None, ssm_kept)
    if cfg.norm_after:
        attn = _norm(cfg, attn, lp["attn_norm_scale"])
    res = x + _scaled(attn, cfg.residual_multiplier)
    if not has_mlp:
        return res, jnp.float32(0.0), None, state
    x, aux, counts = _block_mlp(cfg, lp, x, res, rng, deterministic,
                                token_mask, expert_offset, h)
    return x, aux, counts, state


def _block_mlp(cfg: TransformerConfig, lp: Dict[str, Any], x, res, rng,
               deterministic, token_mask, expert_offset, h=None):
    """:func:`_block`'s second half, the MLP or expert layer on ``res`` (the
    layer's input ``x`` with its first sublayer added, or ``x`` itself in a
    layer that is its MLP alone; ``h``: the first sublayer's normed input,
    which a GPT-J block's MLP reads): ``(x, moe_aux_loss, expert_counts)``."""
    post = cfg.post_layernorm
    if post:
        res = _norm(cfg, res, lp["attn_norm_scale"], lp.get("attn_norm_bias"))
        h2 = _maybe_act_quant(cfg, res)
    elif cfg.parallel_residual and cfg.shared_layernorm:
        h2 = h
    elif cfg.norm_after:
        h2 = _maybe_act_quant(cfg, res)
    else:
        h2 = _maybe_act_quant(cfg, _off_stream(cfg, _norm(
            cfg, x if cfg.parallel_residual else res,
            lp["mlp_norm_scale"], lp.get("mlp_norm_bias"))))
    rng, sub = jax.random.split(rng)
    m, aux, counts = _mlp(cfg, lp, h2, sub, deterministic,
                          token_mask=token_mask, expert_offset=expert_offset)
    if cfg.sandwich_norm:
        m = _norm(cfg, m, lp["mlp_post_norm_scale"])
    if cfg.norm_after:
        m = _norm(cfg, m, lp["mlp_norm_scale"])
    m, rng = _dropout(cfg, m, rng, deterministic)
    x = res + _scaled(m, cfg.residual_multiplier)
    if post:
        x = _norm(cfg, x, lp["mlp_norm_scale"], lp.get("mlp_norm_bias"))
    return x, aux, counts


def _attend_full(cfg: TransformerConfig, positions, attn_impl: str = "xla",
                 custom_positions: bool = False, window=None, sink=None):
    """:func:`_block`'s ``attend`` over the block's own tokens (training,
    the uncached forward): nothing is kept."""
    def attend(q, k, v):
        with jax.named_scope("attn"):
            if is_latent(cfg):     # the expanded path
                k, v = _latent_expand(cfg, k, v)
            return _attention(cfg, q, k, v, positions, attn_impl,
                              custom_positions, window=window,
                              sink=sink), None
    return attend


# ``remat_policy`` left at REMAT_AUTO names no policy: the training engine
# resolves one for each compiled fused step from the memory the step has
# (``DeepSpeedEngine.resolve_remat``: the richest rung of REMAT_LADDER whose
# program fits).  Where no resolver runs (``forward`` under a caller's own
# jit, the 1F1B executor, the layer-streamed offload path, evaluation) it
# means "nothing_saveable".
REMAT_AUTO = "auto"
# The residuals a rung keeps, by their ``checkpoint_name`` (:func:`_block`,
# :func:`_attention`, :func:`_mlp`, the flash kernel's vjp); everything else of the layer is run
# again inside its backward.  Kept bytes a layer in bf16, T tokens, d hidden,
# f the MLP's width, H heads: save_attn the attention's output and the flash
# kernel's row statistics (T*d + 4*T*H), so the backward runs the projections
# again but not the flash forward; save_qkv adds q, k, v (T*(d + 2*kv));
# save_matmuls adds the up (and gate) projection (T*f each), so what is run
# again is norms and elementwise.
# ("dots_saveable" would also pin the [S,S] scores of the XLA attention.)
REMAT_SAVED_NAMES = {
    "save_attn": ("attn_out", "attn_lse"),
    "save_qkv": ("attn_out", "attn_lse", "q_proj", "k_proj", "v_proj"),
    "save_matmuls": ("attn_out", "attn_lse", "q_proj", "k_proj", "v_proj",
                     "mlp_gate", "mlp_up"),
}
# richest first: each rung keeps a superset of the next one's residuals
REMAT_LADDER = ("save_matmuls", "save_qkv", "save_attn", "nothing_saveable")


def _build_block(cfg: TransformerConfig, attn_impl: str, deterministic: bool,
                 custom_positions: bool):
    """One layer's apply fn ``block(lp, x, rng, positions)`` with the remat
    policy and random-LTD wrapping applied — shared by forward() and the
    1F1B pipeline executor."""
    # a state-space mixer beside attention starts its sequence here
    ssm = _mixer_of(cfg)
    block = lambda lp, x, sub, pos, window=None: _block(  # noqa: E731
        cfg, lp, x, pos, sub,
        _attend_full(cfg, pos, attn_impl, custom_positions, window),
        deterministic, ssm=ssm)[:2]
    if cfg.remat:
        name = ("nothing_saveable" if cfg.remat_policy == REMAT_AUTO
                else cfg.remat_policy)
        if name in REMAT_SAVED_NAMES:
            policy = jax.checkpoint_policies.save_only_these_names(
                *REMAT_SAVED_NAMES[name])
        else:
            policy = getattr(jax.checkpoint_policies, name, None)
        block = jax.checkpoint(block, policy=policy)
    if cfg.random_ltd and cfg.random_ltd_keep > 0:
        # token drop wraps OUTSIDE remat so only the kept-subset compute is
        # rematerialized; the gather/scatter bookkeeping is cheap and saved
        from ..runtime.data_pipeline.data_routing.random_ltd import \
            random_ltd_block

        if cfg.attention_layers is not None:
            raise NotImplementedError(
                "random-LTD with per-layer attention types is not supported "
                "(the token-subset wrapper does not thread the window)")
        inner_block = block

        def block(lp, x, sub, pos, window=None):
            return random_ltd_block(inner_block, cfg, lp, x, pos, sub,
                                    cfg.random_ltd_keep, deterministic)
    return block


def _layer_step(block, positions, carry, xs):
    """One layer of a training forward, in the form ``lax.scan`` takes:
    ``carry = (x, rng, aux_sum)``, ``xs = (lp, keep, window)`` with ``keep``
    (progressive layer drop: False skips the layer) and ``window`` (a local
    attention layer's span) None where the call has none."""
    (x, rng, aux_sum), (lp, keep, window) = carry, xs
    rng, sub = jax.random.split(rng)
    y, aux = block(lp, x, sub, positions, window)
    if keep is not None:
        y, aux = jnp.where(keep, y, x), jnp.where(keep, aux, 0.0)
    return (y, rng, aux_sum + aux), None


def forward(cfg: TransformerConfig, params: Dict[str, Any], tokens: jax.Array,
            positions: Optional[jax.Array] = None, rng: Optional[jax.Array] = None,
            attn_impl: str = "xla", deterministic: bool = True,
            seq_sharded: bool = True, return_aux: bool = False,
            pld_theta: Optional[jax.Array] = None,
            token_type_ids: Optional[jax.Array] = None):
    """tokens [B, S] int32 -> logits [B, S, V] (+ aux dict if return_aux)."""
    B, S = tokens.shape
    custom_positions = positions is not None
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    if rng is None:
        rng = jax.random.PRNGKey(0)

    x = _embed(cfg, params, tokens, positions, token_type_ids)
    # activations: batch over DP axes, sequence over 'seq' axis
    act_spec = P(BATCH_AXES, "seq" if seq_sharded else None, None)
    x = constrain_spec(x, act_spec)

    if is_grouped(cfg):
        # layers in groups, inference only: each layer its group's uniform
        # config and its own slice of the group's stack, through the masked
        # product (window, sink, values of their own width; a latent
        # layer's expanded keys and values)
        if not deterministic or pld_theta is not None:
            _hybrid_refuse("training (dropout, layer drop, a backward pass)",
                           cfg)
        if attn_impl not in ("xla", "auto"):
            _hybrid_refuse(f"the flash kernel (attn_impl={attn_impl!r}: keys "
                           "and values of one width, no sink)", cfg)
        groups = layer_groups(cfg)
        for group, index, kind, _ in layer_plan(cfg):
            # a stack, or a tuple of the layers' own arrays where the
            # serving executor holds the tree (per_layer_leaves)
            lp = {k: v[index] for k, v in params["layers"][group].items()}
            g = groups[group][0]
            has_attn = sublayers(g)[0]
            x = _block(g, lp, x, positions, rng, _attend_full(
                cfg, positions, "xla", custom_positions,
                window=cfg.window_size if kind == "window" else None,
                sink=lp.get("attn_sink")) if has_attn else None,
                ssm=_mixer_of(g))[0]
            x = constrain_spec(x, act_spec)
        logits = _head(cfg, params, x)
        return (logits, {"moe_aux_loss": jnp.float32(0.0)}) if return_aux \
            else logits

    if is_ssm(cfg) and (not deterministic or pld_theta is not None):
        _hybrid_refuse("training (dropout, layer drop, a backward pass "
                       "through the chunked scan)", cfg)
    block = _build_block(cfg, attn_impl, deterministic, custom_positions)
    step = functools.partial(_layer_step, block)

    het = isinstance(params["layers"], (list, tuple))  # PR-MoE pyramid
    windows = layer_windows(cfg)
    if pld_theta is not None and (cfg.pipeline_stages > 1
                                  or not cfg.scan_layers or het):
        raise NotImplementedError(
            "progressive layer drop requires the scanned-layers path "
            "(scan_layers=True, pipeline_stages=1, uniform layers)")
    if windows is not None and cfg.pipeline_stages > 1:
        raise NotImplementedError(
            "pipeline parallelism with per-layer attention types "
            "(attention_layers) is not supported")
    if cfg.pipeline_stages > 1:
        from ..runtime.pipe.spmd import pipeline_apply

        assert not custom_positions, "pipeline path requires default positions"
        M = cfg.pipeline_microbatches or cfg.pipeline_stages
        assert B % M == 0, f"batch {B} not divisible by {M} pipeline microbatches"
        mb = B // M
        pos_mb = positions[:mb]
        xm = x.reshape((M, mb) + x.shape[1:])

        def stage_fn(lp_stage, xs, srng):
            (xs, _, aux), _ = jax.lax.scan(
                lambda c, lp: step(pos_mb, c, (lp, None, None)),
                (xs, srng, jnp.float32(0.0)), lp_stage)
            return xs, aux

        y, aux_sum = pipeline_apply(stage_fn, params["layers"], xm, rng)
        x = y.reshape((B,) + y.shape[2:])
        x = constrain_spec(x, act_spec)
        aux_total = aux_sum / M      # mean over microbatches, sum over layers
    elif cfg.scan_layers and not het:
        keep = None
        if pld_theta is not None:
            # progressive layer drop (runtime/progressive_layer_drop.py): a
            # dropped layer is the residual identity and contributes no aux
            from ..runtime.progressive_layer_drop import pld_keep_mask

            rng, sub = jax.random.split(rng)
            keep = pld_keep_mask(sub, cfg.num_layers, pld_theta)

        # the per-layer keep decisions and local-attention windows ride the
        # scan beside the layer stack where the call has them (None: an
        # empty pytree), so layers stay uniform under one body
        def body(carry, xs):
            (x, r, aux), _ = step(positions, carry, xs)
            return (constrain_spec(x, act_spec), r, aux), None

        if keep is not None and cfg.loop_passes > 1:
            raise NotImplementedError(
                "progressive layer drop does not take a looped model "
                "(loop_passes)")
        (x, _, aux_total), _ = _passes(
            cfg, params, lambda carry, _: jax.lax.scan(
                body, carry, (params["layers"], keep, windows)),
            (x, rng, jnp.float32(0.0)))
    else:
        carry = (x, rng, jnp.float32(0.0))
        for i in range(cfg.num_layers):
            lp = (params["layers"][i] if het else
                  jax.tree_util.tree_map(lambda a: a[i], params["layers"]))
            carry, _ = step(positions, carry, (
                lp, None, None if windows is None else windows[i]))
        x, _, aux_total = carry

    logits = _head(cfg, params, x)
    if return_aux:
        return logits, {"moe_aux_loss": aux_total}
    return logits


def pipeline_1f1b_loss_and_grads(cfg: TransformerConfig, params: Dict[str, Any],
                                 tokens: jax.Array, labels: jax.Array,
                                 rng: jax.Array, attn_impl: str = "xla",
                                 loss_scale=1.0):
    """Training fwd+bwd through the 1F1B pipeline executor.

    Returns ``(grads, losses [M])`` with the same contract as the engine's
    ``grad_of_batch`` (grads of the scaled MEAN loss; losses unscaled).
    AD cannot express the interleaved schedule (it must finish forward
    before backward starts), so the executor produces the gradients and
    this function stitches the embed/head ends back into the full tree.
    """
    if has_moe(cfg):
        raise NotImplementedError(
            "pipeline_schedule='1f1b' with MoE layers: the manual backward "
            "does not thread the aux loss; use the gpipe schedule")
    if cfg.dropout:
        raise NotImplementedError(
            "pipeline_schedule='1f1b' with dropout: the stage rng chain "
            "differs between the paired fwd/bwd stage calls under remat; "
            "use the gpipe schedule")
    B, S = tokens.shape
    M = cfg.pipeline_microbatches or cfg.pipeline_stages
    assert B % M == 0, f"batch {B} not divisible by {M} microbatches"
    mb = B // M
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :],
                                 (mb, S))
    act_spec = P(BATCH_AXES, "seq", None)
    block = _build_block(cfg, attn_impl, deterministic=True,
                         custom_positions=False)

    def stage_fn(lp_stage, xs, srng):
        (xs, _, _), _ = jax.lax.scan(
            lambda c, lp: _layer_step(block, positions, c, (lp, None, None)),
            (xs, srng, jnp.float32(0.0)), lp_stage)
        return xs

    stem_keys = [k for k in ("embed", "pos_embed", "embed_norm_scale",
                             "embed_norm_bias") if k in params]
    head_keys = [k for k in ("final_norm_scale", "final_norm_bias",
                             "lm_head", "lm_head_bias") if k in params]
    stem = {k: params[k] for k in stem_keys}
    head = {k: params[k] for k in head_keys}
    if cfg.tie_embeddings:
        head["embed"] = params["embed"]  # grads from the head sum with stem's

    def embed_fn(stem_p):
        x = _embed(cfg, stem_p, tokens, jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None], (B, S)))
        x = constrain_spec(x, act_spec)
        return x.reshape((M, mb) + x.shape[1:])

    def head_fn(hp, y, lbl):
        # scaled so the executor's vjp carries exactly the engine's gradient
        # (scale * mean-over-microbatches)
        return (cross_entropy_loss(_head(cfg, hp, y), lbl)
                * loss_scale / M)

    from ..runtime.pipe.spmd import pipeline_1f1b

    labels_micro = labels.reshape(M, mb, S)
    x_micro, embed_vjp = jax.vjp(embed_fn, stem)
    losses_scaled, dstage, dhead, dx_micro = pipeline_1f1b(
        stage_fn, head_fn, params["layers"], head, x_micro, labels_micro, rng)
    (dstem,) = embed_vjp(dx_micro.astype(x_micro.dtype))

    grads: Dict[str, Any] = {"layers": dstage}
    for k in stem_keys:
        grads[k] = dstem[k].astype(jnp.float32)
    for k in head_keys:
        grads[k] = dhead[k]
    if cfg.tie_embeddings:
        grads["embed"] = grads["embed"] + dhead["embed"]
    losses = losses_scaled * (M / loss_scale)   # unscaled per-micro losses
    return grads, losses


# ---------------------------------------------------------------------------
# KV-cached decode path (reference: the inference_context KV workspace,
# csrc/transformer/inference/includes/inference_context.h, and the
# softmax_context attention kernels, ops/transformer/inference/ds_attention.py).
# TPU redesign: the cache is a pytree of static-shape ring buffers threaded
# through lax.scan over layers, so prefill and every decode step are each ONE
# compiled XLA program — the per-token retrace/recompile of a growing-sequence
# forward disappears.  Ragged (right-padded) prompts are handled with an
# explicit validity bitmap instead of compaction: pad slots are written but
# never attended, which keeps every write a static dynamic_update_slice.
# ---------------------------------------------------------------------------

def init_cache(cfg: TransformerConfig, batch_size: int, max_len: int,
               dtype=None) -> Dict[str, Any]:
    """Allocate a static-shape KV cache for ``batch_size`` rows of up to
    ``max_len`` total tokens (prompt + generated).

    Layout: ``k``/``v`` are ``[L, B, T, Hkv, hd]`` (stacked over layers so the
    layer scan consumes/produces them as xs/ys; ``L`` is :func:`cache_depth`,
    a looped model's ``loop_passes * num_layers``); ``valid`` marks attended
    slots, ``pos`` stores each slot's position id (alibi needs relative
    positions), ``next_slot`` is the global write cursor (identical across
    rows because pad tokens occupy slots too).
    """
    dtype = dtype or cfg.dtype
    L, B, T = cache_depth(cfg), batch_size, max_len
    kv = (L, B, T, cfg.kv_heads, cfg.dims_per_head)
    return {
        "k": jnp.zeros(kv, dtype),
        "v": jnp.zeros(kv, dtype),
        "valid": jnp.zeros((B, T), jnp.bool_),
        "pos": jnp.zeros((B, T), jnp.int32),
        "next_slot": jnp.int32(0),
    }


def cache_specs(cfg: TransformerConfig) -> Dict[str, P]:
    """Shardings for the cache: batch over DP axes, KV heads over 'model'."""
    kv = P(None, BATCH_AXES, None, "model", None)
    return {"k": kv, "v": kv, "valid": P(BATCH_AXES, None),
            "pos": P(BATCH_AXES, None), "next_slot": P()}


def _check_decodable(cfg, params, what: str) -> None:
    """What neither cached forward (contiguous, paged) can serve."""
    assert cfg.pipeline_stages == 1, f"{what} requires pipeline_stages=1"
    if not cfg.causal:
        raise NotImplementedError(
            f"{what} is a causal-LM operation; encoder models "
            "(causal=False) have no autoregressive cache")
    if isinstance(params["layers"], (list, tuple)):
        raise NotImplementedError(
            f"{what} with a PR-MoE pyramid (per-layer num_experts) is not "
            "supported: the layer scan needs uniform stacks")


def _attention_cached(cfg, q, ck, cv, q_pos, q_slot, valid, kpos, window=None):
    """q:[B,S,Hq,hd] against the full cache ck/cv:[B,T,Hkv,hd].

    GQA contracts grouped query heads against the Hkv cache directly (no
    materialized repeat).  Mask: a key slot is attendable iff it holds a real
    token (``valid``) and was written at or before the query's slot (slot
    order == time order, so this is exactly causality even for ragged rows).
    """
    B, S, Hq, hd = q.shape
    T, Hkv = ck.shape[1], ck.shape[2]
    G = Hq // Hkv
    # No custom kernel: decode attention is bandwidth-bound and XLA's einsum
    # reaches the roof (tools/artifacts/decode_r5.json: 21 of 22 cells), and
    # it partitions under GSPMD for every sharded layout.
    qg = q.reshape(B, S, Hkv, G, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, ck).astype(jnp.float32)
    scores = scores * _sm_scale(cfg, hd)
    if cfg.position == "alibi":
        slopes = jnp.asarray(_alibi_slopes(Hq)).reshape(Hkv, G)
        rel = (q_pos[:, :, None] - kpos[:, None, :]).astype(jnp.float32)  # [B,S,T]
        scores = scores - (jnp.abs(rel)[:, None, None, :, :]
                           * slopes[None, :, :, None, None])
    slot_t = jnp.arange(T, dtype=jnp.int32)
    ok = valid[:, None, :] & (slot_t[None, None, :] <= q_slot[None, :, None])
    if window is not None:
        # GPT-Neo local layers: only keys within `window` positions of the
        # query (window == 0 -> global, mask all-true)
        rel_pos = q_pos[:, :, None] - kpos[:, None, :]          # [B,S,T]
        ok = ok & ((window <= 0) | (rel_pos < window))
    scores = jnp.where(ok[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, cv)
    return out.reshape(B, S, Hq, hd)


def _attend_cached(cfg, ck, cv, q_pos, q_slot, valid, kpos, next_slot,
                   window=None):
    """:func:`_block`'s ``attend`` against a contiguous cache: the layer's
    K/V land in its ``[B,T,Hkv,hd]`` buffers ``ck``/``cv`` at ``next_slot``,
    the queries read the whole buffers, and the buffers are what is kept."""
    def attend(q, k, v):
        spec, at = P(BATCH_AXES, None, "model", None), (0, next_slot, 0, 0)
        with jax.named_scope("kv_write"):
            nk = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), at)
            nv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), at)
            nk, nv = constrain_spec(nk, spec), constrain_spec(nv, spec)
        with jax.named_scope("attn"):
            return _attention_cached(cfg, q, nk, nv, q_pos, q_slot, valid,
                                     kpos, window=window), (nk, nv)
    return attend


def forward_cached(cfg: TransformerConfig, params: Dict[str, Any],
                   tokens: jax.Array, cache: Dict[str, Any],
                   positions: jax.Array, input_mask: jax.Array):
    """Run ``tokens [B,S]`` (prefill chunk or a single decode token) against
    the cache, appending their K/V at slots ``next_slot..next_slot+S-1``.

    ``positions [B,S]``: absolute position ids (pad rows repeat the previous
    position — they're masked out anyway).  ``input_mask [B,S]``: True for
    real tokens; False slots are written but never attended.

    Returns ``(logits [B,S,V], new_cache)``.  Both prefill and decode are this
    ONE function under two static shapes, so a whole generation run compiles
    exactly twice.
    """
    _check_decodable(cfg, params, "cached decode")
    if is_grouped(cfg) or is_latent(cfg) or has_state(cfg):
        _hybrid_refuse("the contiguous cache (forward_cached, generate())",
                       cfg)
    B, S = tokens.shape
    next_slot = cache["next_slot"]

    valid = jax.lax.dynamic_update_slice(cache["valid"], input_mask, (0, next_slot))
    kpos = jax.lax.dynamic_update_slice(cache["pos"], positions.astype(jnp.int32),
                                        (0, next_slot))
    q_slot = next_slot + jnp.arange(S, dtype=jnp.int32)

    x = constrain_spec(_embed(cfg, params, tokens, positions),
                       P(BATCH_AXES, None, None))
    rng = jax.random.PRNGKey(0)

    # the cache rides the scan as xs/ys beside the layer stack, and so does
    # a per-layer local window where the model has them (GPT-Neo)
    def body(x, layer):
        lp, ck, cv, w = layer
        x, _, _, kv = _block(cfg, lp, x, positions, rng, _attend_cached(
            cfg, ck, cv, positions, q_slot, valid, kpos, next_slot, w))
        return constrain_spec(x, P(BATCH_AXES, None, None)), kv

    # a looped model's cache is cut into its passes' own layers
    kv = tuple(a.reshape(cfg.loop_passes, -1, *a.shape[1:])
               if cfg.loop_passes > 1 else a
               for a in (cache["k"], cache["v"]))

    def run_pass(carry, kv):
        x, ys = jax.lax.scan(body, carry[0], (params["layers"], *kv,
                                              layer_windows(cfg)))
        return (x,), ys

    (x,), kv = _passes(cfg, params, run_pass, (x,), kv)
    ck_all, cv_all = (a.reshape(cache["k"].shape) for a in kv)
    logits = _head(cfg, params, x)
    new_cache = {"k": ck_all, "v": cv_all, "valid": valid, "pos": kpos,
                 "next_slot": next_slot + S}
    return logits, new_cache


# ---------------------------------------------------------------------------
# Block-paged KV cache (the serving path).  Reference: the inference_context
# KV workspace sizes one persistent cache and multiplexes requests through it
# (csrc/transformer/inference/includes/inference_context.h); vLLM's
# PagedAttention (SOSP '23) showed the block-table indirection that lets
# requests of different lengths share one physical pool.  TPU redesign: the
# pool is a fixed-shape [L, P, page, Hkv, hd] array, a page is 128 tokens
# (lane-aligned), and every program over it — bucketed prefill, one-token
# decode — has a static shape, so XLA compiles the whole serving loop into a
# constant program inventory.  Slot-local token index == position (the
# serving engine admits each request at position 0 of a fresh slot), so the
# causal mask IS the validity mask and no per-slot bitmap is needed.
# ---------------------------------------------------------------------------

PAGE_SIZE = 128   # tokens per KV page; 128 keeps cache tiles lane-aligned

# quantized pool storage dtypes accepted by ``init_paged_cache(kv_dtype=)``.
# int8 is the only wired width: the per-row symmetric absmax scheme below
# needs a sign bit + enough mantissa that greedy decode stays token-exact
# on realistic logit margins (docs/SERVING.md "Quantized KV pages")
KV_QUANT_DTYPES = ("int8",)

# canonical leaf order of a paged cache dict — the pool TUPLE the executor
# threads through every program (k/v always; the scale planes only when the
# pool is quantized).  Keeping the order fixed is what lets one generic
# program body serve both pool layouts with a stable donation index.
# A model with window layers (``layer_pattern``) keeps a pool per kind of
# layer: ``k``/``v`` are its full layers', ``k_window``/``v_window`` its
# window layers' rings, each leaf with its kind's KV heads and its own width.
# A latent-attention model (``kv_lora_rank``) keeps ONE leaf, ``latent``:
# a token's normed latent and its shared rotated key row, no head axis.
# A model with a mixer of ``MIXERS`` keeps, beside ``k``/``v``, that row's
# ``pool_keys``: leaves with NO page axis, a row a slot (a state and its
# convolution's tail, or the tail alone; :func:`~.mixers.paged`).
STATE_POOL_KEYS = tuple(k for m in MIXERS.values() for k in m.pool_keys)
PAGED_POOL_KEYS = ("k", "v", "k_scale", "v_scale", "k_window", "v_window",
                   "latent") + STATE_POOL_KEYS


def paged_pool_tuple(cache: Dict[str, Any]) -> tuple:
    """The cache dict's pool arrays in canonical order (len 2 = full
    precision, len 4 = int8 + per-page scale planes)."""
    return tuple(cache[k] for k in PAGED_POOL_KEYS if k in cache)


def paged_pool_cache(pools, keys=PAGED_POOL_KEYS) -> Dict[str, Any]:
    """Inverse of :func:`paged_pool_tuple`; ``keys``: the leaves the tuple
    was made of, where they are not the leading ones of the order."""
    return dict(zip(keys, pools))


def _normalize_kv_dtype(kv_dtype):
    """None (full precision) or the canonical string "int8"."""
    if kv_dtype is None:
        return None
    name = getattr(kv_dtype, "name", None) or str(kv_dtype)
    if name not in KV_QUANT_DTYPES:
        raise ValueError(
            f"kv_dtype={kv_dtype!r} is not a quantized KV storage dtype; "
            f"supported: {KV_QUANT_DTYPES} (None = full precision)")
    return name


# what a kind of layer's K/V leaves add to ``k``/``v`` in the cache's keys
# (an "ssm", "linear" or "conv" layer has none: its leaves are
# STATE_POOL_KEYS)
_KIND_SUFFIX = {"full": "", "window": "_window"}


def _head_major_leaves(cfg: TransformerConfig) -> Dict[str, bool]:
    """Which K/V leaves of ``cfg``'s cache are kept head-major, by the
    cache's own keys (:func:`pool_leaf_head_major`): of a model with two
    kinds of layer each kind's (``k_window``/``v_window`` the window layers'),
    of a one-pool model ``k``/``v`` where it keeps a state a slot and no
    other: every mover that addresses a pool by page row (COW, tiering, the
    int8 scales, heads sharded over chips) refuses such a model, so none of
    them has to know the second order."""
    if not is_hybrid(cfg):
        return {n: has_state(cfg) and pool_leaf_head_major(cfg.kv_heads, w)
                for n, w in (("k", cfg.dims_per_head),
                             ("v", cfg.v_dims_per_head))}
    return {n + _KIND_SUFFIX[kind]: pool_leaf_head_major(g.kv_heads, w)
            for kind, (g, _) in kind_layers(cfg).items()
            if kind in _KIND_SUFFIX
            for n, w in (("k", g.dims_per_head), ("v", g.v_dims_per_head))}


def _leaf_order(pool_order, name: str):
    """The stored order of the pool leaf ``name``: ``pool_order`` is one
    observed order for every leaf, or one a leaf (K and V of different
    widths may be stored differently)."""
    return (pool_order.get(name) if isinstance(pool_order, dict)
            else pool_order)


def _seen_order(head_major: Dict[str, bool], pool_order, key: str):
    """The stored order of the cache leaf ``key`` as the paged forward sees
    it, ``[N, page, Hkv, w]``: a head-major leaf through the transpose that
    moves nothing, any other as the caller observed it."""
    return ((0, 1, 3, 2, 4) if head_major.get(key)
            else _leaf_order(pool_order, key))


def kv_write_paths(cfg: TransformerConfig, cache: Dict[str, Any],
                   pool_order) -> Dict[str, str]:
    """:func:`kv_write_path` of a decode tick for every paged leaf of
    ``cache`` (``init_paged_cache``'s arrays or their shapes) stored in
    ``pool_order``, as :func:`forward_paged` hands the leaf to its layers:
    ``{leaf: "row" | "page"}``.  The same function of the same leaf and
    order that :func:`_attend_paged` asks when the tick is traced (a latent
    leaf, which :func:`_attend_latent_paged` merges without asking, has no
    head axis for the tile plan to take);
    ``tests/unit/test_kv_row_write_kernel.py`` holds this report to the
    kernels a traced tick holds, leaf by leaf."""
    head_major = _head_major_leaves(cfg)
    return {key: kv_write_path(
        jax.ShapeDtypeStruct((a.shape[0] * a.shape[1],) + tuple(a.shape[2:]),
                             a.dtype),
        _seen_order(head_major, pool_order, key))
        for key, a in cache.items() if key not in STATE_POOL_KEYS}


def kv_read_paths(cfg: TransformerConfig, cache: Dict[str, Any],
                  pool_order, slots: int = 1, dtype=None) -> Dict[str, str]:
    """:func:`kv_read_path` of a decode tick for every paged leaf of
    ``cache`` stored in ``pool_order``, as :func:`forward_paged` hands the
    leaves to its layers: ``{leaf: "pages" | "gather"}``, the leaves of one
    kind of layer together (K and V are read by one call, with the scale
    planes of a quantised pool), for a tick of ``slots`` slots whose queries
    are of ``dtype`` (``None``: the model's own).  The same function of the
    same leaves and orders that :func:`_attention_paged` asks when the tick
    is traced (a latent leaf, alone in its cache: :func:`_attention_latent_paged`);
    a window layer's read carries its window's lower bound and its sink and
    keeps the gather."""
    if is_latent(cfg):
        a = cache["latent"]
        return {"latent": kv_read_path(
            {"latent": jax.ShapeDtypeStruct(
                (a.shape[0] * a.shape[1],) + tuple(a.shape[2:]), a.dtype)},
            pool_order, jax.ShapeDtypeStruct(
                (slots, cfg.num_heads), jnp.dtype(dtype or cfg.dtype)),
            values=cfg.kv_lora_rank)}
    head_major = _head_major_leaves(cfg)
    suffix = _KIND_SUFFIX["window"]
    kinds: Dict[bool, Dict[str, str]] = {}      # window? -> {"k": its key}
    for key in cache:
        if key not in STATE_POOL_KEYS:
            window = key.endswith(suffix)
            kinds.setdefault(window, {})[
                key[:-len(suffix)] if window else key] = key

    def seen(key):              # [N, page, Hkv, w], whichever way it is kept
        a = cache[key]
        rest = tuple(a.shape[2:])
        if head_major.get(key):
            rest = (rest[1], rest[0]) + rest[2:]
        return jax.ShapeDtypeStruct((a.shape[0] * a.shape[1],) + rest,
                                    a.dtype)

    paths = {}
    groups = ({kind: g for kind, (g, _) in kind_layers(cfg).items()}
              if is_hybrid(cfg) else {})
    for window, keys in kinds.items():
        heads = groups.get("window" if window else "full", cfg).num_heads
        path = kv_read_path(
            {n: seen(key) for n, key in keys.items()},
            {n: _seen_order(head_major, pool_order, key)
             for n, key in keys.items()},
            jax.ShapeDtypeStruct((slots, heads),
                                 jnp.dtype(dtype or cfg.dtype)),
            plain=not window and cfg.position != "alibi")
        paths.update({key: path for key in keys.values()})
    return paths


def init_paged_cache(cfg: TransformerConfig, num_pages: int,
                     page_size: int = PAGE_SIZE, dtype=None,
                     kv_dtype=None, window_pages: Optional[int] = None,
                     slots: int = 1) -> Dict[str, Any]:
    """Allocate the physical page pool: ``k``/``v`` are
    ``[L, num_pages, page_size, Hkv, hd]``, ``L`` the cache's depth
    (:func:`cache_depth`: a looped model's ``loop_passes * num_layers``, pass
    ``r``'s layer ``l`` at ``r * num_layers + l``; a page id addresses every
    pass's and every layer's rows of its 128 tokens).

    A model with state-space layers adds ``ssm_state [L, slots, heads,
    head_dim, state]`` (float32) and ``ssm_conv [L, slots, taps - 1,
    channels]``: one row a slot, ``slots`` of them, and no pages at all (a
    state is not written position by position: it is reset when a block
    starts at position 0 and advanced whole by every token after).

    Physical page 0 is RESERVED as the trash page: a page write none of
    whose rows is real (pad tokens, an inactive slot) is redirected there
    (a masked write must still be a static-shape scatter), and it is also
    the page-table value for unallocated entries — its slot-indices always
    sit beyond every real query position, so the causal mask keeps it out
    of attention.  The serving engine hands out pages 1..num_pages-1.

    Sharing contract (cross-request KV reuse): pages are **immutable once
    full**.  A slot only ever writes at its own current position, which
    advances monotonically, so a page whose whole ``page_size`` token span
    lies behind the owner's position is never written again — its contents
    are a pure function of the token prefix it holds (K/V at position ``t``
    depends only on tokens ``0..t``), making it safe to map read-only into
    any other slot whose prompt starts with the same tokens.  Sharing is
    pure page-table indirection: no program here changes shape for it.  The
    one mutable case — a *partial* boundary page the owner is still
    appending to — is shared by value instead: :func:`cow_copy_pool`
    snapshots it into the reader's own page (copy-on-write).

    ``kv_dtype="int8"`` allocates the pools in int8 plus per-page scale
    planes ``k_scale``/``v_scale`` of shape ``[L, num_pages, page_size]``
    (float32): each page carries one symmetric-absmax scale per token row
    per layer, written by the same scatter that stores the row and applied
    inside the gather (docs/SERVING.md "Quantized KV pages").  Every
    sharing/COW/tiering contract above is dtype-blind — a page is still a
    page; only its at-rest representation narrows.
    """
    dtype = dtype or cfg.dtype
    if ((is_hybrid(cfg) or is_latent(cfg) or has_state(cfg))
            and _normalize_kv_dtype(kv_dtype) is not None):
        _hybrid_refuse("the int8 pool", cfg)
    if is_hybrid(cfg) or has_state(cfg):
        # a pool per kind of layer: the full layers' ``k``/``v`` over
        # ``num_pages`` pages, the window layers' ``k_window``/``v_window``
        # over ``window_pages`` (each slot a ring of
        # :func:`window_ring_pages`; page 0 the trash page of its own pool);
        # a mixer beside attention in every layer (no pattern): both kinds'
        # leaves over every layer
        kinds = (kind_layers(cfg) if is_hybrid(cfg) else
                 {kind: (cfg, cfg.num_layers)
                  for kind in ("full", mixers_of(cfg)[0].kind)})
        cache = {}
        for kind, suffix, pages in (
                ("full", "", num_pages),
                ("window", "_window",
                 num_pages if window_pages is None else window_pages)):
            if kind in kinds:
                g, layers = kinds[kind]
                for n, w in (("k", g.dims_per_head),
                             ("v", g.v_dims_per_head)):
                    rows = ((g.kv_heads, page_size)
                            if pool_leaf_head_major(g.kv_heads, w)
                            else (page_size, g.kv_heads))
                    cache[n + suffix] = jnp.zeros(
                        (layers, pages) + rows + (w,), dtype)
        for kind in kinds:
            if kind in MIXERS:
                # the state leaves cover the kind's layers and no other
                cache.update(MIXERS[kind].leaves(*kinds[kind], slots, dtype))
        return cache
    if is_latent(cfg):
        return {"latent": jnp.zeros(
            (cfg.num_layers, num_pages, page_size,
             cfg.kv_lora_rank + cfg.rotary_dim), dtype)}
    lead = (cache_depth(cfg), num_pages, page_size, cfg.kv_heads)
    kv = lead + (cfg.dims_per_head,)
    if _normalize_kv_dtype(kv_dtype) is None:
        return {"k": jnp.zeros(kv, dtype),
                "v": jnp.zeros(lead + (cfg.v_dims_per_head,), dtype)}
    sc = lead[:3]
    return {"k": jnp.zeros(kv, jnp.int8), "v": jnp.zeros(kv, jnp.int8),
            "k_scale": jnp.zeros(sc, jnp.float32),
            "v_scale": jnp.zeros(sc, jnp.float32)}


def paged_cache_specs(cfg: TransformerConfig, kv_dtype=None) -> Dict[str, P]:
    """Shardings for the page pool: KV heads over 'model'; pages replicated
    (any slot on any data shard may own any page).  A quantized pool's
    scale planes ``[L, P, page]`` have no head dim, so they ride replicated
    alongside their (page-replicated) int8 payload."""
    kv = P(None, None, None, "model", None)
    if is_latent(cfg):      # no head axis: every chip holds whole rows
        return {"latent": P(None, None, None, None)}
    if has_state(cfg):  # whole on one chip: sharded serving refuses it
        return {k: P() for k in ("k", "v") + mixers_of(cfg)[0].pool_keys}
    if is_hybrid(cfg):
        return {"k": kv, "v": kv, "k_window": kv, "v_window": kv}
    if _normalize_kv_dtype(kv_dtype) is None:
        return {"k": kv, "v": kv}
    sc = P(None, None, None)
    return {"k": kv, "v": kv, "k_scale": sc, "v_scale": sc}


def cow_copy_pool(pools, src: jax.Array, dst: jax.Array):
    """Copy-on-write primitive: snapshot physical page ``src`` onto ``dst``
    across every layer of every array of the canonical pool tuple (k/v
    ``[L, P, page, Hkv, hd]``, plus the ``[L, P, page]`` scale planes of a
    quantized pool) — raw bytes, so an int8 page's snapshot never
    round-trips through float.

    Used when a new request's prompt extends partway into a donor's
    *partial* boundary page: the donor keeps appending to its own page, so
    the sharer takes a value snapshot into a private page and overwrites
    every row past the matched prefix itself before its query positions can
    reach them (slot-index == position, so a row is causally invisible
    until the sharer has written it).  ``src``/``dst`` are traced int32
    scalars — ONE fixed program shape regardless of which pages move.
    ``dst == src`` (the trash page 0 onto itself pre-warms the compile) is
    a harmless self-copy."""
    return tuple(a.at[:, dst].set(a[:, src]) for a in pools)


def kv_quantize_rows(x: jax.Array):
    """Symmetric absmax int8 quantization of one write slice: ``x``
    ``[N, Hkv, hd]`` -> (int8 rows, float32 per-row scales ``[N]``).

    One scale per token row (the page slice being written), computed over
    the row's whole ``Hkv*hd`` K (or V) vector: a row is written exactly
    once at its position and never rescaled, so incremental page fills
    need no running-max bookkeeping and a full page's bytes are a pure
    function of the tokens that produced it — the property prefix sharing,
    COW and demote/promote round trips rely on.  An all-zero row (padding,
    trash-page writes) stores scale 1 so dequantization is exact zero.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(1, 2))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale[:, None, None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def kv_dequantize(q: jax.Array, scale: jax.Array, dtype):
    """Invert :func:`kv_quantize_rows` on a gathered ``[..., Hkv, hd]``
    block with its ``[...]`` scale rows (or, for a block whose row axis
    does not lead, scales already shaped to broadcast against it);
    dequantizes in float32 before casting to the compute dtype so the
    scale multiply never loses the int8 mantissa."""
    if scale.ndim < q.ndim:
        scale = scale[..., None, None]
    return (q.astype(jnp.float32) * scale).astype(dtype)


# Pairs a step of the paged read covers, for each slot of the program's
# shape: a call of B slots reads B * PAGED_READ_GRANULE (slot, page) pairs of
# its live pages a step, whichever slots they belong to (PERF.md, PR 29).
PAGED_READ_GRANULE = 2


def paged_read_pairs(slots: int, max_pages: int) -> int:
    """(slot, page) pairs one step of the paged read gathers in a program
    of ``slots`` slots whose page-table rows hold ``max_pages`` pages:
    static, a function of the program's shape alone."""
    return slots * min(PAGED_READ_GRANULE, max_pages)


def paged_read_rows(lengths, page_size: int, max_pages: int,
                    slots: int, whole_steps: bool = True) -> int:
    """K/V rows a layer of a paged forward of ``slots`` slots reads when its
    live slots hold ``lengths`` rows each (the rows being written counted
    in; a slot with no real token is left out or 0): the slots' own whole
    pages, at most the page-table row each, summed and, where the read
    gathers (``whole_steps``), rounded up to whole steps of
    :func:`paged_read_pairs` pairs; the kernel that fetches a page at a time
    (:func:`kv_read_path`'s ``"pages"``) stops at the last live one.  The
    host's copy of what :func:`_paged_read_plan` computes from ``start``,
    ``seq_mask`` and the table's shape on the device (the serving engine's
    ``gathered_rows`` span attr)."""
    pairs = paged_read_pairs(slots, max_pages) if whole_steps else 1
    live = int(np.minimum(-(-np.asarray(lengths, np.int64) // page_size),
                          max_pages).sum())
    return -(-live // pairs) * pairs * page_size


def _paged_read_plan(page_table, start, seq_mask, ps: int):
    """What a paged forward reads, as one flat slot-major list of the
    call's live (slot, logical page) pairs, cut into steps of N =
    :func:`paged_read_pairs` pairs: ``(steps, slot [T, N], pages [T, N],
    limit [T, N, S])``, one plan for all layers.

    Slot b holds ``ceil((its longest real position + 1) / ps)`` live pages,
    at most its page-table row and **none if it has no real token**; pair i
    of the list is page ``j`` of the slot whose pages span i (offsets by
    cumulative sum).  ``pages`` is its physical page and ``limit[i, s]`` the
    last row of that page query ``s`` of its slot may see (``q_pos - j*ps``;
    rows ``r <= limit`` pass, so a page wholly behind the query passes whole
    and one ahead of it not at all).  Pairs past the total have ``slot ==
    B`` (no slot's), the trash page and ``limit == -1``: they contribute
    exactly nothing.  ``steps`` (traced) is the total in whole steps, the
    read's trip count; ``T`` is the whole table's."""
    B, maxp = page_table.shape
    S = seq_mask.shape[1]
    pairs = paged_read_pairs(B, maxp)
    positions = start[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    rows = jnp.max(jnp.where(seq_mask, positions, -1), axis=1) + 1
    n_pages = jnp.minimum((rows + ps - 1) // ps, maxp)            # [B]
    ends = jnp.cumsum(n_pages)
    i = jnp.arange(-(-B * maxp // pairs) * pairs, dtype=jnp.int32)
    # the slot whose pages span i: the offsets at or under it, counted
    slot = jnp.sum(i[:, None] >= ends[None, :], axis=1, dtype=jnp.int32)
    valid = slot < B
    at = jnp.minimum(slot, B - 1)
    j = i - (ends - n_pages)[at]
    pages = jnp.where(valid, page_table[at, jnp.clip(j, 0, maxp - 1)], 0)
    limit = jnp.where(valid[:, None], positions[at] - j[:, None] * ps, -1)
    return ((ends[-1] + pairs - 1) // pairs, slot.reshape(-1, pairs),
            pages.reshape(-1, pairs), limit.reshape(-1, pairs, S))


def paged_pool_order(leaf) -> Optional[Tuple[int, ...]]:
    """The order, major to minor, in which the device stores the five axes
    of a K/V pool leaf (an array, not a tracer, or the ``Format`` a compiled
    program reports for one), for :func:`forward_paged`'s ``pool_order``;
    ``None`` where that is the axes' own order (row-major) or the backend
    does not say."""
    layout = getattr(leaf, "format", leaf).layout
    order = None if layout is None else tuple(layout.major_to_minor)
    return None if order == tuple(range(len(order or ()))) else order


def _pool_views(pools, pool_order):
    """``(views, axes)``: each K/V leaf ``[N, page, Hkv, hd]`` with its
    trailing axes in the order the device stores them (``pool_order``, of
    the unstacked leaf: one order, or ``{leaf: order}``), and each leaf's
    order as einsum letters (``t`` page row, ``k`` head, ``d`` head dim).  A computation nested two deep (the read's
    loop inside the layer scan) takes its operands row-major in their
    logical shape, so the pool enters it as the view whose row-major order
    is the bytes as they lie: a transpose that moves nothing.  Handed the
    leaf in any other order the compiler re-lays the whole pool out in
    front of every read (a 64-wide head puts the page rows minor-most on
    the TPU, PERF.md PR 25 and PR 27).  The scale planes of a quantized
    pool ``[N, page]`` go as they are."""
    views, axes = {}, {}
    for n, a in pools.items():
        perm, letters = _view_axes(_leaf_order(pool_order, n))
        views[n] = jnp.transpose(a, perm) if a.ndim == 4 else a
        if a.ndim == 4:
            axes[n] = letters
    return views, axes


def _view_axes(order) -> Tuple[Tuple[int, ...], str]:
    """``(perm, letters)``: the axes of a stacked K/V leaf ``[N, page, Hkv,
    hd]`` in the order the device stores them, given the observed ``order``
    of the unstacked leaf (:func:`paged_pool_order`; ``None``: row-major),
    as :func:`_pool_views`' transpose and as its einsum letters."""
    perm = (0, 1, 2, 3)
    if order is not None and tuple(order[:2]) == (0, 1):
        perm = (0,) + tuple(i - 1 for i in order[2:])
    return perm, "".join(" tkd"[i] for i in perm[1:])


def _attention_paged(cfg, q, pools, read, pool_order=None, sink=None):
    """q:[B,S,Hq,hd] against the call's live pages, ``read`` =
    :func:`_paged_read_plan`'s flat list of (slot, page) pairs with the
    pages moved to this layer's: a step of the loop gathers the whole pages
    of N pairs, whichever slots they belong to, and the loop runs ``steps``
    (traced) times.  Every slot is read to its own length, and a slot with
    no real token not at all.

    Slot-local index == position, so the mask is purely causal
    (``t <= q_pos``, as ``r <= limit`` within a page): every slot-index at
    or before the query holds a real token of this request, everything
    after is masked.  A row past a slot's longest real position fails the
    mask for every real query of the slot, so its probability is exactly 0
    and leaving it unread is the same mathematics: the softmax is computed
    blockwise (running max, sum and accumulator in float32 **per slot**, as
    a flash kernel keeps them per query block), over exactly the rows that
    can pass the mask.  A step's pairs are folded into their slots' state
    by a ``[B, N]`` one-hot: the max by a masked reduction, the
    probabilities by placing each pair's block in its slot's row of the
    product with V (scores are laid out ``[Hkv, G, S, N, page]``, pair and
    row last as the contraction wants them; with the pair leading the
    compiler recomputes a prefill's exponentials for the product).  A
    slot's first page leads its pairs and its row 0 passes every query's
    mask, so from a slot's first pair on its ``m`` is a real score and a
    masked row's weight ``exp(-1e30 - m)`` is exactly 0; a pair past the
    total is in no slot's row.  Masked queries (padding, idle slots) may
    sit past what is read; their output is garbage either way (0 for a
    slot that is not read at all).

    GQA contracts grouped heads against the Hkv pages directly, no kernel.
    The products keep the pages' own axes as the device stores them
    (:func:`_pool_views`) and only the scores are flattened: merging or
    moving axes of K/V would re-lay the gathered block out, which the
    compiler then does to the whole pool (PERF.md, PR 25).  The pool is
    only ever gathered from, whole pages at a time, so it stays where the
    layer scan carries it.

    A window layer's ``read`` (:func:`_ring_read_plan`) carries a fifth
    member, ``low``: the first row of each pair's page its queries may see
    (rows ``low <= r <= limit`` pass: the window).  ``sink [Hq]`` is a
    learned logit a head that joins each row's softmax at the end of the
    walk, one more term of the sum that adds nothing to the accumulator.

    Where :func:`kv_read_path` says ``"pages"`` (one token a slot over
    bfloat16 leaves the kernel's tile plan takes, on a TPU) the same list,
    mask and softmax run in ``ops/pallas/paged_read.py``, which fetches each
    live page once from the pool and copies nothing
    (:func:`_attention_pages`); the loop below is every other read.
    """
    steps, slot, pages, limit = read[:4]
    low = read[4] if len(read) > 4 else None
    B, S, Hq, hd = q.shape
    ps, Hkv = pools["k"].shape[1], pools["k"].shape[2]
    vd = pools["v"].shape[3]
    N = slot.shape[1]
    G = Hq // Hkv
    qg = q.reshape(B, S, Hkv, G, hd)
    views, axes = _pool_views(pools, pool_order)
    slopes = (jnp.asarray(_alibi_slopes(Hq)).reshape(Hkv, G)
              if cfg.position == "alibi" else None)
    if kv_read_path(pools, pool_order, jax.ShapeDtypeStruct((B, Hq), q.dtype),
                    S, plain=(slopes is None and low is None
                              and sink is None)) == "pages":
        return _attention_pages(cfg, q, views, axes, read)
    r = jnp.arange(ps, dtype=jnp.int32)

    def step(i, carry):
        m, l, acc = carry
        at, pg, lim = slot[i], pages[i], limit[i]        # [N] [N] [N,S]
        if B > 1:
            # mine[b, n]: pair n is a page of slot b
            mine = (at[None, :] == jnp.arange(B, dtype=jnp.int32)[:, None])
            mine = mine[:, None, None, None, :]          # [B,1,1,1,N]

            def in_slot(x, fill):    # [Hkv,G,S,N,..] -> [B,Hkv,G,S,N,..]
                own = mine.reshape(mine.shape + (1,) * (x.ndim - 4))
                return jnp.where(own, x[None], fill)

            def of_slot(x):          # [B,Hkv,G,S] -> [Hkv,G,S,N]
                return jnp.where(mine, x[..., None], 0.0).sum(0)
        else:
            # one slot (every prefill program): every pair is its (one past
            # the total is masked whole and weighs 0) and the fold is the
            # plain reduction.  Written out, because through the one-hot the
            # compiler computes the 2048-token bucket's exponentials twice
            # (prefill +4.8%, PERF.md PR 29)
            def in_slot(x, fill):
                return x[None]

            def of_slot(x):
                return jnp.broadcast_to(x[0, ..., None], x.shape[1:] + (N,))
        with jax.named_scope("kv_gather"):
            # N whole pages: [N, *axes]
            ck, cv = views["k"][pg], views["v"][pg]
            if "k_scale" in views:
                # dequantize inside the gather: the narrow representation
                # is what crosses HBM; attention sees compute-dtype values
                along = tuple(-1 if c == "t" else 1 for c in axes["k"])
                ck, cv = (
                    kv_dequantize(c, views[n][pg].reshape(N, *along),
                                  cfg.dtype)
                    for c, n in ((ck, "k_scale"), (cv, "v_scale")))
        # each pair's own slot's queries; one slot's are every pair's
        qn = (qg[jnp.minimum(at, B - 1)] if B > 1
              else jnp.broadcast_to(qg, (N,) + qg.shape[1:]))
        scores = jnp.einsum(f"nskgd,n{axes['k']}->kgsnt", qn, ck)
        scores = scores.astype(jnp.float32) * _sm_scale(cfg, hd)
        lim_sn = lim.T                                          # [S,N]
        if slopes is not None:
            rel = (lim_sn[:, :, None] - r[None, None, :]).astype(jnp.float32)
            scores = scores - (jnp.abs(rel)[None, None]
                               * slopes[:, :, None, None, None])
        ok = r[None, None, :] <= lim_sn[:, :, None]              # [S,N,ps]
        if low is not None:
            ok = ok & (r[None, None, :] >= low[i].T[:, :, None])
        scores = jnp.where(ok[None, None], scores, -1e30)
        m_new = jnp.maximum(m, in_slot(scores.max(-1), -1e30).max(-1))
        p = jnp.exp(scores - of_slot(m_new)[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + in_slot(p.sum(-1), 0.0).sum(-1)
        pv = jnp.einsum(f"bkgsnt,n{axes['v']}->bskgd",
                        in_slot(p.astype(q.dtype), 0), cv)
        acc = (acc * jnp.moveaxis(alpha, 3, 1)[..., None]
               + pv.astype(jnp.float32))
        return m_new, l, acc

    m0 = jnp.full((B, Hkv, G, S), -1e30, jnp.float32)
    m, l, acc = jax.lax.fori_loop(
        0, steps, step,
        (m0, jnp.zeros_like(m0), jnp.zeros((B, S, Hkv, G, vd), jnp.float32)))
    if sink is not None:
        # the sink's term joins the sum under the larger of the two maxima
        b = sink.astype(jnp.float32).reshape(1, Hkv, G, 1)
        m_new = jnp.maximum(m, b)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.exp(b - m_new)
        acc = acc * jnp.moveaxis(alpha, 3, 1)[..., None]
    # a slot that was not read has l == 0: its output is 0, not NaN
    out = acc / jnp.moveaxis(jnp.where(l > 0, l, 1.0), 3, 1)[..., None]
    return out.astype(q.dtype).reshape(B, S, Hq, vd)


# How the v5e stores the unstacked latent leaf ``[L, P, page, r + rd]``: the
# page rows minor-most (``r + rd`` = 576 is no whole number of 128 lanes)
LATENT_PAGE_ROWS_MINOR = (0, 1, 3, 2)


def kv_read_path(pools, pool_order, query, tokens: int = 1,
                 plain: bool = True, values: Optional[int] = None) -> str:
    """How a block of ``tokens`` a slot reads the live pages of the pool
    leaves ``pools`` (``{"k", "v"[, "k_scale", "v_scale"]}``: arrays ``[N,
    page, Hkv, hd]`` or their shapes and dtypes) that the device stores in
    ``pool_order`` (:func:`paged_pool_order`'s, one order or one a leaf):
    ``"pages"`` (``ops/pallas/paged_read.py``: each live page fetched once
    from where it lies into on-chip memory and attended there) for one token
    a slot with its queries (``query``: ``[B, Hq]`` and their dtype, an array
    or its shape and dtype) and the K and V leaves in bfloat16, every slot's
    softmax state small enough to stay on chip for the call
    (``resident_bytes``), no scale planes, heads of whole 128 lanes, both
    leaves stored row-major or both head-major with the page block's
    second-minor axis in whole tiles (``page_block``), a page block of at least
    ``MIN_BLOCK_BYTES`` (the smallest the chip has read: a model of fewer KV
    heads was not measured), a ``plain`` softmax (no ALiBi, no window's
    lower bound, no sink), in a program that may hold a Pallas kernel
    (``mixers.common._pallas_interpret``: a TPU, one device); ``"gather"``
    (:func:`_attention_paged`'s loop: a step's pages copied out of the pool,
    then attended) for every other read: a prompt or a verify block, a
    quantised pool, a leaf stored page-rows-minor (a 64-wide or 192-wide head
    on the v5e), a sharded mesh, any other backend.  One plan
    (:func:`_paged_read_plan`), one mask and one blockwise softmax either
    way.  Read at trace time from what the code can observe; the serving
    executor reports it (``mesh_info()["kv_read"]``).

    The one leaf of a latent cache (``pools`` = ``{"latent"}``: ``[N, page,
    r + rd]``, the first ``values`` = ``r`` columns of a row its values)
    answers by the same observables: ``"pages"``
    (``paged_read.latent_read``: one block a pair, fetched once for both
    products) for one token a slot, bfloat16 queries and leaf, the leaf
    OBSERVED stored page-rows-minor (:data:`LATENT_PAGE_ROWS_MINOR`: the
    kernel's view ``[N, r + rd, page]`` then moves nothing, where over a
    row-major leaf the compiler would copy the whole pool in front of every
    read), a block the tile plan takes (``latent_block``) of at least
    ``MIN_BLOCK_BYTES``, the state within ``RESIDENT_BYTES``, where a kernel
    may run; ``"gather"`` (:func:`_attention_latent_paged`'s loop) for a
    prompt or a verify block, any other dtype or stored order, a sharded
    mesh, every other backend."""
    from ..ops.pallas.paged_read import (MIN_BLOCK_BYTES, RESIDENT_BYTES,
                                         latent_block, page_block,
                                         resident_bytes)

    if set(pools) == {"latent"}:
        leaf = pools["latent"]
        n, page, width = leaf.shape
        block = (tokens == 1 and plain and values is not None
                 and _common._pallas_interpret() is not None
                 and leaf.dtype == query.dtype
                 and _leaf_order(pool_order, "latent")
                 == LATENT_PAGE_ROWS_MINOR
                 and resident_bytes(*query.shape, width, values)
                 <= RESIDENT_BYTES
                 and latent_block((n, width, page), values, leaf.dtype))
        return "pages" if block and block >= MIN_BLOCK_BYTES else "gather"
    if (tokens != 1 or not plain or set(pools) != {"k", "v"}
            or _common._pallas_interpret() is None
            or not pools["k"].dtype == pools["v"].dtype == query.dtype
            or resident_bytes(*query.shape, pools["k"].shape[3],
                              pools["v"].shape[3]) > RESIDENT_BYTES):
        return "gather"
    shapes, letters = {}, set()
    for n, a in pools.items():
        perm, axes = _view_axes(_leaf_order(pool_order, n))
        shapes[n] = tuple(a.shape[i] for i in perm)
        letters.add(axes)
    block = len(letters) == 1 and page_block(
        shapes["k"], shapes["v"], pools["k"].dtype, letters.pop())
    return "pages" if block and block >= MIN_BLOCK_BYTES else "gather"


def _attention_pages(cfg, q, views, axes, read):
    """:func:`_attention_paged` where :func:`kv_read_path` says ``"pages"``:
    the same list of live pairs (flat: the kernel's grid is as long as the
    live ones, so the list's rounding to whole steps is not read), the same
    mask and the same blockwise softmax in float32, each page fetched once
    from the pool where it lies (``ops/pallas/paged_read.py``)."""
    from ..ops.pallas.paged_read import paged_read

    _, slot, pages, limit = read
    B, hd = q.shape[0], q.shape[3]
    slot = slot.reshape(-1)
    with jax.named_scope("kv_read"):
        acc, l = paged_read(
            q[:, 0], views["k"], views["v"],
            jnp.sum(slot < B, dtype=jnp.int32), slot, pages.reshape(-1),
            limit.reshape(-1), axes=axes["k"], scale=_sm_scale(cfg, hd),
            interpret=_common._pallas_interpret())
    # a slot that was not read has l == 0: its output is 0, not NaN
    out = acc / jnp.where(l > 0, l, 1.0)[..., None]
    return out.astype(q.dtype)[:, None]


def _adapter_delta(h, ab, scale):
    """Per-slot batched LoRA delta: ``((h @ A_b) @ B_b) * scale_b``.

    ``h`` [B,S,d_in] activations; ``ab["A"]`` [B,d_in,R] / ``ab["B"]``
    [B,R,d_out] this layer's per-slot factor slices (rank-padded to the
    traced R — zero-padded columns contribute exactly zero, so a slot
    with no adapter, or a lower-rank adapter, is mathematically exact);
    ``scale`` [B] per-slot alpha/true_rank.  Accumulates in float32 like
    :func:`apply_lora` so low-precision compute dtypes do not lose the
    low-rank product before the scale multiply."""
    hf = h.astype(jnp.float32)
    t = jnp.einsum("bsd,bdr->bsr", hf, ab["A"].astype(jnp.float32))
    d = jnp.einsum("bsr,bro->bso", t, ab["B"].astype(jnp.float32))
    return d * scale.astype(jnp.float32)[:, None, None]


def _adapter_proj(adapters, ad_scale):
    """:func:`_block`'s ``proj`` for multi-tenant adapter serving
    (docs/SERVING.md): ``adapters`` are one layer's per-slot LoRA factor
    slices ``{target: {"A": [B,d_in,R], "B": [B,R,d_out]}}`` and ``ad_scale``
    the ``[B]`` per-slot scales; each projection named in the dict gains its
    slot's batched delta.  All-zero factors reproduce the base projection
    exactly, so one traced program serves any tenant mix.  None without
    adapters."""
    if adapters is None:
        return None

    def proj(y, name, hin):
        if name in adapters:
            y = y + _adapter_delta(hin, adapters[name],
                                   ad_scale).astype(y.dtype)
        return y
    return proj


def kv_write_path(leaf, order, tokens: int = 1) -> str:
    """How a block of ``tokens`` a slot lays its rows into the pool leaf
    ``leaf [N, page, ...]`` (an array or its shape and dtype) that the device
    stores in ``order`` (:func:`paged_pool_order`; ``None``: row-major):
    ``"row"`` (``ops/pallas/kv_row_write.py``: one row a slot stored where it
    lies) for one token a slot into a row-major K or V leaf whose shape the
    kernel's tile plan takes, in a program that may hold a Pallas kernel
    (``mixers.common._pallas_interpret``); ``"page"`` (:func:`_merge_pages`: each
    slot's pages gathered, merged and scattered back whole) for every other
    write: a prompt or a verify block, the scale planes of a quantized pool
    and its int8 rows, the latent leaf, a leaf stored page-rows-minor or kept
    head-major (the kernel would have the whole pool copied into row-major
    order and back), a mesh of more than one device, any other backend.  One
    plan says where a row goes either way (:func:`_paged_write_plan`,
    :func:`_ring_write_plan`).  Read at trace time from what the code can
    observe; the serving executor reports it (``mesh_info()["kv_write"]``)."""
    from ..ops.pallas.kv_row_write import row_block

    if (tokens == 1 and order is None
            and _common._pallas_interpret() is not None
            and row_block(leaf.shape, leaf.dtype) is not None):
        return "row"
    return "page"


def _merge_pages(pool, rows, write):
    """``rows [B,S,...]`` of a block of tokens laid into ``pool [N, page,
    ...]`` by the block's page-merge plan ``write = (src, keep, pages)``
    (:func:`_paged_write_plan`): the rows of their pages ``[B,n_pg,page,
    ...]`` over what those pages hold, gathered and scattered back whole."""
    src, keep, pages = write[:3]
    w = (1,) * (rows.ndim - 2)
    new = jnp.take_along_axis(rows, src.reshape(*src.shape, *w),
                              axis=1).astype(pool.dtype)
    new = jnp.where(keep.reshape(*keep.shape, *w),
                    new.reshape(*keep.shape, *rows.shape[2:]),
                    pool[pages])
    return pool.at[pages.reshape(-1)].set(new.reshape(-1, *new.shape[2:]))


def _attention_latent_paged(cfg, q, wkv_b, pool, read, pool_order=None):
    """The absorbed path: ``q [B,S,Hq,hd]`` against the call's live pages of
    the latent leaf ``pool [N, page, r + rd]``, ``read`` =
    :func:`_paged_read_plan`'s list of (slot, page) pairs moved to this
    layer's pages.  The same numbers as attention over the expanded keys
    and values, in another order: with ``(W_UK, W_UV)`` =
    :func:`_latent_up`, a query's unrotated part goes through ``W_UK`` once
    (``r`` wide) and then meets each cached row ``[c ; k_pe]`` as it lies,
    one ``r + rd``-wide key and one ``r``-wide value (``c`` itself) for
    every head; ``W_UV`` is applied to the ``r``-wide sum at the end.
    Nothing ``Hq`` times a row's width is ever made of the cache.

    A step gathers the whole pages of N pairs and computes each PAIR's own
    partial softmax (its maximum, sum and ``r``-wide weighted sum: two
    batched products over the pair axis, ``Hq`` rows against a page); the
    pairs are then folded into their slots' running state by a ``[B, N]``
    one-hot product, each weighed by ``exp(its maximum - the slot's)``.  A
    pair past the total is in no slot's row; a page ahead of a query has
    every row masked, its maximum ``-1e30`` and its weight exactly 0 from
    the slot's first page on (whose row 0 passes every query's mask).

    The products take the gathered pages as ``[N, r + rd, page]``, the page
    rows minor-most, whatever order the caller observed: that is how the
    v5e stores this leaf (``major_to_minor`` (0, 1, 3, 2): ``r + rd`` = 576
    is no whole number of 128 lanes), so there the transpose moves nothing
    (:func:`_pool_views`' rule, for a leaf with no head axis) and the pool
    stays where the layer scan carries it; on a backend that stores it
    row-major it is the same arithmetic, and the CPU tests run the code
    the chip times.

    Where :func:`kv_read_path` says ``"pages"`` (one token a slot over a
    bfloat16 leaf that ``pool_order``, the caller's observation, says is
    stored that way, on a TPU) the same list, mask and softmax run in
    ``ops/pallas/paged_read.py``'s ``latent_read`` over the same view: each
    live page fetched once for both products, a pair folded straight into
    its slot's float32 state on chip, no copy of a step's pages, no partial
    softmax a pair and no one-hot; the loop below is every other read."""
    steps, slot, pages, limit = read
    B, S, Hq, hd = q.shape
    r, rd = cfg.kv_lora_rank, cfg.rotary_dim
    ps = pool.shape[1]
    N = slot.shape[1]
    w_uk, w_uv = _latent_up(cfg, wkv_b)
    with jax.named_scope("absorb_q"):
        qa = jnp.concatenate(
            [jnp.einsum("bshn,rhn->bshr", q[..., :hd - rd], w_uk),
             q[..., hd - rd:]], axis=-1)                    # [B,S,Hq,r+rd]
    view = jnp.transpose(pool, (0, 2, 1))             # [N, r+rd, page]
    scale = _sm_scale(cfg, hd)

    def absorb_out(acc, l):
        # a slot that was not read has l == 0: its output is 0, not NaN
        u = (acc / jnp.where(l > 0, l, 1.0)[..., None]).astype(q.dtype)
        with jax.named_scope("absorb_out"):
            return jnp.einsum("bshr,rhv->bshv", u, w_uv)

    if kv_read_path({"latent": pool}, pool_order,
                    jax.ShapeDtypeStruct((B, Hq), qa.dtype), S,
                    values=r) == "pages":
        from ..ops.pallas.paged_read import latent_read

        flat = slot.reshape(-1)
        with jax.named_scope("kv_read"):
            acc, l = latent_read(
                qa[:, 0], view, jnp.sum(flat < B, dtype=jnp.int32), flat,
                pages.reshape(-1), limit.reshape(-1), values=r, scale=scale,
                interpret=_common._pallas_interpret())
        return absorb_out(acc[:, None], l[:, None])
    row = jnp.arange(ps, dtype=jnp.int32)
    slots = jnp.arange(B, dtype=jnp.int32)

    def step(i, carry):
        m, l, acc = carry                                # [B,S,Hq] x2, +[r]
        at, pg, lim = slot[i], pages[i], limit[i]        # [N] [N] [N,S]
        with jax.named_scope("kv_gather"):
            c = view[pg]                                 # [N,r+rd,page]
        own = jnp.minimum(at, B - 1)
        qn = qa[own]                                     # [N,S,Hq,r+rd]
        s = jnp.einsum("nshd,ndt->nsht", qn, c)
        ok = row[None, None, :] <= lim[:, :, None]                # [N,S,ps]
        s = jnp.where(ok[:, :, None, :], s.astype(jnp.float32) * scale,
                      -1e30)
        m_n = s.max(-1)                                           # [N,S,Hq]
        p = jnp.exp(s - m_n[..., None])
        u = jnp.einsum("nsht,nrt->nshr", p.astype(q.dtype), c[:, :r])
        mine = at[None, :] == slots[:, None]                      # [B,N]
        m_new = jnp.maximum(m, jnp.max(
            jnp.where(mine[:, :, None, None], m_n[None], -1e30), axis=1))
        alpha = jnp.exp(m - m_new)
        w = jnp.exp(m_n - m_new[own])                             # [N,S,Hq]
        fold = functools.partial(jnp.einsum, precision=(
            jax.lax.Precision.HIGH), preferred_element_type=jnp.float32)
        mine = mine.astype(jnp.float32)
        l = l * alpha + fold("bn,nsh->bsh", mine, p.sum(-1) * w)
        acc = (acc * alpha[..., None]
               + fold("bn,nshr->bshr", mine,
                      u.astype(jnp.float32) * w[..., None]))
        return m_new, l, acc

    m0 = jnp.full((B, S, Hq), -1e30, jnp.float32)
    _, l, acc = jax.lax.fori_loop(
        0, steps, step,
        (m0, jnp.zeros_like(m0), jnp.zeros((B, S, Hq, r), jnp.float32)))
    return absorb_out(acc, l)


def _attend_latent_paged(cfg, pools, write, read, pool_order=None,
                         within=None):
    """:func:`_block`'s ``attend`` of a latent-attention layer against the
    paged pool, whose one leaf ``latent [N, page, r + rd]`` holds a token's
    normed latent and its shared rotated key row.  The block's rows are
    written by the same whole-page merge as K and V (``write``,
    :func:`_paged_write_plan`).  Which path reads is decided by what the
    call is: a block of more than one token starts its slot and attends
    ``within`` itself (``(positions, None, reach)``, as
    :func:`_attend_paged`'s) over the expanded keys and values, reading
    nothing back (:func:`_attention_causal_block`, the expanded path); one
    token a slot reads the live pairs of ``read`` through the absorbed path
    (:func:`_attention_latent_paged`), by pages or by the gather as
    :func:`kv_read_path` answers for the leaf stored in ``pool_order`` (the
    caller's observation, as :func:`_attend_paged`'s)."""
    def attend(q, latent, wkv_b):
        with jax.named_scope("kv_write"):
            new = {"latent": _merge_pages(pools["latent"], latent, write)}
        with jax.named_scope("attn"):
            if within is not None:
                positions, _, reach = within
                k, v = _latent_expand(cfg, latent, wkv_b)
                return _attention_causal_block(cfg, q, k, v, positions,
                                               reach), new
            with jax.named_scope("attn_latent"):
                return _attention_latent_paged(
                    cfg, q, wkv_b, new["latent"], read, pool_order), new
    return attend


def _attend_paged(cfg, pools, write, read, pool_order=None, sink=None,
                  within=None):
    """:func:`_block`'s ``attend`` against the paged pool; the pool is what
    is kept.  ``pools`` maps each pool leaf (``k``/``v``, plus
    ``k_scale``/``v_scale`` on a quantized pool) to its array with the page
    axis leading: ``[N, page, Hkv, hd]`` (scales ``[N, page]``) — the
    stacked pool with ``N = L*P`` from :func:`forward_paged`.

    Write: ``write = (src, keep, pages, row)`` is the block's plan
    (:func:`_paged_write_plan`, one for all layers, ``pages`` moved to this
    layer's), laid into each leaf at one of two granularities
    (:func:`kv_write_path`).  **A page at a time** (:func:`_merge_pages`):
    the slots' pages are gathered, merged and scattered back whole: every
    prompt and verify block, and every block into a leaf that is not
    row-major as the device stores it (``pool_order``), that the row write's
    tile plan refuses (scale planes, int8 rows, a head that is not whole
    lanes, a number of KV heads that is no whole tile of sublanes), or in a
    program that may hold no Pallas kernel (not a TPU, a mesh of several
    devices).  **A row** (``ops/pallas/kv_row_write.py``):
    one token a slot into a row-major K or V leaf stores the slot's one row
    where it lies, the leaf aliased through the kernel: 4 KB a slot where
    the merge moves two pages (PERF.md, PR 45).
    Read: ``read`` is the call's list of live (slot, page) pairs
    (:func:`_paged_read_plan`, one for all layers, its pages moved to this
    layer's), which :func:`_attention_paged` gathers a step's worth of
    whole pages at a time, or, for one token a slot where the leaves' shape
    allows (:func:`kv_read_path`), fetches a page at a time into on-chip
    memory through ``ops/pallas/paged_read.py`` and copies nothing (PERF.md,
    PR 52).  The XLA pool ops thus slice all
    trailing axes, so they run in whatever layout the pool is stored in
    (``pool_order``, :func:`_pool_views`) and the pool stays in place.  What
    is known of a row-granular write: an XLA scatter of rows makes the TPU
    compiler re-lay the whole pool out around the layer scan (PERF.md,
    PR 25); a Pallas kernel fixes its operands' layout, so over a row-major
    leaf it leaves the pool where it is, through the layer scan and a looped
    model's scan of passes; over a leaf stored page-rows-minor the compiler
    would copy the whole pool into row-major order in front of the kernel
    and back behind it, which is why the rule reads the stored order.

    A quantized pool quantizes each written row on store (symmetric absmax,
    :func:`kv_quantize_rows`), merges its scale through the SAME plan, and
    dequantizes inside the gather — the scale planes are two more leaves of
    ``pools``, so the program shapes (and the zero-recompile inventory built
    on them) are unchanged.

    A window layer: ``write`` is :func:`_ring_write_plan`'s (the block's
    last pages into the slot's ring), ``sink`` the layer's learned sink
    logits, and a block of more than one token attends ``within`` itself
    (``(positions, window, reach)``, :func:`_attention_window_block`)
    instead of reading the ring, which cannot hold a prompt longer than the
    window.  ``within = (positions, None, reach)``: a full layer of such a
    model, whose block starts its slot too, attends within itself causally
    (:func:`_attention_causal_block`) and reads nothing back; ``reach``
    (:func:`_block_reach`) is how far into the block real tokens reach,
    where both stop."""
    def merge(name, pool, rows):
        if kv_write_path(pool, _leaf_order(pool_order, name),
                         rows.shape[1]) == "row":
            from ..ops.pallas.kv_row_write import kv_row_write

            return kv_row_write(pool, rows[:, 0].astype(pool.dtype),
                                write[2][:, 0], write[3],
                                interpret=_common._pallas_interpret())
        return _merge_pages(pool, rows, write)

    def attend(q, k, v):
        B, S, nkv, hd = k.shape
        with jax.named_scope("kv_write"):
            rows = {"k": k, "v": v}
            if "k_scale" in pools:
                # quantize on store: int8 rows + per-row scales, one plan
                for name, r in (("k", k), ("v", v)):
                    q8, sc = kv_quantize_rows(r.reshape(B * S, nkv, hd))
                    rows[name] = q8.reshape(B, S, nkv, hd)
                    rows[name + "_scale"] = sc.reshape(B, S)
            new = {name: merge(name, pool, rows[name])
                   for name, pool in pools.items()}
            for name in ("k", "v"):
                new[name] = constrain_spec(new[name],
                                           P(None, None, "model", None))
        with jax.named_scope("attn"):
            if within is not None:
                positions, window, reach = within
                if window is None:
                    return _attention_causal_block(cfg, q, k, v, positions,
                                                   reach), new
                return _attention_window_block(cfg, q, k, v, positions,
                                               window, sink, reach), new
            return _attention_paged(cfg, q, new, read, pool_order, sink), new
    return attend


# Chunks of ``window`` queries a long block's window attention takes at a time
WINDOW_BLOCK_CHUNKS = 16
# Queries, and keys, a step of a long block's causal attention takes
CAUSAL_BLOCK_CHUNK = 512


def causal_walk_steps(block: int, tokens: Optional[int] = None,
                      window: Optional[int] = None) -> int:
    """Chunk steps a full or latent layer runs for a block of ``block``
    tokens that starts its slot and holds ``tokens`` real ones from its
    start (all of them if ``None``): chunk ``i`` of the ``r`` chunks that
    real tokens reach walks ``i + 1`` chunks of keys and a chunk past them
    none, ``r (r + 1) / 2``; a short block is one masked product.  Under a
    ``window`` (a window layer's walk) chunk ``i`` starts at the chunk that
    holds its first query's oldest key, ``(i C - window + 1) // C``.  The
    host's copy of :func:`_attention_causal_block`'s trip counts (the
    ``walk_steps`` span attrs of a prompt)."""
    C = CAUSAL_BLOCK_CHUNK
    if block % C or block < 2 * C:
        return 1
    r = -(-min(block if tokens is None else tokens, block) // C)
    if window is None:
        return r * (r + 1) // 2
    return sum(i + 1 - max(i * C - window + 1, 0) // C for i in range(r))


def block_read_rows(block: int, window: Optional[int] = None,
                    tokens: Optional[int] = None) -> int:
    """K/V rows a block of ``block`` tokens that starts its slot reads of
    itself, a layer, when ``tokens`` of them from its start are real (all
    if ``None``): through a full layer each chunk of queries that holds a
    real token the chunks of keys at or before it
    (:func:`causal_walk_steps`), through a window layer each chunk of
    ``window`` queries two chunks of keys, of a long block the groups of
    ``WINDOW_BLOCK_CHUNKS`` chunks that hold a real token
    (:func:`_attention_window_block`), and where the window is longer than
    a chunk of the causal walk that walk's chunks inside the window; a
    short block all of itself, once.  The host's copy of those functions'
    shapes (the ``kv_rows_*`` span attrs of a prompt)."""
    tokens = block if tokens is None else min(tokens, block)
    C = CAUSAL_BLOCK_CHUNK
    if window is not None and window <= C:
        if block % window or block < 2 * window:
            return block
        group = WINDOW_BLOCK_CHUNKS * window
        if block > group and block % group == 0:
            block = -(-tokens // group) * group
        return 2 * block
    if block % C or block < 2 * C:
        return block
    return C * causal_walk_steps(block, tokens, window)


def _block_reach(seq_mask):
    """How far into a block ``[B,S]`` real tokens reach: the tokens from
    its start to the last real one of any row (a traced scalar: one program
    a bucket).  A prompt is right-padded to its bucket, so what lies past
    holds padding alone, and a block that attends within itself
    (:func:`_attention_causal_block`, :func:`_attention_window_block`) runs
    nothing for the chunks there."""
    S = seq_mask.shape[1]
    return jnp.max(jnp.where(seq_mask, jnp.arange(1, S + 1, dtype=jnp.int32),
                             0))


def _attention_causal_block(cfg, q, k, v, positions, reach=None, window=None,
                            sink=None):
    """A block of tokens ``[B,S,...]`` that starts its slot, through a full
    layer (or, with ``window``, a window layer whose window is longer than a
    chunk: the walk then starts at the chunk that holds the oldest key the
    chunk's first query sees, and a key ``window`` or more positions back
    is masked; ``sink``: the layer's learned logits, which join each row's
    softmax at the end of its walk): plain causal attention over the block's
    own keys, values of their own width, nothing read from the pool.  Where ``S`` is whole
    chunks of ``CAUSAL_BLOCK_CHUNK``, a chunk of queries walks the chunks of
    keys at or before it with a running maximum, sum and accumulator of its
    own size (float32), the diagonal chunk masked: the work is the causal
    half, and no array is ``[S, S]`` or rescaled ``S``-wide a step (the
    paged read's per-slot state is, which at 16,384 queries rewrote 0.5 GB
    every two pages: PERF.md, PR 30).  A shorter block takes the masked
    product.

    ``reach`` (:func:`_block_reach`; ``None``: every row is real) bounds
    the walk by what the block holds: the query chunks past the
    ``ceil(reach / CAUSAL_BLOCK_CHUNK)`` that real tokens reach hold a
    bucket's padding alone, the last and longest walks, and run no step.
    Their rows come out 0 (a ``0 / 0`` there would reach real rows of the
    next layer through ``0 * NaN``); every key chunk at or before a real
    query chunk is walked as before."""
    B, S, Hq, hd = q.shape
    Hkv, vd, C = k.shape[2], v.shape[-1], CAUSAL_BLOCK_CHUNK
    if S % C or S < 2 * C:
        return _attention(cfg, q, k, v, positions, "xla",
                          custom_positions=True, window=window, sink=sink)
    n, G = S // C, Hq // Hkv
    qc = jnp.moveaxis(q.reshape(B, n, C, Hkv, G, hd), 1, 0)
    diagonal = (jnp.arange(C, dtype=jnp.int32)[None, :]
                <= jnp.arange(C, dtype=jnp.int32)[:, None])       # [Cq, Ck]
    if window is not None:      # how far behind its query a key of the
        back = (jnp.arange(C, dtype=jnp.int32)[:, None]   # same chunk lies
                - jnp.arange(C, dtype=jnp.int32)[None, :])

    def chunk(args):
        i, qi = args                                  # qi [B,C,Hkv,G,hd]

        def step(j, carry):
            m, l, acc = carry
            kj = jax.lax.dynamic_slice_in_dim(k, j * C, C, axis=1)
            vj = jax.lax.dynamic_slice_in_dim(v, j * C, C, axis=1)
            s = jnp.einsum("bckgd,bjkd->bkgcj", qi, kj).astype(jnp.float32)
            seen = diagonal | (j < i)
            if window is not None:
                # a row none of whose keys of the walk's first chunk is
                # inside the window weighs them exp(0) under m = -1e30; the
                # next chunk's real maximum scales that to exactly 0
                seen = seen & ((i - j) * C + back < window)
            s = jnp.where(seen, s * _sm_scale(cfg, hd), -1e30)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            pv = jnp.einsum("bkgcj,bjkd->bkgcd", p.astype(q.dtype), vj)
            return (m_new, l * alpha + p.sum(-1),
                    acc * alpha[..., None] + pv.astype(jnp.float32))

        steps = i + 1 if reach is None else jnp.where(i * C < reach, i + 1, 0)
        first = (0 if window is None
                 else jnp.maximum(i * C - window + 1, 0) // C)
        m0 = jnp.full((B, Hkv, G, C), -1e30, jnp.float32)
        m, l, acc = jax.lax.fori_loop(first, steps, step, (
            m0, jnp.zeros_like(m0), jnp.zeros((B, Hkv, G, C, vd),
                                              jnp.float32)))
        if sink is not None:
            # the sink's term joins the sum under the larger of the two
            # maxima, as at the end of :func:`_attention_paged`'s walk
            b = sink.astype(jnp.float32).reshape(1, Hkv, G, 1)
            m_new = jnp.maximum(m, b)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.exp(b - m_new)
            acc = acc * alpha[..., None]
        # a chunk that ran no step has l == 0: its output is 0, not NaN
        return (acc / jnp.where(l > 0, l, 1.0)[..., None]).astype(q.dtype)

    out = jax.lax.map(chunk, (jnp.arange(n, dtype=jnp.int32), qc))
    # [n,B,Hkv,G,C,vd] -> [B, n*C, Hkv*G, vd]
    return jnp.transpose(out, (1, 0, 4, 2, 3, 5)).reshape(B, S, Hq, vd)


def _attention_window_block(cfg, q, k, v, positions, window: int, sink=None,
                            reach=None):
    """A block of tokens ``[B,S,...]`` that starts its slot, through a
    window layer: every query sees the block's own keys at most ``window -
    1`` positions back.  Where ``S`` is whole chunks of ``window`` tokens, a
    chunk of queries meets its own chunk of keys and the one before it
    (scores ``[.., S/window, window, 2*window]``, never ``[S, S]``); a
    shorter or ragged block takes the masked product.  A long block takes
    ``WINDOW_BLOCK_CHUNKS`` chunks at a time, and of those groups the ones
    that real tokens ``reach`` (:func:`_block_reach`; ``None``: all): a
    group of a bucket's padding alone is not computed and comes out 0.

    A window longer than a chunk of the causal walk (``CAUSAL_BLOCK_CHUNK``;
    Trinity's 4,096, where a chunk of ``window`` queries against two of keys
    is 0.8 GB of float32 scores a KV head) takes that walk, bounded below by
    the window (:func:`_attention_causal_block`)."""
    B, S, Hq, hd = q.shape
    Hkv, C = k.shape[2], window
    if window > CAUSAL_BLOCK_CHUNK:
        return _attention_causal_block(cfg, q, k, v, positions, reach, window,
                                       sink)
    if S % C or S < 2 * C:
        return _attention(cfg, q, k, v, positions, "xla",
                          custom_positions=True, window=window, sink=sink)
    n, G = S // C, Hq // Hkv
    qc = q.reshape(B, n, C, Hkv, G, hd)

    def with_previous(x):     # [B,S,Hkv,w] -> [B,n,2C,Hkv,w]
        xc = x.reshape(B, n, C, Hkv, x.shape[-1])
        before = jnp.concatenate([jnp.zeros_like(xc[:, :1]), xc[:, :-1]], 1)
        return jnp.concatenate([before, xc], axis=2)

    c = jnp.arange(C, dtype=jnp.int32)[:, None]
    j = jnp.arange(2 * C, dtype=jnp.int32)[None, :] - C    # key - chunk start
    ok = (j <= c) & (c - j < window)                       # [C, 2C]
    # the first chunk has no chunk before it
    ok = ok[None] & ~((jnp.arange(n) == 0)[:, None, None] & (j < 0)[None])
    b = (None if sink is None
         else sink.astype(jnp.float32).reshape(1, 1, Hkv, G, 1, 1))

    def chunks(qc, kk, vv, ok):     # [B,m,...] for m of the n chunks
        scores = jnp.einsum("bnckgd,bnjkd->bnkgcj", qc, kk)
        scores = scores.astype(jnp.float32) * _sm_scale(cfg, hd)
        scores = jnp.where(ok[None, :, None, None], scores, -1e30)
        probs = _softmax_with_sink(scores, b).astype(q.dtype)
        return jnp.einsum("bnkgcj,bnjkd->bnckgd", probs, vv)

    kk, vv = with_previous(k), with_previous(v)
    m = WINDOW_BLOCK_CHUNKS
    if n > m and n % m == 0:
        # a long prompt some chunks at a time: the float32 scores of all
        # 128 chunks of a 16,384-token bucket are 1 GB, and there are
        # several arrays of their size
        def group(i, out):
            def take(x, axis=1):
                return jax.lax.dynamic_slice_in_dim(x, i * m, m, axis)

            return jax.lax.dynamic_update_slice_in_dim(
                out, chunks(take(qc), take(kk), take(vv), take(ok, 0)),
                i * m, axis=1)

        groups = (n // m if reach is None
                  else jnp.minimum(-(-reach // (m * C)), n // m))
        out = jax.lax.fori_loop(0, groups, group, jnp.zeros(
            (B, n, C, Hkv, G, v.shape[-1]), jnp.result_type(q.dtype, v.dtype)))
    else:
        out = chunks(qc, kk, vv, ok)
    return out.reshape(B, S, Hq, v.shape[-1])


def _kept_row(keep, tokens: int):
    """The fourth member of a write plan: for a block of ONE token a slot,
    the row ``[B]`` of its one page that the plan keeps (``start % page``; -1
    where it keeps none: a masked or idle slot, a position past the page
    table), which is all a write by row needs beside ``pages``; ``None`` for
    a longer block.  Whether a leaf is written by row is
    :func:`kv_write_path`'s to say, leaf by leaf; where none is, nothing
    reads the row and the compiler drops it."""
    if tokens != 1:
        return None
    kept = keep[:, 0]
    return jnp.where(kept.any(-1), jnp.argmax(kept, axis=-1), -1).astype(
        jnp.int32)


def _plan_at(write, first_page):
    """A block's write plan moved to the layer whose pages start at
    ``first_page`` of the stacked pool."""
    src, keep, pages, row = write
    return src, keep, pages + first_page, row


def _paged_write_plan(page_table, start, seq_mask, ps: int):
    """How a block of tokens ``[B,S]`` at slot positions ``start + s`` lands
    in whole pages: ``(src [B, n_pg*ps], keep [B, n_pg, ps], pages [B,
    n_pg], row)``, one plan for all layers (``row``: :func:`_kept_row`).

    S consecutive positions fall in at most ``n_pg`` logical pages; row r
    of the j-th of them holds position ``(start // ps + j) * ps + r``,
    which is token ``src`` of the block if ``keep``.  Masked tokens and
    positions past the page table are kept out of every page, so real
    pages are never corrupted (a verify-k block past the table end must not
    wrap into the clamped last page and overwrite confirmed K/V).  A page
    none of whose rows is written is redirected to the trash page, so no
    two slots ever write one real page and the scatter keeps its static
    shape."""
    B, S = seq_mask.shape
    maxp = page_table.shape[1]
    n_pg = (S + ps - 2) // ps + 1
    lpage = (start // ps)[:, None] + jnp.arange(n_pg, dtype=jnp.int32)
    src = (lpage[:, :, None] * ps + jnp.arange(ps, dtype=jnp.int32)
           - start[:, None, None])                         # [B,n_pg,ps]
    in_block = (src >= 0) & (src < S) & (lpage < maxp)[:, :, None]
    src = jnp.clip(src, 0, S - 1).reshape(B, n_pg * ps)
    keep = in_block & jnp.take_along_axis(seq_mask, src,
                                          axis=1).reshape(in_block.shape)
    pages = jnp.where(
        keep.any(-1),
        jnp.take_along_axis(page_table, jnp.minimum(lpage, maxp - 1), axis=1),
        0)
    return src, keep, pages, _kept_row(keep, S)


def _ring_write_plan(ring_table, start, seq_mask, ps: int):
    """:func:`_paged_write_plan` for a window layer, whose slot keeps a ring
    of ``R = ring_table.shape[1]`` pages (logical page ``j`` in ring page
    ``j % R``): ``(src, keep, pages, row)`` over the LAST pages the block's
    real tokens reach, at most ``R`` of them.  A decode token lands in its page
    (what the page held of logical page ``j - R`` behind it is past every
    query's mask); of a prompt longer than the ring only the last rows are
    kept, the rest is never read again (a window layer's queries attend
    inside the block, :func:`_attention_window_block`)."""
    B, S = seq_mask.shape
    R = ring_table.shape[1]
    n_pg = min(R, (S + ps - 2) // ps + 1)
    positions = start[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    last = jnp.max(jnp.where(seq_mask, positions, -1), axis=1)       # [B]
    lpage = ((jnp.maximum(last, 0) // ps)[:, None] - (n_pg - 1)
             + jnp.arange(n_pg, dtype=jnp.int32))                    # [B,n_pg]
    src = (lpage[:, :, None] * ps + jnp.arange(ps, dtype=jnp.int32)
           - start[:, None, None])
    in_block = (src >= 0) & (src < S) & (lpage >= 0)[:, :, None]
    src = jnp.clip(src, 0, S - 1).reshape(B, n_pg * ps)
    keep = in_block & jnp.take_along_axis(seq_mask, src,
                                          axis=1).reshape(in_block.shape)
    pages = jnp.where(keep.any(-1),
                      jnp.take_along_axis(ring_table, lpage % R, axis=1), 0)
    return src, keep, pages, _kept_row(keep, S)


def window_read_rows(lengths, page_size: int, window: int,
                     slots: int) -> int:
    """:func:`paged_read_rows` for a window layer's ring: a live slot of
    ``lengths`` rows (the row being written counted in) is read over the
    pages that hold its last ``window`` positions, the list rounded up to
    whole steps.  The host's copy of :func:`_ring_read_plan`."""
    pairs = paged_read_pairs(slots, window_ring_pages(window, page_size))
    at = np.asarray(lengths, np.int64) - 1
    at = at[at >= 0]
    live = int((at // page_size
                - np.maximum(at - window + 1, 0) // page_size + 1).sum())
    return -(-live // pairs) * pairs * page_size


def _ring_read_plan(ring_table, start, seq_mask, ps: int, window: int):
    """:func:`_paged_read_plan` for a window layer's decode step (one token
    a slot): the pairs are the ring pages that hold positions ``pos -
    window + 1 .. pos`` of each live slot, ``limit`` as there and ``low``
    the first row of each page inside the window.  ``(steps, slot, pages,
    limit, low)``."""
    B, R = ring_table.shape
    S = seq_mask.shape[1]
    pairs = paged_read_pairs(B, R)
    positions = start[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    last = jnp.max(jnp.where(seq_mask, positions, -1), axis=1)       # [B]
    first_page = jnp.maximum(last - window + 1, 0) // ps
    n_pages = jnp.where(last >= 0, last // ps - first_page + 1, 0)
    ends = jnp.cumsum(n_pages)
    i = jnp.arange(-(-B * R // pairs) * pairs, dtype=jnp.int32)
    slot = jnp.sum(i[:, None] >= ends[None, :], axis=1, dtype=jnp.int32)
    valid = slot < B
    at = jnp.minimum(slot, B - 1)
    j = first_page[at] + i - (ends - n_pages)[at]
    pages = jnp.where(valid, ring_table[at, j % R], 0)
    limit = jnp.where(valid[:, None], positions[at] - j[:, None] * ps, -1)
    low = limit - (window - 1)
    return ((ends[-1] + pairs - 1) // pairs, slot.reshape(-1, pairs),
            pages.reshape(-1, pairs), limit.reshape(-1, pairs, S),
            low.reshape(-1, pairs, S))


def cache_kind(cfg: TransformerConfig) -> Tuple[str, str]:
    """``(kind, description)`` of the cache a sequence of this model keeps:
    which model it is, and why a page of it cannot be shared, parked,
    rescaled or split by head.  Written once, for :func:`_hybrid_refuse`
    and the serving engine's refusals (``inference/cache_layout.py``)."""
    if has_state(cfg):
        return "state", mixers_of(cfg)[0].keeps
    if is_hybrid(cfg):
        return "window", (
            "window and full attention layers (layer_pattern): a window "
            "layer's ring (a pool of its own) holds its slot's last positions "
            "only: no page of it to share, park, verify against or rescale")
    if is_latent(cfg):
        return "latent", (
            "latent attention (kv_lora_rank): its rows have no head axis to "
            "shard or scale and only one token a slot reads them back: a tail "
            "behind shared pages or a draft block has nothing to attend through")
    if is_grouped(cfg):
        return "grouped", "leading dense layers (dense_layers)"
    if cfg.loop_passes > 1:
        return "looped", (
            f"one stack of equal layers run {cfg.loop_passes} times "
            "(loop_passes): K and V pages of one pool, loop_passes x "
            "num_layers layers deep, so a page is every pass's rows of its "
            "tokens")
    return "uniform", ("one stack of equal layers over K and V pages of one "
                       "pool, num_layers layers deep")


def _hybrid_refuse(what: str, cfg: TransformerConfig):
    """What only the uniform K/V models go through, refused by name."""
    raise NotImplementedError(
        f"{what} does not support a model with {cache_kind(cfg)[1]} (it "
        "runs through forward() and the paged serving path, forward_paged)")


def _head_at(cfg, params, x, logits_at):
    """:func:`_head` over every position of ``x [B,S,d]``, or over the one
    position a row ``logits_at [B]`` names (``[B,1,V]``): a prompt's prefill
    reads one row of logits, and the head over a whole bucket is a quarter
    of its operations where the vocabulary is large."""
    if logits_at is not None:
        x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
    return _head(cfg, params, x)


# Bytes of weights and cache in a call past which a prompt's block of a
# model of window and full layers pins x after each layer
# (:func:`_forward_paged_hybrid`): three quarters of a v5e's 16 GB
PIN_RESIDENT_BYTES = 12e9


def _forward_paged_hybrid(cfg, params, tokens, cache, page_table, start,
                          seq_mask, expert_counts, pool_order,
                          logits_at=None, state_slot=None):
    """:func:`forward_paged` for a model with layers of more than one kind:
    leaves and a plan per kind, the layers in their published order (a
    Python loop: the kinds' stacks differ in shape, so there is nothing to
    scan).

    ``page_table`` is ``(full [B, maxp], ring [B, R])``; a lone table's first
    ``R`` columns serve as the ring (one sequence over pools of equal page
    counts).  A full layer is :func:`forward_paged`'s own.  A window layer
    writes into its slot's ring (:func:`_ring_write_plan`); one token a
    slot reads the ring through the window (:func:`_ring_read_plan`).  A
    longer block must start its slot (the engine refuses what would start
    one elsewhere: prefix sharing, speculation) and attends inside itself in
    both kinds of layer, writing its K/V for the tokens to come.  A layer
    of a kind of :data:`~.mixers.MIXERS` has no pages: its slot-indexed
    leaves, ``[layers of the kind * slots, ...]`` stacked, are read and
    written where they lie (:func:`~.mixers.paged`; ``state_slot`` as
    :func:`forward_paged`'s).  A layer that is its MLP or expert layer alone
    (``one_sublayer``'s "mlp") owns no leaf: it is handed neither an
    ``attend`` nor a mixer and nothing of it is kept."""
    full_table, ring_table = (page_table if isinstance(page_table,
                                                       (tuple, list))
                              else (page_table, None))
    groups = layer_groups(cfg)
    suffix = _KIND_SUFFIX
    kind_cfg = {kind: g for kind, (g, _) in kind_layers(cfg).items()}
    # each kind's pool stacked [L_kind * P_kind, page, Hkv, w], a leaf kept
    # head-major (pool_leaf_head_major) seen through the transpose that
    # moves nothing, its layers' pages at l * P_kind
    head_major = _head_major_leaves(cfg)

    def stacked(kind, n):
        a = cache[n + suffix[kind]]
        a = a.reshape(-1, *a.shape[2:])
        return (jnp.transpose(a, (0, 2, 1, 3))
                if head_major[n + suffix[kind]] else a)

    pools = {kind: {n: stacked(kind, n) for n in ("k", "v")}
             for kind in kind_cfg if kind in suffix}
    ps = next(iter(pools.values()))["k"].shape[1]
    slots = 0
    # a kind with a state a slot: its leaves stacked
    stateful = [kind for kind in MIXERS if kind in kind_cfg]
    for kind in stateful:
        keys = MIXERS[kind].pool_keys
        slots = cache[keys[0]].shape[1]
        pools[kind] = {n: cache[n].reshape(-1, *cache[n].shape[2:])
                       for n in keys}
    W = cfg.window_size
    R = window_ring_pages(W, ps)
    if ring_table is None:
        ring_table = full_table[:, :R]
    S = tokens.shape[1]
    positions = start[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    # one token a slot reads each kind's pool by its plan; a longer block
    # starts its slot and attends within itself, in both kinds
    plans = {}
    if "full" in pools:
        plans["full"] = (_paged_write_plan(full_table, start, seq_mask, ps),
                         _paged_read_plan(full_table, start, seq_mask, ps)
                         if S == 1 else None)
    if "window" in pools:
        plans["window"] = (_ring_write_plan(ring_table, start, seq_mask, ps),
                           _ring_read_plan(ring_table, start, seq_mask, ps, W)
                           if S == 1 else None)
    reach = None if S == 1 else _block_reach(seq_mask)
    x = _embed(cfg, params, tokens,
               jnp.minimum(positions, cfg.max_seq_len - 1))
    x = constrain_spec(x, P(BATCH_AXES, None, None))
    rng = jax.random.PRNGKey(0)
    n_pages = {kind: cache["k" + suffix[kind]].shape[1] for kind in plans}
    orders = {kind: {n: _seen_order(head_major, pool_order, n + suffix[kind])
                     for n in ("k", "v")} for kind in plans}
    # each group's expert stacks whole [n * E, ...] with a layer's experts
    # at l * E: nothing of a layer's size is cut out
    experts = {name: {k: v.reshape(-1, *v.shape[2:]) for k, v in lp.items()
                      if _whole_in_group(lp, k)}
               for name, lp in params["layers"].items()}
    seen = {kind: 0 for kind in pools}
    counts = []
    # A prompt's head reads ONE row of x (``logits_at``).  Left to itself the
    # compiler takes that row out of every layer's two branch outputs at the
    # very end and keeps them all until then: 0.54 GB a layer of a
    # 16,384-token block, 5.3 GB over ten layers beside 12.5 GB of weights
    # and cache.  x is pinned after each layer so that the next reads the
    # sum and the branches die.  A model of window and full layers alone is
    # pinned where the weights and the cache the call holds leave the kept
    # branches no room (``PIN_RESIDENT_BYTES``: Trinity's 12.8 GB, where an
    # 8,192-token block's temporaries are 2.2 GB unpinned and 0.7 GB pinned);
    # under that it keeps the program it had (MiMo's 9.8 GB: its seven
    # layers fit).
    resident = sum(a.size * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves((params, cache)))
    pin = logits_at is not None and (slots > 0
                                     or resident > PIN_RESIDENT_BYTES)
    for group, index, kind, _ in layer_plan(cfg):
        g = groups[group][0]
        # ``v`` is the group's stack or, as the serving executor holds it, a
        # tuple of its layers' arrays (per_layer_leaves): then nothing is
        # cut out of a stack here
        lp = {k: v[index] for k, v in params["layers"][group].items()
              if k not in experts[group]}
        attend = mixer = None
        # a layer that is its MLP alone has no cache leaf to read or keep
        layer = seen.get(kind)
        if layer is not None:
            seen[kind] += 1
        if kind in stateful:
            mixer = mixers.paged(MIXERS[kind], g, pools[kind],
                                 layer * slots, state_slot, start, seq_mask)
        elif layer is not None:
            first_page = layer * n_pages[kind]
            write, read = plans[kind]
            if read is not None:
                read = (read[0], read[1], read[2] + first_page) + read[3:]
            attend = _attend_paged(
                g, pools[kind], _plan_at(write, first_page), read,
                orders[kind], sink=lp.get("attn_sink"),
                within=(None if S == 1 else
                        (positions, W if kind == "window" else None, reach)))
        x, _, c, kept = _block(
            g, {**lp, **experts[group]}, x, positions, rng, attend,
            token_mask=seq_mask,
            expert_offset=(jnp.int32(index * (g.moe_experts_held
                                              or g.num_experts))
                           if experts[group] else None), ssm=mixer)
        if layer is not None:
            pools[kind] = kept[1] if kind in stateful else kept
        x = constrain_spec(x, P(BATCH_AXES, None, None))
        if pin:
            x = jax.lax.optimization_barrier(x)
        if c is not None:
            counts.append(c)
    logits = _head_at(cfg, params, x, logits_at)
    state = {n: a for kind in stateful
             for n, a in pools.pop(kind, {}).items()}
    out = {n + suffix[kind]: (jnp.transpose(a, (0, 2, 1, 3))
                              if head_major[n + suffix[kind]] else a
                              ).reshape(cache[n + suffix[kind]].shape)
           for kind, leaves in pools.items() for n, a in leaves.items()}
    out.update({n: a.reshape(cache[n].shape) for n, a in state.items()})
    if not expert_counts:
        return logits, out
    return logits, out, (jnp.stack(counts) if counts else None)


def forward_paged(cfg: TransformerConfig, params: Dict[str, Any],
                  tokens: jax.Array, cache: Dict[str, Any],
                  page_table: jax.Array, start: jax.Array,
                  seq_mask: jax.Array, adapters=None,
                  expert_counts: bool = False,
                  pool_order: Optional[Tuple[int, ...]] = None,
                  state_slot: Optional[jax.Array] = None,
                  logits_at: Optional[jax.Array] = None):
    """Run ``tokens [B,S]`` against the paged pool, writing each real token's
    K/V at its slot position and attending each query to its own slot only.

    ``page_table [B, maxp]`` int32: physical page id of each slot's logical
    page (0 = the reserved trash page, also the unallocated filler).
    ``start [B]``: slot position of ``tokens[:, 0]`` (0 for prefill, the
    current length for decode).  ``seq_mask [B,S]``: True for real tokens —
    False tokens' K/V are written nowhere and their logits are garbage (the
    caller reads logits only at real positions).

    One function, three static shapes at steady state — bucketed prefill
    ``[1, S_pad]``, fleet decode ``[B_slots, 1]``, and (with speculative
    decoding) the verify-k block ``[B_slots, k+1]`` that writes the pending
    token plus k draft proposals and returns all k+1 next-token
    distributions in one traversal (``inference/speculative.py``) — so
    admission into a running batch never recompiles.  Positions past the
    slot's page table (a verify block straddling the reserved region, or a
    rejected-draft tail near ``max_model_len``) are dropped like masked
    tokens rather than wrapping into the clamped last page, so multi-token
    decode can never corrupt live K/V; their logits are garbage the caller
    never reads.  Returns ``(logits [B,S,V], new_cache)``.

    The pool stays in place for the whole program: its leaves ride the
    layer scan as carry, stacked ``[L*P, page, ...]``, and each layer
    gathers and scatters whole pages of the stack at ``l*P + page``
    (:func:`_attend_paged`), so the donated buffers are updated where they
    lie.  A quantized cache (``init_paged_cache(kv_dtype="int8")``) is two
    more leaves of that carry and the same program shapes.

    What is read: every slot's pages up to its own longest real position
    (decode: an active slot's length + 1; prefill: ``start + n_real``;
    verify-k: ``start + k + 1``) and nothing of a slot with no real token,
    as one flat list of (slot, page) pairs walked a fixed number of pairs a
    step — not the ``maxp`` pages of every page-table row, nor every slot to
    the longest slot's length.  The list is computed here, on the device,
    from ``start``, ``seq_mask`` and the table (:func:`_paged_read_plan`),
    so one compiled program serves every length and a tick launched ahead
    on ``lengths + k`` reads what it needs.  ``pool_order``
    (:func:`paged_pool_order` of the arrays the caller holds; optional, a
    matter of speed only) is the order in which the device stores a K/V
    leaf's axes, where that is not row-major (:func:`_pool_views`).

    ``expert_counts=True`` adds a third result: the rows each expert of
    each layer computed, ``[L, E]`` int32, for a model whose expert layers
    are dropless (masked tokens are in no group and in no count); ``None``
    for every other model.

    ``logits_at [B]`` (optional): the one position of each row whose logits
    are wanted, ``[B,1,V]`` in place of ``[B,S,V]`` (a prompt's prefill reads
    its last real position's).

    A model with state-space layers (``ssm_heads``) reads and writes, beside
    the pages, one row a sequence of the cache's ``ssm_state`` / ``ssm_conv``
    leaves: row ``b`` of the batch is state row ``b`` unless ``state_slot
    [B]`` names the rows (the serving engine's one-row prefill passes its
    slot).  A row whose ``start`` is 0 begins from the zero state and a zero
    tail, any other continues what its state row holds; a masked token
    leaves both as they are, so a padded prompt leaves what the unpadded one
    does, and a row with no real token is not touched.  Real tokens lead
    their block.

    ``adapters`` (optional) is the per-slot LoRA operand pytree of
    multi-tenant adapter serving (docs/SERVING.md): ``{"scale": [B] f32,
    "factors": {target: {"A": [L,B,d_in,R], "B": [L,B,R,d_out]}}}``.  The
    factor stacks ride the layer scan beside the layers, so the program
    count is unchanged (:func:`_adapter_proj`); ``None`` traces no adapter
    operand at all.
    """
    _check_decodable(cfg, params, "paged decode")
    if cfg.attention_layers is not None:
        raise NotImplementedError(
            "paged decode does not support per-layer attention windows "
            "(attention_layers); use the contiguous cache path")
    if (is_grouped(cfg) or has_state(cfg)) and adapters is not None:
        _hybrid_refuse("multi-tenant adapter serving (per-slot LoRA "
                       "factors)", cfg)
    if is_hybrid(cfg):
        return _forward_paged_hybrid(cfg, params, tokens, cache, page_table,
                                     start, seq_mask, expert_counts,
                                     pool_order, logits_at, state_slot)
    # a K/V leaf kept head-major (_head_major_leaves) is seen through the
    # transpose that moves nothing, as a two-kind model's (stacked below)
    head_major = _head_major_leaves(cfg)
    lead = cache["latent" if is_latent(cfg) else "k"]
    num_pages, ps = lead.shape[1], lead.shape[3 if head_major["k"] else 2]
    positions = (start[:, None]
                 + jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :])
    write = _paged_write_plan(page_table, start, seq_mask, ps)
    # a latent model's block of more than one token starts its slot (the
    # engine refuses what would start one elsewhere) and attends within
    # itself, as far as its real tokens reach; only one token a slot reads
    # the pool
    within = ((positions, None, _block_reach(seq_mask))
              if is_latent(cfg) and tokens.shape[1] > 1 else None)
    read = (None if within is not None
            else _paged_read_plan(page_table, start, seq_mask, ps))

    # a slot may run to positions past the learned table's end
    x = _embed(cfg, params, tokens,
               jnp.minimum(positions, cfg.max_seq_len - 1))
    x = constrain_spec(x, P(BATCH_AXES, None, None))

    # The pool rides the layer scan as CARRY, stacked: [L*P, page, ...]
    # merges its two major axes (free in any layout), and layer l's page p
    # is page l*P + p.  Nothing may materialise one layer's slice: as the
    # scan's xs/ys the pool is sliced, re-laid out, written back and copied
    # whole around the loop, more than half of a decode tick (PERF.md, PR 25)
    pools = {k: cache[k].reshape(-1, *cache[k].shape[2:])
             for k in PAGED_POOL_KEYS if k in cache}
    for n in ("k", "v"):
        if head_major[n]:
            pools[n] = jnp.transpose(pools[n], (0, 2, 1, 3))
    if any(head_major.values()):
        pool_order = {n: _seen_order(head_major, pool_order, n)
                      for n in ("k", "v")}
    # the slot rows of a model with a mixer in every layer: ``slots`` a
    # layer, the batch's at ``state_slot`` (None: rows 0 .. B - 1)
    ssm = None
    if has_state(cfg):
        row = mixers_of(cfg)[0]
        slots = cache[row.pool_keys[0]].shape[1]
        ssm = lambda pools, layer: mixers.paged(  # noqa: E731
            row, cfg, pools, layer * slots, state_slot, start, seq_mask)
    # one scan a group of equal layers, in the published order: the whole
    # model, or a model's leading dense layers and then its expert layers
    groups = ({name: (g, n, params["layers"][name])
               for name, (g, n) in layer_groups(cfg).items()}
              if is_grouped(cfg)
              else {None: (cfg, cfg.num_layers, params["layers"])})
    def run_pass(carry, pool_first):
        x, pools = carry
        first, counts = 0, None
        for g, n, layers in groups.values():
            x, pools, c = _paged_layers(
                g, layers, x, pools, first, n, num_pages, positions,
                seq_mask, write, read, within, pool_order, adapters, ssm,
                pool_first)
            first, counts = first + n, (counts if c is None else c)
        return (x, pools), counts

    # a looped model: every pass the same weights over its own layers of
    # the pool, pass r's from layer r * num_layers on
    (x, pools), counts = _passes(
        cfg, params, run_pass, (x, pools),
        None if cfg.loop_passes == 1 else
        jnp.arange(cfg.loop_passes, dtype=jnp.int32) * cfg.num_layers)
    logits = _head_at(cfg, params, x, logits_at)
    cache = {k: (jnp.transpose(a, (0, 2, 1, 3)) if head_major.get(k) else a
                 ).reshape(cache[k].shape) for k, a in pools.items()}
    return (logits, cache, counts) if expert_counts else (logits, cache)


def _paged_layers(cfg, layers, x, pools, first: int, n: int, num_pages: int,
                  positions, seq_mask, write, read, within, pool_order,
                  adapters, ssm=None, pool_first=None):
    """``n`` equal layers of :func:`forward_paged` as one scan, the model's
    layers ``first .. first + n - 1``: ``(x, pools, counts)`` with the pool
    as carry, layer ``l``'s pages at ``l * num_pages`` of the stacked
    leaves.  ``cfg`` is the layers' uniform config and ``layers`` their
    stack (the whole model's, or one group's of :func:`layer_groups`).
    ``first`` is an index into the WEIGHTS' layers (whose experts, whose
    state rows); where the pool is deeper than the weights (a looped model's
    pass ``r``) ``pool_first`` (a traced scalar, ``r * num_layers``) is the
    pool layer that weight layer 0 reads and writes in this call, and layer
    ``l``'s pages lie at ``(pool_first + l) * num_pages``.  None: the two
    are one.
    ``ssm(pools, layer)``: the layers have a mixer, whose slot-indexed leaves
    ride the carry with the pages (:func:`~.mixers.paged` at this layer)."""
    rng = jax.random.PRNGKey(0)
    ad_scale = (adapters["scale"].astype(jnp.float32)
                if adapters is not None else None)
    # The expert stacks of a dropless model stay out of the scan's xs, for
    # the pool's reason (an 800 MB slice a layer, cut out and copied before
    # the grouped matmuls read it): whole, [n*E, ...], and layer l's experts
    # are the groups from l*E on (moe_ffn_nodrop).
    experts = {}
    if expert_counts_shape(cfg):
        experts = {k: v.reshape(-1, *v.shape[2:]) for k, v in layers.items()
                   if k in _EXPERT_LEAVES}
        layers = {k: v for k, v in layers.items() if k not in experts}
    held = cfg.moe_experts_held or cfg.num_experts

    def body(carry, layer):
        x, pools = carry
        lp, first_page, factors = layer
        pool_page = (first_page if pool_first is None
                     else first_page + pool_first * num_pages)
        wplan = _plan_at(write, pool_page)
        rplan = (None if read is None else
                 (read[0], read[1], read[2] + pool_page, read[3]))
        kv = {k: v for k, v in pools.items() if k not in STATE_POOL_KEYS}
        attend = (_attend_latent_paged(cfg, kv, wplan, rplan, pool_order,
                                       within)
                  if is_latent(cfg) else
                  _attend_paged(cfg, kv, wplan, rplan, pool_order))
        layer = first_page // num_pages
        mixer = None if ssm is None else ssm(pools, layer)
        x, _, counts, kept = _block(
            cfg, {**lp, **experts}, x, positions, rng, attend,
            proj=_adapter_proj(factors, ad_scale), token_mask=seq_mask,
            expert_offset=(layer - first) * held if experts else None,
            ssm=mixer)
        x = constrain_spec(x, P(BATCH_AXES, None, None))
        return (x, kept if ssm is None else {**kept[0], **kept[1]}), counts

    # the per-slot factor stacks scan beside the layers where the call has
    # them: each step's slice is THAT layer's
    index = jnp.arange(n, dtype=jnp.int32)
    (x, pools), counts = jax.lax.scan(body, (x, pools), (
        layers, (index + first) * num_pages,
        None if adapters is None else adapters["factors"]))
    return x, pools, counts


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       ignore_index: int = -100) -> jax.Array:
    """Mean next-token NLL; positions with ``labels == ignore_index`` masked."""
    with jax.named_scope("loss"):
        mask = (labels != ignore_index)
        safe = jnp.where(mask, labels, 0)
        logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        gold = jnp.take_along_axis(logits.astype(jnp.float32),
                                   safe[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * mask
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1)
