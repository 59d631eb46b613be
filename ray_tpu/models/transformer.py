"""Flagship decoder-only transformer LM (functional JAX, GSPMD-shardable).

Mirrors the capability of the reference's north-star workload (GPT-J-6B
fine-tune, BASELINE.md; reference trains it via DeepSpeed ZeRO-3 on GPUs —
`release/air_examples/gptj_deepspeed_finetuning/`). TPU-first design:

- pure pytree params + functional apply; no framework magic between the
  model and `jax.jit`, so shardings attach cleanly;
- layers stacked and iterated with `lax.scan` → O(1) compile time in depth,
  XLA-friendly static control flow (one scan per stack of like layers:
  leading dense layers, then the rest);
- the block is DESCRIBED by `TransformerConfig`, and parameters, logical
  axes, the parameter count, the cache and the forwards follow from the
  description. The defaults are the GPT-J-style *parallel* attention+MLP
  block (one residual add, fuses well) with rotary position embeddings,
  RMSNorm, optional GQA (n_kv_heads); the other forms are a sequential
  two-norm block, gated FFNs, latent attention (`mixer="mla"`), and
  dropless routed experts with a shared expert (`moe_impl="dropless"`):
  `TransformerConfig.glm47_flash()` is all of them at once; a latent
  block may also own an indexer that picks the cache rows its queries
  attend (`index_topk`, `indexer_types`), and a routed layer may hold a
  share of its experts (`moe_experts_held`); and a model may hold layers
  of two KINDS (`layer_types`): beside the attention layers, layers whose
  mixer is a state-space recurrence (`ops/ssm.py`: a fixed-size state a
  head and a short convolution), or layers of a second ATTENTION kind
  that attend a window of the last rows and a learned sink ("window":
  their own KV head count and rotary base; such a model may route its
  FFNs and lead with dense layers), or layers whose mixer is a gated
  delta rule ("kda", ``ops/kda.py``: a matrix state a head under a decay
  a channel, beside attention layers of either mixer, every FFN routed
  but the leading ones'), or a decoder-hybrid-decoder's five kinds in
  one list ("mamba": a recurrence with a decay a (channel, state) pair,
  ``ops/mamba.py``; "window" and "attention" layers with differential
  attention; and two kinds that keep NOTHING of their own: "cross" layers
  that attend the one "attention" layer's rows and "gmu" layers gated by
  the last "mamba" layer's output), or layers that attend their own
  window of rows exactly and every earlier window through pooled chunk
  summaries ("eva", ``ops/eva.py``: such a model has no layer that keeps
  every row), or layers that are ONE branch each (``block`` "single":
  a state-space mixer, an attention or a routed FFN alone, "experts",
  whose experts may live in a latent: ``moe_latent``), one stack of
  parameters a kind, run in the order the list gives;
- every parameter carries logical axis names (`param_logical_axes`) mapped
  to mesh axes by `ray_tpu.parallel.AxisRules` — TP/SP/DP/FSDP are sharding
  annotations, not code changes;
- attention pluggable: 'dense' (XLA-fused), 'ring' (sequence-parallel over
  the sp mesh axis), 'flash' (Pallas kernel).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import causal_attention, window_attention
from ray_tpu.ops.eva import eva_attention, eva_pool
from ray_tpu.ops.kda import kda_chunked
from ray_tpu.ops.mamba import mamba_scan
from ray_tpu.ops.ssm import causal_conv, ssm_chunked


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: Optional[int] = None  # None => MHA
    d_head: int = 64
    d_ff: int = 2048
    rotary_dim: int = 32
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16  # activation/compute dtype
    param_dtype: Any = jnp.float32
    attn_impl: str = "dense"  # dense | ring | ulysses | flash
    # Mixture-of-experts FFN (0 = dense MLP). Experts shard over the `ep`
    # mesh axis; dispatch/combine einsums carry GSPMD sharding constraints so
    # XLA inserts the expert all-to-all (reference has NO EP — SURVEY §2.5).
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 2.0
    moe_aux_weight: float = 0.01
    remat: bool = False
    # What the checkpointed layer saves: "dots" keeps matmul outputs (cheap
    # elementwise recompute only, ~0 extra FLOPs), "full" saves nothing
    # (classic full-layer remat, ~+33% recompute — only for memory-bound
    # configs).
    remat_policy: str = "dots"
    tie_embeddings: bool = False
    # ---- the block's description; the defaults are GPT-J's block ----
    # Mixer: "mha" (MHA/GQA, the fields above) or "mla" (latent attention:
    # queries through a q_lora_rank bottleneck, keys and values expanded
    # from one kv_lora_rank latent per token, plus a qk_rope_dim rotary key
    # that all heads share; d_head and rotary_dim are then unused). The
    # cache a mixer keeps follows from it (generation.init_kv_cache).
    mixer: str = "mha"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # "parallel": y = x + attn(ln1 x) + ffn(ln1 x), one norm;
    # "sequential": h = x + attn(ln1 x), y = h + ffn(ln2 h).
    residual: str = "parallel"
    activation: str = "gelu"  # gelu | silu | relu2
    gated_ffn: bool = False  # wo(act(wg x) * wi x) instead of wo(act(wi x))
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # Routed experts. "capacity": GShard dispatch with dropped overflow
    # (ops/moe.moe_ffn, softmax gates, ungated experts of width d_ff).
    # "dropless": ops/moe.routed_ffn: sigmoid scores, the moe_top_k
    # largest of score + bias chosen, weights from the scores, normalised,
    # times moe_route_scale; gated experts of width moe_d_ff;
    # moe_shared_experts experts that every token takes. The first
    # n_dense_layers layers keep a dense FFN of width d_ff (their weights
    # are params["dense_layers"]; params["layers"] holds the rest).
    moe_impl: str = "capacity"
    moe_d_ff: int = 0
    moe_shared_experts: int = 0
    moe_route_scale: float = 1.0
    n_dense_layers: int = 0
    # A chip's share of a layer's experts: this stack holds the
    # moe_experts_held experts from moe_first_expert on (0 = all of them).
    # The router keeps its moe_experts outputs and its moe_top_k a token;
    # what an absent expert would have added is left out (ops/moe.py).
    moe_experts_held: int = 0
    moe_first_expert: int = 0
    # Learned sparse attention over the latent cache (mixer "mla" only;
    # index_topk 0 = every row is attended). A layer whose indexer_types
    # entry is "full" owns an indexer: index_n_heads queries of
    # index_head_dim from the query latent, ONE key a token from the
    # layer's input (LayerNorm, rotary on its first qk_rope_dim dims), a
    # weight a head; row s scores sum_j w_j relu(q_j . k_s) for query t,
    # and the query attends the min(t + 1, index_topk) best rows s <= t,
    # softmax over those alone. A "shared" layer has no indexer and
    # attends the choice of the nearest "full" layer below it (the first
    # layer is "full"). One entry a layer, dense layers first.
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    indexer_types: Tuple[str, ...] = ()
    # Layers of two kinds in one model (mixer "mha", sequential residual,
    # dense FFN). layer_types: one entry a layer, "attention" (the mixer
    # above) or "ssm"; empty = every layer attends. An "ssm" layer's mixer
    # is a state-space recurrence (ops/ssm.py): [z | xBC | dt] = W_in h of
    # widths ssm_inner | ssm_conv_width | ssm_heads; xBC through a causal
    # depthwise convolution of ssm_conv taps and a SiLU, split into x
    # (ssm_heads x ssm_head_dim), B and C (ssm_groups x ssm_state each);
    # dt = softplus(dt + dt_bias), A = -exp(a_log) a head; the state
    # H [ssm_head_dim, ssm_state] a head: H_t = exp(dt A) H_{t-1} + dt x
    # B^T, y = H C + D x; out = W_out RMSNorm(y * silu(z)). Its parameters
    # are params["ssm_layers"]; params["layers"] holds the attention layers.
    layer_types: Tuple[str, ...] = ()
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 256  # tokens a chunk of the prefill's scan
    # A second kind of attention layer, "window" in layer_types (beside
    # "attention"; not beside "ssm"): query t attends the rows t - window
    # < s <= t alone (``window`` rows, itself among them), through
    # window_kv_heads KV heads (0: as the other layers) with rotary base
    # window_rope_theta (0: rope_theta) and, with window_sink, a learned
    # logit b a head that joins the softmax's denominator and carries no
    # value: p[t,s] = exp(a[t,s]) / (exp(b) + sum_s' exp(a[t,s'])). What
    # such a layer keeps of a sequence is its last ``window`` rows
    # (generation.init_kv_cache: a ring, a STATE leaf). Its parameters
    # are params["window_layers"] (the mixer's under "swa"); such a model
    # may route its FFNs (moe_impl "dropless"), its leading dense layers
    # all of kind "attention". For every "mha" layer: values of width
    # v_head_dim (0: d_head, as the keys) and multiplied by value_scale.
    window: int = 0
    window_kv_heads: int = 0
    window_rope_theta: float = 0.0
    window_sink: bool = False
    value_scale: float = 1.0
    # Four scalars a block may multiply by (1 / None: the usual forms).
    # x_0 = embed_scale * E[token]; x += residual_scale * Mixer(..) and
    # x += residual_scale * FFN(..); logits = logit_scale * (x E^T);
    # attention scores q . k * attn_scale (None: 1 / sqrt(d_head)).
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0
    attn_scale: Optional[float] = None
    # A linear-attention kind of layer, "kda" in layer_types (beside
    # "attention" layers of either mixer; not beside "ssm" or "window"):
    # kda_heads heads whose keys AND values are kda_head_dim wide (D).
    # [q | k | v] = silu(conv(h W_qkv)): one causal depthwise convolution
    # of kda_conv taps over the three streams, no bias; q / ||q|| / sqrt(D)
    # and k / ||k|| a head. Log-decay a channel g = -exp(a_log) softplus(
    # (h W_fa) W_fb + dt_bias) (a_log a head, the low-rank pair kda_head_dim
    # wide), write strength beta = sigmoid(h W_b) a head. The state S
    # [D, D] a head in float32: S <- Diag(exp g) S; S <- S + beta k (v -
    # S^T k)^T; o = S^T q (ops/kda.py). out = W_o (RMSNorm_w(o) a head x
    # sigmoid((h W_ga) W_gb)). Its parameters are params["kda_layers"]
    # (the mixer's under "kda"); such a model may route its FFNs (moe_impl
    # "dropless"), and its leading dense layers, all of ONE kind, may be
    # "kda" layers (params["dense_layers"] then holds a "kda" mixer).
    # For a latent mixer beside them or alone: q_lora_rank 0 projects the
    # queries directly (W_q [d, H, nope + rope], no bottleneck, no norm),
    # and without mla_rope the qk_rope_dim dims all heads share are kept
    # and NOT rotated (no positional term).
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_chunk: int = 64  # tokens a chunk of the prefill's delta rule
    mla_rope: bool = True
    # A decoder-hybrid-decoder (SambaY, Phi-4-mini-flash): three more kinds
    # in layer_types (mixer "mha", sequential residual, dense FFN), beside
    # "attention" and "window" layers. "mamba": a Mamba-1 mixer over
    # mamba_inner channels and mamba_state state dims: [x | z] = W h; x =
    # silu(conv(x)), causal depthwise, mamba_conv taps, with bias; [dt_low |
    # B | C] = W_x x (mamba_dt_rank + 2 x mamba_state); dt = softplus(W_dt
    # dt_low + b) a channel; A = -exp(a_log) [state, channel]; h_t[n, c] =
    # exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]; y_t[c] =
    # sum_n h_t[n, c] C_t[n] + D[c] x_t[c]; out = W_out (y * silu(z))
    # (ops/mamba.py). "gmu", a gated memory unit: out = W_2 (m * silu(W_1
    # h)), m the y (before its gate) of the nearest "mamba" layer below:
    # no state, no cache. "cross": queries and an output projection alone,
    # attending the K/V rows of the LAST "attention" layer below, every row
    # s <= t: no K/V weights, no cache of its own. Their parameters are
    # params["mamba_layers" | "gmu_layers" | "cross_layers"]. For every
    # "mha" layer of any model: norm "layer" is LayerNorm with a scale and a
    # bias (mean subtracted) where "rms" is RMSNorm; attn_bias puts a bias
    # on the q, k, v and output projections; diff_attn pairs the heads:
    # query heads (2i, 2i + 1) and KV heads (2j, 2j + 1), j = i // (n_heads
    # / kv_heads), V_j = [v_2j | v_2j+1]; o_i = A(q_2i, k_2j, V_j) - lambda
    # A(q_2i+1, k_2j+1, V_j), lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
    # lambda_init, lambda_init = 0.8 - 0.6 exp(-0.3 depth) by the layer's
    # index in the model; then RMSNorm_w(o_i) over its 2 x d_head channels
    # times (1 - lambda_init); W_o takes the n_heads / 2 outputs.
    norm: str = "rms"
    attn_bias: bool = False
    diff_attn: bool = False
    mamba_inner: int = 0
    mamba_state: int = 0
    mamba_conv: int = 4
    mamba_dt_rank: int = 0
    # EVA layers (EvaByte; arXiv:2302.04542), "eva" in layer_types, which
    # then lists nothing else: MHA projections (rotary as the other "mha"
    # layers') and two learned d_head-vectors a head, phi and mu. Token t
    # lies in window t // eva_window, chunk c holds eva_chunk tokens; a
    # chunk's summary is k~_c = sum_j softmax_j(k_j . phi / sqrt(d_head))
    # k_j over its tokens' ROTATED keys and v~_c the same under mu over
    # its values (float32 softmax). Query t attends, in ONE softmax, the
    # summaries of every chunk of every window before its own and the
    # tokens of its own window up to itself (ops/eva.py). What a slot keeps
    # is the closed windows' summaries and the open window's rows
    # (generation._eva_keeps). Its parameters are params["eva_layers"] (the
    # mixer's under "eva"). Three more things such a model does, each a
    # field any model may set: n_pred_heads linear heads side by side in
    # lm_head [d, n_pred_heads x vocab_size], logits [.., n_pred_heads,
    # vocab_size] in float32 (head i scores token t + 1 + i; the served
    # path samples from head 0); residual_f32 carries the residual stream
    # in float32 (each branch's output is added to it unrounded; the norms
    # hand the branches the compute dtype); norm_unit_offset scales an
    # RMSNorm by 1 + w, w the stored parameter.
    eva_window: int = 0
    eva_chunk: int = 0
    n_pred_heads: int = 1
    residual_f32: bool = False
    norm_unit_offset: bool = False
    # A layer that is ONE branch (Nemotron-H; arXiv:2504.03624): with block
    # "single" every layer is x + Branch(ln1 x), one norm, and
    # layer_types says which branch: "attention" (the "mha" mixer), "ssm"
    # (the recurrence above) or "experts", a dropless routed FFN alone
    # (params["expert_layers"]: ln1 and "moe"), which keeps nothing of a
    # slot. "pair" is every other model's block: a mixer AND an FFN a
    # layer. Three more sizes a routed or a state-space layer of any
    # model may set. moe_latent (0: none): the routed experts live in a
    # latent moe_latent wide, reached through ONE matrix latent_in [d,
    # moe_latent] and left through ONE latent_out [moe_latent, d]: the
    # router reads the layer's input, expert i is W2_i act(W1_i u) on u =
    # h latent_in (gated as gated_ffn says), the routed sum goes through
    # latent_out, and the shared expert works on the full width.
    # moe_shared_d_ff (0: moe_d_ff): the width of a shared expert.
    # ssm_norm_groups: the state-space mixer's gated norm takes its mean
    # square over each of that many groups of channels (1: over all).
    # activation "relu2" is relu(x)^2.
    block: str = "pair"
    moe_latent: int = 0
    moe_shared_d_ff: int = 0
    ssm_norm_groups: int = 1

    def __post_init__(self):
        if self.layer_types:
            kinds = tuple(self.layer_types)
            single = self.block == "single"
            if len(kinds) != self.n_layers or set(kinds) - {
                    "attention", "ssm", "window", "kda", "mamba", "gmu",
                    "cross", "eva", "experts"} or (
                    self.mixer != "mha" and set(kinds) - {
                        "attention", "kda"}) or (
                    self.residual != "sequential") or (
                    "ssm" in kinds and (
                        (self.moe_experts and not single)
                        or "window" in kinds
                        or not self.ssm_heads * self.ssm_head_dim
                        * self.ssm_state or self.ssm_heads
                        % self.ssm_groups or self.ssm_inner
                        % self.ssm_norm_groups)):
                raise ValueError(
                    "layer_types needs one entry a layer ('attention' | "
                    "'ssm' | 'window' | 'kda' | 'mamba' | 'gmu' | 'cross' | "
                    "'eva' | 'experts'), "
                    "mixer 'mha' (beside 'kda' layers alone: either "
                    "mixer), a sequential block; 'attention' stands beside "
                    "every kind, 'window' beside all but 'ssm' and 'kda', "
                    "'mamba', 'gmu' and 'cross' beside each other, "
                    "'attention' and 'window'; beside 'ssm' layers a dense "
                    "FFN (routed layers in a 'single' block alone) and the "
                    "ssm_* sizes (heads a multiple of groups, channels of "
                    "ssm_norm_groups)")
            routed = "experts" in kinds
            if (routed and not single) or (single and (
                    set(kinds) - {"attention", "ssm", "experts"}
                    or self.mixer != "mha" or self.n_dense_layers
                    or routed != bool(self.moe_experts)
                    or (routed and self.moe_impl != "dropless"))):
                raise ValueError(
                    "an 'experts' layer stands in a 'single' block alone, "
                    "beside 'attention' and 'ssm' layers: mixer 'mha', "
                    "dropless experts (none without such a layer), no "
                    "leading dense layer")
            if "eva" in kinds and (
                    set(kinds) != {"eva"} or self.moe_experts
                    or self.eva_chunk < 1 or self.eva_window < self.eva_chunk
                    or self.eva_window % self.eva_chunk or self.diff_attn
                    or self.n_kv_heads not in (None, self.n_heads)
                    or self.v_head_dim not in (0, self.d_head)):
                raise ValueError(
                    "'eva' layers stand alone, under a dense FFN: MHA with "
                    "values as wide as keys, eva_window a multiple of "
                    "eva_chunk >= 1")
            n_dense = self.n_dense_layers if self.moe_experts else 0
            upper = set(kinds) & {"mamba", "gmu", "cross"}
            if upper and (
                    set(kinds) & {"ssm", "kda"} or self.moe_experts
                    or ("mamba" in kinds and (
                        not self.mamba_inner * self.mamba_state
                        * self.mamba_dt_rank or self.mamba_inner % 8
                        or self.mamba_conv < 2))
                    or ("gmu" in kinds and "mamba" not in
                        kinds[:kinds.index("gmu")])
                    or ("cross" in kinds and (
                        "attention" not in kinds[:kinds.index("cross")]
                        or "attention" in kinds[kinds.index("cross"):]))):
                raise ValueError(
                    "'mamba', 'gmu' and 'cross' layers stand beside "
                    "'attention' and 'window' layers alone, under a dense "
                    "FFN; a 'mamba' layer needs mamba_inner (a multiple of "
                    "8), mamba_state, mamba_dt_rank and mamba_conv >= 2, a "
                    "'gmu' layer a 'mamba' layer below it, and a 'cross' "
                    "layer an 'attention' layer below it and none above")
            if "kda" in kinds and (
                    set(kinds) - {"attention", "kda"}
                    or not self.kda_heads * self.kda_head_dim
                    or self.kda_conv < 2 or len(set(kinds[:n_dense])) > 1
                    or (self.moe_experts and self.moe_impl != "dropless")):
                raise ValueError(
                    "a 'kda' layer stands beside 'attention' layers alone "
                    "and needs kda_heads, kda_head_dim, kda_conv >= 2, "
                    "dropless experts where the FFNs are routed, and "
                    "leading dense layers of one kind")
            if "window" in kinds and (
                    self.window < 1 or self.n_heads % (
                        self.window_kv_heads or self.kv_heads)
                    or set(kinds[:n_dense]) - {"attention"} or (
                        self.moe_experts and self.moe_impl != "dropless")):
                raise ValueError(
                    "a 'window' layer needs window >= 1, n_heads a multiple "
                    "of window_kv_heads, dropless experts where the FFNs "
                    "are routed, and leading dense layers of kind "
                    "'attention'")
            object.__setattr__(self, "layer_types", kinds)
        elif self.window:
            raise ValueError("window needs 'window' layers in layer_types")
        if self.block not in ("pair", "single") or (
                self.block == "single" and not self.layer_types) or (
                (self.moe_latent or self.moe_shared_d_ff) and not (
                    self.moe_experts and self.moe_impl == "dropless")):
            raise ValueError(
                "block is 'pair' or 'single' (with layer_types); moe_latent "
                "and moe_shared_d_ff need dropless experts")
        if self.n_pred_heads < 1 or (self.n_pred_heads > 1
                                     and self.tie_embeddings) or (
                self.norm_unit_offset and self.norm != "rms"):
            raise ValueError(
                "n_pred_heads >= 1 (several: an untied head); "
                "norm_unit_offset needs norm 'rms'")
        if self.norm not in ("rms", "layer") or (
                (self.diff_attn or self.attn_bias) and self.mixer != "mha"
                ) or (self.diff_attn and (
                    self.n_heads % 2 or self.kv_heads % 2
                    or (self.window_kv_heads or 2) % 2)):
            raise ValueError(
                "norm is 'rms' or 'layer'; attn_bias and diff_attn need "
                "mixer 'mha', diff_attn even counts of heads")
        if self.index_topk:
            kinds = tuple(self.indexer_types)
            if self.mixer != "mla" or len(kinds) != self.n_layers or (
                    kinds and kinds[0] != "full") or (
                    set(kinds) - {"full", "shared"}):
                raise ValueError(
                    "index_topk needs mixer 'mla' and one indexer_types "
                    "entry a layer ('full' | 'shared'), the first 'full'")
            object.__setattr__(self, "indexer_types", kinds)

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def experts_held(self) -> int:
        return self.moe_experts_held or self.moe_experts

    def index_slots(self, first: int = 0, n: Optional[int] = None):
        """For the layers ``first .. first + n``: the slot (counted over
        the whole model's "full" layers) of the indexer whose choice each
        attends. A "full" layer's is its own."""
        owns = [k == "full" for k in self.indexer_types]
        slots = [sum(owns[:i + 1]) - 1 for i in range(len(owns))]
        return slots[first:None if n is None else first + n]

    @property
    def n_index_layers(self) -> int:
        return sum(k == "full" for k in self.indexer_types
                   ) if self.index_topk else 0

    @property
    def n_ssm_layers(self) -> int:
        return sum(k == "ssm" for k in self.layer_types)

    @property
    def n_window_layers(self) -> int:
        return sum(k == "window" for k in self.layer_types)

    @property
    def n_kda_layers(self) -> int:
        return sum(k == "kda" for k in self.layer_types)

    def n_of(self, kind: str) -> int:
        """How many of the model's layers ``layer_types`` lists as
        ``kind``."""
        return sum(k == kind for k in self.layer_types)

    @property
    def n_attn_layers(self) -> int:
        """Layers that attend every row AND keep them: the layers the K/V
        cache holds rows for."""
        return (self.n_layers - self.n_ssm_layers - self.n_window_layers
                - self.n_kda_layers - self.n_of("mamba") - self.n_of("gmu")
                - self.n_of("cross") - self.n_of("eva")
                - self.n_of("experts"))

    @property
    def kda_inner(self) -> int:
        """Width of one of a "kda" layer's three streams."""
        return self.kda_heads * self.kda_head_dim

    @property
    def v_dim(self) -> int:
        """Width of an "mha" head's value."""
        return self.v_head_dim or self.d_head

    def mha_kind(self, window: bool = False):
        """(KV heads, rotary base) of an "mha" layer of either kind."""
        if window:
            return (self.window_kv_heads or self.kv_heads,
                    self.window_rope_theta or self.rope_theta)
        return self.kv_heads, self.rope_theta

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_width(self) -> int:
        """Channels of the convolution: x, B and C side by side."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def n_expert_layers(self) -> int:
        if self.block == "single":
            return self.n_of("experts")
        return self.n_layers - self.n_dense_layers if self.moe_experts else 0

    def dense_variant(self) -> "TransformerConfig":
        """The same block with a dense FFN: the leading layers' config."""
        return dataclasses.replace(self, moe_experts=0, n_dense_layers=0)

    def param_count(self) -> int:
        d, f, h, kv, dh = (
            self.d_model,
            self.d_ff,
            self.n_heads,
            self.kv_heads,
            self.d_head,
        )
        mats = 3 if self.gated_ffn else 2
        dense_ffn = mats * d * f
        if not self.moe_experts:
            ffn = dense_ffn
        elif self.moe_impl == "dropless":
            fe, dl = self.moe_d_ff or f, self.moe_latent or d
            fs = (self.moe_shared_d_ff or fe) * self.moe_shared_experts
            ffn = ((d + 1) * self.moe_experts
                   + mats * dl * fe * self.experts_held + mats * d * fs
                   + (2 * d * dl if self.moe_latent else 0))
        else:
            ffn = d * self.moe_experts + 2 * self.moe_experts * d * f
        if self.mixer == "mla":
            qk = self.qk_nope_dim + self.qk_rope_dim
            queries = (d * self.q_lora_rank + self.q_lora_rank
                       + self.q_lora_rank * h * qk
                       ) if self.q_lora_rank else d * h * qk
            attn = (queries
                    + d * (self.kv_lora_rank + self.qk_rope_dim)
                    + self.kv_lora_rank
                    + self.kv_lora_rank * h * (self.qk_nope_dim
                                               + self.v_head_dim)
                    + h * self.v_head_dim * d)
        else:
            attn = d * dh * (h + kv) + d * self.v_dim * kv + h * self.v_dim * d
        wkv = self.mha_kind(True)[0]
        window = (d * dh * (h + wkv) + d * self.v_dim * wkv
                  + h * self.v_dim * d + h * self.window_sink)
        # a "cross" layer's queries and output projection
        cross = d * dh * h + h * self.v_dim * d
        if self.attn_bias:
            attn += dh * (h + kv) + self.v_dim * kv + d
            window += dh * (h + wkv) + self.v_dim * wkv + d
            cross += dh * h + d
        if self.diff_attn:  # four lambda vectors and the pair's norm
            attn, window, cross = (n + 4 * dh + 2 * self.v_dim
                                   for n in (attn, window, cross))
        mi, ms, mr = self.mamba_inner, self.mamba_state, self.mamba_dt_rank
        mamba = (d * 2 * mi + mi * (self.mamba_conv + 1)
                 + mi * (mr + 2 * ms) + mr * mi + mi + ms * mi + mi + mi * d)
        upper = (self.n_of("mamba") * mamba + self.n_of("gmu") * 2 * d * mi
                 + self.n_of("cross") * cross
                 + self.n_of("eva") * (attn + 2 * h * dh))
        norms = d * (2 if self.residual == "sequential" else 1) * (
            2 if self.norm == "layer" else 1)
        n_dense = self.n_dense_layers if self.moe_experts else 0
        indexer = self.n_index_layers * (
            self.q_lora_rank * self.index_n_heads * self.index_head_dim
            + d * (self.index_head_dim + self.index_n_heads)
            + 2 * self.index_head_dim)
        inner, width = self.ssm_inner, self.ssm_conv_width
        ssm = (d * (inner + width + self.ssm_heads) + inner * d
               + width * (self.ssm_conv + 1) + 3 * self.ssm_heads + inner)
        ki, kd = self.kda_inner, self.kda_head_dim
        kda = (d * 3 * ki + self.kda_conv * 3 * ki + self.kda_heads + ki
               + 2 * (d * kd + kd * ki) + d * self.kda_heads + kd + ki * d)
        layers = (self.n_attn_layers * attn + self.n_ssm_layers * ssm
                  + self.n_window_layers * window + self.n_kda_layers * kda
                  + upper + self.n_layers * norms + n_dense * dense_ffn
                  + (self.n_layers - n_dense) * ffn + indexer)
        if self.block == "single":  # one branch and one norm a layer
            layers = (self.n_attn_layers * attn + self.n_ssm_layers * ssm
                      + self.n_expert_layers * ffn + self.n_layers * d)
        head = (0 if self.tie_embeddings
                else d * self.vocab_size * self.n_pred_heads)
        final = d * (2 if self.norm == "layer" else 1)
        return self.vocab_size * d + layers + final + head

    # ---- canonical sizes ----
    @staticmethod
    def gptj_6b() -> "TransformerConfig":
        """The north-star fine-tune model size (GPT-J-6B-equivalent)."""
        return TransformerConfig(
            vocab_size=50432, d_model=4096, n_layers=28, n_heads=16,
            d_head=256, d_ff=16384, rotary_dim=64, max_seq_len=2048,
        )

    @staticmethod
    def small_1b() -> "TransformerConfig":
        return TransformerConfig(
            vocab_size=32000, d_model=2048, n_layers=16, n_heads=16,
            d_head=128, d_ff=8192, rotary_dim=64, max_seq_len=2048,
        )

    @staticmethod
    def bench_400m() -> "TransformerConfig":
        # 8 heads x 128 head_dim (vs 16x64): same params/FLOPs, but 128-lane
        # blocks map 1:1 onto the MXU/VPU tiling for the flash kernel.
        return TransformerConfig(
            vocab_size=32000, d_model=1024, n_layers=24, n_heads=8,
            d_head=128, d_ff=4096, rotary_dim=64, max_seq_len=2048,
            attn_impl="flash", remat=True, remat_policy="dots",
        )

    @staticmethod
    def serve_7b() -> "TransformerConfig":
        """7B-class serving config (BASELINE Serve north star is
        Llama-2-7B): MHA 32x128 over d=4096, 32 layers, dense-gelu MLP at
        d_ff=16384 — 6.7B params, the same count as Llama-2's swiglu at
        11008. Served int8 (models/quant.py) on one chip: ~6.5GB weights
        + bf16 KV."""
        return TransformerConfig(
            vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
            d_head=128, d_ff=16384, rotary_dim=128, max_seq_len=2048,
            attn_impl="dense", remat=False,
        )

    @staticmethod
    def glm47_flash(n_layers: int = 47, **kw) -> "TransformerConfig":
        """GLM-4.7-Flash (zai-org/GLM-4.7-Flash config.json, model_type
        glm4_moe_lite) at its published widths: latent attention with 20
        heads, one dense layer, then layers of 64 routed experts (4 a
        token, sigmoid scores, bias-corrected choice) and a shared one.
        ``n_layers`` counts the dense layer. Its multi-token-prediction
        module (num_nextn_predict_layers 1) is not part of the block."""
        base = dict(
            vocab_size=154880, d_model=2048, n_layers=n_layers, n_heads=20,
            d_ff=10240, max_seq_len=202752, mixer="mla", q_lora_rank=768,
            kv_lora_rank=512, qk_nope_dim=192, qk_rope_dim=64,
            v_head_dim=256, residual="sequential", activation="silu",
            gated_ffn=True, norm_eps=1e-5, rope_theta=1e6, moe_experts=64,
            moe_top_k=4, moe_impl="dropless", moe_d_ff=1536,
            moe_shared_experts=1, moe_route_scale=1.8, n_dense_layers=1,
        )
        base.update(kw)
        return TransformerConfig(**base)

    @staticmethod
    def glm52(n_layers: int = 78, **kw) -> "TransformerConfig":
        """GLM-5.2 (zai-org/GLM-5.2 config.json, model_type glm_moe_dsa)
        at its published widths: latent attention with 64 heads and a
        learned selection of 2,048 cache rows a query (an indexer on the
        three dense layers and then on every fourth layer, its choice
        shared by the three layers above it), three dense layers, then
        layers of 256 routed experts (8 a token) and a shared one.
        ``n_layers`` counts the dense layers; a cut passes its own
        ``n_dense_layers`` / ``indexer_types`` / ``moe_experts_held``. The
        multi-token-prediction module is not part of the block."""
        dense = kw.get("n_dense_layers", 3)
        base = dict(
            vocab_size=154880, d_model=6144, n_layers=n_layers, n_heads=64,
            d_ff=12288, max_seq_len=1048576, mixer="mla", q_lora_rank=2048,
            kv_lora_rank=512, qk_nope_dim=192, qk_rope_dim=64,
            v_head_dim=256, residual="sequential", activation="silu",
            gated_ffn=True, norm_eps=1e-5, rope_theta=8e6, moe_experts=256,
            moe_top_k=8, moe_impl="dropless", moe_d_ff=2048,
            moe_shared_experts=1, moe_route_scale=2.5, n_dense_layers=dense,
            index_topk=2048, index_n_heads=32, index_head_dim=128,
            indexer_types=tuple(
                "full" if i < dense or (i - dense) % 4 == 3 else "shared"
                for i in range(n_layers)),
        )
        base.update(kw)
        return TransformerConfig(**base)

    @staticmethod
    def granite4_h_micro(**kw) -> "TransformerConfig":
        """granite-4.0-h-micro (ibm-granite/granite-4.0-h-micro
        config.json, model_type granitemoehybrid) as published: 40 layers
        in a period of ten, attention (32 query and 8 KV heads of 64, no
        positional term, scores x 1/64) at 5, 15, 25, 35 and state-space
        layers (64 heads of 64, state 128, one group, 4 taps, chunks of
        256) elsewhere; every layer's gated FFN 8,192 wide; the embedding
        x 12, each branch x 0.22, the tied head's logits / 8."""
        base = dict(
            vocab_size=100352, d_model=2048, n_layers=40, n_heads=32,
            n_kv_heads=8, d_head=64, d_ff=8192, rotary_dim=0,
            max_seq_len=131072, residual="sequential", activation="silu",
            gated_ffn=True, norm_eps=1e-5, tie_embeddings=True,
            layer_types=tuple("attention" if i % 10 == 5 else "ssm"
                              for i in range(40)),
            ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_groups=1,
            ssm_conv=4, ssm_chunk=256, embed_scale=12.0,
            residual_scale=0.22, logit_scale=1 / 8, attn_scale=1 / 64,
        )
        base.update(kw)
        return TransformerConfig(**base)

    @staticmethod
    def tiny_ssm_hybrid(**kw) -> "TransformerConfig":
        """The same kind of model at test size (CPU): six layers
        ``ssm ssm attention ssm ssm attention`` (a period of three, twice),
        4 state-space heads of 8 over a state of 16, chunks of 8; two KV
        heads of 64, which lie flat in their cache row as the model's do
        (``generation._kv_row``)."""
        base = dict(
            vocab_size=256, d_model=64, n_layers=6, n_heads=4, n_kv_heads=2,
            d_head=64, d_ff=96, rotary_dim=0, max_seq_len=1024,
            residual="sequential", activation="silu", gated_ffn=True,
            norm_eps=1e-5, tie_embeddings=True,
            layer_types=("ssm", "ssm", "attention") * 2,
            ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_groups=1,
            ssm_conv=4, ssm_chunk=8, embed_scale=12.0, residual_scale=0.22,
            logit_scale=1 / 8, attn_scale=1 / 64,
        )
        base.update(kw)
        return TransformerConfig(**base)

    @staticmethod
    def nemotron3_super(pattern: str = (
            "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*E"
            "MEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME"),
            **kw) -> "TransformerConfig":
        """NVIDIA-Nemotron-3-Super-120B-A12B (nvidia/NVIDIA-Nemotron-3-
        Super-120B-A12B-BF16 config.json, model_type nemotron_h) at its
        published widths: 88 layers of ONE branch each, by ``pattern``
        (``hybrid_override_pattern``): ``M`` a Mamba-2 mixer (128 heads of
        64, state 128, 8 B/C groups, the gated norm over 8 groups, chunks
        of 128), ``*`` attention (32 query and 2 KV heads of 128, no
        positional term), ``E`` 512 routed squared-ReLU experts 2,688 wide
        in a latent of 1,024 (22 a token, sigmoid scores, weights x 5)
        beside a shared expert 5,376 wide on the full 4,096; an untied
        head. A cut passes its own ``pattern`` / ``moe_experts_held`` /
        ``vocab_size``. The multi-token-prediction module is not part of
        the block."""
        kinds = {"M": "ssm", "*": "attention", "E": "experts"}
        base = dict(
            vocab_size=131072, d_model=4096, n_layers=len(pattern),
            n_heads=32, n_kv_heads=2, d_head=128, d_ff=2688, rotary_dim=0,
            max_seq_len=262144, residual="sequential", activation="relu2",
            gated_ffn=False, norm_eps=1e-5, block="single",
            layer_types=tuple(kinds[k] for k in pattern),
            ssm_heads=128, ssm_head_dim=64, ssm_state=128, ssm_groups=8,
            ssm_norm_groups=8, ssm_conv=4, ssm_chunk=128, moe_experts=512,
            moe_top_k=22, moe_impl="dropless", moe_d_ff=2688,
            moe_latent=1024, moe_shared_experts=1, moe_shared_d_ff=5376,
            moe_route_scale=5.0,
        )
        base.update(kw)
        return TransformerConfig(**base)

    @staticmethod
    def tiny_ssm_moe(**kw) -> "TransformerConfig":
        """The same kind of model at test size (CPU): seven layers ``M E M
        * E M E``, 4 state-space heads of 8 over a state of 16 in 2 groups
        (the gated norm over 2), chunks of 8; 4 query heads over 2 KV
        heads of 64 (flat in their cache row, as the model's two of 128);
        16 experts 24 wide in a latent of 32, 3 a token, of which the
        stack holds 4..7; a shared expert 48 wide."""
        base = dict(
            vocab_size=256, d_model=64, n_layers=7, n_heads=4, n_kv_heads=2,
            d_head=64, d_ff=24, rotary_dim=0, max_seq_len=1024,
            residual="sequential", activation="relu2", gated_ffn=False,
            norm_eps=1e-5, block="single",
            layer_types=("ssm", "experts", "ssm", "attention", "experts",
                         "ssm", "experts"),
            ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_groups=2,
            ssm_norm_groups=2, ssm_conv=4, ssm_chunk=8, moe_experts=16,
            moe_top_k=3, moe_impl="dropless", moe_d_ff=24, moe_latent=32,
            moe_shared_experts=1, moe_shared_d_ff=48, moe_route_scale=5.0,
            moe_experts_held=4, moe_first_expert=4,
        )
        base.update(kw)
        return TransformerConfig(**base)

    @staticmethod
    def mimo_v2_flash(n_layers: int = 48, **kw) -> "TransformerConfig":
        """MiMo-V2-Flash (XiaomiMiMo/MiMo-V2-Flash config.json, model_type
        mimo_v2_flash) at its published widths: 64 query heads with keys
        192 wide (rotary on the first 64) and values 128 wide (x 0.707);
        layers 0, 5, 11, 17, ... attend every row through 4 KV heads
        (rotary base 5e6), the others a window of 128 rows through 8 KV
        heads (base 1e4) with a learned sink a head; one dense layer,
        then layers of 256 routed experts (8 a token, sigmoid scores, no
        shared expert). A cut passes its own ``layer_types`` /
        ``moe_experts_held``. The three multi-token-prediction layers are
        not part of the block."""
        base = dict(
            vocab_size=152576, d_model=4096, n_layers=n_layers, n_heads=64,
            n_kv_heads=4, d_head=192, v_head_dim=128, d_ff=16384,
            rotary_dim=64, max_seq_len=262144, residual="sequential",
            activation="silu", gated_ffn=True, norm_eps=1e-5,
            rope_theta=5e6, value_scale=0.707, window=128,
            window_kv_heads=8, window_rope_theta=1e4, window_sink=True,
            layer_types=tuple(
                "attention" if i == 0 or i % 6 == 5 else "window"
                for i in range(n_layers)),
            moe_experts=256, moe_top_k=8, moe_impl="dropless",
            moe_d_ff=2048, n_dense_layers=1,
        )
        base.update(kw)
        return TransformerConfig(**base)

    @staticmethod
    def tiny_swa_moe(**kw) -> "TransformerConfig":
        """The same kind of model at test size (CPU): a dense full layer,
        then ``window window full window`` with 8 routed experts (2 a
        token); a window of 8 rows; 4 query heads over 2 KV heads (full)
        or 4 (window); keys 64 wide, which lie flat in their cache row as
        the model's 192 do (``generation._kv_rows``), values 32."""
        base = dict(
            vocab_size=256, d_model=64, n_layers=5, n_heads=4, n_kv_heads=2,
            d_head=64, v_head_dim=32, d_ff=96, rotary_dim=16,
            max_seq_len=1024, residual="sequential", activation="silu",
            gated_ffn=True, norm_eps=1e-5, rope_theta=5e6, value_scale=0.707,
            window=8, window_kv_heads=4, window_rope_theta=1e4,
            window_sink=True,
            layer_types=("attention", "window", "window", "attention",
                         "window"),
            moe_experts=8, moe_top_k=2, moe_impl="dropless", moe_d_ff=48,
            n_dense_layers=1,
        )
        base.update(kw)
        return TransformerConfig(**base)

    @staticmethod
    def kimi_linear(n_layers: int = 27, **kw) -> "TransformerConfig":
        """Kimi-Linear-48B-A3B (moonshotai/Kimi-Linear-48B-A3B-Instruct
        config.json, model_type kimi_linear) at its published widths: 27
        layers in a period of four, three "kda" layers (32 heads of 128,
        4 taps) and then a latent-attention layer (32 heads, direct
        queries, 512 + 64 wide rows, no rotation: no positional term
        anywhere); the first layer's FFN dense, the others 256 routed
        experts (8 a token, sigmoid scores, bias-corrected choice) and a
        shared one. A cut passes its own ``layer_types`` /
        ``moe_experts_held``."""
        base = dict(
            vocab_size=163840, d_model=2304, n_layers=n_layers, n_heads=32,
            d_ff=9216, max_seq_len=1048576, mixer="mla", q_lora_rank=0,
            kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
            v_head_dim=128, mla_rope=False, residual="sequential",
            activation="silu", gated_ffn=True, norm_eps=1e-5,
            layer_types=tuple(
                "attention" if i % 4 == 3 or i == n_layers - 1 else "kda"
                for i in range(n_layers)),
            kda_heads=32, kda_head_dim=128, kda_conv=4, kda_chunk=64,
            moe_experts=256, moe_top_k=8, moe_impl="dropless", moe_d_ff=1024,
            moe_shared_experts=1, moe_route_scale=2.446, n_dense_layers=1,
        )
        base.update(kw)
        return TransformerConfig(**base)

    @staticmethod
    def tiny_kda_moe(**kw) -> "TransformerConfig":
        """The same kind of model at test size (CPU): six layers ``kda
        (dense) kda kda attention kda attention``, 2 kda heads of 16 in
        chunks of 8; latent attention as ``tiny_mla_moe``'s with direct
        queries and no rotation; 8 routed experts (2 a token) and a shared
        one."""
        base = dict(
            vocab_size=256, d_model=64, n_layers=6, n_heads=4, d_ff=160,
            max_seq_len=1024, mixer="mla", q_lora_rank=0, kv_lora_rank=16,
            qk_nope_dim=12, qk_rope_dim=8, v_head_dim=16, mla_rope=False,
            residual="sequential", activation="silu", gated_ffn=True,
            norm_eps=1e-5,
            layer_types=("kda", "kda", "kda", "attention", "kda",
                         "attention"),
            kda_heads=2, kda_head_dim=16, kda_conv=4, kda_chunk=8,
            moe_experts=8, moe_top_k=2, moe_impl="dropless", moe_d_ff=48,
            moe_shared_experts=1, moe_route_scale=2.446, n_dense_layers=1,
        )
        base.update(kw)
        return TransformerConfig(**base)

    @staticmethod
    def phi4_mini_flash(**kw) -> "TransformerConfig":
        """Phi-4-mini-flash-reasoning (microsoft/Phi-4-mini-flash-reasoning
        config.json, model_type phi4flash; arXiv:2507.06607) as published:
        32 layers ``(mamba window) x 8, mamba attention, (gmu cross) x 7``.
        Mamba-1 over 5,120 channels (state 16, 4 taps, dt rank 160);
        differential attention with 40 query and 20 KV heads of 64, a
        window of 512 rows on the odd layers below the middle, every row
        at layer 17, whose rows the seven "cross" layers above attend too;
        the seven "gmu" layers are gated by layer 16's recurrence output.
        LayerNorm with a bias, projections with a bias, no positional
        term, gated SiLU FFNs 10,240 wide, a tied head."""
        base = dict(
            vocab_size=200064, d_model=2560, n_layers=32, n_heads=40,
            n_kv_heads=20, d_head=64, d_ff=10240, rotary_dim=0,
            max_seq_len=262144, residual="sequential", activation="silu",
            gated_ffn=True, norm_eps=1e-5, tie_embeddings=True,
            norm="layer", attn_bias=True, diff_attn=True, window=512,
            layer_types=("mamba", "window") * 8 + ("mamba", "attention")
            + ("gmu", "cross") * 7,
            mamba_inner=5120, mamba_state=16, mamba_conv=4,
            mamba_dt_rank=160,
        )
        base.update(kw)
        return TransformerConfig(**base)

    @staticmethod
    def tiny_sambay(**kw) -> "TransformerConfig":
        """The same kind of model at test size (CPU): eight layers ``mamba
        window mamba window | mamba attention | gmu cross``, 128 channels
        over a state of 16 (dt rank 4), a window of 8 rows, 8 query and 4
        KV heads of 64 (four differential heads over two pairs of KV
        heads)."""
        base = dict(
            vocab_size=256, d_model=64, n_layers=8, n_heads=8, n_kv_heads=4,
            d_head=64, d_ff=96, rotary_dim=0, max_seq_len=1024,
            residual="sequential", activation="silu", gated_ffn=True,
            norm_eps=1e-5, tie_embeddings=True, norm="layer",
            attn_bias=True, diff_attn=True, window=8,
            layer_types=("mamba", "window") * 2 + ("mamba", "attention",
                                                   "gmu", "cross"),
            mamba_inner=128, mamba_state=16, mamba_conv=4, mamba_dt_rank=4,
        )
        base.update(kw)
        return TransformerConfig(**base)

    @staticmethod
    def evabyte(n_layers: int = 32, **kw) -> "TransformerConfig":
        """EvaByte 6.5B (EvaByte/EvaByte config.json, model_type evabyte,
        attention_class eva) at its published widths: a byte-level model,
        vocabulary 320 (256 bytes and 64 specials), 32 layers of EVA
        attention (32 heads of 128, rotary over all 128 dims at base 1e5,
        windows of 2,048 bytes exact, every earlier window as 128 pooled
        summaries of 16-byte chunks) under gated SiLU FFNs 11,008 wide;
        RMSNorm scaled by 1 + w, the residual stream in float32, eight
        linear prediction heads whose logits stay float32."""
        base = dict(
            vocab_size=320, d_model=4096, n_layers=n_layers, n_heads=32,
            d_head=128, d_ff=11008, rotary_dim=128, max_seq_len=32768,
            residual="sequential", activation="silu", gated_ffn=True,
            norm_eps=1e-5, rope_theta=1e5,
            layer_types=("eva",) * n_layers, eva_window=2048, eva_chunk=16,
            n_pred_heads=8, residual_f32=True, norm_unit_offset=True,
        )
        base.update(kw)
        return TransformerConfig(**base)

    @staticmethod
    def tiny_eva(**kw) -> "TransformerConfig":
        """The same kind of model at test size (CPU): three layers, 4
        heads of 16, windows of 32 tokens in chunks of 4 (three windows
        close within a hundred tokens), 3 prediction heads."""
        base = dict(
            vocab_size=64, d_model=64, n_layers=3, n_heads=4, d_head=16,
            d_ff=96, rotary_dim=16, max_seq_len=256, residual="sequential",
            activation="silu", gated_ffn=True, norm_eps=1e-5, rope_theta=1e5,
            layer_types=("eva",) * 3, eva_window=32, eva_chunk=4,
            n_pred_heads=3, residual_f32=True, norm_unit_offset=True,
        )
        base.update(kw)
        return TransformerConfig(**base)

    @staticmethod
    def tiny_mla_moe(**kw) -> "TransformerConfig":
        """The same block at test size (CPU)."""
        base = dict(
            vocab_size=256, d_model=64, n_layers=3, n_heads=4, d_ff=160,
            max_seq_len=1024, mixer="mla", q_lora_rank=24, kv_lora_rank=16,
            qk_nope_dim=12, qk_rope_dim=8, v_head_dim=16,
            residual="sequential", activation="silu", gated_ffn=True,
            norm_eps=1e-5, rope_theta=1e6, moe_experts=8, moe_top_k=4,
            moe_impl="dropless", moe_d_ff=48, moe_shared_experts=1,
            moe_route_scale=1.8, n_dense_layers=1,
        )
        base.update(kw)
        return TransformerConfig(**base)

    @staticmethod
    def tiny_dsa_moe(**kw) -> "TransformerConfig":
        """``tiny_mla_moe`` with an indexer (GLM-5.2's kind of block) at
        test size: six layers ``full | shared shared shared full shared``,
        16 rows a query."""
        base = dict(
            n_layers=6, rope_theta=8e6, moe_top_k=2, moe_route_scale=2.5,
            index_topk=16, index_n_heads=4, index_head_dim=16,
            indexer_types=("full",) + ("shared",) * 3 + ("full", "shared"),
        )
        base.update(kw)
        return TransformerConfig.tiny_mla_moe(**base)

    @staticmethod
    def tiny(**kw) -> "TransformerConfig":
        base = dict(
            vocab_size=256, d_model=64, n_layers=2, n_heads=4,
            d_head=16, d_ff=128, rotary_dim=8, max_seq_len=128,
        )
        base.update(kw)
        return TransformerConfig(**base)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_params(config: TransformerConfig, rng: jax.Array) -> Dict:
    c = config
    k_emb, k_q, k_k, k_v, k_o, k_wi, k_wo, k_head = jax.random.split(rng, 8)
    pd = c.param_dtype

    def dense_init(key, shape, fan_in):
        return (jax.random.normal(key, shape) * (fan_in ** -0.5)).astype(pd)

    def norm_init(shape, key):
        # a LayerNorm's bias: 0 in a fresh model, here drawn so that a
        # comparison with a reference sees it; so is the w of a norm
        # scaled by 1 + w
        if c.norm_unit_offset:
            return {"scale": (0.02 * jax.random.normal(key, shape)
                              ).astype(pd)}
        return {"scale": jnp.ones(shape, pd),
                **({"bias": (0.02 * jax.random.normal(key, shape)
                             ).astype(pd)} if c.norm == "layer" else {})}

    def mha_extras(key, L, h_kv, queries_only=False):
        """What ``attn_bias`` and ``diff_attn`` add to an "mha" mixer (a
        "cross" layer's: the queries' and the output's alone). Biases are
        drawn, not 0, so that a comparison sees them; the four lambda
        vectors normal(0, 0.1) as the differential transformer draws
        them, the pair's norm 1."""
        ks = jax.random.split(jax.random.fold_in(key, 9), 5)
        out = {}
        if c.attn_bias:
            out["bq"] = 0.02 * jax.random.normal(
                ks[0], (L, c.n_heads, c.d_head))
            out["bo"] = 0.02 * jax.random.normal(ks[3], (L, c.d_model))
            if not queries_only:
                out["bk"] = 0.02 * jax.random.normal(
                    ks[1], (L, h_kv, c.d_head))
                out["bv"] = 0.02 * jax.random.normal(
                    ks[2], (L, h_kv, c.v_dim))
        if c.diff_attn:
            out["lambda"] = 0.1 * jax.random.normal(
                ks[4], (L, 4, c.d_head))
            out["subln"] = jnp.ones((L, 2 * c.v_dim))
        return {k: v.astype(pd) for k, v in out.items()}

    def gate(key, shape, fan_in):  # the third matrix of a gated FFN
        return {"wg": dense_init(jax.random.fold_in(key, 2), shape, fan_in)
                } if c.gated_ffn else {}

    def stack(lc: TransformerConfig, L: int, salt: int, first: int,
              attends: bool = True, window: bool = False,
              ffn: bool = True) -> Dict:
        """L layers of ``lc``'s block (the model's layers from ``first``
        on), stacked on a leading axis; without ``attends`` the norms and
        the FFN alone (another mixer's weights are the caller's); with
        ``window`` the mixer is a window layer's, under "swa"; without
        ``ffn`` no FFN (a "single" block's mixer layer, which has one norm
        whichever its branch)."""
        kq, kk, kv, ko, kwi, kwo = (
            (k_q, k_k, k_v, k_o, k_wi, k_wo) if not salt else
            [jax.random.fold_in(k, salt) for k in (k_q, k_k, k_v, k_o,
                                                   k_wi, k_wo)])
        d = lc.d_model
        layers = {"ln1": norm_init((L, d), jax.random.fold_in(kq, 21))}
        if lc.residual == "sequential" and lc.block == "pair":
            layers["ln2"] = norm_init((L, d), jax.random.fold_in(kq, 22))
        if not attends:
            pass
        elif lc.mixer == "mla":
            h, r_q, r_kv = lc.n_heads, lc.q_lora_rank, lc.kv_lora_rank
            qk = lc.qk_nope_dim + lc.qk_rope_dim
            layers["attn"] = {
                **({"wdq": dense_init(kq, (L, d, r_q), d),
                    "q_norm": jnp.ones((L, r_q), pd),
                    "wuq": dense_init(jax.random.fold_in(kq, 1),
                                      (L, r_q, h, qk), r_q)} if r_q else
                   {"wq": dense_init(kq, (L, d, h, qk), d)}),
                "wdkv": dense_init(kk, (L, d, r_kv + lc.qk_rope_dim), d),
                "kv_norm": jnp.ones((L, r_kv), pd),
                "wuk": dense_init(jax.random.fold_in(kk, 1),
                                  (L, r_kv, h, lc.qk_nope_dim), r_kv),
                "wuv": dense_init(kv, (L, r_kv, h, lc.v_head_dim), r_kv),
                "wo": dense_init(ko, (L, h, lc.v_head_dim, d),
                                 h * lc.v_head_dim),
            }
            n_own = lc.index_topk and sum(
                k == "full" for k in lc.indexer_types[first:first + L])
            if n_own:  # one indexer for each "full" layer of this stack
                ki = jax.random.fold_in(kq, 5)
                nI, dI = lc.index_n_heads, lc.index_head_dim
                layers["attn"]["indexer"] = {
                    "wq": dense_init(ki, (n_own, r_q, nI, dI), r_q),
                    "wk": dense_init(jax.random.fold_in(ki, 1),
                                     (n_own, d, dI), d),
                    "k_norm": {"scale": jnp.ones((n_own, dI), pd),
                               "bias": jnp.zeros((n_own, dI), pd)},
                    "ww": dense_init(jax.random.fold_in(ki, 2),
                                     (n_own, d, nI), d),
                }
        else:
            h_kv = lc.mha_kind(window)[0]
            layers["swa" if window else "attn"] = {
                "wq": dense_init(kq, (L, d, lc.n_heads, lc.d_head), d),
                "wk": dense_init(kk, (L, d, h_kv, lc.d_head), d),
                "wv": dense_init(kv, (L, d, h_kv, lc.v_dim), d),
                "wo": dense_init(ko, (L,) + wo_shape(lc),
                                 lc.n_heads * lc.v_dim),
                **mha_extras(kq, L, h_kv),
            }
            if window and lc.window_sink:
                # trained in a published model; here seeded around
                # ln(window), where the sink weighs about what the whole
                # window does: around 0 it would hold 1 part in window + 1
                # of the mass, which bf16 rounding hides, and no comparison
                # with a reference could tell a sink from none
                layers["swa"]["sink"] = (
                    math.log(lc.window) + jax.random.normal(
                        jax.random.fold_in(kq, 3), (L, lc.n_heads))
                ).astype(pd)
        if not ffn:
            pass
        elif lc.moe_experts and lc.moe_impl == "dropless":
            E, f = lc.moe_experts, lc.moe_d_ff or lc.d_ff
            dl = lc.moe_latent or d  # the width the experts work in
            k_rt = jax.random.fold_in(kwi, 1)
            router = dense_init(k_rt, (L, d, E), d)
            E = lc.experts_held  # the router stays whole; the rest is held
            layers["moe"] = {
                "router": router,
                # the selection's correction bias: trained in the published
                # model (to even out the experts' load), here seeded, non-zero
                # and small against the scores' spread (~0.2): at 0.1 the
                # fullest expert drew 5.5 x the mean load on the chip
                "bias": (0.02 * jax.random.normal(
                    jax.random.fold_in(k_rt, 1),
                    (L, lc.moe_experts))).astype(pd),
                "wi": dense_init(kwi, (L, E, dl, f), dl),
                "wo": dense_init(kwo, (L, E, f, dl), f),
                **gate(kwi, (L, E, dl, f), dl),
            }
            if lc.moe_latent:
                k_lt = jax.random.fold_in(kwi, 4)
                layers["moe"].update(
                    latent_in=dense_init(k_lt, (L, d, dl), d),
                    latent_out=dense_init(jax.random.fold_in(k_lt, 1),
                                          (L, dl, d), dl))
            if lc.moe_shared_experts:
                fs = (lc.moe_shared_d_ff or f) * lc.moe_shared_experts
                ks = jax.random.fold_in(kwi, 3)
                layers["moe"]["shared"] = {
                    "wi": dense_init(ks, (L, d, fs), d),
                    "wo": dense_init(jax.random.fold_in(kwo, 3),
                                     (L, fs, d), fs),
                    **gate(ks, (L, d, fs), d),
                }
        elif lc.moe_experts:
            E = lc.moe_experts
            k_rt = jax.random.fold_in(kwi, 1)
            layers["moe"] = {
                "router": dense_init(k_rt, (L, d, E), d),
                "wi": dense_init(kwi, (L, E, d, lc.d_ff), d),
                "wo": dense_init(kwo, (L, E, lc.d_ff, d), lc.d_ff),
            }
        else:
            layers["mlp"] = {
                "wi": dense_init(kwi, (L, d, lc.d_ff), d),
                "wo": dense_init(kwo, (L, lc.d_ff, d), lc.d_ff),
                **gate(kwi, (L, d, lc.d_ff), d),
            }
        return layers

    def wo_shape(lc):  # a differential pair's outputs lie side by side
        r = 2 if lc.diff_attn else 1
        return (lc.n_heads // r, r * lc.v_dim, lc.d_model)

    def mamba_stack(L: int) -> Dict:
        """L "mamba" layers: norms and FFN as ``stack`` draws them, and
        the mixer's own parameters with Mamba-1's own initialisers: A =
        -exp(a_log) with exp(a_log) = 1..N along the state dims of every
        channel, dt_bias the inverse softplus of a step log-uniform in
        0.001-0.1 a channel, the step's projection U(+-1/sqrt(dt_rank)),
        D = 1, the convolution and its bias U(+-1/sqrt(taps))."""
        layers = stack(c, L, 29, 0, attends=False)
        d, inner, n, r = (c.d_model, c.mamba_inner, c.mamba_state,
                          c.mamba_dt_rank)
        ks = jax.random.split(jax.random.fold_in(k_q, 31), 8)
        step = jnp.exp(jax.random.uniform(
            ks[5], (L, inner), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
        bound = c.mamba_conv ** -0.5

        def uniform(key, shape, bound):
            return jax.random.uniform(
                key, shape, minval=-bound, maxval=bound).astype(pd)

        layers["mamba"] = {
            "wx": dense_init(ks[0], (L, d, inner), d),
            "wz": dense_init(ks[1], (L, d, inner), d),
            "conv_w": uniform(ks[2], (L, c.mamba_conv, inner), bound),
            "conv_b": uniform(ks[3], (L, inner), bound),
            "wxp": dense_init(ks[4], (L, inner, r + 2 * n), inner),
            "wdt": uniform(ks[6], (L, r, inner), r ** -0.5),
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(pd),
            "a_log": jnp.broadcast_to(jnp.log(jnp.arange(
                1.0, n + 1))[None, :, None], (L, n, inner)).astype(pd),
            "d": jnp.ones((L, inner), pd),
            "wo": dense_init(ks[7], (L, inner, d), inner),
        }
        return layers

    def gmu_stack(L: int) -> Dict:
        layers = stack(c, L, 37, 0, attends=False)
        kg = jax.random.fold_in(k_q, 41)
        layers["gmu"] = {
            "wi": dense_init(kg, (L, c.d_model, c.mamba_inner), c.d_model),
            "wo": dense_init(jax.random.fold_in(kg, 1),
                             (L, c.mamba_inner, c.d_model), c.mamba_inner)}
        return layers

    def cross_stack(L: int) -> Dict:
        layers = stack(c, L, 43, 0, attends=False)
        kc = jax.random.fold_in(k_q, 47)
        layers["cross"] = {
            "wq": dense_init(kc, (L, c.d_model, c.n_heads, c.d_head),
                             c.d_model),
            "wo": dense_init(jax.random.fold_in(kc, 1), (L,) + wo_shape(c),
                             c.n_heads * c.v_dim),
            **mha_extras(kc, L, 0, queries_only=True)}
        return layers

    def eva_stack(L: int) -> Dict:
        """L "eva" layers: norms and FFN as ``stack`` draws them, MHA
        projections, and the two pooling vectors a head, ``phi`` (keys)
        and ``mu`` (values), clip(normal, -1, 1) / sqrt(d_head): a chunk's
        pooling logits k . phi / sqrt(d_head) then spread by about a
        tenth of the key's norm, and a summary is no plain mean."""
        layers = stack(c, L, 53, 0, attends=False)
        ks = jax.random.split(jax.random.fold_in(k_q, 59), 6)
        d, h, dh = c.d_model, c.n_heads, c.d_head

        def pooling(key):
            return (jnp.clip(jax.random.normal(key, (L, h, dh)), -1, 1)
                    * dh ** -0.5).astype(pd)

        layers["eva"] = {
            "wq": dense_init(ks[0], (L, d, h, dh), d),
            "wk": dense_init(ks[1], (L, d, h, dh), d),
            "wv": dense_init(ks[2], (L, d, h, dh), d),
            "wo": dense_init(ks[3], (L, h, dh, d), h * dh),
            "phi": pooling(ks[4]), "mu": pooling(ks[5])}
        return layers

    def ssm_stack(L: int) -> Dict:
        """L state-space layers: the block's norms and FFN as ``stack``
        draws them, and the mixer's own parameters with the initialisers
        the layer was published with (they set how fast a state forgets:
        normal values would let it explode or vanish): A = -exp(a_log)
        with exp(a_log) ~ U(1, 16), dt_bias the inverse softplus of a step
        log-uniform in 0.001-0.1, D = 1, the convolution U(+-1/sqrt(taps))."""
        layers = stack(c, L, 11, 0, attends=False, ffn=c.block == "pair")
        d, inner, width, nh = c.d_model, c.ssm_inner, c.ssm_conv_width, \
            c.ssm_heads
        ks = jax.random.split(jax.random.fold_in(k_q, 13), 7)
        step = jnp.exp(jax.random.uniform(
            ks[5], (L, nh), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
        bound = c.ssm_conv ** -0.5
        layers["ssm"] = {
            "wz": dense_init(ks[0], (L, d, inner), d),
            "wxbc": dense_init(ks[1], (L, d, width), d),
            "wdt": dense_init(ks[2], (L, d, nh), d),
            "conv_w": jax.random.uniform(
                ks[3], (L, c.ssm_conv, width), minval=-bound,
                maxval=bound).astype(pd),
            "conv_b": jnp.zeros((L, width), pd),
            "a_log": jnp.log(jax.random.uniform(
                ks[4], (L, nh), minval=1.0, maxval=16.0)).astype(pd),
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(pd),
            "d": jnp.ones((L, nh), pd),
            "norm": jnp.ones((L, inner), pd),
            "wo": dense_init(ks[6], (L, inner, d), inner),
        }
        return layers

    def kda_stack(lc: TransformerConfig, L: int, salt: int) -> Dict:
        """L "kda" layers of ``lc``'s block: norms and FFN as ``stack``
        draws them, and the mixer's own parameters. The decay's are the
        family's published ones (``ssm_stack``): exp(a_log) ~ U(1, 16) a
        head, dt_bias the inverse softplus of a step log-uniform in
        0.001-0.1 a channel, and the low-rank pair's second matrix drawn
        at a quarter of the usual spread so that softplus(. + dt_bias)
        stays near that step: a token's decay a channel then lies in about
        0.2-0.999 and differs by channel. At normal(0, 1/sqrt(fan_in))
        throughout the state forgets within a few tokens."""
        layers = stack(lc, L, salt, 0, attends=False)
        d, inner, nh, dk = lc.d_model, lc.kda_inner, lc.kda_heads, \
            lc.kda_head_dim
        ks = jax.random.split(jax.random.fold_in(k_q, salt + 2), 10)
        step = jnp.exp(jax.random.uniform(
            ks[5], (L, inner), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
        bound = lc.kda_conv ** -0.5
        layers["kda"] = {
            "wqkv": dense_init(ks[0], (L, d, 3 * inner), d),
            "conv_w": jax.random.uniform(
                ks[1], (L, lc.kda_conv, 3 * inner), minval=-bound,
                maxval=bound).astype(pd),
            "a_log": jnp.log(jax.random.uniform(
                ks[2], (L, nh), minval=1.0, maxval=16.0)).astype(pd),
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(pd),
            "wfa": dense_init(ks[3], (L, d, dk), d),
            "wfb": 0.25 * dense_init(ks[4], (L, dk, inner), dk),
            "wb": dense_init(ks[6], (L, d, nh), d),
            "wga": dense_init(ks[7], (L, d, dk), d),
            "wgb": dense_init(ks[8], (L, dk, inner), dk),
            "norm": jnp.ones((L, dk), pd),
            "wo": dense_init(ks[9], (L, inner, d), inner),
        }
        return layers

    n_dense = c.n_dense_layers if c.moe_experts else 0
    # the leading dense layers are all of one kind (__post_init__)
    dense_kda = n_dense if c.layer_types[:1] == ("kda",) else 0
    params = {
        "embed": (jax.random.normal(k_emb, (c.vocab_size, c.d_model)) * 0.02
                  ).astype(pd),
        "final_ln": norm_init((c.d_model,), jax.random.fold_in(k_emb, 1)),
    }
    n_rest = c.n_attn_layers - n_dense + dense_kda
    if n_rest or not c.layer_types:  # a model may have no such layer
        params["layers"] = stack(c, n_rest, 0, n_dense,
                                 ffn=c.block == "pair")
    if c.n_of("experts"):  # a "single" block's routed layers: no mixer
        params["expert_layers"] = stack(c, c.n_of("experts"), 61, 0,
                                        attends=False)
    for kind, make in (("mamba", mamba_stack), ("gmu", gmu_stack),
                       ("cross", cross_stack), ("eva", eva_stack)):
        if c.n_of(kind):
            params[_KIND_STACKS[kind]] = make(c.n_of(kind))
    if c.n_ssm_layers:
        params["ssm_layers"] = ssm_stack(c.n_ssm_layers)
    if c.n_window_layers:
        params["window_layers"] = stack(c, c.n_window_layers, 17, 0,
                                        window=True)
    if c.n_kda_layers - dense_kda:
        params["kda_layers"] = kda_stack(c, c.n_kda_layers - dense_kda, 19)
    if dense_kda:
        params["dense_layers"] = kda_stack(c.dense_variant(), n_dense, 23)
    elif n_dense:
        params["dense_layers"] = stack(c.dense_variant(), n_dense, 7, 0)
    if not c.tie_embeddings:
        params["lm_head"] = dense_init(
            k_head, (c.d_model, c.n_pred_heads * c.vocab_size), c.d_model)
    return params


def param_logical_axes(config: TransformerConfig) -> Dict:
    """Same-structure tree of logical axis-name tuples (None = no sharding)."""

    def gate(axes):
        return {"wg": axes} if config.gated_ffn else {}

    def norm_axes(axes):
        return {"scale": axes,
                **({"bias": axes} if config.norm == "layer" else {})}

    def mha_extras(queries_only=False):
        out = {}
        if config.attn_bias:
            out.update(bq=("layers", "heads", "head_dim"),
                       bo=("layers", "embed"))
            if not queries_only:
                out.update(bk=("layers", "kv_heads", "head_dim"),
                           bv=("layers", "kv_heads", "head_dim"))
        if config.diff_attn:
            out.update({"lambda": ("layers", None, None),
                        "subln": ("layers", None)})
        return out

    def stack(lc: TransformerConfig, first: int, L: int) -> Dict:
        layers = {"ln1": norm_axes(("layers", "embed"))}
        if lc.residual == "sequential":
            layers["ln2"] = norm_axes(("layers", "embed"))
        if lc.mixer == "mla":
            layers["attn"] = {
                **({"wdq": ("layers", "embed", None),
                    "q_norm": ("layers", None),
                    "wuq": ("layers", None, "heads", "head_dim")}
                   if lc.q_lora_rank else
                   {"wq": ("layers", "embed", "heads", "head_dim")}),
                "wdkv": ("layers", "embed", None),
                "kv_norm": ("layers", None),
                "wuk": ("layers", None, "heads", "head_dim"),
                "wuv": ("layers", None, "heads", "head_dim"),
                "wo": ("layers", "heads", "head_dim", "embed"),
            }
            if lc.index_topk and "full" in lc.indexer_types[first:first + L]:
                layers["attn"]["indexer"] = {
                    "wq": ("layers", None, None, None),
                    "wk": ("layers", "embed", None),
                    "k_norm": {"scale": ("layers", None),
                               "bias": ("layers", None)},
                    "ww": ("layers", "embed", None),
                }
        else:
            layers["attn"] = {
                "wq": ("layers", "embed", "heads", "head_dim"),
                "wk": ("layers", "embed", "kv_heads", "head_dim"),
                "wv": ("layers", "embed", "kv_heads", "head_dim"),
                "wo": ("layers", "heads", "head_dim", "embed"),
                **mha_extras(),
            }
        if lc.moe_experts:
            wi = ("layers", "experts", "embed", "mlp")
            layers["moe"] = {
                "router": ("layers", "embed", "experts"),
                "wi": wi,
                "wo": ("layers", "experts", "mlp", "embed"),
            }
            if lc.moe_latent:  # the experts' "embed" is then the latent
                layers["moe"].update(latent_in=("layers", "embed", None),
                                     latent_out=("layers", None, "embed"))
            if lc.moe_impl == "dropless":
                layers["moe"].update(bias=("layers", "experts"), **gate(wi))
                if lc.moe_shared_experts:
                    layers["moe"]["shared"] = {
                        "wi": ("layers", "embed", "mlp"),
                        "wo": ("layers", "mlp", "embed"),
                        **gate(("layers", "embed", "mlp")),
                    }
        else:
            layers["mlp"] = {
                "wi": ("layers", "embed", "mlp"),
                "wo": ("layers", "mlp", "embed"),
                **gate(("layers", "embed", "mlp")),
            }
        return layers

    def branch(layers: Dict, name: str) -> Dict:
        """Of a "single" block's layer: its one norm and its one branch,
        ``name``; any other block's layers as they are."""
        if config.block != "single":
            return layers
        return {"ln1": layers["ln1"], name: layers[name]}

    n_dense = config.n_dense_layers if config.moe_experts else 0
    axes = {
        "embed": ("vocab", "embed"),
        "final_ln": norm_axes(("embed",)),
    }
    if config.n_attn_layers or not config.layer_types:
        axes["layers"] = branch(
            stack(config, n_dense, config.n_layers - n_dense), "attn")
    if config.n_of("experts"):
        axes["expert_layers"] = branch(
            stack(config, 0, config.n_of("experts")), "moe")
    for kind, mixer in (
            ("mamba", {
                "wx": ("layers", "embed", "mlp"),
                "wz": ("layers", "embed", "mlp"),
                "conv_w": ("layers", None, "mlp"),
                "conv_b": ("layers", "mlp"),
                "wxp": ("layers", "mlp", None),
                "wdt": ("layers", None, "mlp"),
                "dt_bias": ("layers", "mlp"),
                "a_log": ("layers", None, "mlp"),
                "d": ("layers", "mlp"),
                "wo": ("layers", "mlp", "embed")}),
            ("gmu", {"wi": ("layers", "embed", "mlp"),
                     "wo": ("layers", "mlp", "embed")}),
            ("cross", {"wq": ("layers", "embed", "heads", "head_dim"),
                       "wo": ("layers", "heads", "head_dim", "embed"),
                       **mha_extras(queries_only=True)}),
            ("eva", {"wq": ("layers", "embed", "heads", "head_dim"),
                     "wk": ("layers", "embed", "heads", "head_dim"),
                     "wv": ("layers", "embed", "heads", "head_dim"),
                     "wo": ("layers", "heads", "head_dim", "embed"),
                     "phi": ("layers", "heads", "head_dim"),
                     "mu": ("layers", "heads", "head_dim")})):
        if config.n_of(kind):
            layers = stack(config, 0, config.n_of(kind))
            del layers["attn"]
            layers[kind] = mixer
            axes[_KIND_STACKS[kind]] = layers
    if config.n_ssm_layers:
        ssm = stack(config, 0, config.n_ssm_layers)
        del ssm["attn"]
        ssm["ssm"] = {
            "wz": ("layers", "embed", "mlp"),
            "wxbc": ("layers", "embed", None),
            "wdt": ("layers", "embed", None),
            "conv_w": ("layers", None, None),
            "conv_b": ("layers", None),
            "a_log": ("layers", None),
            "dt_bias": ("layers", None),
            "d": ("layers", None),
            "norm": ("layers", "mlp"),
            "wo": ("layers", "mlp", "embed"),
        }
        axes["ssm_layers"] = branch(ssm, "ssm")
    if config.n_window_layers:
        swa = stack(config, 0, config.n_window_layers)
        swa["swa"] = swa.pop("attn")
        if config.window_sink:
            swa["swa"]["sink"] = ("layers", "heads")
        axes["window_layers"] = swa
    def as_kda(layers: Dict) -> Dict:
        del layers["attn"]
        layers["kda"] = {
            "wqkv": ("layers", "embed", "mlp"),
            "conv_w": ("layers", None, "mlp"),
            "a_log": ("layers", None),
            "dt_bias": ("layers", "mlp"),
            "wfa": ("layers", "embed", None),
            "wfb": ("layers", None, "mlp"),
            "wb": ("layers", "embed", None),
            "wga": ("layers", "embed", None),
            "wgb": ("layers", None, "mlp"),
            "norm": ("layers", None),
            "wo": ("layers", "mlp", "embed"),
        }
        return layers

    dense_kda = n_dense and config.layer_types[:1] == ("kda",)
    if config.n_kda_layers - (n_dense if dense_kda else 0):
        axes["kda_layers"] = as_kda(stack(config, 0, config.n_kda_layers))
    if n_dense:
        axes["dense_layers"] = stack(config.dense_variant(), 0, n_dense)
        if dense_kda:
            axes["dense_layers"] = as_kda(axes["dense_layers"])
    if not config.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


# where ``init_params`` puts the stack of each kind ``layer_types`` names
_KIND_STACKS = {"attention": "layers", "ssm": "ssm_layers",
                "window": "window_layers", "kda": "kda_layers",
                "mamba": "mamba_layers", "gmu": "gmu_layers",
                "cross": "cross_layers", "eva": "eva_layers",
                "experts": "expert_layers"}


def layer_groups(params: Dict, config: TransformerConfig):
    """The stacks of layers in the order they run, each as (stacked
    weights, the config of that stack's block, index of its first layer):
    the leading dense layers where the model has them, then the rest. Each
    stack is one ``lax.scan``. A model with layers of several kinds
    (``config.layer_types``) is ONE group whose "stack" holds each kind's
    stack under its name there, ``{"attention": .., "ssm": ..}``
    (``_KIND_STACKS``): ``scan_stack`` runs them in the listed order. Its
    leading dense layers, of whichever ONE kind they are, are a group of
    their own before it."""
    n_dense = config.n_dense_layers if config.moe_experts else 0
    dense, rest = params.get("dense_layers"), params.get("layers")
    if config.layer_types:
        dense = {config.layer_types[0]: dense}
        rest = {kind: params[name] for kind, name in _KIND_STACKS.items()
                if name in params}
    lead = [(dense, config.dense_variant(), 0)] if n_dense else []
    return lead + [(rest, config, n_dense)]


_EXPERT_WEIGHTS = ("wg", "wi", "wo")


def scan_stack(body, carry, stack: Dict, lc: TransformerConfig, first: int):
    """``lax.scan`` of ``body(carry, lp, li) -> carry`` over one stack of
    layers, ``li`` counting from ``first``. A scan hands its body one
    layer's slice of every stacked weight, and a slice that feeds a kernel
    (the compiler's own or a Pallas one: a custom call's operand is a
    whole array) is COPIED out of the stack first: for dropless routed
    experts that copy is the whole layer's experts, read or not. So those
    weights stay out of the scanned tree: ``lp`` carries them whole,
    [layers, E, ...], with ``lp["moe"]["layer"]`` saying which layer's
    experts to use, and the grouped product's block index picks
    (layer, expert) inside the kernel (``ops/moe.routed_ffn``,
    ``ops/grouped_matmul``).

    Layers of one stack may differ in kind where the block has an indexer
    (``lc.index_topk``): only the "full" ones own one, so the stack's
    indexers, [own layers, ...], travel whole as well, and each layer is
    told, as scanned scalars in ``lp["attn"]``, whether it owns one
    (``index_own``), which of the stack's it is (``index_local``) and
    which of the model's choices it attends (``index_slot``).

    Layers of several kinds with different parameters
    (``lc.layer_types``) are a stack a kind, ``stack[kind]``
    (``layer_groups``), run in the listed order by ``_scan_kinds``, which
    cuts the list into SEGMENTS of one period each (``(mamba window) x 8,
    mamba attention, (gmu cross) x 7``: three scans); ``li`` then counts
    the MODEL's layers of the layer's own kind (its place in whatever
    cache leaf that kind keeps; its place in its kind's stack is that
    less the kind's layers before ``first``). ``stack`` may hold some of
    the kinds only: the layers from ``first`` on that are of those kinds
    (a prefill runs the layers that keep nothing apart from the others).
    A differential attention's layer is told its index in the model,
    ``depth`` in its mixer's weights: its lambda_init follows from it."""
    if lc.layer_types:
        n = sum(s["ln1"]["scale"].shape[0] for s in stack.values())
        before = lc.layer_types[:first]
        depths = {kind: jnp.array(
            [i for i, k in enumerate(lc.layer_types) if k == kind],
            jnp.float32) for kind in stack} if lc.diff_attn else {}
        return _scan_kinds(
            body, carry, stack, lc.layer_types[first:first + n],
            {kind: before.count(kind) for kind in stack},
            lc.moe_experts and lc.moe_impl == "dropless", depths)
    n = stack["ln1"]["scale"].shape[0]
    held, kinds = {}, None
    if lc.moe_experts and lc.moe_impl == "dropless":
        held, stack = _hold_experts(stack)
    if lc.index_topk:
        types = lc.indexer_types
        own = [k == "full" for k in types[first:first + n]]
        before = sum(k == "full" for k in types[:first])
        slots = lc.index_slots(first, n)
        kinds = {"index_own": jnp.array(own),
                 "index_slot": jnp.array(slots, jnp.int32),
                 "index_local": jnp.array(
                     [max(s - before, 0) for s in slots], jnp.int32)}
        indexer = stack["attn"].get("indexer")
        stack = {**stack, "attn": {k: v for k, v in stack["attn"].items()
                                   if k != "indexer"}}

    def step(carry, layer_in):
        lp, li, kind = layer_in
        if held:
            lp = {**lp, "moe": {**lp["moe"], **held, "layer": li - first}}
        if kind is not None:
            lp = {**lp, "attn": {**lp["attn"], **kind, "indexer": indexer}}
        return body(carry, lp, li), None

    carry, _ = lax.scan(
        step, carry, (stack, jnp.arange(first, first + n), kinds))
    return carry


def _hold_experts(stack: Dict):
    """(the experts' weights of a routed stack, whole; the stack without
    them, for a scan to slice)."""
    held = {k: stack["moe"][k] for k in _EXPERT_WEIGHTS if k in stack["moe"]}
    return held, {**stack, "moe": {k: v for k, v in stack["moe"].items()
                                   if k not in held}}


def _segments(kinds: Tuple[str, ...]):
    """``kinds`` cut into segments ``(start, period, repeats)``, each a
    period of layers repeated, so that the periods' runs of one kind are
    as few as can be in all (the bodies ``_scan_kinds`` makes), then the
    segments as few, then the periods as short. ``(ssm x 5, attention,
    ssm x 4) x 4`` is one segment of three runs; ``(mamba window) x 8,
    mamba attention, (gmu cross) x 7`` has no period of its own and is
    three segments of two runs each."""
    n = len(kinds)

    def runs(period):
        return 1 + sum(a != b for a, b in zip(period, period[1:]))

    best = {n: (0, 0, ())}  # from layer i on: (bodies, segments, the cut)
    for i in range(n - 1, -1, -1):
        for p in range(1, n - i + 1):
            period, reps = kinds[i:i + p], 1
            while kinds[i + reps * p:i + (reps + 1) * p] == period:
                reps += 1
            for r in range(reps, 0, -1):
                bodies, segments, cut = best[i + r * p]
                found = (bodies + runs(period), segments + 1,
                         ((i, period, r),) + cut)
                if i not in best or found[:2] < best[i][:2]:
                    best[i] = found
    return best[0][2]


def _scan_kinds(body, carry, stacks: Dict, kinds: Tuple[str, ...],
                offsets: Dict[str, int], routed: bool = False,
                depths: Optional[Dict] = None):
    """``body(carry, lp, li) -> carry`` over layers whose kind, one of
    ``stacks``' keys, is listed in ``kinds``; ``lp`` is the layer's slice
    of its kind's stack and ``li`` its index there plus ``offsets[kind]``
    (the kind's layers that ran before these stacks). Where the layers
    are ``routed`` the experts' weights stay whole in ``lp`` beside
    ``lp["moe"]["layer"]``, as in ``scan_stack``; ``depths[kind]``, where
    given, is the index in the model of each of the kind's layers, and
    the layer's own goes into its mixer's weights as ``depth``. The list
    is cut into SEGMENTS, each one period repeated (``_segments``: ten
    layers four times is one segment; ``(mamba window) x 8, mamba
    attention, (gmu cross) x 7`` three), and a period into runs of one
    kind: a ``lax.scan`` over a segment's periods holds one ``lax.scan``
    a run (a run of one layer: the body itself), so the program has one
    body a RUN OF A PERIOD, not one a layer. Every scan
    counts indices and the body takes its layer out of the WHOLE stack
    with a dynamic index, which the compiler fuses into the products that
    read it: a scan handed a run's slice of a stack as its ``xs`` would
    copy the slice first (hundreds of MB a run)."""
    held = {}
    if routed:
        split = {kind: _hold_experts(stack)
                 for kind, stack in stacks.items() if "moe" in stack}
        held = {kind: h for kind, (h, _rest) in split.items()}
        stacks = {**stacks, **{kind: rest
                               for kind, (_h, rest) in split.items()}}

    def segment(carry, start: int, period: Tuple[str, ...], repeats: int):
        runs = []  # (kind, the kind's layers before it, length)
        for i, kind in enumerate(period):
            if runs and runs[-1][0] == kind:
                runs[-1][2] += 1
            else:
                runs.append([kind, kinds[:start].count(kind)
                             + period[:i].count(kind), 1])

        def one_period(carry, rep):
            for kind, before, length in runs:
                stack = stacks[kind]
                base = rep * period.count(kind) + before

                def one(carry, j, stack=stack, base=base, kind=kind):
                    li = base + j
                    lp = jax.tree.map(lambda a: lax.dynamic_index_in_dim(
                        a, li, 0, keepdims=False), stack)
                    if kind in held:
                        lp = {**lp, "moe": {**lp["moe"], **held[kind],
                                            "layer": li}}
                    # no "+ 0": where the stacks hold all of a kind's
                    # layers the program's text stays what it was before
                    # offsets
                    at = li + offsets[kind] if offsets[kind] else li
                    if depths:
                        mixer = layer_kind(lp)
                        lp = {**lp, mixer: {**lp[mixer],
                                            "depth": depths[kind][at]}}
                    return body(carry, lp, at), None

                if length == 1:
                    carry, _ = one(carry, 0)
                else:
                    carry, _ = lax.scan(one, carry, jnp.arange(length))
            return carry, None

        if repeats == 1:
            return one_period(carry, 0)[0]
        return lax.scan(one_period, carry, jnp.arange(repeats))[0]

    for start, period, repeats in _segments(kinds):
        carry = segment(carry, start, period, repeats)
    return carry


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _rms_norm(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


def _norm(x, p, c: "TransformerConfig"):
    """The block's norm under its parameters ``p``: RMSNorm with a scale
    (``c.norm_unit_offset``: the scale is 1 + w, formed in float32), or
    (``c.norm`` "layer") LayerNorm with a scale and a bias, the mean
    subtracted; float32 statistics either way. Returns x's type, but of a
    float32 residual stream (``c.residual_f32``) the compute dtype: what
    the branches' products take."""
    if c.norm_unit_offset:
        y = _rms_norm(x, 1.0 + p["scale"].astype(jnp.float32), c.norm_eps)
    elif c.norm != "layer":
        y = _rms_norm(x, p["scale"], c.norm_eps)
    else:
        x32 = x.astype(jnp.float32)
        x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        y = ((x32 * lax.rsqrt(var + c.norm_eps)).astype(x.dtype)
             * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype))
    return y.astype(c.dtype) if c.residual_f32 else y


def _rotary(q, k, rotary_dim, positions, base=10000.0):
    """Apply rotary embeddings to the first `rotary_dim` dims of q/k.

    q/k: [B, S, H, D] (k's H may be 1: a key all heads share); positions:
    [S] global token positions, or [B, S] per-sequence positions
    (continuous-batching decode, where slots sit at different depths).
    """
    d2 = rotary_dim // 2
    inv_freq = 1.0 / (base ** (jnp.arange(0, d2) / d2))
    freqs = (
        positions[..., None].astype(jnp.float32) * inv_freq
    )  # [S,d2] or [B,S,d2]
    if positions.ndim == 1:
        cos = jnp.cos(freqs)[None, :, None, :]
        sin = jnp.sin(freqs)[None, :, None, :]
    else:
        cos = jnp.cos(freqs)[:, :, None, :]
        sin = jnp.sin(freqs)[:, :, None, :]

    def rot(x):
        xr, xp = x[..., :rotary_dim], x[..., rotary_dim:]
        x1, x2 = xr[..., :d2], xr[..., d2:]
        xr = jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        ).astype(x.dtype)
        return jnp.concatenate([xr, xp], axis=-1)

    return rot(q), rot(k)


def select_attn_fn(config: TransformerConfig,
                   mesh: Optional[jax.sharding.Mesh]):
    c = config
    if c.attn_impl == "ring":
        if mesh is None:
            raise ValueError("ring attention needs a mesh")
        from ray_tpu.ops.ring_attention import ring_attention

        return partial(ring_attention, mesh=mesh)
    if c.attn_impl == "ulysses":
        if mesh is None:
            raise ValueError("ulysses attention needs a mesh")
        from ray_tpu.ops.ulysses_attention import ulysses_attention

        return partial(ulysses_attention, mesh=mesh)
    if c.attn_impl == "flash":
        from ray_tpu.ops.flash_attention import (
            flash_attention,
            flash_attention_sharded,
        )

        # pallas_call is opaque to the GSPMD partitioner: under a mesh it
        # must sit inside shard_map (batch->dp, heads->tp).
        if mesh is not None:
            return partial(flash_attention_sharded, mesh=mesh)
        return flash_attention
    if c.attn_impl == "dense":
        return causal_attention
    raise ValueError(f"unknown attn_impl {c.attn_impl!r}")


def _diff_pairs(q, k=None, v=None):
    """Differential attention read as plain grouped-query attention. A
    pair of query heads (2i, 2i + 1) attends a pair of KV heads (2j, 2j +
    1): ``A(q_2i, k_2j, V_j)`` and ``A(q_2i+1, k_2j+1, V_j)``, ``V_j =
    [v_2j | v_2j+1]``. With K_j = [k_2j | k_2j+1] (the two heads as they
    lie side by side in a row: a reshape) and the queries padded with
    zeros, [q_2i | 0] and [0 | q_2i+1], that is H query heads over Hkv / 2
    KV heads of twice the width, and every attention the program has
    (tiles, windows, the decode kernel over flat rows) computes it as it
    stands, reading each cached row once. The scale is the narrow head's:
    every attention scales by 1 / sqrt(its q's width), now twice d_head,
    so the queries carry sqrt(2). q [B,S,H,D], k [B,S,Hkv,D], v
    [B,S,Hkv,Dv] -> q [B,S,H,2D], k [B,S,Hkv/2,2D], v [B,S,Hkv/2,2Dv]."""
    B, S, H, D = q.shape
    side = jnp.eye(2, dtype=q.dtype) * (2.0 ** 0.5)  # head 2i+e: side e
    q = (q.reshape(B, S, H // 2, 2, 1, D) * side[:, :, None]
         ).reshape(B, S, H, 2 * D)
    if k is None:
        return q
    return (q, k.reshape(B, S, k.shape[2] // 2, 2 * D),
            v.reshape(B, S, v.shape[2] // 2, 2 * v.shape[-1]))


def diff_lambdas(wp, c: "TransformerConfig"):
    """(lambda, lambda_init) of a differential attention layer whose
    weights ``wp`` hold the four lambda vectors and the layer's ``depth``
    (its index in the model: ``scan_stack``), float32 scalars."""
    f32 = jnp.float32
    init = 0.8 - 0.6 * jnp.exp(-0.3 * wp["depth"].astype(f32))
    lam = wp["lambda"].astype(f32)
    return (jnp.exp((lam[0] * lam[1]).sum()) - jnp.exp(
        (lam[2] * lam[3]).sum()) + init, init)


def _diff_combine(out, wp, c: "TransformerConfig"):
    """The other half of ``_diff_pairs``: the pairs' two attentions out
    [B,S,H,2Dv] -> o_i = out_2i - lambda out_2i+1, RMSNorm_w over its 2Dv
    channels, x (1 - lambda_init): [B,S,H/2,2Dv] in out's type (float32
    inside)."""
    B, S, H, W = out.shape
    with jax.named_scope("raytpu.diff.combine"):
        lam, init = diff_lambdas(wp, c)
        o = out.astype(jnp.float32).reshape(B, S, H // 2, 2, W)
        o = o[:, :, :, 0] - lam * o[:, :, :, 1]
        o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + c.norm_eps)
        o = o * wp["subln"].astype(jnp.float32) * (1.0 - init)
    return o.astype(out.dtype)


def _mha_mixer(h, wp, c: TransformerConfig, positions, attn_fn,
               window: bool = False):
    """MHA / GQA. A ``window`` layer (``TransformerConfig.window``) has
    its own KV heads (the weights' shapes) and rotary base, and its
    ``attn_fn`` takes the layer's sink logits as well: ``attn_fn(q, k, v,
    sink)``, ``sink`` [H] or None. With ``c.diff_attn`` the heads pair
    (``_diff_pairs``: ``attn_fn`` sees grouped-query attention over heads
    twice as wide) and the pairs' outputs are subtracted and normed
    (``_diff_combine``)."""
    scope = "raytpu.swa" if window else "raytpu.attn"
    with jax.named_scope(scope + ".project"):
        q = jnp.einsum("bsd,dhk->bshk", h, wp["wq"].astype(c.dtype))
        k = jnp.einsum("bsd,dhk->bshk", h, wp["wk"].astype(c.dtype))
        v = jnp.einsum("bsd,dhk->bshk", h, wp["wv"].astype(c.dtype))
        if c.attn_bias:
            q, k, v = (x + wp[b].astype(c.dtype)
                       for x, b in ((q, "bq"), (k, "bk"), (v, "bv")))
        if c.value_scale != 1.0:
            v = v * c.value_scale
        if c.rotary_dim:  # 0: no positional term at all
            q, k = _rotary(q, k, c.rotary_dim, positions,
                           c.mha_kind(window)[1])
        if c.attn_scale is not None:
            # every attention (dense, flash, the decode kernel) scales its
            # scores by 1/sqrt(d_head) itself: the queries carry the rest
            q = q * (c.attn_scale * c.d_head ** 0.5)
        if c.diff_attn:
            q, k, v = _diff_pairs(q, k, v)
    if window:  # marks raytpu.swa.attend, and .ring where it keeps one
        attn_out = attn_fn(q, k, v, wp.get("sink"))
    else:
        with jax.named_scope("raytpu.attn.attend"):
            attn_out = attn_fn(q, k, v)
    extra = None
    if isinstance(attn_out, tuple):
        attn_out, extra = attn_out
    if c.diff_attn:
        attn_out = _diff_combine(attn_out, wp, c)
    with jax.named_scope(scope + ".project"):
        out = jnp.einsum("bshk,hkd->bsd", attn_out, wp["wo"].astype(c.dtype))
        if c.attn_bias:
            out = out + wp["bo"].astype(c.dtype)
    return out, extra


def _cross_mixer(h, wp, c: TransformerConfig, positions, attn_fn):
    """A "cross" layer's mixer: queries of its own against the K/V rows
    ANOTHER layer keeps (the last "attention" layer below), every row s <=
    t: ``attn_fn(q)`` knows where they are (the uncached forward hands the
    rows on in its carry; the serving paths read that layer's cache:
    ``generation.py``). No K/V weights, nothing kept."""
    with jax.named_scope("raytpu.cross.project"):
        q = jnp.einsum("bsd,dhk->bshk", h, wp["wq"].astype(c.dtype))
        if c.attn_bias:
            q = q + wp["bq"].astype(c.dtype)
        if c.attn_scale is not None:
            q = q * (c.attn_scale * c.d_head ** 0.5)
        if c.diff_attn:
            q = _diff_pairs(q)
    with jax.named_scope("raytpu.cross.attend"):
        attn_out, extra = attn_fn(q)
    if c.diff_attn:
        attn_out = _diff_combine(attn_out, wp, c)
    with jax.named_scope("raytpu.cross.project"):
        out = jnp.einsum("bshk,hkd->bsd", attn_out, wp["wo"].astype(c.dtype))
        if c.attn_bias:
            out = out + wp["bo"].astype(c.dtype)
    return out, extra


def mamba_inputs(x, wp, c: TransformerConfig):
    """From a "mamba" layer's convolved channels ``x`` [..., inner] (after
    their SiLU) what the recurrence takes beside them: the step ``dt``
    [..., inner] (after its softplus) and ``B``, ``C`` [..., state], all
    float32; and A [state, inner]."""
    f32 = jnp.float32
    r, n = c.mamba_dt_rank, c.mamba_state
    low = jnp.einsum("...f,fr->...r", x, wp["wxp"].astype(c.dtype),
                     preferred_element_type=f32)
    dt = jax.nn.softplus(
        jnp.einsum("...r,rf->...f", low[..., :r].astype(c.dtype),
                   wp["wdt"].astype(c.dtype), preferred_element_type=f32)
        + wp["dt_bias"].astype(f32))
    return (dt, low[..., r:r + n], low[..., r + n:],
            -jnp.exp(wp["a_log"].astype(f32)))


def _mamba_whole_sequence(x, wp, c: TransformerConfig):
    """A "mamba" layer's recurrence over whole sequences from an empty
    state (the uncached forward): the convolution, then the scan. x
    [B,S,inner] before its convolution. Returns (y [B,S,inner], what the
    layer hands on: its ``y`` as ``m``)."""
    with jax.named_scope("raytpu.mamba1.conv"):
        x = jax.nn.silu(causal_conv(x, wp["conv_w"], wp["conv_b"]))
    with jax.named_scope("raytpu.mamba1.scan"):
        dt, B, C, A = mamba_inputs(x, wp, c)
        y, _state = mamba_scan(x, dt, A, B, C, wp["d"])
    return y, {"m": y}


def _mamba_mixer(h, wp, c: TransformerConfig, positions, attn_fn):
    """The Mamba-1 mixer of a "mamba" layer (``TransformerConfig.
    layer_types``; the equations are in the config's comment). The
    recurrence itself, convolution, projections and scan, is
    ``attn_fn.recur(x, wp) -> (y, extra)`` where the serving paths bring
    one (they keep the state and the convolution's tail in a slot:
    ``generation.py``), else the whole sequence from an empty state. ``y``
    BEFORE its gate is what the "gmu" layers above are gated by: whoever
    runs the recurrence hands it on in ``extra``."""
    with jax.named_scope("raytpu.mamba1.project"):
        x = jnp.einsum("bsd,df->bsf", h, wp["wx"].astype(c.dtype))
        z = jnp.einsum("bsd,df->bsf", h, wp["wz"].astype(c.dtype))
    recur = getattr(attn_fn, "recur", None) or partial(
        _mamba_whole_sequence, c=c)
    y, extra = recur(x, wp)
    with jax.named_scope("raytpu.mamba1.gate"):
        y = y * jax.nn.silu(z)
    with jax.named_scope("raytpu.mamba1.project"):
        out = jnp.einsum("bsf,fd->bsd", y, wp["wo"].astype(c.dtype))
    return out, extra


def recalling(m, extra):
    """A "gmu" layer's ``attn_fn`` (``_gmu_mixer`` calls its ``recall``):
    ``m`` [B,S,inner] as the last "mamba" layer handed it on, and what the
    caller wants back beside the mixer's output (a cache as it is)."""
    def recall():
        return m, extra

    recall.recall = recall
    return recall


def _gmu_mixer(h, wp, c: TransformerConfig, positions, attn_fn):
    """A gated memory unit: ``W_2 (m * silu(W_1 h))``, ``m`` the
    recurrence output of the nearest "mamba" layer below for the same
    tokens, which ``attn_fn.recall() -> (m [B,S,inner], extra)`` brings.
    No state, no cache."""
    m, extra = attn_fn.recall()
    with jax.named_scope("raytpu.gmu.gate"):
        g = jnp.einsum("bsd,df->bsf", h, wp["wi"].astype(c.dtype))
        g = m.astype(c.dtype) * jax.nn.silu(g)
        out = jnp.einsum("bsf,fd->bsd", g, wp["wo"].astype(c.dtype))
    return out, extra


def ssm_split(xbc, c: TransformerConfig):
    """The convolved channels [..., ssm_conv_width] as x [..., H, P] and
    B, C [..., G, N]."""
    inner, gn = c.ssm_inner, c.ssm_groups * c.ssm_state
    lead = xbc.shape[:-1]
    return (xbc[..., :inner].reshape(lead + (c.ssm_heads, c.ssm_head_dim)),
            xbc[..., inner:inner + gn].reshape(
                lead + (c.ssm_groups, c.ssm_state)),
            xbc[..., inner + gn:].reshape(lead + (c.ssm_groups, c.ssm_state)))


def _ssm_whole_sequence(xbc, dt, wp, c: TransformerConfig):
    """An "ssm" layer's recurrence over whole sequences from an empty
    state (the uncached forward): the convolution, then the chunked scan.
    xbc [B,S,width] before its convolution, dt [B,S,H] after its
    softplus. Returns (y [B,S,H,P], None)."""
    with jax.named_scope("raytpu.ssm.conv"):
        x, B, C = ssm_split(jax.nn.silu(causal_conv(
            xbc, wp["conv_w"], wp["conv_b"])), c)
    with jax.named_scope("raytpu.ssm.scan"):
        y, _state = ssm_chunked(
            x, dt, -jnp.exp(wp["a_log"].astype(jnp.float32)), B, C,
            wp["d"], c.ssm_chunk)
    return y, None


def _ssm_mixer(h, wp, c: TransformerConfig, positions, attn_fn):
    """The state-space mixer of an "ssm" layer (``TransformerConfig.
    layer_types``; the equations are in the config's comment). The
    recurrence itself, convolution and scan, is ``attn_fn.recur(xbc, dt,
    wp) -> (y, extra)`` where the serving paths bring one (they keep the
    state and the convolution's tail in a slot: ``generation.py``), else
    the whole sequence from an empty state."""
    with jax.named_scope("raytpu.ssm.project"):
        z = jnp.einsum("bsd,df->bsf", h, wp["wz"].astype(c.dtype))
        xbc = jnp.einsum("bsd,df->bsf", h, wp["wxbc"].astype(c.dtype))
        dt = jax.nn.softplus(
            jnp.einsum("bsd,dh->bsh", h, wp["wdt"].astype(c.dtype),
                       preferred_element_type=jnp.float32)
            + wp["dt_bias"].astype(jnp.float32))
    recur = getattr(attn_fn, "recur", None) or partial(
        _ssm_whole_sequence, c=c)
    y, extra = recur(xbc, dt, wp)
    with jax.named_scope("raytpu.ssm.gate"):
        y = y.reshape(z.shape) * jax.nn.silu(z)
        if c.ssm_norm_groups > 1:  # the mean square over each group alone
            grouped = z.shape[:-1] + (c.ssm_norm_groups, -1)
            y = _rms_norm(y.reshape(grouped), wp["norm"].reshape(
                grouped[-2:]), c.norm_eps).reshape(z.shape)
        else:
            y = _rms_norm(y, wp["norm"], c.norm_eps)
    with jax.named_scope("raytpu.ssm.project"):
        out = jnp.einsum("bsf,fd->bsd", y, wp["wo"].astype(c.dtype))
    return out, extra


def kda_split(qkv, c: TransformerConfig):
    """The convolved streams [..., 3 x kda_inner] as a "kda" layer's q, k
    and v [..., H, D], in the compute dtype: SiLU, then q / ||q|| /
    sqrt(D) and k / ||k|| a head (float32 norms, eps 1e-6 under the
    root)."""
    lead, nh, dk = qkv.shape[:-1], c.kda_heads, c.kda_head_dim
    q, k, v = jnp.split(jax.nn.silu(qkv), 3, axis=-1)

    def unit(x, scale):
        x32 = x.reshape(lead + (nh, dk)).astype(jnp.float32)
        return (x32 * (scale * lax.rsqrt(
            (x32 * x32).sum(-1, keepdims=True) + 1e-6))).astype(c.dtype)

    return unit(q, dk ** -0.5), unit(k, 1.0), v.reshape(lead + (nh, dk))


def _kda_whole_sequence(qkv, g, beta, wp, c: TransformerConfig):
    """A "kda" layer's recurrence over whole sequences from an empty
    state (the uncached forward): the convolution, then the chunked delta
    rule. qkv [B,S,3 x inner] before its convolution, g [B,S,H,D] and
    beta [B,S,H] in float32. Returns (o [B,S,H,D], None)."""
    with jax.named_scope("raytpu.kda.conv"):
        q, k, v = kda_split(causal_conv(qkv, wp["conv_w"], None), c)
    with jax.named_scope("raytpu.kda.chunk"):
        o, _state = kda_chunked(q, k, v, g, beta, c.kda_chunk)
    return o, None


def _kda_mixer(h, wp, c: TransformerConfig, positions, attn_fn):
    """The gated-delta-rule mixer of a "kda" layer (``TransformerConfig.
    layer_types``; the equations are in the config's comment). The
    recurrence itself, convolution and delta rule, is ``attn_fn.recur(
    qkv, g, beta, wp) -> (o, extra)`` where the serving paths bring one
    (they keep the state and the convolution's tail in a slot:
    ``generation.py``), else the whole sequence from an empty state."""
    f32 = jnp.float32
    nh, dk = c.kda_heads, c.kda_head_dim
    with jax.named_scope("raytpu.kda.project"):
        qkv = jnp.einsum("bsd,df->bsf", h, wp["wqkv"].astype(c.dtype))

        def low_rank(a, b):
            return jnp.einsum(
                "bsr,rf->bsf",
                jnp.einsum("bsd,dr->bsr", h, wp[a].astype(c.dtype)),
                wp[b].astype(c.dtype), preferred_element_type=f32)

        step = jax.nn.softplus(
            low_rank("wfa", "wfb") + wp["dt_bias"].astype(f32))
        g = -jnp.exp(wp["a_log"].astype(f32))[:, None] * step.reshape(
            step.shape[:2] + (nh, dk))
        beta = jax.nn.sigmoid(jnp.einsum(
            "bsd,dh->bsh", h, wp["wb"].astype(c.dtype),
            preferred_element_type=f32))
        gate = low_rank("wga", "wgb")
    recur = getattr(attn_fn, "recur", None) or partial(
        _kda_whole_sequence, c=c)
    o, extra = recur(qkv, g, beta, wp)
    with jax.named_scope("raytpu.kda.gate"):
        o = _rms_norm(o, wp["norm"], c.norm_eps)  # over each head's D
        o = o.reshape(gate.shape) * jax.nn.sigmoid(gate).astype(o.dtype)
    with jax.named_scope("raytpu.kda.project"):
        out = jnp.einsum("bsf,fd->bsd", o, wp["wo"].astype(c.dtype))
    return out, extra


def mla_expand(c_kv, k_r, wp, c: TransformerConfig):
    """The plain form's keys and values from latents: c_kv [B,S,r] and the
    shared rotary key k_r [B,S,1,rope] -> k [B,S,H,nope+rope], v
    [B,S,H,v]."""
    k_nope = jnp.einsum("bsc,chk->bshk", c_kv, wp["wuk"].astype(c.dtype))
    v = jnp.einsum("bsc,chk->bshk", c_kv, wp["wuv"].astype(c.dtype))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r, k_nope.shape[:3] + k_r.shape[3:])],
        axis=-1)
    return k, v


def index_project(h, c_q, ip, c: TransformerConfig, positions):
    """One indexer's side of a layer: from the layer's normed input ``h``
    [B,S,d] and its query latent ``c_q`` [B,S,r_q] the index queries
    [B,S,nI,dI] and the ONE key a token [B,S,dI] (LayerNorm, then like
    the queries rotary on the first ``qk_rope_dim`` dims), both in the
    compute dtype, and the heads' weights [B,S,nI] in float32, scaled by
    1/sqrt(nI dI). Row s then scores ``sum_j w_j relu(q_j . k_s)``."""
    f32 = jnp.float32
    with jax.named_scope("raytpu.dsa.index"):
        q = jnp.einsum("bsr,rjk->bsjk", c_q, ip["wq"].astype(c.dtype))
        k = jnp.einsum("bsd,dk->bsk", h, ip["wk"].astype(c.dtype))
        k32 = k.astype(f32)
        k32 = k32 - k32.mean(-1, keepdims=True)
        k32 = k32 * lax.rsqrt((k32 * k32).mean(-1, keepdims=True) + 1e-6)
        k = (k32 * ip["k_norm"]["scale"].astype(f32)
             + ip["k_norm"]["bias"].astype(f32)).astype(c.dtype)
        q, k = _rotary(q, k[:, :, None], c.qk_rope_dim, positions,
                       c.rope_theta)
        w = jnp.einsum("bsd,dj->bsj", h, ip["ww"].astype(c.dtype),
                       preferred_element_type=f32)
        w = w * (c.index_n_heads * c.index_head_dim) ** -0.5
    return q, k[:, :, 0], w


def _mla_mixer(h, wp, c: TransformerConfig, positions, attn_fn):
    """Latent attention. Scores are (q_nope . k_nope + q_rope . k_r) /
    sqrt(nope + rope); every head shares the one rotary key. ``attn_fn``
    is either the usual ``attn_fn(q, k, v)`` over per-head keys and values
    (the plain form: training, the uncached forward), or, marked
    ``attn_fn.latent``, ``attn_fn(q_nope, q_rope, c_kv, k_r, wp)`` working
    on the latents themselves and returning per-head outputs [B,S,H,v]:
    that is how the serving paths keep and walk a cache of latents."""
    r, nope = c.kv_lora_rank, c.qk_nope_dim
    with jax.named_scope("raytpu.mla.project"):
        if c.q_lora_rank:
            c_q = _rms_norm(
                jnp.einsum("bsd,dr->bsr", h, wp["wdq"].astype(c.dtype)),
                wp["q_norm"], c.norm_eps)
            q = jnp.einsum("bsr,rhk->bshk", c_q, wp["wuq"].astype(c.dtype))
        else:  # no bottleneck: the queries straight from the input
            q = jnp.einsum("bsd,dhk->bshk", h, wp["wq"].astype(c.dtype))
        kv = jnp.einsum("bsd,dr->bsr", h, wp["wdkv"].astype(c.dtype))
        c_kv = _rms_norm(kv[..., :r], wp["kv_norm"], c.norm_eps)
        q_rope, k_r = q[..., nope:], kv[:, :, None, r:]
        if c.mla_rope:  # else the shared dims are kept as they are
            q_rope, k_r = _rotary(q_rope, k_r, c.qk_rope_dim, positions,
                                  c.rope_theta)
        q_nope = q[..., :nope]
    chosen = ()
    if c.index_topk:
        if not hasattr(attn_fn, "choose"):
            raise NotImplementedError(
                "a block with an indexer runs on the serving paths only "
                "(generation.prefill_into_slot / decode_block)")
        chosen = (attn_fn.choose(
            lambda ip: index_project(h, c_q, ip, c, positions)),)
    with jax.named_scope("raytpu.mla.attend"):
        if getattr(attn_fn, "latent", False):
            attn_out = attn_fn(q_nope, q_rope, c_kv, k_r, wp, *chosen)
        else:
            k, v = mla_expand(c_kv, k_r, wp, c)
            attn_out = attn_fn(jnp.concatenate([q_nope, q_rope], -1), k, v)
    extra = None
    if isinstance(attn_out, tuple):
        attn_out, extra = attn_out
    with jax.named_scope("raytpu.mla.project"):
        a = jnp.einsum("bshk,hkd->bsd", attn_out, wp["wo"].astype(c.dtype))
    return a, extra


def _eva_whole_sequence(q, k, v, wp, c: TransformerConfig):
    """An "eva" layer's attention over whole sequences with nothing kept
    (the uncached forward): every window the sequence reaches is pooled
    (``ops/eva.eva_pool``; the last, where it is not whole, is padded with
    rows that no query sees as a summary) and the queries attend their
    own window and the summaries before it (``ops/eva.eva_attention``,
    told the sequences' own length: the padding is not attended)."""
    W = c.eva_window
    k, v = (jnp.pad(x, ((0, 0), (0, -x.shape[1] % W), (0, 0), (0, 0)))
            for x in (k, v))
    with jax.named_scope("raytpu.eva.pool"):
        ks, vs = eva_pool(k, v, wp["phi"], wp["mu"], c.eva_chunk)
    with jax.named_scope("raytpu.eva.attend"):
        out = eva_attention(q, k, v, ks, vs, window=W, chunk=c.eva_chunk)
    return out, None


def _eva_mixer(h, wp, c: TransformerConfig, positions, attn_fn):
    """An "eva" layer's mixer (``TransformerConfig.eva_window``): MHA
    projections with the model's rotary, then the layer's attention,
    ``attn_fn(q, k, v, wp) -> (out, extra)``: ``_eva_whole_sequence``
    with nothing kept (``forward``), or the serving paths' over the open
    window's rows and the closed windows' summaries that a slot keeps
    (``generation.py``)."""
    with jax.named_scope("raytpu.eva.project"):
        q = jnp.einsum("bsd,dhk->bshk", h, wp["wq"].astype(c.dtype))
        k = jnp.einsum("bsd,dhk->bshk", h, wp["wk"].astype(c.dtype))
        v = jnp.einsum("bsd,dhk->bshk", h, wp["wv"].astype(c.dtype))
        if c.rotary_dim:
            q, k = _rotary(q, k, c.rotary_dim, positions, c.rope_theta)
    out, extra = attn_fn(q, k, v, wp)
    with jax.named_scope("raytpu.eva.project"):
        out = jnp.einsum("bshk,hkd->bsd", out, wp["wo"].astype(c.dtype))
    return out, extra


def _attn_mixer(h, wp, c: TransformerConfig, positions, attn_fn):
    """An "attention" layer's mixer: ``c.mixer``'s."""
    return {"mha": _mha_mixer, "mla": _mla_mixer}[c.mixer](
        h, wp, c, positions, attn_fn)


# A layer's KIND as the program sees it is the key its mixer's weights sit
# under in ``lp`` (``init_params``); a "swa" layer's ``attn_fn`` is a
# window's.
_MIXERS = {"attn": _attn_mixer, "ssm": _ssm_mixer, "kda": _kda_mixer,
           "swa": partial(_mha_mixer, window=True), "mamba": _mamba_mixer,
           "gmu": _gmu_mixer, "cross": _cross_mixer, "eva": _eva_mixer}


def layer_kind(lp: Dict) -> str:
    """The kind of the layer whose weights are ``lp``: the one place that
    tells the kinds apart (``_MIXERS``, and ``generation``'s table of
    what each keeps of a slot and how it decodes and prefills). A layer
    with no mixer at all is a "single" block's routed FFN, "moe"."""
    return next((kind for kind in _MIXERS if kind in lp), "moe")


_ACTIVATIONS = {"gelu": jax.nn.gelu, "silu": jax.nn.silu,
                "relu2": lambda x: jnp.square(jax.nn.relu(x))}


def _dense_ffn(h, wp, c: TransformerConfig):
    act = _ACTIVATIONS[c.activation]
    m = jnp.einsum("bsd,df->bsf", h, wp["wi"].astype(c.dtype))
    if c.gated_ffn:
        m = act(jnp.einsum("bsd,df->bsf", h, wp["wg"].astype(c.dtype))) * m
    else:
        m = act(m)
    return jnp.einsum("bsf,fd->bsd", m, wp["wo"].astype(c.dtype))


def _routed_ffn(h, lp, c: TransformerConfig, token_mask):
    """A dropless routed layer's FFN as ``c`` describes it: (output, the
    layer's counters)."""
    from ray_tpu.ops.moe import routed_ffn

    return routed_ffn(
        h, lp["moe"], top_k=c.moe_top_k, route_scale=c.moe_route_scale,
        act=_ACTIVATIONS[c.activation], token_mask=token_mask,
        first_expert=c.moe_first_expert)


def apply_block(
    x: jax.Array,  # [B, S, D]
    lp: Dict,  # ONE layer's params (no leading L dim)
    config: TransformerConfig,
    positions: jax.Array,
    attn_fn,
    mesh: Optional[jax.sharding.Mesh] = None,
    token_mask: Optional[jax.Array] = None,  # [B, S] bool
):
    """One block as ``config`` describes it (mixer, residual form, FFN;
    ``config.block`` "single": ONE of them, under one norm).
    ``token_mask`` marks the tokens that count (a parked decode lane, the
    padding of a prompt do not): a dropless routed layer sends the others
    to no expert. Returns (y, aux_loss, extra, moe_stats): ``extra`` is
    what ``attn_fn`` returned beside its output, ``moe_stats`` a dict of
    int32 scalars from a dropless routed layer (else empty)."""
    c = config
    h = _norm(x, lp["ln1"], c)
    kind = layer_kind(lp)
    if c.block == "single":  # one branch: x + Branch(ln1 x)
        aux, stats = jnp.zeros((), jnp.float32), {}
        if kind == "moe":
            a, stats = _routed_ffn(h, lp, c, token_mask)
            extra = attn_fn()  # no mixer: what the caller keeps, as it is
        else:
            a, extra = _MIXERS[kind](h, lp[kind], c, positions, attn_fn)
        if c.residual_scale != 1.0:
            a = a * c.residual_scale
        return x + a, aux, extra, stats
    a, extra = _MIXERS[kind](h, lp[kind], c, positions, attn_fn)
    if c.residual_scale != 1.0:
        a = a * c.residual_scale
    if c.residual == "sequential":
        x = x + a
        h = _norm(x, lp["ln2"], c)
    aux, stats = jnp.zeros((), jnp.float32), {}
    if c.moe_experts and c.moe_impl == "dropless":
        m, stats = _routed_ffn(h, lp, c, token_mask)
    elif c.moe_experts:
        from ray_tpu.ops.moe import moe_ffn

        m, aux = moe_ffn(
            h,
            lp["moe"]["router"],
            lp["moe"]["wi"],
            lp["moe"]["wo"],
            top_k=c.moe_top_k,
            capacity_factor=c.moe_capacity_factor,
            mesh=mesh,
        )
    else:
        m = _dense_ffn(h, lp["mlp"], c)
    if c.residual_scale != 1.0:
        m = m * c.residual_scale
    if c.residual == "sequential":
        return x + m, aux, extra, stats
    return x + a + m, aux, extra, stats


def apply_layer(x, lp, config, positions, attn_fn, mesh=None):
    """``apply_block`` without the routed layer's counters: (y, aux_loss,
    extra). Shared by the scanned single-program forward below and the
    pipeline schedule (parallel/pipeline.py); the KV-cached generation
    paths (models/generation.py) call ``apply_block``. ``attn_fn(q, k,
    v)`` may return either the attention output or ``(output, extra)`` —
    ``extra`` (e.g. updated KV caches) is passed through."""
    return apply_block(x, lp, config, positions, attn_fn, mesh)[:3]


def remat_wrap(layer_fn, config: TransformerConfig):
    if not config.remat:
        return layer_fn
    cp = jax.checkpoint_policies
    if config.remat_policy == "full":
        policy = None  # save nothing: classic full-layer remat
    elif config.remat_policy == "dots":
        policy = cp.dots_with_no_batch_dims_saveable
    else:
        raise ValueError(f"unknown remat_policy {config.remat_policy!r}")
    return jax.checkpoint(layer_fn, policy=policy)


def forward(
    params: Dict,
    tokens: jax.Array,  # [B, S] int32
    config: TransformerConfig,
    mesh: Optional[jax.sharding.Mesh] = None,
    return_aux: bool = False,
):
    """Returns logits [B, S, vocab] (and the MoE aux loss if return_aux)."""
    c = config
    x = embed_tokens(params, tokens, c)  # [B, S, D]
    positions = jnp.arange(tokens.shape[1])
    attn_fn = select_attn_fn(c, mesh)

    def window_fn(q, k, v, sink):
        with jax.named_scope("raytpu.swa.attend"):
            return window_attention(q, k, v, sink, window=c.window)

    own = {"swa": window_fn, "eva": partial(_eva_whole_sequence, c=c),
           "moe": lambda: None}

    def handing(kind, handed):
        """``attn_fn`` of a layer of a model whose layers hand things on
        (``handed``: the last "mamba" layer's ``m`` and the last
        "attention" layer's keys and values, for the "gmu" and "cross"
        layers above). Each returns what it adds to ``handed`` as its
        extra."""
        if kind == "swa":
            return window_fn
        if kind == "attn":
            def rows_kept(q, k, v):
                return attn_fn(q, k, v), {"k": k, "v": v}
            return rows_kept
        if kind == "cross":
            return lambda q: (attn_fn(q, handed["k"], handed["v"]), None)
        # "gmu"; a "mamba" layer runs its own recurrence
        return recalling(handed["m"], None)

    hands = {"mamba", "gmu", "cross"} & set(c.layer_types)
    carry = (x, jnp.zeros((), jnp.float32), None)
    if hands:  # keys and values as the attentions see them: pairs as one
        r = 2 if c.diff_attn else 1
        lead = tokens.shape + (c.kv_heads // r,)
        carry = carry[:2] + ({
            "m": jnp.zeros(tokens.shape + (c.mamba_inner,), c.dtype),
            "k": jnp.zeros(lead + (r * c.d_head,), c.dtype),
            "v": jnp.zeros(lead + (r * c.v_dim,), c.dtype)},)
    for stack, lc, first in layer_groups(params, c):
        def layer(carry, lp, lc=lc):
            x, aux, handed = carry
            kind = layer_kind(lp)
            y, a, extra = apply_layer(
                x, lp, lc, positions,
                handing(kind, handed) if hands else own.get(kind, attn_fn),
                mesh=mesh)
            if hands and extra:
                handed = {**handed, **extra}
            return (y, aux + a, handed), None

        layer = remat_wrap(layer, c)
        if lc.layer_types:  # two kinds of layer: scan_stack orders them
            carry = scan_stack(lambda carry, lp, _li: layer(carry, lp)[0],
                               carry, stack, lc, first)
        else:
            carry, _ = lax.scan(layer, carry, stack)
    x, aux = carry[:2]
    logits = lm_logits(params, x, c)
    return (logits, aux) if return_aux else logits


def embed_tokens(params, tokens, c: TransformerConfig):
    """Token ids [...] -> the first hidden state [..., D]."""
    x = params["embed"].astype(c.dtype)[tokens]
    if c.residual_f32:
        x = x.astype(jnp.float32)
    return x * c.embed_scale if c.embed_scale != 1.0 else x


def lm_logits(params, x, c: TransformerConfig):
    """Hidden states [B, S, D] -> logits [B, S, V]: the final norm and
    the head (the embedding's transpose where tied). With
    ``c.n_pred_heads`` > 1: [B, S, n_pred_heads, V] in float32."""
    x = _norm(x, params["final_ln"], c)
    head = (params["embed"].T if c.tie_embeddings else params["lm_head"])
    if c.n_pred_heads > 1:
        return pred_logits(x, head, c)
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(c.dtype))
    return logits * c.logit_scale if c.logit_scale != 1.0 else logits


def pred_logits(x, head, c: TransformerConfig):
    """Several prediction heads side by side in ``head`` [D, n_pred_heads
    x V]: normed hidden states [..., D] -> logits [..., n_pred_heads, V]
    in float32 (operands in the compute dtype, float32 products, never
    rounded)."""
    logits = jnp.einsum("...d,dv->...v", x, head.astype(c.dtype),
                        preferred_element_type=jnp.float32)
    logits = logits.reshape(
        logits.shape[:-1] + (c.n_pred_heads, c.vocab_size))
    return logits * c.logit_scale if c.logit_scale != 1.0 else logits


def loss_fn(
    params: Dict,
    batch: Dict[str, jax.Array],  # tokens [B,S], targets [B,S], mask [B,S]
    config: TransformerConfig,
    mesh: Optional[jax.sharding.Mesh] = None,
) -> jax.Array:
    logits, aux = forward(
        params, batch["tokens"], config, mesh, return_aux=True
    )
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, batch["targets"][..., None], axis=-1)[..., 0]
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones_like(ll)
    ce = -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    if config.moe_experts and config.moe_impl == "capacity":
        ce = ce + config.moe_aux_weight * aux / config.n_layers
    return ce
