"""The benchmark's own arithmetic for configurations of kind
``serve_ssm_moe`` (layers that are ONE branch each: a Mamba-2 mixer with a
fixed-size recurrent state, an attention with K/V rows, or a chip's share
of routed squared-ReLU experts in a latent beside a full-width shared
expert; Nemotron-3-Super's kind): the program's config object from a
published ``config.json``'s keys, the plain reference's constants, the
weights from a seed, and the bytes a decode step and its grouped products
must move. Kept under ``benchmarks/`` so that no later PR that claims a
gain can change how a number is computed. Only the byte functions are free
of JAX.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

from benchmarks import common

_KINDS = {"M": "ssm", "*": "attention", "E": "experts"}


def transformer_config(model: Dict, **over):
    """The published keys of ``configs/<name>.json`` (a nemotron_h
    ``config.json``) as the program's ``TransformerConfig``.
    ``n_routed_experts`` counts the experts HELD here (the first of the
    layer's ``published.n_routed_experts``)."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    pattern = model["hybrid_override_pattern"]
    if set(pattern) - set(_KINDS) or len(pattern) != (
            model["num_hidden_layers"]) or model["mlp_hidden_act"] != (
            "relu2") or model["mamba_hidden_act"] != "silu" or (
            model["attention_bias"] or model["mlp_bias"] or model["use_bias"]
            or model["mamba_proj_bias"]) or not model["use_conv_bias"] or (
            model["tie_word_embeddings"]) or model["n_group"] != 1 or (
            model["topk_group"] != 1) or not model["norm_topk_prob"] or (
            model["residual_in_fp32"]) or model["norm_eps"] != (
            model["layer_norm_epsilon"]):
        raise common.BenchFailure(
            "the block here is a pattern of M, * and E layers alone, "
            "squared-ReLU experts chosen from ONE group with normalised "
            "weights, a SiLU convolution with a bias, no other bias, an "
            "untied head and a residual stream in the compute dtype")
    kw = dict(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], d_head=model["head_dim"],
        d_ff=model["intermediate_size"], rotary_dim=0,
        max_seq_len=model["max_position_embeddings"],
        residual="sequential", activation="relu2", gated_ffn=False,
        norm_eps=model["norm_eps"], block="single",
        layer_types=tuple(_KINDS[k] for k in pattern),
        ssm_heads=model["mamba_num_heads"],
        ssm_head_dim=model["mamba_head_dim"],
        ssm_state=model["ssm_state_size"], ssm_groups=model["n_groups"],
        ssm_norm_groups=model["n_groups"], ssm_conv=model["conv_kernel"],
        ssm_chunk=model["chunk_size"],
        moe_experts=model["published"]["n_routed_experts"],
        moe_experts_held=model["n_routed_experts"], moe_first_expert=0,
        moe_top_k=model["num_experts_per_tok"], moe_impl="dropless",
        moe_d_ff=model["moe_intermediate_size"],
        moe_latent=model["moe_latent_size"],
        moe_shared_experts=model["n_shared_experts"],
        moe_shared_d_ff=model["moe_shared_expert_intermediate_size"],
        moe_route_scale=float(model["routed_scaling_factor"]),
        param_dtype=jnp.bfloat16,
    )
    if kw["ssm_heads"] * kw["ssm_head_dim"] != (
            model["expand"] * model["hidden_size"]):
        raise common.BenchFailure(
            "expand x hidden_size is not mamba_num_heads x mamba_head_dim")
    kw.update(over)
    return TransformerConfig(**kw)


def dims(cfg) -> Dict:
    """What the functions below and the result's ``model_dims`` use."""
    return {
        "vocab_size": cfg.vocab_size, "d_model": cfg.d_model,
        "n_layers": cfg.n_layers, "n_attn_layers": cfg.n_attn_layers,
        "n_ssm_layers": cfg.n_ssm_layers,
        "n_expert_layers": cfg.n_expert_layers, "n_heads": cfg.n_heads,
        "n_kv_heads": cfg.kv_heads, "d_head": cfg.d_head,
        "ssm_heads": cfg.ssm_heads, "ssm_head_dim": cfg.ssm_head_dim,
        "ssm_state": cfg.ssm_state, "ssm_groups": cfg.ssm_groups,
        "ssm_conv": cfg.ssm_conv, "moe_experts": cfg.moe_experts,
        "moe_experts_held": cfg.experts_held, "moe_top_k": cfg.moe_top_k,
        "moe_d_ff": cfg.moe_d_ff, "moe_latent": cfg.moe_latent,
        "moe_shared_d_ff": cfg.moe_shared_d_ff * cfg.moe_shared_experts,
    }


def reference_constants(cfg) -> Dict:
    """``hp`` of ``benchmarks/reference_ssm_moe.py``."""
    return {
        "n_heads": cfg.n_heads, "n_kv_heads": cfg.kv_heads,
        "d_head": cfg.d_head, "eps": cfg.norm_eps,
        "layer_types": cfg.layer_types, "ssm_heads": cfg.ssm_heads,
        "ssm_head_dim": cfg.ssm_head_dim, "ssm_state": cfg.ssm_state,
        "ssm_groups": cfg.ssm_groups, "norm_groups": cfg.ssm_norm_groups,
        "top_k": cfg.moe_top_k, "route_scale": cfg.moe_route_scale,
        "first_expert": cfg.moe_first_expert,
    }


def make_bf16_params(cfg, seed: int):
    """Every weight on the device from the seed, in ONE jitted call, in
    bf16 as the configuration is served, with the program's own
    initialisers (``init_params``: the state-space layers' decay, step and
    convolution as the family publishes them). A layer exists in float32
    only inside its own iteration."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import init_params

    stacks = {"attention": "layers", "ssm": "ssm_layers",
              "experts": "expert_layers"}
    # a model with no routed layer has no experts to describe
    unrouted = dict(moe_experts=0, moe_experts_held=0, moe_latent=0,
                    moe_shared_d_ff=0)
    one = {kind: dataclasses.replace(
        cfg, n_layers=1, layer_types=(kind,),
        **({} if kind == "experts" else unrouted)) for kind in stacks}
    ends = dataclasses.replace(one["attention"], n_layers=0, layer_types=(),
                               block="pair")

    @jax.jit
    def make(words):
        key = jax.random.fold_in(jax.random.key(words[0]), words[1])
        k_ends, *keys = jax.random.split(key, 1 + len(stacks))
        params = {k: v for k, v in init_params(ends, k_ends).items()
                  if k != "layers"}
        for (kind, name), k in zip(stacks.items(), keys):
            n = sum(t == kind for t in cfg.layer_types)
            if n:
                params[name] = jax.lax.map(
                    lambda k, kind=kind, name=name: jax.tree.map(
                        lambda x: x[0], init_params(one[kind], k)[name]),
                    jax.random.split(k, n))
        return params

    return make(jnp.asarray(common.seed_words(seed), jnp.int32))


def param_count(c: Dict) -> Dict[str, int]:
    """Parameters of each piece, ``c`` from ``dims``: ISSUE 60's
    arithmetic. A layer is one branch and ONE norm."""
    d, inner = c["d_model"], c["ssm_heads"] * c["ssm_head_dim"]
    width = inner + 2 * c["ssm_groups"] * c["ssm_state"]
    lat, f = c["moe_latent"], c["moe_d_ff"]
    out = {
        # W_z, W_xbc, W_dt; W_out; the convolution and its bias; a_log,
        # dt_bias, D a head; the gated norm's scale
        "ssm": (d * (inner + width + c["ssm_heads"]) + inner * d
                + width * (c["ssm_conv"] + 1) + 3 * c["ssm_heads"] + inner),
        "attn": d * c["d_head"] * (2 * c["n_heads"] + 2 * c["n_kv_heads"]),
        "expert": 2 * lat * f,
        "router": (d + 1) * c["moe_experts"],
        "latent": 2 * d * lat,
        "shared": 2 * d * c["moe_shared_d_ff"],
        "ends": 2 * c["vocab_size"] * d + d,
    }
    out["routed"] = (out["router"] + out["latent"] + out["shared"]
                     + c["moe_experts_held"] * out["expert"])
    out["total"] = (c["n_ssm_layers"] * out["ssm"]
                    + c["n_attn_layers"] * out["attn"]
                    + c["n_expert_layers"] * out["routed"]
                    + c["n_layers"] * d + out["ends"])
    return out


def slot_state_bytes(c: Dict) -> int:
    """What one slot keeps whatever its length: a float32 state a head
    and the convolution's last inputs in bf16, every state-space layer."""
    inner = c["ssm_heads"] * c["ssm_head_dim"]
    width = inner + 2 * c["ssm_groups"] * c["ssm_state"]
    return c["n_ssm_layers"] * (inner * c["ssm_state"] * 4
                                + (c["ssm_conv"] - 1) * width * 2)


def slot_row_bytes(c: Dict, itemsize: int = 2) -> int:
    """What one slot keeps a cached token: the attention layers' K and V."""
    return itemsize * c["n_attn_layers"] * 2 * c["n_kv_heads"] * c["d_head"]


def grouped_products_cost(c: Dict, experts_touched: float,
                          pairs: float, itemsize: int = 2) -> Dict[str, float]:
    """The least the two grouped products of the routed layers
    (``ops/grouped_matmul``) move and compute for ``experts_touched``
    (layer, expert) pairs that got a token and ``pairs`` (token, expert)
    pairs in all: both matrices of every touched expert once, [l, f] and
    [f, l]; a pair's row in (l) and out (f) of the first product and in
    (f) and out (l) of the second; 2 x l x f multiply-adds x 2 a pair."""
    lat, f = c["moe_latent"], c["moe_d_ff"]
    return {"bytes": itemsize * (experts_touched * 2 * lat * f
                                 + pairs * 2 * (lat + f)),
            "flops": pairs * 2 * 2 * lat * f}


def decode_step_bytes(c: Dict, experts_touched: float, slots_updated: float,
                      kv_rows: float, itemsize: int = 2) -> float:
    """The LEAST bytes one decode step must move, ``c`` from ``dims``:

    - every weight that is no routed expert, once: the state-space and the
      attention layers, every layer's norm, in each routed layer the
      router over ALL experts with its bias, the two projections round the
      latent and the shared expert; the output head over the vocabulary
      held here and the final norm (the embedding is a gather of a few
      rows and is left out);
    - the routed experts held here THAT GOT A TOKEN: ``experts_touched``
      is their number summed over the step's routed layers (the engine's
      ``moe_experts_touched`` per step), two matrices each;
    - for every slot the program updates, ``slots_updated`` (the engine's
      ``state_slots_updated`` per step and state layer: the live lanes'),
      its state READ AND WRITTEN: 2 x ``slot_state_bytes``;
    - the K/V rows the counters say the decode attention read,
      ``kv_rows`` (the engine's ``attn_rows_read`` per step: rows of a
      slot, each ``slot_row_bytes``).

    What a step moves beyond this (a state read twice, a row's padding,
    the pairs' rows round the grouped products) is its own business: the
    share of the HBM bandwidth this gives cannot pass 100 %."""
    n, d = param_count(c), c["d_model"]
    fixed = (c["n_ssm_layers"] * n["ssm"] + c["n_attn_layers"] * n["attn"]
             + c["n_expert_layers"] * (n["router"] + n["latent"]
                                       + n["shared"])
             + c["n_layers"] * d + d * c["vocab_size"] + d)
    return float(itemsize * (fixed + experts_touched * n["expert"])
                 + kv_rows * slot_row_bytes(c, itemsize)
                 + 2 * slots_updated * slot_state_bytes(c))
