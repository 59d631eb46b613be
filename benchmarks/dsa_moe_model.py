"""The benchmark's own arithmetic for configurations of kind
``serve_dsa_moe`` (latent attention with a learned selection of cache
rows, a chip's share of dropless routed experts; GLM-5.2's block): the
program's config object from a published ``config.json``'s keys, the
plain reference's constants, the weights from a seed, and the bytes a
decode step must read. Kept under ``benchmarks/`` so that no later PR
that claims a gain can change how a number is computed. Only
``decode_step_bytes`` is free of JAX.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

from benchmarks import common


def transformer_config(model: Dict, **over):
    """The published keys of ``configs/<name>.json`` (a glm_moe_dsa
    ``config.json``) as the program's ``TransformerConfig``. The file's
    ``n_routed_experts`` and ``vocab_size`` are what this chip HOLDS; the
    router's width is ``published.n_routed_experts``."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    n_dense = model["first_k_dense_replace"]
    kinds = ["dense"] * n_dense + ["sparse"] * (
        model["num_hidden_layers"] - n_dense)
    if model["n_group"] != 1 or model["topk_group"] != 1 or (
            not model["norm_topk_prob"]) or model["hidden_act"] != "silu" or (
            model["topk_method"] != "noaux_tc") or (
            model["scoring_func"] != "sigmoid") or model["attention_bias"] \
            or model["tie_word_embeddings"] or model["index_topk_pattern"] \
            or model["mlp_layer_types"] != kinds or (
            model["rope_parameters"]["rope_type"] != "default"):
        raise common.BenchFailure(
            "the block here has no group limit, normalises the chosen "
            "sigmoid scores, has no bias, unties the head, scores every "
            "row s <= t on a full layer, and puts its dense layers first")
    kw = dict(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"], d_ff=model["intermediate_size"],
        max_seq_len=model["max_position_embeddings"], mixer="mla",
        q_lora_rank=model["q_lora_rank"], kv_lora_rank=model["kv_lora_rank"],
        qk_nope_dim=model["qk_nope_head_dim"],
        qk_rope_dim=model["qk_rope_head_dim"], v_head_dim=model["v_head_dim"],
        residual="sequential", activation="silu", gated_ffn=True,
        norm_eps=model["rms_norm_eps"],
        rope_theta=float(model["rope_parameters"]["rope_theta"]),
        moe_experts=model["published"]["n_routed_experts"],
        moe_experts_held=model["n_routed_experts"], moe_first_expert=0,
        moe_top_k=model["num_experts_per_tok"], moe_impl="dropless",
        moe_d_ff=model["moe_intermediate_size"],
        moe_shared_experts=model["n_shared_experts"],
        moe_route_scale=model["routed_scaling_factor"],
        n_dense_layers=n_dense, index_topk=model["index_topk"],
        index_n_heads=model["index_n_heads"],
        index_head_dim=model["index_head_dim"],
        indexer_types=tuple(model["indexer_types"]),
        param_dtype=jnp.bfloat16,
    )
    kw.update(over)
    return TransformerConfig(**kw)


def dims(cfg) -> Dict:
    """What ``decode_step_bytes`` and the result's ``model_dims`` use."""
    return {
        "vocab_size": cfg.vocab_size, "d_model": cfg.d_model,
        "n_layers": cfg.n_layers, "n_dense_layers": cfg.n_dense_layers,
        "n_heads": cfg.n_heads, "d_ff": cfg.d_ff,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_dim": cfg.qk_nope_dim, "qk_rope_dim": cfg.qk_rope_dim,
        "v_head_dim": cfg.v_head_dim, "moe_experts": cfg.moe_experts,
        "moe_experts_held": cfg.experts_held,
        "moe_top_k": cfg.moe_top_k, "moe_d_ff": cfg.moe_d_ff,
        "moe_shared_experts": cfg.moe_shared_experts,
        "index_topk": cfg.index_topk, "index_n_heads": cfg.index_n_heads,
        "index_head_dim": cfg.index_head_dim,
        "n_index_layers": cfg.n_index_layers,
    }


def reference_constants(cfg) -> Dict:
    """``hp`` of ``benchmarks/reference_dsa_moe.py``."""
    return {
        "n_heads": cfg.n_heads, "qk_nope": cfg.qk_nope_dim,
        "qk_rope": cfg.qk_rope_dim, "kv_rank": cfg.kv_lora_rank,
        "top_k": cfg.moe_top_k, "route_scale": cfg.moe_route_scale,
        "eps": cfg.norm_eps, "theta": cfg.rope_theta,
        "index_topk": cfg.index_topk, "indexer_types": cfg.indexer_types,
        "first_expert": cfg.moe_first_expert,
    }


def make_bf16_params(cfg, seed: int):
    """Every weight on the device from the seed, in ONE jitted call, in
    bf16 as the configuration is served. A layer exists in float32 only
    inside its own iteration. Every layer is drawn with an indexer; the
    layers that own none drop theirs."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import init_params

    n_dense = cfg.n_dense_layers
    full = ("full",)
    one = dataclasses.replace(cfg, n_layers=1, n_dense_layers=0,
                              indexer_types=full)
    dense = dataclasses.replace(cfg.dense_variant(), n_layers=1,
                                indexer_types=full)
    ends = dataclasses.replace(cfg.dense_variant(), n_layers=0,
                               index_topk=0, indexer_types=())

    def stack_of(c, keys, kinds):
        layers = jax.lax.map(lambda k: jax.tree.map(
            lambda x: x[0], init_params(c, k)["layers"]), keys)
        own = jnp.asarray([i for i, k in enumerate(kinds) if k == "full"],
                          jnp.int32)
        indexer = layers["attn"].pop("indexer")
        if len(own):  # [layers, ...] -> [own layers, ...]
            layers["attn"]["indexer"] = jax.tree.map(
                lambda a: a[own], indexer)
        return layers

    @jax.jit
    def make(words):
        key = jax.random.fold_in(jax.random.key(words[0]), words[1])
        k_layers, k_dense, k_ends = jax.random.split(key, 3)
        params = {k: v for k, v in init_params(ends, k_ends).items()
                  if k != "layers"}
        params["layers"] = stack_of(
            one, jax.random.split(k_layers, cfg.n_layers - n_dense),
            cfg.indexer_types[n_dense:])
        if n_dense:
            params["dense_layers"] = stack_of(
                dense, jax.random.split(k_dense, n_dense),
                cfg.indexer_types[:n_dense])
        return params

    return make(jnp.asarray(common.seed_words(seed), jnp.int32))


def decode_step_bytes(c: Dict, experts_touched: float, index_rows: float,
                      latent_rows: float, itemsize: int = 2) -> float:
    """The LEAST bytes one decode step must read, ``c`` from ``dims``:

    - every layer's attention weights (W_dq, W_uq, W_dkv, W_uk, W_uv, W_o)
      and its norms; the indexer's weights (W_Iq, W_Ik, W_Iw, its
      LayerNorm) on each layer that owns one;
    - the dense layers' FFN; in each expert layer the router over ALL
      experts and its bias, the shared experts, and the routed experts
      held here THAT GOT A TOKEN: ``experts_touched`` is their number
      summed over the step's expert layers (the engine's
      ``moe_experts_touched`` per step);
    - the output head over the vocabulary held here and the final norm
      (the embedding is a gather of a few rows and is left out);
    - the index keys scored, ``index_rows`` (the engine's
      ``dsa_rows_scored`` per step: summed over lanes and indexed
      layers), each index_head_dim numbers;
    - the latent rows attended, ``latent_rows`` (the engine's
      ``dsa_rows_selected`` per step: min(pos + 1, index_topk) a live
      lane a layer), each kv_lora_rank + qk_rope_dim numbers.

    All in ``itemsize`` bytes (bf16). What a step reads beyond this (a
    gathered row's padding to whole lanes, parked lanes' rows) is its own
    business: the share of the HBM bandwidth this gives cannot pass
    100 %."""
    d, h, L = c["d_model"], c["n_heads"], c["n_layers"]
    n_dense = c["n_dense_layers"]
    qk = c["qk_nope_dim"] + c["qk_rope_dim"]
    row = c["kv_lora_rank"] + c["qk_rope_dim"]
    attn = (d * c["q_lora_rank"] + c["q_lora_rank"] * h * qk + d * row
            + c["kv_lora_rank"] * h * (c["qk_nope_dim"] + c["v_head_dim"])
            + h * c["v_head_dim"] * d
            + c["q_lora_rank"] + c["kv_lora_rank"] + 2 * d)
    indexer = (c["q_lora_rank"] * c["index_n_heads"] * c["index_head_dim"]
               + d * (c["index_head_dim"] + c["index_n_heads"])
               + 2 * c["index_head_dim"])
    expert = 3 * d * c["moe_d_ff"]
    fixed = (L * attn + c["n_index_layers"] * indexer
             + n_dense * 3 * d * c["d_ff"]
             + (L - n_dense) * ((d + 1) * c["moe_experts"]
                                + c["moe_shared_experts"] * expert)
             + d * c["vocab_size"] + d)
    return float(itemsize * (fixed + experts_touched * expert
                             + index_rows * c["index_head_dim"]
                             + latent_rows * row))
