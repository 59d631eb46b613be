"""The benchmark's own arithmetic for the latent-attention / routed-experts
configurations (kind ``serve_mla_moe``): the program's config object from a
published ``config.json``'s keys, the plain reference's constants, the
weights from a seed, and the bytes a decode step must read. Kept under
``benchmarks/`` so that no later PR that claims a gain can change how a
number is computed. Only ``decode_step_bytes`` is free of JAX.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

from benchmarks import common


def transformer_config(model: Dict, **over):
    """The published keys of ``configs/<name>.json`` (a glm4_moe_lite
    ``config.json``) as the program's ``TransformerConfig``."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    if model["n_group"] != 1 or model["topk_group"] != 1 or (
            not model["norm_topk_prob"]) or model["hidden_act"] != "silu" or (
            model["topk_method"] != "noaux_tc") or model["rope_scaling"] or (
            model["partial_rotary_factor"] != 1) or model["attention_bias"] \
            or model["tie_word_embeddings"]:
        raise common.BenchFailure(
            "the routed layer here has no group limit, normalises the "
            "chosen scores, rotates all rope dims, has no bias, and unties "
            "the head")
    kw = dict(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"], d_ff=model["intermediate_size"],
        max_seq_len=model["max_position_embeddings"], mixer="mla",
        q_lora_rank=model["q_lora_rank"], kv_lora_rank=model["kv_lora_rank"],
        qk_nope_dim=model["qk_nope_head_dim"],
        qk_rope_dim=model["qk_rope_head_dim"], v_head_dim=model["v_head_dim"],
        residual="sequential", activation="silu", gated_ffn=True,
        norm_eps=model["rms_norm_eps"], rope_theta=float(model["rope_theta"]),
        moe_experts=model["n_routed_experts"],
        moe_top_k=model["num_experts_per_tok"], moe_impl="dropless",
        moe_d_ff=model["moe_intermediate_size"],
        moe_shared_experts=model["n_shared_experts"],
        moe_route_scale=model["routed_scaling_factor"],
        n_dense_layers=model["first_k_dense_replace"],
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
        "moe_top_k": cfg.moe_top_k, "moe_d_ff": cfg.moe_d_ff,
        "moe_shared_experts": cfg.moe_shared_experts,
    }


def reference_constants(cfg) -> Dict:
    """``hp`` of ``benchmarks/reference_mla_moe.py``."""
    return {
        "n_heads": cfg.n_heads, "qk_nope": cfg.qk_nope_dim,
        "qk_rope": cfg.qk_rope_dim, "kv_rank": cfg.kv_lora_rank,
        "top_k": cfg.moe_top_k, "route_scale": cfg.moe_route_scale,
        "eps": cfg.norm_eps, "theta": cfg.rope_theta,
    }


def make_bf16_params(cfg, seed: int):
    """Every weight on the device from the seed, in ONE jitted call, in
    bf16 as the configuration is served. A layer exists in float32 only
    inside its own iteration (one expert layer's float32 copy is 2.5 GB)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import init_params

    n_dense = cfg.n_dense_layers
    one = dataclasses.replace(cfg, n_layers=1, n_dense_layers=0)
    dense = dataclasses.replace(cfg.dense_variant(), n_layers=1)
    ends = dataclasses.replace(cfg.dense_variant(), n_layers=0)

    @jax.jit
    def make(words):
        key = jax.random.fold_in(jax.random.key(words[0]), words[1])
        k_layers, k_dense, k_ends = jax.random.split(key, 3)

        def layer_of(c):
            return lambda k: jax.tree.map(
                lambda x: x[0], init_params(c, k)["layers"])

        params = {k: v for k, v in init_params(ends, k_ends).items()
                  if k != "layers"}
        params["layers"] = jax.lax.map(
            layer_of(one), jax.random.split(k_layers, cfg.n_layers - n_dense))
        if n_dense:
            params["dense_layers"] = jax.lax.map(
                layer_of(dense), jax.random.split(k_dense, n_dense))
        return params

    return make(jnp.asarray(common.seed_words(seed), jnp.int32))


def decode_step_bytes(c: Dict, latent_rows: float, experts_touched: float,
                      itemsize: int = 2) -> float:
    """The LEAST bytes one decode step must read, ``c`` from ``dims``:

    - every layer's attention weights (W_dq, W_uq, W_dkv, W_uk, W_uv, W_o)
      and its norms;
    - the dense layers' FFN; in each expert layer the router and its bias,
      the shared experts, and the routed experts THAT GOT A TOKEN:
      ``experts_touched`` is their number summed over the step's expert
      layers (the engine's ``moe_experts_touched`` per step), not all of
      them;
    - the output head and the final norm (the embedding is a gather of a
      few rows and is left out);
    - the latent rows of the live lanes: ``latent_rows`` = sum over live
      lanes of tokens already cached, each kv_lora_rank + qk_rope_dim
      numbers in every layer.

    All in ``itemsize`` bytes (bf16). What a step reads beyond this (whole
    chunks of latent rows up to the longest lane, for every lane; rows
    re-read) is its own business: the share of the HBM bandwidth this
    gives cannot pass 100 %."""
    d, h, L = c["d_model"], c["n_heads"], c["n_layers"]
    n_dense = c["n_dense_layers"]
    qk = c["qk_nope_dim"] + c["qk_rope_dim"]
    row = c["kv_lora_rank"] + c["qk_rope_dim"]
    attn = (d * c["q_lora_rank"] + c["q_lora_rank"] * h * qk + d * row
            + c["kv_lora_rank"] * h * (c["qk_nope_dim"] + c["v_head_dim"])
            + h * c["v_head_dim"] * d
            + c["q_lora_rank"] + c["kv_lora_rank"] + 2 * d)
    expert = 3 * d * c["moe_d_ff"]
    fixed = (L * attn + n_dense * 3 * d * c["d_ff"]
             + (L - n_dense) * ((d + 1) * c["moe_experts"]
                                + c["moe_shared_experts"] * expert)
             + d * c["vocab_size"] + d)
    return float(itemsize * (fixed + experts_touched * expert
                             + latent_rows * L * row))
