"""The benchmark's own arithmetic for configurations of kind
``serve_kda_moe`` (gated-delta-rule linear-attention layers that keep a
matrix state a head beside latent-attention layers that keep one row a
token, under a chip's share of dropless routed experts and a shared one;
Kimi-Linear's block): the program's config object from a published
``config.json``'s keys, the plain reference's constants, the weights from
a seed, the bytes a decode step must move and what the new kernel must
move and compute. Kept under ``benchmarks/`` so that no later PR that
claims a gain can change how a number is computed. ``param_count``,
``slot_bytes``, ``decode_step_bytes``, ``kda_update_cost`` and
``kda_chunk_flops`` are free of JAX.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

from benchmarks import common


def layer_kinds(model: Dict):
    """``linear_attn_config``'s two lists (layers counted from 1) as one
    kind a layer."""
    lin, n = model["linear_attn_config"], model["num_hidden_layers"]
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    if kda & full or kda | full != set(range(1, n + 1)):
        raise common.BenchFailure(
            "kda_layers and full_attn_layers must divide layers 1..n")
    return tuple("kda" if i in kda else "attention"
                 for i in range(1, n + 1))


def transformer_config(model: Dict, **over):
    """The published keys of ``configs/<name>.json`` (a kimi_linear
    ``config.json``) as the program's ``TransformerConfig``. The file's
    ``num_experts`` and ``vocab_size`` are what this chip HOLDS; the
    router's width is ``published.num_experts``."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    lin = model["linear_attn_config"]
    if model["num_expert_group"] != 1 or model["topk_group"] != 1 or (
            not model["moe_renormalize"]) or model["hidden_act"] != "silu" \
            or model["moe_router_activation_func"] != "sigmoid" or (
            model["tie_word_embeddings"]) or model["moe_layer_freq"] != 1 \
            or model["q_lora_rank"] is not None or not model["mla_use_nope"] \
            or model["rope_scaling"] is not None or (
            model["num_nextn_predict_layers"]) or (
            model["num_key_value_heads"] != model["num_attention_heads"]):
        raise common.BenchFailure(
            "the block here has no group limit, normalises the chosen "
            "sigmoid scores, unties the head, routes every layer after the "
            "leading dense ones, projects its queries directly, rotates "
            "nothing and predicts one token")
    kw = dict(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"], d_ff=model["intermediate_size"],
        max_seq_len=model["model_max_length"], mixer="mla", q_lora_rank=0,
        kv_lora_rank=model["kv_lora_rank"],
        qk_nope_dim=model["qk_nope_head_dim"],
        qk_rope_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"], mla_rope=False,
        rope_theta=float(model["rope_theta"]), residual="sequential",
        activation="silu", gated_ffn=True, norm_eps=model["rms_norm_eps"],
        layer_types=layer_kinds(model), kda_heads=lin["num_heads"],
        kda_head_dim=lin["head_dim"],
        kda_conv=lin["short_conv_kernel_size"], kda_chunk=64,
        moe_experts=model["published"]["num_experts"],
        moe_experts_held=model["num_experts"], moe_first_expert=0,
        moe_top_k=model["num_experts_per_token"], moe_impl="dropless",
        moe_d_ff=model["moe_intermediate_size"],
        moe_shared_experts=model["num_shared_experts"],
        moe_route_scale=model["routed_scaling_factor"],
        n_dense_layers=model["first_k_dense_replace"],
        param_dtype=jnp.bfloat16,
    )
    kw.update(over)
    return TransformerConfig(**kw)


def dims(cfg) -> Dict:
    """What the functions below and the result's ``model_dims`` use."""
    return {
        "vocab_size": cfg.vocab_size, "d_model": cfg.d_model,
        "n_layers": cfg.n_layers, "n_dense_layers": cfg.n_dense_layers,
        "n_full_layers": cfg.n_attn_layers, "n_kda_layers": cfg.n_kda_layers,
        "n_heads": cfg.n_heads, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_dim": cfg.qk_nope_dim, "qk_rope_dim": cfg.qk_rope_dim,
        "v_head_dim": cfg.v_head_dim, "d_ff": cfg.d_ff,
        "kda_heads": cfg.kda_heads, "kda_head_dim": cfg.kda_head_dim,
        "kda_conv": cfg.kda_conv, "kda_chunk": cfg.kda_chunk,
        "moe_experts": cfg.moe_experts,
        "moe_experts_held": cfg.experts_held, "moe_top_k": cfg.moe_top_k,
        "moe_d_ff": cfg.moe_d_ff,
        "moe_shared_experts": cfg.moe_shared_experts,
    }


def reference_constants(cfg) -> Dict:
    """``hp`` of ``benchmarks/reference_kda_moe.py``."""
    return {
        "n_heads": cfg.n_heads, "qk_nope": cfg.qk_nope_dim,
        "qk_rope": cfg.qk_rope_dim, "kv_rank": cfg.kv_lora_rank,
        "eps": cfg.norm_eps, "theta": cfg.rope_theta,
        "top_k": cfg.moe_top_k, "route_scale": cfg.moe_route_scale,
        "first_expert": cfg.moe_first_expert,
        "layer_types": cfg.layer_types,
        "n_dense_layers": cfg.n_dense_layers, "kda_heads": cfg.kda_heads,
        "kda_head_dim": cfg.kda_head_dim,
    }


def make_bf16_params(cfg, seed: int):
    """Every weight on the device from the seed, in ONE jitted call, in
    bf16 as the configuration is served, with the program's own
    initialisers (``init_params``: a "kda" layer's decay as the family
    publishes it). A layer exists in float32 only inside its own
    iteration."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import init_params

    n_dense = cfg.n_dense_layers
    lead = cfg.layer_types[0]  # the leading dense layers' one kind
    n_kda = cfg.n_kda_layers - (n_dense if lead == "kda" else 0)
    n_full = cfg.n_attn_layers - (n_dense if lead == "attention" else 0)
    stacks = {  # stack -> (one layer's config, its layers)
        "dense_layers": (dataclasses.replace(
            cfg, n_layers=1, n_dense_layers=1, layer_types=(lead,)),
            n_dense),
        "layers": (dataclasses.replace(
            cfg, n_layers=1, n_dense_layers=0, layer_types=("attention",)),
            n_full),
        "kda_layers": (dataclasses.replace(
            cfg, n_layers=1, n_dense_layers=0, layer_types=("kda",)), n_kda),
    }
    ends = dataclasses.replace(cfg.dense_variant(), n_layers=0,
                               layer_types=())

    @jax.jit
    def make(words):
        key = jax.random.fold_in(jax.random.key(words[0]), words[1])
        k_ends, *keys = jax.random.split(key, 1 + len(stacks))
        params = {k: v for k, v in init_params(ends, k_ends).items()
                  if k != "layers"}
        for (name, (one, n)), k in zip(stacks.items(), keys):
            if n:
                params[name] = jax.lax.map(
                    lambda k, one=one, name=name: jax.tree.map(
                        lambda x: x[0], init_params(one, k)[name]),
                    jax.random.split(k, n))
        return params

    return make(jnp.asarray(common.seed_words(seed), jnp.int32))


def param_count(c: Dict) -> Dict[str, int]:
    """Parameters of each piece, ``c`` from ``dims``: ISSUE 44's
    arithmetic."""
    d, h = c["d_model"], c["n_heads"]
    kh, kd = c["kda_heads"], c["kda_head_dim"]
    inner = kh * kd
    qk = c["qk_nope_dim"] + c["qk_rope_dim"]
    rank = c["kv_lora_rank"]
    expert = 3 * d * c["moe_d_ff"]
    out = {
        # q, k, v; the convolutions; a_log; dt_bias; the decay's pair; W_b;
        # the gate's pair; the head norm; W_o
        "kda": (3 * d * inner + c["kda_conv"] * 3 * inner + kh + inner
                + (d * kd + kd * inner) + d * kh + (d * kd + kd * inner)
                + kd + inner * d),
        # W_q; W_kva; its norm; W_kvb; W_o
        "attn_full": (d * h * qk + d * (rank + c["qk_rope_dim"]) + rank
                      + rank * h * (c["qk_nope_dim"] + c["v_head_dim"])
                      + h * c["v_head_dim"] * d),
        "dense_ffn": 3 * d * c["d_ff"], "expert": expert,
        "router": (d + 1) * c["moe_experts"],
        "shared": c["moe_shared_experts"] * expert,
        "ends": 2 * c["vocab_size"] * d + d,
    }
    out["routed"] = (out["router"] + c["moe_experts_held"] * expert
                     + out["shared"])
    out["total"] = (
        c["n_kda_layers"] * out["kda"]
        + c["n_full_layers"] * out["attn_full"] + c["n_layers"] * 2 * d
        + c["n_dense_layers"] * out["dense_ffn"]
        + (c["n_layers"] - c["n_dense_layers"]) * out["routed"]
        + out["ends"])
    return out


def slot_bytes(c: Dict, itemsize: int = 2) -> Dict[str, int]:
    """What one slot keeps: ``row`` bytes a cached token (the full
    layers' latent rows) and ``state`` bytes whatever its length (a "kda"
    layer's float32 matrix state a head and its convolution's tail)."""
    inner = c["kda_heads"] * c["kda_head_dim"]
    return {
        "row": itemsize * c["n_full_layers"] * (
            c["kv_lora_rank"] + c["qk_rope_dim"]),
        "state": c["n_kda_layers"] * (
            4 * inner * c["kda_head_dim"]
            + itemsize * (c["kda_conv"] - 1) * 3 * inner),
    }


def kda_update_cost(c: Dict, slot_layers: float) -> Dict[str, float]:
    """The least ``ops/kda.kda_update`` moves and computes for
    ``slot_layers`` (slot, layer) states stepped once: each float32 state
    read once and written once, and 7 operations a state element (four
    multiplies: the decay, ``k decayed``, ``k u^T``, ``q new``; three
    adds: the two sums over Dk and ``decayed + k u^T``). The vectors
    beside a state are 1/32 of it and left out."""
    elements = c["kda_heads"] * c["kda_head_dim"] ** 2
    return {"bytes": slot_layers * 2 * 4 * elements,
            "flops": slot_layers * 7 * elements}


def kda_chunk_flops(c: Dict, tokens: int) -> float:
    """Multiply-adds x 2 the chunked delta rule must do for ``tokens``
    valid tokens of one layer (whole chunks of them): a chunk a head,
    keys against keys and queries against keys (2 x C^2 D), the solve's
    right-hand sides (C^2 x 2 D / 2), the state's three products (W S,
    Q S, K^T U: 3 x C D^2) and the queries' own (C^2 D). What a tile's
    masked corner multiplies beyond this is the program's own business."""
    chunk, d = c["kda_chunk"], c["kda_head_dim"]
    chunks = -(-tokens // chunk)
    per = 2 * (2 * chunk * chunk * d + chunk * chunk * d
               + 3 * chunk * d * d + chunk * chunk * d)
    return float(chunks * c["kda_heads"] * per)


def decode_step_bytes(c: Dict, experts_touched: float, latent_rows: float,
                      live_slots: float, itemsize: int = 2) -> float:
    """The LEAST bytes one decode step must move, ``c`` from ``dims``:

    - every weight that is no routed expert, once: the mixers of both
      kinds, every layer's two norms, the dense layers' FFN, in each
      routed layer the router over ALL experts, its bias and the shared
      expert; the output head over the vocabulary held here and the final
      norm (the embedding is a gather of a few rows and is left out);
    - the routed experts held here THAT GOT A TOKEN: ``experts_touched``
      is their number summed over the step's routed layers (the engine's
      ``moe_experts_touched`` per step);
    - the float32 matrix state of every LIVE lane in every "kda" layer,
      read once and written once (``live_slots``: the engine's
      ``slot_steps`` per step; a parked lane's state is not moved, and
      the convolutions' tails, 1/28 of a state, are left out);
    - the latent rows the decode attention read, ``latent_rows`` (the
      engine's ``attn_rows_read`` per step: rows of a slot, each
      ``n_full_layers`` x (kv_lora_rank + qk_rope_dim) numbers).

    Weights and rows in ``itemsize`` bytes (bf16). The share of the HBM
    bandwidth this gives cannot pass 100 %."""
    n, d = param_count(c), c["d_model"]
    n_routed = c["n_layers"] - c["n_dense_layers"]
    fixed = (c["n_kda_layers"] * n["kda"]
             + c["n_full_layers"] * n["attn_full"] + c["n_layers"] * 2 * d
             + c["n_dense_layers"] * n["dense_ffn"]
             + n_routed * (n["router"] + n["shared"])
             + d * c["vocab_size"] + d)
    state = kda_update_cost(c, live_slots * c["n_kda_layers"])["bytes"]
    return float(
        itemsize * (fixed + experts_touched * n["expert"]
                    + latent_rows * c["n_full_layers"]
                    * (c["kv_lora_rank"] + c["qk_rope_dim"]))
        + state)
