"""The benchmark's own arithmetic for configurations of kind
``serve_swa_moe`` (full attention layers that keep every row beside window
layers that keep a ring of their last rows and attend a learned sink,
under a chip's share of dropless routed experts; MiMo-V2-Flash's block):
the program's config object from a published ``config.json``'s keys, the
plain reference's constants, the weights from a seed, the bytes a decode
step must read and the operations a prefill's attention must do. Kept
under ``benchmarks/`` so that no later PR that claims a gain can change
how a number is computed. ``decode_step_bytes``, ``param_count`` and
``prefill_attention_flops`` are free of JAX.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

from benchmarks import common

_KINDS = {0: "attention", 1: "window"}


def transformer_config(model: Dict, **over):
    """The published keys of ``configs/<name>.json`` (a mimo_v2_flash
    ``config.json``) as the program's ``TransformerConfig``. The file's
    ``n_routed_experts`` and ``vocab_size`` are what this chip HOLDS; the
    router's width is ``published.n_routed_experts``."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    n = model["num_hidden_layers"]
    routed = list(model["moe_layer_freq"])
    n_dense = routed.count(0)
    if model["n_group"] != 1 or model["topk_group"] != 1 or (
            not model["norm_topk_prob"]) or model["hidden_act"] != "silu" or (
            model["topk_method"] != "noaux_tc") or (
            model["scoring_func"] != "sigmoid") or model["attention_bias"] \
            or model["tie_word_embeddings"] or model["n_shared_experts"] or (
            routed != [0] * n_dense + [1] * (n - n_dense)) or (
            len(model["hybrid_layer_pattern"]) != n) or (
            not model["add_swa_attention_sink_bias"]) or (
            model["add_full_attention_sink_bias"]) or (
            model["swa_head_dim"] != model["head_dim"]) or (
            model["swa_v_head_dim"] != model["v_head_dim"]) or (
            model["swa_num_attention_heads"] != model["num_attention_heads"]
            ) or model["sliding_window"] != model["sliding_window_size"]:
        raise common.BenchFailure(
            "the block here has no group limit, normalises the chosen "
            "sigmoid scores, has no bias and no shared expert, unties the "
            "head, puts its dense layers first, gives its window layers a "
            "sink and its full layers none, and both kinds the same query "
            "heads and head widths")
    kw = dict(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=n, n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], d_head=model["head_dim"],
        v_head_dim=model["v_head_dim"], d_ff=model["intermediate_size"],
        rotary_dim=int(model["partial_rotary_factor"] * model["head_dim"]),
        max_seq_len=model["max_position_embeddings"],
        residual="sequential", activation="silu", gated_ffn=True,
        norm_eps=model["layernorm_epsilon"],
        rope_theta=float(model["rope_theta"]),
        value_scale=model["attention_value_scale"],
        window=model["sliding_window"],
        window_kv_heads=model["swa_num_key_value_heads"],
        window_rope_theta=float(model["swa_rope_theta"]), window_sink=True,
        layer_types=tuple(_KINDS[k] for k in model["hybrid_layer_pattern"]),
        moe_experts=model["published"]["n_routed_experts"],
        moe_experts_held=model["n_routed_experts"], moe_first_expert=0,
        moe_top_k=model["num_experts_per_tok"], moe_impl="dropless",
        moe_d_ff=model["moe_intermediate_size"],
        moe_route_scale=model["routed_scaling_factor"] or 1.0,
        n_dense_layers=n_dense, param_dtype=jnp.bfloat16,
    )
    kw.update(over)
    return TransformerConfig(**kw)


def dims(cfg) -> Dict:
    """What the functions below and the result's ``model_dims`` use."""
    return {
        "vocab_size": cfg.vocab_size, "d_model": cfg.d_model,
        "n_layers": cfg.n_layers, "n_dense_layers": cfg.n_dense_layers,
        "n_full_layers": cfg.n_attn_layers,
        "n_window_layers": cfg.n_window_layers, "n_heads": cfg.n_heads,
        "n_kv_heads": cfg.kv_heads, "window_kv_heads": cfg.mha_kind(True)[0],
        "d_head": cfg.d_head, "v_head_dim": cfg.v_dim, "d_ff": cfg.d_ff,
        "window": cfg.window, "moe_experts": cfg.moe_experts,
        "moe_experts_held": cfg.experts_held, "moe_top_k": cfg.moe_top_k,
        "moe_d_ff": cfg.moe_d_ff,
    }


def reference_constants(cfg) -> Dict:
    """``hp`` of ``benchmarks/reference_swa_moe.py``."""
    return {
        "n_heads": cfg.n_heads,
        "kv_heads": {"F": cfg.mha_kind(False)[0], "W": cfg.mha_kind(True)[0]},
        "theta": {"F": cfg.mha_kind(False)[1], "W": cfg.mha_kind(True)[1]},
        "d_head": cfg.d_head, "rotary_dim": cfg.rotary_dim,
        "window": cfg.window, "value_scale": cfg.value_scale,
        "eps": cfg.norm_eps, "top_k": cfg.moe_top_k,
        "route_scale": cfg.moe_route_scale,
        "first_expert": cfg.moe_first_expert,
        "layer_types": cfg.layer_types, "n_dense_layers": cfg.n_dense_layers,
    }


def make_bf16_params(cfg, seed: int):
    """Every weight on the device from the seed, in ONE jitted call, in
    bf16 as the configuration is served, with the program's own
    initialisers (``init_params``: a window layer's sink logits around
    ln(window)). A layer exists in float32 only inside its own
    iteration."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import init_params

    n_dense = cfg.n_dense_layers
    stacks = {  # stack -> (one layer's config, its layers)
        "dense_layers": (dataclasses.replace(
            cfg.dense_variant(), n_layers=1, layer_types=("attention",)),
            n_dense),
        "layers": (dataclasses.replace(
            cfg, n_layers=1, n_dense_layers=0, layer_types=("attention",)),
            cfg.n_attn_layers - n_dense),
        "window_layers": (dataclasses.replace(
            cfg, n_layers=1, n_dense_layers=0, layer_types=("window",)),
            cfg.n_window_layers),
    }
    ends = dataclasses.replace(cfg.dense_variant(), n_layers=0,
                               layer_types=(), window=0)

    @jax.jit
    def make(words):
        key = jax.random.fold_in(jax.random.key(words[0]), words[1])
        k_ends, *keys = jax.random.split(key, 1 + len(stacks))
        params = {k: v for k, v in init_params(ends, k_ends).items()
                  if k != "layers"}
        for (name, (one, n)), k in zip(stacks.items(), keys):
            if n:
                # a one-layer model of the other kind has an empty
                # "layers" stack beside it
                src = "layers" if name == "dense_layers" else name
                params[name] = jax.lax.map(
                    lambda k, one=one, src=src: jax.tree.map(
                        lambda x: x[0], init_params(one, k)[src]),
                    jax.random.split(k, n))
        return params

    return make(jnp.asarray(common.seed_words(seed), jnp.int32))


def param_count(c: Dict) -> Dict[str, int]:
    """Parameters of each piece, ``c`` from ``dims``: ISSUE 39's
    arithmetic."""
    d, h, dk, dv = c["d_model"], c["n_heads"], c["d_head"], c["v_head_dim"]

    def attn(h_kv):
        return d * dk * (h + h_kv) + d * dv * h_kv + h * dv * d

    expert = 3 * d * c["moe_d_ff"]
    routed = (d + 1) * c["moe_experts"] + c["moe_experts_held"] * expert
    out = {
        "attn_full": attn(c["n_kv_heads"]),
        "attn_window": attn(c["window_kv_heads"]) + h,  # + a sink a head
        "dense_ffn": 3 * d * c["d_ff"], "expert": expert, "routed": routed,
        "ends": 2 * c["vocab_size"] * d + d,
    }
    n_full_routed = c["n_full_layers"] - c["n_dense_layers"]
    out["total"] = (
        c["n_full_layers"] * out["attn_full"]
        + c["n_window_layers"] * out["attn_window"] + c["n_layers"] * 2 * d
        + c["n_dense_layers"] * out["dense_ffn"]
        + (n_full_routed + c["n_window_layers"]) * routed + out["ends"])
    return out


def slot_bytes(c: Dict, itemsize: int = 2) -> Dict[str, int]:
    """What one slot keeps: ``row`` bytes a cached token (the full layers'
    K and V) and ``state`` bytes whatever its length (the window layers'
    rings)."""
    return {
        "row": itemsize * c["n_full_layers"] * c["n_kv_heads"] * (
            c["d_head"] + c["v_head_dim"]),
        "state": itemsize * c["n_window_layers"] * c["window"]
        * c["window_kv_heads"] * (c["d_head"] + c["v_head_dim"]),
    }


def decode_step_bytes(c: Dict, experts_touched: float, full_rows: float,
                      ring_rows: float, itemsize: int = 2) -> float:
    """The LEAST bytes one decode step must read, ``c`` from ``dims``:

    - every layer's attention weights (W_q, W_k, W_v, W_o of its kind, a
      window layer's sinks) and its two norms; the dense layers' FFN; in
      each routed layer the router over ALL experts and its bias, and the
      routed experts held here THAT GOT A TOKEN: ``experts_touched`` is
      their number summed over the step's routed layers (the engine's
      ``moe_experts_touched`` per step);
    - the output head over the vocabulary held here and the final norm
      (the embedding is a gather of a few rows and is left out);
    - the full layers' rows the decode attention read, ``full_rows`` (the
      engine's ``attn_rows_read`` per step: rows of a slot, each
      ``n_full_layers`` x Hkv x (D + Dv) numbers);
    - the ring rows it read, ``ring_rows`` (the engine's
      ``window_rows_read`` per step: summed over lanes AND window layers,
      each Hkv_w x (D + Dv) numbers).

    All in ``itemsize`` bytes (bf16). What a step reads beyond this (an
    expert's weights twice, a chunk's dead rows) is its own business: the
    share of the HBM bandwidth this gives cannot pass 100 %."""
    n, d = param_count(c), c["d_model"]
    n_routed = c["n_layers"] - c["n_dense_layers"]
    fixed = (c["n_full_layers"] * n["attn_full"]
             + c["n_window_layers"] * n["attn_window"]
             + c["n_layers"] * 2 * d + c["n_dense_layers"] * n["dense_ffn"]
             + n_routed * (d + 1) * c["moe_experts"]
             + d * c["vocab_size"] + d)
    width = c["d_head"] + c["v_head_dim"]
    return float(itemsize * (
        fixed + experts_touched * n["expert"]
        + full_rows * c["n_full_layers"] * c["n_kv_heads"] * width
        + ring_rows * c["window_kv_heads"] * width))


def prefill_attention_flops(c: Dict, tokens: int) -> Dict[str, float]:
    """Multiply-adds x 2 of one prompt's attention proper (scores and
    values, no projection), a layer of each kind: a full layer's over the
    rows s <= t, a window layer's over min(t + 1, window) rows a query.
    What a tiled program multiplies beyond this (a tile's masked corner,
    the rows of a block's stretch outside a query's window) is its own
    business."""
    per_pair = 2 * c["n_heads"] * (c["d_head"] + c["v_head_dim"])
    w = min(c["window"], tokens)
    return {
        "full": per_pair * tokens * (tokens + 1) / 2,
        "window": per_pair * (w * (w + 1) / 2 + (tokens - w) * w),
    }
