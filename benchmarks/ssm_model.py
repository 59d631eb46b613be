"""The benchmark's own arithmetic for configurations of kind ``serve_ssm``
(state-space layers with a fixed-size recurrent state beside attention
layers with K/V rows; granite-4.0-h-micro's kind): the program's config
object from a published ``config.json``'s keys, the plain reference's
constants, the weights from a seed, and the bytes a decode step must
move. Kept under ``benchmarks/`` so that no later PR that claims a gain
can change how a number is computed. Only ``decode_step_bytes`` is free
of JAX.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

from benchmarks import common

_KINDS = {"mamba": "ssm", "attention": "attention"}


def transformer_config(model: Dict, **over):
    """The published keys of ``configs/<name>.json`` (a granitemoehybrid
    ``config.json``) as the program's ``TransformerConfig``."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    if model["num_local_experts"] or model["num_experts_per_tok"] or (
            model["position_embedding_type"] != "nope") or (
            model["hidden_act"] != "silu") or model["attention_bias"] or (
            model["mamba_proj_bias"]) or not model["mamba_conv_bias"] or (
            not model["tie_word_embeddings"]) or (
            model["normalization_function"] != "rmsnorm") or (
            model["shared_intermediate_size"] != model["intermediate_size"]):
        raise common.BenchFailure(
            "the block here has no routed part, no positional term, no "
            "projection bias, a bias on its convolution, a tied head, "
            "RMSNorm and one gated SiLU FFN a layer")
    d_head = model["hidden_size"] // model["num_attention_heads"]
    kw = dict(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], d_head=d_head,
        d_ff=model["shared_intermediate_size"], rotary_dim=0,
        max_seq_len=model["max_position_embeddings"],
        residual="sequential", activation="silu", gated_ffn=True,
        norm_eps=model["rms_norm_eps"], tie_embeddings=True,
        layer_types=tuple(_KINDS[k] for k in model["layer_types"]),
        ssm_heads=model["mamba_n_heads"], ssm_head_dim=model["mamba_d_head"],
        ssm_state=model["mamba_d_state"], ssm_groups=model["mamba_n_groups"],
        ssm_conv=model["mamba_d_conv"], ssm_chunk=model["mamba_chunk_size"],
        embed_scale=float(model["embedding_multiplier"]),
        residual_scale=model["residual_multiplier"],
        logit_scale=1.0 / model["logits_scaling"],
        attn_scale=model["attention_multiplier"],
        param_dtype=jnp.bfloat16,
    )
    if kw["ssm_heads"] * kw["ssm_head_dim"] != (
            model["mamba_expand"] * model["hidden_size"]):
        raise common.BenchFailure("mamba_expand x hidden_size is not "
                                  "mamba_n_heads x mamba_d_head")
    kw.update(over)
    return TransformerConfig(**kw)


def dims(cfg) -> Dict:
    """What ``decode_step_bytes`` and the result's ``model_dims`` use."""
    return {
        "vocab_size": cfg.vocab_size, "d_model": cfg.d_model,
        "n_layers": cfg.n_layers, "n_attn_layers": cfg.n_attn_layers,
        "n_ssm_layers": cfg.n_ssm_layers, "n_heads": cfg.n_heads,
        "n_kv_heads": cfg.kv_heads, "d_head": cfg.d_head, "d_ff": cfg.d_ff,
        "ssm_heads": cfg.ssm_heads, "ssm_head_dim": cfg.ssm_head_dim,
        "ssm_state": cfg.ssm_state, "ssm_groups": cfg.ssm_groups,
        "ssm_conv": cfg.ssm_conv,
    }


def reference_constants(cfg) -> Dict:
    """``hp`` of ``benchmarks/reference_ssm.py``."""
    return {
        "n_heads": cfg.n_heads, "n_kv_heads": cfg.kv_heads,
        "d_head": cfg.d_head, "eps": cfg.norm_eps,
        "embed_scale": cfg.embed_scale,
        "residual_scale": cfg.residual_scale,
        "logit_scale": cfg.logit_scale, "attn_scale": cfg.attn_scale,
        "layer_types": cfg.layer_types, "ssm_heads": cfg.ssm_heads,
        "ssm_head_dim": cfg.ssm_head_dim, "ssm_state": cfg.ssm_state,
        "ssm_groups": cfg.ssm_groups,
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

    one = {kind: dataclasses.replace(cfg, n_layers=1, layer_types=(kind,))
           for kind in ("attention", "ssm")}
    stacks = {"attention": "layers", "ssm": "ssm_layers"}
    ends = dataclasses.replace(cfg, n_layers=0, layer_types=())

    @jax.jit
    def make(words):
        key = jax.random.fold_in(jax.random.key(words[0]), words[1])
        k_attn, k_ssm, k_ends = jax.random.split(key, 3)
        params = {k: v for k, v in init_params(ends, k_ends).items()
                  if k != "layers"}
        for kind, k, n in (("attention", k_attn, cfg.n_attn_layers),
                           ("ssm", k_ssm, cfg.n_ssm_layers)):
            params[stacks[kind]] = jax.lax.map(
                lambda k, kind=kind: jax.tree.map(
                    lambda x: x[0],
                    init_params(one[kind], k)[stacks[kind]]),
                jax.random.split(k, n))
        return params

    return make(jnp.asarray(common.seed_words(seed), jnp.int32))


def param_count(c: Dict) -> Dict[str, int]:
    """Parameters of one layer of each kind and of the two ends, ``c``
    from ``dims``: ISSUE 35's arithmetic."""
    d, inner = c["d_model"], c["ssm_heads"] * c["ssm_head_dim"]
    width = inner + 2 * c["ssm_groups"] * c["ssm_state"]
    ffn = 3 * d * c["d_ff"]
    ssm = (d * (inner + width + c["ssm_heads"]) + inner * d
           + width * (c["ssm_conv"] + 1) + 3 * c["ssm_heads"] + inner)
    attn = d * c["d_head"] * (2 * c["n_heads"] + 2 * c["n_kv_heads"])
    return {"ssm_layer": ssm + ffn + 2 * d, "attn_layer": attn + ffn + 2 * d,
            "ends": c["vocab_size"] * d + d}


def slot_state_bytes(c: Dict) -> int:
    """What one slot keeps whatever its length: a float32 state a head
    and the convolution's last inputs in bf16, every state-space layer."""
    inner = c["ssm_heads"] * c["ssm_head_dim"]
    width = inner + 2 * c["ssm_groups"] * c["ssm_state"]
    return c["n_ssm_layers"] * (inner * c["ssm_state"] * 4
                                + (c["ssm_conv"] - 1) * width * 2)


def decode_step_bytes(c: Dict, slots_updated: float, kv_rows: float,
                      itemsize: int = 2) -> float:
    """The LEAST bytes one decode step must move, ``c`` from ``dims``:

    - every weight once: all layers of both kinds, the final norm, and
      the tied embedding as the output head (the embedding's own gather
      of a few rows is left out);
    - for every slot the program updates, ``slots_updated`` (the engine's
      ``state_slots_updated`` per step and state layer: the states the
      step moved, which since PR 46 are the live lanes' alone; a parked
      lane's is counted under ``state_slots_skipped``), its state READ
      AND WRITTEN: 2 x ``slot_state_bytes``;
    - the K/V rows the counters say the decode attention read,
      ``kv_rows`` (the engine's ``attn_rows_read`` per step: rows of a
      slot, each ``n_attn_layers`` x 2 x Hkv x D numbers).

    What a step moves beyond this (a state read twice, a row's padding)
    is its own business: the share of the HBM bandwidth this gives cannot
    pass 100 %."""
    n = param_count(c)
    weights = (c["n_ssm_layers"] * n["ssm_layer"]
               + c["n_attn_layers"] * n["attn_layer"] + n["ends"])
    row = c["n_attn_layers"] * 2 * c["n_kv_heads"] * c["d_head"]
    return float(itemsize * (weights + kv_rows * row)
                 + 2 * slots_updated * slot_state_bytes(c))
