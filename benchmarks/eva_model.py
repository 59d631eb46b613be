"""The benchmark's own arithmetic for configurations of kind ``serve_eva``
(EvaByte's kind: every layer attends its own window of rows exactly and
every earlier window through pooled chunk summaries, so that no layer
keeps every row and a slot's cache folds ``window`` rows into ``window /
chunk`` each time a window closes): the program's config object from a
published ``config.json``'s keys, the plain reference's constants, the
weights from a seed, and the bytes a decode step must move. Kept under
``benchmarks/`` so that no later PR that claims a gain can change how a
number is computed. Only ``param_count``, ``row_bytes``, ``slot_rows`` and
``decode_step_bytes`` are free of JAX.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

from benchmarks import common


def transformer_config(model: Dict, **over):
    """The published keys of ``configs/<name>.json`` (an evabyte
    ``config.json``) as the program's ``TransformerConfig``."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    if model["hidden_act"] != "silu" or model["attention_bias"] or (
            model["attention_class"] != "eva") or (
            model["tie_word_embeddings"]) or model["rope_scaling"] or (
            model["num_key_value_heads"] != model["num_attention_heads"]
            ) or not (model["norm_add_unit_offset"] and model["fp32_skip_add"]
                      and model["fp32_logits"]):
        raise common.BenchFailure(
            "the block here is EVA attention over MHA projections without "
            "a bias, plain rotary, gated SiLU FFNs, an untied head, norms "
            "scaled by 1 + w, a float32 residual stream and float32 logits")
    n = model["num_hidden_layers"]
    d = model["hidden_size"]
    kw = dict(
        vocab_size=model["vocab_size"], d_model=d, n_layers=n,
        n_heads=model["num_attention_heads"],
        d_head=d // model["num_attention_heads"],
        d_ff=model["intermediate_size"],
        rotary_dim=d // model["num_attention_heads"],
        max_seq_len=model["max_position_embeddings"],
        residual="sequential", activation="silu", gated_ffn=True,
        norm_eps=model["rms_norm_eps"], rope_theta=float(model["rope_theta"]),
        layer_types=("eva",) * n, eva_window=model["window_size"],
        eva_chunk=model["chunk_size"], n_pred_heads=model["num_pred_heads"],
        residual_f32=True, norm_unit_offset=True, param_dtype=jnp.bfloat16,
    )
    kw.update(over)
    return TransformerConfig(**kw)


def dims(cfg) -> Dict:
    """What the byte functions and the result's ``model_dims`` use."""
    return {
        "vocab_size": cfg.vocab_size, "d_model": cfg.d_model,
        "n_layers": cfg.n_layers, "n_heads": cfg.n_heads,
        "d_head": cfg.d_head, "d_ff": cfg.d_ff, "window": cfg.eva_window,
        "chunk": cfg.eva_chunk, "n_pred_heads": cfg.n_pred_heads,
    }


def reference_constants(cfg) -> Dict:
    """``hp`` of ``benchmarks/reference_eva.py``."""
    return {
        "n_heads": cfg.n_heads, "d_head": cfg.d_head, "eps": cfg.norm_eps,
        "theta": cfg.rope_theta, "window": cfg.eva_window,
        "chunk": cfg.eva_chunk, "n_pred_heads": cfg.n_pred_heads,
    }


def make_bf16_params(cfg, seed: int):
    """Every weight on the device from the seed, in ONE jitted call, in
    bf16 as the configuration is served, with the program's own
    initialisers (``init_params``: the pooling vectors clip(normal, -1,
    1) / sqrt(d_head), the norms' w normal(0, 0.02)). A layer exists in
    float32 only inside its own iteration."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import init_params

    one = dataclasses.replace(cfg, n_layers=1, layer_types=("eva",))
    ends = dataclasses.replace(cfg, n_layers=0, layer_types=())

    @jax.jit
    def make(words):
        key = jax.random.fold_in(jax.random.key(words[0]), words[1])
        k_layers, k_ends = jax.random.split(key)
        params = {k: v for k, v in init_params(ends, k_ends).items()
                  if k != "layers"}
        params["eva_layers"] = jax.lax.map(
            lambda k: jax.tree.map(
                lambda x: x[0], init_params(one, k)["eva_layers"]),
            jax.random.split(k_layers, cfg.n_layers))
        return params

    return make(jnp.asarray(common.seed_words(seed), jnp.int32))


def param_count(c: Dict) -> Dict[str, int]:
    """Parameters of one layer and of the two ends, ``c`` from ``dims``:
    ISSUE 55's arithmetic (a layer: four projections, a gated FFN, two
    pooling vectors a head, two norms)."""
    d, h, dh = c["d_model"], c["n_heads"], c["d_head"]
    return {"layer": 4 * d * h * dh + 3 * d * c["d_ff"] + 2 * h * dh + 2 * d,
            "ends": c["vocab_size"] * d + d
            + d * c["n_pred_heads"] * c["vocab_size"]}


def row_bytes(c: Dict, itemsize: int = 2) -> int:
    """One cached row of ONE layer, a token's or a chunk summary's: K and
    V, every head."""
    return 2 * c["n_heads"] * c["d_head"] * itemsize


def slot_rows(c: Dict, max_len: int) -> int:
    """Rows a slot-layer has for ``max_len`` tokens: the summaries of the
    windows that can have closed before the last token, and the open
    window's rows."""
    return ((max_len - 1) // c["window"] * (c["window"] // c["chunk"])
            + min(c["window"], max_len))


def decode_step_bytes(c: Dict, window_rows: float, summary_rows: float,
                      itemsize: int = 2) -> float:
    """The LEAST bytes one decode step must move, ``c`` from ``dims``:

    - every weight once: the layers, the final norm and the eight heads'
      matrix (the embedding's own gather of a few rows is left out);
    - the cached rows the engine's counters say a step's attention read:
      ``window_rows`` (its ``eva_window_rows_read`` a step: the open
      windows' tokens, a (row, layer) pair each) and ``summary_rows``
      (``eva_summary_rows_read``: the closed windows' summaries).

    What a step moves beyond this (the kernel reads whole chunks of 64
    rows; a token's own row is written; a window that closes is read
    whole and its summaries written, once in 2,048 steps a lane) is its
    own business: the share of the HBM bandwidth this gives cannot pass
    100 %."""
    n = param_count(c)
    weights = c["n_layers"] * n["layer"] + n["ends"] - (
        c["vocab_size"] * c["d_model"])
    return float(itemsize * weights
                 + (window_rows + summary_rows) * row_bytes(c, itemsize))
