"""The benchmark's own arithmetic for configurations of kind
``serve_sambay`` (a decoder-hybrid-decoder: Mamba-1 and window
differential-attention layers below ONE full differential-attention
layer, and above it "cross" layers that attend that layer's rows and
gated memory units gated by the last Mamba layer's output, none of which
keeps anything; Phi-4-mini-flash-reasoning's kind): the program's config
object from a published ``config.json``'s keys and the configuration's
``assumed`` sizes, the plain reference's constants, the weights from a
seed, the bytes a decode step must move and the bytes and operations of
the ``mamba_scan`` kernel's calls. Kept under ``benchmarks/`` so that no
later PR that claims a gain can change how a number is computed. Only
``decode_step_bytes`` and ``mamba_scan_cost`` are free of JAX.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

from benchmarks import common

_STACKS = {"attention": "layers", "window": "window_layers",
           "mamba": "mamba_layers", "gmu": "gmu_layers",
           "cross": "cross_layers"}


def layer_types(n_layers: int, mb_per_layer: int) -> Tuple[str, ...]:
    """The kinds of a phi4flash model's layers: a "mamba" layer every
    ``mb_per_layer`` layers and a "window" layer between them up to the
    middle, the ONE "attention" layer right above the middle's "mamba"
    layer, then "gmu" where a "mamba" layer and "cross" where an attention
    layer would stand: ``(M W) x 8, M F, (G X) x 7`` at 32 layers."""
    half = n_layers // 2
    return tuple(
        ("mamba" if i <= half else "gmu") if i % mb_per_layer == 0 else
        "window" if i < half else "attention" if i == half + 1 else "cross"
        for i in range(n_layers))


def transformer_config(model: Dict, **over):
    """The published keys of ``configs/<name>.json`` (a phi4flash
    ``config.json``) and its ``assumed`` sizes as the program's
    ``TransformerConfig``."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    if model["hidden_act"] != "silu" or model["mlp_bias"] or (
            model["lm_head_bias"]) or not model["tie_word_embeddings"] or (
            model["mb_per_layer"] != 2) or model["num_hidden_layers"] % 4:
        raise common.BenchFailure(
            "the block here has gated SiLU FFNs without a bias, a tied "
            "head without a bias, a Mamba layer every second layer and a "
            "depth that halves into such pairs")
    size = model["assumed"]["sizes"]
    d = model["hidden_size"]
    kw = dict(
        vocab_size=model["vocab_size"], d_model=d,
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        d_head=d // model["num_attention_heads"],
        d_ff=model["intermediate_size"], rotary_dim=0,
        max_seq_len=model["max_position_embeddings"],
        residual="sequential", activation="silu", gated_ffn=True,
        norm_eps=model["layer_norm_eps"], tie_embeddings=True,
        norm="layer", attn_bias=True, diff_attn=True,
        window=model["sliding_window"],
        layer_types=layer_types(model["num_hidden_layers"],
                                model["mb_per_layer"]),
        mamba_inner=size["mamba_expand"] * d,
        mamba_state=size["mamba_d_state"], mamba_conv=size["mamba_d_conv"],
        mamba_dt_rank=size["mamba_dt_rank"], param_dtype=jnp.bfloat16,
    )
    kw.update(over)
    return TransformerConfig(**kw)


def dims(cfg) -> Dict:
    """What the byte functions and the result's ``model_dims`` use."""
    return {
        "vocab_size": cfg.vocab_size, "d_model": cfg.d_model,
        "n_layers": cfg.n_layers, "n_heads": cfg.n_heads,
        "n_kv_heads": cfg.kv_heads, "d_head": cfg.d_head, "d_ff": cfg.d_ff,
        "window": cfg.window, "mamba_inner": cfg.mamba_inner,
        "mamba_state": cfg.mamba_state, "mamba_conv": cfg.mamba_conv,
        "mamba_dt_rank": cfg.mamba_dt_rank,
        **{"n_" + kind: cfg.layer_types.count(kind) for kind in _STACKS},
    }


def reference_constants(cfg) -> Dict:
    """``hp`` of ``benchmarks/reference_sambay.py``."""
    return {
        "n_heads": cfg.n_heads, "n_kv_heads": cfg.kv_heads,
        "d_head": cfg.d_head, "eps": cfg.norm_eps, "window": cfg.window,
        "layer_types": cfg.layer_types, "mamba_state": cfg.mamba_state,
        "mamba_dt_rank": cfg.mamba_dt_rank,
    }


def make_bf16_params(cfg, seed: int):
    """Every weight on the device from the seed, in ONE jitted call, in
    bf16 as the configuration is served, with the program's own
    initialisers (``init_params``: Mamba-1's decay, step and convolution
    as the family publishes them, the four lambda vectors normal(0, 0.1)).
    A layer exists in float32 only inside its own iteration."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import init_params

    # one layer of a kind alone (a "gmu" or "cross" layer's config names
    # the layer it reads below it; that layer's weights are dropped)
    below = {"gmu": ("mamba",), "cross": ("attention",)}
    one = {kind: dataclasses.replace(
        cfg, n_layers=1 + len(below.get(kind, ())),
        layer_types=below.get(kind, ()) + (kind,)) for kind in _STACKS}
    ends = dataclasses.replace(cfg, n_layers=0, layer_types=(), window=0)
    counts = {kind: cfg.layer_types.count(kind) for kind in _STACKS}

    @jax.jit
    def make(words):
        key = jax.random.fold_in(jax.random.key(words[0]), words[1])
        keys = jax.random.split(key, len(_STACKS) + 1)
        params = {k: v for k, v in init_params(ends, keys[-1]).items()
                  if k != "layers"}
        for (kind, name), k in zip(_STACKS.items(), keys):
            params[name] = jax.lax.map(
                lambda k, kind=kind, name=name: jax.tree.map(
                    lambda x: x[0], init_params(one[kind], k)[name]),
                jax.random.split(k, counts[kind]))
        return params

    return make(jnp.asarray(common.seed_words(seed), jnp.int32))


def param_count(c: Dict) -> Dict[str, int]:
    """Parameters of one layer of each kind and of the two ends, ``c``
    from ``dims``: ISSUE 49's arithmetic."""
    d, h, kv, dh = c["d_model"], c["n_heads"], c["n_kv_heads"], c["d_head"]
    inner, n, r = c["mamba_inner"], c["mamba_state"], c["mamba_dt_rank"]
    rest = 3 * d * c["d_ff"] + 4 * d  # the FFN and two LayerNorms
    diff = 4 * dh + 2 * dh  # four lambda vectors and the pair's norm
    mamba = (d * 2 * inner + inner * (c["mamba_conv"] + 1)
             + inner * (r + 2 * n) + r * inner + inner + n * inner + inner
             + inner * d)
    attn = d * dh * (h + 2 * kv) + dh * (h + 2 * kv) + h * dh * d + d + diff
    cross = 2 * (d * dh * h) + dh * h + d + diff
    return {"mamba": mamba + rest, "window": attn + rest,
            "attention": attn + rest, "gmu": 2 * d * inner + rest,
            "cross": cross + rest, "ends": c["vocab_size"] * d + 2 * d}


def row_bytes(c: Dict, itemsize: int = 2) -> int:
    """One cached token of ONE attention layer: K and V, all KV heads."""
    return 2 * c["n_kv_heads"] * c["d_head"] * itemsize


def mamba_state_bytes(c: Dict, itemsize: int = 2) -> int:
    """What one slot keeps for its "mamba" layers: a float32 state [state,
    channels] and the convolution's last inputs a layer."""
    return c["n_mamba"] * c["mamba_inner"] * (
        c["mamba_state"] * 4 + (c["mamba_conv"] - 1) * itemsize)


def slot_state_bytes(c: Dict, itemsize: int = 2) -> int:
    """What one slot keeps whatever its length: the "mamba" layers'
    states and tails, and a ring of ``window`` rows a "window" layer."""
    return (mamba_state_bytes(c, itemsize)
            + c["n_window"] * c["window"] * row_bytes(c, itemsize))


def decode_step_bytes(c: Dict, live_slots: float, owner_rows: float,
                      cross_rows: float, ring_rows: float,
                      itemsize: int = 2) -> float:
    """The LEAST bytes one decode step must move, ``c`` from ``dims``:

    - every weight once: all 32 layers, the final norm, and the tied
      embedding as the output head (the embedding's own gather of a few
      rows is left out);
    - for every LIVE lane, ``live_slots`` (the engine's ``slot_steps`` a
      step), its "mamba" states and convolution tails READ AND WRITTEN (a
      parked lane's are not moved);
    - the rows of the ONE "attention" layer's cache that its own decode
      attention read, ``owner_rows`` (the engine's ``attn_rows_read`` a
      step), and that the "cross" layers read, ``cross_rows`` (its
      ``cross_rows_read`` a step, a (row, layer) pair each);
    - the ring rows the "window" layers read, ``ring_rows`` (its
      ``window_rows_read`` a step, a (row, layer) pair each).

    What a step moves beyond this (a new state read again for its output,
    the queries laid out wide) is its own business: the share of the HBM
    bandwidth this gives cannot pass 100 %."""
    n = param_count(c)
    weights = sum(c["n_" + kind] * n[kind] for kind in _STACKS) + n["ends"]
    state = mamba_state_bytes(c, itemsize)
    return float(itemsize * weights + 2 * live_slots * state
                 + (owner_rows + cross_rows + ring_rows)
                 * row_bytes(c, itemsize))


def mamba_scan_cost(c: Dict, tokens: float) -> Dict[str, float]:
    """What ONE call of the ``mamba_scan`` kernel over ``tokens`` tokens
    (a prefill's bucket, padding included: the kernel walks it all) needs,
    every operand counted: bytes, x, dt and y a channel a token in float32
    (4 B each), B and C a state dim a token (4 B each), A once, the state
    in and out; operations, an exponential and six multiplies or adds a
    (token, state dim, channel). 9 operations a byte, where the chip sustains 5: the kernel is bound
    by the vector unit, its byte share says how far from the memory's
    bound that leaves it."""
    inner, n = c["mamba_inner"], c["mamba_state"]
    return {"bytes": float(tokens * 4 * (3 * inner + 2 * n)
                           + 3 * n * inner * 4),
            "ops": float(7 * tokens * n * inner)}
