"""Int8 weight quantization for serving (weight-only, symmetric
per-output-channel).

Parity role: the reference Serve LLM stack leans on vLLM-style quantized
serving for 7B-class models on single devices; here the TPU-native
equivalent: weights live in HBM as int8 + float scales, and
dequantization happens INSIDE the consuming matmul fusion (XLA fuses the
convert+scale producer into the dot's operand read), so decode — a
weight-bandwidth-bound workload — streams half the bytes of bf16.

Design: :class:`QTensor` is a pytree node whose ``astype(dtype)``
returns the dequantized array. Every weight use in the model/generation
code is already ``w.astype(cfg.dtype)``, so quantized checkpoints are
drop-in — no forward-path changes, and ``lax.scan`` over stacked layer
weights slices the (q, s) leaves together.

Where a quantised weight's PHYSICAL layout is decided: not here. This
module fixes names, logical shapes ([L, d, h, k], ...) and dtypes, which
every reader of a parameter tree relies on. The order of a leaf's bytes
in device memory belongs to whoever runs the weight: a training step or
``generate()`` takes the chip's default, and the serving engine places
each leaf once, at set-up, in the layout its compiled ``decode_block``
reads (``generation.lay_out_for_decode``; on a v5e the int8 ``wq`` /
``wk`` / ``wv`` then lie with the head dimension outside the contracted
one). A ``QTensor`` keeps its structure through that: ``q`` may move,
``s`` stays, and the node is told where ``q`` then lies (``order``), which
the admission program reads (``generation.prefill_into_slot``).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp


@jax.tree_util.register_pytree_node_class
class QTensor:
    """Symmetric int8 weight + broadcastable float32 scale. ``order`` is
    where ``q`` lies: the major-to-minor order of the STACKED leaf's axes in
    device memory, noted by whoever placed it otherwise than the chip's
    default (``generation.lay_out_for_decode``), else None. It is static
    (the node's aux data), so it rides through ``jit`` and ``lax.scan``
    with the leaf and a program can be written for the bytes as they
    lie (``lies``)."""

    def __init__(self, q: jax.Array, s: jax.Array,
                 order: Optional[Tuple[int, ...]] = None):
        self.q = q
        self.s = s
        self.order = order

    # -- the drop-in surface the model code uses --
    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    def astype(self, dtype) -> jax.Array:
        return self.q.astype(dtype) * self.s.astype(dtype)

    def lies(self) -> Optional[Tuple[int, ...]]:
        """``order`` for ``q`` as it is held here: the stack's, less the
        leading axes a scan or an index has taken off."""
        if self.order is None:
            return None
        lead = len(self.order) - self.q.ndim
        return tuple(a - lead for a in self.order if a >= lead)

    @property
    def T(self):  # tied-embedding head path
        return self.astype(jnp.bfloat16).T

    def __repr__(self):
        return f"QTensor(int8 {self.q.shape}, scale {self.s.shape})"

    # -- pytree --
    def tree_flatten(self):
        return (self.q, self.s), self.order

    @classmethod
    def tree_unflatten(cls, order, children):
        return cls(*children, order=order)


def quantize_tensor(w: jax.Array, reduce_axes: Tuple[int, ...]) -> QTensor:
    """Symmetric per-channel quantization: scales keep every axis NOT in
    ``reduce_axes`` (the contracted axes of the consuming matmul)."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=reduce_axes,
                   keepdims=True)
    s = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / s), -127, 127).astype(
        jnp.int8
    )
    return QTensor(q, s)


# Per-weight contracted axes (leading axis 0 is the stacked layer dim):
#   wq/wk/wv [L, d, h, k]: contract d      -> scales per (h, k)
#   wo       [L, h, k, d]: contract (h, k) -> scales per d
#   mlp wi   [L, d, f]:    contract d      -> scales per f
#   mlp wo   [L, f, d]:    contract f      -> scales per d
#   moe wi   [L, E, d, f]: contract d      -> scales per (E, f)
#   moe wo   [L, E, f, d]: contract f      -> scales per (E, d)
_LAYER_RULES = {
    ("attn", "wq"): (1,),
    ("attn", "wk"): (1,),
    ("attn", "wv"): (1,),
    ("attn", "wo"): (1, 2),
    ("mlp", "wi"): (1,),
    ("mlp", "wo"): (1,),
    ("moe", "wi"): (2,),
    ("moe", "wo"): (2,),
}


def quantize_layer_params(layers: Dict) -> Dict:
    """Quantize one stacked layer tree (norm scales and the MoE router
    stay high-precision: tiny, accuracy-critical)."""
    out = {}
    for group, sub in layers.items():
        out[group] = {}
        for name, w in sub.items():
            axes = _LAYER_RULES.get((group, name))
            out[group][name] = (
                quantize_tensor(w, axes) if axes is not None else w
            )
    return out


def quantize_params_int8(params: Dict) -> Dict:
    """Quantize a full param tree's layer weights. Embedding and lm_head
    stay bf16 (gather/logit accuracy, and together they are <5% of a
    7B-class model's bytes)."""
    out = dict(params)
    out["layers"] = quantize_layer_params(params["layers"])
    return out


def init_params_int8(config, rng: jax.Array) -> Dict:
    """Initialize a model DIRECTLY into int8 layer weights, one layer at
    a time — a 7B-class bf16 init (~13GB) would not fit single-chip HBM
    alongside anything else, so bf16 exists only one layer at a time."""
    from ray_tpu.models.transformer import init_params

    c = config
    import dataclasses

    one = dataclasses.replace(c, n_layers=1)

    @jax.jit
    def make_layer(key):
        p = init_params(one, key)
        return quantize_layer_params(p["layers"])

    per_layer = [
        make_layer(jax.random.fold_in(rng, 1000 + li))
        for li in range(c.n_layers)
    ]

    @jax.jit
    def stack(*trees):
        return jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *trees
        )

    layers = stack(*per_layer)
    head = jax.jit(
        lambda k: {
            name: w
            for name, w in init_params(
                dataclasses.replace(c, n_layers=0), k
            ).items()
            if name != "layers"
        }
    )(jax.random.fold_in(rng, 7))
    head["layers"] = layers
    return head
