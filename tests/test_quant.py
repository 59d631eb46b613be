"""Int8 weight-only quantization (VERDICT r3 item 2 support): QTensor
drop-in behavior through the forward and KV-cached generation paths.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.quant import (
    QTensor,
    init_params_int8,
    quantize_params_int8,
    quantize_tensor,
)
from ray_tpu.models.transformer import (
    TransformerConfig,
    forward,
    init_params,
)


def test_quantize_tensor_roundtrip_error():
    w = jax.random.normal(jax.random.key(0), (64, 32)) * 0.02
    qt = quantize_tensor(w, (0,))
    assert qt.q.dtype == jnp.int8
    assert qt.s.shape == (1, 32)  # per-output-channel
    deq = qt.astype(jnp.float32)
    err = float(jnp.abs(deq - w).max() / jnp.abs(w).max())
    assert err < 0.01  # int8 grid on a per-channel range


def test_qtensor_is_pytree_and_scan_slices_it():
    qt = quantize_tensor(
        jax.random.normal(jax.random.key(1), (4, 8, 8)), (1,)
    )
    leaves = jax.tree_util.tree_leaves(qt)
    assert len(leaves) == 2

    def body(carry, sl):  # sl: QTensor sliced along axis 0 by scan
        assert isinstance(sl, QTensor)
        return carry + sl.astype(jnp.float32).sum(), None

    total, _ = jax.lax.scan(body, jnp.zeros(()), qt)
    np.testing.assert_allclose(
        float(total), float(qt.astype(jnp.float32).sum()), rtol=1e-5
    )


def test_quantized_forward_close_to_bf16():
    cfg = TransformerConfig.tiny(n_layers=2)
    cfg = dataclasses.replace(cfg, remat=False)
    params = init_params(cfg, jax.random.key(0))
    qparams = quantize_params_int8(params)
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    ref = np.asarray(forward(params, toks, cfg), np.float32)
    out = np.asarray(forward(qparams, toks, cfg), np.float32)
    # int8 weight grid: logits track closely; argmax rarely flips on a
    # random tiny model, so compare distributions not exact values
    denom = np.abs(ref).max() + 1e-6
    assert np.abs(out - ref).max() / denom < 0.12
    agree = (out.argmax(-1) == ref.argmax(-1)).mean()
    assert agree > 0.9, agree


def test_quantized_generation_decodes():
    from ray_tpu.models.generation import generate, prepare_for_inference

    cfg = TransformerConfig.tiny(n_layers=2)
    params = quantize_params_int8(init_params(cfg, jax.random.key(0)))
    params, cfg = prepare_for_inference(params, cfg)
    # QTensors survived the inference cast
    assert isinstance(
        params["layers"]["attn"]["wq"], QTensor
    )
    prompt = jax.random.randint(jax.random.key(1), (2, 8), 0,
                                cfg.vocab_size).astype(jnp.int32)
    out = generate(params, prompt, cfg, max_new_tokens=8, max_len=32)
    assert np.asarray(out).shape == (2, 8)


def test_init_params_int8_shapes_and_dtypes():
    cfg = TransformerConfig.tiny(n_layers=3)
    p = init_params_int8(cfg, jax.random.key(0))
    wq = p["layers"]["attn"]["wq"]
    assert isinstance(wq, QTensor)
    assert wq.q.shape == (3, cfg.d_model, cfg.n_heads, cfg.d_head)
    assert wq.q.dtype == jnp.int8
    assert p["embed"].dtype == cfg.param_dtype  # embedding not quantized
    # distinct layers got distinct weights
    assert not np.array_equal(
        np.asarray(wq.q[0]), np.asarray(wq.q[1])
    )


def test_serve_7b_config_is_7b_class():
    cfg = TransformerConfig.serve_7b()
    assert cfg.param_count() >= 6_000_000_000, cfg.param_count()
