"""Model + parallel layer tests (8 virtual CPU devices via conftest)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.transformer import (
    TransformerConfig,
    forward,
    init_params,
    loss_fn,
    param_logical_axes,
)
from ray_tpu.ops.attention import causal_attention
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.parallel.mesh import (
    DEFAULT_RULES,
    MeshConfig,
    build_mesh,
    shardings_for,
)
from ray_tpu.parallel.train_step import (
    batch_sharding,
    default_optimizer,
    make_sharded_state,
    make_train_step,
)


def test_mesh_resolve():
    assert MeshConfig(dp=-1, tp=2).resolve(8) == (4, 1, 1, 1, 2)
    assert MeshConfig(dp=2, sp=2, tp=2).resolve(8) == (2, 1, 1, 2, 2)
    with pytest.raises(ValueError):
        MeshConfig(dp=3, tp=3).resolve(8)


def test_forward_shapes_and_logical_axes():
    cfg = TransformerConfig.tiny()
    params = init_params(cfg, jax.random.key(0))
    axes = param_logical_axes(cfg)
    # logical-axis tree matches param tree leaf-for-leaf, rank-for-rank
    jax.tree.map(
        lambda p, a: None
        if p.ndim == len(a)
        else pytest.fail(f"rank mismatch {p.shape} vs {a}"),
        params,
        axes,
        is_leaf=lambda x: isinstance(x, tuple),
    )
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)


def test_causal_attention_is_causal():
    key = jax.random.key(0)
    q = jax.random.normal(key, (1, 8, 2, 4))
    k = jax.random.normal(jax.random.key(1), (1, 8, 2, 4))
    v = jax.random.normal(jax.random.key(2), (1, 8, 2, 4))
    out1 = causal_attention(q, k, v)
    # Perturbing a future position must not change earlier outputs.
    k2 = k.at[:, -1].set(99.0)
    v2 = v.at[:, -1].set(99.0)
    out2 = causal_attention(q, k2, v2)
    np.testing.assert_allclose(out1[:, :-1], out2[:, :-1], atol=1e-6)
    assert not np.allclose(out1[:, -1], out2[:, -1])


def test_ring_attention_matches_dense():
    mesh = build_mesh(MeshConfig(dp=2, sp=2, tp=2))
    key = jax.random.key(0)
    b, s, h, d = 2, 32, 4, 8
    q = jax.random.normal(key, (b, s, h, d), jnp.float32)
    k = jax.random.normal(jax.random.key(1), (b, s, h, d), jnp.float32)
    v = jax.random.normal(jax.random.key(2), (b, s, h, d), jnp.float32)
    dense = causal_attention(q, k, v)
    ring = ring_attention(q, k, v, mesh=mesh)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(ring), atol=2e-5)


def test_ring_attention_gqa():
    mesh = build_mesh(MeshConfig(dp=4, sp=2, tp=1))
    b, s, h, hkv, d = 4, 16, 4, 2, 8
    q = jax.random.normal(jax.random.key(0), (b, s, h, d))
    k = jax.random.normal(jax.random.key(1), (b, s, hkv, d))
    v = jax.random.normal(jax.random.key(2), (b, s, hkv, d))
    dense = causal_attention(q, k, v)
    ring = ring_attention(q, k, v, mesh=mesh)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(ring), atol=2e-5)


def _tiny_batch(cfg, batch=4, seq=32, sharding=None):
    tokens = jnp.ones((batch, seq), jnp.int32)
    b = {
        "tokens": tokens,
        "targets": tokens,
        "mask": jnp.ones((batch, seq), jnp.float32),
    }
    if sharding is not None:
        b = {k: jax.device_put(v, sharding) for k, v in b.items()}
    return b


def test_train_step_dp_tp_sp_loss_decreases():
    mesh = build_mesh(MeshConfig(dp=2, sp=2, tp=2))
    cfg = TransformerConfig.tiny(max_seq_len=32)
    cfg = dataclasses.replace(cfg, attn_impl="ring")
    opt = default_optimizer(lr=1e-2)
    state, state_sh = make_sharded_state(cfg, mesh, opt, jax.random.key(0))
    step = make_train_step(cfg, mesh, opt, state_sh)
    batch = _tiny_batch(cfg, sharding=batch_sharding(mesh))
    losses = []
    for _ in range(5):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    # params actually sharded: embed row dim split over tp (vocab axis)
    emb_sh = state.params["embed"].sharding
    assert emb_sh.spec[0] == "tp"


def test_sharded_state_consistent_with_single_device():
    """Same seed, same loss whether sharded over 8 devices or on 1."""
    cfg = TransformerConfig.tiny(max_seq_len=32)
    opt = default_optimizer()
    mesh8 = build_mesh(MeshConfig(dp=2, sp=1, tp=4))
    mesh1 = build_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    s8, sh8 = make_sharded_state(cfg, mesh8, opt, jax.random.key(0))
    s1, sh1 = make_sharded_state(cfg, mesh1, opt, jax.random.key(0))
    b8 = _tiny_batch(cfg, sharding=batch_sharding(mesh8))
    b1 = _tiny_batch(cfg, sharding=batch_sharding(mesh1))
    _, m8 = make_train_step(cfg, mesh8, opt, sh8)(s8, b8)
    _, m1 = make_train_step(cfg, mesh1, opt, sh1)(s1, b1)
    # bf16 compute: reduction order differs across shardings
    np.testing.assert_allclose(float(m8["loss"]), float(m1["loss"]), rtol=5e-3)


def test_graft_entry():
    import __graft_entry__ as ge

    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert np.isfinite(np.asarray(out, dtype=np.float32)).all()


def test_dryrun_multichip():
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


def test_flash_attention_matches_dense():
    from ray_tpu.ops.flash_attention import flash_attention

    b, s, h, d = 2, 128, 2, 64
    q = jax.random.normal(jax.random.key(0), (b, s, h, d), jnp.float32)
    k = jax.random.normal(jax.random.key(1), (b, s, h, d), jnp.float32)
    v = jax.random.normal(jax.random.key(2), (b, s, h, d), jnp.float32)
    dense = causal_attention(q, k, v)
    flash = flash_attention(q, k, v, block_q=64, block_kv=64, interpret=True)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense), atol=2e-5)


def test_flash_attention_grads_match_dense():
    from ray_tpu.ops.flash_attention import flash_attention

    b, s, h, d = 1, 128, 2, 64
    q = jax.random.normal(jax.random.key(0), (b, s, h, d), jnp.float32)
    k = jax.random.normal(jax.random.key(1), (b, s, h, d), jnp.float32)
    v = jax.random.normal(jax.random.key(2), (b, s, h, d), jnp.float32)
    w = jax.random.normal(jax.random.key(3), (b, s, h, d), jnp.float32)

    def loss(attn):
        def f(q, k, v):
            return (attn(q, k, v) * w).sum()
        return f

    gf = jax.grad(
        loss(lambda q, k, v: flash_attention(
            q, k, v, block_q=64, block_kv=64, interpret=True)),
        argnums=(0, 1, 2),
    )(q, k, v)
    gd = jax.grad(
        loss(lambda q, k, v: causal_attention(q, k, v)), argnums=(0, 1, 2)
    )(q, k, v)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-5)


def test_flash_attention_gqa():
    from ray_tpu.ops.flash_attention import flash_attention

    b, s, h, hkv, d = 1, 128, 4, 2, 32
    q = jax.random.normal(jax.random.key(0), (b, s, h, d))
    k = jax.random.normal(jax.random.key(1), (b, s, hkv, d))
    v = jax.random.normal(jax.random.key(2), (b, s, hkv, d))
    dense = causal_attention(q, k, v)
    flash = flash_attention(q, k, v, block_q=64, block_kv=64, interpret=True)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense), atol=2e-5)


def test_flash_attention_sharded_under_mesh():
    from ray_tpu.ops.flash_attention import flash_attention_sharded

    mesh = build_mesh(MeshConfig(dp=4, sp=1, tp=2))
    b, s, h, d = 4, 128, 2, 32
    q = jax.random.normal(jax.random.key(0), (b, s, h, d), jnp.float32)
    k = jax.random.normal(jax.random.key(1), (b, s, h, d), jnp.float32)
    v = jax.random.normal(jax.random.key(2), (b, s, h, d), jnp.float32)
    dense = causal_attention(q, k, v)
    flash = flash_attention_sharded(
        q, k, v, mesh=mesh, block_q=64, block_kv=64, interpret=True
    )
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense), atol=2e-5)


def test_flash_transformer_forward_matches_dense():
    cfg = TransformerConfig.tiny(max_seq_len=128)
    cfg_f = dataclasses.replace(cfg, attn_impl="flash")
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 128), 0, cfg.vocab_size)
    ld = forward(params, tokens, cfg)
    lf = forward(params, tokens, cfg_f)
    np.testing.assert_allclose(
        np.asarray(ld, np.float32), np.asarray(lf, np.float32),
        atol=5e-2, rtol=1e-2,
    )


@pytest.mark.parametrize("max_len", [64, 300])
@pytest.mark.parametrize("n_kv_heads", [None, 4])  # MHA and GQA
def test_kv_cache_generation_matches_full_forward(n_kv_heads, max_len):
    """Greedy decode through the KV cache (``generate``: the engine's two
    programs) must give the tokens that recomputing the full forward pass
    every step gives, in float32: the chunked online softmax is not the
    dense softmax bit for bit, so the tokens are pinned, not the logits.
    The prompt fills the cache but for 30 rows, and the heads are wide
    enough that the decode attention's kernel reads a slot of 300 rows in
    several visits (128 rows each for MHA, 256 for GQA), the last of which
    starts early because the chunk does not divide the cache."""
    from ray_tpu.models.generation import decode_attn_chunk, generate

    cfg = dataclasses.replace(
        TransformerConfig.tiny(max_seq_len=max_len, n_heads=8, d_head=128),
        dtype=jnp.float32, n_kv_heads=n_kv_heads,
    )
    chunk = decode_attn_chunk(cfg, max_len)
    assert chunk == max_len == 64 or (
        max_len - 30 > chunk and max_len % chunk)
    params = init_params(cfg, jax.random.key(0))
    prompt = jax.random.randint(
        jax.random.key(1), (2, max_len - 30), 0, cfg.vocab_size)

    out = generate(params, prompt, cfg, max_new_tokens=6)

    toks = prompt
    ref = []
    for _ in range(6):
        logits = forward(params, toks, cfg)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        ref.append(nxt)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(jnp.stack(ref, axis=1))
    )


def test_generation_sampling_and_bounds():
    cfg = dataclasses.replace(
        TransformerConfig.tiny(max_seq_len=32), dtype=jnp.float32
    )
    params = init_params(cfg, jax.random.key(0))
    prompt = jnp.ones((1, 4), jnp.int32)

    from ray_tpu.models.generation import generate

    out = generate(params, prompt, cfg, max_new_tokens=5, temperature=1.0,
                   rng=jax.random.key(7))
    assert out.shape == (1, 5)
    assert ((out >= 0) & (out < cfg.vocab_size)).all()
    with pytest.raises(ValueError, match="exceeds max_len"):
        generate(params, prompt, cfg, max_new_tokens=64)


def test_sampled_generation_is_deterministic_per_rng():
    """``temperature > 0``: the same ``rng`` gives the same tokens, another
    ``rng`` another stream, and ``max_new_tokens=1`` returns the first
    token of the longer run alone."""
    from ray_tpu.models.generation import generate

    cfg = dataclasses.replace(
        TransformerConfig.tiny(max_seq_len=32), dtype=jnp.float32
    )
    params = init_params(cfg, jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (3, 4), 0, cfg.vocab_size)

    def sample(seed, n):
        return np.asarray(generate(
            params, prompt, cfg, max_new_tokens=n, temperature=1.0,
            rng=jax.random.key(seed)))

    out = sample(7, 12)
    assert out.shape == (3, 12)
    np.testing.assert_array_equal(sample(7, 12), out)
    assert (sample(8, 12) != out).any()
    np.testing.assert_array_equal(sample(7, 1), out[:, :1])
    # rows of one prompt under one rng still draw their own streams
    same = jnp.tile(prompt[:1], (3, 1))
    rows = np.asarray(generate(params, same, cfg, max_new_tokens=12,
                               temperature=1.0, rng=jax.random.key(7)))
    assert (rows[0] != rows[1]).any()
