"""The decode attention's chunked walk == the plain masked form.

``_attend_prefix_plus_self`` reads the cache in row chunks up to the
longest live sequence with an online softmax; it must give what scoring
every row under a mask gives, for GQA, per-slot positions, parked lanes
and a cache length the chunk does not divide.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _masked_full_row_attention(q, ck, cv, k_new, v_new, pos):
    """The plain form of ``_attend_prefix_plus_self``: every row of the
    cache scored, rows at or past ``pos`` masked, one softmax with the
    self position appended."""
    from ray_tpu.ops.attention import NEG_INF, repeat_kv

    n_rep = q.shape[2] // ck.shape[2]
    f32 = jnp.float32
    k, v = repeat_kv(ck, n_rep).astype(f32), repeat_kv(cv, n_rep).astype(f32)
    kn = repeat_kv(k_new, n_rep).astype(f32)
    vn = repeat_kv(v_new, n_rep).astype(f32)
    qf = q.astype(f32)
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, k) * scale
    mask = jnp.arange(k.shape[1])[None, :] < pos[:, None]
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    self_score = jnp.einsum("bqhd,bqhd->bhq", qf, kn)[..., None] * scale
    probs = jax.nn.softmax(
        jnp.concatenate([scores, self_score], axis=-1), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs[..., :-1], v)
    return out + probs[..., -1:].transpose(0, 2, 1, 3) * vn


S_MAX, CHUNK = 72, 16  # 72 = 4 x 16 + 8: the last chunk starts early


@pytest.mark.parametrize("kv_heads", [4, 2])  # MHA and GQA
@pytest.mark.parametrize("pos", [
    (0, 1, CHUNK - 1, CHUNK, CHUNK + 1, S_MAX - 1),
    (0, 0, 0, 0, 0, 0),  # every lane parked: no chunk is walked
    (S_MAX - 1, 3, 0, 2 * CHUNK, S_MAX - 9, S_MAX - 8),
    (5, 5, 5, 5, 5, 5),
])
def test_chunked_attention_equals_masked_full_rows(kv_heads, pos):
    from ray_tpu.models.generation import _attend_prefix_plus_self

    B, H, D = len(pos), 4, 8
    ks = jax.random.split(jax.random.key(1), 5)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (B, 1, H, D), bf)
    ck = jax.random.normal(ks[1], (B, S_MAX, kv_heads, D), bf)
    cv = jax.random.normal(ks[2], (B, S_MAX, kv_heads, D), bf)
    k_new = jax.random.normal(ks[3], (B, 1, kv_heads, D), bf)
    v_new = jax.random.normal(ks[4], (B, 1, kv_heads, D), bf)
    pos = jnp.asarray(pos, jnp.int32)
    want = _masked_full_row_attention(q, ck, cv, k_new, v_new, pos)
    got = _attend_prefix_plus_self(q, ck, cv, k_new, v_new, pos,
                                   chunk=CHUNK)
    assert got.dtype == bf and got.shape == (B, 1, H, D)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), atol=2e-2, rtol=2e-2)
    # the same rows out of the whole cache by layer index, bit for bit
    got_l = _attend_prefix_plus_self(
        q, jnp.stack([jnp.zeros_like(ck), ck]),
        jnp.stack([jnp.zeros_like(cv), cv]), k_new, v_new, pos, layer=1,
        chunk=CHUNK)
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(got))
    # rows at or past pos hold garbage the result must not see
    past = jnp.arange(S_MAX)[None, :, None, None] >= pos[:, None, None, None]
    got_g = _attend_prefix_plus_self(
        q, jnp.where(past, 1e4, ck).astype(bf),
        jnp.where(past, -1e4, cv).astype(bf), k_new, v_new, pos, chunk=CHUNK)
    np.testing.assert_array_equal(np.asarray(got_g), np.asarray(got))
