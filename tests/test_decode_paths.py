"""The decode attention's kernel == the plain masked form.

``_attend_prefix_plus_self`` and ``_attend_latent_prefix_plus_self`` read
each slot's cache rows in chunks up to the slot's own length with an
online softmax (``ops/decode_attention``, here in the Pallas interpreter);
they must give what scoring every row under a mask gives, for MHA, GQA and
the latent cache, per-slot positions, parked lanes and a cache length the
chunk does not divide.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _masked_full_row_attention(q, ck, cv, k_new, v_new, pos, scale=None):
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
    scale = scale or q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, k) * scale
    mask = jnp.arange(k.shape[1])[None, :] < pos[:, None]
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    self_score = jnp.einsum("bqhd,bqhd->bhq", qf, kn)[..., None] * scale
    probs = jax.nn.softmax(
        jnp.concatenate([scores, self_score], axis=-1), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs[..., :-1], v)
    return out + probs[..., -1:].transpose(0, 2, 1, 3) * vn


S_MAX, CHUNK = 72, 16  # 72 = 4 x 16 + 8: the last chunk starts early


def _schedule(pos):
    """The step's visits in chunks of this test's own size."""
    from ray_tpu.ops.decode_attention import slot_schedule

    return slot_schedule(pos, S_MAX, CHUNK)


def _dense_case(kv_heads, pos):
    """(attend, cache, plain): MHA / GQA over k and v [B,S_MAX,Hkv,D].
    ``attend`` takes the cache's arrays, each stacked [L,B,S_MAX,...],
    and ``layer``, the one to read, and returns the kernel's output;
    ``plain`` is what it must equal."""
    from ray_tpu.models.generation import _attend_prefix_plus_self

    B, H, D = len(pos), 4, 8
    ks = jax.random.split(jax.random.key(1), 5)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (B, 1, H, D), bf)
    cache = (jax.random.normal(ks[1], (B, S_MAX, kv_heads, D), bf),
             jax.random.normal(ks[2], (B, S_MAX, kv_heads, D), bf))
    k_new = jax.random.normal(ks[3], (B, 1, kv_heads, D), bf)
    v_new = jax.random.normal(ks[4], (B, 1, kv_heads, D), bf)

    def attend(ck, cv, layer):
        return _attend_prefix_plus_self(
            q, ck, cv, k_new, v_new, pos, layer=layer,
            schedule=_schedule(pos))

    return attend, cache, _masked_full_row_attention(
        q, *cache, k_new, v_new, pos)


def _latent_case(pos):
    """The same for the latent cache: ONE key for the 4 query heads, in
    two parts (``ckv`` [B,S_MAX,R], the value too, and ``kr``
    [B,S_MAX,rope]). The plain form sees it as one KV head whose key is
    the two parts side by side."""
    from ray_tpu.models.generation import _attend_latent_prefix_plus_self

    B, H, R, P = len(pos), 4, 16, 8
    ks = jax.random.split(jax.random.key(2), 6)
    bf = jnp.bfloat16
    q_lat = jax.random.normal(ks[0], (B, H, R), bf)
    q_rope = jax.random.normal(ks[1], (B, H, P), bf)
    cache = (jax.random.normal(ks[2], (B, S_MAX, R), bf),
             jax.random.normal(ks[3], (B, S_MAX, P), bf))
    c_new = jax.random.normal(ks[4], (B, R), bf)
    r_new = jax.random.normal(ks[5], (B, P), bf)
    scale = 0.2

    def attend(ckv, kr, layer):
        return _attend_latent_prefix_plus_self(
            q_lat, q_rope, ckv, kr, c_new, r_new, pos, layer=layer,
            scale=scale, schedule=_schedule(pos))[:, None]

    def one_head(c, r):  # [..., R], [..., P] -> key [..., 1, R + P]
        return jnp.concatenate([c, r], -1)[..., None, :]

    return attend, cache, _masked_full_row_attention(
        jnp.concatenate([q_lat, q_rope], -1)[:, None], one_head(*cache),
        cache[0][:, :, None], one_head(c_new, r_new)[:, None],
        c_new[:, None, None], pos, scale)


@pytest.mark.parametrize("form", ["mha", "gqa", "latent"])
@pytest.mark.parametrize("pos", [
    (0, 1, CHUNK - 1, CHUNK, CHUNK + 1, S_MAX - 1),
    (0, 0, 0, 0, 0, 0),  # every lane parked: no chunk is read
    (S_MAX - 1, 3, 0, 2 * CHUNK, S_MAX - 9, S_MAX - 8),
    (5, 5, 5, 5, 5, 5),
])
def test_chunked_attention_equals_masked_full_rows(form, pos):
    pos = jnp.asarray(pos, jnp.int32)
    attend, cache, want = (
        _latent_case(pos) if form == "latent"
        else _dense_case({"mha": 4, "gqa": 2}[form], pos))
    got = attend(*(a[None] for a in cache), layer=0)
    assert got.dtype == jnp.bfloat16 and got.shape == want.shape
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), atol=2e-2, rtol=2e-2)
    # the same rows out of the whole cache by layer index, bit for bit:
    # the kernel reads its own layer alone, whatever the others hold
    got_l = attend(*(jnp.stack([jnp.full_like(a, jnp.nan), a,
                                jnp.full_like(a, jnp.nan)]) for a in cache),
                   layer=1)
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(got))
    # rows at or past pos hold garbage the result must not see
    past = jnp.arange(S_MAX)[None, :] >= pos[:, None]
    got_g = attend(*(
        jnp.where(past.reshape(past.shape + (1,) * (a.ndim - 2)), g, a
                  ).astype(a.dtype)[None]
        for a, g in zip(cache, (1e4, -1e4))), layer=0)
    np.testing.assert_array_equal(np.asarray(got_g), np.asarray(got))


@pytest.mark.parametrize("kind", ["tiny", "tiny_mla_moe", "tiny_dsa_moe"])
def test_which_decode_blocks_hold_the_kernel(kind):
    """Both dense caches are read by the kernel (once a layer stack); the
    block with an indexer keeps its masked walk over the chosen rows."""
    from ray_tpu.models import generation as gen
    from ray_tpu.models.transformer import TransformerConfig, init_params

    cfg = getattr(TransformerConfig, kind)()
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    cache = jax.eval_shape(lambda: gen.init_kv_cache(cfg, 2, 64))
    lane = jax.ShapeDtypeStruct((2,), jnp.int32)
    program = str(jax.make_jaxpr(
        lambda *a: gen.decode_block(*a, cfg, 2))(
        params, cache, lane, lane, jax.ShapeDtypeStruct((2,), jnp.float32),
        lane, lane))
    assert ("decode_attention" in program) == (kind != "tiny_dsa_moe")
