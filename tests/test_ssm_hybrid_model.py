"""A model of state-space layers and attention layers (granite-4.0-h-micro's
kind) against its plain reference, at test size on the CPU with seeded
random weights, what is this model's own: the chunked scan against the
token-by-token recurrence, the update kernel, a padded bucket's state, a
parked lane's state, slots left alone for several blocks, the engine end
to end with its counters, and the existing models' configs under the new
defaults.

What it shares with the other served models
(the parameter tree, the uncached forward, the two programs through a
slot, ``generate``, the ablations, the reference's independence, the cell's
listing and rehearsal) is ``tests/test_served_models.py``'s."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_ssm as ref
from ray_tpu.models import generation as gen
from ray_tpu.models.transformer import (
    TransformerConfig,
    init_params,
)
from ray_tpu.ops.ssm import causal_conv, ssm_chunked, ssm_step, ssm_update

# six layers, ssm ssm attention twice over; chunks of 8 tokens
CFG = TransformerConfig.tiny_ssm_hybrid(dtype=jnp.float32)
TOL = 2e-4  # float32 against float32: rounding order only


def hp_of(cfg):
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.kv_heads,
            "d_head": cfg.d_head, "eps": cfg.norm_eps,
            "embed_scale": cfg.embed_scale,
            "residual_scale": cfg.residual_scale,
            "logit_scale": cfg.logit_scale, "attn_scale": cfg.attn_scale,
            "layer_types": cfg.layer_types, "ssm_heads": cfg.ssm_heads,
            "ssm_head_dim": cfg.ssm_head_dim, "ssm_state": cfg.ssm_state,
            "ssm_groups": cfg.ssm_groups}


HP = hp_of(CFG)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.key(0))


def tokens_of(n, seed=1):
    return jax.random.randint(jax.random.key(seed), (n,), 0, CFG.vocab_size)


def ref_logits(params, tokens, **kw):
    with jax.default_matmul_precision("highest"):
        return ref.forward_logits(params, tokens, HP, **kw)


def prefill(params, cache, slot, prompt, bucket):
    padded = jnp.zeros((1, bucket), jnp.int32).at[0, :len(prompt)].set(prompt)
    return gen.prefill_into_slot(
        params, padded, jnp.int32(len(prompt)), jnp.int32(slot), cache, CFG)


# -- the recurrence itself -------------------------------------------------

def scan_inputs(seed, b, s, h=4, p=8, g=2, n=16):
    ks = jax.random.split(jax.random.key(seed), 6)
    return dict(
        x=jax.random.normal(ks[0], (b, s, h, p)),
        dt=jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 2.0),
        A=-jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.5)),
        B=jax.random.normal(ks[3], (b, s, g, n)),
        C=jax.random.normal(ks[4], (b, s, g, n)),
        D=jax.random.normal(ks[5], (h,)))


def token_by_token(a, state0=None):
    """The recurrence one ``ssm_step`` a token: what ``ssm_chunked`` must
    equal."""
    b, s, h, p = a["x"].shape
    state = (jnp.zeros((b, h, p, a["B"].shape[-1])) if state0 is None
             else state0)
    ys = []
    for t in range(s):
        y, state = ssm_step(state, a["x"][:, t], a["dt"][:, t], a["A"],
                            a["B"][:, t], a["C"][:, t], a["D"])
        ys.append(y)
    return jnp.stack(ys, 1), state


@pytest.mark.parametrize("length", [5, 8, 19, 37])
@pytest.mark.parametrize("with_state0", [False, True], ids=["empty", "state0"])
def test_chunked_scan_equals_the_recurrence(length, with_state0):
    a = scan_inputs(length, 2, length)
    state0 = (jax.random.normal(jax.random.key(9), (2, 4, 8, 16))
              if with_state0 else None)
    y, end = ssm_chunked(**a, chunk=8, state0=state0)
    want_y, want_end = token_by_token(a, state0)
    np.testing.assert_allclose(y, want_y, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(end, want_end, atol=TOL, rtol=TOL)


def test_ssm_step_equals_the_references_token():
    a = scan_inputs(3, 1, 6, g=1)
    _, state = token_by_token(a)
    # the reference's own recurrence over the same six tokens, one head's
    # B and C for all (one group)
    want = jnp.zeros((4, 8, 16))
    for t in range(6):
        want = (jnp.exp(a["dt"][0, t] * a["A"])[:, None, None] * want
                + (a["dt"][0, t][:, None] * a["x"][0, t])[:, :, None]
                * a["B"][0, t, 0][None, None, :])
    np.testing.assert_allclose(state[0], want, atol=TOL, rtol=TOL)


# slots, heads, head dim, groups, state, layers, the layer stepped, bytes a tile
@pytest.mark.parametrize("b,h,p,g,n,layers,layer,tile", [
    (4, 4, 8, 1, 16, 3, 1, 2 ** 20),  # one group, one tile
    (4, 4, 8, 2, 16, 3, 2, 2 ** 20),  # two groups, two heads a row block
    (5, 24, 8, 3, 16, 2, 0, 2 * 8 * 8 * 16 * 4),  # 2 slots a tile, of 5
    (3, 320, 8, 1, 16, 2, 1, 8 * 128 * 16 * 4),  # 8 row blocks a tile, of 20
    (2, 3, 16, 3, 8, 4, 3, 2 ** 20),  # a head a group, a block
], ids=["one_group", "two_groups", "slots_undivided", "heads_undivided",
        "head_a_group"])
def test_the_update_kernel_equals_the_step_on_one_layer_in_place(
        b, h, p, g, n, layers, layer, tile):
    """``ssm_update`` (the served path's kernel, here in the Pallas
    interpreter) against ``ssm_step``, the plain form: the stepped
    layer's states and ``y`` to rounding, every OTHER layer's states bit
    for bit, and a lane whose ``dt`` is 0 keeps its state exactly."""
    a = scan_inputs(layer + 7, b, 1, h=h, p=p, g=g, n=n)
    a = {k: (v[:, 0] if v.ndim > 1 else v) for k, v in a.items()}
    a["dt"] = a["dt"].at[0].set(0.0)  # slot 0: nothing decays, nothing adds
    states = jax.random.normal(jax.random.key(11), (layers, b, h, p, n))
    want_y, want = ssm_step(states[layer], **a)
    y, new = jax.jit(ssm_update, static_argnames="tile_bytes")(
        states, jnp.int32(layer), **a, tile_bytes=tile)
    assert new.shape == states.shape and new.dtype == jnp.float32
    np.testing.assert_allclose(new[layer], want, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(y, want_y, atol=1e-4, rtol=1e-5)
    others = np.arange(layers) != layer
    np.testing.assert_array_equal(new[others], states[others])
    np.testing.assert_array_equal(new[layer, 0], states[layer, 0])
    assert float(jnp.abs(new[layer, 1] - states[layer, 1]).max()) > 1e-2


LIVE = {"all": [1, 1, 1, 1, 1], "none": [0, 0, 0, 0, 0],
        "leading_parked": [0, 0, 1, 1, 1], "trailing_parked": [1, 1, 1, 0, 0],
        "alternating": [1, 0, 1, 0, 1], "one_live": [0, 0, 0, 1, 0]}


# heads, groups, bytes a tile: (slots a tile, row blocks a tile) of 5 slots
@pytest.mark.parametrize("h,g,tile", [
    (8, 2, 32 * 16 * 4),  # a slot's group a tile: tb = 1, two groups
    (8, 2, 2 * 32 * 16 * 4),  # two slots a tile: tb = 2, the last half full
    (320, 1, 8 * 128 * 16 * 4),  # 8 of a slot's 20 row blocks a tile
], ids=["a_slot_a_tile", "two_slots_a_tile", "row_blocks_a_tile"])
@pytest.mark.parametrize("lanes", list(LIVE))
def test_the_update_kernel_visits_the_live_lanes_only(lanes, h, g, tile):
    """``ssm_update`` told which lanes are live (the Pallas interpreter
    here): a live lane's ``y`` and new state equal ``ssm_step``'s, a
    parked lane's state is bit for bit the input's and its ``y`` zeros
    (whether its tile is skipped whole or shared with a live lane), every
    other layer is bit for bit the input's, and no ``live`` at all is
    every lane live."""
    layers, layer, live = 3, 1, np.array(LIVE[lanes], bool)
    a = scan_inputs(5, 5, 1, h=h, p=8, g=g, n=16)
    a = {k: (v[:, 0] if v.ndim > 1 else v) for k, v in a.items()}
    states = jax.random.normal(jax.random.key(12), (layers, 5, h, 8, 16))
    want_y, want = ssm_step(states[layer], **a)
    update = jax.jit(ssm_update, static_argnames="tile_bytes")
    y, new = update(states, jnp.int32(layer), **a, live=jnp.asarray(live),
                    tile_bytes=tile)
    assert new.shape == states.shape and new.dtype == jnp.float32
    np.testing.assert_allclose(new[layer][live], want[live],
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(y[live], want_y[live], atol=1e-4, rtol=1e-5)
    np.testing.assert_array_equal(new[layer][~live], states[layer][~live])
    assert not np.asarray(y)[~live].any()
    others = np.arange(layers) != layer
    np.testing.assert_array_equal(new[others], states[others])
    if live.all():
        plain_y, plain = update(states, jnp.int32(layer), **a,
                                tile_bytes=tile)
        np.testing.assert_array_equal(y, plain_y)
        np.testing.assert_array_equal(new, plain)


def test_a_padded_buckets_end_state_is_the_state_at_prompt_len():
    a = scan_inputs(4, 1, 32)
    n = 21
    valid = (jnp.arange(32) < n)[None]
    _, end = ssm_chunked(**a, chunk=8, valid=valid)
    short = {k: (v[:, :n] if v.ndim > 1 else v) for k, v in a.items()}
    _, want = ssm_chunked(**short, chunk=8)
    np.testing.assert_allclose(end, want, atol=TOL, rtol=TOL)
    _, ignored = ssm_chunked(**a, chunk=8)  # the padding taken as tokens
    assert float(jnp.abs(ignored - want).max()) > 1e-2


def test_causal_conv_continues_from_its_tail():
    x = jax.random.normal(jax.random.key(0), (2, 11, 6))
    w = jax.random.normal(jax.random.key(1), (4, 6))
    bias = jax.random.normal(jax.random.key(2), (6,))
    whole = causal_conv(x, w, bias)
    rest = causal_conv(x[:, 7:], w, bias, tail=x[:, 4:7])
    np.testing.assert_allclose(rest, whole[:, 7:], atol=1e-6)


# -- the model --------------------------------------------------------------

def test_config_follows_the_published_numbers():
    c = TransformerConfig.granite4_h_micro()
    assert c.param_count() == 3_191_396_096  # 3.19 B: ISSUE 35's count
    assert (c.n_layers, c.n_ssm_layers, c.n_attn_layers) == (40, 36, 4)
    assert [i for i, k in enumerate(c.layer_types) if k == "attention"] == [
        5, 15, 25, 35]
    assert (c.ssm_inner, c.ssm_conv_width) == (4096, 4352)
    assert (c.embed_scale, c.residual_scale, c.logit_scale,
            c.attn_scale) == (12.0, 0.22, 0.125, 0.015625)
    assert c.rotary_dim == 0 and c.tie_embeddings
    foot = gen.slot_footprint(jax.eval_shape(
        lambda: gen.init_kv_cache(dataclasses.replace(
            c, dtype=jnp.bfloat16), 48, 4096)))
    assert foot == {"state_bytes": 76_437_504, "row_bytes": 8192,
                    "state_layers": 36}


def test_layer_types_are_checked():
    with pytest.raises(ValueError):
        TransformerConfig.tiny_ssm_hybrid(layer_types=("ssm",) * 5)
    with pytest.raises(ValueError):
        TransformerConfig.tiny_ssm_hybrid(ssm_state=0)


def test_existing_models_are_untouched_by_the_new_defaults():
    for c in (TransformerConfig.gptj_6b(), TransformerConfig.glm47_flash(8),
              TransformerConfig.glm52(6, n_dense_layers=1, indexer_types=(
                  "full",) + ("shared",) * 3 + ("full", "shared")),
              TransformerConfig.tiny()):
        assert c.layer_types == () and c.n_ssm_layers == 0
        assert c.n_attn_layers == c.n_layers
        assert (c.embed_scale, c.residual_scale, c.logit_scale,
                c.attn_scale) == (1.0, 1.0, 1.0, None)
    c = TransformerConfig.gptj_6b()
    cache = jax.eval_shape(lambda: gen.init_kv_cache(
        dataclasses.replace(c, dtype=jnp.bfloat16), 8, 1024))
    assert set(cache) == {"k", "v"} and cache["k"].shape == (
        28, 8, 1024, 16, 256)
    assert gen.slot_footprint(cache) == {
        "state_bytes": 0, "row_bytes": 458_752, "state_layers": 0}


def test_decode_block_leaves_a_parked_lanes_state_where_it_lies(params):
    """What ``state_slots_skipped`` counts: a parked lane's state is
    neither read nor written (ISSUE 46; it used to be stepped with the
    others), whatever it held: here what a request left behind."""
    cache = gen.init_kv_cache(CFG, 2, 64)
    _, cache = prefill(params, cache, 0, tokens_of(9, 2), 16)
    _, cache = prefill(params, cache, 1, tokens_of(7, 3), 16)
    parked = np.asarray(gen.cache_state(cache)["ssm"][:, 1])
    assert parked.any()
    zeros = jnp.zeros(2, jnp.int32)
    _t, cache, _tok, pos, _c, stats = gen.decode_block(
        params, cache, jnp.array([3, 5], jnp.int32),
        jnp.array([9, 0], jnp.int32), jnp.zeros(2), zeros, zeros, CFG, 2)
    assert pos.tolist() == [11, 0] and stats == {}
    state = np.asarray(gen.cache_state(cache)["ssm"])
    np.testing.assert_array_equal(state[:, 1], parked)
    assert np.abs(state[:, 0]).max() > 0


# -- the engine ---------------------------------------------------------------

def engine_of(params, **kw):
    from ray_tpu.serve.llm import LLMEngine

    return LLMEngine(
        jax.tree.map(jnp.array, params), CFG, max_slots=2, max_len=64,
        prefill_buckets=(16, 32), **kw)


def worst_margin(params, prompt, ids):
    """How far the served tokens' logits lie under the reference's
    largest, teacher-forced on the served tokens (0: the same tokens)."""
    seq = jnp.asarray(list(prompt) + list(ids[:-1]), jnp.int32)
    logits, _ = ref_logits(params, seq)
    return float(ref.served_token_margin(
        logits[len(prompt) - 1:], jnp.asarray(ids, jnp.int32)).max())


def test_engine_serves_two_requests_admitted_at_different_times(params):
    from ray_tpu.serve.llm import _END

    eng = engine_of(params)
    try:
        a, b = np.asarray(tokens_of(13, 5)), np.asarray(tokens_of(20, 6))
        first = eng.submit(a, max_new_tokens=12)
        got_a = [first.out.get(timeout=120)]  # decoding when b arrives
        got_b = eng.generate(b, max_new_tokens=6)
        while (item := first.out.get(timeout=120)) is not _END:
            assert not isinstance(item, BaseException), item
            got_a.append(item)
        assert len(got_a) == 12 and len(got_b) == 6
        assert worst_margin(params, a, got_a) < TOL
        assert worst_margin(params, b, got_b) < TOL
        s = eng.stats()
        assert s["slot_state_bytes"] == 4 * (4 * 8 * 16 * 4 + 3 * 64 * 4)
        assert s["slot_row_bytes"] == 2 * 2 * 128 * 4
        # every slot's state a step and state layer is moved or skipped
        assert (s["state_slots_updated"] + s["state_slots_skipped"]
                == 2 * 4 * s["steps"])
        assert s["slot_steps"] * 4 <= s["state_slots_updated"]
        assert s["state_slots_skipped"] > 0  # b came late and left early
        assert s["requests_failed"] == 0
    finally:
        eng.shutdown()


def test_a_slot_skipped_for_several_blocks_serves_as_a_fresh_one(params):
    """Slot 1 holds what a request left, then stays parked (its states
    skipped) while slot 0 decodes for several blocks; the request then
    admitted into it gets the tokens a fresh engine gives."""
    from ray_tpu.serve.llm import _END

    eng = engine_of(params)
    try:
        p, q, r = (np.asarray(tokens_of(n, s))
                   for n, s in ((17, 7), (11, 8), (13, 9)))
        long = eng.submit(p, max_new_tokens=40)  # slot 0
        eng.generate(q, max_new_tokens=3)  # slot 1, then freed and parked
        before, until = eng.stats(), time.time() + 120
        while eng.stats()["steps"] < before["steps"] + 12:
            assert time.time() < until
            time.sleep(0.01)  # slot 0 alone: slot 1's states are skipped
        after = eng.stats()
        again = eng.generate(r, max_new_tokens=8)  # into the skipped slot
        while long.out.get(timeout=120) is not _END:
            pass
    finally:
        eng.shutdown()
    assert (after["state_slots_skipped"] - before["state_slots_skipped"]
            >= 4 * 8)
    fresh = engine_of(params)
    try:
        assert again == fresh.generate(r, max_new_tokens=8)
    finally:
        fresh.shutdown()
    assert worst_margin(params, r, again) < TOL
