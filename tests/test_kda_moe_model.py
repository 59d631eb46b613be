"""A model of gated-delta-rule linear-attention layers ("kda") and latent
attention layers under a share of routed experts and a shared one
(Kimi-Linear's kind) against its plain reference, at test size on the CPU
with seeded random weights, what is this model's own: the three forms of
the delta rule against each other, a padded bucket's state, a parked
lane, mixed lanes in one engine batch, a slot skipped for several blocks,
the quarter shares of a routed layer against the uncut layer, ill-formed
``layer_types``, and the benchmark's arithmetic.

What it shares with the other served models
(the parameter tree, the uncached forward, the two programs through a
slot, ``generate``, the ablations, the reference's independence, the cell's
listing and rehearsal) is ``tests/test_served_models.py``'s."""

import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_kda_moe as ref
from ray_tpu.models import generation as gen
from ray_tpu.models.transformer import (
    TransformerConfig,
    init_params,
)
from ray_tpu.ops.kda import kda_chunked, kda_step, kda_update
from ray_tpu.ops.moe import routed_ffn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# K(dense) K K F K F, chunks of 8, 8 experts (2 a token) and a shared one
CFG = TransformerConfig.tiny_kda_moe(dtype=jnp.float32)
TOL = 2e-4  # float32 against float32: rounding order only


def hp_of(cfg):
    return {
        "n_heads": cfg.n_heads, "qk_nope": cfg.qk_nope_dim,
        "qk_rope": cfg.qk_rope_dim, "kv_rank": cfg.kv_lora_rank,
        "eps": cfg.norm_eps, "theta": cfg.rope_theta,
        "top_k": cfg.moe_top_k, "route_scale": cfg.moe_route_scale,
        "first_expert": cfg.moe_first_expert,
        "layer_types": cfg.layer_types,
        "n_dense_layers": cfg.n_dense_layers, "kda_heads": cfg.kda_heads,
        "kda_head_dim": cfg.kda_head_dim}


HP = hp_of(CFG)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.key(0))


def tokens_of(n, seed=1):
    return jax.random.randint(jax.random.key(seed), (n,), 0, CFG.vocab_size)


def ref_logits(params, tokens, hp=HP, **kw):
    with jax.default_matmul_precision("highest"):
        return ref.forward_logits(params, tokens, hp, **kw)


def prefill(params, cache, slot, prompt, bucket, cfg=CFG):
    padded = jnp.zeros((1, bucket), jnp.int32).at[0, :len(prompt)].set(prompt)
    return gen.prefill_into_slot(
        params, padded, jnp.int32(len(prompt)), jnp.int32(slot), cache, cfg)


# -- the description ---------------------------------------------------------

def test_config_follows_the_published_numbers():
    cut = TransformerConfig.kimi_linear(
        8, layer_types=("kda", "kda", "kda", "attention") * 2,
        vocab_size=40960, moe_experts_held=64)
    assert cut.param_count() == 3_772_368_832  # ISSUE 44's arithmetic
    assert (cut.n_attn_layers, cut.n_kda_layers) == (2, 6)
    whole = TransformerConfig.kimi_linear()
    # config.json counts layers from 1: full_attn_layers 4, 8, .., 24, 27
    assert [i + 1 for i, k in enumerate(whole.layer_types)
            if k == "attention"] == [4, 8, 12, 16, 20, 24, 27]
    assert whole.layer_types[:8] == cut.layer_types
    assert (whole.q_lora_rank, whole.mla_rope) == (0, False)
    cache = jax.eval_shape(lambda: gen.init_kv_cache(
        dataclasses.replace(cut, dtype=jnp.bfloat16), 96, 10240))
    assert cache["ckv"].shape == (2, 96, 10240, 512)
    assert cache["kr"].shape == (2, 96, 10240, 64)
    assert cache["state"]["kda"].shape == (6, 96, 32, 128, 128)
    assert cache["state"]["kda"].dtype == jnp.float32
    assert cache["state"]["conv"].shape == (6, 96, 3 * 3 * 4096)
    assert gen.slot_footprint(cache) == {
        "state_bytes": 6 * (2_097_152 + 73_728), "row_bytes": 2304,
        "state_layers": 6}
    keys = gen.block_stat_keys(cut)
    assert "moe_experts_touched" in keys and "window_rows_read" not in keys


def test_the_decay_is_drawn_to_differ_by_channel(params):
    """What the initialiser is for: over seeded inputs a token's decay a
    channel spreads over about 0.2-0.999 and differs between the channels
    of one head; a decay every probe would round away teaches nothing."""
    wp = jax.tree.map(lambda a: a[0], params["kda_layers"]["kda"])
    h = jax.random.normal(jax.random.key(2), (64, CFG.d_model))
    step = jax.nn.softplus((h @ wp["wfa"]) @ wp["wfb"] + wp["dt_bias"])
    alpha = jnp.exp(-jnp.exp(wp["a_log"])[:, None] * step.reshape(64, 2, 16))
    assert 0.05 < float(alpha.min()) < 0.6 and float(alpha.max()) > 0.99
    assert 0.7 < float(jnp.median(alpha)) < 0.99
    assert float((alpha.max(-1) - alpha.min(-1)).mean()) > 0.1


@pytest.mark.parametrize("bad", [
    dict(layer_types=("kda", "attention")),  # one entry a layer
    dict(layer_types=("kda", "kda", "kda", "linear", "kda", "attention")),
    dict(kda_heads=0),
    dict(kda_conv=1),
    dict(moe_impl="capacity"),
    dict(layer_types=("kda", "attention", "kda", "attention", "kda",
                      "attention"), n_dense_layers=2),  # two kinds lead
    dict(layer_types=("kda", "kda", "ssm", "attention", "kda", "attention"),
         ssm_heads=4, ssm_head_dim=8, ssm_state=16),
    dict(layer_types=("kda", "kda", "window", "attention", "kda",
                      "attention"), window=8),
    dict(residual="parallel"),
], ids=["length", "kind", "no_heads", "no_taps", "capacity", "dense_kinds",
        "beside_ssm", "beside_window", "parallel"])
def test_ill_formed_layer_types_are_refused(bad):
    with pytest.raises(ValueError):
        TransformerConfig.tiny_kda_moe(**bad)


def test_existing_models_are_untouched_by_the_new_defaults():
    for c in (TransformerConfig.gptj_6b(), TransformerConfig.glm47_flash(8),
              TransformerConfig.granite4_h_micro(),
              TransformerConfig.mimo_v2_flash(7, layer_types=(
                  "attention",) + ("window",) * 6)):
        assert c.n_kda_layers == 0 and c.mla_rope
        assert c.n_attn_layers == c.layer_types.count("attention") or (
            not c.layer_types and c.n_attn_layers == c.n_layers)
    glm = TransformerConfig.glm47_flash(8, dtype=jnp.bfloat16)
    cache = jax.eval_shape(lambda: gen.init_kv_cache(glm, 32, 4096))
    assert set(cache) == {"ckv", "kr"}
    assert cache["ckv"].shape == (8, 32, 4096, 512)


# -- the three forms of the delta rule ---------------------------------------

def _delta_inputs(seed, b, s, h, dk, dv, lo=0.2, hi=0.999):
    """Unit keys, queries / sqrt(dk), and decays a channel spread over
    ``lo`` .. ``hi``."""
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (b, s, h, dk))
    k = jax.random.normal(ks[1], (b, s, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    alpha = jax.random.uniform(ks[3], (b, s, h, dk), minval=lo, maxval=hi)
    return (q, k, jax.random.normal(ks[2], (b, s, h, dv)), jnp.log(alpha),
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h))))


def _token_by_token(q, k, v, g, beta, state=None):
    b, s, h, dk = k.shape
    if state is None:
        state = jnp.zeros((b, h, dk, v.shape[-1]))
    out = []
    for t in range(s):
        o, state = kda_step(state, q[:, t], k[:, t], v[:, t], g[:, t],
                            beta[:, t])
        out.append(o)
    return jnp.stack(out, 1), state


@pytest.mark.parametrize("length,chunk", [
    (5, 8), (8, 8), (19, 8), (64, 64), (150, 64), (100, 32)])
@pytest.mark.parametrize("with_state0", [False, True], ids=["empty", "state0"])
def test_the_chunked_delta_rule_equals_the_recurrence(length, chunk,
                                                      with_state0):
    """Lengths below, at and above a chunk and no multiples of it; chunks
    of one sub-block (8), two (32) and four (64)."""
    q, k, v, g, beta = _delta_inputs(length, 2, length, 3, 16, 8)
    state0 = jax.random.normal(
        jax.random.key(7), (2, 3, 16, 8)) if with_state0 else None
    want_o, want_s = _token_by_token(q, k, v, g, beta, state0)
    got_o, got_s = kda_chunked(q, k, v, g, beta, chunk, state0=state0)
    assert float(jnp.abs(got_o - want_o).max()) < 2e-5
    assert float(jnp.abs(got_s - want_s).max()) < 2e-5


def test_the_chunked_form_survives_a_channel_that_forgets_at_once():
    """A decay of 0.02 a token: exp(-G) over a chunk of 64 is 1e108, far
    past float32; the sub-blocks' reference points keep every factor
    finite."""
    q, k, v, g, beta = _delta_inputs(3, 1, 128, 2, 16, 8, lo=0.02, hi=0.03)
    want_o, want_s = _token_by_token(q, k, v, g, beta)
    got_o, got_s = kda_chunked(q, k, v, g, beta, 64)
    assert bool(jnp.isfinite(got_o).all())
    assert float(jnp.abs(got_o - want_o).max()) < 2e-5
    assert float(jnp.abs(got_s - want_s).max()) < 2e-5


@pytest.mark.parametrize("heads,length", [
    (8, 1), (8, 63), (8, 64), (8, 65), (8, 200), (32, 65)])
def test_the_chunk_kernel_at_the_served_heads_equals_the_recurrence(
        heads, length):
    """The served geometry's structure through the interpreter: heads of
    128 x 128 (whole lanes: four heads a grid step, two of them side by
    side in the solve), chunks of 64 in four sub-blocks, a batch of two,
    a state to start from; lengths below, at and above a chunk."""
    q, k, v, g, beta = _delta_inputs(length, 2, length, heads, 128, 128)
    state0 = jax.random.normal(jax.random.key(8), (2, heads, 128, 128))
    want_o, want_s = _token_by_token(q, k, v, g, beta, state0)
    got_o, got_s = jax.jit(kda_chunked, static_argnums=5)(
        q, k, v, g, beta, 64, state0)
    assert float(jnp.abs(got_o - want_o).max()) < 2e-5
    assert float(jnp.abs(got_s - want_s).max()) < 2e-5


def test_the_chunk_kernel_in_bf16_stays_near_the_float32_recurrence():
    """The operands the chip runs (q, k, v in bf16, g and beta float32)
    against the recurrence in float32 on the same values: the kernel
    rounds the large products' operands and nothing else, so ``o`` and the
    end state stay within 1.5 % of their largest value over 130 tokens
    (read here: 0.46 % and 0.22 %)."""
    q, k, v, g, beta = _delta_inputs(6, 1, 130, 4, 128, 128)
    q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, v))
    want_o, want_s = _token_by_token(
        *(a.astype(jnp.float32) for a in (q, k, v)), g, beta)
    got_o, got_s = kda_chunked(q, k, v, g, beta, 64)
    assert got_o.dtype == jnp.bfloat16 and got_s.dtype == jnp.float32
    off = float(jnp.abs(got_o.astype(jnp.float32) - want_o).max()
                / jnp.abs(want_o).max())
    assert off < 0.015, off
    off = float(jnp.abs(got_s - want_s).max() / jnp.abs(want_s).max())
    assert off < 0.015, off


def test_kda_step_equals_the_references_token(params):
    """One layer's mixer over a sequence through ``kda_step``'s recurrence
    is the reference's, state and all."""
    wp = jax.tree.map(lambda a: a[1], params["kda_layers"]["kda"])
    h = jax.random.normal(jax.random.key(4), (23, CFG.d_model))
    with jax.default_matmul_precision("highest"):
        want, want_state = ref.kda(h, wp, HP, {})
        from ray_tpu.models import transformer as tf

        def recur(qkv, g, beta, wp):
            from ray_tpu.ops.ssm import causal_conv

            q, k, v = tf.kda_split(
                causal_conv(qkv, wp["conv_w"], None), CFG)
            o, state = _token_by_token(q, k, v, g, beta)
            return o, state

        got, got_state = tf._kda_mixer(
            h[None], wp, CFG, None, gen._recurrence(recur))
    assert float(jnp.abs(got[0] - want).max()) < TOL
    assert float(jnp.abs(got_state[0] - want_state).max()) < TOL


@pytest.mark.parametrize("b,h,dk,dv,layers,layer,tile,parked", [
    (4, 4, 16, 8, 3, 1, 2 * 16 * 8 * 4, 1),  # two heads a tile
    (3, 2, 16, 16, 2, 0, 16 * 16 * 4, 1),  # one head a tile
    (4, 2, 8, 16, 2, 1, 2 ** 20, 1),  # every slot and head in one tile
    # the chip's widths (whole 128-lane rows turned to columns): four
    # heads and two slots a tile, the parked slot inside a live tile
    (4, 4, 128, 128, 2, 1, 8 * 128 * 128 * 4, 1),
    (2, 8, 128, 128, 2, 0, 4 * 128 * 128 * 4, 1),  # half a slot's heads
    (2, 4, 128, 128, 2, 1, 8 * 128 * 128 * 4, None),  # no live at all
    (3, 2, 128, 256, 2, 0, 2 * 128 * 256 * 4, 2),  # Dv is not Dk
    (4, 4, 16, 8, 3, 2, 2 * 16 * 8 * 4, None),
], ids=["heads", "head", "whole", "lanes128_slots", "lanes128_heads",
        "lanes128_no_live", "lanes128_wide_values", "heads_no_live"])
def test_the_update_kernel_equals_the_step_on_one_layer_in_place(
        b, h, dk, dv, layers, layer, tile, parked):
    """``kda_update`` (the Pallas interpreter here) against ``kda_step``:
    the layer it is told, the other layers' bytes untouched, a parked
    lane's state bit for bit what it was (``parked`` None: called without
    ``live``, every lane stepped)."""
    states = jax.random.normal(jax.random.key(1), (layers, b, h, dk, dv))
    q, k, v, g, beta = (a[:, 0] for a in _delta_inputs(2, b, 1, h, dk, dv))
    live = None if parked is None else jnp.arange(b) != parked
    o, new = jax.jit(kda_update, static_argnames=("tile_bytes",))(
        states, layer, q, k, v, g, beta, live, tile_bytes=tile)
    want_o, want_s = kda_step(states[layer], q, k, v, g, beta)
    stepped = jnp.ones(b, bool) if parked is None else live
    assert float(jnp.abs(o - want_o)[stepped].max()) < 1e-5
    assert float(jnp.abs(new[layer] - want_s)[stepped].max()) < 1e-5
    if parked is not None:
        assert np.array_equal(new[layer, parked], states[layer, parked])
        assert not np.asarray(o[parked]).any()
    others = [i for i in range(layers) if i != layer]
    assert np.array_equal(new[jnp.array(others)], states[jnp.array(others)])


LIVE = {"all": [1] * 6, "none": [0] * 6,
        "leading_parked": [0, 0, 0, 1, 1, 1],
        "trailing_parked": [1, 1, 1, 0, 0, 0],
        "alternating": [1, 0, 1, 0, 1, 0], "one_live": [0, 0, 0, 0, 1, 0]}


@pytest.mark.parametrize("d", [16, 128], ids=["d16", "d128"])
@pytest.mark.parametrize("heads_a_tile", [1, 2, 4],
                         ids=["a_head_a_tile", "a_slot_a_tile",
                              "two_slots_a_tile"])
@pytest.mark.parametrize("lanes", list(LIVE))
def test_the_update_kernel_visits_the_live_lanes_only(lanes, heads_a_tile, d):
    """``kda_update`` told which lanes are live (the Pallas interpreter
    here), 6 slots of 2 heads, d x d a head (16, and the chip's 128): a
    live lane's ``o`` and new state equal ``kda_step``'s, a parked lane's
    state is bit for bit the input's and its ``o`` zeros (whether its
    tile is skipped whole or shared with a live lane), every other layer
    is bit for bit the input's, and no ``live`` at all is every lane
    live."""
    layers, layer, live = 3, 2, np.array(LIVE[lanes], bool)
    tile = heads_a_tile * d * d * 4
    states = jax.random.normal(jax.random.key(3), (layers, 6, 2, d, d))
    q, k, v, g, beta = (a[:, 0] for a in _delta_inputs(4, 6, 1, 2, d, d))
    update = jax.jit(kda_update, static_argnames=("tile_bytes",))
    o, new = update(states, layer, q, k, v, g, beta, jnp.asarray(live),
                    tile_bytes=tile)
    want_o, want = kda_step(states[layer], q, k, v, g, beta)
    np.testing.assert_allclose(new[layer][live], want[live], atol=1e-5)
    np.testing.assert_allclose(o[live], want_o[live], atol=1e-5)
    np.testing.assert_array_equal(new[layer][~live], states[layer][~live])
    assert not np.asarray(o)[~live].any()
    np.testing.assert_array_equal(new[:layer], states[:layer])
    if live.all():
        plain_o, plain = update(states, layer, q, k, v, g, beta,
                                tile_bytes=tile)
        np.testing.assert_array_equal(o, plain_o)
        np.testing.assert_array_equal(new, plain)


def test_a_padded_buckets_end_state_is_the_state_at_prompt_len():
    q, k, v, g, beta = _delta_inputs(5, 1, 32, 2, 16, 16)
    valid = (jnp.arange(32) < 21)[None]
    _, padded = kda_chunked(q, k, v, g, beta, 8, valid=valid)
    _, exact = kda_chunked(q[:, :21], k[:, :21], v[:, :21], g[:, :21],
                           beta[:, :21], 8)
    assert float(jnp.abs(padded - exact).max()) < 1e-6
    _, at_end = kda_chunked(q, k, v, g, beta, 8)
    assert float(jnp.abs(at_end - exact).max()) > 1e-2


def test_a_chunk_wholly_past_the_prompt_leaves_the_state_bit_for_bit():
    """Three chunks of 64, the prompt ends with the first: the kernel
    neither reads nor computes the other two (their ``o`` is zeros) and
    the end state is, bit for bit, the state after the first chunk alone;
    a prompt of no tokens hands ``state0`` back bit for bit."""
    q, k, v, g, beta = _delta_inputs(9, 2, 192, 4, 128, 128)
    state0 = jax.random.normal(jax.random.key(10), (2, 4, 128, 128))
    valid = jnp.broadcast_to(jnp.arange(192) < 64, (2, 192))
    o, padded = kda_chunked(q, k, v, g, beta, 64, state0, valid)
    first_o, first = kda_chunked(
        *(a[:, :64] for a in (q, k, v, g, beta)), 64, state0)
    np.testing.assert_array_equal(padded, first)
    np.testing.assert_array_equal(o[:, :64], first_o)
    assert not np.asarray(o[:, 64:]).any()
    _, untouched = kda_chunked(q, k, v, g, beta, 64, state0,
                               jnp.zeros((2, 192), bool))
    np.testing.assert_array_equal(untouched, state0)


# -- a parked lane ----------------------------------------------------------

def test_a_parked_lanes_state_and_rows_do_not_change_while_others_step(
        params):
    """Slot 1 holds a finished request's state, tails and rows and is
    parked (``pos`` 0); slot 0 decodes 4 steps: nothing of slot 1 moves,
    and the block's counters count the live lane's picks alone."""
    cache = gen.init_kv_cache(CFG, 2, 64)
    _, cache = prefill(params, cache, 0, tokens_of(9, 2), 16)
    _, cache = prefill(params, cache, 1, tokens_of(11, 3), 16)
    before = jax.tree.map(lambda a: np.asarray(a[:, 1]), cache)
    assert before["state"]["kda"].any() and before["state"]["conv"].any()
    zeros = jnp.zeros(2, jnp.int32)
    _t, cache, _tok, pos, _c, stats = gen.decode_block(
        params, cache, jnp.array([3, 5], jnp.int32),
        jnp.array([9, 0], jnp.int32), jnp.zeros(2), zeros, zeros, CFG, 4)
    assert pos.tolist() == [13, 0]
    after = jax.tree.map(lambda a: np.asarray(a[:, 1]), cache)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        assert np.array_equal(a, b)
    # 5 routed layers x 4 steps x 2 picks of the one live lane
    assert int(stats["moe_assignments"]) == 5 * 4 * 2


# -- the engine ---------------------------------------------------------------

def engine_of(params, **kw):
    from ray_tpu.serve.llm import LLMEngine

    return LLMEngine(
        jax.tree.map(jnp.array, params), CFG, max_slots=3, max_len=64,
        prefill_buckets=(8, 16, 32), **kw)


def worst_margin(params, prompt, ids):
    """How far the served tokens' logits lie under the reference's
    largest, teacher-forced on the served tokens (0: the same tokens)."""
    seq = jnp.asarray(list(prompt) + list(ids[:-1]), jnp.int32)
    logits, _ = ref_logits(params, seq)
    return float(ref.served_token_margin(
        logits[len(prompt) - 1:], jnp.asarray(ids, jnp.int32)).max())


def test_engine_serves_mixed_lanes_end_to_end(params):
    """Three requests of unlike lengths (under the convolution's taps,
    under a chunk, over two chunks), the later two admitted while the
    first decodes: every lane through its own state, tails and latent
    rows, in the same blocks; the engine's counters report the states
    updated, the latent rows read and the routed layers' picks."""
    from ray_tpu.serve.llm import _END

    eng = engine_of(params)
    try:
        a, b, c = (np.asarray(tokens_of(n, s))
                   for n, s in ((3, 5), (7, 6), (27, 7)))
        first = eng.submit(a, max_new_tokens=24)
        got_a = [first.out.get(timeout=120)]  # decoding when b, c arrive
        second = eng.submit(b, max_new_tokens=10)
        got_c = eng.generate(c, max_new_tokens=12)
        got_b = []
        for req, got in ((first, got_a), (second, got_b)):
            while (item := req.out.get(timeout=120)) is not _END:
                assert not isinstance(item, BaseException), item
                got.append(item)
        assert (len(got_a), len(got_b), len(got_c)) == (24, 10, 12)
        for prompt, got in ((a, got_a), (b, got_b), (c, got_c)):
            assert worst_margin(params, prompt, got) < TOL
        s = eng.stats()
        assert s["slot_state_bytes"] == 4 * (2 * 16 * 16 * 4 + 3 * 3 * 32 * 4)
        assert s["slot_row_bytes"] == 2 * (16 + 8) * 4
        # every slot's state a step and kda layer is moved or skipped
        assert (s["state_slots_updated"] + s["state_slots_skipped"]
                == 4 * s["capacity_steps"])
        assert s["slot_steps"] * 4 <= s["state_slots_updated"]
        assert s["state_slots_skipped"] > 0  # a decoded alone at first
        assert s["attn_rows_read"] > 0 and s["moe_assignments"] > 0
        assert s["prefill_moe_assignments"] > 0
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
                   for n, s in ((17, 7), (6, 8), (13, 9)))
        long = eng.submit(p, max_new_tokens=40)  # slot 0
        eng.generate(q, max_new_tokens=3)  # slot 1, then freed and parked
        before, until = eng.stats(), time.time() + 120
        while eng.stats()["steps"] < before["steps"] + 12:
            assert time.time() < until
            time.sleep(0.01)  # slot 0 alone: the others' states are skipped
        after = eng.stats()
        again = eng.generate(r, max_new_tokens=8)  # into the skipped slot
        while long.out.get(timeout=120) is not _END:
            pass
    finally:
        eng.shutdown()
    assert (after["state_slots_skipped"] - before["state_slots_skipped"]
            >= 4 * 8 * 2)
    fresh = engine_of(params)
    try:
        assert again == fresh.generate(r, max_new_tokens=8)
    finally:
        fresh.shutdown()
    assert worst_margin(params, r, again) < TOL


# -- a chip's share of a routed layer ----------------------------------------

def test_the_quarter_shares_of_a_routed_layer_add_up_to_the_uncut_layer(
        params):
    """The four shares of 2 of the 8 experts, each run as the chip that
    holds it would (``routed_ffn`` over its experts, the router whole),
    with the shared expert counted ONCE, add up to the uncut reference's
    layer; each share alone equals the reference's share."""
    moe = jax.tree.map(lambda a: a[0], params["kda_layers"]["moe"])
    routed_only = {k: v for k, v in moe.items() if k != "shared"}
    x = jax.random.normal(jax.random.key(8), (40, CFG.d_model))
    hp = {"top_k": CFG.moe_top_k, "route_scale": CFG.moe_route_scale}
    with jax.default_matmul_precision("highest"):
        whole = ref.routed_experts(x, moe, hp, {})
    total = jnp.zeros_like(x)
    for first in range(0, CFG.moe_experts, 2):
        mine = moe if first == 0 else routed_only  # the shared one once
        share = {**mine, **{k: moe[k][first:first + 2]
                            for k in ("wg", "wi", "wo")}}
        got, stats = routed_ffn(x, share, top_k=CFG.moe_top_k,
                                route_scale=CFG.moe_route_scale,
                                first_expert=first)
        assert int(stats["moe_experts_capacity"]) == 2
        with jax.default_matmul_precision("highest"):
            want = ref.routed_experts(
                x, share, {**hp, "first_expert": first}, {})
        assert float(jnp.abs(got - want).max()) < TOL
        total = total + got
    assert float(jnp.abs(total - whole).max()) < TOL


def test_a_model_that_holds_a_share_matches_the_reference_of_that_share():
    cfg = dataclasses.replace(CFG, moe_experts_held=2, moe_first_expert=4)
    params = init_params(cfg, jax.random.key(5))
    assert params["kda_layers"]["moe"]["wi"].shape[:2] == (3, 2)
    assert params["layers"]["moe"]["router"].shape == (2, 64, 8)
    toks = tokens_of(27, 9)
    want, _ = ref_logits(params, toks, hp_of(cfg))
    cache = gen.init_kv_cache(cfg, 1, 32)
    lg, cache = prefill(params, cache, 0, toks[:21], 32, cfg)
    assert float(jnp.abs(lg - want[20]).max()) < TOL
    pos = jnp.array([21], jnp.int32)
    for t in range(21, 27):
        lg, cache = gen.decode_step_multi(
            params, toks[t][None], cache, pos, cfg)
        assert float(jnp.abs(lg[0] - want[t]).max()) < TOL
        pos = pos + 1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_a_quarter_shares_loop_brings_rows_back_by_product(monkeypatch,
                                                           dtype):
    """Where a share is a quarter of the layer the live-pair loop adds a
    turn's rows to their tokens as a product with the turn's 0/1 matrix
    (``ROUTED_SHARE_BY_PRODUCT``; XLA's scatter-add did not return on the
    chip there): the same sums as ``np.add.at`` and as the scatter-add a
    sixteenth's share keeps, and that share's program is the one it was."""
    from ray_tpu.ops import moe

    ks = jax.random.split(jax.random.key(3), 7)
    rows = jax.random.normal(ks[5], (64, 48)) * 3
    token = jax.random.randint(ks[6], (64,), 0, 40)
    want = np.zeros((40, 48), np.float32)
    np.add.at(want, np.asarray(token), np.asarray(rows))
    got = moe._rows_to_tokens(token, rows, 40, dtype == jnp.float32)
    lost = 1e-6 if dtype == jnp.float32 else 2.0 ** -15
    assert np.abs(np.asarray(got) - want).max() <= lost * np.abs(want).max()

    d, f, outputs, held, n = 64, 48, 16, 4, 300

    def w(k, shape, fan):
        return (jax.random.normal(k, shape) / fan ** 0.5).astype(dtype)

    wp = {"router": jax.random.normal(ks[0], (d, outputs)) / 8,
          "bias": jnp.zeros(outputs), "wg": w(ks[1], (held, d, f), d),
          "wi": w(ks[2], (held, d, f), d), "wo": w(ks[3], (held, f, d), f)}
    x = jax.random.normal(ks[4], (n, d)).astype(dtype)
    monkeypatch.setattr(moe, "ROUTED_ROWS_A_TILE", 64)  # 1,200 pairs: looped

    def run(share):
        monkeypatch.setattr(moe, "ROUTED_SHARE_BY_PRODUCT", share)
        fn = jax.jit(lambda x, wp: moe.routed_ffn(
            x, wp, top_k=4, route_scale=2.0, first_expert=4))
        y, stats = fn(x, wp)
        return (np.asarray(y.astype(jnp.float32)), int(stats["moe_pair_rows"]),
                fn.lower(x, wp).as_text().count("scatter"))

    by_product, moved, scatters = run(8)  # 4 of 16: a quarter, by product
    by_scatter, moved_s, scatters_s = run(1)  # the same share, scatter-add
    assert moved == moved_s == 320 and scatters < scatters_s
    ulp = 2.0 ** -7 if dtype == jnp.bfloat16 else 1e-6
    assert np.abs(by_product - by_scatter).max() <= ulp * np.abs(
        by_scatter).max()
    # a sixteenth's share (one expert of 16) keeps the scatter-add
    sixteenth = {**wp, **{k: wp[k][:1] for k in ("wg", "wi", "wo")}}
    monkeypatch.setattr(moe, "ROUTED_SHARE_BY_PRODUCT", 8)
    text = jax.jit(lambda x, wp: moe.routed_ffn(
        x, wp, top_k=4, first_expert=4)).lower(x, sixteenth).as_text()
    assert text.count("scatter") == scatters_s


# -- the benchmark's arithmetic ----------------------------------------------


def test_the_benchmarks_arithmetic_agrees_with_the_program():
    sys.path.insert(0, ROOT)
    try:
        from benchmarks import kda_moe_model
    finally:
        sys.path.remove(ROOT)
    with open(os.path.join(
            ROOT, "benchmarks/configs/kimi-linear-l8-e64-bf16-serve.json"
    )) as f:
        model = json.load(f)
    cfg = kda_moe_model.transformer_config(model)
    dims = kda_moe_model.dims(cfg)
    n = kda_moe_model.param_count(dims)
    assert n["total"] == cfg.param_count() == 3_772_368_832
    assert (n["kda"], n["attn_full"]) == (39_514_272, 29_114_880)
    assert n["routed"] == 460_652_800 and n["dense_ffn"] == 63_700_992
    cache = jax.eval_shape(lambda: gen.init_kv_cache(cfg, 96, 10240))
    foot = gen.slot_footprint(cache)
    assert kda_moe_model.slot_bytes(dims) == {
        "row": foot["row_bytes"], "state": foot["state_bytes"]}
    assert cfg.layer_types == ("kda", "kda", "kda", "attention") * 2
    assert (cfg.moe_experts, cfg.experts_held, cfg.vocab_size) == (
        256, 64, 40960)
    shapes = jax.eval_shape(
        lambda: kda_moe_model.make_bf16_params(cfg, 2 ** 31 + 5))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == n["total"]
    assert {a.dtype for a in jax.tree.leaves(shapes)} == {
        jnp.dtype(jnp.bfloat16)}
    assert jax.tree.structure(shapes) == jax.tree.structure(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0))))
    tiny = kda_moe_model.transformer_config(
        {**model, **model["rehearsal"]})
    assert tiny.layer_types == ("kda", "kda", "kda", "attention", "kda",
                                "attention")
    assert (tiny.moe_experts, tiny.experts_held) == (8, 2)
