"""A model of full attention layers and window layers under a share of
routed experts (MiMo-V2-Flash's kind) against its plain reference, at test
size on the CPU with seeded random weights, what is this model's own: what
a window layer keeps and counts, two requests of unlike lengths in one
engine batch, the blocked attentions and the ring's kernel (interpreter)
against the plain forms, the shares of a routed layer against the uncut
layer, ill-formed ``layer_types``, and the benchmark's arithmetic.

What it shares with the other served models
(the parameter tree, the uncached forward, the two programs through a
slot, ``generate``, the ablations, the reference's independence, the cell's
listing and rehearsal) is ``tests/test_served_models.py``'s."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_swa_moe as ref
from ray_tpu.models import generation as gen
from ray_tpu.models.transformer import (
    TransformerConfig,
    init_params,
)
from ray_tpu.ops.attention import (
    NEG_INF,
    blocked_causal_attention,
    causal_attention,
    window_attention,
)
from ray_tpu.ops.decode_attention import slot_schedule
from ray_tpu.ops.moe import routed_ffn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# F(dense) | W W F W, a window of 8 rows, 8 experts (2 a token)
CFG = TransformerConfig.tiny_swa_moe(dtype=jnp.float32)
TOL = 2e-4  # float32 against float32: rounding order only


def hp_of(cfg):
    return {
        "n_heads": cfg.n_heads,
        "kv_heads": {"F": cfg.mha_kind(False)[0], "W": cfg.mha_kind(True)[0]},
        "theta": {"F": cfg.mha_kind(False)[1], "W": cfg.mha_kind(True)[1]},
        "d_head": cfg.d_head, "rotary_dim": cfg.rotary_dim,
        "window": cfg.window, "value_scale": cfg.value_scale,
        "eps": cfg.norm_eps, "top_k": cfg.moe_top_k,
        "route_scale": cfg.moe_route_scale,
        "first_expert": cfg.moe_first_expert,
        "layer_types": cfg.layer_types, "n_dense_layers": cfg.n_dense_layers}


HP = hp_of(CFG)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.key(0))


def tokens_of(n, seed=1):
    return jax.random.randint(jax.random.key(seed), (n,), 0, CFG.vocab_size)


def ref_logits(params, tokens, **kw):
    with jax.default_matmul_precision("highest"):
        return ref.forward_logits(params, tokens, HP, **kw)


def prefill(params, cache, slot, prompt, bucket, cfg=CFG):
    padded = jnp.zeros((1, bucket), jnp.int32).at[0, :len(prompt)].set(prompt)
    return gen.prefill_into_slot(
        params, padded, jnp.int32(len(prompt)), jnp.int32(slot), cache, cfg)


# -- the description ---------------------------------------------------------

def test_config_follows_the_published_numbers():
    kinds = ("attention",) + ("window",) * 4 + ("attention", "window")
    cut = TransformerConfig.mimo_v2_flash(
        7, layer_types=kinds, vocab_size=19072, moe_experts_held=16)
    assert cut.param_count() == 3_429_955_392  # ISSUE 39's arithmetic
    assert (cut.n_attn_layers, cut.n_window_layers) == (2, 5)
    whole = TransformerConfig.mimo_v2_flash()
    assert whole.layer_types.count("attention") == 9
    assert whole.layer_types[:7] == kinds
    assert whole.mha_kind(False) == (4, 5e6)
    assert whole.mha_kind(True) == (8, 1e4)
    cache = jax.eval_shape(lambda: gen.init_kv_cache(cut, 48, 17408))
    assert cache["k"].shape == (2, 48, 17408, 768)  # the heads lie flat
    assert cache["v"].shape == (2, 48, 17408, 512)
    assert cache["state"]["wk"].shape == (5, 48, 128, 1536)
    assert cache["state"]["wv"].shape == (5, 48, 128, 1024)
    foot = gen.slot_footprint(cache)
    assert (foot["row_bytes"], foot["state_bytes"]) == (5120, 3_276_800)
    assert gen.block_stat_keys(cut)[-1] == "window_rows_read"


@pytest.mark.parametrize("bad", [
    dict(layer_types=("attention", "window")),  # one entry a layer
    dict(layer_types=("attention", "window", "window", "local", "window")),
    dict(window=0),
    dict(window_kv_heads=3),  # 4 heads over 3 KV heads
    dict(layer_types=("window", "attention", "window", "attention",
                      "window")),  # the leading dense layer attends all
    dict(moe_impl="capacity"),
    dict(layer_types=("attention", "window", "ssm", "attention", "window"),
         ssm_heads=4, ssm_head_dim=8, ssm_state=16),
    dict(layer_types=()),  # a window and no window layer
    dict(mixer="mla"),
], ids=["length", "kind", "no_window", "heads", "dense_window", "capacity",
        "beside_ssm", "no_layer", "mla"])
def test_ill_formed_layer_types_are_refused(bad):
    with pytest.raises(ValueError):
        TransformerConfig.tiny_swa_moe(**bad)


# -- what a window layer keeps ----------------------------------------------

def test_a_window_layer_keeps_a_ring_and_counts_what_it_reads(params):
    """What the cache holds after a prefill of 13 tokens and two steps,
    and ``window_rows_read``: min(pos + 1, 8) a live lane a window layer,
    nothing for the parked lane."""
    cache = gen.init_kv_cache(CFG, 2, 64)
    assert cache["state"]["wk"].shape == (3, 2, 8, 4 * 64)
    assert cache["k"].shape == (2, 2, 64, 2 * 64)
    assert cache["v"].shape == (2, 2, 64, 2 * 32)
    _, cache = prefill(params, cache, 0, tokens_of(13, 2), 16)
    ring = np.asarray(cache["state"]["wk"])
    assert ring[:, 0].any(axis=-1).all() and not ring[:, 1].any()
    zeros = jnp.zeros(2, jnp.int32)
    _t, cache, _tok, pos, _c, stats = gen.decode_block(
        params, cache, jnp.array([3, 5], jnp.int32),
        jnp.array([13, 0], jnp.int32), jnp.zeros(2), zeros, zeros, CFG, 2)
    assert pos.tolist() == [15, 0]
    assert int(stats["window_rows_read"]) == 3 * 8 * 2
    assert not np.asarray(cache["state"]["wk"])[:, 1, 1:].any()
    short = gen.init_kv_cache(CFG, 1, 64)
    _, short = prefill(params, short, 0, tokens_of(3, 2), 8)
    *_x, stats = gen.decode_block(
        params, short, jnp.array([3], jnp.int32), jnp.array([3], jnp.int32),
        jnp.zeros(1), zeros[:1], zeros[:1], CFG, 2)
    assert int(stats["window_rows_read"]) == 3 * (4 + 5)


# -- the engine ---------------------------------------------------------------

def engine_of(params, **kw):
    from ray_tpu.serve.llm import LLMEngine

    return LLMEngine(
        jax.tree.map(jnp.array, params), CFG, max_slots=2, max_len=64,
        prefill_buckets=(8, 16, 32), **kw)


def worst_margin(params, prompt, ids):
    """How far the served tokens' logits lie under the reference's
    largest, teacher-forced on the served tokens (0: the same tokens)."""
    seq = jnp.asarray(list(prompt) + list(ids[:-1]), jnp.int32)
    logits = ref_logits(params, seq)
    return float(ref.served_token_margin(
        logits[len(prompt) - 1:], jnp.asarray(ids, jnp.int32)).max())


def test_engine_serves_two_requests_of_unlike_lengths_in_one_batch(params):
    """One below the window (5 tokens) and one far above it (27), the
    second admitted while the first decodes: both decode in the same
    blocks, each through its own rows and its own rings."""
    from ray_tpu.serve.llm import _END

    eng = engine_of(params)
    try:
        a, b = np.asarray(tokens_of(5, 5)), np.asarray(tokens_of(27, 6))
        first = eng.submit(a, max_new_tokens=24)
        got_a = [first.out.get(timeout=120)]  # decoding when b arrives
        got_b = eng.generate(b, max_new_tokens=12)
        while (item := first.out.get(timeout=120)) is not _END:
            assert not isinstance(item, BaseException), item
            got_a.append(item)
        assert len(got_a) == 24 and len(got_b) == 12
        assert worst_margin(params, a, got_a) < TOL
        assert worst_margin(params, b, got_b) < TOL
        s = eng.stats()
        assert s["slot_state_bytes"] == 3 * 8 * (4 * 64 + 4 * 32) * 4
        assert s["slot_row_bytes"] == 2 * (2 * 64 + 2 * 32) * 4
        assert 0 < s["window_rows_read"] <= 3 * 8 * s["slot_steps"]
        assert s["attn_rows_read"] > 0 and s["moe_assignments"] > 0
        assert s["requests_failed"] == 0
    finally:
        eng.shutdown()


# -- the blocked attentions and the ring's kernel against the plain forms ----

def _qkv(seed, b, s, h, g, d, dv):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (b, s, h, d)),
            jax.random.normal(ks[1], (b, s, g, d)),
            jax.random.normal(ks[2], (b, s, g, dv)),
            jax.random.normal(ks[3], (h,)) + 1.0)


def _plain_window(q, k, v, sink, window):
    """Every score of every head, a mask, the sink in the denominator."""
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    t = jnp.arange(q.shape[1])
    seen = (t[None, :] <= t[:, None]) & (t[None, :] > t[:, None] - window)
    e = jnp.where(seen, jnp.exp(s), 0.0)
    total = e.sum(-1, keepdims=True)
    if sink is not None:
        total = total + jnp.exp(sink)[None, :, None, None]
    return jnp.einsum("bhqk,bkhd->bqhd", e / total, v)


@pytest.mark.parametrize("s,window,block,sink", [
    (37, 8, 256, True), (64, 8, 16, True), (64, 24, 16, False),
    (48, 64, 16, True), (16, 1, 8, True)],
    ids=["one_block", "blocks", "no_sink", "window_over_s", "window_1"])
def test_window_attention_equals_the_masked_plain_form(s, window, block, sink):
    q, k, v, b = _qkv(s, 2, s, 4, 2, 16, 8)
    b = b if sink else None
    with jax.default_matmul_precision("highest"):
        got = window_attention(q, k, v, b, window=window, block=block)
        want = _plain_window(q, k, v, b, window)
    assert got.shape == (2, s, 4, 8)
    assert float(jnp.abs(got - want).max()) < 1e-5


@pytest.mark.parametrize("s,block,g", [(40, 1024, 2), (64, 16, 1),
                                       (96, 32, 4)],
                         ids=["one_tile", "tiles_one_kv_head", "tiles_mha"])
def test_blocked_causal_attention_equals_the_dense_form(s, block, g):
    q, k, v, _ = _qkv(s + 1, 2, s, 4, g, 16, 8)  # values narrower than keys
    with jax.default_matmul_precision("highest"):
        got = blocked_causal_attention(q, k, v, block=block)
        want = causal_attention(q, k, v)
    assert float(jnp.abs(got - want).max()) < 1e-5


# query heads, KV heads, key and value widths of the three served models
# whose admissions reach the kernel: MiMo-V2-Flash's full layers,
# Phi-4-mini-flash's one (differential pairs as heads), Kimi-Linear's latent
# layers as ``mla_expand`` hands them over
SERVED_HEADS = {"mimo": (64, 4, 192, 128), "phi4flash": (40, 10, 128, 128),
                "latent": (32, 32, 192, 128)}


@pytest.mark.parametrize("geometry", sorted(SERVED_HEADS))
@pytest.mark.parametrize("s,block,length", [
    (80, 512, None), (96, 64, 96), (96, 64, 70), (128, 32, 33),
    (64, 16, 1)],
    ids=["one_block", "block_halved_full", "short_of_the_bucket",
         "ends_a_block_in", "one_token"])
def test_the_prefill_kernel_at_the_served_heads(geometry, s, block, length):
    """The served geometries cut in length only, through the interpreter:
    a bucket of one block, a length 64 does not divide (blocks of 32),
    blocks of queries smaller than the rows' where heads are grouped, a
    prompt that ends inside a block, at a block's first row, of one token.
    The real rows are the dense form's; the rows past them are ZEROS."""
    h, g, d, dv = SERVED_HEADS[geometry]
    q, k, v, _ = _qkv(s + block, 1, s, h, g, d, dv)
    with jax.default_matmul_precision("highest"):
        got = blocked_causal_attention(q, k, v, length, block=block)
        want = causal_attention(q, k, v)
    n = s if length is None else length
    assert got.shape == (1, s, h, dv) and got.dtype == q.dtype
    assert float(jnp.abs(got - want)[:, :n].max()) < 1e-5
    assert not np.asarray(got[:, n:]).any()


def test_the_prefill_kernel_takes_a_length_a_sequence():
    q, k, v, _ = _qkv(11, 2, 64, 8, 2, 16, 8)
    length = jnp.array([64, 19], jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = blocked_causal_attention(q, k, v, length, block=16)
        want = causal_attention(q, k, v)
    assert float(jnp.abs(got[0] - want[0]).max()) < 1e-5
    assert float(jnp.abs(got[1, :19] - want[1, :19]).max()) < 1e-5
    assert not np.asarray(got[1, 19:]).any()


@pytest.mark.parametrize("geometry", sorted(SERVED_HEADS))
def test_the_prefill_kernel_in_bf16_stays_beside_float32(geometry):
    """bf16 operands, float32 scores and sums: against every score in
    float32 over the same rounded operands, the error is the output's own
    rounding and the probabilities' (a hundredth of the largest value is
    four times what it reads)."""
    h, g, d, dv = SERVED_HEADS[geometry]
    q, k, v = (a.astype(jnp.bfloat16)
               for a in _qkv(5, 1, 96, h, g, d, dv)[:3])
    got = blocked_causal_attention(q, k, v, jnp.int32(81), block=32)
    with jax.default_matmul_precision("highest"):
        want = causal_attention(*(a.astype(jnp.float32) for a in (q, k, v)))
    assert got.dtype == jnp.bfloat16
    err = jnp.abs(got.astype(jnp.float32) - want)[:, :81].max()
    assert float(err / jnp.abs(want).max()) < 1e-2
    assert not np.asarray(got[:, 81:]).any()


def test_the_prefill_kernel_never_hands_on_what_padding_holds():
    """Whatever a padded query holds, its row of the output is zeros: the
    next layer projects its padded keys and values from that row, and a
    masked probability of 0 times a NaN there would be NaN in a real row.
    A block of queries wholly past the length is not even read."""
    q, k, v, _ = _qkv(3, 1, 64, 4, 2, 16, 8)
    q = q.at[:, 40:].set(jnp.nan)
    got = blocked_causal_attention(q, k, v, jnp.int32(40), block=16)
    with jax.default_matmul_precision("highest"):
        want = causal_attention(q[:, :40], k[:, :40], v[:, :40])
    assert float(jnp.abs(got[:, :40] - want).max()) < 1e-5
    assert not np.asarray(got[:, 40:]).any()  # and no NaN: zeros


def _kernel_calls(jaxpr):
    """The ``pallas_call`` equations of a jaxpr, its sub-jaxprs' too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _kernel_calls(sub)


def test_the_prefill_kernel_without_a_choice_takes_no_mask(params):
    """ISSUE 56 gave the kernel a mask of chosen rows for the block with an
    indexer. Without one the call is what it was: the length and q, k, v
    where they lie, the same grid and scratch; the mask is a fifth operand
    in int8 with a block of its own, and a window model's admission (this
    file's, which attends through the kernel) holds no such array."""
    S, H, G, D, Dv, block = 256, 8, 2, 16, 8, 64
    q, k, v, _ = _qkv(9, 1, S, H, G, D, Dv)
    mask = jnp.tril(jnp.ones((S, S), bool))

    def call(**kw):
        (eqn,) = _kernel_calls(jax.make_jaxpr(
            lambda q, k, v, n: blocked_causal_attention(
                q, k, v, n, block=block, **kw))(q, k, v, jnp.int32(70)).jaxpr)
        grid = eqn.params["grid_mapping"]
        inner = [str(x.aval) for x in eqn.params["jaxpr"].invars]
        return ([str(x.aval) for x in eqn.invars], grid.grid,
                inner[-grid.num_scratch_operands:])

    operands, grid, scratch = call()
    assert operands == ["int32[1]", f"float32[1,{S},{H * D}]",
                        f"float32[1,{S},{G * D}]", f"float32[1,{S},{G * Dv}]"]
    assert grid == (1, 1, S // block, S // block)  # both KV heads a step
    rows = H // G * block
    assert scratch == [f"Ref<vmem>{{float32[2,{rows},{D}]}}",
                       f"Ref<vmem>{{float32[2,{rows},1]}}",
                       f"Ref<vmem>{{float32[2,{rows},1]}}",
                       f"Ref<vmem>{{float32[2,{rows},{Dv}]}}"]
    chosen = call(mask=mask)
    assert chosen == (operands + [f"int8[1,{S},{S}]"], grid, scratch)
    cache = gen.init_kv_cache(CFG, 2, 128)
    text = gen.prefill_into_slot.lower(
        params, jnp.zeros((1, 64), jnp.int32), jnp.int32(40), jnp.int32(0),
        cache, CFG).as_text()
    assert "xi8>" not in text


@pytest.mark.parametrize("sink", [True, False], ids=["sink", "no_sink"])
def test_the_rings_kernel_equals_the_plain_softmax(sink):
    """Lanes at 0 (parked), under a window, at it and far past it: the
    kernel (interpreter) over each lane's first ``ring_rows`` rows,
    seeded by the sink, against every score materialised."""
    B, H, G, D, Dv, W, L = 5, 4, 2, 16, 8, 8, 3
    ks = jax.random.split(jax.random.key(4), 4)
    q = jax.random.normal(ks[0], (B, 1, H, D))
    wk = jax.random.normal(ks[1], (L, B, W, G * D))
    wv = jax.random.normal(ks[2], (L, B, W, G * Dv))
    b = jax.random.normal(ks[3], (H,)) + 1.0 if sink else None
    pos = jnp.array([0, 3, 7, 8, 1000], jnp.int32)
    rows = gen.ring_rows(pos, W)
    assert rows.tolist() == [0, 4, 8, 8, 8]
    with jax.default_matmul_precision("highest"):
        got = gen._attend_ring(q, wk, wv, b, rows, layer=1,
                               schedule=slot_schedule(rows, W, W))[:, 0]
        kk = jnp.repeat(wk[1].reshape(B, W, G, D), H // G, 2)
        vv = jnp.repeat(wv[1].reshape(B, W, G, Dv), H // G, 2)
        s = jnp.einsum("bhd,bwhd->bhw", q[:, 0], kk) * D ** -0.5
        e = jnp.where(jnp.arange(W)[None, None, :] < rows[:, None, None],
                      jnp.exp(s), 0.0)
        total = e.sum(-1, keepdims=True) + (
            jnp.exp(b)[None, :, None] if sink else 0.0)
        want = jnp.einsum("bhw,bwhd->bhd", e / jnp.maximum(total, 1e-30), vv)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert not np.asarray(got[0]).any()  # the parked lane: nothing read
    assert NEG_INF < -1e29


# -- a chip's share of a routed layer ----------------------------------------

def test_the_shares_of_a_routed_layer_add_up_to_the_uncut_layer(params):
    """Every share of 2 of the 8 experts, each run as the chip that holds
    it would (``routed_ffn``, no shared expert, scale 1), summed over all
    four shares equals the uncut layer; each share alone equals the
    reference's share."""
    moe = jax.tree.map(lambda a: a[0], params["window_layers"]["moe"])
    x = jax.random.normal(jax.random.key(8), (40, CFG.d_model))
    hp = {"top_k": CFG.moe_top_k, "route_scale": CFG.moe_route_scale}
    with jax.default_matmul_precision("highest"):
        whole = ref.routed_experts(x, moe, hp, {})
    total = jnp.zeros_like(x)
    for first in range(0, CFG.moe_experts, 2):
        share = {**moe, **{k: moe[k][first:first + 2]
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


# name: tokens, experts held, first held, bias on the held experts, real
# tokens (None: all), tokens a pass, with a shared expert
SHARE_CASES = {
    "no_pick_is_held": (40, 4, 4, -100.0, None, 4096, False),
    "every_pick_is_held": (40, 4, 4, 100.0, None, 4096, False),
    "live_count_off_the_tile": (50, 4, 0, 0.0, None, 4096, False),
    "padding_picks_no_expert": (64, 4, 0, 0.5, 41, 4096, False),
    "first_expert_above_zero": (48, 6, 9, 0.5, None, 4096, False),
    "last_experts_and_a_shared_one": (48, 3, 13, 0.5, 45, 4096, True),
    "prompt_over_a_pass": (128, 4, 2, 0.5, 100, 32, False),
    "rows_of_one_tile": (8, 4, 4, 0.5, None, 4096, False),
    "whole_layer": (40, 16, 0, 0.0, 33, 4096, False),
}


@pytest.mark.parametrize("case", SHARE_CASES)
def test_a_share_moves_its_live_pairs_and_equals_the_zeroed_layer(
        case, monkeypatch):
    """A share's ``routed_ffn`` (the loop over the live pairs' tiles)
    equals the whole layer's with the absent experts' weights zeroed, and
    ``moe_pair_rows`` says how many sorted-pair rows it moved: every pair
    of a whole layer and of a share whose pairs fit one tile, the live
    pairs rounded up to tiles otherwise."""
    from ray_tpu.ops import moe

    n, held, first, bias, real, a_pass, shared = SHARE_CASES[case]
    d, E, f, k, tile = 32, 16, 16, 4, 32
    monkeypatch.setattr(moe, "ROUTED_ROWS_A_TILE", tile)
    monkeypatch.setattr(moe, "ROUTED_TOKENS_A_PASS", a_pass)
    ks = jax.random.split(jax.random.key(len(case)), 8)
    mine = (jnp.arange(E) >= first) & (jnp.arange(E) < first + held)
    wp = {"router": jax.random.normal(ks[0], (d, E)) * 0.3,
          "bias": jax.random.normal(ks[1], (E,)) * 0.02 + bias * mine}
    for i, (name, shape) in enumerate(
            (("wg", (E, d, f)), ("wi", (E, d, f)), ("wo", (E, f, d)))):
        wp[name] = jax.random.normal(ks[2 + i], shape) * 0.2
    if shared:
        wp["shared"] = {"wg": wp["wg"][0], "wi": wp["wi"][1],
                        "wo": wp["wo"][2]}
    x = jax.random.normal(ks[5], (n, d))
    mask = None if real is None else jnp.arange(n) < real
    kw = dict(top_k=k, route_scale=1.5, token_mask=mask)
    zeroed = {**wp, **{name: jnp.where(mine[:, None, None], wp[name], 0)
                       for name in ("wg", "wi", "wo")}}
    want, all_pairs = routed_ffn(x, zeroed, **kw)
    share = {**wp, **{name: wp[name][first:first + held]
                      for name in ("wg", "wi", "wo")}}
    got, stats = routed_ffn(x, share, first_expert=first, **kw)
    assert float(jnp.abs(got - want).max()) < 1e-5
    # the pairs, counted from the router alone
    s = jax.nn.sigmoid(jnp.dot(x, wp["router"], precision="highest"))
    picks = np.asarray(jax.lax.top_k(s + wp["bias"], k)[1])
    counted = np.asarray(mine)[picks] & (
        np.ones(n, bool) if real is None else np.arange(n) < real)[:, None]
    live, moved = int(counted.sum()), int(stats["moe_pair_rows"])
    assert int(stats["moe_assignments"]) == live
    assert int(all_pairs["moe_pair_rows"]) == n * k  # a whole layer: all
    if held == E or min(n, a_pass) * k <= tile:
        assert moved == n * k
    else:
        assert live <= moved <= live + (held + 1) * tile * -(-n // a_pass)
        assert moved % tile == 0
    if case == "no_pick_is_held":
        assert live == moved == 0 and not float(jnp.abs(got).max())
    if case == "every_pick_is_held":
        assert live == n * k
    if case == "live_count_off_the_tile":
        assert live % tile and live % 128


def test_a_model_that_holds_a_share_matches_the_reference_of_that_share():
    cfg = dataclasses.replace(CFG, moe_experts_held=4, moe_first_expert=2)
    params = init_params(cfg, jax.random.key(5))
    assert params["window_layers"]["moe"]["wi"].shape[:2] == (3, 4)
    assert params["window_layers"]["moe"]["router"].shape == (3, 64, 8)
    toks = tokens_of(21, 9)
    with jax.default_matmul_precision("highest"):
        want = ref.forward_logits(params, toks, hp_of(cfg))
    cache = gen.init_kv_cache(cfg, 1, 32)
    lg, cache = prefill(params, cache, 0, toks[:15], 16, cfg)
    assert float(jnp.abs(lg - want[14]).max()) < TOL
    pos = jnp.array([15], jnp.int32)
    for t in range(15, 21):
        lg, cache = gen.decode_step_multi(
            params, toks[t][None], cache, pos, cfg)
        assert float(jnp.abs(lg[0] - want[t]).max()) < TOL
        pos = pos + 1


# -- the benchmark's arithmetic ----------------------------------------------


def test_the_benchmarks_arithmetic_agrees_with_the_program():
    sys.path.insert(0, ROOT)
    try:
        from benchmarks import swa_moe_model
    finally:
        sys.path.remove(ROOT)
    with open(os.path.join(
            ROOT, "benchmarks/configs/mimo-v2-flash-l7-e16-bf16-serve.json"
    )) as f:
        model = json.load(f)
    cfg = swa_moe_model.transformer_config(model)
    dims = swa_moe_model.dims(cfg)
    n = swa_moe_model.param_count(dims)
    assert n["total"] == cfg.param_count() == 3_429_955_392
    assert (n["attn_full"], n["attn_window"]) == (89_128_960, 94_371_904)
    assert n["routed"] == 403_702_016 and n["dense_ffn"] == 201_326_592
    assert swa_moe_model.slot_bytes(dims) == {"row": 5120, "state": 3_276_800}
    assert cfg.layer_types == ("attention",) + ("window",) * 4 + (
        "attention", "window")
    assert (cfg.rotary_dim, cfg.window, cfg.value_scale) == (64, 128, 0.707)
    # every weight once, nothing touched, nothing read
    fixed = swa_moe_model.decode_step_bytes(dims, 0, 0, 0)
    assert fixed == 2 * (n["total"] - 6 * 16 * n["expert"]
                         - 19072 * 4096)  # less the experts, the embedding
    flops = swa_moe_model.prefill_attention_flops(dims, 16384)
    assert flops["window"] < 0.02 * flops["full"]
    tiny = swa_moe_model.transformer_config(
        {**model, **model["rehearsal"]})
    assert tiny.layer_types.count("window") == 3 and tiny.window == 8
