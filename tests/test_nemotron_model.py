"""What only the one-branch model has (Nemotron-3-Super's kind: a layer
is a state-space mixer, an attention or a routed FFN ALONE, the routed
experts in a latent): the shares of a routed layer add up to the whole,
``routed_ffn`` with a latent in each of its forms, the grouped gated norm,
the published counts, what the description refuses, and that the new
fields' defaults leave the other models' programs alone. The claims every
served model shares are ``tests/test_served_models.py``'s, row "ssm_moe"."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_mla_moe as reference
from benchmarks import reference_ssm_moe as ref
from ray_tpu.models import generation as gen
from ray_tpu.models.transformer import (
    _ACTIVATIONS,
    TransformerConfig,
    _ssm_mixer,
    init_params,
)
from ray_tpu.ops import moe

F32 = jnp.float32
CFG = TransformerConfig.tiny_ssm_moe(dtype=F32)


def hp_of(cfg):
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.kv_heads,
            "d_head": cfg.d_head, "eps": cfg.norm_eps,
            "layer_types": cfg.layer_types, "ssm_heads": cfg.ssm_heads,
            "ssm_head_dim": cfg.ssm_head_dim, "ssm_state": cfg.ssm_state,
            "ssm_groups": cfg.ssm_groups,
            "norm_groups": cfg.ssm_norm_groups, "top_k": cfg.moe_top_k,
            "route_scale": cfg.moe_route_scale,
            "first_expert": cfg.moe_first_expert}


def routed_layer(cfg, seed=0):
    """One routed layer's weights (no layer axis) of ``cfg``'s model."""
    params = init_params(cfg, jax.random.key(seed))
    return jax.tree.map(lambda a: a[0], params["expert_layers"]["moe"])


def share_of(whole, first, held):
    return {**whole, "wi": whole["wi"][first:first + held],
            "wo": whole["wo"][first:first + held]}


def dense_routed(h, wp, cfg, first=0):
    """The routed layer as one dense einsum over the experts ``wp`` holds:
    the chosen experts' weights g [n, E], 0 elsewhere."""
    s = jax.nn.sigmoid(h @ wp["router"])
    _, idx = jax.lax.top_k(s + wp["bias"], cfg.moe_top_k)
    g = jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None], idx].set(1) * s
    g = cfg.moe_route_scale * g / g.sum(-1, keepdims=True)
    g = g[:, first:first + wp["wi"].shape[0]]
    u = h @ wp["latent_in"]
    m = jnp.square(jax.nn.relu(jnp.einsum("nl,elf->nef", u, wp["wi"])))
    y = jnp.einsum("nef,efl,ne->nl", m, wp["wo"], g) @ wp["latent_out"]
    sp = wp["shared"]
    return y + jnp.square(jax.nn.relu(h @ sp["wi"])) @ sp["wo"]


def test_the_four_quarters_of_a_routed_layer_add_up_to_the_whole():
    """Each chip of four holds a quarter of the experts and all of the
    router, the two projections and the shared expert: the four parts,
    with the shared expert counted once, are the uncut reference's whole
    layer (the projection out of the latent is linear)."""
    cfg = dataclasses.replace(CFG, moe_experts_held=0, moe_first_expert=0)
    whole = routed_layer(cfg)
    h = jax.random.normal(jax.random.key(5), (48, cfg.d_model), F32)
    hp = hp_of(cfg)
    with jax.default_matmul_precision("highest"):
        want = ref.experts(h, whole, hp, {})
        shared = ref.experts(h, share_of(whole, 0, 0), hp, {})
        parts = []
        for q in range(4):
            wp = share_of(whole, 4 * q, 4)
            parts.append(ref.experts(h, wp, {**hp, "first_expert": 4 * q},
                                     {}) - shared)
            # and the program's share is the reference's
            got, _ = moe.routed_ffn(
                h, wp, top_k=cfg.moe_top_k, route_scale=cfg.moe_route_scale,
                act=_ACTIVATIONS["relu2"], first_expert=4 * q)
            assert float(jnp.abs(got - parts[-1] - shared).max()) < 1e-4
    assert float(jnp.abs(sum(parts) + shared - want).max()) < 1e-4
    assert float(jnp.abs(parts[0]).max()) > 1e-2  # a part is no nothing


@pytest.mark.parametrize("first,held", [(0, 16), (4, 4)],
                         ids=["whole", "a_quarter"])
@pytest.mark.parametrize("tokens", [8, 200], ids=["fused", "looped"])
def test_routed_ffn_with_a_latent_is_the_dense_sum(first, held, tokens,
                                                   monkeypatch):
    """Both forms of ``routed_ffn`` (one fused pass; a share's loop over
    its live pairs' tiles, brought back by the 0/1 product) with experts
    in a latent against the dense einsum over the chosen experts, and a
    masked token's routed part is nothing."""
    monkeypatch.setattr(moe, "ROUTED_ROWS_A_TILE", 64)
    cfg = dataclasses.replace(CFG, moe_experts_held=0, moe_first_expert=0)
    wp = share_of(routed_layer(cfg), first, held)
    h = jax.random.normal(jax.random.key(7), (tokens, cfg.d_model), F32)
    mask = jnp.arange(tokens) % 5 != 3
    got, stats = moe.routed_ffn(
        h, wp, top_k=cfg.moe_top_k, route_scale=cfg.moe_route_scale,
        act=_ACTIVATIONS["relu2"], token_mask=mask, first_expert=first)
    with jax.default_matmul_precision("highest"):
        want = dense_routed(h, wp, cfg, first)
        alone = dense_routed(h, {**wp, "wi": wp["wi"][:0],
                                 "wo": wp["wo"][:0]}, cfg, first)
    want = jnp.where(mask[:, None], want, alone)  # masked: shared alone
    assert float(jnp.abs(got - want).max()) < 2e-4
    looped = held < 16 and tokens * cfg.moe_top_k > 64
    assert (int(stats["moe_pair_rows"]) % 64 == 0) == looped
    assert int(stats["moe_experts_capacity"]) == held


def test_the_gated_norm_over_groups():
    """With ``ssm_norm_groups`` G the mean square is each group's own:
    on ONE group's channels it is the one-group norm of those channels,
    and over eight groups it differs from one norm over all."""
    cfg = dataclasses.replace(
        CFG, ssm_heads=8, ssm_groups=2, ssm_norm_groups=8)
    params = init_params(cfg, jax.random.key(1))
    wp = jax.tree.map(lambda a: a[0], params["ssm_layers"]["ssm"])
    wp["norm"] = 1.0 + 0.1 * jax.random.normal(
        jax.random.key(2), wp["norm"].shape)
    h = jax.random.normal(jax.random.key(3), (1, 12, cfg.d_model), F32)
    # W_out as the identity on the channels: the mixer's output IS the norm
    wp = {**wp, "wo": jnp.eye(cfg.ssm_inner)}
    seen = {groups: _ssm_mixer(
        h, wp, dataclasses.replace(cfg, ssm_norm_groups=groups), None,
        None)[0][0] for groups in (8, 1)}
    width = cfg.ssm_inner // 8
    assert float(jnp.abs(seen[8] - seen[1]).max()) > 1e-2
    # a group's channels, normed alone: their ratio to the one-group norm
    # is one number a token (the two mean squares'), another a group
    ratio = (seen[8] / seen[1]).reshape(12, 8, width)
    assert float(jnp.abs(ratio - ratio[..., :1]).max()) < 1e-4
    assert float(jnp.abs(ratio[:, 0, 0] - ratio[:, 1, 0]).max()) > 1e-2


def test_the_published_counts():
    """120.7 B parameters in all and 12.2 B a token ("120B-A12B"), from
    ``config.json``'s sizes alone; the cut holds 4.65 B."""
    full = TransformerConfig.nemotron3_super()
    assert (full.n_ssm_layers, full.n_expert_layers, full.n_attn_layers
            ) == (40, 40, 8)
    assert full.param_count() == 120_668_707_840
    d, lat, f = full.d_model, full.moe_latent, full.moe_d_ff
    expert = 2 * lat * f
    routed = full.param_count() - full.vocab_size * d  # but the embedding
    active = routed - 40 * (full.moe_experts - full.moe_top_k) * expert
    assert 12.1e9 < active < 12.3e9
    cut = TransformerConfig.nemotron3_super(
        "MEMEMEM*EME", moe_experts_held=128, vocab_size=32768)
    assert cut.layer_types == full.layer_types[:11]
    assert cut.param_count() == 4_648_163_712
    cache = jax.eval_shape(lambda: gen.init_kv_cache(cut, 64, 8192))
    foot = gen.slot_footprint(cache)
    assert foot == {"state_bytes": 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2),
                    "row_bytes": 2 * 2 * 128 * 2, "state_layers": 5}
    assert cache["k"].shape == (1, 64, 8192, 256)  # two KV heads, flat


def test_what_the_description_refuses():
    base = dict(dtype=F32)
    with pytest.raises(ValueError):  # "ssm" beside experts in a pair block
        TransformerConfig.tiny_ssm_hybrid(
            moe_experts=8, moe_impl="dropless", moe_d_ff=48, **base)
    with pytest.raises(ValueError):  # an "experts" layer in a pair block
        TransformerConfig.tiny_ssm_moe(block="pair", **base)
    with pytest.raises(ValueError):  # experts and no layer that holds them
        TransformerConfig.tiny_ssm_moe(
            layer_types=("ssm", "attention") * 3 + ("ssm",), **base)
    with pytest.raises(ValueError):  # a latent needs dropless experts
        TransformerConfig.tiny(moe_latent=32)
    with pytest.raises(ValueError):  # channels no multiple of the groups
        TransformerConfig.tiny_ssm_moe(ssm_norm_groups=3, **base)
    with pytest.raises(ValueError):
        TransformerConfig.tiny_ssm_moe(block="triple", **base)
    # a one-branch stack with no routed layer describes nothing routed
    plain = TransformerConfig.tiny_ssm_moe(
        layer_types=("ssm", "attention"), n_layers=2, moe_experts=0,
        moe_experts_held=0, moe_first_expert=0, moe_latent=0,
        moe_shared_d_ff=0, **base)
    assert set(init_params(plain, jax.random.key(0))) == {
        "embed", "final_ln", "layers", "ssm_layers", "lm_head"}


@pytest.mark.parametrize("preset", ["tiny_mla_moe", "tiny_ssm_hybrid",
                                    "tiny"])
def test_the_new_fields_leave_the_other_models_programs_alone(preset):
    """The defaults are every other model's block: a config that spells
    them out is the same config, its decode program's text has none of
    the new scopes, and its slot keeps what it kept (a latent model, a
    state-space hybrid with a dense FFN a layer, GPT-J's block). The
    benchmark's configurations are compared with the parent commit by
    ``tools/lowered_texts.py`` (PERF.md section 6, PR 60)."""
    cfg = getattr(TransformerConfig, preset)(dtype=F32)
    spelt = dataclasses.replace(cfg, block="pair", moe_latent=0,
                                moe_shared_d_ff=0, ssm_norm_groups=1)
    assert spelt == cfg and hash(spelt) == hash(cfg)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    cache = jax.eval_shape(lambda: gen.init_kv_cache(cfg, 2, 32))
    lane = jax.ShapeDtypeStruct((2,), jnp.int32)
    text = gen.decode_step_multi.lower(
        params, lane, cache, lane, cfg).as_text(debug_info=True)
    assert "raytpu.moe.latent" not in text
    assert "expert_layers" not in params
    if preset == "tiny":  # four MHA heads of 16: a row keeps its heads
        assert cache["k"].shape == (2, 2, 32, 4, 16)


def test_taps_hand_back_every_layers_input_and_change_nothing_else():
    """``prefill_into_slot`` and ``decode_block`` asked for ``taps`` give
    the same logits, tokens and cache as the engine's programs, and beside
    them each layer's input as they made it: the first layer's is the
    embedding, the reference's layer over a layer's inputs gives the next
    one's (the benchmark's check of the timed path: ``runners/serve_ssm_moe
    ._cmd_layers_served``), and the head over "out" gives the logits."""
    params = init_params(CFG, jax.random.key(2))
    hp, n, steps, slots = hp_of(CFG), 11, 4, 2
    prompt = jnp.zeros((1, 16), jnp.int32).at[0, :n].set(
        jax.random.randint(jax.random.key(3), (n,), 0, CFG.vocab_size))

    def prefill(taps):
        return gen.prefill_into_slot(
            params, prompt, jnp.int32(n), jnp.int32(1),
            gen.init_kv_cache(CFG, slots, 32), CFG, taps=taps)

    logits, cache = prefill(False)
    logits_t, cache_t, tap = prefill(True)
    assert jnp.array_equal(logits, logits_t)
    assert all(map(jnp.array_equal, jax.tree.leaves(cache),
                   jax.tree.leaves(cache_t)))
    assert {k: v.shape[0] for k, v in tap.items() if k != "out"} == {
        "ssm": CFG.n_ssm_layers, "attn": CFG.n_attn_layers,
        "moe": CFG.n_expert_layers}
    lanes = jnp.zeros(slots, jnp.int32)
    tok, pos = lanes.at[1].set(jnp.argmax(logits)), lanes.at[1].set(n)

    def block(cache, taps):
        return gen.decode_block(params, cache, tok, pos, lanes.astype(F32),
                                lanes, lanes + 1, CFG, steps, taps=taps)

    toks, *_rest = block(cache, False)
    toks_t, *_rest, step_tap = block(cache_t, True)
    assert jnp.array_equal(toks, toks_t) and len(_rest) == 5
    # lane 1's sequence of inputs, a layer: the prompt's, then the steps'
    seq = {k: jnp.concatenate(
        [tap[k][..., 0, :n, :], jnp.moveaxis(step_tap[k][..., 1, 0, :], 0, -2)],
        -2) for k in tap}
    kinds = {"layers": "attn", "ssm_layers": "ssm", "expert_layers": "moe"}
    order = ref.layers_in_order(params, hp)
    ins = [seq[kinds[name]][i] for name, i in order]
    assert jnp.array_equal(ins[0][:n], params["embed"][prompt[0, :n]])
    with jax.default_matmul_precision("highest"):
        for (name, i), x_in, x_out in zip(order, ins, ins[1:] + [seq["out"]]):
            lp = jax.tree.map(lambda a: a[i], params[name])
            want = ref.layer(x_in, lp, hp, {})[0]
            np.testing.assert_allclose(x_out, want, rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(
            ref.head(params, seq["out"][n - 1], hp), logits, rtol=2e-3,
            atol=2e-4)
        assert jnp.array_equal(
            jnp.argmax(ref.head(params, seq["out"][n:], hp), -1), toks[1])
