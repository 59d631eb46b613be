"""The latent-attention / routed-experts block (GLM-4.7-Flash's) against
its plain reference, at test size on the CPU with seeded random weights:
what is this block's own: the engine serving the reference's tokens, the
absorbed decode attention, the dropless routed layer, a bf16 router told
by the layer alone, and the engine's counters.

What it shares with the other served models
(the parameter tree, the uncached forward, the two programs through a
slot, ``generate``, the ablations, the reference's independence, the cell's
listing and rehearsal) is ``tests/test_served_models.py``'s."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_mla_moe as ref
from ray_tpu.models import generation as gen
from ray_tpu.models.transformer import (
    TransformerConfig,
    init_params,
)
from ray_tpu.ops.moe import routed_ffn

CFG = TransformerConfig.tiny_mla_moe(dtype=jnp.float32)
HP = {"n_heads": CFG.n_heads, "qk_nope": CFG.qk_nope_dim,
      "qk_rope": CFG.qk_rope_dim, "kv_rank": CFG.kv_lora_rank,
      "top_k": CFG.moe_top_k, "route_scale": CFG.moe_route_scale,
      "eps": CFG.norm_eps, "theta": CFG.rope_theta}
TOL = 1e-4  # float32 against float32: rounding order only


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.key(0))


def ref_logits(params, tokens, **kw):
    with jax.default_matmul_precision("highest"):
        return ref.forward_logits(params, jnp.asarray(tokens, jnp.int32),
                                  HP, **kw)


def prompt(seed, n):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, n, dtype=np.int32)


# -- the description of the block ------------------------------------------

def test_config_follows_the_published_numbers():
    full = TransformerConfig.glm47_flash()
    cut = TransformerConfig.glm47_flash(8)
    assert (full.n_layers, full.n_dense_layers, full.n_expert_layers) == (
        47, 1, 46)
    # ISSUE 28's arithmetic: 21.76 M attention, 9.44 M an expert, 635.3 M an
    # expert layer, 84.7 M the dense layer, 5.166 B for the cut
    per_expert_layer = (cut.param_count()
                        - TransformerConfig.glm47_flash(7).param_count())
    assert round(per_expert_layer / 1e6, 1) == 635.3
    assert round(cut.param_count() / 1e9, 3) == 5.166
    cache = jax.eval_shape(lambda: gen.init_kv_cache(cut, 32, 4096))
    # 576 numbers a token a layer: the latent, and the shared rotary key
    assert {k: v.shape for k, v in cache.items()} == {
        "ckv": (8, 32, 4096, 512), "kr": (8, 32, 4096, 64)}


def test_gptj_block_keeps_its_parameters_and_cache():
    c = TransformerConfig.tiny()
    p = init_params(c, jax.random.key(0))
    assert set(p["layers"]) == {"ln1", "attn", "mlp"}
    assert set(p["layers"]["mlp"]) == {"wi", "wo"}
    assert "dense_layers" not in p
    assert set(gen.init_kv_cache(c, 2, 16)) == {"k", "v"}
    assert gen.block_stat_keys(c) == ()


# -- (a) the engine against the full forward ------------------------------

def test_engine_serves_the_reference_tokens(params):
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(params, CFG, max_slots=4, max_len=320,
                    prefill_buckets=(64, 256), block_steps=4,
                    burst_block_steps=2)
    try:
        prompts = [prompt(20, 250), prompt(21, 40), prompt(22, 130)]
        reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        for p, r in zip(prompts, reqs):
            ids = []
            while (item := r.out.get(timeout=120)) is not None and (
                    isinstance(item, int)):
                ids.append(item)
            assert len(ids) == 12
            logits = ref_logits(params, list(p) + ids[:-1])[len(p) - 1:]
            margin = ref.served_token_margin(
                logits, jnp.asarray(ids, jnp.int32))
            assert float(margin.max()) < TOL
    finally:
        eng.shutdown()


# -- (b) absorbed decode attention against the plain form ------------------

def test_absorbed_decode_attention_matches_plain_form():
    c, B, s_max = CFG, 3, 300
    r, rope, H = c.kv_lora_rank, c.qk_rope_dim, c.n_heads
    ks = jax.random.split(jax.random.key(3), 8)
    cache = {"ckv": jax.random.normal(ks[0], (2, B, s_max, r)),
             "kr": jax.random.normal(ks[7], (2, B, s_max, rope))}
    wp = {"wuk": jax.random.normal(ks[1], (r, H, c.qk_nope_dim)),
          "wuv": jax.random.normal(ks[2], (r, H, c.v_head_dim))}
    q_nope = jax.random.normal(ks[3], (B, 1, H, c.qk_nope_dim))
    q_rope = jax.random.normal(ks[4], (B, 1, H, rope))
    c_kv = jax.random.normal(ks[5], (B, 1, r))
    k_r = jax.random.normal(ks[6], (B, 1, 1, rope))
    pos = jnp.asarray([290, 0, 17], jnp.int32)  # lane 1 is parked
    schedule = gen._visits(pos, (cache["ckv"], cache["kr"]))
    out, new = gen._decode_attn(cache, 1, pos, jnp.arange(B), c, schedule)(
        q_nope, q_rope, c_kv, k_r, wp)
    row = jnp.concatenate([c_kv, k_r[:, :, 0]], -1)[:, 0]
    kv = jnp.concatenate([cache["ckv"], cache["kr"]], -1)
    for b in range(B):
        n = int(pos[b])
        rows = jnp.concatenate([kv[1, b, :n], row[b][None]])
        k_nope = jnp.einsum("tc,chk->thk", rows[:, :r], wp["wuk"])
        v = jnp.einsum("tc,chk->thk", rows[:, :r], wp["wuv"])
        s = (jnp.einsum("hk,thk->ht", q_nope[b, 0], k_nope)
             + jnp.einsum("hk,tk->ht", q_rope[b, 0], rows[:, r:]))
        p = jax.nn.softmax(s * (c.qk_nope_dim + rope) ** -0.5, -1)
        want = jnp.einsum("ht,thk->hk", p, v)
        np.testing.assert_allclose(out[b, 0], want, rtol=2e-4, atol=2e-4)
        if n == 0:  # a parked lane keeps its rows: its write is dropped
            np.testing.assert_array_equal(new["ckv"][1, b], cache["ckv"][1, b])
            np.testing.assert_array_equal(new["kr"][1, b], cache["kr"][1, b])
            continue
        np.testing.assert_array_equal(new["ckv"][1, b, n], row[b][:r])
        np.testing.assert_array_equal(new["kr"][1, b, n], row[b][r:])


# -- (c) the routed layer against the reference's loop over experts --------

def _routed_weights(key, d, E, f, shared=True):
    ks = jax.random.split(key, 8)
    wp = {"router": jax.random.normal(ks[0], (d, E)) * d ** -0.5,
          "bias": 0.1 * jax.random.normal(ks[1], (E,)),
          "wg": jax.random.normal(ks[2], (E, d, f)) * d ** -0.5,
          "wi": jax.random.normal(ks[3], (E, d, f)) * d ** -0.5,
          "wo": jax.random.normal(ks[4], (E, f, d)) * f ** -0.5}
    if shared:
        wp["shared"] = {
            "wg": jax.random.normal(ks[5], (d, f)) * d ** -0.5,
            "wi": jax.random.normal(ks[6], (d, f)) * d ** -0.5,
            "wo": jax.random.normal(ks[7], (f, d)) * f ** -0.5}
    return wp


def test_routed_layer_matches_loop_over_experts():
    wp = _routed_weights(jax.random.key(5), 32, 8, 24)
    x = jax.random.normal(jax.random.key(6), (2, 37, 32))
    y, stats = routed_ffn(x, wp, top_k=4, route_scale=1.8)
    with jax.default_matmul_precision("highest"):
        want = ref.routed_experts(x.reshape(-1, 32), wp, HP, {})
    np.testing.assert_allclose(y.reshape(-1, 32), want, rtol=1e-4, atol=1e-4)
    assert int(stats["moe_assignments"]) == 2 * 37 * 4
    assert int(stats["moe_experts_capacity"]) == 8
    # masked tokens go to no expert: only the shared expert answers
    mask = jnp.arange(37)[None] < jnp.asarray([[37], [5]])
    y2, stats2 = routed_ffn(x, wp, top_k=4, route_scale=1.8, token_mask=mask)
    np.testing.assert_allclose(y2[1, :5], y[1, :5], rtol=1e-5, atol=1e-5)
    only_shared = ref.gated_ffn(x[1, 5:], wp["shared"]["wg"],
                                wp["shared"]["wi"], wp["shared"]["wo"])
    np.testing.assert_allclose(y2[1, 5:], only_shared, rtol=1e-4, atol=1e-4)
    assert int(stats2["moe_assignments"]) == (37 + 5) * 4


def test_selection_by_biased_score_weights_by_score():
    """Router = identity on the first E dims, so x IS the router logits.
    Token 0: 4th and 5th scores set 0.01 apart by hand. Token 1: the bias
    puts expert 7 (lowest score) in place of expert 3, and the weights are
    still the scores' shares."""
    E, d = 8, 8
    wp = _routed_weights(jax.random.key(7), d, E, 16, shared=False)
    wp["router"] = jnp.eye(d)
    bias = np.zeros(E, np.float32)
    bias[7] = 1.0
    logits = np.array([[3.0, 2.0, 1.0, 0.5, 0.49, -1, -2, -3],
                       [3.0, 2.0, 1.0, 0.5, 0.0, -1, -2, -3]], np.float32)
    for b, tok, chosen in ((np.zeros(E, np.float32), 0, [0, 1, 2, 3]),
                           (bias, 1, [0, 1, 2, 7])):
        wp["bias"] = jnp.asarray(b)
        y, _ = routed_ffn(jnp.asarray(logits), wp, top_k=4, route_scale=1.8)
        s = jax.nn.sigmoid(logits[tok])
        w = s[jnp.asarray(chosen)] / s[jnp.asarray(chosen)].sum() * 1.8
        x = jnp.asarray(logits[tok])[None]
        want = sum(w[i] * ref.gated_ffn(x, wp["wg"][e], wp["wi"][e],
                                        wp["wo"][e])[0]
                   for i, e in enumerate(chosen))
        np.testing.assert_allclose(y[tok], want, rtol=1e-4, atol=1e-5)
        with jax.default_matmul_precision("highest"):
            np.testing.assert_allclose(
                y, ref.routed_experts(jnp.asarray(logits), wp, HP, {}),
                rtol=1e-4, atol=1e-5)


def test_no_token_dropped_when_all_pick_one_expert():
    wp = _routed_weights(jax.random.key(8), 16, 8, 12)
    x = jnp.tile(jax.random.normal(jax.random.key(9), (1, 16)), (64, 1))
    x = x * (1 + 1e-3 * jnp.arange(64)[:, None])  # same choice, not same x
    y, stats = routed_ffn(x, wp, top_k=4, route_scale=1.8)
    with jax.default_matmul_precision("highest"):
        want = ref.routed_experts(x, wp, HP, {})
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)
    assert int(stats["moe_max_load"]) == 64  # GShard's capacity: 64*4/8*2
    assert int(stats["moe_experts_touched"]) == 4
    assert int(stats["moe_assignments"]) == 256


# -- (d) what the comparison must refuse ------------------------------------

def test_a_bf16_router_fails_the_routed_layer_alone():
    """Router logits rounded to bf16 flip a near-tie for a few tokens in a
    hundred and move whole logit vectors too little to be told from other
    rounding: the layer alone, on the same input, tells (the benchmark's
    ``routed_layer`` check). The share of tokens whose output is off by
    over 5 % is 0 for the program and not for it (0.3 % at this size, E = 16)."""
    wp = _routed_weights(jax.random.key(11), 64, 16, 32)
    wp = jax.tree.map(lambda a: a.astype(jnp.bfloat16), wp)
    x = jax.random.normal(jax.random.key(12), (4096, 64)).astype(
        jnp.bfloat16)
    got, _ = routed_ffn(x, wp, top_k=4, route_scale=1.8)

    def share_off(ablate):
        with jax.default_matmul_precision("highest"):
            want = ref.routed_experts(x.astype(jnp.float32), wp, HP, ablate)
        err = jnp.linalg.norm(got.astype(jnp.float32) - want, axis=-1) / (
            jnp.linalg.norm(want, axis=-1))
        return float((err > 0.05).mean())

    assert share_off({}) == 0.0
    assert share_off({"router_bf16": True}) > 0.002


# -- (e) the engine's counters ---------------------------------------------

def test_block_counters_add_up_on_a_scripted_run(params):
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(params, CFG, max_slots=4, max_len=128,
                    prefill_buckets=(64,), block_steps=4,
                    burst_block_steps=2)
    try:
        unparked = []  # lanes off row 0 x steps, per dispatched block
        dispatch = eng._dispatch_block

        def counted():
            rows = sum(r > 0 for r in eng._rows)
            before = eng._steps
            out = dispatch()
            unparked.append(rows * (eng._steps - before))
            return out

        eng._dispatch_block = counted
        reqs = [eng.submit(prompt(30 + i, 20 + i), max_new_tokens=9)
                for i in range(3)]
        for r in reqs:
            while isinstance(r.out.get(timeout=120), int):
                pass
        layers = CFG.n_expert_layers
        deadline = time.monotonic() + 60
        while True:  # until the block still in flight has been retired
            s = eng.stats()
            if s["moe_experts_capacity"] == (
                    CFG.moe_experts * layers * s["steps"]):
                break
            assert time.monotonic() < deadline, s
            time.sleep(0.01)
    finally:
        eng.shutdown()
    steps = s["steps"]
    assert s["moe_assignments"] == sum(unparked) * CFG.moe_top_k * layers
    assert s["moe_experts_capacity"] == CFG.moe_experts * layers * steps
    assert layers * steps <= s["moe_experts_touched"] <= min(
        s["moe_assignments"], s["moe_experts_capacity"])
    assert s["moe_max_load"] * CFG.moe_experts >= s["moe_assignments"]
    assert s["attn_rows_read"] > 0
