"""The latent-attention block with a learned selection of cache rows and a
held share of the routed experts (GLM-5.2's) against its plain reference,
at test size on the CPU with seeded random weights, what is this block's
own: the engine's counters of its rows, the unselected path below
``index_topk``, who chooses and who shares, the self position as a
candidate, the exact selection, and the shares of a routed layer adding
up.

What it shares with the other served models
(the parameter tree, the uncached forward, the two programs through a
slot, ``generate``, the ablations, the reference's independence, the cell's
listing and rehearsal) is ``tests/test_served_models.py``'s."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmarks import reference_mla_moe as ref_mla
from benchmarks import reference_dsa_moe as ref
from ray_tpu.models import generation as gen
from ray_tpu.models.transformer import (
    TransformerConfig,
    init_params,
)
from ray_tpu.ops.attention import (
    blocked_causal_attention,
    prefill_block_pairs,
    prefill_blocks,
    repeat_kv,
)
from ray_tpu.ops.moe import routed_ffn

# six layers, full | shared shared shared full shared; 16 rows a query; the
# stack holds experts 2..5 of 8
CFG = TransformerConfig.tiny_dsa_moe(
    dtype=jnp.float32, moe_experts_held=4, moe_first_expert=2)
K = CFG.index_topk
TOL = 1e-4  # float32 against float32: rounding order only


def hp_of(cfg):
    return {"n_heads": cfg.n_heads, "qk_nope": cfg.qk_nope_dim,
            "qk_rope": cfg.qk_rope_dim, "kv_rank": cfg.kv_lora_rank,
            "top_k": cfg.moe_top_k, "route_scale": cfg.moe_route_scale,
            "eps": cfg.norm_eps, "theta": cfg.rope_theta,
            "index_topk": cfg.index_topk,
            "indexer_types": cfg.indexer_types,
            "first_expert": cfg.moe_first_expert}


HP = hp_of(CFG)


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


def prefill(params, cfg, cache, slot, p, bucket=256):
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(p)] = p
    return gen.prefill_into_slot(
        params, jnp.asarray(padded), jnp.int32(len(p)), jnp.int32(slot),
        cache, cfg)


# -- the description of the block ------------------------------------------

def test_config_follows_the_published_numbers():
    full = TransformerConfig.glm52()
    assert (full.n_layers, full.n_dense_layers, full.n_index_layers) == (
        78, 3, 21)
    assert full.indexer_types[:8] == ("full",) * 3 + ("shared",) * 3 + (
        "full", "shared")
    # ISSUE 32's arithmetic: 743 B for the whole model; the chip's cut (the
    # dense layer + 5 expert layers, 16 of 256 experts, 1/8 vocabulary) 4.69 B
    assert round(full.param_count() / 1e9) == 743
    cut = TransformerConfig.glm52(
        6, n_dense_layers=1, moe_experts_held=16, vocab_size=19360,
        indexer_types=("full",) + ("shared",) * 3 + ("full", "shared"))
    assert round(cut.param_count() / 1e9, 2) == 4.69
    one_indexer = (cut.param_count() - dataclasses.replace(
        cut, indexer_types=("full",) + ("shared",) * 5).param_count())
    assert round(one_indexer / 1e6, 2) == 9.37
    cache = jax.eval_shape(lambda: gen.init_kv_cache(cut, 12, 25600))
    # a latent row padded to whole lanes, and an index key a layer that
    # owns an indexer
    assert {k: v.shape for k, v in cache.items()} == {
        "ckr": (6, 12, 25600, 640), "ik": (2, 12, 25600, 128)}
    with pytest.raises(ValueError):
        TransformerConfig.tiny_dsa_moe(indexer_types=("shared",) * 6)


# -- the engine against the full forward ----------------------------------

def test_engine_serves_the_reference_tokens_and_counts_its_rows(params):
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(params, CFG, max_slots=4, max_len=320,
                    prefill_buckets=(64, 256), block_steps=4,
                    burst_block_steps=2)
    try:
        prompts = [prompt(20, 250), prompt(21, 12), prompt(22, 130)]
        reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        for p, r in zip(prompts, reqs):
            ids = []
            while (item := r.out.get(timeout=120)) is not None and (
                    isinstance(item, int)):
                ids.append(item)
            assert len(ids) == 10
            logits = ref_logits(params, list(p) + ids[:-1])[len(p) - 1:]
            margin = ref.served_token_margin(
                logits, jnp.asarray(ids, jnp.int32))
            assert float(margin.max()) < TOL
        s = eng.stats()
    finally:
        eng.shutdown()
    # rows attended never exceed the live rows nor index_topk a lane a layer
    assert 0 < s["dsa_rows_selected"] < s["dsa_rows_live"]
    assert s["dsa_rows_selected"] <= K * CFG.n_layers * s["slot_steps"]
    assert s["dsa_rows_scored"] > 0
    # a share's capacity is the experts HELD: 4 an expert layer a step
    assert s["moe_experts_capacity"] == 4 * 5 * s["steps"]
    assert s["moe_experts_touched"] <= s["moe_experts_capacity"]
    assert s["attn_rows_read"] > 0


def test_a_context_within_index_topk_equals_the_unselected_path(params):
    """At most index_topk rows: every row is chosen, and the block gives
    what the same weights give with no indexer at all (the latent path of
    the model without selection), in prefill and in decode."""
    plain = dataclasses.replace(CFG, index_topk=0, indexer_types=())
    bare = jax.tree.map(lambda x: x, params)
    for group in ("dense_layers", "layers"):
        bare[group] = {**bare[group], "attn": {
            k: v for k, v in bare[group]["attn"].items() if k != "indexer"}}
    p = prompt(40, K - 6)
    got, cache = prefill(params, CFG, gen.init_kv_cache(CFG, 2, 64), 1, p, 64)
    want, plain_cache = prefill(
        bare, plain, gen.init_kv_cache(plain, 2, 64), 1, p, 64)
    assert ref_mla.vector_distance(got, want)[1] < TOL
    tok = jnp.asarray([0, int(jnp.argmax(got))], jnp.int32)
    pos = np.array([0, len(p)], np.int32)
    for _ in range(5):  # up to index_topk rows: the two paths agree
        got, cache = gen.decode_step_multi(
            params, tok, cache, jnp.asarray(pos), CFG)
        want, plain_cache = gen.decode_step_multi(
            bare, tok, plain_cache, jnp.asarray(pos), plain)
        assert ref_mla.vector_distance(got[1], want[1])[1] < TOL
        tok = tok.at[1].set(jnp.argmax(want[1]).astype(jnp.int32))
        pos[1] += 1
    for _ in range(8):  # beyond it they part
        got, cache = gen.decode_step_multi(
            params, tok, cache, jnp.asarray(pos), CFG)
        want, plain_cache = gen.decode_step_multi(
            bare, tok, plain_cache, jnp.asarray(pos), plain)
        pos[1] += 1
    assert ref_mla.vector_distance(got[1], want[1])[1] > 100 * TOL


# -- who chooses, who shares, and what is a candidate ----------------------

def _masks(params, tokens, ablate=None):
    """The reference's mask of every layer over one sequence."""
    ablate = ablate or {}
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        masks, mask = [], None
        for lp, ip, _kind in ref.layers_of(params, HP):
            x, mask = ref.block(x, lp, ip, HP, ablate, mask)
            masks.append(np.asarray(mask))
    return masks


def test_shared_layers_attend_their_full_layers_choice(params):
    masks = _masks(params, prompt(50, 90))
    for layer in (1, 2, 3):
        assert (masks[layer] == masks[0]).all()
    assert (masks[5] == masks[4]).all()
    assert (masks[4] != masks[0]).any()  # two full layers choose differently
    rows = masks[0].sum(-1)
    assert (rows == np.minimum(np.arange(90) + 1, K)).all()
    # ... and the program follows: a shared layer that chose afresh, or the
    # second full layer reusing the first's choice, would miss the logits
    # (test_each_ablation_fails_the_comparison)


def test_the_self_position_is_dropped_when_it_scores_low():
    """The token's own row is a candidate like any other: with index
    weights that make it score lowest it is not attended, in the decode
    step (where its cache row is not written yet) as in the reference."""
    B, s_max, n_i, d_i, k = 2, 64, 2, 8, 4
    key = jax.random.key(3)
    ik = jax.random.normal(key, (1, B, s_max, d_i))
    q = jax.random.normal(jax.random.fold_in(key, 1), (B, n_i, d_i))
    w = jnp.ones((B, n_i))
    pos = jnp.asarray([40, 20], jnp.int32)
    low = -q.sum(1)  # relu(q_j . k) = 0 for both heads: the lowest score
    high = q.sum(1)
    for k_new, chosen in ((low, False), (high, True)):
        mask = np.asarray(gen._decode_choice(
            q, w, ik, jnp.int32(0), k_new, pos, k))
        assert (mask.sum(-1) == k).all()
        assert [bool(mask[b, pos[b]]) for b in range(B)] == [chosen] * B
        assert not mask[0, 41:].any() and not mask[1, 21:].any()


def test_selection_is_top_ks_set_with_ties_to_the_lower_row():
    scores = jax.random.normal(jax.random.key(0), (3, 500))
    scores = scores.at[:, 100:140].set(0.5)  # forty rows tie
    pos = jnp.array([400, 20, 0])
    valid = jnp.arange(500)[None] <= pos[:, None]
    best, rows = lax.top_k(jnp.where(valid, scores, -jnp.inf), 64)
    want = np.zeros((3, 500), bool)
    for b in range(3):
        want[b, np.asarray(rows[b])[np.asarray(best[b]) > -np.inf]] = True
    got = np.asarray(gen.select_rows(scores, valid, 64))
    assert (got == want).all()
    assert got.sum(-1).tolist() == [64, 21, 1]
    assert (np.asarray(ref.chosen_rows(
        jnp.where(valid, scores, -jnp.inf), 64)) == want).all()


# sequence, query heads, KV heads, key width, value width, block, length,
# whether the late queries choose nothing in the first blocks of rows
MASKED = {
    "block_32": (96, 3, 3, 8, 8, 32, None, False),
    "block_96": (96, 3, 3, 8, 8, 96, None, False),
    "block_1024": (96, 3, 3, 8, 8, 1024, None, False),
    "length_in_a_block": (96, 3, 3, 8, 8, 32, 70, False),
    "no_chosen_row_in_a_block": (96, 3, 3, 8, 8, 32, None, True),
    "no_chosen_row_and_a_length": (128, 2, 2, 8, 8, 32, 97, True),
    "narrow_values": (96, 3, 3, 16, 8, 32, 50, False),
    "grouped_heads": (96, 4, 2, 16, 8, 32, 81, True),
    "queries_block_halved": (64, 128, 2, 8, 8, 64, 40, True),
}


@pytest.mark.parametrize("case", sorted(MASKED))
def test_masked_prefill_attention_matches_a_dense_softmax(case):
    """The prefill kernel under a choice's mask (interpreter) against
    every score materialised: blocks that divide the sequence, that are
    it, that are larger; a prompt that ends inside a block (the rows past
    it zeros, the blocks of queries past it never read: they hold NaN);
    queries whose first chosen row lies past whole blocks of rows (their
    running maximum is still NEG_INF there, and exp(NEG_INF - NEG_INF) is
    1); values narrower than keys; heads that share a KV head, and so
    many of them that a block of queries is half a block of rows."""
    S, H, G, D, Dv, block, length, hole = MASKED[case]
    key = jax.random.key(5)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (1, S, h, d))
               for i, (h, d) in enumerate(((H, D), (G, D), (G, Dv))))
    mask = jnp.tril(jax.random.uniform(key, (S, S)) < 0.3) | jnp.eye(
        S, dtype=bool)
    if hole:  # the last third's queries choose nothing in the first half
        mask = mask.at[2 * S // 3:, :S // 2].set(False)
    n = S if length is None else length
    bq, bk = prefill_blocks(S, H // G, block)
    assert (H // G * bq <= 2048) and (bq < bk) == (case.startswith("queries"))
    past = -(-n // bq) * bq  # the first query of the blocks never read
    q = q.at[:, past:].set(jnp.nan)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, repeat_kv(k, H // G)) * D ** -0.5
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(
        jnp.where(mask[None, None], scores, -jnp.inf), -1),
        repeat_kv(v, H // G))
    with jax.default_matmul_precision("highest"):
        got = blocked_causal_attention(q, k, v, length, mask=mask,
                                       block=block)
        same = blocked_causal_attention(
            q, k, v, length, mask=mask.astype(jnp.int8)[None], block=block)
    assert got.shape == (1, S, H, Dv)
    assert float(jnp.abs(got - want)[:, :n].max()) < 1e-5
    assert not np.asarray(got[:, n:]).any()  # zeros, and no NaN
    assert (np.asarray(got) == np.asarray(same)).all()
    live = -(-n // bq)
    assert int(prefill_block_pairs(S, n, H // G, block)) == sum(
        i * bq // bk + 1 for i in range(live))


def test_the_prefill_kernel_takes_a_choice_a_sequence():
    """A mask [B, S, S] and a length a sequence: each sequence under its
    own, as two calls of one sequence would."""
    S = 64
    key = jax.random.key(6)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (2, S, 2, 8))
               for i in range(3))
    mask = jnp.tril(jax.random.uniform(key, (2, S, S)) < 0.4) | jnp.eye(
        S, dtype=bool)
    length = jnp.array([64, 19], jnp.int32)
    got = blocked_causal_attention(q, k, v, length, mask=mask, block=16)
    for b in range(2):
        one = blocked_causal_attention(
            q[b:b + 1], k[b:b + 1], v[b:b + 1], length[b], mask=mask[b],
            block=16)
        assert (np.asarray(got[b]) == np.asarray(one[0])).all()
    assert np.asarray(got[1, :19]).any()
    assert not np.asarray(got[1, 19:]).any()


def test_an_admission_skips_and_counts_the_blocks_of_its_padding():
    """A prompt of 700 in a bucket of 2,048 (two blocks of 1,024): the
    admission's logits are those of the 1,024 bucket, and its counters say
    one of the bucket's three (queries, rows) blocks was computed, a layer."""
    params = init_params(CFG, jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (700,), 0, CFG.vocab_size)
    got = {}
    for bucket in (1024, 2048):
        padded = jnp.zeros((1, bucket), jnp.int32).at[0, :700].set(prompt)
        args = (params, padded, np.int32(700), np.int32(0))
        got[bucket] = gen.prefill_into_slot(
            *args, gen.init_kv_cache(CFG, 2, 2304), CFG)[0]
        lanes = tuple(jnp.zeros((2,), t) for t in (  # donated
            jnp.int32, jnp.int32, jnp.float32, jnp.int32, jnp.int32))
        stats = gen.prefill_into_slot(
            *args, gen.init_kv_cache(CFG, 2, 2304), CFG, lanes,
            np.float32(0), np.int32(0))[3]
        assert int(stats["prefill_attn_blocks"]) == CFG.n_layers
        assert int(stats["prefill_attn_blocks_bucket"]) == CFG.n_layers * (
            1 if bucket == 1024 else 3)
    assert float(jnp.abs(got[1024] - got[2048]).max()) < TOL


# -- a chip's share of a layer's experts -----------------------------------

def _routed_weights(key, d, E, f):
    ks = jax.random.split(key, 8)
    return {
        "router": jax.random.normal(ks[0], (d, E)) * d ** -0.5,
        "bias": 0.05 * jax.random.normal(ks[1], (E,)),
        "wg": jax.random.normal(ks[2], (E, d, f)) * d ** -0.5,
        "wi": jax.random.normal(ks[3], (E, d, f)) * d ** -0.5,
        "wo": jax.random.normal(ks[4], (E, f, d)) * f ** -0.5,
        "shared": {"wg": jax.random.normal(ks[5], (d, f)) * d ** -0.5,
                   "wi": jax.random.normal(ks[6], (d, f)) * d ** -0.5,
                   "wo": jax.random.normal(ks[7], (f, d)) * f ** -0.5}}


def test_the_shares_of_a_routed_layer_add_up_to_the_whole_layer():
    """Four shares of 2 of 8 experts, each run as the chip that holds it
    would, summed with the shared expert counted once, equal the uncut
    reference layer; each share alone equals the reference's share."""
    d, E, f, n, top_k, scale = 32, 8, 16, 40, 3, 2.5
    wp = _routed_weights(jax.random.key(7), d, E, f)
    x = jax.random.normal(jax.random.key(8), (n, d))
    hp = {"top_k": top_k, "route_scale": scale}
    with jax.default_matmul_precision("highest"):
        whole = ref_mla.routed_experts(x, wp, hp, {})
        shared = ref_mla.gated_ffn(x, *(wp["shared"][k]
                                        for k in ("wg", "wi", "wo")))
    total = jnp.zeros_like(x)
    for first in range(0, E, 2):
        share = {**wp, **{k: wp[k][first:first + 2]
                          for k in ("wg", "wi", "wo")}}
        got, stats = routed_ffn(x, share, top_k=top_k, route_scale=scale,
                                first_expert=first)
        assert int(stats["moe_experts_capacity"]) == 2
        with jax.default_matmul_precision("highest"):
            want = ref.routed_experts(x, share, {**hp, "first_expert": first},
                                      {})
        assert float(jnp.abs(got - want).max()) < TOL
        total = total + got - shared  # the shared expert: once, below
    assert float(jnp.abs(total + shared - whole).max()) < TOL


def test_a_long_prompt_is_routed_in_passes_of_the_same_result(monkeypatch):
    from ray_tpu.ops import moe

    d, E, f, n = 32, 8, 16, 64
    wp = _routed_weights(jax.random.key(9), d, E, f)
    wp = {**wp, **{k: wp[k][2:6] for k in ("wg", "wi", "wo")}}
    x = jax.random.normal(jax.random.key(10), (n, d))
    mask = jnp.arange(n) < 50
    kw = dict(top_k=2, route_scale=2.5, token_mask=mask, first_expert=2)
    want, s1 = routed_ffn(x, wp, **kw)
    monkeypatch.setattr(moe, "ROUTED_TOKENS_A_PASS", 16)
    got, s4 = routed_ffn(x, wp, **kw)
    assert float(jnp.abs(got - want).max()) < 1e-6
    assert int(s4["moe_assignments"]) == int(s1["moe_assignments"])
    assert int(s4["moe_experts_capacity"]) == 4
