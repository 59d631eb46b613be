"""EvaByte's kind of model (``TransformerConfig.layer_types`` all "eva":
every layer attends its own window of rows exactly and every earlier
window through pooled chunk summaries; no layer keeps every row) against
the plain reference, on the CPU at test size: windows of 32 tokens in
chunks of 4, so that three windows close within a hundred tokens.

The uncached forward, the two serving programs (a prefill at a padded
bucket, then decode steps across window closings, all prediction heads,
row by row), what a slot holds after a close, a parked lane, a model
without an "attn" layer, the published numbers, and the benchmark's own
arithmetic.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_eva as ref
from ray_tpu.models import generation as gen
from ray_tpu.models.transformer import (
    TransformerConfig,
    forward,
    init_params,
)
from ray_tpu.ops.eva import eva_attention, eva_pool

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = TransformerConfig.tiny_eva(dtype=jnp.float32)
W, C = CFG.eva_window, CFG.eva_chunk
PER = W // C  # summaries a closed window leaves
TOL = 2e-5  # relative RMS, float32 against float32: rounding order only
TOL_BF16 = 1e-2  # bf16 operands and result against float32: ~1.3 of its eps
HP = {"n_heads": CFG.n_heads, "d_head": CFG.d_head, "eps": CFG.norm_eps,
      "theta": CFG.rope_theta, "window": W, "chunk": C,
      "n_pred_heads": CFG.n_pred_heads}
ABLATIONS = ("pool_15_of_16", "swap_phi_mu", "open_summaries",
             "residual_bf16", "pool_unrotated", "pool_unscaled",
             "no_summaries", "fp8_weights")


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.key(0))


def tokens_of(n, seed=1):
    return jax.random.randint(jax.random.key(seed), (n,), 0, CFG.vocab_size)


def ref_logits(params, tokens, **kw):
    with jax.default_matmul_precision("highest"):
        return ref.forward_logits(params, tokens, HP, **kw)


def rel(got, want):
    return float(ref.relative_rms(got, want))


def prefill(params, cache, slot, prompt, bucket):
    padded = jnp.zeros((1, bucket), jnp.int32).at[0, :len(prompt)].set(prompt)
    with jax.default_matmul_precision("highest"):
        return gen.prefill_into_slot(
            params, padded, jnp.int32(len(prompt)), jnp.int32(slot), cache,
            CFG)


def step(params, cache, tok, pos):
    """One decode step of slot 1 of two (slot 0 parked)."""
    with jax.default_matmul_precision("highest"):
        logits, cache = gen.decode_step_multi(
            params, jnp.array([0, tok], jnp.int32), cache,
            jnp.array([0, pos], jnp.int32), CFG)
    return logits[1], cache


# -- the layer in its whole-sequence form ------------------------------------

@pytest.mark.parametrize("length", [19, 64, 110])
def test_the_uncached_forward_equals_the_reference(params, length):
    toks = tokens_of(length, length)
    got = forward(params, toks[None], CFG)[0]
    want, _ = ref_logits(params, toks)
    assert got.shape == (length, CFG.n_pred_heads, CFG.vocab_size)
    assert got.dtype == jnp.float32
    assert rel(got, want) < TOL


@pytest.mark.parametrize("switch", ABLATIONS)
def test_the_forward_is_none_of_the_wrong_models(params, switch):
    """Every way the reference can be computed wrong moves the logits of
    a sequence of three and a half windows by far more than rounding."""
    toks = tokens_of(110, 5)
    got = forward(params, toks[None], CFG)[0]
    wrong, _ = ref_logits(params, toks, ablate={switch: True})
    assert rel(got, wrong) > 50 * TOL


@pytest.mark.parametrize("S,length,block,dtype", [
    (3 * W, None, 1024, jnp.float32),  # whole windows, a block a window
    (3 * W, None, 8, jnp.float32),  # four blocks a window, one a window's
    (3 * W, None, 16, jnp.float32),  # summaries; two: window 1 sees half
    (W + W // 4, None, 8, jnp.float32),  # the 2,560 bucket's shape
    (W + W // 2, None, 16, jnp.float32),  # the 3,072 bucket's
    (3 * W, 20, 8, jnp.float32),  # inside window 0: no summary swept
    (3 * W, 2 * W, 16, jnp.float32),  # at a window's edge
    (3 * W, 2 * W + 13, 8, jnp.float32),  # mid-window in the last
    (W + W // 2, W + 5, 16, jnp.float32),  # in a bucket's half window
    (3 * W, None, 16, jnp.bfloat16),
    (3 * W, 2 * W + 13, 8, jnp.bfloat16)])
def test_the_pooling_and_the_blocked_attention_are_the_references(
        S, length, block, dtype):
    """``eva_pool`` and ``eva_attention`` (the prefill kernel over the
    windows, the summaries its prefix) against the reference's one softmax
    over [summaries | tokens], a head at a time: whole windows and a
    bucket's part of one, every block size's way through the summaries
    (none, whole blocks, the block the count falls in), and under a
    ``length`` the rows before it those of the call on the real tokens
    alone, the rows from it on zeros. bf16 operands are held to their own
    rounding against the float32 reference of the same operands."""
    tol = TOL if dtype == jnp.float32 else TOL_BF16
    k = jax.random.split(jax.random.key(3), 5)
    H, D = CFG.n_heads, CFG.d_head
    q, key, v = (jax.random.normal(k[i], (1, S, H, D), dtype)
                 for i in range(3))
    phi, mu = (jax.random.normal(k[i], (H, D)) * D ** -0.5 for i in (3, 4))
    whole = S // W * W  # the windows a bucket pools
    ks, vs = eva_pool(key[:, :whole], v[:, :whole], phi, mu, C)
    assert ks.shape == (1, whole // C, H, D) and ks.dtype == dtype
    out = eva_attention(q, key, v, ks, vs, length, window=W, chunk=C,
                        block=block)
    assert out.shape == q.shape and out.dtype == dtype
    n = S if length is None else length
    if length is not None:
        assert not out[:, n:].any()
        closed = n // W * PER
        alone = eva_attention(
            q[:, :n], key[:, :n], v[:, :n], ks[:, :closed], vs[:, :closed],
            window=W, chunk=C, block=block)
        assert rel(out[:, :n], alone) < tol
    f32 = [x.astype(jnp.float32) for x in (q, key, v, ks, vs)]
    with jax.default_matmul_precision("highest"):
        for h in range(H):
            want_k, want_v = ref.summaries(
                f32[1][0, :whole, h], f32[2][0, :whole, h], phi[h], mu[h],
                HP, {})
            assert rel(ks[0, :, h], want_k) < tol
            assert rel(vs[0, :, h], want_v) < tol
            # the attention alone: over the summaries it was handed
            assert rel(out[0, :n, h], ref.eva_head(
                *(x[0, :, h] for x in f32), HP, {})[:n]) < tol


@pytest.mark.parametrize("bucket,length,computed,every", [
    # four windows of two blocks of queries; a window's diagonal 1 + 2
    # blocks of rows, and its 128 w summaries one block (512 in all)
    (8192, 8192, 4 * 3 + 3 * 2, 4 * 2 * (1 + 2)),
    # 904 bytes into window 2: its first block of queries alone
    (8192, 5000, 2 * 3 + 2 + (1 + 1), 4 * 2 * (1 + 2)),
    # 1,536 summaries in two blocks of 1,024: windows 9-11 sweep both
    (24576, 24576, 12 * 3 + (8 + 3 * 2) * 2, 12 * 2 * (2 + 2)),
    # a window and a quarter: the part sees the one window's 128
    (2560, 2560, 3 + 1 + 1, 2 * 2 * (1 + 2)),
    (2560, 2048, 3, 2 * 2 * (1 + 2))])
def test_the_admissions_counters_are_the_kernels_blocks(
        bucket, length, computed, every):
    """EvaByte's geometry (windows of 2,048 in chunks of 16, blocks of
    1,024): what ``eva_block_pairs`` counts is what the kernel's grid
    computes and steps through, by hand."""
    from ray_tpu.ops.eva import eva_block_pairs

    got = eva_block_pairs(bucket, jnp.int32(length), window=2048, chunk=16)
    assert (int(got[0]), got[1]) == (computed, every)


def test_an_admission_reports_the_blocks_its_attention_computed(params):
    """A prompt of 40 in a bucket of 80 (two windows and a half: a block
    a window): the admission's counters say two of the three windows were
    attended, the second over the first's summaries, a layer."""
    lanes = tuple(jnp.zeros((2,), t) for t in (
        jnp.int32, jnp.int32, jnp.float32, jnp.int32, jnp.int32))
    padded = jnp.zeros((1, 80), jnp.int32).at[0, :40].set(tokens_of(40))
    stats = gen.prefill_into_slot(
        params, padded, jnp.int32(40), jnp.int32(1),
        gen.init_kv_cache(CFG, 2, 128), CFG, lanes, jnp.float32(0),
        jnp.int32(0))[3]
    assert tuple(stats) == gen.prefill_stat_keys(CFG) == (
        "prefill_attn_blocks", "prefill_attn_blocks_bucket")
    assert int(stats["prefill_attn_blocks"]) == CFG.n_layers * (1 + 2)
    assert int(stats["prefill_attn_blocks_bucket"]) == CFG.n_layers * 3 * 2


def test_a_bf16_model_keeps_its_residual_and_its_logits_in_float32():
    c = TransformerConfig.tiny_eva()
    shapes = jax.eval_shape(lambda: init_params(c, jax.random.key(0)))
    out = jax.eval_shape(lambda p: forward(p, jnp.zeros((1, 8), jnp.int32),
                                           c), shapes)
    assert out.dtype == jnp.float32 and c.dtype == jnp.bfloat16
    cache = jax.eval_shape(lambda: gen.init_kv_cache(c, 2, 64))
    assert {a.dtype for a in jax.tree.leaves(cache)} == {
        jnp.dtype(jnp.bfloat16)}


# -- the two programs ----------------------------------------------------------

def test_decode_across_two_closings_matches_the_reference_row_by_row(params):
    """A prompt longer than a window in a padded bucket, then steps
    through the closing of the second and of the third window: every
    step's logits, all prediction heads, against the reference's one
    forward; then the slot's first rows against the reference's
    summaries."""
    n, total = 37, 110
    toks = tokens_of(total, 6)
    want, pooled = ref_logits(params, toks)
    cache = gen.init_kv_cache(CFG, 2, 128)
    logits, cache = prefill(params, cache, 1, toks[:n], 48)
    assert logits.shape == (CFG.n_pred_heads, CFG.vocab_size)
    assert rel(logits, want[n - 1]) < TOL
    for t in range(n, total):
        logits, cache = step(params, cache, toks[t], t)
        assert rel(logits, want[t]) < TOL, t
    closed = total // W * PER  # three windows' summaries
    for li, (ks, vs) in enumerate(pooled):
        assert rel(cache["ek"][li, 1, :closed], ks[:closed]) < TOL
        assert rel(cache["ev"][li, 1, :closed], vs[:closed]) < TOL


@pytest.mark.parametrize("length", [W - 1, W, W + 1, 2 * W - 1, 2 * W,
                                    2 * W + 1])
def test_a_prompt_that_ends_at_a_windows_edge(params, length):
    """A prompt a token short of a whole number of windows (its first
    decoded token fills and closes the window), one that is whole (the
    prefill folds its last window) and one a token over: the prefill's
    logits, the rows the slot is handed, and four decode steps."""
    toks = tokens_of(length + 4, 40 + length)
    want, pooled = ref_logits(params, toks)
    cache = gen.init_kv_cache(CFG, 2, 128)
    logits, cache = prefill(params, cache, 1, toks[:length], 80)
    assert rel(logits, want[length - 1]) < TOL
    rows = gen.eva_read_len(CFG, length)
    assert rows == length // W * PER + length % W
    closed = length // W * PER
    for li, (ks, _vs) in enumerate(pooled):
        if closed:
            assert rel(cache["ek"][li, 1, :closed], ks[:closed]) < TOL
    for t in range(length, length + 4):
        logits, cache = step(params, cache, toks[t], t)
        assert rel(logits, want[t]) < TOL, t


def test_a_padded_buckets_rows_never_reach_a_summary(params):
    """The same prompt at two buckets and after other tokens held the
    slot: what the slot keeps below its length and what the next steps
    give are the same (the padding's rows fold into no summary that a
    real token sees)."""
    toks = tokens_of(70, 8)
    outs = []
    for bucket, before in ((80, None), (128, tokens_of(100, 9))):
        cache = gen.init_kv_cache(CFG, 2, 128)
        if before is not None:
            _, cache = prefill(params, cache, 1, before, 112)
        logits, cache = prefill(params, cache, 1, toks[:66], bucket)
        rows = gen.eva_read_len(CFG, 66)
        kept = np.asarray(cache["ek"][:, 1, :rows])
        for t in range(66, 70):
            logits, cache = step(params, cache, toks[t], t)
        outs.append((kept, np.asarray(logits)))
    np.testing.assert_allclose(outs[0][0], outs[1][0], atol=1e-5)
    np.testing.assert_allclose(outs[0][1], outs[1][1], atol=1e-4)


def test_a_parked_lane_keeps_everything_while_another_closes(params):
    cache = gen.init_kv_cache(CFG, 3, 128)
    _, cache = prefill(params, cache, 0, tokens_of(W + 29, 2), 64)
    _, cache = prefill(params, cache, 2, tokens_of(41, 3), 48)
    before = jax.tree.map(lambda a: np.asarray(a[:, 2]), cache)
    zeros = jnp.zeros(3, jnp.int32)
    _t, cache, _tok, pos, _c, stats = gen.decode_block(
        params, cache, jnp.array([3, 0, 5], jnp.int32),
        jnp.array([W + 29, 0, 0], jnp.int32), jnp.zeros(3), zeros, zeros,
        CFG, 4)
    assert pos.tolist() == [W + 33, 0, 0]
    after = jax.tree.map(lambda a: np.asarray(a[:, 2]), cache)
    for name in ("ek", "ev"):  # every row of the parked lane, row 0 too
        np.testing.assert_array_equal(before[name], after[name])
    # lane 0's steps at 61, 62, 63, 64: the third closes its window
    n = CFG.n_layers
    assert {k: int(v) for k, v in stats.items()} == {
        "eva_window_rows_read": n * (29 + 30 + 31 + 0),
        "eva_summary_rows_read": n * (3 * PER + 2 * PER),
        "eva_windows_closed": n}
    assert gen.block_stat_keys(CFG) == (
        "eva_window_rows_read", "eva_summary_rows_read",
        "eva_windows_closed")
    # the slot after the close: two windows' summaries, then nothing live
    assert gen.eva_read_len(CFG, W + 33) == 2 * PER + 1


def test_two_lanes_close_in_one_step_beside_a_live_lane(params):
    """Lanes 1 and 3 (one and two windows closed before) fill a window at
    the SAME step of one ``decode_block``, lane 4 decodes beside them
    and closes nothing, lanes 0 and 2 are parked: the two lanes' tokens,
    the slots after the fold and the next step's logits are the
    reference's, and lane 4's tokens and every row of its slot are
    bit for bit what it gets with the other two parked."""
    prompts = {1: tokens_of(W + 29, 21), 3: tokens_of(2 * W + 29, 22),
               4: tokens_of(41, 23)}
    first = {1: 7, 3: 9, 4: 5}
    cache = gen.init_kv_cache(CFG, 5, 160)
    for b, prompt in prompts.items():
        _, cache = prefill(params, cache, b, prompt, 96)
    zeros = jnp.zeros(5, jnp.int32)

    def block(live):
        tok = zeros.at[jnp.array(live)].set(
            jnp.array([first[b] for b in live]))
        pos = zeros.at[jnp.array(live)].set(
            jnp.array([len(prompts[b]) for b in live]))
        with jax.default_matmul_precision("highest"):  # donates the cache
            return gen.decode_block(
                params, jax.tree.map(jnp.copy, cache), tok, pos,
                jnp.zeros(5), zeros, zeros, CFG, 4)

    toks, after, tok, pos, _c, stats = block([1, 3, 4])
    assert pos.tolist() == [0, W + 33, 0, 2 * W + 33, 45]
    # steps at 61, 62, 63, 64 and 93, 94, 95, 96: the third closes both
    assert int(stats["eva_windows_closed"]) == 2 * CFG.n_layers
    with jax.default_matmul_precision("highest"):
        logits, _ = gen.decode_step_multi(
            params, tok, jax.tree.map(jnp.copy, after), pos, CFG)
    for b in (1, 3):
        seq = jnp.concatenate(
            [prompts[b], jnp.array([first[b]]), toks[b]])
        want, pooled = ref_logits(params, seq)
        n = len(prompts[b])
        assert toks[b].tolist() == want[n:n + 4, 0].argmax(-1).tolist()
        assert rel(logits[b], want[n + 4]) < TOL
        closed = (n + 4) // W * PER
        assert gen.eva_read_len(CFG, n + 4) == closed + 1
        for li, (ks, vs) in enumerate(pooled):
            assert rel(after["ek"][li, b, :closed], ks[:closed]) < TOL
            assert rel(after["ev"][li, b, :closed], vs[:closed]) < TOL
    # the lanes alone: nothing of a neighbour's fold reaches them
    for live in ([4], [1], [3]):
        toks_alone, alone = block(live)[:2]
        for b in live:
            np.testing.assert_array_equal(toks[b], toks_alone[b])
            for name in ("ek", "ev"):
                np.testing.assert_array_equal(
                    after[name][:, b], alone[name][:, b])
    for name in ("ek", "ev"):  # the parked lanes keep everything
        np.testing.assert_array_equal(after[name][:, [0, 2]],
                                      cache[name][:, [0, 2]])


def test_the_engines_programs_sample_the_next_token_from_head_0(params):
    """``generate`` (a prefill a row, then ONE ``decode_block``) gives
    the greedy tokens of the reference's head 0, teacher-forced."""
    prompt = tokens_of(29, 11)
    with jax.default_matmul_precision("highest"):
        ids = gen.generate(params, prompt[None], CFG, max_new_tokens=8,
                           max_len=64)[0]
    seq = jnp.concatenate([prompt, ids[:-1]])
    want, _ = ref_logits(params, seq)
    assert ids.tolist() == want[28:, 0].argmax(-1).tolist()


# -- a model without an "attn" layer -----------------------------------------

def test_a_model_without_an_attn_layer_builds_its_cache_and_counters():
    assert CFG.n_attn_layers == 0 and gen._length_kind(CFG) == "eva"
    assert [kind for kind, _row, _n in gen._kinds_of(CFG)] == ["eva"]
    cache = gen.init_kv_cache(CFG, 2, 128)
    # 3 windows can have closed before token 127: 3 x 8 + 32 rows
    assert {k: v.shape for k, v in cache.items()} == {
        "ek": (3, 2, 56, 4, 16), "ev": (3, 2, 56, 4, 16)}
    assert gen.eva_rows(CFG, 128) == 56 and gen.eva_rows(CFG, 20) == 20
    assert gen.slot_footprint(cache) == {
        "state_bytes": 0, "row_bytes": 3 * 2 * 64 * 4, "state_layers": 0}
    # the host's count of what the kernel reads: whole chunks of the rows
    # a position leaves, not of the position
    chunk = gen.decode_attn_chunk(CFG, 128)
    assert chunk == 56
    assert gen.attn_rows_read(CFG, [0, 37], 4, 128) == 4 * 56
    tall = dataclasses.replace(CFG, n_heads=64, d_model=1024)
    assert gen.decode_attn_chunk(tall, 8192) == 128
    # a lane's 43 and then 44 rows; another's 31 and, after a close, 8
    assert gen.attn_rows_read(tall, [5 * W + 3, 0, W - 1], 2, 8192) == 4 * 128
    assert gen.attn_rows_read(tall, [40 * W + 3], 1, 8192) == 3 * 128
    params = jax.eval_shape(lambda: init_params(CFG, jax.random.key(0)))
    assert "layers" not in params and set(params) == {
        "embed", "eva_layers", "final_ln", "lm_head"}


def test_existing_models_are_untouched_by_the_new_defaults():
    for c in (TransformerConfig.gptj_6b(), TransformerConfig.glm47_flash(8),
              TransformerConfig.granite4_h_micro(),
              TransformerConfig.mimo_v2_flash(7),
              TransformerConfig.kimi_linear(8),
              TransformerConfig.phi4_mini_flash()):
        assert (c.eva_window, c.eva_chunk, c.n_pred_heads, c.residual_f32,
                c.norm_unit_offset) == (0, 0, 1, False, False)
        assert gen._length_kind(c) == "attn"
        assert "attn" in [kind for kind, _r, _n in gen._kinds_of(c)]
        row = gen._row("attn", c)
        assert row.closes is None  # a row a token: the position itself
        assert (row.read_len(c, 77), row.slot_rows(c, 512)) == (77, 512)
    shapes = jax.eval_shape(lambda: init_params(
        TransformerConfig.tiny_sambay(), jax.random.key(0)))
    assert "layers" in shapes and shapes["embed"].shape == (256, 64)


def test_ill_formed_eva_models_are_refused():
    for bad in (dict(layer_types=("eva", "eva", "attention")),
                dict(eva_chunk=5), dict(eva_window=0), dict(n_kv_heads=2),
                dict(tie_embeddings=True), dict(norm="layer")):
        with pytest.raises(ValueError):
            TransformerConfig.tiny_eva(**bad)


# -- the published numbers and the benchmark's arithmetic --------------------

def test_config_follows_the_published_numbers():
    c = TransformerConfig.evabyte()
    layer = 202_375_168 + 2 * 32 * 128 + 2 * 4096
    assert c.param_count() == 32 * layer + 320 * 4096 + 4096 + 4096 * 2560
    assert (c.n_layers, c.d_model, c.vocab_size, c.d_ff) == (
        32, 4096, 320, 11008)
    assert (c.n_heads, c.kv_heads, c.d_head, c.rotary_dim) == (
        32, 32, 128, 128)
    assert (c.eva_window, c.eva_chunk, c.n_pred_heads, c.rope_theta) == (
        2048, 16, 8, 1e5)
    assert c.layer_types == ("eva",) * 32 and c.max_seq_len == 32768
    cut = TransformerConfig.evabyte(8)
    cache = jax.eval_shape(lambda: gen.init_kv_cache(cut, 16, 32768))
    assert cache["ek"].shape == (8, 16, 3968, 32, 128)
    assert gen.slot_footprint(cache) == {
        "state_bytes": 0, "row_bytes": 8 * 16384, "state_layers": 0}
    assert gen.decode_attn_chunk(cut, 32768) == 64
    shapes = jax.eval_shape(lambda: init_params(cut, jax.random.key(0)))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == cut.param_count()
    assert shapes["lm_head"].shape == (4096, 8 * 320)


def test_the_benchmarks_arithmetic_agrees_with_the_program():
    sys.path.insert(0, ROOT)
    try:
        from benchmarks import eva_model
    finally:
        sys.path.remove(ROOT)
    with open(os.path.join(
            ROOT, "benchmarks/configs/evabyte-l8-bf16-serve.json")) as f:
        model = json.load(f)
    cfg = eva_model.transformer_config(model)
    assert cfg == dataclasses.replace(
        TransformerConfig.evabyte(8), param_dtype=jnp.bfloat16)
    dims = eva_model.dims(cfg)
    n = eva_model.param_count(dims)
    assert 8 * n["layer"] + n["ends"] == cfg.param_count()
    assert n["layer"] == 202_375_168 + 8192 + 8192
    eng = model["run"]["engine"]
    cache = jax.eval_shape(lambda: gen.init_kv_cache(
        cfg, eng["max_slots"], eng["max_len"]))
    assert eva_model.slot_rows(dims, eng["max_len"]) == (
        cache["ek"].shape[2]) == 3968
    assert 8 * eva_model.row_bytes(dims) == gen.slot_footprint(cache)[
        "row_bytes"]
    shapes = jax.eval_shape(
        lambda: eva_model.make_bf16_params(cfg, 2 ** 31 + 5))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == cfg.param_count()
    assert {a.dtype for a in jax.tree.leaves(shapes)} == {
        jnp.dtype(jnp.bfloat16)}
    assert jax.tree.structure(shapes) == jax.tree.structure(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0))))
    tiny = eva_model.transformer_config({**model, **model["rehearsal"]})
    assert tiny.layer_types == ("eva",) * tiny.n_layers
    assert eva_model.reference_constants(cfg) == {
        "n_heads": 32, "d_head": 128, "eps": 1e-5, "theta": 1e5,
        "window": 2048, "chunk": 16, "n_pred_heads": 8}
    # ISSUE 55's step at 16 lanes of ~10.6 k bytes: the weights and ~1,660
    # live rows a lane a layer
    step = eva_model.decode_step_bytes(
        dims, 8 * 16 * 1024, 8 * 16 * 640)
    assert abs(step - (3.26e9 + 3.49e9)) < 0.02e9
