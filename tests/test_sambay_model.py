"""A decoder-hybrid-decoder (Phi-4-mini-flash-reasoning's kind) against its
plain reference, at test size on the CPU with seeded random weights, what
is this model's own: the Mamba-1 scan kernel and the in-place update
against the token-by-token recurrence, the padded grouped-query reading of
differential attention against the four attentions a pair, the layer list
cut into segments against the unrolled loop, the upper layers on a
prompt's last real token alone, what the layers hand on through a decode
step, a parked lane, the counters, the published numbers and the
benchmark's arithmetic.

What it shares with the other served models (the parameter tree, the
uncached forward, the two programs through a slot with prompts on both
sides of the window and lanes at different depths, a reused slot through
``LLMEngine``, ``generate``, the ablations, the reference's independence,
the cell's listing and rehearsal) is ``tests/test_served_models.py``'s."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_sambay as ref
from ray_tpu.models import generation as gen
from ray_tpu.models import transformer as tf
from ray_tpu.models.transformer import (
    TransformerConfig,
    forward,
    init_params,
)
from ray_tpu.ops.mamba import mamba_scan, mamba_step, mamba_update

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# M W M W | M F | G X: 128 channels over a state of 16, a window of 8 rows
CFG = TransformerConfig.tiny_sambay(dtype=jnp.float32)
TOL = 2e-4  # float32 against float32: rounding order only
HP = {"n_heads": CFG.n_heads, "n_kv_heads": CFG.kv_heads,
      "d_head": CFG.d_head, "eps": CFG.norm_eps, "window": CFG.window,
      "layer_types": CFG.layer_types, "mamba_state": CFG.mamba_state,
      "mamba_dt_rank": CFG.mamba_dt_rank}


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


def scan_inputs(b, s, c=128, n=16, seed=0):
    k = jax.random.split(jax.random.key(seed), 7)
    return dict(
        x=jax.random.normal(k[0], (b, s, c)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (b, s, c)) - 2),
        A=-jnp.exp(jax.random.normal(k[2], (n, c))),
        Bm=jax.random.normal(k[3], (b, s, n)),
        Cm=jax.random.normal(k[4], (b, s, n)),
        D=jax.random.normal(k[5], (c,)),
        state0=jax.random.normal(k[6], (b, n, c)))


def by_token(x, dt, A, Bm, Cm, D, state0, valid=None):
    state, ys = state0, []
    for t in range(x.shape[1]):
        y, new = mamba_step(state, x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t],
                            D)
        if valid is not None:
            new = jnp.where(valid[:, t, None, None], new, state)
        state = new
        ys.append(y)
    return jnp.stack(ys, 1), state


# -- Mamba-1 in its three forms ----------------------------------------------

@pytest.mark.parametrize("length,block", [(5, 8), (8, 8), (21, 8), (40, 16)])
@pytest.mark.parametrize("with_state0", [False, True])
def test_the_scan_kernel_equals_the_recurrence(length, block, with_state0):
    a = scan_inputs(2, length)
    if not with_state0:
        a["state0"] = jnp.zeros_like(a["state0"])
    y, last = mamba_scan(a["x"], a["dt"], a["A"], a["Bm"], a["Cm"], a["D"],
                         a["state0"] if with_state0 else None,
                         time_block=block)
    want_y, want = by_token(**a)
    assert float(jnp.abs(y - want_y).max()) < 1e-4
    assert float(jnp.abs(last - want).max()) < 1e-4


def test_a_padded_buckets_end_state_is_the_state_at_prompt_len():
    a = scan_inputs(2, 24, seed=3)
    valid = jnp.arange(24)[None] < jnp.array([[24], [13]])
    y, last = mamba_scan(a["x"], a["dt"], a["A"], a["Bm"], a["Cm"], a["D"],
                         a["state0"], valid, time_block=8)
    want_y, want = by_token(**a, valid=valid)
    assert float(jnp.abs(last - want).max()) < 1e-4
    assert float(jnp.abs((y - want_y) * valid[..., None]).max()) < 1e-4


def test_mamba_step_equals_the_references_token(params):
    """One layer of the reference over a sequence, token by token, against
    ``mamba_step`` fed the same convolved inputs: the two recurrences are
    written apart (the reference's in its scan, [N, C] a token)."""
    lp = jax.tree.map(lambda a: a[0], params["mamba_layers"])
    h = jax.random.normal(jax.random.key(5), (11, CFG.d_model))
    with jax.default_matmul_precision("highest"):
        _out, want, m = ref.mamba(h, lp["mamba"], HP, {})
        wp = lp["mamba"]
        x = h @ wp["wx"]
        taps = wp["conv_w"].shape[0]
        conv = sum(jnp.pad(x, ((taps - 1 - k, 0), (0, 0)))[:11]
                   * wp["conv_w"][k] for k in range(taps)) + wp["conv_b"]
        xc = jax.nn.silu(conv)
        dt, B, C, A = tf.mamba_inputs(xc, wp, CFG)
        ys, state = by_token(xc[None], dt[None], A, B[None], C[None],
                             wp["d"], jnp.zeros((1,) + A.shape))
    assert float(ref.state_distance(state[0], want)) < 1e-5
    assert float(jnp.abs(ys[0] - m).max()) < 1e-4


@pytest.mark.parametrize("live", [None, (True, False, True)])
def test_the_update_steps_one_layer_in_place(live):
    """The served step: layer 1 of three stepped, its parked lane and the
    other layers' states bit for bit what they were."""
    a = scan_inputs(3, 1, seed=7)
    states = jax.random.normal(jax.random.key(8), (3, 3, 16, 128))
    mask = None if live is None else jnp.array(live)
    y, new = mamba_update(states, jnp.int32(1), a["x"][:, 0], a["dt"][:, 0],
                          a["A"], a["Bm"][:, 0], a["Cm"][:, 0], a["D"], mask)
    want_y, want = mamba_step(states[1], a["x"][:, 0], a["dt"][:, 0], a["A"],
                              a["Bm"][:, 0], a["Cm"][:, 0], a["D"])
    np.testing.assert_array_equal(new[0], states[0])
    np.testing.assert_array_equal(new[2], states[2])
    lanes = np.array([i for i in range(3) if live is None or live[i]])
    assert float(jnp.abs(new[1, lanes] - want[lanes]).max()) < 1e-6
    assert float(jnp.abs(y[lanes] - want_y[lanes]).max()) < 1e-6
    if live is not None:
        np.testing.assert_array_equal(new[1, 1], states[1, 1])
        assert not np.asarray(y[1]).any()


# -- differential attention read as grouped-query attention -------------------

def test_the_padded_grouped_query_reading_is_the_four_attentions():
    """``_diff_pairs`` + plain causal GQA + ``_diff_combine`` against the
    reference's four softmax attentions a pair and their subtraction."""
    from ray_tpu.ops.attention import causal_attention

    k = jax.random.split(jax.random.key(2), 6)
    S, H, Hkv, D = 13, 8, 4, 64
    q = jax.random.normal(k[0], (1, S, H, D))
    kk = jax.random.normal(k[1], (1, S, Hkv, D))
    v = jax.random.normal(k[2], (1, S, Hkv, D))
    wp = {"lambda": 0.5 * jax.random.normal(k[3], (4, D)),
          "subln": 1 + 0.1 * jax.random.normal(k[4], (2 * D,)),
          "depth": jnp.float32(5)}
    with jax.default_matmul_precision("highest"):
        q2, k2, v2 = tf._diff_pairs(q, kk, v)
        assert (q2.shape, k2.shape) == ((1, S, H, 2 * D), (1, S, 2, 2 * D))
        got = tf._diff_combine(causal_attention(q2, k2, v2), wp, CFG)
        rows = jnp.arange(S)
        want = ref.differential(
            q[0], kk[0], v[0], wp, HP, {}, 5,
            rows[:, None] >= rows[None, :])
    assert float(jnp.abs(got.reshape(S, -1) - want).max()) < 1e-5
    lam, init = tf.diff_lambdas(wp, CFG)
    assert abs(float(init) - (0.8 - 0.6 * np.exp(-1.5))) < 1e-6
    assert float(lam) != float(init)


# -- the list of layers in segments -------------------------------------------

def _old_period(kinds):
    n = len(kinds)
    return next(p for p in range(1, n + 1)
                if n % p == 0 and kinds == kinds[:p] * (n // p))


def test_a_list_without_a_period_is_cut_into_segments():
    kinds = TransformerConfig.phi4_mini_flash().layer_types
    assert kinds == ("mamba", "window") * 8 + ("mamba", "attention") + (
        "gmu", "cross") * 7
    assert tf._segments(kinds) == (
        (0, ("mamba", "window"), 8), (16, ("mamba", "attention"), 1),
        (18, ("gmu", "cross"), 7))
    assert tf._segments(CFG.layer_types) == (
        (0, ("mamba", "window"), 2),
        (4, ("mamba", "attention", "gmu", "cross"), 1))
    # a list with a period stays ONE segment of its shortest period
    for c in (TransformerConfig.granite4_h_micro(),
              TransformerConfig.tiny_ssm_hybrid(),
              TransformerConfig.kimi_linear(8),
              TransformerConfig.mimo_v2_flash(7),
              TransformerConfig.tiny_swa_moe()):
        kinds = c.layer_types[c.n_dense_layers if c.moe_experts else 0:]
        p = _old_period(kinds)
        assert tf._segments(kinds) == ((0, kinds[:p], len(kinds) // p),)
    # and a list that had none is cut where that makes fewer bodies (3 for 4)
    assert tf._segments(TransformerConfig.tiny_kda_moe().layer_types[1:]) == (
        (0, ("kda",), 1), (1, ("kda", "attention"), 2))


def test_the_segments_run_the_layers_in_the_listed_order():
    """``_scan_kinds`` over a three-segment list against the unrolled
    loop: each layer's (kind, index in its kind) in running order, and a
    value carried through every body."""
    kinds = ("a", "b") * 3 + ("a", "c") + ("d", "e") * 2
    stacks = {kind: {"w": jnp.arange(kinds.count(kind), dtype=jnp.float32)
                     + 10 * i, "ln1": {"scale": jnp.zeros(
                         (kinds.count(kind), 1))}}
              for i, kind in enumerate("abcde")}
    assert len(tf._segments(kinds)) == 3
    seen = []

    def body(carry, lp, li):
        seen.append(None)  # one trace a body: a run of a period
        return carry * 1.5 + lp["w"] + li

    got = tf._scan_kinds(body, jnp.float32(1.0), stacks, kinds,
                         dict.fromkeys(stacks, 0))
    assert len(seen) == 6  # two runs a segment, three segments
    want, count = 1.0, dict.fromkeys(stacks, 0)
    for kind in kinds:
        li = count[kind]
        want = want * 1.5 + float(stacks[kind]["w"][li]) + li
        count[kind] += 1
    assert abs(float(got) - want) / want < 1e-6


def test_ill_formed_layer_types_are_refused():
    for bad in (dict(layer_types=("gmu",) + CFG.layer_types[1:]),
                dict(layer_types=CFG.layer_types[:5] + ("cross",) * 3),
                dict(layer_types=CFG.layer_types[:6] + ("cross",
                                                        "attention")),
                dict(mamba_inner=0), dict(mamba_inner=100), dict(n_heads=7),
                dict(norm="batch"),
                dict(layer_types=("ssm",) + CFG.layer_types[1:])):
        with pytest.raises(ValueError):
            TransformerConfig.tiny_sambay(**bad)


def test_existing_models_are_untouched_by_the_new_defaults():
    for c in (TransformerConfig.gptj_6b(), TransformerConfig.glm47_flash(8),
              TransformerConfig.granite4_h_micro(),
              TransformerConfig.mimo_v2_flash(7),
              TransformerConfig.kimi_linear(8)):
        assert (c.norm, c.attn_bias, c.diff_attn, c.mamba_inner) == (
            "rms", False, False, 0)
        assert not {"mamba", "gmu", "cross"} & set(c.layer_types)
        assert gen._row("attn", c) is not gen._SHARED
    c = TransformerConfig.mimo_v2_flash(7)
    assert [len(p) for p in (gen._prompt_parts(
        {k: {"ln1": {"scale": jnp.zeros((c.layer_types[1:].count(k), 1))},
             key: {}} for k, key in (("attention", "attn"),
                                     ("window", "swa"))}, c, 1),)] == [1]


# -- the two programs ----------------------------------------------------------

def test_the_upper_layers_run_on_the_last_real_token_alone(params):
    """A prefill cuts the model where the layers that keep nothing begin,
    and what they give for a padded prompt's last real token is what the
    reference gives, which runs them over every token."""
    stack, lc, first = tf.layer_groups(params, CFG)[0]
    parts = gen._prompt_parts(stack, lc, first)
    assert [(sorted(p), at, last) for p, at, last in parts] == [
        (["attention", "mamba", "window"], 0, False),
        (["cross", "gmu"], 6, True)]
    prompt = tokens_of(21, 4)
    cache = gen.init_kv_cache(CFG, 2, 64)
    logits, cache = prefill(params, cache, 1, prompt, 32)
    want, states = ref_logits(params, prompt)
    assert float(jnp.abs(logits - want[-1]).max()) < TOL
    # one "attn" layer's rows, two rings, three states and tails: the
    # upper two layers keep nothing
    assert {k: v.shape for k, v in cache.items() if k != "state"} == {
        "k": (1, 2, 64, 256), "v": (1, 2, 64, 256)}
    assert {k: v.shape for k, v in gen.cache_state(cache).items()} == {
        "mamba": (3, 2, 16, 128), "conv": (3, 2, 3 * 128),
        "wk": (2, 2, 8, 256), "wv": (2, 2, 8, 256)}
    for i, state in enumerate(states):
        assert float(ref.state_distance(
            gen.cache_state(cache)["mamba"][i, 1], state)) < TOL
    # the full layer's rows as the reference's layer 5 makes them
    with jax.default_matmul_precision("highest"):
        x, handed = ref.embed(params, prompt, HP), {}
        for depth, (name, i) in enumerate(ref.layers_in_order(params, HP)):
            lp = jax.tree.map(lambda a: a[i], params[name])
            x, _s, handed = ref.layer(x, lp, HP, {}, depth, handed)
    k, v = handed["kv"]
    assert float(jnp.abs(cache["k"][0, 1, :21] - k.reshape(21, -1)).max()
                 ) < TOL
    assert float(jnp.abs(cache["v"][0, 1, :21] - v.reshape(21, -1)).max()
                 ) < TOL


def test_a_parked_lane_keeps_everything_while_others_step(params):
    cache = gen.init_kv_cache(CFG, 3, 64)
    _, cache = prefill(params, cache, 0, tokens_of(19, 2), 32)
    _, cache = prefill(params, cache, 2, tokens_of(9, 3), 16)
    before = jax.tree.map(lambda a: np.asarray(a[:, 2]), cache)
    zeros = jnp.zeros(3, jnp.int32)
    _t, cache, _tok, pos, _c, stats = gen.decode_block(
        params, cache, jnp.array([3, 0, 5], jnp.int32),
        jnp.array([19, 0, 0], jnp.int32), jnp.zeros(3), zeros, zeros, CFG, 4)
    assert pos.tolist() == [23, 0, 0]
    after = jax.tree.map(lambda a: np.asarray(a[:, 2]), cache)
    for name in ("mamba", "conv"):  # a parked lane's state and window
        np.testing.assert_array_equal(before["state"][name],
                                      after["state"][name])
    np.testing.assert_array_equal(before["k"][:, 1:], after["k"][:, 1:])
    # one live lane, four steps: the ring rows of two window layers and
    # the chunks of the full layer's cache the one cross layer walked
    assert set(stats) == {"window_rows_read", "cross_rows_read"}
    assert int(stats["window_rows_read"]) == 2 * 8 * 4
    assert int(stats["cross_rows_read"]) == gen.attn_rows_read(
        CFG, [19, 0, 0], 4, 64) == 4 * 64
    assert gen.block_stat_keys(CFG) == ("window_rows_read",
                                        "cross_rows_read")


def test_decode_steps_after_a_prefill_match_the_reference_row_by_row(params):
    """Through ``LLMEngine``-sized programs: a prompt longer than the
    window in a padded bucket, then steps past the ring's wrap, every
    step's logits against the reference's one forward."""
    n, steps = 13, 10
    toks = tokens_of(n + steps, 6)
    cache = gen.init_kv_cache(CFG, 2, 64)
    logits, cache = prefill(params, cache, 0, toks[:n], 16)
    want, _ = ref_logits(params, toks)
    assert float(jnp.abs(logits - want[n - 1]).max()) < TOL
    for step in range(steps):
        tok = jnp.zeros(2, jnp.int32).at[0].set(toks[n + step])
        pos = jnp.zeros(2, jnp.int32).at[0].set(n + step)
        logits, cache = gen.decode_step_multi(params, tok, cache, pos, CFG)
        assert float(jnp.abs(logits[0] - want[n + step]).max()) < TOL


# -- the published numbers and the benchmark's arithmetic --------------------

def test_config_follows_the_published_numbers():
    c = TransformerConfig.phi4_mini_flash()
    assert c.param_count() == 3_852_562_944  # "3.8B"
    assert (c.n_layers, c.d_model, c.vocab_size, c.d_ff) == (
        32, 2560, 200064, 10240)
    assert (c.n_heads, c.kv_heads, c.d_head, c.window) == (40, 20, 64, 512)
    assert (c.mamba_inner, c.mamba_state, c.mamba_dt_rank) == (5120, 16, 160)
    assert [c.n_of(k) for k in ("mamba", "window", "attention", "gmu",
                                "cross")] == [9, 8, 0 + 1, 7, 7]
    assert c.n_attn_layers == 1
    cache = jax.eval_shape(lambda: gen.init_kv_cache(c, 48, 16384))
    assert cache["k"].shape == (1, 48, 16384, 1280)
    state = gen.cache_state(cache)
    assert state["mamba"].shape == (9, 48, 16, 5120)
    assert state["mamba"].dtype == jnp.float32
    assert state["wk"].shape == (8, 48, 512, 1280)
    assert gen.slot_footprint(cache) == {
        "state_bytes": 9 * (16 * 5120 * 4 + 3 * 5120 * 2)
        + 8 * 512 * 5120, "row_bytes": 5120, "state_layers": 9}
    shapes = jax.eval_shape(lambda: init_params(c, jax.random.key(0)))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == c.param_count()


def test_the_benchmarks_arithmetic_agrees_with_the_program():
    sys.path.insert(0, ROOT)
    try:
        from benchmarks import sambay_model
    finally:
        sys.path.remove(ROOT)
    with open(os.path.join(
            ROOT, "benchmarks/configs/phi4-mini-flash-bf16-serve.json")) as f:
        model = json.load(f)
    cfg = sambay_model.transformer_config(model)
    assert cfg == dataclasses.replace(
        TransformerConfig.phi4_mini_flash(), param_dtype=jnp.bfloat16)
    dims = sambay_model.dims(cfg)
    n = sambay_model.param_count(dims)
    assert sum(dims["n_" + k] * n[k] for k in (
        "mamba", "window", "attention", "gmu", "cross")) + n["ends"] == (
            cfg.param_count())
    assert (n["mamba"], n["window"], n["gmu"], n["cross"]) == (
        119_895_040, 98_322_304, 104_867_840, 91_766_144)
    cache = jax.eval_shape(lambda: gen.init_kv_cache(cfg, 48, 16384))
    foot = gen.slot_footprint(cache)
    assert sambay_model.slot_state_bytes(dims) == foot["state_bytes"]
    assert sambay_model.row_bytes(dims) == foot["row_bytes"]
    shapes = jax.eval_shape(
        lambda: sambay_model.make_bf16_params(cfg, 2 ** 31 + 5))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == cfg.param_count()
    assert {a.dtype for a in jax.tree.leaves(shapes)} == {
        jnp.dtype(jnp.bfloat16)}
    assert jax.tree.structure(shapes) == jax.tree.structure(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0))))
    tiny = sambay_model.transformer_config({**model, **model["rehearsal"]})
    assert tiny.layer_types == CFG.layer_types
    # a step's bytes at 48 lanes of 4,600 rows: the weights, and the one
    # cached layer's rows read by eight layers
    step = sambay_model.decode_step_bytes(
        dims, 48, 48 * 4600, 7 * 48 * 4600, 8 * 48 * 512)
    assert abs(step - (7.705e9 + 0.310e9 + 9.044e9 + 1.007e9)) < 0.01e9
    cost = sambay_model.mamba_scan_cost(dims, 4096)
    assert 9 < cost["ops"] / cost["bytes"] < 10


def test_forward_hands_the_rows_and_the_memory_upward(params):
    """The uncached forward against the reference with the upper layers'
    inputs ablated: it is the handed values that the upper layers read."""
    toks = tokens_of(19, 9)
    got = forward(params, toks[None], CFG)[0]
    want, _ = ref_logits(params, toks)
    assert float(jnp.abs(got - want).max()) < TOL
    for ablate in ({"m_after_gate": True}, {"cross_strict": True}):
        wrong, _ = ref_logits(params, toks, ablate=ablate)
        assert float(jnp.abs(got - wrong).max()) > 1e-3
