"""Who owns a served weight's physical layout (ISSUE 29).

``generation.lay_out_for_decode`` asks the compiled ``decode_block`` which
layout it reads each weight in and moves the weights that lie otherwise,
once, at the engine's set-up. On the CPU the compiler asks for the layouts
the weights came in, so nothing moves; a weight put into another layout on
purpose is moved back, which drives the same code the chip drives. What the
chip's compiler asks for at the served size is in ``test_tpu_compile.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import generation as gen
from ray_tpu.models.quant import QTensor, quantize_params_int8
from ray_tpu.models.transformer import TransformerConfig, init_params

SLOTS, MAX_LEN = 2, 64


def _model(kind):
    if kind == "mha_int8":
        cfg = TransformerConfig.tiny()
        params = quantize_params_int8(init_params(cfg, jax.random.key(0)))
    else:
        cfg = TransformerConfig.tiny_mla_moe()
        params = init_params(cfg, jax.random.key(0))
    return gen.prepare_for_inference(params, cfg)


def _elsewhere(x):
    """The same array in the layout furthest from the one it came in."""
    from jax.experimental.layout import Format, Layout

    return jax.device_put(
        x, Format(Layout(tuple(reversed(range(x.ndim)))), x.sharding))


def _projections_elsewhere(params):
    attn = dict(params["layers"]["attn"])
    for name in ("wq", "wk", "wv"):
        attn[name] = QTensor(_elsewhere(attn[name].q), attn[name].s)
    return {**params, "layers": {**params["layers"], "attn": attn}}


@pytest.mark.parametrize("kind", ["mha_int8", "mla_moe"])
def test_lay_out_for_decode_returns_the_tree_it_was_given(kind):
    """Same structure, names, shapes, dtypes and values; on a backend
    whose compiler asks for the layouts the weights have, the very same
    buffers (committed where they lie, no byte copied), nothing counted."""
    params, cfg = _model(kind)
    paths = jax.tree_util.tree_flatten_with_path(params)[0]
    before = [(jax.tree_util.keystr(p), x.unsafe_buffer_pointer(),
               np.asarray(x)) for p, x in paths]
    out, moved, nbytes = gen.lay_out_for_decode(
        params, cfg, SLOTS, MAX_LEN, 2)
    assert (moved, nbytes) == (0, 0)
    assert jax.tree.structure(out) == jax.tree.structure(params)
    after = jax.tree_util.tree_flatten_with_path(out)[0]
    assert len(after) == len(before)
    for (name, buffer, value), (p, y) in zip(before, after):
        assert jax.tree_util.keystr(p) == name
        assert y.unsafe_buffer_pointer() == buffer and y.committed, name
        assert (y.shape, y.dtype) == (value.shape, value.dtype), name
        np.testing.assert_array_equal(np.asarray(y), value, err_msg=name)


def test_decode_weight_formats_answers_for_shapes_alone():
    """The question needs no weights: the tier-1 compile for a described
    chip asks it of ``ShapeDtypeStruct`` s."""
    params, cfg = _model("mha_int8")
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.sharding), params)
    asked = gen.decode_weight_formats(shapes, cfg, SLOTS, MAX_LEN, 2)
    assert jax.tree.structure(asked) == jax.tree.structure(params)
    for f, x in zip(jax.tree.leaves(asked), jax.tree.leaves(params)):
        assert f.layout == x.format.layout


def test_lay_out_for_decode_moves_only_what_lies_elsewhere():
    params, cfg = _model("mha_int8")
    wq = params["layers"]["attn"]["wq"]
    value = np.asarray(wq.q)
    home = wq.q.format.layout
    away = _elsewhere(wq.q)
    assert away.format.layout != home
    attn = {**params["layers"]["attn"], "wq": QTensor(away, wq.s)}
    given = {**params, "layers": {**params["layers"], "attn": attn}}
    out, moved, nbytes = gen.lay_out_for_decode(
        given, cfg, SLOTS, MAX_LEN, 2)
    assert (moved, nbytes) == (1, value.nbytes)
    back = out["layers"]["attn"]["wq"]
    assert isinstance(back, QTensor)
    assert back.s.unsafe_buffer_pointer() == wq.s.unsafe_buffer_pointer()
    assert back.q.format.layout == home
    assert (back.q.shape, back.q.dtype) == (value.shape, value.dtype)
    np.testing.assert_array_equal(np.asarray(back.q), value)
    assert away.is_deleted()  # donated: no weight exists twice
    for stays in (lambda t: t["layers"]["mlp"]["wi"].q,
                  lambda t: t["lm_head"]):
        assert stays(out).unsafe_buffer_pointer() == (
            stays(params).unsafe_buffer_pointer())


def test_an_int8_leaf_carries_where_it_lies_through_jit_and_scan():
    """``QTensor.order`` is the node's static part: a scan's body and a
    jitted function see it, a slice keeps it, and ``lies`` is the order of
    the axes the holder still has."""
    q = jnp.arange(2 * 3 * 4 * 5, dtype=jnp.int8).reshape(2, 3, 4, 5)
    w = QTensor(q, jnp.ones((2, 1, 4, 5)), order=(0, 2, 1, 3))
    assert w.lies() == (0, 2, 1, 3)
    leaves, treedef = jax.tree.flatten(w)
    assert len(leaves) == 2 and treedef.unflatten(leaves).order == w.order
    assert jax.tree.map(lambda x: x[0], w).lies() == (1, 0, 2)
    assert QTensor(q, w.s).lies() is None
    seen = []

    def body(carry, lp):
        seen.append((lp.order, lp.q.shape, lp.lies()))
        return carry + lp.astype(jnp.float32).sum(), None

    total, _ = jax.jit(lambda t: jax.lax.scan(body, 0.0, t))(w)
    assert seen == [((0, 2, 1, 3), (3, 4, 5), (1, 0, 2))]
    assert float(total) == float(np.asarray(q, np.float32).sum())


def test_told_where_they_lie_marks_what_lies_otherwise_and_nothing_else():
    """Every int8 leaf whose format is not row by row learns its order;
    the others, the scales and the plain leaves come back as they are
    (on the CPU, where nothing lies otherwise, the tree's structure is
    the one it had: ``lay_out_for_decode`` returns what it was given)."""
    params, _cfg = _model("mha_int8")
    assert jax.tree.structure(_as_they_lie(params)) == (
        jax.tree.structure(params))
    told = _as_they_lie(_projections_elsewhere(params))
    attn = told["layers"]["attn"]
    assert [attn[w].order for w in ("wq", "wk", "wv", "wo")] == [
        (3, 2, 1, 0)] * 3 + [None]
    assert told["layers"]["mlp"]["wi"].order is None
    for a, b in zip(jax.tree.leaves(told), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_admission_holds_told_leaves_to_their_order_and_decode_does_not():
    """ISSUE 62. Handed int8 leaves that say where they lie,
    ``prefill_into_slot`` pins each layer's slice to that order (a
    ``LayoutConstraint`` a leaf in the layer scan's body: the compiler may
    not lay the slice out again before the product) and gives the very
    logits; handed the same leaves untold, its text has no such
    operation; ``decode_block``'s text is the same either way (the decode
    step decided where the weights lie: it needs no telling)."""
    params, cfg = _model("mha_int8")
    away = _projections_elsewhere(params)
    told = _as_they_lie(away)
    prompt = (jnp.arange(1, 17, dtype=jnp.int32) % cfg.vocab_size)[None]

    def admit(tree):
        cache = gen.init_kv_cache(cfg, SLOTS, MAX_LEN)
        args = (tree, prompt, np.int32(11), np.int32(1), cache, cfg)
        text = gen.prefill_into_slot.lower(*args).as_text()
        logits, cache = gen.prefill_into_slot(*args)
        return text, np.asarray(logits), jax.tree.map(np.asarray, cache)

    def decode_text(tree):
        lane = jnp.zeros(SLOTS, jnp.int32)
        return gen.decode_block.lower(
            tree, gen.init_kv_cache(cfg, SLOTS, MAX_LEN), lane, lane,
            jnp.zeros(SLOTS, jnp.float32), lane, lane, cfg, 2).as_text()

    text, logits, cache = admit(told)
    plain_text, plain_logits, plain_cache = admit(away)
    assert text.count("@LayoutConstraint") == 3
    assert "@LayoutConstraint" not in plain_text
    np.testing.assert_array_equal(logits, plain_logits)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(plain_cache)):
        np.testing.assert_array_equal(a, b)
    assert decode_text(told) == decode_text(away)
    assert "@LayoutConstraint" not in decode_text(told)


SCRIPT = [(np.arange(1, 9), 10), (np.arange(3, 23) % 200, 7),
          (np.full(5, 7), 12), (np.arange(40, 10, -1), 9)]


def _serve(params, cfg):
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(params, cfg, max_slots=SLOTS, max_len=MAX_LEN,
                    prefill_buckets=(16, 32))
    try:
        reqs = [eng.submit(p.astype(np.int32), max_new_tokens=n)
                for p, n in SCRIPT]
        toks = []
        for r in reqs:
            out = []
            while isinstance(item := r.out.get(timeout=180), int):
                out.append(item)
            toks.append(out)
        return toks, eng.stats(), eng.params
    finally:
        eng.shutdown()


def _as_they_lie(params):
    """``params`` with every int8 leaf told where it lies now."""
    return gen.told_where_they_lie(
        params, jax.tree.map(lambda x: x.format, params))


@pytest.mark.parametrize("engine_lays_out", [True, False, "told"],
                         ids=["with", "without", "told"])
def test_engine_tokens_do_not_depend_on_where_the_weights_lay(
        engine_lays_out, monkeypatch):
    """A fixed script of requests: the same tokens from weights that came
    in the layout the decode step reads, from weights that came otherwise
    and were moved at set-up, (``without``) from weights left where they
    lay, for which the programs simply compile, and (``told``) from
    weights left there and TOLD so, whose admissions hold them to that
    order (what the chip's engine serves from, ISSUE 62)."""
    params, cfg = _model("mha_int8")
    want, stats, _ = _serve(params, cfg)
    assert [len(t) for t in want] == [n for _, n in SCRIPT]
    assert (stats["weights_relaid"], stats["weights_relaid_bytes"]) == (0, 0)

    given = _projections_elsewhere(params)
    if engine_lays_out == "told":
        monkeypatch.setattr(gen, "lay_out_for_decode",
                            lambda p, *_a: (_as_they_lie(p), 0, 0))
    elif not engine_lays_out:
        monkeypatch.setattr(gen, "lay_out_for_decode",
                            lambda p, *_a: (p, 0, 0))
    got, stats, served = _serve(given, cfg)
    assert got == want
    wq = params["layers"]["attn"]["wq"].q
    if engine_lays_out == "told":
        assert served["layers"]["attn"]["wq"].order == (3, 2, 1, 0)
        assert stats["weights_relaid"] == 0
    elif engine_lays_out:
        assert stats["weights_relaid"] == 3
        assert stats["weights_relaid_bytes"] == 3 * wq.nbytes
        assert served["layers"]["attn"]["wq"].q.format.layout == (
            wq.format.layout)
    else:
        assert stats["weights_relaid"] == 0
    # names, logical shapes and dtypes are what the reference reads
    for a, b in zip(jax.tree_util.tree_flatten_with_path(served)[0],
                    jax.tree_util.tree_flatten_with_path(params)[0]):
        assert a[0] == b[0]
        assert (a[1].shape, a[1].dtype) == (b[1].shape, b[1].dtype)
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


def test_no_program_is_built_once_every_shape_was_served(monkeypatch):
    """Weights that were moved are committed arrays, and jit compiles once
    for a committed argument and once for an uncommitted one. The engine
    commits all it holds at set-up, so the programs a warm-up builds are
    the ones that serve later: warm as ``benchmarks/runners/serve.py``
    does (each bucket alone, then ``jnp.stack`` of k first tokens made
    from fresh zeros), then traffic that fills the slots builds nothing."""
    from ray_tpu.serve.llm import LLMEngine

    params, cfg = _model("mha_int8")
    eng = LLMEngine(_projections_elsewhere(params), cfg, max_slots=4,
                    max_len=MAX_LEN, prefill_buckets=(16, 32))
    builds = []

    def on_event(event, *_a, **_kw):
        if event.endswith("backend_compile_duration"):
            builds.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        assert eng.stats()["weights_relaid"] == 3
        for n in (16, 32):
            eng.generate(np.zeros(n, np.int32), max_new_tokens=2)
        first = eng._first_token(jnp.zeros(cfg.vocab_size, cfg.dtype), 0.0, 0)
        for k in range(1, eng.max_slots + 1):
            np.asarray(jnp.stack([first] * k))
        warmed = len(builds)
        reqs = [eng.submit(np.arange(1, 6 + 5 * i, dtype=np.int32),
                           max_new_tokens=12) for i in range(6)]
        for r in reqs:
            while isinstance(r.out.get(timeout=180), int):
                pass
        blocks = eng.stats()["blocks_by_steps"]
        assert blocks["8"] > 0 and blocks["2"] > 0  # both lengths served
        assert len(builds) == warmed, builds[warmed:]
    finally:
        eng.shutdown()
        jax.monitoring.unregister_event_duration_listener(on_event)


def test_benchmarks_reference_reads_weights_in_any_layout():
    """``benchmarks/reference.py`` reads ``engine.params`` by name and
    logical shape: its logits over weights that lie elsewhere are those
    over the weights as they came."""
    from benchmarks import reference

    params, cfg = _model("mha_int8")

    def logits(tree):
        plain = jax.tree.map(
            lambda x: (x.q, x.s) if isinstance(x, QTensor) else x, tree,
            is_leaf=lambda x: isinstance(x, QTensor))
        seq = jnp.arange(1, 20, dtype=jnp.int32)
        return np.asarray(jax.jit(
            reference.forward_logits, static_argnums=(2,)
        )(plain, seq, cfg.rotary_dim))

    np.testing.assert_array_equal(
        logits(_projections_elsewhere(params)), logits(params))
