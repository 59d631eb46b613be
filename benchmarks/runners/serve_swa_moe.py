"""Serving runner for configurations of kind ``serve_swa_moe`` (full
attention layers that keep every row beside window layers that keep a
ring of their last rows and attend a learned sink, under a chip's share of
dropless routed experts; MiMo-V2-Flash's kind): the same one replica of
``serve.LLMServer`` behind ``serve.run``, the same load generator, window
and trace reduction as ``runners/serve.py`` (``measure``, ``serve_owner``,
``wait_session_gone`` and the deployment's recorders and control calls
are imported from there, the scope reduction and the stretch's counters
from ``runners/serve_mla_moe.py``, the served probes from ``runners/
serve_ssm.py``; nothing there is edited). What differs is the model's side
of the bench:

- the config object, the bf16 weights from the seed and the byte function
  come from ``benchmarks/swa_moe_model.py``;
- ``correct`` holds what the timed programs produce at the timed sizes to
  ``benchmarks/reference_swa_moe.py`` (``run.probe`` and ``correctness``
  of the configuration), LOGITS, not tokens. (1) Two seeded prompts whose
  lengths are no multiples of the window go through the whole served
  path; then, on the idle engine, each is run again by the window's own
  programs into slot 0: the logits ``prefill_into_slot`` returns after its
  blocked, padded prefill, the logits of the first decode step (which
  takes the rings over from the prefill) and those after
  ``decode_steps`` more steps of ``decode_block`` (every ring row
  overwritten at least once), teacher-forced on the engine's own tokens,
  against the reference's full forward over prompt + answer: the RMS of
  the difference over the RMS of the reference's logits, each at
  ``PROBE_ROWS`` positions in a row (the prompt cut one token shorter
  each time and prefilled again at the timed bucket; that many single
  decode steps at the end) and reduced to their LOWER QUARTILE, as
  GLM-5.2's are: a bf16-rounded hidden state flips a near-tie between
  the 8th and 9th of 256 experts at about one layer-token in eight, and
  where one of the two is held here the position reads ~0.094 beside
  ~0.009 (one position of six in each of the first two chip runs), so no
  limit is on a single position or on a median. (2) ONE WINDOW
  LAYER ALONE over a seeded input: the program's prefill attention and,
  from the ring that prefill hands over, its decode attention at 136
  positions in a row (past a wrap of the ring), per query the relative
  error of the layer's output. (3) The first routed layer alone over the
  held experts (``routed_ffn``), per token. The reference is driven half a
  layer a compiled call, the dense FFN a stretch of rows at a time (a
  layer is 2 GB in float32);
- the traced stretch is also reduced by ``jax.named_scope``, and
  ``decode_bytes`` counts the experts the engine's counters say a step
  touched, the full layers' rows and the ring rows they say it read.

The replica is built in a first CALL, not in the actor's constructor (an
actor whose constructor takes over 120 s never becomes ALIVE). The knee
sweep is ``benchmarks/sweep.py`` with this module as its runner:

    chiprun --timeout 3000 -- python3 -c "import sys; \\
        from benchmarks import sweep; \\
        from benchmarks.runners import serve_swa_moe; \\
        sweep.runner = serve_swa_moe; sys.exit(sweep.main())" \\
        --config mimo-v2-flash-l7-e16-bf16-serve \\
        --traffic codeagent-saturated --rates 2,2.5,3,3.5 --seeds 1,2 \\
        --seconds 30

The readings of every ``ablate`` switch of the reference (what
``correctness.why`` of the configuration quotes) come from this module
run as a script, on the chip: ``python3 -m benchmarks.runners.
serve_swa_moe --config mimo-v2-flash-l7-e16-bf16-serve --seed <n>``.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from benchmarks import common, swa_moe_model
from benchmarks.common import BenchFailure
from benchmarks.runners import serve as base
from benchmarks.runners import serve_mla_moe as mla
from benchmarks.runners.serve import (  # noqa: F401 (sweep.py's runner API)
    measure,
    serve_owner,
    wait_session_gone,
)
from benchmarks.runners.serve_ssm import served_probes

ABLATIONS = (
    {"window": 127}, {"window": 129}, {"no_sink": True},
    {"sink_on_full": True}, {"no_value_scale": True}, {"swap_theta": True},
    {"rotary_all": True}, {"window_grouping": True},
    {"window_attends_all": True}, {"fp8_weights": True},
)
# decode positions probe (2) walks from the ring its prefill hands over:
# more than a window, so that every ring row is overwritten and read again
RING_STEPS = 136
PROBE_ROWS = 8  # positions behind each of probe (1)'s three quartiles
FFN_ROWS = 2048  # rows of the reference's dense FFN a compiled call


def _first(tree):
    """The first layer of a stack of weights."""
    import jax

    return jax.tree.map(lambda a: a[0], tree)


def _make_deployment_class():
    from ray_tpu.serve.llm import LLMServer

    class BenchSwaMoe(mla._make_deployment_class()):
        """``runners/serve_mla_moe.py``'s deployment (recorders,
        ``stream``, trace, scopes and counters) around this kind's model,
        reference and probes."""

        def __init__(self, spec):
            self._spec = spec

        def _cmd_build(self):
            """Everything a replica's constructor does elsewhere, as the
            first call: weights, engine, every bucket warmed through the
            engine, and the one extra program the probes use (one decode
            step that returns its logits). Returns the report."""
            import jax
            import jax.numpy as jnp

            from ray_tpu.models.generation import decode_step_multi

            spec = self._spec
            self.rec = base._Recorder()

            def on_event(event, *_a, **_kw):
                if event.endswith("backend_compile_duration"):
                    self.rec.builds += 1

            jax.monitoring.register_event_duration_secs_listener(on_event)
            eng = spec["engine"]
            cfg = swa_moe_model.transformer_config(spec["model"])
            LLMServer.__init__(
                self,
                lambda: (swa_moe_model.make_bf16_params(cfg, spec["seed"]),
                         cfg),
                max_slots=eng["max_slots"], max_len=eng["max_len"],
                prefill_buckets=tuple(eng["prefill_buckets"]),
            )
            for k in ("block_steps", "burst_block_steps"):
                if getattr(self.engine, k) != eng[k]:
                    raise BenchFailure(f"engine {k} is not {eng[k]}")
            base._instrument(self.engine, self.rec)
            for b in spec["warm_buckets"]:  # through the engine itself
                n = min(b, eng["max_len"] - 2)
                self.engine.generate(np.zeros(n, np.int32), max_new_tokens=2)
            first = self.engine._first_token(
                jnp.zeros(cfg.vocab_size, cfg.dtype), 0.0, 0)
            for k in range(1, eng["max_slots"] + 1):
                np.asarray(jnp.stack([first] * k))
            e = self.engine  # idle: every lane parked, no slot in use
            _logits, e.cache = decode_step_multi(
                e.params, e.tok, e.cache, e.pos, e.config)
            self._trace_dir = None
            self._stretch = {}
            self._kept = {}  # what the programs gave a probe, for ablations
            return self._cmd_report()

        def _cmd_report(self):
            rep = super()._cmd_report()
            rep["dims"] = swa_moe_model.dims(self.engine.config)
            return rep

        def _served(self, prompt, ids, steps):
            """One probe as the window's programs run it, into slot 0 of
            the idle engine. For each of the prompt's last ``PROBE_ROWS``
            lengths, the whole prompt last: the prefill at its bucket and
            one decode step that takes the slot's rows and rings over from
            it (``decode_step_multi``, for its logits; the token the
            prompt holds there, after the whole prompt the engine's own).
            Then ``steps`` more in the long blocks teacher-forced on
            ``ids`` (greedy: the programs give the engine's own tokens
            again, which is checked) and ``PROBE_ROWS`` single steps for
            their logits."""
            import jax.numpy as jnp

            from ray_tpu.models.generation import (
                decode_block,
                decode_step_multi,
                prefill_into_slot,
            )

            eng, n = self.engine, len(prompt)
            s = eng.stats()
            if s["active"] or s["pending"]:
                raise BenchFailure("a probe needs an idle engine")

            def lanes(value):  # parked lanes, slot 0 at ``value``
                return eng._lanes(jnp.int32).at[0].set(value)

            def step(tok, pos):
                logits, eng.cache = decode_step_multi(
                    eng.params, tok, eng.cache, pos, eng.config)
                return logits[0]

            at_prefill, at_first = [], []
            for m in range(n - PROBE_ROWS + 1, n + 1):
                padded = np.zeros((1, eng._bucket_for(m)), np.int32)
                padded[0, :m] = prompt[:m]
                logits, eng.cache = prefill_into_slot(
                    eng.params, jnp.asarray(padded), jnp.int32(m),
                    jnp.int32(0), eng.cache, eng.config)
                at_prefill.append(logits)
                at_first.append(step(
                    lanes(ids[0] if m == n else prompt[m]), lanes(m)))
            tok, pos, counts = lanes(ids[1]), lanes(n + 1), lanes(2)
            zeros_f, zeros_i = eng._lanes(jnp.float32), eng._lanes(jnp.int32)
            fed = [int(ids[0]), int(ids[1])]
            for _ in range(steps // eng.block_steps):
                toks, eng.cache, tok, pos, counts, _st = decode_block(
                    eng.params, eng.cache, tok, pos, zeros_f, zeros_i,
                    counts, eng.config, eng.block_steps)
                fed += np.asarray(toks[0]).tolist()
            replayed = fed == list(ids[:len(fed)])
            at_decode = []
            for _ in range(PROBE_ROWS):
                at_decode.append(step(tok, pos))
                tok, pos = lanes(ids[len(fed)]), pos.at[0].add(1)
                fed.append(int(ids[len(fed)]))
            return {"prefill": jnp.stack(at_prefill),
                    "first": jnp.stack(at_first),
                    "decode": jnp.stack(at_decode), "fed": fed[:-1],
                    "replayed": replayed}

        def _reference(self, tokens, rows, ablate):
            """``reference.forward_logits`` computed half a layer a
            compiled call, each slicing its layer out of the served stacks
            inside the call, so that it fits beside the engine. Returns
            the logits at ``rows``."""
            import jax
            import jax.numpy as jnp

            from benchmarks import reference_swa_moe as ref

            params = self.engine.params
            hp = swa_moe_model.reference_constants(self.engine.config)
            with jax.default_matmul_precision("highest"):
                @jax.jit
                def attend(x, stack, i, sink):
                    lp = jax.tree.map(lambda a: a[i], stack)
                    return ref.attend(x, lp, hp, ablate, sink)

                @jax.jit
                def ffn(x, stack, i):
                    lp = jax.tree.map(lambda a: a[i], {
                        k: v for k, v in stack.items()
                        if k not in ("attn", "swa")})
                    return ref.ffn(x, lp, hp, ablate)

                x = jax.jit(ref.embed)(params, tokens)
                sink = ref.first_window_sink(params)
                for name, i in ref.layers_in_order(params, hp):
                    i = jnp.int32(i)
                    x = attend(x, params[name], i, sink)
                    # per token: a stretch of rows at a time is exact
                    x = jnp.concatenate([
                        ffn(x[a:a + FFN_ROWS], params[name], i)
                        for a in range(0, x.shape[0], FFN_ROWS)])
                return jax.jit(lambda p, x: ref.head(p, x, hp, ablate))(
                    params, x[np.asarray(rows)])

        def _cmd_reference(self, prompt, ids, steps, ablate=None):
            """Probe (1) of one prompt against the plain reference
            (``ablate``: against a deliberately wrong one): for each of the
            three kinds of logits the lower quartile over its
            ``PROBE_ROWS`` positions of their distance (and every
            position's, sorted, for the note)."""
            import jax.numpy as jnp

            from benchmarks import reference_swa_moe as ref

            key = ("served", tuple(prompt[:8]))
            if key not in self._kept:
                self._kept[key] = self._served(prompt, ids, steps)
            got, n = self._kept[key], len(prompt)
            tokens = jnp.asarray(list(prompt) + got["fed"], jnp.int32)
            k, end = PROBE_ROWS, len(tokens)
            rows = {"prefill": range(n - k, n), "first": range(n - k + 1,
                                                               n + 1),
                    "decode": range(end - k, end)}
            want = self._reference(
                tokens, [r for kind in rows.values() for r in kind],
                dict(ablate or {}))
            size = jnp.sqrt(jnp.mean(want ** 2, -1))  # the logits' own RMS
            out = {}
            for i, kind in enumerate(rows):
                rel = np.asarray([
                    float(ref.vector_distance(got[kind][j], want[i * k + j])[
                        1] / size[i * k + j]) for j in range(k)])
                out[kind + "_rel"] = float(np.quantile(rel, 0.25))
                out[kind + "_at"] = np.round(np.sort(rel), 4).tolist()
            top2 = jnp.sort(want, -1)[:, -2:]
            return {
                **out, "logits_rms": float(size[-1]),
                "top2_gap": np.asarray(top2[:, 1] - top2[:, 0]).tolist(),
                "replayed": got["replayed"], "tokens": len(tokens)}

        def _seeded_input(self, seed, rows):
            import jax

            cfg = self.engine.config
            return jax.random.normal(jax.random.key(seed & 0x7FFFFFFF),
                                     (rows, cfg.d_model)).astype(cfg.dtype)

        def _cmd_window_layer(self, seed, rows, ablate=None):
            """Probe (2): the first window layer's mixer alone over a
            seeded (normed) input of ``rows`` rows, as the programs run
            it. The prefill's attention over the first ``rows - 200``
            rows (a length that is no multiple of the window, padded to
            ``rows``), which also hands over the ring; then, from that
            ring, the decode attention at the next ``RING_STEPS``
            positions one after the other. Against the reference's
            attention over the same input: per query the relative error
            of the layer's output, the two stretches apart."""
            import jax
            import jax.numpy as jnp

            from benchmarks import reference_swa_moe as ref
            from ray_tpu.models import generation as gen
            from ray_tpu.models import transformer as tf
            from ray_tpu.ops.decode_attention import slot_schedule

            cfg = self.engine.config
            swa = self.engine.params["window_layers"]["swa"]
            x = self._seeded_input(seed, rows)
            n0, w = rows - 200, cfg.window
            h_kv = cfg.mha_kind(True)[0]

            @jax.jit
            def program(x, swa):
                wp, h = _first(swa), x[None]
                ring = {"state": {
                    "wk": jnp.zeros((1, 1, w, h_kv * cfg.d_head), cfg.dtype),
                    "wv": jnp.zeros((1, 1, w, h_kv * cfg.v_dim), cfg.dtype)}}
                out, ring = tf._mha_mixer(
                    h, wp, cfg, jnp.arange(rows),
                    gen._prefill_window_attn(ring, 0, jnp.int32(n0), cfg),
                    True)

                def step(ring, t):
                    pos = t[None]
                    attn = gen._decode_window_attn(
                        ring, 0, pos, jnp.arange(1), cfg, slot_schedule(
                            gen.ring_rows(pos, w), w, w))
                    o, ring = tf._mha_mixer(
                        jax.lax.dynamic_slice_in_dim(h, t, 1, 1), wp, cfg,
                        pos[:, None], attn, True)
                    return ring, o[0, 0]

                _, dec = jax.lax.scan(step, ring,
                                      n0 + jnp.arange(RING_STEPS))
                return out[0, :n0], dec

            if ("window", seed) not in self._kept:
                self._kept["window", seed] = tuple(
                    a.astype(jnp.float32) for a in program(x, swa))
            got_prefill, got_ring = self._kept["window", seed]
            hp = swa_moe_model.reference_constants(cfg)
            ablate = dict(ablate or {})
            with jax.default_matmul_precision("highest"):
                want = jax.jit(lambda x, swa: ref.attention(
                    x.astype(jnp.float32), _first(swa), hp, ablate, "W"))(
                        x[:n0 + RING_STEPS], swa)

            def err(got, want):
                return jnp.linalg.norm(got - want, axis=-1) / jnp.linalg.norm(
                    want, axis=-1)

            pre, ring = err(got_prefill, want[:n0]), err(got_ring, want[n0:])
            return {"prefill_median": float(jnp.median(pre)),
                    "prefill_q90": float(jnp.quantile(pre, 0.9)),
                    "ring_median": float(jnp.median(ring)),
                    "ring_largest": float(ring.max())}

        def _cmd_routed_layer(self, seed, tokens, ablate=None):
            """Probe (3): the first routed layer alone over the held
            share, as the program runs it (``routed_ffn``), against the
            reference's loop over the held experts on the same seeded
            input: per token the relative error of the layer's output."""
            import jax
            import jax.numpy as jnp

            from benchmarks import reference_swa_moe as ref
            from ray_tpu.ops.moe import routed_ffn

            cfg = self.engine.config
            moe = self.engine.params["window_layers"]["moe"]
            x = self._seeded_input(seed, tokens)

            @jax.jit
            def program(x, moe):
                held = {k: moe[k] for k in ("wg", "wi", "wo")}
                rest = {k: v for k, v in moe.items() if k not in held}
                return routed_ffn(
                    x, {**_first(rest), **held, "layer": 0},
                    top_k=cfg.moe_top_k, route_scale=cfg.moe_route_scale,
                    first_expert=cfg.moe_first_expert)[0]

            if ("routed", seed) not in self._kept:
                self._kept["routed", seed] = program(x, moe).astype(
                    jnp.float32)
            got = self._kept["routed", seed]
            hp = swa_moe_model.reference_constants(cfg)
            ablate = dict(ablate or {})
            with jax.default_matmul_precision("highest"):
                want = jax.jit(lambda x, moe: ref.routed_experts(
                    x.astype(jnp.float32), _first(moe), hp, ablate))(x, moe)
            # a token that chose no held expert: both sides give 0
            size = jnp.linalg.norm(want, axis=-1)
            err = jnp.where(size > 0, jnp.linalg.norm(got - want, axis=-1)
                            / jnp.maximum(size, 1e-30),
                            jnp.linalg.norm(got, axis=-1))
            return {"median": float(jnp.median(err[size > 0])),
                    "largest": float(err.max()),
                    "share_over_5pct": float((err > 0.05).mean())}

    return BenchSwaMoe


def _program_has_the_block() -> None:
    """Before any process starts: a program from before this kind existed
    cannot describe the block, and says so at once (importing the module
    imports JAX and initialises no backend)."""
    import dataclasses

    from ray_tpu.models.transformer import TransformerConfig

    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    if not {"layer_types", "window", "window_kv_heads", "value_scale"
            } <= fields:
        raise BenchFailure(
            "this program's TransformerConfig cannot describe window "
            "layers beside full attention layers: the cell cannot run")


def start_replica(ctx, cfg: Dict, warm_buckets) -> tuple:
    """``runners/serve.py``'s, with this kind's deployment, built in its
    first call."""
    _program_has_the_block()
    import ray_tpu
    from ray_tpu import serve

    model = dict(cfg)
    if ctx["rehearsal"]:
        model.update(cfg["rehearsal"])
        warm_buckets = model["traffic"]["warm_buckets"]
    run_cfg = model["run"]
    session_dir = ray_tpu.init(
        num_cpus=8, num_tpus=ctx["chips"])["session_dir"]
    try:
        dep = serve.deployment(
            num_replicas=1, ray_actor_options=dict(run_cfg["replica"]),
        )(_make_deployment_class())
        handle = serve.run(dep.bind({
            "model": model, "engine": run_cfg["engine"],
            "seed": ctx["seed"], "warm_buckets": list(warm_buckets),
        }))
        rep = handle.remote("build").result(timeout=3000)
        rep["engine"] = run_cfg["engine"]
        ctx["check_device"](rep)
    except BaseException:  # no TPU, wrong device: leave no process behind
        ray_tpu.shutdown()
        wait_session_gone(session_dir)
        raise
    return handle, rep, session_dir, model


def probes(handle, ctx, model, served: Dict,
           ablate: Optional[Dict] = None) -> Dict:
    """The three probes against the plain reference (``ablate``: against
    a deliberately wrong one). Probe (1) is reduced to the larger reading
    of the two prompts: every limit must hold for both."""
    size, tol = model["run"]["probe"], model["correctness"]
    rows = [handle.remote("reference", p.tolist(), ids, size["decode_steps"],
                          ablate).result(timeout=2400)
            for p, ids in zip(served["prompts"], served["ids"])]
    window = handle.remote("window_layer", ctx["seed"] + 3,
                           size["window_layer_rows"], ablate).result(
                               timeout=2400)
    routed = handle.remote("routed_layer", ctx["seed"] + 2,
                           size["routed_layer_tokens"], ablate).result(
                               timeout=2400)
    out = {
        "prefill_rel": max(r["prefill_rel"] for r in rows),
        "first_rel": max(r["first_rel"] for r in rows),
        "decode_rel": max(r["decode_rel"] for r in rows),
        "replayed": all(r["replayed"] for r in rows),
        "by_prompt": [{k: (round(v, 5) if isinstance(v, float) else v)
                       for k, v in r.items() if k != "top2_gap"}
                      for r in rows],
        "median_top2_gap": mla._quantile(
            [g for r in rows for g in r["top2_gap"]], 0.5),
        "window_layer": window, "routed_layer": routed}
    out["ok"] = bool(
        out["prefill_rel"] <= tol["prefill_rel_tol"]
        and out["first_rel"] <= tol["first_rel_tol"]
        and out["decode_rel"] <= tol["decode_rel_tol"]
        and window["prefill_median"] <= tol["window_prefill_median_tol"]
        and window["prefill_q90"] <= tol["window_prefill_q90_tol"]
        and window["ring_median"] <= tol["window_ring_median_tol"]
        and window["ring_largest"] <= tol["window_ring_largest_tol"]
        and routed["median"] <= tol["routed_layer_median_tol"]
        and routed["share_over_5pct"] <= tol["routed_layer_share_tol"]
        and out["replayed"])
    return out


def trace_scalars(tr: Dict, model_dims: Dict, eng: Dict) -> Dict:
    """``runners/serve.py``'s device times of the traced decode blocks and
    prefills, with this model's bytes: every traced step is charged the
    experts, the full layers' rows and the ring rows that the engine's
    counters say a step of that stretch touched and read."""
    out = base.trace_scalars(tr, mla._NO_GPTJ_BYTES, eng)
    st = tr.get("stretch_stats") or {}
    need = ("moe_experts_touched", "attn_rows_read", "window_rows_read")
    if st.get("steps") and all(k in st for k in need):
        per_step = {k: st[k] / st["steps"] for k in need}
        out["decode_bytes"] = out["decode_steps"] * \
            swa_moe_model.decode_step_bytes(
                model_dims, *(per_step[k] for k in need))
        out["decode_experts_touched_per_step"] = per_step[need[0]]
        out["decode_full_rows_per_step"] = per_step[need[1]]
        out["decode_ring_rows_per_step"] = per_step[need[2]]
    else:
        out.pop("decode_bytes", None)
    return out


def run(ctx) -> Dict:
    cfg, mix, seconds = ctx["config"], ctx["traffic"], ctx["seconds"]
    import ray_tpu

    if ctx["rehearsal"]:  # the host walks the mix at the tiny engine's sizes
        mix = dict(mix, **cfg["rehearsal"].get("traffic", {}))
    handle, rep, session_dir, model = start_replica(
        ctx, cfg, mix["warm_buckets"])
    try:
        served = served_probes(handle, ctx, rep, model)
        pr = probes(handle, ctx, model, served)
        m = measure(handle, ctx, mix, rep, seconds, ctx["trace"])
        again = base._collect(handle.stream(
            served["prompts"][0], max_new_tokens=len(served["ids"][0])))
        facts = dict(m)
        # per expert HELD: the counters' capacity is the share's
        facts["scalars"].update(mla.moe_scalars(m["backlog"], {
            "moe_experts": rep["dims"]["moe_experts_held"]}))
        if ctx["trace"]:
            tr = handle.remote("trace_reduce", ctx["keep_trace"],
                               ctx["rehearsal"]).result(timeout=2400)
            facts["trace"] = tr
            facts["scalars"].update(
                trace_scalars(tr, rep["dims"], rep["engine"]))
        final = handle.remote("report").result(timeout=60)
    finally:
        ray_tpu.shutdown()
        wait_session_gone(session_dir)
    s = facts["samples"]
    e2e = {"setup_s": m["t0"] - ctx["t_start"],
           "tokens_per_s": m["scalars"]["tokens_per_s"]}
    if s["tpot_ms"]:
        e2e["tpot_p50_ms"] = common.percentile(s["tpot_ms"], 50)
    facts["scalars"]["peak_bytes"] = final["peak_bytes"]
    checks = {
        "probes_match_reference": pr["ok"],
        "repeat_identical": again == served["ids"][0],
        "no_build_in_window": m["builds_in_window"] == 0,
        "none_failed": m["failed"] == 0,
    }
    facts.update(
        e2e=e2e, checks=checks, device=final, model_dims=rep["dims"],
        note={
            "offered": m["offered"], "cut": m["cut"],
            "samples": {k: len(v) for k, v in s.items()},
            "backlog": m["backlog"],
            "probe": {k: v for k, v in pr.items() if k != "ok"},
            "builds_in_window": m["builds_in_window"],
            "tokens_per_s": m["scalars"]["tokens_per_s"],
            "ttft_ms": {q: common.percentile(s["ttft_ms"], q)
                        for q in (50, 90, 99)} if s["ttft_ms"] else None,
            "tpot_ms": {q: common.percentile(s["tpot_ms"], q)
                        for q in (50, 90)} if s["tpot_ms"] else None,
            # traced runs: device seconds by scope, per program
            "scope_s": (facts.get("trace") or {}).get("scope_s"),
        })
    return facts


def main() -> int:
    """The readings of every ``ablate`` switch, on the chip: the served
    outputs of one replica against the reference computed wrong in each
    way in turn. Prints one JSON row a switch; never a result line."""
    import argparse
    import json
    import os
    import time

    from benchmarks.run import Manifest

    p = argparse.ArgumentParser(description=main.__doc__)
    p.add_argument("--config", default="mimo-v2-flash-l7-e16-bf16-serve")
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--rehearse-cpu", action="store_true")
    args = p.parse_args()
    man = Manifest(os.path.join(common.ROOT, "BENCHMARK.json"))
    cfg = man.config(args.config)
    common.prepare_env(args.rehearse_cpu)
    ctx = {"t_start": time.time(), "seed": args.seed, "chips": 1,
           "rehearsal": args.rehearse_cpu,
           "check_device": lambda rep: args.rehearse_cpu
           or common.peaks_for(rep["kind"])}
    import ray_tpu

    run_cfg = dict(cfg, **(cfg["rehearsal"] if args.rehearse_cpu else {}))[
        "run"]
    buckets = sorted({min(b for b in run_cfg["engine"]["prefill_buckets"]
                          if b >= n)
                      for n in run_cfg["probe"]["prompt_tokens"]})
    handle, rep, session_dir, model = start_replica(ctx, cfg, buckets)
    try:
        served = served_probes(handle, ctx, rep, model)
        for ablate in ({},) + ABLATIONS:
            row = probes(handle, ctx, model, served, ablate)
            print(json.dumps({"ablate": ablate, **row}), flush=True)
    finally:
        ray_tpu.shutdown()
        wait_session_gone(session_dir)
    return common.REHEARSAL_RC if args.rehearse_cpu else 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
