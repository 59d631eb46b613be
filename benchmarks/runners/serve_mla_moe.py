"""Serving runner for configurations of kind ``serve_mla_moe`` (latent
attention, dropless routed experts; GLM-4.7-Flash's block): the same one
replica of ``serve.LLMServer`` behind ``serve.run``, the same load
generator, window and trace reduction as ``runners/serve.py`` (``measure``,
``trace_scalars``, ``serve_owner``, ``wait_session_gone`` and the
deployment's recorders and control calls are imported from there; nothing
there is edited). What differs is the model's side of the bench:

- the config object, the bf16 weights from the seed and the byte function
  come from ``benchmarks/mla_moe_model.py``;
- ``correct`` holds the served path to ``benchmarks/reference_mla_moe.py``
  at the cell's own sizes (``run.probe`` of the configuration: four seeded
  prompts of 1,536 tokens, 16 new tokens each): the served token's logit
  against the reference's largest at every decoded position, and the whole
  logit vector that ``prefill_into_slot`` (the timed program at the timed
  bucket) returns for the prompt against the reference's, by largest and
  by root-mean-square difference (``correctness`` of the configuration);
- the traced stretch is also reduced by ``jax.named_scope``
  (``readers/scope_time.py``), and ``decode_bytes`` counts the experts the
  engine's counters say were touched in that stretch.

The knee sweep is ``benchmarks/sweep.py`` with this module as its runner:

    chiprun --timeout 2400 -- python3 -c "import sys; \\
        from benchmarks import sweep; \\
        from benchmarks.runners import serve_mla_moe; \\
        sweep.runner = serve_mla_moe; sys.exit(sweep.main())" \\
        --config glm47flash-l8-bf16-serve --traffic reason-saturated \\
        --rates 3,4,5 --seeds 1,2 --seconds 30
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from benchmarks import common, mla_moe_model
from benchmarks.common import BenchFailure
from benchmarks.runners import serve as base
from benchmarks.runners.serve import (  # noqa: F401 (sweep.py's runner API)
    measure,
    serve_owner,
    wait_session_gone,
)

# base.trace_scalars adds up GPT-J's bytes from these; here they count for
# nothing and ``decode_bytes`` is set from mla_moe_model.decode_step_bytes
_NO_GPTJ_BYTES = dict.fromkeys(
    ("d_model", "n_heads", "d_head", "d_ff", "n_layers", "vocab_size"), 0)


def _make_deployment_class():
    from ray_tpu.serve.llm import LLMServer

    class BenchMlaMoe(base._make_deployment_class()):
        """``runners/serve.py``'s deployment (recorders, ``stream``, trace
        and counter calls) around this kind's model and reference."""

        def __init__(self, spec):
            import jax
            import jax.numpy as jnp

            self.rec = base._Recorder()

            def on_event(event, *_a, **_kw):
                if event.endswith("backend_compile_duration"):
                    self.rec.builds += 1

            jax.monitoring.register_event_duration_secs_listener(on_event)
            eng = spec["engine"]
            cfg = mla_moe_model.transformer_config(spec["model"])
            LLMServer.__init__(
                self,
                lambda: (mla_moe_model.make_bf16_params(cfg, spec["seed"]),
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
            self._trace_dir = None
            self._stretch = {}

        def _cmd_report(self):
            rep = super()._cmd_report()
            rep["dims"] = mla_moe_model.dims(self.engine.config)
            return rep

        def _cmd_reference(self, prompt, ids, positions):
            """One probe against the plain reference over the same weights:
            margins of the served tokens at the decoded positions, and the
            distance of the logit vectors that ``prefill_into_slot``
            returns for the prompt cut after its last ``positions``
            tokens in turn (the same program and bucket every time)."""
            import jax
            import jax.numpy as jnp

            from benchmarks import reference_mla_moe as reference
            from ray_tpu.models.generation import prefill_into_slot

            eng, n = self.engine, len(prompt)
            s = eng.stats()
            if s["active"] or s["pending"]:
                raise BenchFailure("a probe needs an idle engine")
            # the engine's loop is idle and touches no cache: the prompt is
            # run again by the program the window times, at its bucket,
            # into slot 0 (free: its next prefill overwrites it)
            padded = np.zeros((1, eng._bucket_for(n)), np.int32)
            padded[0, :n] = prompt
            served = []
            for j in range(positions):
                logits, eng.cache = prefill_into_slot(
                    eng.params, jnp.asarray(padded), jnp.int32(n - j),
                    jnp.int32(0), eng.cache, eng.config)
                served.append(logits)
            seq = jnp.asarray(list(prompt) + list(ids[:-1]), jnp.int32)
            hp = mla_moe_model.reference_constants(eng.config)
            with jax.default_matmul_precision("highest"):
                want = jax.jit(lambda p, t: reference.forward_logits(
                    p, t, hp, last=positions - 1 + len(ids)))(
                        eng.params, seq)
            tail = want[positions - 1:]  # the prompt's end, then decoded
            margin = reference.served_token_margin(
                tail, jnp.asarray(ids, jnp.int32))
            dist = [reference.vector_distance(v, want[positions - 1 - j])
                    for j, v in enumerate(served)]
            top2 = jnp.sort(tail, -1)[:, -2:]
            return {"margin": np.asarray(margin).tolist(),
                    "top2_gap": np.asarray(top2[:, 1] - top2[:, 0]).tolist(),
                    "prefill_max": [float(m) for m, _r in dist],
                    "prefill_rms": [float(r) for _m, r in dist]}

        def _cmd_routed_layer(self, seed, tokens):
            """The first expert layer alone, as the program runs it
            (``routed_ffn`` over the served weights), against the
            reference's loop over experts on the same seeded input: per
            token the relative error of the layer's output. A choice of
            experts that differs shows as a large error of that token."""
            import jax
            import jax.numpy as jnp

            from benchmarks import reference_mla_moe as reference
            from ray_tpu.ops.moe import routed_ffn

            cfg = self.engine.config
            moe = self.engine.params["layers"]["moe"]
            hp = mla_moe_model.reference_constants(cfg)
            x = jax.random.normal(jax.random.key(seed & 0x7FFFFFFF),
                                  (tokens, cfg.d_model)).astype(cfg.dtype)

            def first(tree):
                return jax.tree.map(lambda a: a[0], tree)

            @jax.jit
            def program(x, moe):
                held = {k: moe[k] for k in ("wg", "wi", "wo")}
                rest = {k: v for k, v in moe.items() if k not in held}
                return routed_ffn(
                    x, {**first(rest), **held, "layer": 0},
                    top_k=cfg.moe_top_k, route_scale=cfg.moe_route_scale)[0]

            got = program(x, moe).astype(jnp.float32)
            with jax.default_matmul_precision("highest"):
                want = jax.jit(lambda x, moe: reference.routed_experts(
                    x.astype(jnp.float32), first(moe), hp, {}))(x, moe)
            err = jnp.linalg.norm(got - want, axis=-1) / jnp.linalg.norm(
                want, axis=-1)
            return {"median": float(jnp.median(err)),
                    "largest": float(err.max()),
                    "share_over_5pct": float((err > 0.05).mean())}

        def _cmd_trace_start(self, trace_dir):
            # the counters are read INSIDE the traced stretch, next to its
            # edges: the profiler takes seconds to start and to hand its
            # trace over, the engine runs on meanwhile, and a house that
            # fills or empties in those seconds is not the traced one
            t = super()._cmd_trace_start(trace_dir)
            self._stretch["start"] = self.engine.stats()
            return t

        def _cmd_trace_stop(self):
            self._stretch["stop"] = self.engine.stats()
            return super()._cmd_trace_stop()

        def _compiled_texts(self):
            """The compiled text of the traced programs AS THE ENGINE
            RUNS THEM, for their scopes (``readers/scope_time.py`` pairs a
            traced operation with its scope by instruction name): the
            decode blocks, and of ``prefill_into_slot`` the fused
            admission form (lanes, a temperature and a seed), every
            scalar a numpy value of one dtype as ``LLMEngine._admit``
            hands them over. Compiled again after the window; the compile
            cache answers."""
            import jax.numpy as jnp

            from ray_tpu.models.generation import (
                decode_block,
                prefill_into_slot,
            )

            eng = self.engine
            lanes = (eng.tok, eng.pos, eng.temps, eng.seeds, eng.counts)
            blocks = [decode_block.lower(
                eng.params, eng.cache, *lanes, eng.config, steps)
                for steps in {eng.burst_block_steps, eng.block_steps}]
            prefills = [prefill_into_slot.lower(
                eng.params, jnp.zeros((1, b), jnp.int32), np.int32(1),
                np.int32(0), eng.cache, eng.config, lanes, np.float32(0.0),
                np.int32(0)) for b in eng.buckets]
            return {"decode_block": [x.compile().as_text() for x in blocks],
                    "prefill_into_slot": [x.compile().as_text()
                                          for x in prefills]}

        def _cmd_trace_reduce(self, keep_copy, rehearsal=False):
            from benchmarks import trace
            from benchmarks.readers import scope_time

            red = super()._cmd_trace_reduce(keep_copy, rehearsal)
            red["scope_s"] = scope_time.scope_seconds(
                trace.find_xplane(self._trace_dir), self._compiled_texts())
            a, b = self._stretch["start"], self._stretch["stop"]
            red["stretch_stats"] = {
                k: b[k] - a[k] for k in b
                if isinstance(b[k], (int, float)) and k in a}
            return red

    return BenchMlaMoe


def _program_has_the_block() -> None:
    """Before any process starts: a program from before this kind existed
    cannot describe the block, and says so at once (importing the module
    imports JAX and initialises no backend)."""
    import dataclasses

    from ray_tpu.models.transformer import TransformerConfig

    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    if not {"mixer", "moe_impl", "n_dense_layers"} <= fields:
        raise BenchFailure(
            "this program's TransformerConfig cannot describe latent "
            "attention and dropless routed experts: the cell cannot run")


def start_replica(ctx, cfg: Dict, warm_buckets) -> tuple:
    """``runners/serve.py``'s, with this kind's deployment."""
    _program_has_the_block()
    import ray_tpu
    from ray_tpu import serve

    model = dict(cfg)
    if ctx["rehearsal"]:
        model.update(cfg["rehearsal"])
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
        rep = handle.remote("report").result(timeout=1100)
        rep["engine"] = run_cfg["engine"]
        ctx["check_device"](rep)
    except BaseException:  # no TPU, wrong device: leave no process behind
        ray_tpu.shutdown()
        wait_session_gone(session_dir)
        raise
    return handle, rep, session_dir, model


def _quantile(xs, q: float) -> float:
    return common.percentile(xs, 100.0 * q)


def probes(handle, ctx, rep, model) -> Dict:
    """Seeded prompts at the cell's sizes, each alone through the whole
    served path, then held to the plain reference. With random weights a
    bf16-rounded hidden state flips a near-tie between the k-th and the
    next expert at about a third of the positions, and a flipped position's
    logits move by 0.1-0.8 RMS; the others sit at 0.022-0.028 (measured,
    ``correctness.why`` of the configuration). So the limits are on robust
    statistics over all probed positions: the MEDIAN margin of the served
    tokens and the LOWER QUARTILE of the prefill vectors' distances, which
    a flip at under half (three quarters) of the positions leaves alone and
    which every systematic omission moves; and on the routed layer alone,
    where the same input leaves no rounded hidden state to flip a choice."""
    size, tol = model["run"]["probe"], model["correctness"]
    rng = np.random.default_rng(ctx["seed"] + 1)
    out = {"prompts": [], "ids": []}
    margins, rms, largest, gaps = [], [], [], []
    for _ in range(size["n"]):
        p = rng.integers(0, rep["dims"]["vocab_size"],
                         size["prompt_tokens"], dtype=np.int32)
        ids = base._collect(handle.stream(
            p, max_new_tokens=size["new_tokens"]))
        if len(ids) != size["new_tokens"]:
            raise BenchFailure(f"probe returned {len(ids)} ids")
        base._wait_idle(handle)
        ref = handle.remote("reference", p.tolist(), ids,
                            size["prefill_positions"]).result(timeout=1500)
        out["prompts"].append(p)
        out["ids"].append(ids)
        margins += ref["margin"]
        rms += ref["prefill_rms"]
        largest += ref["prefill_max"]
        gaps += ref["top2_gap"]
    layer = handle.remote("routed_layer", ctx["seed"] + 2,
                          size["routed_layer_tokens"]).result(timeout=900)
    out.update(
        margin_median=_quantile(margins, 0.5),
        margin_zero_share=sum(m == 0 for m in margins) / len(margins),
        margin_largest=max(margins),
        prefill_rms_q25=_quantile(rms, 0.25),
        prefill_max_q25=_quantile(largest, 0.25),
        prefill_rms=sorted(round(x, 4) for x in rms),
        median_top2_gap=_quantile(gaps, 0.5), routed_layer=layer)
    out["ok"] = bool(
        out["margin_median"] <= tol["margin_median_tol"]
        and out["prefill_rms_q25"] <= tol["prefill_rms_q25_tol"]
        and out["prefill_max_q25"] <= tol["prefill_max_q25_tol"]
        and layer["median"] <= tol["routed_layer_median_tol"]
        and layer["share_over_5pct"] <= tol["routed_layer_share_tol"])
    return out


def trace_scalars(tr: Dict, model_dims: Dict, eng: Dict) -> Dict:
    """``runners/serve.py``'s device times of the traced decode blocks and
    prefills, with this model's bytes: every traced step is charged the
    mean latent rows of the live lanes (from the dispatch marks) and the
    experts the engine's counters say a step of that stretch touched."""
    out = base.trace_scalars(tr, _NO_GPTJ_BYTES, eng)
    marks = [m["stats"] for m in tr["marks"]
             if m["name"] == "bench.dispatch"]
    st = tr.get("stretch_stats") or {}
    if marks and st.get("steps") and "moe_experts_touched" in st:
        steps = sum(m["steps"] for m in marks)
        rows = sum(m["steps"] * (m["kv_rows"] + 0.5 * (m["steps"] - 1)
                                 * m["live"]) for m in marks) / steps
        out["decode_bytes"] = out["decode_steps"] * \
            mla_moe_model.decode_step_bytes(
                model_dims, rows, st["moe_experts_touched"] / st["steps"])
        out["decode_latent_rows"] = rows
        out["decode_experts_touched_per_step"] = (
            st["moe_experts_touched"] / st["steps"])
    else:
        out.pop("decode_bytes", None)
    return out


def moe_scalars(backlog: Dict, model_dims: Dict) -> Dict:
    """Mean and fullest expert load per expert layer and step over the
    window's second half, from the engine's counters (none where the
    program has none)."""
    mid, end = backlog.get("mid") or {}, backlog.get("end") or {}
    keys = ("moe_assignments", "moe_experts_capacity", "moe_max_load")
    if any(k not in mid or k not in end for k in keys):
        return {}
    d = {k: end[k] - mid[k] for k in keys}
    if not d["moe_experts_capacity"]:
        return {}
    layer_steps = d["moe_experts_capacity"] / model_dims["moe_experts"]
    return {"moe_mean_load": d["moe_assignments"] / d["moe_experts_capacity"],
            "moe_fullest_load": d["moe_max_load"] / layer_steps}


def run(ctx) -> Dict:
    cfg, mix, seconds = ctx["config"], ctx["traffic"], ctx["seconds"]
    import ray_tpu

    handle, rep, session_dir, model = start_replica(
        ctx, cfg, mix["warm_buckets"])
    try:
        pr = probes(handle, ctx, rep, model)
        m = measure(handle, ctx, mix, rep, seconds, ctx["trace"])
        again = base._collect(handle.stream(
            pr["prompts"][0], max_new_tokens=len(pr["ids"][0])))
        facts = dict(m)
        if ctx["trace"]:
            tr = handle.remote("trace_reduce", ctx["keep_trace"],
                               ctx["rehearsal"]).result(timeout=600)
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
    facts["scalars"].update(moe_scalars(m["backlog"], rep["dims"]))
    checks = {
        "probes_match_reference": pr["ok"],
        "repeat_identical": again == pr["ids"][0],
        "no_build_in_window": m["builds_in_window"] == 0,
        "none_failed": m["failed"] == 0,
    }
    facts.update(
        e2e=e2e, checks=checks, device=final, model_dims=rep["dims"],
        note={
            "offered": m["offered"], "cut": m["cut"],
            "samples": {k: len(v) for k, v in s.items()},
            "backlog": m["backlog"],
            "probe": {k: v for k, v in pr.items()
                      if k not in ("prompts", "ids", "ok")},
            "builds_in_window": m["builds_in_window"],
            "tokens_per_s": m["scalars"]["tokens_per_s"],
            "ttft_ms": {q: common.percentile(s["ttft_ms"], q)
                        for q in (50, 90, 99)} if s["ttft_ms"] else None,
            # traced runs: device seconds by scope, per program
            "scope_s": (facts.get("trace") or {}).get("scope_s"),
        })
    return facts
