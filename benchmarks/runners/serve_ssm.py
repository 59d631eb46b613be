"""Serving runner for configurations of kind ``serve_ssm`` (state-space
layers with a fixed-size recurrent state a slot beside attention layers
with K/V rows; granite-4.0-h-micro's kind): the same one replica of
``serve.LLMServer`` behind ``serve.run``, the same load generator, window
and trace reduction as ``runners/serve.py`` (``measure``,
``trace_scalars``, ``serve_owner``, ``wait_session_gone`` and the
deployment's recorders and control calls are imported from there, the
scope reduction and the stretch's counters from ``runners/
serve_mla_moe.py``; nothing there is edited). What differs is the model's
side of the bench:

- the config object, the bf16 weights from the seed and the byte function
  come from ``benchmarks/ssm_model.py``;
- ``correct`` holds what the timed programs produce at the timed sizes to
  ``benchmarks/reference_ssm.py`` (``run.probe`` and ``correctness`` of
  the configuration), LOGITS and STATES, not tokens. Two seeded prompts,
  one for each of two prefill buckets, go through the whole served path;
  then, on the idle engine, each is run again by the window's own
  programs into slot 0: (a) the logits ``prefill_into_slot`` returns for
  the prompt after its chunked, padded prefill; (b) the logits of the
  first decode step, which takes the state and the convolution's tail over
  from the prefill, and those after 256 more steps of ``decode_block``
  through the slot's rows and state (``decode_step_multi``, the same body,
  to see logits), teacher-forced on the engine's own tokens, against the
  reference's full forward over prompt + answer, all three as the RMS of
  the difference over the RMS of the reference's logits; (c) the recurrent
  state of the first and of the last state-space layer at that point, as
  relative RMS, each under its own limit (the first layer's input is the
  embedding itself, so its state's error is the recurrence's alone; the
  last layer's holds 35 layers of bf16 hidden states as well). The
  reference is driven one layer a compiled call (a layer is 0.3 GB in
  float32; the whole model, 12.8 GB, does not fit beside the engine);
- the traced stretch is also reduced by ``jax.named_scope``, and
  ``decode_bytes`` counts the slot states the engine's counters say a step
  updated and the K/V rows they say it read.

The replica is built in a first CALL, not in the actor's constructor (an
actor whose constructor takes over 120 s never becomes ALIVE; PERF.md
7(n)). The knee sweep is ``benchmarks/sweep.py`` with this module as its
runner:

    chiprun --timeout 3000 -- python3 -c "import sys; \\
        from benchmarks import sweep; \\
        from benchmarks.runners import serve_ssm; \\
        sweep.runner = serve_ssm; sys.exit(sweep.main())" \\
        --config granite4-h-micro-bf16-serve --traffic agent-saturated \\
        --rates 3,3.5,4,4.5 --seeds 1,2 --seconds 30

The readings of every ``ablate`` switch of the reference (what
``correctness.why`` of the configuration quotes) come from this module
run as a script, on the chip: ``python3 -m benchmarks.runners.serve_ssm
--config granite4-h-micro-bf16-serve --seed <n>``.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from benchmarks import common, ssm_model
from benchmarks.common import BenchFailure
from benchmarks.runners import serve as base
from benchmarks.runners import serve_mla_moe as mla
from benchmarks.runners.serve import (  # noqa: F401 (sweep.py's runner API)
    measure,
    serve_owner,
    wait_session_gone,
)

# True: the runner fills in the probe's own prompt length (and bucket)
ABLATIONS = (
    {"state_bf16": True}, {"state_at_bucket_end": True},
    {"drop_conv_tail": True}, {"residual_one": True},
    {"usual_attn_scale": True},
)


def _make_deployment_class():
    from ray_tpu.serve.llm import LLMServer

    class BenchSsm(mla._make_deployment_class()):
        """``runners/serve_mla_moe.py``'s deployment (recorders,
        ``stream``, trace, scopes and counters) around this kind's model,
        reference and probes."""

        def __init__(self, spec):
            self._spec = spec

        def _cmd_build(self):
            """Everything a replica's constructor does elsewhere, as the
            first call: weights, engine, every bucket warmed through the
            engine, and the one extra program the probes use (one decode
            step that returns its logits). A call may take as long as it
            needs; a constructor may not. Returns the report."""
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
            cfg = ssm_model.transformer_config(spec["model"])
            LLMServer.__init__(
                self,
                lambda: (ssm_model.make_bf16_params(cfg, spec["seed"]), cfg),
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
            rep["dims"] = ssm_model.dims(self.engine.config)
            return rep

        def _served(self, prompt, ids, steps):
            """One probe as the window's programs run it, into slot 0 of
            the idle engine: the prefill at its bucket, ``steps`` decode
            step that takes the slot's state over from the prefill
            (``decode_step_multi``, for its logits), ``steps`` more in the
            long blocks teacher-forced on ``ids`` (greedy: the programs
            give the engine's own tokens again, which is checked), one
            more step for its logits. Returns the three logit vectors, the
            tokens fed, the first and the last state-space layer's state
            after them."""
            import jax.numpy as jnp

            from ray_tpu.models.generation import (
                cache_state,
                decode_block,
                decode_step_multi,
                prefill_into_slot,
            )

            eng, n = self.engine, len(prompt)
            s = eng.stats()
            if s["active"] or s["pending"]:
                raise BenchFailure("a probe needs an idle engine")
            padded = np.zeros((1, eng._bucket_for(n)), np.int32)
            padded[0, :n] = prompt
            at_prefill, eng.cache = prefill_into_slot(
                eng.params, jnp.asarray(padded), jnp.int32(n), jnp.int32(0),
                eng.cache, eng.config)
            def lanes(value):  # parked lanes, slot 0 at ``value``
                return eng._lanes(jnp.int32).at[0].set(value)

            at_first, eng.cache = decode_step_multi(
                eng.params, lanes(ids[0]), eng.cache, lanes(n), eng.config)
            tok, pos, counts = lanes(ids[1]), lanes(n + 1), lanes(2)
            zeros_f, zeros_i = eng._lanes(jnp.float32), eng._lanes(jnp.int32)
            fed = [int(ids[0]), int(ids[1])]
            for _ in range(steps // eng.block_steps):
                toks, eng.cache, tok, pos, counts, _st = decode_block(
                    eng.params, eng.cache, tok, pos, zeros_f, zeros_i,
                    counts, eng.config, eng.block_steps)
                fed += np.asarray(toks[0]).tolist()
            at_decode, eng.cache = decode_step_multi(
                eng.params, tok, eng.cache, pos, eng.config)
            ssm = cache_state(eng.cache)["ssm"]
            return {"prefill": at_prefill, "first": at_first[0],
                    "decode": at_decode[0], "fed": fed,
                    "replayed": fed == list(ids[:len(fed)]),
                    "state_first": ssm[0, 0], "state_last": ssm[-1, 0]}

        def _reference(self, tokens, rows, ablate):
            """``reference.forward_logits`` computed one layer a compiled
            call, each slicing its layer out of the served stacks inside
            the call, so that it fits beside the engine. Returns the
            logits at ``rows`` and the first and last state-space layer's
            state after the last token."""
            import jax
            import jax.numpy as jnp

            from benchmarks import reference_ssm as ref

            params = self.engine.params
            hp = ssm_model.reference_constants(self.engine.config)
            seq, real = ref.with_padding(tokens, ablate)
            unseen = jnp.asarray(~real)
            with jax.default_matmul_precision("highest"):
                @jax.jit
                def one(x, stack, i):
                    lp = jax.tree.map(lambda a: a[i], stack)
                    return ref.layer(x, lp, hp, ablate, unseen)

                x = jax.jit(lambda p, t: ref.embed(p, t, hp))(params, seq)
                first = last = None
                for name, i in ref.layers_in_order(params, hp):
                    x, state = one(x, params[name], jnp.int32(i))
                    if state is not None:
                        first, last = (state if first is None else first,
                                       state)
                logits = jax.jit(lambda p, x: ref.head(p, x, hp))(
                    params, x[np.flatnonzero(real)[np.asarray(rows)]])
            return logits, first, last

        def _cmd_reference(self, prompt, ids, steps, ablate=None):
            """One probe against the plain reference (``ablate``: against
            a deliberately wrong one): the distances of the two logit
            vectors and of the two states."""
            import jax.numpy as jnp

            from benchmarks import reference_ssm as ref

            key = ("served", tuple(prompt[:8]))
            if key not in self._kept:
                self._kept[key] = self._served(prompt, ids, steps)
            got, n = self._kept[key], len(prompt)
            ablate = dict(ablate or {})
            if ablate.get("state_at_bucket_end") is True:
                ablate["state_at_bucket_end"] = (
                    n, self.engine._bucket_for(n))
            if ablate.get("drop_conv_tail") is True:
                ablate["drop_conv_tail"] = n
            tokens = jnp.asarray(list(prompt) + got["fed"], jnp.int32)
            want, first, last = self._reference(
                tokens, [n - 1, n, len(tokens) - 1], ablate)
            size = jnp.sqrt(jnp.mean(want ** 2, -1))  # the logits' own RMS
            rel = [float(ref.vector_distance(got[k], want[i])[1] / size[i])
                   for i, k in enumerate(("prefill", "first", "decode"))]
            top2 = jnp.sort(want, -1)[:, -2:]
            return {
                "prefill_rel": rel[0], "first_rel": rel[1],
                "decode_rel": rel[2],
                "decode_max": float(ref.vector_distance(
                    got["decode"], want[2])[0]),
                "state_first": float(ref.state_distance(
                    got["state_first"], first)),
                "state_last": float(ref.state_distance(
                    got["state_last"], last)),
                "logits_rms": float(size[2]),
                "top2_gap": np.asarray(top2[:, 1] - top2[:, 0]).tolist(),
                "replayed": got["replayed"], "tokens": len(tokens)}

    return BenchSsm


def _program_has_the_block() -> None:
    """Before any process starts: a program from before this kind existed
    cannot describe the block, and says so at once (importing the module
    imports JAX and initialises no backend)."""
    import dataclasses

    from ray_tpu.models.transformer import TransformerConfig

    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    if not {"layer_types", "ssm_heads", "residual_scale"} <= fields:
        raise BenchFailure(
            "this program's TransformerConfig cannot describe state-space "
            "layers beside attention layers: the cell cannot run")


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
        rep = handle.remote("build").result(timeout=2400)
        rep["engine"] = run_cfg["engine"]
        ctx["check_device"](rep)
    except BaseException:  # no TPU, wrong device: leave no process behind
        ray_tpu.shutdown()
        wait_session_gone(session_dir)
        raise
    return handle, rep, session_dir, model


def served_probes(handle, ctx, rep, model) -> Dict:
    """The seeded prompts, one a bucket, each alone through the whole
    served path: what the engine itself produced."""
    size = model["run"]["probe"]
    rng = np.random.default_rng(ctx["seed"] + 1)
    out = {"prompts": [], "ids": []}
    for n in size["prompt_tokens"]:
        p = rng.integers(0, rep["dims"]["vocab_size"], n, dtype=np.int32)
        ids = base._collect(handle.stream(
            p, max_new_tokens=size["new_tokens"]))
        if len(ids) != size["new_tokens"]:
            raise BenchFailure(f"probe returned {len(ids)} ids")
        base._wait_idle(handle)
        out["prompts"].append(p)
        out["ids"].append(ids)
    return out


def probes(handle, model, served: Dict,
           ablate: Optional[Dict] = None) -> Dict:
    """Probes (a)-(c) of every served prompt against the plain reference
    (``ablate``: against a deliberately wrong one), reduced to the largest
    reading of each kind: every limit must hold for every prompt."""
    size, tol = model["run"]["probe"], model["correctness"]
    rows = [handle.remote("reference", p.tolist(), ids, size["decode_steps"],
                          ablate).result(timeout=2400)
            for p, ids in zip(served["prompts"], served["ids"])]
    out = {
        "prefill_rel": max(r["prefill_rel"] for r in rows),
        "first_rel": max(r["first_rel"] for r in rows),
        "decode_rel": max(r["decode_rel"] for r in rows),
        "state_first": max(r["state_first"] for r in rows),
        "state_last": max(r["state_last"] for r in rows),
        "replayed": all(r["replayed"] for r in rows),
        "by_prompt": [{k: (round(v, 5) if isinstance(v, float) else v)
                       for k, v in r.items() if k != "top2_gap"}
                      for r in rows],
        "median_top2_gap": mla._quantile(
            [g for r in rows for g in r["top2_gap"]], 0.5)}
    out["ok"] = bool(
        out["prefill_rel"] <= tol["prefill_rel_tol"]
        and out["first_rel"] <= tol["first_rel_tol"]
        and out["decode_rel"] <= tol["decode_rel_tol"]
        and out["state_first"] <= tol["state_first_tol"]
        and out["state_last"] <= tol["state_last_tol"]
        and out["replayed"])
    return out


def trace_scalars(tr: Dict, model_dims: Dict, eng: Dict) -> Dict:
    """``runners/serve.py``'s device times of the traced decode blocks and
    prefills, with this model's bytes: every traced step is charged the
    slot states and the K/V rows that the engine's counters say a step of
    that stretch updated and read."""
    out = base.trace_scalars(tr, mla._NO_GPTJ_BYTES, eng)
    st = tr.get("stretch_stats") or {}
    need = ("state_slots_updated", "attn_rows_read")
    if st.get("steps") and all(k in st for k in need):
        slots = st["state_slots_updated"] / st["steps"] / max(
            1, model_dims["n_ssm_layers"])
        rows = st["attn_rows_read"] / st["steps"]
        out["decode_bytes"] = out["decode_steps"] * \
            ssm_model.decode_step_bytes(model_dims, slots, rows)
        out["decode_slots_updated_per_step"] = slots
        out["decode_kv_rows_per_step"] = rows
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
        pr = probes(handle, model, served)
        m = measure(handle, ctx, mix, rep, seconds, ctx["trace"])
        again = base._collect(handle.stream(
            served["prompts"][0], max_new_tokens=len(served["ids"][0])))
        facts = dict(m)
        if ctx["trace"]:
            tr = handle.remote("trace_reduce", ctx["keep_trace"],
                               ctx["rehearsal"]).result(timeout=1200)
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
    p.add_argument("--config", default="granite4-h-micro-bf16-serve")
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
            row = probes(handle, model, served, ablate)
            print(json.dumps({"ablate": ablate, **row}), flush=True)
    finally:
        ray_tpu.shutdown()
        wait_session_gone(session_dir)
    return common.REHEARSAL_RC if args.rehearse_cpu else 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
