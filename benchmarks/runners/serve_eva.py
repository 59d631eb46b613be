"""Serving runner for configurations of kind ``serve_eva`` (EvaByte's kind:
every layer attends its own window of 2,048 rows exactly and every
earlier window through 128 pooled chunk summaries, so that NO layer keeps
every row; a slot's cache is one contiguous prefix of summaries and then
the open window's tokens, and folds 2,048 rows into 128 each time a
window closes; eight prediction heads, the next byte sampled from head
0): the same one replica of ``serve.LLMServer`` behind ``serve.run``, the
same load generator, window and trace reduction as ``runners/serve.py``
(``measure``, ``serve_owner``, ``wait_session_gone`` and the deployment's
recorders and control calls are imported from there, the scope reduction
and the stretch's counters from ``runners/serve_mla_moe.py``, the served
probes from ``runners/serve_ssm.py``; nothing there is edited). What
differs is the model's side of the bench:

- the config object, the bf16 weights from the seed and the byte function
  come from ``benchmarks/eva_model.py``;
- ``correct`` holds what the timed programs produce at the timed sizes to
  ``benchmarks/reference_eva.py`` (``run.probe`` and ``correctness`` of
  the configuration), LOGITS and SUMMARIES, not tokens. Three seeded
  prompts (one of a few bytes; one a byte short of a window, whose first
  decoded byte fills and closes it; one of two and a half windows, in a
  padded bucket) go through the whole served path; then, on the idle
  engine, each is run again by the window's own programs, NOT alone and
  NOT in slot 0: into two lanes at once (``run.probe.lanes``: the same
  prompt, so that both lanes' windows close in the SAME step) beside a
  live neighbour (``run.probe.neighbour_lane``: two thirds of the prompt
  reversed, which decodes its own bytes and closes nothing meanwhile).
  Both lanes are held to the reference, and the larger reading counts:
  (a) the logits ``prefill_into_slot`` returns for the prompt, all eight
  heads; (b) all eight heads' logits of the first decode step (2,560
  numbers: a root mean square over 320 would wander by 4 %), which takes
  the slot's summaries and rows over from the prefill, and after
  ``decode_steps`` more steps of ``decode_block`` teacher-forced on the
  engine's own tokens, against the reference's full forward over prompt +
  answer, each as the RMS of the difference over the RMS of the
  reference's logits, and the MEAN of these nine readings (three prompts,
  three places: the one number in which a residual stream rounded to bf16
  stands clear of the seeds' scatter); (c) the summaries [k~ | v~] that
  every layer's slot holds of its FIRST closed window, right after a
  prefill that closed one and, for a window that closed during the
  replay, after it, relative RMS. The reference is driven one layer a
  compiled call, a head of the attention at a time;
- the traced stretch is also reduced by ``jax.named_scope`` over the
  programs AS THE ENGINE RUNS THEM, and ``decode_bytes`` counts the rows
  the engine's counters say a step's attention read: the open windows'
  tokens and the closed windows' summaries.

The replica is built in a first CALL, not in the actor's constructor (an
actor whose constructor takes over 120 s never becomes ALIVE). The knee
sweep is ``benchmarks/sweep.py`` with this module as its runner:

    chiprun --timeout 3000 -- python3 -c "import sys; \\
        from benchmarks import sweep; \\
        from benchmarks.runners import serve_eva; \\
        sweep.runner = serve_eva; sys.exit(sweep.main())" \\
        --config evabyte-l8-bf16-serve \\
        --traffic bytedoc-saturated --rates 1.3,1.4,1.5 \\
        --seeds 1,2 --seconds 50

(``PERF.md`` section 4 has the knee's sweep, made so with 30 s windows
from 0.7 requests/s).

The readings of every ``ablate`` switch of the reference (what
``correctness.why`` of the configuration quotes) come from this module
run as a script, on the chip: ``python3 -m benchmarks.runners.serve_eva
--config evabyte-l8-bf16-serve --seed <n>``.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from benchmarks import common, eva_model
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
    {"pool_15_of_16": True}, {"swap_phi_mu": True},
    {"open_summaries": True}, {"residual_bf16": True},
    {"pool_unrotated": True}, {"pool_unscaled": True},
    {"no_summaries": True}, {"fp8_weights": True},
)


def _make_deployment_class():
    from ray_tpu.serve.llm import LLMServer

    class BenchEva(mla._make_deployment_class()):
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

            from ray_tpu.models.generation import decode_step_multi

            spec = self._spec
            self.rec = base._Recorder()

            def on_event(event, *_a, **_kw):
                if event.endswith("backend_compile_duration"):
                    self.rec.builds += 1

            jax.monitoring.register_event_duration_secs_listener(on_event)
            eng = spec["engine"]
            cfg = eva_model.transformer_config(spec["model"])
            LLMServer.__init__(
                self,
                lambda: (eva_model.make_bf16_params(cfg, spec["seed"]), cfg),
                max_slots=eng["max_slots"], max_len=eng["max_len"],
                prefill_buckets=tuple(eng["prefill_buckets"]),
            )
            for k in ("block_steps", "burst_block_steps"):
                if getattr(self.engine, k) != eng[k]:
                    raise BenchFailure(f"engine {k} is not {eng[k]}")
            base._instrument(self.engine, self.rec)
            self._kept = {}  # what the programs gave a probe, for ablations
            for b in spec["warm_buckets"]:  # through the engine itself
                n = min(b, eng["max_len"] - 2)
                self.engine.generate(np.zeros(n, np.int32), max_new_tokens=2)
            e = self.engine  # idle: every lane parked, no slot in use
            _logits, e.cache = decode_step_multi(
                e.params, e.tok, e.cache, e.pos, e.config)
            self._trace_dir = None
            self._stretch = {}
            return self._cmd_report()

        def _cmd_report(self):
            rep = super()._cmd_report()
            rep["dims"] = eva_model.dims(self.engine.config)
            return rep

        def _first_window(self, lanes):
            """The summaries [k~ | v~] every layer's slots ``lanes`` hold
            of their first closed window: [lanes, layers, chunks a
            window, heads, 2 x d_head]."""
            import jax.numpy as jnp

            c = self.engine.config
            per = c.eva_window // c.eva_chunk
            cache = self.engine.cache
            return jnp.stack([
                jnp.concatenate([cache["ek"][:, b, :per],
                                 cache["ev"][:, b, :per]], -1)
                for b in lanes])

        def _served(self, prompt, ids, steps):
            """One probe as the window's programs run it on the idle
            engine, in TWO lanes at once beside a live neighbour
            (``run.probe``'s ``lanes`` and ``neighbour_lane``; none is
            slot 0, every other lane is parked): the prompt's prefill at
            its bucket into both lanes and two thirds of it, reversed,
            at the same bucket into the neighbour's; one decode step that
            takes the slots' summaries and rows over from the prefills
            (``decode_step_multi``, for its logits), ``steps`` more in
            the long blocks, the two lanes teacher-forced on ``ids``
            (greedy: the programs give the engine's own tokens again in
            both, which is checked) and the neighbour on its own, one
            more step for its logits. Where the prompt is a byte short of
            a window, both lanes' windows close in the first of these
            steps. Returns, a lane each, the three logit arrays and the
            first closed window's summaries after the prefill and after
            the replay (None where no window had closed by then), and the
            tokens fed."""
            import jax.numpy as jnp

            from ray_tpu.models.generation import (
                decode_block,
                decode_step_multi,
                prefill_into_slot,
            )

            eng, n = self.engine, len(prompt)
            window = eng.config.eva_window
            size = self._spec["model"]["run"]["probe"]
            twins, beside = list(size["lanes"]), size["neighbour_lane"]
            s = eng.stats()
            if s["active"] or s["pending"]:
                raise BenchFailure("a probe needs an idle engine")

            def admit(tokens, slot):
                padded = np.zeros((1, eng._bucket_for(n)), np.int32)
                padded[0, :len(tokens)] = tokens
                logits, eng.cache = prefill_into_slot(
                    eng.params, jnp.asarray(padded),
                    jnp.int32(len(tokens)), jnp.int32(slot), eng.cache,
                    eng.config)
                return logits

            other = np.asarray(prompt)[::-1][:max(2, 2 * n // 3)]
            m = len(other)
            at_prefill = jnp.stack([admit(prompt, b) for b in twins])
            theirs = int(jnp.argmax(admit(other, beside)[0]))
            pooled_prefill = (self._first_window(twins) if n >= window
                              else None)
            live = jnp.asarray(twins + [beside])

            def lanes(probe, neighbour):  # every other lane parked
                return eng._lanes(jnp.int32).at[live].set(jnp.asarray(
                    [probe] * len(twins) + [neighbour], jnp.int32))

            at_first, eng.cache = decode_step_multi(
                eng.params, lanes(ids[0], theirs), eng.cache, lanes(n, m),
                eng.config)
            theirs = int(jnp.argmax(at_first[beside, 0]))
            tok, pos, counts = (lanes(ids[1], theirs), lanes(n + 1, m + 1),
                                lanes(2, 2))
            zeros_f, zeros_i = eng._lanes(jnp.float32), eng._lanes(jnp.int32)
            fed = [[int(ids[0]), int(ids[1])] for _ in twins]
            for _ in range(steps // eng.block_steps):
                toks, eng.cache, tok, pos, counts, _st = decode_block(
                    eng.params, eng.cache, tok, pos, zeros_f, zeros_i,
                    counts, eng.config, eng.block_steps)
                for got, b in zip(fed, twins):
                    got += np.asarray(toks[b]).tolist()
            at_decode, eng.cache = decode_step_multi(
                eng.params, tok, eng.cache, pos, eng.config)
            closed_in_replay = n < window <= n + len(fed[0])
            lane = jnp.asarray(twins)
            return {"prefill": at_prefill, "first": at_first[lane],
                    "decode": at_decode[lane], "fed": fed[0],
                    "replayed": all(got == list(ids[:len(got)])
                                    for got in fed),
                    "pooled_prefill": pooled_prefill,
                    "pooled_decode": (self._first_window(twins)
                                      if closed_in_replay else None)}

        def _reference(self, tokens, rows, ablate):
            """``reference.forward_logits`` computed one layer a compiled
            call, each slicing its layer out of the served stack inside
            the call, so that it fits beside the engine. Returns the
            logits [len(rows), heads, V] at ``rows`` and the first closed
            window's summaries [k~ | v~] of every layer (None: the
            sequence closes none)."""
            import jax
            import jax.numpy as jnp

            from benchmarks import reference_eva as ref

            params = self.engine.params
            hp = eva_model.reference_constants(self.engine.config)
            per = hp["window"] // hp["chunk"]
            stack = params["eva_layers"]
            with jax.default_matmul_precision("highest"):
                @jax.jit
                def one(x, stack, i):
                    lp = jax.tree.map(lambda a: a[i], stack)
                    y, (ks, vs) = ref.layer(x, lp, hp, ablate)
                    return y, jnp.concatenate([ks[:per], vs[:per]], -1)

                x = jax.jit(lambda p, t: ref.embed(p, t, hp))(params, tokens)
                pooled = []
                for i in range(self.engine.config.n_layers):
                    x, kv = one(x, stack, jnp.int32(i))
                    pooled.append(kv)
                logits = jax.jit(lambda p, x: ref.head(p, x, hp, ablate))(
                    {k: params[k] for k in ("final_ln", "lm_head")},
                    x[np.asarray(rows)])
            return logits, (jnp.stack(pooled)
                            if len(tokens) >= hp["window"] else None)

        def _cmd_reference(self, prompt, ids, steps, ablate=None):
            """One probe against the plain reference (``ablate``: against
            a deliberately wrong one): the distances of the three logit
            arrays and of the summaries, the larger of the two lanes'
            each."""
            import jax.numpy as jnp

            from benchmarks import reference_eva as ref

            key = ("served", tuple(prompt[:8]), len(prompt))
            if key not in self._kept:
                self._kept[key] = self._served(prompt, ids, steps)
            got, n = self._kept[key], len(prompt)
            tokens = jnp.asarray(list(prompt) + got["fed"], jnp.int32)
            want, pooled = self._reference(
                tokens, [n - 1, n, len(tokens) - 1], dict(ablate or {}))
            top2 = jnp.sort(want[:, 0], -1)[:, -2:]

            def worst(lanes, wanted):  # the larger reading of the lanes
                return max(float(ref.relative_rms(g, wanted)) for g in lanes)

            out = {
                "prefill_rel": worst(got["prefill"], want[0]),
                "first_rel": worst(got["first"], want[1]),
                "decode_rel": worst(got["decode"], want[2]),
                "lanes_identical": all(
                    bool(jnp.array_equal(got[k][0], g))
                    for k in ("prefill", "first", "decode")
                    for g in got[k][1:]),
                "logits_rms": float(jnp.sqrt(jnp.mean(want[2, 0] ** 2))),
                "top2_gap": np.asarray(top2[:, 1] - top2[:, 0]).tolist(),
                "replayed": got["replayed"], "tokens": len(tokens)}
            for k in ("pooled_prefill", "pooled_decode"):
                if got[k] is not None:
                    out[k.replace("pooled", "summary")] = worst(
                        got[k], pooled)
            return out

    return BenchEva


def _program_has_the_block() -> None:
    """Before any process starts: a program from before this kind existed
    cannot describe the block, and says so at once (importing the module
    imports JAX and initialises no backend)."""
    import dataclasses

    from ray_tpu.models.transformer import TransformerConfig

    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    if not {"eva_window", "eva_chunk", "n_pred_heads",
            "residual_f32"} <= fields:
        raise BenchFailure(
            "this program's TransformerConfig cannot describe 'eva' "
            "layers, several prediction heads and a float32 residual "
            "stream: the cell cannot run")


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


# the limits of ``correctness``, by the reading each bounds
_LIMITS = {
    "prefill_rel": "prefill_rel_tol", "first_rel": "first_rel_tol",
    "decode_rel": "decode_rel_tol", "summary_prefill": "summary_prefill_tol",
    "summary_decode": "summary_decode_tol", "logits_mean": "logits_mean_tol",
}
_LOGITS = ("prefill_rel", "first_rel", "decode_rel")


def probes(handle, model, served: Dict,
           ablate: Optional[Dict] = None) -> Dict:
    """Probes (a)-(c) of every served prompt against the plain reference
    (``ablate``: against a deliberately wrong one), reduced to the largest
    reading of each kind: every limit must hold for every prompt, and
    each kind of summary must have been read from some prompt.
    ``logits_mean`` is the mean of the prompts' logit readings, three
    each. ``refused_by`` names the limits a reading passed."""
    size, tol = model["run"]["probe"], model["correctness"]
    rows = [handle.remote("reference", p.tolist(), ids, size["decode_steps"],
                          ablate).result(timeout=2400)
            for p, ids in zip(served["prompts"], served["ids"])]
    out = {
        **{k: max((r[k] for r in rows if k in r), default=None)
           for k in _LIMITS},
        "logits_mean": sum(r[k] for r in rows for k in _LOGITS)
        / (len(rows) * len(_LOGITS)),
        "replayed": all(r["replayed"] for r in rows),
        "by_prompt": [{k: (round(v, 5) if isinstance(v, float) else v)
                       for k, v in r.items() if k != "top2_gap"}
                      for r in rows],
        "median_top2_gap": mla._quantile(
            [g for r in rows for g in r["top2_gap"]], 0.5)}
    out["refused_by"] = [name for name, limit in _LIMITS.items()
                         if out[name] is None or not out[name] <= tol[limit]]
    out["ok"] = bool(not out["refused_by"] and out["replayed"])
    return out


def trace_scalars(tr: Dict, model_dims: Dict, eng: Dict) -> Dict:
    """``runners/serve.py``'s device times of the traced decode blocks and
    prefills, with this model's bytes: every traced step is charged the
    weights once and the rows that the engine's counters say a step of
    that stretch read, the open windows' tokens and the closed windows'
    summaries."""
    out = base.trace_scalars(tr, mla._NO_GPTJ_BYTES, eng)
    st = tr.get("stretch_stats") or {}
    need = ("eva_window_rows_read", "eva_summary_rows_read")
    if st.get("steps") and all(k in st for k in need):
        per_step = {k: st[k] / st["steps"] for k in need}
        out["decode_bytes"] = out["decode_steps"] * \
            eva_model.decode_step_bytes(
                model_dims, *(per_step[k] for k in need))
        out["decode_window_rows_per_step"] = per_step[need[0]]
        out["decode_summary_rows_per_step"] = per_step[need[1]]
        if "eva_windows_closed" in st:
            out["decode_windows_closed"] = st["eva_windows_closed"]
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
    p.add_argument("--config", default="evabyte-l8-bf16-serve")
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--rehearse-cpu", action="store_true")
    p.add_argument("--switches", default=None,
                   help="comma-separated names of the switches to read "
                        "(default: all of ABLATIONS)")
    args = p.parse_args()
    wanted = args.switches.split(",") if args.switches else None
    switches = tuple(a for a in ABLATIONS
                     if wanted is None or next(iter(a)) in wanted)
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
        for ablate in ({},) + switches:
            row = probes(handle, model, served, ablate)
            print(json.dumps({"ablate": ablate, **row}), flush=True)
    finally:
        ray_tpu.shutdown()
        wait_session_gone(session_dir)
    return common.REHEARSAL_RC if args.rehearse_cpu else 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
