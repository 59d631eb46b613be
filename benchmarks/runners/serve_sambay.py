"""Serving runner for configurations of kind ``serve_sambay`` (a
decoder-hybrid-decoder: Mamba-1 layers with a [16, 5120] float32 state a
slot and window differential-attention layers with a ring of rows, below
ONE full differential-attention layer whose K/V rows are all the model
keeps of a token; above it "cross" layers that attend that layer's rows
and gated memory units gated by the last Mamba layer's output, none of
which keeps anything; Phi-4-mini-flash-reasoning's kind): the same one
replica of ``serve.LLMServer`` behind ``serve.run``, the same load
generator, window and trace reduction as ``runners/serve.py`` (``measure``,
``serve_owner``, ``wait_session_gone`` and the deployment's recorders and
control calls are imported from there, the scope reduction and the
stretch's counters from ``runners/serve_mla_moe.py``, the served probes
from ``runners/serve_ssm.py``; nothing there is edited). What differs is
the model's side of the bench:

- the config object, the bf16 weights from the seed and the byte functions
  come from ``benchmarks/sambay_model.py``;
- ``correct`` holds what the timed programs produce at the timed sizes to
  ``benchmarks/reference_sambay.py`` (``run.probe`` and ``correctness`` of
  the configuration), LOGITS, STATES and a ROW, not tokens. Two seeded
  prompts (one longer than the window of 512 rows, in a padded bucket)
  go through the whole served path; then, on the idle engine, each is run
  again by the window's own programs into slot 0: (a) the logits
  ``prefill_into_slot`` returns for the prompt, whose upper 14 layers ran
  on its last real token alone; (b) the logits of the first decode step,
  which takes the states, the convolutions' tails, the rings and the rows
  over from the prefill, and those after ``decode_steps`` more steps of
  ``decode_block`` teacher-forced on the engine's own tokens, against the
  reference's full forward over prompt + answer (every layer over every
  token, differential attention as four attentions a pair), each as the
  RMS of the difference over the RMS of the reference's logits; (c) the
  recurrent state of the first and of the last Mamba-1 layer at that
  point, relative RMS; (d) the row [k | v] the full layer has kept of the
  LAST token (what the seven cross layers read of it), relative RMS. The
  reference is driven one layer a compiled call, its head in blocks of
  the vocabulary (the embedding alone is 2 GB in float32);
- the traced stretch is also reduced by ``jax.named_scope`` over the
  programs AS THE ENGINE RUNS THEM (the fused admission form of
  ``prefill_into_slot``), the device time of the ``mamba_scan`` kernel's
  calls inside the traced admissions is summed beside the bytes they had
  to move, and ``decode_bytes`` counts the live lanes' states and the
  rows the engine's counters say a step read: the full layer's own, the
  cross layers' of the same cache, and the rings'.

The replica is built in a first CALL, not in the actor's constructor (an
actor whose constructor takes over 120 s never becomes ALIVE). The knee
sweep is ``benchmarks/sweep.py`` with this module as its runner:

    chiprun --timeout 3000 -- python3 -c "import sys; \\
        from benchmarks import sweep; \\
        from benchmarks.runners import serve_sambay; \\
        sweep.runner = serve_sambay; sys.exit(sweep.main())" \\
        --config phi4-mini-flash-bf16-serve \\
        --traffic histreason-saturated --rates 1.2,1.5,1.8,2.1 \\
        --seeds 1,2 --seconds 30

The readings of every ``ablate`` switch of the reference (what
``correctness.why`` of the configuration quotes) come from this module
run as a script, on the chip: ``python3 -m benchmarks.runners.
serve_sambay --config phi4-mini-flash-bf16-serve --seed <n>``.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from benchmarks import common, sambay_model
from benchmarks.common import BenchFailure
from benchmarks.runners import serve as base
from benchmarks.runners import serve_mla_moe as mla
from benchmarks.runners.serve import (  # noqa: F401 (sweep.py's runner API)
    measure,
    serve_owner,
    wait_session_gone,
)
from benchmarks.runners.serve_ssm import served_probes

# True: the runner fills in the probe's own prompt length (and bucket)
ABLATIONS = (
    {"lambda_zero": True}, {"m_after_gate": True}, {"state_bf16": True},
    {"keep_lambda_init": True}, {"cross_strict": True}, {"window": 511},
    {"rms_norm": True}, {"state_at_bucket_end": True},
    {"drop_conv_tail": True},
)
KERNEL = "mamba_scan"  # the Pallas call's name in the compiled text
HEAD_ROWS = 50016  # rows of the vocabulary a call of the reference's head


def _make_deployment_class():
    from ray_tpu.serve.llm import LLMServer

    class BenchSambay(mla._make_deployment_class()):
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
            cfg = sambay_model.transformer_config(spec["model"])
            LLMServer.__init__(
                self,
                lambda: (sambay_model.make_bf16_params(cfg, spec["seed"]),
                         cfg),
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
            rep["dims"] = sambay_model.dims(self.engine.config)
            return rep

        def _served(self, prompt, ids, steps):
            """One probe as the window's programs run it, into slot 0 of
            the idle engine: the prefill at its bucket, one decode step
            that takes the slot's states, tails, rings and rows over from
            the prefill (``decode_step_multi``, for its logits), ``steps``
            more in the long blocks teacher-forced on ``ids`` (greedy: the
            programs give the engine's own tokens again, which is
            checked), one more step for its logits. Returns the three
            logit vectors, the tokens fed, the first and the last Mamba-1
            layer's state after them and the full layer's last row."""
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
            state = cache_state(eng.cache)["mamba"]
            last = n + len(fed) - 1  # the row of the last token fed
            return {"prefill": at_prefill, "first": at_first[0],
                    "decode": at_decode[0], "fed": fed,
                    "replayed": fed == list(ids[:len(fed)]),
                    "state_first": state[0, 0], "state_last": state[-1, 0],
                    "row_last": jnp.concatenate(
                        [eng.cache["k"][-1, 0, last],
                         eng.cache["v"][-1, 0, last]])}

        def _reference(self, tokens, rows, ablate):
            """``reference.forward_logits`` computed one layer a compiled
            call, each slicing its layer out of the served stacks inside
            the call, and the head in blocks of the vocabulary, so that it
            fits beside the engine. Returns the logits at ``rows``, the
            first and last Mamba-1 layer's state after the last token and
            the full layer's row [k | v] of the last token."""
            import jax
            import jax.numpy as jnp

            from benchmarks import reference_sambay as ref

            params = self.engine.params
            hp = sambay_model.reference_constants(self.engine.config)
            seq, real = ref.with_padding(tokens, ablate)
            with jax.default_matmul_precision("highest"):
                @jax.jit  # one program a kind of layer (and of ``handed``)
                def one(x, stack, i, depth, handed):
                    lp = jax.tree.map(lambda a: a[i], stack)
                    return ref.layer(x, lp, hp, ablate, depth, handed, real)

                x = jax.jit(lambda p, t: ref.embed(p, t, hp))(params, seq)
                first = last = None
                handed = {}
                for depth, (name, i) in enumerate(
                        ref.layers_in_order(params, hp)):
                    x, state, handed = one(
                        x, params[name], jnp.int32(i), jnp.float32(depth),
                        handed)
                    if state is not None:
                        first, last = (state if first is None else first,
                                       state)
                x = x[np.flatnonzero(real)[np.asarray(rows)]]
                embed = params["embed"]
                logits = jnp.concatenate([
                    jax.jit(lambda e, x: ref.head(
                        {"final_ln": params["final_ln"], "embed": e}, x, hp,
                        ablate))(embed[v:v + HEAD_ROWS], x)
                    for v in range(0, embed.shape[0], HEAD_ROWS)], -1)
            k, v = (a[np.flatnonzero(real)[-1]].reshape(-1)
                    for a in handed["kv"])
            return logits, first, last, jnp.concatenate([k, v])

        def _cmd_reference(self, prompt, ids, steps, ablate=None):
            """One probe against the plain reference (``ablate``: against
            a deliberately wrong one): the distances of the three logit
            vectors, of the two states and of the row."""
            import jax.numpy as jnp

            from benchmarks import reference_sambay as ref

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
            want, first, last, row = self._reference(
                tokens, [n - 1, n, len(tokens) - 1], ablate)
            size = jnp.sqrt(jnp.mean(want ** 2, -1))  # the logits' own RMS
            rel = [float(ref.vector_distance(got[k], want[i])[1] / size[i])
                   for i, k in enumerate(("prefill", "first", "decode"))]
            top2 = jnp.sort(want, -1)[:, -2:]
            return {
                "prefill_rel": rel[0], "first_rel": rel[1],
                "decode_rel": rel[2],
                "state_first": float(ref.state_distance(
                    got["state_first"], first)),
                "state_last": float(ref.state_distance(
                    got["state_last"], last)),
                "row_last": float(ref.state_distance(got["row_last"], row)),
                "logits_rms": float(size[2]),
                "top2_gap": np.asarray(top2[:, 1] - top2[:, 0]).tolist(),
                "replayed": got["replayed"], "tokens": len(tokens)}

        def _cmd_trace_reduce(self, keep_copy, rehearsal=False):
            """``runners/serve_mla_moe.py``'s, and the device seconds and
            the calls of the ``mamba_scan`` kernel inside the traced
            ``prefill_into_slot`` programs (an operation's event is named
            by its HLO text, ``%mamba_scan.36 = ...``)."""
            import bisect

            from benchmarks import trace

            red = super()._cmd_trace_reduce(keep_copy, rehearsal)
            dev = trace.load(trace.find_xplane(self._trace_dir),
                             rehearsal=rehearsal)["devices"]
            calls, seconds = 0, 0.0
            for d in dev.values():
                progs = sorted((p["start"], p["end"]) for p in d["programs"]
                               if trace.program_of(p["name"])
                               == "prefill_into_slot")
                starts = [p[0] for p in progs]
                for o in d["ops"]:
                    if not o["name"].lstrip("%").startswith(KERNEL):
                        continue
                    i = bisect.bisect_right(starts, o["start"]) - 1
                    if i >= 0 and o["start"] < progs[i][1]:
                        calls += 1
                        seconds += o["end"] - o["start"]
            red["kernel_calls"] = {KERNEL: calls}
            red["kernel_s"] = {KERNEL: seconds}
            return red

    return BenchSambay


def _program_has_the_block() -> None:
    """Before any process starts: a program from before this kind existed
    cannot describe the block, and says so at once (importing the module
    imports JAX and initialises no backend)."""
    import dataclasses

    from ray_tpu.models.transformer import TransformerConfig

    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    if not {"layer_types", "mamba_inner", "diff_attn", "norm"} <= fields:
        raise BenchFailure(
            "this program's TransformerConfig cannot describe 'mamba', "
            "'gmu' and 'cross' layers beside differential attention "
            "layers: the cell cannot run")


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
    "decode_rel": "decode_rel_tol", "state_first": "state_first_tol",
    "state_last": "state_last_tol", "row_last": "row_last_tol",
}


def probes(handle, model, served: Dict,
           ablate: Optional[Dict] = None) -> Dict:
    """Probes (a)-(d) of every served prompt against the plain reference
    (``ablate``: against a deliberately wrong one), reduced to the largest
    reading of each kind: every limit must hold for every prompt.
    ``refused_by`` names the limits a reading passed."""
    size, tol = model["run"]["probe"], model["correctness"]
    rows = [handle.remote("reference", p.tolist(), ids, size["decode_steps"],
                          ablate).result(timeout=2400)
            for p, ids in zip(served["prompts"], served["ids"])]
    out = {
        **{k: max(r[k] for r in rows) for k in _LIMITS},
        "replayed": all(r["replayed"] for r in rows),
        "by_prompt": [{k: (round(v, 5) if isinstance(v, float) else v)
                       for k, v in r.items() if k != "top2_gap"}
                      for r in rows],
        "median_top2_gap": mla._quantile(
            [g for r in rows for g in r["top2_gap"]], 0.5)}
    out["refused_by"] = [name for name, limit in _LIMITS.items()
                         if not out[name] <= tol[limit]]
    out["ok"] = bool(not out["refused_by"] and out["replayed"])
    return out


def trace_scalars(tr: Dict, model_dims: Dict, eng: Dict) -> Dict:
    """``runners/serve.py``'s device times of the traced decode blocks and
    prefills, with this model's bytes: every traced step is charged the
    live lanes' states and the rows that the engine's counters say a step
    of that stretch read (the full layer's own, the cross layers' of the
    same cache, the rings'); and the ``mamba_scan`` kernel's device time
    beside the bytes its calls had to move (each call walks its prefill's
    whole bucket: the buckets of the stretch's admissions, by their
    ``bench.prefill`` marks)."""
    out = base.trace_scalars(tr, mla._NO_GPTJ_BYTES, eng)
    st = tr.get("stretch_stats") or {}
    need = ("slot_steps", "attn_rows_read", "cross_rows_read",
            "window_rows_read")
    if st.get("steps") and all(k in st for k in need):
        per_step = {k: st[k] / st["steps"] for k in need}
        out["decode_bytes"] = out["decode_steps"] * \
            sambay_model.decode_step_bytes(
                model_dims, *(per_step[k] for k in need))
        out["decode_live_slots_per_step"] = per_step[need[0]]
        out["decode_kv_rows_per_step"] = per_step[need[1]]
        out["decode_cross_rows_per_step"] = per_step[need[2]]
        out["decode_ring_rows_per_step"] = per_step[need[3]]
    else:
        out.pop("decode_bytes", None)
    calls = (tr.get("kernel_calls") or {}).get(KERNEL)
    marks = [m["stats"]["tokens"] for m in tr["marks"]
             if m["name"] == "bench.prefill"]
    if calls and marks:
        buckets = sorted(eng["prefill_buckets"])
        walked = [min(b for b in buckets if b >= n) for n in marks]
        out["mamba_scan_device_s"] = tr["kernel_s"][KERNEL]
        out["mamba_scan_calls"] = calls
        out["mamba_scan_bytes"] = calls * sum(
            sambay_model.mamba_scan_cost(model_dims, b)["bytes"]
            for b in walked) / len(walked)
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
    p.add_argument("--config", default="phi4-mini-flash-bf16-serve")
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
