"""Serving runner for configurations of kind ``serve_ssm_moe`` (layers
that are ONE branch each: Mamba-2 mixers with a fixed-size recurrent state
a slot, attention with K/V rows, and a chip's share of routed experts in a
latent beside a shared expert; Nemotron-3-Super's kind): the same one
replica of ``serve.LLMServer`` behind ``serve.run``, the same load
generator, window and trace reduction as ``runners/serve.py``
(``measure``, ``serve_owner``, ``wait_session_gone``), the deployment, the
served probes and the five readings of ``runners/serve_ssm.py`` (its
``_served``, ``_cmd_reference``, ``served_probes``, ``probes``: logits of
the prefill, of the first decode step and after 256 more, the first and
the last state-space layer's state) and the expert counters of
``runners/serve_mla_moe.py`` (``moe_scalars``); nothing there is edited.
What differs is the model's side of the bench:

- the config object, the bf16 weights from the seed and the byte functions
  come from ``benchmarks/ssm_moe_model.py``;
- the plain reference is ``benchmarks/reference_ssm_moe.py``, driven one
  layer a compiled call (a routed layer's held experts are 1.4 GB in
  bf16 and are turned to float32 one expert at a time);
- beside those five readings, EVERY LAYER ON WHAT THE TIMED PROGRAMS GAVE
  IT (``_tapped``, ``_cmd_layers_served``): both probes are run again by
  ``prefill_into_slot`` and ``decode_block`` with every one of the 64
  lanes live (a step's ~352 live rows over the 128 held experts, the
  looped form, the share's sum by the 0/1 product: the window's step),
  compiled with ``taps``, which hands back each layer's input; the
  reference's layer runs over that same sequence of inputs and the branch
  the programs added is compared with its, token by token, the prompt's
  tokens and the decode steps' apart; and the head over the last layer's
  output. 22 picks of 512 make a near-tie between the 22nd and the 23rd
  expert the rule, a bf16-rounded hidden state decides it the other way
  in most tokens of some layer, and the five readings through eleven
  layers carry that noise (0.01-0.35 of the logits' RMS from one position
  to the next); a layer given the same input on both sides reads its
  mathematics to ~1 %, which is what tells 21 picks from 22 and one norm
  group from eight, and here it is the timed programs' own layer;
- the check runs AFTER the window, so that what it compiles and runs is no
  part of ``setup_s``;
- ``decode_bytes`` counts the experts the engine's counters say a step
  touched, the slot states they say it updated and the K/V rows they say
  it read; and the ``grouped_matmul`` kernel's device time inside the
  traced ``decode_block`` programs stands beside the bytes its calls had
  to move (``ssm_moe_model.grouped_products_cost``).

The replica is built in a first CALL, not in the actor's constructor. The
knee sweep is ``benchmarks/sweep.py`` with this module as its runner:

    chiprun --timeout 3000 -- python3 -c "import sys; \\
        from benchmarks import sweep; \\
        from benchmarks.runners import serve_ssm_moe; \\
        sweep.runner = serve_ssm_moe; sys.exit(sweep.main())" \\
        --config nemotron3-super-l11-e128-bf16-serve \\
        --traffic multiagent-saturated --rates 3,3.5,4 --seeds 1,2 \\
        --seconds 30

The readings of every ``ablate`` switch of the reference (what
``correctness.why`` of the configuration quotes) come from this module
run as a script, on the chip: ``python3 -m benchmarks.runners.
serve_ssm_moe --config nemotron3-super-l11-e128-bf16-serve --seed <n>``.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from benchmarks import common, ssm_moe_model
from benchmarks.common import BenchFailure
from benchmarks.runners import serve as base
from benchmarks.runners import serve_mla_moe as mla
from benchmarks.runners import serve_ssm
from benchmarks.runners.serve import (  # noqa: F401 (sweep.py's runner API)
    measure,
    serve_owner,
    wait_session_gone,
)
from benchmarks.runners.serve_ssm import served_probes

# True: the runner fills in the probe's own prompt length (and bucket)
ABLATIONS = (
    {"state_bf16": True}, {"route_scale_one": True}, {"relu": True},
    {"one_norm_group": True}, {"top_k": 21}, {"no_shared": True},
    {"drop_conv_tail": True}, {"state_at_bucket_end": True},
    # the layers-served probe's own: the two KV heads read in the other
    # order, as a slip in the flat layout of a cache row would have them
    {"swap_kv_heads": True},
)
KERNEL = "grouped_matmul"  # the Pallas call's name in the compiled text


def kernel_seconds(trace_dir, rehearsal: bool, kernel: str):
    """(calls, device seconds, {calls in one program: programs}) of the
    operations named ``kernel`` inside the traced ``decode_block``
    programs (an operation's event is named by its HLO text,
    ``%grouped_matmul.36 = ...``). A program the trace's edge cuts is
    there with the operations on the traced side of the edge alone, which
    the third value shows."""
    import bisect
    from collections import Counter

    from benchmarks import trace

    dev = trace.load(trace.find_xplane(trace_dir),
                     rehearsal=rehearsal)["devices"]
    calls, seconds, by_program = 0, 0.0, Counter()
    for d in dev.values():
        progs = sorted((p["start"], p["end"]) for p in d["programs"]
                       if trace.program_of(p["name"]) == "decode_block")
        starts = [p[0] for p in progs]
        inside = Counter()
        for o in d["ops"]:
            if not o["name"].lstrip("%").startswith(kernel):
                continue
            i = bisect.bisect_right(starts, o["start"]) - 1
            if i >= 0 and o["start"] < progs[i][1]:
                inside[i] += 1
                calls += 1
                seconds += o["end"] - o["start"]
        by_program.update(inside[i] for i in range(len(progs)))
    return calls, seconds, {str(k): v for k, v in sorted(by_program.items())}


def _make_deployment_class():
    from ray_tpu.serve.llm import LLMServer

    class BenchSsmMoe(serve_ssm._make_deployment_class()):
        """``runners/serve_ssm.py``'s deployment (recorders, ``stream``,
        trace, scopes, counters, the served probe and its five readings)
        around this kind's model and reference."""

        def _cmd_build(self):
            """``runners/serve_ssm.py``'s first call with this kind's
            model: weights, engine, every bucket warmed through the
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
            cfg = ssm_moe_model.transformer_config(spec["model"])
            LLMServer.__init__(
                self,
                lambda: (ssm_moe_model.make_bf16_params(cfg, spec["seed"]),
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
            e = self.engine  # idle: every lane parked, no slot in use
            _logits, e.cache = decode_step_multi(
                e.params, e.tok, e.cache, e.pos, e.config)
            self._trace_dir = None
            self._stretch = {}
            self._kept = {}  # what the programs gave a probe, for ablations
            return self._cmd_report()

        def _cmd_report(self):
            rep = super()._cmd_report()
            rep["dims"] = ssm_moe_model.dims(self.engine.config)
            return rep

        def _reference(self, tokens, rows, ablate):
            """``reference.forward_logits`` computed one layer a compiled
            call, each slicing its layer out of the served stacks inside
            the call, so that it fits beside the engine. Returns the
            logits at ``rows`` and the first and last state-space layer's
            state after the last token."""
            import jax
            import jax.numpy as jnp

            from benchmarks import reference_ssm_moe as ref

            params = self.engine.params
            hp = ssm_moe_model.reference_constants(self.engine.config)
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
            self._reference_first = first  # for ``state_first_head``
            return logits, first, last

        def _cmd_reference(self, prompt, ids, steps, ablate=None):
            """``runners/serve_ssm.py``'s five readings of one probe, and
            ``state_first_head``: the first state-space layer's state A
            HEAD, the largest of the 128 distances. The whole state's
            distance is carried by the heads that forget fastest (the
            largest states) and reads a state rounded to bf16 a token as
            0.0061 against 0.0044; a slow head's own state shows it."""
            from benchmarks import reference_ssm_moe as ref

            out = super()._cmd_reference(prompt, ids, steps, ablate)
            got = self._kept[("served", tuple(prompt[:8]))]["state_first"]
            out["state_first_head"] = float(ref.state_head_distances(
                got, self._reference_first).max())
            return out

        def _tapped(self, prompts, ids, steps):
            """The probes as the window's programs run them WITH EVERY
            LANE LIVE, each layer's input handed back (``taps``: the same
            ``prefill_into_slot`` and ``decode_block`` over the same 64
            slots, compiled with one output more). Probe ``j`` goes into
            slot ``j`` through its bucket's chunked, padded prefill; every
            other slot gets the first prompt rolled by its number (other
            tokens, other picks: a step's ~352 live rows over the 128 held
            experts, as in the window); then ``steps`` tokens in the long
            blocks, greedy. Returns a probe's ``x``: {kind: [layers, T, d]}
            and "out" [T, d] over its prompt and the ``steps`` tokens,
            ``logits`` of its prefill, ``toks`` [steps] and ``as_served``:
            how many of them, from the first, are the tokens the engine
            gave the probe ALONE (a note, no condition: another compile of
            the bucket, or other lanes' rows between a token's pairs in the
            0/1 product's float32 sum, and a near-tie of the head falls the
            other way some tens of tokens on; on the chip the first probe
            gives all of them again and the second its first 7-38)."""
            import jax.numpy as jnp

            from ray_tpu.models.generation import (
                decode_block,
                prefill_into_slot,
            )

            eng = self.engine
            s = eng.stats()
            if s["active"] or s["pending"]:
                raise BenchFailure("a probe needs an idle engine")
            lanes = eng._lanes(jnp.int32).shape[0]

            def admit(prompt, slot, taps):
                padded = np.zeros((1, eng._bucket_for(len(prompt))), np.int32)
                padded[0, :len(prompt)] = prompt
                logits, eng.cache, *tapped = prefill_into_slot(
                    eng.params, jnp.asarray(padded),
                    jnp.int32(len(prompt)), jnp.int32(slot), eng.cache,
                    eng.config, taps=taps)
                return logits, tapped

            out, first = [], []
            for j, prompt in enumerate(prompts):
                logits, (tap,) = admit(prompt, j, True)
                out.append({"logits": logits, "n": len(prompt), "x": [{
                    k: v[..., 0, :len(prompt), :] for k, v in tap.items()}]})
                first.append(jnp.int32(ids[j][0]))
            for slot in range(len(prompts), lanes):
                first.append(jnp.argmax(admit(
                    np.roll(prompts[0], slot), slot, False)[0]))
            tok = jnp.stack(first).astype(jnp.int32)
            pos = jnp.asarray([len(p) for p in prompts] + [
                len(prompts[0])] * (lanes - len(prompts)), jnp.int32)
            counts = jnp.ones(lanes, jnp.int32)
            zeros_f, zeros_i = eng._lanes(jnp.float32), eng._lanes(jnp.int32)
            toks = []
            for _ in range(steps // eng.block_steps):
                got, eng.cache, tok, pos, counts, _st, tap = decode_block(
                    eng.params, eng.cache, tok, pos, zeros_f, zeros_i,
                    counts, eng.config, eng.block_steps, taps=True)
                toks.append(got[:len(prompts)])
                for j, probe in enumerate(out):  # [steps, (layers,) B, 1, d]
                    probe["x"].append({
                        k: jnp.moveaxis(v[..., j, 0, :], 0, -2)
                        for k, v in tap.items()})
            toks = np.asarray(jnp.concatenate(toks, 1))
            for j, probe in enumerate(out):
                probe["x"] = {k: jnp.concatenate([x[k] for x in probe["x"]],
                                                 -2) for k in probe["x"][0]}
                probe["toks"] = toks[j]
                same = toks[j] == np.asarray(ids[j][1:toks.shape[1] + 1])
                probe["as_served"] = int(np.argmin(same)) if not same.all(
                    ) else len(same)
            return out

        def _cmd_layers_served(self, prompts, ids, steps, ablate=None):
            """EVERY LAYER ON WHAT THE TIMED PROGRAMS GAVE IT (``_tapped``):
            the reference's layer (``ablate``: a deliberately wrong one)
            over the sequence of that layer's inputs as ``prefill_into_
            slot`` and ``decode_block`` made them, against the branch they
            added to it, token by token: the same rows on both sides, so a
            near-tie a layer below decided the other way is not in it. A
            kind's reading is the largest over its layers, over the
            prompt's and the decode steps' tokens apart and over the
            probes, of the median, the 0.9 quantile and the share over
            5 %. And the head: the prefill's logits against the
            reference's head over the last layer's output there
            (``head_rel``), and the share of the decode steps' tokens that
            are not the reference head's first choice (``head_tokens``)."""
            import jax
            import jax.numpy as jnp

            from benchmarks import reference_ssm_moe as ref

            if "tapped" not in self._kept:
                self._kept["tapped"] = self._tapped(prompts, ids, steps)
            ablate = dict(ablate or {})
            params = self.engine.params
            hp = ssm_moe_model.reference_constants(self.engine.config)
            kinds = {"layers": "attn", "ssm_layers": "ssm",
                     "expert_layers": "moe"}
            order = ref.layers_in_order(params, hp)
            F32 = jnp.float32
            by_kind = {kind: [] for kind in kinds.values()}
            heads = {"head_rel": [], "head_tokens": []}
            with jax.default_matmul_precision("highest"):
                @jax.jit
                def branch(x, stack, i, cut):
                    lp = jax.tree.map(lambda a: a[i], stack)
                    if "attn" in lp and ablate.get("swap_kv_heads"):
                        lp["attn"] = {**lp["attn"], **{
                            w: lp["attn"][w][:, ::-1] for w in ("wk", "wv")}}
                    x = x.astype(F32)
                    tail = {"drop_conv_tail": cut} if ablate.get(
                        "drop_conv_tail") else {}  # at the hand-off
                    return ref.layer(x, lp, hp, {**ablate, **tail})[0] - x

                head = jax.jit(lambda p, x: ref.head(p, x.astype(F32), hp))
                for probe in self._kept["tapped"]:
                    x, n = probe["x"], probe["n"]
                    ins = [x[kinds[name]][i] for name, i in order]
                    for (name, i), x_in, x_out in zip(
                            order, ins, ins[1:] + [x["out"]]):
                        want = branch(x_in, params[name], jnp.int32(i),
                                      jnp.int32(n))
                        got = x_out.astype(F32) - x_in.astype(F32)
                        err = jnp.linalg.norm(got - want, axis=-1) / (
                            jnp.linalg.norm(want, axis=-1) + 1e-6)
                        by_kind[kinds[name]] += [
                            {"median": float(jnp.median(part)),
                             "q90": float(jnp.quantile(part, 0.9)),
                             "share_over_5pct": float((part > 0.05).mean()),
                             "branch_over_input": float(
                                 jnp.linalg.norm(want) / jnp.linalg.norm(
                                     x_in.astype(F32))),
                             "layer": f"{name}[{i}]", "tokens": tokens}
                            for tokens, part in (("prompt", err[:n]),
                                                 ("decode", err[n:]))]
                    want = head(params, x["out"][n - 1:])
                    heads["head_rel"].append(float(
                        ref.vector_distance(probe["logits"], want[0])[1]
                        / jnp.sqrt(jnp.mean(want[0] ** 2))))
                    heads["head_tokens"].append(float(
                        (jnp.argmax(want[1:], -1) != probe["toks"]).mean()))
            out = {name: {k: max(r[k] for r in by_kind[kind])
                          for k in ("median", "q90", "share_over_5pct")}
                   for name, kind in (("ssm_layer", "ssm"),
                                      ("attn_layer", "attn"),
                                      ("routed_layer", "moe"))}
            out.update({k: max(v) for k, v in heads.items()})
            out["tokens_as_served"] = [
                p["as_served"] for p in self._kept["tapped"]]
            out["by_layer"] = {k: [{n: (round(v, 5) if isinstance(v, float)
                                        else v) for n, v in r.items()}
                                   for r in rows]
                               for k, rows in by_kind.items()}
            return out

        def _cmd_trace_reduce(self, keep_copy, rehearsal=False):
            """``runners/serve_mla_moe.py``'s, and the device seconds and
            the calls of the grouped products inside the traced
            ``decode_block`` programs."""
            red = super()._cmd_trace_reduce(keep_copy, rehearsal)
            calls, seconds, by_program = kernel_seconds(
                self._trace_dir, rehearsal, KERNEL)
            red["kernel_calls"] = {KERNEL: calls}
            red["kernel_s"] = {KERNEL: seconds}
            red["kernel_calls_by_program"] = {KERNEL: by_program}
            return red

    return BenchSsmMoe


def _program_has_the_block() -> None:
    """Before any process starts: a program from before this kind existed
    cannot describe the block, and says so at once (importing the module
    imports JAX and initialises no backend)."""
    import dataclasses

    from ray_tpu.models.transformer import TransformerConfig

    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    if not {"block", "moe_latent", "ssm_norm_groups"} <= fields:
        raise BenchFailure(
            "this program's TransformerConfig cannot describe layers of "
            "one branch each with experts in a latent: the cell cannot run")


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
    "state_last": "state_last_tol",
    "state_first_head": "state_first_head_tol",
    "ssm_layer.median": "ssm_layer_median_tol",
    "ssm_layer.q90": "ssm_layer_q90_tol",
    "attn_layer.median": "attn_layer_median_tol",
    "attn_layer.q90": "attn_layer_q90_tol",
    "routed_layer.median": "routed_layer_median_tol",
    "routed_layer.share_over_5pct": "routed_layer_share_tol",
    "head_rel": "head_rel_tol", "head_tokens": "head_tokens_tol",
}


def probes(handle, ctx, model, served: Dict, ablate=None) -> Dict:
    """``runners/serve_ssm.py``'s five readings of every served prompt
    (the larger of the prompts' counts) and every layer and the head on
    what the timed programs gave them (``layers_served``), against the
    plain reference (``ablate``: against a deliberately wrong one).
    ``refused_by`` names the limits a reading passed."""
    size, tol = model["run"]["probe"], model["correctness"]
    out = serve_ssm.probes(handle, model, served, ablate)
    out["state_first_head"] = max(
        r["state_first_head"] for r in out["by_prompt"])
    out.update(handle.remote(
        "layers_served", [p.tolist() for p in served["prompts"]],
        served["ids"], size["decode_steps"], ablate).result(timeout=2400))

    def reading(name):
        group, _, key = name.rpartition(".")
        return out[group][key] if group else out[key]

    out["refused_by"] = [name for name, limit in _LIMITS.items()
                         if not reading(name) <= tol[limit]]
    out["ok"] = bool(not out["refused_by"] and out["replayed"])
    return out


def trace_scalars(tr: Dict, model_dims: Dict, eng: Dict) -> Dict:
    """``runners/serve.py``'s device times of the traced decode blocks and
    prefills, with this model's bytes: every traced step is charged the
    experts, the slot states and the K/V rows that the engine's counters
    say a step of that stretch touched, updated and read; and the grouped
    products' device time beside the bytes their calls had to move (the
    stretch's touched experts, both matrices each, and its pairs' rows)."""
    out = base.trace_scalars(tr, mla._NO_GPTJ_BYTES, eng)
    st = tr.get("stretch_stats") or {}
    need = ("moe_experts_touched", "state_slots_updated", "attn_rows_read")
    if st.get("steps") and all(k in st for k in need):
        experts = st["moe_experts_touched"] / st["steps"]
        slots = st["state_slots_updated"] / st["steps"] / max(
            1, model_dims["n_ssm_layers"])
        rows = st["attn_rows_read"] / st["steps"]
        out["decode_bytes"] = out["decode_steps"] * \
            ssm_moe_model.decode_step_bytes(model_dims, experts, slots, rows)
        out["decode_experts_touched_per_step"] = experts
        out["decode_slots_updated_per_step"] = slots
        out["decode_kv_rows_per_step"] = rows
    else:
        out.pop("decode_bytes", None)
    calls = (tr.get("kernel_calls") or {}).get(KERNEL)
    if calls and out.get("decode_steps") and all(
            k in st for k in ("steps", "moe_experts_touched",
                              "moe_assignments")) and st["steps"]:
        # the share of the stretch's counters that the calls WITH AN EVENT
        # answer for: a step makes two products a routed layer, and a
        # program at the trace's edge has events for some of its calls only
        whole = 2 * model_dims["n_expert_layers"] * out["decode_steps"]
        part = calls / whole * out["decode_steps"] / st["steps"]
        out["grouped_matmul_device_s"] = tr["kernel_s"][KERNEL]
        out["grouped_matmul_calls"] = calls
        out["grouped_matmul_calls_of_steps"] = whole
        out["grouped_matmul_steps"] = out["decode_steps"]
        out["grouped_matmul_bytes"] = ssm_moe_model.grouped_products_cost(
            model_dims, part * st["moe_experts_touched"],
            part * st["moe_assignments"])["bytes"]
    return out


def run(ctx) -> Dict:
    cfg, mix, seconds = ctx["config"], ctx["traffic"], ctx["seconds"]
    import ray_tpu

    if ctx["rehearsal"]:  # the host walks the mix at the tiny engine's sizes
        mix = dict(mix, **cfg["rehearsal"].get("traffic", {}))
    handle, rep, session_dir, model = start_replica(
        ctx, cfg, mix["warm_buckets"])
    try:
        m = measure(handle, ctx, mix, rep, seconds, ctx["trace"])
        # the check comes AFTER the window: what it compiles and runs (the
        # reference, the programs with taps) is no part of ``setup_s``
        served = served_probes(handle, ctx, rep, model)
        pr = probes(handle, ctx, model, served)
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
            # traced runs: device seconds by scope, per program, and what
            # the grouped products' share was made of
            "scope_s": (facts.get("trace") or {}).get("scope_s"),
            "grouped_matmul": {
                **{k: v for k, v in facts["scalars"].items()
                   if k.startswith("grouped_matmul_")},
                "calls_by_program": ((facts.get("trace") or {}).get(
                    "kernel_calls_by_program") or {}).get(KERNEL)},
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
    p.add_argument("--config", default="nemotron3-super-l11-e128-bf16-serve")
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
            if args.rehearse_cpu and "top_k" in ablate:
                ablate = {"top_k": model["num_experts_per_tok"] - 1}
            row = probes(handle, ctx, model, served, ablate)
            print(json.dumps({"ablate": ablate, **row}), flush=True)
    finally:
        ray_tpu.shutdown()
        wait_session_gone(session_dir)
    return common.REHEARSAL_RC if args.rehearse_cpu else 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
