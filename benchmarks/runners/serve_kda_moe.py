"""Serving runner for configurations of kind ``serve_kda_moe``
(gated-delta-rule linear-attention layers that keep a matrix state a head
beside latent-attention layers that keep one row a token, under a chip's
share of dropless routed experts and a shared one; Kimi-Linear's kind):
the same one replica of ``serve.LLMServer`` behind ``serve.run``, the same
load generator, window and trace reduction as ``runners/serve.py``
(``measure``, ``serve_owner``, ``wait_session_gone`` and the deployment's
recorders and control calls are imported from there, the scope reduction
and the stretch's counters from ``runners/serve_mla_moe.py``, the served
probes from ``runners/serve_ssm.py``; nothing there is edited). What
differs is the model's side of the bench:

- the config object, the bf16 weights from the seed and the byte functions
  come from ``benchmarks/kda_moe_model.py``;
- ``correct`` holds what the timed programs produce at the timed sizes to
  ``benchmarks/reference_kda_moe.py`` (``run.probe`` and ``correctness``
  of the configuration), LOGITS, states and layer outputs, not tokens.
  (1) Two seeded prompts whose lengths are no multiples of the chunk go
  through the whole served path; then, on the idle engine, each is run
  again by the window's own programs into slot 0: the logits
  ``prefill_into_slot`` returns after its chunked, padded prefill, the
  logits of the first decode step (which takes the state and the
  convolution's tails over from the prefill) and those after
  ``decode_steps`` more steps of ``decode_block``, teacher-forced on the
  engine's own tokens, against the reference's full forward over prompt +
  answer, each at ``PROBE_ROWS`` positions in a row and reduced to their
  LOWER QUARTILE (a bf16-rounded hidden state flips a near-tie between
  the 8th and 9th of 256 experts: ``runners/serve_swa_moe.py``). (2) The
  state of the first and of the last "kda" layer after those steps, and
  the rows the first full layer has kept of every token by then ([c |
  k_r]: where a rotation of the shared dims, which the residual stream's
  noise hides from the logits, is the whole difference), relative RMS.
  (3) ONE "KDA" LAYER ALONE over seeded rows: the prefill's
  chunked form (outputs and final state) and then ``KDA_STEPS`` decode
  updates from that state, against the reference's token-by-token
  recurrence. (4) ONE ROUTED LAYER ALONE over the held experts and
  the shared one (``routed_ffn``), per token. (3) and (4) are asked of
  the first and of the last layer of their kind, the larger reading
  counting. The reference is driven half a layer a compiled call;
- the traced stretch is also reduced by ``jax.named_scope`` over the
  programs AS THE ENGINE RUNS THEM (the fused admission form of
  ``prefill_into_slot``), the device time of the ``kda_update`` kernel's
  calls is summed beside the states they moved, and ``decode_bytes``
  counts the experts the engine's counters say a step touched, the latent
  rows they say it read and the live lanes' states.

The replica is built in a first CALL, not in the actor's constructor (an
actor whose constructor takes over 120 s never becomes ALIVE). The knee
sweep is ``benchmarks/sweep.py`` with this module as its runner:

    chiprun --timeout 3000 -- python3 -c "import sys; \\
        from benchmarks import sweep; \\
        from benchmarks.runners import serve_kda_moe; \\
        sweep.runner = serve_kda_moe; sys.exit(sweep.main())" \\
        --config kimi-linear-l8-e64-bf16-serve \\
        --traffic longreason-saturated --rates 4,5,6,7 --seeds 1,2 \\
        --seconds 30

The readings of every ``ablate`` switch of the reference (what
``correctness.why`` of the configuration quotes) come from this module
run as a script, on the chip: ``python3 -m benchmarks.runners.
serve_kda_moe --config kimi-linear-l8-e64-bf16-serve --seed <n>``.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from benchmarks import common, kda_moe_model
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
    {"head_decay": True}, {"no_delta": True}, {"decay_after": True},
    {"beta_one": True}, {"no_l2norm": True}, {"drop_conv_tail": True},
    {"state_at_bucket_end": True}, {"state_bf16": True},
    {"silu_gate": True}, {"rotate_kr": True}, {"no_scale": True},
    {"no_shared": True}, {"fp8_weights": True},
)
KDA_STEPS = 128  # decode updates probe (3) makes from its prefill's state
KDA_PAD = 37  # rows of probe (3)'s bucket that are padding
PROBE_ROWS = 8  # positions behind each of probe (1)'s three quartiles
FFN_ROWS = 2048  # rows of the reference's FFN a compiled call
KERNEL = "kda_update"  # the Pallas call's name in the compiled text


def _make_deployment_class():
    from ray_tpu.serve.llm import LLMServer

    class BenchKdaMoe(mla._make_deployment_class()):
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
            cfg = kda_moe_model.transformer_config(spec["model"])
            LLMServer.__init__(
                self,
                lambda: (kda_moe_model.make_bf16_params(cfg, spec["seed"]),
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
            rep["dims"] = kda_moe_model.dims(self.engine.config)
            return rep

        def _served(self, prompt, ids, steps):
            """One probe as the window's programs run it, into slot 0 of
            the idle engine. For each of the prompt's last ``PROBE_ROWS``
            lengths, the whole prompt last: the prefill at its bucket and
            one decode step that takes the slot's state, tails and rows
            over from it (``decode_step_multi``, for its logits; the token
            the prompt holds there, after the whole prompt the engine's
            own). Then ``steps`` more in the long blocks teacher-forced on
            ``ids`` (greedy: the programs give the engine's own tokens
            again, which is checked) and ``PROBE_ROWS`` single steps for
            their logits; last, the first and the last "kda" layer's
            state and the first full layer's rows as the slot then holds
            them."""
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
            kda = cache_state(eng.cache)["kda"]
            kept = jnp.concatenate([eng.cache[k][0, 0, :n + len(fed) - 1]
                                    for k in ("ckv", "kr")], -1)
            return {"prefill": jnp.stack(at_prefill), "rows_first": kept,
                    "first": jnp.stack(at_first),
                    "decode": jnp.stack(at_decode), "fed": fed[:-1],
                    "replayed": replayed, "state_first": kda[0, 0] + 0,
                    "state_last": kda[-1, 0] + 0}

        def _reference(self, tokens, rows, ablate):
            """``reference.forward_logits`` computed half a layer a
            compiled call, each slicing its layer out of the served stacks
            inside the call, so that it fits beside the engine. Returns
            the logits at ``rows`` (of the real tokens), the first and
            the last "kda" layer's state after the last token, and the
            rows the first full layer keeps of the real tokens."""
            import jax
            import jax.numpy as jnp

            from benchmarks import reference_kda_moe as ref

            params = self.engine.params
            hp = kda_moe_model.reference_constants(self.engine.config)
            seq, real = ref.with_padding(tokens, ablate)
            unseen = jnp.asarray(~real)
            with jax.default_matmul_precision("highest"):
                @jax.jit
                def mix(x, stack, i):
                    lp = jax.tree.map(lambda a: a[i], {
                        k: v for k, v in stack.items()
                        if k not in ("moe", "mlp")})
                    return ref.mix(x, lp, hp, ablate, unseen)

                @jax.jit
                def kept(x, stack):
                    lp = jax.tree.map(lambda a: a[0], {
                        k: stack[k] for k in ("ln1", "attn")})
                    return ref.kept_rows(
                        ref._rms_norm(x, ref._weights(ablate)(
                            lp["ln1"]["scale"]), hp["eps"]),
                        lp["attn"], hp, ablate)

                @jax.jit
                def ffn(x, stack, i):
                    lp = jax.tree.map(lambda a: a[i], {
                        k: v for k, v in stack.items()
                        if k not in ("attn", "kda")})
                    return ref.ffn(x, lp, hp, ablate)

                x = jax.jit(ref.embed)(params, seq)
                first = last = None
                for name, i in ref.layers_in_order(params, hp):
                    if (name, i) == ("layers", 0):
                        rows_first = kept(x, params[name])[
                            np.flatnonzero(real)]
                    i = jnp.int32(i)
                    x, state = mix(x, params[name], i)
                    if state is not None:
                        first, last = (state if first is None else first,
                                       state)
                    # per token: a stretch of rows at a time is exact
                    x = jnp.concatenate([
                        ffn(x[a:a + FFN_ROWS], params[name], i)
                        for a in range(0, x.shape[0], FFN_ROWS)])
                logits = jax.jit(lambda p, x: ref.head(p, x, hp, ablate))(
                    params, x[np.flatnonzero(real)[np.asarray(rows)]])
            return logits, first, last, rows_first

        def _cmd_reference(self, prompt, ids, steps, ablate=None):
            """Probes (1) and (2) of one prompt against the plain
            reference (``ablate``: against a deliberately wrong one): for
            each of the three kinds of logits the lower quartile over its
            ``PROBE_ROWS`` positions of their distance (and every
            position's, sorted, for the note), and the two states'."""
            import jax.numpy as jnp

            from benchmarks import reference_kda_moe as ref

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
            k, end = PROBE_ROWS, len(tokens)
            rows = {"prefill": range(n - k, n), "first": range(n - k + 1,
                                                               n + 1),
                    "decode": range(end - k, end)}
            want, first, last, rows_first = self._reference(
                tokens, [r for kind in rows.values() for r in kind], ablate)
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
                **out,
                "state_first": float(ref.state_distance(
                    got["state_first"], first)),
                "state_last": float(ref.state_distance(
                    got["state_last"], last)),
                "rows_first": float(ref.state_distance(
                    got["rows_first"], rows_first)),
                "logits_rms": float(size[-1]),
                "top2_gap": np.asarray(top2[:, 1] - top2[:, 0]).tolist(),
                "replayed": got["replayed"], "tokens": len(tokens)}

        def _seeded_input(self, seed, rows):
            import jax

            cfg = self.engine.config
            return jax.random.normal(jax.random.key(seed & 0x7FFFFFFF),
                                     (rows, cfg.d_model)).astype(cfg.dtype)

        def _cmd_kda_layer(self, seed, rows, ablate=None, layer=0):
            """Probe (3): ONE routed "kda" layer's mixer alone (``layer``
            of their stack: the first, published layer 2, and the last,
            layer 7, are asked for) over
            a seeded (normed) input, as the programs run it. The prefill's
            chunked form over the first ``rows - KDA_PAD`` rows (no
            multiple of the chunk; padded to ``rows``), which also hands
            over the state and the convolution's tail; then, from those,
            ``KDA_STEPS`` decode updates one after the other. Against the
            reference's token-by-token recurrence over the same input:
            per row the relative error of the layer's output, the two
            stretches apart, and the relative RMS of the state where the
            prefill ends and after the last update."""
            import jax
            import jax.numpy as jnp

            from benchmarks import reference_kda_moe as ref
            from ray_tpu.models import generation as gen
            from ray_tpu.models import transformer as tf

            cfg = self.engine.config
            kda = self.engine.params["kda_layers"]["kda"]
            layer %= jax.tree.leaves(kda)[0].shape[0]  # -1: the last
            n0 = rows - KDA_PAD
            x = self._seeded_input(seed, rows + KDA_STEPS)
            inner = cfg.kda_inner

            def at(tree):
                return jax.tree.map(lambda a: a[layer], tree)

            @jax.jit
            def program(x, kda):
                wp = at(kda)
                slot = {"state": {
                    "kda": jnp.zeros((1, 1, cfg.kda_heads, cfg.kda_head_dim,
                                      cfg.kda_head_dim), jnp.float32),
                    "conv": jnp.zeros((1, 1, (cfg.kda_conv - 1) * 3 * inner),
                                      cfg.dtype)}}
                out, slot = tf._kda_mixer(
                    x[None, :rows], wp, cfg, None,
                    gen._prefill_kda(slot, 0, jnp.int32(n0), cfg))
                handed = slot["state"]["kda"][0, 0]

                def step(slot, t):
                    # the rows after the padding: position n0 + t reads
                    # input row rows + t
                    o, slot = tf._kda_mixer(
                        jax.lax.dynamic_slice_in_dim(
                            x, rows + t, 1)[None], wp, cfg, None,
                        gen._decode_kda(slot, 0, (n0 + t)[None], cfg))
                    return slot, o[0, 0]

                slot, dec = jax.lax.scan(step, slot, jnp.arange(KDA_STEPS))
                return out[0, :n0], dec, handed, slot["state"]["kda"][0, 0]

            if ("kda", seed, layer) not in self._kept:
                self._kept["kda", seed, layer] = tuple(
                    a.astype(jnp.float32) for a in program(x, kda))
            got_chunk, got_step, got_handed, got_end = self._kept[
                "kda", seed, layer]
            hp = kda_moe_model.reference_constants(cfg)
            ablate = dict(ablate or {})
            if ablate.get("drop_conv_tail") is True:
                ablate["drop_conv_tail"] = n0
            ablate.pop("state_at_bucket_end", None)  # probe (1)'s alone
            seq = jnp.concatenate([x[:n0], x[rows:]]).astype(jnp.float32)
            with jax.default_matmul_precision("highest"):
                mixer = jax.jit(lambda h, kda: ref.kda(
                    h, at(kda), hp, ablate))
                want, want_end = mixer(seq, kda)
                _, want_handed = mixer(seq[:n0], kda)

            def err(got, want):
                return jnp.linalg.norm(got - want, axis=-1) / jnp.linalg.norm(
                    want, axis=-1)

            chunk, step = err(got_chunk, want[:n0]), err(got_step, want[n0:])
            return {"chunk_median": float(jnp.median(chunk)),
                    "chunk_q90": float(jnp.quantile(chunk, 0.9)),
                    "chunk_state": float(ref.state_distance(
                        got_handed, want_handed)),
                    "step_median": float(jnp.median(step)),
                    "step_largest": float(step.max()),
                    "step_state": float(ref.state_distance(
                        got_end, want_end))}

        def _routed_program(self, seed, tokens, stack, layer):
            """Probe (4)'s served half: ``routed_ffn`` over the held share
            of routed layer ``layer`` of ``stack`` on the seeded input,
            float32; run once and kept."""
            import jax
            import jax.numpy as jnp

            from ray_tpu.ops.moe import routed_ffn

            cfg = self.engine.config

            @jax.jit
            def program(x, moe):
                held = {k: moe[k] for k in ("wg", "wi", "wo")}
                rest = {k: v for k, v in moe.items() if k not in held}
                return routed_ffn(
                    x, {**jax.tree.map(lambda a: a[layer], rest), **held,
                        "layer": layer},
                    top_k=cfg.moe_top_k, route_scale=cfg.moe_route_scale,
                    first_expert=cfg.moe_first_expert)[0]

            key = ("routed", seed, tokens, stack, layer)
            if key not in self._kept:
                self._kept[key] = jax.block_until_ready(
                    program(self._seeded_input(seed, tokens),
                            self.engine.params[stack]["moe"]
                            ).astype(jnp.float32))
            return self._kept[key]

        def _cmd_routed_layer(self, seed, tokens, ablate=None,
                              stack="kda_layers", layer=0):
            """Probe (4): ONE routed layer alone (``layer`` of ``stack``:
            the first, published layer 2, and the last, layer 8 under the
            second full layer, are asked for) over the held
            share and the shared expert, as the program runs it
            (``routed_ffn``), against the reference's loop over the held
            experts on the same seeded input: per token the relative error
            of the layer's output."""
            import jax
            import jax.numpy as jnp

            from benchmarks import reference_kda_moe as ref

            cfg = self.engine.config
            moe = self.engine.params[stack]["moe"]
            layer %= moe["router"].shape[0]  # -1: the stack's last
            x = self._seeded_input(seed, tokens)
            got = self._routed_program(seed, tokens, stack, layer)
            hp = kda_moe_model.reference_constants(cfg)
            ablate = dict(ablate or {})
            with jax.default_matmul_precision("highest"):
                want = jax.jit(lambda x, moe: ref.routed_experts(
                    x.astype(jnp.float32),
                    jax.tree.map(lambda a: a[layer], moe), hp, ablate))(
                        x, moe)
            err = jnp.linalg.norm(got - want, axis=-1) / jnp.linalg.norm(
                want, axis=-1)
            return {"median": float(jnp.median(err)),
                    "largest": float(err.max()),
                    "share_over_5pct": float((err > 0.05).mean())}

        def _cmd_trace_reduce(self, keep_copy, rehearsal=False):
            """``runners/serve_mla_moe.py``'s, and the device seconds and
            the calls of the ``kda_update`` kernel inside the traced
            ``decode_block`` programs (an operation's event is named by
            its HLO text, ``%kda_update.36 = ...``)."""
            import bisect

            from benchmarks import trace

            red = super()._cmd_trace_reduce(keep_copy, rehearsal)
            dev = trace.load(trace.find_xplane(self._trace_dir),
                             rehearsal=rehearsal)["devices"]
            calls, seconds = 0, 0.0
            for d in dev.values():
                progs = sorted((p["start"], p["end"]) for p in d["programs"]
                               if trace.program_of(p["name"])
                               == "decode_block")
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

    return BenchKdaMoe


def _program_has_the_block() -> None:
    """Before any process starts: a program from before this kind existed
    cannot describe the block, and says so at once (importing the module
    imports JAX and initialises no backend)."""
    import dataclasses

    from ray_tpu.models.transformer import TransformerConfig

    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    if not {"layer_types", "kda_heads", "kda_head_dim", "mla_rope"
            } <= fields:
        raise BenchFailure(
            "this program's TransformerConfig cannot describe 'kda' "
            "layers beside latent attention layers: the cell cannot run")


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
    "state_last": "state_last_tol", "rows_first": "rows_first_tol",
    "kda_layer.chunk_median": "kda_chunk_median_tol",
    "kda_layer.chunk_q90": "kda_chunk_q90_tol",
    "kda_layer.chunk_state": "kda_chunk_state_tol",
    "kda_layer.step_median": "kda_step_median_tol",
    "kda_layer.step_largest": "kda_step_largest_tol",
    "kda_layer.step_state": "kda_step_state_tol",
    "routed_layer.median": "routed_layer_median_tol",
    "routed_layer.share_over_5pct": "routed_layer_share_tol",
}


def probes(handle, ctx, model, served: Dict,
           ablate: Optional[Dict] = None) -> Dict:
    """The four probes against the plain reference (``ablate``: against
    a deliberately wrong one). Probes (1) and (2) are reduced to the
    larger reading of the two prompts, probes (3) and (4) to the larger
    reading of the first and the last layer of their kind (the second
    period's layers are so judged alone too, where the residual stream's
    routing noise does not reach): every limit must hold for both.
    ``refused_by`` names the limits a reading passed."""
    size, tol = model["run"]["probe"], model["correctness"]
    rows = [handle.remote("reference", p.tolist(), ids, size["decode_steps"],
                          ablate).result(timeout=2400)
            for p, ids in zip(served["prompts"], served["ids"])]
    kdas = [handle.remote("kda_layer", ctx["seed"] + 3,
                          size["kda_layer_rows"], ablate, i).result(
                              timeout=2400) for i in (0, -1)]
    routeds = [handle.remote("routed_layer", ctx["seed"] + 2,
                             size["routed_layer_tokens"], ablate, *at).result(
                                 timeout=2400)
               for at in (("kda_layers", 0), ("layers", -1))]
    kda, routed = ({k: max(r[k] for r in both) for k in both[0]}
                   for both in (kdas, routeds))
    out = {
        **{k: max(r[k] for r in rows) for k in (
            "prefill_rel", "first_rel", "decode_rel", "state_first",
            "state_last", "rows_first")},
        "replayed": all(r["replayed"] for r in rows),
        "by_prompt": [{k: (round(v, 5) if isinstance(v, float) else v)
                       for k, v in r.items() if k != "top2_gap"}
                      for r in rows],
        "median_top2_gap": mla._quantile(
            [g for r in rows for g in r["top2_gap"]], 0.5),
        "kda_layer": kda, "routed_layer": routed,
        "first_and_last": {"kda_layer": kdas, "routed_layer": routeds}}

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
    experts, the latent rows and the live lanes' states that the engine's
    counters say a step of that stretch touched, read and stepped; and
    the ``kda_update`` kernel's device time beside the bytes its calls
    had to move (a call the states of one layer that the stretch's
    ``state_slots_updated`` says it stepped, in and out: the live lanes',
    never ``max_slots``)."""
    out = base.trace_scalars(tr, mla._NO_GPTJ_BYTES, eng)
    st = tr.get("stretch_stats") or {}
    need = ("moe_experts_touched", "attn_rows_read", "slot_steps")
    if st.get("steps") and all(k in st for k in need):
        per_step = {k: st[k] / st["steps"] for k in need}
        out["decode_bytes"] = out["decode_steps"] * \
            kda_moe_model.decode_step_bytes(
                model_dims, *(per_step[k] for k in need))
        out["decode_experts_touched_per_step"] = per_step[need[0]]
        out["decode_latent_rows_per_step"] = per_step[need[1]]
        out["decode_live_slots_per_step"] = per_step[need[2]]
    else:
        out.pop("decode_bytes", None)
    calls = (tr.get("kernel_calls") or {}).get(KERNEL)
    if calls:
        out["kda_update_device_s"] = tr["kernel_s"][KERNEL]
        out["kda_update_calls"] = calls
        # a call steps the live lanes' states alone (ops/kda.kda_update,
        # since PR 46): charge what the stretch's counters say it stepped,
        # and nothing where they are missing (the share is then left out)
        if st.get("steps") and "state_slots_updated" in st:
            lanes = (st["state_slots_updated"] / st["steps"]
                     / model_dims["n_kda_layers"])
            out["kda_update_states_per_call"] = lanes
            out["kda_update_bytes"] = kda_moe_model.kda_update_cost(
                model_dims, calls * lanes)["bytes"]
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
            # traced runs: device seconds by scope, per program, and
            # what the kernel's share was made of
            "scope_s": (facts.get("trace") or {}).get("scope_s"),
            "kda_update": {k: v for k, v in facts["scalars"].items()
                           if k.startswith("kda_update_")},
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
    p.add_argument("--config", default="kimi-linear-l8-e64-bf16-serve")
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--rehearse-cpu", action="store_true")
    p.add_argument("--prompts", type=int, default=None,
                   help="probe (1)'s first N prompts alone (the long one's "
                        "reference takes 90 s a switch)")
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
        served = {k: v[:args.prompts] for k, v in served.items()}
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
