"""Serving runner for configurations of kind ``serve_dsa_moe`` (latent
attention with a learned selection of cache rows, a chip's share of
dropless routed experts; GLM-5.2's block): the same one replica of
``serve.LLMServer`` behind ``serve.run``, the same load generator, window
and trace reduction as ``runners/serve.py`` (``measure``,
``trace_scalars``, ``serve_owner``, ``wait_session_gone`` and the
deployment's recorders and control calls are imported from there, the
scope reduction and the stretch's counters from ``runners/
serve_mla_moe.py``; nothing there is edited). What differs is the model's
side of the bench:

- the config object, the bf16 weights from the seed and the byte function
  come from ``benchmarks/dsa_moe_model.py``;
- ``correct`` holds the served path to ``benchmarks/reference_dsa_moe.py``
  at the cell's own sizes by four probes (``run.probe`` and
  ``correctness`` of the configuration): (1) seeded prompts through the
  whole served path, the served tokens' margins and the logit vectors
  ``prefill_into_slot`` (the timed program at the timed bucket) returns;
  (2) THE SELECTION ALONE: the first layer's chosen rows for queries
  spread over a long seeded input, as the prefill chooses them
  (``generation._prefill_choice``) and as a decode step does
  (``_decode_choice``), against the reference's explicit top-k on the
  same input; (3) ONE SELECTED ATTENTION LAYER ALONE, the program's mixer
  with the prefill's attention over a seeded input against the
  reference's, relative to the output's own size; (4) the first expert
  layer alone over the held share;
- the traced stretch is also reduced by ``jax.named_scope``, and
  ``decode_bytes`` counts the experts touched, the index keys scored and
  the rows attended that the engine's counters give for that stretch.

The knee sweep is ``benchmarks/sweep.py`` with this module as its runner:

    chiprun --timeout 3000 -- python3 -c "import sys; \\
        from benchmarks import sweep; \\
        from benchmarks.runners import serve_dsa_moe; \\
        sweep.runner = serve_dsa_moe; sys.exit(sweep.main())" \\
        --config glm52-l6-e16-bf16-serve --traffic longdoc-steady \\
        --rates 0.6,0.8,1.0,1.2 --seeds 1,2 --seconds 30

The readings of every ``ablate`` switch of the reference (what
``correctness.why`` of the configuration quotes) come from this module
run as a script, on the chip: ``python3 -m benchmarks.runners.
serve_dsa_moe --config glm52-l6-e16-bf16-serve --seed <n>``.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from benchmarks import common, dsa_moe_model
from benchmarks.common import BenchFailure
from benchmarks.runners import serve as base
from benchmarks.runners import serve_mla_moe as mla
from benchmarks.runners.serve import (  # noqa: F401 (sweep.py's runner API)
    measure,
    serve_owner,
    wait_session_gone,
)

ABLATIONS = (
    {"no_selection": True}, {"index_topk": None},  # None: half the cell's
    {"shared_chooses_afresh": True}, {"no_relu": True},
    {"unrotated_index_k": True}, {"no_index_layernorm": True},
    {"weights_over_held": True}, {"fp8_weights": True},
)


def _make_deployment_class():
    from ray_tpu.serve.llm import LLMServer

    class BenchDsaMoe(mla._make_deployment_class()):
        """``runners/serve_mla_moe.py``'s deployment (recorders, ``stream``,
        trace, scopes and counters) around this kind's model, reference
        and probes."""

        def __init__(self, spec):
            self._spec = spec

        def _cmd_build(self):
            """Everything a replica's constructor does elsewhere, as the
            first call: weights, engine, every bucket warmed through the
            engine. This replica compiles eight programs of up to 24,576
            tokens, two to five minutes cold, and an actor whose
            constructor takes over 120 s never becomes ALIVE (the GCS's
            ``create_actor`` call times out and placement is tried again
            while the first worker still builds; seen on the chip, PR 32).
            A call may take as long as it needs. Returns the report."""
            import jax
            import jax.numpy as jnp

            spec = self._spec
            self.rec = base._Recorder()

            def on_event(event, *_a, **_kw):
                if event.endswith("backend_compile_duration"):
                    self.rec.builds += 1

            jax.monitoring.register_event_duration_secs_listener(on_event)
            eng = spec["engine"]
            cfg = dsa_moe_model.transformer_config(spec["model"])
            LLMServer.__init__(
                self,
                lambda: (dsa_moe_model.make_bf16_params(cfg, spec["seed"]),
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
            self._kept = {}  # what the program gave a probe, for ablations
            return self._cmd_report()

        def _cmd_report(self):
            rep = super()._cmd_report()
            rep["dims"] = dsa_moe_model.dims(self.engine.config)
            return rep

        def _hp(self, ablate):
            hp = dsa_moe_model.reference_constants(self.engine.config)
            ablate = dict(ablate or {})
            if "index_topk" in ablate and ablate["index_topk"] is None:
                ablate["index_topk"] = hp["index_topk"] // 2
            return hp, ablate

        def _first_layer(self):
            """The first layer's weights (it owns an indexer), as the
            program's scan hands them to the mixer, and as the reference
            takes them."""
            import jax
            import jax.numpy as jnp

            p = self.engine.params
            stack = p.get("dense_layers", p["layers"])
            lp = jax.tree.map(lambda a: a[0], {
                k: v for k, v in stack.items() if k != "moe"})
            ip = lp["attn"].pop("indexer")
            wp = {**lp["attn"], "indexer": stack["attn"]["indexer"],
                  "index_own": jnp.bool_(True),
                  "index_local": jnp.int32(0), "index_slot": jnp.int32(0)}
            return lp, ip, wp

        def _reference_logits(self, seq, last, ablate):
            """``reference.forward_logits`` computed in blocks so that it
            fits beside the engine (4.7 GB are free): the reference's own
            functions, one layer's attention and one layer's FFN a
            compiled call, each slicing its layer out of the served stacks
            inside the call (the whole forward as one program wanted 6.8
            GB at 8,192 tokens; every layer's weights sliced out at once,
            as ``reference.layers_of`` does, 6 GB more)."""
            import jax
            import jax.numpy as jnp

            from benchmarks import reference_dsa_moe as ref

            hp, ablate = self._hp(ablate)
            w = ref._weights(ablate)
            params = self.engine.params

            def at(tree, i):
                return jax.tree.map(lambda a: a[i], tree)

            with jax.default_matmul_precision("highest"):
                @jax.jit
                def attend(x, ln1, attn, i, chooser, mask):
                    lp = {"attn": at(attn, i)}
                    ip = None if chooser is None else at(*chooser)
                    return ref.attention(ref._rms_norm(
                        x, w(at(ln1, i)["scale"]), hp["eps"]),
                        lp, ip, hp, ablate, mask)

                @jax.jit
                def ffn(x, a, ln2, rest, i):
                    h = x + a
                    n = ref._rms_norm(h, w(at(ln2, i)["scale"]), hp["eps"])
                    if "moe" in rest:
                        return h + ref.routed_experts(
                            n, at(rest["moe"], i), hp, ablate)
                    m = at(rest["mlp"], i)
                    return h + ref.gated_ffn(n, w(m["wg"]), w(m["wi"]),
                                             w(m["wo"]))

                @jax.jit
                def head(x, scale, lm_head):
                    return ref._rms_norm(x[-last:], w(scale),
                                         hp["eps"]) @ w(lm_head)

                x = params["embed"][seq].astype(jnp.float32)
                kinds = list(hp["indexer_types"])
                mask = below = None
                n_done = 0
                for group in ("dense_layers", "layers"):
                    if group not in params:
                        continue
                    stack = params[group]
                    attn = {k: v for k, v in stack["attn"].items()
                            if k != "indexer"}
                    rest = {k: stack[k] for k in ("moe", "mlp")
                            if k in stack}
                    own = 0
                    for i in range(stack["ln1"]["scale"].shape[0]):
                        chooser = None
                        if kinds[n_done] == "full":
                            chooser = below = (
                                stack["attn"]["indexer"], jnp.int32(own))
                            own += 1
                        elif ablate.get("shared_chooses_afresh"):
                            chooser = below
                        a, mask = attend(x, stack["ln1"], attn, jnp.int32(i),
                                         chooser, mask)
                        x = ffn(x, a, stack["ln2"], rest, jnp.int32(i))
                        n_done += 1
                return head(x, params["final_ln"]["scale"],
                            params["lm_head"])

        def _cmd_reference(self, prompt, ids, positions, ablate=None):
            """Probe (1): margins of the served tokens at the decoded
            positions under the plain reference over the same weights, and
            the distance of the logit vectors that ``prefill_into_slot``
            returns for the prompt cut after its last ``positions`` tokens
            in turn (the same program and bucket every time)."""
            import jax
            import jax.numpy as jnp

            from benchmarks import reference_dsa_moe as reference
            from ray_tpu.models.generation import prefill_into_slot

            eng, n = self.engine, len(prompt)
            s = eng.stats()
            if s["active"] or s["pending"]:
                raise BenchFailure("a probe needs an idle engine")
            key = ("reference", tuple(prompt[:8]))
            if key not in self._kept:
                padded = np.zeros((1, eng._bucket_for(n)), np.int32)
                padded[0, :n] = prompt
                served = []
                for j in range(positions):
                    logits, eng.cache = prefill_into_slot(
                        eng.params, jnp.asarray(padded), jnp.int32(n - j),
                        jnp.int32(0), eng.cache, eng.config)
                    served.append(logits)
                self._kept[key] = served
            served = self._kept[key]
            seq = jnp.asarray(list(prompt) + list(ids[:-1]), jnp.int32)
            want = self._reference_logits(
                seq, positions - 1 + len(ids), ablate)
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

        def _seeded_input(self, seed, rows):
            import jax

            cfg = self.engine.config
            return jax.random.normal(
                jax.random.key(seed & 0x7FFFFFFF),
                (rows, cfg.d_model)).astype(cfg.dtype)

        def _cmd_selection(self, seed, rows, n_queries, ablate=None):
            """Probe (2), the selection alone: over a seeded input of
            ``rows`` rows, the first layer's chosen rows for ``n_queries``
            queries spread over it as the PREFILL chooses them, and for
            the last row as a DECODE step chooses it from the index keys
            the prefill would have cached, each against the reference's
            explicit top-k on the same input: per query the share of the
            reference's rows that the program chose too."""
            import jax
            import jax.numpy as jnp

            from benchmarks import reference_dsa_moe as reference
            from ray_tpu.models import generation as gen
            from ray_tpu.models.transformer import _mla_mixer, _rms_norm

            cfg = self.engine.config
            lp, ip, wp = self._first_layer()
            x = self._seeded_input(seed, rows)
            queries = jnp.asarray(np.unique(np.linspace(
                cfg.index_topk, rows - 1, n_queries).astype(np.int32)))
            topk = cfg.index_topk

            @jax.jit
            def program(x, lp, wp):
                h = _rms_norm(x, lp["ln1"]["scale"], cfg.norm_eps)[None]
                got = {}

                @gen._latent
                def attn(q_nope, *_a):  # the mixer's projections only
                    return jnp.zeros(q_nope.shape[:3] + (cfg.v_head_dim,),
                                     cfg.dtype)

                attn.choose = lambda project: got.update(zip(
                    "qkw", project(gen._own_indexer(wp))))
                _mla_mixer(h, wp, cfg, jnp.arange(rows), attn)
                q, k, w = got["q"][0], got["k"][0], got["w"][0]
                prefill = gen._prefill_choice(q, k, w, topk)[queries]
                last = rows - 1
                decode = gen._decode_choice(
                    q[last:], w[last:], k[None, None], jnp.int32(0),
                    k[last:], jnp.full((1,), last, jnp.int32), topk)
                return prefill, decode

            if ("selection", seed) not in self._kept:
                self._kept["selection", seed] = program(x, lp, wp)
            prefill, decode = self._kept["selection", seed]
            hp, ablate = self._hp(ablate)
            with jax.default_matmul_precision("highest"):
                @jax.jit
                def want(x, lp, ip):
                    f32 = jnp.float32
                    h = reference._rms_norm(
                        x.astype(f32), lp["ln1"]["scale"].astype(f32),
                        hp["eps"])
                    c_q = reference.query_latent(h, lp["attn"], hp, ablate)
                    return reference.chosen_rows(reference.index_scores(
                        h, c_q, ip, hp, ablate, queries),
                        ablate.get("index_topk", topk))

                ref = want(x, lp, ip)
            common_rows = (prefill & ref).sum(-1) / ref.sum(-1)
            at_decode = (decode[0] & ref[-1]).sum() / ref[-1].sum()
            return {"prefill": np.asarray(common_rows).tolist(),
                    "decode": float(at_decode)}

        def _cmd_attention_layer(self, seed, rows, ablate=None):
            """Probe (3), one selected attention layer alone: the
            program's mixer of the first layer with the prefill's own
            attention (``generation._prefill_attn_chosen``) over a seeded
            input against the reference's: per token the error of the
            layer's output relative to the output's own size."""
            import jax
            import jax.numpy as jnp

            from benchmarks import reference_dsa_moe as reference
            from ray_tpu.models import generation as gen
            from ray_tpu.models.transformer import _mla_mixer, _rms_norm

            cfg = self.engine.config
            lp, ip, wp = self._first_layer()
            x = self._seeded_input(seed, rows)

            @jax.jit
            def program(x, lp, wp):
                h = _rms_norm(x, lp["ln1"]["scale"], cfg.norm_eps)[None]
                single = gen.init_kv_cache(cfg, 1, rows)
                choice = {"mask": jnp.zeros((rows, rows), bool),
                          "k": jnp.zeros((rows, cfg.index_head_dim),
                                         cfg.dtype)}
                attn = gen._prefill_attn_chosen(
                    single, jnp.int32(0), wp, choice, cfg)
                return _mla_mixer(h, wp, cfg, jnp.arange(rows), attn)[0][0]

            if ("attention", seed) not in self._kept:
                self._kept["attention", seed] = program(x, lp, wp).astype(
                    jnp.float32)
            got = self._kept["attention", seed]
            hp, ablate = self._hp(ablate)
            with jax.default_matmul_precision("highest"):
                @jax.jit
                def want(x, lp, ip):
                    f32 = jnp.float32
                    h = reference._rms_norm(
                        x.astype(f32), lp["ln1"]["scale"].astype(f32),
                        hp["eps"])
                    return reference.attention(h, lp, ip, hp, ablate)[0]

                ref = want(x, lp, ip)
            err = jnp.linalg.norm(got - ref, axis=-1) / jnp.linalg.norm(
                ref, axis=-1)
            past = err[cfg.index_topk:]  # queries that had to choose
            return {"median": float(jnp.median(past)),
                    "q90": float(jnp.quantile(past, 0.9)),
                    "median_all_rows_attended": float(
                        jnp.median(err[:cfg.index_topk]))}

        def _cmd_routed_layer(self, seed, tokens, ablate=None):
            """Probe (4): the first expert layer alone over the held
            share, as the program runs it (``routed_ffn``), against the
            reference's loop over the held experts on the same seeded
            input: per token the relative error of the layer's output."""
            import jax
            import jax.numpy as jnp

            from benchmarks import reference_dsa_moe as reference
            from ray_tpu.ops.moe import routed_ffn

            cfg = self.engine.config
            moe = self.engine.params["layers"]["moe"]
            x = self._seeded_input(seed, tokens)

            def first(tree):
                return jax.tree.map(lambda a: a[0], tree)

            @jax.jit
            def program(x, moe):
                held = {k: moe[k] for k in ("wg", "wi", "wo")}
                rest = {k: v for k, v in moe.items() if k not in held}
                return routed_ffn(
                    x, {**first(rest), **held, "layer": 0},
                    top_k=cfg.moe_top_k, route_scale=cfg.moe_route_scale,
                    first_expert=cfg.moe_first_expert)[0]

            if ("routed", seed) not in self._kept:
                self._kept["routed", seed] = program(x, moe).astype(
                    jnp.float32)
            got = self._kept["routed", seed]
            hp, ablate = self._hp(ablate)
            with jax.default_matmul_precision("highest"):
                want = jax.jit(lambda x, moe: reference.routed_experts(
                    x.astype(jnp.float32), first(moe), hp, ablate))(x, moe)
            err = jnp.linalg.norm(got - want, axis=-1) / jnp.linalg.norm(
                want, axis=-1)
            return {"median": float(jnp.median(err)),
                    "largest": float(err.max()),
                    "share_over_5pct": float((err > 0.05).mean())}

    return BenchDsaMoe


def _program_has_the_block() -> None:
    """Before any process starts: a program from before this kind existed
    cannot describe the block, and says so at once (importing the module
    imports JAX and initialises no backend)."""
    import dataclasses

    from ray_tpu.models.transformer import TransformerConfig

    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    if not {"index_topk", "indexer_types", "moe_experts_held"} <= fields:
        raise BenchFailure(
            "this program's TransformerConfig cannot describe a learned "
            "selection of cache rows and a held share of the experts: the "
            "cell cannot run")


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
        rep = handle.remote("build").result(timeout=2400)
        rep["engine"] = run_cfg["engine"]
        ctx["check_device"](rep)
    except BaseException:  # no TPU, wrong device: leave no process behind
        ray_tpu.shutdown()
        wait_session_gone(session_dir)
        raise
    return handle, rep, session_dir, model


def served_probes(handle, ctx, rep, model) -> Dict:
    """The seeded prompts of probe (1), each alone through the whole
    served path: what the window's programs produced."""
    size = model["run"]["probe"]
    rng = np.random.default_rng(ctx["seed"] + 1)
    out = {"prompts": [], "ids": []}
    for _ in range(size["n"]):
        p = rng.integers(0, rep["dims"]["vocab_size"],
                         size["prompt_tokens"], dtype=np.int32)
        ids = base._collect(handle.stream(
            p, max_new_tokens=size["new_tokens"]))
        if len(ids) != size["new_tokens"]:
            raise BenchFailure(f"probe returned {len(ids)} ids")
        base._wait_idle(handle)
        out["prompts"].append(p)
        out["ids"].append(ids)
    return out


def probes(handle, ctx, model, served: Dict,
           ablate: Optional[Dict] = None) -> Dict:
    """The four probes against the plain reference (``ablate``: against a
    deliberately wrong one), reduced to the statistics the limits are on.
    As for the latent / routed kind, a bf16-rounded hidden state flips a
    near-tie between two experts (and here between two rows at the edge
    of a choice) at some positions, so probe (1)'s limits are on robust
    statistics over all probed positions: the MEDIAN margin and the LOWER
    QUARTILE of the prefill vectors' distances. Probes (2)-(4) compare one
    layer on the same input, where nothing upstream can flip."""
    size, tol = model["run"]["probe"], model["correctness"]
    margins, rms, largest, gaps = [], [], [], []
    for p, ids in zip(served["prompts"], served["ids"]):
        ref = handle.remote(
            "reference", p.tolist(), ids, size["prefill_positions"],
            ablate).result(timeout=2400)
        margins += ref["margin"]
        rms += ref["prefill_rms"]
        largest += ref["prefill_max"]
        gaps += ref["top2_gap"]
    sel = handle.remote(
        "selection", ctx["seed"] + 3, size["selection_rows"],
        size["selection_queries"], ablate).result(timeout=2400)
    att = handle.remote("attention_layer", ctx["seed"] + 4,
                        size["attention_rows"], ablate).result(timeout=2400)
    layer = handle.remote("routed_layer", ctx["seed"] + 2,
                          size["routed_layer_tokens"], ablate).result(
                              timeout=2400)
    q = mla._quantile
    out = dict(
        margin_median=q(margins, 0.5),
        margin_zero_share=sum(m == 0 for m in margins) / len(margins),
        margin_largest=max(margins),
        prefill_rms_q25=q(rms, 0.25), prefill_max_q25=q(largest, 0.25),
        prefill_rms=sorted(round(x, 4) for x in rms),
        median_top2_gap=q(gaps, 0.5),
        selection_common_median=q(sel["prefill"], 0.5),
        selection_common_least=min(sel["prefill"] + [sel["decode"]]),
        selection_common_decode=sel["decode"],
        attention_layer=att, routed_layer=layer)
    out["ok"] = bool(
        out["margin_median"] <= tol["margin_median_tol"]
        and out["prefill_rms_q25"] <= tol["prefill_rms_q25_tol"]
        and out["prefill_max_q25"] <= tol["prefill_max_q25_tol"]
        and out["selection_common_median"] >= tol["selection_median_min"]
        and out["selection_common_least"] >= tol["selection_least_min"]
        and att["median"] <= tol["attention_layer_median_tol"]
        and att["q90"] <= tol["attention_layer_q90_tol"]
        and layer["median"] <= tol["routed_layer_median_tol"]
        and layer["share_over_5pct"] <= tol["routed_layer_share_tol"])
    return out


def trace_scalars(tr: Dict, model_dims: Dict, eng: Dict) -> Dict:
    """``runners/serve.py``'s device times of the traced decode blocks and
    prefills, with this model's bytes: every traced step is charged what
    the engine's counters say a step of that stretch touched, scored and
    attended."""
    out = base.trace_scalars(tr, mla._NO_GPTJ_BYTES, eng)
    st = tr.get("stretch_stats") or {}
    need = ("moe_experts_touched", "dsa_rows_scored", "dsa_rows_selected")
    if st.get("steps") and all(k in st for k in need):
        per = {k: st[k] / st["steps"] for k in need}
        out["decode_bytes"] = out["decode_steps"] * \
            dsa_moe_model.decode_step_bytes(model_dims, *(
                per[k] for k in need))
        out.update({"decode_" + k + "_per_step": v for k, v in per.items()})
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
    if s["ttft_ms"]:
        e2e["ttft_p50_ms"] = common.percentile(s["ttft_ms"], 50)
    if s["tpot_ms"]:
        e2e["tpot_p50_ms"] = common.percentile(s["tpot_ms"], 50)
    facts["scalars"]["peak_bytes"] = final["peak_bytes"]
    facts["scalars"].update(mla.moe_scalars(
        m["backlog"], {"moe_experts": rep["dims"]["moe_experts_held"]}))
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
    p.add_argument("--config", default="glm52-l6-e16-bf16-serve")
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
    bucket = min(b for b in run_cfg["engine"]["prefill_buckets"]
                 if b >= run_cfg["probe"]["prompt_tokens"])
    handle, rep, session_dir, model = start_replica(ctx, cfg, [bucket])
    try:
        served = served_probes(handle, ctx, rep, model)
        for ablate in ({},) + ABLATIONS:
            row = probes(handle, ctx, model, served, ablate)
            row.pop("prefill_rms")
            print(json.dumps({"ablate": ablate, **row}), flush=True)
    finally:
        ray_tpu.shutdown()
        wait_session_gone(session_dir)
    return common.REHEARSAL_RC if args.rehearse_cpu else 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
