"""Serving runner: one replica of ``serve.LLMServer`` with ``num_tpus=1``
behind ``serve.run``, reached by ``DeploymentHandle.stream`` from this
driver process, which never touches JAX.

Copied from ``chip_smoke.py`` (``phase_serve``): the deployment's shape,
the device report, the wait for the session's processes. Everything the
per-layer metrics read is recorded here, from the benchmark's own
subclass, around the calls into each layer: the program has no spans yet.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, List

import numpy as np

from benchmarks import common, loadgen
from benchmarks.common import BenchFailure

PROBE_PROMPT, PROBE_NEW, N_PROBES = 48, 8, 4


def transformer_config(model: Dict, **over):
    """The published keys of ``configs/<name>.json`` as the program's
    ``TransformerConfig`` (see the file's "departures")."""
    from ray_tpu.models.transformer import TransformerConfig

    run = model["run"]
    return TransformerConfig(
        vocab_size=run["padded_vocab_size"], d_model=model["n_embd"],
        n_layers=model["n_layer"], n_heads=model["n_head"],
        d_head=model["n_embd"] // model["n_head"],
        d_ff=model["n_inner"] or 4 * model["n_embd"],
        rotary_dim=model["rotary_dim"], max_seq_len=model["n_positions"],
        **over,
    )


def dims(cfg) -> Dict:
    d = {k: getattr(cfg, k) for k in (
        "vocab_size", "d_model", "n_layers", "n_heads", "d_head", "d_ff")}
    d["n_kv_heads"] = cfg.kv_heads
    return d


def make_int8_params(cfg, seed: int):
    """Every weight on the device from the seed, in ONE jitted call, in
    the type it is served in: int8 blocks with float32 scales (the
    program's ``quantize_layer_params``), bf16 embedding and head. A layer
    exists in float32 only inside its own iteration."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.quant import quantize_layer_params
    from ray_tpu.models.transformer import init_params

    one = dataclasses.replace(cfg, n_layers=1)
    ends = dataclasses.replace(cfg, n_layers=0, param_dtype=jnp.bfloat16)

    @jax.jit
    def make(words):
        key = jax.random.fold_in(jax.random.key(words[0]), words[1])
        k_layers, k_ends = jax.random.split(key)

        def layer(k):
            q = quantize_layer_params(init_params(one, k)["layers"])
            return jax.tree.map(lambda x: x[0], q)

        params = {k: v for k, v in init_params(ends, k_ends).items()
                  if k != "layers"}
        params["layers"] = jax.lax.map(
            layer, jax.random.split(k_layers, cfg.n_layers))
        return params

    return make(jnp.asarray(common.seed_words(seed), jnp.int32))


class _Recorder:
    def __init__(self):
        self.submit_at: Dict[int, float] = {}
        self.engine_ttft: List = []  # (t_submit, seconds)
        self.hops: List = []  # (t_send, seconds)
        self.blocks: List = []  # (t, steps, live slots)
        self.builds = 0  # programs compiled or fetched from the cache


def _instrument(engine, rec: _Recorder) -> None:
    """Wraps the engine's own methods on the INSTANCE: counters always,
    and ``TraceAnnotation`` marks (free while no trace runs) carrying the
    engine's state, so that a device gap finds its owner on one clock."""
    from jax.profiler import TraceAnnotation

    def state():
        return {
            "live": sum(r is not None and not r.finished
                        for r in engine.slot_req),
            "firsts": len(engine._pending_first),
            "pending": len(engine.pending),
        }

    def marked(name, fn):
        def wrapper(*a, **kw):
            with TraceAnnotation("bench." + name, **state()):
                out = fn(*a, **kw)
            with TraceAnnotation("bench.state", **state()):
                pass
            return out
        return wrapper

    submit, emit, dispatch, bucket_for = (
        engine.submit, engine._emit, engine._dispatch_block,
        engine._bucket_for)

    def timed_submit(*a, **kw):
        t = time.time()
        req = submit(*a, **kw)
        rec.submit_at[id(req)] = t
        return req

    def timed_emit(req, token):
        if req is not None and req.produced == 0 and not req.finished:
            t = rec.submit_at.pop(id(req), None)
            if t is not None:
                rec.engine_ttft.append((t, time.time() - t))
        return emit(req, token)

    def counted_dispatch():
        live = [r for r in engine.slot_req
                if r is not None and not r.finished]
        kv_rows = sum(len(r.prompt) + r.produced for r in live)
        before = engine._steps
        with TraceAnnotation("bench.dispatch", live=len(live),
                             kv_rows=kv_rows, firsts=len(
                                 engine._pending_first),
                             pending=len(engine.pending)):
            out = dispatch()
        steps = engine._steps - before
        with TraceAnnotation("bench.block", steps=steps):
            pass  # the block's length is known only after the dispatch
        rec.blocks.append((time.time(), steps, len(live)))
        return out

    def marked_bucket(n):
        with TraceAnnotation("bench.prefill", tokens=int(n)):
            return bucket_for(n)

    engine.submit = timed_submit
    engine._emit = timed_emit
    engine._dispatch_block = counted_dispatch
    engine._bucket_for = marked_bucket
    engine._admit = marked("admit", engine._admit)
    engine._retire_firsts = marked("retire_firsts", engine._retire_firsts)
    engine._retire_block = marked("retire_block", engine._retire_block)


def serve_owner(marks: List[Dict]):
    """Who owns an idle gap of the device: the engine's state at the
    gap's start, from the marks (each carries live/firsts/pending)."""
    import bisect

    pts = [(m["start"], m["stats"]) for m in marks
           if "live" in m.get("stats", {})]
    starts = [p[0] for p in pts]

    def owner(a: float, b: float) -> str:
        i = bisect.bisect_right(starts, a) - 1
        if i < 0:
            return "engine-unattributed:before-first-mark"
        s = pts[i][1]
        if s["live"] + s["firsts"] + s["pending"] == 0:
            return "no-request-in-replica"
        if s["firsts"]:
            return "engine-unattributed:first-token-pending"
        return "engine-unattributed:decoding"

    return owner


def _make_deployment_class():
    from ray_tpu.serve.llm import LLMServer

    class BenchLLM(LLMServer):
        """``chip_smoke.py``'s ``LLM`` deployment, with the benchmark's
        recorders and its control calls."""

        def __init__(self, spec):
            import jax

            self.rec = _Recorder()

            def on_event(event, *_a, **_kw):
                if event.endswith("backend_compile_duration"):
                    self.rec.builds += 1

            jax.monitoring.register_event_duration_secs_listener(on_event)
            eng = spec["engine"]
            cfg = transformer_config(spec["model"])
            super().__init__(
                lambda: (make_int8_params(cfg, spec["seed"]), cfg),
                max_slots=eng["max_slots"], max_len=eng["max_len"],
                prefill_buckets=tuple(eng["prefill_buckets"]),
            )
            for k in ("block_steps", "burst_block_steps"):
                if getattr(self.engine, k) != eng[k]:
                    raise BenchFailure(f"engine {k} is not {eng[k]}")
            _instrument(self.engine, self.rec)
            # warm this cell's prefill shapes through the engine itself
            for b in spec["warm_buckets"]:
                n = min(b, eng["max_len"] - 2)
                self.engine.generate(np.zeros(n, np.int32),
                                     max_new_tokens=2)
            # _retire_firsts stacks as many first tokens as were admitted
            # together: one small program for each count, warmed here
            import jax.numpy as jnp

            first = self.engine._first_token(
                jnp.zeros(cfg.vocab_size, cfg.dtype), 0.0, 0)
            for k in range(1, eng["max_slots"] + 1):
                np.asarray(jnp.stack([first] * k))
            self._trace_dir = None

        def stream(self, prompt_ids, max_new_tokens=64, t_send=None):
            if t_send is not None:
                self.rec.hops.append((t_send, time.time() - t_send))
            yield from self.engine.generate_stream(
                prompt_ids, max_new_tokens=max_new_tokens)

        def __call__(self, cmd, *args):
            return getattr(self, "_cmd_" + cmd)(*args)

        def _cmd_report(self):
            import jax

            d = jax.devices()[0]
            return {
                "platform": d.platform, "kind": d.device_kind,
                "count": len(jax.devices()), "pid": os.getpid(),
                "peak_bytes": (d.memory_stats() or {}).get(
                    "peak_bytes_in_use"),
                "builds": self.rec.builds,
                "dims": dims(self.engine.config),
                "stats": self.engine.stats(),
            }

        def _cmd_counters(self):
            r = self.rec
            return {"engine_ttft": r.engine_ttft, "hops": r.hops,
                    "blocks": r.blocks}

        def _cmd_reference(self, prompt, ids):
            """Margins of the served tokens under the benchmark's plain
            forward over the same weights (see benchmarks/reference.py)."""
            import jax
            import jax.numpy as jnp

            from benchmarks import reference
            from ray_tpu.models.quant import QTensor

            plain = jax.tree.map(
                lambda x: (x.q, x.s) if isinstance(x, QTensor) else x,
                self.engine.params,
                is_leaf=lambda x: isinstance(x, QTensor))
            seq = jnp.asarray(list(prompt) + list(ids[:-1]), jnp.int32)
            with jax.default_matmul_precision("highest"):
                logits = jax.jit(
                    reference.forward_logits, static_argnums=(2,)
                )(plain, seq, self.engine.config.rotary_dim)
            tail = logits[len(prompt) - 1:]
            margin = reference.served_token_margin(
                tail, jnp.asarray(ids, jnp.int32))
            top2 = jnp.sort(tail, -1)[:, -2:]
            return {"margin": np.asarray(margin).tolist(),
                    "top2_gap": np.asarray(top2[:, 1] - top2[:, 0]).tolist()}

        def _cmd_trace_start(self, trace_dir):
            import jax

            from benchmarks import trace

            self._trace_dir = trace_dir
            trace.start(trace_dir)
            with jax.profiler.TraceAnnotation("bench.window"):
                pass
            return time.time()

        def _cmd_trace_stop(self):
            import jax

            with jax.profiler.TraceAnnotation("bench.window"):
                pass
            jax.profiler.stop_trace()
            return time.time()

        def _cmd_trace_reduce(self, keep_copy, rehearsal=False):
            """After the window: the trace reduced to what readers use."""
            from benchmarks import trace

            path = trace.find_xplane(self._trace_dir)
            if keep_copy:
                import shutil

                os.makedirs(keep_copy, exist_ok=True)
                with open(os.path.join(keep_copy, "describe.txt"),
                          "w") as f:
                    f.write(trace.describe(path))
                if os.path.getsize(path) < 24 * 2 ** 20:
                    shutil.copy(path, keep_copy)
            red = trace.reduce(trace.load(path, rehearsal=rehearsal),
                               owner_for=serve_owner)
            dev = red["per_device"][0]
            by_prog: Dict[str, List] = {}
            for p in dev["programs"]:
                by_prog.setdefault(trace.program_of(p["name"]), []).append(
                    {"start": p["start"], "end": p["end"],
                     "id": p["name"]})
            last = None  # each dispatch mark takes its block's length
            for m in red["marks"]:
                if m["name"] == "bench.dispatch":
                    last = m
                elif m["name"] == "bench.block" and last is not None:
                    last["stats"]["steps"] = m["stats"]["steps"]
                    last = None
            return {
                "window_s": red["window_s"], "busy_s": red["busy_s"],
                "device_ops": red["device_ops"],
                "idle_gaps": red["idle_gaps"],
                "programs": by_prog,
                "marks": [m for m in red["marks"]
                          if m["name"] == "bench.prefill" or (
                              m["name"] == "bench.dispatch"
                              and "steps" in m["stats"])],
            }

    return BenchLLM


# ---------------------------------------------------------------------------

def _collect(stream) -> List[int]:
    return [int(t) for t in stream]


def _wait_idle(handle, timeout_s: float = 60.0) -> None:
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        s = handle.remote("report").result(timeout=60)["stats"]
        if s["active"] == 0 and s["pending"] == 0:
            return
        time.sleep(0.25)
    raise BenchFailure("the engine did not drain after the window")


def wait_session_gone(session_dir: str, timeout_s: float = 30.0) -> None:
    """Copied from chip_smoke.py: workers die with their raylet a moment
    after ``shutdown()`` returns; wait, so that the chip is free and no
    process is left behind when this one exits."""
    deadline = time.monotonic() + timeout_s
    while True:
        alive = []
        for pid in filter(str.isdigit, os.listdir("/proc")):
            try:
                with open(f"/proc/{pid}/cmdline", "rb") as f:
                    if session_dir.encode() in f.read():
                        alive.append(int(pid))
            except OSError:
                continue
        if not alive:
            return
        if time.monotonic() >= deadline:
            raise BenchFailure(f"processes of this run still alive: {alive}")
        time.sleep(0.1)


def measure(handle, ctx, mix: Dict, rep: Dict, seconds: float,
            trace: bool) -> Dict:
    """Pre-roll, window and drain of one mix against a live replica.
    Returns samples and scalars; used by ``run`` and by the rate sweep."""
    vocab = rep["dims"]["vocab_size"]
    gen = common.GENERATORS[mix["generator"]]
    reqs = gen(mix, seconds, ctx["seed"], vocab)
    off = loadgen.offered(reqs)

    def stream_fn(r):
        return handle.stream(r["prompt"], max_new_tokens=r["n_new"],
                             t_send=time.time())

    loop = loadgen.OpenLoop(stream_fn, int(mix["client_threads"]))
    builds0 = handle.remote("report").result(timeout=60)["builds"]
    t0 = time.time() + float(mix["preroll_s"]) + 0.2
    backlog = {}

    def at_mid():
        backlog["mid"] = handle.remote("report").result(
            timeout=60)["stats"]

    def at_end():
        backlog["end"] = handle.remote("report").result(
            timeout=60)["stats"]

    tracer = None
    if trace:
        import threading

        def traced():
            # at trace_at_s, or earlier where a short try would end first
            at = min(mix["trace_at_s"], max(0.0, seconds - mix["trace_s"]) / 2)
            time.sleep(max(0.0, t0 + at - time.time()))
            handle.remote("trace_start", ctx["trace_dir"]).result(
                timeout=120)
            time.sleep(mix["trace_s"])
            handle.remote("trace_stop").result(timeout=300)

        tracer = threading.Thread(target=traced, daemon=True)
        tracer.start()
    try:
        loop.run(reqs, t0, seconds, float(mix["drain_s"]),
                 mix["on_window_end"], at_mid, at_end)
    finally:
        loop.close()
    after = handle.remote("report").result(timeout=60)
    if tracer is not None:
        tracer.join(timeout=400)
    _wait_idle(handle)

    def inside(t):
        return t0 <= t < t0 + seconds

    def whole(r):
        return len(r["ids"]) == r["n_new"] and all(
            isinstance(t, int) and 0 <= t < vocab for t in r["ids"])

    counted = [r for r in reqs if r["counted"]]
    # a request cut at the window's end failed where the mix says "drain";
    # where it says "cancel" (above the knee) it is neither failed nor whole
    drained = mix["on_window_end"] == "drain"
    failed, ttft, tpot, late = 0, [], [], []
    for r in counted:
        cut = bool(r.get("cut"))
        if "error" in r or (cut and drained) or not (cut or whole(r)):
            failed += 1
            continue
        if "sent" in r:
            late.append((r["sent"] - (t0 + r["due"])) * 1e3)
        if r["times"]:
            ttft.append((r["times"][0] - (t0 + r["due"])) * 1e3)
        x = None if cut else common.tpot_ms(r["times"])
        if x is not None:
            tpot.append(x)
    in_window = sum(inside(t) for r in reqs for t in r.get("times", ()))
    c = handle.remote("counters").result(timeout=120)
    blocks = [(s, live) for t, s, live in c["blocks"] if inside(t)]
    eng = rep["engine"]
    return {
        "t0": t0, "offered": off,
        "attempted": len(counted), "failed": failed,
        "cut": sum(1 for r in counted if r.get("cut")),
        "builds_in_window": after["builds"] - builds0,
        "backlog": backlog,
        "samples": {
            "ttft_ms": ttft, "tpot_ms": tpot, "late_ms": late,
            "hop_ms": [v * 1e3 for t, v in c["hops"] if inside(t)],
            "engine_ttft_ms": [v * 1e3 for t, v in c["engine_ttft"]
                               if inside(t)],
        },
        "scalars": {
            "tokens_in_window": in_window,
            "tokens_per_s": in_window / seconds,
            "blocks": len(blocks),
            "long_blocks": sum(s == eng["block_steps"] for s, _ in blocks),
            "slot_steps": sum(s * live for s, live in blocks),
            "capacity_steps": sum(s for s, _ in blocks) * eng["max_slots"],
        },
    }


def trace_scalars(tr: Dict, model_dims: Dict, eng: Dict) -> Dict:
    """Device time of the traced decode blocks and prefills. The device's
    clock runs about 1.3 ms ahead of the host marks' (read from a recorded
    trace, PR 24), so an execution is NOT paired with the mark that
    dispatched it. A decode block's length follows from its program (the
    2-step and the 8-step block are two compiled programs: the one whose
    executions take longer is the long one); what the marks add are means:
    the cached rows per live slot for each block length, and the unpadded
    tokens per prefill."""
    progs = tr["programs"]
    out = {"busy_s": tr["busy_s"], "window_s": tr["window_s"],
           "idle_s": tr["window_s"] - tr["busy_s"]}
    dispatch = [m["stats"] for m in tr["marks"]
                if m["name"] == "bench.dispatch"]
    prefill = [m["stats"] for m in tr["marks"] if m["name"] == "bench.prefill"]

    by_id: Dict[str, List[float]] = {}
    for e in progs.get("decode_block", []):
        by_id.setdefault(e["id"], []).append(e["end"] - e["start"])
    ids = sorted(by_id, key=lambda i: sorted(by_id[i])[len(by_id[i]) // 2])
    lengths = sorted({eng["burst_block_steps"], eng["block_steps"]})
    if len(ids) == len(lengths):
        steps_of = dict(zip(ids, lengths))
    elif len(ids) == 1 and dispatch:  # one length only in this stretch
        seen = [m["steps"] for m in dispatch]
        steps_of = {ids[0]: max(set(seen), key=seen.count)}
    else:
        steps_of = {}
    d_s = d_steps = d_bytes = 0.0
    for i, durs in by_id.items():
        if i not in steps_of:
            continue
        steps = steps_of[i]
        same = [m for m in dispatch if m["steps"] == steps] or dispatch
        rows = sum(m["kv_rows"] + 0.5 * (steps - 1) * m["live"]
                   for m in same) / max(1, len(same))
        d_s += sum(durs)
        d_steps += steps * len(durs)
        d_bytes += steps * len(durs) * common.decode_step_bytes(
            model_dims, rows)
    p = [e["end"] - e["start"] for e in progs.get("prefill_into_slot", [])]
    out.update(decode_device_s=d_s, decode_steps=d_steps,
               decode_bytes=d_bytes, prefill_device_s=sum(p))
    if p and prefill:
        out["prefill_s_per_call"] = sum(p) / len(p)
        out["prefill_tokens_per_call"] = sum(
            m["tokens"] for m in prefill) / len(prefill)
    return out


def start_replica(ctx, cfg: Dict, warm_buckets) -> tuple:
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


def probes(handle, ctx, rep, model) -> Dict:
    """Four seeded prompts, each alone through the whole served path, then
    held to the plain reference at every generated position."""
    rng = np.random.default_rng(ctx["seed"] + 1)
    vocab = rep["dims"]["vocab_size"]
    out = {"prompts": [], "ids": [], "worst_margin": 0.0,
           "median_top2_gap": None}
    gaps = []
    for _ in range(N_PROBES):
        p = rng.integers(0, vocab, PROBE_PROMPT, dtype=np.int32)
        ids = _collect(handle.stream(p, max_new_tokens=PROBE_NEW))
        if len(ids) != PROBE_NEW:
            raise BenchFailure(f"probe returned {len(ids)} ids")
        ref = handle.remote("reference", p.tolist(), ids).result(
            timeout=900)
        out["prompts"].append(p)
        out["ids"].append(ids)
        out["worst_margin"] = max(out["worst_margin"], max(ref["margin"]))
        gaps += ref["top2_gap"]
    out["median_top2_gap"] = float(np.median(gaps))
    out["ok"] = out["worst_margin"] <= model["correctness"][
        "logit_margin_tol"]
    return out


def run(ctx) -> Dict:
    cfg, mix, seconds = ctx["config"], ctx["traffic"], ctx["seconds"]
    import ray_tpu

    handle, rep, session_dir, model = start_replica(
        ctx, cfg, mix["warm_buckets"])
    try:
        pr = probes(handle, ctx, rep, model)
        m = measure(handle, ctx, mix, rep, seconds, ctx["trace"])
        again = _collect(handle.stream(pr["prompts"][0],
                                       max_new_tokens=PROBE_NEW))
        facts = dict(m)
        if ctx["trace"]:
            tr = handle.remote("trace_reduce", ctx["keep_trace"],
                               ctx["rehearsal"]).result(
                timeout=600)
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
        e2e["ttft_p90_ms"] = common.percentile(s["ttft_ms"], 90)
    if s["tpot_ms"]:
        e2e["tpot_p50_ms"] = common.percentile(s["tpot_ms"], 50)
    facts["scalars"]["peak_bytes"] = final["peak_bytes"]
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
            "probe_worst_margin": pr["worst_margin"],
            "probe_median_top2_gap": pr["median_top2_gap"],
            "builds_in_window": m["builds_in_window"],
            "long_block_share": m["scalars"]["long_blocks"]
            / max(1, m["scalars"]["blocks"]),
            "ttft_ms": {q: common.percentile(s["ttft_ms"], q)
                        for q in (50, 90, 99)} if s["ttft_ms"] else None,
        })
    return facts
