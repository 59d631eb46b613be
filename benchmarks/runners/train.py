"""Training runner: one ``JaxTrainer`` worker that holds all the cell's
chips and runs the loop of ``chip_smoke.py`` / ``examples/train_flagship.py``
(sharded state born on the devices, one jitted donated step, a host fetch of
the loss and a ``session.report`` every step), with a FRESH seeded batch
through ``session.distribute_batch`` each step, so that the ingest path is
inside the window. The driver process never touches JAX.
"""

from __future__ import annotations

import math
import os
import tempfile
import time
from typing import Dict, List

from benchmarks import common, loadgen  # noqa: F401 (registers)
from benchmarks.common import BenchFailure
from benchmarks.runners.serve import dims, transformer_config, \
    wait_session_gone


def train_owner(marks: List[Dict]):
    """An idle gap of the device belongs to ``report`` if the loop was
    inside ``session.report`` at its middle, else to ``between-steps``
    (loss fetch, batch making, ``distribute_batch``, dispatch)."""
    reports = [(m["start"], m["end"]) for m in marks
               if m["name"] == "bench.report"]

    def owner(a: float, b: float) -> str:
        mid = (a + b) / 2
        return "report" if any(s <= mid <= e for s, e in reports) \
            else "between-steps"

    return owner


def _slice_check(model: Dict, seed: int) -> Dict:
    """A one-layer slice at the published widths on ONE device: the
    program's loss and gradient norm (bf16 compute, flash kernel) against
    the benchmark's plain float32 reference on the same weights."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmarks import reference
    from ray_tpu.models.transformer import init_params, loss_fn

    sl = model["correctness"]["slice"]
    run = model["run"]
    cfg = dataclasses.replace(
        transformer_config(model, attn_impl=run["attn_impl"], remat=True,
                           remat_policy=run["remat_policy"]),
        n_layers=1)
    params = jax.jit(lambda: init_params(cfg, jax.random.key(7)))()
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (sl["batch"], sl["seq"] + 1)), jnp.int32)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:],
             "mask": jnp.ones((sl["batch"], sl["seq"]), jnp.float32)}
    got_loss, grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, batch, cfg, None)))(params)
    got_norm = optax.global_norm(grads)
    del grads
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_norm = jax.jit(
            reference.loss_and_grad_norm, static_argnums=(3,)
        )(params, batch["tokens"], batch["targets"], cfg.rotary_dim)
    out = {"loss": float(got_loss), "ref_loss": float(ref_loss),
           "grad_norm": float(got_norm), "ref_grad_norm": float(ref_norm)}
    out["ok"] = (
        abs(out["loss"] - out["ref_loss"]) <= sl["loss_rel_tol"]
        * out["ref_loss"]
        and abs(out["grad_norm"] - out["ref_grad_norm"])
        <= sl["grad_norm_rel_tol"] * out["ref_grad_norm"])
    return out


def train_loop(config):
    import gc

    import jax
    from jax.profiler import TraceAnnotation

    import ray_tpu.parallel.mesh as pmesh
    from benchmarks import trace
    from ray_tpu.parallel.train_step import (
        batch_sharding,
        default_optimizer,
        make_sharded_state,
        make_train_step,
    )
    from ray_tpu.train import session

    builds = [0]

    def on_event(event, *_a, **_kw):
        if event.endswith("backend_compile_duration"):
            builds[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    model, mix, seed = config["model"], config["traffic"], config["seed"]
    run = model["run"]
    cfg = transformer_config(model, attn_impl=run["attn_impl"], remat=True,
                             remat_policy=run["remat_policy"])
    seq = min(cfg.max_seq_len, mix["seq"])
    gb = mix["global_batch"]
    devices = jax.devices()
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "dims": dims(cfg), "seq": seq,
           "global_batch": gb, "mesh": run["mesh"], "rules": run["rules"]}
    mesh = session.make_mesh(pmesh.MeshConfig(**run["mesh"]))
    rules = getattr(pmesh, run["rules"])
    opt = default_optimizer()
    words = common.seed_words(seed)
    key = jax.random.fold_in(jax.random.key(words[0]), words[1])
    state, state_sh = make_sharded_state(cfg, mesh, opt, key, rules)
    step = make_train_step(cfg, mesh, opt, state_sh, rules)
    spec = batch_sharding(mesh, rules).spec
    common.load_plugins(common.HERE)
    batches = common.GENERATORS[mix["generator"]](
        dict(mix, seq=seq), config["seconds"], seed, cfg.vocab_size)

    def fresh():
        return session.distribute_batch(next(batches), mesh, spec=spec)

    fixed = fresh()
    compiled = step.lower(state, fixed).compile()
    hlo = compiled.as_text()
    out["tpu_custom_calls"] = hlo.count("tpu_custom_call")
    warm = []
    for _ in range(mix["warmup_steps"]):  # the fixed batch, repeated
        state, m = compiled(state, fixed)
        warm.append(float(m["loss"]))
    out["warm_losses"] = warm
    del fixed

    # -- the window: nothing below may compile ------------------------------
    builds0 = builds[0]
    seconds = config["seconds"]
    tracing = None  # None -> the step it began at -> "done"
    trace_calls_s = 0.0  # inside start_trace / stop_trace: not the trainer's
    ends, losses = [], []
    t0 = time.time()
    out["t0"] = t0
    while True:
        i = len(ends)
        if config["trace"] and tracing is None and i == mix["trace_at_step"]:
            t = time.time()
            trace.start(config["trace_dir"])
            trace_calls_s += time.time() - t
            tracing = i
            with TraceAnnotation("bench.window"):
                pass
        with TraceAnnotation("bench.ingest"):
            batch = fresh()
        with TraceAnnotation("bench.step"):
            state, m = compiled(state, batch)
            loss = float(m["loss"])  # host fetch: the step has run
        now = time.time()
        ends.append(now)
        losses.append(loss)
        with TraceAnnotation("bench.report"):
            session.report({"step": i, "loss": loss})
        if isinstance(tracing, int) and i + 1 == tracing + mix["trace_steps"]:
            with TraceAnnotation("bench.window"):
                pass
            t = time.time()
            jax.profiler.stop_trace()
            trace_calls_s += time.time() - t
            tracing = "done"
        if now - t0 >= seconds and tracing in (None, "done"):
            break
    out.update(steps=len(ends), window_s=ends[-1] - t0 - trace_calls_s,
               trace_calls_s=trace_calls_s, losses=losses,
               builds_in_window=builds[0] - builds0)
    out["peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use") or 0
        for d in mesh.devices.flat)
    del state, batch, compiled, step
    gc.collect()
    if config["trace"]:
        path = trace.find_xplane(config["trace_dir"])
        if config["keep_trace"]:
            os.makedirs(config["keep_trace"], exist_ok=True)
            with open(os.path.join(config["keep_trace"], "describe.txt"),
                      "w") as f:
                f.write(trace.describe(path))
        loaded = trace.load(path, rehearsal=config["rehearsal"])
        if config["keep_trace"]:
            with open(os.path.join(config["keep_trace"], "ops.txt"),
                      "w") as f:
                f.write(trace.op_table(loaded))
        red = trace.reduce(loaded, owner_for=train_owner)
        per_dev = []
        for d in red["per_device"]:
            steps = [p for p in d["programs"]
                     if trace.program_of(p["name"]) == "step_fn"]
            kernel_ops = [
                {"name": trace.short_op(o["name"]),
                 "result": o["name"].partition(" = ")[2].partition(
                     " custom-call(")[0],
                 "s": o["end"] - o["start"]}
                for o in d["ops"] if any(
                    k in o["name"] for k in mix["kernel_op_marks"])]
            per_dev.append({
                "steps": [{"start": p["start"], "end": p["end"]}
                          for p in steps],
                "kernel_ops": kernel_ops,
                "collective_s": d["collective_s"],
                "busy_s": d["busy_s"],
            })
        out["trace"] = {
            "window_s": red["window_s"], "busy_s": red["busy_s"],
            "device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"],
            "per_device": per_dev,
        }
        out["slice"] = _slice_check(model, seed)
    jax.clear_caches()  # leave the chips with nothing live on them
    gc.collect()
    session.report(out)


def run(ctx) -> Dict:
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    cfg, mix, chips = ctx["config"], ctx["traffic"], ctx["chips"]
    model = dict(cfg)
    if ctx["rehearsal"]:
        model.update(cfg["rehearsal"])
    session_dir = ray_tpu.init(num_cpus=8, num_tpus=chips)["session_dir"]
    try:
        with tempfile.TemporaryDirectory(prefix="bench_train_") as results:
            m = JaxTrainer(
                train_loop,
                train_loop_config={
                    "model": model, "traffic": mix, "seed": ctx["seed"],
                    "seconds": ctx["seconds"], "trace": ctx["trace"],
                    "trace_dir": ctx["trace_dir"],
                    "keep_trace": ctx["keep_trace"],
                    "rehearsal": ctx["rehearsal"],
                },
                scaling_config=ScalingConfig(
                    num_workers=1, use_tpu=True,
                    resources_per_worker={"TPU": float(chips)},
                    # host rehearsal only: virtual devices stand in
                    devices_per_worker=chips if ctx["rehearsal"] else None,
                ),
                run_config=RunConfig(name="bench", storage_path=results),
            ).fit().metrics
    finally:
        ray_tpu.shutdown()
        wait_session_gone(session_dir)
    if "steps" not in m:
        raise BenchFailure(f"the trainer's last report is not the result: {m}")
    ctx["check_device"](m)
    tokens = m["global_batch"] * m["seq"] * m["steps"]
    tokens_per_s = tokens / m["window_s"]
    warm, losses = m["warm_losses"], m["losses"]
    ln_v = math.log(m["dims"]["vocab_size"])
    checks = {
        "losses_finite": all(math.isfinite(x) for x in warm + losses),
        "first_loss_is_ln_vocab": abs(warm[0] - ln_v) <= model[
            "correctness"]["first_loss_rel_tol"] * ln_v,
        "fixed_batch_losses_fall": all(
            b < a for a, b in zip(warm, warm[1:])),
        "no_build_in_window": m["builds_in_window"] == 0,
    }
    if not ctx["rehearsal"]:
        checks["flash_kernel_in_step"] = m["tpu_custom_calls"] > 0
    scalars = {
        "peak_bytes": m["peak_bytes"], "tokens_per_s": tokens_per_s,
        "flops_per_token": common.train_flops_per_token(
            m["dims"], m["seq"]),
        "chips": m["count"],
    }
    facts = {
        "e2e": {"setup_s": m["t0"] - ctx["t_start"],
                "tokens_per_s": tokens_per_s},
        "attempted": m["steps"], "failed": 0, "checks": checks,
        "device": m, "model_dims": m["dims"], "scalars": scalars,
        "samples": {}, "train": {k: m[k] for k in (
            "seq", "global_batch", "mesh")},
        "note": {"steps": m["steps"], "window_s": m["window_s"],
                 "warm_losses": warm, "last_loss": losses[-1],
                 "mesh": m["mesh"], "rules": m["rules"],
                 "builds_in_window": m["builds_in_window"],
                 "tpu_custom_calls": m["tpu_custom_calls"]},
    }
    if ctx["trace"]:
        tr = facts["trace"] = m["trace"]
        scalars.update(busy_s=tr["busy_s"], window_s=tr["window_s"],
                       idle_s=tr["window_s"] - tr["busy_s"])
        facts["checks"]["slice_matches_reference"] = m["slice"]["ok"]
        facts["note"]["slice"] = m["slice"]
    return facts
