"""The quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, on
ONE cluster started with ``ray_tpu.init()``:

  train  ``JaxTrainer`` + ``ScalingConfig(use_tpu=True)`` running the loop of
         ``examples/train_flagship.py`` on ``TransformerConfig.bench_400m()``
         at its own widths, seq 2048, batch 8: 1 compile step + 3 steps;
  serve  ``serve.run`` of an ``LLMServer`` deployed with ``num_tpus=1`` over
         ``TransformerConfig.serve_7b()`` in int8, behind the HTTP proxy:
         two concurrent ``POST /LLM``, one ``POST /LLM/stream``, one repeat.

    python chip_smoke.py                  # one chip: train, then serve
    python chip_smoke.py --phase train    # one phase alone
    python chip_smoke.py --chips 4        # four chips: the sharded trainer
                                          # and its one-device comparison only
    python chip_smoke.py --rehearse-cpu   # control flow at TransformerConfig
                                          # .tiny on the host; never a result

Weights and prompts are random, made from ``--seed``. The driver process
never initialises a JAX backend: the chip belongs to the worker the raylet
spawns for the trainer or the replica, and the device on the last line is
what that worker reported. Any failed check, any exception, any phase that
did not finish: non-zero exit and no result line. The last line of a run
that passed on the chip is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import sys
import tempfile
import threading
import time
import urllib.request

_HERE = os.path.dirname(os.path.abspath(__file__))
_GIB = 2 ** 30
_REHEARSAL_RC = 10  # every phase passed on the host; not a result


class SmokeFailure(Exception):
    """A check that did not hold."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    log(f"  ok    {what}")


# ---------------------------------------------------------------------------
# train: the loop of examples/train_flagship.py, shipped to the TPU worker
# ---------------------------------------------------------------------------

def train_loop(config):
    import copy
    import gc

    import jax
    import numpy as np

    from ray_tpu.mesh import make_mesh
    from ray_tpu.models.transformer import TransformerConfig
    from ray_tpu.parallel.mesh import MeshConfig
    from ray_tpu.parallel.train_step import (
        batch_sharding,
        default_optimizer,
        make_sharded_state,
        make_train_step,
    )
    from ray_tpu.train import session

    cfg = getattr(TransformerConfig, config["size"])()
    seq = min(cfg.max_seq_len, config["seq"])
    devices = jax.devices()
    out = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "pid": os.getpid(),
        "runs": {},
    }
    tokens = np.random.RandomState(config["seed"]).randint(
        0, cfg.vocab_size, (config["batch"], seq)
    ).astype(np.int32)

    def run(name, mesh, batch_size):
        """1 compile step + ``steps`` steps on ``mesh``; every step is
        reported through ``session.report``."""
        opt = default_optimizer()
        state, state_sh = make_sharded_state(
            cfg, mesh, opt, jax.random.key(config["seed"])
        )
        step = make_train_step(cfg, mesh, opt, state_sh)
        toks = tokens[:batch_size]
        batch = session.distribute_batch(
            {"tokens": toks, "targets": toks,
             "mask": np.ones_like(toks, np.float32)},
            mesh, spec=batch_sharding(mesh).spec,
        )
        cache_dir = os.environ["JAX_COMPILATION_CACHE_DIR"]
        cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
        t0 = time.perf_counter()
        compiled = step.lower(state, batch).compile()
        compile_s = time.perf_counter() - t0
        hlo = compiled.as_text()
        r = out["runs"][name] = {
            "mesh": {k: v for k, v in mesh.shape.items() if v > 1},
            "batch": batch_size,
            "seq": seq,
            "compile_s": compile_s,
            "cache_entries_before": cached,
            "tpu_custom_calls": hlo.count("tpu_custom_call"),
            "all_reduces": hlo.count("all-reduce(")
            + hlo.count("all-reduce-start("),
            "losses": [],
            "step_ms": [],
        }
        for _ in range(1 + config["steps"]):
            t0 = time.perf_counter()
            state, m = compiled(state, batch)
            r["losses"].append(float(m["loss"]))  # host fetch: it has run
            r["step_ms"].append((time.perf_counter() - t0) * 1e3)
            # a copy: the actor's poll thread pickles the event while this
            # thread goes on filling ``out``
            session.report(copy.deepcopy(out))
        r["param_devices_min"] = min(
            len(leaf.sharding.device_set)
            for leaf in jax.tree.leaves(state.params)
        )
        r["bytes_in_use"] = [
            (d.memory_stats() or {}).get("bytes_in_use") for d in mesh.devices.flat
        ]
        del state, batch, compiled, step
        gc.collect()

    def run_at_largest_batch(name, mesh):
        """If HBM refuses the batch, halve the BATCH only."""
        batch_size = config["batch"]
        while True:
            try:
                run(name, mesh, batch_size)
                return batch_size
            except jax.errors.JaxRuntimeError as e:
                if "RESOURCE_EXHAUSTED" not in str(e) or batch_size == 1:
                    raise
                out.setdefault("refused_batches", []).append(batch_size)
                out["runs"].pop(name, None)
                gc.collect()
                batch_size //= 2

    if config["sharded"] is None:
        run_at_largest_batch(
            "one_chip", session.make_mesh(MeshConfig(dp=-1))
        )
    else:
        # the comparison first, on one of the devices; then the same loop,
        # seed and global batch over all of them
        used = run_at_largest_batch(
            "one_device", make_mesh(MeshConfig(dp=1), devices=devices[:1])
        )
        run("sharded", session.make_mesh(MeshConfig(**config["sharded"])),
            used)
    session.report(copy.deepcopy(out))


def _fit(mode, *, chips: int, sharded, seed: int):
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as results:
        t0 = time.perf_counter()
        result = JaxTrainer(
            train_loop,
            train_loop_config={
                "size": mode["train_size"], "seq": 2048, "batch": 8,
                "steps": 3, "seed": seed, "sharded": sharded,
            },
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=True,
                resources_per_worker={"TPU": float(chips)},
                # host rehearsal only: virtual devices stand in for chips
                devices_per_worker=chips if mode["rehearsal"] else None,
            ),
            run_config=RunConfig(name="chip_smoke", storage_path=results),
        ).fit()
    m = result.metrics
    log(f"  fit() returned in {time.perf_counter() - t0:.1f} s; worker pid "
        f"{m['pid']} saw {m['count']} x {m['kind']!r} ({m['platform']})")
    check(m["platform"] == mode["platform"],
          f"trainer worker platform is {mode['platform']!r}")
    check(m["count"] == chips, f"trainer worker holds {chips} device(s)")
    if m.get("refused_batches"):
        log(f"  HBM refused batch {m['refused_batches']}: batch halved")
    return m


def _check_run(mode, name: str, r) -> None:
    losses = r["losses"]
    log(f"  [{name}] mesh {r['mesh'] or '1 device'}, batch {r['batch']} x "
        f"seq {r['seq']}: compile {r['compile_s']:.1f} s "
        f"({r['cache_entries_before']} entries in the compile cache "
        f"before), step ms "
        f"{[round(x, 1) for x in r['step_ms']]}, losses "
        f"{[round(x, 4) for x in losses]}")
    check(all(math.isfinite(x) for x in losses),
          f"[{name}] {len(losses)} finite losses")
    check(all(b < a for a, b in zip(losses, losses[1:])),
          f"[{name}] losses fall on the fixed batch")
    if not mode["rehearsal"]:
        check(r["tpu_custom_calls"] > 0,
              f"[{name}] flash kernel is in the compiled step "
              f"({r['tpu_custom_calls']} tpu_custom_call)")


def phase_train(mode, seed: int):
    log(f"== train: {mode['train_size']}, seq 2048, batch 8, "
        "1 compile step + 3 steps ==")
    m = _fit(mode, chips=1, sharded=None, seed=seed)
    _check_run(mode, "one_chip", m["runs"]["one_chip"])
    return m


def phase_train_sharded(mode, seed: int):
    log(f"== train over 4 chips: {mode['train_size']}, one worker process "
        "owns all four; 1-device mesh, then dp=2 x tp=2 ==")
    m = _fit(mode, chips=4, sharded={"dp": 2, "tp": 2}, seed=seed)
    one, four = m["runs"]["one_device"], m["runs"]["sharded"]
    _check_run(mode, "one_device", one)
    _check_run(mode, "sharded", four)
    check(four["param_devices_min"] == 4,
          "every parameter leaf's sharding spans 4 devices")
    check(four["all_reduces"] > 0,
          f"sharded step has collectives ({four['all_reduces']} all-reduce)")
    if not mode["rehearsal"]:
        log(f"  bytes in use per device: {four['bytes_in_use']}")
        check(all(b and b > 0 for b in four["bytes_in_use"]),
              "each of the 4 devices holds bytes")
    first = abs(four["losses"][0] - one["losses"][0]) / one["losses"][0]
    check(first <= 2 ** -8,
          f"step-1 loss equals the 1-device run's within bf16 tolerance "
          f"(rel {first:.2e})")
    worst = max(abs(b - a) / a for a, b in zip(one["losses"], four["losses"]))
    check(worst <= 0.01,
          f"loss trajectory within 1 % of the 1-device run's "
          f"(worst rel {worst:.2e})")
    return m


def _wait_gone(pid: int, timeout_s: float = 30.0) -> bool:
    deadline = time.monotonic() + timeout_s
    while os.path.exists(f"/proc/{pid}"):
        if time.monotonic() >= deadline:
            return False
        time.sleep(0.1)
    return True


# ---------------------------------------------------------------------------
# serve: the shape of examples/serve_llm_streaming.py
# ---------------------------------------------------------------------------

def _chip_holders():
    """Pids that have the TPU runtime mapped: every process that opened, or
    tried to open, the chip. A worker pinned to the host never loads it."""
    holders = set()
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/maps") as f:
                if "libtpu" in f.read():
                    holders.add(int(pid))
        except OSError:
            continue  # gone, or not ours to read
    return holders


def phase_serve(mode, seed: int):
    import numpy as np

    from ray_tpu import serve

    size = mode["serve_size"]
    log(f"== serve: {size} int8, max_slots 8, max_len 512, bucket 128, "
        "greedy, 64 new tokens ==")

    def model_factory():
        import jax

        from ray_tpu.models.quant import init_params_int8
        from ray_tpu.models.transformer import TransformerConfig

        cfg = getattr(TransformerConfig, size)()
        return init_params_int8(cfg, jax.random.key(seed)), cfg

    @serve.deployment(
        num_replicas=1,
        ray_actor_options={"num_tpus": 1, "max_concurrency": 16},
    )
    class LLM(serve.LLMServer):
        def __init__(self):
            super().__init__(model_factory, max_slots=8, max_len=512,
                             prefill_buckets=(128,))

        def __call__(self, request, **kw):
            if request == "device_report":
                return self.device_report()
            return super().__call__(request, **kw)

        def device_report(self):
            import jax

            d = jax.devices()[0]
            return {
                "platform": d.platform,
                "kind": d.device_kind,
                "count": len(jax.devices()),
                "bytes_in_use": (d.memory_stats() or {}).get("bytes_in_use"),
                "pid": os.getpid(),
                "vocab": self.engine.config.vocab_size,
            }

    t0 = time.perf_counter()
    handle = serve.run(LLM.bind())
    base = serve.start_http_proxy()
    # the replica builds the weights and compiles its decode blocks in its
    # constructor: wait for it here, not inside an HTTP request's timeout
    rep = handle.remote("device_report").result(timeout=600)
    log(f"  replica ready in {time.perf_counter() - t0:.1f} s at {base}/LLM; "
        f"pid {rep['pid']} saw {rep['count']} x {rep['kind']!r} "
        f"({rep['platform']})")
    check(rep["platform"] == mode["platform"],
          f"replica platform is {mode['platform']!r}")

    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(0, rep["vocab"], int(n)).tolist()
        for n in rng.integers(64, 129, 3)
    ]
    opener = urllib.request.build_opener(urllib.request.ProxyHandler({}))

    def post(path, prompt):
        return opener.open(
            urllib.request.Request(
                base + path, data=json.dumps(prompt).encode(),
                headers={"Content-Type": "application/json"},
            ),
            timeout=300,
        )

    def generate(prompt):
        t0 = time.perf_counter()
        with post("/LLM", prompt) as resp:
            status, body = resp.status, json.loads(resp.read())
        return status, body["result"], time.perf_counter() - t0

    def check_ids(what, status, ids):
        check(status == 200, f"{what}: HTTP 200")
        check(len(ids) == 64 and all(
            isinstance(t, int) and 0 <= t < rep["vocab"] for t in ids
        ), f"{what}: 64 ids in [0, {rep['vocab']})")

    # two concurrent POST /LLM
    got = [None, None]

    def client(i):
        try:
            got[i] = generate(prompts[i])
        except Exception as e:  # noqa: BLE001 — re-raised on the main thread
            got[i] = e

    threads = [threading.Thread(target=client, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=320)
    for i, g in enumerate(got):
        if not isinstance(g, tuple):
            raise SmokeFailure(f"concurrent POST /LLM #{i} failed: {g!r}")
        status, ids, dt = g
        log(f"  POST /LLM #{i}: prompt {len(prompts[i])} tokens -> "
            f"{len(ids)} ids in {dt:.2f} s (first includes the prefill "
            "compile)")
        check_ids(f"concurrent POST /LLM #{i}", status, ids)

    # one POST /LLM/stream, read chunk by chunk
    streamed, arrivals = [], []
    with post("/LLM/stream", prompts[2]) as resp:
        status = resp.status
        for line in resp:
            if line.strip():
                streamed.append(json.loads(line)["chunk"])
                arrivals.append(time.perf_counter())
    log(f"  POST /LLM/stream: prompt {len(prompts[2])} tokens -> "
        f"{len(streamed)} chunks over {arrivals[-1] - arrivals[0]:.2f} s")
    check_ids("POST /LLM/stream", status, streamed)
    check(len(arrivals) > 1 and arrivals[-1] > arrivals[0],
          "the stream arrives in more than one chunk")

    # the first prompt again: greedy decoding is deterministic
    status, again, dt = generate(prompts[0])
    log(f"  POST /LLM #0 again: {len(again)} ids in {dt:.2f} s")
    check(status == 200 and again == got[0][1], "the repeat is identical")

    rep = handle.remote("device_report").result(timeout=60)
    if not mode["rehearsal"]:
        log(f"  replica bytes_in_use: {rep['bytes_in_use']} "
            f"({rep['bytes_in_use'] / _GIB:.2f} GiB)")
        check(rep["bytes_in_use"] >= 6 * _GIB,
              "the weights are on the device (>= 6 GiB in use)")
        holders = _chip_holders()
        check(holders == {rep["pid"]},
              f"no process but the replica has the chip (holders {holders})")
    return rep


# ---------------------------------------------------------------------------

def _build_native() -> None:
    """``ray_tpu/_native/`` is git-ignored and the chip tool copies the disk
    as it stands: remove it, so that both libraries are rebuilt from src/."""
    shutil.rmtree(os.path.join(_HERE, "ray_tpu", "_native"),
                  ignore_errors=True)
    from ray_tpu._private import conduit, object_store

    t0 = time.perf_counter()
    object_store._ensure_built()
    conduit._ensure_built()
    log(f"native store + conduit rebuilt from src/ in "
        f"{time.perf_counter() - t0:.1f} s")


def _keep_logs(session_dir: str) -> None:
    """The machine and its temp dir are gone when the chip tool returns:
    leave the daemons' and workers' logs where the tool brings them back."""
    shutil.copytree(
        os.path.join(session_dir, "logs"),
        os.path.join(_HERE, "chiprun_out", "chip_smoke_logs",
                     os.path.basename(session_dir)),
        dirs_exist_ok=True,
    )


def _dump_logs(session_dir: str) -> None:
    """After a failure: the end of every daemon and worker log."""
    logs = os.path.join(session_dir, "logs")
    for name in sorted(os.listdir(logs)):
        with open(os.path.join(logs, name), errors="replace") as f:
            tail = f.readlines()[-40:]
        if tail:
            sys.stderr.write(f"---- {name} (last {len(tail)} lines)\n")
            sys.stderr.writelines(tail)
    sys.stderr.flush()


def _wait_session_gone(session_dir: str, timeout_s: float = 20.0) -> None:
    """Workers die with their raylet, a moment after ``shutdown()``
    returns: wait, so that the chip is free when this script exits."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
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
        time.sleep(0.1)
    raise SmokeFailure(f"processes of this run are still alive: {alive}")


def _on_alarm(signum, frame):
    raise SmokeFailure("watchdog: the run did not finish in time")


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--phase", choices=["train", "serve"],
                   help="one chip: run this phase alone (default: both)")
    p.add_argument("--chips", type=int, choices=[1, 4], default=1,
                   help="4: the sharded trainer and its comparison only")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="TransformerConfig.tiny on the host; no result line")
    args = p.parse_args()
    if args.chips == 4 and args.phase:
        p.error("--chips 4 runs the sharded trainer only; no --phase")

    log(f"chip_smoke: inherited JAX_PLATFORMS="
        f"{os.environ.get('JAX_PLATFORMS')!r}, JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')!r}")
    rehearsal = args.rehearse_cpu
    mode = {
        # skips the checks only a chip can meet: kernel, HBM, chip holders
        "rehearsal": rehearsal,
        "platform": "cpu" if rehearsal else "tpu",
        "train_size": "tiny" if rehearsal else "bench_400m",
        "serve_size": "tiny" if rehearsal else "serve_7b",
    }
    signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(1140)

    from ray_tpu._private.node import default_compile_cache_dir

    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR", default_compile_cache_dir()
    )
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        # cleared, so that the rule at worker spawn decides: the TPU worker
        # gets the chip or JAX's error, every other worker the host
        os.environ.pop("JAX_PLATFORMS", None)
    _build_native()

    import ray_tpu

    # num_cpus is a logical count: controller, proxy and replica all place
    # whatever the machine has. No num_tpus: detection is under test.
    session_dir = ray_tpu.init(
        num_cpus=8, **({"num_tpus": args.chips} if rehearsal else {})
    )["session_dir"]
    try:
        total = ray_tpu.cluster_resources().get("TPU", 0)
        log(f"cluster resources: {ray_tpu.cluster_resources()}")
        check(total == args.chips,
              f"the cluster counts {args.chips} TPU chip(s) (found {total})")
        if args.chips == 4:
            device = phase_train_sharded(mode, args.seed)
        else:
            device = None
            if args.phase in (None, "train"):
                device = phase_train(mode, args.seed)
                check(_wait_gone(device["pid"]),
                      "the trainer's worker is gone: the chip is free")
            if args.phase in (None, "serve"):
                device = phase_serve(mode, args.seed)
        if "jax" in sys.modules:
            from jax._src import xla_bridge

            check(not xla_bridge.backends_are_initialized(),
                  "the driver initialised no JAX backend")
        else:
            log("  ok    the driver never imported JAX")
    except BaseException:
        _dump_logs(session_dir)
        raise
    finally:
        _keep_logs(session_dir)
        ray_tpu.shutdown()
        _wait_session_gone(session_dir)
        signal.alarm(0)

    if rehearsal:
        log("rehearsal on the host passed: control flow only, no result")
        return _REHEARSAL_RC
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"],
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
