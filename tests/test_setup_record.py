"""Set-up measured from inside (PR 57): the jit's own count
(``ray_tpu/util/jit_stats.py``), the engine's and the server's stretches
and the buckets' first admissions (``LLMEngine.stats()``), the worker's
boot (``RuntimeContext.get_worker_boot``), and the benchmark's readers of
all three (``benchmarks/readers/setup.py``) with the twelve per-layer
metrics that name them. Host, tiny model."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import ray_tpu

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import common  # noqa: E402

if "stats_end_sum" not in common.READERS:
    # once a process: loading again would re-register every reader as a
    # new function behind the back of a test file that holds the old one
    common.load_plugins(os.path.join(ROOT, "benchmarks"))

STRETCHES = ("setup_prepare_s", "setup_layout_s", "setup_cache_s",
             "setup_warm_blocks_s")
ENGINE_KEYS = STRETCHES + (
    "setup_engine_s", "engine_ready_unix", "admission_programs_built",
    "admission_build_s", "admission_build_by_bucket",
    "engine_jit_trace_lower_s", "engine_jit_backend_s")
SERVER_KEYS = ("server_init_begin_unix", "setup_backend_s",
               "setup_weights_s")
JIT_KEYS = ("jit_trace_lower_s", "jit_backend_s", "jit_programs",
            "jit_cache_hits", "jit_cache_misses", "jit_cache_retrieval_s")
WORKER_KEYS = ("worker_process_start_unix", "worker_chips_wait_s",
               "worker_boot_s")
METRICS = [
    "runtime.to_replica_worker_s", "runtime.worker_boot_s",
    "engine.setup_backend_s", "engine.setup_weights_s",
    "engine.setup_build_s", "engine.admission_build_s",
    "jit.trace_lower_s", "jit.backend_s", "jit.cache_miss_programs",
    "setup.unowned_s", "setup.worker_to_server_s", "setup.after_engine_s"]
# the seven whose sum is setup_s
PARTS = METRICS[:6] + ["setup.unowned_s"]
CELLS = [
    "serve-chat-steady", "serve-chat-saturated", "serve-doc-burst",
    "serve-glm-reason-saturated", "serve-glm52-longdoc-steady",
    "serve-granite-agent-saturated", "serve-mimo-codeagent-saturated",
    "serve-kimi-longreason-saturated", "serve-phi4flash-reason-saturated"]
OTHER_CELLS = {"train4-gptj-seq2048": 7,
               "serve-evabyte-bytedoc-saturated": 10}


def _tiny_model():
    import jax

    from ray_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig.tiny()
    return init_params(cfg, jax.random.key(0)), cfg


def _engine(max_len=64):
    """``max_len`` is part of every program's shapes: a test that counts
    what its engine BUILDS asks for a length no other test of the process
    has built (the jit keeps its programs for the process's life)."""
    from ray_tpu.serve.llm import LLMEngine

    params, cfg = _tiny_model()
    return LLMEngine(params, cfg, max_slots=2, max_len=max_len,
                     prefill_buckets=(8, 16))


def _idle_stats(eng):
    """A snapshot once the loop has freed every slot (the lane's parking,
    the last thing a request makes the loop build, is behind it then)."""
    deadline = time.monotonic() + 60
    while True:
        s = eng.stats()
        if s["active"] == 0 and s["pending"] == 0:
            return s
        assert time.monotonic() < deadline, s
        time.sleep(0.01)


def _plain(x):
    return type(x) in (int, float)


# -- the engine's record -----------------------------------------------------

def test_every_key_is_there_from_the_first_snapshot():
    eng = _engine(max_len=88)
    try:
        s = eng.stats()
    finally:
        eng.shutdown()
    for k in ENGINE_KEYS + JIT_KEYS:
        assert k in s, k
        assert _plain(s[k]) or (
            isinstance(s[k], dict) and all(map(_plain, s[k].values()))), k
    assert s["admission_build_by_bucket"] == {}
    assert s["admission_programs_built"] == 0
    # no worker: no boot; built directly: no server's stretches
    assert not any(k in s for k in WORKER_KEYS + SERVER_KEYS)
    assert all(s[k] > 0 for k in STRETCHES)
    assert sum(s[k] for k in STRETCHES) <= s["setup_engine_s"]
    assert 0 < time.time() - s["engine_ready_unix"] < 120
    # the constructor built the two decode blocks at least, on this thread
    assert s["jit_programs"] >= 2
    assert 0 < s["engine_jit_backend_s"] <= s["jit_backend_s"]
    assert 0 < s["engine_jit_trace_lower_s"] <= s["jit_trace_lower_s"]
    json.dumps(s)  # plain all the way down


def test_a_buckets_first_admission_is_the_one_that_builds():
    eng = _engine(max_len=104)
    try:
        def run(n):
            eng.generate(np.arange(1, n + 1, dtype=np.int32),
                         max_new_tokens=3)
            return _idle_stats(eng)

        s0 = eng.stats()
        s1 = run(5)  # bucket 8, its first
        s2 = run(6)  # bucket 8 again
        s3 = run(11)  # bucket 16, its first
    finally:
        eng.shutdown()
    assert s1["admission_programs_built"] == 1
    assert s1["jit_programs"] > s0["jit_programs"]
    assert set(s1["admission_build_by_bucket"]) == {"8"}
    assert s1["admission_build_s"] == s1["admit_launch_s"] > 0
    # a second admission in the bucket builds nothing and adds nothing
    assert s2["requests_admitted"] == 2
    assert s2["admit_launch_s"] > s1["admit_launch_s"]
    for k in ("admission_programs_built", "admission_build_s",
              "admission_build_by_bucket", "jit_programs",
              "engine_jit_trace_lower_s", "engine_jit_backend_s"):
        assert s2[k] == s1[k], k
    assert s3["admission_programs_built"] == 2
    assert s3["jit_programs"] > s2["jit_programs"]
    assert set(s3["admission_build_by_bucket"]) == {"8", "16"}
    assert s3["admission_build_s"] == pytest.approx(
        sum(s3["admission_build_by_bucket"].values()))
    assert s3["engine_jit_backend_s"] > s2["engine_jit_backend_s"]
    assert s3["engine_jit_trace_lower_s"] > s2["engine_jit_trace_lower_s"]
    # set-up's own stretches ended with the constructor
    assert all(s3[k] == s0[k] for k in STRETCHES + ("setup_engine_s",))


def test_a_second_engine_of_the_same_shapes_builds_nothing():
    """The jit keeps a program for the process: the second engine's first
    admission launches what the first engine's built, and a launch is no
    build."""
    def first_admission():
        eng = _engine(max_len=120)
        try:
            s0 = eng.stats()
            eng.generate(np.arange(1, 6, dtype=np.int32), max_new_tokens=3)
            return s0, _idle_stats(eng)
        finally:
            eng.shutdown()

    _, a = first_admission()
    b0, b = first_admission()
    assert a["admission_programs_built"] == 1
    assert a["admission_build_s"] > 0
    assert b["requests_admitted"] == 1 and b["admit_launch_s"] > 0
    assert b["admission_programs_built"] == 0
    assert b["admission_build_s"] == 0
    assert b["admission_build_by_bucket"] == {}
    # nor did its constructor go through the jit again
    assert b0["engine_jit_backend_s"] == 0
    assert b["jit_programs"] == a["jit_programs"]


def test_two_engines_count_a_compile_once():
    import jax
    import jax.numpy as jnp

    from ray_tpu.util import jit_stats

    a, b = _engine(), _engine()
    try:
        x = jnp.arange(7.0)
        before = a.stats()
        jax.jit(lambda v: v * 3.0 + 1.0)(x).block_until_ready()
        after, other = a.stats(), b.stats()
    finally:
        a.shutdown()
        b.shutdown()
    assert after["jit_programs"] == before["jit_programs"] + 1
    assert after["jit_backend_s"] > before["jit_backend_s"]
    # the process's count, whichever engine is asked
    assert other["jit_programs"] == after["jit_programs"]
    # ... and nobody's engine built it
    assert after["engine_jit_backend_s"] == before["engine_jit_backend_s"]
    from jax._src import monitoring

    listeners = monitoring.get_event_duration_listeners()
    assert listeners.count(jit_stats._on_duration) == 1


def test_a_nested_trace_is_time_that_passed_once():
    import jax
    import jax.numpy as jnp

    from ray_tpu.util import jit_stats

    jit_stats.install()

    @jax.jit
    def inner(v):
        time.sleep(0.2)  # while it is traced, inside outer's trace
        return v * 2.0

    @jax.jit
    def outer(v):
        return inner(v) + inner(v + 1.0)

    x = jnp.arange(5.0)
    before, mine = jit_stats.snapshot(), jit_stats.mine()
    t0 = time.perf_counter()
    outer(x).block_until_ready()
    wall = time.perf_counter() - t0
    after = jit_stats.snapshot()
    spent = after["jit_trace_lower_s"] - before["jit_trace_lower_s"]
    # inner's event and outer's both hold the 0.2 s: summed as they come
    # they would read 0.4 s of a call that took little more than 0.2
    assert 0.2 <= spent <= wall
    assert after["jit_programs"] == before["jit_programs"] + 1
    # this thread's own share: all of it, unless another thread traced
    assert 0.2 <= jit_stats.mine()[0] - mine[0] <= spent + 1e-9


def test_a_server_outside_a_worker_times_its_own_stretches():
    from ray_tpu.serve.llm import LLMServer

    t0 = time.time()
    srv = LLMServer(_tiny_model, max_slots=2, max_len=64,
                    prefill_buckets=(8,))
    try:
        s = srv.stats()
    finally:
        srv.engine.shutdown()
    assert t0 <= s["server_init_begin_unix"] <= s["engine_ready_unix"]
    assert s["setup_backend_s"] > 0 and s["setup_weights_s"] > 0
    assert (s["setup_backend_s"] + s["setup_weights_s"]
            + s["setup_engine_s"]) <= (
        s["engine_ready_unix"] - s["server_init_begin_unix"] + 0.05)
    assert not any(k in s for k in WORKER_KEYS)


# -- the worker's record -----------------------------------------------------

def test_an_actor_reads_its_workers_boot_and_a_server_copies_it(rt):
    @ray_tpu.remote
    class Replica:
        def __init__(self):
            self.began = time.time()

        def boot(self):
            return (ray_tpu.get_runtime_context().get_worker_boot(),
                    self.began)

        def served(self):
            import jax

            from ray_tpu.models.transformer import (
                TransformerConfig,
                init_params,
            )
            from ray_tpu.serve.llm import LLMServer

            cfg = TransformerConfig.tiny()
            srv = LLMServer(
                lambda: (init_params(cfg, jax.random.key(0)), cfg),
                max_slots=2, max_len=64, prefill_buckets=(8,))
            try:
                return srv.stats()
            finally:
                srv.engine.shutdown()

    assert ray_tpu.get_runtime_context().get_worker_boot() is None  # driver
    actor = Replica.remote()
    boot, began = ray_tpu.get(actor.boot.remote(), timeout=60)
    assert set(boot) == {"process_start_unix", "chips_wait_s", "boot_s"}
    assert all(map(_plain, boot.values()))
    assert boot["process_start_unix"] <= began
    assert boot["boot_s"] >= 0
    assert boot["chips_wait_s"] == 0  # no chip on the host
    assert boot["process_start_unix"] + boot["boot_s"] <= began + 0.05
    s = ray_tpu.get(actor.served.remote(), timeout=120)
    assert {k: s[k] for k in WORKER_KEYS} == {
        "worker_" + k: v for k, v in boot.items()}
    # the server's constructor began after the worker stood ready
    assert (s["worker_process_start_unix"] + s["worker_boot_s"]
            <= s["server_init_begin_unix"] + 0.05)


# -- the benchmark's readers -------------------------------------------------

# the worker is ready at 1005.5 and waits 1.5 s for the server's
# constructor, whose three stretches end at 1020.0
END = {"worker_process_start_unix": 1004.5, "worker_boot_s": 1.0,
       "worker_chips_wait_s": 0.25, "server_init_begin_unix": 1007.0,
       "setup_backend_s": 4.0, "setup_weights_s": 6.0,
       "setup_engine_s": 3.0, "engine_ready_unix": 1020.0,
       "admission_build_s": 8.0, "jit_trace_lower_s": 7.5,
       "jit_backend_s": 1.5, "jit_cache_misses": 0}
WANT = {"runtime.to_replica_worker_s": 4.5, "runtime.worker_boot_s": 1.0,
        "engine.setup_backend_s": 4.0, "engine.setup_weights_s": 6.0,
        "engine.setup_build_s": 3.0, "engine.admission_build_s": 8.0,
        "jit.trace_lower_s": 7.5, "jit.backend_s": 1.5,
        "jit.cache_miss_programs": 0.0, "setup.unowned_s": 28.5,
        "setup.worker_to_server_s": 1.5, "setup.after_engine_s": 27.0}


def _facts(end):
    # the run started at 1000.0 and its window 55 s later
    return {"t0": 1055.0, "e2e": {"setup_s": 55.0},
            "backlog": {"mid": {}, "end": end}}


def _read(name, facts):
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert spec["name"] == name
    return common.READERS[spec["reader"]](facts, spec["params"])


@pytest.mark.parametrize("name", METRICS)
def test_a_metric_reads_the_end_snapshot(name):
    assert _read(name, _facts(END)) == pytest.approx(WANT[name])
    assert isinstance(_read(name, _facts(END)), float)
    # a tree without the record: nothing, and no error
    assert _read(name, _facts({"steps": 3})) is None
    assert _read(name, {"t0": 1055.0, "e2e": {"setup_s": 55.0}}) is None


def test_the_seven_parts_add_up_to_setup_s():
    parts = {n: _read(n, _facts(END)) for n in PARTS}
    assert sum(parts.values()) == pytest.approx(55.0)
    assert all(v >= 0 for v in parts.values())
    # the remainder has two readers of its own: before the server, and
    # after the engine
    assert parts["setup.unowned_s"] == pytest.approx(
        _read("setup.worker_to_server_s", _facts(END))
        + _read("setup.after_engine_s", _facts(END)))
    # whichever owned key is missing, the remainder is not guessed
    for k in ("worker_boot_s", "setup_engine_s", "admission_build_s",
              "worker_process_start_unix"):
        end = {a: b for a, b in END.items() if a != k}
        assert _read("setup.unowned_s", _facts(end)) is None, k


@pytest.fixture(scope="module")
def listing():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--list"], capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return {r["cell"]: r for r in map(json.loads, out.stdout.splitlines())}


@pytest.mark.parametrize("cell", CELLS)
def test_the_twelve_metrics_resolve_in_a_serving_cell(listing, cell):
    row = listing[cell]
    assert "setup_s" in row["end_to_end"]
    assert list(row["per_layer"])[-len(METRICS):] == METRICS  # appended
    assert {row["per_layer"][n] for n in METRICS} == {
        "stats_end_sum", "setup_to_worker_s", "setup_unowned_s",
        "setup_worker_to_server_s", "setup_after_engine_s"}


@pytest.mark.parametrize("cell", sorted(OTHER_CELLS))
def test_the_other_cells_lists_are_as_they_were(listing, cell):
    got = listing[cell]["per_layer"]
    assert not set(got) & set(METRICS)
    assert len(got) == OTHER_CELLS[cell]


def test_the_entries_are_appended_and_move_setup_s():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    # one block, in order, where PR 57 appended it (later PRs append theirs)
    at = [m["name"] for m in doc["per_layer"]].index(METRICS[0])
    mine = doc["per_layer"][at:at + len(METRICS)]
    assert [m["name"] for m in mine] == METRICS
    layers = {"runtime": 3, "serving engine": 4, "compiler": 3,
              "benchmark": 2}
    for m in mine:
        assert (m["moves"], m["better"], m["source"]) == (
            "setup_s", "lower", "program_counter")
        # the nine of PR 57 first; a later cell joins by appending its
        # name: what follows the nine are cells of the benchmark, each once
        assert m["workloads"][:len(CELLS)] == CELLS
        later = m["workloads"][len(CELLS):]
        assert len(set(later)) == len(later) and set(later) <= {
            w["name"] for w in doc["workloads"]} - set(CELLS)
        assert m["unit"] == (
            "programs" if m["name"] == "jit.cache_miss_programs" else "s")
        layers[m["layer"]] -= 1
    assert not any(layers.values())
    # until PR 57 nothing moved setup_s
    assert [m["name"] for m in doc["per_layer"]
            if m["moves"] == "setup_s"] == METRICS
