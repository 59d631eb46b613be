import os

# Force CPU with 8 virtual devices BEFORE jax import anywhere in tests.
# (Parity with reference test strategy: fake resources / simulated multi-node,
# SURVEY.md §4 — JAX-side tests use host-platform virtual devices.)
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# Persistent compilation cache: the suite compiles many small programs
# (often identical across test processes/runs); caching them on disk cuts
# total suite wall time substantially. Placed from outside: JAX reads the
# variable itself, and spawned workers inherit it.
from ray_tpu._private.node import default_compile_cache_dir  # noqa: E402

os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", default_compile_cache_dir())
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

import faulthandler  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402

# A time limit of its own for every phase (setup, call, teardown) of every
# test: the slowest test here takes ~35 s, and a cluster test that waits for
# ever otherwise holds its xdist worker, and the files queued behind it,
# until the whole run's limit cuts everything (PR 27's refused runs). At
# _PHASE_LIMIT_S the phase fails with every thread's stack in its report.
# It ends waits that Python can interrupt. Not a wait inside a ``__del__``,
# which swallows the exception, nor one in C; and no hard exit behind it,
# because under --dist loadfile xdist hands a crashed worker's file to a
# new worker, which waits out the same test again. A test that rehearses a
# whole benchmark cell in a subprocess (a cluster, a replica, a window: a
# minute alone, several under six workers) asks for its own limit with
# ``@pytest.mark.phase_limit(seconds)``.
_PHASE_LIMIT_S = 240


def _limited(item, phase):
    if threading.current_thread() is not threading.main_thread():
        yield
        return
    own = item.get_closest_marker("phase_limit")
    limit = own.args[0] if own else _PHASE_LIMIT_S

    def on_alarm(signum, frame):
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        pytest.fail(f"{item.nodeid}: {phase} still running after "
                    f"{limit} s (tests/conftest.py)")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_setup(item):
    yield from _limited(item, "setup")


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_call(item):
    yield from _limited(item, "call")


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_teardown(item):
    yield from _limited(item, "teardown")


def _mappings(path: str) -> int:
    try:
        with open(path) as f:
            return int(f.read()) if path.endswith("count") else sum(
                1 for _ in f)
    except (OSError, ValueError):  # no /proc: nothing to watch
        return 0


def pytest_runtest_logfinish(nodeid, location):
    """A worker lives for a quarter of an hour and keeps every program it
    ever compiled: each XLA:CPU executable holds ~6 memory mappings, one
    served model's cases leave ~20,000, and a process may have
    ``vm.max_map_count`` of them (65,530 here). Past that an ``mmap``
    fails inside the next compile and the worker dies of a segmentation
    fault in ``backend_compile_and_load``, in whichever test compiles next
    (``tests/test_parallel.py::test_moe_transformer_train_step_ep`` in the
    driver's run of PR 59's tree; four, two and two workers in three runs
    of PR 60's, which adds a model; two at once end the whole run in
    xdist's loadfile scheduler). So between tests, a process past half its
    allowance drops JAX's compiled programs (``jax.clear_caches``: 2,396
    mappings -> 594 for 300 small programs); the persistent cache gives
    back what is needed again."""
    limit = _mappings("/proc/sys/vm/max_map_count")
    if "jax" in sys.modules and limit and (
            _mappings("/proc/self/maps") > limit // 2):
        import gc

        import jax

        jax.clear_caches()
        gc.collect()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running scale/chaos tests (deselect with -m 'not slow' "
        "for the fast tier)",
    )
    config.addinivalue_line(
        "markers",
        "phase_limit(seconds): this test's own limit for each of its "
        "phases, in place of the 240 s every test gets",
    )
    config.addinivalue_line(
        "markers",
        "chaos: network fault-injection tests (the bounded smoke variants "
        "run in the default tier; full soaks are additionally marked slow)",
    )


@pytest.hookimpl(optionalhook=True)
def pytest_xdist_make_scheduler(config, log):
    """``--dist loadfile`` (the driver's tier-1 command) makes a file one
    work unit. ``tests/test_served_models.py`` holds one body a claim for
    ALL served models, and every model compiles programs of its own: as
    one unit it would take one worker as long as the five files it took
    the place of took five. Its cases are units by (file, model) instead:
    a case's id begins with its model's name. Every other file, and
    every other ``--dist``, is scheduled as xdist does."""
    if config.getvalue("dist") != "loadfile":
        return None
    from xdist.scheduler import LoadFileScheduling

    class ByFileAndServedModel(LoadFileScheduling):
        def _split_scope(self, nodeid):
            scope = super()._split_scope(nodeid)
            if scope.endswith("test_served_models.py") and "[" in nodeid:
                case = nodeid.split("[", 1)[1]
                scope += "[" + case.replace("]", "-").split("-", 1)[0] + "]"
            return scope

    return ByFileAndServedModel(config, log)


@pytest.fixture
def rehearsal_manifest(tmp_path):
    """``make(traffic, rate_rps)`` -> a ``--manifest`` for ``benchmarks/
    run.py --rehearse-cpu``: the repository's benchmark, file for file
    (links), but for one traffic mix's arrival rate. A cell offers what
    its chip serves. The host serves that alone, and a fraction of it with
    six test workers on its cores: the engine then falls behind, the
    backlog is still there when the window ends (a request that waits in
    ``pending`` is not even cancelled before it has been prefilled), and
    the rehearsal fails on ``none_failed`` or on its drain, whatever the
    code under test does. A rehearsal walks control flow: it offers a
    rate that a loaded host serves too."""
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def link_all(src, dst, but=()):
        os.makedirs(dst)
        for name in os.listdir(src):
            if name not in but:
                os.symlink(os.path.join(src, name), os.path.join(dst, name))

    def make(traffic, rate_rps):
        bench, mix_file = os.path.join(root, "benchmarks"), traffic + ".json"
        copy = tmp_path / "benchmarks"
        link_all(bench, str(copy), but=("traffic",))
        link_all(os.path.join(bench, "traffic"), str(copy / "traffic"),
                 but=(mix_file,))
        with open(os.path.join(bench, "traffic", mix_file)) as f:
            mix = json.load(f)
        assert rate_rps < mix["rate_rps"]
        mix["rate_rps"] = rate_rps
        with open(copy / "traffic" / mix_file, "w") as f:
            json.dump(mix, f)
        os.symlink(os.path.join(root, "BENCHMARK.json"),
                   tmp_path / "BENCHMARK.json")
        return str(tmp_path / "BENCHMARK.json")

    return make


@pytest.fixture
def tmp_store(tmp_path):
    from ray_tpu._private.object_store import SharedMemoryStore

    store = SharedMemoryStore.create(str(tmp_path / "store"), 64 * 1024 * 1024)
    yield store
    store.close()


@pytest.fixture
def rt():
    """A running single-node cluster, shut down after the test."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def rt_tune():
    """Shared tune-suite cluster (4 CPUs, small store)."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    yield ray_tpu
    ray_tpu.shutdown()
