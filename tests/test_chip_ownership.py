"""One process per chip, no silent CPU (PR 22).

A chip belongs to the one process that opened it. These tests pin down, on
CPU, the three places where processes meet the device: the driver's chip
count (never opens a backend, never turns a failure into "no TPU"), the
env a worker is spawned with (TPU-flavour workers are pinned to the chip,
every other worker away from it), and where the compile cache goes.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu._private import node, worker
from ray_tpu.ops.attention import causal_attention
from ray_tpu.ops.flash_attention import flash_attention

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# worker spawn env
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "inherited,tpu,expected",
    [
        ("cpu", True, "cpu"),    # tests / CPU rehearsal: handed down as is
        ("cpu", False, "cpu"),
        (None, True, "tpu"),     # the chip's worker: the chip or JAX's error
        (None, False, "cpu"),    # everyone else keeps off the chip
    ],
)
def test_worker_env_pins_platform(monkeypatch, inherited, tpu, expected):
    if inherited is None:
        monkeypatch.delenv("JAX_PLATFORMS")
    else:
        monkeypatch.setenv("JAX_PLATFORMS", inherited)
    assert node.worker_env(tpu)["JAX_PLATFORMS"] == expected
    # daemons import no JAX: their env names no platform of its own, or
    # the raylet would hand its pin down to the TPU workers it spawns
    assert node.clean_env().get("JAX_PLATFORMS") == inherited


@pytest.mark.parametrize("preset", ["/some/where/else", None])
def test_compile_cache_is_placed_from_outside(monkeypatch, preset):
    if preset is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", preset)
    want = preset or os.path.join(_REPO, ".jax_cache")
    assert node.worker_env(True)["JAX_COMPILATION_CACHE_DIR"] == want
    assert node.worker_env(False)["JAX_COMPILATION_CACHE_DIR"] == want


# ---------------------------------------------------------------------------
# the driver's chip count
# ---------------------------------------------------------------------------

_DETECT = (
    "import sys; sys.argv = sys.argv[:1]\n"
    "from ray_tpu._private import worker\n"
    "from jax._src import xla_bridge\n"
    "worker._TPU_PROBE_CMD = [sys.executable, '-c', {child!r}]\n"
    "n = worker._detect_tpu_chips()\n"
    "assert not xla_bridge.backends_are_initialized()\n"
    "print('chips', n)\n"
)


def _detect_in_fresh_process(child: str) -> subprocess.CompletedProcess:
    """``_detect_tpu_chips`` in a process that has not touched JAX, with
    ``JAX_PLATFORMS`` unset so that it does probe, and the probe's child
    replaced: there is no chip here for a real one to count."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = _REPO
    return subprocess.run(
        [sys.executable, "-c", _DETECT.format(child=child)],
        capture_output=True, text=True, timeout=120, env=env,
    )


def test_detect_counts_in_a_child_and_opens_no_backend():
    r = _detect_in_fresh_process("print('noise'); print(1)")
    assert r.returncode == 0, r.stderr
    assert r.stdout.split()[-2:] == ["chips", "1"]


def test_detect_failing_child_is_an_error_not_zero():
    r = _detect_in_fresh_process(
        "import sys; sys.stderr.write('chip is on fire'); sys.exit(3)"
    )
    assert r.returncode != 0
    assert "chips" not in r.stdout
    assert "TPU probe exited with 3" in r.stderr
    assert "chip is on fire" in r.stderr


def test_detect_hung_child_is_an_error_not_zero(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.setattr(
        worker, "_TPU_PROBE_CMD",
        [sys.executable, "-c", "import time; time.sleep(60)"],
    )
    monkeypatch.setattr(worker, "_TPU_PROBE_TIMEOUT_S", 0.5)
    # this process's backend is up (cpu): hide jax so that the probe runs
    monkeypatch.delitem(sys.modules, "jax")
    with pytest.raises(RuntimeError, match="did not finish"):
        worker._detect_tpu_chips()


def test_detect_asks_a_backend_the_caller_already_holds(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.setattr(worker, "_TPU_PROBE_CMD", ["/nonexistent"])
    jax.devices()  # the caller holds a backend: cpu devices only
    assert worker._detect_tpu_chips() == 0


def test_detect_pinned_to_cpu_never_probes(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(worker, "_TPU_PROBE_CMD", ["/nonexistent"])
    monkeypatch.delitem(sys.modules, "jax")
    assert worker._detect_tpu_chips() == 0


# ---------------------------------------------------------------------------
# one TPU-flavour worker at a time on a one-chip node
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("busy_opens,timeout_s,free", [
    (None, 5.0, True),  # no such directory: no chip, or another driver
    (0, 5.0, True),  # every chip's file opens at once
    (3, 5.0, True),  # a chip still being given back, then free
    (10 ** 6, 0.6, False),  # held for good: the caller goes on, JAX reports
    (-1, 5.0, True),  # another error than EBUSY is not this function's
], ids=["no_files", "free", "busy_then_free", "held", "other_error"])
def test_a_tpu_worker_waits_for_the_chips_device_files(
        tmp_path, monkeypatch, busy_opens, timeout_s, free):
    """ISSUE 46's second four-chip run died of ``open(/dev/vfio/1): Device
    or resource busy`` 15 s after the first had ended: a TPU-flavour
    worker now waits, before it touches JAX, until each chip's file can be
    opened, and ``Cluster.shutdown()`` of a cluster that had chips returns
    only when they are (``node.wait_chips_free``)."""
    import errno

    root = tmp_path / "vfio"
    if busy_opens is not None:
        root.mkdir()
        for name in ("0", "1", "vfio"):  # the last is no chip's file
            (root / name).write_bytes(b"")
    left, real_open, opened = [busy_opens or 0], os.open, []

    def chip_open(path, flags, *a):
        if str(path) != str(root / "1"):
            opened.append(os.path.basename(str(path)))
            return real_open(path, flags, *a)
        if left[0] < 0:
            raise OSError(errno.EPERM, "not permitted")
        if left[0] > 0:
            left[0] -= 1
            raise OSError(errno.EBUSY, "Device or resource busy")
        opened.append("1")
        return real_open(path, flags, *a)

    monkeypatch.setattr(node.os, "open", chip_open)
    assert node.wait_chips_free(str(root), timeout_s) is free
    assert "vfio" not in opened
    if busy_opens == 3:
        assert left[0] == 0 and "1" in opened


def test_one_chip_node_runs_one_tpu_worker_at_a_time():
    """Half-chip tasks share ONE worker process instead of a second one
    being spawned beside it, and a TPU actor's worker starts only after
    the previous holder of the chip is gone."""
    ray_tpu.init(num_cpus=4, num_tpus=1,
                 object_store_memory=128 * 1024 * 1024)
    try:
        @ray_tpu.remote(num_tpus=0.5)
        def half_chip():
            import time

            time.sleep(0.5)
            return os.getpid(), os.environ["JAX_PLATFORMS"]

        @ray_tpu.remote(num_tpus=1)
        class Holder:
            def __init__(self, previous):
                # every earlier holder of the chip must be gone before this
                # one starts (or be this one: an idle task worker is reused)
                self.previous_alive = [
                    pid for pid in previous
                    if pid != os.getpid() and os.path.exists(f"/proc/{pid}")
                ]

            def report(self):
                return os.getpid(), self.previous_alive

        got = ray_tpu.get([half_chip.remote() for _ in range(2)], timeout=60)
        task_pids = {pid for pid, _ in got}
        assert len(task_pids) == 1, got
        # inherited cpu (this suite) is handed down even to the TPU flavour
        assert {plat for _, plat in got} == {"cpu"}

        seen = sorted(task_pids)
        for _ in range(2):
            holder = Holder.remote(seen)
            pid, previous_alive = ray_tpu.get(
                holder.report.remote(), timeout=60
            )
            assert previous_alive == []
            ray_tpu.kill(holder)
            seen.append(pid)
    finally:
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# the failure detector's own pause is not a node's silence
# ---------------------------------------------------------------------------

def test_gcs_pause_longer_than_health_timeout_kills_no_node():
    """Opening a TPU freezes the whole host for seconds per chip (seen on
    the chip: 4.7 s for one, past the 10 s health timeout for four). The
    GCS, frozen with everyone else, must not read its own pause as the
    node's death when it wakes — that killed the trainer's actor."""
    import signal
    import time

    from ray_tpu._private.worker import global_worker

    ray_tpu.init(
        num_cpus=2, object_store_memory=128 * 1024 * 1024,
        system_config={"health_check_period_ms": 200,
                       "health_check_timeout_ms": 1000},
    )
    try:
        @ray_tpu.remote
        class Survivor:
            def pid(self):
                return os.getpid()

        actor = Survivor.remote()
        pid = ray_tpu.get(actor.pid.remote(), timeout=60)
        gcs = global_worker.cluster.gcs_proc
        gcs.send_signal(signal.SIGSTOP)
        try:
            time.sleep(3.0)  # 3x the health timeout
        finally:
            gcs.send_signal(signal.SIGCONT)
        time.sleep(1.0)  # a few health checks after waking
        assert all(n["alive"] for n in ray_tpu.nodes())
        assert ray_tpu.get(actor.pid.remote(), timeout=30) == pid
        with open(os.path.join(global_worker.cluster.session_dir,
                               "logs", "gcs.log")) as f:
            log = f.read()
        assert "node dead" not in log, log
        assert "not counted against the nodes" in log, log
    finally:
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# no fallback around the kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seq", [128, 192], ids=["tiles", "one_tile"])
def test_flash_on_cpu_is_the_kernel_and_matches_dense(seq):
    """No dense arm is left in ``flash_attention``: with default arguments
    on CPU it is the Pallas kernel (interpreted), also for a sequence no
    block divides, and it still agrees with ``causal_attention``."""
    b, h, d = 1, 2, 64
    q, k, v = (
        jax.random.normal(jax.random.key(i), (b, seq, h, d), jnp.float32)
        for i in range(3)
    )
    fn = lambda q, k, v: flash_attention(q, k, v, block_q=128, block_kv=128)
    assert "pallas_call" in str(jax.make_jaxpr(fn)(q, k, v))
    np.testing.assert_allclose(
        np.asarray(fn(q, k, v)), np.asarray(causal_attention(q, k, v)),
        atol=2e-5,
    )
