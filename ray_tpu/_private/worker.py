"""Global driver/worker state + connect/disconnect.

Parity: reference ``python/ray/_private/worker.py`` — the module-level
``global_worker`` (:410), ``init`` (:1108), ``connect`` (:2049).
"""

from __future__ import annotations

import atexit
import logging
import os
import subprocess
import sys
import time
from typing import Dict, Optional

from ray_tpu._private.config import GLOBAL_CONFIG
from ray_tpu._private.core_worker import MODE_DRIVER, CoreWorker
from ray_tpu._private.ids import JobID, NodeID, WorkerID
from ray_tpu._private.node import Cluster

logger = logging.getLogger(__name__)


class Worker:
    def __init__(self):
        self.core_worker: Optional[CoreWorker] = None
        self.mode: Optional[str] = None
        self.connected = False
        self.cluster: Optional[Cluster] = None  # owned if we started it
        self.job_id: bytes = b"\x00" * 16


global_worker = Worker()


def init(
    *,
    address: Optional[str] = None,
    num_cpus: Optional[float] = None,
    num_tpus: Optional[float] = None,
    resources: Optional[Dict[str, float]] = None,
    object_store_memory: Optional[int] = None,
    system_config: Optional[Dict] = None,
    _node_defaults: bool = True,
) -> Dict:
    """Start a local cluster (GCS + raylet) and connect this process as driver.

    ``address="tcp:<head-ip>:<port>"`` instead joins an existing cluster's GCS
    (parity: ray.init(address=...) — a local raylet is started and registered
    against the remote head).
    """
    if global_worker.connected:
        logger.warning("ray_tpu.init() called twice; ignoring")
        return {}
    if address is None:
        # submitted jobs (job_submission) and CLI tools join the running
        # cluster via RAYTPU_ADDRESS (parity: RAY_ADDRESS)
        address = os.environ.get("RAYTPU_ADDRESS") or None
    from ray_tpu._private import chaos

    chaos.install_from_env("driver")  # spec env inherited by all daemons
    GLOBAL_CONFIG.initialize(system_config)
    if object_store_memory:
        GLOBAL_CONFIG.load({"object_store_memory_bytes": int(object_store_memory)})

    res = dict(resources or {})
    if num_cpus is not None:
        res["CPU"] = float(num_cpus)
    elif _node_defaults:
        res.setdefault("CPU", float(os.cpu_count() or 4))
    if num_tpus is not None:
        res["TPU"] = float(num_tpus)
    elif _node_defaults and "TPU" not in res:
        n = _detect_tpu_chips()
        if n:
            res["TPU"] = float(n)

    cluster = Cluster(gcs_address=address)
    if address is None:
        # no wait: the raylet (and the driver below) connect-retry while
        # the GCS binds, so both daemons boot concurrently
        cluster.start_gcs(system_config, wait=False)
    cluster.add_node(resources=res, head=True)
    if cluster.gcs_proc is not None and cluster.gcs_proc.poll() is not None:
        raise RuntimeError(
            f"GCS exited with {cluster.gcs_proc.returncode} during startup "
            f"(see {cluster.session_dir}/logs/gcs.log)"
        )
    global_worker.cluster = cluster
    cw = connect(
        raylet_addr=cluster.head_node.raylet_addr,
        gcs_addr=cluster.gcs_addr,
        store_path=cluster.head_node.store_path,
        node_id=cluster.head_node.node_id,
        session_dir=cluster.session_dir,
    )
    atexit.register(shutdown)
    _wait_node_registered(cw, cluster.head_node.node_id)
    return {
        "session_dir": cluster.session_dir,
        "gcs_address": cluster.gcs_addr,
        "node_id": cluster.head_node.node_id.hex(),
    }


def _wait_node_registered(cw: CoreWorker, node_id: bytes,
                          timeout_s: float = 30.0) -> None:
    """The raylet serves its socket before it has registered with the
    GCS: wait for that, so that ``cluster_resources()`` right after
    ``init()`` counts this node and a placement is never judged against
    an empty cluster."""
    deadline = time.monotonic() + timeout_s
    while not any(
        bytes(n["node_id"]) == node_id
        for n in cw.gcs.call("get_all_nodes", None)
    ):
        if time.monotonic() >= deadline:
            raise RuntimeError(
                f"the head node did not register with the GCS in "
                f"{timeout_s:.0f}s (see {cw.session_dir}/logs)"
            )
        time.sleep(0.01)


# Counts this host's non-CPU devices. Runs in a child of its own: a chip
# belongs to the one process that opened it, and that must be the worker
# the raylet spawns for it, never the driver.
_TPU_PROBE_CMD = [
    sys.executable, "-c",
    "import jax; print(sum(d.platform != 'cpu' for d in jax.devices()))",
]
_TPU_PROBE_TIMEOUT_S = 120.0


def _detect_tpu_chips() -> int:
    """Count accelerator devices without taking one.

    Never initialises a JAX backend in the calling process. A caller
    that already holds one is asked; otherwise a short-lived child
    counts and has exited before this returns, so the chip is free for
    the first TPU worker. A child that fails or outlives
    ``_TPU_PROBE_TIMEOUT_S`` is an error naming its stderr, never "no
    TPU". ``JAX_PLATFORMS=cpu`` means none without probing."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return 0
    jax = sys.modules.get("jax")
    if jax is not None:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            return sum(d.platform != "cpu" for d in jax.devices())
    try:
        child = subprocess.run(
            _TPU_PROBE_CMD, capture_output=True,
            timeout=_TPU_PROBE_TIMEOUT_S,
        )
    except subprocess.TimeoutExpired as e:
        raise RuntimeError(
            f"TPU probe did not finish in {_TPU_PROBE_TIMEOUT_S:.0f}s; "
            f"stderr:\n{(e.stderr or b'').decode(errors='replace')}"
        ) from e
    stderr = child.stderr.decode(errors="replace")
    if child.returncode != 0:
        raise RuntimeError(
            f"TPU probe exited with {child.returncode}; stderr:\n{stderr}"
        )
    try:
        return int(child.stdout.split()[-1])
    except (IndexError, ValueError) as e:
        raise RuntimeError(
            f"TPU probe printed no device count: {child.stdout!r}; "
            f"stderr:\n{stderr}"
        ) from e


def connect(*, raylet_addr, gcs_addr, store_path, node_id, session_dir):
    job_id = JobID.from_random().binary()
    cw = CoreWorker(
        mode=MODE_DRIVER,
        worker_id=WorkerID.from_random().binary(),
        node_id=node_id,
        raylet_addr=raylet_addr,
        gcs_addr=gcs_addr,
        store_path=store_path,
        session_dir=session_dir,
        job_id=job_id,
    )
    cw.gcs.call("register_job", [job_id, {"driver_pid": os.getpid()}])
    global_worker.core_worker = cw
    global_worker.mode = MODE_DRIVER
    global_worker.connected = True
    global_worker.job_id = job_id
    return cw


def shutdown():
    if not global_worker.connected:
        return
    try:
        global_worker.core_worker.shutdown()
    except Exception:
        pass
    if global_worker.cluster is not None:
        global_worker.cluster.shutdown()
    global_worker.core_worker = None
    global_worker.cluster = None
    global_worker.connected = False
    try:
        atexit.unregister(shutdown)
    except Exception:
        pass


def require_connected() -> CoreWorker:
    if not global_worker.connected:
        raise RuntimeError(
            "ray_tpu.init() must be called before using the API"
        )
    return global_worker.core_worker
