"""Node bootstrap: session dir + head/worker-node process spawning.

Parity: reference ``python/ray/_private/node.py:37`` (Node), ``services.py``
(start_gcs_server:1280, start_raylet:1353). A "node" here is one raylet +
one shared-memory store; the head node also runs the GCS. Multi-node
simulation on one host = N raylets with faked resources against one GCS
(the reference's cluster_utils.Cluster trick, SURVEY.md §4).
"""

from __future__ import annotations

import errno
import json
import os
import socket
import subprocess
import sys
import tempfile
import time
import uuid
from typing import Dict, Optional

from ray_tpu._private import rpc
from ray_tpu._private.config import GLOBAL_CONFIG
from ray_tpu._private.ids import NodeID
from ray_tpu.exceptions import GetTimeoutError

_SHM_DIR = "/dev/shm" if os.path.isdir("/dev/shm") else tempfile.gettempdir()


def pick_free_port(host: str = "127.0.0.1") -> int:
    """Kernel-assigned free TCP port (tiny race window; fine for bootstrap)."""
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind((host, 0))
    port = s.getsockname()[1]
    s.close()
    return port


def node_ip_address() -> str:
    """This host's primary outbound IP (parity: services.get_node_ip_address)."""
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.connect(("8.8.8.8", 80))  # no packets sent for UDP connect
        return s.getsockname()[0]
    except OSError:
        return "127.0.0.1"
    finally:
        s.close()


def new_session_dir() -> str:
    base = os.path.join(tempfile.gettempdir(), "raytpu")
    os.makedirs(base, exist_ok=True)
    d = os.path.join(base, f"session_{time.strftime('%H%M%S')}_{uuid.uuid4().hex[:8]}")
    os.makedirs(os.path.join(d, "logs"), exist_ok=True)
    os.makedirs(os.path.join(d, "sockets"), exist_ok=True)
    return d


_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def clean_env() -> Dict[str, str]:
    """Env for spawned processes: the caller's, with this checkout
    importable. Daemons import no JAX, so nothing here names a platform;
    ``worker_env`` does, for the processes that do."""
    env = dict(os.environ)
    parts = [p for p in env.get("PYTHONPATH", "").split(":") if p]
    if _REPO_ROOT not in parts:
        parts.append(_REPO_ROOT)
    env["PYTHONPATH"] = ":".join(parts)
    return env


def default_compile_cache_dir() -> str:
    """Where JAX's persistent compile cache goes unless
    ``JAX_COMPILATION_CACHE_DIR`` already says: a fixed path, because
    the path is part of the cache key."""
    return os.path.join(_REPO_ROOT, ".jax_cache")


def worker_env(tpu: bool) -> Dict[str, str]:
    """Env for a worker process, decided where it is spawned.

    One process per chip: a TPU-flavour worker is pinned to ``tpu`` — if
    it cannot open the chip it fails with JAX's error, it never computes
    on the host instead — and every other worker is pinned to ``cpu`` so
    that importing JAX cannot take the chip from the worker that needs
    it. A raylet that inherited ``JAX_PLATFORMS=cpu`` (tests, CPU
    rehearsal) hands that down to both flavours."""
    env = clean_env()
    if env.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        env["JAX_PLATFORMS"] = "tpu" if tpu else "cpu"
    env.setdefault("JAX_COMPILATION_CACHE_DIR", default_compile_cache_dir())
    env["RAYTPU_WORKER"] = "1"
    # stdout is the worker's log file: unbuffered, a print() reaches the
    # log, and through the log monitor the driver, when it is made and not
    # a block-buffer later (log_to_driver; green only under a shell that
    # exported the variable until PR 27)
    env["PYTHONUNBUFFERED"] = "1"
    return env


def wait_chips_free(root: str = "/dev/vfio", timeout_s: float = 45.0) -> bool:
    """Wait until every chip's device file under ``root``
    (``/dev/vfio/<n>``: one group a chip, one opener at a time) can be
    opened, and close it again. A process that held four chips is still
    giving them back ~10 s after it has stopped showing in ``/proc`` by
    name (its memory goes first, then its files, each chip reset in
    turn), and whoever opens the runtime meanwhile dies of
    ``open(/dev/vfio/1): Device or resource busy``: the second of two
    four-chip runs on one host did (PERF.md, PR 46). So a TPU-flavour
    worker waits here before it touches JAX (``worker_main``), and a
    cluster that had chips waits here before ``shutdown()`` returns. Any
    other error, or no such files (no chip, or another driver), is not
    this function's to judge. Returns whether the chips were free in
    time; the caller goes on either way and JAX says what is wrong."""
    try:
        chips = [os.path.join(root, n) for n in os.listdir(root) if n.isdigit()]
    except OSError:
        return True
    deadline = time.monotonic() + timeout_s
    for path in chips:
        while True:
            try:
                os.close(os.open(path, os.O_RDWR))
                break
            except OSError as e:
                if e.errno != errno.EBUSY:
                    break
                if time.monotonic() >= deadline:
                    return False
                time.sleep(0.25)
    return True


def _spawn(cmd, log_path) -> subprocess.Popen:
    out = open(log_path, "wb")
    proc = subprocess.Popen(
        cmd, stdout=out, stderr=subprocess.STDOUT, start_new_session=True,
        env=clean_env(),
    )
    out.close()
    return proc


def _wait_addr(addr: str, timeout=30.0, proc: Optional[subprocess.Popen] = None):
    """Wait until a daemon serves at `addr` (unix: path exists; tcp: connects)."""
    scheme, rest = rpc.parse_addr(addr)
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if scheme == "unix":
            if os.path.exists(rest):
                return
        else:
            host, port = rest.rsplit(":", 1)
            try:
                socket.create_connection((host, int(port)), timeout=1).close()
                return
            except OSError:
                pass
        if proc is not None and proc.poll() is not None:
            raise RuntimeError(
                f"process exited with {proc.returncode} before serving {addr}"
            )
        time.sleep(0.02)
    raise GetTimeoutError(f"timed out waiting for {addr}")


class NodeProcs:
    """One raylet (+store) on this host."""

    def __init__(self, node_id: bytes, proc: subprocess.Popen,
                 raylet_addr: str, store_path: str):
        self.node_id = node_id
        self.proc = proc
        self.raylet_addr = raylet_addr
        self.store_path = store_path

    def kill(self):
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        try:
            os.unlink(self.store_path)
        except OSError:
            pass


class Cluster:
    """Head processes: GCS + head raylet; `add_node` fakes extra nodes.

    Parity: reference python/ray/cluster_utils.py Cluster:99/add_node:165.

    ``use_tcp=True`` runs every control-plane endpoint over TCP (the DCN
    path of a real multi-host deployment); ``gcs_address`` joins an existing
    remote GCS instead of starting one (parity: ray start --address).
    """

    def __init__(
        self,
        session_dir: Optional[str] = None,
        use_tcp: bool = False,
        node_ip: Optional[str] = None,
        gcs_address: Optional[str] = None,
    ):
        self.session_dir = session_dir or new_session_dir()
        self.use_tcp = use_tcp or (
            gcs_address is not None and gcs_address.startswith("tcp:")
        )
        if node_ip is None:
            # Joining a remote head: register a cross-host-reachable IP.
            # Local (single-host) TCP clusters stay on loopback.
            node_ip = (
                node_ip_address() if gcs_address is not None else "127.0.0.1"
            )
        self.node_ip = node_ip
        self.gcs_sock = os.path.join(self.session_dir, "sockets", "gcs.sock")
        self._gcs_addr: Optional[str] = gcs_address
        self.gcs_proc: Optional[subprocess.Popen] = None
        self.standby_proc: Optional[subprocess.Popen] = None
        self._standby_addr: Optional[str] = None
        self._standby_n = 0
        self.nodes: Dict[bytes, NodeProcs] = {}
        self.head_node: Optional[NodeProcs] = None
        self._had_chips = False  # a node of this cluster was given TPUs

    @property
    def gcs_primary_addr(self):
        if self._gcs_addr is not None:
            return self._gcs_addr
        return "unix:" + self.gcs_sock

    @property
    def gcs_addr(self):
        """The endpoint list clients dial. With a warm standby this is
        "primary,standby" — every raylet/driver gets BOTH from boot, so
        failover needs no address redistribution, just reconnect
        cycling."""
        if self._standby_addr is not None:
            return self.gcs_primary_addr + "," + self._standby_addr
        return self.gcs_primary_addr

    def start_gcs(self, system_config: Optional[Dict] = None,
                  wait: bool = True):
        """``wait=False`` returns right after the spawn: every client
        (raylet registration, driver CoreWorker) connect-retries while the
        GCS binds, so a head-node boot can overlap the GCS and raylet
        process startups instead of serializing them."""
        if self._gcs_addr is not None:
            raise RuntimeError("joined an external GCS; not starting one")
        if self.use_tcp:
            self._gcs_addr = f"tcp:{self.node_ip}:{pick_free_port(self.node_ip)}"
        cfg_dict = dict(GLOBAL_CONFIG.dump())
        if system_config:
            cfg_dict.update(system_config)
        self._gcs_cfg = cfg_dict
        standby = bool(cfg_dict.get("gcs_standby"))
        if standby:
            # the standby's serving address is part of every client's
            # endpoint list from boot, so it must be fixed NOW even
            # though nothing binds it until promotion
            if self.use_tcp:
                self._standby_addr = (
                    f"tcp:{self.node_ip}:{pick_free_port(self.node_ip)}"
                )
            else:
                self._standby_addr = "unix:" + os.path.join(
                    self.session_dir, "sockets", "gcs-standby.sock"
                )
        self._gcs_cmd = [
            sys.executable, "-m", "ray_tpu._private.gcs",
            "--sock", self.gcs_primary_addr,
            "--config", json.dumps(cfg_dict),
        ]
        if cfg_dict.get("gcs_storage_backend") == "file" or standby:
            # a standby implies journaling on the primary: journal_sync
            # refuses otherwise (there is no stream to ship)
            self._gcs_cmd += [
                "--storage", os.path.join(self.session_dir, "gcs_storage.pkl"),
            ]
        if standby:
            self._gcs_cmd += ["--peers", self._standby_addr]
        self.gcs_proc = _spawn(
            self._gcs_cmd,
            os.path.join(self.session_dir, "logs", "gcs.log"),
        )
        if standby:
            self.start_gcs_standby()
        if wait:
            _wait_addr(self.gcs_primary_addr, proc=self.gcs_proc)

    def start_gcs_standby(self, sock_addr: Optional[str] = None,
                          primary_addr: Optional[str] = None):
        """Spawn a warm-standby GCS following ``primary_addr`` (defaults:
        serve at the cluster's standby endpoint, follow the full endpoint
        list — the standby syncs to whichever is serving). Reusable after
        a failover to re-arm the NEXT failover: point a fresh standby at
        the promoted primary. No ``_wait_addr``: a standby binds nothing
        until promotion."""
        self._standby_n += 1
        self._standby_cmd = [
            sys.executable, "-m", "ray_tpu._private.gcs_standby",
            "--sock", sock_addr or self._standby_addr,
            "--primary", primary_addr or self.gcs_addr,
            "--storage", os.path.join(
                self.session_dir, f"gcs_standby{self._standby_n}.pkl"),
            "--config", json.dumps(self._gcs_cfg),
        ]
        self.standby_proc = _spawn(
            self._standby_cmd,
            os.path.join(self.session_dir, "logs",
                         f"gcs-standby{self._standby_n}.log"),
        )
        return self.standby_proc

    def kill_gcs(self):
        """SIGKILL the primary GCS and leave it dead (failover testing —
        the standby must take over). The primary's socket is deliberately
        NOT unlinked: real failovers ride a dead-but-present address, and
        clients must cycle past it, not get a clean FileNotFoundError."""
        if self.gcs_proc is not None and self.gcs_proc.poll() is None:
            self.gcs_proc.kill()
            self.gcs_proc.wait()

    def restart_gcs(self):
        """Kill + restart the GCS process (FT testing: with the file storage
        backend, tables reload and raylets re-register)."""
        if self.gcs_proc.poll() is None:
            self.gcs_proc.kill()
            self.gcs_proc.wait()
        # unix sockets must be unlinked before rebinding
        addr = self.gcs_primary_addr
        if addr.startswith("unix:") or addr.startswith("/"):
            path = addr.split(":", 1)[-1]
            try:
                os.unlink(path)
            except OSError:
                pass
        self.gcs_proc = _spawn(
            self._gcs_cmd,
            os.path.join(self.session_dir, "logs", "gcs-restarted.log"),
        )
        _wait_addr(addr, proc=self.gcs_proc)

    def add_node(
        self,
        resources: Optional[Dict[str, float]] = None,
        labels: Optional[Dict[str, str]] = None,
        object_store_memory: Optional[int] = None,
        head: bool = False,
    ) -> NodeProcs:
        node_id = NodeID.from_random().binary()
        hexid = node_id.hex()[:12]
        if self.use_tcp:
            raylet_addr = f"tcp:{self.node_ip}:{pick_free_port(self.node_ip)}"
        else:
            raylet_addr = "unix:" + os.path.join(
                self.session_dir, "sockets", f"raylet-{hexid}.sock"
            )
        store_path = os.path.join(_SHM_DIR, f"raytpu_{os.getpid()}_{hexid}")
        resources = dict(resources or {})
        resources.setdefault("CPU", float(os.cpu_count() or 4))
        self._had_chips = self._had_chips or bool(resources.get("TPU"))
        cfg = dict(GLOBAL_CONFIG.dump())
        if object_store_memory:
            cfg["object_store_memory_bytes"] = int(object_store_memory)
        proc = _spawn(
            [sys.executable, "-m", "ray_tpu._private.raylet",
             "--sock", raylet_addr,
             "--store", store_path,
             "--gcs", self.gcs_addr,
             "--node-id", node_id.hex(),
             "--resources", json.dumps(resources),
             "--labels", json.dumps(labels or {}),
             "--session-dir", self.session_dir,
             "--config", json.dumps(cfg)],
            os.path.join(self.session_dir, "logs", f"raylet-{hexid}.log"),
        )
        _wait_addr(raylet_addr, proc=proc)
        node = NodeProcs(node_id, proc, raylet_addr, store_path)
        self.nodes[node_id] = node
        if head:
            self.head_node = node
        return node

    def remove_node(self, node: NodeProcs):
        node.kill()
        self.nodes.pop(node.node_id, None)

    def shutdown(self):
        for node in list(self.nodes.values()):
            node.kill()
        self.nodes.clear()
        if self._had_chips:
            # the workers die with their raylet; the chips are free a
            # while after that, and the next process may want them at once
            wait_chips_free(timeout_s=30.0)
            self._had_chips = False
        if self.gcs_proc is not None and self.gcs_proc.poll() is None:
            self.gcs_proc.kill()
            self.gcs_proc.wait()
        self.gcs_proc = None
        if self.standby_proc is not None and self.standby_proc.poll() is None:
            self.standby_proc.kill()
            self.standby_proc.wait()
        self.standby_proc = None
