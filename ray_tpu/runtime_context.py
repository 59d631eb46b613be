"""Runtime context: who/where am I.

Parity: reference ``python/ray/runtime_context.py`` (RuntimeContext,
get_runtime_context) — node/worker/job/actor ids of the current process.
"""

from __future__ import annotations

from typing import Dict, Optional

from ray_tpu._private.worker import global_worker, require_connected


class RuntimeContext:
    def __init__(self, cw):
        self._cw = cw

    def get_node_id(self) -> str:
        return self._cw.node_id.hex()

    def get_worker_id(self) -> str:
        return self._cw.worker_id.hex()

    def get_job_id(self) -> str:
        return self._cw.job_id.hex()

    def get_actor_id(self) -> Optional[str]:
        aid = getattr(self._cw, "_actor_id", None)
        return aid.hex() if aid else None

    def get_worker_boot(self) -> Optional[Dict[str, float]]:
        """How this worker process came up, or None outside a worker (a
        driver): ``process_start_unix`` (``time.time()`` at the entry of
        ``worker_main``, to compare with other processes of the host),
        ``chips_wait_s`` (the wait for the chips' device files, 0 for a
        worker without chips) and ``boot_s`` (that entry -> registered
        with the raylet and ready for its first task; it holds
        ``chips_wait_s``)."""
        boot = self._cw.boot_record
        return dict(boot) if boot else None

    @property
    def was_current_actor_reconstructed(self) -> bool:
        return False


def get_runtime_context() -> RuntimeContext:
    return RuntimeContext(require_connected())
