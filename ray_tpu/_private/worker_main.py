"""Worker process entry point.

Parity: reference ``python/ray/_private/workers/default_worker.py`` — launched
by the raylet's worker pool (worker_pool.cc:426); registers, then runs the
task execution loop on the main thread (JAX device runtime lives there).
"""

from __future__ import annotations

import argparse
import logging
import os
import sys
import time


def main():
    began_unix, began = time.time(), time.perf_counter()
    from ray_tpu._private import chaos
    from ray_tpu._private.fate_share import fate_share_with_parent

    fate_share_with_parent()  # die with the raylet, not ~20s later
    chaos.install_from_env("worker")
    p = argparse.ArgumentParser()
    p.add_argument("--raylet")
    p.add_argument("--gcs")
    p.add_argument("--store")
    p.add_argument("--node-id")
    p.add_argument("--worker-id")
    p.add_argument("--session-dir")
    p.add_argument("--job-id", default="00" * 16)
    args = p.parse_args()

    logging.basicConfig(
        level=logging.INFO,
        format="[worker %(asctime)s] %(levelname)s %(message)s",
        stream=sys.stderr,
    )
    chips_wait_s = 0.0
    if os.environ.get("JAX_PLATFORMS") == "tpu":  # node.worker_env's pin
        from ray_tpu._private.node import wait_chips_free

        t0 = time.perf_counter()
        free = wait_chips_free()
        chips_wait_s = time.perf_counter() - t0
        if not free or chips_wait_s > 1.0:
            logging.warning("waited %.1f s for the chips' device files "
                            "(free: %s)", chips_wait_s, free)

    from ray_tpu._private.core_worker import MODE_WORKER, CoreWorker
    from ray_tpu._private import worker as worker_mod

    cw = CoreWorker(
        mode=MODE_WORKER,
        worker_id=bytes.fromhex(args.worker_id),
        node_id=bytes.fromhex(args.node_id),
        raylet_addr=args.raylet,
        gcs_addr=args.gcs,
        store_path=args.store,
        session_dir=args.session_dir,
        job_id=bytes.fromhex(args.job_id),
    )
    worker_mod.global_worker.core_worker = cw
    worker_mod.global_worker.mode = MODE_WORKER
    worker_mod.global_worker.connected = True
    # registered, and ready for the first task the loop below will take:
    # what this process's start cost whoever waited for it
    # (``RuntimeContext.get_worker_boot``)
    cw.boot_record = {
        "process_start_unix": began_unix, "chips_wait_s": chips_wait_s,
        "boot_s": time.perf_counter() - began,
    }
    try:
        cw.execution_loop()
    except KeyboardInterrupt:
        pass
    finally:
        cw.shutdown()


if __name__ == "__main__":
    main()
