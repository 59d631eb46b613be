"""Object spilling + OOM memory monitor tests.

Parity surfaces: reference ``local_object_manager.h:41`` (spill under
pressure, restore on demand), ``external_storage.py`` (filesystem backend),
``memory_monitor.h:52`` + retriable-FIFO worker killing.
"""

import os
import time

import numpy as np
import pytest

import ray_tpu


def test_overcommit_spills_and_restores():
    """Put 3x the store's capacity; every object must survive via disk."""
    ray_tpu.init(
        num_cpus=2,
        object_store_memory=48 * 1024 * 1024,
        system_config={
            "object_spilling_enabled": True,
            "object_spilling_threshold": 0.5,
            "memory_monitor_refresh_ms": 100,
        },
    )
    try:
        mb8 = 8 * 1024 * 1024 // 8  # 8MB of int64
        # no pacing: full creates escalate synchronously via spill_now
        refs = [
            ray_tpu.put(np.full(mb8, i, dtype=np.int64)) for i in range(16)
        ]  # 128MB total through a 48MB store
        # every object readable, values intact (restored from disk)
        for i, ref in enumerate(refs):
            arr = ray_tpu.get(ref, timeout=60)
            assert arr.shape == (mb8,)
            assert int(arr[0]) == i and int(arr[-1]) == i
    finally:
        ray_tpu.shutdown()


def test_spill_files_cleaned_on_restore(tmp_path):
    ray_tpu.init(
        num_cpus=2,
        object_store_memory=32 * 1024 * 1024,
        system_config={
            "object_spilling_enabled": True,
            "object_spilling_threshold": 0.4,
            "memory_monitor_refresh_ms": 100,
        },
    )
    try:
        mb4 = 4 * 1024 * 1024 // 8
        refs = [ray_tpu.put(np.full(mb4, i, dtype=np.int64)) for i in range(8)]
        time.sleep(1.0)  # monitor spills the LRU tail
        from ray_tpu._private.worker import global_worker

        session_dir = global_worker.core_worker.session_dir
        spill_root = os.path.join(session_dir, "spill")
        n_spilled = sum(
            len(files) for _, _, files in os.walk(spill_root)
        ) if os.path.isdir(spill_root) else 0
        assert n_spilled > 0, "nothing was spilled"
        for ref in refs:  # restores consume the files
            ray_tpu.get(ref, timeout=60)
        n_after = sum(
            len(files) for _, _, files in os.walk(spill_root)
        ) if os.path.isdir(spill_root) else 0
        assert n_after < n_spilled
    finally:
        ray_tpu.shutdown()


def test_oom_monitor_kills_newest_lease_and_task_retries(tmp_path):
    """Fake high host-memory usage: the monitor kills the leased worker;
    once pressure relaxes, the retry completes."""
    fake = tmp_path / "mem_usage"
    fake.write_text("0.99")
    marker_dir = tmp_path / "attempts"
    marker_dir.mkdir()
    os.environ["RAYTPU_FAKE_MEM_USAGE_FILE"] = str(fake)
    try:
        ray_tpu.init(
            num_cpus=2,
            object_store_memory=64 * 1024 * 1024,
            system_config={
                "memory_usage_threshold": 0.9,
                "memory_monitor_refresh_ms": 100,
            },
        )

        @ray_tpu.remote(max_retries=20)
        def slow(marker_dir):
            import os as _os
            import time as _t

            _os.makedirs(
                _os.path.join(marker_dir, str(_os.getpid())), exist_ok=True
            )
            _t.sleep(0.8)
            return "survived"

        from ray_tpu._private.worker import global_worker

        logs = os.path.join(global_worker.cluster.session_dir, "logs")

        def monitor_killed():
            # the raylet's own word for it: a kill can land before the
            # attempt has written its marker, and on a loaded box the
            # first attempt can start later than any fixed sleep
            return any(
                "killing worker" in open(os.path.join(logs, f)).read()
                for f in os.listdir(logs) if f.startswith("raylet-")
            )

        ref = slow.remote(str(marker_dir))
        deadline = time.monotonic() + 30
        while not monitor_killed():  # monitor kills the first attempt(s)
            assert time.monotonic() < deadline, (
                "the OOM monitor never killed an attempt"
            )
            time.sleep(0.05)
        fake.write_text("0.0")  # relax pressure: next retry completes
        assert ray_tpu.get(ref, timeout=60) == "survived"
    finally:
        os.environ.pop("RAYTPU_FAKE_MEM_USAGE_FILE", None)
        ray_tpu.shutdown()
