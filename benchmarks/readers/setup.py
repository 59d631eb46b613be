"""Readers of what the program recorded about its own set-up: the keys
``LLMEngine.stats()`` carries since PR 57 (the worker's boot, the server's
and the engine's stretches, the buckets' first admissions, the jit's own
count), read from ONE snapshot, the window's end
(``facts["backlog"]["end"]``: set-up is over by then, and a warmed engine
builds nothing in the window). Together they split ``setup_s``, which the
runner measures from the run's process start (``benchmarks/run.py``'s
``T_START``) to the window's ``t0``. Where the program publishes no such
key (a commit before the record existed) a reader returns None and the
metric is left out."""

from benchmarks.common import reader


def _end(facts, keys):
    """The end snapshot, or None where it lacks one of ``keys``."""
    end = (facts.get("backlog") or {}).get("end") or {}
    return None if any(k not in end for k in keys) else end


def _to_worker_s(facts, end):
    """Seconds from the run's process start to the replica's worker
    process's: ``t0 - setup_s`` is the runner's ``T_START`` again."""
    start = facts["t0"] - facts["e2e"]["setup_s"]
    return end["worker_process_start_unix"] - start


@reader("stats_end_sum")
def stats_end_sum(facts, params):
    """The sum of the named keys of the end snapshot."""
    end = _end(facts, params["keys"])
    return None if end is None else float(sum(end[k] for k in params["keys"]))


@reader("setup_to_worker_s")
def setup_to_worker_s(facts, params):
    """Process start of the run -> process start of the worker that holds
    the replica (both ``time.time()`` on one host)."""
    end = _end(facts, ["worker_process_start_unix"])
    return None if end is None else _to_worker_s(facts, end)


@reader("setup_worker_to_server_s")
def setup_worker_to_server_s(facts, params):
    """The worker registered and ready -> ``LLMServer.__init__`` entered
    (``time.time()`` instants of one process, and the boot's seconds)."""
    end = _end(facts, ["worker_process_start_unix", "worker_boot_s",
                       "server_init_begin_unix"])
    if end is None:
        return None
    return (end["server_init_begin_unix"] - end["worker_process_start_unix"]
            - end["worker_boot_s"])


@reader("setup_after_engine_s")
def setup_after_engine_s(facts, params):
    """The engine's constructor returned -> the window's ``t0``
    (``time.time()`` on one host), less the buckets' first launches,
    which fall in that stretch and have a metric of their own."""
    end = _end(facts, ["engine_ready_unix", "admission_build_s"])
    if end is None:
        return None
    return facts["t0"] - end["engine_ready_unix"] - end["admission_build_s"]


@reader("setup_unowned_s")
def setup_unowned_s(facts, params):
    """``setup_s`` less the way to the worker and less the named keys
    (stretches that follow one another on the way to the window, none
    inside another): what nobody inside the program has timed."""
    end = _end(facts, ["worker_process_start_unix"] + params["owned"])
    if end is None:
        return None
    return (facts["e2e"]["setup_s"] - _to_worker_s(facts, end)
            - sum(end[k] for k in params["owned"]))
