"""Per-consumer iterators over a shared streaming execution.

Parity: reference ``python/ray/data/_internal/iterator/stream_split_iterator
.py:31`` — one StreamingExecutor runs inside a coordinator actor; N
consumers (JaxTrainer workers, typically in other processes) pull blocks
round-robin via ``next_block`` RPCs. The executor's bounded buffers mean a
slow consumer throttles the whole pipeline instead of ballooning memory.
"""

from __future__ import annotations

from typing import Any, Iterator, List, Optional

import ray_tpu


# Sentinel telling a consumer to back off and re-poll: the pipeline cannot
# advance without overflowing a slower split's bounded queue.
_RETRY = "__raytpu_split_retry__"

# Per-split buffered-block cap: bounds coordinator-side memory to
# n_splits * cap blocks even when one consumer stalls (the stall then
# backpressures every split, which backpressures the executor itself).
_SPLIT_QUEUE_CAP = 4


class _SplitCoordinator:
    """Actor: owns the executor, deals blocks round-robin to n splits.

    Dealing is arrival-ordered and the executor yields in output-index
    order, so split ``i`` receives exactly the blocks with
    ``idx % n == i`` — which is why ``locality_hints[i]`` (the node that
    consumes split ``i``) can route block ``idx``'s production to
    ``hints[idx % n]`` and have every block land on its consumer's
    host.

    Production runs on a dedicated pump thread, AHEAD of demand: the
    executor's launches/harvests overlap consumer think time (demand-
    clocking production behind serialized next_block RPCs leaves every
    block arriving just-in-time — the consumer then eats the full
    production latency as stall on every step), and the split queues
    are already full when an epoch's first ``run_step`` asks for data.
    A full round-robin target queue parks the pump (consumer-lag
    backpressure), which stops pumping the executor, whose own buffer
    caps stall production upstream — a slow consumer bounds the whole
    pipeline's memory.

    NOTE the pump thread is only safe because task_done completions
    carry a starvation-bound flush (conduit_rpc.task_done_fn): without
    it, one consumer's RPC churn could starve the executor's task
    completions and the other consumers' replies indefinitely."""

    def __init__(self, source_refs, stages, n: int,
                 locality_hints=None, gang=None):
        import threading

        from ray_tpu.data.streaming import StreamingExecutor

        self.n = n
        # Wider pipe than the single-consumer default: in-flight tasks
        # count against the buffer cap, so 4/4 leaves ~2 tasks running
        # once the reorder buffer holds a straggler — far under what n
        # consumers drain. 3 in-system blocks per consumer keeps every
        # free CPU producing while staying bounded (refs in the store,
        # spillable; backpressure caps just scale with the fan-out).
        self._executor = StreamingExecutor(
            stages, source_refs,
            max_tasks_in_flight=max(4, 3 * n),
            max_buffered_blocks=max(4, 3 * n),
            locality_hints=locality_hints, gang=gang,
        )
        self._queues: List[List] = [[] for _ in range(n)]
        self._rr = 0
        self._exhausted = False
        self._calls = [0] * n  # next_block arrivals per split (stats)
        self._retries = 0  # _RETRY replies (producer-behind signals)
        self._error: Optional[BaseException] = None
        self._cv = threading.Condition()
        self._pump = threading.Thread(
            target=self._pump_loop, daemon=True, name="split-pump"
        )
        self._pump.start()

    def _pump_loop(self):
        """Deal executor output refs round-robin into the split queues,
        parking when the round-robin target is full (bounded memory)."""
        try:
            for ref in self._executor.iter_output_refs():
                with self._cv:
                    while len(self._queues[self._rr]) >= _SPLIT_QUEUE_CAP:
                        self._cv.wait(0.25)
                    self._queues[self._rr].append(ref)
                    self._rr = (self._rr + 1) % self.n
                    self._cv.notify_all()
        except BaseException as e:  # surfaced to every consumer
            with self._cv:
                self._error = e
        finally:
            with self._cv:
                self._exhausted = True
                self._cv.notify_all()

    def next_block(self, split: int, max_n: int = 1):
        """Up to ``max_n`` block refs (as a list) for ``split``; None at
        end of data; the _RETRY sentinel when the producer is behind
        (the consumer backs off briefly — visible stall, never a
        hang)."""
        with self._cv:
            self._calls[split] += 1
            q = self._queues[split]
            if not q and not self._exhausted:
                # one bounded wait only: actor methods serialize, so a
                # long block here would gate the OTHER splits' RPCs
                self._cv.wait(0.05)
            if q:
                # return REFS (inside a list so the reply is a
                # ref-bearing value, not an auto-resolved task arg):
                # each block then moves producer->consumer over the
                # object plane exactly once, instead of being funneled
                # by value through this actor
                out = q[:max(1, max_n)]
                del q[:len(out)]
                self._cv.notify_all()  # wake a pump parked on this queue
                return out
            if self._exhausted:
                if self._error is not None:
                    raise self._error
                return None
            self._retries += 1
            return _RETRY

    def stats(self):
        with self._cv:
            return {"queues": [len(q) for q in self._queues],
                    "calls": list(self._calls),
                    "retries": self._retries,
                    "exhausted": self._exhausted,
                    "executor": self._executor.stats()}


class DataIterator:
    """Picklable consumer handle: ships to worker processes.

    ``timeout`` (seconds) bounds each next_block RPC; None = wait forever
    (slow stages are a pipeline property, not a failure)."""

    def __init__(self, coordinator, split: int,
                 timeout: Optional[float] = None):
        self._coord = coordinator
        self._split = split
        self._timeout = timeout
        self._prefetcher = None  # active/last BlockPrefetcher (stats)

    def _ref_stream(self) -> Iterator:
        """This split's block refs as the coordinator deals them (the
        RPC runs on whatever thread drains this — under prefetch, the
        agent's thread, off the consumer's step). Refs arrive in
        BATCHES of up to the coordinator's per-split queue cap, and TWO
        requests stay in flight: while this consumer processes one
        reply, its next request is already queued at the coordinator —
        the round-trip latency overlaps the coordinator's fill work
        instead of serializing with it (ordered-actor execution keeps
        the replies in submission order)."""
        import collections
        import time as _time

        pending: "collections.deque" = collections.deque()
        for _ in range(2):
            pending.append(
                self._coord.next_block.remote(self._split,
                                              _SPLIT_QUEUE_CAP)
            )
        draining = False
        while pending:
            reply = ray_tpu.get(pending.popleft(), timeout=self._timeout)
            if reply is None:
                draining = True  # end of data: consume what's in flight
                continue
            if isinstance(reply, str) and reply == _RETRY:
                _time.sleep(0.005)  # producer behind: back off, re-poll
                # (short: this chains behind the coordinator's own 50 ms
                # bounded wait — a long backoff here turns one near-miss
                # at the epoch tail into a visible step stall)
            if not draining:
                pending.append(
                    self._coord.next_block.remote(self._split,
                                                  _SPLIT_QUEUE_CAP)
                )
            if not isinstance(reply, str):
                yield from reply

    def iter_native_blocks(self, prefetch_blocks: int = 0) -> Iterator:
        """Blocks in stored form (row list or columnar dict).

        ``prefetch_blocks`` > 0 runs a per-host
        :class:`~ray_tpu.data.prefetch.BlockPrefetcher`: upcoming blocks
        resolve through the local raylet's windowed striped pulls ahead
        of consumption (bounded by consumer lag, capped at
        ``prefetch_blocks`` buffered blocks)."""
        if prefetch_blocks and prefetch_blocks > 0:
            from ray_tpu.data.prefetch import BlockPrefetcher

            pf = BlockPrefetcher(
                self._ref_stream(), max_ahead=prefetch_blocks,
                timeout=self._timeout,
                name=f"split{self._split}",
            )
            self._prefetcher = pf
            try:
                yield from pf
            finally:
                pf.close()
            return
        for ref in self._ref_stream():
            yield ray_tpu.get(ref, timeout=self._timeout)

    def iter_blocks(self) -> Iterator[List]:
        from ray_tpu.data.block import BlockAccessor

        for block in self.iter_native_blocks():
            yield BlockAccessor.for_block(block).to_rows()

    def stop(self):
        """Kill the shared coordinator actor (call once per split group,
        e.g. when a trainer attempt ends)."""
        try:
            ray_tpu.kill(self._coord)
        except Exception:
            pass

    def iter_rows(self) -> Iterator[Any]:
        from ray_tpu.data.block import BlockAccessor

        for block in self.iter_native_blocks():
            yield from BlockAccessor.for_block(block).iter_rows()

    def stats(self):
        """Ingest observability for this consumer: the active (or last)
        prefetch agent's counters — ``ingest_stall_s`` is the time the
        consumer waited on the producer (slow pipeline), bounded depth
        counters prove backpressure held."""
        pf = self._prefetcher
        return {"prefetch": pf.stats() if pf is not None else None}

    def iter_batches(self, batch_size: int = 256,
                     batch_format: str = "rows",
                     prefetch_blocks: int = 0) -> Iterator:
        from ray_tpu.data.dataset import batches_from_blocks

        return batches_from_blocks(
            self.iter_native_blocks(prefetch_blocks=prefetch_blocks),
            batch_size, batch_format,
        )

    def iter_device_batches(self, batch_size: int = 256, *,
                            prefetch_batches: int = 2,
                            prefetch_blocks: int = 2,
                            sharding=None) -> Iterator:
        """Double-buffered device feed: a background thread fetches the
        NEXT numpy batch and ``jax.device_put``s it while the device
        step consumes the current one, so host decode + the host->device
        transfer overlaps compute instead of serializing with it.

        Parity: reference ``iter_torch_batches(prefetch_batches=...)``
        (python/ray/data/iterator.py) — the same pipeline role, with
        ``jax.device_put`` (optionally to a ``NamedSharding`` for SPMD
        ingestion) in place of the torch CUDA-stream copy.

        ``prefetch_batches`` bounds in-flight device batches (device
        memory = prefetch_batches + 1 live batches).
        ``prefetch_blocks`` runs the per-host block prefetch agent ON
        by default (2 blocks ahead over the zero-copy pull plane, lag-
        bounded): host-side block arrival overlaps the step the same way
        the device double-buffer overlaps the host->device copy. 0
        disables it (blocks resolve inline).
        """
        return _device_batches(
            lambda: self.iter_batches(
                batch_size, batch_format="numpy",
                prefetch_blocks=prefetch_blocks,
            ),
            prefetch_batches, sharding,
        )


def _device_batches(batch_iter_factory, prefetch_batches: int,
                    sharding) -> Iterator:
    """Shared double-buffer pump for Dataset/DataIterator
    iter_device_batches (see the DataIterator docstring)."""
    import queue
    import threading

    import jax

    if prefetch_batches < 1:
        raise ValueError("prefetch_batches must be >= 1")
    q: "queue.Queue" = queue.Queue(maxsize=prefetch_batches)
    _END = object()
    # Abandoned-consumer guard (same class of bug as the serve/asgi
    # stream pump): a train loop that breaks out early drops the
    # generator — the pump must unwind, not block in q.put pinning
    # device buffers + the source iterator forever.
    aborted = threading.Event()

    def _put(item) -> bool:
        while not aborted.is_set():
            try:
                q.put(item, timeout=0.25)
                return True
            except queue.Full:
                continue
        return False

    def pump():
        try:
            for batch in batch_iter_factory():
                if sharding is not None:
                    dev = jax.device_put(batch, sharding)
                else:
                    dev = jax.device_put(batch)
                if not _put(dev):
                    return
            _put(_END)
        except BaseException as e:  # surfaced to the consumer
            _put(("__raytpu_prefetch_error__", e))

    threading.Thread(target=pump, daemon=True,
                     name="device-prefetch").start()
    try:
        while True:
            item = q.get()
            if item is _END:
                return
            if (isinstance(item, tuple) and len(item) == 2
                    and item[0] == "__raytpu_prefetch_error__"):
                raise item[1]
            yield item
    finally:
        aborted.set()
        while not q.empty():  # free a pump blocked awaiting a slot
            q.get_nowait()
