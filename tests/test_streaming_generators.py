"""Caller-owned streaming generator tests.

Parity surfaces: reference ``StreamingObjectRefGenerator``
(``python/ray/_raylet.pyx:237``) and the generator-return protocol in
``src/ray/protobuf/core_worker.proto`` — yields stream to the caller
before the task finishes, the CALLER owns every yielded object (lineage
covers them), and an unconsumed stream backpressures the producer.
"""

import os
import time

import numpy as np
import pytest

import ray_tpu


@pytest.fixture
def rt():
    ray_tpu.init(num_cpus=2, object_store_memory=256 * 1024 * 1024)
    yield ray_tpu
    ray_tpu.shutdown()


def test_streaming_basic_and_completion(rt):
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield {"i": i}

    g = gen.remote(5)
    items = [ray_tpu.get(r)["i"] for r in g]
    assert items == list(range(5))
    assert ray_tpu.get(g.completion_ref) == 5


def test_streaming_yields_arrive_before_task_finishes(rt):
    @ray_tpu.remote(num_returns="streaming")
    def slowgen():
        yield "first"
        time.sleep(3.0)
        yield "second"

    g = slowgen.remote()
    it = iter(g)
    t0 = time.monotonic()
    first = ray_tpu.get(next(it))
    dt = time.monotonic() - t0
    assert first == "first"
    assert dt < 2.0, f"first item waited for task completion ({dt:.1f}s)"
    assert ray_tpu.get(next(it)) == "second"


def test_streaming_plasma_yields(rt):
    @ray_tpu.remote(num_returns="streaming")
    def big(n):
        for i in range(n):
            yield np.full(500_000, i, np.float32)  # 2 MB -> plasma

    vals = [float(ray_tpu.get(r)[0]) for r in big.remote(4)]
    assert vals == [0.0, 1.0, 2.0, 3.0]


def test_streaming_backpressure_pauses_producer(rt):
    """With the consumer stalled, the producer parks at roughly
    consumed + backpressure limit — it must not run to completion."""

    @ray_tpu.remote(num_returns="streaming")
    def counter(n):
        for i in range(n):
            yield i

    g = counter.remote(60)
    it = iter(g)
    for _ in range(4):
        ray_tpu.get(next(it))
    time.sleep(1.5)  # producer should be parked on an unacked report
    reported_during_stall = g._stream.reported
    # limit is 8 (config default): 4 consumed + 8 buffered + 1 in flight
    assert reported_during_stall <= 15, reported_during_stall
    rest = [ray_tpu.get(r) for r in it]
    assert rest[-1] == 59
    assert len(rest) == 56


def test_streaming_error_after_consumed_items(rt):
    @ray_tpu.remote(num_returns="streaming", max_retries=0)
    def bad():
        yield 1
        yield 2
        raise ValueError("boom mid-stream")

    g = bad.remote()
    it = iter(g)
    assert ray_tpu.get(next(it)) == 1
    assert ray_tpu.get(next(it)) == 2
    with pytest.raises(Exception, match="boom"):
        next(it)


def test_streaming_worker_death_reexecutes(rt, tmp_path):
    """VERDICT round-3 criterion: kill the executing worker mid-generation;
    the consumer still receives every item (caller-owned refs + task
    re-execution resume the stream)."""

    @ray_tpu.remote(num_returns="streaming", max_retries=2)
    def die_once(n, marker):
        for i in range(n):
            if i == 3 and not os.path.exists(marker):
                open(marker, "w").close()
                os._exit(1)  # SIGKILL-style worker loss mid-stream
            yield np.full(300_000, i, np.float32)  # plasma-sized

    g = die_once.remote(6, str(tmp_path / "died"))
    vals = [int(ray_tpu.get(r)[0]) for r in g]
    assert vals == [0, 1, 2, 3, 4, 5]


def test_streaming_actor_method(rt):
    @ray_tpu.remote(num_cpus=1)
    class Tok:
        def __init__(self):
            self.prefix = "tok"

        def tokens(self, n):
            for i in range(n):
                yield f"{self.prefix}{i}"

    a = Tok.remote()
    g = a.tokens.options(num_returns="streaming").remote(3)
    assert [ray_tpu.get(r) for r in g] == ["tok0", "tok1", "tok2"]


def test_streaming_async_actor_generator(rt):
    @ray_tpu.remote(num_cpus=1, max_concurrency=4)
    class Async:
        async def agen(self, n):
            import asyncio

            for i in range(n):
                await asyncio.sleep(0.01)
                yield i * 10

    a = Async.remote()
    g = a.agen.options(num_returns="streaming").remote(4)
    assert [ray_tpu.get(r) for r in g] == [0, 10, 20, 30]


def test_streaming_generator_not_picklable(rt):
    @ray_tpu.remote(num_returns="streaming")
    def gen():
        yield 1

    g = gen.remote()
    import cloudpickle

    with pytest.raises(TypeError, match="not picklable"):
        cloudpickle.dumps(g)
    list(g)  # drain


def test_streaming_abandoned_stream_frees_worker(rt):
    """Dropping a half-consumed generator must NACK the producer so the
    worker (and its lease) frees up — not park in backpressure forever."""

    @ray_tpu.remote(num_returns="streaming")
    def endless():
        i = 0
        while True:
            yield i
            i += 1

    g = endless.remote()
    it = iter(g)
    for _ in range(3):
        ray_tpu.get(next(it))
    g.close()  # abandon

    # the worker must become available again for other tasks
    @ray_tpu.remote(num_cpus=2)  # needs ALL cpus: blocked if lease leaked
    def ping():
        return "pong"

    assert ray_tpu.get(ping.remote(), timeout=60) == "pong"


def test_streaming_method_decorator(rt):
    @ray_tpu.remote(num_cpus=1)
    class A:
        @ray_tpu.method(num_returns="streaming")
        def gen(self, n):
            for i in range(n):
                yield i * 2

    a = A.remote()
    assert [ray_tpu.get(r) for r in a.gen.remote(3)] == [0, 2, 4]


def test_streaming_yield_with_nested_ref_raises(rt):
    @ray_tpu.remote(num_returns="streaming", max_retries=0)
    def gen():
        inner = ray_tpu.put(1)  # a ref nested inside the yielded value
        yield {"ref": inner}

    g = gen.remote()
    with pytest.raises(Exception, match="ObjectRef"):
        next(iter(g))


def test_streaming_burst_rides_in_few_reports(rt):
    """Yields made in a row while a report is on its way ride together in
    the next one: a generator that yields its items in bursts (a token
    stream yields a decode block's tokens so) reaches the consumer whole
    and in order, in fewer report round trips than yields."""

    @ray_tpu.remote(num_returns="streaming")
    def bursts(n, burst):
        for i in range(n):
            if i % burst == 0:
                time.sleep(0.05)
            yield i

    g = bursts.remote(64, 8)
    assert [ray_tpu.get(r) for r in g] == list(range(64))
    # 8 bursts: the first yield of each leaves alone, the seven behind it
    # wait for its reply; one report a yield would be 64
    assert g._stream.reports <= 40, g._stream.reports


def test_yield_reporter_batches_by_the_callers_room():
    """``_YieldReporter`` alone, against a caller that holds each reply
    back: one report on its way at a time, the next carries what was put
    meanwhile, never more than the last reply had room for; a NACK stops
    ``put``."""
    import asyncio
    import threading
    from types import SimpleNamespace

    from ray_tpu._private.core_worker import _YieldReporter

    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()
    calls, release = [], []

    class Conn:
        async def call_async(self, method, msg, timeout=None):
            assert method == "report_generator_items"
            calls.append([it["index"] for it in msg["items"]])
            fut = loop.create_future()
            release.append(fut)
            return await fut

    async def conn_to(addr):
        return Conn()

    worker = SimpleNamespace(io=SimpleNamespace(loop=loop), _conn_to=conn_to)
    spec = SimpleNamespace(owner=(b"", "addr"), task_id=b"t")
    rep = _YieldReporter(worker, spec)

    def reply(**kw):
        fut = release.pop(0)
        loop.call_soon_threadsafe(fut.set_result, kw)

    def wait_calls(n):
        deadline = time.monotonic() + 10
        while len(calls) < n:
            assert time.monotonic() < deadline, calls
            time.sleep(0.005)

    try:
        assert rep.put({"index": 0})
        wait_calls(1)
        assert calls == [[0]]  # the first leaves at once
        for i in range(1, 6):  # its reply is held back: these wait
            assert rep.put({"index": i})
        reply(ok=True, room=2)
        wait_calls(2)
        assert calls[1] == [1, 2]  # what waited, as far as there is room
        reply(ok=True, room=8)
        wait_calls(3)
        assert calls[2] == [3, 4, 5]
        reply(ok=True, room=8)
        assert rep.flush() is True
        assert rep.put({"index": 6})
        wait_calls(4)
        reply(ok=False, room=1)  # the consumer abandoned the stream
        assert rep.flush() is False
        assert rep.put({"index": 7}) is False
        assert len(calls) == 4
    finally:
        loop.call_soon_threadsafe(loop.stop)
