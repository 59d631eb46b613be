"""Core API tests: tasks, objects, errors — parity with the reference's
python/ray/tests/test_basic.py surface."""

import time

import numpy as np
import pytest

import ray_tpu


def test_put_get(rt):
    ref = ray_tpu.put(42)
    assert ray_tpu.get(ref) == 42
    ref2 = ray_tpu.put({"a": [1, 2, 3]})
    assert ray_tpu.get(ref2) == {"a": [1, 2, 3]}


def test_put_get_large_array_zero_copy(rt):
    arr = np.arange(1 << 20, dtype=np.float32)
    ref = ray_tpu.put(arr)
    out = ray_tpu.get(ref)
    np.testing.assert_array_equal(arr, out)
    assert not out.flags["OWNDATA"]  # zero-copy view over the store


def test_simple_task(rt):
    @ray_tpu.remote
    def add(a, b):
        return a + b

    assert ray_tpu.get(add.remote(1, 2)) == 3


def test_task_kwargs_and_options(rt):
    @ray_tpu.remote
    def f(a, b=10):
        return a * b

    assert ray_tpu.get(f.remote(3)) == 30
    assert ray_tpu.get(f.remote(3, b=2)) == 6
    assert ray_tpu.get(f.options(name="custom").remote(2)) == 20


def test_many_tasks(rt):
    @ray_tpu.remote
    def sq(x):
        return x * x

    refs = [sq.remote(i) for i in range(50)]
    assert ray_tpu.get(refs) == [i * i for i in range(50)]


def test_multiple_returns(rt):
    @ray_tpu.remote(num_returns=3)
    def three():
        return 1, 2, 3

    a, b, c = three.remote()
    assert ray_tpu.get([a, b, c]) == [1, 2, 3]


def test_task_arg_by_ref(rt):
    @ray_tpu.remote
    def plus1(x):
        return x + 1

    r1 = plus1.remote(1)
    r2 = plus1.remote(r1)
    r3 = plus1.remote(r2)
    assert ray_tpu.get(r3) == 4


def test_large_arg_through_plasma(rt):
    arr = np.ones(1 << 20, dtype=np.float32)

    @ray_tpu.remote
    def total(a):
        return float(a.sum())

    assert ray_tpu.get(total.remote(arr)) == float(arr.sum())


def test_large_return_through_plasma(rt):
    @ray_tpu.remote
    def make():
        return np.full(1 << 20, 7, dtype=np.int32)

    out = ray_tpu.get(make.remote())
    assert out.shape == (1 << 20,)
    assert int(out[123]) == 7


def test_task_error_reraised(rt):
    @ray_tpu.remote
    def boom():
        raise ValueError("deliberate")

    with pytest.raises(ray_tpu.exceptions.TaskError) as ei:
        ray_tpu.get(boom.remote())
    assert "deliberate" in str(ei.value)


def test_error_propagates_through_dependency(rt):
    @ray_tpu.remote
    def boom():
        raise RuntimeError("first failure")

    @ray_tpu.remote
    def consume(x):
        return x

    with pytest.raises(ray_tpu.exceptions.TaskError):
        ray_tpu.get(consume.remote(boom.remote()))


def test_nested_tasks(rt):
    @ray_tpu.remote
    def inner(x):
        return x * 2

    @ray_tpu.remote
    def outer(x):
        return ray_tpu.get(inner.remote(x)) + 1

    assert ray_tpu.get(outer.remote(5)) == 11


def test_wait(rt):
    @ray_tpu.remote
    def fast():
        return "fast"

    @ray_tpu.remote
    def slow():
        time.sleep(5)
        return "slow"

    f, s = fast.remote(), slow.remote()
    ready, pending = ray_tpu.wait([f, s], num_returns=1, timeout=4)
    assert ready == [f]
    assert pending == [s]


def test_get_timeout(rt):
    @ray_tpu.remote
    def slow():
        time.sleep(10)

    with pytest.raises(ray_tpu.exceptions.GetTimeoutError):
        ray_tpu.get(slow.remote(), timeout=0.5)


def test_cluster_resources(rt):
    res = ray_tpu.cluster_resources()
    assert res.get("CPU", 0) >= 4


def test_is_initialized(rt):
    assert ray_tpu.is_initialized()


def test_zero_copy_view_pinned_against_eviction():
    """A gotten array's bytes must survive store pressure: the deserialized
    view pins the object's store refcount until the array dies (ADVICE r1:
    LRU eviction could reuse the block under a live numpy view). Runs with
    spilling disabled to exercise the raw eviction path."""
    import ray_tpu as rt_mod
    from ray_tpu._private.worker import global_worker

    store_bytes = 128 * 1024 * 1024
    rt_mod.init(
        num_cpus=4,
        object_store_memory=store_bytes,
        system_config={"object_spilling_enabled": False},
    )
    try:
        n = (store_bytes // 8) // 8  # each array ~1/8 of the store
        ref = rt_mod.put(np.full(n, 7, dtype=np.int64))
        arr = rt_mod.get(ref)
        assert arr.flags["OWNDATA"] is False  # genuinely zero-copy
        # Drop our ref so only the pinned view protects the bytes; flood.
        del ref
        floods = [rt_mod.put(np.zeros(n, dtype=np.int64)) for _ in range(12)]
        stats = global_worker.core_worker.store.stats()
        assert stats["num_evictions"] > 0, "pressure never triggered eviction"
        assert int(arr[0]) == 7 and int(arr[-1]) == 7
        assert int(arr.sum()) == 7 * n
        del floods
    finally:
        rt_mod.shutdown()


def test_wait_on_borrowed_ref(rt):
    """wait() on a ref created by another worker (no local entry) must detect
    readiness by pulling, not block until timeout (ADVICE r1)."""

    @ray_tpu.remote
    def producer():
        return ray_tpu.put(np.arange(1000))

    @ray_tpu.remote
    def check(refs):
        ready, pending = ray_tpu.wait(refs, num_returns=1, timeout=30)
        return len(ready), len(pending)

    inner = ray_tpu.get(producer.remote(), timeout=60)
    # wrap in a list: a top-level ref arg would be auto-resolved to its value
    n_ready, n_pending = ray_tpu.get(check.remote([inner]), timeout=60)
    assert (n_ready, n_pending) == (1, 0)


def test_borrowed_ref_outlives_owner_handle(rt):
    """Borrowing protocol (reference_count.h:61): an actor borrowing a ref
    can still read it after the owner drops its last local handle."""
    import gc

    @ray_tpu.remote
    class Holder:
        def keep(self, refs):
            self.ref = refs[0]  # borrow registered at deserialization
            return True

        def read(self):
            return ray_tpu.get(self.ref, timeout=30)

    h = Holder.remote()
    ref = ray_tpu.put(np.arange(64 * 1024))  # plasma-sized
    # wrap in a list: a top-level ref arg would be auto-resolved to its value
    assert ray_tpu.get(h.keep.remote([ref]), timeout=60)
    time.sleep(0.5)  # let the borrow registration land
    del ref
    gc.collect()
    time.sleep(0.5)  # a buggy owner would free here
    out = ray_tpu.get(h.read.remote(), timeout=60)
    assert int(out.sum()) == int(np.arange(64 * 1024).sum())


def test_actor_pool(rt):
    @ray_tpu.remote
    class Sq:
        def f(self, x):
            return x * x

    from ray_tpu.util import ActorPool

    pool = ActorPool([Sq.remote(), Sq.remote()])
    out = list(pool.map(lambda a, v: a.f.remote(v), range(8)))
    assert out == [x * x for x in range(8)]  # submission order preserved
    out2 = sorted(pool.map_unordered(lambda a, v: a.f.remote(v), range(5)))
    assert out2 == [0, 1, 4, 9, 16]


def test_distributed_queue(rt):
    from ray_tpu.util.queue import Empty, Queue

    q = Queue(maxsize=2)

    @ray_tpu.remote
    def producer(q, n):
        for i in range(n):
            q.put(i)
        return "done"

    @ray_tpu.remote
    def consumer(q, n):
        return [q.get(timeout=30) for _ in range(n)]

    p = producer.remote(q, 6)
    c = consumer.remote(q, 6)
    assert ray_tpu.get(c, timeout=60) == list(range(6))
    assert ray_tpu.get(p, timeout=60) == "done"
    assert q.empty()
    with pytest.raises(Empty):
        q.get_nowait()


def test_dag_bind_execute(rt):
    from ray_tpu.dag import InputNode

    @ray_tpu.remote
    def double(x):
        return 2 * x

    @ray_tpu.remote
    def add(a, b):
        return a + b

    with InputNode() as inp:
        dag = add.bind(double.bind(inp), double.bind(10))
    # (2*x) + 20
    assert ray_tpu.get(dag.execute(5), timeout=60) == 30
    assert ray_tpu.get(dag.execute(1), timeout=60) == 22

    # diamond: shared upstream executes once
    @ray_tpu.remote
    def tag(x):
        import os
        import time

        time.sleep(0.05)
        return (os.getpid(), time.time())

    with InputNode() as inp:
        shared = tag.bind(inp)
        merged = add.bind(shared, shared)

    pid_time = ray_tpu.get(merged.execute(0), timeout=60)
    # tuple+tuple concatenates: identical timestamps prove the shared
    # upstream node executed exactly once
    assert len(pid_time) == 4 and pid_time[1] == pid_time[3]


def test_actor_pool_survives_task_failure(rt):
    @ray_tpu.remote
    class Worker:
        def f(self, x):
            if x == 2:
                raise ValueError("bad input")
            return x * 10

    from ray_tpu.util import ActorPool

    pool = ActorPool([Worker.remote(), Worker.remote()])
    for v in range(5):
        pool.submit(lambda a, x: a.f.remote(x), v)
    out, errors = [], 0
    while pool.has_next():
        try:
            out.append(pool.get_next(timeout=60))
        except ray_tpu.exceptions.TaskError:
            errors += 1
    assert errors == 1
    assert out == [0, 10, 30, 40]  # order preserved around the failure
    # pool still fully usable afterwards
    assert list(pool.map(lambda a, x: a.f.remote(x), [5, 6])) == [50, 60]


def test_queue_parks_blocked_waiters(rt):
    """Blocked get() parks inside the async queue actor (one outstanding
    RPC, no polling) and wakes as soon as the producer puts."""
    import threading

    from ray_tpu.util.queue import Queue

    q = Queue()
    got = {}

    def consumer():
        t0 = time.monotonic()
        got["value"] = q.get(timeout=30)
        got["waited"] = time.monotonic() - t0

    t = threading.Thread(target=consumer)
    t.start()
    time.sleep(1.0)
    q.put("wake")
    t.join(timeout=30)
    assert got["value"] == "wake"
    assert 0.9 < got["waited"] < 25.0  # parked, then woken (loose upper
    # bound: suite machines run heavily loaded)

    # bounded queue: a blocking put parks until space appears
    qb = Queue(maxsize=1)
    qb.put(1)

    def spacemaker():
        time.sleep(0.8)
        qb.get()

    t2 = threading.Thread(target=spacemaker)
    t2.start()
    t0 = time.monotonic()
    qb.put(2, timeout=30)  # blocks ~0.8s until spacemaker drains
    assert time.monotonic() - t0 > 0.5
    t2.join(timeout=30)
    assert qb.get(timeout=10) == 2


def test_multiprocessing_pool_shim(rt):
    """multiprocessing.Pool drop-in over actors (reference
    ray.util.multiprocessing): map/starmap/imap/apply + async variants."""
    from ray_tpu.util.multiprocessing import Pool

    def square(x):
        return x * x

    def add(a, b):
        return a + b

    with Pool(processes=2) as pool:
        assert pool.map(square, range(20)) == [x * x for x in range(20)]
        assert pool.starmap(add, [(1, 2), (3, 4)]) == [3, 7]
        assert list(pool.imap(square, range(8), chunksize=3)) == [
            x * x for x in range(8)
        ]
        assert sorted(pool.imap_unordered(square, range(8))) == sorted(
            x * x for x in range(8)
        )
        assert pool.apply(add, (20, 22)) == 42
        r = pool.map_async(square, range(5))
        r.wait(timeout=60)
        assert r.ready() and r.get(timeout=10) == [0, 1, 4, 9, 16]

    # initializer runs once per worker
    def init_global(v):
        import builtins

        builtins._POOL_TEST_V = v

    def read_global(_):
        import builtins

        return getattr(builtins, "_POOL_TEST_V", None)

    with Pool(processes=2, initializer=init_global, initargs=(7,)) as pool:
        assert pool.map(read_global, range(4)) == [7, 7, 7, 7]


def test_slim_actor_wire_roundtrip():
    """The slim push_task_c codec's positional fields must stay in
    lockstep between sender (_push_actor_stream) and the two decoders —
    a silent field mis-assignment would scramble every actor call."""
    import msgpack

    from ray_tpu._private.core_worker import _spec_from_slim
    from ray_tpu._private.protocol import TaskSpec

    spec = TaskSpec(
        task_id=b"t" * 16, function_id=b"", name="inc",
        args=[["v", b"payload"]], num_returns=2, resources={},
        max_retries=3, owner=[b"w" * 16, "unix:/tmp/x.sock", b"n" * 16],
        actor_id=b"a" * 16, method_name="inc", seq_no=41,
        trace_ctx=["trace", "parent", "span"],
    )
    wire = [spec.task_id, spec.actor_id, spec.method_name, spec.args,
            spec.num_returns, spec.seq_no, spec.owner, spec.max_retries,
            spec.trace_ctx]
    decoded = _spec_from_slim(
        msgpack.unpackb(msgpack.packb(wire, use_bin_type=True), raw=False)
    )
    assert decoded.task_id == spec.task_id
    assert decoded.actor_id == spec.actor_id
    assert decoded.method_name == decoded.name == "inc"
    assert decoded.args == [["v", b"payload"]]
    assert decoded.num_returns == 2
    assert decoded.seq_no == 41
    assert decoded.max_retries == 3
    assert decoded.owner == [b"w" * 16, "unix:/tmp/x.sock", b"n" * 16]
    assert decoded.trace_ctx == ["trace", "parent", "span"]
    assert decoded.return_ids()  # derived ids still work


def test_wait_returns_at_most_num_returns(rt):
    """Reference contract: len(ready) <= num_returns even when one scan
    finds more already-finished refs (regression: r4 verify probe)."""

    @ray_tpu.remote
    def quick(i):
        return i

    refs = [quick.remote(i) for i in range(8)]
    ray_tpu.get(list(refs), timeout=60)  # everything finished
    done, pending = ray_tpu.wait(refs, num_returns=3, timeout=30)
    assert len(done) == 3
    assert len(pending) == 5
    # the leftovers are still waitable
    done2, pending2 = ray_tpu.wait(pending, num_returns=5, timeout=30)
    assert len(done2) == 5 and not pending2


@pytest.mark.parametrize("held", ["_ref_lock", "memory_store._lock"])
def test_ref_dropped_by_the_cyclic_collector_takes_no_held_lock(rt, held):
    """The collector can cut in anywhere Python runs, also inside the
    reference counter's own critical section or the memory store's: an
    ``ObjectRef.__del__`` it runs there used to take the same lock again
    on the same thread and never return (the tier-1 hang of ROADMAP D11:
    ``submit_task -> _on_ref_created -> [gc] -> __del__ ->
    _on_ref_deleted``). The count still comes down, on the IO loop."""
    import functools
    import gc
    import threading

    from ray_tpu._private.worker import global_worker

    cw = global_worker.core_worker
    lock = functools.reduce(getattr, held.split("."), cw)
    ref = ray_tpu.put("x")
    oid = ref.id
    assert cw._refcounts[oid] == 1 and oid in cw._owned

    class Cycle:
        pass

    gc.collect()
    gc.disable()  # the cycle below dies where this test says, nowhere else
    try:
        c = Cycle()
        c.me, c.ref = c, ref
        del c, ref  # the only ref now lives in unreachable garbage

        def collect_inside_the_critical_section():
            with lock:
                gc.collect()

        t = threading.Thread(
            target=collect_inside_the_critical_section, daemon=True)
        t.start()
        t.join(20)
        assert not t.is_alive(), f"collector deadlocked on {held}"
    finally:
        gc.enable()
    deadline = time.monotonic() + 20
    while oid in cw._refcounts or oid in cw._owned:
        assert time.monotonic() < deadline, "the deferred release never ran"
        time.sleep(0.01)
    assert cw.memory_store.get(oid) is None
