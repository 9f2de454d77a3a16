"""Core runtime tests — the W9 contract (Overview_of_Ray.ipynb) plus the
low-level W7 patterns (Scaling_batch_inference.ipynb:cc-88..129)."""

import time

import numpy as np
import pytest

import tpu_air
from tpu_air import ActorPool


# -- objects (ray.put / ray.get: Overview_of_Ray.ipynb:cc-34,44) -------------


def test_put_get_roundtrip(air):
    ref = tpu_air.put({"a": 1, "b": [1, 2, 3]})
    assert tpu_air.get(ref) == {"a": 1, "b": [1, 2, 3]}


def test_put_get_numpy_zero_copy(air):
    arr = np.arange(1_000_000, dtype=np.float32).reshape(1000, 1000)
    ref = tpu_air.put(arr)
    out = tpu_air.get(ref)
    np.testing.assert_array_equal(arr, out)
    # zero-copy contract: result is backed by the store mapping, not writable
    assert not out.flags.writeable


def test_get_list(air):
    refs = [tpu_air.put(i) for i in range(5)]
    assert tpu_air.get(refs) == list(range(5))


def test_get_type_error(air):
    with pytest.raises(TypeError):
        tpu_air.get(42)


# -- tasks (@ray.remote fn: Overview_of_Ray.ipynb:cc-41) ---------------------


def test_task_basic(air):
    @tpu_air.remote
    def add(a, b):
        return a + b

    assert tpu_air.get(add.remote(2, 3)) == 5


def test_task_objectref_args_resolved(air):
    """Top-level ObjectRef args are auto-resolved, as in the model-broadcast
    pattern at Scaling_batch_inference.ipynb:cc-88."""

    @tpu_air.remote
    def total(xs, offset):
        return sum(xs) + offset

    data_ref = tpu_air.put([1, 2, 3])
    assert tpu_air.get(total.remote(data_ref, offset=10)) == 16


def test_task_parallelism(air):
    """W9: parallel tasks overlap (6x-speedup class behavior, cc-48)."""

    @tpu_air.remote
    def snooze(t):
        time.sleep(t)
        return t

    start = time.monotonic()
    refs = [snooze.remote(0.5) for _ in range(4)]
    tpu_air.get(refs)
    elapsed = time.monotonic() - start
    assert elapsed < 4 * 0.5  # strictly better than sequential


def test_task_error_propagates(air):
    @tpu_air.remote
    def boom():
        raise ValueError("kaboom")

    with pytest.raises(tpu_air.RemoteError, match="kaboom"):
        tpu_air.get(boom.remote())


def test_remote_function_direct_call_rejected(air):
    @tpu_air.remote
    def f():
        return 1

    with pytest.raises(TypeError, match="remote"):
        f()


def test_nested_task_submission(air):
    @tpu_air.remote
    def inner(x):
        return x * 2

    @tpu_air.remote
    def outer(x):
        return tpu_air.get(inner.remote(x)) + 1

    assert tpu_air.get(outer.remote(5)) == 11


# -- wait (Scaling_batch_inference.ipynb:cc-115) -----------------------------


def test_wait_returns_ready_and_pending(air):
    @tpu_air.remote
    def snooze(t):
        time.sleep(t)
        return t

    fast = snooze.remote(0.05)
    slow = snooze.remote(2.0)
    ready, pending = tpu_air.wait([fast, slow], num_returns=1, timeout=1.5)
    assert ready == [fast]
    assert pending == [slow]
    tpu_air.get(slow)


def test_wait_timeout(air):
    @tpu_air.remote
    def snooze():
        time.sleep(1.0)
        return 1

    ref = snooze.remote()
    ready, pending = tpu_air.wait([ref], num_returns=1, timeout=0.05)
    assert ready == []
    assert pending == [ref]
    tpu_air.get(ref)


# -- actors (Scaling_batch_inference.ipynb:cc-105) ---------------------------


def test_actor_state(air):
    @tpu_air.remote
    class Counter:
        def __init__(self, start=0):
            self.n = start

        def incr(self, by=1):
            self.n += by
            return self.n

    c = Counter.remote(10)
    assert tpu_air.get(c.incr.remote()) == 11
    assert tpu_air.get(c.incr.remote(5)) == 16


def test_actor_method_ordering(air):
    @tpu_air.remote
    class Appender:
        def __init__(self):
            self.items = []

        def add(self, x):
            self.items.append(x)

        def items_list(self):
            return self.items

    a = Appender.remote()
    for i in range(20):
        a.add.remote(i)
    assert tpu_air.get(a.items_list.remote()) == list(range(20))


def test_actor_init_error_surfaces(air):
    @tpu_air.remote
    class Broken:
        def __init__(self):
            raise RuntimeError("bad init")

        def ping(self):
            return "pong"

    b = Broken.remote()
    with pytest.raises(tpu_air.RemoteError, match="bad init"):
        tpu_air.get(b.ping.remote())


def test_actor_kill(air):
    @tpu_air.remote
    class A:
        def ping(self):
            return "pong"

    a = A.remote()
    assert tpu_air.get(a.ping.remote()) == "pong"
    tpu_air.kill(a)
    with pytest.raises(tpu_air.RemoteError, match="ActorDied"):
        tpu_air.get(a.ping.remote())


def test_actor_handle_passing(air):
    """Handles are serializable and usable from other tasks."""

    @tpu_air.remote
    class Holder:
        def __init__(self):
            self.v = 7

        def value(self):
            return self.v

    @tpu_air.remote
    def reader(h):
        return tpu_air.get(h.value.remote())

    h = Holder.remote()
    assert tpu_air.get(reader.remote(h)) == 7


def test_chip_lease_env(air):
    """num_chips actors receive a chip lease via TPU_AIR_CHIP_IDS
    (SURVEY.md §2B raylet row: placement = sub-mesh assignment)."""
    import os

    @tpu_air.remote(num_chips=2)
    class ChipActor:
        def chips(self):
            return os.environ.get("TPU_AIR_CHIP_IDS")

    a = ChipActor.remote()
    chips = tpu_air.get(a.chips.remote())
    assert chips is not None and len(chips.split(",")) == 2
    tpu_air.kill(a)


def test_unsatisfiable_resources_rejected(air):
    @tpu_air.remote(num_chips=1000)
    def f():
        return 1

    with pytest.raises(tpu_air.TpuAirError, match="exceeds"):
        f.remote()


# -- ActorPool (Scaling_batch_inference.ipynb:cc-124-129) --------------------


def test_actor_pool_map(air):
    @tpu_air.remote
    class Doubler:
        def double(self, x):
            return 2 * x

    pool = ActorPool([Doubler.remote() for _ in range(2)])
    out = list(pool.map(lambda a, v: a.double.remote(v), range(8)))
    assert out == [2 * i for i in range(8)]


def test_actor_pool_map_unordered(air):
    @tpu_air.remote
    class Sq:
        def sq(self, x):
            return x * x

    pool = ActorPool([Sq.remote() for _ in range(2)])
    out = sorted(pool.map_unordered(lambda a, v: a.sq.remote(v), range(6)))
    assert out == [i * i for i in range(6)]


# -- oversubscribed actor creation queues ------------------------------------


def test_oversubscribed_actor_creation_queues(air):
    """8 actors x 2 chips on an 8-chip runtime: creations beyond capacity
    must QUEUE for chip leases (not raise a resource timeout), and complete
    as earlier actors release their chips — the Tune trial-queueing contract
    (Model_finetuning_and_batch_inference.ipynb:cc-53-54)."""

    @tpu_air.remote(num_chips=2)
    class Trial:
        def run(self):
            import os

            return os.environ["TPU_AIR_CHIP_IDS"]

    handles = [Trial.remote() for _ in range(8)]  # 16 chips wanted, 8 exist
    results = []
    for h in handles:
        # each get() can only succeed once predecessors were killed: the
        # final 4 actors start queued
        results.append(tpu_air.get(h.run.remote()))
        tpu_air.kill(h)
    assert len(results) == 8
    for chips in results:
        assert len(chips.split(",")) == 2


def test_queued_actor_kill_cancels(air):
    @tpu_air.remote(num_chips=8)
    class Big:
        def ping(self):
            return "pong"

    a = Big.remote()          # takes every chip
    assert tpu_air.get(a.ping.remote()) == "pong"
    b = Big.remote()          # queued behind a
    ref = b.ping.remote()     # buffered while queued
    tpu_air.kill(b)           # cancel before placement
    with pytest.raises(tpu_air.TpuAirError):
        tpu_air.get(ref)
    tpu_air.kill(a)


def test_chip_lease_shapes_follow_topology():
    """docs/MULTIHOST.md §2 lease shapes, unit level: single-host
    co-location with best-fit, whole-host cross-host spans with contiguity
    preference, None when the request doesn't tile the free topology."""
    from tpu_air.core.runtime import Runtime

    rt = Runtime.__new__(Runtime)  # shape logic only — no processes
    rt.num_chips = 16
    rt.chips_per_host = 4
    rt.free_chips = list(range(16))

    l3 = rt._claim_chips(3)
    assert len({c // 4 for c in l3}) == 1
    # best-fit: the partially-used host (1 free chip) can't serve 2; a
    # fresh host serves it without fragmenting the 1-free host further
    l2 = rt._claim_chips(2)
    assert len({c // 4 for c in l2}) == 1 and (l2[0] // 4) != (l3[0] // 4)
    # 8 chips = 2 whole hosts, contiguous pair preferred
    l8 = rt._claim_chips(8)
    hosts8 = sorted({c // 4 for c in l8})
    assert len(hosts8) == 2 and hosts8[1] - hosts8[0] == 1, hosts8
    assert all(len([c for c in l8 if c // 4 == h]) == 4 for h in hosts8)
    # nothing whole left: another 8-chip request must not be granted
    assert rt._claim_chips(8) is None
    # 1 chip still fits on the fragmented host
    assert rt._claim_chips(1) is not None
    # non-multiple spans never fit
    assert rt._claim_chips(6) is None
    # release everything; a 16-chip lease takes the whole slice
    rt.free_chips = list(range(16))
    assert sorted(rt._claim_chips(16)) == list(range(16))


def test_task_pool_grows_to_num_cpus(air):
    """Driver-submitted task parallelism must reach num_cpus, not stall at
    the initial min(2, num_cpus) pool (W9's 20-parallel-tasks contract,
    Overview_of_Ray.ipynb:cc-41; found by a dispatch benchmark in r5)."""
    import time as _t

    def nap():
        _t.sleep(0.5)
        return 1

    nap_r = tpu_air.remote(nap)
    refs = [nap_r.remote() for _ in range(4)]
    rt = tpu_air.core.runtime.get_runtime()
    # the growth itself is the property under test (wall clock would fold
    # in process-spawn cost, which is load-dependent): the pool must reach
    # num_cpus=4 while the burst is in flight
    deadline = _t.monotonic() + 20
    pool = 0
    while _t.monotonic() < deadline and pool < 4:
        pool = sum(1 for w in rt.workers.values()
                   if w.alive and w.actor_id is None)
        _t.sleep(0.02)
    assert pool >= 4, f"pool stuck at {pool} workers"
    assert sum(tpu_air.get(refs)) == 4
