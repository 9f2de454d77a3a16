"""C++ shared-memory arena store tests (tpu_air/_native/store.cpp): layout,
atomic seal visibility across fork, zero-copy reads, fallback behavior.
The plasma-analog component of SURVEY.md §2B."""

import multiprocessing
import os

import numpy as np
import pytest

from tpu_air.core import serialization
from tpu_air.core.object_store import ObjectStore, new_object_id
from tpu_air.core.shm_arena import Arena, open_arena


@pytest.fixture()
def store(tmp_path):
    s = ObjectStore(str(tmp_path / "store"), create=True)
    yield s
    s.destroy()


def test_arena_available(store):
    assert store._arena is not None, "native arena must build in this environment"


def test_roundtrip_through_arena(store):
    arr = np.arange(10000, dtype=np.float64)
    ref = store.put({"x": arr, "tag": "hello"})
    # object must live in the arena, not a file
    assert store._arena.contains(ref.id)
    assert not os.path.exists(os.path.join(store.root, ref.id))
    out = store.get(ref.id)
    np.testing.assert_array_equal(out["x"], arr)
    assert out["tag"] == "hello"


def test_zero_copy_read_is_view(store):
    arr = np.arange(4096, dtype=np.uint8)
    ref = store.put(arr)
    out = store.get(ref.id)
    # zero-copy contract: the result array's buffer is not a fresh copy —
    # it must be backed by the shared mapping (not writeable)
    assert not out.flags["OWNDATA"]


def test_large_object_falls_back_to_file(tmp_path):
    root = str(tmp_path / "store")
    os.makedirs(root)
    # 1 MB arena → an 8 MB payload must take the file path
    Arena(os.path.join(root, "__arena__"), create=True, capacity=1 << 20, slots=1 << 10)
    s = ObjectStore(root)
    big = np.zeros(1 << 23, dtype=np.uint8)
    ref = s.put(big)
    assert os.path.exists(os.path.join(root, ref.id))
    np.testing.assert_array_equal(s.get(ref.id), big)
    # small objects still use the arena
    small_ref = s.put(b"tiny")
    assert s._arena.contains(small_ref.id)
    assert s.get(small_ref.id) == b"tiny"
    s.destroy()


def test_delete_tombstones_and_id_reuse_safe(store):
    ref = store.put([1, 2, 3])
    assert store.contains(ref.id)
    store.delete(ref.id)
    assert not store.contains(ref.id)
    # tombstoned slot doesn't break probing for other ids
    for _ in range(32):
        r = store.put("v")
        assert store.get(r.id) == "v"


def test_stats_track_objects(store):
    before = store._arena.stats()
    store.put(np.zeros(1000, np.uint8))
    after = store._arena.stats()
    assert after["live_objects"] == before["live_objects"] + 1
    assert after["sealed_bytes"] > before["sealed_bytes"]
    assert after["used"] <= after["capacity"]


def _child_put(root, oid, q):
    s = ObjectStore(root)
    s.put(np.full(5000, 7, dtype=np.int32), object_id=oid)
    q.put("done")


def test_cross_process_visibility(store):
    """Writer in a forked child, reader in the parent — exercises the
    acquire/release seal protocol on the shared mapping."""
    ctx = multiprocessing.get_context("fork")
    oid = new_object_id()
    q = ctx.Queue()
    p = ctx.Process(target=_child_put, args=(store.root, oid, q))
    p.start()
    out = store.get(oid, timeout=30)
    p.join(timeout=10)
    assert q.get(timeout=10) == "done"
    np.testing.assert_array_equal(out, np.full(5000, 7, dtype=np.int32))


def test_concurrent_writers_distinct_objects(store):
    """N forked writers allocate concurrently from the bump allocator."""
    ctx = multiprocessing.get_context("fork")
    oids = [new_object_id() for _ in range(8)]
    q = ctx.Queue()
    procs = [
        ctx.Process(target=_child_put, args=(store.root, oid, q)) for oid in oids
    ]
    for p in procs:
        p.start()
    for oid in oids:
        np.testing.assert_array_equal(
            store.get(oid, timeout=30), np.full(5000, 7, dtype=np.int32)
        )
    for p in procs:
        p.join(timeout=10)


def test_open_arena_missing_compiler_is_none(tmp_path, monkeypatch):
    """Fallback contract: when the native build fails, the store must still
    work through the file path."""
    import tpu_air._native as native

    def boom():
        raise OSError("no compiler")

    monkeypatch.setattr(native, "load_store_lib", boom)
    root = str(tmp_path / "store2")
    os.makedirs(root)
    assert open_arena(root, create=True) is None
    s = ObjectStore(root)
    assert s._arena is None
    ref = s.put({"a": 1})
    assert s.get(ref.id) == {"a": 1}
    s.destroy()


# --------------------------------------------------------------------------
# object spilling (Introduction…ipynb:cc-3 "object spilling")
# --------------------------------------------------------------------------


def _budgeted_store(tmp_path, monkeypatch, budget, arena_cap=1 << 16):
    root = str(tmp_path / "store")
    os.makedirs(root)
    # tiny arena so multi-KB payloads take the file path, tiny file budget so
    # the file path spills
    Arena(os.path.join(root, "__arena__"), create=True,
          capacity=arena_cap, slots=1 << 8)
    monkeypatch.setenv("TPU_AIR_STORE_BYTES", str(budget))
    monkeypatch.setenv("TPU_AIR_SPILL_DIR", str(tmp_path / "spill"))
    return ObjectStore(root)


def test_spill_on_budget_and_transparent_restore(tmp_path, monkeypatch):
    s = _budgeted_store(tmp_path, monkeypatch, budget=300_000)
    arrays = {}
    refs = []
    for i in range(8):  # 8 x ~100KB against a 300KB tmpfs budget
        arr = np.full(100_000, i, dtype=np.uint8)
        refs.append(s.put(arr))
        arrays[refs[-1].id] = arr
    spill = s.spill_stats()
    assert spill["spilled_objects"] >= 4, spill
    # root stays under budget (modulo the newest object)
    root_bytes = sum(
        os.path.getsize(os.path.join(s.root, n))
        for n in os.listdir(s.root) if not n.startswith(("__", "."))
    )
    assert root_bytes <= 300_000 + 100_064
    # every object — resident or spilled — restores transparently
    for ref in refs:
        np.testing.assert_array_equal(s.get(ref.id), arrays[ref.id])
    # delete reaches spilled objects too
    for ref in refs:
        s.delete(ref.id)
    assert s.spill_stats()["spilled_objects"] == 0
    s.destroy()


def test_spill_oldest_first_and_oversized_object(tmp_path, monkeypatch):
    s = _budgeted_store(tmp_path, monkeypatch, budget=250_000)
    first = s.put(np.zeros(100_000, dtype=np.uint8))
    import time as _t
    _t.sleep(0.05)  # mtime-ordered eviction needs distinct stamps
    second = s.put(np.ones(100_000, dtype=np.uint8))
    _t.sleep(0.05)
    s.put(np.full(100_000, 2, dtype=np.uint8))  # pushes over budget
    assert os.path.exists(s._spill_path(first.id)), "oldest object not spilled"
    assert not os.path.exists(s._path(first.id))
    assert os.path.exists(s._path(second.id)), "newer object wrongly evicted"
    # an object larger than the whole budget goes straight to disk
    huge = s.put(np.zeros(400_000, dtype=np.uint8))
    assert os.path.exists(s._spill_path(huge.id))
    assert s.get(huge.id).shape == (400_000,)
    s.destroy()


def test_dataset_larger_than_budget_spills_and_completes(tmp_path, monkeypatch):
    """End-to-end: a map_batches pipeline whose blocks exceed the tmpfs
    budget completes correctly, with spilled blocks restored on read."""
    import subprocess
    import sys

    script = """
import numpy as np
import os
import tpu_air
from tpu_air.core import runtime as rt_mod

tpu_air.init(num_cpus=2, num_chips=0)
import tpu_air.data as data
ds = data.from_items([{"x": np.zeros(100_000, dtype=np.uint8) + i} for i in range(12)])
out = ds.map_batches(lambda df: df, batch_size=1).take_all()
assert len(out) == 12
sums = sorted(int(r["x"].sum()) for r in out)
assert sums == sorted(i * 100_000 for i in range(12)), sums[:3]
spill = rt_mod.get_runtime().store.spill_stats()
assert spill["spilled_objects"] > 0, f"nothing spilled: {spill}"
print("SPILL_E2E_OK", spill["spilled_objects"])
tpu_air.shutdown()
"""
    env = dict(os.environ)
    env["TPU_AIR_STORE_BYTES"] = "400000"
    env["TPU_AIR_SPILL_DIR"] = str(tmp_path / "spill")
    env["TPU_AIR_ARENA_BYTES"] = str(1 << 16)  # tiny arena: blocks hit files
    proc = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=180,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, f"stdout={proc.stdout}\nstderr={proc.stderr[-2000:]}"
    assert "SPILL_E2E_OK" in proc.stdout


# --------------------------------------------------------------------------
# native ownership / ref-counting / block reuse (SURVEY.md §2B core_worker
# row: "ownership/ref-counting in native code"; plasma reclamation contract)
# --------------------------------------------------------------------------


def test_delete_reclaims_space_for_reuse(tmp_path):
    """An unpinned delete returns the block to the shared free list and a
    later alloc reuses it — the arena no longer only-grows."""
    root = str(tmp_path / "store")
    os.makedirs(root)
    Arena(os.path.join(root, "__arena__"), create=True,
          capacity=1 << 20, slots=1 << 10)
    s = ObjectStore(root)
    payload = np.zeros(200_000, dtype=np.uint8)
    # churn 50 x 200KB through a 1MB arena: without reuse this needs 10MB
    for i in range(50):
        ref = s.put(payload + (i % 251))
        assert s._arena.contains(ref.id), f"round {i} fell back to file"
        val = s.get(ref.id)
        assert val[0] == i % 251
        del val
        import gc
        gc.collect()  # drop the value's pin before deleting
        s.delete(ref.id)
    st = s._arena.stats()
    assert st["used"] <= (1 << 20), st
    assert not [n for n in os.listdir(root) if not n.startswith("__")]
    s.destroy()


def test_pinned_object_survives_delete_until_value_dies(tmp_path):
    """Ray/plasma ownership: delete while a zero-copy reader holds the value
    parks the object (ZOMBIE); bytes stay valid; the last reference's death
    releases the pin and reclaims the block."""
    import gc

    root = str(tmp_path / "store")
    os.makedirs(root)
    Arena(os.path.join(root, "__arena__"), create=True,
          capacity=1 << 20, slots=1 << 10)
    s = ObjectStore(root)
    arr = np.arange(50_000, dtype=np.uint32)
    ref = s.put(arr)
    val = s.get(ref.id)  # zero-copy view, pinned
    assert s._arena.pins(ref.id) == 1
    s.delete(ref.id)
    assert not s.contains(ref.id)  # invisible immediately
    # hammer the arena with new objects that would love the freed block
    for i in range(20):
        s.put(np.full(60_000, i, dtype=np.uint8))
    np.testing.assert_array_equal(val, arr)  # bytes never reused while pinned
    free_before = s._arena.stats()["free_bytes"]
    del val
    gc.collect()
    free_after = s._arena.stats()["free_bytes"]
    assert free_after > free_before, "last unpin did not reclaim the zombie"
    s.destroy()


def test_self_contained_values_release_pin_immediately(tmp_path):
    root = str(tmp_path / "store")
    os.makedirs(root)
    Arena(os.path.join(root, "__arena__"), create=True,
          capacity=1 << 20, slots=1 << 10)
    s = ObjectStore(root)
    ref = s.put({"k": "v", "n": 17})  # no out-of-band buffers
    v = s.get(ref.id)
    assert v == {"k": "v", "n": 17}
    assert s._arena.pins(ref.id) == 0, "nbuf==0 value must not hold a pin"
    s.destroy()


def test_derived_object_outliving_container_keeps_pin(tmp_path):
    """The pin must be tied to the out-of-band BUFFERS, not the top-level
    value: a Series extracted from a DataFrame (or an array pulled out of a
    dict) outlives its container while still referencing arena bytes.
    Regression for the round-3 advisor finding (object_store._get_pinned)."""
    import gc

    import pandas as pd

    root = str(tmp_path / "store")
    os.makedirs(root)
    Arena(os.path.join(root, "__arena__"), create=True,
          capacity=1 << 21, slots=1 << 10)
    s = ObjectStore(root)

    # case 1: array extracted from a dict container
    arr = np.arange(30_000, dtype=np.uint32)
    ref = s.put({"payload": arr, "meta": "x"})
    val = s.get(ref.id)
    inner = val["payload"]          # derived: shares the arena bytes
    del val
    gc.collect()                    # container dies; pin must survive
    s.delete(ref.id)
    for i in range(20):             # block-reuse pressure
        s.put(np.full(40_000, i, dtype=np.uint8))
    np.testing.assert_array_equal(inner, arr)
    del inner
    gc.collect()

    # case 2: Series extracted from a DataFrame
    df = pd.DataFrame({"a": np.arange(20_000, dtype=np.int64),
                       "b": np.ones(20_000)})
    ref2 = s.put(df)
    got = s.get(ref2.id)
    series = got["a"]               # derived view of the block manager
    del got
    gc.collect()
    s.delete(ref2.id)
    for i in range(20):
        s.put(np.full(40_000, i, dtype=np.uint8))
    np.testing.assert_array_equal(series.to_numpy(),
                                  np.arange(20_000, dtype=np.int64))
    s.destroy()


def test_reput_same_id_while_old_generation_zombie(tmp_path):
    """Pin disambiguation: unpinning an old generation must not touch a
    re-put of the same id."""
    import gc

    root = str(tmp_path / "store")
    os.makedirs(root)
    Arena(os.path.join(root, "__arena__"), create=True,
          capacity=1 << 20, slots=1 << 10)
    s = ObjectStore(root)
    oid = new_object_id()
    s.put(np.zeros(10_000, dtype=np.uint8), oid)
    old = s.get(oid)          # pin generation 1
    s.delete(oid)             # gen 1 → zombie
    s.put(np.ones(10_000, dtype=np.uint8), oid)  # gen 2, same id
    new = s.get(oid)
    assert new[0] == 1 and old[0] == 0
    del old
    gc.collect()              # unpin gen 1 → reclaimed
    assert s._arena.pins(oid) == 1, "gen-2 pin must survive gen-1 unpin"
    np.testing.assert_array_equal(new, np.ones(10_000, dtype=np.uint8))
    s.destroy()
