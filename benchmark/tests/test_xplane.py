"""The trace reduction, on a hand-made trace with known answers
(data/synthetic.xplane.txt; microseconds from the line's start):

device 0, XLA Ops: fusion.1 0-100, convolution.2 50-200 (overlaps: the union
is 0-200), while.3 200-600 (a container: never work), fusion.4 250-300 and
350-400 inside it, all-reduce.5 600-700 with fusion.6 650-680 under it
(70 us exposed), fusion.7 900-1000.  Busy 500 of 1000.
device 1: fusion.1 0-500.  host: thread_main 0-1000, wait_loss 395-605,
collate 690-910.
"""

import os

import pytest

from benchmark import xplane

DATA = os.path.join(os.path.dirname(__file__), "data")
US = 1e-6


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "synthetic.xplane.txt")) as f:
        return xplane.reduce_profile(ProfileData.from_text_proto(f.read()))


def test_intervals():
    assert xplane.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)]) == [
        (0, 3), (5, 7)]
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert xplane.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert xplane.subtract([(0, 4)], []) == [(0, 4)]


def test_busy_is_a_union_not_a_sum(summary):
    assert summary.window_s == pytest.approx(1000 * US)
    assert xplane.total(summary.busy(0)) == pytest.approx(500 * US)
    summed = sum(e - s for _, s, e in summary.devices[0].ops)
    assert summed == pytest.approx(580 * US)  # what a sum would have said
    assert summary.busy_s == pytest.approx(500 * US)  # mean of 500 and 500
    assert summary.idle_share == pytest.approx(0.5)


def test_containers_are_not_work(summary):
    names = [n for n, _, _ in summary.devices[0].ops]
    assert "while.3" not in names
    assert summary.longest_container("while") == pytest.approx(400 * US)
    assert summary.longest_container("conditional") is None


def test_known_gaps_found_and_named(summary):
    gaps = summary.gaps()
    assert [(round(s / US) - 5000, round(e / US) - 5000) for s, e in gaps] == [
        (200, 250), (300, 350), (400, 600), (700, 900)]
    named = dict(summary.idle_gaps())
    # the most specific host event covering a gap names it, not the thread's
    # outermost one
    assert named["wait_loss"] == pytest.approx(200 * US)
    assert named["collate"] == pytest.approx(200 * US)
    assert named["thread_main"] == pytest.approx(100 * US)


def test_collective_exposed_part(summary):
    assert summary.collective_exposed_s(0) == pytest.approx(70 * US)
    assert summary.collective_exposed_s(1) is None


def test_top_operations(summary):
    ops = dict((n, t) for n, t in summary.device_ops())
    # fusion.1: 100 us on device 0 and 500 on device 1, averaged
    assert ops["fusion.1"] == pytest.approx(300 * US)
    assert ops["all-reduce.5"] == pytest.approx(50 * US)


def test_no_device_plane_reduces_to_nothing():
    from jax.profiler import ProfileData

    host_only = ('planes { id: 1 name: "/host:CPU" lines { id: 1 name: "t" '
                 'events { metadata_id: 1 offset_ps: 0 duration_ps: 5 } } '
                 'event_metadata { key: 1 value { id: 1 name: "x" } } }')
    assert xplane.reduce_profile(
        ProfileData.from_text_proto(host_only)) is None


def test_recorded_v5e_trace():
    """data/v5e_small.xplane.pb, recorded on a TPU v5e (PR 22): a jitted
    chain of four 2048^3 bf16 matmuls run twice with ``time.sleep(0.05)``
    between the runs."""
    s = xplane.reduce_file(os.path.join(DATA, "v5e_small.xplane.pb"))
    assert sorted(s.devices) == [0]
    names = {n for n, _, _ in s.devices[0].ops}
    assert {"fusion", "fusion.1", "fusion.2", "fusion.3"} <= names
    assert all(" = " not in n and not n.startswith("%") for n in names)
    # eight matmuls of about 90 us: 2 * 2048^3 FLOPs each, 190 TFLOP/s
    assert 0.6e-3 < s.busy_s < 0.9e-3
    assert 0.050 < s.window_s < 0.060
    assert s.idle_share > 0.98
    assert max(e - b for b, e in s.gaps()) > 0.049  # the sleep
    assert s.collective_exposed_s() is None
    assert s.longest_container("while") is None
    top = s.device_ops()[0]
    assert top[0].startswith("fusion") and 1.5e-4 < top[1] < 2.2e-4


def test_recorded_four_chip_trace():
    """data/v5e_small_4chip.xplane.pb, recorded on four v5e chips (PR 22):
    the matmul chain on device 0 twice, then twice a program sharded over
    the four chips that ends in an all-reduce (5.0 and 6.8 us on device 0,
    no compute under either)."""
    s = xplane.reduce_file(os.path.join(DATA, "v5e_small_4chip.xplane.pb"))
    assert sorted(s.devices) == [0, 1, 2, 3]
    for d in s.devices:
        assert any(n == "all-reduce" for n, _, _ in s.devices[d].ops)
        assert 1.0e-5 < s.collective_exposed_s(d) < 1.6e-5
    assert s.collective_exposed_s() == s.collective_exposed_s(0)
    assert s.collective_exposed_s(0) == pytest.approx(11.8e-6, rel=0.01)
    # busy is averaged over the chips: device 0 ran the chain as well
    assert xplane.total(s.busy(0)) > 2 * xplane.total(s.busy(1))
    per_dev = [xplane.total(s.busy(d)) for d in s.devices]
    assert s.busy_s == pytest.approx(sum(per_dev) / 4)
