"""chip_smoke.py rehearsed on the CPU, and the rules it relies on.

The script's phases are functions of a model config, an expected platform and
sizes; here they run at ``T5Config.tiny()`` on the virtual CPU mesh, in a
process of their own so that "the driver held no backend" means something (the
pytest process computes with JAX).  The script itself must refuse to run on
this TPU-less host.  The rest are the unit tests of the process model the chip
forces: chips are found not assumed, a lease is an ownership, a chip is free
when its process has exited, one rule for the compile cache.
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

import tpu_air
from tpu_air.core import chips, runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PHASES_AT_TINY = """
import sys
sys.path.insert(0, {repo!r})
import chip_smoke
from tpu_air.core import chips
from tpu_air.models.t5 import T5Config

if __name__ == "__main__":
    small = chip_smoke.Sizes(
        train_batch=2, enc_len=16, dec_len=8, train_steps=4, gen_rows=8,
        new_tokens=4, serve_batch=4, serve_new_tokens=4, requests=8)
    device = chip_smoke.run(chip_smoke.one_chip, T5Config.tiny(), "cpu", small)
    print("DEVICE", device["platform"], "DRIVER_BACKEND", chips.backend_live())
"""


def test_phases_run_on_cpu_at_tiny_and_driver_holds_no_backend():
    proc = subprocess.run(
        [sys.executable, "-c", _PHASES_AT_TINY.format(repo=REPO)],
        capture_output=True, text=True, timeout=600, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    assert [ln["phase"] for ln in lines] == ["train", "generate", "serve"]
    for ln in lines:
        # seen from inside the leased worker, one chip lease each
        assert ln["platform"] == "cpu" and ln["device_kind"]
        assert len(ln["worker_chips"]) == 1 and len(ln["worker_chips"][0]) == 1
        assert ln["seconds"] >= ln["compile_seconds"] >= 0
        assert isinstance(ln["cache_warm"], bool)
    train, generate, serve = lines
    assert train["steps"] == 4 and train["checkpoint_params"] > 0
    assert generate["rows"] == 8 and generate["repeatable_rows"] == 8
    assert serve["http_200"] == serve["completed"] == 8 and serve["shed"] == 0
    assert "DEVICE cpu DRIVER_BACKEND False" in proc.stdout


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_script_fails_on_a_host_without_a_tpu(script):
    """No chip means a failure — no CPU stand-in, no result line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, script)],
        capture_output=True, text=True, timeout=300, cwd=REPO)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


# -- chips are found, not assumed ---------------------------------------------


def _fake_host(root, pci_chips, vfio_groups, accel_nodes=0):
    """A /dev + /sys tree: ``pci_chips`` Google devices on the bus, each in
    its own iommu group, of which ``vfio_groups`` are handed to us."""
    for g in range(pci_chips):
        dev = root / "sys/kernel/iommu_groups" / str(g) / "devices" / f"0000:00:0{g}.0"
        dev.mkdir(parents=True)
        (dev / "vendor").write_text("0x1ae0\n")
    other = root / "sys/kernel/iommu_groups/9/devices/0000:00:1f.0"
    other.mkdir(parents=True)
    (other / "vendor").write_text("0x8086\n")
    (root / "dev/vfio").mkdir(parents=True)
    for g in list(vfio_groups) + [9]:
        (root / "dev/vfio" / str(g)).write_text("")
    (root / "dev/vfio/vfio").write_text("")
    for i in range(accel_nodes):
        (root / "dev" / f"accel{i}").write_text("")
    return str(root)


@pytest.mark.parametrize("pci, vfio, accel, want", [
    (4, [2], 0, 1),           # four on the bus, one handed to us (the one-chip machine)
    (4, [0, 1, 2, 3], 0, 4),  # the four-chip host
    (0, [], 4, 4),            # an older generation's /dev/accel* nodes
    (0, [], 0, 0),            # this sandbox
])
def test_local_chip_count_reads_device_nodes(tmp_path, pci, vfio, accel, want):
    assert chips.local_chip_count(_fake_host(tmp_path, pci, vfio, accel)) == want


@pytest.mark.parametrize("platforms, nodes, want", [
    ("", 1, True), ("tpu,cpu", 1, True), ("cpu", 4, False), ("", 0, False),
])
def test_accelerator_expected_from_what_the_process_sees(
        monkeypatch, platforms, nodes, want):
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    monkeypatch.setattr(chips, "local_chip_count", lambda root="/": nodes)
    assert chips.accelerator_expected() is want


_DISCOVERY = """
import json, os, sys
sys.path.insert(0, {repo!r})
import tpu_air
from tpu_air.core import chips
chips.local_chip_count = lambda root="/": 4   # a four-chip host
os.environ["JAX_PLATFORMS"] = "tpu,cpu"
os.environ["TPU_AIR_NO_GCS"] = "1"
out = {{}}
if __name__ == "__main__":
    for name, env, arg in (("found", None, None), ("env", "2", None),
                           ("arg", "2", 3)):
        os.environ.pop("TPU_AIR_NUM_CHIPS", None)
        if env:
            os.environ["TPU_AIR_NUM_CHIPS"] = env
        rt = tpu_air.init(num_cpus=1, num_chips=arg)
        out[name] = rt.num_chips
        tpu_air.shutdown()
    out["driver_backend"] = chips.backend_live()
    print(json.dumps(out))
"""


def test_init_discovers_chips_and_explicit_counts_win():
    proc = subprocess.run(
        [sys.executable, "-c", _DISCOVERY.format(repo=REPO)],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(proc.stdout.splitlines()[-1]) == {
        "found": 4, "env": 2, "arg": 3, "driver_backend": False}


# -- a lease is an ownership ----------------------------------------------------


@tpu_air.remote
class _Leased:
    def devices(self):
        from tpu_air.parallel import visible_devices

        return os.environ.get("TPU_AIR_CHIP_IDS"), \
            [d.id for d in visible_devices()]

    def slow_exit(self, seconds):
        import atexit

        atexit.register(time.sleep, seconds)


def test_leased_worker_sees_only_its_lease_on_the_cpu_mesh(air):
    a = _Leased.options(num_chips=2).remote()
    b = _Leased.options(num_chips=4).remote()
    try:
        (la, da), (lb, db) = tpu_air.get(
            [a.devices.remote(), b.devices.remote()])
    finally:
        tpu_air.kill(a)
        tpu_air.kill(b)
    # on the virtual mesh a lease indexes the eight devices
    assert da == [int(c) for c in la.split(",")] and len(da) == 2
    assert db == [int(c) for c in lb.split(",")] and len(db) == 4
    assert not set(da) & set(db)


def test_lease_beyond_the_devices_raises_instead_of_wrapping(monkeypatch):
    from tpu_air.parallel import visible_devices

    monkeypatch.setenv("TPU_AIR_CHIP_IDS", "7,8")  # eight devices: 0..7
    with pytest.raises(chips.ChipLeaseError, match=r"names device\(s\) \[8\]"):
        visible_devices()


@pytest.fixture
def chip_host(monkeypatch):
    """``chips`` believes it is in a fresh worker on a four-chip host."""
    monkeypatch.setattr(chips, "accelerator_expected", lambda: True)
    monkeypatch.setattr(chips, "local_chip_count", lambda root="/": 4)
    monkeypatch.setattr(chips, "backend_live", lambda: False)
    monkeypatch.setattr(chips, "sync_jax_config_from_env", lambda: None)
    for k in ("TPU_AIR_CHIP_IDS", "TPU_VISIBLE_CHIPS", "JAX_PLATFORMS",
              "TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_BOUNDS"):
        monkeypatch.setenv(k, os.environ.get(k, ""))  # restored afterwards
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")


@pytest.mark.parametrize("lease, bounds", [
    ([3], "1,1,1"), ([2, 3], "1,2,1"), ([0, 1, 2, 3], "2,2,1")])
def test_confine_restricts_the_process_before_its_backend(
        chip_host, lease, bounds):
    chips.confine(lease)
    want = ",".join(map(str, lease))
    assert os.environ["TPU_VISIBLE_CHIPS"] == want
    assert os.environ["TPU_AIR_CHIP_IDS"] == want
    assert os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] == bounds
    assert os.environ["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert chips.confined()


def test_confine_keeps_a_worker_without_a_lease_on_the_cpu(chip_host):
    chips.confine([])
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert "TPU_AIR_CHIP_IDS" not in os.environ


@pytest.mark.parametrize("lease", [[1, 2], [4], [0, 1, 2], [0, 2]])
def test_confine_refuses_a_lease_it_cannot_honour(chip_host, lease):
    with pytest.raises(chips.ChipLeaseError, match="cannot confine"):
        chips.confine(lease)


def test_confine_refuses_a_process_that_already_has_a_backend(
        chip_host, monkeypatch):
    monkeypatch.setattr(chips, "backend_live", lambda: True)
    with pytest.raises(chips.ChipLeaseError, match="before its chip lease"):
        chips.confine([0])


def test_driver_with_a_backend_cannot_lease_chips(air, monkeypatch):
    """On a chip host a driver that computes with JAX holds the chips: the
    request is refused with the reason, not left to hang a worker."""
    monkeypatch.setattr(chips, "accelerator_expected", lambda: True)
    monkeypatch.setattr(chips, "backend_live", lambda: True)
    with pytest.raises(tpu_air.TpuAirError, match="holds the host's chips"):
        _Leased.options(num_chips=1).remote()

    @tpu_air.remote(num_chips=1)
    def chip_task():
        return 0

    monkeypatch.setattr(chips, "backend_live", lambda: False)
    with pytest.raises(tpu_air.TpuAirError, match="a task cannot hold a chip"):
        chip_task.remote()


# -- a chip is free when its process has exited ----------------------------------


def test_chip_is_not_released_until_its_worker_has_exited(air):
    rt = runtime.get_runtime()
    a = _Leased.options(num_chips=8).remote()
    tpu_air.get(a.slow_exit.remote(1.0))
    proc = rt.actors[a._actor_id].worker.proc
    killer = threading.Thread(target=tpu_air.kill, args=(a,))
    killer.start()
    # a second killer (serve.shutdown() racing a watcher's kill) owns
    # nothing, but for it too "killed" must mean "the chips are free"
    free_after_second_kill = []
    second = threading.Thread(target=lambda: (
        tpu_air.kill(a), free_after_second_kill.append(len(rt.free_chips))))
    second.start()
    held_while_alive = 0
    while killer.is_alive():
        free = len(rt.free_chips)     # read BEFORE the liveness check:
        alive = proc.is_alive()       # free chips imply an exited process
        assert not (free == 8 and alive), \
            "chips went back to the pool while their process was alive"
        held_while_alive += alive and free == 0
        time.sleep(0.01)
    killer.join(timeout=30)
    second.join(timeout=30)
    assert not killer.is_alive() and not second.is_alive()
    assert free_after_second_kill == [8]
    assert held_while_alive > 0, "never saw the dying process hold its chips"
    assert not proc.is_alive()
    assert sorted(rt.free_chips) == list(range(8))
    assert rt.avail["chip"] == 8.0


# -- one rule for the compile cache ----------------------------------------------


@pytest.mark.parametrize("preset", [None, "/some/dir"])
def test_compile_cache_rule(monkeypatch, preset):
    import jax

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", preset or "")
    try:
        path = runtime.place_compile_cache()
        # set: left alone; unset: one fixed directory inside the checkout
        assert path == (preset or os.path.join(REPO, ".jax_cache"))
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == path
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
