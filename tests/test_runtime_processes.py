"""No process outlives a run (tpu_air/core/runtime.py): a worker's own
children go with it at shutdown, and a worker whose driver is killed goes
too, while it computes, as does the driver's control-plane daemon
(tpu_air/_native/gcs_server.cpp)."""

import json
import os
import shutil
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DRIVER = os.path.join(REPO, "tests", "_process_driver.py")


def _running(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False


def test_shutdown_leaves_no_descendant():
    out = subprocess.run([sys.executable, DRIVER, "shutdown"], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    report = json.loads(out.stdout.strip().splitlines()[-1])
    assert report["left"] == []
    assert set(report["state"].values()) <= {"gone", "Z"}, report


def test_a_killed_driver_takes_its_computing_worker_along():
    proc = subprocess.Popen([sys.executable, DRIVER, "orphan"], cwd=REPO,
                            stdout=subprocess.PIPE, text=True)
    pids, store = {}, None
    try:
        pids = json.loads(proc.stdout.readline())
        store = pids.pop("store")
        assert all(_running(p) for p in pids.values()), pids
        proc.send_signal(signal.SIGKILL)
        proc.wait(10)
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and any(
                _running(p) for p in pids.values()):
            time.sleep(0.1)
        assert not _running(pids["worker"]), "the worker outlived its driver"
        assert not _running(pids["child"]), "the worker's child outlived it"
        assert not _running(pids["gcs"]), (
            "the control-plane daemon outlived its driver")
    finally:
        if proc.poll() is None:
            proc.kill()
        for p in pids.values():
            try:
                os.kill(p, signal.SIGKILL)
            except OSError:
                pass
        # a killed driver cannot remove its store's directory: the test does
        if store and os.path.basename(store).startswith("tpu_air-"):
            shutil.rmtree(store, ignore_errors=True)


def test_stop_waits_for_a_slow_process_and_reaps_its_children():
    """``_stop_process`` does not give up on a process that ignores SIGTERM
    and is slow to go: it returns True, and what the process started is
    gone too."""
    import multiprocessing as mp

    from tpu_air.core import runtime

    def stubborn(q):
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        child = subprocess.Popen([sys.executable, "-c",
                                  "import time; time.sleep(600)"])
        q.put(child.pid)
        time.sleep(600)

    ctx = mp.get_context("fork")
    q = ctx.Queue()
    proc = ctx.Process(target=stubborn, args=(q,), daemon=True)
    proc.start()
    child = q.get(timeout=30)
    assert runtime._stop_process(proc, grace=0.2) is True
    assert not proc.is_alive()
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and _running(child):
        time.sleep(0.05)
    assert not _running(child)
