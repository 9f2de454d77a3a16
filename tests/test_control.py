"""C++ GCS control-plane tests (SURVEY.md §2B GCS row: cluster metadata,
actor directory, node membership, heartbeat failure detection)."""

import time

import pytest

try:
    from tpu_air.control import GcsClient, HeartbeatThread, start_gcs
    _gcs_err = None
except Exception as e:  # pragma: no cover - missing protobuf toolchain
    _gcs_err = e

pytestmark = pytest.mark.skipif(
    _gcs_err is not None, reason=f"gcs unavailable: {_gcs_err}"
)


@pytest.fixture()
def gcs():
    proc, port = start_gcs(dead_after_ms=600)
    client = GcsClient(f"127.0.0.1:{port}")
    yield client, f"127.0.0.1:{port}"
    client.close()
    proc.kill()


def test_kv_roundtrip(gcs):
    client, _ = gcs
    client.kv_put("mesh/topology", b"v5e-8")
    assert client.kv_get("mesh/topology") == b"v5e-8"
    client.kv_del("mesh/topology")
    assert client.kv_get("mesh/topology") is None


def test_node_membership_and_failure_detection(gcs):
    client, addr = gcs
    client.register_node("host-0", address="127.0.0.1:9999", num_chips=4)
    client.register_node("host-1", address="127.0.0.1:9998", num_chips=4)
    hb = HeartbeatThread(addr, "host-0", interval=0.1)
    hb.start()
    time.sleep(0.9)  # host-1 never heartbeats past dead_after=600ms
    nodes = {n["node_id"]: n for n in client.list_nodes()}
    assert nodes["host-0"]["alive"] is True
    assert nodes["host-1"]["alive"] is False, "dead host not detected"
    assert nodes["host-0"]["num_chips"] == 4
    hb.stop()


def test_actor_directory(gcs):
    client, _ = gcs
    client.register_actor("a-123", node_id="host-0", name="trainer",
                          chip_ids=[0, 1])
    byname = client.lookup_actor("trainer")
    assert byname and byname["actor_id"] == "a-123" and byname["chip_ids"] == [0, 1]
    client.mark_actor_dead("a-123")
    assert client.lookup_actor("trainer") is None  # name released
    byid = client.lookup_actor("a-123")
    assert byid and byid["dead"] is True


def test_object_directory(gcs):
    client, _ = gcs
    assert client.locate_object("obj-1") is None
    client.publish_object("obj-1", "host-0", size_bytes=128)
    client.publish_object("obj-1", "host-1", size_bytes=128)
    loc = client.locate_object("obj-1")
    assert sorted(loc["node_ids"]) == ["host-0", "host-1"]


_DEFAULT_INIT_SCRIPT = """
import subprocess, time
import tpu_air
from tpu_air.control import GcsClient, start_gcs
from tpu_air.core import runtime as rt_mod

tpu_air.init(num_cpus=2, num_chips=8)
rt = rt_mod.get_runtime()
assert rt.gcs_address, "default init() did not start the GCS daemon"
nodes = {n["node_id"]: n for n in tpu_air.nodes()}
assert nodes["host-0"]["alive"] is True
assert nodes["host-0"]["num_chips"] == 8

@tpu_air.remote
class A:
    def ping(self):
        return "pong"

a = A.options(name="gcs-probe").remote()
assert tpu_air.get(a.ping.remote()) == "pong"
client = GcsClient(rt.gcs_address)
info = client.lookup_actor("gcs-probe")
assert info is not None and not info["dead"], info

# actor death reaches the directory (checked before the restart -- a
# restarted daemon forgets directory state, like a real GCS w/o persistence)
tpu_air.kill(a)
deadline = time.time() + 5
while time.time() < deadline:
    info = client.lookup_actor(a._actor_id)
    if info is not None and info["dead"]:
        break
    time.sleep(0.1)
assert info is not None and info["dead"], "actor death not in directory"
client.close()

# daemon restart on the same port: liveness machinery must recover
port = int(rt.gcs_address.rsplit(":", 1)[1])
rt._gcs_proc.kill()
rt._gcs_proc.wait()
assert tpu_air.nodes() == []  # dead daemon degrades, never raises
deadline = time.time() + 10
proc2 = None
while proc2 is None:
    try:
        proc2, _ = start_gcs(port=port)
    except RuntimeError:
        if time.time() > deadline:
            raise
        time.sleep(0.2)
rt._gcs_proc = proc2
deadline = time.time() + 10
alive = False
while time.time() < deadline and not alive:
    nodes = {n["node_id"]: n for n in tpu_air.nodes()}
    alive = nodes.get("host-0", {}).get("alive", False)
    time.sleep(0.2)
assert alive, "heartbeat did not re-register after GCS restart"
tpu_air.shutdown()
print("DEFAULT_INIT_GCS_OK")
"""


def test_gcs_on_default_init_path():
    """Single-host ``tpu_air.init()`` runs the control
    plane by default (reference: ray.init() always starts GCS, SURVEY.md
    par.3.6) -- membership observable via tpu_air.nodes(), actors appear in
    the directory, and the wiring survives a daemon restart (heartbeat
    re-registers, resilient client reconnects).  Subprocess-isolated: the
    suite's session runtime must stay untouched."""
    import os
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, "-c", _DEFAULT_INIT_SCRIPT],
        capture_output=True, text=True, timeout=180, env=dict(os.environ),
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, f"stdout={proc.stdout}\nstderr={proc.stderr}"
    assert "DEFAULT_INIT_GCS_OK" in proc.stdout



def test_concurrent_clients(gcs):
    import threading

    client, addr = gcs
    errs = []

    def worker(i):
        try:
            c = GcsClient(addr)
            for j in range(50):
                c.kv_put(f"k{i}-{j}", bytes([i, j]))
                assert c.kv_get(f"k{i}-{j}") == bytes([i, j])
            c.close()
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs, errs
