"""Multi-host runtime: jax.distributed rendezvous + per-host agents.

The reference runs real multi-node clusters (Install_locally.md:58-64;
flan-t5-batch-inference-job-setup.yml:2-3 hands the job a managed multi-node
compute config).  The TPU-native shape of that is JAX's multi-controller
SPMD: every host of a pod slice runs the SAME program; host 0 additionally
runs the user's driver code.  This module owns:

* **rendezvous** — `ensure_initialized()` joins the cluster-wide coordination
  service (`jax.distributed.initialize`) from env or explicit args; after it,
  `jax.devices()` is the GLOBAL device list and pjit programs span hosts, ICI
  collectives intra-slice and DCN across slices (SURVEY.md §2D).
* **per-host agents** — host 0 cannot call remote Python on other hosts via
  XLA; it ships *programs*.  `HostAgentServer` (driver) + `agent_loop`
  (non-zero hosts) form the control plane: cloudpickled thunks broadcast over
  a socket, executed lockstep on every host — exactly how the SPMD train step
  launches everywhere (SURVEY.md §3.6, §7 hard-part 3).
* **local emulation** — `spawn_local_cluster()` forks N processes with
  `xla_force_host_platform_device_count` CPU devices each, so multi-host
  tests run on one machine with zero TPUs (SURVEY.md §4.3's "multi-node
  without a cluster" technique).

Env contract (set by the pod launcher / job YAML):
    TPU_AIR_COORDINATOR   host:port of process 0 (jax coordination service)
    TPU_AIR_NUM_PROCESSES world size (one per host)
    TPU_AIR_PROCESS_ID    this host's rank
    TPU_AIR_CONTROL       host:port of the agent control plane (driver side)
"""

from __future__ import annotations

import multiprocessing.connection as mpc
import os
import re
import secrets
import socket
import subprocess
import sys
import threading
import time
import traceback
from typing import Any, Callable, List, Optional

def _authkey() -> bytes:
    """Per-cluster control-plane authkey.  The launcher generates a random
    key and distributes it via the job env contract (TPU_AIR_AUTHKEY); a
    compiled-in constant would be remote code execution for anyone who can
    reach a non-loopback HostAgentServer.  The static fallback only covers
    single-host loopback emulation with no launcher."""
    key = os.environ.get("TPU_AIR_AUTHKEY")
    return key.encode() if key else b"tpu_air-local-loopback"


def _routable_host(toward: Optional[str]) -> str:
    """The local address other hosts can reach us at: the source address of
    a route toward the coordinator/GCS.  Stays 127.0.0.1 in single-host
    emulation (where the coordinator itself is loopback)."""
    target = (toward or "").split(":")[0] or "127.0.0.1"
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        try:
            s.connect((target, 1))  # no packets sent; just picks a route
            return s.getsockname()[0]
        finally:
            s.close()
    except OSError:
        return "127.0.0.1"


_initialized = False
_ACTIVE_CLUSTER: Optional["LocalCluster"] = None


def set_active_cluster(cluster) -> None:
    """Register the cluster handle trainers use for SPMD-multihost fits
    (docs/MULTIHOST.md §3: leases spanning hosts run through the agent
    plane).  ``spawn_local_cluster`` registers automatically."""
    global _ACTIVE_CLUSTER
    _ACTIVE_CLUSTER = cluster


def active_cluster():
    return _ACTIVE_CLUSTER


# --------------------------------------------------------------------------
# rendezvous
# --------------------------------------------------------------------------


def ensure_initialized(
    coordinator: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Join the jax.distributed cluster if configured; returns True when this
    process is part of a multi-process run.  Idempotent.  Reads the env
    contract when args are omitted — `tpu_air.init()` calls this first so a
    job YAML env block is all a multi-host launch needs."""
    global _initialized
    if _initialized:
        return True
    coordinator = coordinator or os.environ.get("TPU_AIR_COORDINATOR")
    num_processes = num_processes or _env_int("TPU_AIR_NUM_PROCESSES")
    process_id = process_id if process_id is not None else _env_int("TPU_AIR_PROCESS_ID")
    if not coordinator or not num_processes or num_processes <= 1:
        return False
    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id or 0,
    )
    _initialized = True
    return True


def _env_int(name: str) -> Optional[int]:
    raw = os.environ.get(name)
    return int(raw) if raw else None


def process_index() -> int:
    import jax

    return jax.process_index()


def process_count() -> int:
    import jax

    return jax.process_count()


def is_coordinator() -> bool:
    return process_index() == 0


# --------------------------------------------------------------------------
# control plane: program broadcast from host 0
# --------------------------------------------------------------------------


class HostAgentServer:
    """Driver-side (host 0) control plane.

    Accepts one connection per non-zero host, then `run(fn)` broadcasts a
    cloudpickled zero-arg thunk, executes it locally too (multi-controller
    SPMD requires every process to enter the same computation), and gathers
    per-host results.  Exceptions on any host propagate with their remote
    traceback."""

    def __init__(self, num_processes: int, address: Optional[tuple] = None):
        self.num_processes = num_processes
        addr = address or ("127.0.0.1", 0)
        self._listener = mpc.Listener(addr, authkey=_authkey())
        self.address = self._listener.address
        self._conns: dict[int, Any] = {}

    def wait_for_agents(self, timeout: float = 60.0) -> None:
        deadline = time.monotonic() + timeout
        while len(self._conns) < self.num_processes - 1:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"only {len(self._conns)}/{self.num_processes - 1} host "
                    "agents connected"
                )
            conn = self._listener.accept()  # blocks; launcher enforces timeout
            pid = conn.recv()  # handshake: agent sends its process_id
            self._conns[int(pid)] = conn

    def run(self, fn: Callable[[], Any]) -> List[Any]:
        """Execute ``fn`` on every host (including this one); returns results
        ordered by process id."""
        import cloudpickle

        payload = cloudpickle.dumps(fn)
        for conn in self._conns.values():
            conn.send(("run", payload))
        local = _call_guarded(fn)
        results: dict[int, Any] = {0: local}
        for pid, conn in self._conns.items():
            results[pid] = conn.recv()
        out = []
        for pid in range(self.num_processes):
            status, value = results[pid]
            if status == "err":
                raise RuntimeError(f"host {pid} failed:\n{value}")
            out.append(value)
        return out

    def barrier(self) -> None:
        self.run(lambda: None)

    def shutdown(self) -> None:
        for conn in self._conns.values():
            try:
                conn.send(("exit", None))
                conn.close()
            except OSError:
                pass
        self._listener.close()


def _call_guarded(fn):
    try:
        return ("ok", fn())
    except BaseException:  # noqa: BLE001 - control-plane boundary
        return ("err", traceback.format_exc())


def agent_loop(control_address, process_id: int) -> None:
    """Non-zero hosts: connect to host 0 and execute broadcast programs in
    lockstep until told to exit."""
    import cloudpickle

    conn = mpc.Client(tuple(control_address) if isinstance(control_address, list)
                      else control_address, authkey=_authkey())
    conn.send(process_id)
    while True:
        kind, payload = conn.recv()
        if kind == "exit":
            return
        fn = cloudpickle.loads(payload)
        conn.send(_call_guarded(fn))


# --------------------------------------------------------------------------
# cross-host object plane
# --------------------------------------------------------------------------


class ObjectPlane:
    """Cross-host object fetch over the control plane (MULTIHOST.md §5).

    Each host serves its local ObjectStore on a socket and advertises the
    endpoint in the GCS KV (``objplane/<node_id>``); ``fetch`` resolves an
    object's holders through the GCS object directory, pulls the serialized
    value from one of them, and caches it in the local store — mirroring the
    reference stack's raylet-to-raylet transfer with its "zero copy is not
    guaranteed" cross-node caveat (Scaling_batch_inference.ipynb:cc-87-88)."""

    def __init__(self, store, node_id: str, gcs_address: str):
        from tpu_air.control import GcsClient

        self.store = store
        self.node_id = node_id
        self.gcs = GcsClient(gcs_address)
        # Advertise an address other hosts can actually reach: bind the
        # interface that routes toward the GCS (loopback only when the GCS
        # itself is loopback, i.e. single-host emulation) — advertising
        # 127.0.0.1 cluster-wide would make every remote fetch a KeyError.
        bind_host = _routable_host(gcs_address)
        self._listener = mpc.Listener((bind_host, 0), authkey=_authkey())
        host, port = self._listener.address
        self.address = f"{host}:{port}"
        self.gcs.kv_put(f"objplane/{node_id}", self.address.encode())
        self._stop = False
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._thread.start()

    # -- owner side ---------------------------------------------------------
    def put(self, value, object_id: Optional[str] = None) -> str:
        """Store locally and publish the location to the GCS directory."""
        ref = self.store.put(value, object_id)
        oid = getattr(ref, "id", object_id)
        self.gcs.publish_object(oid, self.node_id)
        return oid

    def _serve(self) -> None:
        from tpu_air.core import serialization

        # airlint: disable=CC001 — GIL-atomic stop flag; close() also
        # closes the listener, so a blocked accept() exits via OSError
        while not self._stop:
            try:
                conn = self._listener.accept()
            except OSError:
                return

            def handle(c):
                try:
                    while True:
                        object_id = c.recv()
                        if object_id is None:
                            return
                        if self.store.contains(object_id):
                            c.send(serialization.dumps(self.store.get(object_id)))
                        else:
                            c.send(None)
                except (EOFError, OSError):
                    pass
                finally:
                    c.close()

            threading.Thread(target=handle, args=(conn,), daemon=True).start()

    # -- consumer side --------------------------------------------------------
    def fetch(self, object_id: str):
        """Local hit, else pull from a holder named by the GCS directory and
        cache locally."""
        from tpu_air.core import serialization

        if self.store.contains(object_id):
            return self.store.get(object_id)
        loc = self.gcs.locate_object(object_id)
        if loc is None:
            raise KeyError(f"object {object_id} not in the cluster directory")
        last_err: Optional[Exception] = None
        for node_id in loc["node_ids"]:
            if node_id == self.node_id:
                continue
            raw = self.gcs.kv_get(f"objplane/{node_id}")
            if raw is None:
                continue
            host, port = raw.decode().rsplit(":", 1)
            try:
                conn = mpc.Client((host, int(port)), authkey=_authkey())
                conn.send(object_id)
                blob = conn.recv()
                conn.send(None)
                conn.close()
            except (OSError, EOFError) as e:  # holder died — try the next one
                last_err = e
                continue
            if blob is not None:
                value = serialization.loads(blob)
                try:  # cache for later readers on this host
                    self.store.put(value, object_id)
                    self.gcs.publish_object(object_id, self.node_id)
                except Exception:  # noqa: BLE001 — cache write is best-effort; value is in hand
                    pass
                return value
        raise KeyError(
            f"object {object_id} unreachable from {loc['node_ids']}: {last_err}"
        )

    def close(self) -> None:
        self._stop = True
        try:
            self._listener.close()
        except OSError:
            pass
        self.gcs.close()


# --------------------------------------------------------------------------
# local multi-process emulation (tests / single machine)
# --------------------------------------------------------------------------

_AGENT_MAIN = """\
import os, sys
from tpu_air.parallel import distributed as D
pid = int(os.environ["TPU_AIR_PROCESS_ID"])
gcs = os.environ.get("TPU_AIR_GCS")
if gcs:
    # register with the C++ control plane + heartbeat (failure detection)
    try:
        from tpu_air.control import GcsClient, HeartbeatThread
        ctrl = os.environ.get("TPU_AIR_CONTROL", "")
        GcsClient(gcs).register_node(f"host-{pid}", address=ctrl)
        HeartbeatThread(gcs, f"host-{pid}", interval=0.5, node_address=ctrl).start()
    except Exception as e:
        print(f"agent {pid}: gcs registration failed: {e}", file=sys.stderr)
D.ensure_initialized()
host, port = os.environ["TPU_AIR_CONTROL"].rsplit(":", 1)
D.agent_loop((host, int(port)), pid)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class LocalCluster:
    """N-process virtual cluster on one machine: process 0 is the caller's
    subprocess-free *driver script*; use `spawn_local_cluster` from a fresh
    process whose jax is not yet initialized."""

    def __init__(self, server: HostAgentServer, procs: List[subprocess.Popen],
                 gcs_proc: Optional[subprocess.Popen] = None,
                 gcs_address: Optional[str] = None,
                 heartbeat: Optional[Any] = None,
                 devices_per_process: int = 0):
        self.server = server
        self.procs = procs
        self.gcs_proc = gcs_proc
        self.gcs_address = gcs_address
        self.num_processes = server.num_processes
        self.devices_per_process = devices_per_process
        self._heartbeat = heartbeat
        self._gcs_client = None

    def run(self, fn):
        return self.server.run(fn)

    def nodes(self) -> list:
        """Cluster membership from the C++ control plane (alive = heartbeat
        fresh) — the failure-detection view.  Best-effort like the rest of
        the GCS wiring: a dead daemon degrades to []."""
        if self.gcs_address is None:
            return []
        try:
            if self._gcs_client is None:
                from tpu_air.control import GcsClient

                self._gcs_client = GcsClient(self.gcs_address)
            return self._gcs_client.list_nodes()
        except (ConnectionError, OSError, RuntimeError):
            self._gcs_client = None
            return []

    def shutdown(self):
        if active_cluster() is self:
            set_active_cluster(None)
        self.server.shutdown()
        for p in self.procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        if self._heartbeat is not None:
            self._heartbeat.stop()
        if self._gcs_client is not None:
            self._gcs_client.close()
        if self.gcs_proc is not None:
            self.gcs_proc.kill()
        # a later init() in this process must not try to join the dead daemon
        if os.environ.get("TPU_AIR_GCS") == self.gcs_address:
            os.environ.pop("TPU_AIR_GCS", None)


def spawn_local_cluster(
    num_processes: int, devices_per_process: int = 4, timeout: float = 120.0
) -> LocalCluster:
    """Start a local multi-host emulation: this process becomes host 0 of a
    ``num_processes``-process jax.distributed cluster with
    ``devices_per_process`` virtual CPU devices each; the other hosts run
    `agent_loop` in subprocesses.  Must be called before jax is imported
    (the XLA device-count flag binds at backend init)."""
    if "jax" in sys.modules and getattr(sys.modules["jax"], "_tpu_air_probe", None):
        pass  # best-effort; callers use a fresh process anyway
    coord_port = _free_port()
    coordinator = f"127.0.0.1:{coord_port}"

    # C++ control plane: membership + heartbeats for the virtual hosts.
    # Best-effort — a missing protobuf toolchain degrades to no GCS.
    gcs_proc, gcs_address = None, None
    try:
        from tpu_air.control import GcsClient, HeartbeatThread, start_gcs

        gcs_proc, gcs_port = start_gcs(dead_after_ms=3000)
        gcs_address = f"127.0.0.1:{gcs_port}"
    except Exception as e:  # noqa: BLE001 — degrade to no control plane (e.g. no protoc)
        print(f"spawn_local_cluster: no gcs ({e})", file=sys.stderr)

    # per-cluster random control-plane key (see _authkey): must land in OUR
    # env BEFORE HostAgentServer binds its listener so driver and agents agree
    os.environ.setdefault("TPU_AIR_AUTHKEY", secrets.token_hex(16))

    server = HostAgentServer(num_processes)
    host, port = server.address

    env_base = dict(os.environ)
    # strip ANY inherited device-count flag (not just the test default of 8) —
    # two conflicting flags in a child's XLA_FLAGS is an init-time error
    inherited_xla = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        env_base.get("XLA_FLAGS", ""),
    ).strip()
    env_base.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=(
            inherited_xla
            + f" --xla_force_host_platform_device_count={devices_per_process}"
        ).strip(),
        TPU_AIR_COORDINATOR=coordinator,
        TPU_AIR_NUM_PROCESSES=str(num_processes),
        TPU_AIR_CONTROL=f"{host}:{port}",
    )
    if gcs_address:
        env_base["TPU_AIR_GCS"] = gcs_address

    procs = []
    for pid in range(1, num_processes):
        env = dict(env_base)
        env["TPU_AIR_PROCESS_ID"] = str(pid)
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", _AGENT_MAIN],
                env=env,
                cwd=os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
            )
        )

    # become host 0
    os.environ.update(
        {k: env_base[k] for k in ("JAX_PLATFORMS", "XLA_FLAGS", "TPU_AIR_COORDINATOR",
                                  "TPU_AIR_NUM_PROCESSES", "TPU_AIR_CONTROL")}
    )
    os.environ["TPU_AIR_PROCESS_ID"] = "0"
    # Global chip pool for the scheduler: every virtual device is a "chip",
    # host boundaries at devices_per_process (lease shapes —
    # docs/MULTIHOST.md §2).  A later tpu_air.init() picks these up.
    os.environ["TPU_AIR_NUM_CHIPS"] = str(num_processes * devices_per_process)
    os.environ["TPU_AIR_CHIPS_PER_HOST"] = str(devices_per_process)
    heartbeat = None
    if gcs_address:
        os.environ["TPU_AIR_GCS"] = gcs_address
        try:
            GcsClient(gcs_address).register_node("host-0", address=f"{host}:{port}")
            heartbeat = HeartbeatThread(gcs_address, "host-0", interval=0.5,
                                        node_address=f"{host}:{port}")
            heartbeat.start()
        except Exception as e:  # noqa: BLE001 — liveness is optional; cluster runs without it
            print(f"spawn_local_cluster: host-0 gcs registration failed: {e}",
                  file=sys.stderr)
    ensure_initialized()

    t = threading.Thread(target=server.wait_for_agents, kwargs={"timeout": timeout})
    t.start()
    t.join(timeout)
    if t.is_alive() or len(server._conns) < num_processes - 1:
        server._listener.close()  # unblocks the accept() so the thread exits
        for p in procs:
            p.kill()
        if gcs_proc is not None:
            gcs_proc.kill()
        if heartbeat is not None:
            heartbeat.stop()
        raise TimeoutError("host agents failed to connect")
    cluster = LocalCluster(server, procs, gcs_proc, gcs_address, heartbeat,
                           devices_per_process=devices_per_process)
    set_active_cluster(cluster)
    return cluster
