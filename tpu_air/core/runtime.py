"""tpu_air core runtime: tasks, actors, objects over host processes.

This is the TPU-native counterpart of the reference stack's Ray Core layer
(raylet + GCS + core_worker, SURVEY.md §1-L1/§2B), collapsed for a single-host
control domain into one driver-side scheduler plus a pool of persistent worker
processes:

* **tasks** — stateless remote functions (``@tpu_air.remote`` on a function,
  Overview_of_Ray.ipynb:cc-41), executed on any idle worker with enough
  resources;
* **actors** — stateful remote classes (Scaling_batch_inference.ipynb:cc-105),
  each pinned to a dedicated worker process, method calls executed FIFO;
* **objects** — immutable values in the shared-memory store
  (object_store.py); every task/actor result is sealed there and resolved by
  ``get``/``wait`` exactly like ``ray.get``/``ray.wait``
  (Overview_of_Ray.ipynb:cc-44, Scaling_batch_inference.ipynb:cc-115).

Scheduling resources are **CPUs and TPU chips** (not GPUs): an actor asking
for ``num_chips=k`` receives a lease of k physical chip ids.  A chip belongs
to one process at a time, so a lease is an ownership, not an index: the
actor's worker is confined to those chips before its JAX backend starts
(``chips.py``), a worker without a lease computes on the CPU, the driver
starts no backend while chips are out on lease, and a chip returns to the
pool only when the process that held it has exited.  On a host without chips
(the virtual CPU mesh of the tests) a lease indexes the virtual devices
instead (SURVEY.md §2B raylet row: "placement = sub-mesh assignment").

Workers may themselves submit tasks / create actors (nested ``.remote``):
control messages ride the worker⇄driver pipe up to the scheduler, and results
always come back through the object store, so there is a single data plane.
"""

from __future__ import annotations

import itertools
import os
import secrets
import signal
import sys
import tempfile
import threading
import time
import traceback
import multiprocessing as mp
import multiprocessing.connection as mpc
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from . import chips, serialization
from .object_store import ObjectRef, ObjectStore, new_object_id

# airtrace propagation (stdlib-only module; the observability package pulls
# in nothing heavy at import time)
from tpu_air.faults import plan as _faults
from tpu_air.observability import tracing as _tracing
from tpu_air.observability.profiler import finish_capture as _finish_capture
from tpu_air.observability.profiler import phase as _phase

# --------------------------------------------------------------------------
# errors
# --------------------------------------------------------------------------


class TpuAirError(Exception):
    pass


class RemoteError(TpuAirError):
    """A task/actor method raised; carries the remote traceback and, when
    the failed call was traced, the trace id (``/api/traces?trace_id=...``
    answers "which hop killed this request")."""

    def __init__(self, cause_repr: str, remote_traceback: str,
                 trace_id: Optional[str] = None):
        super().__init__(f"{cause_repr}\n\n--- remote traceback ---\n{remote_traceback}")
        self.cause_repr = cause_repr
        self.remote_traceback = remote_traceback
        self.trace_id = trace_id


class ActorDiedError(TpuAirError):
    pass


class ChipLease(list):
    """A granted chip lease: a ``list`` of physical chip ids (drop-in for
    the plain ``List[int]`` existing callers index, join, and pass back to
    :meth:`Runtime.release_chips`) plus revocation plumbing for preemptible
    capacity.

    Real TPU preemption arrives with *notice*: the infrastructure says
    "these chips go away in N seconds", and a holder that drains or
    migrates within the window loses nothing.  The handle models exactly
    that: :meth:`on_revoke` registers a callback; when the lease is
    revoked (by the ``runtime.lease`` fault site's ``notice`` action or by
    :meth:`Runtime.revoke_lease`), every callback fires once with the
    advance warning in seconds, and ``notice_s`` seconds later the lease
    reports :attr:`expired` — past that point the holder must treat the
    chips as gone.

    Callbacks run on the revoker's thread and never under the handle's
    lock; a callback registered *after* the notice was delivered fires
    immediately (no lost-wakeup window between engine construction and
    watcher registration).
    """

    def __init__(self, chip_ids):
        super().__init__(chip_ids)
        self._lease_lock = threading.Lock()
        self._callbacks: List[Any] = []
        self._notice_s: Optional[float] = None
        self._expired = threading.Event()

    @property
    def chip_ids(self) -> List[int]:
        return list(self)

    @property
    def revoking(self) -> bool:
        """True once a revocation notice has been delivered."""
        with self._lease_lock:
            return self._notice_s is not None

    @property
    def notice_s(self) -> Optional[float]:
        """The advance warning the notice carried, or None if not revoked."""
        with self._lease_lock:
            return self._notice_s

    @property
    def expired(self) -> bool:
        """True once the notice window has elapsed: the chips are gone."""
        return self._expired.is_set()

    def on_revoke(self, callback) -> None:
        """Register ``callback(notice_s: float)`` to fire when this lease
        is revoked.  Fires immediately (on the caller's thread) if the
        notice already arrived."""
        with self._lease_lock:
            if self._notice_s is None:
                self._callbacks.append(callback)
                return
            notice = self._notice_s
        callback(notice)

    def deliver_notice(self, notice_s: float) -> None:
        """Deliver the revocation notice: fire callbacks with ``notice_s``
        of warning, then mark the lease expired once the window elapses.
        Idempotent — only the first delivery counts."""
        notice = max(0.0, float(notice_s))
        with self._lease_lock:
            if self._notice_s is not None:
                return
            self._notice_s = notice
            callbacks, self._callbacks = self._callbacks, []
        for cb in callbacks:
            try:
                cb(notice)
            except Exception:  # a broken callback must not mask the notice
                pass
        if notice > 0:
            t = threading.Timer(notice, self._expired.set)
            t.daemon = True
            t.start()
        else:
            self._expired.set()

    def wait_expired(self, timeout: Optional[float] = None) -> bool:
        return self._expired.wait(timeout)

    def __reduce__(self):
        # a lease crossing a process boundary (spmd closures pickled to
        # host agents) degrades to its chip ids — the revocation plumbing
        # (lock, timer, callbacks) is meaningful only in the driver that
        # holds the lease
        return (list, (list(self),))


class _ErrorSentinel:
    """Stored in the object store in place of a result when a task fails."""

    def __init__(self, cause_repr: str, tb: str, trace_id: Optional[str] = None):
        self.cause_repr = cause_repr
        self.tb = tb
        self.trace_id = trace_id

    def raise_(self):
        raise RemoteError(self.cause_repr, self.tb,
                          trace_id=getattr(self, "trace_id", None))


def _resolve_if_error(value):
    if isinstance(value, _ErrorSentinel):
        value.raise_()
    return value


# --------------------------------------------------------------------------
# specs / messages
# --------------------------------------------------------------------------

_INLINE_LIMIT = 512 * 1024  # payloads larger than this travel via the store


@dataclass
class _TaskSpec:
    task_id: str            # also the result object id
    payload: Optional[bytes]  # cloudpickle of (fn, args, kwargs); None if via store
    payload_ref: Optional[str]
    resources: Dict[str, float]
    kind: str = "task"      # "task" | "actor_create" | "actor_task"
    actor_id: Optional[str] = None
    method: Optional[str] = None
    from_worker: bool = False
    # airtrace carrier captured at submit time (None unless the submitting
    # thread had tracing on and an active span — the zero-cost-off default)
    trace_ctx: Optional[Dict[str, str]] = None


@dataclass
class _WorkerState:
    worker_id: int
    proc: mp.process.BaseProcess
    conn: mpc.Connection
    busy_task: Optional[str] = None
    actor_id: Optional[str] = None   # set => dedicated actor worker
    alive: bool = True
    # set by the worker while, told to exit, it stays to let a profiler
    # capture finish (``_stop_process``)
    finishing: Any = None


@dataclass
class _ActorState:
    actor_id: str
    worker: _WorkerState
    name: Optional[str]
    chip_ids: List[int] = field(default_factory=list)
    resources: Dict[str, float] = field(default_factory=dict)
    dead: bool = False
    pending: int = 0
    # set once the dead actor's claim is back in the pool (its process has
    # exited): what a second killer waits for
    released: threading.Event = field(default_factory=threading.Event)


# --------------------------------------------------------------------------
# worker process
# --------------------------------------------------------------------------

_worker_ctx: Optional["_WorkerContext"] = None
_sent_report: Optional[Dict[str, Any]] = None  # last device report shipped


class _WorkerContext:
    """Per-worker client handle back to the driver scheduler."""

    def __init__(self, conn: mpc.Connection, store: ObjectStore, worker_id: int):
        self.conn = conn
        self.store = store
        self.worker_id = worker_id
        self.send_lock = threading.Lock()

    def send(self, msg):
        with self.send_lock:
            self.conn.send(msg)


def current_worker() -> Optional["_WorkerContext"]:
    return _worker_ctx


def _store_result(store: ObjectStore, object_id: str, fn, args, kwargs):
    try:
        result = fn(*args, **kwargs)
        store.put(result, object_id)
        return True
    except BaseException as e:  # noqa: BLE001 - remote boundary
        store.put(
            _ErrorSentinel(repr(e), traceback.format_exc(),
                           trace_id=_tracing.current_trace_id()),
            object_id,
        )
        return False


def _send_done(worker_id: int, task_id: str, leased: bool = False) -> None:
    """Send the task-complete control message, piggybacking any spans this
    worker recorded since the last done (engine spans, nested task spans) so
    the driver's recorder sees one merged timeline — and, from a worker that
    holds a chip lease and has started its backend, what JAX sees in here
    (``chips.device_report``).  The common untraced, lease-less case ships
    the plain 3-tuple."""
    global _sent_report
    spans = _tracing.drain_if_any()
    report = chips.device_report() if leased else None
    if report == _sent_report:
        report = None  # unchanged since the last done: a serving replica's
        # polls are actor tasks, and in steady state they ship nothing extra
    else:
        _sent_report = report
    if spans is None and report is None:
        _worker_ctx.send(("done", worker_id, task_id))
    else:
        _worker_ctx.send(("done", worker_id, task_id, spans, report))


def _load_payload(store: ObjectStore, spec: dict):
    blob = spec["payload"]
    if blob is None:
        blob = store.get(spec["payload_ref"])
    return serialization.loads(blob)


def _resolve_args(store: ObjectStore, args, kwargs):
    def r(v):
        return store.get(v.id) if isinstance(v, ObjectRef) else v

    args = [r(a) for a in args]
    kwargs = {k: r(v) for k, v in kwargs.items()}
    for v in itertools.chain(args, kwargs.values()):
        _resolve_if_error(v)
    return args, kwargs


# -- no process outlives a run --------------------------------------------------
#
# Three ways a process was left behind a run that had printed its result:
# a worker whose driver was killed noticed only at its next ``conn.recv()``,
# not while it computed; a stop gave up on a process 11 s after asking, with
# the process alive (a worker holding gigabytes on a chip can take longer to
# tear down); nothing stopped what a worker itself had started.  So: a worker
# dies with its driver (`_die_with_driver`), a stop waits until the process is
# gone (`_stop_process`), and a worker's descendants go with it
# (`_kill_descendants`, from the worker on its way out and from the driver
# once the worker is gone).

_PR_SET_PDEATHSIG = 1


def _proc_stat(pid: int) -> Optional[Tuple[int, str, str]]:
    """``(parent pid, state, start time)`` of a process from ``/proc``; None:
    no such process (or no ``/proc``)."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
    except (OSError, IndexError):
        return None
    return int(fields[1]), fields[0], fields[19]


def _descendants(pid: int) -> List[Tuple[int, str]]:
    """Every live (non-zombie) process below ``pid`` as ``(pid, start
    time)``, children before grandchildren.  The start time makes a pid that
    was reused since tell itself apart."""
    children: Dict[int, List[Tuple[int, str]]] = {}
    try:
        pids = [int(p) for p in os.listdir("/proc") if p.isdigit()]
    except OSError:
        return []
    for p in pids:
        st = _proc_stat(p)
        if st is not None and st[1] != "Z":
            children.setdefault(st[0], []).append((p, st[2]))
    out, frontier = [], [pid]
    while frontier:
        nxt = [c for p in frontier for c in children.get(p, [])]
        out.extend(nxt)
        frontier = [p for p, _ in nxt]
    return out


def _kill_descendants(found: List[Tuple[int, str]], wait: float = 5.0) -> None:
    """SIGKILL the processes of a :func:`_descendants` listing that are still
    the ones listed, and wait (briefly) until none is left running."""
    def alive(p, started):
        st = _proc_stat(p)
        return st is not None and st[1] != "Z" and st[2] == started

    for p, started in found:
        if alive(p, started):
            try:
                os.kill(p, signal.SIGKILL)
            except (OSError, ProcessLookupError):
                pass
    deadline = time.monotonic() + wait
    while time.monotonic() < deadline:
        for p, _ in found:
            try:        # ours to reap if it is our child; else init's
                os.waitpid(p, os.WNOHANG)
            except (ChildProcessError, OSError):
                pass
        if not any(alive(p, s) for p, s in found):
            return
        time.sleep(0.02)


def _die_with_driver(driver_pid: int) -> None:
    """Arrange for this worker to die when its driver does, whatever it is
    doing then.  The parent-death signal where the platform has it and the
    parent is one that lives as long as the driver (the forkserver's single
    thread: the signal is tied to the THREAD that forked, and a plain fork
    may come from a thread that ends long before its process).  And, always, a
    watcher that looks for the driver's pid twice a second: it takes what the
    worker started down with it."""
    if driver_pid <= 0:
        return
    if os.getppid() != driver_pid:
        try:
            import ctypes

            ctypes.CDLL(None, use_errno=True).prctl(
                _PR_SET_PDEATHSIG, int(signal.SIGKILL), 0, 0, 0)
        except (OSError, AttributeError):
            pass
    seen = _proc_stat(driver_pid)
    if seen is None:        # no /proc here: nothing to watch by
        return
    started = seen[2]

    def watch() -> None:
        while True:
            st = _proc_stat(driver_pid)
            if st is None or st[1] == "Z" or st[2] != started:
                _kill_descendants(_descendants(os.getpid()), wait=1.0)
                os._exit(1)
            time.sleep(0.5)

    threading.Thread(target=watch, name="tpu_air-driver-watch",
                     daemon=True).start()


def _worker_main(
    worker_id: int,
    store_root: str,
    conn: mpc.Connection,
    driver_env: Optional[Dict[str, str]] = None,
    driver_pid: int = 0,
    finishing: Any = None,
):
    try:
        _die_with_driver(driver_pid)
        _worker_loop(worker_id, store_root, conn, driver_env)
        # told to exit (or the driver's end of the pipe closed): a capture a
        # thread of this worker is still writing gets its ``.xplane.pb``
        # first, and the driver waits while ``finishing`` is set
        # (``_stop_process``)
        _finish_capture(_CAPTURE_WAIT_S, finishing)
    finally:
        # whatever this worker started ends with it
        _kill_descendants(_descendants(os.getpid()), wait=2.0)


def _worker_loop(
    worker_id: int,
    store_root: str,
    conn: mpc.Connection,
    driver_env: Optional[Dict[str, str]] = None,
):
    global _worker_ctx, _runtime
    # a forked worker inherits the driver's Runtime object with none of its
    # threads; a worker is never the driver
    _runtime = None
    if driver_env:
        # apply the driver's environ as of spawn time (forkserver children
        # otherwise see the env snapshot from forkserver start) — must happen
        # before any jax backend init reads JAX_PLATFORMS/XLA_FLAGS
        for k, v in driver_env.items():
            os.environ[k] = v
        for k in list(os.environ):
            if k not in driver_env:
                os.environ.pop(k, None)
    # the tracing flag (and any installed fault plan) was read at import
    # time, which for forkserver children predates the env application
    # above — re-read both
    _tracing._sync_from_env()
    _faults._sync_from_env()
    chips.sync_jax_config_from_env()
    store = ObjectStore(store_root)
    _worker_ctx = _WorkerContext(conn, store, worker_id)
    actors: Dict[str, Any] = {}
    failed_actors: Dict[str, _ErrorSentinel] = {}
    leased = False         # this worker hosts an actor that holds chips
    pool_confined = False  # a pooled task worker was kept off the chips
    while True:
        try:
            msg = conn.recv()
        except (EOFError, OSError):
            return
        kind = msg[0]
        if kind == "shutdown":
            return
        spec = msg[1]
        if kind == "task":
            if not pool_confined:
                # pooled task workers hold no lease: keep them off the chips
                # before the first task can start a backend
                chips.confine([])
                pool_confined = True
            fn, args, kwargs = _load_payload(store, spec)
            try:
                args, kwargs = _resolve_args(store, args, kwargs)
            except RemoteError as e:
                store.put(_ErrorSentinel(repr(e), e.remote_traceback), spec["task_id"])
                _send_done(worker_id, spec["task_id"])
                continue
            name = getattr(fn, "__name__", None) or "task"
            with _tracing.task_span(f"task.{name}", spec.get("trace_ctx")) as sp:
                if not _store_result(store, spec["task_id"], fn, args, kwargs):
                    sp.set_status("error")
            _send_done(worker_id, spec["task_id"])
        elif kind == "actor_create":
            chip_ids = spec.get("chip_ids") or []
            try:
                # The lease becomes this process's whole view of the chips
                # BEFORE anything below can start a backend (unpickling the
                # class may import jax; its __init__ may compute).
                chips.confine(chip_ids)
            except chips.ChipLeaseError as e:
                failed_actors[spec["actor_id"]] = _ErrorSentinel(
                    repr(e), traceback.format_exc())
                store.put(failed_actors[spec["actor_id"]], spec["task_id"])
                _send_done(worker_id, spec["task_id"])
                continue
            if chip_ids:
                leased = True
                chips.watch_compiles()
            cls, args, kwargs = _load_payload(store, spec)
            args, kwargs = _resolve_args(store, args, kwargs)
            cname = getattr(cls, "__name__", None) or "actor"
            with _tracing.task_span(f"actor.{cname}.__init__",
                                    spec.get("trace_ctx")) as sp:
                if not _store_result(store, spec["task_id"], cls, args, kwargs):
                    sp.set_status("error")
            # fetch back so a failed __init__ is visible to callers
            inst = store.get(spec["task_id"])
            if isinstance(inst, _ErrorSentinel):
                failed_actors[spec["actor_id"]] = inst
            else:
                actors[spec["actor_id"]] = inst
            _send_done(worker_id, spec["task_id"], leased)
        elif kind == "actor_task":
            # everything one actor call costs inside this worker: payload,
            # arguments, the method, storing the result, the done message
            with _phase("worker.actor_task", method=spec["method"]):
                _actor_task(store, spec, actors.get(spec["actor_id"]),
                            failed_actors.get(spec["actor_id"]))
                _send_done(worker_id, spec["task_id"], leased)


def _actor_task(store: ObjectStore, spec: dict, inst: Any,
                init_err: Optional[_ErrorSentinel]) -> None:
    """Run one actor call and leave its result, or its error, in the store
    under the call's task id."""
    _, args, kwargs = _load_payload(store, spec)
    if inst is None:
        store.put(
            init_err
            if init_err is not None
            else _ErrorSentinel("ActorDiedError('actor failed to initialize')", ""),
            spec["task_id"],
        )
        return
    try:
        args, kwargs = _resolve_args(store, args, kwargs)
        method = getattr(inst, spec["method"])
    except RemoteError as e:
        store.put(_ErrorSentinel(repr(e), e.remote_traceback), spec["task_id"])
        return
    name = f"actor.{type(inst).__name__}.{spec['method']}"
    with _tracing.task_span(name, spec.get("trace_ctx")) as sp:
        if not _store_result(store, spec["task_id"], method, args, kwargs):
            sp.set_status("error")


# --------------------------------------------------------------------------
# driver-side runtime
# --------------------------------------------------------------------------

_STALE_SESSION_AGE_S = 2 * 3600.0


def _kill_quietly(proc) -> None:
    try:
        proc.kill()
    except (OSError, ProcessLookupError):
        pass


#: how long a stop waits for a process that was sent SIGKILL before it says
#: so: a worker tearing down gigabytes on a chip is slow, not immortal
_GONE_WAIT_S = 120.0
#: how long a worker told to exit may stay until a profiler capture a thread
#: of it is writing has its ``.xplane.pb``: a minute for the largest capture a
#: cell of the benchmark makes (PERF.md, PR 57), and half as much again
_CAPTURE_WAIT_S = 90.0


def _stop_process(proc, grace: float, finishing: Any = None) -> bool:
    """Give ``proc`` ``grace`` seconds to exit by itself (and for as long as
    it keeps ``finishing`` set, ``_CAPTURE_WAIT_S`` at most: it got the word
    and is letting a profiler capture reach its ``.xplane.pb``,
    ``profiler.finish_capture``; a worker that holds a TPU rarely exits
    without the SIGTERM, so the wait ends with the event, not with the
    process), then SIGTERM, then SIGKILL, and wait until it
    is GONE (SIGKILL again every few seconds, up to ``_GONE_WAIT_S``); then
    stop whatever it had started itself.  True once it is gone — only then
    may a chip it held go to another process.  One thread per process: the
    caller owns the reap."""
    below = _descendants(proc.pid) if proc.pid else []
    proc.join(timeout=grace)
    if finishing is not None:
        deadline = time.monotonic() + _CAPTURE_WAIT_S
        while (proc.is_alive() and finishing.is_set()
               and time.monotonic() < deadline):
            proc.join(timeout=0.2)
    if proc.is_alive():
        try:
            proc.terminate()
        except (OSError, ProcessLookupError):
            pass
        proc.join(timeout=5)
    deadline = time.monotonic() + _GONE_WAIT_S
    while proc.is_alive() and time.monotonic() < deadline:
        _kill_quietly(proc)
        proc.join(timeout=5)
    _kill_descendants(below)
    return not proc.is_alive()


def _sweep_stale_sessions(base: str, spill_base: str = "/var/tmp") -> None:
    """Remove store dirs leaked by killed sessions (tmpfs is RAM — leaks
    accumulate).  A dir is stale when untouched for _STALE_SESSION_AGE_S.
    ``spill_base`` is injectable for tests."""
    now = time.time()
    names = []
    for d in (base, spill_base):  # spill_base: spill dirs of killed sessions
        try:
            names += [(d, n) for n in os.listdir(d)]
        except OSError:
            pass
    for d, name in names:
        if not name.startswith(("tpu_air-", "tpu_air-spill-")):
            continue
        if d == spill_base and not name.startswith("tpu_air-spill-"):
            continue
        path = os.path.join(d, name)
        try:
            if name.startswith("tpu_air-spill-"):
                # a spill dir's mtime goes stale while its session still
                # runs (spills may all happen early) — it is reapable only
                # once the owning store root is gone.  The dir carries an
                # ``.owner`` marker naming the root's absolute path
                # (ObjectStore._ensure_spill_dir), so liveness is checked
                # against THAT path — a custom-base root named tpu_air-*
                # is not mistaken for dead just because it isn't under a
                # default base.  No marker (pre-marker sessions): fall back
                # to probing the default bases, and never sweep owners that
                # aren't tpu_air-* (they live somewhere we can't check).
                owner_root = None
                try:
                    with open(os.path.join(path, ".owner")) as f:
                        owner_root = f.read().strip()
                except OSError:
                    pass
                if owner_root:
                    if os.path.exists(owner_root):
                        continue
                else:
                    owner = name[len("tpu_air-spill-"):]
                    if not owner.startswith("tpu_air-"):
                        continue
                    if any(
                        os.path.exists(os.path.join(b, owner))
                        for b in ("/dev/shm", tempfile.gettempdir())
                    ):
                        continue
            if now - os.path.getmtime(path) < _STALE_SESSION_AGE_S:
                continue
            for f in os.listdir(path):
                try:
                    os.chmod(os.path.join(path, f), 0o644)
                    os.remove(os.path.join(path, f))
                except OSError:
                    pass
            os.rmdir(path)
        except OSError:
            pass


class Runtime:
    """Driver-side scheduler + control plane (the GCS/raylet analog)."""

    def __init__(
        self,
        num_cpus: Optional[int] = None,
        num_chips: Optional[int] = None,
        start_method: Optional[str] = None,
        store_root: Optional[str] = None,
    ):
        self.session_id = secrets.token_hex(8)
        base = "/dev/shm" if os.path.isdir("/dev/shm") else tempfile.gettempdir()
        _sweep_stale_sessions(base)
        self.store_root = store_root or os.path.join(base, f"tpu_air-{self.session_id}")
        self.store = ObjectStore(self.store_root, create=True)
        self.num_cpus = num_cpus if num_cpus is not None else max(2, os.cpu_count() or 2)
        if num_chips is None:
            # an explicit count wins (argument, then env); otherwise the
            # chips a backend started here would open — counted from the
            # device nodes, so no backend is left alive in the driver
            env_chips = os.environ.get("TPU_AIR_NUM_CHIPS")
            if env_chips:
                num_chips = int(env_chips)
            elif chips.accelerator_expected():
                num_chips = chips.local_chip_count()
            else:
                num_chips = 0
        self.num_chips = num_chips
        # Topology for lease SHAPES (docs/MULTIHOST.md §2): chip g lives on
        # host g // chips_per_host.  Single host (the default) degenerates to
        # chips_per_host == num_chips and the shape policy is a no-op.
        cph = int(os.environ.get("TPU_AIR_CHIPS_PER_HOST", "0") or 0)
        self.chips_per_host = cph if 0 < cph <= num_chips else (num_chips or 1)
        self.free_chips: List[int] = list(range(self.num_chips))
        self.avail = {"cpu": float(self.num_cpus), "chip": float(self.num_chips)}
        method = start_method or os.environ.get("TPU_AIR_START_METHOD", "fork")
        self.mp_ctx = mp.get_context(method)
        self._fs_ctx = None  # lazy preloaded forkserver (see _pick_ctx)
        self.lock = threading.RLock()
        self.workers: Dict[int, _WorkerState] = {}
        self.actors: Dict[str, _ActorState] = {}
        self.named_actors: Dict[str, str] = {}
        self.task_resources: Dict[str, Dict[str, float]] = {}
        self.task_worker: Dict[str, int] = {}
        # task_id -> trace id, for traced tasks only: lets worker-death
        # sentinels carry the trace id of the request they killed
        self.task_trace: Dict[str, str] = {}
        # worker_id -> what JAX saw inside that chip-leased worker (latest
        # chips.device_report, piggybacked on its done messages); outlives
        # the worker so a caller can read it after fit()/predict() returned
        self._device_reports: Dict[int, Dict[str, Any]] = {}
        self.queue: List[_TaskSpec] = []
        # Actor creations wait in their own FIFO queue for resources (chip
        # leases especially) instead of spin-waiting in the caller — an
        # oversubscribed Tune sweep queues its trials rather than timing out
        # (SURVEY.md §7 hard-part 1; Model_finetuning…ipynb:cc-53-54).
        self.actor_queue: List[dict] = []
        self.pending_actors: Dict[str, dict] = {}          # queued, not yet placed
        self.pending_actor_tasks: Dict[str, List[_TaskSpec]] = {}
        # Event-driven wait(): notified whenever a result object may have
        # been sealed (task done / worker death / driver put).
        self._obj_cv = threading.Condition()
        self._next_worker_id = itertools.count()
        self._stop = threading.Event()
        self._wakeup_r, self._wakeup_w = mp.Pipe(duplex=False)
        # Worker-process spawns (forkserver first spin-up imports jax/pandas,
        # seconds) run on a dedicated placement thread so the listener thread
        # never blocks — done/submit messages from all workers must keep
        # flowing while an actor is being placed.
        self._placement_event = threading.Event()
        self._spawn_requests = 0
        self._to_spawn: List[tuple] = []  # claimed creations awaiting spawn
        self._placement_thread = threading.Thread(
            target=self._placement_loop, daemon=True
        )
        self._placement_thread.start()
        self._listener = threading.Thread(target=self._listen, daemon=True)
        self._listener.start()
        # GCS control plane on the DEFAULT path: reference ray.init() always
        # runs GCS on the head node (SURVEY.md §3.6, Install_locally.md:58-64),
        # so single-host runs get the same membership / actor-directory /
        # liveness machinery as multi-host instead of a dark control plane.
        self.node_id = f"host-{os.environ.get('TPU_AIR_PROCESS_ID', '0')}"
        self.gcs_address: Optional[str] = None
        self._gcs_proc = None
        self._gcs_heartbeat = None
        self._gcs_client = None
        self._gcs_lock = threading.Lock()
        if os.environ.get("TPU_AIR_NO_GCS", "0") != "1":
            self._start_gcs()
        self._min_idle = min(2, self.num_cpus)
        for _ in range(self._min_idle):
            self._spawn_worker()

    # -- GCS control plane ---------------------------------------------------
    def _start_gcs(self):
        """Start (or join) the C++ control-plane daemon.  Best-effort: a
        missing protobuf toolchain degrades to ``gcs_address=None`` and every
        directory call becomes a no-op."""
        existing = os.environ.get("TPU_AIR_GCS")
        if existing:
            # multi-host member / local-cluster child: join the cluster's
            # daemon — membership/heartbeat already owned by the
            # distributed layer (spawn_local_cluster / host agents)
            self.gcs_address = existing
            return
        from tpu_air.control import client as _gcs_mod

        if os.path.exists(os.path.join(_gcs_mod._NATIVE, "tpu_air_gcs")):
            self._launch_gcs_daemon()  # binary ready: ~ms, synchronous
        else:
            # first use on a fresh checkout: build.sh (protoc + C++) can take
            # minutes — init() must not block on it; the control plane comes
            # up late and everything degrades gracefully until then
            threading.Thread(
                target=self._launch_gcs_daemon, daemon=True,
                name="tpu_air-gcs-build",
            ).start()

    def _launch_gcs_daemon(self):
        try:
            import atexit

            from tpu_air.control import HeartbeatThread, start_gcs

            proc, port = start_gcs(dead_after_ms=3000)
            if self._stop.is_set():  # runtime shut down mid-build
                proc.kill()
                return
            # airlint: disable=CC001 — builder-thread publish vs shutdown
            # read: the _stop check above plus the atexit kill below close
            # the race (worst case the daemon dies at exit, not shutdown)
            self._gcs_proc = proc
            # the daemon must not outlive this process even when an
            # exception skips shutdown(): an orphan daemon holds the
            # inherited stderr pipe open, wedging any parent reading it
            atexit.register(_kill_quietly, proc)
            # airlint: disable=CC001 — best-effort control plane: readers
            # treat a not-yet-published address as None and no-op
            self.gcs_address = f"127.0.0.1:{port}"
            self._gcs("register_node", self.node_id, address="",
                      num_chips=self.num_chips)
            # airlint: disable=CC001 — shutdown may miss a heartbeat that
            # starts mid-build; the thread is daemonic and its daemon is
            # killed at exit anyway
            self._gcs_heartbeat = HeartbeatThread(
                self.gcs_address, self.node_id, interval=0.5,
                num_chips=self.num_chips,
            )
            self._gcs_heartbeat.start()
        except Exception as e:  # noqa: BLE001 — control plane is best-effort
            print(f"tpu_air: gcs control plane unavailable: {e}", file=sys.stderr)
            self.gcs_address = None

    def _gcs(self, method: str, *args, **kwargs):
        """Resilient GCS RPC: reconnect on failure (the daemon may restart),
        never raise into the scheduler.  The client is shared across the
        listener/placement/driver threads — create/teardown under a lock so
        one thread can't close a socket another is about to use."""
        if self.gcs_address is None:
            return None
        with self._gcs_lock:
            try:
                if self._gcs_client is None:
                    from tpu_air.control import GcsClient

                    self._gcs_client = GcsClient(self.gcs_address)
                return getattr(self._gcs_client, method)(*args, **kwargs)
            except (ConnectionError, OSError, RuntimeError):
                if self._gcs_client is not None:
                    self._gcs_client.close()
                self._gcs_client = None
                return None

    def device_reports(self) -> List[Dict[str, Any]]:
        """What JAX saw inside each chip-leased worker that has started a
        backend — platform, device kind and count, its lease, its compile
        seconds and cache traffic (``chips.device_report``) — oldest worker
        first, dead workers included."""
        with self.lock:
            return [dict(r) for r in self._device_reports.values()]

    def nodes(self) -> List[Dict]:
        """Cluster membership with heartbeat liveness, from the control plane
        (``ray.nodes()`` analog).  [] when the GCS is unavailable."""
        return self._gcs("list_nodes") or []

    # -- worker management -------------------------------------------------
    def _pick_ctx(self):
        """fork is fast, but forking after a JAX/XLA backend is live in this
        process inherits dead compiler threadpools → child deadlocks on its
        first jax op.  Once a backend exists (a CPU backend: a driver that
        holds the chips is refused a lease, ``_check_satisfiable``), switch
        to a preloaded FORKSERVER: the server process imports the
        heavy module graph once (worker_preload.py — jax/pandas/numpy, no
        backend init) and children fork from it in ~10ms, vs ~3s of
        re-imports per spawn worker."""
        if self.mp_ctx.get_start_method() == "fork":
            if chips.backend_live():
                if self._fs_ctx is None:
                    # NB: the forkserver is a process-global singleton; the
                    # preload applies to any other forkserver user in this
                    # process, and if one is already running the preload is
                    # silently skipped (workers then pay the imports — slower,
                    # still correct).  Env snapshot staleness is handled by
                    # shipping the driver's current environ with each worker
                    # (_spawn_worker) and applying it in _worker_main before
                    # any backend init.
                    ctx = mp.get_context("forkserver")
                    ctx.set_forkserver_preload(["tpu_air.core.worker_preload"])
                    self._fs_ctx = ctx
                return self._fs_ctx
        return self.mp_ctx

    def _spawn_worker(self, actor_id: Optional[str] = None) -> _WorkerState:
        wid = next(self._next_worker_id)
        parent, child = mp.Pipe(duplex=True)
        ctx = self._pick_ctx()
        finishing = ctx.Event()
        # Ship the driver's CURRENT environ: forkserver children inherit the
        # env frozen at server start, so vars set since (JAX_PLATFORMS,
        # multi-host contract, …) must be re-applied in the worker before it
        # initializes any backend.
        proc = ctx.Process(
            target=_worker_main,
            args=(wid, self.store_root, child, dict(os.environ),
                  os.getpid(), finishing),
            daemon=True,
            name=f"tpu_air-worker-{wid}",
        )
        proc.start()
        child.close()
        ws = _WorkerState(worker_id=wid, proc=proc, conn=parent,
                          actor_id=actor_id, finishing=finishing)
        with self.lock:
            self.workers[wid] = ws
        self._poke_listener()
        return ws

    def _poke_listener(self):
        try:
            self._wakeup_w.send(b"x")
        except OSError:
            pass

    # -- listener thread ----------------------------------------------------
    def _listen(self):
        while not self._stop.is_set():
            with self.lock:
                conns = [w.conn for w in self.workers.values() if w.alive]
                conn_owner = {id(w.conn): w for w in self.workers.values() if w.alive}
            ready = mpc.wait(conns + [self._wakeup_r], timeout=0.2)
            for conn in ready:
                if conn is self._wakeup_r:
                    try:
                        self._wakeup_r.recv()
                    except (EOFError, OSError):
                        pass
                    continue
                owner = conn_owner.get(id(conn))
                if owner is None:
                    continue
                try:
                    msg = conn.recv()
                except (EOFError, OSError):
                    self._on_worker_death(owner)
                    continue
                try:
                    self._handle_msg(owner, msg)
                except Exception:  # noqa: BLE001 - listener must survive
                    traceback.print_exc(file=sys.stderr)

    def _handle_msg(self, worker: _WorkerState, msg):
        kind = msg[0]
        if kind == "done":
            _, wid, task_id = msg[:3]
            # traced tasks piggyback their worker-side spans on the done
            # message; fold them into the driver recorder so /api/traces
            # serves one merged timeline
            if len(msg) > 3 and msg[3]:
                _tracing.recorder().record_many(msg[3])
            with self.lock:
                if len(msg) > 4 and msg[4]:
                    self._device_reports[wid] = dict(
                        msg[4], worker_id=wid, actor_id=worker.actor_id)
                    while len(self._device_reports) > 256:
                        self._device_reports.pop(
                            next(iter(self._device_reports)))
                res = self.task_resources.pop(task_id, None)
                self.task_worker.pop(task_id, None)
                self.task_trace.pop(task_id, None)
                if res:
                    self._release(res)
                if worker.busy_task == task_id:
                    worker.busy_task = None
                st = self.actors.get(worker.actor_id) if worker.actor_id else None
                if st:
                    st.pending = max(0, st.pending - 1)
            self._notify_objects()
            self._schedule()
        elif kind == "submit":
            spec = _TaskSpec(**msg[1])
            spec.from_worker = True
            if spec.trace_ctx:
                with self.lock:
                    self.task_trace[spec.task_id] = spec.trace_ctx["trace_id"]
            self._enqueue(spec)
        elif kind == "create_actor":
            # Non-blocking: the creation queues for resources in _schedule.
            self._create_actor(**msg[1], from_worker=True)
        elif kind == "actor_call":
            spec = _TaskSpec(**msg[1])
            spec.from_worker = True
            if spec.trace_ctx:
                with self.lock:
                    self.task_trace[spec.task_id] = spec.trace_ctx["trace_id"]
            self._submit_actor_task_spec(spec)
        elif kind == "kill_actor":
            self.kill_actor(msg[1], no_restart=True)

    def _on_worker_death(self, worker: _WorkerState):
        crashed_traces = []
        with self.lock:
            worker.alive = False
            outstanding = [
                t for t, wid in self.task_worker.items() if wid == worker.worker_id
            ]
            for task_id in outstanding:
                self.task_worker.pop(task_id, None)
                res = self.task_resources.pop(task_id, None)
                if res:
                    self._release(res)
                if not self.store.contains(task_id):
                    trace_id = self.task_trace.pop(task_id, None)
                    if trace_id:
                        crashed_traces.append(trace_id)
                    self.store.put(  # airlint: disable=CC003 — chaos-only: the fault-plan delay inside put models the slow-disk stall this bounded error-sentinel write already risks under the lock; zero cost with no plan installed
                        _ErrorSentinel(
                            f"WorkerCrashed(worker={worker.worker_id})",
                            "worker process died while executing this task",
                            trace_id=trace_id,
                        ),
                        task_id,
                    )
            dead_actor = claim = st = None
            if worker.actor_id and worker.actor_id in self.actors:
                st = self.actors[worker.actor_id]
                # st.dead means kill_actor owns the claim — a killed
                # worker's pipe-close lands here too, and releasing twice
                # inflates avail until free_chips.pop underflows
                if not st.dead:
                    st.dead = True
                    dead_actor = worker.actor_id
                    if st.name:
                        self.named_actors.pop(st.name, None)
                    claim = self._take_claim(st)
            self.workers.pop(worker.worker_id, None)
        if dead_actor:
            # the pipe closes before the process is gone; the FULL claim
            # (cpu + chip) comes back once it is, exactly like kill_actor
            self._return_claim(
                claim, _stop_process(worker.proc, 2, worker.finishing), st)
            self._gcs("mark_actor_dead", dead_actor)
        # flight recorder (outside the lock: dump() scrapes snapshot()/
        # engine_stats(), which re-take it); no-op unless
        # TPU_AIR_POSTMORTEM_DIR is set, and dump() never raises
        from tpu_air.observability import postmortem as _postmortem

        if _postmortem.enabled():
            _postmortem.dump(
                f"WorkerCrashed(worker={worker.worker_id})",
                {
                    "worker_id": worker.worker_id,
                    "pid": worker.proc.pid,
                    "actor_id": worker.actor_id,
                    "busy_task": worker.busy_task,
                    "outstanding_tasks": outstanding,
                    "trace_ids": crashed_traces,
                },
            )
        self._notify_objects()
        self._schedule()

    # -- resources ----------------------------------------------------------
    def _can_fit(self, res: Dict[str, float]) -> bool:
        return all(self.avail.get(k, 0.0) >= v for k, v in res.items())

    def _claim_chips(
        self, n: int, exclude_hosts: frozenset = frozenset()
    ) -> Optional[List[int]]:
        """Topology-aware chip-lease allocation (docs/MULTIHOST.md §2).

        Shapes: a lease of ``n <= chips_per_host`` chips lives entirely on
        ONE host (best-fit: the feasible host with the fewest free chips, so
        big leases aren't starved by fragmentation); a larger lease is built
        from WHOLE free hosts (contiguous host range preferred — the induced
        mesh's collectives then ride ICI), so it is always a contiguous
        sub-slice rather than an arbitrary k-subset.  Returns None when the
        request doesn't tile the free topology right now (caller keeps it
        queued, FIFO).  ``exclude_hosts``: hosts reserved for an earlier
        shape-blocked request in the queue (see ``_claim_queued_actors``) —
        their free chips are invisible to this claim.  Caller holds the
        lock.
        """
        if n == 0:
            return []
        cph = self.chips_per_host
        by_host: Dict[int, List[int]] = {}
        for c in sorted(self.free_chips):
            if c // cph not in exclude_hosts:
                by_host.setdefault(c // cph, []).append(c)
        if n <= cph:
            fitting = [h for h, f in by_host.items() if len(f) >= n]
            if not fitting:
                return None
            host = min(fitting, key=lambda h: (len(by_host[h]), h))
            ids = by_host[host][:n]
        else:
            if n % cph != 0:
                return None
            k = n // cph
            full = sorted(h for h, f in by_host.items() if len(f) == cph)
            if len(full) < k:
                return None
            # prefer a contiguous run of k hosts; fall back to any k full
            # hosts (documented relaxation — strict contiguity could wedge
            # a sweep forever on a fragmented slice)
            chosen = None
            for i in range(len(full) - k + 1):
                if full[i + k - 1] - full[i] == k - 1:
                    chosen = full[i : i + k]
                    break
            if chosen is None:
                chosen = full[:k]
            ids = [c for h in chosen for c in by_host[h]]
        for c in ids:
            self.free_chips.remove(c)
        return ids

    @staticmethod
    def _take_claim(st: _ActorState) -> Tuple[Dict[str, float], List[int]]:
        """Detach a dying actor's claim; whoever takes it returns it with
        ``_return_claim`` after the actor's process has exited.  Caller
        holds the lock."""
        claim = (st.resources, st.chip_ids)
        st.resources, st.chip_ids = {}, []
        return claim

    def _return_claim(self, claim, process_gone: bool,
                      st: _ActorState) -> None:
        """A chip is free when the process that held it has exited, not
        before: the next holder's backend cannot open a chip that a dying
        process still has open.  A process that survived SIGKILL keeps its
        chips out of the pool."""
        resources, chip_ids = claim
        if chip_ids and not process_gone:
            print(f"tpu_air: worker holding chips {chip_ids} did not exit; "
                  "they stay out of the pool", file=sys.stderr)
            resources = {k: v for k, v in resources.items() if k != "chip"}
            chip_ids = []
        with self.lock:
            self._release(resources)
            self.free_chips.extend(chip_ids)
        st.released.set()

    def _acquire(self, res: Dict[str, float]):
        for k, v in res.items():
            self.avail[k] = self.avail.get(k, 0.0) - v

    def _release(self, res: Dict[str, float]):
        for k, v in res.items():
            self.avail[k] = self.avail.get(k, 0.0) + v

    def _reserve_closest(self, nchips: int, reserved: set) -> None:
        """Reserve the hosts a shape-blocked request is closest to
        recombining (the whole free hosts for a multi-host span; the
        freest host for a single-host lease).  Shared by the real queue
        scan and its ``_queued_reservations`` simulation.  Caller holds
        the lock; mutates ``reserved`` in place."""
        cph = self.chips_per_host
        free_by_host: Dict[int, int] = {}
        for c in self.free_chips:
            h = c // cph
            if h not in reserved:
                free_by_host[h] = free_by_host.get(h, 0) + 1
        if nchips > cph:
            need = nchips // cph
            whole = sorted(h for h, f in free_by_host.items() if f == cph)
            if whole:
                # Some whole hosts are free: reserve only those.  Partial
                # hosts stay unreserved on purpose — smaller shape-blocked
                # requests behind this head reserve them for themselves
                # (see test_lease_stress.py), which transitively protects
                # the recombination capacity without this head hoarding it.
                reserved.update(whole[:need])
            else:
                # ZERO whole hosts free: reserve the hosts with the MOST
                # free chips — the ones closest to recombining into whole
                # hosts — mirroring the single-host branch.  Without this,
                # a stream of 1-chip leases behind a shape-blocked
                # multi-host span could keep nibbling partially-free hosts
                # and no host would ever become whole (ADVICE r5
                # starvation).
                partial = sorted(
                    free_by_host, key=lambda h: (-free_by_host[h], h)
                )
                reserved.update(partial[:need])
        elif free_by_host:
            reserved.add(max(free_by_host, key=lambda h: (free_by_host[h], -h)))

    def _queued_reservations(self) -> set:
        """Hosts queued actor requests are entitled to, per the same FIFO
        scan ``_claim_queued_actors`` runs — simulated claim-free (feasible
        requests consume chips from a scratch copy of the free list;
        shape-blocked ones reserve recombination hosts; the scan stops at
        the first count-infeasible head, like the real one).  Driver-level
        ``lease_chips`` consults this so it can neither nibble capacity a
        shape-blocked queued request is waiting to recombine NOR outrace a
        feasible queue head (a simulated claim reserves its hosts whole —
        slightly broader than the claim itself, which only costs the
        driver one extra 50 ms poll).  Caller holds the lock."""
        saved = list(self.free_chips)
        avail = dict(self.avail)
        reserved: set = set()
        try:
            for rec in self.actor_queue:
                if not all(avail.get(kk, 0.0) >= vv
                           for kk, vv in rec["resources"].items()):
                    break
                nchips = int(rec["resources"].get("chip", 0))
                ids = self._claim_chips(nchips, frozenset(reserved))
                if ids is None:
                    self._reserve_closest(nchips, reserved)
                else:
                    for kk, vv in rec["resources"].items():
                        avail[kk] = avail.get(kk, 0.0) - vv
                    reserved.update(c // self.chips_per_host for c in ids)
        finally:
            self.free_chips = saved
        return reserved

    def lease_chips(self, n: int, timeout: Optional[float] = None) -> ChipLease:
        """Driver-level chip lease (shape-aware, docs/MULTIHOST.md §2) for
        runs that execute on the driver itself rather than in an actor —
        the SPMD-multihost trainer path.  Blocks until a correctly-shaped
        lease frees up, honoring the hosts reserved for queued actor
        requests (``_queued_reservations``) so driver leases cannot starve
        a shape-blocked queue head.  Returns a :class:`ChipLease` (a list
        of chip ids carrying ``on_revoke`` preemption plumbing).  Pair
        with :meth:`release_chips`."""
        self._check_satisfiable({"chip": float(n)})
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            ids = None
            with self.lock:
                if self._can_fit({"chip": float(n)}):
                    ids = self._claim_chips(
                        n, frozenset(self._queued_reservations()))
                    if ids is not None:
                        self._acquire({"chip": float(n)})
            if ids is not None:
                lease = ChipLease(ids)
                if _faults.enabled():
                    try:
                        spec = _faults.perturb("runtime.lease", key=str(n))
                    except _faults.LeaseRevokedError:
                        # the claim must not leak: hand the chips back
                        # before surfacing the revocation
                        self.release_chips(ids)
                        raise
                    if spec is not None and spec.action == "notice":
                        # graceful preemption: grant the lease, then
                        # delay_s later deliver notice_s of warning via
                        # the handle (preemption lands mid-work, not at
                        # acquisition)
                        t = threading.Timer(
                            spec.delay_s, lease.deliver_notice,
                            args=(spec.notice_s,))
                        t.daemon = True
                        t.start()
                return lease
            if deadline is not None and time.monotonic() > deadline:
                raise TimeoutError(f"no {n}-chip lease available after {timeout}s")
            time.sleep(0.05)

    def revoke_lease(self, lease: ChipLease, notice_s: float = 0.0) -> None:
        """Programmatic preemption: deliver a revocation notice to a lease
        this runtime granted.  The holder's ``on_revoke`` callbacks fire
        with ``notice_s`` of warning; the holder still calls
        :meth:`release_chips` when its drain completes (or the driver
        reclaims on expiry)."""
        lease.deliver_notice(notice_s)

    def release_chips(self, chip_ids: List[int]) -> None:
        with self.lock:
            self._release({"chip": float(len(chip_ids))})
            self.free_chips.extend(chip_ids)
        self._schedule()

    def _check_satisfiable(self, res: Dict[str, float]):
        total = {"cpu": float(self.num_cpus), "chip": float(self.num_chips)}
        for k, v in res.items():
            if v > total.get(k, 0.0):
                raise TpuAirError(
                    f"resource request {res} exceeds cluster total {total}"
                )
        nchips = int(res.get("chip", 0))
        if nchips and chips.accelerator_expected() and chips.backend_live():
            raise TpuAirError(
                "this process has started a JAX backend, so it holds the "
                "host's chips and no worker can be given one: keep JAX "
                "computation out of the driver, or run without tpu_air "
                "workers")
        if nchips > self.chips_per_host and nchips % self.chips_per_host != 0:
            raise TpuAirError(
                f"chip lease of {nchips} spans hosts and must be a multiple "
                f"of chips_per_host={self.chips_per_host} (whole-host lease "
                "shapes, docs/MULTIHOST.md)"
            )

    # -- task submission -----------------------------------------------------
    def _pack_payload(self, payload_tuple) -> Tuple[Optional[bytes], Optional[str]]:
        blob = serialization.dumps(payload_tuple)
        if len(blob) <= _INLINE_LIMIT:
            return blob, None
        ref = self.store.put(blob)
        return None, ref.id

    def submit_task(self, fn, args, kwargs, resources: Dict[str, float],
                    trace_ctx: Optional[Dict[str, str]] = None) -> ObjectRef:
        if _faults.enabled():
            _faults.perturb(
                "runtime.task", key=getattr(fn, "__name__", "") or "")
        self._check_satisfiable(resources)
        if resources.get("chip") and chips.accelerator_expected():
            raise TpuAirError(
                "a task cannot hold a chip: tasks run in pooled workers, "
                "which stay on the CPU, and a chip belongs to one process "
                "until it exits — ask for num_chips on an actor")
        task_id = new_object_id()
        payload, payload_ref = self._pack_payload((fn, args, kwargs))
        spec = _TaskSpec(task_id, payload, payload_ref, resources,
                         trace_ctx=trace_ctx)
        if trace_ctx:
            with self.lock:
                self.task_trace[task_id] = trace_ctx["trace_id"]
        self._enqueue(spec)
        return ObjectRef(task_id)

    def _enqueue(self, spec: _TaskSpec):
        with self.lock:
            self.queue.append(spec)
        self._schedule()

    def _placement_loop(self):
        """Dedicated thread for anything that spawns worker processes:
        queued-actor placement and deadlock-avoidance spawns.  Fed by
        ``_placement_event`` from ``_schedule`` (which may run on the
        listener thread and must never block on a process spawn)."""
        while not self._stop.is_set():
            self._placement_event.wait(timeout=0.2)
            if self._stop.is_set():
                return
            self._placement_event.clear()
            try:
                self._place_queued_actors()
                with self.lock:
                    n = self._spawn_requests
                    self._spawn_requests = 0
                for _ in range(n):
                    self._spawn_worker()
                if n:
                    self._schedule()  # fresh workers can take queued tasks
            except Exception:  # noqa: BLE001 - placement must survive
                traceback.print_exc(file=sys.stderr)

    def _schedule(self):
        spawn_needed = 0
        # claim actor resources FIRST (fast, synchronous) so queued tasks
        # can't outrace a queued actor lease; only the spawn is deferred
        self._claim_queued_actors()
        with self.lock:
            remaining: List[_TaskSpec] = []
            idle = [
                w
                for w in self.workers.values()
                if w.alive and w.busy_task is None and w.actor_id is None
            ]
            for spec in self.queue:
                if not idle or not self._can_fit(spec.resources):
                    remaining.append(spec)
                    continue
                worker = idle.pop()
                self._acquire(spec.resources)
                self.task_resources[spec.task_id] = spec.resources
                self.task_worker[spec.task_id] = worker.worker_id
                worker.busy_task = spec.task_id
                worker.conn.send(
                    (
                        "task",
                        {
                            "task_id": spec.task_id,
                            "payload": spec.payload,
                            "payload_ref": spec.payload_ref,
                            "trace_ctx": spec.trace_ctx,
                        },
                    )
                )
            self.queue = remaining
            stuck = [s for s in remaining if self._can_fit(s.resources)]
            if stuck and not idle:
                # Grow the pool toward num_cpus for ANY dispatchable queued
                # task: the initial pool is only min(2, num_cpus), and
                # without growth driver-submitted parallelism stays capped
                # at 2 workers regardless of num_cpus (the W9 20-parallel-
                # tasks contract needs the full width).  Workers persist
                # once spawned, so this converges after the first burst.
                pool = sum(
                    1 for w in self.workers.values()
                    if w.alive and w.actor_id is None
                )
                headroom = max(0, int(self.num_cpus) - pool)
                # Deadlock avoidance: a worker blocked on a nested task's
                # result occupies its process slot, so nested submissions
                # get fresh workers (beyond num_cpus if needed) when the
                # pool is saturated.
                nested = sum(1 for s in stuck if s.from_worker)
                # cap each spawn burst: _placement_loop re-runs _schedule
                # after the burst, so already-spawned workers start taking
                # tasks between bursts instead of idling behind a serial
                # spawn of num_cpus processes
                spawn_needed = min(len(stuck), max(headroom, nested), 4)
        if spawn_needed:
            with self.lock:
                self._spawn_requests = max(self._spawn_requests, spawn_needed)
            self._placement_event.set()

    # -- actors --------------------------------------------------------------
    def create_actor(
        self,
        cls,
        args,
        kwargs,
        resources: Dict[str, float],
        name: Optional[str] = None,
        trace_ctx: Optional[Dict[str, str]] = None,
    ) -> Tuple[str, ObjectRef]:
        actor_id = new_object_id()
        ready_id = new_object_id()
        payload, payload_ref = self._pack_payload((cls, args, kwargs))
        self._create_actor(
            actor_id=actor_id,
            ready_id=ready_id,
            payload=payload,
            payload_ref=payload_ref,
            resources=resources,
            name=name,
            trace_ctx=trace_ctx,
        )
        return actor_id, ObjectRef(ready_id)

    def _create_actor(
        self,
        actor_id: str,
        ready_id: str,
        payload,
        payload_ref,
        resources: Dict[str, float],
        name: Optional[str],
        from_worker: bool = False,
        trace_ctx: Optional[Dict[str, str]] = None,
    ):
        try:
            self._check_satisfiable(resources)
        except TpuAirError:
            if not from_worker:
                raise
            # worker-originated creation: surface the error through the ready ref
            self.store.put(
                _ErrorSentinel(f"resource request {resources} unsatisfiable", ""),
                ready_id,
            )
            self._notify_objects()
            return
        # Actors hold their resources for their whole lifetime; creation
        # QUEUES for them (FIFO) like a task rather than spin-waiting in the
        # caller — an oversubscribed sweep waits its turn instead of timing
        # out (SURVEY.md §7 hard-part 1).
        rec = {
            "actor_id": actor_id,
            "ready_id": ready_id,
            "payload": payload,
            "payload_ref": payload_ref,
            "resources": resources,
            "name": name,
            "trace_ctx": trace_ctx,
        }
        if trace_ctx:
            with self.lock:
                self.task_trace[ready_id] = trace_ctx["trace_id"]
        with self.lock:
            self.actor_queue.append(rec)
            self.pending_actors[actor_id] = rec
        self._schedule()

    def _claim_queued_actors(self):
        """FAST phase, runs synchronously inside ``_schedule`` (any thread):
        claim resources for queued actor creations that now fit, FIFO with
        one carve-out — if the head's chip COUNT doesn't fit, later (smaller)
        requests do NOT jump it (strict FIFO, so a big lease can't be starved
        by a stream of small ones), but a head whose count fits while no
        valid lease SHAPE exists (e.g. 4 chips free as 2+2 across hosts
        cannot serve a 4-chip single-host lease) is scanned PAST, so
        fragmentation cannot stall unrelated work indefinitely.

        Starvation bound for the skipped request: it RESERVES the hosts
        closest to satisfying its shape (the currently-whole free hosts for
        a multi-host span; the freest host for a single-host lease), and
        requests behind it in the queue cannot claim chips on reserved
        hosts — so a stream of small leases can consume fragments, never
        the capacity the blocked request is waiting to recombine.
        Reservations are recomputed on every pass in FIFO order, so the
        moment a feasible shape exists the blocked request (scanned first,
        with nothing reserved against it) claims before anything behind it.
        Because the claim happens before ``_schedule`` dispatches tasks, a
        stream of chip tasks cannot outrace a queued chip lease either.
        The slow process spawn is handed to the placement thread via
        ``_to_spawn``."""
        claimed = False
        with self.lock:
            reserved: set = set()
            i = 0
            while i < len(self.actor_queue):
                rec = self.actor_queue[i]
                if not self._can_fit(rec["resources"]):
                    break
                nchips = int(rec["resources"].get("chip", 0))
                chip_ids = self._claim_chips(nchips, frozenset(reserved))
                if chip_ids is None:
                    # shape-blocked: reserve the hosts this request is
                    # closest to recombining, then keep scanning
                    self._reserve_closest(nchips, reserved)
                    i += 1
                    continue
                self.actor_queue.pop(i)
                self._acquire(rec["resources"])
                self._to_spawn.append((rec, chip_ids))
                claimed = True
        if claimed:
            self._placement_event.set()

    def _place_queued_actors(self):
        """SLOW phase (placement thread only): spawn a worker process for
        each claimed creation and register the actor."""
        while True:
            with self.lock:
                if not self._to_spawn:
                    return
                rec, chip_ids = self._to_spawn.pop(0)
            try:
                worker = self._spawn_worker(actor_id=rec["actor_id"])
            except Exception as e:  # noqa: BLE001 - spawn failure (EAGAIN/OOM)
                # the claim already happened — it MUST be rolled back and the
                # ready ref resolved, or callers blocked on the actor (some
                # deliberately without timeout) hang forever on a leaked lease
                with self.lock:
                    self._release(rec["resources"])
                    self.free_chips.extend(chip_ids)
                    self.pending_actors.pop(rec["actor_id"], None)
                    buffered = self.pending_actor_tasks.pop(rec["actor_id"], [])
                sentinel = _ErrorSentinel(
                    f"ActorPlacementFailed(actor={rec['actor_id']})",
                    f"worker spawn failed: {type(e).__name__}: {e}",
                )
                # resolve the ready ref AND every method call buffered while
                # the actor was queued — a caller blocked (often without
                # timeout) on a buffered call must not hang forever
                for tid in [rec["ready_id"]] + [s.task_id for s in buffered]:
                    self.store.put(sentinel, tid)
                self._notify_objects()
                continue
            with self.lock:
                if rec.get("cancelled") or self._stop.is_set():
                    # kill_actor() cancelled this creation while we were
                    # spawning (lock released around the process spawn), or
                    # the runtime is shutting down and must not register a
                    # worker after shutdown() cleared the table — undo the
                    # placement so nothing leaks
                    self._release(rec["resources"])
                    self.free_chips.extend(chip_ids)
                    worker.alive = False
                    self.workers.pop(worker.worker_id, None)
                    try:
                        worker.conn.send(("shutdown",))
                    except OSError:
                        pass
                    continue
                actor_id, ready_id = rec["actor_id"], rec["ready_id"]
                st = _ActorState(actor_id, worker, rec["name"], chip_ids, rec["resources"])
                self.actors[actor_id] = st
                if rec["name"]:
                    self.named_actors[rec["name"]] = actor_id
                worker.busy_task = ready_id
                st.pending += 1
                self.task_resources[ready_id] = {}
                self.task_worker[ready_id] = worker.worker_id
                worker.conn.send(
                    (
                        "actor_create",
                        {
                            "task_id": ready_id,
                            "payload": rec["payload"],
                            "payload_ref": rec["payload_ref"],
                            "actor_id": actor_id,
                            "chip_ids": chip_ids,
                            "trace_ctx": rec.get("trace_ctx"),
                        },
                    )
                )
                # Flush method calls buffered while the actor was queued
                # BEFORE leaving pending state, all under the lock: a
                # concurrent direct submit must not reach the worker pipe
                # ahead of earlier buffered calls (per-caller FIFO).
                for spec in self.pending_actor_tasks.pop(actor_id, []):
                    st.pending += 1
                    self.task_resources[spec.task_id] = {}
                    self.task_worker[spec.task_id] = worker.worker_id
                    worker.conn.send(
                        (
                            "actor_task",
                            {
                                "task_id": spec.task_id,
                                "payload": spec.payload,
                                "payload_ref": spec.payload_ref,
                                "actor_id": spec.actor_id,
                                "method": spec.method,
                                "trace_ctx": spec.trace_ctx,
                            },
                        )
                    )
                self.pending_actors.pop(actor_id, None)
            # publish to the GCS actor directory (outside the lock: localhost
            # RPC, best-effort, must never stall the placement thread's lock)
            self._gcs("register_actor", actor_id, node_id=self.node_id,
                      name=rec["name"] or "", chip_ids=list(chip_ids))

    def submit_actor_task(self, actor_id, method, args, kwargs,
                          trace_ctx: Optional[Dict[str, str]] = None) -> ObjectRef:
        task_id = new_object_id()
        payload, payload_ref = self._pack_payload((None, args, kwargs))
        spec = _TaskSpec(
            task_id, payload, payload_ref, {}, kind="actor_task",
            actor_id=actor_id, method=method, trace_ctx=trace_ctx,
        )
        if trace_ctx:
            with self.lock:
                self.task_trace[task_id] = trace_ctx["trace_id"]
        self._submit_actor_task_spec(spec)
        return ObjectRef(task_id)

    def _submit_actor_task_spec(self, spec: _TaskSpec):
        with self.lock:
            if spec.actor_id in self.pending_actors:
                # actor is still queued for resources — buffer the call
                self.pending_actor_tasks.setdefault(spec.actor_id, []).append(spec)
                return
            st = self.actors.get(spec.actor_id)
            if st is None or st.dead or not st.worker.alive:
                self.store.put(  # airlint: disable=CC003 — chaos-only: the fault-plan delay inside put models the slow-disk stall this bounded error-sentinel write already risks under the lock; zero cost with no plan installed
                    _ErrorSentinel(
                        f"ActorDiedError(actor={spec.actor_id})", "",
                        trace_id=(spec.trace_ctx or {}).get("trace_id"),
                    ),
                    spec.task_id,
                )
                self._notify_objects()
                return
            st.pending += 1
            self.task_resources[spec.task_id] = {}
            self.task_worker[spec.task_id] = st.worker.worker_id
            try:
                st.worker.conn.send(
                    (
                        "actor_task",
                        {
                            "task_id": spec.task_id,
                            "payload": spec.payload,
                            "payload_ref": spec.payload_ref,
                            "actor_id": spec.actor_id,
                            "method": spec.method,
                            "trace_ctx": spec.trace_ctx,
                        },
                    )
                )
            except OSError:
                # the worker died between the liveness check and the send
                # (broken pipe before the listener reaps it) — resolve the
                # call as actor death instead of leaking an OSError into
                # the caller (serve failover keys off ActorDiedError); the
                # listener's death path does the full cleanup when it lands
                st.pending -= 1
                self.task_resources.pop(spec.task_id, None)
                self.task_worker.pop(spec.task_id, None)
                self.store.put(  # airlint: disable=CC003 — chaos-only: the fault-plan delay inside put models the slow-disk stall this bounded error-sentinel write already risks under the lock; zero cost with no plan installed
                    _ErrorSentinel(
                        f"ActorDiedError(actor={spec.actor_id})",
                        "worker pipe broken at submit",
                        trace_id=(spec.trace_ctx or {}).get("trace_id"),
                    ),
                    spec.task_id,
                )
                self._notify_objects()

    def actor_pending_placement(self, actor_id: str) -> bool:
        """True while the actor's creation is still queued for resources
        (no lease claimed yet).  Once False, the actor owns its lease and
        only construction time separates it from serving calls."""
        with self.lock:
            return any(r["actor_id"] == actor_id for r in self.actor_queue)

    def crash_actor(self, actor_id: str) -> bool:
        """Hard-kill an actor's worker process with NO bookkeeping — unlike
        :meth:`kill_actor` there is no shutdown message, no join, and no
        resource release here.  The listener thread discovers the corpse via
        pipe EOF and runs the real ``_on_worker_death`` path, which is
        exactly what fault injection needs: a crash indistinguishable from
        an involuntary one.  Returns False if the actor is unknown/dead."""
        with self.lock:
            st = self.actors.get(actor_id)
            if st is None or st.dead:
                return False
            proc = st.worker.proc
        _kill_quietly(proc)
        return True

    def kill_actor(self, actor_id: str, no_restart: bool = True):
        with self.lock:
            rec = self.pending_actors.pop(actor_id, None)
            if rec is not None:
                # Still queued (or mid-placement) — cancel.  The cancelled
                # flag covers the race where _place_queued_actors already
                # popped the record and is spawning the worker: it checks the
                # flag under the lock before registering and rolls back.
                rec["cancelled"] = True
                self.actor_queue = [r for r in self.actor_queue if r["actor_id"] != actor_id]
                buffered = self.pending_actor_tasks.pop(actor_id, [])
                for tid in [rec["ready_id"]] + [s.task_id for s in buffered]:
                    self.store.put(  # airlint: disable=CC003 — chaos-only: the fault-plan delay inside put models the slow-disk stall this bounded error-sentinel write already risks under the lock; zero cost with no plan installed
                        _ErrorSentinel(f"ActorDiedError(actor={actor_id})", ""), tid
                    )
                self._notify_objects()
                return
            st = self.actors.get(actor_id)
            if st is None:
                return
            owner = not st.dead
            if owner:
                st.dead = True
                if st.name:
                    self.named_actors.pop(st.name, None)
                claim = self._take_claim(st)
                worker = st.worker
                worker.alive = False
                self.workers.pop(worker.worker_id, None)
        if not owner:
            # double-kill / crash: someone else owns the claim and is
            # waiting for the process to exit.  Wait with them, so that for
            # every caller "killed" means "its chips are free" —
            # serve.shutdown() racing a watcher's kill must not hand the
            # next deployment a shrunken pool.
            st.released.wait(timeout=15)
            return
        self._gcs("mark_actor_dead", actor_id)
        try:
            worker.conn.send(("shutdown",))
        except OSError:
            pass
        self._return_claim(
            claim, _stop_process(worker.proc, 2, worker.finishing), st)
        self._schedule()  # freed chips/cpus may place queued actors

    # -- object plane ---------------------------------------------------------
    def _notify_objects(self):
        with self._obj_cv:
            self._obj_cv.notify_all()

    def put(self, value) -> ObjectRef:
        ref = self.store.put(value)
        self._notify_objects()
        return ref

    def get(self, ref, timeout: Optional[float] = None):
        if isinstance(ref, list):
            return [self.get(r, timeout) for r in ref]
        if not isinstance(ref, ObjectRef):
            raise TypeError(f"get() expects ObjectRef(s), got {type(ref)}")
        return _resolve_if_error(self.store.get(ref.id, timeout=timeout))

    def wait(self, refs, num_returns=1, timeout=None):
        if not isinstance(refs, list):
            raise TypeError("wait() expects a list of ObjectRefs")
        if num_returns > len(refs):
            raise ValueError("num_returns may not exceed len(refs)")
        deadline = None if timeout is None else time.monotonic() + timeout
        pending = list(refs)
        ready: List[ObjectRef] = []
        while True:
            still = []
            for r in pending:
                if self.store.contains(r.id):
                    ready.append(r)
                else:
                    still.append(r)
            pending = still
            if len(ready) >= num_returns:
                break
            if deadline is not None and time.monotonic() >= deadline:
                break
            # Event-driven: task completions / worker deaths / driver puts
            # notify _obj_cv, so the hot ray.wait load-balance loop (W7,
            # Scaling_batch_inference.ipynb:cc-115) wakes with no poll
            # latency.  The 50ms cap covers objects sealed out-of-band
            # (e.g. a worker's own store.put with no control message).
            slot = 0.05
            if deadline is not None:
                slot = min(slot, max(deadline - time.monotonic(), 0.0))
            with self._obj_cv:
                self._obj_cv.wait(timeout=slot)
        return ready, pending

    # -- lifecycle -------------------------------------------------------------
    def shutdown(self):
        self._stop.set()
        self._placement_event.set()  # wake the placement thread to exit
        self._poke_listener()
        self._listener.join(timeout=2)
        self._placement_thread.join(timeout=2)
        with self.lock:
            workers = list(self.workers.values())
            self.workers.clear()
            self.actors.clear()
        for w in workers:
            try:
                w.conn.send(("shutdown",))
            except OSError:
                pass
        for w in workers:
            _stop_process(w.proc, 1, w.finishing)
        if self._gcs_heartbeat is not None:
            self._gcs_heartbeat.stop()
        # airlint: disable=CC001 — shutdown-time teardown: _gcs() holds
        # _gcs_lock for create/use and tolerates a concurrently closed
        # client (reconnect-or-None path), so an unlocked read is safe here
        if self._gcs_client is not None:
            self._gcs_client.close()
            self._gcs_client = None
        if self._gcs_proc is not None:
            self._gcs_proc.kill()
            self._gcs_proc.wait()
            self._gcs_proc = None
        self.store.destroy()


# --------------------------------------------------------------------------
# module-level singleton API
# --------------------------------------------------------------------------

_runtime: Optional[Runtime] = None

#: where compiled programs are kept when the environment names no place: one
#: fixed, git-ignored directory in the checkout.  The path is part of the
#: cache key, so it is never a temporary name, a pid or a time.
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def place_compile_cache() -> str:
    """The program's one compile-cache rule: ``JAX_COMPILATION_CACHE_DIR``
    stays as the environment set it; unset, it becomes
    :data:`DEFAULT_COMPILE_CACHE`.  ``init()`` runs this before any worker
    is spawned, and workers inherit it through the environment they are
    handed.  Returns the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.environ["JAX_COMPILATION_CACHE_DIR"] = DEFAULT_COMPILE_CACHE
    jax = sys.modules.get("jax")
    if jax is not None:  # imported before the variable was set
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def init(
    num_cpus: Optional[int] = None,
    num_chips: Optional[int] = None,
    ignore_reinit_error: bool = True,
    include_dashboard: Optional[bool] = None,
    dashboard_port: int = 8265,
    **kwargs,
) -> Runtime:
    """Start the tpu_air runtime (the ``ray.init()`` analog,
    Install_locally.md:58-64). Idempotent by default.

    ``include_dashboard=True`` starts the status service at
    127.0.0.1:<dashboard_port> and prints the URL — the reference's
    "Follow the link … to open the Ray Dashboard" flow
    (Model_finetuning…ipynb:cc-9).  Default off (None) to keep tests quiet;
    set env TPU_AIR_DASHBOARD=1 to default on.
    """
    global _runtime
    if _runtime is not None:
        if not ignore_reinit_error:
            raise TpuAirError("tpu_air.init() called twice")
        if include_dashboard:  # honor an explicit request on reinit too
            _start_dashboard(dashboard_port)
        return _runtime
    place_compile_cache()
    # multi-host rendezvous first (no-op unless the TPU_AIR_COORDINATOR env
    # contract is set, and then a failure is the caller's to see): after
    # this, jax sees the global device list and this process knows its rank
    # (SURVEY.md §3.6 "initialize the multi-host runtime on every host")
    from tpu_air.parallel import distributed as _dist

    _dist.ensure_initialized()
    _runtime = Runtime(num_cpus=num_cpus, num_chips=num_chips, **kwargs)
    if include_dashboard is None:
        include_dashboard = os.environ.get("TPU_AIR_DASHBOARD", "0") == "1"
    if include_dashboard:
        _start_dashboard(dashboard_port)
    return _runtime


def _start_dashboard(port: int) -> None:
    try:
        from tpu_air.observability import start_dashboard

        url = start_dashboard(port=port)
        print(f"tpu_air dashboard: {url}")
    except OSError as e:
        print(f"tpu_air dashboard failed to start: {e}")


def is_initialized() -> bool:
    return _runtime is not None


def shutdown():
    global _runtime
    if _runtime is not None:
        try:
            from tpu_air.observability import stop_dashboard

            stop_dashboard()
        except Exception:  # noqa: BLE001 — shutdown is best-effort; dashboard may never have started
            pass
        _runtime.shutdown()
        _runtime = None


def get_runtime() -> Runtime:
    """Return the active runtime, auto-initializing like Ray does on first
    ``.remote()`` call."""
    if _runtime is None:
        init()
    return _runtime


def attach_chip_lease(chip_ids: Optional[List[int]] = None) -> ChipLease:
    """ACTOR-side lease attachment: wrap the chips this process was placed
    on (``TPU_AIR_CHIP_IDS``, set by the worker loop at task start, or an
    explicit ``chip_ids``) in a :class:`ChipLease` so in-actor holders —
    the serving engine, a training step — get the same ``on_revoke``
    preemption surface as driver-side :meth:`Runtime.lease_chips` holders.

    Consults the ``runtime.lease`` fault site exactly like the driver
    path, with one difference: a cold ``revoke`` here delivers an
    immediate zero-notice revocation through the handle instead of
    raising — the actor is already *placed* on the chips, so the
    interesting failure is losing them mid-work, not failing to get
    them."""
    if chip_ids is None:
        raw = os.environ.get("TPU_AIR_CHIP_IDS", "")
        chip_ids = [int(c) for c in raw.split(",") if c.strip()]
    lease = ChipLease(chip_ids)
    if _faults.enabled():
        try:
            # keyed by the PHYSICAL chip ids so a plan's ``match`` can aim
            # a preemption at the replica holding a specific chip
            spec = _faults.perturb(
                "runtime.lease",
                key="chips=" + ",".join(str(c) for c in lease),
            )
        except _faults.LeaseRevokedError:
            spec = None
            lease.deliver_notice(0.0)
        if spec is not None and spec.action == "notice":
            t = threading.Timer(spec.delay_s, lease.deliver_notice,
                                args=(spec.notice_s,))
            t.daemon = True
            t.start()
    return lease
