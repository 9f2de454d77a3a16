"""Profiling hooks (SURVEY.md §5 tracing: "per-step timing in the trainer
loop, JAX profiler hooks (xplane traces)").

* ``phase`` — a named host phase with counts, as a
  ``jax.profiler.TraceAnnotation``: the engine step, the worker's actor call
  and the train loop mark theirs, on the device trace's clock.
* ``finish_capture`` — what a process about to exit owes a capture that a
  thread of it is still writing out.
* ``profile_trace`` — context manager around ``jax.profiler.trace`` producing
  xplane/perfetto traces viewable in TensorBoard or ui.perfetto.dev.
"""

from __future__ import annotations

import contextlib
import glob
import os
import sys
import time
from typing import Any, Iterator, Optional, Tuple

from . import tracing as _tracing


_INERT = contextlib.nullcontext()  # reusable and re-entrant: one for all


def phase(name: str, **counts: Any):
    """A host phase on the profiler's clock: ``with phase("engine.step",
    live=37, batch=64): ...`` is a ``jax.profiler.TraceAnnotation`` — an
    event on the host thread's row of an xplane capture, its counts the
    event's stats — live exactly while a profiler session is (``--trace 1``,
    ``profile_trace``, TensorBoard's capture) and well under a microsecond
    otherwise.  It never imports JAX itself: a process that has not (the
    driver, a pooled task worker) gets a shared no-op.

    Names are lower-case ``layer.part`` (docs/OBSERVABILITY.md lists them)
    and never start with ``trace_`` or ``$``; counts are integers or short
    strings known at entry.  Phases do not go into airtrace's ring buffer."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _INERT
    return jax.profiler.TraceAnnotation(name, **counts)


def finish_capture(limit_s: float, busy=None) -> None:
    """Let a profiler capture that another thread of this process is WRITING
    get as far as its ``.xplane.pb``, for at most ``limit_s`` seconds.
    ``jax.profiler.stop_trace`` turns the device's events into that file on
    the calling thread, about 17 s and 20 µs a device operation on a v5e
    host (61 s for a 2 s capture of a 3 ms decode step, PERF.md, PR 57); a
    process that exits meanwhile takes the thread with it and the capture is
    never written.  ``stop_trace`` holds the profiler's lock for all of
    that, so the lock says whether one is running, and AFTER the file it
    keeps it for the trace viewer's ``trace.json.gz`` (minutes for the same
    capture), which nobody here reads: the wait ends when the lock is free
    or the session's directory holds a whole ``.xplane.pb``, whichever is
    first.  ``busy`` (an event) is set for as long as the wait lasts, if
    there is one.  A session nobody is stopping is not waited for, and a
    process that never imported JAX has none."""
    jax = sys.modules.get("jax")
    if jax is None:
        return
    state = getattr(getattr(getattr(jax, "_src", None), "profiler", None),
                    "_profile_state", None)
    lock = getattr(state, "lock", None)
    if lock is None:
        # a private attribute: where it has moved, say so rather than lose
        # a capture without a word
        print("tpu_air: no jax._src.profiler._profile_state.lock in jax "
              f"{getattr(jax, '__version__', '?')}: a capture being written "
              "as this process exits is lost", file=sys.stderr)
        return
    if not lock.locked():
        return
    if busy is not None:
        busy.set()
    try:
        deadline = time.monotonic() + limit_s
        while lock.locked() and time.monotonic() < deadline:
            log_dir = getattr(state, "log_dir", None)
            if log_dir and any(map(_whole_proto, glob.glob(os.path.join(
                    str(log_dir), "plugins", "profile", "*", "*.xplane.pb")))):
                return
            time.sleep(0.2)
    finally:
        if busy is not None:
            busy.clear()


def _whole_proto(path: str) -> bool:
    """Whether the protobuf message in the file at ``path`` is all there: its
    top-level fields, walked by their lengths, end exactly where the file
    does.  (The profiler writes the file in place, so one that exists may be
    a prefix of itself; an ``XSpace`` is a few planes of megabytes each.)"""

    def varint(buf: bytes, at: int) -> Tuple[int, int]:
        value = shift = 0
        while True:
            value |= (buf[at] & 0x7F) << shift
            at, shift = at + 1, shift + 7
            if not buf[at - 1] & 0x80:
                return value, at

    try:
        size, at = os.path.getsize(path), 0
        with open(path, "rb") as f:
            while at < size:
                f.seek(at)
                head = f.read(20)
                tag, n = varint(head, 0)
                if tag & 7 == 2:        # length-delimited: a plane, a string
                    length, n = varint(head, n)
                    at += n + length
                elif tag & 7 == 0:
                    at += varint(head, n)[1]
                else:
                    return False        # an XSpace has no other kind
        return size > 0 and at == size
    except (OSError, IndexError):
        return False


@contextlib.contextmanager
def profile_trace(log_dir: str, host_tracer_level: Optional[int] = None) -> Iterator[None]:
    """JAX xplane trace around a region — open the resulting directory in
    TensorBoard's profile plugin (tensorboardX is in the pinned stack,
    requirements.txt:156-equivalent).

    When tracing is enabled, the region also lands as an airtrace span whose
    ``log_dir`` attr points at the xplane dump — the trace id is the join
    key between the host-side timeline and the on-chip profile."""
    import jax

    opts = {}
    if host_tracer_level is not None:
        # jax>=0.4.x takes tracer levels via ProfileOptions, not a kwarg
        try:
            po = jax.profiler.ProfileOptions()
            po.host_tracer_level = host_tracer_level
            opts["profiler_options"] = po
        except AttributeError:  # older jax: legacy kwarg
            opts["host_tracer_level"] = host_tracer_level
    t0 = _tracing.now_ns() if _tracing.enabled() else 0
    try:
        with jax.profiler.trace(log_dir, **opts):
            yield
    finally:
        if t0:
            ctx = _tracing.current_context()
            _tracing.record_span(
                "profiler.xplane_trace",
                trace_id=ctx.trace_id if ctx else None,
                parent_id=ctx.span_id if ctx else None,
                start_ns=t0,
                end_ns=_tracing.now_ns(),
                attrs={"log_dir": log_dir},
            )
