"""Profiling hooks (SURVEY.md §5 tracing: "per-step timing in the trainer
loop, JAX profiler hooks (xplane traces)").

* ``phase`` — a named host phase with counts, as a
  ``jax.profiler.TraceAnnotation``: the engine step, the worker's actor call
  and the train loop mark theirs, on the device trace's clock.
* ``profile_trace`` — context manager around ``jax.profiler.trace`` producing
  xplane/perfetto traces viewable in TensorBoard or ui.perfetto.dev.
"""

from __future__ import annotations

import contextlib
import sys
from typing import Any, Iterator, Optional

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
