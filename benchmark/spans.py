"""The program's host phases (``tpu_air.observability.profiler.phase``), read
from a traced run: ``TraceAnnotation`` events on the ``/host:CPU`` plane,
which ``xplane.reduce_profile`` already keeps in ``TraceSummary.host`` as
``(name, start, end)`` on the device trace's clock.

Phases are selected by NAME, never by thread: the line of every Python thread
is called ``python3``.  A trace with no phase in it (a program older than the
phases) gives empty lists here and ``None`` from every reader.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import harness, manifest, stats, xplane
from .xplane import Interval


def intervals(trace: xplane.TraceSummary, name: str) -> List[Interval]:
    """The ``(start, end)`` of every phase called ``name``, by start."""
    return sorted((s, e) for n, s, e in trace.host if n == name)


def clip(xs: Sequence[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in xs if e > lo and s < hi]


def inside(xs: Sequence[Interval], outer: Interval) -> List[Interval]:
    """Those of ``xs`` that ``outer`` contains: a parent's children."""
    return [(s, e) for s, e in xs if outer[0] <= s and e <= outer[1]]


def covered(a: List[Interval], b: List[Interval]) -> float:
    """Seconds of ``a`` (disjoint, sorted) that ``b`` (any) covers."""
    return xplane.total(a) - xplane.total(xplane.subtract(a, xplane.union(b)))


LAUNCH = "DoEnqueueProgram"  # the TPU runtime's host event that hands the device a program
AFTER_IDLE = 2e-5            # a program start: device work after this much idle


def device_lead(trace: xplane.TraceSummary) -> Optional[float]:
    """Seconds by which the capture stamps the first device's events EARLIER
    than the host events that caused them.  The two planes' clocks are not
    aligned: on the v5e a program's first operation is stamped 0.45 to 2.2
    ms before the host event that launched it, by one offset a capture
    (PERF.md, PR 24).  Each launch is paired with the nearest start of
    device work after idle time; the lead is the median of launch minus
    start, and 0 if that is negative (a device that starts late is launch
    latency, which no capture can tell from an offset the other way).  It is
    a lower bound: the time from launch to first operation stays in it.
    None where the capture holds no launch to pair."""
    launches = sorted(s for n, s, _ in trace.host if n == LAUNCH)
    busy = trace.busy(min(trace.devices))
    starts = [b[0] for a, b in zip([(0.0, float("-inf"))] + busy, busy)
              if b[0] - a[1] >= AFTER_IDLE]
    if len(launches) < 2 or not starts:
        return None
    near = 0.5 * stats.percentile(
        [b - a for a, b in zip(launches, launches[1:])], 0.5)
    leads = []
    for at in launches:
        k = bisect.bisect_left(starts, at)
        s = min(starts[max(k - 1, 0):k + 1], key=lambda v: abs(v - at))
        if abs(s - at) < near:
            leads.append(at - s)
    return max(stats.percentile(leads, 0.5), 0.0) if leads else None


def shift(xs: Sequence[Interval], by: float) -> List[Interval]:
    return [(s + by, e + by) for s, e in xs]


def newest_xplane() -> Optional[str]:
    """The traced run's ``.xplane.pb``: the newest under the checkout's
    trace directory, whichever cell wrote it.  Readers run before
    ``harness.run_cell`` removes it."""
    found = [xplane.find_xplane(d) for d in glob.glob(
        os.path.join(manifest.REPO, harness.TRACE_DIR, "*", ""))]
    found = [p for p in found if p]
    return max(found, key=os.path.getmtime) if found else None


Counted = Tuple[str, float, float, Dict[str, Any]]


def stats_in(data, names: Sequence[str]) -> List[Counted]:
    """``(name, start, end, counts)`` of every phase of a ``ProfileData``
    called one of ``names``, by start; counts are what the program gave
    ``phase(name, **counts)``."""
    out = [(ev.name, ev.start_ns * 1e-9,
            (ev.start_ns + ev.duration_ns) * 1e-9, dict(ev.stats))
           for plane in data.planes if plane.name.startswith("/host:")
           for line in plane.lines for ev in line.events if ev.name in names]
    return sorted(out, key=lambda p: p[1])


def phase_stats(names: Sequence[str]) -> Optional[List[Counted]]:
    """:func:`stats_in` of the traced run.  ``TraceSummary`` keeps no
    stats, so this opens the file again; None when there is no file."""
    path = newest_xplane()
    if path is None:
        return None
    from jax.profiler import ProfileData

    return stats_in(ProfileData.from_file(path), names)
