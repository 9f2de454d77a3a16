"""From a profiler trace (``.xplane.pb``) to numbers, on
``jax.profiler.ProfileData`` and nothing else.

What is taken from a trace:

* a *device plane* is a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line
  holds one event for each operation the core ran.  There is no fall-back to
  a host plane: a trace without a device plane reduces to nothing.
* control-flow containers (``while``, ``conditional``, ``call``) span their
  bodies on the same line; they are kept apart as ``containers`` and never
  counted as work.
* busy time is the UNION of the operation intervals (nested and overlapping
  events count once); the window is first start to last end over all device
  planes; idle gaps are the complement, each named by the host event that
  overlaps it most.
* a collective's exposed part is what of its intervals no compute interval
  of the same device covers.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]  # seconds

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
CONTAINER = re.compile(r"^(while|conditional|call)([.\-_\d]|$)")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast")
# host-side events that only say "the profiler is on" name no cause
HOST_NOISE = re.compile(r"^(\$|ProfilerSession|Profiler|trace_)")


def op_name(event_name: str) -> str:
    """The operation's own name: a device event is named by its whole HLO
    line (``%fusion.3 = bf16[2048,2048]{...} fusion(...)``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The part of ``a`` (disjoint, sorted) that ``b`` (disjoint, sorted)
    does not cover."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclass
class DeviceOps:
    ops: List[Tuple[str, float, float]] = field(default_factory=list)
    containers: List[Tuple[str, float, float]] = field(default_factory=list)


@dataclass
class TraceSummary:
    """One traced window, reduced."""

    devices: Dict[int, DeviceOps]
    host: List[Tuple[str, float, float]]
    window: Interval

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy(self, dev: int) -> List[Interval]:
        return union([(s, e) for _, s, e in self.devices[dev].ops])

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        return (sum(total(self.busy(d)) for d in self.devices)
                / len(self.devices))

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def device_ops(self, top: int = 10) -> List[List]:
        """[name, seconds] of the operations that took most time, summed
        over events and averaged over devices."""
        acc: Dict[str, float] = {}
        for d in self.devices.values():
            for name, s, e in d.ops:
                acc[name] = acc.get(name, 0.0) + (e - s)
        rows = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
        return [[n, t / len(self.devices)] for n, t in rows]

    def gaps(self, dev: Optional[int] = None) -> List[Interval]:
        dev = min(self.devices) if dev is None else dev
        return subtract([self.window], self.busy(dev))

    def idle_gaps(self, top: int = 10, attribute: int = 200) -> List[List]:
        """[what the host was doing, seconds] for the idle time of the
        first device.  The ``attribute`` longest gaps are each named by the
        most specific host event (the shortest one) that covers at least
        half of the gap, else by the one overlapping it most; the rest are
        summed as ``shorter gaps``.  Grouped by name, longest total first."""
        import numpy as np

        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])
        acc: Dict[str, float] = {}
        rest = total(gaps[attribute:])
        if rest > 0:
            acc["shorter gaps"] = rest
        starts = np.array([s for _, s, _ in self.host])
        ends = np.array([e for _, _, e in self.host])
        for gs, ge in gaps[:attribute]:
            name = "untraced host time"
            if len(starts):
                overlap = np.minimum(ends, ge) - np.maximum(starts, gs)
                covering = np.flatnonzero(overlap >= 0.5 * (ge - gs))
                if len(covering):
                    i = covering[np.argmin((ends - starts)[covering])]
                    name = self.host[int(i)][0]
                elif overlap.max() >= 0.1 * (ge - gs):
                    name = self.host[int(overlap.argmax())][0]
                else:
                    name = "untraced host time"
            acc[name] = acc.get(name, 0.0) + (ge - gs)
        rows = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
        return [[n, t] for n, t in rows]

    def collective_exposed_s(self, dev: Optional[int] = None) -> Optional[float]:
        """Seconds of collective operations on ``dev`` that no compute
        operation on it covers; None when the device ran no collective."""
        dev = min(self.devices) if dev is None else dev
        ops = self.devices[dev].ops
        coll = union([(s, e) for n, s, e in ops if COLLECTIVE.search(n)])
        if not coll:
            return None
        comp = union([(s, e) for n, s, e in ops if not COLLECTIVE.search(n)])
        return total(subtract(coll, comp))

    def longest_container(self, pattern: str = "while") -> Optional[float]:
        """Duration of the longest control-flow container whose name holds
        ``pattern`` on the first device (a decode loop is one ``while``)."""
        dev = min(self.devices)
        xs = [e - s for n, s, e in self.devices[dev].containers
              if pattern in n]
        return max(xs) if xs else None


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def reduce_profile(data) -> Optional[TraceSummary]:
    """``jax.profiler.ProfileData`` → :class:`TraceSummary`; None when no
    device plane holds an operation."""
    devices: Dict[int, DeviceOps] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = DeviceOps()
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    name = op_name(ev.name)
                    rec = (name, s, s + ev.duration_ns * 1e-9)
                    (dev.containers if CONTAINER.match(name)
                     else dev.ops).append(rec)
            if dev.ops:
                devices[int(m.group(1))] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns <= 0 or HOST_NOISE.match(ev.name):
                        continue
                    s = ev.start_ns * 1e-9
                    host.append((ev.name, s, s + ev.duration_ns * 1e-9))
    if not devices:
        return None
    start = min(s for d in devices.values() for _, s, _ in d.ops)
    end = max(e for d in devices.values() for _, _, e in d.ops)
    return TraceSummary(devices, host, (start, end))


def reduce_file(path: str) -> Optional[TraceSummary]:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))


def reduce_dir(trace_dir: str) -> Optional[TraceSummary]:
    path = find_xplane(trace_dir)
    return reduce_file(path) if path else None
