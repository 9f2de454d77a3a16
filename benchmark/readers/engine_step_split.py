"""One engine decode step, split between host and device by the engine's
phases, in ms.  ``part`` is one of:

* ``host``: over consecutive ``engine.step`` phases with no ``engine.prefill``
  between them, the median of (start of the next - start of this - this
  step's ``engine.readback``): dispatch, emit and what lies between two steps
  (the step lock, the gauges, the loop's own test);
* ``idle_host``: device-0 idle time outside every ``engine.readback`` and
  ``engine.prefill`` interval, over the number of whole ``engine.step``
  phases in the window: idle the host's own work causes, a step;
* ``idle_readback``: the same inside ``engine.readback`` intervals: the
  device idle while the host already waits for it (launch and copy-back).

Idle time inside ``engine.prefill`` is in neither: a window opening is no
decode step.  The two idle parts lay the device's gaps against host phases,
so the gaps are first moved onto the host's clock by ``spans.device_lead``
(a capture stamps device events 0.45 to 2.2 ms early, another offset every
capture, which would move that much idle from one part to the other); with
no launch event to measure the lead by, they read nothing.
"""

from benchmark import spans, stats, xplane


def read(rc, part):
    if rc.trace is None:
        return None
    steps = spans.intervals(rc.trace, "engine.step")
    if not steps:
        return None
    readbacks = spans.intervals(rc.trace, "engine.readback")
    prefills = spans.intervals(rc.trace, "engine.prefill")
    if part == "host":
        xs = []
        for this, nxt in zip(steps, steps[1:]):
            if any(this[0] <= s < nxt[0] for s, _ in prefills):
                continue
            wait = xplane.total(spans.inside(readbacks, this))
            xs.append(nxt[0] - this[0] - wait)
        return stats.percentile(xs, 0.5) * 1000.0 if xs else None
    lead = spans.device_lead(rc.trace)
    if lead is None:
        return None
    whole = len(spans.inside(steps, spans.shift([rc.trace.window], lead)[0]))
    if not whole:
        return None
    gaps = spans.shift(rc.trace.gaps(), lead)
    if part == "idle_readback":
        idle = spans.covered(gaps, readbacks)
    elif part == "idle_host":
        idle = xplane.total(xplane.subtract(
            gaps, xplane.union(readbacks + prefills)))
    else:
        raise ValueError(f"part {part!r}")
    return 1000.0 * idle / whole
