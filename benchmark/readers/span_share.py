"""Share of the traced window, in percent, in which a phase ``name`` was
open: the union of its intervals, cut to the window, over the window.  For a
phase one serial loop opens (the worker's actor call) it is how busy that
loop was."""

from benchmark import spans, xplane


def read(rc, name):
    if rc.trace is None:
        return None
    xs = spans.clip(spans.intervals(rc.trace, name), rc.trace.window)
    if not xs:
        return None
    return 100.0 * xplane.total(xplane.union(xs)) / rc.trace.window_s
