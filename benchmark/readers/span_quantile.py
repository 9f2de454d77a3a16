"""The ``q``-quantile of the durations of the phase ``name`` in the traced
run, times ``scale`` (1000: milliseconds)."""

from benchmark import spans, stats


def read(rc, name, q, scale=1.0):
    if rc.trace is None:
        return None
    xs = [e - s for s, e in spans.intervals(rc.trace, name)]
    if not xs:
        return None
    return stats.percentile(xs, float(q)) * scale
