"""The ``q``-quantile of the count ``count`` over the phases ``name`` of the
traced run, times ``scale`` (0.001: a count in microseconds, read as
milliseconds).  The counts are what the program gave the phase at entry: for
``engine.first_token``, one event a request, ``queue_us`` is how long the
request waited to be admitted.  None where the capture holds no such phase,
or none that carries the count (a program older than either)."""

from benchmark import spans, stats


def read(rc, name, count, q, scale=1.0):
    found = None if rc.trace is None else spans.phase_stats([name])
    xs = [c[count] for _, _, _, c in found or [] if count in c]
    if not xs:
        return None
    return stats.percentile(xs, float(q)) * scale
