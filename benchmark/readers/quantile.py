"""The ``q``-quantile of a series the kind observed (``stats.percentile``)."""

from benchmark import stats


def read(rc, series, q, scale=1.0):
    xs = rc.facts.get(series)
    if not xs:
        return None
    return stats.percentile(xs, float(q)) * scale
