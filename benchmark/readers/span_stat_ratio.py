"""100 x (sum of count ``num``) / (sum of count ``den``) over the phases
``name`` of the traced run, the counts being what the program gave the phase
at entry: for ``engine.step``, live rows over rows attempted."""

from benchmark import spans


def read(rc, name, num, den):
    found = None if rc.trace is None else spans.phase_stats([name])
    counts = [c for _, _, _, c in found or [] if num in c and den in c]
    bottom = sum(c[den] for c in counts)
    if not bottom:
        return None
    return 100.0 * sum(c[num] for c in counts) / bottom
