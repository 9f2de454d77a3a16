"""An amount the kind counted over the seconds of the measured window."""


def read(rc, amount, seconds="window_s"):
    n, s = rc.facts.get(amount), rc.facts.get(seconds)
    if n is None or not s:
        return None
    return float(n) / float(s)
