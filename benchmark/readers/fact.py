"""A number the kind observed, as it is (times ``scale``)."""


def read(rc, key, scale=1.0):
    value = rc.facts.get(key)
    return None if value is None else float(value) * scale
