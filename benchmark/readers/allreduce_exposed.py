"""Share of the traced window, in percent, in which device 0 ran a
collective that no compute operation on it covered."""


def read(rc):
    if rc.trace is None:
        return None
    exposed = rc.trace.collective_exposed_s()
    if exposed is None:
        return None
    return 100.0 * exposed / rc.trace.window_s
