"""Device: share of the traced window in which no leaf operation ran on
it. Container operations (a layer scan's ``while``) and waits on the host
(callbacks, host transfers) are not work and count as idle
(``trace_reduce``)."""


def read(data):
    t = data.trace
    if not t or not t["devices"] or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
