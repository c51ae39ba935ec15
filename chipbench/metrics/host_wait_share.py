"""Device: share of the traced window in which the device waits on the
host inside a program (host callbacks such as the program's jit-metric
``debug_callback`` sites, and host transfers). ``device_idle_share``
counts this time as idle; this metric says how much of it is such waits."""


def read(data):
    t = data.trace
    if not t or not t["devices"] or t["window_s"] <= 0 \
            or t["host_wait_s"] <= 0:
        return None
    return 100.0 * t["host_wait_s"] / t["window_s"]
