"""Output tokens handed back inside the window, over the window."""


def read(data):
    n = sum(1 for r in data.all_recs for s in r.stamps
            if data.t0 <= s <= data.t1)
    return n / data.seconds
