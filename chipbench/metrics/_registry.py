"""Means of the program's own histograms (``repro.obs``), read as sum over
count of what the window added. The histograms' quantiles are bucket edges
and are never read."""


def mean(data, name):
    h = data.counters.get(name)
    if not h or h.get("kind") != "histogram" or h["count"] <= 0:
        return None
    return h["sum"] / h["count"]
