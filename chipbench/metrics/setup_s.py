"""Process start to the window's start: weights, corpus registration,
compilation (or loading from the cache), and the mix's warm-up."""


def read(data):
    return data.setup_s
