"""Engine: seconds of XLA backend compiles inside the window
(``jax/backend_compile_s``; persistent-cache loads are not compiles). A
window that compiles nothing reads 0.0; a program that does not count its
compiles reads nothing."""


def read(data):
    c = data.counters.get("jax/backend_compile_s")
    if not c or c.get("kind") != "counter":
        return None
    return c["value"]
