"""Programs obtained inside the window, compiled or loaded from the
persistent cache, from JAX's monitoring events: a shape that warm-up
did not cover."""


def read(f):
    p = f.window_programs
    return p["compilations"] + p["cache_hits"]
