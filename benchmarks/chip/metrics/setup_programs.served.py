"""Programs obtained in set-up, compiled or loaded from the persistent
cache, from JAX's monitoring events: the served path's shapes (one set of
gather, codec and cloud programs per refused-sample count) and the
set-up's own."""


def read(f):
    return f.setup_programs
