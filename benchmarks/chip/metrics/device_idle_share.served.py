"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, from the profiler trace."""


def read(f):
    t = f.trace
    return t.idle_share * 100.0 if t.devices else None
