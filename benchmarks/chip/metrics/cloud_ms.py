"""Mean wall time of one cloud-partition call, as the program's EngineStats
times it (host clock around `block_until_ready`), over the measured window."""


def read(f):
    return f.cloud_s / f.cloud_calls * 1e3 if f.cloud_calls else None
