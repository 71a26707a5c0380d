"""Mean wall time of one edge-partition call, as the program's EngineStats
times it (host clock around `block_until_ready`), over the measured window."""


def read(f):
    return f.edge_s / f.edge_calls * 1e3 if f.edge_calls else None
