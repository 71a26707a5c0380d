"""Per batch, the time `OffloadEngine.infer` spends outside its two timed
partitions: the gate, the mask sync, the gather, the codec dispatch and
the host softmax (benchmark host clock minus EngineStats)."""


def read(f):
    if not f.batches:
        return None
    return (f.infer_s - f.edge_s - f.cloud_s) / f.batches * 1e3
