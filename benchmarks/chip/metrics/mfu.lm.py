"""The whole served LM step's share of the chip's peak: the model
operations the window's batches required (the edge partition for every
prompt, the cloud partition for every refused one, at the traffic's
sequence length, counted from the configuration's shapes by
`workcount_lm`) over the measured window's length times the bf16 peak."""
import workcount_lm


def read(f):
    if not f.peaks:
        return None
    cfg, seq = f.cell.config, f.cell.traffic["seq_len"]
    flops = (f.images * workcount_lm.edge_flops(cfg, seq)
             + f.offloaded * workcount_lm.cloud_flops(cfg, seq))
    return flops / f.window_s / f.peaks["bf16_flops_per_s"] * 100.0
