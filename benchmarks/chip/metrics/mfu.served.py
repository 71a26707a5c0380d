"""The whole served step's share of the chip's peak: the model operations
the window's batches required (edge partition for every image, cloud
partition for every offloaded one, counted from the configuration's
shapes) over the measured window's length times the bf16 peak."""
import workcount


def read(f):
    if not f.peaks:
        return None
    cfg = f.cell.config
    b = cfg["served"]["branch"]
    flops = (f.images * workcount.edge_flops(cfg, b)
             + f.offloaded * workcount.cloud_flops(cfg, b))
    return flops / f.window_s / f.peaks["bf16_flops_per_s"] * 100.0
