"""The uplink codec's share of its roofline, encode and decode together.

Roofline time: the bytes counted from shapes (float32 payload read,
packed words and scales written, and the reverse) over the peak HBM
bandwidth. Time: the device time of the `_encode_wire` and `_decode_wire`
programs (the Pallas kernels with the layout transposes around them) from
the trace's program line. Rows and time are both the traced window's.
"""
import workcount

PROGRAM = r"_(en|de)code_wire"


def read(f):
    secs, calls = f.trace.time_of(PROGRAM, "module")
    if not calls or not f.peaks:
        return None
    cfg = f.cell.config
    served = cfg["served"]
    n = workcount.payload_elements(cfg, served["branch"])
    # one encode and one decode of every offloaded row; the count is linear in rows
    bytes_ = workcount.codec_bytes(f.traced["offloaded"], n, served["codec_bits"], served["codec_tile"])
    return bytes_ / f.peaks["hbm_bytes_per_s"] / secs * 100.0
