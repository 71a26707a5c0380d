"""The uplink codec's share of its roofline on the LM payload, encode and
decode together.

Roofline time: the bytes counted from shapes (each refused sequence's
(seq_len, hidden) payload in the configuration's dtype read, packed words
and scales written, and the reverse; `workcount_lm.codec_bytes`) over the
peak HBM bandwidth. Time: the device time of the `_encode_wire` and
`_decode_wire` programs (the Pallas kernels with the casts and layout
transposes around them) from the trace's program line. Rows and time are
both the traced window's.
"""
import workcount_lm

PROGRAM = r"_(en|de)code_wire"


def read(f):
    secs, calls = f.trace.time_of(PROGRAM, "module")
    if not calls or not f.peaks or not f.traced:
        return None
    cfg = f.cell.config
    served = cfg["served"]
    n = workcount_lm.payload_elements(cfg, f.cell.traffic["seq_len"])
    bytes_ = workcount_lm.codec_bytes(f.traced["offloaded"], n, served["codec_bits"],
                                      served["codec_tile"], workcount_lm.value_bytes(cfg))
    return bytes_ / f.peaks["hbm_bytes_per_s"] / secs * 100.0
