"""The exit gate's share of its roofline.

Roofline time: the bytes the algorithm moves (rows x classes float32
logits in, three 4-byte values per row out; the padded tile is not
counted) over the peak HBM bandwidth. Time: the device time of the
`exit_gate` program (the Pallas kernel with its padding and slicing)
from the trace's program line. Rows and time are both the traced window's.
"""
import workcount

PROGRAM = r"exit_gate"


def read(f):
    secs, calls = f.trace.time_of(PROGRAM, "module")
    if not calls or not f.peaks:
        return None
    rows = f.traced["images"]  # one gate call per batch
    bytes_ = workcount.gate_bytes(rows, f.cell.config["classes"])
    return bytes_ / f.peaks["hbm_bytes_per_s"] / secs * 100.0
