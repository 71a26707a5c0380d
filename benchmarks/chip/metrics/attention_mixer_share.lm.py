"""Share of the traced window's device busy time spent in the attention
mixers of both partitions: the device time of the operations whose JAX
name stack holds the `attention` scope (`models/transformer.py` wraps
each attention mixer in `jax.named_scope("attention")`; the stack is read
from the trace's op metadata by `xplane_ops`), over the union of all
operations' intervals."""
import xplane_ops


def read(f):
    return xplane_ops.busy_share(f, "attention")
