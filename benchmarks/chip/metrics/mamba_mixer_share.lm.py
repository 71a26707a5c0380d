"""Share of the traced window's device busy time spent in the Mamba-2
mixers of both partitions: the device time of the operations whose JAX
name stack holds the `mamba` scope (`models/transformer.py` wraps each
Mamba mixer in `jax.named_scope("mamba")`; the stack is read from the
trace's op metadata by `xplane_ops`), over the union of all operations'
intervals."""
import xplane_ops


def read(f):
    return xplane_ops.busy_share(f, "mamba")
