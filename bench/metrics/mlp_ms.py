"""Device self time per train step, in ms, of the ops under the ``mlp``
name scope: each layer's second norm and MLP, forward, remat recompute
and backward (``train_scope``)."""

from .train_scope import read_scope


def read(run):
    return read_scope(run, "mlp")
