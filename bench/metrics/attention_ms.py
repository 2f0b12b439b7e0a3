"""Device self time per train step, in ms, of the ops under the
``attention`` name scope: each layer's first norm and attention block,
forward, remat recompute and backward (``train_scope``)."""

from .train_scope import read_scope


def read(run):
    return read_scope(run, "attention")
