"""Device self time per train step, in ms, of the ops under the
``optimizer`` name scope: gradient clipping and the optimizer's update
(``train_scope``)."""

from .train_scope import read_scope


def read(run):
    return read_scope(run, "optimizer")
