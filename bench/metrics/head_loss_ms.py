"""Device self time per train step, in ms, of the ops under the
``head_loss`` name scope: the final norm, the head's logits and the loss,
forward and backward (``train_scope``)."""

from .train_scope import read_scope


def read(run):
    return read_scope(run, "head_loss")
