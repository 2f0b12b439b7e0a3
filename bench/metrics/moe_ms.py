"""Device self time per train step, in ms, of the ops under the ``moe``
name scope: each MoE layer's second norm, routing, dispatch, the held
experts' grouped matmuls (the nested ``experts`` scope, ``moe_scope``)
and the shared experts, forward, remat recompute and backward."""

from .moe_scope import read_ms


def read(run):
    return read_ms(run, ("moe", "experts"))
