"""Operations of the held experts' grouped matmuls, from the pairs counted.

One (token, expert) pair costs three matrix products of d x f (gate, up,
down), 2 FLOPs per multiply-add: 6·d·f forward. Under full remat the step
runs the forward, its recomputation in the backward pass and the backward
at twice the forward: 4 x 6·d·f = 8 · 3 · d · f per pair. The pairs are
the program's ``moe.expert_pairs`` counter, each step's sum over its MoE
layers; d and f are the widths of the expert weights of the train step
the program noted.
"""

from __future__ import annotations


def expert_matmul_flops(d: int, f: int, pairs: float) -> float:
    """FLOPs the device does in the held experts' matmuls for ``pairs``
    (token, expert) pairs: forward, its remat recompute and backward."""
    return 8.0 * 3.0 * d * f * pairs


def expert_widths(state) -> "tuple[int, int] | None":
    """(d, f) of the train state's routed-expert gate weights (a leaf
    ``moe/wi_gate`` of shape (..., experts, d, f)), or None where the state
    holds no routed experts."""
    import jax

    leaves = jax.tree_util.tree_flatten_with_path(state)[0]
    for path, leaf in leaves:
        keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        if keys[-2:] == ["moe", "wi_gate"] and "values" in keys:
            return int(leaf.shape[-2]), int(leaf.shape[-1])
    return None
