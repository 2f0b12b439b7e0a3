"""Public entry of the flash-attention training kernels (GQA layout).

``interpret=None`` (default) auto-detects the backend: compiled on TPU,
interpreted elsewhere (``kernels.common``).
"""

from __future__ import annotations

from ..common import resolve_interpret
from .flash_attention import Blocks, block_sizes, flash_attention_vjp

__all__ = ["block_sizes", "flash_attention_train"]


def flash_attention_train(q, k, v, *, causal=True, window=0, scale=None,
                          block_q=None, block_k=None, interpret=None):
    """softmax(scale · q kᵀ) v, differentiable, for q, k (B, S, H or KVH,
    Dqk) and v (B, S, KVH, Dv) with H a multiple of KVH; returns (B, S, H,
    Dv). ``scale`` defaults to 1 / sqrt(Dqk).

    ``window`` > 0 also masks keys ``window`` or more positions before the
    query. ``block_q``/``block_k`` set the q and kv rows of every block,
    forward and backward; by default :func:`block_sizes` picks them. ``S``
    must be a multiple of each.
    """
    b, s, h, d = q.shape
    kvh, dv = k.shape[2], v.shape[3]
    if h % kvh:
        raise ValueError(f"{h} query heads do not share {kvh} kv heads evenly")
    if k.shape[3] != d or v.shape[2] != kvh:
        raise ValueError(f"q {q.shape}, k {k.shape} and v {v.shape} do not agree")
    fwd, bwd = block_sizes(s, d) or ((s, s), (s, s))
    if block_q or block_k:
        fwd = bwd = (block_q or fwd[0], block_k or fwd[1])
    blocks = Blocks(bool(causal), int(window), fwd, bwd, resolve_interpret(interpret),
                    float(d**-0.5 if scale is None else scale))
    if any(s % blk for blk in fwd + bwd):
        raise ValueError(f"sequence length {s} is not a multiple of the blocks {blocks}")
    out = flash_attention_vjp(q.reshape(b, s, h * d), k.reshape(b, s, kvh * d),
                              v.reshape(b, s, kvh * dv), blocks, h, kvh)
    return out.reshape(b, s, h, dv)
