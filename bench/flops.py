"""Operations and bytes of the timed work, computed from shapes.

Model FLOPs count what training requires, not what the program happens to
compute: forward and backward matrix products (backward = 2x forward),
with the embedding lookup counted as no matrix work and the recomputation
of full remat left out, plus causal attention at half the square.
"""

from __future__ import annotations

LANE = 128


def matmul_params(cfg: dict) -> int:
    """Weights that enter a matrix product once per position."""
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    hd = int(cfg.get("head_dim") or d // h)
    kvh = int(cfg["num_key_value_heads"])
    f, v = int(cfg["intermediate_size"]), int(cfg["vocab_size"])
    layer = d * h * hd + 2 * d * kvh * hd + h * hd * d + 3 * d * f
    return int(cfg["num_hidden_layers"]) * layer + d * v


def train_flops_per_position(cfg: dict, seq_len: int) -> float:
    """Forward + backward FLOPs per position of a causal sequence.

    Each weight costs 2 FLOPs (multiply, add) per position forward. Causal
    attention per layer costs 2 * S^2 * head_dim per head for QK^T and PV
    together (half of the full 4 * S^2 * head_dim), i.e. 2 * S * heads *
    head_dim per position. Backward is twice forward.
    """
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    hd = int(cfg.get("head_dim") or d // h)
    attn = int(cfg["num_hidden_layers"]) * 2 * seq_len * h * hd
    return 3.0 * (2.0 * matmul_params(cfg) + attn)


def gather_bytes(batch: int, seq_len: int) -> int:
    """HBM bytes one ``chunk_gather_train`` call must move.

    In: one lane-padded int32 slot row per output row (the index_map DMA),
    the int32 index and length tables. Out: three (B, S) grids of 4-byte
    elements (tokens, targets, loss mask).
    """
    lp = -(-(seq_len + 1) // LANE) * LANE
    return batch * lp * 4 + 2 * batch * 4 + 3 * batch * seq_len * 4
