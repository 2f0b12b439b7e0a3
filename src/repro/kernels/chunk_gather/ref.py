"""Pure-jnp oracles for chunk_gather / chunk_gather_train.

Same contract as ``ops``: a ``(num_slots, 1, Lp)`` slot buffer in,
``(B, ·)`` grids out.
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["chunk_gather_ref", "chunk_gather_train_ref"]


def chunk_gather_ref(chunk_tokens, record_lens, indices, *, pad_id=0):
    rows = chunk_tokens[indices, 0]                # (B, L)
    lens = record_lens[indices]                    # (B,)
    pos = jnp.arange(rows.shape[1])[None, :]
    valid = pos < lens[:, None]
    return jnp.where(valid, rows, pad_id), valid.astype(jnp.float32)


def chunk_gather_train_ref(chunk_tokens, record_lens, indices, *, seq_len, pad_id=0):
    rows = chunk_tokens[indices, 0]                # (B, Lp)
    lens = record_lens[indices][:, None]           # (B, 1)
    pos = jnp.arange(seq_len)[None, :]
    tokens = jnp.where(pos < lens, rows[:, :seq_len], pad_id)
    targets = jnp.where(pos + 1 < lens, rows[:, 1 : seq_len + 1], pad_id)
    mask = (pos + 1 < lens).astype(jnp.float32)
    return tokens, targets, mask
