"""Jit'd wrappers for chunk_gather / chunk_gather_train.

Both take the ``(num_slots, 1, Lp)`` slot buffer the kernels tile and
return ``(B, L)`` / ``(B, S)`` grids: the unit middle axis of the kernel
outputs is dropped here, inside the same jit.

``interpret=None`` (the default) auto-detects the backend: the kernel is
compiled on TPU and interpreted elsewhere (``kernels.common``).
"""

from __future__ import annotations

import functools

import jax

from .chunk_gather import chunk_gather as _kernel_call
from .chunk_gather import chunk_gather_train as _train_call

__all__ = ["chunk_gather", "chunk_gather_train"]


@functools.partial(jax.jit, static_argnames=("pad_id", "interpret"))
def chunk_gather(chunk_tokens, record_lens, indices, *, pad_id=0, interpret=None):
    outs = _kernel_call(
        chunk_tokens, record_lens, indices, pad_id=pad_id, interpret=interpret
    )
    return tuple(o[:, 0, :] for o in outs)


@functools.partial(jax.jit, static_argnames=("seq_len", "pad_id", "interpret"))
def chunk_gather_train(
    chunk_tokens, record_lens, indices, *, seq_len, pad_id=0, interpret=None
):
    outs = _train_call(
        chunk_tokens, record_lens, indices,
        seq_len=seq_len, pad_id=pad_id, interpret=interpret,
    )
    return tuple(o[:, 0, :] for o in outs)
