"""chunk_gather: device-side redirected batch assembly (the paper's
technique as a Pallas kernel; DESIGN.md §2 "Where a Pallas kernel is
warranted", §12 "Device-resident data path").

Redox's host protocol batches whole chunks into memory and *redirects* each
framework request to whatever record currently occupies the target slot.
On TPU the analogous hot loop is assembling the device batch: a chunk
buffer lands in HBM as one contiguous DMA (the batched read), and the
per-step redirection table picks `B` variable-length records to form the
padded (B, L) token grid + loss mask.

The kernel streams one output row per grid step: the redirection index is
a scalar-prefetch operand (known before the body runs), so the BlockSpec
index_map selects which chunk-slot row to DMA into VMEM — the gather
happens in the *data movement*, not in compute. Lengths produce the mask.

Two entry points:

* :func:`chunk_gather` — the raw gather: (tokens, mask) grids, the unit
  the parity suite sweeps.
* :func:`chunk_gather_train` — the fused training-batch assembly used by
  the :class:`~repro.core.device.DeviceStager`: one slot-row DMA yields
  the shifted ``tokens``/``targets`` pair *and* the target-aligned loss
  mask in a single pass, so the host ships one int32 slot buffer instead
  of three pre-assembled grids (~1/3 of the H2D bytes) and the grid
  assembly runs on-device, overlapped with the previous train step.

Layout for real TPUs: Mosaic tiles the last two dims of every block by
(8, 128) unless a block spans the whole dim, so a ``(1, L)`` row block of a
``(num_slots, L)`` buffer is refused. Every slot buffer and output grid
therefore carries a unit middle axis — ``(num_slots, 1, Lp)`` in,
``(B, 1, S)`` out, blocks ``(None, 1, ·)`` — and the jitted ``ops`` wrappers
drop it from the outputs. Slot rows are padded to the 128-lane width by the
host packer (``row_pad``); the slot row arrives VMEM-resident; the scalar
redirection/length tables live in SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import resolve_interpret

__all__ = ["chunk_gather", "chunk_gather_train"]


def _kernel(idx_ref, len_ref, chunk_ref, tok_ref, mask_ref, *, pad_id):
    # chunk_ref block == the slot row selected by the index_map via the
    # scalar-prefetched redirection table; body only pads + masks.
    row = chunk_ref[...]  # (1, L)
    i = pl.program_id(0)
    n = len_ref[idx_ref[i]]
    pos = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    valid = pos < n
    tok_ref[...] = jnp.where(valid, row, pad_id)
    mask_ref[...] = valid.astype(mask_ref.dtype)


def chunk_gather(
    chunk_tokens: jax.Array,  # (num_slots, 1, L) int32, slot-padded records
    record_lens: jax.Array,   # (num_slots,) int32
    indices: jax.Array,       # (B,) int32 — the redirection table
    *,
    pad_id: int = 0,
    interpret: "bool | None" = None,
):
    """Returns (tokens (B, 1, L) int32, mask (B, 1, L) float32)."""
    num_slots, _, l = chunk_tokens.shape
    b = indices.shape[0]
    kernel = functools.partial(_kernel, pad_id=pad_id)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # indices, record_lens
        grid=(b,),
        in_specs=[
            pl.BlockSpec((None, 1, l), lambda i, idx, lens: (idx[i], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, l), lambda i, idx, lens: (i, 0, 0)),
            pl.BlockSpec((None, 1, l), lambda i, idx, lens: (i, 0, 0)),
        ],
    )
    tokens, mask = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, l), jnp.int32),
            jax.ShapeDtypeStruct((b, 1, l), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(indices, record_lens, chunk_tokens)
    return tokens, mask


def _train_kernel(
    idx_ref, len_ref, chunk_ref, tok_ref, tgt_ref, mask_ref, *, seq_len, pad_id
):
    # One slot-row DMA per grid step (index_map gather, as above); the body
    # fuses the next-token shift with the length mask: tokens = row[:S],
    # targets = row[1:S+1], loss over targets where the *target* position is
    # still inside the record.
    row = chunk_ref[...]  # (1, Lp) — lane-padded slot row, Lp >= seq_len + 1
    i = pl.program_id(0)
    n = len_ref[idx_ref[i]]
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, seq_len), 1)
    tok = row[:, :seq_len]
    tgt = row[:, 1 : seq_len + 1]
    tok_ref[...] = jnp.where(pos < n, tok, pad_id)
    tgt_ref[...] = jnp.where(pos + 1 < n, tgt, pad_id)
    mask_ref[...] = (pos + 1 < n).astype(mask_ref.dtype)


def chunk_gather_train(
    chunk_tokens: jax.Array,  # (num_slots, 1, Lp) int32, slot-padded records
    record_lens: jax.Array,   # (num_slots,) int32, clipped to seq_len + 1
    indices: jax.Array,       # (B,) int32 — the redirection table
    *,
    seq_len: int,
    pad_id: int = 0,
    interpret: "bool | None" = None,
):
    """Fused redirected-gather + shift + mask: the (B, S) training triple.

    Returns ``(tokens, targets, loss_mask)``, each ``(B, 1, S)`` (int32,
    int32, float32) — exactly what ``RedoxLoader._assemble`` builds on the
    host, produced on-device from one slot buffer.
    """
    num_slots, _, lp = chunk_tokens.shape
    assert lp >= seq_len + 1, (lp, seq_len)
    b = indices.shape[0]
    kernel = functools.partial(_train_kernel, seq_len=seq_len, pad_id=pad_id)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # indices, record_lens
        grid=(b,),
        in_specs=[
            pl.BlockSpec((None, 1, lp), lambda i, idx, lens: (idx[i], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, seq_len), lambda i, idx, lens: (i, 0, 0)),
            pl.BlockSpec((None, 1, seq_len), lambda i, idx, lens: (i, 0, 0)),
            pl.BlockSpec((None, 1, seq_len), lambda i, idx, lens: (i, 0, 0)),
        ],
    )
    tokens, targets, mask = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, seq_len), jnp.int32),
            jax.ShapeDtypeStruct((b, 1, seq_len), jnp.int32),
            jax.ShapeDtypeStruct((b, 1, seq_len), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(indices, record_lens, chunk_tokens)
    return tokens, targets, mask
