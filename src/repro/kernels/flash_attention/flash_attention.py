"""Flash attention for training, Pallas/TPU: a forward and a backward
kernel (FlashAttention-2 [arXiv:2307.08691], adapted to the TPU grid model).

Layout: q is (B, S, H*Dqk), k is (B, S, KVH*Dqk) and v is (B, S, KVH*Dv),
the projections' own layout, so no transpose is made around the kernels.
A block is one head's (rows, D) slice, picked by its block index along the
last axis, so Dqk and Dv are multiples of 128 on the chip; they may differ
(latent attention's 128 + 64 q/k lanes, zero-padded to 256, over 128-wide
values), and the softmax scale is the caller's. GQA is native: query head ``h`` reads kv head
``h // (H // KVH)`` through the index map, and the backward kernel sums a
group's query heads into its kv head's gradient block, so k and v are never
repeated in HBM.

Schedule: the (q block, kv block) pairs that the causal and window mask
leave visible are listed at trace time and handed to the kernels as
scalar-prefetch tables, which the last grid axis walks. A block wholly
masked is neither fetched nor computed, and only the blocks that cross the
mask's edge build and apply the mask (``pl.when``). The online-softmax
state (row max, row sum, accumulator) lives in VMEM scratch across the kv
blocks of one q block.

Numerics: q, k, v, P and dS enter the MXU in their own dtype (bfloat16 in
training) with float32 accumulation; the softmax statistics, the saved
per-row log-sum-exp and every accumulator are float32.

Kernels, by ``pallas_call`` name as the device trace shows them (the
instruction adds the transformations around it, ``jvp_flash_attention_fwd_``):
``flash_attention_fwd`` (output and per-row log-sum-exp; q block outer, kv
blocks inner) and ``flash_attention_bwd`` (dq, dk and dv in one pass: kv
block outer, the group's query heads and q blocks inner; dk and dv
accumulate per kv block, dq for the whole sequence of the group's heads in
VMEM, written once per batch row and kv head).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["Blocks", "block_sizes", "flash_attention_vjp"]

NEG_INF = -1e30
LANES = 128
# schedule flags, one int32 per visited block pair
_FIRST, _LAST, _MASKED = 1, 2, 4
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b
_VMEM_LIMIT = 64 * 2**20


@dataclasses.dataclass(frozen=True)
class Blocks:
    """Static arguments of one attention call: the mask, the (q rows, kv
    rows) of a forward and of a backward block, and the mode."""

    causal: bool
    window: int
    fwd: "tuple[int, int]"
    bwd: "tuple[int, int]"
    interpret: bool
    scale: float                 # on q·k before the softmax


def block_sizes(s: int, d: int) -> "tuple[tuple[int, int], tuple[int, int]] | None":
    """(forward, backward) blocks, each (q rows, kv rows), for sequence
    length ``s`` and head size ``d``; None where the chip's tiling cannot
    take them (``s`` or ``d`` not a multiple of 128)."""
    if d % LANES or s % LANES:
        return None
    return (math.gcd(s, 1024),) * 2, (math.gcd(s, 512),) * 2


def _visible(q0, q1, k0, k1, causal, window):
    """(any, all) of the positions q in [q0, q1], k in [k0, k1] unmasked.
    Masked are k > q (causal) and k <= q - window (window > 0)."""
    anyv = (not causal or k0 <= q1) and (not window or k1 > q0 - window)
    allv = (not causal or k1 <= q0) and (not window or k0 > q1 - window)
    return anyv, allv


def _flags(keys):
    """FIRST on the first entry of each run of equal keys, LAST on the last."""
    flags = np.zeros(len(keys), np.int32)
    for t, key in enumerate(keys):
        if t == 0 or keys[t - 1] != key:
            flags[t] |= _FIRST
        if t == len(keys) - 1 or keys[t + 1] != key:
            flags[t] |= _LAST
    return flags


def _pairs(s, bq, bk, causal, window):
    """{(q block, kv block): 0 or _MASKED} of the pairs with a visible position."""
    pairs = {}
    for i in range(s // bq):
        for j in range(s // bk):
            anyv, allv = _visible(i * bq, i * bq + bq - 1, j * bk, j * bk + bk - 1,
                                  causal, window)
            if anyv:
                pairs[i, j] = 0 if allv else _MASKED
    return pairs


@functools.lru_cache(maxsize=64)
def _fwd_schedule(s, blocks: Blocks):
    """Scalar-prefetch tables ``(qi, kj, flags)`` of the visible pairs, q
    block outer; FIRST/LAST mark a q block's first and last kv block."""
    pairs = _pairs(s, *blocks.fwd, blocks.causal, blocks.window)
    rows = sorted(pairs)
    qi = np.array([i for i, _ in rows], np.int32)
    kj = np.array([j for _, j in rows], np.int32)
    return qi, kj, _flags(list(qi)) | np.array([pairs[p] for p in rows], np.int32)


@functools.lru_cache(maxsize=64)
def _bwd_schedule(s, blocks: Blocks, groups: int):
    """Scalar-prefetch tables ``(kj, g, qi, flags)``: kv block outer, then
    the query head in the kv head's group, then q block; FIRST/LAST mark a
    kv block's first and last visit."""
    pairs = _pairs(s, *blocks.bwd, blocks.causal, blocks.window)
    order = sorted((j, g, i) for i, j in pairs for g in range(groups))
    kj, g, qi = (np.array(col, np.int32) for col in zip(*order))
    masked = np.array([pairs[i, j] for j, _, i in order], np.int32)
    return kj, g, qi, _flags(list(kj)) | masked


def _mask(q0, k0, shape, blocks: Blocks, transposed=False):
    """The visible positions of the block whose first q and kv positions
    are ``q0`` and ``k0``; rows are q unless ``transposed`` (then kv)."""
    qdim, kdim = (1, 0) if transposed else (0, 1)
    diff = (q0 - k0) + (jax.lax.broadcasted_iota(jnp.int32, shape, qdim)
                        - jax.lax.broadcasted_iota(jnp.int32, shape, kdim))
    mask = diff >= 0 if blocks.causal else None
    if blocks.window:
        inside = diff < blocks.window
        mask = inside if mask is None else mask & inside
    return mask


def _on(flags, bit):
    return (flags & bit) != 0


def _each_mask_branch(flags, body, blocks: Blocks):
    """Run ``body(masked)``, with the mask only where the pair needs it."""
    if not (blocks.causal or blocks.window):
        return body(False)
    pl.when(_on(flags, _MASKED))(lambda: body(True))
    pl.when(jnp.logical_not(_on(flags, _MASKED)))(lambda: body(False))


def _lanes(x, n):
    """(rows, LANES) with equal lanes -> (rows, n)."""
    if n % LANES == 0:
        return jnp.tile(x, (1, n // LANES)) if n > LANES else x
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


# ---------------------------------------------------------------- forward
def _fwd_kernel(qi_ref, kj_ref, fl_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_sc, l_sc, acc_sc, *, blocks: Blocks, sm_scale):
    t = pl.program_id(2)
    qi, kj, flags = qi_ref[t], kj_ref[t], fl_ref[t]
    bq, bk = blocks.fwd

    @pl.when(_on(flags, _FIRST))
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def body(masked):
        # Row statistics are (bq, LANES) with equal lanes. A row with no
        # visible key in this block adds exp(NEG_INF - m) = 0 once it has
        # a real maximum; before it has one (keys left of a window) it adds
        # exp(0), which the first real maximum's correction multiplies by 0.
        v = v_ref[...]
        s = jax.lax.dot_general(q_ref[...], k_ref[...], _NT,
                                preferred_element_type=jnp.float32) * sm_scale
        if masked:
            s = jnp.where(_mask(qi * bq, kj * bk, s.shape, blocks), s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, bk))
        alpha = jnp.exp(m_prev - m_new)
        l_sc[...] = alpha * l_sc[...] + p.sum(axis=1, keepdims=True)
        acc_sc[...] = _lanes(alpha, acc_sc.shape[1]) * acc_sc[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    _each_mask_branch(flags, body, blocks)

    @pl.when(_on(flags, _LAST))
    def _finish():
        l = l_sc[...]
        o_ref[...] = (acc_sc[...] * _lanes(1.0 / l, acc_sc.shape[1])).astype(o_ref.dtype)
        lse_ref[...] = jnp.transpose(m_sc[...] + jnp.log(l))[:1]


def _forward(q, k, v, blocks: Blocks, heads: int, kv_heads: int):
    """q (B, S, H*Dqk), k (B, S, KVH*Dqk), v (B, S, KVH*Dv) -> out
    (B, S, H*Dv), lse (B, H, 1, S)."""
    b, s, hd = q.shape
    d, dv = hd // heads, v.shape[2] // kv_heads
    groups = heads // kv_heads
    bq, bk = blocks.fwd
    tables = _fwd_schedule(s, blocks)

    def q_map(b, h, t, qi, kj, f):
        return b, qi[t], h

    def kv_map(b, h, t, qi, kj, f):
        return b, kj[t], h // groups

    def lse_map(b, h, t, qi, kj, f):
        return b, h, 0, qi[t]

    return pl.pallas_call(
        functools.partial(_fwd_kernel, blocks=blocks, sm_scale=blocks.scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, heads, len(tables[0])),
            in_specs=[pl.BlockSpec((None, bq, d), q_map),
                      pl.BlockSpec((None, bk, d), kv_map),
                      pl.BlockSpec((None, bk, dv), kv_map)],
            out_specs=[pl.BlockSpec((None, bq, dv), q_map),
                       pl.BlockSpec((None, None, 1, bq), lse_map)],
            scratch_shapes=[pltpu.VMEM((bq, LANES), jnp.float32),  # row max
                            pltpu.VMEM((bq, LANES), jnp.float32),  # row sum
                            pltpu.VMEM((bq, dv), jnp.float32)],    # numerator
        ),
        out_shape=[jax.ShapeDtypeStruct((b, s, heads * dv), q.dtype),
                   jax.ShapeDtypeStruct((b, heads, 1, s), jnp.float32)],
        compiler_params=_params(),
        interpret=blocks.interpret,
        name="flash_attention_fwd",
    )(*map(jnp.asarray, tables), q, k, v)


# --------------------------------------------------------------- backward
def _bwd_kernel(kj_ref, g_ref, qi_ref, fl_ref, q_ref, k_ref, v_ref, do_ref,
                lse_ref, di_ref, dq_ref, dk_ref, dv_ref, dq_sc, dk_sc, dv_sc, *,
                blocks: Blocks, sm_scale):
    t = pl.program_id(2)
    kj, g, qi, flags = kj_ref[t], g_ref[t], qi_ref[t], fl_ref[t]
    bq, bk = blocks.bwd

    @pl.when(t == 0)
    def _init_dq():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    @pl.when(_on(flags, _FIRST))
    def _init_dkv():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def body(masked):
        # transposed scores (kv rows, q columns): the per-q statistics are rows
        q, k, do = q_ref[...], k_ref[...], do_ref[...]
        s_t = jax.lax.dot_general(k, q, _NT,
                                  preferred_element_type=jnp.float32) * sm_scale
        if masked:
            s_t = jnp.where(_mask(qi * bq, kj * bk, s_t.shape, blocks, transposed=True),
                            s_t, NEG_INF)
        p_t = jnp.exp(s_t - lse_ref[...])
        dv_sc[...] += jnp.dot(p_t.astype(do.dtype), do,
                              preferred_element_type=jnp.float32)
        dp_t = jax.lax.dot_general(v_ref[...], do, _NT,
                                   preferred_element_type=jnp.float32)
        ds_t = (p_t * (dp_t - di_ref[...])).astype(q.dtype)
        dk_sc[...] += jnp.dot(ds_t, q, preferred_element_type=jnp.float32)
        rows = pl.ds(pl.multiple_of(qi * bq, bq), bq)
        dq_sc[g, rows, :] += jax.lax.dot_general(ds_t, k, _TN,
                                                 preferred_element_type=jnp.float32)

    _each_mask_branch(flags, body, blocks)

    @pl.when(_on(flags, _LAST))
    def _finish_dkv():
        dk_ref[...] = (dk_sc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)

    @pl.when(t == pl.num_programs(2) - 1)
    def _finish_dq():
        groups, _, d = dq_sc.shape
        for h in range(groups):
            dq_ref[:, h * d:(h + 1) * d] = (dq_sc[h] * sm_scale).astype(dq_ref.dtype)


def _backward(q, k, v, o, lse, do, blocks: Blocks, heads: int, kv_heads: int):
    b, s, hd = q.shape
    d, dv = hd // heads, v.shape[2] // kv_heads
    groups = heads // kv_heads
    bq, bk = blocks.bwd
    # di = rowsum(dO * O) per q position and head, a row like lse: (B, H, 1, S)
    di = jnp.einsum("bshd,bshd->bhs", do.reshape(b, s, heads, dv).astype(jnp.float32),
                    o.reshape(b, s, heads, dv).astype(jnp.float32))[:, :, None, :]
    tables = _bwd_schedule(s, blocks, groups)

    def q_map(b, kvh, t, kj, g, qi, f):
        return b, qi[t], kvh * groups + g[t]

    def kv_map(b, kvh, t, kj, g, qi, f):
        return b, kj[t], kvh

    def row_map(b, kvh, t, kj, g, qi, f):
        return b, kvh * groups + g[t], 0, qi[t]

    def group_map(b, kvh, t, kj, g, qi, f):
        return b, 0, kvh

    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, blocks=blocks, sm_scale=blocks.scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, kv_heads, len(tables[0])),
            in_specs=[pl.BlockSpec((None, bq, d), q_map),
                      pl.BlockSpec((None, bk, d), kv_map),
                      pl.BlockSpec((None, bk, dv), kv_map),
                      pl.BlockSpec((None, bq, dv), q_map),
                      pl.BlockSpec((None, None, 1, bq), row_map),
                      pl.BlockSpec((None, None, 1, bq), row_map)],
            out_specs=[pl.BlockSpec((None, s, groups * d), group_map),
                       pl.BlockSpec((None, bk, d), kv_map),
                       pl.BlockSpec((None, bk, dv), kv_map)],
            scratch_shapes=[pltpu.VMEM((groups, s, d), jnp.float32),
                            pltpu.VMEM((bk, d), jnp.float32),
                            pltpu.VMEM((bk, dv), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=_params(),
        interpret=blocks.interpret,
        name="flash_attention_bwd",
    )(*map(jnp.asarray, tables), q, k, v, do, lse, di)
    return dq, dk, dv


# ------------------------------------------------------------- custom_vjp
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_vjp(q, k, v, blocks: Blocks, heads: int, kv_heads: int):
    """Attention of flat q (B, S, H*Dqk) over k (B, S, KVH*Dqk) and v
    (B, S, KVH*Dv), with the backward kernel as its VJP."""
    return _forward(q, k, v, blocks, heads, kv_heads)[0]


def _vjp_fwd(q, k, v, blocks, heads, kv_heads):
    o, lse = _forward(q, k, v, blocks, heads, kv_heads)
    return o, (q, k, v, o, lse)


def _vjp_bwd(blocks, heads, kv_heads, res, do):
    return _backward(*res, do, blocks, heads, kv_heads)


flash_attention_vjp.defvjp(_vjp_fwd, _vjp_bwd)
