"""Pure-jnp oracle for flash_attention (naive full-matrix attention)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["attention_ref", "attention_ref_gqa"]


def attention_ref(q, k, v, *, causal=True, window=0, scale=None):
    """q/k: (BH, S, Dqk), v: (BH, S, Dv). fp32 softmax, output in q.dtype.
    ``scale`` defaults to 1 / sqrt(Dqk)."""
    bh, s, d = q.shape
    scale = d**-0.5 if scale is None else scale
    logits = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    # fully-masked rows (window) produce uniform probs; zero them like the kernel
    any_valid = mask.any(axis=1)[None, :, None]
    probs = jnp.where(any_valid, probs, 0.0)
    return jnp.einsum("bqk,bkd->bqd", probs, v.astype(jnp.float32)).astype(q.dtype)


def attention_ref_gqa(q, k, v, *, causal=True, window=0, scale=None):
    """q (B, S, H, Dqk), k (B, S, KVH, Dqk), v (B, S, KVH, Dv): each kv head
    repeated over its group of H // KVH query heads, then
    :func:`attention_ref`."""
    b, s, h, _ = q.shape
    groups = h // k.shape[2]
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, s, t.shape[-1])
    out = attention_ref(fold(q), fold(jnp.repeat(k, groups, axis=2)),
                        fold(jnp.repeat(v, groups, axis=2)),
                        causal=causal, window=window, scale=scale)
    return out.reshape(b, h, s, v.shape[-1]).transpose(0, 2, 1, 3)
