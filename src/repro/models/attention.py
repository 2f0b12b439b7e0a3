"""GQA and latent attention: dense, chunked (online-softmax), and decode paths.

Layouts: activations (B, S, d_model); q (B, S, H, D); k/v (B, S, KVH, D).

Multi-head latent attention (MLA, DeepSeek-V2 [arXiv:2405.04434], no q
compression; ``cfg.attn_kind == "mla"``): q = x W_q per head as a 128-wide
part without position and a 64-wide rotary part; x W_kva = [c_kv ‖ k_pe],
the latent c_kv RMS-normed and expanded per head to [k_nope ‖ v], k_pe
rotated once and shared by every head. Attention then runs as MHA over
q·k of 192 lanes and v of 128, with the softmax scale 1/sqrt(192) times
YaRN's mscale squared. Training computes k and v per head (no latent
cache); decode is not built for it.

Sharding: by default heads shard over the "model"/tp mesh axis
(``shard(q, "batch", None, "heads", None)``). Architectures whose head
count does not divide the TP degree (phi3: 40, llava: 56) set
``attn_shard="seq"`` — queries shard over the *sequence* dim instead and
K/V are gathered, a context-parallel fallback that keeps compute balanced
at the price of an all-gather (visible in the roofline collective term).

Train and prefill take ``kernels/flash_attention`` where the step is
lowered for a TPU and the kernel applies: causal, no logit soft-cap, head
size and sequence length on the kernel's tiling, and no active mesh that
splits q/k/v over more than one device (``_kernel_applies``). Everywhere
else, the CPU included, the pure-jnp paths run: dense up to
``attn_dense_threshold``, chunked online-softmax above it. Decode is
always jnp. Tracing ``attention_block`` records the path it takes on the
process's default backend, with its q·k and v widths, as an
``attention.path`` instant. On the kernel path MLA's q and k are
zero-padded to the chip's 128-lane tiling (192 -> 256): zero lanes change
no score.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..kernels.flash_attention.ops import block_sizes, flash_attention_train
from ..obs import trace
from ..parallel.axes import current_ctx, shard
from .common import Param, apply_rope, make_rope, rms_norm, scaled_init, yarn_mscale

__all__ = ["init_attention", "attention_block", "decode_attention_block"]

NEG_INF = -1e30
LANES = 128


def _qkv_axes(cfg):
    if cfg.attn_shard == "seq":
        # heads not divisible by tp: shard sequence instead
        return ("batch", "seq_tp", "heads_r", None)
    return ("batch", None, "heads", None)


def init_attention(rng, cfg, dtype):
    if cfg.attn_kind == "mla":
        return _init_mla(rng, cfg, dtype)
    d, hd = cfg.d_model, cfg.head_dim_
    h, kvh = cfg.num_heads, cfg.num_kv_heads
    return {
        "wq": Param(scaled_init(rng.next(), (d, h * hd), dtype), ("embed", "heads_flat")),
        "wk": Param(scaled_init(rng.next(), (d, kvh * hd), dtype), ("embed", "kv_flat")),
        "wv": Param(scaled_init(rng.next(), (d, kvh * hd), dtype), ("embed", "kv_flat")),
        "wo": Param(scaled_init(rng.next(), (h * hd, d), dtype, fan_in=h * hd), ("heads_flat", "embed")),
    }


def _init_mla(rng, cfg, dtype):
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return {
        "wq": Param(scaled_init(rng.next(), (d, h * (dn + dr)), dtype), ("embed", "heads_flat")),
        "wkv_a": Param(scaled_init(rng.next(), (d, r + dr), dtype), ("embed", None)),
        "kv_norm": Param(jnp.zeros((r,), dtype), (None,)),
        "wkv_b": Param(scaled_init(rng.next(), (r, h * (dn + dv)), dtype), (None, "heads_flat")),
        "wo": Param(scaled_init(rng.next(), (h * dv, d), dtype, fan_in=h * dv),
                    ("heads_flat", "embed")),
    }


def mla_scale(cfg) -> float:
    """MLA's softmax scale: 1/sqrt(q·k width), times YaRN's mscale (of
    ``mscale_all_dim``) squared."""
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def _mla_qkv(p, x, cfg, positions):
    """q, k (B, S, H, dn + dr) and v (B, S, H, dv) of latent attention."""
    b, s, _ = x.shape
    h, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    q = jnp.einsum("bsd,dn->bsn", x, p["wq"]).reshape(b, s, h, dn + dr)
    kv_a = jnp.einsum("bsd,dn->bsn", x, p["wkv_a"])
    c_kv = rms_norm(kv_a[..., :r], p["kv_norm"], cfg.norm_eps)
    kv = jnp.einsum("bsr,rn->bsn", c_kv, p["wkv_b"]).reshape(b, s, h, dn + dv)
    sin, cos = make_rope(positions, dr, cfg.rope_theta, cfg)
    q_pe = apply_rope(q[..., dn:], sin, cos)
    k_pe = apply_rope(kv_a[:, :, None, r:], sin, cos)  # one head, shared
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (b, s, h, dr))], axis=-1)
    return q, k, kv[..., dn:]


def _pad_lanes(t):
    """Zero-pad the last axis to a multiple of the chip's 128 lanes."""
    pad = -t.shape[-1] % LANES
    return jnp.pad(t, [(0, 0)] * (t.ndim - 1) + [(0, pad)]) if pad else t


def _project_qkv(p, x, cfg):
    b, s, _ = x.shape
    hd = cfg.head_dim_
    q = jnp.einsum("bsd,dn->bsn", x, p["wq"]).reshape(b, s, cfg.num_heads, hd)
    k = jnp.einsum("bsd,dn->bsn", x, p["wk"]).reshape(b, s, cfg.num_kv_heads, hd)
    v = jnp.einsum("bsd,dn->bsn", x, p["wv"]).reshape(b, s, cfg.num_kv_heads, hd)
    return q, k, v


def _expand_kv(k, cfg):
    """(B,S,KVH,D) -> (B,S,H,D) by repeating each kv head over its group."""
    groups = cfg.num_heads // cfg.num_kv_heads
    return jnp.repeat(k, groups, axis=2)


def _dense_attention(q, k, v, cfg, q_offset=0, scale=None):
    """Direct (S_q x S_kv) attention with causal/window masking. fp32 softmax.
    ``scale`` defaults to 1/sqrt(q's head width)."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    sq, sk = q.shape[1], k.shape[1]
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), dtype=bool)
    if cfg.causal:
        mask &= kpos <= qpos
    if cfg.window:
        mask &= kpos > qpos - cfg.window
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _chunked_attention_vecq(q, k, v, cfg, scale):
    """Online-softmax over KV chunks with ALL query blocks vectorised.

    Used for ``attn_shard="seq"`` (head count not divisible by TP): the q
    block axis stays a *batch* dimension sharded over the model axis, so
    every device processes only its own sequence shard — a scan over q
    blocks would instead make each shard recompute the full S² (observed as
    16x redundant FLOPs in the phi3/llava prefill dry-run before this path
    existed). Memory: one (b, nq_local, blk, h, blk) logits tile per step.
    """
    blk = min(cfg.attn_chunk, q.shape[1])
    b, s, h, d = q.shape
    dv = v.shape[-1]
    assert s % blk == 0, (s, blk)
    nq = s // blk
    qb = q.reshape(b, nq, blk, h, d)
    qb = shard(qb, "batch", "seq_tp", None, None, None)
    kb = k.reshape(b, nq, blk, h, d)
    vb = v.reshape(b, nq, blk, h, dv)

    def kv_step(state, ki):
        m, l, acc = state
        kk = kb[:, ki]  # (b, blk, h, d)
        vv = vb[:, ki]
        logits = (
            jnp.einsum("bnqhd,bkhd->bnhqk", qb, kk).astype(jnp.float32) * scale
        )
        if cfg.logit_softcap:
            logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
        qpos = (
            jnp.arange(nq)[:, None, None] * blk + jnp.arange(blk)[None, :, None]
        )  # (nq, blk, 1)
        kpos = (ki * blk + jnp.arange(blk))[None, None, :]
        mask = jnp.ones((nq, blk, blk), dtype=bool)
        if cfg.causal:
            mask = mask & (kpos <= qpos)
        if cfg.window:
            mask = mask & (kpos > qpos - cfg.window)
        mask = mask[None, :, None]  # (1, nq, 1, blk, blk)
        logits = jnp.where(mask, logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bnhqk,bkhd->bnhqd", p.astype(vv.dtype), vv
        ).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, nq, h, blk), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, nq, h, blk), jnp.float32)
    a0 = jnp.zeros((b, nq, h, blk, dv), jnp.float32)
    m0, l0, a0 = (shard(t, "batch", "seq_tp", *([None] * (t.ndim - 2))) for t in (m0, l0, a0))
    (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nq))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 1, 3, 2, 4).reshape(b, s, h, dv).astype(q.dtype)


def _chunked_attention(q, k, v, cfg, scale):
    """Online-softmax over KV chunks, queries blocked — O(S·chunk) memory.

    This is the flash-attention recurrence in pure jnp. Causal masking is
    applied per chunk pair; the XLA path computes masked blocks too (see
    DESIGN.md roofline notes), which the Pallas kernel skips.
    """
    blk = min(cfg.attn_chunk, q.shape[1])
    b, s, h, d = q.shape
    dv = v.shape[-1]
    assert s % blk == 0, (s, blk)
    nq = s // blk

    qb = q.reshape(b, nq, blk, h, d)
    kb = k.reshape(b, nq, blk, h, d)
    vb = v.reshape(b, nq, blk, h, dv)

    def q_block(carry, qi):
        del carry
        qi_q = qb[:, qi]  # (b, blk, h, d)

        def kv_step(state, ki):
            m, l, acc = state
            kk = kb[:, ki]
            vv = vb[:, ki]
            logits = (
                jnp.einsum("bqhd,bkhd->bhqk", qi_q, kk).astype(jnp.float32) * scale
            )
            if cfg.logit_softcap:
                logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
            qpos = qi * blk + jnp.arange(blk)[:, None]
            kpos = ki * blk + jnp.arange(blk)[None, :]
            mask = jnp.ones((blk, blk), dtype=bool)
            if cfg.causal:
                mask &= kpos <= qpos
            if cfg.window:
                mask &= kpos > qpos - cfg.window
            logits = jnp.where(mask[None, None], logits, NEG_INF)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(vv.dtype), vv
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, blk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, blk), jnp.float32)
        a0 = jnp.zeros((b, h, blk, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nq))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.transpose(0, 2, 1, 3).astype(q.dtype)  # (b, blk, h, dv)

    _, blocks = jax.lax.scan(q_block, None, jnp.arange(nq))  # (nq, b, blk, h, dv)
    return blocks.transpose(1, 0, 2, 3, 4).reshape(b, s, h, dv)


def _jnp_attention(q, k, v, cfg, scale):
    """The pure-jnp paths: kv heads repeated, dense or chunked."""
    s = q.shape[1]
    k = _expand_kv(k, cfg)
    v = _expand_kv(v, cfg)
    axes = _qkv_axes(cfg)
    q, k, v = shard(q, *axes), shard(k, *axes), shard(v, *axes)
    if s <= cfg.attn_dense_threshold:
        out = _dense_attention(q, k, v, cfg, scale=scale)
    elif cfg.attn_shard == "seq":
        out = _chunked_attention_vecq(q, k, v, cfg, scale)
    else:
        out = _chunked_attention(q, k, v, cfg, scale)
    return shard(out, *axes)


def _qk_lanes(q, cfg) -> int:
    """The q·k width the kernel takes: MLA's zero-padded to 128 lanes."""
    d = q.shape[3]
    return d + (-d % LANES) if cfg.attn_kind == "mla" else d


def _kernel_applies(q, k, cfg, v=None) -> bool:
    """Whether the flash-attention kernel computes exactly this attention on
    one device: causal, unsoftcapped, on the kernel's tiling, unsharded."""
    if not cfg.causal or cfg.logit_softcap:
        return False
    s = q.shape[1]
    if block_sizes(s, _qk_lanes(q, cfg)) is None:
        return False
    if v is not None and block_sizes(s, v.shape[3]) is None:
        return False
    ctx = current_ctx()
    if ctx is None:
        return True
    axes = _qkv_axes(cfg)
    return all(
        ctx.sharding_for(axes, t.shape).shard_shape(t.shape) == t.shape
        for t in (q, k)
    )


def _record_path(path, q, k, v):
    b, s, h, _ = q.shape
    trace.instant("attention.path", "compute", path=path, b=b, s=s, h=h,
                  kvh=k.shape[2], qk=q.shape[3], v=v.shape[3])


def attention_block(p, x, cfg, *, positions=None):
    """Full-sequence attention (train / prefill). Returns (out, (k, v))."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    if cfg.attn_kind == "mla":
        q, k, v = _mla_qkv(p, x, cfg, positions)
        scale = mla_scale(cfg)
    else:
        q, k, v = _project_qkv(p, x, cfg)
        sin, cos = make_rope(positions, cfg.head_dim_, cfg.rope_theta, cfg)
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
        scale = cfg.head_dim_ ** -0.5
    kv = (k, v)
    jnp_path = "dense" if s <= cfg.attn_dense_threshold else "chunked"
    if _kernel_applies(q, k, cfg, v):
        _record_path("kernel" if jax.default_backend() == "tpu" else jnp_path, q, k, v)
        out = jax.lax.platform_dependent(
            q, k, v,
            tpu=lambda q, k, v: flash_attention_train(
                _pad_lanes(q), _pad_lanes(k), v, causal=True, window=cfg.window,
                scale=scale, interpret=False),
            default=lambda q, k, v: _jnp_attention(q, k, v, cfg, scale),
        )
    else:
        _record_path(jnp_path, q, k, v)
        out = _jnp_attention(q, k, v, cfg, scale)
    out = jnp.einsum("bsn,nd->bsd", out.reshape(b, s, -1), p["wo"])
    return out, kv


def quantize_kv(t: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-(token, head) symmetric int8 quantisation. t: (..., D)."""
    scale = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)).astype(dtype)


def decode_attention_block(p, x, cache_k, cache_v, cache_pos, cfg,
                           k_scale=None, v_scale=None):
    """One-token decode against a (possibly rotating-window) KV cache.

    x: (B, 1, d); cache_k/v: (B, S_c, KVH, D); cache_pos: scalar int32.
    Slot ``j`` holds the KV of absolute position ``p_j = cache_pos -
    ((cache_pos - j) mod S_c)`` — when ``S_c > cache_pos`` (full cache) this
    reduces to ``p_j = j``; when ``S_c == window`` it is the rotating buffer
    that keeps zamba2's 500k decode at O(window) memory. Keys are stored
    RoPE'd at absolute positions, so rotation needs no re-rotation.

    With ``cfg.kv_cache_dtype == "int8"`` the cache is int8 with bf16
    per-(token, head) scales (k_scale/v_scale: (B, S_c, KVH, 1)): the
    decode memory term is KV-streaming-bound, so halving cache bytes halves
    it (§Perf decode lever).
    """
    b = x.shape[0]
    hd = cfg.head_dim_
    s_c = cache_k.shape[1]
    quant = cfg.kv_cache_dtype == "int8"
    q, k, v = _project_qkv(p, x, cfg)
    pos = jnp.full((b, 1), cache_pos, dtype=jnp.int32)
    sin, cos = make_rope(pos, hd, cfg.rope_theta, cfg)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)
    write_idx = jnp.mod(cache_pos, s_c)
    upd = lambda c, t: jax.lax.dynamic_update_slice_in_dim(
        c, t.astype(c.dtype), write_idx, axis=1
    )
    if quant:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        cache_k, k_scale = upd(cache_k, kq), upd(k_scale, ks)
        cache_v, v_scale = upd(cache_v, vq), upd(v_scale, vs)
        k_eff = dequantize_kv(cache_k, k_scale, x.dtype)
        v_eff = dequantize_kv(cache_v, v_scale, x.dtype)
    else:
        cache_k = upd(cache_k, k)
        cache_v = upd(cache_v, v)
        k_eff, v_eff = cache_k, cache_v

    groups = cfg.num_heads // cfg.num_kv_heads
    scale = hd**-0.5
    qg = q.reshape(b, 1, cfg.num_kv_heads, groups, hd)
    # (B, KVH, G, S)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_eff).astype(jnp.float32) * scale
    logits = logits[:, :, :, 0]
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    j = jnp.arange(s_c)[None, None, None, :]
    slot_pos = cache_pos - jnp.mod(cache_pos - j, s_c)  # absolute position held
    valid = slot_pos >= 0
    if cfg.window:
        valid &= slot_pos > cache_pos - cfg.window
    logits = jnp.where(valid, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v_eff.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, v_eff)
    out = out.reshape(b, 1, cfg.num_heads * hd)
    out = jnp.einsum("bsn,nd->bsd", out.astype(x.dtype), p["wo"])
    if quant:
        return out, (cache_k, k_scale), (cache_v, v_scale)
    return out, cache_k, cache_v
