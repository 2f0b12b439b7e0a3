"""Fine-grained MoE: shared + routed experts, top-k token-choice routing.

Follows DeepSeekMoE [arXiv:2401.06066] (deepseek-moe-16b: 2 shared + 64
routed, top-6) and the same structure at Kimi-K2 scale (384 routed, top-8).

Dispatch is **sort-based with capacity dropping**, grouped GShard-style by
batch row: each sequence dispatches its own tokens into per-expert capacity
slots (``cap = seq·k·cf / E``). Grouping keeps the expert buffers sharded
along the batch/data axis — a single global dispatch would make the
(E, cap, d) buffer unshardable over tokens (≈7 TB/device at kimi-k2 scale);
the grouped buffer is (B, E, cap, d) with B on the data axis and E on the
model axis (EP). A (tokens, experts, capacity) one-hot GShard dispatch
einsum was rejected for the same reason (≈4 GB/device in bf16 at kimi
scale). Under pjit, XLA lowers the batched gather/scatter across the E
axis into all-to-alls (measured in the roofline; a shard_map variant is a
§Perf candidate).

An auxiliary load-balance loss (Switch-style) is returned for training.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..parallel.axes import shard
from .common import Param, scaled_init

__all__ = ["init_moe", "moe_block"]


def init_moe(rng, cfg, dtype):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe_num_experts
    p = {
        "router": Param(scaled_init(rng.next(), (d, e), dtype), ("embed", None)),
        "wi_gate": Param(
            scaled_init(rng.next(), (e, d, f), dtype, fan_in=d), ("experts", "embed", None)
        ),
        "wi_up": Param(
            scaled_init(rng.next(), (e, d, f), dtype, fan_in=d), ("experts", "embed", None)
        ),
        "wo": Param(
            scaled_init(rng.next(), (e, f, d), dtype, fan_in=f), ("experts", None, "embed")
        ),
    }
    if cfg.moe_num_shared:
        sf = f * cfg.moe_num_shared
        p["shared"] = {
            "wi_gate": Param(scaled_init(rng.next(), (d, sf), dtype), ("embed", "mlp")),
            "wi_up": Param(scaled_init(rng.next(), (d, sf), dtype), ("embed", "mlp")),
            "wo": Param(scaled_init(rng.next(), (sf, d), dtype, fan_in=sf), ("mlp", "embed")),
        }
    return p


def moe_block(p, x, cfg):
    """x: (B, S, d) -> (out, aux_loss)."""
    b, s, d = x.shape
    e, k = cfg.moe_num_experts, cfg.moe_top_k
    cap = max(int(s * k * cfg.capacity_factor / e), 1)

    # --- routing (fp32 for numerics) ---
    logits = jnp.einsum("bsd,de->bse", x, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)  # (b, s, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # --- aux load-balance loss (Switch eq. 4-6), via scatter (no one-hot) ---
    t = b * s
    density = (
        jnp.zeros((e,), jnp.float32).at[top_e[..., 0].reshape(-1)].add(1.0) / t
    )
    router_mean = probs.reshape(t, e).mean(axis=0)
    aux = e * jnp.sum(density * router_mean)

    # --- per-row sort-based dispatch with capacity dropping ---
    # All scatters here move 4-byte *integers* (slot maps), never d_model
    # vectors: data moves only through gathers whose outputs carry sharding
    # ("experts" or "seq_act" on the gathered dim), so no (s*k, d)-sized
    # unsharded intermediate ever materialises (15 GB/device at kimi scale).
    flat_e = top_e.reshape(b, s * k)
    flat_p = top_p.reshape(b, s * k).astype(x.dtype)

    def slot_maps(se_r):
        """One row: se_r (s*k,) expert ids -> integer routing maps."""
        order = jnp.argsort(se_r, stable=True)
        se = se_r[order]
        st = (order // k).astype(jnp.int32)   # token of each sorted assignment
        counts = jnp.zeros((e,), jnp.int32).at[se].add(1)
        starts = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(s * k, dtype=jnp.int32) - starts[se]
        keep = pos < cap
        slot = se * cap + pos                  # valid only where keep
        # slot -> token map (dropped assignments go to a dump slot e*cap)
        s2t = jnp.full((e * cap + 1,), 0, jnp.int32)
        s2t = s2t.at[jnp.where(keep, slot, e * cap)].set(st)
        s2v = jnp.zeros((e * cap + 1,), jnp.bool_)
        s2v = s2v.at[jnp.where(keep, slot, e * cap)].set(keep)
        # original-order assignment -> slot map (for the combine gathers)
        a2s = jnp.zeros((s * k,), jnp.int32).at[order].set(jnp.where(keep, slot, 0))
        a2v = jnp.zeros((s * k,), jnp.bool_).at[order].set(keep)
        return s2t[: e * cap], s2v[: e * cap], a2s, a2v

    s2t, s2v, a2s, a2v = jax.vmap(slot_maps)(flat_e)

    # gather tokens into expert buffers; output sharded over "experts"
    buf = jnp.take_along_axis(x, s2t[..., None], axis=1)       # (b, e*cap, d)
    buf = jnp.where(s2v[..., None], buf, 0).reshape(b, e, cap, d)
    buf = shard(buf, "batch", "experts", None, None)

    # --- expert FFN (grouped einsum over the expert dim; EP over "model") ---
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", buf, p["wi_gate"]))
    h = h * jnp.einsum("becd,edf->becf", buf, p["wi_up"])
    h = shard(h, "batch", "experts", None, None)
    y = jnp.einsum("becf,efd->becd", h, p["wo"]).reshape(b, e * cap, d)

    # --- combine: k gathers in original token order (seq-shardable) ---
    out = jnp.zeros((b, s, d), x.dtype)
    for j in range(k):
        idx = a2s.reshape(b, s, k)[:, :, j]
        wj = (flat_p * a2v).reshape(b, s, k)[:, :, j]
        yj = jnp.take_along_axis(y, idx[..., None], axis=1)    # (b, s, d)
        yj = shard(yj, "batch", "seq_act", None)
        out = out + yj * wj[..., None]
    out = shard(out, "batch", "seq_act", None)

    if cfg.moe_num_shared:
        sp_ = p["shared"]
        hs = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, sp_["wi_gate"]))
        hs = hs * jnp.einsum("bsd,df->bsf", x, sp_["wi_up"])
        out = out + jnp.einsum("bsf,fd->bsd", hs, sp_["wo"])

    return out, aux.astype(jnp.float32)


# --------------------------------------------------------- shard_map variant
def moe_block_a2a(p, x, cfg):
    """Explicit all-to-all expert parallelism via shard_map (§Perf lever).

    GSPMD lowers the pjit dispatch above into all-gathers of the expert
    buffers (tokens replicate across the expert axis). This variant is the
    structural fix: tokens are sequence-sharded over the "model" axis, each
    shard routes its own tokens, sends exactly the chosen token vectors to
    the owning expert shard with ``jax.lax.all_to_all``, and reverses the
    route for the combine — moving tokens·k·d bytes instead of
    tokens·E_shard·cap·d. Two-stage capacity dropping (per (src,dst) pair,
    then per expert) follows GShard practice; with generous capacity the
    output equals :func:`moe_block` (equivalence-tested).

    Requires an active mesh whose "model" axis divides both the sequence
    and the expert count; ``_apply_block`` selects it via
    ``cfg.moe_impl == "a2a"``.
    """
    from jax.sharding import PartitionSpec as P

    from ..parallel.axes import current_ctx

    ctx = current_ctx()
    assert ctx is not None and "model" in ctx.mesh.shape, (
        "moe_block_a2a needs an active sharding ctx with a 'model' axis"
    )
    mesh = ctx.mesh
    e_sh = mesh.shape["model"]
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    b, s, d = x.shape
    e, k = cfg.moe_num_experts, cfg.moe_top_k
    assert e % e_sh == 0 and s % e_sh == 0, (e, s, e_sh)
    e_l = e // e_sh
    s_l = s // e_sh
    cap_pair = max(int(s_l * k * cfg.capacity_factor / e_sh) * max(b // max(
        __import__("math").prod(mesh.shape[a] for a in dp), 1), 1), 1)
    cap_local = max(int(e_sh * cap_pair * cfg.capacity_factor / e_l), 1)

    # routing + aux loss on the global view (router weights are replicated)
    logits = jnp.einsum("bsd,de->bse", x, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    t_all = b * s
    density = (
        jnp.zeros((e,), jnp.float32).at[top_e[..., 0].reshape(-1)].add(1.0) / t_all
    )
    aux = e * jnp.sum(density * probs.reshape(t_all, e).mean(axis=0))

    def local_fn(xl, wig, wiu, wo, te, tp):
        """One model-shard: xl (b_l, s_l, d); te/tp (b_l, s_l, k)."""
        bl, sl, _ = xl.shape
        t = bl * sl * k
        xt = xl.reshape(bl * sl, d)
        se = te.reshape(-1)
        sp = tp.reshape(-1).astype(xl.dtype)
        tok = (jnp.arange(t, dtype=jnp.int32) // k).astype(jnp.int32)
        dst = (se // e_l).astype(jnp.int32)
        eid = (se % e_l).astype(jnp.int32)

        # --- send-side: rank within destination shard, capacity-dropped ---
        order = jnp.argsort(dst, stable=True)
        dst_s, tok_s, eid_s, sp_s = dst[order], tok[order], eid[order], sp[order]
        counts = jnp.zeros((e_sh,), jnp.int32).at[dst_s].add(1)
        starts = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(t, dtype=jnp.int32) - starts[dst_s]
        keep = pos < cap_pair
        slot = jnp.where(keep, dst_s * cap_pair + pos, e_sh * cap_pair)
        send_x = (
            jnp.zeros((e_sh * cap_pair + 1, d), xl.dtype).at[slot].set(xt[tok_s])
        )[: e_sh * cap_pair]
        send_e = (
            jnp.full((e_sh * cap_pair + 1,), -1, jnp.int32).at[slot].set(eid_s)
        )[: e_sh * cap_pair]

        # --- all-to-all: tokens travel to their experts' shard -------------
        recv_x = jax.lax.all_to_all(
            send_x.reshape(e_sh, cap_pair, d), "model", 0, 0, tiled=False
        ).reshape(e_sh * cap_pair, d)
        recv_e = jax.lax.all_to_all(
            send_e.reshape(e_sh, cap_pair, 1), "model", 0, 0, tiled=False
        ).reshape(e_sh * cap_pair)

        # --- recv-side: group by local expert, capacity-dropped ------------
        r = e_sh * cap_pair
        valid = recv_e >= 0
        key = jnp.where(valid, recv_e, e_l)
        order2 = jnp.argsort(key, stable=True)
        re2 = recv_e[order2]
        counts2 = jnp.zeros((e_l + 1,), jnp.int32).at[jnp.where(valid, recv_e, e_l)].add(1)
        starts2 = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(counts2)[:-1]])
        pos2 = jnp.arange(r, dtype=jnp.int32) - starts2[jnp.where(re2 >= 0, re2, e_l)]
        keep2 = (re2 >= 0) & (pos2 < cap_local)
        slot2 = jnp.where(keep2, re2 * cap_local + pos2, e_l * cap_local)
        buf = (
            jnp.zeros((e_l * cap_local + 1, d), xl.dtype).at[slot2].set(recv_x[order2])
        )[: e_l * cap_local].reshape(e_l, cap_local, d)

        # --- expert FFN ----------------------------------------------------
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wig))
        h = h * jnp.einsum("ecd,edf->ecf", buf, wiu)
        y = jnp.einsum("ecf,efd->ecd", h, wo).reshape(e_l * cap_local, d)

        # --- route back (inverse permutations + reverse all-to-all) --------
        y_sorted = jnp.where(
            keep2[:, None], y[jnp.minimum(slot2, e_l * cap_local - 1)], 0
        )
        y_recv = jnp.zeros((r, d), xl.dtype).at[order2].set(y_sorted)
        y_send = jax.lax.all_to_all(
            y_recv.reshape(e_sh, cap_pair, d), "model", 0, 0, tiled=False
        ).reshape(e_sh * cap_pair, d)
        contrib = (
            jnp.where(keep[:, None], y_send[jnp.minimum(slot, e_sh * cap_pair - 1)], 0)
            * sp_s[:, None]
        )
        out_l = jnp.zeros((bl * sl, d), xl.dtype).at[tok_s].add(contrib)
        return out_l.reshape(bl, sl, d)

    spec_x = P(dp if dp else None, "model", None)
    spec_w = P("model", None, None)
    out = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(spec_x, spec_w, spec_w, spec_w, spec_x, spec_x),
        out_specs=spec_x,
    )(x, p["wi_gate"], p["wi_up"], p["wo"], top_e, top_p)

    if cfg.moe_num_shared:
        sp_ = p["shared"]
        hs = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, sp_["wi_gate"]))
        hs = hs * jnp.einsum("bsd,df->bsf", x, sp_["wi_up"])
        out = out + jnp.einsum("bsf,fd->bsd", hs, sp_["wo"])
    return out, aux.astype(jnp.float32)
