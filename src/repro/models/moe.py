"""Fine-grained MoE: shared + routed experts, top-k token-choice routing.

Follows DeepSeekMoE [arXiv:2401.06066] (deepseek-moe-16b: 2 shared + 64
routed, top-6; DeepSeek-V2-Lite the same with softmax scores left
unnormalised and a sequence-wise balance loss) and the same structure at
Kimi-K2 scale (384 routed, top-8).

**Expert share** (``moe_block`` on one device): the layer is told which
experts it holds (``cfg.moe_experts_held`` from ``cfg.moe_expert_offset``),
routes every token over all ``moe_num_experts``, and computes its own
experts' part of the result for the tokens routed to them, as one rank of
an expert-parallel group does (the exchange between ranks is not part of
it). It is dropless: the (token, expert) pairs are sorted by held expert,
the other ranks' pairs last, the held pairs' tokens are gathered into a
dispatch buffer, and the expert matmuls are grouped
(``jax.lax.ragged_dot``) over the held experts' runs. The buffer has 1.5x
the rows that even routing gives the held experts (``_buffer_rows``); a
layer whose held pairs outnumber them takes a buffer of every pair, so no
pair is left out for capacity, and reports it (``moe_overflow``). With
every expert held the buffer has every pair and it is the whole layer.
Each expert's weights are drawn from one key per matrix folded with the
expert's global id, so a share holds exactly the uncut model's experts of
its ids.

**Capacity dispatch** (``moe_block`` under a multi-device sharding
context, the sharded dry-run): sort-based with capacity dropping, grouped
GShard-style by batch row into per-expert slots (``cap = seq·k·cf / E``),
the (B, E, cap, d) buffer sharded B over data and E over model;
``moe_block_a2a`` is its shard_map all-to-all variant.

Routing (fp32 scores): softmax over all experts, top-k, optionally
renormalised (``moe_norm_topk``). The balance
loss is Switch-style (``moe_aux="switch"``) or DeepSeek-V2's per sequence
(``"seq"``). Tracing records the dispatch as a ``moe.route`` instant, with
the buffer's rows beside the rows a buffer of every pair would have.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..obs import trace
from ..parallel.axes import current_ctx, shard
from .common import Param, scaled_init

__all__ = ["init_moe", "moe_block", "moe_block_a2a", "route"]


def _expert_draw(key, ids, shape, dtype, fan_in):
    """One (d, f) matrix per global expert id, each from ``key`` folded
    with its id."""
    draw = lambda i: jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
    return (jax.vmap(draw)(ids) / fan_in ** 0.5).astype(dtype)


def init_moe(rng, cfg, dtype):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe_num_experts
    ids = cfg.moe_expert_offset + jnp.arange(cfg.experts_held, dtype=jnp.uint32)
    p = {
        "router": Param(scaled_init(rng.next(), (d, e), dtype), ("embed", None)),
        "wi_gate": Param(_expert_draw(rng.next(), ids, (d, f), dtype, d),
                         ("experts", "embed", None)),
        "wi_up": Param(_expert_draw(rng.next(), ids, (d, f), dtype, d),
                       ("experts", "embed", None)),
        "wo": Param(_expert_draw(rng.next(), ids, (f, d), dtype, f),
                    ("experts", None, "embed")),
    }
    if cfg.moe_num_shared:
        sf = f * cfg.moe_num_shared
        p["shared"] = {
            "wi_gate": Param(scaled_init(rng.next(), (d, sf), dtype), ("embed", "mlp")),
            "wi_up": Param(scaled_init(rng.next(), (d, sf), dtype), ("embed", "mlp")),
            "wo": Param(scaled_init(rng.next(), (sf, d), dtype, fan_in=sf), ("mlp", "embed")),
        }
    return p


def route(p, x, cfg):
    """Scores over all experts for x (B, S, d): ``(top_p, top_e, aux)``,
    the k gate weights and expert ids per token (B, S, k) and the balance
    loss. Scores are fp32 (the router's product accumulates in fp32)."""
    b, s, _ = x.shape
    e, k = cfg.moe_num_experts, cfg.moe_top_k
    logits = jnp.einsum("bsd,de->bse", x, p["router"], preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)  # (b, s, k)
    if cfg.moe_norm_topk:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    if cfg.moe_aux == "seq":
        # DeepSeek-V2: per sequence, each expert's picks among the
        # sequence's s*k, times E / (s*k), against its mean score over the
        # sequence, summed over experts; mean over sequences.
        rows = jnp.arange(b)[:, None]
        picks = jnp.zeros((b, e), jnp.float32).at[rows, top_e.reshape(b, s * k)].add(1.0)
        aux = jnp.mean(jnp.sum(picks * (e / (s * k)) * probs.mean(axis=1), axis=-1))
    else:
        # Switch (eq. 4-6): top-1 share of the batch against the mean score
        t = b * s
        density = jnp.zeros((e,), jnp.float32).at[top_e[..., 0].reshape(-1)].add(1.0) / t
        aux = e * jnp.sum(density * probs.reshape(t, e).mean(axis=0))
    return top_p, top_e, aux.astype(jnp.float32)


def _shared_experts(p, x, cfg, out):
    if not cfg.moe_num_shared:
        return out
    sp_ = p["shared"]
    hs = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, sp_["wi_gate"]))
    hs = hs * jnp.einsum("bsd,df->bsf", x, sp_["wi_up"])
    return out + jnp.einsum("bsf,fd->bsd", hs, sp_["wo"])


def _aux(balance, overflow=0):
    return {"balance": balance, "moe_overflow": jnp.asarray(overflow, jnp.int32)}


def moe_block(p, x, cfg):
    """x: (B, S, d) -> (out, aux, pairs): ``aux`` holds the balance loss
    (``balance``) and ``moe_overflow``, 1 where the share's held pairs
    outnumbered its compact buffer and took the full-size one; ``pairs``
    counts the (token, expert) pairs the held experts computed. The expert
    share, dropless, on one device; the capacity dispatch under a sharding
    context over more than one device."""
    ctx = current_ctx()
    if ctx is not None and ctx.mesh.size > 1:
        return _moe_capacity(p, x, cfg)
    return _moe_share(p, x, cfg)


#: Slots of the expert share's compact dispatch buffer per held pair that
#: even routing would give: a layer whose held pairs outnumber the slots
#: takes the full-size buffer instead. Of 1.25 to 2.5, 1.5 gave the lowest
#: mean step on the V2-Lite cell's held pairs per layer (PERF.md §6).
_HEADROOM = 1.5


def _buffer_rows(pairs: int, held: int, routed: int) -> int:
    """Static slots of the share's dispatch buffer: ``_HEADROOM`` times the
    held experts' even share of the ``pairs``, rounded up to 512 rows, at
    most every pair (so every pair where every expert is held)."""
    return min(pairs, math.ceil(_HEADROOM * pairs * held / routed / 512) * 512)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _take_rows(t, x, tok):
    """The dispatch buffer: row ``r`` is ``x[tok[r]]`` (x has ``t`` rows).
    Its gradient is :func:`_add_rows`."""
    return jnp.take(x, tok, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _add_rows(t, v, tok):
    """The combine: each of the ``t`` tokens' sum of its rows of ``v``,
    added in float32; the transpose of :func:`_take_rows`. A scatter-add of
    the buffer's rows, which on a v5e beat a gather of every pair's row
    (PERF.md §6)."""
    v32 = v.astype(jnp.float32)
    return jnp.zeros((t, v.shape[1]), jnp.float32).at[tok].add(v32).astype(v.dtype)


def _take_rows_fwd(t, x, tok):
    return _take_rows(t, x, tok), tok


def _take_rows_bwd(t, tok, g):
    return _add_rows(t, g, tok), None


def _add_rows_fwd(t, v, tok):
    return _add_rows(t, v, tok), tok


def _add_rows_bwd(t, tok, g):
    return _take_rows(t, g, tok), None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)
_add_rows.defvjp(_add_rows_fwd, _add_rows_bwd)


def _share_rows(experts, x, w, order, sizes, rows):
    """The held experts' part of each token's output, through a dispatch
    buffer of the first ``rows`` pairs in ``order`` (every held pair must
    be among them). x (t, d); w (t, k) the pairs' gate weights, zero for
    pairs held elsewhere; ``order`` the pairs sorted by held expert, the
    others last; ``sizes`` the held experts' pair counts."""
    t, k = w.shape
    tok = order[:rows] // k
    held_rows = (jnp.arange(rows) < jnp.sum(sizes))[:, None]
    xs = _take_rows(t, x, tok)
    with jax.named_scope("experts"):
        # what ragged_dot leaves in the rows past the groups is undefined
        # (the TPU's kernel does not write them), so those rows are zeroed
        # on the way in and out, which zeroes them in the backward pass too
        xs = jnp.where(held_rows, xs, 0)
        h = jax.nn.silu(jax.lax.ragged_dot(xs, experts["wi_gate"], sizes))
        h = h * jax.lax.ragged_dot(xs, experts["wi_up"], sizes)
        ys = jnp.where(held_rows, jax.lax.ragged_dot(h, experts["wo"], sizes), 0)
    wr = jnp.take(w.reshape(t * k), order[:rows])[:, None].astype(ys.dtype)
    return _add_rows(t, ys * wr, tok)


def _overflows(sizes, rows):
    return jnp.sum(sizes) > rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _share(rows, experts, x, w, order, sizes):
    """:func:`_share_rows` through ``rows`` slots, or through one for every
    pair where the held pairs outnumber them: dropless either way.

    The backward pass derives the taken branch's VJP again from the inputs,
    so it runs that branch's forward again. Under full remat the layer does
    that anyway; under ``remat="dots"`` plain autodiff recomputes the
    grouped matmuls too, since ``ragged_dot`` is not a ``dot_general``
    (the V2-Lite cell's step compiled for a v5e, either policy: the same 24
    grouped-matmul ops as a plain ``lax.cond``, no more FLOPs, and 4.6 GB
    fewer temporaries, PERF.md §6)."""
    return jax.lax.cond(_overflows(sizes, rows),
                        functools.partial(_share_rows, rows=w.size),
                        functools.partial(_share_rows, rows=rows),
                        experts, x, w, order, sizes)


def _share_fwd(rows, experts, x, w, order, sizes):
    return _share(rows, experts, x, w, order, sizes), (experts, x, w, order, sizes)


def _share_bwd(rows, res, g):
    # The branch's own VJP, derived again from its inputs: ``cond``'s
    # linearisation would carry the full-size branch's residuals, zeroed,
    # through the compact one (the V2-Lite cell's step under full remat,
    # compiled for a v5e: 12.2 GB of temporaries against 7.5 GB).
    experts, x, w, order, sizes = res

    def pull(n_rows):
        def f(experts, x, w):
            _, vjp = jax.vjp(lambda e, x, w: _share_rows(e, x, w, order, sizes, n_rows),
                             experts, x, w)
            return vjp(g)
        return f

    grads = jax.lax.cond(_overflows(sizes, rows), pull(w.size), pull(rows), experts, x, w)
    return (*grads, None, None)


_share.defvjp(_share_fwd, _share_bwd)


def _moe_share(p, x, cfg):
    b, s, d = x.shape
    e, k, held = cfg.moe_num_experts, cfg.moe_top_k, cfg.experts_held
    t = b * s
    rows = _buffer_rows(t * k, held, e)
    top_p, top_e, aux = route(p, x, cfg)
    trace.instant("moe.route", "compute", path="share", routed=e, held=held,
                  first=cfg.moe_expert_offset, top_k=k, rows=rows, rows_full=t * k,
                  dropped=0)

    # (token, expert) pairs in token order -> sorted by held expert, the
    # pairs of experts held elsewhere last (their group, ``held``, is not
    # computed): the held pairs fill the buffer's first rows
    local = top_e.reshape(t * k) - cfg.moe_expert_offset
    mine = (local >= 0) & (local < held)
    group = jnp.where(mine, local, held).astype(jnp.int32)
    order = jnp.argsort(group, stable=True).astype(jnp.int32)       # row -> pair
    sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
    w = jnp.where(mine, top_p.reshape(t * k), 0.0).reshape(t, k)
    experts = {name: p[name] for name in ("wi_gate", "wi_up", "wo")}
    x2 = x.reshape(t, d)
    if rows == t * k:
        # every pair fits (every expert held, or a small layer): no choice
        out, overflow = _share_rows(experts, x2, w, order, sizes, rows), 0
    else:
        out = _share(rows, experts, x2, w, order, sizes)
        overflow = _overflows(sizes, rows)
    out = _shared_experts(p, x, cfg, out.reshape(b, s, d))
    return out, _aux(aux, overflow), jnp.sum(sizes)


def _moe_capacity(p, x, cfg):
    """The capacity dispatch (module docstring); all experts held."""
    b, s, d = x.shape
    e, k = cfg.moe_num_experts, cfg.moe_top_k
    cap = max(int(s * k * cfg.capacity_factor / e), 1)

    top_p, top_e, aux = route(p, x, cfg)
    trace.instant("moe.route", "compute", path="capacity", routed=e, held=e,
                  first=0, top_k=k, rows=b * e * cap, rows_full=b * s * k,
                  dropped="unknown")

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

    out = _shared_experts(p, x, cfg, out)
    return out, _aux(aux), jnp.sum(s2v.astype(jnp.int32))


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
    output equals the capacity dispatch of :func:`moe_block` (equivalence-tested).

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
    top_p, top_e, aux = route(p, x, cfg)
    trace.instant("moe.route", "compute", path="a2a", routed=e, held=e_l,
                  first=0, top_k=k, rows=e_sh * cap_pair, rows_full=b * s * k,
                  dropped="unknown")

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
        kept = jax.lax.psum(jnp.sum(keep2.astype(jnp.int32)), tuple(mesh.axis_names))
        return out_l.reshape(bl, sl, d), kept

    spec_x = P(dp if dp else None, "model", None)
    spec_w = P("model", None, None)
    out, pairs = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(spec_x, spec_w, spec_w, spec_w, spec_x, spec_x),
        out_specs=(spec_x, P()),
    )(x, p["wi_gate"], p["wi_up"], p["wo"], top_e, top_p)

    out = _shared_experts(p, x, cfg, out)
    return out, _aux(aux), pairs
