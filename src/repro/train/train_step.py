"""train_step / serve_step builders (the jitted top-level programs).

``build_train_step`` returns the function lowered by both the real training
loop (examples/train_lm.py) and the multi-pod dry-run. Structure:

    loss(values) -> grads -> [cast for all-reduce] -> clip -> optimizer

Microbatching (gradient accumulation) wraps the loss/grad in a lax.scan
over microbatch slices — the standard way to trade HBM for steps at large
global batch. The gradient all-reduce over the data axis is implicit in
GSPMD (params replicated over "data" unless FSDP); casting grads to
``grad_allreduce_dtype`` before they cross the data axis halves collective
bytes when set to bfloat16 (§Perf lever).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import RunConfig
from ..models.transformer import Model
from ..optim.optimizers import Optimizer, clip_by_global_norm
from .losses import lm_loss

__all__ = ["build_train_step", "build_prefill_step", "build_decode_step", "init_train_state"]


def init_train_state(model: Model, optimizer: Optimizer, seed: int = 0):
    from ..models.common import split_params

    values, _ = split_params(model.init(seed))
    return {"values": values, "opt": optimizer.init(values), "step": jnp.zeros((), jnp.int32)}


def build_train_step(model: Model, run_cfg: RunConfig, optimizer: Optimizer):
    cfg = model.cfg

    def loss_fn(values, batch):
        logits, aux, _ = model.forward(values, batch, remat=run_cfg.remat)
        # The model names its own parts (attention, mlp, head_loss); the
        # loss joins the head's scope.
        with jax.named_scope("head_loss"):
            loss, metrics = lm_loss(
                logits,
                batch["targets"],
                batch["loss_mask"],
                aux=aux,
                aux_weight=cfg.router_aux_weight if cfg.moe_num_experts else 0.0,
            )
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def compute_grads(values, batch):
        if run_cfg.microbatch and run_cfg.microbatch > 1:
            k = run_cfg.microbatch
            micro = jax.tree.map(
                lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]), batch
            )

            acc_dt = (
                jnp.dtype(run_cfg.grad_allreduce_dtype)
                if run_cfg.grad_allreduce_dtype
                else None
            )

            def acc(carry, mb):
                (l_acc, g_acc) = carry
                (l, m), g = grad_fn(values, mb)
                g = jax.tree.map(lambda a, b: a + b.astype(a.dtype), g_acc, g)
                return (l_acc + l, g), m

            # Accumulate in the param dtype (bf16 for big models) unless a
            # grad dtype is forced — an fp32 accumulator alone is 16 GB/dev
            # for kimi-k2-1t.
            zeros = jax.tree.map(
                lambda v: jnp.zeros(v.shape, acc_dt or v.dtype), values
            )
            (loss, grads), metrics = jax.lax.scan(acc, (jnp.zeros(()), zeros), micro)
            loss = loss / k
            grads = jax.tree.map(lambda g: g / k, grads)
            metrics = jax.tree.map(lambda m: m[-1], metrics)
        else:
            (loss, metrics), grads = grad_fn(values, batch)
        return loss, grads, metrics

    def train_step(state, batch):
        loss, grads, metrics = compute_grads(state["values"], batch)
        if run_cfg.grad_allreduce_dtype:
            dt = jnp.dtype(run_cfg.grad_allreduce_dtype)
            grads = jax.tree.map(lambda g: g.astype(dt), grads)
        with jax.named_scope("optimizer"):
            grads, gnorm = clip_by_global_norm(grads, run_cfg.grad_clip)
            new_values, new_opt = optimizer.update(
                grads, state["opt"], state["values"], state["step"]
            )
        metrics = dict(metrics, loss=loss, grad_norm=gnorm)
        return (
            {"values": new_values, "opt": new_opt, "step": state["step"] + 1},
            metrics,
        )

    return train_step


# ------------------------------------------------------------------ serving
def build_prefill_step(model: Model, max_len: int):
    """Full-prompt pass that builds the decode cache (sized to max_len)."""
    cfg = model.cfg

    def prefill(values, inputs):
        logits, _, caches = model.forward(values, inputs, want_cache=True)
        sized = []
        for (kind, count), cache in zip(cfg.segments(), caches):
            if kind in ("attn_mlp", "attn_dense_moe", "attn_moe", "shared_attn"):
                if kind == "shared_attn":
                    cache = jax.tree.map(lambda t: t[None], cache)
                k, v = cache["k"], cache["v"]  # (n, B, S, KVH, D)
                s = k.shape[2]
                s_c = min(max_len, cfg.window) if cfg.window else max_len
                tgt = lambda t: jnp.zeros(
                    t.shape[:2] + (s_c,) + t.shape[3:], t.dtype
                )
                if s_c >= s:
                    k_c = jax.lax.dynamic_update_slice_in_dim(tgt(k), k, 0, axis=2)
                    v_c = jax.lax.dynamic_update_slice_in_dim(tgt(v), v, 0, axis=2)
                else:
                    # rotating window layout: slot = position % window
                    pos = jnp.arange(s - s_c, s)
                    slots = jnp.mod(pos, s_c)
                    k_c = tgt(k).at[:, :, slots].set(k[:, :, pos])
                    v_c = tgt(v).at[:, :, slots].set(v[:, :, pos])
                if cfg.kv_cache_dtype == "int8":
                    from ..models.attention import quantize_kv

                    kq, ks = quantize_kv(k_c)
                    vq, vs = quantize_kv(v_c)
                    sized.append({"k": kq, "k_scale": ks, "v": vq, "v_scale": vs})
                else:
                    sized.append({"k": k_c, "v": v_c})
            else:
                sized.append(cache)  # recurrent state is already the cache
        return logits[:, -1:], sized

    return prefill


def build_decode_step(model: Model):
    def decode(values, caches, tokens, cache_pos):
        return model.decode_step(values, caches, tokens, cache_pos)

    return decode
