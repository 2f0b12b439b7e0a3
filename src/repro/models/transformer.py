"""Model assembly: block zoo + scan-over-layers segments + caches.

A model is a sequence of *segments* (run-length-encoded runs of identical
block kinds, ``ModelConfig.segments()``). Each segment's parameters are
stacked on a leading "layers" axis and executed with ``jax.lax.scan`` —
compile time and HLO size stay O(1 block) regardless of depth, which is
what makes the 512-device dry-run (and real-world compiles at depth 61)
tractable. Zamba2's *shared* attention block holds one parameter set
applied at every site (segments of kind "shared_attn" reference it).

Decode caches mirror the segment structure: stacked KV tensors for
attention segments (rotating window buffers when ``cfg.window`` is set, so
zamba2's 500k-context decode holds only the window), SSD/mLSTM/sLSTM state
dicts for the recurrent kinds.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..parallel.axes import shard
from .attention import attention_block, decode_attention_block, init_attention
from .common import Param, RngStream, rms_norm
from .mamba2 import init_mamba2, mamba2_block, mamba2_decode, mamba2_state_shape
from .mlp import init_mlp, mlp_block
from .moe import init_moe, moe_block, moe_block_a2a
from .xlstm import (
    init_mlstm,
    init_slstm,
    mlstm_block,
    mlstm_decode,
    mlstm_state_shape,
    slstm_block,
    slstm_decode,
    slstm_state_shape,
)

__all__ = ["Model", "build_model"]

_ATTN_KINDS = ("attn_mlp", "attn_dense_moe", "attn_moe", "shared_attn")


# ----------------------------------------------------------------- blocks
def _init_block(kind: str, rng: RngStream, cfg: ModelConfig, dtype):
    zeros = lambda: Param(jnp.zeros((cfg.d_model,), dtype), ("embed",))
    if kind in ("attn_mlp", "shared_attn"):
        return {
            "ln1": zeros(),
            "attn": init_attention(rng, cfg, dtype),
            "ln2": zeros(),
            "mlp": init_mlp(rng, cfg, dtype),
        }
    if kind == "attn_dense_moe":
        return {
            "ln1": zeros(),
            "attn": init_attention(rng, cfg, dtype),
            "ln2": zeros(),
            "mlp": init_mlp(rng, cfg, dtype, d_ff=cfg.moe_dense_ff or cfg.d_ff),
        }
    if kind == "attn_moe":
        return {
            "ln1": zeros(),
            "attn": init_attention(rng, cfg, dtype),
            "ln2": zeros(),
            "moe": init_moe(rng, cfg, dtype),
        }
    if kind == "mamba2":
        return {"ln": zeros(), "mixer": init_mamba2(rng, cfg, dtype)}
    if kind == "mlstm":
        return {"ln": zeros(), "cell": init_mlstm(rng, cfg, dtype)}
    if kind == "slstm":
        return {"ln": zeros(), "cell": init_slstm(rng, cfg, dtype)}
    raise ValueError(kind)


def _no_aux():
    """The auxiliary outputs a block adds up: the MoE balance loss, the
    (token, expert) pairs its held experts computed and whether its expert
    share took the full-size dispatch buffer (``moe_overflow``)."""
    return {"balance": jnp.zeros((), jnp.float32), "expert_pairs": jnp.zeros((), jnp.int32),
            "moe_overflow": jnp.zeros((), jnp.int32)}


def _apply_block(kind, p, x, cfg, state=None):
    """Full-sequence block application.

    Returns (x_out, cache_entry, aux) with aux as ``_no_aux``. cache_entry
    is the KV (for attn kinds) or the final recurrent state (ssm kinds);
    None in pure train mode consumers (it is still produced — XLA DCEs it
    when unused).
    """
    aux = _no_aux()
    if kind in ("attn_mlp", "attn_dense_moe", "shared_attn", "attn_moe"):
        # Named scopes mark the block's parts in the compiled step's op
        # metadata, so a device trace can split the step by them.
        with jax.named_scope("attention"):
            h, kv = attention_block(p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps), cfg)
        x = x + h
        if kind != "attn_moe":
            with jax.named_scope("mlp"):
                h = mlp_block(p["mlp"], rms_norm(x, p["ln2"], cfg.norm_eps))
            return x + h, kv, aux
        moe_fn = moe_block_a2a if cfg.moe_impl == "a2a" else moe_block
        with jax.named_scope("moe"):
            h, moe_aux, pairs = moe_fn(p["moe"], rms_norm(x, p["ln2"], cfg.norm_eps), cfg)
        return x + h, kv, dict(moe_aux, expert_pairs=pairs)
    if kind == "mamba2":
        h, st = mamba2_block(p["mixer"], rms_norm(x, p["ln"], cfg.norm_eps), cfg,
                             init_state=state)
        return x + h, st, aux
    if kind == "mlstm":
        h, st = mlstm_block(p["cell"], rms_norm(x, p["ln"], cfg.norm_eps), cfg,
                            init_state=state)
        return x + h, st, aux
    if kind == "slstm":
        h, st = slstm_block(p["cell"], rms_norm(x, p["ln"], cfg.norm_eps), cfg,
                            init_state=state)
        return x + h, st, aux
    raise ValueError(kind)


def _decode_block(kind, p, x, cache, cache_pos, cfg):
    """One-token block application against the cache. Returns (x, cache)."""
    if kind in ("attn_mlp", "attn_dense_moe", "attn_moe", "shared_attn"):
        quant = cfg.kv_cache_dtype == "int8"
        h, ck, cv = decode_attention_block(
            p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps),
            cache["k"], cache["v"], cache_pos, cfg,
            k_scale=cache.get("k_scale"), v_scale=cache.get("v_scale"),
        )
        x = x + h
        if kind == "attn_moe":
            h, _, _ = moe_block(p["moe"], rms_norm(x, p["ln2"], cfg.norm_eps), cfg)
        else:
            h = mlp_block(p["mlp"], rms_norm(x, p["ln2"], cfg.norm_eps))
        if quant:
            return x + h, {"k": ck[0], "k_scale": ck[1], "v": cv[0], "v_scale": cv[1]}
        return x + h, {"k": ck, "v": cv}
    if kind == "mamba2":
        h, st = mamba2_decode(p["mixer"], rms_norm(x, p["ln"], cfg.norm_eps), cache, cfg)
        return x + h, st
    if kind == "mlstm":
        h, st = mlstm_decode(p["cell"], rms_norm(x, p["ln"], cfg.norm_eps), cache, cfg)
        return x + h, st
    if kind == "slstm":
        h, st = slstm_decode(p["cell"], rms_norm(x, p["ln"], cfg.norm_eps), cache, cfg)
        return x + h, st
    raise ValueError(kind)


def _cache_shapes(kind, cfg, batch, max_len, cdt):
    """(shape, dtype, logical_axes) tree for one block's cache entry.

    KV caches live in the compute dtype; recurrent states (SSD / mLSTM /
    sLSTM) live in fp32 — they integrate over the whole sequence, and the
    decode functions keep them fp32 so serve-loop lowering is dtype-stable.
    The axes (with a leading 'layers') drive the cache sharding in the
    dry-run/serving launchers.
    """
    hd = cfg.head_dim_
    f32 = jnp.float32
    if kind in _ATTN_KINDS:
        s = min(max_len, cfg.window) if cfg.window else max_len
        shp = (batch, s, cfg.num_kv_heads, hd)
        ax = ("batch", None, "kv_heads", None)
        if cfg.kv_cache_dtype == "int8":
            sshp = (batch, s, cfg.num_kv_heads, 1)
            return {
                "k": (shp, jnp.int8, ax),
                "k_scale": (sshp, jnp.bfloat16, ax),
                "v": (shp, jnp.int8, ax),
                "v_scale": (sshp, jnp.bfloat16, ax),
            }
        return {"k": (shp, cdt, ax), "v": (shp, cdt, ax)}
    if kind == "mamba2":
        shp = mamba2_state_shape(cfg, batch)
        return {
            "ssm": (shp["ssm"], f32, ("batch", "inner_heads", None, None)),
            "conv": (shp["conv"], cdt, ("batch", None, "inner_flat")),
        }
    if kind == "mlstm":
        shp = mlstm_state_shape(cfg, batch)
        return {
            "C": (shp["C"], f32, ("batch", "inner_heads", None, None)),
            "n": (shp["n"], f32, ("batch", "inner_heads", None)),
        }
    if kind == "slstm":
        shp = slstm_state_shape(cfg, batch)
        return {k: (v, f32, ("batch", "embed_state")) for k, v in shp.items()}
    raise ValueError(kind)


def _stack_params(blocks: list[dict]) -> dict:
    """Stack per-layer Param trees onto a leading 'layers' axis."""
    def stack(*ps):
        return Param(
            jnp.stack([p.value for p in ps]), ("layers", *ps[0].axes)
        )
    return jax.tree.map(stack, *blocks, is_leaf=lambda x: isinstance(x, Param))


# ------------------------------------------------------------------ model
@dataclasses.dataclass
class Model:
    cfg: ModelConfig

    # ------------------------------------------------------------- init
    def init(self, seed: int = 0):
        """Returns a Param tree (use split_params to get values + axes)."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.param_dtype)
        rng = RngStream(seed)
        d = cfg.d_model
        params: dict = {
            "embed": Param(
                (jax.random.normal(rng.next(), (cfg.vocab_size, d), jnp.float32) * 0.02
                 ).astype(dtype),
                ("vocab", "embed"),
            ),
            "final_norm": Param(jnp.zeros((d,), dtype), ("embed",)),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = Param(
                (jax.random.normal(rng.next(), (d, cfg.vocab_size), jnp.float32)
                 / d**0.5).astype(dtype),
                ("embed", "vocab"),
            )
        if cfg.frontend != "none":
            params["frontend"] = Param(
                (jax.random.normal(rng.next(), (cfg.frontend_dim, d), jnp.float32)
                 / cfg.frontend_dim**0.5).astype(dtype),
                (None, "embed"),
            )
        segs = []
        shared = None
        for kind, count in cfg.segments():
            if kind == "shared_attn":
                if shared is None:
                    shared = _init_block(kind, rng, cfg, dtype)
                segs.append({})  # placeholder; params live in params["shared_attn"]
            else:
                blocks = [_init_block(kind, rng, cfg, dtype) for _ in range(count)]
                segs.append(_stack_params(blocks))
        params["segments"] = segs
        if shared is not None:
            params["shared_attn"] = shared
        return params

    # ----------------------------------------------------------- embedding
    def _embed_inputs(self, values, inputs):
        cfg = self.cfg
        cdt = jnp.dtype(cfg.compute_dtype)
        embed = values["embed"]
        if cfg.frontend == "patch":
            pe = jnp.einsum(
                "bpf,fd->bpd", inputs["patch_embeds"].astype(cdt),
                values["frontend"].astype(cdt),
            )
            tok = jnp.take(embed, inputs["tokens"], axis=0).astype(cdt)
            x = jnp.concatenate([pe, tok], axis=1)
        elif cfg.frontend == "frame":
            x = jnp.einsum(
                "bsf,fd->bsd", inputs["frames"].astype(cdt),
                values["frontend"].astype(cdt),
            )
        else:
            x = jnp.take(embed, inputs["tokens"], axis=0).astype(cdt)
        return shard(x, "batch", None, "embed_act")

    def _logits(self, values, x):
        cfg = self.cfg
        with jax.named_scope("head_loss"):
            x = rms_norm(x, values["final_norm"], cfg.norm_eps)
            head = (
                values["embed"].T if cfg.tie_embeddings else values["lm_head"]
            )
            logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
        return shard(logits, "batch", None, "vocab")

    # ------------------------------------------------------------ forward
    def forward(self, values, inputs, *, remat: str = "none", want_cache: bool = False):
        """Full-sequence pass. Returns (logits, aux, cache_list); aux sums
        the blocks' ``balance`` loss, ``expert_pairs`` and ``moe_overflow``."""
        cfg = self.cfg
        x = self._embed_inputs(values, inputs)
        aux_total = _no_aux()
        caches = []
        for seg, seg_vals in zip(cfg.segments(), values["segments"]):
            kind, count = seg
            if kind == "shared_attn":
                x, kv, aux = _apply_block(kind, values["shared_attn"], x, cfg)
                caches.append(self._kv_to_cache(kv) if want_cache else None)
                aux_total = jax.tree.map(jnp.add, aux_total, aux)
                continue

            def body(carry, lp, kind=kind):
                xx, aux_acc = carry
                xx, cache, aux = _apply_block(kind, lp, xx, cfg)
                # Megatron-SP: with run_cfg.seq_parallel the "seq_act" rule
                # maps to "model" and the residual stream lives sequence-
                # sharded between blocks (all-gather in, reduce-scatter out).
                xx = shard(xx, "batch", "seq_act", "embed_act")
                return (xx, jax.tree.map(jnp.add, aux_acc, aux)), (
                    self._kv_to_cache(cache) if kind in _ATTN_KINDS else cache
                )

            if remat == "full":
                body = jax.checkpoint(body)
            elif remat == "dots":
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                )
            (x, aux_total), seg_cache = jax.lax.scan(body, (x, aux_total), seg_vals)
            caches.append(seg_cache if want_cache else None)
        return self._logits(values, x), aux_total, caches

    def _kv_to_cache(self, kv):
        k, v = kv
        return {"k": k, "v": v}

    # ------------------------------------------------------------- decode
    def cache_specs(self, batch: int, max_len: int, dtype=None):
        """Cache shape/dtype pytree (mirrors segment structure)."""
        cfg = self.cfg
        cdt = dtype or jnp.dtype(cfg.compute_dtype)
        is_entry = lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)
        specs = []
        for kind, count in cfg.segments():
            shapes = _cache_shapes(kind, cfg, batch, max_len, cdt)
            lead = 1 if kind == "shared_attn" else count
            specs.append(
                jax.tree.map(
                    lambda sd: jax.ShapeDtypeStruct((lead, *sd[0]), sd[1]),
                    shapes,
                    is_leaf=is_entry,
                )
            )
        return specs

    def cache_axes(self, batch: int, max_len: int, tp: int | None = None):
        """Logical axes for every cache leaf (same treedef as cache_specs).

        When the KV-head count does not divide the tensor-parallel degree
        (starcoder2/tinyllama: kv=4 vs tp=16), KV caches shard on the
        *sequence* dim instead ("kv_seq" -> model): flash-decoding-style
        split-K, which XLA realises as a partial-softmax reduction. This
        keeps e.g. starcoder2's decode_32k cache at ~0.5 GB/device instead
        of a replicated ~10 GB/device.
        """
        cfg = self.cfg
        is_entry = lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)
        split_k = tp is not None and cfg.num_kv_heads % tp != 0
        out = []
        for kind, count in cfg.segments():
            shapes = _cache_shapes(kind, cfg, batch, max_len, jnp.bfloat16)
            axes_tree = jax.tree.map(
                lambda sd: ("layers", *sd[2]), shapes, is_leaf=is_entry
            )
            if split_k and kind in _ATTN_KINDS:
                axes_tree = jax.tree.map(
                    lambda a: ("layers", "batch", "kv_seq", None, None),
                    axes_tree,
                    is_leaf=lambda x: isinstance(x, tuple),
                )
            out.append(axes_tree)
        return out

    def init_cache(self, batch: int, max_len: int, dtype=None):
        """Zero cache pytree (mirrors segment structure)."""
        return jax.tree.map(
            lambda sds: jnp.zeros(sds.shape, sds.dtype),
            self.cache_specs(batch, max_len, dtype),
        )

    def decode_step(self, values, caches, tokens, cache_pos):
        """One token for the whole batch. tokens: (B, 1) int32."""
        cfg = self.cfg
        cdt = jnp.dtype(cfg.compute_dtype)
        x = jnp.take(values["embed"], tokens, axis=0).astype(cdt)
        new_caches = []
        for seg, seg_vals, cache in zip(cfg.segments(), values["segments"], caches):
            kind, count = seg
            if kind == "shared_attn":
                c0 = jax.tree.map(lambda t: t[0], cache)
                x, c0 = _decode_block(kind, values["shared_attn"], x, c0, cache_pos, cfg)
                new_caches.append(jax.tree.map(lambda t: t[None], c0))
                continue

            def body(xx, lp_cache, kind=kind):
                lp, c = lp_cache
                xx, c = _decode_block(kind, lp, xx, c, cache_pos, cfg)
                return xx, c

            x, new_c = jax.lax.scan(body, x, (seg_vals, cache))
            new_caches.append(new_c)
        return self._logits(values, x), new_caches


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
