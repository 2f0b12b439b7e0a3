"""Plain float32 reference of a DeepSeek-V2 decoder and its training.

Written from the published description (DeepSeek-V2, arXiv:2405.04434,
and its released ``modeling_deepseek.py``; YaRN, arXiv:2309.00071): a
pre-norm decoder whose attention is multi-head latent attention without q
compression, whose first ``first_k_dense_replace`` layers carry a SwiGLU
MLP and the rest a DeepSeekMoE layer (softmax scores over every routed
expert, greedy top-k, shared experts, a sequence-wise balance loss), under
the optimizer the configuration file states. It imports nothing of the
program under test; the pieces it shares with the dense decoder (products,
RMSNorm, head and loss, Adafactor's moments) come from
``bench/reference/dense_decoder.py``.

Of the routed experts it holds the configuration's share: ``n_routed_experts``
experts from ``expert_parallel.first_expert``, the router over all the
published experts. Each held expert's part is computed for every token,
weighted by the token's gate weight for it, which is zero unless the
expert is among the token's top-k: a dense sum over the held experts, not
a dispatch.

Departures, each the same in the program: rotary position rotates the two
halves of the 64-wide rotary part, where the released code interleaves
pairs (a fixed permutation of the rotary columns of W_q and W_kva, under
random init the same model); weights are drawn from the seed as the
configuration file states, each routed expert's matrices from one key per
matrix folded with the expert's global id.

Every matrix product runs at ``Precision.HIGHEST``; ``precision="int8"``
is the control, every operand of every product rounded to int8 first. The
reference runs layer by layer and in blocks of rows, each layer's backward
pass recomputing its forward pass, the attention in blocks of queries.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import dense_decoder as dense
from .dense_decoder import F32, diff_norm  # noqa: F401  (diff_norm: the harness's)

#: Per-layer parameter names, in the order the init scheme draws them.
ATTN_DRAWS = ("attn.wq", "attn.wkv_a", "attn.wkv_b", "attn.wo")
MLP_DRAWS = ("mlp.wi_gate", "mlp.wi_up", "mlp.wo")
MOE_DRAWS = ("moe.router", "moe.wi_gate", "moe.wi_up", "moe.wo",
             "moe.shared.wi_gate", "moe.shared.wi_up", "moe.shared.wo")
#: Drawn per routed expert from one key folded with the expert's id.
EXPERT_DRAWS = ("moe.wi_gate", "moe.wi_up", "moe.wo")
#: Per-layer RMSNorm scales (the kv latent's too), started at 0.
LAYER_NORMS = ("ln1", "ln2", "attn.kv_norm")


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    dense_ff: int
    expert_ff: int
    experts: int          # routed over (the router's width)
    held: int             # held here
    first: int            # global id of the first held expert
    top_k: int
    shared: int
    dense_layers: int
    layers: int
    vocab: int
    eps: float
    theta: float
    yarn_factor: float
    yarn_original: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float
    norm_topk: bool
    aux_alpha: float
    dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        rope = cfg["rope_scaling"]
        return cls(
            d=int(cfg["hidden_size"]), heads=int(cfg["num_attention_heads"]),
            kv_rank=int(cfg["kv_lora_rank"]), nope=int(cfg["qk_nope_head_dim"]),
            rope=int(cfg["qk_rope_head_dim"]), v=int(cfg["v_head_dim"]),
            dense_ff=int(cfg["intermediate_size"]),
            expert_ff=int(cfg["moe_intermediate_size"]),
            experts=int(cfg["published"]["n_routed_experts"]),
            held=int(cfg["n_routed_experts"]),
            first=int(cfg["expert_parallel"]["first_expert"]),
            top_k=int(cfg["num_experts_per_tok"]), shared=int(cfg["n_shared_experts"]),
            dense_layers=int(cfg["first_k_dense_replace"]),
            layers=int(cfg["num_hidden_layers"]), vocab=int(cfg["vocab_size"]),
            eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
            yarn_factor=float(rope["factor"]),
            yarn_original=int(rope["original_max_position_embeddings"]),
            beta_fast=float(rope["beta_fast"]), beta_slow=float(rope["beta_slow"]),
            mscale=float(rope["mscale"]), mscale_all_dim=float(rope["mscale_all_dim"]),
            norm_topk=bool(cfg["norm_topk_prob"]),
            aux_alpha=float(cfg["aux_loss_alpha"]), dtype=cfg["torch_dtype"],
        )

    def is_moe(self, layer: int) -> bool:
        return layer >= self.dense_layers

    def segment(self, layer: int) -> int:
        """The program stacks the dense layers and the MoE layers apart."""
        return int(self.is_moe(layer))

    def shapes(self, layer: int) -> dict:
        d, h = self.d, self.heads
        out = {
            "attn.wq": (d, h * (self.nope + self.rope)),
            "attn.wkv_a": (d, self.kv_rank + self.rope),
            "attn.wkv_b": (self.kv_rank, h * (self.nope + self.v)),
            "attn.wo": (h * self.v, d),
        }
        if not self.is_moe(layer):
            f = self.dense_ff
            out.update({"mlp.wi_gate": (d, f), "mlp.wi_up": (d, f), "mlp.wo": (f, d)})
            return out
        f, sf = self.expert_ff, self.expert_ff * self.shared
        out.update({"moe.router": (d, self.experts),
                    "moe.wi_gate": (d, f), "moe.wi_up": (d, f), "moe.wo": (f, d),
                    "moe.shared.wi_gate": (d, sf), "moe.shared.wi_up": (d, sf),
                    "moe.shared.wo": (sf, d)})
        return out

    def draws(self, layer: int) -> tuple:
        return ATTN_DRAWS + (MOE_DRAWS if self.is_moe(layer) else MLP_DRAWS)


# ------------------------------------------------------------------ init
def init_draws(dims: Dims, seed: int):
    """Yield ``(name, layer, array)`` for every drawn weight, in draw order.

    Each draw is rounded to the stored type and held as float32. The key
    chain is ``key, sub = split(key)`` once per draw; a routed expert's
    matrix is drawn from ``fold_in(sub, expert id)``, stacked over the held
    experts.
    """
    key = jax.random.PRNGKey(seed)

    def sub():
        nonlocal key
        key, out = jax.random.split(key)
        return out

    yield "embed", None, dense._draw(sub(), (dims.vocab, dims.d), 0.02, True, dims.dtype)
    yield "lm_head", None, dense._draw(sub(), (dims.d, dims.vocab), dims.d ** 0.5,
                                       False, dims.dtype)
    for layer in range(dims.layers):
        shapes = dims.shapes(layer)
        for name in dims.draws(layer):
            shape = shapes[name]
            k = sub()
            if name in EXPERT_DRAWS:
                ids = range(dims.first, dims.first + dims.held)
                value = jnp.stack([
                    dense._draw(jax.random.fold_in(k, i), shape, math.sqrt(shape[0]),
                                False, dims.dtype) for i in ids])
            else:
                value = dense._draw(k, shape, math.sqrt(shape[0]), False, dims.dtype)
            yield name, layer, value


def init_params(dims: Dims, seed: int) -> dict:
    params = {"final_norm": jnp.zeros((dims.d,), F32), "layers": []}
    for layer in range(dims.layers):
        params["layers"].append({n: jnp.zeros((dims.kv_rank if n == "attn.kv_norm"
                                                else dims.d,), F32)
                                 for n in LAYER_NORMS})
    for name, layer, value in init_draws(dims, seed):
        if layer is None:
            params[name] = value
        else:
            params["layers"][layer][name] = value
    return params


leaf_items = dense.leaf_items


# --------------------------------------------------------------- forward
def yarn_inv_freq(dims: Dims) -> np.ndarray:
    """YaRN's inverse frequencies of the rotary part, as the released
    ``DeepseekV2YarnRotaryEmbedding`` computes them."""
    dim, base = dims.rope, dims.theta

    def correction_dim(rotations):
        return (dim * math.log(dims.yarn_original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(dims.beta_fast)), 0)
    high = min(math.ceil(correction_dim(dims.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    extrapolated = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    interpolated = extrapolated / dims.yarn_factor
    keep = 1.0 - ramp
    return (interpolated * (1.0 - keep) + extrapolated * keep).astype(np.float32)


def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def softmax_scale(dims: Dims) -> float:
    m = yarn_get_mscale(dims.yarn_factor, dims.mscale_all_dim)
    return (dims.nope + dims.rope) ** -0.5 * m * m


def _rope(x, dims: Dims):
    """Rotate the two halves of the rotary part by position (x: b, s, h, rope)."""
    s, half = x.shape[1], dims.rope // 2
    mag = (yarn_get_mscale(dims.yarn_factor, dims.mscale)
           / yarn_get_mscale(dims.yarn_factor, dims.mscale_all_dim))
    ang = jnp.arange(s, dtype=F32)[:, None] * yarn_inv_freq(dims)[None, :]
    sin = (jnp.sin(ang) * mag)[None, :, None, :]
    cos = (jnp.cos(ang) * mag)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, scale, precision):
    """Causal softmax attention over every head, in blocks of queries
    (q, k: b, s, h, qk; v: b, s, h, v)."""
    b, s, h, _ = q.shape
    blk = min(dense.Q_BLOCK, s)
    nq = s // blk
    qb = q.reshape(b, nq, blk, h, q.shape[-1]).transpose(1, 0, 2, 3, 4)

    @jax.checkpoint
    def one(args):
        qi, i = args
        logits = dense._mm("bqhd,bkhd->bhqk", qi, k, precision) * scale
        qpos = i * blk + jnp.arange(blk)[:, None]
        kpos = jnp.arange(s)[None, :]
        logits = jnp.where(kpos <= qpos, logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        return dense._mm("bhqk,bkhd->bqhd", probs, v, precision)

    out = jax.lax.map(one, (qb, jnp.arange(nq)))  # (nq, b, blk, h, v)
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h * v.shape[-1])


def latent_attention(p: dict, h, dims: Dims, precision: str = "f32"):
    b, s, _ = h.shape
    H, r, dn = dims.heads, dims.kv_rank, dims.nope
    q = dense._mm("bsd,dn->bsn", h, p["attn.wq"], precision).reshape(b, s, H, dn + dims.rope)
    kv_a = dense._mm("bsd,dn->bsn", h, p["attn.wkv_a"], precision)
    latent = dense._rms(kv_a[..., :r], p["attn.kv_norm"], dims.eps)
    kv = dense._mm("bsr,rn->bsn", latent, p["attn.wkv_b"], precision)
    kv = kv.reshape(b, s, H, dn + dims.v)
    k_pe = jnp.broadcast_to(_rope(kv_a[:, :, None, r:], dims), (b, s, H, dims.rope))
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], dims)], axis=-1)
    k = jnp.concatenate([kv[..., :dn], k_pe], axis=-1)
    out = _attention(q, k, kv[..., dn:], softmax_scale(dims), precision)
    return dense._mm("bsn,nd->bsd", out, p["attn.wo"], precision)


def _swiglu(h, gate, up, down, precision):
    a = dense._mm("bsd,df->bsf", h, gate, precision)
    u = dense._mm("bsd,df->bsf", h, up, precision)
    return dense._mm("bsf,fd->bsd", jax.nn.silu(a) * u, down, precision)


def moe_layer(p: dict, h, dims: Dims, precision: str = "f32"):
    """(the held experts' part plus the shared experts, the balance loss
    of each row)."""
    b, s, _ = h.shape
    scores = jax.nn.softmax(dense._mm("bsd,de->bse", h, p["moe.router"], precision), axis=-1)
    top_w, top_i = jax.lax.top_k(scores, dims.top_k)
    if dims.norm_topk:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    ids = dims.first + jnp.arange(dims.held)
    gate = jnp.sum(jnp.where(top_i[..., None] == ids, top_w[..., None], 0.0), axis=2)
    a = dense._mm("bsd,edf->bsef", h, p["moe.wi_gate"], precision)
    u = dense._mm("bsd,edf->bsef", h, p["moe.wi_up"], precision)
    act = jax.nn.silu(a) * u * gate[..., None]
    routed = dense._mm("bsef,efd->bsd", act, p["moe.wo"], precision)
    shared = _swiglu(h, p["moe.shared.wi_gate"], p["moe.shared.wi_up"],
                     p["moe.shared.wo"], precision)
    # sequence-wise balance: f_e = E / (s k) * picks of e in the row,
    # P_e = mean score of e over the row; sum_e f_e P_e per row
    picks = jnp.sum(jax.nn.one_hot(top_i, dims.experts, dtype=F32), axis=(1, 2))
    f = picks * dims.experts / (s * dims.top_k)
    return routed + shared, jnp.sum(f * scores.mean(axis=1), axis=-1)


def layer_forward(p: dict, x, dims: Dims, moe: bool, precision: str = "f32"):
    """(layer output, the layer's balance loss summed over the rows)."""
    x = x + latent_attention(p, dense._rms(x, p["ln1"], dims.eps), dims, precision)
    h = dense._rms(x, p["ln2"], dims.eps)
    if not moe:
        return (x + _swiglu(h, p["mlp.wi_gate"], p["mlp.wi_up"], p["mlp.wo"], precision),
                jnp.zeros((), F32))
    y, aux = moe_layer(p, h, dims, precision)
    return x + y, jnp.sum(aux)


# ---------------------------------------------------------- loss & grads
class Trainer:
    """Loss, gradients and Adafactor steps of the reference, one chip.

    The loss is the mean masked cross entropy plus the z-loss plus alpha
    times the sum over MoE layers of the balance loss averaged over the
    batch's rows; ``rows`` is the row-block size (each row's balance loss
    is its own, so blocks change nothing). ``keep_rows`` (a fault reading,
    never the reference) keeps only the first that many rows' targets.
    """

    def __init__(self, dims: Dims, *, precision: str = "f32", rows: int = 4,
                 keep_rows: "int | None" = None):
        self.dims, self.precision, self.rows = dims, precision, rows
        self.keep_rows = keep_rows
        self._fwd, self._bwd = {}, {}
        for moe in (False, True):
            fwd = functools.partial(layer_forward, dims=dims, moe=moe, precision=precision)
            self._fwd[moe] = jax.jit(fwd)

            def bwd(p, x, g, g_aux, fwd=fwd):
                _, pull = jax.vjp(fwd, p, x)
                return pull((g, g_aux))

            self._bwd[moe] = jax.jit(bwd)

        def head(fn, hw, x, targets, mask, denom):
            ce, z = dense.head_loss_sums(fn, hw, x, targets, mask, dims, precision)
            return (ce + dense.Z_WEIGHT * z) / denom, ce

        self._head = jax.jit(jax.value_and_grad(head, argnums=(0, 1, 2), has_aux=True))
        self._embed = jax.jit(lambda table, tokens: jnp.take(table, tokens, axis=0))
        self._embed_grad = jax.jit(
            lambda acc, tokens, g: acc.at[tokens.reshape(-1)].add(g.reshape(-1, g.shape[-1])),
            donate_argnums=0)
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)

    def loss_and_grads(self, params: dict, batch: dict):
        """Total loss and float32 gradients (same structure as params)."""
        dims = self.dims
        tokens = np.asarray(batch["tokens"])
        targets = np.asarray(batch["targets"])
        mask = np.asarray(batch["loss_mask"], dtype=np.float32)
        if self.keep_rows is not None:
            mask = mask.copy()
            mask[self.keep_rows:] = 0.0
        denom = max(float(mask.sum()), 1.0)
        g_aux = jnp.asarray(dims.aux_alpha / tokens.shape[0], F32)
        grads = {"embed": jnp.zeros_like(params["embed"]),
                 "lm_head": None, "final_norm": None, "layers": [None] * dims.layers}
        total = 0.0
        for r0 in range(0, tokens.shape[0], self.rows):
            sl = slice(r0, r0 + self.rows)
            tok = jnp.asarray(tokens[sl])
            xs = [self._embed(params["embed"], tok)]
            aux = 0.0
            for i, layer in enumerate(params["layers"]):
                x, a = self._fwd[dims.is_moe(i)](layer, xs[-1])
                xs.append(x)
                aux += float(a)
            (loss, _), (g_fn, g_head, g_x) = self._head(
                params["final_norm"], params["lm_head"], xs[-1],
                jnp.asarray(targets[sl]), jnp.asarray(mask[sl]), denom)
            total += float(loss) + float(g_aux) * aux
            grads["final_norm"] = dense._acc(self._add, grads["final_norm"], g_fn)
            grads["lm_head"] = dense._acc(self._add, grads["lm_head"], g_head)
            for i in reversed(range(dims.layers)):
                g_p, g_x = self._bwd[dims.is_moe(i)](params["layers"][i], xs[i], g_x, g_aux)
                grads["layers"][i] = dense._acc(self._add, grads["layers"][i], g_p)
            del xs
            grads["embed"] = self._embed_grad(grads["embed"], tok, g_x)
        return total, grads


# ------------------------------------------------------------- optimizer
class Adafactor:
    """The stated Adafactor, per parameter tensor as the program holds it.

    The program stacks each per-layer parameter over the layers of its
    segment (the dense layers, the MoE layers) into one tensor: a matrix
    or a stack of expert matrices is factored over its last two dims per
    layer, with its update RMS clipped over the segment's layers; a
    per-layer vector (an RMSNorm scale) stacks into a (layers, width)
    matrix, factored and decayed as one."""

    def __init__(self, params: dict, dims: Dims):
        self.dims = dims
        self.state: dict = {}
        for name, group in _tensors(params, dims).items():
            if isinstance(group, list):
                self.state[name] = [(jnp.zeros(p.shape[:-1], F32),
                                     jnp.zeros(p.shape[:-2] + p.shape[-1:], F32))
                                    for p in group]
            elif group.ndim >= 2:
                self.state[name] = (jnp.zeros(group.shape[:-1], F32),
                                    jnp.zeros(group.shape[:-2] + group.shape[-1:], F32))
            else:
                self.state[name] = jnp.zeros(group.shape, F32)

    def step(self, params: dict, grads: dict, step: int) -> dict:
        """One update of ``params`` with already clipped ``grads``."""
        lr = dense.lr_at(step)
        beta = 1.0 - (step + 1.0) ** (-dense.AF_DECAY)
        ps, gs = _tensors(params, self.dims), _tensors(grads, self.dims)
        out: dict = {}
        for name, p in ps.items():
            g = gs[name]
            if isinstance(p, list):
                moments = [dense._factored_moments(gi, vr, vc, beta)
                           for gi, (vr, vc) in zip(g, self.state[name])]
                self.state[name] = moments
                ssq = sum(float(jnp.sum(jnp.square(dense._factored_direction(gi, vr, vc))))
                          for gi, (vr, vc) in zip(g, moments))
                n = sum(gi.size for gi in g)
                inv = 1.0 / max(1.0, math.sqrt(ssq / n + dense.AF_EPS) / dense.AF_CLIP)
                out[name] = [dense._apply(pi, dense._factored_direction(gi, vr, vc), inv, lr,
                                          dense.WEIGHT_DECAY)
                             for pi, gi, (vr, vc) in zip(p, g, moments)]
                continue
            if p.ndim >= 2:
                vr, vc = dense._factored_moments(g, *self.state[name], beta)
                self.state[name] = (vr, vc)
                u = dense._factored_direction(g, vr, vc)
                decay = dense.WEIGHT_DECAY
            else:
                v = beta * self.state[name] + (1 - beta) * (g * g + dense.AF_EPS)
                self.state[name] = v
                u = g / jnp.sqrt(v + dense.AF_EPS)
                decay = 0.0
            inv = 1.0 / max(1.0, math.sqrt(float(jnp.mean(u * u)) + dense.AF_EPS)
                            / dense.AF_CLIP)
            out[name] = dense._apply(p, u, inv, lr, decay)
        return _untensor(out, params, self.dims)


def _segments(dims: Dims) -> dict:
    """{segment: its layers}."""
    out: dict = {}
    for layer in range(dims.layers):
        out.setdefault(dims.segment(layer), []).append(layer)
    return out


def _tensors(params: dict, dims: Dims) -> dict:
    """The program's tensors: top-level leaves, each segment's stacked
    vectors as (layers, width) arrays and its matrices as lists."""
    out = {n: params[n] for n in ("embed", "lm_head", "final_norm")}
    for seg, layers in _segments(dims).items():
        for name in params["layers"][layers[0]]:
            group = [params["layers"][i][name] for i in layers]
            out[f"{seg}/{name}"] = jnp.stack(group) if group[0].ndim == 1 else group
    return out


def _untensor(t: dict, like: dict, dims: Dims) -> dict:
    out = {n: t[n] for n in ("embed", "lm_head", "final_norm")}
    out["layers"] = [None] * len(like["layers"])
    for seg, layers in _segments(dims).items():
        for j, i in enumerate(layers):
            out["layers"][i] = {name: t[f"{seg}/{name}"][j] for name in like["layers"][i]}
    return out


def train_readings(dims: Dims, seed: int, batches: list, *,
                   precision: str = "f32", rows: int = 4,
                   keep_rows: "int | None" = None) -> dict:
    """Follow the first ``len(batches)`` steps from the seed's init.

    Returns the loss of each step, the per-leaf norm of the first clipped
    gradient (what the optimizer gets), the unclipped global norm of the
    first gradient and the per-leaf norm of the parameters' change after
    the last step.
    """
    with jax.default_matmul_precision("highest"):
        trainer = Trainer(dims, precision=precision, rows=rows, keep_rows=keep_rows)
        params = init_params(dims, seed)
        opt = Adafactor(params, dims)
        losses, grad_norms, first_global = [], None, None
        for step, batch in enumerate(batches):
            loss, grads = trainer.loss_and_grads(params, batch)
            losses.append(loss)
            gnorm = dense.global_norm(grads)
            clip = min(1.0, dense.GRAD_CLIP / max(gnorm, 1e-9))
            grads = dense.scale_tree(grads, clip)
            if step == 0:
                first_global = gnorm
                grad_norms = {n: float(jnp.sqrt(jnp.sum(g * g)))
                              for n, g in leaf_items(grads)}
            params = opt.step(params, grads, step)
            del grads
        del opt, trainer
        change = {}
        for name, layer, init in init_draws(dims, seed):
            key = name if layer is None else f"layer{layer}.{name}"
            cur = params[name] if layer is None else params["layers"][layer][name]
            change[key] = diff_norm(cur, init)
        for name, value in leaf_items(params):
            if name not in change:  # norms start at 0
                change[name] = diff_norm(value, jnp.zeros((), F32))
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_global_norm": first_global, "change_norms": change}
