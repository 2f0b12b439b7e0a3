"""Plain float32 reference of a Llama-style dense decoder and its training.

Written from the published description (pre-norm RMSNorm, rotary position
embedding on the two halves of each head, grouped-query attention, SwiGLU
MLP, untied head) and the optimizer the configuration file states. It
imports nothing of the program under test. Weights are drawn from the seed
by the scheme the configuration file states, with the same random draws in
the same order, so the reference starts where the program starts.

Every matrix product runs at ``Precision.HIGHEST``: on a TPU a float32
product otherwise runs in bfloat16 passes. ``precision="int8"`` is the
control: every operand of every product is rounded to int8 (symmetric,
one scale per tensor) first, the nearest precision below the bfloat16 the
configuration states, and the one a v5e's int8 units would tempt.

To fit one chip the reference runs layer by layer and in blocks of rows:
each layer's backward pass recomputes its forward pass, and the attention
runs in blocks of queries. Parameters are lists of per-layer dicts.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
INT8_MAX = 127.0
Q_BLOCK = 512

#: The optimizer and loss settings the configuration files state.
LEARNING_RATE = 3e-4
WARMUP = 200
TOTAL_STEPS = 10_000
WEIGHT_DECAY = 0.1
GRAD_CLIP = 1.0
AF_DECAY = 0.8
AF_EPS = 1e-30
AF_CLIP = 1.0
Z_WEIGHT = 1e-4

#: Per-layer parameter names, in the order the init scheme draws them.
LAYER_DRAWS = ("attn.wq", "attn.wk", "attn.wv", "attn.wo",
               "mlp.wi_gate", "mlp.wi_up", "mlp.wo")
LAYER_NORMS = ("ln1", "ln2")


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    layers: int
    eps: float
    theta: float
    dtype: str = "bfloat16"   # the type weights are stored in

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        heads = int(cfg["num_attention_heads"])
        d = int(cfg["hidden_size"])
        return cls(
            d=d, heads=heads, kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg.get("head_dim") or d // heads),
            ff=int(cfg["intermediate_size"]), vocab=int(cfg["vocab_size"]),
            layers=int(cfg["num_hidden_layers"]),
            eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
            dtype=cfg["torch_dtype"],
        )

    def shapes(self) -> dict:
        hq, hkv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return {
            "attn.wq": (self.d, hq), "attn.wk": (self.d, hkv),
            "attn.wv": (self.d, hkv), "attn.wo": (hq, self.d),
            "mlp.wi_gate": (self.d, self.ff), "mlp.wi_up": (self.d, self.ff),
            "mlp.wo": (self.ff, self.d),
        }


# ------------------------------------------------------------------ init
def _draw(key, shape, scale: float, mul: bool, dtype: str):
    x = jax.random.normal(key, shape, F32)
    x = x * scale if mul else x / scale
    return x.astype(dtype).astype(F32)


def init_draws(dims: Dims, seed: int):
    """Yield ``(name, layer, array)`` for every drawn weight, in draw order.

    Each draw is rounded to the stored type and held as float32.
    The key chain is ``key, sub = split(key)`` once per draw.
    """
    key = jax.random.PRNGKey(seed)

    def sub():
        nonlocal key
        key, out = jax.random.split(key)
        return out

    yield "embed", None, _draw(sub(), (dims.vocab, dims.d), 0.02, True, dims.dtype)
    yield "lm_head", None, _draw(sub(), (dims.d, dims.vocab), dims.d ** 0.5, False,
                                 dims.dtype)
    shapes = dims.shapes()
    for layer in range(dims.layers):
        for name in LAYER_DRAWS:
            shape = shapes[name]
            yield name, layer, _draw(sub(), shape, math.sqrt(shape[0]), False, dims.dtype)


def init_params(dims: Dims, seed: int) -> dict:
    params = {"final_norm": jnp.zeros((dims.d,), F32),
              "layers": [{n: jnp.zeros((dims.d,), F32) for n in LAYER_NORMS}
                         for _ in range(dims.layers)]}
    for name, layer, value in init_draws(dims, seed):
        if layer is None:
            params[name] = value
        else:
            params["layers"][layer][name] = value
    return params


def leaf_items(params: dict):
    """``(leaf name, array)`` for every parameter, per layer."""
    for name in ("embed", "lm_head", "final_norm"):
        yield name, params[name]
    for i, layer in enumerate(params["layers"]):
        for name, value in layer.items():
            yield f"layer{i}.{name}", value


# --------------------------------------------------------------- forward
def _int8(x):
    """Round to int8 levels with one scale per tensor. The gradient passes
    straight through the rounding."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / INT8_MAX
    q = jnp.clip(jnp.round(x / scale), -INT8_MAX, INT8_MAX) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec: str, a, b, precision: str):
    if precision == "int8":
        a, b = _int8(a), _int8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, theta):
    """Rotate the two halves of each head by position (x: b, s, h, d)."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs[None, :]
    sin, cos = jnp.sin(ang)[None, :, None, :], jnp.cos(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, precision):
    """Causal softmax attention, in blocks of queries (q: b, s, h, d)."""
    b, s, h, hd = q.shape
    groups = h // k.shape[2]
    k = jnp.repeat(k, groups, axis=2)
    v = jnp.repeat(v, groups, axis=2)
    blk = min(Q_BLOCK, s)
    nq = s // blk
    qb = q.reshape(b, nq, blk, h, hd).transpose(1, 0, 2, 3, 4)

    @jax.checkpoint
    def one(args):
        qi, i = args
        logits = _mm("bqhd,bkhd->bhqk", qi, k, precision) / math.sqrt(hd)
        qpos = i * blk + jnp.arange(blk)[:, None]
        kpos = jnp.arange(s)[None, :]
        logits = jnp.where(kpos <= qpos, logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        return _mm("bhqk,bkhd->bqhd", probs, v, precision)

    out = jax.lax.map(one, (qb, jnp.arange(nq)))  # (nq, b, blk, h, d)
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h * hd)


def layer_forward(p: dict, x, dims: Dims, precision: str = "f32"):
    b, s, _ = x.shape
    h = _rms(x, p["ln1"], dims.eps)
    q = _mm("bsd,dn->bsn", h, p["attn.wq"], precision)
    k = _mm("bsd,dn->bsn", h, p["attn.wk"], precision)
    v = _mm("bsd,dn->bsn", h, p["attn.wv"], precision)
    q = _rope(q.reshape(b, s, dims.heads, dims.head_dim), dims.theta)
    k = _rope(k.reshape(b, s, dims.kv_heads, dims.head_dim), dims.theta)
    v = v.reshape(b, s, dims.kv_heads, dims.head_dim)
    a = _attention(q, k, v, precision)
    x = x + _mm("bsn,nd->bsd", a, p["attn.wo"], precision)
    h = _rms(x, p["ln2"], dims.eps)
    gate = _mm("bsd,df->bsf", h, p["mlp.wi_gate"], precision)
    up = _mm("bsd,df->bsf", h, p["mlp.wi_up"], precision)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, p["mlp.wo"], precision)


def head_loss_sums(final_norm, head, x, targets, mask, dims: Dims,
                   precision: str = "f32"):
    """(sum of masked cross entropy, sum of masked squared log-partition)."""
    logits = _mm("bsd,dv->bsv", _rms(x, final_norm, dims.eps), head, precision)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - gold) * mask), jnp.sum(logz * logz * mask)


# ---------------------------------------------------------- loss & grads
class Trainer:
    """Loss, gradients and Adafactor steps of the reference, one chip.

    ``rows`` is the row-block size: gradients are summed over blocks of
    that many sequences. ``keep_rows`` (a fault reading, never the
    reference) keeps only the first that many rows of each batch, with the
    mean taken over them.
    """

    def __init__(self, dims: Dims, *, precision: str = "f32", rows: int = 4,
                 keep_rows: "int | None" = None):
        self.dims, self.precision, self.rows = dims, precision, rows
        self.keep_rows = keep_rows
        fwd = functools.partial(layer_forward, dims=dims, precision=precision)
        self._fwd = jax.jit(fwd)

        def bwd(p, x, g):
            _, pull = jax.vjp(fwd, p, x)
            return pull(g)

        self._bwd = jax.jit(bwd)

        def head(fn, hw, x, targets, mask, denom):
            ce, z = head_loss_sums(fn, hw, x, targets, mask, dims, precision)
            return (ce + Z_WEIGHT * z) / denom, ce

        self._head = jax.jit(jax.value_and_grad(head, argnums=(0, 1, 2),
                                                has_aux=True))
        self._embed = jax.jit(lambda table, tokens: jnp.take(table, tokens, axis=0))
        self._embed_grad = jax.jit(
            lambda acc, tokens, g: acc.at[tokens.reshape(-1)].add(
                g.reshape(-1, g.shape[-1])),
            donate_argnums=0)
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                            donate_argnums=0)

    def loss_and_grads(self, params: dict, batch: dict):
        """Total loss and float32 gradients (same structure as params)."""
        tokens = np.asarray(batch["tokens"])
        targets = np.asarray(batch["targets"])
        mask = np.asarray(batch["loss_mask"], dtype=np.float32)
        if self.keep_rows is not None:
            mask = mask.copy()
            mask[self.keep_rows:] = 0.0
        denom = max(float(mask.sum()), 1.0)
        grads = {"embed": jnp.zeros_like(params["embed"]),
                 "lm_head": None, "final_norm": None,
                 "layers": [None] * self.dims.layers}
        total = 0.0
        for r0 in range(0, tokens.shape[0], self.rows):
            sl = slice(r0, r0 + self.rows)
            if not mask[sl].any():
                continue
            tok = jnp.asarray(tokens[sl])
            xs = [self._embed(params["embed"], tok)]
            for layer in params["layers"]:
                xs.append(self._fwd(layer, xs[-1]))
            (loss, _), (g_fn, g_head, g_x) = self._head(
                params["final_norm"], params["lm_head"], xs[-1],
                jnp.asarray(targets[sl]), jnp.asarray(mask[sl]), denom)
            total += float(loss)
            grads["final_norm"] = _acc(self._add, grads["final_norm"], g_fn)
            grads["lm_head"] = _acc(self._add, grads["lm_head"], g_head)
            for i in reversed(range(self.dims.layers)):
                g_p, g_x = self._bwd(params["layers"][i], xs[i], g_x)
                grads["layers"][i] = _acc(self._add, grads["layers"][i], g_p)
            del xs
            grads["embed"] = self._embed_grad(grads["embed"], tok, g_x)
        return total, grads


def _acc(add, acc, g):
    return g if acc is None else add(acc, g)


# ------------------------------------------------------------- optimizer
def lr_at(step: int) -> float:
    if step < WARMUP:
        return LEARNING_RATE * (step + 1) / WARMUP
    prog = min(max((step - WARMUP) / max(TOTAL_STEPS - WARMUP, 1), 0.0), 1.0)
    cos = LEARNING_RATE * 0.5 * (1 + math.cos(math.pi * prog))
    return max(cos, LEARNING_RATE * 0.1)


def global_norm(grads: dict) -> float:
    return math.sqrt(sum(float(jnp.sum(g * g)) for _, g in leaf_items(grads)))


@jax.jit
def _factored_moments(g, vr, vc, beta):
    g2 = g * g + AF_EPS
    vr = beta * vr + (1 - beta) * g2.mean(axis=-1)
    vc = beta * vc + (1 - beta) * g2.mean(axis=-2)
    return vr, vc


@jax.jit
def _factored_direction(g, vr, vc):
    denom = jnp.maximum(vr.mean(axis=-1, keepdims=True), AF_EPS)
    vhat = (vr[..., None] / denom[..., None]) * vc[..., None, :]
    return g / jnp.sqrt(vhat + AF_EPS)


@functools.partial(jax.jit, donate_argnums=0)
def _apply(p, u, inv_clip, lr, decay):
    return p - lr * (u * inv_clip + decay * p)


class Adafactor:
    """The stated Adafactor, applied per parameter tensor as the program
    holds it. The program stacks each per-layer parameter over the layers
    into one tensor, so a per-layer name (``attn.wq``) is one tensor of
    shape (layers, ...): its update RMS is clipped over all layers, and the
    stacked RMSNorm scales (layers, d) are a 2-dim tensor with factored
    moments and weight decay."""

    def __init__(self, params: dict):
        self.state: dict = {}
        for name, group in _tensors(params).items():
            if isinstance(group, list):
                self.state[name] = [
                    (jnp.zeros(p.shape[:-1], F32), jnp.zeros(p.shape[1:], F32))
                    for p in group]
            elif group.ndim >= 2:
                self.state[name] = (jnp.zeros(group.shape[:-1], F32),
                                    jnp.zeros(group.shape[:-2] + group.shape[-1:], F32))
            else:
                self.state[name] = jnp.zeros(group.shape, F32)

    def step(self, params: dict, grads: dict, step: int) -> dict:
        """One update of ``params`` with already clipped ``grads``."""
        lr = lr_at(step)
        beta = 1.0 - (step + 1.0) ** (-AF_DECAY)
        ps, gs = _tensors(params), _tensors(grads)
        out: dict = {}
        for name, p in ps.items():
            g = gs[name]
            if isinstance(p, list):  # one stacked matrix, held per layer
                moments = [_factored_moments(gi, vr, vc, beta)
                           for gi, (vr, vc) in zip(g, self.state[name])]
                self.state[name] = moments
                ssq = sum(float(jnp.sum(jnp.square(_factored_direction(gi, vr, vc))))
                          for gi, (vr, vc) in zip(g, moments))
                n = sum(gi.size for gi in g)
                inv = 1.0 / max(1.0, math.sqrt(ssq / n + AF_EPS) / AF_CLIP)
                out[name] = [_apply(pi, _factored_direction(gi, vr, vc), inv, lr,
                                    WEIGHT_DECAY)
                             for pi, gi, (vr, vc) in zip(p, g, moments)]
                continue
            if p.ndim >= 2:
                vr, vc = _factored_moments(g, *self.state[name], beta)
                self.state[name] = (vr, vc)
                u = _factored_direction(g, vr, vc)
                decay = WEIGHT_DECAY
            else:
                v = beta * self.state[name] + (1 - beta) * (g * g + AF_EPS)
                self.state[name] = v
                u = g / jnp.sqrt(v + AF_EPS)
                decay = 0.0
            inv = 1.0 / max(1.0, math.sqrt(float(jnp.mean(u * u)) + AF_EPS) / AF_CLIP)
            out[name] = _apply(p, u, inv, lr, decay)
        return _untensor(out, params)


def _tensors(params: dict) -> dict:
    """The program's tensors: top-level leaves, the stacked norms as
    (layers, d) arrays and every per-layer matrix as a list over layers."""
    out = {n: params[n] for n in ("embed", "lm_head", "final_norm")}
    layers = params["layers"]
    for name in LAYER_NORMS:
        out[name] = jnp.stack([lay[name] for lay in layers])
    for name in LAYER_DRAWS:
        out[name] = [lay[name] for lay in layers]
    return out


def _untensor(t: dict, like: dict) -> dict:
    out = {n: t[n] for n in ("embed", "lm_head", "final_norm")}
    out["layers"] = []
    for i in range(len(like["layers"])):
        lay = {name: t[name][i] for name in LAYER_NORMS}
        lay.update({name: t[name][i] for name in LAYER_DRAWS})
        out["layers"].append(lay)
    return out


@functools.partial(jax.jit, donate_argnums=0)
def _scale(g, s):
    return g * s


def scale_tree(grads: dict, s: float) -> dict:
    """``grads * s``, leaf by leaf and in place."""
    return jax.tree.map(lambda g: _scale(g, s), grads)


def train_readings(dims: Dims, seed: int, batches: list, *,
                   precision: str = "f32", rows: int = 4,
                   keep_rows: "int | None" = None) -> dict:
    """Follow the first ``len(batches)`` steps from the seed's init.

    Returns the loss of each step, the per-leaf norm of the first clipped
    gradient (what the optimizer gets), the unclipped global norm of the
    first gradient and the per-leaf norm of the parameters' change after
    the last step.
    """
    trainer = Trainer(dims, precision=precision, rows=rows, keep_rows=keep_rows)
    params = init_params(dims, seed)
    opt = Adafactor(params)
    losses, grad_norms, first_global = [], None, None
    for step, batch in enumerate(batches):
        loss, grads = trainer.loss_and_grads(params, batch)
        losses.append(loss)
        gnorm = global_norm(grads)
        clip = min(1.0, GRAD_CLIP / max(gnorm, 1e-9))
        grads = scale_tree(grads, clip)
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


@jax.jit
def _diff_sq(a, b):
    d = a.astype(F32) - b
    return jnp.sum(d * d)


def diff_norm(a, b) -> float:
    """``|a - b|`` in float32, ``a`` of any float type."""
    return math.sqrt(float(_diff_sq(a, b)))
