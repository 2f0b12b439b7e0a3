"""Shared model machinery: params with logical axes, norms, RoPE, init."""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "Param",
    "split_params",
    "merge_params",
    "RngStream",
    "rms_norm",
    "make_rope",
    "apply_rope",
    "rope_inv_freq",
    "yarn_mscale",
    "normal_init",
    "scaled_init",
]


@dataclasses.dataclass
class Param:
    """A parameter leaf: value + logical axis names (one per dim).

    Registered as a pytree node (value = child, axes = static aux data) so
    ``jax.eval_shape`` can trace ``Model.init`` at full scale without ever
    allocating parameters — that's how the 1T-param dry-run stays lazy.
    """

    value: jax.Array
    axes: tuple[str | None, ...]

    def __post_init__(self):
        assert len(self.axes) == len(self.value.shape), (self.axes, self.value.shape)


jax.tree_util.register_pytree_node(
    Param,
    lambda p: ((p.value,), p.axes),
    lambda axes, children: Param(children[0], axes),
)


def _is_param(x) -> bool:
    return isinstance(x, Param)


def split_params(tree):
    """Param tree -> (values tree, axes tree) with identical structure."""
    values = jax.tree.map(lambda p: p.value, tree, is_leaf=_is_param)
    axes = jax.tree.map(lambda p: p.axes, tree, is_leaf=_is_param)
    return values, axes


def merge_params(values, axes):
    return jax.tree.map(Param, values, axes, is_leaf=lambda x: x is None)


class RngStream:
    """Deterministic rng splitter: stream.next() never reuses a key."""

    def __init__(self, seed_or_key):
        self._key = (
            seed_or_key
            if isinstance(seed_or_key, jax.Array)
            else jax.random.PRNGKey(seed_or_key)
        )

    def next(self) -> jax.Array:
        self._key, out = jax.random.split(self._key)
        return out


def normal_init(rng, shape, dtype, stddev=0.02):
    return (jax.random.normal(rng, shape, jnp.float32) * stddev).astype(dtype)


def scaled_init(rng, shape, dtype, fan_in=None):
    """Truncated-normal-ish fan-in scaled init (1/sqrt(fan_in))."""
    fan_in = fan_in if fan_in is not None else shape[0]
    return (
        jax.random.normal(rng, shape, jnp.float32) / math.sqrt(max(fan_in, 1))
    ).astype(dtype)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm in fp32, result cast back to x.dtype (LLaMA convention)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor ``0.1 * mscale * ln(factor) + 1`` (1 for a
    factor of 1 or less)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_inv_freq(head_dim: int, theta: float, cfg=None) -> np.ndarray:
    """Inverse frequencies of the ``head_dim // 2`` rotary pairs.

    With ``cfg.yarn_factor`` set, YaRN's blend [arXiv:2309.00071, as in
    DeepSeek-V2]: pairs that turn fewer than ``beta_slow`` times over the
    original context are interpolated (frequency / factor), pairs that turn
    more than ``beta_fast`` times are kept, with a linear ramp between.
    """
    half = head_dim // 2
    extra = 1.0 / (theta ** (np.arange(0, half, dtype=np.float64) / half))
    factor = getattr(cfg, "yarn_factor", 0.0)
    if not factor:
        return extra.astype(np.float32)

    def dim_of(turns):  # pair index that turns ``turns`` times
        return (head_dim * math.log(cfg.yarn_original_max_pos / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(dim_of(cfg.yarn_beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def make_rope(positions: jax.Array, head_dim: int, theta: float, cfg=None) -> tuple:
    """(sin, cos) tables for the given positions; fp32. YaRN's tables
    (``cfg.yarn_factor``) carry its magnitude ratio ``mscale(mscale) /
    mscale(mscale_all_dim)``."""
    freqs = rope_inv_freq(head_dim, theta, cfg)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., half)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    if getattr(cfg, "yarn_factor", 0.0):
        mag = (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
               / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
        if mag != 1.0:
            sin, cos = sin * mag, cos * mag
    return sin, cos


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """Rotate pairs (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin).

    x: (..., S, H, D); sin/cos: (..., S, D/2) broadcast over heads.
    Odd head_dims leave the last lane unrotated (kimi's 112 stays exact).
    """
    half = sin.shape[-1]
    sin = sin[..., None, :]  # add head axis
    cos = cos[..., None, :]
    x1 = x[..., :half]
    x2 = x[..., half : 2 * half]
    rest = x[..., 2 * half :]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    out = jnp.concatenate([y1, y2] + ([rest] if rest.shape[-1] else []), axis=-1)
    return out.astype(x.dtype)
