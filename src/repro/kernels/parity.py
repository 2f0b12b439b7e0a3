"""Ref-vs-kernel parity and throughput harness for every Pallas kernel.

The xformers idiom (see PAPERS.md and the ``test_mem_eff_attention`` /
``triton/softmax`` exemplars): each kernel declares a *shape grid* and a
*per-dtype tolerance table*, a case generator materialises deterministic
inputs for every (shape, dtype) cell, and one checker compares the kernel
against its pure-jnp oracle under a scale-normalised max-error metric.
``tests/test_kernel_parity.py`` sweeps the full grid as the correctness
gate; ``benchmarks/device_path.py`` reuses the same cases for the
throughput tables, so the benchmarked shapes are exactly the verified
ones.

All entry points accept ``interpret=None`` (auto: compiled on TPU,
interpreted elsewhere — ``kernels.common``), so the same sweep runs
compiled on real hardware and interpreted in CPU CI.
"""

from __future__ import annotations

import dataclasses
import time
import zlib

import jax.numpy as jnp
import numpy as np

from .chunk_gather.ops import chunk_gather, chunk_gather_train
from .chunk_gather.ref import chunk_gather_ref, chunk_gather_train_ref
from .common import round_up
from .decode_attention.ops import decode_attention
from .decode_attention.ref import decode_attention_ref
from .flash_attention.ops import flash_attention_train
from .flash_attention.ref import attention_ref
from .ssd_scan.ops import ssd_scan
from .ssd_scan.ref import ssd_scan_ref

__all__ = [
    "KERNELS",
    "KernelCase",
    "check_case",
    "iter_cases",
    "measure_case",
    "round_up",
]


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One cell of a kernel's parity grid."""

    kernel: str     # registry key
    shape: tuple    # kernel-specific shape tuple (see KERNELS[...]["shapes"])
    dtype: str      # jnp dtype name

    @property
    def name(self) -> str:
        dims = "x".join(str(d) for d in self.shape)
        return f"{self.kernel}[{dims}]{self.dtype}"


# Per-kernel shape grids + per-dtype tolerances (scale-normalised max
# error, see _max_err). The integer gathers are exact by construction.
KERNELS: dict[str, dict] = {
    "flash_attention": {
        # (bh, s, d, causal)
        "shapes": [
            (2, 128, 32, True), (2, 128, 32, False),
            (4, 256, 64, True), (4, 256, 64, False),
            (3, 192, 64, True),
            (1, 512, 128, True),
        ],
        "quick_shapes": [(2, 128, 32, True)],
        "tols": {"float32": 2e-5, "bfloat16": 2e-2},
    },
    "decode_attention": {
        # (b, h, kvh, s, d)
        "shapes": [
            (2, 8, 2, 512, 64),
            (1, 4, 4, 256, 32),
            (3, 16, 4, 1024, 128),
        ],
        "quick_shapes": [(1, 4, 4, 256, 32)],
        "tols": {"float32": 2e-5, "bfloat16": 2e-2},
    },
    "ssd_scan": {
        # (bh, s, p, n, chunk)
        "shapes": [
            (4, 256, 64, 16, 64),
            (2, 128, 32, 32, 32),
            (1, 512, 64, 64, 128),
        ],
        "quick_shapes": [(2, 128, 32, 32, 32)],
        "tols": {"float32": 2e-4, "bfloat16": 5e-2},
    },
    "chunk_gather": {
        # (num_slots, L, B) — slot buffer (num_slots, 1, L)
        "shapes": [(64, 128, 16), (32, 256, 8), (16, 64, 32), (128, 512, 4)],
        "quick_shapes": [(64, 128, 16)],
        "tols": {"int32": 0.0},
    },
    "chunk_gather_train": {
        # (num_slots, seq_len, B) — slot rows lane-padded like the packer;
        # (4, 2048, 4) is the one-chip smoke run's batch
        "shapes": [(64, 128, 16), (32, 100, 8), (16, 64, 32), (4, 2048, 4)],
        "quick_shapes": [(64, 128, 16)],
        "tols": {"int32": 0.0},
    },
}


def iter_cases(kernels=None, *, quick: bool = False) -> list[KernelCase]:
    out = []
    for kernel, spec in KERNELS.items():
        if kernels is not None and kernel not in kernels:
            continue
        shapes = spec["quick_shapes" if quick else "shapes"]
        for shape in shapes:
            for dtype in spec["tols"]:
                out.append(KernelCase(kernel, shape, dtype))
    return out


# ---------------------------------------------------------------- inputs
def make_inputs(case: KernelCase, seed: int = 0) -> tuple:
    # zlib.crc32, not hash(): stable across processes (PYTHONHASHSEED).
    rng = np.random.default_rng((seed, zlib.crc32(case.kernel.encode()), *case.shape))
    dt = jnp.dtype(case.dtype)
    k = case.kernel
    if k == "flash_attention":
        bh, s, d, _ = case.shape
        return tuple(jnp.asarray(rng.normal(size=(bh, s, d)), dt) for _ in range(3))
    if k == "decode_attention":
        b, h, kvh, s, d = case.shape
        q = jnp.asarray(rng.normal(size=(b, h, d)), dt)
        ck = jnp.asarray(rng.normal(size=(b, s, kvh, d)), dt)
        cv = jnp.asarray(rng.normal(size=(b, s, kvh, d)), dt)
        mask = jnp.asarray(rng.random((b, s)) < 0.75)
        return q, ck, cv, mask
    if k == "ssd_scan":
        bh, s, p, n, _ = case.shape
        x = jnp.asarray(rng.normal(size=(bh, s, p)), dt)
        dts = jnp.asarray(rng.random((bh, s)) * 0.5 + 0.01, jnp.float32)
        a = jnp.asarray(-rng.random((bh, 1)) * 2 - 0.1, jnp.float32)
        b_ = jnp.asarray(rng.normal(size=(bh, s, n)), dt)
        c = jnp.asarray(rng.normal(size=(bh, s, n)), dt)
        return x, dts, a, b_, c
    if k == "chunk_gather":
        slots, length, batch = case.shape
        ct = jnp.asarray(rng.integers(1, 1000, (slots, 1, length)), jnp.int32)
        lens = jnp.asarray(rng.integers(1, length + 1, (slots,)), jnp.int32)
        idx = jnp.asarray(rng.integers(0, slots, (batch,)), jnp.int32)
        return ct, lens, idx
    if k == "chunk_gather_train":
        slots, seq_len, batch = case.shape
        lp = round_up(seq_len + 1, 128)
        lens = rng.integers(1, seq_len + 2, (slots,))
        ct = np.zeros((slots, 1, lp), np.int32)
        for i, n in enumerate(lens):
            ct[i, 0, :n] = rng.integers(1, 1000, n)
        idx = jnp.asarray(rng.integers(0, slots, (batch,)), jnp.int32)
        return jnp.asarray(ct), jnp.asarray(lens, jnp.int32), idx
    raise ValueError(f"unknown kernel {k!r}")


# ------------------------------------------------------------------- run
def run_kernel(case: KernelCase, inputs: tuple, *, interpret=None):
    k = case.kernel
    if k == "flash_attention":
        causal = case.shape[3]
        s = case.shape[1]
        bq = min(64, s)
        # (BH, S, D) as BH sequences of one head: (BH, S, 1, D)
        q, kk, v = (t[:, :, None] for t in inputs)
        return flash_attention_train(
            q, kk, v, causal=causal, block_q=bq, block_k=bq, interpret=interpret
        )[:, :, 0]
    if k == "decode_attention":
        return decode_attention(*inputs, block_k=128, interpret=interpret)
    if k == "ssd_scan":
        chunk = case.shape[4]
        return ssd_scan(*inputs, chunk=chunk, interpret=interpret)
    if k == "chunk_gather":
        return chunk_gather(*inputs, interpret=interpret)
    if k == "chunk_gather_train":
        seq_len = case.shape[1]
        return chunk_gather_train(*inputs, seq_len=seq_len, interpret=interpret)
    raise ValueError(f"unknown kernel {k!r}")


def run_ref(case: KernelCase, inputs: tuple):
    k = case.kernel
    if k == "flash_attention":
        return attention_ref(*inputs, causal=case.shape[3])
    if k == "decode_attention":
        q, ck, cv, mask = inputs
        b, h, d = q.shape
        s, kvh = ck.shape[1], ck.shape[2]
        g = h // kvh
        qg = q.reshape(b * kvh, g, d)
        fold = lambda t: t.transpose(0, 2, 1, 3).reshape(b * kvh, s, d)
        m = jnp.repeat(mask[:, None, :], kvh, 1).reshape(b * kvh, s)
        return decode_attention_ref(qg, fold(ck), fold(cv), m).reshape(b, h, d)
    if k == "ssd_scan":
        return ssd_scan_ref(*inputs)
    if k == "chunk_gather":
        return chunk_gather_ref(*inputs)
    if k == "chunk_gather_train":
        return chunk_gather_train_ref(*inputs, seq_len=case.shape[1])
    raise ValueError(f"unknown kernel {k!r}")


# ----------------------------------------------------------------- check
def _leaves(out):
    return list(out) if isinstance(out, (tuple, list)) else [out]


def _max_err(out, ref) -> float:
    """Scale-normalised max abs error, maxed over output leaves."""
    worst = 0.0
    for o, r in zip(_leaves(out), _leaves(ref)):
        o32 = np.asarray(o, np.float32)
        r32 = np.asarray(r, np.float32)
        scale = float(np.max(np.abs(r32))) + 1e-6
        worst = max(worst, float(np.max(np.abs(o32 - r32))) / scale)
    return worst


def check_case(case: KernelCase, *, interpret=None, seed: int = 0) -> dict:
    """Run one grid cell; returns {case, max_err, tol, ok}."""
    inputs = make_inputs(case, seed)
    out = run_kernel(case, inputs, interpret=interpret)
    ref = run_ref(case, inputs)
    err = _max_err(out, ref)
    tol = KERNELS[case.kernel]["tols"][case.dtype]
    return dict(case=case.name, max_err=err, tol=tol, ok=err <= tol)


# ------------------------------------------------------------- throughput
def _block(out) -> None:
    for leaf in _leaves(out):
        leaf.block_until_ready()


def measure_case(
    case: KernelCase, *, iters: int = 5, interpret=None, seed: int = 0
) -> dict:
    """Best-of-``iters`` wall time for kernel and oracle (post-warmup).

    ``out_mb`` sizes the assembled output, so ``mb_per_s`` reads as
    delivered bandwidth for the gather kernels and stays an honest
    relative number for the compute kernels. Interpret-mode timings only
    rank shapes against each other; absolute numbers are meaningful on a
    compiled backend.
    """
    inputs = make_inputs(case, seed)
    out = run_kernel(case, inputs, interpret=interpret)  # warmup/compile
    _block(out)
    ref = run_ref(case, inputs)
    _block(ref)
    out_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in _leaves(out))

    def best(fn) -> float:
        t = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            _block(fn())
            t = min(t, time.perf_counter() - t0)
        return t

    kernel_s = best(lambda: run_kernel(case, inputs, interpret=interpret))
    ref_s = best(lambda: run_ref(case, inputs))
    return dict(
        case=case.name,
        kernel_us=kernel_s * 1e6,
        ref_us=ref_s * 1e6,
        out_mb=out_bytes / 1e6,
        mb_per_s=out_bytes / 1e6 / kernel_s if kernel_s else 0.0,
    )
