"""Kernel parity harness: every kernel vs its pure-jnp oracle.

The shape x dtype grid and the per-dtype tolerances live in
``repro.kernels.parity`` — the same registry ``benchmarks/device_path.py``
prints as a table — so the CI sweep and the benchmark can never drift
apart. Semantics edge cases that a grid sweep cannot express (sliding
windows, block-shape independence, ring-buffer masks, duplicate
redirection indices) are kept as explicit tests below.

Runs in interpret mode on CPU (``interpret=None`` auto-detects); on a
real TPU the identical suite exercises the compiled kernels.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import parity
from repro.kernels.chunk_gather.ops import chunk_gather, chunk_gather_train
from repro.kernels.chunk_gather.ref import chunk_gather_train_ref
from repro.kernels.common import resolve_interpret
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ops import flash_attention_train
from repro.kernels.flash_attention.ref import attention_ref_gqa
from repro.kernels.ssd_scan.ops import ssd_scan

pytestmark = pytest.mark.kernels

RNG = np.random.default_rng(7)


# ----------------------------------------------------------- registry sweep
@pytest.mark.parametrize(
    "case", parity.iter_cases(), ids=lambda c: c.name
)
def test_parity_grid(case):
    r = parity.check_case(case)
    assert r["ok"], (
        f"{r['case']}: max err {r['max_err']:.3e} exceeds tol {r['tol']:.0e}"
    )


def test_grid_covers_every_kernel():
    """The sweep must touch all four kernel packages (and stay in sync
    with the registry if one is added)."""
    swept = {c.kernel for c in parity.iter_cases()}
    assert swept == set(parity.KERNELS) and len(swept) >= 4


def test_interpret_auto_detection():
    """interpret=None resolves per backend: interpreted off-TPU, compiled
    on TPU; explicit values pass through."""
    import jax

    auto = resolve_interpret(None)
    assert auto == (jax.default_backend() != "tpu")
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


# --------------------------------------------------- flash_attention extras
def _qkv(b, s, h, kvh, d, dtype=jnp.float32):
    return tuple(jnp.asarray(RNG.normal(size=(b, s, n, d)), dtype) for n in (h, kvh, kvh))


def _scaled_err(out, ref):
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    return float(np.max(np.abs(out - ref))) / (float(np.max(np.abs(ref))) + 1e-6)


def _out_and_grads(attn, q, k, v, **kw):
    """The output, and the gradients of q, k, v for a fixed random
    cotangent of the output."""
    w = np.random.default_rng(3).normal(size=q.shape)

    @jax.jit
    def run(q, k, v):
        out, vjp = jax.vjp(lambda q, k, v: attn(q, k, v, **kw), q, k, v)
        return (out, *vjp(jnp.asarray(w, out.dtype)))

    return run(q, k, v)


def _ref_f32(q, k, v, **kw):
    f32 = lambda t: t.astype(jnp.float32)
    return attention_ref_gqa(f32(q), f32(k), f32(v), **kw)


class TestFlashAttentionTraining:
    """The training kernel (forward and backward) against the float32
    reference, causal, at head_dim 128: MHA and GQA with 4 query heads per
    kv head (phi3's ratio)."""

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)],
                             ids=["float32", "bfloat16"])
    @pytest.mark.parametrize("s", [256, 512])
    @pytest.mark.parametrize("h,kvh", [(2, 2), (8, 2)], ids=["mha", "gqa4"])
    def test_forward_and_gradients(self, h, kvh, s, dtype, tol):
        q, k, v = _qkv(1, s, h, kvh, 128, dtype)
        got = _out_and_grads(flash_attention_train, q, k, v, block_q=128, block_k=128)
        want = _out_and_grads(_ref_f32, q, k, v)
        for name, g, r in zip(("out", "dq", "dk", "dv"), got, want):
            assert g.dtype == dtype and g.shape == r.shape, name
            assert _scaled_err(g, r) <= tol, (name, _scaled_err(g, r))

    def test_matches_dense_attention_path(self):
        """Same numbers as the model's dense jnp path on its GQA config."""
        from repro.configs import ModelConfig
        from repro.models.attention import _dense_attention, _expand_kv

        cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=1024,
                          num_heads=8, num_kv_heads=2, d_ff=64, vocab_size=64,
                          head_dim=128)
        q, k, v = _qkv(2, 256, 8, 2, 128)

        def dense(q, k, v):
            return _dense_attention(q, _expand_kv(k, cfg), _expand_kv(v, cfg), cfg)

        got = _out_and_grads(flash_attention_train, q, k, v, block_q=128, block_k=64)
        for g, r in zip(got, _out_and_grads(dense, q, k, v)):
            assert _scaled_err(g, r) <= 2e-5


class TestFlashAttentionEdges:
    @pytest.mark.parametrize("window", [32, 96, 1024])
    def test_sliding_window(self, window):
        q, k, v = _qkv(2, 256, 1, 1, 64)
        got = _out_and_grads(flash_attention_train, q, k, v, window=window,
                             block_q=64, block_k=64)
        want = _out_and_grads(_ref_f32, q, k, v, window=window)
        for g, r in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-5, rtol=2e-5)

    def test_block_shape_independence(self):
        q, k, v = _qkv(2, 256, 1, 1, 64)
        outs = [
            flash_attention_train(q, k, v, block_q=bq, block_k=bk)
            for bq, bk in [(32, 32), (64, 128), (128, 64), (256, 256)]
        ]
        for o in outs[1:]:
            np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o), atol=1e-5, rtol=1e-5)

    def test_gqa_native(self):
        """Grouped kv heads are read through the index map, never repeated,
        and the kv gradients sum over each group's query heads."""
        q, k, v = _qkv(2, 128, 8, 2, 32)
        got = _out_and_grads(flash_attention_train, q, k, v, block_q=64, block_k=64)
        want = _out_and_grads(_ref_f32, q, k, v)
        assert got[0].shape == q.shape and got[2].shape == k.shape
        for g, r in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-5, rtol=2e-5)


class TestLatentAttentionWidths:
    """Latent attention's shape: q·k over 256 lanes (128 + 64 rotary,
    zero-padded to the chip's tiling) against values 128 wide, with the
    caller's softmax scale, forward and backward against the float32
    reference."""

    SCALE = 0.1147214  # 192^-0.5 times YaRN's mscale squared (DeepSeek-V2-Lite)

    @staticmethod
    def _mla_out_and_grads(attn, q, k, v, **kw):
        w = np.random.default_rng(5).normal(size=q.shape[:3] + v.shape[3:])

        @jax.jit
        def run(q, k, v):
            out, vjp = jax.vjp(lambda q, k, v: attn(q, k, v, **kw), q, k, v)
            return (out, *vjp(jnp.asarray(w, out.dtype)))

        return run(q, k, v)

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)],
                             ids=["float32", "bfloat16"])
    @pytest.mark.parametrize("h,kvh", [(2, 2), (4, 2)], ids=["mha", "gqa2"])
    def test_forward_and_gradients(self, h, kvh, dtype, tol):
        b, s = 1, 256
        q, k = (jnp.asarray(RNG.normal(size=(b, s, n, 256)), dtype) for n in (h, kvh))
        v = jnp.asarray(RNG.normal(size=(b, s, kvh, 128)), dtype)
        got = self._mla_out_and_grads(flash_attention_train, q, k, v, scale=self.SCALE,
                                      block_q=128, block_k=128)
        want = self._mla_out_and_grads(_ref_f32, q, k, v, scale=self.SCALE)
        assert got[0].shape == (b, s, h, 128)
        for name, g, r in zip(("out", "dq", "dk", "dv"), got, want):
            assert g.dtype == dtype and g.shape == r.shape, name
            assert _scaled_err(g, r) <= tol, (name, _scaled_err(g, r))

    def test_zero_lanes_change_no_score(self):
        """q and k of 192 lanes padded with zeros to 256 give the attention
        of the 192, and zero gradients in the padding."""
        q192, k192 = (jnp.asarray(RNG.normal(size=(1, 256, 2, 192)), jnp.float32)
                      for _ in range(2))
        v = jnp.asarray(RNG.normal(size=(1, 256, 2, 128)), jnp.float32)
        pad = lambda t: jnp.pad(t, ((0, 0), (0, 0), (0, 0), (0, 64)))
        got = self._mla_out_and_grads(flash_attention_train, pad(q192), pad(k192), v,
                                      scale=self.SCALE, block_q=128, block_k=128)
        want = self._mla_out_and_grads(_ref_f32, q192, k192, v, scale=self.SCALE)
        assert _scaled_err(got[0], want[0]) <= 2e-5
        for g, r in zip(got[1:3], want[1:3]):
            assert _scaled_err(g[..., :192], r) <= 2e-5
            assert float(jnp.max(jnp.abs(g[..., 192:]))) == 0.0
        assert _scaled_err(got[3], want[3]) <= 2e-5


# -------------------------------------------------- decode_attention extras
class TestDecodeAttentionEdges:
    def test_ring_buffer_mask(self):
        """Rotating-window cache = arbitrary validity pattern; exactness."""
        b, h, kvh, s, d = 1, 4, 2, 256, 64
        q = jnp.asarray(RNG.normal(size=(b, h, d)), jnp.float32)
        ck = jnp.asarray(RNG.normal(size=(b, s, kvh, d)), jnp.float32)
        cv = jnp.asarray(RNG.normal(size=(b, s, kvh, d)), jnp.float32)
        # only slots [64:128) valid, as after ring wrap-around
        mask = jnp.zeros((b, s), bool).at[:, 64:128].set(True)
        out = decode_attention(q, ck, cv, mask, block_k=64)
        qg = q.reshape(b * kvh, h // kvh, d)

        def fold(t):
            return t.transpose(0, 2, 1, 3).reshape(b * kvh, s, d)

        m = jnp.repeat(mask[:, None, :], kvh, 1).reshape(b * kvh, s)
        ref = decode_attention_ref(qg, fold(ck), fold(cv), m).reshape(b, h, d)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


# ------------------------------------------------------ chunk_gather extras
class TestChunkGatherEdges:
    def test_duplicate_indices(self):
        """Redirection may serve the same slot to multiple rows in a step."""
        ct = jnp.asarray(RNG.integers(1, 100, (8, 1, 32)), jnp.int32)
        lens = jnp.full((8,), 32, jnp.int32)
        idx = jnp.asarray([3, 3, 3, 0], jnp.int32)
        t, _ = chunk_gather(ct, lens, idx)
        np.testing.assert_array_equal(np.asarray(t[0]), np.asarray(t[1]))
        np.testing.assert_array_equal(np.asarray(t[0]), np.asarray(ct[3, 0]))

    def test_train_matches_host_grid_semantics(self):
        """chunk_gather_train == the loader's _to_grid slicing: tokens are
        row[:-1], targets row[1:], mask aligned to targets."""
        slots, full, b = 6, 33, 9  # seq_len 32
        ct = jnp.asarray(RNG.integers(1, 500, (slots, 1, 40)), jnp.int32)
        lens = jnp.asarray([1, 5, 33, 17, 40, 2], jnp.int32).clip(max=full)
        idx = jnp.asarray(RNG.integers(0, slots, (b,)), jnp.int32)
        tok, tgt, mask = chunk_gather_train(ct, lens, idx, seq_len=32, pad_id=0)
        rt, rg, rm = chunk_gather_train_ref(ct, lens, idx, seq_len=32, pad_id=0)
        np.testing.assert_array_equal(np.asarray(tok), np.asarray(rt))
        np.testing.assert_array_equal(np.asarray(tgt), np.asarray(rg))
        np.testing.assert_array_equal(np.asarray(mask), np.asarray(rm))
        # length-1 record (slot 0): no target at all -> all-zero mask row
        rows = np.flatnonzero(np.asarray(idx) == 0)
        for r in rows:
            assert np.asarray(mask)[r].sum() == 0

    def test_train_duplicate_slots_share_one_row(self):
        ct = jnp.asarray(RNG.integers(1, 100, (8, 1, 40)), jnp.int32)
        lens = jnp.full((8,), 33, jnp.int32)
        idx = jnp.asarray([5, 5, 2, 5], jnp.int32)
        tok, tgt, _ = chunk_gather_train(ct, lens, idx, seq_len=32)
        np.testing.assert_array_equal(np.asarray(tok[0]), np.asarray(tok[1]))
        np.testing.assert_array_equal(np.asarray(tok[0]), np.asarray(tok[3]))
        np.testing.assert_array_equal(np.asarray(tok[0]), np.asarray(ct[5, 0, :32]))
        np.testing.assert_array_equal(np.asarray(tgt[0]), np.asarray(ct[5, 0, 1:33]))


# ---------------------------------------------------------- ssd_scan extras
class TestSSDScanEdges:
    def test_chunk_size_independence(self):
        bh, s, p, n = 2, 256, 32, 16
        x = jnp.asarray(RNG.normal(size=(bh, s, p)), jnp.float32)
        dt = jnp.asarray(RNG.random((bh, s)) * 0.3 + 0.01, jnp.float32)
        a = jnp.asarray(-RNG.random((bh, 1)) - 0.1, jnp.float32)
        b = jnp.asarray(RNG.normal(size=(bh, s, n)), jnp.float32)
        c = jnp.asarray(RNG.normal(size=(bh, s, n)), jnp.float32)
        outs = [np.asarray(ssd_scan(x, dt, a, b, c, chunk=cs)) for cs in (32, 64, 128, 256)]
        for o in outs[1:]:
            np.testing.assert_allclose(outs[0], o, atol=1e-4, rtol=1e-4)
