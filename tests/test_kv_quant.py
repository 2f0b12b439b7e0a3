"""int8 KV-cache quantization: accuracy + cache-structure tests (§Perf lever)."""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, reduced
from repro.models import build_model, split_params
from repro.models.attention import dequantize_kv, quantize_kv
from repro.train.train_step import build_decode_step, build_prefill_step


class TestQuantPrimitive:
    def test_roundtrip_error_bounded(self):
        rng = np.random.default_rng(0)
        t = jnp.asarray(rng.normal(size=(2, 64, 4, 32)), jnp.float32)
        q, s = quantize_kv(t)
        back = dequantize_kv(q, s, jnp.float32)
        rel = float(jnp.max(jnp.abs(back - t)) / jnp.max(jnp.abs(t)))
        assert q.dtype == jnp.int8 and s.dtype == jnp.bfloat16
        assert rel < 0.02  # 1/127 per-row symmetric quantisation

    def test_zero_rows_safe(self):
        q, s = quantize_kv(jnp.zeros((3, 8)))
        assert np.all(np.asarray(q) == 0)
        assert np.isfinite(np.asarray(s, np.float32)).all()


@pytest.mark.parametrize("name", ["tinyllama-1.1b", "deepseek-moe-16b"])
class TestQuantizedDecode:
    def test_prefill_decode_close_to_fp(self, name):
        base = reduced(ARCHS[name])  # MoE: the dropless expert layer
        qcfg = dataclasses.replace(base, kv_cache_dtype="int8")
        rng = np.random.default_rng(4)
        T, b = 16, 2
        toks = jnp.asarray(rng.integers(0, base.vocab_size, (b, T + 1)), jnp.int32)

        outs = {}
        for cfg in (base, qcfg):
            model = build_model(cfg)
            values, _ = split_params(model.init(0))
            prefill = build_prefill_step(model, max_len=32)
            decode = build_decode_step(model)
            _, cache = prefill(values, {"tokens": toks[:, :T]})
            lg, _ = decode(values, cache, toks[:, T : T + 1], jnp.int32(T))
            outs[cfg.kv_cache_dtype] = np.asarray(lg[:, 0], np.float32)
        err = np.max(np.abs(outs[""] - outs["int8"]))
        scale = np.max(np.abs(outs[""])) + 1e-9
        assert err / scale < 0.05, err / scale
        # ranking of the argmax token should survive quantisation
        assert (outs[""].argmax(-1) == outs["int8"].argmax(-1)).mean() >= 0.5

    def test_cache_is_int8(self, name):
        qcfg = dataclasses.replace(reduced(ARCHS[name]), kv_cache_dtype="int8")
        model = build_model(qcfg)
        cache = model.init_cache(batch=2, max_len=16)
        leaves = jax.tree.leaves(cache)
        assert any(l.dtype == jnp.int8 for l in leaves)
        # int8 cache + bf16 scales is ~half the bf16 cache footprint
        q_bytes = sum(l.size * l.dtype.itemsize for l in leaves)
        fp = build_model(reduced(ARCHS[name])).init_cache(batch=2, max_len=16)
        fp_bytes = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(fp))
        assert q_bytes < 0.7 * fp_bytes