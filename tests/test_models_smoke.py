"""Per-architecture smoke tests (assignment deliverable (f)).

Each assigned arch instantiates a REDUCED same-family config and runs a
real forward + train step on CPU, asserting output shapes and no NaNs.
Full configs are exercised only via the dry-run (ShapeDtypeStruct).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, RunConfig, get_config, list_archs, reduced
from repro.launch.specs import dummy_train_inputs
from repro.models import build_model, split_params
from repro.optim.optimizers import make_optimizer
from repro.train.train_step import (
    build_decode_step,
    build_prefill_step,
    build_train_step,
    init_train_state,
)

ALL = list_archs()


@pytest.fixture(scope="module")
def built():
    cache = {}

    def get(name):
        if name not in cache:
            cfg = reduced(ARCHS[name])
            model = build_model(cfg)
            values, axes = split_params(model.init(0))
            cache[name] = (cfg, model, values)
        return cache[name]

    return get


@pytest.mark.parametrize("name", ALL)
def test_forward_shapes_and_finite(name, built):
    cfg, model, values = built(name)
    b, s = 2, 128
    inputs = dummy_train_inputs(cfg, b, s, seed=1)
    logits, aux, _ = model.forward(values, inputs)
    assert logits.shape == (b, s, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all(), f"{name}: NaNs"
    assert np.isfinite(float(aux["balance"]))


@pytest.mark.parametrize("name", ALL)
def test_train_step_descends(name, built):
    cfg, model, _ = built(name)
    run = RunConfig(optimizer="adamw", learning_rate=1e-3)
    opt = make_optimizer(run)
    state = init_train_state(model, opt, 0)
    step = jax.jit(build_train_step(model, run, opt), donate_argnums=0)
    batch = dummy_train_inputs(cfg, 4, 64, seed=0)
    losses = []
    for _ in range(4):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
        assert np.isfinite(losses[-1])
    assert losses[-1] < losses[0], f"{name}: loss did not descend {losses}"


@pytest.mark.parametrize(
    "name", [n for n in ALL if ARCHS[n].supports_decode()]
)
def test_prefill_decode_consistency(name, built):
    """decode(cache(prefill(x[:T]))) logits == forward(x[:T+1]) at position T.

    MoE archs run the dropless expert layer, so a prefill of T tokens and a
    one-token decode route every token alike and a cache bug cannot hide
    behind dropped tokens. The VLM arch prefixes patch embeddings in both
    paths.
    """
    cfg, model, values = built(name)
    rng = np.random.default_rng(3)
    T, b = 16, 2
    toks = rng.integers(0, cfg.vocab_size, (b, T + 1)).astype(np.int32)
    if cfg.frontend == "patch":
        p = cfg.frontend_len
        patches = jnp.asarray(rng.normal(size=(b, p, cfg.frontend_dim)), jnp.float32)
        full_inp = {"tokens": jnp.asarray(toks), "patch_embeds": patches}
        pre_inp = {"tokens": jnp.asarray(toks[:, :T]), "patch_embeds": patches}
        pos_t = p + T
    else:
        full_inp = {"tokens": jnp.asarray(toks)}
        pre_inp = {"tokens": jnp.asarray(toks[:, :T])}
        pos_t = T
    full, _, _ = model.forward(values, full_inp)
    prefill = build_prefill_step(model, max_len=pos_t + 8)
    decode = build_decode_step(model)
    _, cache = prefill(values, pre_inp)
    lg, cache = decode(values, cache, jnp.asarray(toks[:, T : T + 1]), jnp.int32(pos_t))
    err = float(jnp.max(jnp.abs(lg[:, 0] - full[:, pos_t])))
    assert err < 5e-3, f"{name}: prefill/decode mismatch {err}"


@pytest.mark.parametrize(
    "name", [n for n in ALL if ARCHS[n].supports_decode()]
)
def test_multi_step_decode_finite(name, built):
    cfg, model, values = built(name)
    b = 2
    cache = model.init_cache(batch=b, max_len=64)
    decode = build_decode_step(model)
    tok = jnp.zeros((b, 1), jnp.int32)
    for t in range(4):
        lg, cache = decode(values, cache, tok, jnp.int32(t))
        assert np.isfinite(np.asarray(lg, np.float32)).all()
        tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)


def test_param_count_formula_matches_dense():
    """ModelConfig.param_count() is exact for attention-family archs."""
    for name in ("tinyllama-1.1b", "deepseek-7b", "deepseek-moe-16b", "deepseek-v2-lite-16b",
                 "hubert-xlarge", "llava-next-34b"):
        cfg = reduced(ARCHS[name])
        model = build_model(cfg)
        values, _ = split_params(model.init(0))
        actual = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(values))
        assert cfg.param_count() == actual, (name, cfg.param_count(), actual)


def test_full_config_layer_structure():
    """Full configs expose the exact assigned hyperparameters."""
    sc = get_config("starcoder2-15b")
    assert (sc.num_layers, sc.d_model, sc.num_heads, sc.num_kv_heads) == (40, 6144, 48, 4)
    assert (sc.d_ff, sc.vocab_size) == (24576, 49152)
    kimi = get_config("kimi-k2-1t-a32b")
    assert (kimi.moe_num_experts, kimi.moe_top_k) == (384, 8)
    assert kimi.param_count() > 0.9e12, "kimi must be ~1T params"
    z = get_config("zamba2-1.2b")
    layout = z.block_layout()
    assert layout.count("mamba2") == 38 and layout.count("shared_attn") == 6
    x = get_config("xlstm-350m")
    lx = x.block_layout()
    assert lx.count("slstm") == 3 and lx.count("mlstm") == 21


def test_reduced_zamba_has_shared_attention(built):
    cfg, model, values = built("zamba2-1.2b")
    assert "shared_attn" in values
    assert any(k == "shared_attn" for k, _ in cfg.segments())


@pytest.mark.parametrize("threshold,path", [(2048, "dense"), (128, "chunked")])
def test_attention_block_takes_jnp_path_on_cpu(threshold, path, monkeypatch):
    """A causal config on the kernel's tiling (head_dim 128) still runs the
    jnp path on the CPU, to the bit, and records the path it takes."""
    from repro.configs import ModelConfig
    from repro.models import attention
    from repro.models.common import RngStream
    from repro.obs import trace

    cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=512,
                      num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                      head_dim=128, attn_dense_threshold=threshold)
    p, _ = split_params(attention.init_attention(RngStream(0), cfg, jnp.float32))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 256, 512)), jnp.float32)

    def run():  # a new function each time: jit keeps traces by function
        return jax.jit(lambda p, x: attention.attention_block(p, x, cfg)[0])(p, x)

    with trace.tracing() as t:
        out = run()
    events = [ev[5] for ev in t.events() if ev[0] == "attention.path"]
    assert events == [dict(path=path, b=2, s=256, h=4, kvh=2, qk=128, v=128)]
    monkeypatch.setattr(attention, "_kernel_applies", lambda *a: False)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(run()))


def test_attention_kernel_needs_an_unsplit_causal_layout():
    """The kernel is taken only where it computes exactly this attention on
    one device."""
    import dataclasses

    from jax.sharding import AbstractMesh

    from repro.configs import ModelConfig
    from repro.models.attention import _kernel_applies
    from repro.parallel.axes import ShardingRules, sharding_ctx

    cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=512,
                      num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                      head_dim=128)
    q = jax.ShapeDtypeStruct((2, 256, 4, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2, 256, 2, 128), jnp.bfloat16)
    assert _kernel_applies(q, k, cfg)
    assert not _kernel_applies(q, k, dataclasses.replace(cfg, causal=False))
    assert not _kernel_applies(q, k, dataclasses.replace(cfg, logit_softcap=30.0))
    q64 = jax.ShapeDtypeStruct((2, 256, 8, 64), jnp.bfloat16)
    assert not _kernel_applies(q64, q64, dataclasses.replace(cfg, head_dim=64))
    odd = jax.ShapeDtypeStruct((2, 200, 4, 128), jnp.bfloat16)
    assert not _kernel_applies(odd, odd, cfg)
    rules = {"batch": "data", "heads": "model"}
    with sharding_ctx(ShardingRules(AbstractMesh((1, 1), ("data", "model")), rules)):
        assert _kernel_applies(q, k, cfg)
    with sharding_ctx(ShardingRules(AbstractMesh((1, 2), ("data", "model")), rules)):
        assert not _kernel_applies(q, k, cfg)
