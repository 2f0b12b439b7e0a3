"""DeepSeek-V2 (MLA + DeepSeekMoE) against its float32 reference at a small
size, on the CPU: weights, loss and gradients, Adafactor steps through the
train step, the expert share, dropless dispatch and YaRN.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_deepseek_v2.py
"""

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.models.deepseek_v2 import program_config
from bench.reference import data as ref_data
from bench.reference import deepseek_v2 as ref

#: Every width of the published block cut down, 1 dense + 2 MoE layers,
#: 8 routed experts of which 2 are held (ids 2-3), top-3, YaRN over 32
#: original positions so that the 64-position rows reach its blend.
CONFIG = {
    "name": "tiny-v2", "source": "test", "bench_model": "deepseek_v2",
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 128, "intermediate_size": 192, "kv_lora_rank": 64,
    "max_position_embeddings": 4096, "moe_intermediate_size": 64, "moe_layer_freq": 1,
    "n_group": 1, "n_routed_experts": 2, "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 4, "num_experts_per_tok": 3, "num_hidden_layers": 3,
    "num_key_value_heads": 4, "q_lora_rank": None, "qk_nope_head_dim": 32,
    "qk_rope_head_dim": 16, "rms_norm_eps": 1e-6,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4, "mscale": 0.707,
                     "mscale_all_dim": 0.707, "original_max_position_embeddings": 32,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1, "scoring_func": "softmax",
    "seq_aux": True, "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "greedy", "v_head_dim": 32, "vocab_size": 512,
    "aux_loss_alpha": 0.001, "torch_dtype": "bfloat16",
    "published": {"n_routed_experts": 8},
    "expert_parallel": {"ranks": 4, "rank": 1, "first_expert": 2},
}
F32_CONFIG = dict(CONFIG, torch_dtype="float32")
SEQ = 64
#: Float32 program against the float32 reference: the same products in
#: another order (attention blocks, the held experts' grouped matmuls
#: against a dense sum over them), so round-off of about 1e-6 relative;
#: 2e-3 on a gradient leaf leaves room for leaves whose entries cancel.
LOSS_RTOL, GRAD_RTOL, GRAD_ATOL = 1e-5, 2e-3, 1e-6


def _published(cfg=CONFIG, **kw):
    """The same block with every routed expert held (the uncut layer)."""
    return dict(cfg, n_routed_experts=cfg["published"]["n_routed_experts"],
                expert_parallel={"ranks": 1, "rank": 0, "first_expert": 0}, **kw)


def _program(cfg):
    from repro.configs import RunConfig
    from repro.models import build_model
    from repro.optim.optimizers import make_optimizer
    from repro.train.train_step import build_train_step, init_train_state

    model = build_model(program_config(cfg))
    run = RunConfig(optimizer="adafactor", remat="full")
    opt = make_optimizer(run)
    return model, init_train_state(model, opt, 0), jax.jit(build_train_step(model, run, opt))


def _batches(n, seed=3):
    corpus = ref_data.Corpus(64, CONFIG["vocab_size"], SEQ // 2, seed)
    out = []
    for s in range(n):
        rows = [ref_data.expected_row(corpus.tokens(d), SEQ) for d in range(4 * s, 4 * s + 4)]
        out.append({k: np.stack([r[i] for r in rows])
                    for i, k in enumerate(("tokens", "targets", "loss_mask"))})
    return out


def _program_leaf(values, name, dims):
    if not name.startswith("layer"):
        return values[name]
    layer, path = name.split(".", 1)
    i = int(layer[5:])
    seg = dims.segment(i)
    leaf = values["segments"][seg]
    for k in path.split("."):
        leaf = leaf[k]
    return leaf[i - (dims.dense_layers if seg else 0)]


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_init_is_the_programs_init(dtype):
    cfg = dict(CONFIG, torch_dtype=dtype)
    _, state, _ = _program(cfg)
    dims = ref.Dims.from_config(cfg)
    params = ref.init_params(dims, 0)
    names = [n for n, _, _, _ in harness._layer_slices(state["values"])]
    assert sorted(names) == sorted(n for n, _ in ref.leaf_items(params))
    for name, value in ref.leaf_items(params):
        got = np.asarray(_program_leaf(state["values"], name, dims).astype(jnp.float32))
        np.testing.assert_array_equal(got, np.asarray(value), err_msg=name)


def test_a_share_holds_the_uncut_models_experts():
    _, share, _ = _program(CONFIG)
    _, whole, _ = _program(_published())
    got = share["values"]["segments"][1]["moe"]
    want = whole["values"]["segments"][1]["moe"]
    for name in ("wi_gate", "wi_up", "wo"):
        np.testing.assert_array_equal(np.asarray(got[name], np.float32),
                                      np.asarray(want[name][:, 2:4], np.float32))
    np.testing.assert_array_equal(np.asarray(got["router"], np.float32),
                                  np.asarray(want["router"], np.float32))


def test_loss_and_gradients_match_the_program():
    from repro.train.losses import lm_loss

    model, state, _ = _program(F32_CONFIG)
    batch = _batches(1)[0]
    feed = {k: jnp.asarray(v) for k, v in batch.items()}
    cfg = model.cfg

    def loss_fn(values):
        logits, aux, _ = model.forward(values, feed)
        return lm_loss(logits, feed["targets"], feed["loss_mask"], aux=aux["balance"],
                       aux_weight=cfg.router_aux_weight)[0]

    want_loss, want = jax.value_and_grad(loss_fn)(state["values"])
    dims = ref.Dims.from_config(F32_CONFIG)
    for rows in (4, 1):  # one block, and blocks of one row
        loss, grads = ref.Trainer(dims, rows=rows).loss_and_grads(
            ref.init_params(dims, 0), batch)
        assert loss == pytest.approx(float(want_loss), rel=LOSS_RTOL)
        for name, g in ref.leaf_items(grads):
            np.testing.assert_allclose(np.asarray(g),
                                       np.asarray(_program_leaf(want, name, dims)),
                                       rtol=GRAD_RTOL, atol=GRAD_ATOL, err_msg=name)


def test_three_adafactor_steps_match_the_program():
    """Through the train step the launcher runs: the losses, and each
    leaf's change after three updates (Adafactor per stacked tensor of
    each segment, as the reference groups them)."""
    _, state, step = _program(F32_CONFIG)
    batches = _batches(3)
    losses, pairs = [], []
    for b in batches:
        state, metrics = step(state, {k: jnp.asarray(v) for k, v in b.items()})
        losses.append(float(metrics["loss"]))
        pairs.append(int(metrics["expert_pairs"]))
    dims = ref.Dims.from_config(F32_CONFIG)
    got = ref.train_readings(dims, 0, batches, rows=4)
    assert got["losses"] == pytest.approx(losses, rel=LOSS_RTOL)
    params = ref.init_params(dims, 0)
    for name, init in ref.leaf_items(params):
        prog = ref.diff_norm(_program_leaf(state["opt"]["master"], name, dims), init)
        assert got["change_norms"][name] == pytest.approx(prog, rel=1e-3, abs=1e-9), name
    # 2 MoE layers x 4 x 64 tokens x top-3 over 8 experts: the 2 held see a share
    assert all(0 < n < 2 * 4 * SEQ * 3 for n in pairs)


def _moe_inputs(cfg, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, 16, cfg["hidden_size"]), jnp.float32)
    return x


def _share_layer(cfg):
    """The program's MoE layer of ``cfg`` (float32) and its weights."""
    from repro.models.common import RngStream, split_params
    from repro.models.moe import init_moe, moe_block

    pc = program_config(dict(cfg, torch_dtype="float32"))
    values, _ = split_params(init_moe(RngStream(7), pc, jnp.float32))
    return jax.jit(lambda v, x: moe_block(v, x, pc)), values


def _ref_layer(values, x, cfg):
    dims = ref.Dims.from_config(dict(cfg, torch_dtype="float32"))
    p = {f"moe.{k}": v for k, v in values.items() if k != "shared"}
    p.update({f"moe.shared.{k}": v for k, v in values["shared"].items()})
    with jax.default_matmul_precision("highest"):
        out, aux = ref.moe_layer(p, x, dims)
    return out, aux


def test_the_four_shares_sum_to_the_uncut_layer():
    """Each of the 4 ranks of the expert-parallel group computes its own 2
    experts' part for the tokens routed to them; with the shared experts
    (which every rank computes alike) counted once, the parts add up to the
    uncut reference layer, and the pairs to every token's top-k."""
    whole = _published()
    x = _moe_inputs(whole)
    ref_fn, ref_values = _share_layer(whole)
    want, _ = _ref_layer(ref_values, x, whole)
    parts, pairs = [], 0
    for rank in range(4):
        cfg = dict(CONFIG, expert_parallel={"ranks": 4, "rank": rank, "first_expert": 2 * rank})
        fn, values = _share_layer(cfg)
        out, _, n = fn(values, x)
        parts.append(out)
        pairs += int(n)
    shared = ref._swiglu(x, ref_values["shared"]["wi_gate"], ref_values["shared"]["wi_up"],
                         ref_values["shared"]["wo"], "f32")
    total = sum(parts) - 3 * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=1e-4, atol=1e-5)
    assert pairs == x.shape[0] * x.shape[1] * CONFIG["num_experts_per_tok"]


def test_dropless_when_every_token_picks_one_expert():
    """A router that sends every token to expert 2 (held here) first: every
    one of those pairs is computed, where a capacity of 1.25 x the even
    share would keep 7 of each row's 16."""
    fn, values = _share_layer(CONFIG)
    router = np.zeros(values["router"].shape, np.float32)
    router[:, 2] = 1.0
    values = dict(values, router=jnp.asarray(router))
    x = jnp.abs(_moe_inputs(CONFIG)) + 1.0   # every score favours expert 2
    out, _, pairs = fn(values, x)
    t = x.shape[0] * x.shape[1]
    # ties among the other 7 experts pick ids 0, 1: none of them held
    assert int(pairs) == t
    want, _ = _ref_layer(values, x, CONFIG)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4, atol=1e-5)
    cap = int(x.shape[1] * CONFIG["num_experts_per_tok"] * 1.25 / 8)
    assert cap < x.shape[1] / 2


def test_rows_past_the_groups_reach_neither_output_nor_gradient(monkeypatch):
    """The grouped matmul leaves the rows past its groups undefined (the
    TPU's kernel does not write them, forward or backward); with junk
    there, the share's output and its gradients still equal the
    reference's."""
    real = jax.lax.ragged_dot

    def junk(t, sizes):
        return jnp.where((jnp.arange(t.shape[0]) < jnp.sum(sizes))[:, None], t, 7.0)

    @jax.custom_vjp
    def leaky(lhs, rhs, sizes):
        return junk(real(lhs, rhs, sizes), sizes)

    def leaky_fwd(lhs, rhs, sizes):
        return leaky(lhs, rhs, sizes), (lhs, rhs, sizes)

    def leaky_bwd(res, g):
        lhs, rhs, sizes = res
        _, pull = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)
        d_lhs, d_rhs = pull(g)
        return junk(d_lhs, sizes), d_rhs, None

    leaky.defvjp(leaky_fwd, leaky_bwd)
    monkeypatch.setattr(jax.lax, "ragged_dot", lambda lhs, rhs, sizes: leaky(lhs, rhs, sizes))
    fn, values = _share_layer(CONFIG)
    x = _moe_inputs(CONFIG)
    w = jax.random.normal(jax.random.PRNGKey(1), x.shape, jnp.float32)

    def prog(v, x):
        return jnp.sum(fn(v, x)[0] * w)

    def want(v, x):
        return jnp.sum(_ref_layer(v, x, CONFIG)[0] * w)

    got = jax.grad(prog, (0, 1))(values, x)
    ref_g = jax.grad(want, (0, 1))(values, x)
    assert prog(values, x) == pytest.approx(float(want(values, x)), rel=1e-5)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref_g)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-3, atol=1e-5)


def test_yarn_frequencies_and_scale_in_closed_form():
    """DeepSeek-V2-Lite's rotary part (64 lanes, theta 10,000, factor 40
    over 4,096 positions, beta 32 / 1): pairs 0-10 keep their frequency,
    pairs 23-31 are divided by 40, the ones between blend linearly; the
    softmax scale is 192^-0.5 (0.1 * 0.707 * ln 40 + 1)^2."""
    from repro.configs import get_config
    from repro.models.attention import mla_scale
    from repro.models.common import rope_inv_freq

    cfg = get_config("deepseek-v2-lite-16b")
    # correction dims: 64 ln(4096 / (r 2 pi)) / (2 ln 10^4) = 10.47 (r=32), 22.51 (r=1)
    low, high = 10, 23
    i = np.arange(32)
    base = 10000.0 ** (-2 * i / 64)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = base * (1 - ramp) + base / 40 * ramp
    np.testing.assert_allclose(rope_inv_freq(64, 10000.0, cfg), want, rtol=1e-6)
    assert np.all(rope_inv_freq(64, 10000.0, cfg)[:11] == np.float32(base[:11]))
    m = 0.1 * 0.707 * math.log(40) + 1
    assert mla_scale(cfg) == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    assert mla_scale(cfg) == pytest.approx(0.1147214, rel=1e-6)
    dims = ref.Dims.from_config(json_config())
    np.testing.assert_allclose(ref.yarn_inv_freq(dims), want, rtol=1e-6)
    assert ref.softmax_scale(dims) == pytest.approx(mla_scale(cfg), rel=1e-12)


def json_config():
    import json

    return json.loads((harness.BENCH / "configs" / "deepseek-v2-lite.json").read_text())


def test_the_cut_is_the_published_block():
    """The one-chip file maps onto the published widths, 16 of the 64
    experts held, and the registered published configuration counts the
    model's 15,706,484,224 parameters."""
    from repro.configs import get_config

    pc = program_config(json_config())
    full = get_config("deepseek-v2-lite-16b")
    for key in ("d_model", "num_heads", "d_ff", "moe_num_experts", "moe_top_k",
                "moe_num_shared", "moe_dense_ff", "kv_lora_rank", "qk_nope_dim",
                "qk_rope_dim", "v_head_dim", "yarn_factor", "router_aux_weight"):
        assert getattr(pc, key) == getattr(full, key), key
    assert (pc.num_layers, pc.experts_held, pc.moe_expert_offset, pc.vocab_size) == (
        5, 16, 0, 25600)
    assert full.param_count() == 15_706_484_224
    assert pc.param_count() == 864_313_856


def test_sound_run_is_correct():
    """A whole benchmark run of the tiny block on the CPU, as ``run.py``
    makes it: trained through the launcher, checked against the reference."""
    spec_cell = harness.Cell("tiny-v2.test", 1, dict(CONFIG),
                             {"argv": ["--batch", "2", "--seq-len", "64", "--num-docs", "128"],
                              "storage": {"latency_s": 0.0}}, "tiny-v2", [], [],
                             {"rows_vs_host_loader": 0, "rows_vs_corpus": 0,
                              "repeated_docs": 0, "grad_norm_gap": 0.02,
                              "change_norm_gap": 0.02})
    out = harness.run(spec_cell, 2**31 + 12345, 0.5, False, time.perf_counter(),
                      require_chip=False, log=lambda msg: None)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_config_lists_what_the_program_cannot_compute():
    with pytest.raises(ValueError, match="q_lora_rank"):
        program_config(dict(CONFIG, q_lora_rank=1536))
    with pytest.raises(ValueError, match="scoring_func"):
        program_config(dict(CONFIG, scoring_func="sigmoid"))
    assert dataclasses.replace(program_config(CONFIG)).attn_kind == "mla"
