"""The expert share's compact dispatch buffer (``models/moe.py``), float32 on
the CPU: a share of 2 of 8 experts, top-2, over 1,024 tokens, whose buffer
holds 1,024 of the 2,048 (token, expert) pairs. Against the full-size
buffer and a dense sum over the held experts: the output, the balance
loss, the pairs, the gradients; the full-size fallback where the held pairs
outnumber the buffer; no ``cond`` where every expert is held; and the rows
past the groups, which the grouped matmul leaves undefined.

    JAX_PLATFORMS=cpu python -m pytest -q tests/test_moe_share.py
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, RunConfig, reduced
from repro.launch.specs import dummy_train_inputs
from repro.models import build_model, moe
from repro.models.common import RngStream, split_params
from repro.optim.optimizers import make_optimizer
from repro.train.train_step import build_train_step, init_train_state

B, S = 2, 512
#: 2 of 8 experts held (ids 2-3), top-2, widths cut to keep the CPU quick
CFG = dataclasses.replace(reduced(ARCHS["deepseek-v2-lite-16b"]), d_model=32, d_ff=16,
                          moe_experts_held=2, moe_expert_offset=2)
PAIRS = B * S * CFG.moe_top_k
ROWS = moe._buffer_rows(PAIRS, CFG.experts_held, CFG.moe_num_experts)
#: float32, the same products summed in another order: each leaf within
#: 1e-5 of its largest entry (weight gradients sum over 1,024 tokens)
RTOL = 1e-5


def _values(cfg=CFG):
    values, _ = split_params(moe.init_moe(RngStream(0), cfg, jnp.float32))
    return values


def _x(cfg=CFG, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, S, cfg.d_model), jnp.float32)


def _full_size(monkeypatch):
    """Every pair gets a row: the buffer the share had before it was cut."""
    monkeypatch.setattr(moe, "_HEADROOM", 1e9)


def _dense(values, x, cfg=CFG):
    """The layer as a dense sum over the held experts: each token's gate
    weight for each held expert times that expert's SwiGLU of the token,
    plus the shared experts."""
    top_p, top_e, aux = moe.route(values, x, cfg)
    out = moe._shared_experts(values, x, cfg, jnp.zeros_like(x))
    for i in range(cfg.experts_held):
        gate = jnp.sum(jnp.where(top_e == cfg.moe_expert_offset + i, top_p, 0.0), -1)
        h = jax.nn.silu(x @ values["wi_gate"][i]) * (x @ values["wi_up"][i])
        out = out + gate[..., None] * (h @ values["wo"][i])
    return out, aux


def _readings(fn, values, x):
    """Output, aux, pairs, and the gradients of x and every weight of a
    weighted sum of the output plus the balance loss."""
    w = jax.random.normal(jax.random.PRNGKey(2), x.shape, jnp.float32)

    def loss(v, x):
        out, aux, pairs = fn(v, x)
        return jnp.sum(out * w) + aux["balance"], (out, aux, pairs)

    (_, got), grads = jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(values, x)
    return got, grads


def _share(values, x, cfg=CFG):
    return moe.moe_block(values, x, cfg)


def _as_share(dense):
    def fn(values, x):
        out, aux = dense(values, x)
        return out, {"balance": aux}, None
    return fn


def _assert_same(got, want):
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        r = np.asarray(r)
        np.testing.assert_allclose(np.asarray(g), r, rtol=0,
                                   atol=RTOL * float(np.max(np.abs(r))) + 1e-7)


def test_the_buffer_is_sized_by_the_held_share():
    """The DeepSeek-V2-Lite cell's 16 of 64 experts over 16,384 tokens,
    top-6: 1.5 x 24,576 expected pairs, 36,864 of 98,304 rows; every pair
    where every expert is held; never more rows than pairs."""
    assert moe._buffer_rows(98_304, 16, 64) == 36_864
    assert moe._buffer_rows(98_304, 64, 64) == 98_304
    assert moe._buffer_rows(96, 2, 8) == 96
    assert ROWS == 1024 < PAIRS


def test_the_compact_buffer_equals_the_full_size_one(monkeypatch):
    values, x = _values(), _x()
    (out, aux, pairs), grads = _readings(_share, values, x)
    assert int(aux["moe_overflow"]) == 0
    assert 0 < int(pairs) <= ROWS
    (d_out, d_aux, _), d_grads = _readings(_as_share(_dense), values, x)
    _assert_same((out, aux["balance"], grads), (d_out, d_aux["balance"], d_grads))
    _full_size(monkeypatch)
    (f_out, f_aux, f_pairs), f_grads = _readings(_share, values, x)
    assert int(f_pairs) == int(pairs)
    assert int(f_aux["moe_overflow"]) == 0
    _assert_same((out, aux["balance"], grads), (f_out, f_aux["balance"], f_grads))


def _favour_held(values, x, cfg=CFG):
    """A router under which held expert 2 scores feature 0 and held expert
    3 feature 1, the others 0, with features 0 and 1 shifted up: a token
    picks expert 2 where its feature 0 is positive (84%) and expert 3 where
    its feature 1 is (69%), about 1,570 held pairs with gate weights of
    order 0.3."""
    router = np.zeros(values["router"].shape, np.float32)
    router[0, cfg.moe_expert_offset] = router[1, cfg.moe_expert_offset + 1] = 1.0
    shift = np.zeros(x.shape[-1], np.float32)
    shift[:2] = [1.0, 0.5]
    return dict(values, router=jnp.asarray(router)), x + shift


def test_held_pairs_past_the_buffer_take_the_full_size_one(monkeypatch):
    """Routing biased to the held experts: more held pairs than the 1,024
    rows. The layer takes the full-size buffer, drops nothing and says so."""
    values, x = _favour_held(_values(), _x())
    (out, aux, pairs), grads = _readings(_share, values, x)
    assert ROWS < int(pairs) < PAIRS
    assert int(aux["moe_overflow"]) == 1
    (d_out, d_aux, _), d_grads = _readings(_as_share(_dense), values, x)
    _assert_same((out, aux["balance"], grads), (d_out, d_aux["balance"], d_grads))
    _full_size(monkeypatch)
    (f_out, f_aux, f_pairs), f_grads = _readings(_share, values, x)
    assert int(f_aux["moe_overflow"]) == 0
    _assert_same((out, aux["balance"], grads), (f_out, f_aux["balance"], f_grads))


def _step_readings(cfg, batch):
    """Loss, metrics and gradients of the whole model under full remat,
    the MoE layers in a scan, as the train step takes them."""
    run = RunConfig(optimizer="adamw", remat="full")
    model = build_model(cfg)
    opt = make_optimizer(run)
    state = init_train_state(model, opt, 0)
    _, metrics = jax.jit(build_train_step(model, run, opt))(state, batch)

    def loss(values):
        logits, aux, _ = model.forward(values, batch, remat="full")
        return jnp.sum(logits[..., :4]) + aux["balance"]

    return metrics, jax.jit(jax.grad(loss))(state["values"])


def test_every_moe_layer_of_the_step_counts_its_overflow(monkeypatch):
    """Two MoE layers, a router patched to send every pair to the held
    experts: the step's ``moe_overflow`` counts both layers, and its loss
    and gradients equal the full-size buffer's."""
    cfg = dataclasses.replace(CFG, num_layers=3)
    real = moe.route

    def held_first(p, x, cfg):
        top_p, top_e, aux = real(p, x, cfg)
        first = cfg.moe_expert_offset
        return top_p, jnp.broadcast_to(jnp.arange(first, first + 2), top_e.shape), aux

    batch = dummy_train_inputs(cfg, B, S, seed=0)
    monkeypatch.setattr(moe, "route", held_first)
    metrics, grads = _step_readings(cfg, batch)
    assert int(metrics["moe_overflow"]) == 2
    assert int(metrics["expert_pairs"]) == 2 * PAIRS
    _full_size(monkeypatch)
    f_metrics, f_grads = _step_readings(cfg, batch)
    assert int(f_metrics["moe_overflow"]) == 0
    _assert_same((metrics["loss"], grads), (f_metrics["loss"], f_grads))


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


def test_every_expert_held_traces_no_cond():
    """With every expert held the buffer has a row for every pair, the
    choice is static, and neither the layer nor its gradient holds a
    ``cond``; the share of 2 holds one."""
    whole = dataclasses.replace(CFG, moe_experts_held=0, moe_expert_offset=0)
    assert moe._buffer_rows(PAIRS, whole.experts_held, whole.moe_num_experts) == PAIRS

    def grad_jaxpr(cfg):
        fn = lambda v, x: jnp.sum(_share(v, x, cfg)[0])
        return jax.make_jaxpr(jax.grad(fn, (0, 1)))(_values(cfg), _x(cfg)).jaxpr

    assert "cond" not in set(_primitives(grad_jaxpr(whole)))
    assert "cond" in set(_primitives(grad_jaxpr(CFG)))


@pytest.mark.parametrize("overflow", [False, True], ids=["compact", "full-size"])
def test_rows_past_the_groups_reach_neither_output_nor_gradient(monkeypatch, overflow):
    """The grouped matmul leaves the rows past its groups undefined (the
    TPU's kernel does not write them, forward or backward); with junk there,
    the share's output and gradients still equal the dense sum's."""
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
    values, x = _values(), _x()
    if overflow:
        values, x = _favour_held(values, x)
    (d_out, d_aux, _), d_grads = _readings(_as_share(_dense), values, x)
    monkeypatch.setattr(jax.lax, "ragged_dot", lambda lhs, rhs, sizes: leaky(lhs, rhs, sizes))
    (out, aux, pairs), grads = _readings(_share, values, x)
    assert int(aux["moe_overflow"]) == int(overflow)
    assert int(pairs) < (PAIRS if overflow else ROWS)  # rows past the groups
    _assert_same((out, aux["balance"], grads), (d_out, d_aux["balance"], d_grads))
