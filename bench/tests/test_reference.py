"""The float32 reference against the program at a small size, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.models.dense_decoder import program_config
from bench.reference import data as ref_data
from bench.reference import dense_decoder as ref
from bench.tests import tiny

F32_CONFIG = dict(tiny.CONFIG, torch_dtype="float32")
SEQ = 64


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
    corpus = ref_data.Corpus(64, tiny.CONFIG["vocab_size"], SEQ // 2, seed)
    out = []
    for s in range(n):
        rows = [ref_data.expected_row(corpus.tokens(d), SEQ) for d in range(4 * s, 4 * s + 4)]
        out.append({k: np.stack([r[i] for r in rows])
                    for i, k in enumerate(("tokens", "targets", "loss_mask"))})
    return out


def _program_leaf(values, name):
    if not name.startswith("layer"):
        return values[name]
    layer, path = name.split(".", 1)
    leaf = values["segments"][0]
    for k in path.split("."):
        leaf = leaf[k]
    return leaf[int(layer[5:])]


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_init_is_the_programs_init(dtype):
    cfg = dict(tiny.CONFIG, torch_dtype=dtype)
    _, state, _ = _program(cfg)
    params = ref.init_params(ref.Dims.from_config(cfg), 0)
    for name, value in ref.leaf_items(params):
        got = np.asarray(_program_leaf(state["values"], name).astype(jnp.float32))
        np.testing.assert_array_equal(got, np.asarray(value), err_msg=name)


def test_loss_and_gradients_match_the_program():
    from repro.train.losses import lm_loss

    model, state, _ = _program(F32_CONFIG)
    batch = _batches(1)[0]
    feed = {k: jnp.asarray(v) for k, v in batch.items()}

    def loss_fn(values):
        logits, _, _ = model.forward(values, feed)
        return lm_loss(logits, feed["targets"], feed["loss_mask"])[0]

    want_loss, want = jax.value_and_grad(loss_fn)(state["values"])
    dims = ref.Dims.from_config(F32_CONFIG)
    for rows in (4, 1):  # one block, and blocks of one row
        loss, grads = ref.Trainer(dims, rows=rows).loss_and_grads(
            ref.init_params(dims, 0), batch)
        assert loss == pytest.approx(float(want_loss), rel=1e-5)
        for name, g in ref.leaf_items(grads):
            np.testing.assert_allclose(np.asarray(g), np.asarray(_program_leaf(want, name)),
                                       rtol=2e-3, atol=1e-6, err_msg=name)


def test_three_adafactor_steps_match_the_program():
    _, state, step = _program(F32_CONFIG)
    batches = _batches(3)
    losses = []
    for b in batches:
        state, metrics = step(state, {k: jnp.asarray(v) for k, v in b.items()})
        losses.append(float(metrics["loss"]))
    dims = ref.Dims.from_config(F32_CONFIG)
    got = ref.train_readings(dims, 0, batches, rows=4)
    assert got["losses"] == pytest.approx(losses, rel=1e-5)
    params = ref.init_params(dims, 0)
    for name, init in ref.leaf_items(params):
        prog = ref.diff_norm(_program_leaf(state["opt"]["master"], name), init)
        assert got["change_norms"][name] == pytest.approx(prog, rel=1e-3, abs=1e-9), name


def test_corpus_is_the_trainers_corpus():
    from repro.data import SyntheticTokenDataset

    ds = SyntheticTokenDataset(40, 1000, mean_len=48, seed=17)
    corpus = ref_data.Corpus(40, 1000, 48, 17)
    np.testing.assert_array_equal(corpus.lengths, ds.lengths)
    for doc in range(40):
        np.testing.assert_array_equal(corpus.tokens(doc), ds.record_tokens(doc))


def test_expected_row_shifts_and_masks():
    doc = np.arange(1, 6, dtype=np.int32)  # 5 tokens
    tokens, targets, mask = ref_data.expected_row(doc, 8)
    assert tokens.tolist() == [1, 2, 3, 4, 5, 0, 0, 0]
    assert targets.tolist() == [2, 3, 4, 5, 0, 0, 0, 0]
    assert mask.tolist() == [1, 1, 1, 1, 0, 0, 0, 0]
    tokens, targets, mask = ref_data.expected_row(np.arange(1, 20, dtype=np.int32), 8)
    assert tokens.tolist() == list(range(1, 9))
    assert targets.tolist() == list(range(2, 10))
    assert mask.sum() == 8
