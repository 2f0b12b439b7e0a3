"""Whole runs of the harness on a tiny configuration on the CPU, sound and
with the timed path broken underneath, and the control.

The harness's look for a chip is skipped (``require_chip=False``); the rest
of a run is what ``run.py`` does: the trainer driven through its hook,
the window, and the checks against the host loader, the corpus and the
float32 reference. Each fault the one-chip training cells can have must
turn ``correct`` false, and so must the control.
"""

import os
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.reference import data as ref_data
from bench.tests import tiny

SECONDS = 1.0


def _run(seed=20260101):
    out = harness.run(tiny.cell(), seed, SECONDS, False, time.perf_counter(),
                      require_chip=False, log=lambda msg: None)
    return out, {k: c["value"] for k, c in out["checks"].items()}


def test_sound_run_is_correct():
    out, checks = _run()
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "step_ms_p90", "setup_s"}
    assert list(out)[-1] == "checks"


def test_window_counts_the_steps_whose_completion_it_times(monkeypatch):
    """``done[i]`` is the completion of step WARMUP+1+i, the batch that
    ``run`` counts as the window's i-th: no step before the window is
    waited for again inside it."""
    from types import SimpleNamespace

    import jax

    clock = iter(range(10_000))
    monkeypatch.setattr(harness.time, "perf_counter", lambda: float(next(clock)))
    monkeypatch.setattr(harness, "_trainer_state", lambda: {"opt": {"master": None}})
    monkeypatch.setattr(harness, "grad_norms_from_adafactor", lambda state: {})
    monkeypatch.setattr(harness, "change_norms", lambda *a: {})
    waited = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda m: waited.append(int(m["loss"])) or m)
    drv = harness.Driver(tiny.cell(), 10.0, 0.0, [], require_chip=False)
    grid = np.zeros((1, 2), np.int32)
    with pytest.raises(harness.WindowClosed):
        for step in range(1, 100):
            drv(SimpleNamespace(
                step=step, metrics={"loss": np.float32(step)}, stager=None,
                batch={"tokens": grid, "targets": grid, "loss_mask": grid,
                       "returned": [0], "step": step - 1}))
    n = len(drv.done)
    window = waited[harness.WARMUP:]
    # one wait per step in the window, the last one the step that overran it
    assert window == list(range(harness.WARMUP + 1, harness.WARMUP + 2 + n))
    assert [b["step"] + 1 for b in drv.batches[harness.WARMUP:harness.WARMUP + n]] \
        == window[:n]
    assert drv.done[-1] - drv.t_w <= 10.0 and n > 0


@pytest.fixture
def broken_step(monkeypatch):
    """Swap the trainer's step for ``make(real_step)``."""
    from repro.launch import train

    def patch(make):
        real = train.build_train_step
        monkeypatch.setattr(train, "build_train_step",
                            lambda *a, **k: make(real(*a, **k)))
    return patch


def test_state_left_unchanged_is_not_correct(broken_step):
    def make(step):
        def same_state(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return same_state

    broken_step(make)
    out, checks = _run()
    assert not out["correct"]
    assert checks["change_norm_gap"] == pytest.approx(1.0, abs=1e-6)


def test_half_the_batch_left_out_is_not_correct(broken_step):
    def make(step):
        def half(state, batch):
            rows = batch["loss_mask"].shape[0]
            keep = (jnp.arange(rows) < rows // 2)[:, None]
            return step(state, dict(batch, loss_mask=batch["loss_mask"] * keep))
        return half

    broken_step(make)
    out, checks = _run()
    assert not out["correct"]
    assert checks["grad_norm_gap"] > tiny.LIMITS["grad_norm_gap"]


def test_token_altered_in_the_gather_is_not_correct(monkeypatch):
    from repro.core import device

    real = device.chunk_gather_train

    def altered(*a, **k):
        tokens, targets, mask = real(*a, **k)
        return tokens.at[0, 1].add(1), targets, mask

    monkeypatch.setattr(device, "chunk_gather_train", altered)
    out, checks = _run()
    assert not out["correct"]
    assert checks["rows_vs_host_loader"] > 0 and checks["rows_vs_corpus"] > 0


def test_control_is_not_correct():
    """The reference in int8, put in the program's place, fails the
    comparison that the bfloat16 program passes."""
    cell = tiny.cell()
    corpus = ref_data.Corpus(256, tiny.CONFIG["vocab_size"], 32, 11)
    batches = []
    for s in range(harness.CHECKED_STEPS):
        rows = [ref_data.expected_row(corpus.tokens(d), 64) for d in range(4 * s, 4 * s + 4)]
        batches.append({k: np.stack([r[i] for r in rows])
                        for i, k in enumerate(harness.GRIDS)})
    ref = harness.reference_readings(cell, batches)
    control = harness.reference_readings(cell, batches, precision="int8")
    gaps = harness.training_gaps(control, ref)
    assert any(gaps[k] > cell.limits[k] for k in gaps if k in cell.limits), gaps


def test_no_chip_no_result(tmp_path):
    """Without a TPU, and in a directory holding only the benchmark, the
    command exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "bench/run.py", "--workload", "deepseek-llm-7b.docs512-nas",
           "--seed", "3000000000", "--seconds", "1", "--trace", "0"]
    r = subprocess.run(cmd, cwd=harness.ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0 and '"correct"' not in r.stdout
    lone = tmp_path / "lone"
    lone.mkdir()
    (lone / "BENCHMARK.json").write_bytes((harness.ROOT / "BENCHMARK.json").read_bytes())
    subprocess.run(["cp", "-r", str(harness.BENCH), str(lone / "bench")], check=True)
    r = subprocess.run(cmd, cwd=lone, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0 and '"correct"' not in r.stdout
