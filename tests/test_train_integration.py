"""Integration: training loop × Redox loader × optimizers × microbatching."""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, RunConfig, reduced
from repro.core import Cluster, EpochSampler, RedoxLoader
from repro.data import SyntheticTokenDataset
from repro.launch.specs import dummy_train_inputs
from repro.models import build_model
from repro.optim.optimizers import make_optimizer
from repro.train.train_step import build_train_step, init_train_state


def _setup(name="tinyllama-1.1b", **run_kw):
    cfg = reduced(ARCHS[name])
    model = build_model(cfg)
    run = RunConfig(optimizer=run_kw.pop("optimizer", "adamw"),
                    learning_rate=1e-3, **run_kw)
    opt = make_optimizer(run)
    state = init_train_state(model, opt, 0)
    return cfg, model, run, opt, state


class TestOptimizers:
    @pytest.mark.parametrize("optimizer", ["adamw", "adafactor", "sgdm"])
    def test_descends(self, optimizer):
        cfg, model, run, opt, state = _setup(optimizer=optimizer)
        step = jax.jit(build_train_step(model, run, opt), donate_argnums=0)
        batch = dummy_train_inputs(cfg, 4, 64, seed=0)
        losses = []
        for _ in range(5):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], (optimizer, losses)

    def test_no_master_tracks_master(self):
        """bf16-params + no fp32 master must follow the master trajectory
        closely for a few steps (the kimi-k2 memory recipe)."""
        losses = {}
        for master in (True, False):
            cfg, model, run, opt, state = _setup(
                optimizer="adafactor", master_fp32=master
            )
            step = jax.jit(build_train_step(model, run, opt), donate_argnums=0)
            batch = dummy_train_inputs(cfg, 4, 64, seed=0)
            ls = []
            for _ in range(5):
                state, m = step(state, batch)
                ls.append(float(m["loss"]))
            losses[master] = ls
        np.testing.assert_allclose(losses[True], losses[False], rtol=5e-3)

    def test_adafactor_state_is_factored(self):
        cfg, model, run, opt, state = _setup(optimizer="adafactor")
        v = state["opt"]["v"]
        leaves = jax.tree.leaves(v)
        # factored states are strictly smaller than the largest param
        values = jax.tree.leaves(state["values"])
        assert max(l.size for l in leaves) < max(p.size for p in values)


class TestMicrobatching:
    def test_microbatch_matches_full_batch_loss(self):
        cfg, model, run, opt, state = _setup()
        run_mb = dataclasses.replace(run, microbatch=4)
        step_full = jax.jit(build_train_step(model, run, opt))
        step_mb = jax.jit(build_train_step(model, run_mb, make_optimizer(run_mb)))
        batch = dummy_train_inputs(cfg, 8, 64, seed=0)
        _, m_full = step_full(state, batch)
        cfg2, model2, run2, opt2, state2 = _setup()
        _, m_mb = step_mb(state2, batch)
        # mean loss over microbatches == full-batch loss (same token count)
        assert abs(float(m_full["loss"]) - float(m_mb["loss"])) < 5e-2


class TestRedoxTraining:
    def test_loader_feeds_train_step_multi_epoch(self, tmp_path):
        cfg, model, run, opt, state = _setup()
        cfg = dataclasses.replace(cfg, vocab_size=97)
        model = build_model(cfg)
        state = init_train_state(model, opt, 0)
        step = jax.jit(build_train_step(model, run, opt), donate_argnums=0)
        ds = SyntheticTokenDataset(96, cfg.vocab_size, mean_len=40, seed=0)
        store = ds.build_store(tmp_path / "c", 4, num_slots=16, seed=1)
        cluster = Cluster(store.plan, 2, store=store, seed=2)
        sampler = EpochSampler(96, 2, seed=3)
        loader = RedoxLoader(cluster, sampler, batch_per_node=4, seq_len=48)
        losses = []
        for epoch in range(2):
            for b in loader.epoch(epoch):
                state, m = step(
                    state,
                    {k: jnp.asarray(b[k]) for k in ("tokens", "targets", "loss_mask")},
                )
                losses.append(float(m["loss"]))
        assert all(np.isfinite(losses))
        assert np.mean(losses[-4:]) < np.mean(losses[:4])

    def test_grad_allreduce_dtype_flag(self):
        cfg, model, run, opt, state = _setup(grad_allreduce_dtype="bfloat16")
        step = jax.jit(build_train_step(model, run, opt), donate_argnums=0)
        state, m = step(state, dummy_train_inputs(cfg, 4, 64, seed=0))
        assert np.isfinite(float(m["loss"]))


class TestLauncherInProcess:
    """``launch.train.main(argv, on_step=...)``: the in-process entry
    ``chip_smoke.py`` drives, at the reduced size on the gather path."""

    def test_on_step_sees_every_step_and_the_host_grids(self, tmp_path, capsys):
        from repro.core import ChunkStore
        from repro.launch import train

        argv = ["--arch", "tinyllama-1.1b", "--steps", "3", "--ckpt-every", "10",
                "--batch", "4", "--seq-len", "32", "--num-docs", "64",
                "--device-path", "gather", "--workdir", str(tmp_path)]
        events = []
        assert train.main(argv, on_step=events.append) == 0
        assert "done: 3 steps" in capsys.readouterr().out
        assert [e.step for e in events] == [1, 2, 3]
        assert all(np.isfinite(float(e.metrics["loss"])) for e in events)
        assert all(e.stager is events[0].stager for e in events)
        assert events[0].stager.stats.kernel_steps >= 3

        # The first gathered batch == the host loader's first grids.
        spec = train.session_spec(train.build_parser().parse_args(argv))
        store = ChunkStore.open(tmp_path / "chunks")
        it = RedoxLoader.from_spec(spec, store).epoch_async(0)
        host = next(it)
        it.close()
        store.close()
        first = events[0].batch
        assert int(first["step"]) == int(host["step"]) == 0
        for k in ("tokens", "targets", "loss_mask"):
            np.testing.assert_array_equal(np.asarray(first[k]), host[k])
            assert np.asarray(first[k]).dtype == host[k].dtype


class TestNamedScopes:
    """The train step names its parts (attention, mlp, head_loss,
    optimizer) as HLO metadata only: the compiled step does the same
    work with and without the names."""

    SCOPES = ("attention", "mlp", "head_loss", "optimizer")

    def _compiled(self, kind="attn_mlp"):
        name = "tinyllama-1.1b" if kind == "attn_mlp" else "deepseek-moe-16b"
        cfg, model, run, opt, state = _setup(name, optimizer="adafactor", remat="full")
        batch = dummy_train_inputs(cfg, 2, 32, seed=0)
        return jax.jit(build_train_step(model, run, opt)).lower(state, batch).compile()

    @staticmethod
    def _scopes(text):
        import re

        found = set()
        for path in re.findall(r'op_name="([^"]+)"', text):
            for part in path.split("/"):
                found.add(re.sub(r"^(?:[\w.-]+\()*([\w.-]+)\)*$", r"\1", part))
        return found

    def test_scopes_name_the_step_and_leave_its_flops(self, monkeypatch):
        import contextlib

        named = self._compiled()
        assert set(self.SCOPES) <= self._scopes(named.as_text())
        monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        plain = self._compiled()
        assert not set(self.SCOPES) & self._scopes(plain.as_text())
        assert named.cost_analysis()["flops"] == plain.cost_analysis()["flops"] > 0

    def test_moe_kinds_name_their_experts(self):
        scopes = self._scopes(self._compiled("attn_moe").as_text())
        assert {"attention", "moe", "head_loss", "optimizer"} <= scopes
