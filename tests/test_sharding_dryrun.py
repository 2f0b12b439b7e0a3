"""Sharding rules unit tests + a miniature dry-run in a subprocess.

The subprocess sets XLA_FLAGS for 8 emulated devices (the assignment
forbids setting it globally — smoke tests must see 1 device), builds a
(2,4) mesh, and lowers+compiles reduced configs of three families.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.configs import RunConfig
from repro.parallel.axes import ShardingRules
from repro.parallel.sharding import activation_rules, param_rules


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


class TestShardingRules:
    def test_divisibility_rail(self):
        mesh = FakeMesh({"data": 2, "model": 4})
        rules = ShardingRules.__new__(ShardingRules)
        rules.mesh = mesh
        rules.rules = {"heads": "model", "batch": ("data",)}
        spec = rules.spec_for(("batch", "heads"), (6, 8))
        assert spec == __import__("jax").sharding.PartitionSpec(("data",), "model")
        # 6 % 4 != 0 on heads -> replicated
        spec2 = rules.spec_for(("batch", "heads"), (8, 6))
        assert spec2[1] is None

    def test_axis_used_once(self):
        mesh = FakeMesh({"model": 4})
        rules = ShardingRules.__new__(ShardingRules)
        rules.mesh = mesh
        rules.rules = {"a": "model", "b": "model"}
        spec = rules.spec_for(("a", "b"), (8, 8))
        assert spec[0] == "model" and spec[1] is None

    def test_param_rules_policies(self):
        mesh = FakeMesh({"data": 2, "model": 4})
        tp = param_rules(mesh, RunConfig())
        assert tp["mlp"] == "model" and tp["embed"] is None
        fsdp = param_rules(mesh, RunConfig(fsdp=True))
        assert fsdp["embed"] == ("data",)
        dp = param_rules(mesh, RunConfig(parallelism="dp_only"))
        assert all(v is None for v in dp.values())

    def test_activation_rules_seq_parallel(self):
        mesh = FakeMesh({"data": 2, "model": 4})
        assert activation_rules(mesh, RunConfig())["seq_act"] is None
        assert activation_rules(mesh, RunConfig(seq_parallel=True))["seq_act"] == "model"


SUBPROC = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, dataclasses, jax
    from repro.launch import dryrun_lib
    from repro.launch.mesh import make_mesh
    from repro.configs import ARCHS, reduced, get_shape

    small = dataclasses.replace(get_shape("train_4k"), seq_len=256, global_batch=8)
    dryrun_lib.get_config = lambda name: reduced(ARCHS[name])
    dryrun_lib.get_shape = lambda name: small
    mesh = make_mesh((2, 4), ("data", "model"))
    out = {}
    for arch in ("tinyllama-1.1b", "deepseek-moe-16b", "zamba2-1.2b"):
        r = dryrun_lib.run_cell(arch, "train_4k", mesh)
        out[arch] = dict(status=r.status, flops=r.flops_per_device,
                         coll=r.collectives["total_bytes"] if r.collectives else 0,
                         err=r.error[:200])
    print("RESULT " + json.dumps(out))
    """
)


MOE_EQ_SUBPROC = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, numpy as np, jax, jax.numpy as jnp
    from repro.configs import ARCHS, reduced, RunConfig
    from repro.models.common import RngStream, split_params
    from repro.models.moe import init_moe, moe_block, moe_block_a2a
    from repro.parallel.axes import ShardingRules, sharding_ctx
    from repro.parallel import sharding as shd
    from repro.launch.mesh import make_mesh

    cfg = dataclasses.replace(reduced(ARCHS["deepseek-moe-16b"]), capacity_factor=16.0)
    mesh = make_mesh((2, 4), ("data", "model"))
    values, _ = split_params(init_moe(RngStream(0), cfg, jnp.float32))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 64, cfg.d_model)), jnp.float32)
    rules = ShardingRules(mesh, shd.activation_rules(mesh, RunConfig()))
    with mesh, sharding_ctx(rules):
        ref, aux_r, pairs_r = jax.jit(lambda v, x: moe_block(v, x, cfg))(values, x)
        a2a, aux_a, pairs_a = jax.jit(lambda v, x: moe_block_a2a(v, x, cfg))(values, x)
    err = float(jnp.max(jnp.abs(ref - a2a))) / float(jnp.max(jnp.abs(ref)))
    assert err < 1e-4, err
    assert abs(float(aux_r["balance"]) - float(aux_a["balance"])) < 1e-5
    assert int(aux_r["moe_overflow"]) == int(aux_a["moe_overflow"]) == 0
    assert int(pairs_r) == int(pairs_a) == 4 * 64 * cfg.moe_top_k  # nothing dropped
    print("RESULT ok", err)
    """
)


@pytest.mark.slow
def test_moe_a2a_equivalent_to_gspmd_on_8_devices():
    """shard_map all-to-all MoE == pjit MoE at generous capacity (§Perf)."""
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run(
        [sys.executable, "-c", MOE_EQ_SUBPROC], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert "RESULT ok" in proc.stdout, proc.stderr[-2000:]


@pytest.mark.slow
def test_mini_dryrun_compiles_on_8_devices():
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run(
        [sys.executable, "-c", SUBPROC], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, f"no result line; stderr tail: {proc.stderr[-2000:]}"
    out = json.loads(line[0][len("RESULT "):])
    for arch, r in out.items():
        assert r["status"] == "ok", (arch, r["err"])
        assert r["flops"] > 0 and r["coll"] > 0
