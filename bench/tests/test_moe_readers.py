"""The readers of the MoE layer: ``moe_ms`` (device self time under the
``moe`` scope) and ``expert_matmul_roofline`` (the held experts' matmul
FLOPs over the self time under the nested ``experts`` scope), on a small
trace with known answers, on a program that names no such scope or counts
no pairs, and after a short traced training run on the CPU."""

import jax
import jax.numpy as jnp
import pytest

from bench import harness, moe_flops
from bench import trace as tracefile
from bench.metrics import expert_matmul_roofline, moe_ms, moe_scope, train_scope
from repro.obs import programs, trace

STEP = "jit(train_step)/jvp()/while/body/closed_call"
D, F, PAIRS = 2048, 1408, 1000


def _op(name, shape="f32[4]{0}", opcode="fusion"):
    return f"%{name} = {shape} {opcode}(f32[4]{{0}} %p), kind=kLoop, calls=%c"


#: Two whole train steps in the window: a layer loop holding the body's
#: ops, the routing and combine under ``moe``, the activation under
#: ``moe/experts`` and the grouped matmuls as XLA's TPU rewrite names them
#: (``ragged-dot-none``, the scope path gone), forward and backward.
NESTED = {
    "devices": [{
        "name": "/device:TPU:0",
        "ops": [
            [_op("while.1", "(s32[], f32[4]{0})", "while"), 0, 800, "jit_train_step"],
            [_op("fusion.1"), 0, 100, "jit_train_step"],
            [_op("fusion.2"), 100, 150, "jit_train_step"],
            [_op("ragged.1", "bf16[4]{0}", "custom-call"), 150, 450, "jit_train_step"],
            [_op("ragged.2", "bf16[4]{0}", "fusion"), 450, 750, "jit_train_step"],
            [_op("fusion.3"), 800, 900, "jit_train_step"],
            [_op("fusion.2"), 1000, 1100, "jit_train_step"],
            [_op("ragged.1", "bf16[4]{0}", "custom-call"), 1100, 1500, "jit_train_step"],
        ],
        "modules": [["jit_train_step", 0, 900], ["jit_train_step", 1000, 1600]],
    }],
    "host": [["bench.window", 0, 2000, "python3"]],
}
NESTED_HLO = {
    "while.1": ("(s32[], f32[4]{0}) while", "jit(train_step)/jvp()/while"),
    "fusion.1": ("f32[4]{0} fusion", STEP + "/attention/dot_general"),
    "fusion.2": ("f32[4]{0} fusion", STEP + "/moe/sort"),
    "ragged.1": ("bf16[4]{0} custom-call", "ragged-dot-none"),
    "ragged.2": ("bf16[4]{0} fusion",
                 "jit(train_step)/transpose(jvp(moe))/experts/mul"),
    "fusion.3": ("f32[4]{0} fusion", "jit(train_step)/optimizer/mul"),
}
PEAK = {"flops_per_s": 197e12}


def _record(summary, steps=2):
    return harness.RunRecord(steps=steps, window_s=1.0, counters={}, trace=summary,
                             flops_per_step=1.0, gather_bytes=1, peak=PEAK)


def _noted_state(d=D, f=F):
    w = jax.ShapeDtypeStruct((4, 16, d, f), jnp.bfloat16)
    return {"values": {"segments": [{}, {"moe": {"wi_gate": w}}]},
            "opt": {"master": {"segments": [{}, {"moe": {"wi_gate": w}}]}}}


@pytest.fixture
def program(monkeypatch):
    """The program's side as a traced run leaves it: the noted train step,
    its compiled ops and the pairs counted while the profiler ran."""
    monkeypatch.setattr(train_scope, "program_ops", lambda: NESTED_HLO)
    monkeypatch.setattr(moe_scope, "program_ops", lambda: NESTED_HLO)
    monkeypatch.setattr(programs, "_programs", {"train_step": [None, (_noted_state(), {}),
                                                               None]})
    monkeypatch.setattr(trace, "_profiled", {"moe.expert_pairs": (4, 4.0 * PAIRS)})


def test_nested_scopes_split_innermost_first():
    assert moe_scope.scope_of(NESTED_HLO["ragged.2"][1]) == "experts"
    assert moe_scope.scope_of(NESTED_HLO["ragged.1"][1]) == "experts"
    assert moe_scope.scope_of(NESTED_HLO["fusion.2"][1]) == "moe"
    # the benchmark's own split puts the activation under moe, and cannot
    # place the rewritten matmuls
    assert train_scope.scope_of(NESTED_HLO["ragged.2"][1]) == "moe"
    assert train_scope.scope_of(NESTED_HLO["ragged.1"][1]) is None
    rec = _record(tracefile.summarize(tracefile.Trace.from_dict(NESTED)))
    assert moe_scope.split(rec, NESTED_HLO) == [(2, pytest.approx(
        {None: 50, "attention": 100, "moe": 150, "experts": 1000, "optimizer": 100}))]


def test_readers_on_the_small_trace(program):
    rec = _record(tracefile.summarize(tracefile.Trace.from_dict(NESTED)))
    # moe 150 + experts 1000 ns over 2 steps
    assert moe_ms.read(rec) == pytest.approx(575e-6)
    flops = 8 * 3 * D * F * PAIRS
    assert moe_flops.expert_matmul_flops(D, F, PAIRS) == flops
    assert moe_flops.expert_widths(_noted_state()) == (D, F)
    assert expert_matmul_roofline.read(rec) == pytest.approx(
        100 * flops / 500e-9 / PEAK["flops_per_s"])


def test_readers_read_nothing_without_the_programs_part(program, monkeypatch):
    """A program that counts no pairs, notes no experts, or names no
    ``experts`` scope (the parent's dense path) gives nothing, and raises
    nothing."""
    rec = _record(tracefile.summarize(tracefile.Trace.from_dict(NESTED)))
    monkeypatch.setattr(trace, "_profiled", {})
    assert expert_matmul_roofline.read(rec) is None
    monkeypatch.setattr(trace, "_profiled", {"moe.expert_pairs": (4, 4.0 * PAIRS)})
    monkeypatch.setattr(programs, "_programs", {"train_step": [None, ({"values": {}}, {}),
                                                               None]})
    assert expert_matmul_roofline.read(rec) is None
    monkeypatch.setattr(programs, "_programs", {"train_step": [None, (_noted_state(), {}),
                                                               None]})
    dense = {k: (sig, STEP + "/attention/x") for k, (sig, _) in NESTED_HLO.items()}
    monkeypatch.setattr(moe_scope, "program_ops", lambda: dense)
    assert expert_matmul_roofline.read(rec) is None
    assert moe_ms.read(rec) is None
    assert expert_matmul_roofline.read(_record({})) is None


def test_a_traced_moe_run_counts_its_expert_pairs(tmp_path, monkeypatch):
    """A short run of the reduced DeepSeek-V2-Lite through the launcher with
    the profiler on: the pairs are counted at the loss reads (steps 1 and
    10 of 12 here), the noted step's expert widths are read, and its
    compiled text puts the grouped matmuls under ``experts`` inside ``moe``."""
    from repro.configs import ARCHS, reduced
    from repro.launch import train

    monkeypatch.setattr(trace, "_profiled", {})
    monkeypatch.setattr(programs, "_programs", {})
    argv = ["--arch", "deepseek-v2-lite-16b", "--steps", "12", "--ckpt-every", "100",
            "--batch", "2", "--seq-len", "32", "--num-docs", "64",
            "--device-path", "stage", "--workdir", str(tmp_path / "run")]
    with jax.profiler.trace(str(tmp_path / "prof")):
        assert train.main(argv) == 0
    count, total = trace.profiled()["moe.expert_pairs"]
    cfg = reduced(ARCHS["deepseek-v2-lite-16b"])
    # steps 1..10: every token's top-k over all held experts, in each MoE layer
    assert count == 10
    assert total == 10 * 2 * 32 * cfg.moe_top_k * (cfg.num_layers - cfg.moe_first_dense)
    assert expert_matmul_roofline.expert_widths() == (cfg.d_model, cfg.d_ff)
    paths = [path for _, path in train_scope.program_ops().values()]
    scopes = {moe_scope.scope_of(p) for p in paths}
    assert {"attention", "mlp", "moe", "experts", "head_loss", "optimizer"} <= scopes
    assert all("/moe/experts/" in p for p in paths if moe_scope.scope_of(p) == "experts")
