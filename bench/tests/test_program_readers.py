"""The readers that take what the program itself gives while it is traced:
the train step's device self time by name scope (``train_scope`` and its
four readers) and the data plane's spans (``program_spans`` and its two
readers), on small traces with known answers, on a part of a trace recorded
on the chip, and after a short training run on the CPU."""

import json
import time
from pathlib import Path

import pytest

from bench import flops, harness, peaks
from bench import trace as tracefile
from bench.metrics import (attention_ms, chunk_gather_roofline, device_idle_share,
                           head_loss_ms, input_wait_ms, mlp_ms, optimizer_ms, pack_ms,
                           read_wait_ms, stage_ms, step_mfu, storage_ops_per_step,
                           train_scope)
from repro.obs import programs, trace

#: Two gathers and three train steps of a deepseek-llm-7b.docs512-nas run
#: on one TPU v5 lite, from before the program named its scopes.
RECORDED = Path(__file__).with_name("trace_v5e.json")
#: Three train steps of the same cell with each op's name scope path as
#: the profiler recorded it (its "about").
RECORDED_SCOPES = Path(__file__).with_name("trace_v5e_spans.json")


def _record(summary, steps=1, **kw):
    return harness.RunRecord(steps=steps, window_s=1.0, counters=kw.pop("counters", {}),
                             trace=summary, flops_per_step=kw.pop("flops_per_step", 1.0),
                             gather_bytes=kw.pop("gather_bytes", 1), peak=kw.pop("peak", {}))


def _op(name, shape="f32[4]{0}", opcode="fusion"):
    return f"%{name} = {shape} {opcode}(f32[4]{{0}} %p), kind=kLoop, calls=%c"


STEP = "jit(train_step)/jvp()/while/body/closed_call"
#: Two train steps wholly in the window [0, 2100) and one that is not, a
#: layer loop's op holding its body's ops, and a gather between steps.
SCOPED = {
    "devices": [{
        "name": "/device:TPU:0",
        "ops": [
            [_op("while.1", "(s32[], f32[4]{0})", "while"), 0, 600, "jit_train_step"],
            [_op("fusion.1"), 10, 210, "jit_train_step"],
            [_op("fusion.2"), 220, 520, "jit_train_step"],
            [_op("fusion.3"), 600, 700, "jit_train_step"],
            [_op("fusion.4"), 700, 750, "jit_train_step"],
            [_op("chunk_gather_train.1", "s32[4]{0}", "custom-call"), 800, 820,
             "jit_chunk_gather_train"],
            [_op("fusion.1"), 1000, 1400, "jit_train_step"],
            [_op("fusion.2"), 1400, 1500, "jit_train_step"],
            [_op("fusion.1"), 1900, 2200, "jit_train_step"],
        ],
        "modules": [
            ["jit_train_step", 0, 760],
            ["jit_chunk_gather_train", 800, 820],
            ["jit_train_step", 1000, 1800],
            ["jit_train_step", 1900, 2200],
        ],
    }],
    "host": [["bench.window", 0, 2100, "python3"]],
}
#: What the compiled step's HLO text gives those instructions.
SCOPED_HLO = {
    "while.1": ("(s32[], f32[4]{0}) while", "jit(train_step)/jvp()/while"),
    "fusion.1": ("f32[4]{0} fusion", STEP + "/attention/dot_general"),
    "fusion.2": ("f32[4]{0} fusion", "jit(train_step)/transpose(jvp(mlp))/dot_general"),
    "fusion.3": ("f32[4]{0} fusion", "jit(train_step)/jvp(head_loss)/dot_general"),
    "fusion.4": ("f32[4]{0} fusion", "jit(train_step)/optimizer/mul"),
}


def test_scopes_in_a_path():
    assert train_scope.scope_of(STEP + "/attention/dot_general") == "attention"
    assert train_scope.scope_of("jit(train_step)/transpose(jvp(head_loss))/exp") == "head_loss"
    assert train_scope.scope_of("jit(train_step)/jvp(mlp)/moe/dot_general") == "moe"
    assert train_scope.scope_of("jit(train_step)/jvp()/while/body/add") is None
    assert train_scope.scope_of("jit(train_step)/attention_mask/add") is None
    assert train_scope.scope_of("") is None


@pytest.mark.parametrize("text, expected", [
    ("%fusion.553 = (f32[16,32]{1,0:T(8,128)S(1)}, f32[2]{0}) fusion(pred[512]{0} %a, "
     "bf16[4]{0} %b), kind=kOutput, calls=%fused_computation.515.clone.clone",
     "(f32[16,32]{1,0:T(8,128)S(1)}, f32[2]{0}) fusion"),
    # the compiled module's own print of the same instruction
    ("%fusion.553 = (f32[16,32]{1,0:T(8,128)S(1)}, /*index=1*/f32[2]{0}) fusion(%a, %b), "
     'kind=kOutput, calls=%fused_computation.515.clone.clone, metadata={op_name="x"}',
     "(f32[16,32]{1,0:T(8,128)S(1)}, f32[2]{0}) fusion"),
    # an async pair's half, as the trace and as the module print it
    ("%slice-done.7 = bf16[1024]{0:S(1)} async-done(((bf16[4096]{0}), bf16[1024]{0}) %s)",
     "bf16[1024]{0:S(1)} async-done"),
    ("%slice-done.7 = bf16[1024]{0:S(1)} slice-done(%slice-start.7)",
     "bf16[1024]{0:S(1)} async-done"),
])
def test_signature_reads_alike_from_the_trace_and_the_module(text, expected):
    assert train_scope.signature(text) == expected


def test_hlo_ops_from_compiled_text():
    text = "\n".join([
        "HloModule jit_train_step, entry_computation_layout={(f32[4]{0})->f32[4]{0}}",
        "%fused_computation (p: f32[4]) -> f32[4] {",
        '  ROOT %m.1 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(f)/mlp/mul"}',
        "}",
        "ENTRY %main (a: f32[4]) -> f32[4] {",
        "  %a = f32[4]{0} parameter(0)",
        '  ROOT %fusion = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation, '
        'metadata={op_name="jit(f)/attention/tanh" source_file="m.py" source_line=3}',
        "}",
    ])
    assert train_scope.hlo_ops(text) == {
        "m.1": ("f32[4]{0} multiply", "jit(f)/mlp/mul"),
        "a": ("f32[4]{0} parameter", ""),
        "fusion": ("f32[4]{0} fusion", "jit(f)/attention/tanh"),
    }


def test_self_time_takes_out_nested_ops():
    ops = [(s, e) for _, s, e, _ in SCOPED["devices"][0]["ops"]]
    assert train_scope.self_times(ops)[:5] == [100, 200, 300, 100, 50]


def test_scope_readers_on_the_small_trace(monkeypatch):
    s = tracefile.summarize(tracefile.Trace.from_dict(SCOPED))
    rec = _record(s, steps=2)
    # self ns of the two whole steps: attention 200 + 400, mlp 300 + 100,
    # head_loss 100, optimizer 50, the loop's own 100 unscoped
    assert train_scope.split(rec, SCOPED_HLO) == [(2, pytest.approx(
        {"attention": 600, "mlp": 400, "head_loss": 100, "optimizer": 50, None: 100}))]
    monkeypatch.setattr(train_scope, "program_ops", lambda: SCOPED_HLO)
    # per step, in ms
    assert attention_ms.read(rec) == pytest.approx(300e-6)
    assert mlp_ms.read(rec) == pytest.approx(200e-6)
    assert head_loss_ms.read(rec) == pytest.approx(50e-6)
    assert optimizer_ms.read(rec) == pytest.approx(25e-6)


def test_scope_readers_read_nothing_from_another_module(monkeypatch):
    """A traced op missing from the compiled module, or there with another
    shape or opcode, means the module is not the one that ran."""
    rec = _record(tracefile.summarize(tracefile.Trace.from_dict(SCOPED)), steps=2)
    other_shape = {**SCOPED_HLO, "fusion.2": ("f32[8]{0} fusion", SCOPED_HLO["fusion.2"][1])}
    missing = {k: v for k, v in SCOPED_HLO.items() if k != "fusion.4"}
    for hlo in (other_shape, missing):
        assert train_scope.split(rec, hlo) == []
        monkeypatch.setattr(train_scope, "program_ops", lambda: hlo)
        assert attention_ms.read(rec) is None
    # a program that notes no train step (or names no scopes)
    monkeypatch.setattr(train_scope, "program_ops", lambda: None)
    assert mlp_ms.read(rec) is None
    monkeypatch.setattr(train_scope, "program_ops",
                        lambda: {k: (sig, "jit(train_step)/add") for k, (sig, _) in
                                 SCOPED_HLO.items()})
    assert optimizer_ms.read(rec) is None


def test_scope_readers_on_a_recorded_trace(monkeypatch):
    """Three steps of a chip trace, the map taken from each op's recorded
    name scope path; the sums agree with the ops by hand, each op's
    duration less its direct children's."""
    rec_trace = json.loads(RECORDED_SCOPES.read_text())
    s = tracefile.summarize(tracefile.Trace.from_dict(rec_trace["trace"]),
                            tuple(rec_trace["span"]))
    hlo = {chunk_gather_roofline.instruction(op[0]): (train_scope.signature(op[0]),
                                                      (op[4:5] or [""])[0])
           for op in rec_trace["trace"]["devices"][0]["ops"]}
    rec = _record(s, steps=3)
    assert train_scope.split(rec, hlo) == [(3, pytest.approx(
        {"attention": 435581407.0, "mlp": 576255379.0, "head_loss": 98269140.0,
         "optimizer": 80214445.0, None: 62795412.0}))]
    monkeypatch.setattr(train_scope, "program_ops", lambda: hlo)
    assert attention_ms.read(rec) == pytest.approx(145.19380233333334)
    assert mlp_ms.read(rec) == pytest.approx(192.08512633333333)
    assert head_loss_ms.read(rec) == pytest.approx(32.75638)
    assert optimizer_ms.read(rec) == pytest.approx(26.73814833333333)


def test_readers_the_benchmark_had_on_the_recorded_trace(monkeypatch):
    """The readers the benchmark had give the numbers they gave before the
    program wrote spans and scopes; the new ones find nothing to read in a
    run whose program notes no train step and sums no spans."""
    rec_trace = json.loads(RECORDED.read_text())
    s = tracefile.summarize(tracefile.Trace.from_dict(rec_trace["trace"]),
                            tuple(rec_trace["span"]))
    cfg = json.loads((harness.BENCH / "configs" / "deepseek-llm-7b.json").read_text())
    rec = _record(s, steps=3,
                  counters={"wait_s": 0.0004, "stage_s": 0.005, "chunk_reads": 9,
                            "ranged_reads": 1},
                  flops_per_step=flops.train_flops_per_position(cfg, 512) * 16 * 512,
                  gather_bytes=flops.gather_bytes(16, 512), peak=peaks.peak("TPU v5 lite"))
    assert chunk_gather_roofline.read(rec) == 4.530151775294176
    assert device_idle_share.read(rec) == 2.9083013876035624
    assert step_mfu.read(rec) == 55.10259655939823
    assert input_wait_ms.read(rec) == 0.13333333333333333
    assert stage_ms.read(rec) == 1.6666666666666667
    assert storage_ops_per_step.read(rec) == 3.3333333333333335
    monkeypatch.setattr(train_scope, "program_ops", lambda: None)
    monkeypatch.setattr(trace, "_profiled", {})
    for reader in (attention_ms, mlp_ms, head_loss_ms, optimizer_ms, read_wait_ms,
                   pack_ms):
        assert reader.read(rec) is None


def test_span_readers_sum_what_the_profiler_saw(tmp_path, monkeypatch):
    """Spans begun while the profiler runs count, over the window's steps;
    spans before or after it do not."""
    import jax

    monkeypatch.setattr(trace, "_profiled", {})
    with trace.span("store.read_chunk", "read", chunk=0):
        time.sleep(0.05)
    with jax.profiler.trace(str(tmp_path)):
        for step in range(2):
            with trace.span("replay.step", "proto", step=step):
                for chunk in range(3):
                    with trace.span("store.read_chunk", "read", chunk=chunk):
                        time.sleep(0.002)
            with trace.span("loader.pack", "decode", step=step):
                pass
    with trace.span("loader.pack", "decode", step=2):
        time.sleep(0.05)
    totals = trace.profiled()
    assert totals["store.read_chunk"][0] == 6 and totals["loader.pack"][0] == 2
    rec = _record({}, steps=2)
    assert read_wait_ms.read(rec) == pytest.approx(1e3 * totals["store.read_chunk"][1] / 2)
    assert 6e-3 <= read_wait_ms.read(rec) < 40
    assert pack_ms.read(rec) == pytest.approx(1e3 * totals["loader.pack"][1] / 2)
    assert pack_ms.read(rec) < 25
    assert read_wait_ms.read(_record({}, steps=0)) is None


def test_a_traced_training_run_feeds_every_new_reader(tmp_path, monkeypatch):
    """A short training run on the gather path with the profiler on, as
    the benchmark's traced run has it: the launcher notes its train step,
    whose compiled text names the four scopes in ``jit_train_step``, and
    sums the data plane's spans."""
    import jax

    from repro.launch import train

    monkeypatch.setattr(trace, "_profiled", {})
    monkeypatch.setattr(programs, "_programs", {})
    argv = ["--arch", "tinyllama-1.1b", "--steps", "4", "--ckpt-every", "100",
            "--batch", "4", "--seq-len", "32", "--num-docs", "64",
            "--device-path", "gather", "--workdir", str(tmp_path / "run")]
    with jax.profiler.trace(str(tmp_path / "prof")):
        assert train.main(argv) == 0
    text = programs.compiled_text("train_step")
    assert text.split(",", 1)[0] == f"HloModule {train_scope.MODULE}"
    scopes = {train_scope.scope_of(path) for _, path in train_scope.program_ops().values()}
    assert {"attention", "mlp", "head_loss", "optimizer", None} == scopes
    rec = _record({}, steps=4)
    totals = trace.profiled()
    # the stager packs ahead of the consumer, up to an epoch's 16 steps
    assert 4 <= totals["loader.pack"][0] <= 16
    assert totals["store.read_chunk"][0] >= 1
    assert read_wait_ms.read(rec) == pytest.approx(1e3 * totals["store.read_chunk"][1] / 4)
    assert pack_ms.read(rec) == pytest.approx(1e3 * totals["loader.pack"][1] / 4)
