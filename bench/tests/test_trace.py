"""The trace reduction and the readers of the per-layer metrics, on a small
trace with known answers and on a part of a trace recorded on the chip."""

import json
from pathlib import Path

import pytest

from bench import flops, harness, peaks
from bench import trace as tracefile
from bench.metrics import chunk_gather_roofline, device_idle_share, step_mfu

#: Two gathers and three train steps of a deepseek-llm-7b.docs512-nas run
#: on one TPU v5 lite, as recorded (see its "about").
RECORDED = Path(__file__).with_name("trace_v5e.json")
COPY = "copy.1 = s32[4,8]{1,0} fusion(s32[4,1,8]{2,1,0} %gather_kernel)"

SMALL = {
    "devices": [{
        "name": "/device:TPU:0",
        "ops": [
            ["fusion.1", 0, 100, "jit_train_step"],
            ["fusion.2", 100, 300, "jit_train_step"],
            ["gather_kernel", 320, 330, "jit_chunk_gather_train"],
            [COPY, 330, 335, "jit_chunk_gather_train"],   # names the kernel
            ["fusion.1", 400, 700, "jit_train_step"],
            ["fusion.2", 650, 900, "jit_train_step"],   # overlaps the op before
            ["fusion.1", 1000, 1200, "jit_train_step"],
        ],
        "modules": [
            ["jit_train_step", 0, 300],
            ["jit_chunk_gather_train", 320, 335],
            ["jit_train_step", 400, 900],
            ["jit_train_step", 1000, 1200],
        ],
    }],
    "host": [
        ["bench.window", 50, 1100, "python3"],
        ["PjitFunction(train_step)", 340, 390, "python3"],
        ["queue.get", 350, 380, "python3"],
        ["PjRtStreamExecutorClient::Execute", 905, 995, "python3"],
    ],
}


def test_busy_gaps_and_modules_in_the_window():
    s = tracefile.summarize(tracefile.Trace.from_dict(SMALL))
    # window [50, 1100): busy [50,300) + [320,335) + [400,900) + [1000,1100)
    assert s["window_s"] == pytest.approx(1050e-9)
    assert s["busy_s"] == pytest.approx((250 + 15 + 500 + 100) * 1e-9)
    # gaps: [300,320) 20, [335,400) 65, [900,1000) 100; named by the
    # innermost host event at each gap's middle
    assert s["idle_gaps"][0] == ["PjRtStreamExecutorClient::Execute", pytest.approx(100e-9)]
    assert s["idle_gaps"][1] == ["queue.get", pytest.approx(65e-9)]
    assert s["idle_gaps"][2][0] == "host: no event"
    assert dict(s["device_ops"]) == pytest.approx(
        {"fusion.1": 450e-9, "fusion.2": 450e-9, "gather_kernel": 10e-9, COPY: 5e-9})
    # only the module runs wholly inside the window
    assert [m[0] for m in s["modules"][0]] == ["jit_chunk_gather_train", "jit_train_step"]


def _record(summary, flops_per_step=1.0, gather_bytes=10, peak=None):
    peak = peak or {"flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}
    return harness.RunRecord(steps=3, window_s=1.0, counters={}, trace=summary,
                             flops_per_step=flops_per_step, gather_bytes=gather_bytes,
                             peak=peak)


def test_readers_on_the_small_trace():
    window = (0, 1300)
    s = tracefile.summarize(tracefile.Trace.from_dict(SMALL), window)
    rec = _record(s, flops_per_step=100.0, gather_bytes=5)
    # 3 train steps from 0 to 1200 ns at 1e9 FLOP/s peak: 3 * 100 / 1.2e-6 / 1e9
    assert step_mfu.read(rec) == pytest.approx(100.0 * 300 / 1.2e-6 / 1e9)
    # the kernel op ran 10 ns for 5 bytes at 1e9 B/s (5 ns): 50%; the copy
    # after it, which names the kernel as its operand, is not the kernel
    assert chunk_gather_roofline.read(rec) == pytest.approx(50.0)
    busy = 300 + 15 + 500 + 200
    assert device_idle_share.read(rec) == pytest.approx(100.0 * (1 - busy / 1300))


def test_readers_find_nothing_in_an_empty_trace():
    rec = _record({})
    assert step_mfu.read(rec) is None
    assert chunk_gather_roofline.read(rec) is None
    assert device_idle_share.read(rec) is None


def test_readers_on_a_recorded_trace():
    rec_trace = json.loads(RECORDED.read_text())
    assert {tracefile.OPS_LINE, tracefile.MODULES_LINE} <= set(rec_trace["lines"])
    s = tracefile.summarize(tracefile.Trace.from_dict(rec_trace["trace"]),
                            tuple(rec_trace["span"]))
    assert [m[0] for m in s["modules"][0]] == [step_mfu.MODULE, chunk_gather_roofline.MODULE] * 2 \
        + [step_mfu.MODULE]
    cfg = json.loads((harness.BENCH / "configs" / "deepseek-llm-7b.json").read_text())
    rec = _record(s, flops_per_step=flops.train_flops_per_position(cfg, 512) * 16 * 512,
                  gather_bytes=flops.gather_bytes(16, 512), peak=peaks.peak("TPU v5 lite"))
    # three steps of 4.535e13 FLOPs from 0 to 1.2534 s at 197 TFLOP/s
    assert step_mfu.read(rec) == pytest.approx(100 * 3 * 4.535e13 / 1.253449218 / 197e12,
                                               rel=1e-3)
    # two kernel calls (3,756 and 3,758 ns) of 139,392 bytes at 819 GB/s;
    # the three copies after each are not the kernel
    assert chunk_gather_roofline.read(rec) == pytest.approx(
        100 * 2 * 139_392 / 819e9 / 7_514e-9)
