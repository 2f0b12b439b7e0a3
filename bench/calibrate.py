#!/usr/bin/env python3
"""Readings that the correctness limits are set from, in one process.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3,... [--control 3]

For each seed: the program's first three steps through the timed path
(set-up only, no window) against the float32 reference, as ``run.py``
compares them. For the first ``--control`` seeds also the control (the
reference with every matrix product's operands in int8, the precision
below the configuration's bfloat16) and the fault of half the batch left
out with the mean over the rest, each put in the program's place. Prints
one JSON line per reading; a state left unchanged reads 1 on the change
by construction and needs no run. Not part of a benchmark run.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import run as _run  # noqa: E402,F401  (sets the cache and search paths)


def worst_leaves(prog: dict, ref: dict) -> dict:
    """The leaf that sets each norm gap, for the look at what a gap is."""
    import statistics

    out = {}
    for key in ("grad_norms", "change_norms"):
        med = statistics.median(ref[key].values())
        gaps = {n: abs(prog[key][n] - r) / max(r, med) for n, r in ref[key].items()}
        out[key] = max(gaps, key=gaps.get)
    return out


def readings(cell, seeds, control: int, *, require_chip=True, emit=print):
    from bench import harness

    if require_chip:
        harness.check_device(cell)
    rows = cell.flag("--batch")
    for k, seed in enumerate(seeds):
        workdir = Path(tempfile.mkdtemp(prefix="bench_cal_"))
        try:
            t0 = time.perf_counter()
            drv, _ = harness.drive(cell, seed, 0.0, t0, workdir, window=False,
                                   require_chip=require_chip)
            prog = {"losses": [float(x) for x in drv.losses[:harness.CHECKED_STEPS]],
                    "grad_norms": drv.grad_norms, "change_norms": drv.change}
            first = drv.first_host
            del drv
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
        ref = harness.reference_readings(cell, first)
        emit(json.dumps({"seed": seed, "who": "program",
                         **harness.training_gaps(prog, ref),
                         "losses": prog["losses"], "ref_losses": ref["losses"],
                         "worst": worst_leaves(prog, ref),
                         "ref_grad_global_norm": ref["grad_global_norm"]}))
        if k < control:
            for who, kw in (("control_int8", {"precision": "int8"}),
                            ("fault_half_batch", {"keep_rows": rows // 2})):
                other = harness.reference_readings(cell, first, **kw)
                emit(json.dumps({"seed": seed, "who": who,
                                 **harness.training_gaps(other, ref),
                                 "losses": other["losses"],
                                 "worst": worst_leaves(other, ref)}))


#: The numbers compared with the reference in a training cell.
TRAINING = ("first_loss_gap", "loss_gap", "grad_norm_gap", "change_norm_gap")


def set_limits(rows: list) -> dict:
    """Each number's limit from its two readings.

    The lower reading is the largest that the program's seeds give. The
    upper is the smallest that the control gives, where that is three
    times the lower or more, or that a fault gives, where that is ten
    times the lower or more (a state left unchanged reads 1 on the
    change, three times). The limit lies between them, nearer the upper:
    ``lower**0.4 * upper**0.6``. A number with no upper reading is not
    compared.
    """
    def of(who, key):
        return [r[key] for r in rows if r["who"] == who]

    limits, report = {}, {}
    for key in TRAINING:
        lower = max(of("program", key))
        uppers = []
        ctrl = of("control_int8", key)
        if ctrl and min(ctrl) >= 3 * lower:
            uppers.append(min(ctrl))
        half = of("fault_half_batch", key)
        if half and min(half) >= 10 * lower:
            uppers.append(min(half))
        if key == "change_norm_gap" and 1.0 >= 3 * lower:
            uppers.append(1.0)
        report[key] = {"lower": lower, "upper": min(uppers) if uppers else None,
                       "control": ctrl, "half_batch": half}
        if uppers:
            limits[key] = lower ** 0.4 * min(uppers) ** 0.6
    for who in ("control_int8", "fault_half_batch"):
        for r in rows:
            if r["who"] == who and not any(r[k] > limits[k] for k in limits):
                report.setdefault("passes", []).append((who, r["seed"]))
    return limits, report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--write-limits", action="store_true",
                    help="set the cell's limits file from these readings")
    args = ap.parse_args(argv)
    from bench import harness

    cell = harness.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = []

    def emit(line):
        rows.append(json.loads(line))
        print(line, flush=True)

    readings(cell, seeds, args.control, emit=emit)
    limits, report = set_limits(rows)
    print(json.dumps({"limits": limits, "report": report}), flush=True)
    if args.write_limits:
        path = harness.BENCH / "limits" / f"{args.workload}.json"
        data = {k: 0 for k in ("rows_vs_host_loader", "rows_vs_corpus", "repeated_docs")}
        data.update(limits)
        path.write_text(json.dumps(data, indent=2) + "\n")
    print(f"calibrate: {time.perf_counter() - T_START:.1f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
