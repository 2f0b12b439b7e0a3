#!/usr/bin/env python3
"""Smoke run of the training main path on one TPU chip.

Trains TinyLlama-1.1B at its published widths (22 layers, d_model 2048,
vocab 32000; random weights and a synthetic chunk store, both made from
``--seed``) for a few steps through ``repro.launch.train``, in this one
process, with the Pallas ``chunk_gather_train`` pass assembling every batch
on the device.

It fails -- non-zero exit, no ``"ok"`` line -- unless:

* JAX's first device is a TPU and the stager's gather runs compiled;
* the first gathered batch is identical to the host loader's
  ``epoch_async`` grids for the same step;
* every step's loss is finite and within ``LOSS_BAND`` of ln(vocab), the
  loss of a near-uniform prediction at random init.

Earlier lines report the device, the compile cache, the first step's time
(compile included) against the later steps, each loss and the peak device
memory. The last line is ``{"ok": true, "device": {...}}``.

    python3 chip_smoke.py [--steps 8] [--seed 0]
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import ChunkStore, RedoxLoader  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "tinyllama-1.1b"
#: The shape that fits one 16 GB v5e chip at full width (compiled for a
#: described chip: 6.6 GB of donated arguments + 10.0 GB of temporaries).
TRAIN_FLAGS = ["--arch", ARCH, "--full", "--optimizer", "adafactor",
               "--remat", "full", "--batch", "4", "--seq-len", "2048",
               "--device-path", "gather"]
#: Largest |loss - ln(vocab)| accepted over the first steps. A random
#: init's logits have about unit variance, which puts the loss near
#: ln(vocab) + 0.5; the warm-up learning rate barely moves it in a few steps.
LOSS_BAND = 1.5
GRIDS = ("tokens", "targets", "loss_mask")


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class StepLog:
    """``on_step`` hook: waits for each step, logs its time and loss, and
    keeps the first gathered batch on the host."""

    def __init__(self):
        self.losses: list[float] = []
        self.step_s: list[float] = []
        self.first: "dict | None" = None

    def __call__(self, ev: train.StepEvent) -> None:
        if ev.step == 1:
            check(ev.stager is not None and not ev.stager.interpret,
                  "the device gather resolved to interpret mode")
            print("gather: compiled", flush=True)
            self.first = {k: np.asarray(ev.batch[k]) for k in GRIDS}
            self.first["step"] = int(ev.batch["step"])
        jax.block_until_ready(ev.metrics)
        self.step_s.append(time.perf_counter() - ev.started)
        self.losses.append(float(ev.metrics["loss"]))
        print(f"step {ev.step} loss {self.losses[-1]!r} "
              f"step_s {self.step_s[-1]!r}", flush=True)


def host_grids(argv: list[str], workdir: Path) -> dict:
    """The first batch of epoch 0 from the host loader, on the store the
    trainer built in ``workdir``."""
    spec = train.session_spec(train.build_parser().parse_args(argv))
    store = ChunkStore.open(workdir / "chunks")
    try:
        it = RedoxLoader.from_spec(spec, store).epoch_async(0)
        try:
            return dict(next(it))
        finally:
            it.close()
    finally:
        store.close()


def run(steps: int, seed: int) -> dict:
    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu",
          f"JAX found no TPU (first device is {dev.platform!r})")
    print(f"device: {dev.device_kind} count {len(devices)}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    log = StepLog()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        workdir = Path(tmp)
        argv = TRAIN_FLAGS + ["--steps", str(steps), "--ckpt-every", str(steps + 1),
                              "--seed", str(seed), "--workdir", str(workdir)]
        rc = train.main(argv, on_step=log)
        check(rc == 0, f"launch.train exited {rc}")
        check(len(log.losses) == steps,
              f"{len(log.losses)} of {steps} steps ran")
        host = host_grids(argv, workdir)

    same = log.first["step"] == int(host["step"]) and all(
        log.first[k].dtype == np.asarray(host[k]).dtype
        and np.array_equal(log.first[k], np.asarray(host[k]))
        for k in GRIDS
    )
    print(f"first gathered batch identical to host grids: {same}", flush=True)
    check(same, "the device gather's first batch differs from the host grids")

    ln_v = math.log(get_config(ARCH).vocab_size)
    for i, loss in enumerate(log.losses, 1):
        check(math.isfinite(loss) and abs(loss - ln_v) <= LOSS_BAND,
              f"step {i} loss {loss!r} outside ln(vocab) {ln_v:.3f} "
              f"+- {LOSS_BAND}")
    later = log.step_s[1:]
    print(f"first step s (compile included): {log.step_s[0]!r}; later steps s: "
          f"median {float(np.median(later)) if later else float('nan')!r}",
          flush=True)
    # The in-use peak alone stays near the state's size on a TPU, well
    # under the compiler's arguments + temporaries; print the reserved
    # peak and the limit beside it.
    stats = dev.memory_stats() or {}
    for key in ("peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit"):
        print(f"{key}: {stats.get(key, 'not reported')}", flush=True)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        device = run(args.steps, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
