"""The held experts' grouped matmuls against the chip's bf16 peak, in %.

FLOPs per step (``bench/moe_flops.py``: 8 · 3 · d · f per (token, expert)
pair, the pairs from the program's ``moe.expert_pairs`` counter, which the
launcher adds at each loss read while the profiler runs, ``profiled()``;
d and f from the noted train step's expert weights) over the device self
time per step of the ops under the ``experts`` name scope (``moe_scope``:
the grouped matmuls and the activation between them), over the peak.
Nothing is read where the program counts no pairs, notes no train step or
names no ``experts`` scope.
"""

from .. import moe_flops
from .moe_scope import read_ms
from .train_scope import PROGRAM


def pairs_per_step() -> "float | None":
    """Mean (token, expert) pairs the held experts computed per step, of
    the steps counted while the profiler ran; None where none were."""
    try:
        from repro.obs.tracer import profiled
    except ImportError:
        return None
    found = profiled().get("moe.expert_pairs")
    if not found or not found[0]:
        return None
    return found[1] / found[0]


def expert_widths() -> "tuple[int, int] | None":
    try:
        from repro.obs import programs
    except ImportError:
        return None
    args = getattr(programs, "noted_args", lambda name: None)(PROGRAM)
    return None if args is None else moe_flops.expert_widths(args[0])


def read(run, hlo: "dict | None" = None):
    pairs, widths = pairs_per_step(), expert_widths()
    if pairs is None or widths is None or not run.peak:
        return None
    ms = read_ms(run, ("experts",), hlo)
    if not ms:
        return None
    flops = moe_flops.expert_matmul_flops(*widths, pairs)
    return 100.0 * flops / (ms / 1e3) / run.peak["flops_per_s"]
