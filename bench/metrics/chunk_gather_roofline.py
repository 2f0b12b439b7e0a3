"""Roofline share of the ``chunk_gather_train`` Pallas kernel, in %.

The least time its bytes need at the chip's peak HBM bandwidth
(``bench/flops.py gather_bytes``: slot rows and tables in, three grids
out; the kernel does no arithmetic to speak of, so bandwidth bounds it),
over its measured device time: the kernel's events in the traced window.
An op is the kernel by its own instruction name, the text before `` = ``
in the trace: the copies after it name the kernel among their operands.
"""

import re

MODULE = "jit_chunk_gather_train"
KERNEL = re.compile(r"custom|kernel|pallas|chunk_gather", re.IGNORECASE)


def instruction(name: str) -> str:
    """``%chunk_gather_train.1 = (s32[...]) custom-call(...)`` ->
    ``chunk_gather_train.1``."""
    return name.split(" = ", 1)[0].lstrip("%")


def read(run):
    t = run.trace
    if not t:
        return None
    shares = []
    for ops in t["ops"]:
        calls = [(s, e) for name, s, e, module in ops
                 if module == MODULE and KERNEL.search(instruction(name))]
        busy_s = sum(e - s for s, e in calls) / 1e9
        if not calls or busy_s <= 0:
            continue
        least_s = len(calls) * run.gather_bytes / run.peak["hbm_bytes_per_s"]
        shares.append(least_s / busy_s)
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
