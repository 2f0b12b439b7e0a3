"""Whole-step model FLOP utilization from the device trace, in %.

Model FLOPs per step (``bench/flops.py``: forward and backward matrix
products, causal attention at half the square, no remat, no embedding
lookup) times the train-step executions that lie wholly in the traced
window, over the time from the first one's start to the last one's end,
over the chip's peak.
"""

MODULE = "jit_train_step"


def read(run):
    t = run.trace
    if not t:
        return None
    shares = []
    for runs in t["modules"]:
        steps = sorted((s, e) for name, s, e in runs if name == MODULE)
        if len(steps) < 2:
            continue
        span_s = (steps[-1][1] - steps[0][0]) / 1e9
        shares.append(len(steps) * run.flops_per_step / span_s / run.peak["flops_per_s"])
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
