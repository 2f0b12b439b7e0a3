"""Staging time per step: the stager thread's wall time packing and
shipping batches and dispatching the gather (``DeviceStats.stage_s``)
over the window, in ms per step."""


def read(run):
    if run.steps <= 0 or "stage_s" not in run.counters:
        return None
    return 1e3 * run.counters["stage_s"] / run.steps
