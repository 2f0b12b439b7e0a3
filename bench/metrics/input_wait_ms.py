"""Input wait per step: the consumer's time blocked on the stager's queue
(``DeviceStats.wait_s``) over the window, in ms per step. This is the part
of staging the double buffer failed to hide."""


def read(run):
    if run.steps <= 0 or "wait_s" not in run.counters:
        return None
    return 1e3 * run.counters["wait_s"] / run.steps
