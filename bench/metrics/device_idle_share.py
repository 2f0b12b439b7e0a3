"""Device idle share: 1 - (union of the intervals in which an XLA op ran
on the device / traced window), in %."""


def read(run):
    t = run.trace
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
