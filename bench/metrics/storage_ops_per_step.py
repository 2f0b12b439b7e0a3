"""Storage operations per step: whole-chunk and ranged reads the store's
backend served over the window (``BackendStats``), per step. Each one pays
the storage profile's head time."""


def read(run):
    c = run.counters
    if run.steps <= 0 or "chunk_reads" not in c:
        return None
    return (c["chunk_reads"] + c["ranged_reads"]) / run.steps
