"""Time per window step in one kind of the program's spans.

While the profiler runs, the program writes each ``repro.obs`` span as a
profiler annotation and sums each span name's count and seconds
(``repro.obs.trace.profiled``). The benchmark's traced run profiles its
window alone, so those sums are the spans begun in the window."""


def span_ms(run, name: str) -> "float | None":
    """Summed durations of the spans named ``name``, in ms, over the
    window's steps; None where the program sums no such span (one that
    writes no spans to the profiler)."""
    if run.steps <= 0:
        return None
    try:
        from repro.obs.tracer import profiled
    except ImportError:
        return None
    found = profiled().get(name)
    if found is None:
        return None
    return 1e3 * found[1] / run.steps
