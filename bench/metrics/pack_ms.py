"""Host decode and pack time per window step, in ms: the program's
``loader.pack`` spans (records decoded into the slot buffer the gather
kernel reads) begun in the traced window (``program_spans``)."""

from .program_spans import span_ms


def read(run):
    return span_ms(run, "loader.pack")
