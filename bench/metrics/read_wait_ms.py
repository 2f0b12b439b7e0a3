"""Chunk-read time per window step, in ms: the program's
``store.read_chunk`` spans begun in the traced window
(``program_spans``). Each read pays the storage profile's head time; the
replay engine runs a step's reads one after another inside its
``replay.step`` span, on the thread that produces batches."""

from .program_spans import span_ms


def read(run):
    return span_ms(run, "store.read_chunk")
