"""Jitted programs by name, for reading a device profile back to the source.

A device profile names each op by its HLO instruction (``%fusion.548 =
...``); the instruction's metadata in the compiled module holds the
``op_name`` it was lowered from, ``jax.named_scope`` components included
(``jit(train_step)/jvp()/while/body/closed_call/attention/dot_general``).
A launcher notes the programs it runs with :func:`note`, once, before the
first call; :func:`compiled_text` then gives a program's compiled HLO text
with that metadata. It lowers and compiles the program again for the
noted argument shapes, which the in-memory or persistent compile cache
usually serves, so call it after the timed work, not inside it.
"""

from __future__ import annotations

import threading

__all__ = ["compiled_text", "note", "noted_args"]

_lock = threading.Lock()
# name -> [jitted fn, abstract args, compiled text or None]
_programs: dict = {}


def note(name: str, fn, *args) -> None:
    """Keep ``fn`` (a ``jax.jit`` function) under ``name`` with the shapes,
    dtypes and shardings of ``args``; no array is kept."""
    import jax

    def abstract(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=getattr(x, "sharding", None),
                                    weak_type=getattr(x, "weak_type", False))

    entry = [fn, jax.tree.map(abstract, args), None]
    with _lock:
        _programs[name] = entry


def noted_args(name: str):
    """The abstract arguments (shapes, dtypes) the program noted as
    ``name`` was noted with, or None when none was noted."""
    with _lock:
        entry = _programs.get(name)
    return None if entry is None else entry[1]


def compiled_text(name: str) -> "str | None":
    """The compiled HLO text of the program noted as ``name``, or None
    when none was noted."""
    with _lock:
        entry = _programs.get(name)
    if entry is None:
        return None
    if entry[2] is None:
        fn, args, _ = entry
        entry[2] = fn.lower(*args).compile().as_text()
    return entry[2]
