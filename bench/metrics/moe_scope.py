"""The train step's device self time split by name scope, with the MoE
layer's nested ``experts`` scope (the held experts' grouped matmuls) as a
scope of its own: ``train_scope``'s split, innermost scope first.

XLA's TPU rewrite of ``jax.lax.ragged_dot`` replaces each grouped matmul
with custom calls whose ``op_name`` is ``ragged-dot-<...>`` and no longer
holds the scope path; the program computes ragged dots only inside
``experts``, so such ops are counted there.
"""

import bisect

from .chunk_gather_roofline import instruction
from .train_scope import MODULE, SCOPES, _WRAPPED, program_ops, self_times, signature

NESTED = ("experts",)
RAGGED_DOT = "ragged-dot-"


def scope_of(path: str) -> "str | None":
    """The innermost component of ``path`` that is a scope, ``experts``
    included: ``.../transpose(jvp(moe))/experts/mul`` -> ``experts``; a
    rewritten grouped matmul (``ragged-dot-none``) -> ``experts``."""
    if path.startswith(RAGGED_DOT):
        return "experts"
    for part in reversed(path.split("/")):
        m = _WRAPPED.match(part)
        if m and m.group(1) in SCOPES + NESTED:
            return m.group(1)
    return None


def split(run, hlo: "dict | None" = None) -> "list[tuple[int, dict]]":
    """``train_scope.split`` by :func:`scope_of`: per device, (train-step
    executions, {scope or None: self ns})."""
    t = run.trace
    if not t:
        return []
    if hlo is None:
        hlo = program_ops()
        if hlo is None:
            return []
    out = []
    for runs, ops in zip(t["modules"], t["ops"]):
        steps = sorted((s, e) for name, s, e in runs if name == MODULE)
        if not steps:
            continue
        mine = [(name, s, e) for name, s, e, module in ops if module == MODULE]
        starts = [s for s, _ in steps]
        by: dict = {}
        for (name, start, _), self_ns in zip(mine, self_times([(s, e) for _, s, e in mine])):
            i = bisect.bisect_right(starts, start) - 1
            if i < 0 or start > steps[i][1]:
                continue
            found = hlo.get(instruction(name))
            if found is None or found[0] != signature(name):
                return []
            key = scope_of(found[1])
            by[key] = by.get(key, 0.0) + self_ns
        out.append((len(steps), by))
    return out


def read_ms(run, scopes: tuple, hlo: "dict | None" = None) -> "float | None":
    """ms per step under any of ``scopes``, averaged over devices; None
    where no op carries one of them."""
    shares = [sum(by.get(s, 0.0) for s in scopes) / n / 1e6
              for n, by in split(run, hlo) if any(s in by for s in scopes)]
    if not shares:
        return None
    return sum(shares) / len(shares)
