"""Device self time per step of the train step's ops under one name scope.

The program names the step's parts with ``jax.named_scope``: ``attention``
(the first norm and the attention block), ``mlp`` (the second norm and
the MLP; ``moe`` on the MoE kinds), ``head_loss`` (final norm, head and
loss) and ``optimizer`` (clipping and the update).

The trace names each op by its HLO instruction (``%fusion.548 = ...``);
the scope is in that instruction's metadata in the compiled module. The
program notes the train step it runs (``repro.obs.programs``), and the
reader compiles it again for the noted shapes, which the compile cache
serves, and reads each instruction's ``op_name``. An op's scope is the
innermost of the scopes above that is one ``/``-separated component of
that path, a transform's wrapper (``jvp(...)``, ``transpose(...)``)
taken off. The reader checks every traced op of the step against the
compiled instruction of its name, result shape and opcode, and reads
nothing where one differs or is missing: the module is then not the one
that ran.

An op's time is its self time: its duration less what the ops nested in
it cover on the ops line (a layer loop's op holds its body's ops). The
readers count the ops that start inside one of the train-step executions
that lie wholly in the traced window, and divide by those executions, as
``step_mfu`` does.
"""

import bisect
import functools
import re
import traceback

from .chunk_gather_roofline import instruction

MODULE = "jit_train_step"
PROGRAM = "train_step"
SCOPES = ("attention", "mlp", "moe", "head_loss", "optimizer")
_WRAPPED = re.compile(r"^(?:[\w.-]+\()*([\w.-]+)\)*$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_COMMENT = re.compile(r"/\*.*?\*/")
_ASYNC = re.compile(r"^[\w-]+-(start|update|done)$")


def scope_of(path: str) -> "str | None":
    """``jit(train_step)/transpose(jvp(attention))/dot_general`` ->
    ``attention``; None when no component is a scope."""
    for part in reversed(path.split("/")):
        m = _WRAPPED.match(part)
        if m and m.group(1) in SCOPES:
            return m.group(1)
    return None


def signature(text: str) -> str:
    """Result shape and opcode of an instruction's text:
    ``%f.1 = (f32[2]{0}, s32[]) fusion(%a), kind=kLoop`` ->
    ``(f32[2]{0}, s32[]) fusion``. The trace and the compiled module print
    the operands differently, these two alike, less ``/*index=5*/``
    comments in long tuples; the trace calls an async pair's halves
    ``async-start``/``async-done`` where the module says ``slice-start``/
    ``slice-done``, so each such opcode reads as the former."""
    rest = _COMMENT.sub("", text.split(" = ", 1)[-1])
    depth = 0
    for i, c in enumerate(rest):
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == " " and depth == 0:
            return rest[:i] + " " + _ASYNC.sub(r"async-\1", rest[i + 1:].split("(", 1)[0])
    return rest


@functools.lru_cache(maxsize=2)
def hlo_ops(text: str) -> dict:
    """``{instruction name: (signature, op_name)}`` of a compiled module's
    HLO text, every computation's instructions."""
    out = {}
    for line in text.splitlines():
        line = line.strip().removeprefix("ROOT ")
        if not line.startswith("%") or " = " not in line:
            continue
        m = _OP_NAME.search(line)
        out[instruction(line)] = (signature(line), m.group(1) if m else "")
    return out


def program_ops() -> "dict | None":
    """The noted train step's ``hlo_ops``, or None where the program notes
    none (or it cannot be compiled again)."""
    try:
        from repro.obs import programs
    except ImportError:
        return None
    try:
        text = programs.compiled_text(PROGRAM)
    except Exception:
        # the run goes on and leaves the scope metrics out
        traceback.print_exc()
        return None
    return hlo_ops(text) if text else None


def self_times(ops: list) -> list:
    """Each ``(start, end)``'s duration less what the intervals nested in
    it cover, in the order given. Intervals on one line nest (a loop's body
    ops lie inside the loop's op); one that overlaps another without lying
    inside it is no child of it."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    self_ns = [e - s for s, e in ops]
    stack: list = []
    for i in order:
        s, e = ops[i]
        while stack and not (ops[stack[-1]][0] <= s and e <= ops[stack[-1]][1]):
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= e - s
        stack.append(i)
    return self_ns


def split(run, hlo: "dict | None" = None) -> "list[tuple[int, dict]]":
    """Per device: (train-step executions, {scope or None: self ns}).
    ``hlo`` maps instructions as ``hlo_ops`` does (the noted program's when
    not given). Empty where the map is missing or is not the traced one's."""
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


def read_scope(run, scope: str) -> "float | None":
    """ms per step under ``scope``, averaged over devices; None where no
    op carries the scope (a program that names no scopes or notes no
    train step)."""
    shares = [by[scope] / n / 1e6 for n, by in split(run) if scope in by]
    if not shares:
        return None
    return sum(shares) / len(shares)
