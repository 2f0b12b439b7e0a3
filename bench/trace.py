"""Reduction of a profiler trace to device busy time, kernels and gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain :class:`Trace`: per device plane the XLA op and XLA module events,
and every host event. ``summarize`` reduces a trace over the window that
the benchmark marks with a ``bench.window`` annotation: the union of the
intervals in which an op ran (busy), the ops that took most time, the
longest idle gaps named by the innermost host event that covers each, and
the executions of each XLA module. Times are nanoseconds on the trace's
own clock, on which host and device events are aligned.
"""

from __future__ import annotations

import bisect
import dataclasses
import re

WINDOW_MARK = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float
    module: str = ""

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Device:
    name: str
    ops: list
    modules: list


@dataclasses.dataclass
class Trace:
    devices: list
    host: list

    @classmethod
    def from_dict(cls, d: dict) -> "Trace":
        ev = lambda e: Event(e[0], float(e[1]), float(e[2]), *(e[3:4] or [""]))
        return cls(
            devices=[Device(p["name"], [ev(e) for e in p["ops"]],
                            [ev(e) for e in p["modules"]]) for p in d["devices"]],
            host=[ev(e) for e in d["host"]],
        )

    def to_dict(self) -> dict:
        row = lambda e: [e.name, e.start, e.end, e.module]
        return {"devices": [{"name": p.name, "ops": [row(e) for e in p.ops],
                             "modules": [row(e) for e in p.modules]}
                            for p in self.devices],
                "host": [row(e) for e in self.host]}


def module_name(name: str) -> str:
    """``jit_train_step(1234)`` -> ``jit_train_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def load(path) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        stats = dict(e.stats)
                        ops.append(Event(e.name, e.start_ns, e.start_ns + e.duration_ns,
                                         module_name(str(stats.get("hlo_module", "")))))
                elif line.name == MODULES_LINE:
                    modules.extend(Event(module_name(e.name), e.start_ns,
                                         e.start_ns + e.duration_ns)
                                   for e in line.events)
            if ops or modules:
                _assign_modules(ops, modules)
                devices.append(Device(plane.name, ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.start_ns + e.duration_ns,
                                  line.name)
                            for e in line.events if e.duration_ns > 0)
    return Trace(devices, host)


def _assign_modules(ops: list, modules: list) -> None:
    """Give each op that carries no module name the module whose run
    covers the op's start."""
    runs = sorted((m.start, m.end, m.name) for m in modules)
    starts = [r[0] for r in runs]
    for op in ops:
        if op.module:
            continue
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and runs[i][1] >= op.start:
            op.module = runs[i][2]


def window_of(trace: Trace) -> "tuple[float, float] | None":
    marks = [e for e in trace.host if e.name == WINDOW_MARK]
    if not marks:
        return None
    return min(e.start for e in marks), max(e.end for e in marks)


def union(intervals, lo: float, hi: float) -> list:
    """Merged, clipped ``[start, end)`` intervals inside ``[lo, hi)``."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(busy: list, lo: float, hi: float) -> list:
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def host_cover(host: list, t: float) -> str:
    """Innermost host event (the shortest) that covers time ``t``."""
    best = None
    for e in host:
        if e.start <= t < e.end and e.name != WINDOW_MARK:
            if best is None or e.dur < best.dur:
                best = e
    return best.name if best is not None else "host: no event"


def summarize(trace: Trace, window: "tuple[float, float] | None" = None,
              top: int = 10) -> dict:
    """Busy and idle time, top ops, longest gaps and module runs.

    Busy, op and gap times are averaged over the device planes. Returns
    seconds; ``modules`` holds each device's module executions that lie
    wholly inside the window, as ``(name, start_ns, end_ns)``.
    """
    if window is None:
        window = window_of(trace)
    if window is None or not trace.devices:
        return {}
    lo, hi = window
    n = len(trace.devices)
    busy_ns, op_ns, gap_list, modules = 0.0, {}, [], []
    for dev in trace.devices:
        busy = union(((e.start, e.end) for e in dev.ops), lo, hi)
        busy_ns += sum(e - s for s, e in busy)
        for e in dev.ops:
            d = min(e.end, hi) - max(e.start, lo)
            if d > 0:
                op_ns[e.name] = op_ns.get(e.name, 0.0) + d
        gap_list.extend(gaps(busy, lo, hi))
        modules.append([(e.name, e.start, e.end) for e in dev.modules
                        if e.start >= lo and e.end <= hi])
    gap_list.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "device_ops": [[name, t / n / 1e9] for name, t in
                       sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[host_cover(trace.host, (s + e) / 2), (e - s) / 1e9]
                      for s, e in gap_list[:top]],
        "modules": modules,
        "ops": [[(e.name, e.start, e.end, e.module) for e in dev.ops
                 if e.start >= lo and e.end <= hi] for dev in trace.devices],
    }
