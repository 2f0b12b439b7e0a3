"""One benchmark run: a cell trained through ``repro.launch.train.main``.

The cell's configuration is registered in the program's ``ARCHS`` under
its own name, its storage profile as a backend in the program's
``BACKENDS``, and the trainer is driven in this process through its
``on_step`` hook:

* set-up: store build, state init, compile, and ``WARMUP`` steps, each
  waited for. The first three are read for the correctness check (loss,
  the first gradient from the optimizer's state, the parameters' change);
  the time spent reading them is not set-up and is taken out of ``setup_s``.
* window: from the end of set-up for ``seconds``. At step n the hook waits
  for step n-1 (one step always queued) and records its completion; the
  window holds every step that completed within ``seconds``, and ends at
  the last of them.
* after the window: peak memory, then the program's state is dropped and
  the checks run: every batch the run consumed against the host loader's
  grids and against the corpus, and the first three steps against the
  float32 reference.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import math
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from . import flops as flop_counts
from . import peaks
from . import trace as tracefile
from .reference import data as ref_data

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent

#: Steps that belong to set-up; the first three are the checked steps.
WARMUP = 4
CHECKED_STEPS = 3
#: ``launch/train.py`` seeds the model's init at 0 whatever ``--seed`` is.
INIT_SEED = 0
#: Sequences per row block of the reference: about 2,048 positions a block.
REF_POSITIONS = 2048
GRIDS = ("tokens", "targets", "loss_mask")
FIXED_FLAGS = ["--full", "--nodes", "1", "--device-path", "gather",
               "--optimizer", "adafactor", "--remat", "full",
               "--steps", "1000000", "--ckpt-every", "1000001"]


#: Programs lowered so far in this process (each is then compiled or
#: loaded from the compile cache). One lowered inside the window makes the
#: run not correct (check ``programs_lowered_in_window``, limit 0).
LOWERED = [0]
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_LISTENING: list = []


def _count_lowering(event: str, *_, **__) -> None:
    if event == _LOWER_EVENT:
        LOWERED[0] += 1


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class WindowClosed(Exception):
    """Raised from the step hook to end the trainer when the window closes."""


# ------------------------------------------------------------------ cells
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    traffic_name: str
    end_to_end: list
    per_layer: list
    limits: dict

    @property
    def argv(self) -> list:
        return list(self.traffic["argv"])

    def flag(self, name: str) -> int:
        argv = self.argv
        return int(argv[argv.index(name) + 1])


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((BENCH / "limits" / f"{workload}.json").read_text())
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if workload in m.get("workloads", [workload] if m["moves"] in reported else [])]
    return Cell(workload, int(w["chips"]), config, traffic, w["traffic"],
                e2e, layer, limits)


def model_kind(cell: Cell):
    return importlib.import_module(f"bench.models.{cell.config['bench_model']}")


def reference_kind(cell: Cell):
    return importlib.import_module(f"bench.reference.{cell.config['bench_model']}")


# ------------------------------------------------------------ the program
def register(cell: Cell) -> "tuple[str, list]":
    """Put the cell's configuration and storage profile where the program
    finds them by name. Returns the backend's name and the list of the
    backend instances the program makes, whose counters the run reads."""
    from repro.configs import ARCHS
    from repro.core.storage.store import BACKENDS
    from repro.core.storage.vfs import VFSBackend

    ARCHS[cell.config["name"]] = model_kind(cell).program_config(cell.config)
    latency = float(cell.traffic["storage"]["latency_s"])
    made = []

    def backend(**kw):
        b = VFSBackend(latency_s=latency, **kw)
        made.append(b)
        return b

    name = f"bench-{cell.traffic_name}"
    BACKENDS[name] = backend
    return name, made


def train_argv(cell: Cell, seed: int, workdir: Path, backend: str) -> list:
    return (["--arch", cell.config["name"]] + FIXED_FLAGS + cell.argv
            + ["--backend", backend, "--seed", str(seed), "--workdir", str(workdir)])


def _trainer_state():
    """The trainer's live state, read from ``train.main``'s frame (the
    hook is called from there, after the step was dispatched)."""
    f = sys._getframe(1)
    while f is not None:
        if f.f_code.co_name == "main" and "state" in f.f_locals:
            return f.f_locals["state"]
        f = f.f_back
    raise RuntimeError("train.main's state is not on the stack")


def _is_moment(x) -> bool:
    return isinstance(x, dict) and ("vr" in x or "v" in x)


def _leaves(tree, is_leaf=None) -> list:
    """``(path keys, leaf)`` of a pytree, in the tree's own order."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_leaf)[0]
    return [(tuple(getattr(k, "key", getattr(k, "idx", k)) for k in path), leaf)
            for path, leaf in flat]


def _layer_slices(tree: dict):
    """``(leaf name, array, layer or None, path)`` for the program's
    parameter tree: top-level leaves by key, and each segment's
    layer-stacked leaves once per layer (``layer<i>.<path>``, layers
    counted across segments)."""
    offsets, total = [], 0
    for seg in tree["segments"]:
        offsets.append(total)
        leaves = _leaves(seg)
        total += leaves[0][1].shape[0] if leaves else 0
    for path, leaf in _leaves(tree):
        if path[0] != "segments":
            yield ".".join(map(str, path)), leaf, None, path
            continue
        sub = ".".join(map(str, path[2:]))
        for i in range(leaf.shape[0]):
            yield f"layer{offsets[path[1]] + i}.{sub}", leaf, i, path


def _pick(a, layer):
    return a if layer is None else a[layer]


def grad_norms_from_adafactor(state) -> dict:
    """Per-leaf norm of the first gradient as the optimizer got it.

    After one Adafactor step (beta = 0) a factored tensor's row moment is
    ``mean(g^2 + eps)`` over its last axis, so ``sum(g^2) = cols *
    sum(vr)``; an unfactored one holds ``g^2 + eps``. eps is 1e-30.
    """
    import jax
    import jax.numpy as jnp

    moments = dict(_leaves(state["opt"]["v"], is_leaf=_is_moment))
    out = {}
    for name, value, layer, path in _layer_slices(state["values"]):
        mom = moments[path]
        if "vr" in mom:
            out[name] = value.shape[-1] * jnp.sum(_pick(mom["vr"], layer))
        else:
            out[name] = jnp.sum(_pick(mom["v"], layer))
    out = jax.device_get(out)
    return {n: math.sqrt(max(float(s), 0.0)) for n, s in out.items()}


def change_norms(master: dict, ref, dims) -> dict:
    """Per-leaf norm of (master weights - the reference's init)."""
    import jax.numpy as jnp

    slices = {n: (leaf, i) for n, leaf, i, _ in _layer_slices(master)}
    out = {}
    for name, layer, init in ref.init_draws(dims, INIT_SEED):
        key = name if layer is None else f"layer{layer}.{name}"
        out[key] = ref.diff_norm(_pick(*slices[key]), init)
    for key, (leaf, i) in slices.items():
        if key not in out:  # started at zero
            out[key] = ref.diff_norm(_pick(leaf, i), jnp.zeros((), jnp.float32))
    return out


class Driver:
    """The ``on_step`` hook: set-up reads, then the timed window."""

    def __init__(self, cell: Cell, seconds: float, t_start: float, backends: list, *,
                 trace_dir: "Path | None" = None, require_chip: bool = True,
                 window: bool = True):
        self.cell, self.seconds, self.t_start = cell, seconds, t_start
        self.backends = backends      # the store's backends, for their counters
        self.trace_dir, self.require_chip, self.window = trace_dir, require_chip, window
        self.batches: list = []       # every consumed batch (device arrays)
        self.losses: list = []        # device scalars, every step
        self.first_host: list = []    # host copies of the checked steps
        self.check_s = 0.0
        self.grad_norms = self.change = None
        self.stager = None
        self.t_w = self.setup_s = None
        self.done: list = []          # completion times of steps WARMUP+1, ...
        self.counters0 = self.counters1 = None
        self._prev = None
        self._mark = None

    def counters(self) -> dict:
        out = {}
        if self.stager is not None:
            d = self.stager.stats
            out.update(wait_s=d.wait_s, stage_s=d.stage_s)
        if self.backends:
            out.update(chunk_reads=sum(b.stats.chunk_reads for b in self.backends),
                       ranged_reads=sum(b.stats.ranged_reads for b in self.backends))
        return out

    def __call__(self, ev) -> None:
        import jax

        self.batches.append({**{k: ev.batch[k] for k in GRIDS},
                             "returned": np.asarray(ev.batch["returned"]),
                             "step": int(ev.batch["step"])})
        self.losses.append(ev.metrics["loss"])
        self.stager = ev.stager
        if ev.step <= WARMUP:
            jax.block_until_ready(ev.metrics)
            c0 = time.perf_counter()
            if ev.step == 1 and self.require_chip and (
                    ev.stager is None or ev.stager.interpret):
                raise RuntimeError("the device gather resolved to interpret mode")
            if ev.step <= CHECKED_STEPS:
                self.first_host.append(
                    {k: np.asarray(ev.batch[k]) for k in GRIDS})
                state = _trainer_state()
                if ev.step == 1:
                    self.grad_norms = grad_norms_from_adafactor(state)
                if ev.step == CHECKED_STEPS:
                    ref = reference_kind(self.cell)
                    self.change = change_norms(state["opt"]["master"], ref,
                                               ref.Dims.from_config(self.cell.config))
                del state
            self.check_s += time.perf_counter() - c0
            if ev.step == WARMUP:
                if not self.window:
                    raise WindowClosed
                if self.trace_dir is not None:
                    jax.profiler.start_trace(str(self.trace_dir),
                                             profiler_options=_profile_options())
                self.counters0 = self.counters()
                self.lowered0 = LOWERED[0]
                self.t_w = time.perf_counter()
                self.setup_s = self.t_w - self.t_start - self.check_s
                if self.trace_dir is not None:
                    self._mark = jax.profiler.TraceAnnotation(tracefile.WINDOW_MARK)
                    self._mark.__enter__()
            return
        if ev.step == WARMUP + 1:
            # step WARMUP was waited for before the window opened
            self._prev = ev.metrics
            return
        jax.block_until_ready(self._prev)
        t = time.perf_counter()
        if t - self.t_w > self.seconds:
            self.counters1 = self.counters()
            self.lowered = LOWERED[0] - self.lowered0
            if self._mark is not None:
                self._mark.__exit__(None, None, None)
                jax.profiler.stop_trace()
            raise WindowClosed
        self.done.append(t)
        self._prev = ev.metrics


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


# ----------------------------------------------------------------- checks
def host_grids(argv: list, workdir: Path, wanted: list) -> list:
    """The host loader's ``epoch_async`` batches at (epoch, step) pairs."""
    from repro.core import ChunkStore, RedoxLoader
    from repro.launch import train

    spec = train.session_spec(train.build_parser().parse_args(argv))
    store = ChunkStore.open(workdir / "chunks")
    out = {}
    try:
        loader = RedoxLoader.from_spec(spec, store)
        want, last = set(wanted), max(wanted)
        for epoch in range(last[0] + 1):
            it = loader.epoch_async(epoch)
            try:
                for batch in it:
                    key = (epoch, int(batch["step"]))
                    if key in want:
                        out[key] = {k: np.asarray(batch[k]) for k in (*GRIDS, "returned")}
                    if key >= last:
                        break
            finally:
                it.close()
    finally:
        store.close()
    return [out.get(k) for k in wanted]


def check_data(cell: Cell, seed: int, argv: list, workdir: Path,
               batches: list) -> dict:
    """Rows of every consumed batch that differ from the host loader's
    grids and from the corpus, and documents served twice in an epoch."""
    import jax

    seq_len, vocab = cell.flag("--seq-len"), int(cell.config["vocab_size"])
    got = jax.device_get([{k: b[k] for k in GRIDS} for b in batches])
    keys, epoch, prev = [], 0, -1
    for b in batches:
        if b["step"] <= prev:
            epoch += 1
        prev = b["step"]
        keys.append((epoch, b["step"]))
    host = host_grids(argv, workdir, keys)
    corpus = ref_data.Corpus(cell.flag("--num-docs"), vocab, seq_len // 2, seed + 5)
    vs_host = vs_corpus = repeated = 0
    seen: dict = {}
    for (ep, _), b, g, h in zip(keys, batches, got, host):
        rows = g["tokens"].shape[0]
        for r in range(rows):
            doc = int(b["returned"][r])
            row = [np.asarray(g[k][r]) for k in GRIDS]
            if h is None or any(not np.array_equal(x, h[k][r]) for x, k in zip(row, GRIDS)):
                vs_host += 1
            want = ref_data.expected_row(corpus.tokens(doc), seq_len)
            if any(not np.array_equal(x, y) for x, y in zip(row, want)):
                vs_corpus += 1
            if doc in seen.setdefault(ep, set()):
                repeated += 1
            seen[ep].add(doc)
    return {"rows_vs_host_loader": vs_host, "rows_vs_corpus": vs_corpus,
            "repeated_docs": repeated}


def training_gaps(prog: dict, ref: dict) -> dict:
    """The numbers that compare the program's first steps with the
    reference's: relative loss gap of the first step and the largest over
    the steps, and by the worst leaf the gap between the program's norm and
    the reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger, of the first gradient and of the
    parameters' change. Leaves whose reference gradient is under a
    thousandth of the median leaf's move by round-off alone and are left
    out of the change."""
    g_ref, c_ref = ref["grad_norms"], ref["change_norms"]
    g_med, c_med = statistics.median(g_ref.values()), statistics.median(c_ref.values())
    moved = [n for n in c_ref if g_ref[n] >= 1e-3 * g_med]
    return {
        "first_loss_gap": abs(prog["losses"][0] - ref["losses"][0]) / abs(ref["losses"][0]),
        "loss_gap": _worst(abs(p - r) / abs(r)
                           for p, r in zip(prog["losses"], ref["losses"])),
        "grad_norm_gap": _worst(abs(prog["grad_norms"][n] - g) / max(g, g_med)
                                for n, g in g_ref.items()),
        "change_norm_gap": _worst(abs(prog["change_norms"][n] - c_ref[n])
                                  / max(c_ref[n], c_med) for n in moved),
    }


def _worst(values) -> float:
    """The largest value; NaN if any is NaN (``max`` may skip a NaN)."""
    values = list(values)
    return math.nan if any(math.isnan(v) for v in values) else max(values)


def reference_readings(cell: Cell, batches: list, **kw) -> dict:
    ref = reference_kind(cell)
    seq_len = cell.flag("--seq-len")
    rows = max(1, REF_POSITIONS // seq_len)
    return ref.train_readings(ref.Dims.from_config(cell.config), INIT_SEED,
                              batches, rows=rows, **kw)


# ------------------------------------------------------------------- runs
@dataclasses.dataclass
class RunRecord:
    """What the per-layer metric readers in ``bench/metrics/`` read."""
    steps: int
    window_s: float
    counters: dict
    trace: "dict | None"
    flops_per_step: float
    gather_bytes: int
    peak: dict


def drive(cell: Cell, seed: int, seconds: float, t_start: float, workdir: Path, *,
          trace_dir=None, require_chip=True, window=True) -> "tuple[Driver, list]":
    """Run the trainer on the cell until the window closes (or, with
    ``window=False``, until set-up ends)."""
    import jax

    from repro.launch import train

    if not _LISTENING:
        jax.monitoring.register_event_duration_secs_listener(_count_lowering)
        _LISTENING.append(True)
    backend, made = register(cell)
    argv = train_argv(cell, seed, workdir, backend)
    drv = Driver(cell, seconds, t_start, made, trace_dir=trace_dir,
                 require_chip=require_chip, window=window)
    try:
        rc = train.main(argv, on_step=drv)
        raise RuntimeError(f"launch.train returned {rc} before the window closed")
    except WindowClosed:
        pass
    gc.collect()
    return drv, argv


def check_device(cell: Cell):
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"JAX found no TPU (first device is {dev.platform!r})")
    if len(devices) < cell.chips:
        raise NoChip(f"the cell needs {cell.chips} chips, JAX found {len(devices)}")
    return devices


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float, *,
        require_chip: bool = True, log=print) -> dict:
    """One run of the cell; returns the result line's object."""
    import jax

    devices = check_device(cell) if require_chip else jax.devices()
    dev = devices[0]
    log(f"device: {dev.device_kind} x{len(devices)} ({dev.platform})")
    peak = peaks.peak(dev.device_kind) if require_chip else None
    batch, seq_len = cell.flag("--batch"), cell.flag("--seq-len")
    log(f"corpus: {cell.flag('--num-docs')} documents, lengths geometric with mean "
        f"{seq_len // 2} plus {ref_data.MIN_LEN}, vocabulary {cell.config['vocab_size']}")
    workdir = Path(tempfile.mkdtemp(prefix="bench_"))
    trace_dir = Path(tempfile.mkdtemp(prefix="bench_trace_")) if trace else None
    try:
        drv, argv = drive(cell, seed, seconds, t_start, workdir,
                          trace_dir=trace_dir, require_chip=require_chip)
        mem_peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
        n = len(drv.done)
        window_s = drv.done[-1] - drv.t_w if n else 0.0
        losses = jax.device_get(drv.losses[WARMUP:WARMUP + n])
        failed = sum(not math.isfinite(float(x)) for x in losses)
        tokens = float(sum(jax.device_get(
            [b["loss_mask"].sum() for b in drv.batches[WARMUP:WARMUP + n]])))
        intervals = np.diff([drv.t_w] + drv.done) * 1e3
        log(f"window: {n} steps in {window_s!r} s, {tokens!r} loss-bearing tokens, "
            f"{drv.lowered} programs lowered; setup {drv.setup_s!r} s ({drv.check_s!r} s "
            f"of check reads in set-up left out); peak_bytes_in_use {mem_peak}")
        if n:
            log(f"step interval ms: median {float(np.median(intervals))!r}, "
                f"p90 {p90(intervals)!r}, max {float(intervals.max())!r}, over {n}")
        prog = {"losses": [float(x) for x in jax.device_get(drv.losses[:CHECKED_STEPS])],
                "grad_norms": drv.grad_norms, "change_norms": drv.change}
        record = None
        if trace:
            summary = tracefile.summarize(tracefile.load(_xplane(trace_dir)))
            counters = {k: drv.counters1[k] - drv.counters0[k] for k in drv.counters0}
            per_step = flop_counts.train_flops_per_position(cell.config, seq_len)
            record = RunRecord(n, window_s, counters, summary,
                               per_step * batch * seq_len,
                               flop_counts.gather_bytes(batch, seq_len), peak)
            log(f"counters over the window: {counters}")
        setup_s, lowered = drv.setup_s, drv.lowered
        batches, first = drv.batches, drv.first_host
        del drv
        gc.collect()
        t_check = time.perf_counter()
        checks = check_data(cell, seed, argv, workdir, batches)
        ref = reference_readings(cell, first)
        checks.update(training_gaps(prog, ref))
        log(f"program losses {prog['losses']!r}; reference {ref['losses']!r}; "
            f"checks took {time.perf_counter() - t_check!r} s")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    # A number with no upper reading has no limit and is not compared.
    log(f"not compared: {({k: v for k, v in checks.items() if k not in cell.limits})!r}")
    verdict = {k: {"value": v, "limit": cell.limits[k]} for k, v in checks.items()
               if k in cell.limits}
    verdict["programs_lowered_in_window"] = {"value": lowered, "limit": 0}
    correct = (n > 0 and failed == 0
               and all(v["value"] <= v["limit"] for v in verdict.values()))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    metrics = {}
    if trace:
        for m in cell.per_layer:
            value = importlib.import_module(f"bench.metrics.{m['name']}").read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=record.trace.get("busy_s", 0.0),
                      window_s=record.trace.get("window_s", 0.0))
    else:
        values = {"tokens_per_s": tokens / window_s if window_s else 0.0,
                  "step_ms_p90": p90(intervals) if n else 0.0,
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    out = {"correct": correct, "attempted": n, "failed": failed,
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": record.trace.get("device_ops", []),
                            "idle_gaps": record.trace.get("idle_gaps", [])}
    out["checks"] = verdict
    return out


def p90(values) -> float:
    """90th percentile, interpolated between the closest ranks."""
    values = list(values)
    if len(values) < 2:
        return float(max(values))
    return float(statistics.quantiles(values, n=10, method="inclusive")[-1])


def _xplane(trace_dir: Path) -> Path:
    found = sorted(trace_dir.glob("**/*.xplane.pb"))
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    return found[-1]
