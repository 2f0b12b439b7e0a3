"""Training launcher: ``--arch <id>`` selects any assigned architecture.

By default the model runs at the reduced (same-family) size, which is what
CPU runs use. ``--full`` takes the published widths; one TPU v5e chip
(16 GB) fits TinyLlama-1.1B at full width with ``--full --optimizer
adafactor --remat full --batch 4 --seq-len 2048`` (the default ``adamw``
keeps fp32 m and v beside the fp32 master and does not fit). Data always
flows through the real Redox chunk store + redirection protocol.
Checkpoints/restart and the async loader are on by default. Where the
persistent compilation cache lives is ``launch/compile_cache.py``'s call.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b --steps 50

With ``--data-server SOCKET`` the trainer owns no data plane at all: it
opens a session on a running ``repro.launch.data_service --serve`` process
and consumes batches from the shared-memory ring (DESIGN.md §11).
"""

from __future__ import annotations

import argparse
import dataclasses
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from ..checkpoint.ckpt import AsyncCheckpointer, latest_step, restore_checkpoint
from ..configs import RunConfig, get_config, list_archs, reduced
from ..core import ChunkStore, RedoxLoader, SessionSpec
from ..data import SyntheticTokenDataset
from ..core.stats import PipelineTimeModel, StepIO
from ..models import build_model
from ..obs import (
    MetricsRegistry,
    attribution,
    format_report,
    model_columns,
    programs,
    trace,
)
from ..optim.optimizers import make_optimizer
from ..service.transport import RedoxClient
from ..train.train_step import build_train_step, init_train_state
from .compile_cache import enable_compile_cache
from .cli import (
    add_autotune_args,
    add_data_plane_args,
    add_device_args,
    add_elastic_args,
    add_obs_args,
    resolve_resume_dir,
)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--nodes", type=int, default=2)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--remat", default="dots")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--full", action="store_true", help="full-size config (real HW)")
    add_data_plane_args(ap, batch=8, seq_len=128, num_docs=1024)
    add_device_args(ap)
    add_elastic_args(ap)
    add_autotune_args(ap)
    add_obs_args(ap)
    ap.add_argument("--data-server", metavar="SOCKET", default=None,
                    help="consume batches from a repro.launch.data_service "
                         "--serve process at this unix socket instead of "
                         "building a local data plane")
    ap.add_argument("--job-id", default="train0",
                    help="session id on the data server (--data-server only)")
    return ap


#: Nominal NAS storage/network profile for the DESIGN §6 model columns
#: printed next to the measured attribution under ``--trace`` (same shape
#: as the benchmarks/calibration.py entries; this box's synthetic store is
#: page-cached, so the model shows what the run's I/O demand would cost on
#: the paper's target storage, not what it cost here).
TRACE_TIME_MODEL = PipelineTimeModel(
    disk_bw=200e6, file_overhead=8e-3, chunk_overhead=8e-3,
    net_bw=1e9, net_latency=2e-4,
)


@dataclasses.dataclass(frozen=True)
class StepEvent:
    """What ``main``'s ``on_step`` hook is handed after each train step.

    ``metrics`` hold device scalars: reading one waits for the step.
    ``started`` is ``time.perf_counter()`` just before the step was
    dispatched, so the first event's wait includes trace and compile.
    """

    step: int        # steps taken so far (1-based)
    batch: dict      # the batch the step consumed
    metrics: dict
    started: float
    stager: object   # the DeviceStager, or None on the naive path


def session_spec(args) -> SessionSpec:
    """The data-plane session the trainer opens for parsed ``args``."""
    # Seeds derive from --seed exactly as in data_service.py: protocol
    # +2, sampler +3, dataset +5 (the historical constants at seed 0).
    return SessionSpec(
        policy=args.policy,
        seed=args.seed + 2,
        sampler_seed=args.seed + 3,
        num_nodes=args.nodes,
        batch_per_node=max(args.batch // args.nodes, 1),
        seq_len=args.seq_len,
        engine=args.engine,
        remote_memory_limit_bytes=1_000_000,
        fidelity=args.fidelity,
    )


def _local_metrics(loader, store, stager) -> MetricsRegistry:
    """Registry over a local data plane's live stats objects."""
    reg = MetricsRegistry()
    if store is not None:
        reg.register_stats("backend", lambda: store.backend_stats)
    if stager is not None:
        reg.register_stats("device", lambda: stager.stats)
    cluster = getattr(loader, "cluster", None)
    if cluster is not None:
        for r, node in enumerate(cluster.nodes):
            reg.register_stats(
                "node", lambda n=node: n.stats, labels={"node": str(r)}
            )
    last_plan = getattr(loader, "last_plan", None)
    if last_plan is not None:
        reg.register_stats("planner", lambda: last_plan.stats)
    return reg


def main(argv=None, *, on_step=None) -> int:
    """Run the trainer on ``argv`` (``sys.argv[1:]`` when None).

    ``on_step``, if given, is called with a :class:`StepEvent` after every
    step; callers that drive the trainer in-process (``chip_smoke.py``)
    check losses and batches through it.
    """
    ap = build_parser()
    args = ap.parse_args(argv)
    if args.data_server is not None and args.resume_data is not None:
        ap.error("--resume-data belongs to the server with --data-server "
                 "(run data_service --resume-data there)")
    if args.data_server is not None and args.suspend_after is not None:
        ap.error("--suspend-after belongs to the server with --data-server")
    if args.suspend_after is not None and args.resume_data is None:
        ap.error("--suspend-after requires --resume-data")
    if args.data_server is not None and args.device_path == "gather":
        ap.error("--device-path gather requires a local data plane (ring "
                 "frames ship assembled grids); use --device-path stage")

    enable_compile_cache()
    tracer = trace.enable(args.trace_capacity) if args.trace else None

    cfg = get_config(args.arch)
    if not args.full:
        cfg = reduced(cfg)
    model = build_model(cfg)
    run = RunConfig(optimizer=args.optimizer, remat=args.remat)
    opt = make_optimizer(run)
    state = init_train_state(model, opt, 0)
    step_fn = jax.jit(build_train_step(model, run, opt), donate_argnums=0)
    print(f"arch={args.arch} family={cfg.family} params={cfg.param_count():,d}")

    workdir = Path(args.workdir or tempfile.mkdtemp(prefix=f"redox_{args.arch}_"))
    spec = session_spec(args)
    data_dir = resolve_resume_dir(ap, args.resume_data, workdir / "ckpt" / "data")
    store = None
    if args.data_server is not None:
        loader = RedoxClient(args.data_server, spec, job_id=args.job_id)
        print(f"data plane: {args.data_server} (job {args.job_id})")
    else:
        ds = SyntheticTokenDataset(args.num_docs, args.vocab_size or cfg.vocab_size,
                                   mean_len=args.seq_len // 2, seed=args.seed + 5)
        store = ds.build_store(workdir / "chunks", chunk_size=16,
                               memory_bytes=int(ds.sizes_bytes.sum() // 4),
                               seed=args.seed + 1,
                               codec=args.codec, bands=args.bands)
        if args.backend is not None:
            store.close()
            store = ChunkStore.open(workdir / "chunks", backend=args.backend)
        elif args.autotune:
            # Calibrate the freshly built store and reopen it with the
            # model-selected backend + readahead (DESIGN.md §14). An
            # explicit --backend wins over the autotuner (branch above).
            from .. import autotune
            from ..core.storage import make_backend

            steps_hint = max(args.num_docs // max(args.batch, 1), 1)
            _, choice = autotune.tune_store(
                workdir / "chunks",
                compute_per_step_s=args.compute_per_step,
                num_steps=steps_hint,
                memory_limit_bytes=(
                    int(args.autotune_memory_mb * 1e6)
                    if args.autotune_memory_mb is not None else None
                ),
            )
            print(f"autotune: {choice.describe()}")
            store.close()
            kwargs = {"readahead": choice.readahead} if choice.readahead else {}
            store = ChunkStore.open(
                workdir / "chunks",
                backend=make_backend(choice.backend, **kwargs),
            )
            # The §6 model's fidelity call on a progressive store — an
            # explicit --fidelity wins (it's already in the spec).
            if args.fidelity is None and choice.fidelity is not None:
                spec = dataclasses.replace(spec, fidelity=choice.fidelity)
        if data_dir is not None and (data_dir / "loader_manifest.json").exists():
            loader = RedoxLoader.resume(data_dir, store)
            print(f"data plane resumed at epoch {loader.resume_point[0]} "
                  f"step {loader.resume_point[1]}")
        else:
            loader = RedoxLoader.from_spec(spec, store)
    stager = None
    if args.device_path != "naive":
        from ..core.device import DeviceStager  # deferred: jax-heavy

        stager = DeviceStager(depth=args.stage_depth,
                              use_kernel=(args.device_path == "gather"))
        mode = f"device path: {args.device_path} (depth {args.stage_depth}"
        if args.device_path == "gather":
            mode += f", {'interpret' if stager.interpret else 'compiled'} gather"
        print(mode + ")")

    def epoch_batches(epoch):
        if args.device_path == "gather":
            return loader.epoch_device(epoch, stager)
        if args.device_path == "stage":
            return stager.stream(loader.epoch_async(epoch))
        return loader.epoch_async(epoch)

    ckpt = AsyncCheckpointer(workdir / "ckpt")
    start = latest_step(workdir / "ckpt")
    if start:
        state = restore_checkpoint(workdir / "ckpt", start, state)
        print(f"resumed from step {start}")
    # Commit the state to the device the step's outputs land on: an
    # uncommitted first state keys a second compile of the step at step 2.
    state = jax.device_put(state, jax.devices()[0])

    if cfg.frontend != "none":
        print("note: stub-frontend arch — launcher trains on token records "
              "projected through the frontend stub (see launch/specs.py)")

    step = int(start or 0)
    run_steps = 0
    # Per-node StepIO grid for the §6 model columns (--trace only). NB:
    # a Tracer is sized by its event count — test identity, not truth.
    io_grid = [[] for _ in range(spec.num_nodes)] if tracer is not None else None
    suspended = False
    epoch, t0 = (loader.resume_point or (0, 0))[0], time.time()
    while step < args.steps and not suspended:
        for batch in epoch_batches(epoch):
            if step >= args.steps:
                break
            feed = {
                "tokens": jnp.asarray(batch["tokens"]),
                "targets": jnp.asarray(batch["targets"]),
                "loss_mask": jnp.asarray(batch["loss_mask"]),
            }
            if cfg.frontend == "frame":
                # stub frontend: embed tokens as one-hot-ish frames
                b, s = feed["tokens"].shape
                feed["frames"] = jax.nn.one_hot(
                    feed["tokens"] % cfg.frontend_dim, cfg.frontend_dim,
                    dtype=jnp.dtype(cfg.compute_dtype),
                )
                del feed["tokens"]
            elif cfg.frontend == "patch":
                b = feed["tokens"].shape[0]
                p = cfg.frontend_len
                feed["patch_embeds"] = jnp.zeros(
                    (b, p, cfg.frontend_dim), jnp.dtype(cfg.compute_dtype)
                )
                feed["targets"] = jnp.concatenate(
                    [jnp.zeros((b, p), jnp.int32), feed["targets"]], axis=1
                )
                feed["loss_mask"] = jnp.concatenate(
                    [jnp.zeros((b, p), jnp.float32), feed["loss_mask"]], axis=1
                )
            if run_steps == 0:
                # for tools that map a profile's device ops to name scopes
                programs.note("train_step", step_fn, state, feed)
            started = time.perf_counter()
            # The step's dispatch only: the device runs it asynchronously,
            # so tracing adds no sync. The device's own time is the
            # profiler's (``jit_train_step`` on its ops line).
            with trace.span("train.step", "compute", step=step):
                state, metrics = step_fn(state, feed)
            if io_grid is not None:
                by_node = batch.get("io_by_node") or {}
                for r in range(spec.num_nodes):
                    io_grid[r].append(by_node.get(r, StepIO()))
            step += 1
            run_steps += 1
            if on_step is not None:
                on_step(StepEvent(step, batch, metrics, started, stager))
            if step % 10 == 0 or step == 1:
                # Reading the loss waits for the step to finish on the device.
                with trace.span("train.loss_sync", "compute", step=step):
                    loss = float(metrics["loss"])
                print(f"step {step:4d} loss {loss:.4f} "
                      f"({(time.time()-t0)/step:.2f}s/step)")
            if step % args.ckpt_every == 0:
                ckpt.save(step, state)
                if data_dir is not None:
                    # Replay-engine suspend is derived (shadow simulation),
                    # so the stream keeps flowing while this writes.
                    loader.suspend(data_dir)
            if args.suspend_after is not None and run_steps >= args.suspend_after:
                ckpt.save(step, state)
                loader.suspend(data_dir)
                suspended = True
                break
        epoch += 1
    ckpt.wait()
    elapsed = time.time() - t0
    if stager is not None:
        stager.close()
        d = stager.stats
        print(f"device path {args.device_path}: staged {d.steps} batches "
              f"({d.bytes_to_device / 1e6:.1f} MB to device), "
              f"overlap fraction {d.overlap_fraction:.2f}")
    if run_steps:
        toks = run_steps * spec.num_nodes * spec.batch_per_node * spec.seq_len
        print(f"throughput: {toks / max(elapsed, 1e-9):,.0f} tokens/sec "
              f"over {run_steps} step(s)")
    if args.metrics:
        if args.data_server is not None:
            print(loader.metrics()["text"], end="")  # server-side registry
        else:
            print(_local_metrics(loader, store, stager).exposition(), end="")
    if tracer is not None:
        out = tracer.dump(args.trace)
        print(f"trace: {len(tracer)} events ({tracer.dropped} dropped) -> "
              f"{out}; open in the Perfetto UI or chrome://tracing")
        att = attribution(tracer.events(), wall_s=elapsed)
        model = None
        if run_steps and any(io_grid):
            model = model_columns(
                io_grid, TRACE_TIME_MODEL,
                att["busy_s"].get("compute", 0.0) / run_steps,
            )
        print(format_report(att, model=model, measured_wall_s=elapsed))
        trace.disable()
    if args.data_server is not None:
        loader.close()
    if store is not None:
        store.close()
    if suspended:
        print(f"suspended after {run_steps} step(s) -> {data_dir}; "
              f"rerun with the same flags to continue")
    else:
        print(f"done: {step} steps in {elapsed:.0f}s; workdir={workdir}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
