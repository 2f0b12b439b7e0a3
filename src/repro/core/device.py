"""DeviceStager: the host→device half of the data plane (DESIGN.md §12).

Everything before this module ends at host memory: the protocol batches
chunks, the loader assembles (B, S) grids, and the train loop pays a
synchronous ``jnp.asarray`` per step — decode, grid assembly, and the
host→device copy all sit on the critical path. The stager moves that
whole tail off it:

* a dedicated **staging thread** drives the host batch pipeline (protocol
  walk stays on the loader's own worker thread), so decode/pack and the
  ``jax.device_put`` transfer run while the consumer's previous train
  step computes;
* batches are **double-buffered** (``depth`` staged batches in flight):
  stage(step k+1) overlaps train_step(k), the same pipeline the paper's
  clients use to hide server latency, applied to the PCIe/ICI hop;
* with ``use_kernel=True`` the host ships one lane-padded int32 *slot
  buffer* plus the scalar redirection/length tables instead of three
  pre-assembled grids (~1/3 of the H2D bytes), and the
  :func:`~repro.kernels.chunk_gather.ops.chunk_gather_train` Pallas pass
  assembles tokens/targets/loss-mask on-device — the paper's redirection
  table as a scalar-prefetch gather.

On TPU the slot buffer lands in HBM as one contiguous transfer from
pinned host memory and the gather happens in the BlockSpec index_map DMA;
on CPU/interpret backends ``device_put`` degrades to a memcpy on the
staging thread, which still buys the overlap (NumPy and XLA release the
GIL). Buffer lifetime: staged-but-unconsumed device buffers are tracked
and explicitly released on teardown — including abandoned-consumer
shutdown — so a ``break`` mid-epoch never strands device memory; consumed
batches are donated to the train step's ``donate_argnums`` and die with
it.

Per-step accounting lands in :class:`~repro.core.stats.StepIO`
(``stage_s`` / ``stage_wait_s``) and the stream-level aggregate in
:class:`~repro.core.stats.DeviceStats` (``overlap_fraction``).
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time

import jax
import numpy as np

from repro.obs import tracer as trace

from ..kernels.chunk_gather.ops import chunk_gather_train
from ..kernels.common import resolve_interpret, round_up
from .stats import DeviceStats

__all__ = ["DeviceStager", "HostPack", "pack_records"]

_GRID_KEYS = ("tokens", "targets", "loss_mask")


class HostPack(dict):
    """Host-side staging payload for the on-device gather: one slot-padded
    token buffer (``slot_tokens``), the clipped record lengths and the
    redirection index table, plus the batch metadata that rides along
    (``step`` / ``io_by_node`` / ``returned`` / ``seq_len`` / ``pad_id``)."""


def pack_records(
    records: "list[np.ndarray]",
    returned: "np.ndarray | None",
    *,
    seq_len: int,
    pad_id: int = 0,
    row_pad: int = 8,
) -> tuple:
    """Pack decoded records into (slot_tokens, lens, idx) for the gather.

    Rows redirected to the same record share one slot (``returned`` file
    ids key the dedup — exactly-once makes them distinct within an epoch,
    but the pack stays correct for any index pattern). ``slot_tokens`` is
    ``(num_slots, 1, Lp)``: the unit middle axis is the layout the
    compiled gather tiles (``kernels/chunk_gather``). Slot rows are padded
    to a multiple of ``row_pad`` columns (128 on real TPUs — the lane width
    the kernel DMAs in; small on interpret backends).
    """
    n_rows = len(records)
    if returned is not None and len(returned) == n_rows:
        uniq, first, inv = np.unique(
            np.asarray(returned), return_index=True, return_inverse=True
        )
    else:
        first = np.arange(n_rows)
        inv = np.arange(n_rows)
    full = seq_len + 1
    lp = round_up(full, row_pad)
    slot_tokens = np.full((len(first), 1, lp), pad_id, dtype=np.int32)
    lens = np.zeros(len(first), dtype=np.int32)
    for s, r in enumerate(first):
        rec = records[int(r)]
        n = min(rec.shape[0], full)
        slot_tokens[s, 0, :n] = rec[:n]
        lens[s] = n
    return slot_tokens, lens, inv.astype(np.int32)


class DeviceStager:
    """Double-buffered host→device staging with optional on-device gather.

    ``use_kernel=None`` (auto) enables the Pallas assembly whenever the
    input stream carries :class:`HostPack` items (the
    ``RedoxLoader.epoch_device`` path) and falls back to plain grid
    staging for pre-assembled batches (the ``RedoxClient`` ring path,
    whose frames ship grids). ``interpret`` follows the kernel convention
    (``None`` -> compiled on TPU, interpreted elsewhere).
    """

    def __init__(
        self,
        *,
        device=None,
        use_kernel: "bool | None" = None,
        interpret: "bool | None" = None,
        depth: int = 2,
    ):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self.device = device if device is not None else jax.devices()[0]
        self.use_kernel = use_kernel
        self.interpret = resolve_interpret(interpret)
        self.depth = depth
        self.stats = DeviceStats()
        self._inflight: list = []
        self._lock = threading.Lock()
        self._thread: "threading.Thread | None" = None
        self._streaming = False

    @property
    def row_pad(self) -> int:
        """Slot-row column padding the packer must honour: the (8, 128)
        lane width when the gather compiles, a token-level 8 otherwise."""
        return 8 if self.interpret else 128

    @property
    def live_buffers(self) -> int:
        """Staged-but-unconsumed device batches currently held."""
        with self._lock:
            return len(self._inflight)

    # ---------------------------------------------------------------- stage
    def stage(self, item: dict) -> dict:
        """Ship one host batch/pack to the device; returns the device batch.

        Dispatches asynchronously where the backend allows: the returned
        arrays are futures, forced only when the consumer's train step
        reads them.
        """
        is_pack = "slot_tokens" in item
        kernel = is_pack and self.use_kernel is not False
        if is_pack and not kernel:
            raise ValueError(
                "DeviceStager(use_kernel=False) cannot stage HostPacks; "
                "feed it assembled batches (epoch_async) instead"
            )
        keys = ("slot_tokens", "lens", "idx") if kernel else _GRID_KEYS
        moved = sum(np.asarray(item[k]).nbytes for k in keys)
        with trace.span("stager.stage", "stage", step=int(item.get("step", -1)),
                        bytes=int(moved), kernel=kernel):
            t0 = time.perf_counter()
            if kernel:
                slot = jax.device_put(item["slot_tokens"], self.device)
                lens = jax.device_put(item["lens"], self.device)
                idx = jax.device_put(item["idx"], self.device)
                tokens, targets, loss_mask = chunk_gather_train(
                    slot, lens, idx,
                    seq_len=int(item["seq_len"]),
                    pad_id=int(item["pad_id"]),
                    interpret=self.interpret,
                )
                self.stats.kernel_steps += 1
            else:
                tokens = jax.device_put(item["tokens"], self.device)
                targets = jax.device_put(item["targets"], self.device)
                loss_mask = jax.device_put(item["loss_mask"], self.device)
            stage_s = time.perf_counter() - t0
        # Copy the StepIO entries before annotating: replay-engine batches
        # share them with the EpochPlan, which must stay reusable.
        io = {
            n: dataclasses.replace(s, stage_s=0.0, stage_wait_s=0.0)
            for n, s in item.get("io_by_node", {}).items()
        }
        if io:
            io[min(io)].stage_s = stage_s
        out = dict(item)
        for k in ("slot_tokens", "lens", "idx", "seq_len", "pad_id"):
            out.pop(k, None)
        out.update(
            tokens=tokens, targets=targets, loss_mask=loss_mask,
            io_by_node=io, stage_s=stage_s, stage_wait_s=0.0,
        )
        self.stats.steps += 1
        self.stats.bytes_to_device += int(moved)
        self.stats.stage_s += stage_s
        return out

    # --------------------------------------------------------------- stream
    def stream(self, batches):
        """Yield device-resident batches for a host batch/pack iterator.

        The staging thread drives ``batches`` (so a generator's own
        pipeline — e.g. the loader's protocol worker — runs ahead too),
        stages each item, and feeds a bounded queue of ``depth`` device
        batches. Abandoning this generator tears everything down
        deterministically: the staging thread is signalled and joined, the
        inner iterator is closed *from the staging thread* (its
        ``finally`` runs immediately, not at GC time), and every staged
        batch the consumer never saw has its device buffers released.
        """
        if self._streaming:
            raise RuntimeError("DeviceStager.stream is one-at-a-time; "
                               "create one stager per concurrent stream")
        self._streaming = True
        q: queue.Queue = queue.Queue(maxsize=self.depth)
        end = object()
        stop = threading.Event()
        failure: list[BaseException] = []

        def put(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.05)
                    return True
                except queue.Full:
                    continue
            return False

        def worker():
            it = iter(batches)
            try:
                for item in it:
                    staged = self.stage(item)
                    with self._lock:
                        self._inflight.append(staged)
                    if not put(staged):
                        return
            except BaseException as e:
                failure.append(e)
            finally:
                # Close the inner generator from the thread that iterated
                # it — legal (it is suspended) and deterministic.
                close = getattr(it, "close", None)
                if close is not None:
                    try:
                        close()
                    except BaseException as e:
                        failure.append(e)
                put(end)

        t = threading.Thread(target=worker, daemon=True)
        self._thread = t
        t.start()
        try:
            while True:
                # The slice of staging the double buffer failed to hide.
                with trace.span("stager.wait", "stage") as span:
                    t0 = time.perf_counter()
                    item = q.get()
                    wait = time.perf_counter() - t0
                    if item is not end:
                        span.set(step=int(item.get("step", -1)))
                if item is end:
                    break
                with self._lock:
                    self._inflight.remove(item)
                self.stats.wait_s += wait
                item["stage_wait_s"] = wait
                io = item["io_by_node"]
                if io:
                    io[min(io)].stage_wait_s = wait
                yield item
            if failure:
                raise failure[0]
        finally:
            stop.set()
            t.join()
            self._release_inflight()
            self._streaming = False

    # ------------------------------------------------------------- teardown
    def _release_inflight(self) -> None:
        with self._lock:
            stranded, self._inflight = self._inflight, []
        for batch in stranded:
            for k in _GRID_KEYS:
                arr = batch.get(k)
                if hasattr(arr, "delete"):
                    try:
                        arr.delete()
                    except RuntimeError:
                        pass  # already donated/freed
            self.stats.buffers_released += 1

    def close(self) -> None:
        """Release any staged-but-unconsumed device buffers (idempotent).

        ``stream``'s own ``finally`` already does this on abandonment;
        ``close`` exists for explicit lifecycle management and for
        symmetry with the loader/client teardown paths."""
        if self._thread is not None and self._thread.is_alive():
            raise RuntimeError("close() while a stream is active; abandon "
                               "or exhaust the stream generator first")
        self._release_inflight()
