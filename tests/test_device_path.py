"""DeviceStager: packing, identity vs the host epoch, stats, teardown.

The contract under test (DESIGN.md §12): the device path is a pure
transport — ``epoch_device`` / ``stream`` must yield byte-identical
tokens/targets/loss_mask to the host epoch, annotate (not corrupt) the
per-step IO accounting, and never strand device buffers, whatever the
consumer does.
"""

import numpy as np
import pytest

from repro.core import Cluster, EpochSampler, RedoxLoader, SessionSpec
from repro.core.device import DeviceStager, HostPack, pack_records
from repro.data import SyntheticTokenDataset

pytestmark = pytest.mark.device


def build_loader(tmp_path, *, nodes=1, batch_per_node=8, seq_len=32, **kw):
    ds = SyntheticTokenDataset(96, vocab_size=97, mean_len=48, seed=3)
    store = ds.build_store(tmp_path / "chunks", 4, num_slots=16, seed=1)
    cluster = Cluster(store.plan, nodes, store=store, seed=2)
    sampler = EpochSampler(96, nodes, seed=4)
    return store, RedoxLoader(
        cluster, sampler, batch_per_node=batch_per_node, seq_len=seq_len, **kw
    )


def grids(b):
    return tuple(np.asarray(b[k]) for k in ("tokens", "targets", "loss_mask"))


class TestPackRecords:
    def test_dedup_and_padding(self):
        recs = [np.arange(5, dtype=np.int32), np.arange(9, dtype=np.int32),
                np.arange(5, dtype=np.int32)]
        returned = np.asarray([40, 7, 40])  # rows 0 and 2 share a file
        slots, lens, idx = pack_records(recs, returned, seq_len=16, row_pad=8)
        assert slots.shape == (2, 1, 24)  # 2 unique files, 17 -> pad to 24
        assert slots.dtype == np.int32 and idx.dtype == np.int32
        # np.unique sorts by file id: slot 0 = file 7, slot 1 = file 40
        np.testing.assert_array_equal(idx, [1, 0, 1])
        assert lens[0] == 9 and lens[1] == 5
        np.testing.assert_array_equal(slots[1, 0, :5], np.arange(5))
        assert (slots[1, 0, 5:] == 0).all()

    def test_length_clip_to_seq_plus_one(self):
        recs = [np.arange(100, dtype=np.int32)]
        slots, lens, idx = pack_records(recs, None, seq_len=16, row_pad=8)
        assert lens[0] == 17 and slots.shape[2] == 24

    def test_no_returned_means_one_slot_per_row(self):
        recs = [np.arange(4, dtype=np.int32)] * 3
        slots, lens, idx = pack_records(recs, None, seq_len=8)
        assert slots.shape[0] == 3
        np.testing.assert_array_equal(idx, [0, 1, 2])


class TestEpochDevice:
    def test_matches_host_epoch_bytes(self, tmp_path):
        store, loader = build_loader(tmp_path)
        host = [grids(b) + (int(b["step"]),) for b in loader.epoch(0)]
        stager = DeviceStager()
        dev = [grids(b) + (int(b["step"]),) for b in loader.epoch_device(0, stager)]
        assert len(host) == len(dev) > 0
        for h, d in zip(host, dev):
            for a, b in zip(h, d):
                np.testing.assert_array_equal(a, b)
        assert stager.stats.kernel_steps == len(dev)  # Pallas path taken
        assert stager.stats.bytes_to_device > 0
        assert stager.live_buffers == 0

    def test_grid_stream_matches_host_epoch(self, tmp_path):
        """The RedoxClient-style path: pre-assembled batches, no kernel."""
        store, loader = build_loader(tmp_path)
        host = [grids(b) for b in loader.epoch(0)]
        stager = DeviceStager()
        dev = [grids(b) for b in stager.stream(loader.epoch_async(0))]
        for h, d in zip(host, dev):
            for a, b in zip(h, d):
                np.testing.assert_array_equal(a, b)
        assert stager.stats.kernel_steps == 0
        assert stager.stats.steps == len(host)

    def test_io_accounting_annotated_not_corrupted(self, tmp_path):
        store, loader = build_loader(tmp_path)
        stager = DeviceStager()
        staged = list(loader.epoch_device(0, stager))
        for b in staged:
            assert b["stage_s"] >= 0.0 and b["stage_wait_s"] >= 0.0
            io = b["io_by_node"]
            assert sum(s.stage_s for s in io.values()) == pytest.approx(
                b["stage_s"]
            )
        assert 0.0 <= stager.stats.overlap_fraction <= 1.0
        # Replay-engine plans share StepIO objects with future epochs: the
        # host-side stream must come back with stage fields untouched.
        for b in loader.epoch(1):
            for s in b["io_by_node"].values():
                assert s.stage_s == 0.0 and s.stage_wait_s == 0.0

    def test_use_kernel_false_rejects_packs(self):
        stager = DeviceStager(use_kernel=False)
        with pytest.raises(ValueError, match="cannot stage HostPacks"):
            stager.stage(HostPack(slot_tokens=np.zeros((1, 1, 8), np.int32)))

    def test_stream_is_one_at_a_time(self, tmp_path):
        store, loader = build_loader(tmp_path)
        stager = DeviceStager()
        gen = stager.stream(loader.epoch_async(0))
        next(gen)
        with pytest.raises(RuntimeError, match="one-at-a-time"):
            next(stager.stream(iter([])))
        with pytest.raises(RuntimeError, match="stream is active"):
            stager.close()
        gen.close()
        stager.close()  # fine once torn down


class TestTeardown:
    def test_abandoned_consumer_releases_device_buffers(self, tmp_path):
        store, loader = build_loader(tmp_path, queue_depth=1)
        stager = DeviceStager(depth=1)
        gen = loader.epoch_device(0, stager)
        next(gen)
        # Let the staging thread get ahead: a staged-but-unconsumed batch
        # must exist so abandonment has something to release.
        deadline = 50
        while stager.live_buffers == 0 and deadline:
            import time

            time.sleep(0.02)
            deadline -= 1
        assert stager.live_buffers > 0
        gen.close()
        assert loader._worker is not None
        loader._worker.join(timeout=5.0)
        assert not loader._worker.is_alive(), "protocol worker leaked"
        assert stager._thread is not None
        stager._thread.join(timeout=5.0)
        assert not stager._thread.is_alive(), "staging thread leaked"
        assert stager.live_buffers == 0, "device buffers stranded"
        assert stager.stats.buffers_released >= 1

    def test_worker_error_propagates_through_stager(self, tmp_path):
        store, loader = build_loader(tmp_path)
        calls = {"n": 0}
        real = store.read_chunk

        def flaky(chunk):
            calls["n"] += 1
            if calls["n"] > 3:
                raise OSError("injected storage failure")
            return real(chunk)

        store.read_chunk = flaky
        stager = DeviceStager()
        with pytest.raises(OSError, match="injected storage failure"):
            for _ in loader.epoch_device(0, stager):
                pass
        assert stager.live_buffers == 0


class TestClientEpochDevice:
    def test_ring_stream_staged_byte_identical(self, tmp_path):
        """RedoxClient.epoch_device == the in-process host epoch, through
        the socket + shared-memory ring + DeviceStager."""
        from repro.service.service import DataService
        from repro.service.transport import DataServiceServer, RedoxClient

        ds = SyntheticTokenDataset(96, vocab_size=97, mean_len=48, seed=3)
        store = ds.build_store(tmp_path / "chunks", 4, num_slots=16, seed=1)
        spec = SessionSpec(seed=5, num_nodes=2, batch_per_node=4, seq_len=32)
        host = [
            grids(b) for b in RedoxLoader.from_spec(spec, store).epoch(0)
        ]
        svc = DataService(store)
        server = DataServiceServer(svc, tmp_path / "svc.sock", poll_interval=0.001)
        server.start()
        try:
            client = RedoxClient(tmp_path / "svc.sock", spec, job_id="dev0")
            dev = [grids(b) for b in client.epoch_device(0)]
            client.close()
        finally:
            server.stop()
        assert len(dev) == len(host) > 0
        for h, d in zip(host, dev):
            for a, b in zip(h, d):
                np.testing.assert_array_equal(a, b)
