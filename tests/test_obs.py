"""Observability-plane tests (DESIGN.md §13).

Pins the tentpole contracts of ``repro.obs``:

* the **tracer** records spans/instants into a bounded ring, exports
  Chrome-trace JSON, and — crucially — is *pure* when disabled: zero
  events, zero allocation on the span fast path, and a per-site cost
  small enough that the instrumentation in a small ``epoch_stream`` run
  stays under the 5% overhead budget;
* tracing is *observationally inert*: a traced epoch produces the
  byte-identical :class:`EpochStream` an untraced one does (differential
  harness spot-check);
* the **MetricsRegistry** absorbs every stats dataclass through the
  round-trippable ``to_dict()`` and renders Prometheus text;
* **attribution** folds a trace into per-stage exclusive time with the
  ``sum(exclusive) + idle == wall`` identity the report is built on;
* a live :class:`DataServiceServer` answers the ``metrics`` RPC with
  per-session counters matching the session's final ServiceStats, and
  ``trace_dump`` exports the server-side ring.
"""

import json
import time
import warnings
from pathlib import Path

import pytest

from elastic_harness import (
    assert_streams_equal,
    record_replay,
    record_uninterrupted,
)
from repro.core import ChunkStore, SessionSpec
from repro.core.stats import (
    DeviceStats,
    NodeStats,
    PlannerStats,
    ServiceStats,
    StepIO,
)
from repro.core.storage.base import BackendStats
from repro.data.synthetic import SyntheticTokenDataset
from repro.core.stats import PipelineTimeModel
from repro.obs import (
    MetricsRegistry,
    STAGES,
    attribution,
    format_report,
    model_columns,
    trace,
    tracing,
)
from repro.obs.tracer import _NULL_SPAN
from repro.service import DataService
from repro.service.transport import DataServiceServer, RedoxClient

pytestmark = pytest.mark.obs

HARNESS_KW = dict(n=192, c=4, slots=24, nodes=2, seed=3)
BATCH = 8


# ------------------------------------------------------------------- tracer
class TestTracer:
    def test_span_instant_and_events(self):
        with tracing() as t:
            with trace.span("outer", "plan", epoch=0):
                with trace.span("inner", "read", chunk=7):
                    pass
            trace.instant("evict", "read", chunk=7)
        events = t.events()
        assert [e[0] for e in events] == ["inner", "outer", "evict"]
        (iname, icat, its, idur, itid, iargs) = events[0]
        (oname, ocat, ots, odur, otid, oargs) = events[1]
        assert icat == "read" and iargs == {"chunk": 7}
        assert ocat == "plan" and oargs == {"epoch": 0}
        # Nesting: the inner span lies inside the outer one.
        assert ots <= its and its + idur <= ots + odur + 1e-9
        assert itid == otid
        # Instants carry a negative duration sentinel.
        assert events[2][3] < 0

    def test_complete_with_external_timing(self):
        with tracing() as t:
            t0 = time.perf_counter()
            t.complete("planner.plan", "plan", t0, 0.25, {"steps": 3})
        ((name, cat, ts, dur, _tid, args),) = t.events()
        assert (name, cat, dur, args) == ("planner.plan", "plan", 0.25,
                                          {"steps": 3})

    def test_ring_overflow_drops_oldest(self):
        with tracing(capacity=4) as t:
            for i in range(10):
                trace.instant(f"e{i}")
        assert len(t) == 4
        assert t.dropped == 6
        assert [e[0] for e in t.events()] == ["e6", "e7", "e8", "e9"]

    def test_chrome_export_shape(self, tmp_path):
        with tracing() as t:
            with trace.span("read_chunk", "read", chunk=3):
                pass
            trace.instant("evict", "read")
        doc = t.to_chrome()
        assert doc["otherData"]["dropped_events"] == 0
        by_ph = {}
        for ev in doc["traceEvents"]:
            by_ph.setdefault(ev["ph"], []).append(ev)
        (meta,) = by_ph["M"]
        assert meta["name"] == "thread_name"
        (x,) = by_ph["X"]
        assert x["name"] == "read_chunk" and x["cat"] == "read"
        assert x["dur"] >= 0 and x["args"] == {"chunk": 3}
        (inst,) = by_ph["i"]
        assert inst["s"] == "t" and "dur" not in inst
        # dump() writes the same JSON and it parses back.
        out = t.dump(tmp_path / "trace.json")
        assert json.loads(out.read_text())["traceEvents"]

    def test_enable_disable_and_nesting_restores(self):
        assert trace.get() is None
        outer = trace.enable()
        assert trace.get() is outer
        with tracing() as inner:
            assert trace.get() is inner
        assert trace.get() is outer
        assert trace.disable() is outer
        assert trace.get() is None


# --------------------------------------------------------- disabled overhead
class TestDisabledPurity:
    def test_disabled_emits_nothing_and_allocates_nothing(self):
        assert trace.get() is None
        # The module span() fast path returns one shared no-op object.
        s1 = trace.span("a", "read", chunk=1)
        s2 = trace.span("b", "stage")
        assert s1 is s2 is _NULL_SPAN
        trace.instant("a", "read", chunk=1)  # no tracer: swallowed
        with tracing() as t:
            pass  # nothing was pending from the disabled period
        assert len(t) == 0 and t.dropped == 0

    def test_disabled_site_cost_within_epoch_budget(self):
        """The <5% overhead budget, measured structurally: (events a traced
        run records) x (disabled per-site cost) must stay under 5% of the
        untraced epoch wall. This is the quantity that matters — a disabled
        site costs one module load + None check regardless of what the
        instrumented code does around it."""
        t0 = time.perf_counter()
        record_uninterrupted(HARNESS_KW, BATCH, engine="step")
        wall = time.perf_counter() - t0

        with tracing(capacity=1 << 18) as tr:
            record_uninterrupted(HARNESS_KW, BATCH, engine="step")
        events = tr._recorded

        n = 100_000
        best = min(
            _time_disabled_sites(n) for _ in range(3)
        )
        per_site = best / n
        added = events * per_site
        assert added < 0.05 * wall, (
            f"{events} sites x {per_site * 1e9:.0f}ns = {added * 1e3:.2f}ms "
            f"exceeds 5% of the {wall * 1e3:.0f}ms epoch"
        )

    def test_traced_epoch_stream_is_byte_identical(self, tmp_path):
        """Tracing must be observationally inert: the differential harness
        compares a traced live walk + traced replay against their untraced
        twins on every observable (returned ids, StepIO grids, load/ship
        event sequences, NodeStats)."""
        ref_live = record_uninterrupted(HARNESS_KW, BATCH, engine="step")
        ref_replay = record_replay(HARNESS_KW, BATCH)
        with tracing(capacity=1 << 18) as t:
            got_live = record_uninterrupted(HARNESS_KW, BATCH, engine="step")
            got_replay = record_replay(HARNESS_KW, BATCH)
        assert len(t) > 0, "instrumented run recorded no spans"
        assert_streams_equal(got_live, ref_live, num_files=HARNESS_KW["n"])
        assert_streams_equal(got_replay, ref_replay, num_files=HARNESS_KW["n"])


    def test_disabled_with_jax_loaded_allocates_nothing(self):
        """With JAX loaded the span also asks the profiler whether a trace
        is active; with none active and no ring it still hands back the
        shared null span and keeps no memory."""
        import tracemalloc

        import jax.profiler  # noqa: F401  (the bridge is looked up once JAX is in)

        assert trace.get() is None and not trace._profiling()
        span = trace.span
        tracemalloc.start()
        try:
            before = tracemalloc.take_snapshot()
            for i in range(10_000):
                with span("site", "read", step=i) as s:
                    assert s is _NULL_SPAN
            after = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        kept = sum(d.size_diff for d in after.compare_to(before, "filename")
                   if d.traceback[0].filename == trace.__file__)
        assert kept <= 0


def _time_disabled_sites(n: int) -> float:
    span = trace.span
    t0 = time.perf_counter()
    for _ in range(n):
        with span("site", "read"):
            pass
    return time.perf_counter() - t0


# ----------------------------------------------------------- profiler bridge
def _profiled_spans(tmp_path, body):
    """Run ``body`` under a ``jax.profiler`` trace; the program spans on
    the trace's host plane as ``(name, start_ns, end_ns, line, args)``."""
    import jax
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path / "prof")):
        body()
    (path,) = (tmp_path / "prof").glob("**/*.xplane.pb")
    out = []
    with warnings.catch_warnings():  # the stats type warns as it is read
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(str(path)).planes:
            if plane.name.startswith("/host:"):
                for li, line in enumerate(plane.lines):
                    out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns, li,
                                dict(e.stats)) for e in line.events)
    return out


class TestProfilerBridge:
    def test_span_lands_on_the_host_plane_with_its_args(self, tmp_path):
        """No ring installed: the span is written for the profiler alone,
        args and a late ``set`` included; instants stay ring-only."""
        assert trace.get() is None

        def body():
            assert trace._profiling()
            with trace.span("store.read_chunk", "read", chunk=3, backend="vfs"):
                with trace.span("stager.wait", "stage") as s:
                    s.set(step=7)
            trace.instant("residency.evict", "read", chunk=3)

        spans = {e[0]: e for e in _profiled_spans(tmp_path, body)}
        assert spans["store.read_chunk"][4] == {"chunk": 3, "backend": "vfs"}
        assert spans["stager.wait"][4] == {"step": 7}
        outer, inner = spans["store.read_chunk"], spans["stager.wait"]
        assert outer[1] <= inner[1] and inner[2] <= outer[2]
        assert "residency.evict" not in spans
        assert not trace._profiling()

    def test_ring_and_profiler_both_record(self, tmp_path):
        with tracing() as t:
            def body():
                with trace.span("loader.pack", "decode", step=2):
                    pass
            spans = _profiled_spans(tmp_path, body)
        assert [e[0] for e in t.events()] == ["loader.pack"]
        assert t.events()[0][5] == {"step": 2}
        assert [(e[0], e[4]) for e in spans if e[0] == "loader.pack"] == [
            ("loader.pack", {"step": 2})]

    def test_every_per_step_span_reaches_the_profiler_with_its_step(self, tmp_path):
        """A short training run on the gather path under the profiler:
        each per-step span of the loader, the replay engine, the stager
        and the launcher is on the host plane with its ``step``, and each
        chunk read lies inside a ``replay.step`` span of its thread."""
        from repro.launch import train

        argv = ["--arch", "tinyllama-1.1b", "--steps", "10", "--ckpt-every", "100",
                "--batch", "4", "--seq-len", "32", "--num-docs", "64",
                "--device-path", "gather", "--workdir", str(tmp_path / "run")]
        spans = _profiled_spans(tmp_path, lambda: train.main(argv))
        per_step = ("replay.step", "loader.pack", "stager.stage", "stager.wait",
                    "train.step", "train.loss_sync")
        for name in per_step:
            found = [e for e in spans if e[0] == name]
            assert found, name
            assert all(isinstance(e[4].get("step"), int) for e in found), name
        assert sorted(e[4]["step"] for e in spans if e[0] == "train.step") == list(range(10))
        assert sorted(e[4]["step"] for e in spans if e[0] == "train.loss_sync") == [1, 10]
        steps = [e for e in spans if e[0] == "replay.step"]
        reads = [e for e in spans if e[0] == "store.read_chunk"]
        assert reads
        for r in reads:
            assert any(s[3] == r[3] and s[1] <= r[1] and r[2] <= s[2] for s in steps), r


    def test_profiled_sums_each_name_while_the_profiler_runs(self, tmp_path, monkeypatch):
        """While a profiler trace is active each span name's count and
        seconds are summed in-process, alike to what its file holds; spans
        begun before or after it are not."""
        monkeypatch.setattr(trace, "_profiled", {})
        with trace.span("loader.pack", "decode", step=0):
            pass

        def body():
            for step in range(3):
                with trace.span("loader.pack", "decode", step=step):
                    time.sleep(0.002)

        spans = _profiled_spans(tmp_path, body)
        with trace.span("loader.pack", "decode", step=3):
            time.sleep(0.01)
        n, seconds = trace.profiled()["loader.pack"]
        on_plane = [e for e in spans if e[0] == "loader.pack"]
        assert n == len(on_plane) == 3
        assert seconds == pytest.approx(sum(e[2] - e[1] for e in on_plane) / 1e9, abs=1e-3)


class TestPrograms:
    def test_a_noted_program_compiles_again_with_its_scopes(self, monkeypatch):
        """``note`` keeps shapes, not arrays (the call may donate them);
        ``compiled_text`` gives the module with its name scope metadata,
        once compiled."""
        import jax
        import jax.numpy as jnp

        from repro.obs import programs

        monkeypatch.setattr(programs, "_programs", {})

        def step(s, b):
            with jax.named_scope("attention"):
                return s * 2, jnp.tanh(b).sum()

        fn = jax.jit(step, donate_argnums=0)
        s, b = jnp.ones(4), jnp.ones((2, 4))
        programs.note("step", fn, s, b)
        fn(s, b)
        assert programs.compiled_text("other") is None
        text = programs.compiled_text("step")
        assert text.startswith("HloModule jit_step") and "/attention/tanh" in text
        assert programs.compiled_text("step") is text

# ------------------------------------------------------------------ metrics
class TestMetricsRegistry:
    def test_counter_gauge_histogram(self):
        reg = MetricsRegistry()
        c = reg.counter("batches_total")
        c.inc()
        c.inc(2)
        with pytest.raises(ValueError):
            c.inc(-1)
        g = reg.gauge("cache_bytes")
        g.set(100)
        g.dec(25)
        h = reg.histogram("latency_s", [0.01, 0.1, 1.0])
        for v in (0.005, 0.05, 0.5, 5.0):
            h.observe(v)
        snap = reg.collect()
        assert snap["batches_total"] == 3
        assert snap["cache_bytes"] == 75
        assert snap["latency_s_count"] == 4
        assert snap["latency_s_sum"] == pytest.approx(5.555)
        assert h.cumulative() == [(0.01, 1), (0.1, 2), (1.0, 3)]
        # Same (name, labels) returns the same instrument.
        assert reg.counter("batches_total") is c

    def test_stats_provider_and_labels(self):
        reg = MetricsRegistry()
        st = ServiceStats(physical_reads=4, physical_bytes=1000, shared_hits=2)
        reg.register_stats("service", lambda: st, labels={"job": "a"})
        snap = reg.collect()
        assert snap['service_physical_bytes{job="a"}'] == 1000
        assert snap['service_shared_hits{job="a"}'] == 2
        # Live: the provider re-reads the object at every collect.
        st.shared_hits = 9
        assert reg.collect()['service_shared_hits{job="a"}'] == 9

    def test_reregister_replaces_and_unregister_removes(self):
        reg = MetricsRegistry()
        reg.register_stats("s", lambda: {"v": 1}, labels={"job": "a"})
        reg.register_stats("s", lambda: {"v": 2}, labels={"job": "a"})
        assert reg.collect() == {'s_v{job="a"}': 2}
        reg.unregister("s", labels={"job": "a"})
        assert reg.collect() == {}

    def test_exposition_format(self):
        reg = MetricsRegistry()
        reg.counter("reads_total", labels={"backend": "vfs"}).inc(7)
        reg.histogram("wait_s", [0.5]).observe(0.2)
        reg.register_stats("device", lambda: DeviceStats(steps=3))
        text = reg.exposition()
        assert '# TYPE reads_total counter' in text
        assert 'reads_total{backend="vfs"} 7' in text
        assert 'wait_s_bucket{le="0.5"} 1' in text
        assert 'wait_s_bucket{le="+Inf"} 1' in text
        assert 'wait_s_count 1' in text
        assert 'device_steps 3' in text
        assert text.endswith("\n")


# ------------------------------------------------------- stats round-trips
STATS_SAMPLES = [
    NodeStats(accesses=10, chunk_loads=3, disk_bytes=4096, read_wait_s=0.5,
              fill_rate_num=2.5, peak_local_bytes=99),
    PlannerStats(plan_time_s=0.1, planned_steps=8, planned_chunk_loads=5),
    ServiceStats(physical_reads=2, shared_hits=7, peak_cache_bytes=1 << 20),
    StepIO(chunk_loads=1, disk_bytes=512, stage_s=0.25, stage_wait_s=0.1),
    DeviceStats(steps=4, bytes_to_device=2048, stage_s=1.0, wait_s=0.25),
    BackendStats(chunk_reads=6, bytes_read=9000, wait_seconds=0.75,
                 peak_inflight=3),
]


class TestStatsDict:
    @pytest.mark.parametrize(
        "obj", STATS_SAMPLES, ids=lambda o: type(o).__name__
    )
    def test_round_trip_exact(self, obj):
        d = obj.to_dict()
        assert type(obj).from_dict(d) == obj
        # Fields only — derived @property ratios are not serialized.
        assert "overlap_fraction" not in d
        assert "mean_fill_rate" not in d
        # Unknown keys (e.g. a newer writer) are ignored on the way in.
        assert type(obj).from_dict({**d, "future_field": 1}) == obj
        # JSON-safe end to end.
        assert type(obj).from_dict(json.loads(json.dumps(d))) == obj

    def test_overlap_fraction_zero_denominator(self):
        # Regression: an idle stager used to report a misleading 1.0.
        assert DeviceStats().overlap_fraction == 0.0
        assert DeviceStats(stage_s=2.0, wait_s=0.5).overlap_fraction == 0.75
        assert DeviceStats(stage_s=1.0, wait_s=3.0).overlap_fraction == 0.0

    def test_other_ratio_guards(self):
        assert NodeStats().read_throughput == 0.0
        assert NodeStats().mean_fill_rate == 1.0
        assert BackendStats().throughput() == 0.0


# -------------------------------------------------------------- attribution
def _ev(cat, lo, hi, name=None):
    return (name or cat, cat, lo, hi - lo, 0, None)


class TestAttribution:
    def test_busy_is_interval_union(self):
        att = attribution(
            [_ev("read", 0.0, 1.0), _ev("read", 0.5, 2.0),
             _ev("read", 3.0, 4.0)],
            wall_s=4.0,
        )
        assert att["busy_s"]["read"] == pytest.approx(3.0)
        assert att["spans"] == 3

    def test_exclusive_priority_and_identity(self):
        # compute [0,2] overlaps read [1,3]; proto [2.5,3] sits inside read;
        # [3.5,4] is uncovered idle.
        events = [
            _ev("compute", 0.0, 2.0),
            _ev("read", 1.0, 3.0),
            _ev("proto", 2.5, 3.0),
        ]
        att = attribution(events, wall_s=4.0)
        assert att["exclusive_s"]["compute"] == pytest.approx(2.0)
        # read keeps only what compute did not claim; proto is fully
        # shadowed by the higher-priority read span.
        assert att["exclusive_s"]["read"] == pytest.approx(1.0)
        assert att["exclusive_s"]["proto"] == pytest.approx(0.0)
        assert att["idle_s"] == pytest.approx(1.0)
        total = sum(att["exclusive_s"].values()) + att["idle_s"]
        assert total == pytest.approx(att["wall_s"])

    def test_plan_outranks_proto(self):
        # A planner span encloses its shadow protocol walk: the time must
        # read as planning, not protocol.
        att = attribution(
            [_ev("plan", 0.0, 1.0), _ev("proto", 0.2, 0.8)], wall_s=1.0
        )
        assert att["exclusive_s"]["plan"] == pytest.approx(1.0)
        assert att["exclusive_s"]["proto"] == pytest.approx(0.0)
        assert STAGES.index("plan") < STAGES.index("proto")

    def test_instants_unknown_cats_and_empty(self):
        att = attribution(
            [("evict", "read", 0.5, -1.0, 0, None),  # instant: no duration
             _ev("mystery", 0.0, 1.0)],
            wall_s=2.0,
        )
        assert "read" not in att["busy_s"]
        assert att["busy_s"]["other"] == pytest.approx(1.0)
        empty = attribution([], wall_s=1.5)
        assert empty["idle_s"] == 1.5 and empty["spans"] == 0

    def test_format_report_renders(self):
        att = attribution(
            [_ev("compute", 0.0, 2.0), _ev("read", 1.0, 3.0)], wall_s=4.0
        )
        text = format_report(att, measured_wall_s=4.0)
        assert "compute" in text and "read" in text and "idle" in text
        assert "epoch wall time: 4.000s" in text

    def test_model_columns_from_step_io(self):
        tm = PipelineTimeModel(disk_bw=100e6, file_overhead=1e-3,
                               chunk_overhead=2e-3, net_bw=1e9,
                               net_latency=1e-4)
        grid = [[StepIO(chunk_loads=2, disk_bytes=10_000_000,
                        net_messages=5, net_bytes=1_000_000)],
                [StepIO(chunk_loads=1, disk_bytes=5_000_000)]]
        cols = model_columns(grid, tm, compute_per_step=0.5)
        assert cols["read"] == pytest.approx(
            3 * 2e-3 + 15_000_000 / 100e6
        )
        assert cols["net"] == pytest.approx(5 * 1e-4 + 1_000_000 / 1e9)
        assert cols["compute"] == pytest.approx(0.5)
        assert cols["epoch"] == pytest.approx(
            tm.epoch_time(grid, 0.5)
        )
        # The model columns merge into the rendered report.
        att = attribution([_ev("compute", 0.0, 1.0)], wall_s=1.0)
        text = format_report(att, model=cols, measured_wall_s=1.0)
        assert "model_s" in text and "pipelined epoch-time bound" in text

    def test_real_trace_attribution_sums_to_wall(self):
        """The acceptance identity on a real traced epoch: the exclusive
        breakdown plus idle covers the measured wall to within 10%."""
        with tracing(capacity=1 << 18) as t:
            t0 = time.perf_counter()
            record_uninterrupted(HARNESS_KW, BATCH, engine="step")
            wall = time.perf_counter() - t0
        att = attribution(t.events(), wall_s=wall)
        assert att["spans"] > 0
        covered = sum(att["exclusive_s"].values()) + att["idle_s"]
        assert covered == pytest.approx(wall, rel=0.10)


class TestReplaySpans:
    def test_replay_step_encloses_its_chunk_reads(self, tmp_path):
        """On a store behind storage latency every ``replay.step`` span
        covers the whole step: each of the step's ``store.read_chunk``
        spans lies inside it, on the same thread."""
        from repro.core import RedoxLoader
        from repro.core.storage.vfs import VFSBackend

        ds = SyntheticTokenDataset(96, vocab_size=97, mean_len=24, seed=3)
        built = ds.build_store(tmp_path / "chunks", 2, num_slots=16, seed=1)
        store = ChunkStore.open(built.root, backend=VFSBackend(latency_s=0.002))
        spec = SessionSpec(seed=5, num_nodes=1, batch_per_node=8, seq_len=32)
        try:
            with tracing(capacity=1 << 16) as t:
                for _ in RedoxLoader.from_spec(spec, store).epoch(0):
                    pass
        finally:
            store.close()
        events = t.events()
        steps = [e for e in events if e[0] == "replay.step"]
        reads = [e for e in events if e[0] == "store.read_chunk"]
        assert steps and reads
        per_step = {}
        for name, _, ts, dur, tid, _args in reads:
            (owner,) = [s for s in steps
                        if s[4] == tid and s[2] <= ts and ts + dur <= s[2] + s[3]]
            per_step[owner[5]["step"]] = per_step.get(owner[5]["step"], 0) + 1
        assert max(per_step.values()) >= 2  # a step with several reads


# ---------------------------------------------------------- live server RPC
@pytest.mark.transport
class TestServerObservability:
    SPEC = SessionSpec(seed=5, num_nodes=2, batch_per_node=8, seq_len=32)

    @pytest.fixture
    def served(self, tmp_path):
        ds = SyntheticTokenDataset(96, vocab_size=97, mean_len=48, seed=3)
        store = ds.build_store(tmp_path / "chunks", 4, num_slots=16, seed=1)
        store = ChunkStore.open(store.root)
        svc = DataService(store)
        server = DataServiceServer(svc, tmp_path / "svc.sock",
                                   poll_interval=0.001)
        server.start()
        yield server, tmp_path / "svc.sock"
        server.stop()
        store.close()

    def test_metrics_rpc_matches_final_service_stats(self, served):
        server, sock = served
        client = RedoxClient(sock, self.SPEC, job_id="job0")
        for _ in client.epoch(0):
            pass
        out = client.metrics()
        snap, text = out["metrics"], out["text"]
        svc = server.service
        final = svc.residency.per_job_stats["job0"]
        assert final.physical_reads > 0
        for field, v in final.to_dict().items():
            assert snap[f'service_{field}{{job="job0"}}'] == v
        # Aggregate + residency gauges ride along, and the text exposition
        # carries the same samples.
        agg = svc.aggregate_stats()
        assert snap["service_physical_bytes"] == agg.physical_bytes
        assert snap["residency_open_sessions"] == 1
        assert f'service_physical_reads{{job="job0"}} '\
               f'{final.physical_reads}' in text
        client.close()

    def test_metrics_rpc_scrape_is_idempotent(self, served):
        """Scraping twice must not duplicate the per-job providers."""
        server, sock = served
        client = RedoxClient(sock, self.SPEC, job_id="job0")
        for _ in client.epoch(0):
            pass
        first = client.metrics()["metrics"]
        second = client.metrics()["metrics"]
        assert first == second
        client.close()

    def test_trace_dump_rpc(self, served, tmp_path):
        server, sock = served
        client = RedoxClient(sock, self.SPEC, job_id="job0")
        # Tracing off: the RPC reports that instead of failing.
        obj, events = client.trace_dump()
        assert obj is None and events == 0
        trace.enable(1 << 16)
        try:
            for _ in client.epoch(0):
                pass
            doc, events = client.trace_dump()
            assert events > 0 and len(doc["traceEvents"]) > 0
            cats = {e.get("cat") for e in doc["traceEvents"]}
            assert "service" in cats and "ring" in cats
            out = tmp_path / "server_trace.json"
            path, events2 = client.trace_dump(out)
            assert Path(path) == out and events2 >= events
            assert json.loads(out.read_text())["traceEvents"]
        finally:
            trace.disable()
        client.close()
