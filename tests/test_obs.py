"""strom/obs — event ring, Chrome-trace export, live endpoint, stall
attribution (ISSUE 3 tentpole). The ring is the causal timeline the counters
cannot provide; these tests pin its bounded-drop semantics, the export
format Perfetto actually loads, the HTTP routes, and the bucket arithmetic
the next perf PR will be chosen with."""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from strom.obs import stall
from strom.obs.chrome_trace import (dump, load_events, to_trace_events,
                                    trace_document)
from strom.obs.events import EventRing
from strom.obs.server import MetricsServer


class TestEventRing:
    def test_span_and_instant_shapes(self):
        r = EventRing(capacity=16)
        with r.span("work", cat="read", args={"bytes": 7}):
            pass
        r.instant("tick", cat="meta")
        evs = r.snapshot()
        assert [e["name"] for e in evs] == ["work", "tick"]
        span, inst = evs
        assert span["ph"] == "X" and span["dur_us"] >= 0
        assert span["cat"] == "read" and span["args"] == {"bytes": 7}
        assert inst["ph"] == "i" and "dur_us" not in inst
        assert span["tid"] == threading.get_ident()

    def test_bounded_drop_oldest(self):
        r = EventRing(capacity=4)
        for i in range(10):
            r.instant(f"e{i}")
        evs = [e for e in r.snapshot() if e["name"] != "events_dropped"]
        # only the newest `capacity` retained, oldest first
        assert [e["name"] for e in evs] == ["e6", "e7", "e8", "e9"]
        assert r.events_dropped == 6
        # the truncation is visible in the snapshot itself, never silent
        meta = [e for e in r.snapshot() if e["name"] == "events_dropped"]
        assert meta and meta[0]["args"]["count"] == 6

    def test_disabled_ring_records_nothing(self):
        r = EventRing(capacity=8, enabled=False)
        with r.span("x"):
            r.instant("y")
        assert r.snapshot() == [] and len(r) == 0

    def test_span_recorded_on_exception(self):
        r = EventRing(capacity=8)
        with pytest.raises(ValueError):
            with r.span("boom", cat="read"):
                raise ValueError()
        assert [e["name"] for e in r.snapshot()] == ["boom"]

    def test_snapshot_sorted_by_start_despite_nesting(self):
        r = EventRing(capacity=8)
        with r.span("outer"):  # exits LAST, starts FIRST
            with r.span("inner"):
                pass
        names = [e["name"] for e in r.snapshot()]
        assert names == ["outer", "inner"]

    def test_clear(self):
        r = EventRing(capacity=4)
        for i in range(9):
            r.instant("e")
        r.clear()
        assert r.snapshot() == [] and r.events_dropped == 0

    def test_span_totals_count_wall_and_cpu(self):
        r = EventRing(capacity=16)
        r.complete(0.0, 5.0, "read", "a")
        r.complete(1.0, 7.5, "read", "a", cpu_us=2.0)
        r.instant("a")           # instants and flows are not spans
        r.flow("s", 1, "a")
        r.cpu_lap_us()           # the lap before the span starts
        with r.span("b", cpu=True):
            x = 0
            for i in range(20000):
                x += i
        tot = r.totals()
        assert tot["a"] == (2, 12.5, 2.0)
        count, wall, cpu = tot["b"]
        assert count == 1 and wall > 0 and 0 < cpu <= wall + 1000
        # without cpu= a span adds wall time only
        with r.span("c"):
            pass
        assert r.totals()["c"][2] == 0.0

    def test_cpu_spans_read_the_clock_once_per_lap(self, monkeypatch):
        from strom.obs import events

        reads = []

        def clock():
            reads.append(1)
            return len(reads) * 1e-3

        monkeypatch.setattr(events.time, "thread_time", clock)
        r = EventRing(capacity=8)
        for _ in range(2 * events.CPU_LAP_SPANS + 8):
            with r.span("w", cpu=True):
                pass
        # spans 1, 17 and 33 read it; each lap bills 1 ms
        assert len(reads) == 3
        assert r.totals()["w"][0] == 40 and r.totals()["w"][2] == 3000.0

    def test_span_totals_survive_wrap(self):
        r = EventRing(capacity=8)
        for i in range(20):
            r.complete(float(i), 2.0, "decode", "w", cpu_us=1.0)
        assert len(r) == 8 and r.events_dropped == 12
        assert r.totals() == {"w": (20, 40.0, 20.0)}
        r.clear()
        assert r.totals() == {}

    def test_disabled_ring_adds_no_totals(self):
        r = EventRing(capacity=8, enabled=False)
        with r.span("x", cpu=True):
            pass
        r.complete(0.0, 3.0, "read", "y")
        assert r.totals() == {} and len(r) == 0

    def test_anchor_maps_ring_onto_profiler_clock(self, tmp_path):
        """A ring span and an annotation opened back to back inside an
        anchor start within 100 us of each other once the anchor's start
        and end pairs map ring time onto the profiler's clock."""
        import glob
        import time

        import jax
        from jax.profiler import ProfileData, TraceAnnotation

        r = EventRing(capacity=64)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with r.anchor("obs.test.window"):
                time.sleep(0.01)
                with r.span("obs.test.ring"), \
                        TraceAnnotation("obs.test.annot"):
                    time.sleep(0.005)
                time.sleep(0.01)
        finally:
            jax.profiler.stop_trace()
        xplane, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        prof = {e.name: (e.start_ns, e.start_ns + e.duration_ns)
                for plane in ProfileData.from_file(xplane).planes
                if plane.name.startswith("/host:")
                for line in plane.lines for e in line.events
                if e.name.startswith("obs.test.")}
        ring = {e["name"]: e for e in r.snapshot()}
        win = ring["obs.test.window"]
        assert win["cat"] == "anchor"
        a0, a1 = win["ts_us"], win["ts_us"] + win["dur_us"]
        (p0, p1) = prof["obs.test.window"]
        ns_per_us = (p1 - p0) / (a1 - a0)
        mapped = p0 + (ring["obs.test.ring"]["ts_us"] - a0) * ns_per_us
        assert abs(mapped - prof["obs.test.annot"][0]) < 100e3

    def test_concurrent_writers_never_corrupt(self):
        r = EventRing(capacity=64)

        def spam():
            for _ in range(500):
                r.instant("t")

        ts = [threading.Thread(target=spam) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        evs = r.snapshot()
        assert len(evs) == 64 + 1  # 64 retained + the events_dropped marker
        assert r.events_dropped == 4 * 500 - 64

    def test_concurrent_span_totals_lose_no_update(self):
        import os
        import sys

        r = EventRing(capacity=32)
        n = (os.cpu_count() or 4) + 4

        def spam():  # each name's first span races its insertion
            for i in range(500):
                r.complete(0.0, 1.0, "read", f"t{i % 100}", cpu_us=2.0)

        prev = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            ts = [threading.Thread(target=spam) for _ in range(n)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(prev)
        assert not any(t.is_alive() for t in ts)
        assert r.totals() == {f"t{i}": (n * 5, n * 5.0, n * 10.0)
                              for i in range(100)}


class TestChromeTrace:
    def test_export_and_roundtrip(self, tmp_path):
        r = EventRing(capacity=16)
        with r.span("engine.read", cat="read", args={"ops": 3}):
            pass
        r.instant("prefetch.depth", cat="prefetch", args={"depth": 4})
        p = str(tmp_path / "trace.json")
        assert dump(p, ring=r) == p
        doc = json.loads(open(p).read())
        assert "traceEvents" in doc
        tes = doc["traceEvents"]
        assert {te["ph"] for te in tes} == {"X", "i"}
        x = next(te for te in tes if te["ph"] == "X")
        assert x["name"] == "engine.read" and x["cat"] == "read"
        assert "dur" in x and "ts" in x and "pid" in x and "tid" in x
        # loader inverts the export (tools/trace_report.py rides this)
        evs = load_events(p)
        assert [e["name"] for e in evs] == ["engine.read", "prefetch.depth"]
        assert evs[0]["cat"] == "read" and evs[0]["args"] == {"ops": 3}

    def test_instant_scope_and_meta(self):
        doc = trace_document(
            [{"ts_us": 1.0, "tid": 5, "cat": "", "name": "i", "ph": "i"}],
            meta={"bench": "resnet"})
        te = doc["traceEvents"][0]
        assert te["s"] == "t" and te["cat"] == "strom"
        assert doc["otherData"] == {"bench": "resnet"}

    def test_to_trace_events_pure(self):
        tes = to_trace_events(
            [{"ts_us": 10.0, "dur_us": 5.0, "tid": 1, "cat": "put",
              "name": "p", "ph": "X"}], pid=42)
        assert tes == [{"name": "p", "ph": "X", "ts": 10.0, "pid": 42,
                        "tid": 1, "cat": "put", "dur": 5.0}]


def _span(ts, dur, cat, name="s", tid=1):
    return {"ts_us": float(ts), "dur_us": float(dur), "tid": tid,
            "cat": cat, "name": name, "ph": "X"}


class TestStallAttribution:
    def test_buckets_from_synthetic_timeline(self):
        # step [0, 100]: waits 30us in next() at [0, 30]; during the wait
        # decode ran [0, 20], put [20, 30], engine read [5, 15]; decode also
        # ran [50, 90] OVERLAPPING COMPUTE — free, must not be billed
        events = [
            _span(0, 100, "step", "train.step"),
            _span(0, 30, "ingest_wait", "pipeline.next"),
            _span(0, 20, "decode", "decode.worker", tid=2),
            _span(50, 40, "decode", "decode.worker", tid=2),
            _span(20, 10, "put", "strom.device_put", tid=3),
            _span(5, 10, "read", "engine.python.read_vectored", tid=4),
        ]
        (s,) = stall.step_buckets(events)
        assert s.wall_us == 100 and s.ingest_wait_us == 30
        assert s.decode_us == 20 and s.put_us == 10 and s.read_us == 10
        assert s.compute_us == 70
        summary = stall.steps_summary(events)
        assert summary["steps_observed"] == 1
        assert summary["goodput_pct"] == 70.0
        assert summary["buckets"]["ingest_wait"]["p50_us"] == 30
        assert summary["buckets"]["compute"]["total_us"] == 70

    def test_overlapping_waits_union_not_double_billed(self):
        # pipeline.next and prefetch.stall_wait overlap (nested): the wait
        # bucket is their UNION, not the sum
        events = [
            _span(0, 100, "step", "train.step"),
            _span(10, 40, "ingest_wait", "pipeline.next"),
            _span(15, 30, "ingest_wait", "prefetch.stall_wait"),
        ]
        (s,) = stall.step_buckets(events)
        assert s.ingest_wait_us == 40 and s.compute_us == 60

    def test_steps_derived_from_waits_when_no_step_spans(self):
        # flat-out loader shape: no train.step spans — windows derive from
        # consecutive next() starts, and the FINAL next() still gets a
        # window (closed at the last event edge: N nexts -> N windows)
        events = [
            _span(0, 10, "ingest_wait", "pipeline.next"),
            _span(50, 20, "ingest_wait", "pipeline.next"),
            _span(100, 5, "ingest_wait", "pipeline.next"),
        ]
        steps = stall.step_buckets(events)
        assert [s.wall_us for s in steps] == [50, 50, 5]
        assert [s.ingest_wait_us for s in steps] == [10, 20, 5]

    def test_nested_stall_wait_does_not_split_windows(self):
        # a stalled next() emits BOTH a pipeline.next span and a nested
        # prefetch.stall_wait span (same cat): window derivation must not
        # count the nested span as an extra step boundary
        events = [
            _span(0, 30, "ingest_wait", "pipeline.next"),
            _span(5, 20, "ingest_wait", "prefetch.stall_wait"),
            _span(50, 10, "ingest_wait", "pipeline.next"),
        ]
        steps = stall.step_buckets(events)
        assert len(steps) == 2
        assert [s.wall_us for s in steps] == [50, 10]

    def test_single_next_still_yields_a_window(self):
        events = [
            _span(0, 10, "ingest_wait", "pipeline.next"),
            _span(2, 30, "read", "strom.read_segments", tid=2),
        ]
        (s,) = stall.step_buckets(events)
        assert s.wall_us == 32 and s.ingest_wait_us == 10

    def test_window_bounds_filter(self):
        events = [
            _span(0, 10, "step", "train.step"),
            _span(100, 10, "step", "train.step"),
        ]
        assert len(stall.step_buckets(events, lo_us=50)) == 1
        assert len(stall.step_buckets(events, hi_us=50)) == 1
        assert stall.steps_summary(events, lo_us=50)["steps_observed"] == 1

    def test_empty_events(self):
        summary = stall.steps_summary([])
        assert summary["steps_observed"] == 0
        assert summary["goodput_pct"] == 0.0
        flat = stall.flatten_summary(summary)
        assert flat["goodput_pct"] == 0.0
        assert set(stall.STALL_FIELDS) <= set(flat)

    def test_flatten_matches_stall_fields(self):
        # the bench JSON column contract: flatten_summary emits EXACTLY the
        # single-sourced STALL_FIELDS key set
        flat = stall.flatten_summary(stall.steps_summary(
            [_span(0, 10, "step", "train.step")]))
        assert set(flat) == set(stall.STALL_FIELDS)


class TestMetricsServer:
    def _get(self, port, route):
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{route}", timeout=10) as r:
                return r.status, r.read().decode()
        except urllib.error.HTTPError as e:  # 4xx/5xx still carry a body
            return e.code, e.read().decode()

    def test_routes(self):
        from strom.utils.stats import StatsRegistry

        reg = StatsRegistry("obs_test")
        reg.add("scrapes_total", 3)
        reg.observe_us("lat", 100.0)
        ring = EventRing(capacity=8)
        with ring.span("engine.read", cat="read"):
            pass
        srv = MetricsServer(lambda: {"sec": reg.snapshot()}, port=0,
                            ring=ring)
        try:
            st, metrics = self._get(srv.port, "/metrics")
            assert st == 200
            assert "strom_sec_scrapes_total 3" in metrics
            # live histogram, cumulative, with TYPE line (acceptance: at
            # least one live histogram in a /metrics scrape)
            assert "# TYPE strom_sec_lat_us histogram" in metrics
            assert 'strom_sec_lat_us_bucket{le="+Inf"} 1' in metrics

            st, body = self._get(srv.port, "/stats")
            doc = json.loads(body)
            assert st == 200
            assert doc["sections"]["sec"]["scrapes_total"] == 3
            assert "global" in doc and doc["events_dropped"] == 0

            st, body = self._get(srv.port, "/trace")
            assert st == 200
            tes = json.loads(body)["traceEvents"]
            assert any(te["name"] == "engine.read" for te in tes)

            st, _ = self._get(srv.port, "/nope")
            assert st == 404
        finally:
            srv.close()

    def test_get_raises_404_after_close_or_refuses(self):
        srv = MetricsServer(lambda: {}, port=0)
        port = srv.port
        srv.close()
        with pytest.raises(Exception):
            self._get(port, "/metrics")

    def test_stats_fn_error_returns_500_not_crash(self):
        def bad():
            raise RuntimeError("boom")

        srv = MetricsServer(bad, port=0)
        try:
            st, _ = self._get(srv.port, "/stats")
            assert st == 500
            # server survives the failed scrape
            st, _ = self._get(srv.port, "/trace")
            assert st == 200
        finally:
            srv.close()

    def test_metrics_without_stats_fn_serves_global_registry(self):
        from strom.utils.stats import global_stats

        global_stats.add("obs_server_test_hits")
        srv = MetricsServer(port=0)
        try:
            st, body = self._get(srv.port, "/metrics")
            assert st == 200 and "strom_obs_server_test_hits" in body
        finally:
            srv.close()

    def test_sections_filter_skips_unwanted(self):
        """?sections= restricts the sweep AND the server only asks
        stats_fn for the wanted sections (ISSUE 6 satellite: a
        counters-only scrape never recomputes stall attribution)."""
        calls = []

        def stats_fn(sections=None):
            calls.append(tuple(sections) if sections is not None else None)
            secs = {"cheap": {"a": 1}, "costly": {"b": 2}}
            if sections is None:
                return secs
            return {k: v for k, v in secs.items() if k in sections}

        srv = MetricsServer(stats_fn, port=0, section_ttl_s=0.0)
        try:
            # first scrape learns the section names (full compute, once)
            st, body = self._get(srv.port, "/metrics?sections=cheap")
            assert st == 200
            assert "strom_cheap_a 1" in body
            st, body = self._get(srv.port, "/metrics?sections=cheap")
            assert "strom_cheap_a 1" in body and "strom_costly_b" not in body
            # after warmup, refreshes name only the wanted section
            assert calls[-1] == ("cheap",)
        finally:
            srv.close()

    def test_section_ttl_caches_renders(self):
        calls = []

        def stats_fn(sections=None):
            calls.append(1)
            return {"sec": {"n": len(calls)}}

        srv = MetricsServer(stats_fn, port=0, section_ttl_s=60.0)
        try:
            _, body1 = self._get(srv.port, "/metrics")
            n_after_first = len(calls)
            _, body2 = self._get(srv.port, "/metrics")
            # within the TTL the rendered text is reused: no new compute
            assert len(calls) == n_after_first
            assert "strom_sec_n 1" in body1 and "strom_sec_n 1" in body2
        finally:
            srv.close()

    def test_scoped_series_render_with_help_and_type(self):
        """Labeled twins of a scoped write appear under ONE # HELP/# TYPE
        family header on /metrics (ISSUE 6 satellite)."""
        from strom.utils.stats import global_stats

        global_stats.scoped(pipeline="obs_t").add("obs_scoped_probe", 4)
        srv = MetricsServer(port=0)
        try:
            _, body = self._get(srv.port, "/metrics")
            assert "# TYPE strom_obs_scoped_probe counter" in body
            assert body.count("# TYPE strom_obs_scoped_probe ") == 1
            assert 'strom_obs_scoped_probe{pipeline="obs_t"} 4' in body
        finally:
            srv.close()


class TestWiring:
    """The instrumentation sites actually emit: one pread lights up the
    read spans; a context exposes the steps section; the put span is a
    ring span, request-linked inside a request."""

    def test_trace_span_dual_emit(self):
        """The context's put span lands in the ring (the program's one
        record of spans) and, inside a request, joins its lane: req id in
        the args, a flow event of the same id."""
        import jax

        from strom.config import StromConfig
        from strom.delivery.core import StromContext
        from strom.obs import request as _request
        from strom.obs.events import ring as groll

        ctx = StromContext(StromConfig(engine="python"))
        try:
            with _request.active("batch") as req:
                ctx.device_put(np.arange(4, dtype=np.uint8),
                               jax.devices()[0])
        finally:
            ctx.close()
        evs = groll.snapshot()
        puts = [e for e in evs if e["name"] == "strom.device_put"
                and e.get("args", {}).get("req") == req.id]
        assert len(puts) == 1 and puts[0]["cat"] == "put"
        assert any(e["ph"] in ("s", "t") and e["id"] == req.id for e in evs)

    def test_trace_span_enabled_false_still_feeds_ring(self):
        """Outside a request the put span is a plain ring span, and it
        adds to the span totals like every other site."""
        from strom.obs import request as _request
        from strom.obs.events import ring as groll

        before = groll.totals().get("obs.test.plain", (0, 0.0, 0.0))[0]
        with _request.span("obs.test.plain", cat="put"):
            pass
        ev = [e for e in groll.snapshot() if e["name"] == "obs.test.plain"]
        assert ev and ev[-1]["cat"] == "put" and "args" not in ev[-1]
        assert groll.totals()["obs.test.plain"][0] == before + 1

    def test_trace_span_respects_ring_switch(self):
        from strom.obs import request as _request
        from strom.obs.events import ring as groll

        groll.enabled = False
        try:
            before = len(groll)
            with _request.span("obs.test.ring_off", cat="put"):
                pass
            assert len(groll) == before
            assert "obs.test.ring_off" not in groll.totals()
        finally:
            groll.enabled = True

    def test_pread_emits_read_spans_and_steps_section(self, tmp_path, rng):
        from strom.config import StromConfig
        from strom.delivery.core import StromContext
        from strom.obs.events import ring as groll

        data = rng.integers(0, 256, 64 * 1024, dtype=np.uint8)
        p = tmp_path / "obs.bin"
        data.tofile(p)
        ctx = StromContext(StromConfig(engine="python"))
        try:
            got = ctx.pread(str(p), 0, 4096)
            np.testing.assert_array_equal(got, data[:4096])
            names = {e["name"] for e in groll.snapshot()}
            assert "strom.read_segments" in names
            assert "engine.python.read_vectored" in names
            st = ctx.stats()
            assert "steps" in st
            assert set(st["steps"]) >= set(
                ["goodput_pct", "steps_observed", "events_dropped"])
        finally:
            ctx.close()

    def test_context_metrics_port_serves_live_stats(self, tmp_path, rng):
        """StromContext(metrics_port=0) binds an ephemeral port and serves
        the context's own sections + the global registry mid-run."""
        from strom.config import StromConfig
        from strom.delivery.core import StromContext

        data = rng.integers(0, 256, 8192, dtype=np.uint8)
        p = tmp_path / "live.bin"
        data.tofile(p)
        ctx = StromContext(StromConfig(engine="python"), metrics_port=0)
        try:
            assert ctx.metrics_server is not None
            ctx.pread(str(p), 0, 4096)
            port = ctx.metrics_server.port
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
                text = r.read().decode()
            # context/engine sections present, counters typed as counters
            assert "strom_engine_bytes_read" in text
            assert "# TYPE strom_context_ssd2tpu_bytes counter" in text
            # live engine histogram (the acceptance criterion's shape)
            assert "strom_engine_read_latency_us_bucket" in text
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/stats", timeout=10) as r:
                doc = json.loads(r.read().decode())
            assert doc["sections"]["engine"]["bytes_read"] >= 4096
        finally:
            ctx.close()
        assert ctx.metrics_server is not None  # handle survives for .port

    def test_decode_pool_emits_decode_spans(self):
        from strom.formats.jpeg import DecodePool
        from strom.obs.events import ring as groll

        def tf(item, rng_, out=None):
            out[...] = item
            return out

        pool = DecodePool(workers=2)
        try:
            out = np.zeros((4, 2, 2, 3), dtype=np.uint8)
            pool.map_into(tf, [1, 2, 3, 4], [None] * 4, out)
        finally:
            pool.close()
        decs = [e for e in groll.snapshot()
                if e["name"] == "decode.worker" and e["cat"] == "decode"]
        assert len(decs) >= 4

    @pytest.mark.parametrize("in_request", [False, True])
    def test_decode_worker_totals_carry_thread_cpu(self, in_request):
        """Each decode span adds its worker's CPU time since the span
        before to the ``decode.worker`` totals, plain or request-linked:
        the decode, and a fresh worker's start-up, but no idle time."""
        import contextlib

        from strom.formats.jpeg import DecodePool
        from strom.obs import request as _request
        from strom.obs.events import ring as groll

        def tf(item, rng_, out=None):
            acc = np.zeros(4096)
            for _ in range(50):
                acc = np.sqrt(acc + item)
            out[...] = int(acc[0]) % 256
            return out

        c0, w0, u0 = groll.totals().get("decode.worker", (0, 0.0, 0.0))
        pool = DecodePool(workers=2)
        try:
            out = np.zeros((6, 2, 2, 3), dtype=np.uint8)
            with (_request.active("batch") if in_request
                  else contextlib.nullcontext()):
                pool.map_into(tf, [1, 2, 3, 4, 5, 6], [None] * 6, out)
        finally:
            pool.close()
        c1, w1, u1 = groll.totals()["decode.worker"]
        assert c1 - c0 >= 6
        assert 0 < u1 - u0 <= (w1 - w0) + 5000

    def test_prefetcher_stall_events_and_global_gauge(self):
        import time as _time

        from strom.delivery.prefetch import Prefetcher
        from strom.obs.events import ring as groll
        from strom.utils.stats import global_stats

        def slow():
            _time.sleep(0.05)
            return 1

        pf = Prefetcher(iter([slow, slow]), depth=1)
        try:
            assert next(pf) == 1
            assert next(pf) == 1
        finally:
            pf.close()
        assert pf.data_stall_steps >= 1
        # satellite: the stall counter is mirrored into the GLOBAL registry
        # (appears in /metrics and bench JSON without bespoke plumbing)
        assert global_stats.gauge("prefetch_data_stall_steps").value \
            == pf.data_stall_steps
        assert global_stats.gauge("prefetch_depth").value >= 1
        evs = groll.snapshot()
        assert any(e["name"] == "prefetch.stall_wait"
                   and e["cat"] == "ingest_wait" for e in evs)
        assert any(e["name"] == "prefetch.state"
                   and e["args"]["state"] == "stall" for e in evs)
