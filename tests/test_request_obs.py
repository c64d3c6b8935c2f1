"""Causal request tracing, exemplars, SLO engine, history, strom_top
(ISSUE 8 tentpole).

The acceptance scenario lives in TestAcceptance: a two-tenant run with a
deliberately slow/throttled gather must yield (a) a Perfetto-loadable
trace whose queue→grant→engine→decode→put spans all carry the request's
req_id with flow events connecting them, (b) that request's span tree in
the exemplar store while fast requests are discarded, and (c) /slo
reporting the burn with the throttled tenant flagged on /tenants — with
strom_top --once rendering the per-tenant table from the live server.
"""

from __future__ import annotations

import importlib.util
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from strom.config import StromConfig
from strom.obs import request as obs_request
from strom.obs.events import EventRing, ring as global_ring
from strom.obs.exemplars import ExemplarStore, store as global_store
from strom.obs.history import StatsHistory
from strom.obs.slo import SLO_BENCH_FIELDS, SLO_FIELDS, SloEngine, SloTarget

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_ROOT, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class FakeReq:
    """Duck-typed Request for store/SLO unit tests."""

    def __init__(self, tenant="t", kind="gather", dur_us=1000.0,
                 throttled=False, error=None, queue_wait_us=0.0):
        self.id = 1
        self.tenant = tenant
        self.kind = kind
        self.dur_us = dur_us
        self.throttled = throttled
        self.error = error
        self.queue_wait_us = queue_wait_us
        self.t0_us = 0.0
        self.spans_dropped = 0
        self.spans = []

    def to_doc(self):
        return {"req": self.id, "tenant": self.tenant, "kind": self.kind,
                "t0_us": self.t0_us, "dur_us": self.dur_us,
                "queue_wait_us": self.queue_wait_us,
                "throttled": self.throttled, "error": self.error,
                "spans_dropped": 0, "spans": list(self.spans)}


# --------------------------------------------------------------- ring flows
class TestFlowEvents:
    def test_flow_events_snapshot_and_export(self):
        ring = EventRing(capacity=64)
        ring.flow("s", 7, "req.gather", "req")
        ring.flow("t", 7, "req.gather", "req")
        ring.flow("f", 7, "req.gather", "req")
        snap = ring.snapshot()
        assert [e["ph"] for e in snap] == ["s", "t", "f"]
        assert all(e["id"] == 7 for e in snap)

        from strom.obs.chrome_trace import to_trace_events

        tes = to_trace_events(snap)
        assert [te["ph"] for te in tes] == ["s", "t", "f"]
        assert all(te["id"] == 7 for te in tes)
        # steps/ends bind to the enclosing slice; starts don't need bp
        assert "bp" not in tes[0] and tes[1]["bp"] == "e"

    def test_flow_events_roundtrip_through_file(self, tmp_path):
        from strom.obs import chrome_trace

        ring = EventRing(capacity=16)
        with ring.span("work", cat="read"):
            ring.flow("s", 3, "req.gather", "req")
        p = str(tmp_path / "t.json")
        chrome_trace.dump(p, ring=ring)
        back = chrome_trace.load_events(p)
        phs = {e["ph"] for e in back}
        assert phs == {"X", "s"}
        assert next(e for e in back if e["ph"] == "s")["id"] == 3

    def test_flow_events_invisible_to_stall_attribution(self):
        from strom.obs import stall

        ring = EventRing(capacity=16)
        ring.flow("s", 1, "req.x", "req")
        ring.complete(0.0, 100.0, "ingest_wait", "pipeline.next")
        assert stall.steps_summary(ring.snapshot())["steps_observed"] == 1


# ------------------------------------------------------------ request object
class TestRequest:
    def test_span_tree_parent_links_and_args(self):
        global_ring.clear()
        req = obs_request.Request("gather", "tx")
        with req.span("outer", cat="read"):
            with req.span("inner", cat="sched"):
                pass
        req.finish()
        names = {s[0]: s for s in req.spans}
        assert names["inner"][5] == "outer"      # parent link
        assert names["outer"][5] is None
        evs = [e for e in global_ring.snapshot() if e.get("ph") == "X"]
        by_name = {e["name"]: e for e in evs}
        assert by_name["inner"]["args"]["req"] == req.id
        assert by_name["inner"]["args"]["parent"] == "outer"
        flows = [e for e in global_ring.snapshot() if e.get("ph") in "st"]
        assert [e["ph"] for e in flows] == ["s", "t"]
        assert all(e["id"] == req.id for e in flows)

    def test_span_tree_bounded(self):
        req = obs_request.Request("gather")
        for i in range(obs_request.MAX_SPANS_PER_REQUEST + 10):
            req.record(f"s{i}", "read", 0.0, 1.0)
        assert len(req.spans) == obs_request.MAX_SPANS_PER_REQUEST
        assert req.spans_dropped == 10
        req.finish()

    def test_active_reuses_enclosing_request(self):
        with obs_request.active("batch", "t0") as outer:
            with obs_request.active("gather", "t0") as inner:
                assert inner is outer
            assert not outer._finished  # inner exit must not finish it
        assert outer._finished

    def test_finish_idempotent_and_observers(self):
        seen = []
        obs_request.add_observer(seen.append)
        try:
            with obs_request.active("gather", "t0"):
                pass
        finally:
            obs_request.remove_observer(seen.append)
        assert len(seen) == 1 and seen[0].tenant == "t0"

    def test_attach_propagates_across_threads(self):
        req = obs_request.Request("batch", "t0")
        got = []

        def worker():
            with obs_request.attach(req):
                got.append(obs_request.current())

        t = threading.Thread(target=worker)
        t.start()
        t.join()
        assert got == [req]
        req.finish()

    def test_error_marked_on_exception(self):
        with pytest.raises(ValueError):
            with obs_request.active("gather", "terr") as req:
                raise ValueError("boom")
        assert req.error and "boom" in req.error


# ------------------------------------------------------------ exemplar store
class TestExemplarStore:
    def test_slow_retained_fast_discarded(self):
        st = ExemplarStore(per_tenant=4, min_window=8)
        for _ in range(20):
            assert not st.offer(FakeReq(dur_us=1000.0))
        assert st.offer(FakeReq(dur_us=50_000.0))  # above rolling p99
        snap = st.snapshot()
        assert snap["exemplars_retained"] == 1
        assert [e["dur_us"] for e in snap["tenants"]["t"]] == [50_000.0]

    def test_no_verdict_below_min_window(self):
        st = ExemplarStore(min_window=16)
        assert not st.offer(FakeReq(dur_us=10_000_000.0))  # cold store

    def test_throttled_and_errored_always_retained(self):
        st = ExemplarStore(min_window=16)
        assert st.offer(FakeReq(throttled=True))
        assert st.offer(FakeReq(error="EngineError: boom"))
        s = st.stats()
        assert s["exemplars_throttled"] == 1
        assert s["exemplars_errored"] == 1

    def test_windows_keyed_by_kind(self):
        st = ExemplarStore(min_window=8)
        for _ in range(10):   # slow "step" traffic must not define gather p99
            st.offer(FakeReq(kind="step", dur_us=1_000_000.0))
        for _ in range(10):
            st.offer(FakeReq(kind="gather", dur_us=100.0))
        assert st.offer(FakeReq(kind="gather", dur_us=5_000.0))

    def test_bounded_per_tenant_drop_oldest(self):
        st = ExemplarStore(per_tenant=2, min_window=4)
        for i in range(5):
            st.offer(FakeReq(dur_us=float(i), throttled=True))
        kept = st.exemplars("t")
        assert len(kept) == 2
        assert [e["dur_us"] for e in kept] == [3.0, 4.0]

    def test_clear(self):
        st = ExemplarStore()
        st.offer(FakeReq(throttled=True))
        st.clear()
        assert st.stats()["exemplars_offered"] == 0
        assert st.exemplars() == []


# ---------------------------------------------------------------- SLO engine
class TestSloEngine:
    def test_burn_rates_fast_and_slow_windows(self):
        t = [1000.0]
        eng = SloEngine(fast_s=60, slow_s=600, bucket_s=10,
                        clock=lambda: t[0],
                        default_target=SloTarget(gather_p99_us=1000.0,
                                                 objective_pct=90.0))
        for _ in range(8):
            eng.observe("a", 100.0)
        for _ in range(2):
            eng.observe("a", 50_000.0)  # bad
        fast, slow = eng.burn_rates("a")
        # 2 bad / 10 total = 0.2 bad frac over a 0.1 budget -> burn 2.0
        assert fast == pytest.approx(2.0)
        assert slow == pytest.approx(2.0)
        assert eng.burning("a")
        # advance past the fast window: the spike ages out of it but not
        # the slow one -> not burning any more (multi-window rule)
        t[0] += 120
        fast2, slow2 = eng.burn_rates("a")
        assert fast2 == 0.0 and slow2 == pytest.approx(2.0)
        assert not eng.burning("a")

    def test_queue_wait_counts_as_bad(self):
        eng = SloEngine(default_target=SloTarget(queue_wait_p99_us=100.0))
        eng.observe("a", 10.0, queue_wait_us=5_000.0)
        assert eng.burn_rates("a")[0] > 1.0

    def test_per_tenant_targets_and_report_gauges(self):
        from strom.utils.stats import global_stats

        eng = SloEngine()
        eng.set_target("tight", gather_p99_us=10.0, objective_pct=50.0)
        eng.observe("tight", 100.0)   # bad under the tight target
        eng.observe("loose", 100.0)   # good under the default
        rep = eng.report()
        assert rep["tenants"]["tight"]["slo_burning"]
        assert not rep["tenants"]["loose"]["slo_burning"]
        snap = global_stats.scoped(tenant="tight").snapshot()
        for g in SLO_FIELDS:
            assert g in snap, f"missing labeled gauge {g}"
        assert snap["slo_burning"] == 1

    def test_set_target_rejects_typos(self):
        with pytest.raises(TypeError):
            SloEngine().set_target("a", gather_p99_uss=5)

    def test_step_requests_do_not_feed_slo(self):
        eng = SloEngine(default_target=SloTarget(gather_p99_us=1.0))
        eng.observe_request(FakeReq(kind="step", dur_us=1e9))
        assert eng.burn_rates("t") == (0.0, 0.0)

    def test_ok_and_stats(self):
        eng = SloEngine(default_target=SloTarget(gather_p99_us=10.0,
                                                 objective_pct=50.0))
        assert eng.ok()
        eng.observe("a", 100.0)
        assert not eng.ok()
        s = eng.stats()
        assert s["slo_tenants"] == 1
        assert s["slo_tenants_burning"] == 1
        assert s["slo_worst_burn_fast"] > 1.0


# ------------------------------------------------------------------- history
class TestStatsHistory:
    def test_sample_rate_and_bounds(self):
        from strom.utils.stats import global_stats

        t = [100.0]
        h = StatsHistory(interval_s=1.0, capacity=5, clock=lambda: t[0],
                         start=False)
        c = global_stats.counter("history_test_bytes")
        for i in range(8):
            c.add(1000)
            h.sample()
            t[0] += 1.0
        samples = h.samples()
        assert len(samples) == 5  # bounded, drop-oldest
        assert h.rate("history_test_bytes") == pytest.approx(1000.0)
        assert h.rate("no_such_key") is None
        h.close()

    def test_scoped_series_and_key_filter(self):
        from strom.utils.stats import global_stats

        t = [0.0]
        h = StatsHistory(clock=lambda: t[0], start=False)
        scope = global_stats.scoped(tenant="ht0")
        scope.add("history_scoped_ops", 5)
        h.sample()
        t[0] += 2.0
        scope.add("history_scoped_ops", 5)
        h.sample()
        assert h.rate("history_scoped_ops",
                      scope='tenant="ht0"') == pytest.approx(2.5)
        keyed = h.samples(keys=["history_scoped_ops"])
        assert all(set(s) <= {"ts_s", "history_scoped_ops"} for s in keyed)
        h.close()


# ------------------------------------------------- server routes (new + conc)
class TestServerRoutes:
    def _get(self, port, route):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{route}", timeout=10) as r:
            return r.status, r.read()

    def test_trace_filters_and_stats_sections(self, tmp_path):
        from strom.delivery.core import StromContext

        p = str(tmp_path / "f.bin")
        with open(p, "wb") as f:
            f.write(os.urandom(64 * 1024))
        ctx = StromContext(StromConfig(engine="python", slab_pool_bytes=0,
                                       history_interval_s=0.1),
                           metrics_port=0)
        try:
            ctx.pread(p, 0, 4096)
            port = ctx.metrics_server.port
            _, body = self._get(port, "/trace?cat=read")
            doc = json.loads(body)
            cats = {e["cat"] for e in doc["traceEvents"]}
            assert cats <= {"read"} and cats
            _, body = self._get(port, "/trace?since_us=1e15")
            assert json.loads(body)["traceEvents"] == []
            _, body = self._get(port, "/stats?sections=slo")
            sections = json.loads(body)["sections"]
            assert "slo" in sections and "steps" not in sections
            _, body = self._get(port, "/slo")
            assert "tenants" in json.loads(body)
            time.sleep(0.3)
            _, body = self._get(port, "/history?keys=ssd2tpu_bytes")
            hist = json.loads(body)
            assert hist["samples"]
            assert all(set(s) <= {"ts_s", "ssd2tpu_bytes"}
                       for s in hist["samples"])
        finally:
            ctx.close()

    def test_post_tenants_concurrent_register_drain_never_500s(self):
        """ISSUE 8 satellite: parallel /tenants register/drain against a
        live scheduler must never 500 nor leak a partially-registered
        tenant (every registered row carries the full field set)."""
        from strom.delivery.core import StromContext

        ctx = StromContext(StromConfig(engine="python", slab_pool_bytes=0,
                                       hot_cache_bytes=8 << 20),
                           metrics_port=0)
        port = ctx.metrics_server.port
        bad: list = []

        def post(body: dict) -> int:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/tenants",
                data=json.dumps(body).encode(), method="POST")
            with urllib.request.urlopen(req, timeout=15) as r:
                return r.status

        def hammer(i: int) -> None:
            try:
                for k in range(6):
                    name = f"ct{(i + k) % 4}"
                    post({"op": "register", "name": name,
                          "priority": "training", "weight": 2,
                          "hot_cache_bytes": 1 << 20})
                    post({"op": "drain", "name": name, "timeout_s": 1})
                    self._get(port, "/tenants")
            except urllib.error.HTTPError as e:  # pragma: no cover
                bad.append(e.code)
            except Exception as e:  # pragma: no cover
                bad.append(repr(e))

        import urllib.error

        threads = [threading.Thread(target=hammer, args=(i,), daemon=True)
                   for i in range(6)]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not bad, bad
            _, body = self._get(port, "/tenants")
            rows = json.loads(body)["tenants"]
            need = {"priority", "weight", "queued_ops", "byte_budget",
                    "hot_cache_bytes"}
            for name, row in rows.items():
                assert need <= set(row), f"partial tenant row {name}: {row}"
            # every hammered tenant registered exactly once, fully
            assert {f"ct{i}" for i in range(4)} <= set(rows)
        finally:
            ctx.close()


# ------------------------------------------------------- trace_report rollup
class TestTraceReportRequests:
    def test_critical_path_and_tenant_table(self, tmp_path):
        tr = _load_tool("trace_report")
        events = [
            # req 1: umbrella + queue -> read -> decode chain (one lane)
            {"ph": "X", "ts_us": 0.0, "dur_us": 100.0, "tid": 1,
             "cat": "batch", "name": "umbrella", "args": {"req": 1}},
            {"ph": "X", "ts_us": 0.0, "dur_us": 10.0, "tid": 1,
             "cat": "sched", "name": "sched.queue", "args": {"req": 1}},
            {"ph": "X", "ts_us": 10.0, "dur_us": 50.0, "tid": 1,
             "cat": "read", "name": "engine.slice", "args": {"req": 1}},
            {"ph": "X", "ts_us": 60.0, "dur_us": 40.0, "tid": 2,
             "cat": "decode", "name": "decode.worker", "args": {"req": 1}},
            {"ph": "i", "ts_us": 100.0, "tid": 1, "cat": "req",
             "name": "req.done",
             "args": {"req": 1, "tenant": "t0", "kind": "batch",
                      "dur_us": 100.0, "throttled": True}},
            {"ph": "i", "ts_us": 5.0, "tid": 1, "cat": "req",
             "name": "req.done",
             "args": {"req": 2, "tenant": "t1", "kind": "gather",
                      "dur_us": 5.0}},
        ]
        rows = tr.request_rollup(events)
        assert rows[0]["req"] == 1 and rows[0]["throttled"]
        # the umbrella span is excluded; the chain is the causal sequence
        assert rows[0]["path"].split("→")[0].startswith("sched.queue")
        assert "engine.slice" in rows[0]["path"]
        assert "decode.worker" in rows[0]["path"]
        assert "umbrella" not in rows[0]["path"]
        tenants = tr.tenant_table(events)
        assert [t[0] for t in tenants] == ["t0", "t1"]
        assert tenants[0][4] == 1  # throttled count

    def test_report_renders_request_sections(self, tmp_path, capsys):
        tr = _load_tool("trace_report")
        from strom.obs import chrome_trace

        ring = EventRing(capacity=64)
        req = obs_request.Request("gather", "tr0")
        with obs_request.attach(req):
            with req.span("strom.read_segments", cat="read"):
                time.sleep(0.001)
        req.finish()
        p = str(tmp_path / "t.json")
        chrome_trace.dump(p, ring=global_ring)
        assert tr.main([p]) == 0
        out = capsys.readouterr().out
        assert "slowest requests" in out
        assert "tenant" in out


# ----------------------------------------------------------------- strom_top
class TestStromTop:
    def test_rows_and_render_pure(self):
        top = _load_tool("strom_top")
        cur = {
            "t": 10.0,
            "global": {"pipeline_steps": 3, "ssd2tpu_bytes": 1 << 20},
            "sections": {"sched": {"sched_active_grants": 1,
                                   "sched_queued_ops": 2,
                                   "slab_pool_admission_waits": 0}},
            "scopes": {"t0": {"sched_queue_wait_p99_us": 2048.0,
                              "sched_granted_bytes": 3_000_000,
                              "cache_hit_bytes": 75, "cache_miss_bytes": 25}},
            "tenants": {"t0": {"priority": "training", "queued_ops": 2,
                               "active_grants": 1, "slo_burning": True}},
            "admission": {}, "slo": {"t0": {"slo_burn_fast": 3.0,
                                            "slo_burn_slow": 2.0,
                                            "slo_burning": True}},
        }
        prev = {"t": 9.0, "scopes": {"t0": {"sched_granted_bytes":
                                            1_000_000}},
                "tenants": {}, "slo": {}, "global": {}, "sections": {},
                "admission": {}}
        rows = top.rows(cur, prev)
        assert rows[0]["tenant"] == "t0"
        assert rows[0]["granted_mb_s"] == pytest.approx(2.0)
        assert rows[0]["hit_pct"] == pytest.approx(75.0)
        assert rows[0]["slo"] == "BURNING"
        text = top.render(cur, prev)
        assert "t0" in text and "BURNING" in text

    def test_scope_tenant_extraction_prefers_pure_scope(self):
        top = _load_tool("strom_top")
        scopes = {
            'pipeline="resnet",tenant="t0"': {"a": 1},
            'tenant="t0"': {"a": 2},
        }
        assert top._scope_tenants(scopes)["t0"]["a"] == 2


# -------------------------------------------------------------- acceptance
class TestAcceptance:
    @pytest.fixture()
    def wds(self, tmp_path):
        cv2 = pytest.importorskip("cv2")
        from tests.test_formats import make_wds_shard

        rng = np.random.default_rng(9)
        samples = []
        for i in range(8):
            img = rng.integers(0, 256, (48, 48, 3), dtype=np.uint8)
            ok, buf = cv2.imencode(".jpg", img)
            assert ok
            samples.append((f"s{i:04d}", {"jpg": buf.tobytes(),
                                          "cls": str(i % 3).encode()}))
        p = str(tmp_path / "acc.tar")
        make_wds_shard(p, samples)
        return [p]

    def test_two_tenant_slow_gather_end_to_end(self, wds, tmp_path,
                                               capsys):
        """The ISSUE 8 acceptance criterion, in one scenario."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from strom.delivery.core import StromContext
        from strom.obs import chrome_trace
        from strom.parallel.mesh import make_mesh
        from strom.pipelines import make_wds_vision_pipeline

        global_ring.clear()
        global_store.clear()
        datafile = str(tmp_path / "data.bin")
        with open(datafile, "wb") as f:
            f.write(os.urandom(1 << 20))

        ctx = StromContext(StromConfig(engine="python", slab_pool_bytes=0,
                                       history_interval_s=0.1),
                           metrics_port=0)
        try:
            # two tenants: "fast" unbudgeted interactive, "slow" strangled
            # by a tiny byte budget so its gathers queue on refills
            ctx.register_tenant("fast", priority="interactive")
            ctx.register_tenant("slow", byte_rate=1e6, byte_burst=1024)
            ctx.slo.set_target("slow", gather_p99_us=20_000,
                               queue_wait_p99_us=10_000)

            # the fast tenant's quick gathers fill its rolling window to
            # exactly min_window: the store judges none of them against a
            # p99 (at 16 samples that p99 is their max, and one scheduling
            # hiccup on a loaded host would make a later pread "slow"), so
            # only a throttle or an error could retain one
            n_fast = global_store.min_window
            for _ in range(n_fast):
                ctx.pread(datafile, 0, 4096, tenant="fast")
            # the deliberately slow gathers: the first rides the burst,
            # the rest wait out the 1MB/s refill (throttled + slow)
            for _ in range(3):
                ctx.pread(datafile, 0, 256 * 1024, tenant="slow")

            # one traced vision batch so the decode/put lane exists
            mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
            sharding = NamedSharding(mesh, P("dp", None, None, None))
            pipe = make_wds_vision_pipeline(
                ctx, wds, batch=4, image_size=32, sharding=sharding,
                decode_workers=2,
                scope={"pipeline": "resnet", "tenant": "fast"})
            try:
                next(pipe)[0].block_until_ready()
            finally:
                pipe.close()

            # ---- (a) Perfetto-loadable flow-connected trace ------------
            trace_path = str(tmp_path / "acc_trace.json")
            chrome_trace.dump(trace_path)
            events = chrome_trace.load_events(trace_path)
            spans = {}
            for e in events:
                rid = (e.get("args") or {}).get("req")
                if rid is not None and e["ph"] == "X":
                    spans.setdefault(rid, set()).add(e["name"])
            # a slow-tenant gather: queue -> grant -> engine slice, one id
            slow_req = next(
                rid for rid, names in spans.items()
                if "engine.slice" in names and "sched.queue" in names)
            assert {"sched.queue", "sched.grant", "engine.slice",
                    "strom.read_segments"} <= spans[slow_req]
            # the batch request: decode + put joined the same lane
            batch_req = next(
                rid for rid, names in spans.items()
                if "decode.worker" in names)
            assert "strom.device_put" in spans[batch_req]
            assert {"sched.queue", "sched.grant"} <= spans[batch_req]
            # flow events connect each lane (s first, then t's)
            flows = [e for e in events if e["ph"] in ("s", "t")]
            for rid in (slow_req, batch_req):
                chain = [e for e in flows if e.get("id") == rid]
                assert chain and chain[0]["ph"] == "s"
                assert all(e["ph"] == "t" for e in chain[1:])

            # ---- (b) exemplar store: slow retained, fast not -----------
            kept_slow = global_store.exemplars("slow")
            assert kept_slow, "throttled slow gathers must be retained"
            assert any(e["throttled"] for e in kept_slow)
            assert all(
                {"sched.queue", "strom.read_segments"}
                <= {s["name"] for s in e["spans"]} for e in kept_slow)
            # the fast tenant's plain preads were offered and discarded
            assert global_store.exemplars("fast") == []
            st = global_store.stats()
            assert st["exemplars_discarded"] >= n_fast

            # ---- (c) /slo burn + /tenants flag + strom_top -------------
            port = ctx.metrics_server.port
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/slo", timeout=10) as r:
                slo = json.loads(r.read())
            assert slo["tenants"]["slow"]["slo_burning"]
            assert slo["tenants"]["slow"]["slo_burn_fast"] > 1.0
            assert not slo["tenants"]["fast"]["slo_burning"]
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/tenants", timeout=10) as r:
                tenants = json.loads(r.read())
            assert tenants["tenants"]["slow"]["slo_burning"] is True
            assert tenants["tenants"]["fast"]["slo_burning"] is False

            top = _load_tool("strom_top")
            assert top.main(["--port", str(port), "--once"]) == 0
            out = capsys.readouterr().out
            assert "slow" in out and "fast" in out
            assert "BURNING" in out
        finally:
            ctx.close()


# ------------------------------------------------- review-hardening checks
class TestPerContextOwnership:
    """Requests carry their minting context's owner token; the process-
    global observer list must not let one context's SLO engine ingest a
    concurrent context's requests."""

    def test_owned_requests_feed_only_their_context(self):
        from strom.delivery.core import StromContext

        ctx_a = StromContext(StromConfig(engine="python", slab_pool_bytes=0))
        ctx_b = StromContext(StromConfig(engine="python", slab_pool_bytes=0))
        try:
            with obs_request.active("gather", "own_a",
                                    owner=ctx_a._req_owner):
                pass
            assert "own_a" in ctx_a.slo.tenants()
            assert "own_a" not in ctx_b.slo.tenants()
            # unowned requests (bare mint sites) are seen by every context
            with obs_request.active("gather", "own_none"):
                pass
            assert "own_none" in ctx_a.slo.tenants()
            assert "own_none" in ctx_b.slo.tenants()
        finally:
            ctx_a.close()
            ctx_b.close()

    def test_gathers_and_pipeline_steps_are_owner_stamped(self, tmp_path):
        from strom.delivery.core import StromContext

        p = str(tmp_path / "own.bin")
        with open(p, "wb") as f:
            f.write(os.urandom(8192))
        ctx = StromContext(StromConfig(engine="python", slab_pool_bytes=0))
        seen: list = []
        obs_request.add_observer(seen.append)
        try:
            ctx.pread(p, 0, 4096, tenant="ownt")
            gathers = [r for r in seen if r.kind == "gather"]
            assert gathers and all(r.owner is ctx._req_owner
                                   for r in gathers)
        finally:
            obs_request.remove_observer(seen.append)
            ctx.close()

    def test_grant_span_parent_captured_at_entry(self):
        """A streamed gather releases its grant on the pump thread; the
        sched.grant span must still parent-link to the span that was open
        on the SUBMITTING thread at entry, not the exit thread's stack."""
        from strom.delivery.core import StromContext

        ctx = StromContext(StromConfig(engine="python", slab_pool_bytes=0))
        try:
            req = obs_request.Request("gather", "gp0")
            with obs_request.attach(req):
                with req.span("outer.gather", cat="read"):
                    cm = ctx.scheduler.grant("gp0", 4096)
                    cm.__enter__()
            t = threading.Thread(target=cm.__exit__, args=(None,) * 3)
            t.start()
            t.join(timeout=30)
            by_name = {s[0]: s for s in req.spans}
            assert "sched.grant" in by_name
            assert by_name["sched.grant"][5] == "outer.gather"
        finally:
            ctx.close()


class TestMetricsSloRefresh:
    def test_metrics_scrape_alone_refreshes_slo_gauges(self):
        """The documented contract is labeled slo_* gauges on /metrics; a
        Prometheus-only deployment never hits /slo, so the scrape itself
        must refresh the burn-rate gauges."""
        from strom.delivery.core import StromContext

        ctx = StromContext(StromConfig(engine="python", slab_pool_bytes=0),
                           metrics_port=0)
        try:
            ctx.slo.set_target("m0", gather_p99_us=10.0)
            for _ in range(5):
                ctx.slo.observe("m0", 1000.0)
            port = ctx.metrics_server.port
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
                body = r.read().decode()
            burning = [ln for ln in body.splitlines()
                       if "slo_burning" in ln and 'tenant="m0"' in ln]
            assert burning, "labeled slo_burning gauge missing from /metrics"
            assert all(ln.rsplit(" ", 1)[1] == "1" for ln in burning)
        finally:
            ctx.close()


class TestTenantTableKinds:
    def test_tenant_table_excludes_step_requests(self):
        """Per-tenant percentiles must match req_lat's data-path-only
        policy: a step marker's (compute-dominated) wall never skews them."""
        tr = _load_tool("trace_report")
        events = [
            {"ph": "i", "ts_us": 1.0, "tid": 1, "cat": "req",
             "name": "req.done",
             "args": {"req": 1, "tenant": "t0", "kind": "gather",
                      "dur_us": 100.0}},
            {"ph": "i", "ts_us": 2.0, "tid": 1, "cat": "req",
             "name": "req.done",
             "args": {"req": 2, "tenant": "t0", "kind": "step",
                      "dur_us": 9e9}},
        ]
        rows = tr.tenant_table(events)
        assert len(rows) == 1
        tenant, n, p50_ms, p99_ms, throttled, errors = rows[0]
        assert tenant == "t0" and n == 1
        assert p99_ms == pytest.approx(0.1)
