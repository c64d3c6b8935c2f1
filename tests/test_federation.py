"""Cluster observability plane (ISSUE 18): federation + fleet health.

Covers the acceptance invariants directly:

- merge math: the cluster aggregate equals the element-wise SUM of the
  per-host snapshots (counters, gauges, histogram buckets), with
  percentiles RE-DERIVED from the merged buckets (never averaged);
- missing-host tolerance: a host lacking a key simply doesn't contribute;
- stale ageing: a host whose scrape stops lands unhealthy after
  ``stale_s`` and its last counters drop out of the aggregate;
- progress-stall watchdog: a host that scrapes fine but whose progress
  counters stop advancing flips unhealthy (hung-but-listening);
- the unhealthy transition fires the remote flight trigger ONCE and
  dumps a host-stamped local bundle;
- a live 2-context ``/cluster`` route serves per-host rows + the summed
  aggregate with ``cluster_hosts_unhealthy == 0``;
- a 2-process subprocess run leaves per-host trace files whose merge
  carries cross-host flow-linked peer-fetch spans under one req id.
"""

import glob
import json
import os
import time
import urllib.request

import pytest

from strom.config import StromConfig
from strom.delivery.core import StromContext
from strom.obs.federation import ClusterView, FED_FIELDS, merge_snapshots
from strom.utils.stats import _Histogram, percentile_from_buckets


def _hist_snap(stem, buckets, total_us):
    """One histogram's registry-snapshot keys (stats.snapshot scheme)."""
    h = _Histogram()
    h.add_buckets(buckets, total_us)
    return {f"{stem}_hist": list(h.buckets), f"{stem}_count": h.count,
            f"{stem}_total_us": h.total_us, f"{stem}_mean_us": h.mean_us,
            f"{stem}_p50_us": h.percentile(0.50),
            f"{stem}_p99_us": h.percentile(0.99)}


def _synth(reads, burning, buckets, total_us):
    snap = {"engine_reads": reads, "slo_burning": burning}
    snap.update(_hist_snap("lat", buckets, total_us))
    return snap


# -- merge math ---------------------------------------------------------------

class TestMergeSnapshots:
    def test_aggregate_equals_sum(self):
        """3 synthetic hosts: every counter/gauge sums, histogram buckets
        merge element-wise, and count/total follow."""
        b1 = [0] * 24
        b1[3], b1[10] = 5, 2
        b2 = [0] * 24
        b2[3], b2[20] = 1, 1
        b3 = [0] * 24
        b3[0] = 7
        snaps = {"a": _synth(10, True, b1, 900.0),
                 "b": _synth(32, False, b2, 5000.0),
                 "c": _synth(0, False, b3, 70.0)}
        agg = merge_snapshots(snaps)
        assert agg["engine_reads"] == 42
        assert agg["slo_burning"] == 1  # bools sum as int
        assert agg["lat_hist"] == [x + y + z
                                   for x, y, z in zip(b1, b2, b3)]
        assert agg["lat_count"] == sum(b1) + sum(b2) + sum(b3)
        assert agg["lat_total_us"] == pytest.approx(5970.0)

    def test_percentiles_rederived_not_summed(self):
        """The merged p99 must come from the merged buckets — a sum (or
        average) of per-host p99s is not a percentile of anything."""
        lo = [0] * 24
        lo[2] = 100  # 100 obs in [4, 8) us
        hi = [0] * 24
        hi[12] = 1  # 1 obs in [4096, 8192) us
        snaps = {"a": _synth(0, False, lo, 600.0),
                 "b": _synth(0, False, hi, 5000.0)}
        agg = merge_snapshots(snaps)
        merged = [x + y for x, y in zip(lo, hi)]
        assert agg["lat_p99_us"] == percentile_from_buckets(merged, 0.99)
        assert agg["lat_p99_us"] != snaps["a"]["lat_p99_us"] + \
            snaps["b"]["lat_p99_us"]
        # mean re-derived from merged totals, not averaged
        assert agg["lat_mean_us"] == pytest.approx(5600.0 / 101)

    def test_missing_host_tolerance(self):
        """A host lacking a key (or the histogram) contributes nothing for
        it; the others still sum."""
        b = [0] * 24
        b[5] = 3
        snaps = {"a": _synth(7, False, b, 100.0),
                 "b": {"engine_reads": 5},  # no histogram at all
                 "c": {"other_counter": 2.5}}
        agg = merge_snapshots(snaps)
        assert agg["engine_reads"] == 12
        assert agg["other_counter"] == 2.5
        assert agg["lat_count"] == 3
        assert merge_snapshots({}) == {}

    def test_non_numeric_leaves_dropped(self):
        agg = merge_snapshots({"a": {"name": "worker-a", "n": 1},
                               "b": {"name": "worker-b", "n": 2}})
        assert agg == {"n": 3}


# -- ClusterView health machine (injected fetch/flight, no sockets) ----------

def _snapshot_doc(*, serves=0, traced=0, goodput=97.5, progress=0):
    return {"sections": {"dist": {"peer_serves": serves,
                                  "peer_serves_traced": traced,
                                  "peer_hits": 3, "peer_misses": 1},
                         "steps": {"goodput_pct": goodput}},
            "global": {"ssd2tpu_bytes": progress,
                       "sched_queue_wait_p99_us": 128.0,
                       "slo_burning": 0}}


class TestClusterView:
    def _view(self, hosts, fetch, **kw):
        kw.setdefault("publish", False)
        kw.setdefault("start", False)
        return ClusterView(hosts, fetch_fn=fetch, **kw)

    def test_fields_and_rows(self):
        docs = {"h0:1": _snapshot_doc(serves=10, traced=8, progress=100),
                "h1:1": _snapshot_doc(serves=10, traced=2, progress=50)}
        view = self._view({"h0": "h0:1", "h1": "h1:1"},
                          lambda addr: docs[addr])
        view.poll_now()
        st = view.stats()
        assert set(st) == set(FED_FIELDS)
        assert st["cluster_hosts"] == 2
        assert st["cluster_hosts_unhealthy"] == 0
        assert st["cluster_trace_linked_ratio"] == 0.5
        assert st["cluster_scrape_lag_p99_us"] > 0
        doc = view.snapshot()
        row = doc["hosts"]["h0"]
        assert row["addr"] == "h0:1" and row["healthy"]
        assert row["goodput_pct"] == 97.5
        assert row["peer_hit_ratio"] == 0.75
        assert row["sched_queue_wait_p99_us"] == 128.0
        # aggregate == sum of the per-host globals
        assert doc["aggregate"]["ssd2tpu_bytes"] == 150
        view.close()

    def test_stale_host_ages_out_and_fires_flight_once(self):
        alive = {"ok": True}
        flights, dumps = [], []

        class Rec:
            def dump(self, reason, note=""):
                dumps.append((reason, note))

        def fetch(addr):
            if addr == "bad:1" and not alive["ok"]:
                raise OSError("connection refused")
            return _snapshot_doc(progress=7)

        view = self._view({"good": "good:1", "bad": "bad:1"}, fetch,
                          flight_fn=flights.append, recorder=Rec(),
                          stale_s=0.08, stall_s=60.0)
        view.poll_now()
        assert view.stats()["cluster_hosts_unhealthy"] == 0
        alive["ok"] = False
        time.sleep(0.12)
        view.poll_now()
        view.poll_now()  # still unhealthy: must NOT fire again
        st = view.stats()
        assert st["cluster_hosts_unhealthy"] == 1
        assert flights == ["bad:1"]
        assert dumps == [("cluster_unhealthy", "host=bad")]
        # the dead host's last counters are OUT of the aggregate
        assert view.snapshot()["aggregate"]["ssd2tpu_bytes"] == 7
        # recovery re-arms the one-shot
        alive["ok"] = True
        view.poll_now()
        assert view.stats()["cluster_hosts_unhealthy"] == 0
        alive["ok"] = False
        time.sleep(0.12)
        view.poll_now()
        assert flights == ["bad:1", "bad:1"]
        view.close()

    def test_progress_stall_flags_unhealthy(self):
        """Scrapes keep succeeding but the progress counters never move:
        hung-but-listening must flip unhealthy after stall_s."""
        view = self._view({"h": "h:1"},
                          lambda a: _snapshot_doc(progress=42),
                          stale_s=60.0, stall_s=0.08)
        view.poll_now()
        assert view.stats()["cluster_hosts_unhealthy"] == 0
        time.sleep(0.12)
        view.poll_now()
        assert view.stats()["cluster_hosts_unhealthy"] == 1
        view.close()

    def test_never_scraped_grace_then_unhealthy(self):
        def fetch(addr):
            raise OSError("down from the start")

        view = self._view({"h": "h:1"}, fetch, stale_s=0.08)
        view.poll_now()
        assert view.stats()["cluster_hosts_unhealthy"] == 0  # grace
        time.sleep(0.12)
        view.poll_now()
        assert view.stats()["cluster_hosts_unhealthy"] == 1
        view.close()


# -- live /cluster over two real contexts -------------------------------------

def test_cluster_route_live_two_contexts(tmp_path):
    """Two StromContexts in one process, each serving /stats; the first
    attaches a ClusterView over both and serves /cluster: per-host rows,
    aggregate == sum of the scraped globals, zero unhealthy hosts."""
    cfg = StromConfig(engine="python", queue_depth=4, num_buffers=4)
    ctx0 = StromContext(cfg, metrics_port=0)
    ctx1 = StromContext(cfg, metrics_port=0)
    try:
        addrs = {f"h{i}": f"127.0.0.1:{c.metrics_server.port}"
                 for i, c in enumerate((ctx0, ctx1))}
        view = ctx0.attach_cluster(addrs, interval_s=0.1, publish=False)
        assert ctx0.cluster_view is view
        view.poll_now()
        globals_ = {}
        for h, a in addrs.items():
            with urllib.request.urlopen(f"http://{a}/stats?sections=dist",
                                        timeout=10) as r:
                globals_[h] = json.loads(r.read())["global"]
        with urllib.request.urlopen(
                f"http://{addrs['h0']}/cluster", timeout=10) as r:
            doc = json.loads(r.read())
        assert doc["cluster_hosts"] == 2
        assert doc["cluster_hosts_unhealthy"] == 0
        assert set(doc["hosts"]) == {"h0", "h1"}
        assert all(row["healthy"] for row in doc["hosts"].values())
        # the aggregate is the SUM of the per-host global snapshots (both
        # contexts share one process-global registry, so h0 == h1 and the
        # aggregate is exactly 2x — the invariant is still sum-of-parts)
        expect = merge_snapshots(globals_)
        for k in ("events_dropped",):
            doc["aggregate"].pop(k, None)
            expect.pop(k, None)
        for k, v in expect.items():
            assert doc["aggregate"].get(k) == pytest.approx(v), k
    finally:
        ctx0.close()
        ctx1.close()
    # a context without attach_cluster 404s the route
    ctx = StromContext(cfg, metrics_port=0)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{ctx.metrics_server.port}/cluster",
                timeout=10)
        assert ei.value.code == 404
    finally:
        ctx.close()


def test_unhealthy_host_leaves_stamped_bundle(tmp_path):
    """Killing a worker flips cluster_hosts_unhealthy to 1 and the
    coordinator dumps a flight bundle whose manifest carries the host
    stamp + peer addresses (the fleet-attribution contract)."""
    fdir = str(tmp_path / "fl")
    cfg0 = StromConfig(engine="python", queue_depth=4, num_buffers=4,
                       flight_dir=fdir)
    cfg1 = StromConfig(engine="python", queue_depth=4, num_buffers=4)
    ctx0 = StromContext(cfg0, metrics_port=0)
    ctx1 = StromContext(cfg1, metrics_port=0)
    killed = False
    try:
        view = ctx0.attach_cluster(
            {"h0": f"127.0.0.1:{ctx0.metrics_server.port}",
             "h1": f"127.0.0.1:{ctx1.metrics_server.port}"},
            interval_s=0.1, stale_s=0.3, publish=False, start=False)
        view.poll_now()
        assert view.stats()["cluster_hosts_unhealthy"] == 0
        ctx1.close()  # the "kill"
        killed = True
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            view.poll_now()
            if view.stats()["cluster_hosts_unhealthy"] == 1:
                break
            time.sleep(0.1)
        assert view.stats()["cluster_hosts_unhealthy"] == 1
        bundles = sorted(glob.glob(os.path.join(fdir, "flight-*")))
        assert bundles, "unhealthy transition left no local bundle"
        from strom.obs.flight import load_bundle
        man = load_bundle(bundles[-1])["manifest"]
        assert man["reason"] == "cluster_unhealthy"
        assert man["note"] == "host=h1"
        assert man["host"] and ":" in man["host"]  # hostname:pid
        assert isinstance(man["peer_addrs"], list)
    finally:
        ctx0.close()
        if not killed:
            ctx1.close()


# -- 2-process run: merged trace with cross-host flow-linked spans ------------

def test_two_proc_merged_trace_links_hosts(tmp_path):
    """The acceptance trace artifact: a 2-process dist run leaves
    trace_<rank>.json per host; merged, the peer fetches appear as ONE
    reqx flow chain per fetch — client 's'+'f' on the asking host,
    server 't' spans on the serving host, all billing the same req id —
    and rank 0's result carries the FED fields with zero unhealthy."""
    from strom.dist.launch import launch_local, make_fixture
    from strom.obs.chrome_trace import load_events, merge_host_traces

    data = str(tmp_path / "data")
    make_fixture(data, files=4, records=48, seq_len=16)
    run = str(tmp_path / "run")
    results = launch_local(2, data, run, steps=4, batch=8, seq_len=16)
    for r, res in enumerate(results):
        assert res.get("rc") == 0 and res.get("ok"), \
            f"worker {r}: {res.get('tail', res)}"
    # rank 0 federated the fleet during the run
    r0 = results[0]
    assert r0["cluster_hosts"] == 2
    assert r0["cluster_hosts_unhealthy"] == 0
    assert r0["cluster_trace_linked_ratio"] > 0
    host_events = {}
    for rank in (0, 1):
        path = os.path.join(run, f"trace_{rank}.json")
        assert os.path.exists(path), f"worker {rank} left no trace"
        host_events[f"rank{rank}"] = load_events(path)

    # per-flow census: phases seen per host for every reqx chain
    flows: dict = {}
    for host, evs in host_events.items():
        for e in evs:
            if e.get("cat") == "reqx" and e.get("ph") in ("s", "t", "f"):
                flows.setdefault(e["id"], {}).setdefault(host, set()) \
                    .add(e["ph"])
    linked = {fid: by_host for fid, by_host in flows.items()
              if len(by_host) >= 2}
    assert linked, "no cross-host flow-linked peer fetch in the traces"
    fid, by_host = next(iter(linked.items()))
    client = next(h for h, ps in by_host.items() if "s" in ps)
    server = next(h for h, ps in by_host.items() if "t" in ps)
    assert client != server
    # both sides billed the same request id: the client's peer.fetch span
    # carries args.flow == fid and args.req; the server's spans (bound to
    # the same flow) carry the SAME args.req
    fetch = next(e for e in host_events[client]
                 if e.get("name") == "peer.fetch"
                 and (e.get("args") or {}).get("flow") == fid)
    rid = fetch["args"]["req"]
    srv_spans = [e for e in host_events[server] if e.get("ph") == "X"
                 and (e.get("args") or {}).get("req") == rid]
    assert {"peer.queue", "peer.grant", "peer.send"} <= \
        {e["name"] for e in srv_spans}, srv_spans
    # the merged document keeps both hosts as process rows and the flow
    # events on both sides of the arrow
    doc = merge_host_traces(host_events)
    pids = {te["pid"] for te in doc["traceEvents"]
            if te.get("cat") == "reqx" and te.get("id") == fid}
    assert len(pids) == 2, "merged flow chain lost a side"
    assert set(doc["otherData"]["clock_shifts_us"]) == {"rank0", "rank1"}


def test_fed_fields_lift_into_measure_ingest(tmp_path):
    """measure_ingest folds rank 0's federation gauges into its result
    columns."""
    from strom.dist.launch import measure_ingest

    res = measure_ingest(2, str(tmp_path), steps=3, batch=8, seq_len=16)
    assert res["dist_ok"] == 1
    for k in FED_FIELDS:
        assert k in res, k
    assert res["cluster_hosts"] == 2
    assert res["cluster_hosts_unhealthy"] == 0
