"""Flight recorder (ISSUE 6 tentpole): watchdog, triggers, atomic bundles.

The invariants: a slow-but-ADVANCING run never trips the stall trigger
(progress is counter deltas, not wall-per-step); a genuinely wedged run
dumps exactly one bundle per stall episode; every dumped bundle is atomic
and round-trips through ``load_bundle``; SIGTERM/excepthook dumps chain
the previous handlers.
"""

import glob
import json
import os
import signal
import subprocess
import sys
import time

import pytest

from strom.obs import flight
from strom.obs.events import EventRing
from strom.obs.flight import (FLIGHT_FIELDS, FlightRecorder, capture_doc,
                              load_bundle, thread_stacks)


def mk_recorder(tmp_path, **kw):
    kw.setdefault("install_signal", False)
    kw.setdefault("install_excepthook", False)
    kw.setdefault("interval_s", 0.05)
    return FlightRecorder(str(tmp_path / "fl"), **kw)


def bundles(tmp_path):
    return sorted(glob.glob(str(tmp_path / "fl" / "flight-*")))


class TestWatchdog:
    def test_samples_accumulate_with_flight_fields(self, tmp_path):
        with mk_recorder(tmp_path) as rec:
            time.sleep(0.3)
            samples = rec.samples()
        assert samples
        assert set(samples[0]) == set(FLIGHT_FIELDS)

    def test_sample_ring_bounded(self, tmp_path):
        with mk_recorder(tmp_path, max_samples=8, interval_s=0.01) as rec:
            time.sleep(0.5)
            assert len(rec.samples()) <= 8

    def test_no_false_positive_while_progressing(self, tmp_path):
        """A deliberately slow step loop — progress every ~0.15s against a
        0.3s stall threshold — must never dump: any delta resets the
        stall clock."""
        val = [0]
        with mk_recorder(tmp_path, stall_s=0.3,
                         progress_fn=lambda: val[0]) as rec:
            for _ in range(8):  # ~1.2s of slow-but-advancing stepping
                time.sleep(0.15)
                val[0] += 1
            assert rec._dumps == 0
        assert bundles(tmp_path) == []

    def test_stall_dumps_once_per_episode(self, tmp_path):
        val = [0]
        with mk_recorder(tmp_path, stall_s=0.2,
                         progress_fn=lambda: val[0]) as rec:
            deadline = time.monotonic() + 5.0
            while rec._dumps == 0 and time.monotonic() < deadline:
                time.sleep(0.05)  # no progress: the watchdog should fire
            assert rec._dumps == 1
            time.sleep(0.5)  # STILL no progress: same episode, no re-dump
            assert rec._dumps == 1
            val[0] += 1  # recovery...
            time.sleep(0.3)
            while time.monotonic() < deadline and rec._dumps < 2:
                time.sleep(0.05)  # ...then a second stall episode
            assert rec._dumps == 2
        bs = bundles(tmp_path)
        assert len(bs) == 2
        assert all("stall" in b for b in bs)

    def test_stall_disabled_at_zero(self, tmp_path):
        with mk_recorder(tmp_path, stall_s=0.0,
                         progress_fn=lambda: 7) as rec:
            time.sleep(0.4)
            assert rec._dumps == 0


class TestBundle:
    def test_dump_round_trip(self, tmp_path):
        # seed the global exemplar store: a crash bundle must carry the
        # tail-sampled span trees of the slowest recent requests (ISSUE 8)
        from strom.obs.exemplars import store
        from strom.obs.request import Request

        store.clear()
        req = Request("gather", "flight-t0")
        req.note_queue_wait(123.0, throttled=True)
        req.finish()
        ring = EventRing(capacity=64)
        ring.complete(0.0, 5.0, "read", "t.read", {"bytes": 3})
        with mk_recorder(tmp_path, ring=ring) as rec:
            p = rec.dump("test", note="hello")
        b = load_bundle(p)
        assert b["manifest"]["reason"] == "test"
        assert b["manifest"]["note"] == "hello"
        assert b["manifest"]["fields"] == list(FLIGHT_FIELDS)
        assert b["manifest"]["samples"]  # at least the capture-time sample
        assert any(ev.get("name") == "t.read"
                   for ev in b["trace"]["traceEvents"])
        assert "global" in b["stats"] and "scopes" in b["stats"]
        assert "thread" in b["stacks"]
        # exemplars member round-trips, throttled request tree included,
        # and the watchdog samples carry the retention counter
        exs = b["exemplars"]["tenants"]["flight-t0"]
        assert any(e["req"] == req.id and e["throttled"] for e in exs)
        assert b["exemplars"]["exemplars_retained"] >= 1
        assert all("exemplars_retained" in s
                   for s in b["manifest"]["samples"])
        store.clear()

    def test_dump_atomic_no_tmp_left(self, tmp_path):
        with mk_recorder(tmp_path) as rec:
            rec.dump("a")
            rec.dump("a")
        assert not glob.glob(str(tmp_path / "fl" / ".tmp-*"))
        assert len(bundles(tmp_path)) == 2  # serials keep them apart

    def test_capture_doc_without_recorder(self):
        doc = capture_doc()
        assert doc["reason"] == "on_demand"
        assert "stacks" in doc and "trace" in doc

    def test_thread_stacks_sees_this_test(self):
        assert "test_thread_stacks_sees_this_test" in thread_stacks()


class TestTriggers:
    def test_sigterm_dumps_and_chains(self, tmp_path):
        """Child installs a prior SIGTERM handler (a program's own
        emergency flush), then the recorder; a SIGTERM must dump the bundle
        AND still run the prior handler."""
        child = tmp_path / "child.py"
        fdir = tmp_path / "fl"
        child.write_text(f"""
import os, signal, sys, time
sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})

def prev(signum, frame):
    os.write(1, b"PREV\\n")
    os._exit(0)

signal.signal(signal.SIGTERM, prev)
from strom.obs.flight import FlightRecorder
FlightRecorder({str(fdir)!r}, stall_s=0.0, interval_s=0.05)
os.write(1, b"READY\\n")
time.sleep(30)
""")
        p = subprocess.Popen([sys.executable, str(child)],
                             stdout=subprocess.PIPE)
        try:
            assert p.stdout.readline().strip() == b"READY"
            time.sleep(0.2)
            p.send_signal(signal.SIGTERM)
            out, _ = p.communicate(timeout=15)
        finally:
            if p.poll() is None:
                p.kill()
        assert b"PREV" in out
        assert p.returncode == 0  # the chained handler decided the exit
        bs = sorted(glob.glob(str(fdir / "flight-*")))
        assert len(bs) == 1
        assert load_bundle(bs[0])["manifest"]["reason"] == "sigterm"

    def test_sigterm_default_reraises(self, tmp_path):
        """Without a prior handler the process must still die BY SIGTERM
        (the driver's rc accounting keys off the wait status)."""
        child = tmp_path / "child.py"
        fdir = tmp_path / "fl"
        child.write_text(f"""
import os, sys, time
sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
from strom.obs.flight import FlightRecorder
FlightRecorder({str(fdir)!r}, stall_s=0.0, interval_s=0.05)
os.write(1, b"READY\\n")
time.sleep(30)
""")
        p = subprocess.Popen([sys.executable, str(child)],
                             stdout=subprocess.PIPE)
        try:
            assert p.stdout.readline().strip() == b"READY"
            time.sleep(0.2)
            p.send_signal(signal.SIGTERM)
            p.communicate(timeout=15)
        finally:
            if p.poll() is None:
                p.kill()
        assert p.returncode == -signal.SIGTERM
        assert glob.glob(str(fdir / "flight-*-sigterm-*"))

    def test_sigterm_sig_ign_stays_ignored(self, tmp_path):
        """A process that deliberately ignores SIGTERM must survive it
        with a recorder armed: dump the bundle, keep ignoring."""
        child = tmp_path / "child.py"
        fdir = tmp_path / "fl"
        child.write_text(f"""
import os, signal, sys, time
sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
signal.signal(signal.SIGTERM, signal.SIG_IGN)
from strom.obs.flight import FlightRecorder
FlightRecorder({str(fdir)!r}, stall_s=0.0, interval_s=0.05)
os.write(1, b"READY\\n")
time.sleep(2)
os.write(1, b"SURVIVED\\n")
""")
        p = subprocess.Popen([sys.executable, str(child)],
                             stdout=subprocess.PIPE)
        try:
            assert p.stdout.readline().strip() == b"READY"
            time.sleep(0.2)
            p.send_signal(signal.SIGTERM)
            out, _ = p.communicate(timeout=15)
        finally:
            if p.poll() is None:
                p.kill()
        assert b"SURVIVED" in out and p.returncode == 0
        assert glob.glob(str(fdir / "flight-*-sigterm-*"))

    def test_close_does_not_clobber_chained_recorder(self, tmp_path):
        """Recorder A closes while recorder B (created later, chained on
        top) is still live: B's hooks must stay installed."""
        import sys as _sys

        prev_hook = _sys.excepthook
        a = mk_recorder(tmp_path / "a", install_excepthook=True)
        b = mk_recorder(tmp_path / "b", install_excepthook=True)
        try:
            assert _sys.excepthook is b._installed_excepthook
            a.close()
            # out-of-order close: B's hook survives
            assert _sys.excepthook is b._installed_excepthook
        finally:
            b.close()
        assert _sys.excepthook is not b._installed_excepthook
        # in-order teardown restored the chain all the way down:
        # B restored to A's hook; A's link is inert (already closed)
        # and the original hook is reachable through it
        assert _sys.excepthook is a._installed_excepthook
        _sys.excepthook = prev_hook

    def test_excepthook_dumps_and_chains(self, tmp_path):
        child = tmp_path / "child.py"
        fdir = tmp_path / "fl"
        child.write_text(f"""
import sys
sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
from strom.obs.flight import FlightRecorder
FlightRecorder({str(fdir)!r}, stall_s=0.0, install_signal=False)
raise RuntimeError("boom-42")
""")
        p = subprocess.run([sys.executable, str(child)],
                           capture_output=True, timeout=30)
        assert p.returncode == 1
        assert b"boom-42" in p.stderr  # the default hook still printed
        bs = glob.glob(str(fdir / "flight-*-exception-*"))
        assert len(bs) == 1
        m = load_bundle(bs[0])["manifest"]
        assert "boom-42" in m["note"]


class TestContextIntegration:
    def test_context_starts_and_closes_recorder(self, tmp_path):
        from strom.config import StromConfig
        from strom.delivery.core import StromContext

        cfg = StromConfig(engine="python", slab_pool_bytes=0,
                          flight_dir=str(tmp_path / "fl"),
                          flight_stall_s=0.0)
        ctx = StromContext(cfg)
        try:
            rec = ctx.flight_recorder
            assert rec is not None
            p = rec.dump("ctx")
            b = load_bundle(p)
            # the context-backed capture includes the sections snapshot
            assert "sections" in b["stats"]
            assert "engine" in b["stats"]["sections"]
        finally:
            ctx.close()
        assert rec._closed.is_set()

    def test_flight_route_serves_capture(self, tmp_path):
        import urllib.request

        from strom.config import StromConfig
        from strom.delivery.core import StromContext

        cfg = StromConfig(engine="python", slab_pool_bytes=0,
                          flight_dir=str(tmp_path / "fl"),
                          flight_stall_s=0.0)
        ctx = StromContext(cfg, metrics_port=0)
        try:
            port = ctx.metrics_server.port
            doc = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/flight").read())
            assert doc["reason"] == "on_demand"
            assert doc["fields"] == list(FLIGHT_FIELDS)
            assert "stacks" in doc
            doc2 = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/flight?dump=1").read())
            assert doc2["bundle_path"]
            assert load_bundle(doc2["bundle_path"])["manifest"]["reason"] \
                == "on_demand"
        finally:
            ctx.close()

    def test_flight_route_without_recorder(self):
        """/flight still captures (point-in-time) when no recorder is
        configured."""
        from strom.obs.server import MetricsServer

        srv = MetricsServer(None, port=0)
        try:
            import urllib.request

            doc = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/flight").read())
            assert doc["reason"] == "on_demand"
            assert doc["samples"] == []
        finally:
            srv.close()
