"""Fault injection + end-to-end failure recovery (ISSUE 9, tier-1).

The contracts pinned here, in dependency order:

- **Plan determinism**: a seeded FaultPlan over the same op sequence
  injects the SAME fault sequence — chaos is reproducible on demand, so
  every future PR can soak-test against an identical failure schedule.
- **FaultyEngine semantics**: errno/short-read/bit-flip/stuck/death each
  do exactly what the production failure they model does, through the
  full submit/wait API.
- **Retry policy**: transient-vs-permanent classification, exponential
  backoff under a per-gather budget, and recovery to byte-identical data.
- **Streamed parity**: a StreamingGather under injected EIO + short reads
  delivers output bit-identical to the fault-free read once retries
  succeed; engine death mid-gather recovers per-chunk on the fallback.
- **Breaker lifecycle**: closed → open on error rate → half-open probes
  after cooldown → closed on probe successes; a failed probe re-opens.
- **Hedged reads**: a chunk quiet past the adaptive threshold is re-read
  on the fallback, first completion wins, the stuck loser is cancelled.
- **Deadlines fail fast**: a deadline-carrying request over a wedged
  engine raises DeadlineExceeded well inside the old 30 s hang and mints
  an errored exemplar (PR 8 store); a wedged engine without a deadline
  raises a diagnosable EngineStallError naming the stuck tags.
"""

import errno
import json
import time

import numpy as np
import pytest

from strom.config import StromConfig
from strom.delivery.buffers import alloc_aligned
from strom.delivery.core import StromContext
from strom.delivery.shard import Segment
from strom.engine.base import DeadlineExceeded, EngineError, EngineStallError
from strom.engine.python_engine import PythonEngine
from strom.engine.resilience import (CHAOS_BENCH_FIELDS, RESILIENCE_FIELDS,
                                     CircuitBreaker, HedgeController,
                                     RetryPolicy, classify_errno)
from strom.faults import FaultPlan, FaultRule, FaultyEngine

MiB = 1024 * 1024


def _decisions(plan: FaultPlan, ops):
    """The plan's decision per op (kind or None), in op order."""
    out = []
    for path, off, ln in ops:
        f = plan.decide(path=path, offset=off, length=ln)
        out.append(None if f is None else
                   (f.kind, f.keep_bytes, f.flip_offset, f.flip_mask))
    return out


class TestFaultPlanDeterminism:
    OPS = [(f"/data/shard{i % 3}.bin", (i * 7919) % (64 * MiB), 128 * 1024)
           for i in range(400)]

    def test_same_seed_same_sequence(self):
        a = _decisions(FaultPlan.chaos(seed=42), self.OPS)
        b = _decisions(FaultPlan.chaos(seed=42), self.OPS)
        assert a == b
        assert any(d is not None for d in a), \
            "a 400-op chaos stream must inject something"

    def test_different_seed_different_sequence(self):
        a = _decisions(FaultPlan.chaos(seed=1), self.OPS)
        b = _decisions(FaultPlan.chaos(seed=2), self.OPS)
        assert a != b

    def test_stats_count_injections(self):
        plan = FaultPlan.chaos(seed=7)
        decided = _decisions(plan, self.OPS)
        s = plan.stats()
        assert s["ops_seen"] == len(self.OPS)
        assert s["faults_injected"] == sum(d is not None for d in decided)
        assert s["seed"] == 7

    def test_matchers(self):
        plan = FaultPlan([
            FaultRule("errno", path="shard1", err="EIO", times=2),
            FaultRule("short_read", offset_lo=MiB, offset_hi=2 * MiB),
        ], seed=0)
        # path matcher: shard0 ops below 1MiB never match either rule
        assert plan.decide(path="/d/shard0", offset=0, length=4096) is None
        # first matching rule wins, errno resolved from its name
        f = plan.decide(path="/d/shard1", offset=0, length=4096)
        assert f.kind == "errno" and f.err == errno.EIO
        # offset windows OVERLAP [lo, hi)
        f = plan.decide(path="/d/shard0", offset=MiB - 100, length=4096)
        assert f.kind == "short_read" and 0 <= f.keep_bytes < 4096
        assert plan.decide(path="/d/shard0", offset=2 * MiB,
                           length=4096) is None
        # times cap: the errno rule has one injection left
        assert plan.decide(path="/d/shard1", offset=0,
                           length=4096).kind == "errno"
        f = plan.decide(path="/d/shard1", offset=MiB, length=4096)
        assert f is not None and f.kind == "short_read"

    def test_every_nth(self):
        plan = FaultPlan([FaultRule("errno", every=3)], seed=0)
        kinds = [None if plan.decide(path="p", offset=0, length=64) is None
                 else "errno" for _ in range(9)]
        assert kinds == [None, None, "errno"] * 3

    def test_unwind_restores_times_cap(self):
        """A rolled-back injection (queue-full partial accept: the op
        never ran) un-counts the rule's times-cap and the tallies, so
        the replayed op re-decides against an unspent budget."""
        plan = FaultPlan([FaultRule("errno", times=1)], seed=0)
        f = plan.decide(path="p", offset=0, length=64)
        assert f is not None
        assert plan.decide(path="p", offset=0, length=64) is None
        plan.unwind(f)
        assert plan.stats()["faults_injected"] == 0
        f2 = plan.decide(path="p", offset=0, length=64)
        assert f2 is not None and f2.kind == "errno"

    def test_from_spec_forms(self, tmp_path):
        assert FaultPlan.from_spec("chaos:9").seed == 9
        assert FaultPlan.from_spec("chaos").seed == 0
        doc = {"seed": 3, "rules": [{"kind": "errno", "err": "ENXIO"}]}
        inline = FaultPlan.from_spec(json.dumps(doc))
        assert inline.seed == 3 and inline.rules[0].err == errno.ENXIO
        p = tmp_path / "plan.json"
        p.write_text(json.dumps(doc))
        assert FaultPlan.from_spec(str(p)).seed == 3
        with pytest.raises(ValueError):
            FaultPlan.from_spec("no-such-preset")
        with pytest.raises(ValueError):
            FaultRule("gamma_ray")


@pytest.fixture()
def faulty(data_file):
    """(FaultyEngine-over-python factory, path, golden). The factory takes
    the plan (and config overrides) so each test states its chaos."""
    path, golden = data_file
    engines = []

    def make(plan: FaultPlan, **cfg_kw) -> FaultyEngine:
        cfg_kw.setdefault("io_retry_backoff_s", 0.001)
        cfg_kw.setdefault("io_retry_backoff_max_s", 0.004)
        cfg = StromConfig(engine="python", queue_depth=8, num_buffers=8,
                          **cfg_kw)
        eng = FaultyEngine(PythonEngine(cfg), plan)
        engines.append(eng)
        return eng

    yield make, path, golden
    for eng in engines:
        eng.close()


class TestFaultyEngine:
    def test_transient_errno_absorbed_by_retry(self, faulty):
        make, path, golden = faulty
        eng = make(FaultPlan([FaultRule("errno", times=2)], seed=0))
        fi = eng.register_file(path)
        dest = alloc_aligned(MiB)
        n = eng.read_vectored([(fi, 0, 0, MiB)], dest, retries=3)
        assert n == MiB
        np.testing.assert_array_equal(dest, golden[:MiB])
        assert eng.plan.stats()["faults_injected"] == 2

    def test_short_read_retried_to_full_bytes(self, faulty):
        make, path, golden = faulty
        eng = make(FaultPlan([FaultRule("short_read", times=2,
                                        short_frac=0.25)], seed=0))
        fi = eng.register_file(path)
        dest = alloc_aligned(MiB)
        n = eng.read_vectored([(fi, 0, 0, MiB)], dest, retries=3)
        assert n == MiB
        np.testing.assert_array_equal(dest, golden[:MiB])

    def test_bit_flip_is_silent_corruption(self, faulty):
        make, path, golden = faulty
        eng = make(FaultPlan([FaultRule("bit_flip", times=1)], seed=5))
        fi = eng.register_file(path)
        dest = alloc_aligned(256 * 1024)
        n = eng.read_vectored([(fi, 0, 0, 256 * 1024)], dest, retries=1)
        assert n == 256 * 1024  # reported success: that's the point
        diff = np.nonzero(dest != golden[:256 * 1024])[0]
        assert len(diff) == 1, "exactly one corrupted byte"
        assert bin(int(dest[diff[0]]) ^ int(golden[diff[0]])).count("1") == 1

    def test_permanent_errno_fails_immediately(self, faulty):
        make, path, golden = faulty
        eng = make(FaultPlan([FaultRule("errno", err="EBADF")], seed=0))
        fi = eng.register_file(path)
        dest = alloc_aligned(128 * 1024)
        with pytest.raises(EngineError) as ei:
            eng.read_vectored([(fi, 0, 0, 128 * 1024)], dest, retries=5)
        assert ei.value.errno == errno.EBADF
        # no resubmit for a permanent errno: one op seen, one injected
        assert eng.plan.stats()["ops_seen"] == 1

    def test_engine_death_latches(self, faulty):
        make, path, golden = faulty
        eng = make(FaultPlan([FaultRule("engine_death", op_lo=1)], seed=0),
                   io_retry_budget=4)
        fi = eng.register_file(path)
        dest = alloc_aligned(128 * 1024)
        n = eng.read_vectored([(fi, 0, 0, 128 * 1024)], dest, retries=1)
        assert n == 128 * 1024  # op 0 passes through
        with pytest.raises(EngineError):
            eng.read_vectored([(fi, 0, 0, 128 * 1024)], dest, retries=2)
        assert eng.plan.dead
        # dead is dead: every later op fails instantly too
        with pytest.raises(EngineError):
            eng.read_vectored([(fi, 0, 0, 128 * 1024)], dest, retries=0)

    def test_latency_spike_delays_but_delivers(self, faulty):
        make, path, golden = faulty
        eng = make(FaultPlan([FaultRule("latency", latency_s=0.05,
                                        times=1)], seed=0))
        fi = eng.register_file(path)
        dest = alloc_aligned(128 * 1024)
        t0 = time.monotonic()
        n = eng.read_vectored([(fi, 0, 0, 128 * 1024)], dest, retries=1)
        assert n == 128 * 1024
        assert time.monotonic() - t0 >= 0.045
        np.testing.assert_array_equal(dest, golden[:128 * 1024])

    def test_stuck_released_by_cancel(self, faulty):
        make, path, golden = faulty
        eng = make(FaultPlan([FaultRule("stuck")], seed=0))
        fi = eng.register_file(path)
        dest = alloc_aligned(64 * 1024)
        tok = eng.submit_vectored([(fi, 0, 0, 64 * 1024)], dest, retries=0)
        assert eng.poll(tok, min_completions=1, timeout_s=0.2) == []
        eng.cancel(tok, timeout_s=2.0)  # releases the stuck op as ECANCELED
        assert eng.in_flight() == 0


class TestRetryPolicy:
    def test_classification(self):
        for e in (errno.EIO, errno.EAGAIN, errno.ETIMEDOUT, errno.ENODATA):
            assert classify_errno(e) == "transient"
        for e in (errno.EBADF, errno.EINVAL, errno.ECANCELED, errno.EACCES):
            assert classify_errno(e) == "permanent"
        assert classify_errno(-errno.EIO) == "transient"  # sign-agnostic
        assert classify_errno(12345) == "transient"  # unknown: optimism

    def test_backoff_exponential_and_capped(self):
        pol = RetryPolicy(backoff_s=0.01, backoff_max_s=0.05, jitter=0.0)
        assert pol.delay_s(0) == pytest.approx(0.01)
        assert pol.delay_s(1) == pytest.approx(0.02)
        assert pol.delay_s(2) == pytest.approx(0.04)
        assert pol.delay_s(5) == pytest.approx(0.05)  # capped

    def test_jitter_bounded(self):
        pol = RetryPolicy(backoff_s=0.01, backoff_max_s=1.0, jitter=0.5)
        for a in range(4):
            base = 0.01 * 2 ** a
            for _ in range(20):
                assert base <= pol.delay_s(a) <= base * 1.5 + 1e-12

    def test_should_retry_gates(self):
        pol = RetryPolicy(budget=2)
        assert pol.should_retry(errno.EIO, 0, 3, 0)
        assert not pol.should_retry(errno.EBADF, 0, 3, 0)  # permanent
        assert not pol.should_retry(errno.EIO, 3, 3, 0)    # attempts spent
        assert not pol.should_retry(errno.EIO, 0, 3, 2)    # budget spent

    def test_gather_budget_bounds_retry_storm(self, faulty):
        """A persistently sick extent stops retrying at the per-gather
        budget — bounded resubmits, then the error surfaces."""
        make, path, golden = faulty
        eng = make(FaultPlan([FaultRule("errno")], seed=0),
                   io_retry_budget=3)
        fi = eng.register_file(path)
        dest = alloc_aligned(64 * 1024)
        with pytest.raises(EngineError):
            eng.read_vectored([(fi, 0, 0, 64 * 1024)], dest, retries=100)
        # 1 original + exactly budget resubmits reached the plan
        assert eng.plan.stats()["ops_seen"] == 4


def _ctx(path=None, **cfg_kw):
    cfg_kw.setdefault("engine", "python")
    cfg_kw.setdefault("queue_depth", 8)
    cfg_kw.setdefault("num_buffers", 16)
    cfg_kw.setdefault("io_retry_backoff_s", 0.001)
    cfg_kw.setdefault("io_retry_backoff_max_s", 0.004)
    cfg_kw.setdefault("hot_cache_bytes", 0)
    return StromContext(StromConfig(**cfg_kw))


def _stream_read(ctx, path, nbytes) -> np.ndarray:
    dest = alloc_aligned(nbytes)
    g = ctx.stream_segments(path, [Segment(0, 0, nbytes)], dest)
    try:
        while not g.done:
            g.poll(min_completions=1, timeout_s=0.5)
        g.finish()
    finally:
        g.close()
    return dest


class TestStreamedParityUnderFaults:
    def test_bit_identical_under_eio_and_short_reads(self, data_file):
        """The acceptance bit: injected EIO + short reads + latency spikes,
        streamed output identical to the fault-free bytes."""
        path, golden = data_file
        plan = json.dumps({"seed": 11, "rules": [
            {"kind": "errno", "every": 5, "times": 3},
            {"kind": "short_read", "every": 7, "times": 3,
             "short_frac": 0.5},
            {"kind": "latency", "every": 11, "times": 2,
             "latency_s": 0.005},
        ]})
        ctx = _ctx(fault_plan=plan, io_retries=3)
        try:
            dest = _stream_read(ctx, path, 2 * MiB)
            np.testing.assert_array_equal(dest, golden[:2 * MiB])
            res = ctx.stats(sections=("resilience",))["resilience"]
            assert res["faults_injected"] >= 6
            assert res["chunk_retries"] >= 4
            assert res["fault_plan"]["by_kind"]["errno"] == 3
        finally:
            ctx.close()

    def test_engine_death_recovers_per_chunk_on_fallback(self, data_file):
        """fail_fast=False + per-chunk failover: the engine dying mid-batch
        no longer kills the gather — unserved chunks re-read on the python
        fallback path, output stays golden, counters say failover did it."""
        path, golden = data_file
        plan = json.dumps({"seed": 0, "rules": [
            {"kind": "engine_death", "op_lo": 4}]})
        ctx = _ctx(fault_plan=plan, io_retries=1, io_retry_budget=4,
                   breaker_min_events=2)
        try:
            dest = _stream_read(ctx, path, 2 * MiB)
            np.testing.assert_array_equal(dest, golden[:2 * MiB])
            res = ctx.stats(sections=("resilience",))["resilience"]
            assert res["failover_reads"] > 0
            assert res["failover_bytes"] > 0
            assert res["fault_plan"]["engine_dead"] is True
        finally:
            ctx.close()

    def test_demand_path_breaker_failover(self, data_file):
        """pread over a dead engine: the gather that trips the breaker
        reroutes to the fallback and SERVES; while open, primary is never
        touched; /stats shows the open breaker."""
        path, golden = data_file
        plan = json.dumps({"seed": 0, "rules": [{"kind": "engine_death"}]})
        ctx = _ctx(fault_plan=plan, io_retries=1, io_retry_budget=2,
                   breaker_min_events=2, breaker_error_rate=0.5,
                   breaker_cooldown_s=60.0)
        try:
            # failure 1: breaker still closed (below min_events) — propagates
            with pytest.raises(EngineError):
                ctx.pread(path, 0, 256 * 1024)
            # failure 2 trips it OPEN: THIS gather reroutes and serves
            out = ctx.pread(path, 0, 256 * 1024)
            np.testing.assert_array_equal(out[:256 * 1024],
                                          golden[:256 * 1024])
            res = ctx.stats(sections=("resilience",))["resilience"]
            assert res["state"] == "open"
            assert res["breaker_trips"] == 1
            ops_before = ctx.engine.plan.stats()["ops_seen"]
            # while open: straight to fallback, primary untouched
            out = ctx.pread(path, MiB, 128 * 1024)
            np.testing.assert_array_equal(
                out[:128 * 1024], golden[MiB:MiB + 128 * 1024])
            assert ctx.engine.plan.stats()["ops_seen"] == ops_before
        finally:
            ctx.close()


class TestBreakerGranularity:
    def test_streamed_gather_feeds_breaker_once(self, data_file):
        """Per-GATHER breaker outcomes on the streamed path: a batch with
        several recovered chunks is ONE failure (a handful of recoveries
        in a 10^4-chunk batch must not read as a 100% error rate to the
        rolling window), and a clean gather is one success."""
        path, golden = data_file
        plan = json.dumps({"seed": 0, "rules": [
            {"kind": "errno", "every": 2, "times": 3}]})
        ctx = _ctx(fault_plan=plan, io_retries=0, breaker_min_events=100)
        try:
            dest = _stream_read(ctx, path, 2 * MiB)
            np.testing.assert_array_equal(dest, golden[:2 * MiB])
            info = ctx.resilience.breaker.info()
            assert info["window_events"] == 1, info
            assert info["window_failures"] == 1, info
            dest = _stream_read(ctx, path, MiB)  # plan exhausted: clean
            np.testing.assert_array_equal(dest, golden[:MiB])
            info = ctx.resilience.breaker.info()
            assert info["window_events"] == 2, info
            assert info["window_failures"] == 1, info
        finally:
            ctx.close()


class TestBreakerLifecycle:
    def make(self, **kw):
        self.now = [0.0]
        kw.setdefault("window_s", 10.0)
        kw.setdefault("min_events", 4)
        kw.setdefault("error_rate", 0.5)
        kw.setdefault("cooldown_s", 5.0)
        kw.setdefault("half_open_successes", 2)
        return CircuitBreaker(clock=lambda: self.now[0], **kw)

    def test_trip_half_open_recover(self):
        br = self.make()
        trips = []
        br.on_trip = trips.append
        for _ in range(3):
            br.record_success()
        assert br.state == CircuitBreaker.CLOSED and br.allow()
        # 4 failures out of the last 7 ≥ 50% over ≥ min_events: OPEN
        for _ in range(4):
            br.record_failure()
        assert br.state == CircuitBreaker.OPEN
        assert br.trips == 1 and len(trips) == 1
        assert not br.allow(), "open + inside cooldown: reroute"
        # cooldown elapses: next allow() is a HALF_OPEN probe
        self.now[0] += 5.1
        assert br.allow()
        assert br.state == CircuitBreaker.HALF_OPEN
        br.record_success()
        assert br.state == CircuitBreaker.HALF_OPEN  # 1 of 2
        br.record_success()
        assert br.state == CircuitBreaker.CLOSED
        assert br.recoveries == 1
        assert br.allow()

    def test_failed_probe_reopens(self):
        br = self.make()
        for _ in range(4):
            br.record_failure()
        self.now[0] += 5.1
        assert br.allow()  # half-open probe
        br.record_failure()
        assert br.state == CircuitBreaker.OPEN
        assert br.trips == 2
        assert not br.allow(), "cooldown restarted by the failed probe"

    def test_window_prunes_stale_failures(self):
        br = self.make()
        for _ in range(3):
            br.record_failure()
        self.now[0] += 11.0  # stale: outside the 10 s window
        for _ in range(4):
            br.record_success()
        br.record_failure()  # 1 failure / 5 events < 50%
        assert br.state == CircuitBreaker.CLOSED

    def test_info_shape(self):
        br = self.make()
        info = br.info()
        assert info["state"] == "closed" and info["breaker_state"] == 0
        for k in ("breaker_trips", "breaker_probes", "breaker_recoveries"):
            assert k in info


class TestHedgedReads:
    def test_threshold_floors_on_cold_window(self):
        h = HedgeController(min_s=0.05, multiplier=3.0)
        assert h.threshold_s() == pytest.approx(0.05)
        for _ in range(7):
            h.observe(10.0)  # < 8 observations: still the floor
        assert h.threshold_s() == pytest.approx(0.05)

    def test_threshold_tracks_rolling_p99(self):
        h = HedgeController(min_s=0.001, multiplier=2.0)
        for _ in range(128):
            h.observe(0.01)
        assert h.threshold_s() == pytest.approx(0.02, rel=0.01)

    def test_hedge_first_wins_loser_cancelled(self, data_file):
        """A chunk stuck on the primary past the hedge threshold is served
        by the fallback (hedges_fired/won count it); finish() cancels the
        stuck loser and the batch is bit-identical."""
        path, golden = data_file
        plan = json.dumps({"seed": 0, "rules": [
            {"kind": "stuck", "times": 1}]})
        ctx = _ctx(fault_plan=plan, hedge_min_s=0.05, hedge_multiplier=0.0)
        try:
            t0 = time.monotonic()
            dest = _stream_read(ctx, path, MiB)
            assert time.monotonic() - t0 < 10.0, \
                "hedge must beat any stall watchdog by an order of magnitude"
            np.testing.assert_array_equal(dest, golden[:MiB])
            res = ctx.stats(sections=("resilience",))["resilience"]
            assert res["hedges_fired"] >= 1
            assert res["hedges_won"] >= 1
            assert ctx.engine.in_flight() == 0, "loser reaped by cancel"
        finally:
            ctx.close()

    def test_loser_completion_not_reemitted(self, data_file):
        """The hedged range reaches the consumer exactly once: the losing
        primary completion arriving later is discarded (a duplicate range
        would double-decrement the pump's per-sample byte countdown and
        wedge the batch)."""
        path, golden = data_file
        plan = json.dumps({"seed": 0, "rules": [
            {"kind": "latency", "times": 1, "latency_s": 0.2}]})
        ctx = _ctx(fault_plan=plan, hedge_min_s=0.03, hedge_multiplier=0.0)
        try:
            dest = alloc_aligned(MiB)
            g = ctx.stream_segments(path, [Segment(0, 0, MiB)], dest)
            ranges = []
            try:
                t_end = time.monotonic() + 10.0
                while not g.done and time.monotonic() < t_end:
                    ranges.extend(g.poll(min_completions=1, timeout_s=0.25))
                time.sleep(0.25)  # let the latency-held loser release
                ranges.extend(g.poll(min_completions=0))
                g.finish()
            finally:
                g.close()
            assert len(ranges) == len(set(ranges)), \
                f"duplicate dest range emitted: {sorted(ranges)}"
            assert sum(hi - lo for lo, hi in ranges) == MiB
            np.testing.assert_array_equal(dest, golden[:MiB])
        finally:
            ctx.close()

    def test_hedge_fires_once_per_chunk(self, data_file):
        """A straggler whose fallback read cannot serve it must not
        re-hedge on every poll (a hedge storm through the serialized
        lifeboat and a meaningless hedges_fired count)."""
        path, _ = data_file
        plan = json.dumps({"seed": 0, "rules": [{"kind": "stuck"}]})
        ctx = _ctx(fault_plan=plan, hedge_min_s=0.02, hedge_multiplier=0.0,
                   breaker_enabled=False)
        try:
            # the section reads the process-global registry: delta it
            fired0 = ctx.stats(
                sections=("resilience",))["resilience"]["hedges_fired"]
            dest = alloc_aligned(128 * 1024)
            g = ctx.stream_segments(path, [Segment(0, 0, 128 * 1024)], dest)
            try:
                # a fallback that can never serve: every hedge misses, the
                # chunks stay unaccounted across many polls
                ctx.resilience.read_chunk_fallback = lambda *a, **k: False
                nchunks = len(g._chunks)
                deadline = time.monotonic() + 1.0
                while time.monotonic() < deadline:
                    g.poll(min_completions=1, timeout_s=0.05)
            finally:
                g.close()
            fired = ctx.stats(
                sections=("resilience",))["resilience"]["hedges_fired"]
            assert fired - fired0 == nchunks, \
                "a missed hedge must not refire on every poll"
        finally:
            ctx.close()

    def test_zero_hedge_params_disable_hedging(self):
        """hedge_min_s=0 + hedge_multiplier=0 is the documented OFF
        spelling — it must not become a 0-threshold hedge-everything."""
        ctx = _ctx(hedge_min_s=0.0, hedge_multiplier=0.0)
        try:
            assert ctx.resilience.hedge is None
        finally:
            ctx.close()

    def test_primary_win_counts_wasted_bytes(self, data_file):
        """When the primary completes while the hedge is in flight, the
        hedge's bytes are counted wasted and the primary's data stands."""
        path, golden = data_file
        plan = json.dumps({"seed": 0, "rules": [
            {"kind": "latency", "times": 1, "latency_s": 0.15}]})
        ctx = _ctx(fault_plan=plan, hedge_min_s=0.03, hedge_multiplier=0.0)
        try:
            dest = _stream_read(ctx, path, MiB)
            np.testing.assert_array_equal(dest, golden[:MiB])
            res = ctx.stats(sections=("resilience",))["resilience"]
            assert res["hedges_fired"] >= 1
        finally:
            ctx.close()


class TestDeadlines:
    def test_deadline_fails_fast_and_mints_errored_exemplar(self, data_file):
        """The acceptance bit: a deadline-carrying request over a wedged
        engine fails in ~deadline seconds — not the legacy 30 s — with the
        typed error, a deadline_exceeded count, and an errored exemplar
        retained in the PR 8 store."""
        from strom.obs.exemplars import store

        path, _ = data_file
        plan = json.dumps({"seed": 0, "rules": [{"kind": "stuck"}]})
        ctx = _ctx(fault_plan=plan, breaker_enabled=False)
        try:
            store.clear()
            t0 = time.monotonic()
            with pytest.raises(DeadlineExceeded):
                ctx.pread(path, 0, 256 * 1024, tenant="t9", deadline_s=0.4)
            elapsed = time.monotonic() - t0
            assert elapsed < 5.0, f"fail-fast took {elapsed:.1f}s"
            res = ctx.stats(sections=("resilience",))["resilience"]
            assert res["deadline_exceeded"] >= 1
            kept = store.exemplars("t9")
            assert any(e["error"] and "deadline" in e["error"].lower()
                       for e in kept), f"errored exemplar missing: {kept}"
        finally:
            ctx.close()

    def test_config_default_deadline_applies(self, data_file):
        path, _ = data_file
        plan = json.dumps({"seed": 0, "rules": [{"kind": "stuck"}]})
        ctx = _ctx(fault_plan=plan, request_deadline_s=0.3,
                   breaker_enabled=False)
        try:
            t0 = time.monotonic()
            with pytest.raises(DeadlineExceeded):
                ctx.pread(path, 0, 128 * 1024)
            assert time.monotonic() - t0 < 5.0
        finally:
            ctx.close()

    def test_no_deadline_stall_raises_diagnosable_error(self, data_file):
        """Without a deadline, a wedged engine raises EngineStallError at
        the configured watchdog — naming the stuck tags — instead of
        looping silently for a hard-coded 30 s."""
        path, _ = data_file
        cfg = StromConfig(engine="python", queue_depth=8, num_buffers=8,
                          engine_wait_timeout_s=0.3)
        eng = FaultyEngine(
            PythonEngine(cfg),
            FaultPlan([FaultRule("stuck")], seed=0))
        try:
            fi = eng.register_file(path)
            dest = alloc_aligned(64 * 1024)
            t0 = time.monotonic()
            with pytest.raises(EngineStallError) as ei:
                eng.read_vectored([(fi, 0, 0, 64 * 1024)], dest, retries=0)
            assert time.monotonic() - t0 < 5.0
            assert ei.value.stuck_tags, "the stuck tags are the diagnosis"
            assert ei.value.errno == errno.ETIMEDOUT
        finally:
            eng.close()

    def test_stream_poll_stall_raises(self, data_file):
        """The pipeline pump loop polls in short slices, so the ENGINE
        watchdog can never fire from it — the gather-level watchdog in
        StreamingGather.poll must turn a wedged engine into a diagnosable
        EngineStallError instead of a silent forever-hang."""
        path, _ = data_file
        plan = json.dumps({"seed": 0, "rules": [{"kind": "stuck"}]})
        ctx = _ctx(fault_plan=plan, engine_wait_timeout_s=0.3,
                   hedge_enabled=False, breaker_enabled=False)
        try:
            dest = alloc_aligned(64 * 1024)
            g = ctx.stream_segments(path, [Segment(0, 0, 64 * 1024)], dest)
            try:
                t0 = time.monotonic()
                with pytest.raises(EngineStallError):
                    while not g.done and time.monotonic() - t0 < 5.0:
                        g.poll(min_completions=1, timeout_s=0.05)
                assert time.monotonic() - t0 < 5.0
            finally:
                g.close()
        finally:
            ctx.close()

    def test_deadline_in_poll_path(self, data_file):
        """The async token honors the deadline too: poll stops waiting and
        the token fails fast with DeadlineExceeded."""
        path, _ = data_file
        cfg = StromConfig(engine="python", queue_depth=8, num_buffers=8)
        eng = FaultyEngine(
            PythonEngine(cfg), FaultPlan([FaultRule("stuck")], seed=0))
        try:
            fi = eng.register_file(path)
            dest = alloc_aligned(64 * 1024)
            tok = eng.submit_vectored(
                [(fi, 0, 0, 64 * 1024)], dest, retries=0,
                deadline=time.monotonic() + 0.2)
            t0 = time.monotonic()
            with pytest.raises(DeadlineExceeded):
                eng.drain(tok)
            assert time.monotonic() - t0 < 5.0
            eng.cancel(tok, timeout_s=2.0)
        finally:
            eng.close()


class TestMultiRingQuarantine:
    def test_transient_errors_quarantine_a_ring(self):
        """Unit contract for MultiRingEngine degradation: repeated
        transient failures pull a member from the rotation (while a
        healthy peer remains) and the degraded state is visible."""
        pytest.importorskip("strom.engine.uring_engine")
        from strom.engine.uring_engine import uring_available

        if not uring_available():
            pytest.skip("io_uring unavailable")
        from strom.engine import make_engine

        eng = make_engine(StromConfig(engine="uring", engine_rings=2,
                                      breaker_min_events=2))
        try:
            e = EngineError(errno.EIO, "injected")
            eng._note_ring_error(0, e)
            assert eng._healthy_rings() == [0, 1]
            eng._note_ring_error(0, e)
            assert eng._healthy_rings() == [1]
            s = eng.stats()
            assert s["quarantined_rings"] == [0]
            assert s["ring_errors"][0] == 2
            # stable remap: a healthy home ring keeps its files, only the
            # quarantined ring's files redirect to a survivor
            assert eng._route(1, eng._healthy_rings()) == 1
            assert eng._route(0, eng._healthy_rings()) == 1
            # EOF/short-read is data-dependent, never a ring fault
            eng._note_ring_error(1, EngineError(errno.ENODATA, "eof"))
            eng._note_ring_error(1, EngineError(errno.ENODATA, "eof"))
            assert eng._healthy_rings() == [1]
            # permanent errors never quarantine (retry would fail anywhere)
            eng._note_ring_error(1, EngineError(errno.EBADF, "x"))
            eng._note_ring_error(1, EngineError(errno.EBADF, "x"))
            assert eng._healthy_rings() == [1]
        finally:
            eng.close()


class TestResilienceSurfaces:
    def test_stats_section_covers_resilience_fields(self, data_file):
        """Every RESILIENCE_FIELDS key is present in /stats["resilience"]
        — the producer side of the bench-column / compare_rounds parity."""
        ctx = _ctx()
        try:
            res = ctx.stats(sections=("resilience",))["resilience"]
            for k in RESILIENCE_FIELDS:
                assert k in res, f"missing {k}"
        finally:
            ctx.close()

    def test_chaos_fields_match_cli_arm_keys(self):
        """CHAOS_BENCH_FIELDS (the chaos columns of the checked-in
        records) and the compare_rounds resilience section must agree — a
        rename on either side is a silently dead column."""
        import tools.compare_rounds as cr

        assert list(CHAOS_BENCH_FIELDS) == list(cr.RESIL_KEYS)

    def test_tenants_page_shows_degraded_state(self, data_file):
        ctx = _ctx()
        try:
            rows = ctx.scheduler.tenants_info()
            assert "resilience" in rows
            assert "breaker_state" in rows["resilience"]
        finally:
            ctx.close()

    def test_fallback_engine_lazy(self, data_file):
        """The lifeboat (a second buffer pool + worker threads) costs
        nothing until a read actually fails over — healthy demand reads
        must not build it."""
        path, golden = data_file
        ctx = _ctx()
        try:
            out = ctx.pread(path, 0, 128 * 1024)
            np.testing.assert_array_equal(out[:128 * 1024],
                                          golden[:128 * 1024])
            assert ctx.resilience._fb is None
        finally:
            ctx.close()

    def test_lint_covers_resilience_tuples(self):
        """tools/lint_stats_names.py must scan RESILIENCE_FIELDS /
        CHAOS_BENCH_FIELDS / RESIL_KEYS literals (they name the same series
        the producers feed), so a restyled spelling collides at lint time."""
        import os

        from tools.lint_stats_names import scan_sources

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        found, _ = scan_sources(root)
        for name in ("chunk_retries", "hedges_won", "breaker_trips",
                     "chaos_ok", "chaos_slowdown", "failover_bytes"):
            norm = name.replace("_", "").lower()
            assert norm in found, f"lint does not see {name}"


class TestOpMatchers:
    """ISSUE 13 satellite: read/write direction matchers on fault rules —
    presets tuned against read streams must not silently double-count once
    writes share the engine."""

    def test_op_matcher_scopes_rules(self):
        plan = FaultPlan([FaultRule("errno", op="read", p=1.0),
                          FaultRule("errno", op="write", err="ENOSPC",
                                    p=1.0)], seed=0)
        f = plan.decide(path="/d/x", offset=0, length=4096, op="read")
        assert f is not None and f.err == errno.EIO
        f = plan.decide(path="/d/x", offset=0, length=4096, op="write")
        assert f is not None and f.err == errno.ENOSPC

    def test_unscoped_rule_matches_both(self):
        plan = FaultPlan([FaultRule("errno", p=1.0)], seed=0)
        assert plan.decide(path=None, offset=0, length=1, op="read") \
            is not None
        assert plan.decide(path=None, offset=0, length=1, op="write") \
            is not None

    def test_mismatched_op_consumes_no_rng_draw(self):
        """A read-scoped p<1 rule evaluated against write traffic must not
        advance the plan RNG: the read stream's injected sequence is
        identical with or without interleaved writes (the double-count
        fix)."""
        ops = [(f"/d/s{i % 2}", i * 4096, 4096) for i in range(200)]
        a = FaultPlan([FaultRule("errno", op="read", p=0.1)], seed=3)
        plain = [a.decide(path=p, offset=o, length=ln, op="read")
                 is not None for p, o, ln in ops]
        b = FaultPlan([FaultRule("errno", op="read", p=0.1)], seed=3)
        mixed = []
        for p, o, ln in ops:
            b.decide(path=p, offset=o, length=ln, op="write")  # interleave
            mixed.append(b.decide(path=p, offset=o, length=ln, op="read")
                         is not None)
        assert plain == mixed

    def test_bit_flip_never_matches_writes(self):
        plan = FaultPlan([FaultRule("bit_flip", p=1.0)], seed=0)
        assert plan.decide(path=None, offset=0, length=64,
                           op="write") is None
        assert plan.decide(path=None, offset=0, length=64,
                           op="read") is not None

    def test_chaos_preset_is_read_scoped(self):
        plan = FaultPlan.chaos(seed=0)
        assert all(r.op == "read" for r in plan.rules)
        for i in range(200):
            assert plan.decide(path="/d/w", offset=i * 4096, length=4096,
                               op="write") is None
        assert plan.stats()["faults_injected"] == 0

    def test_chaos_writes_preset(self):
        plan = FaultPlan.from_spec("chaos_writes:5")
        assert all(r.op == "write" for r in plan.rules)
        assert plan.seed == 5
        hits = sum(plan.decide(path="/d/w", offset=i * 4096, length=4096,
                               op="write") is not None for i in range(400))
        assert hits > 0

    def test_bad_op_matcher_rejected(self):
        with pytest.raises(ValueError):
            FaultRule("errno", op="sideways")
