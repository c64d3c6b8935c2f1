"""Hot-set host cache + epoch-aware readahead (ISSUE 4 tentpole):
hit/miss/partial-hit split parity (cache-on and cache-off reads are
bit-identical), eviction under byte pressure, refcounts protecting in-flight
readers/puts, second-touch admission, readahead that never issues a
demand-blocking read, and thread safety under a concurrent prefetcher."""

import json
import threading
import time

import numpy as np
import pytest

from strom.config import StromConfig
from strom.delivery.core import StromContext
from strom.delivery.extents import ExtentList
from strom.delivery.hotcache import HotCache, Readahead
from strom.delivery.shard import Segment

KiB = 1024
MiB = 1024 * KiB


def _cfg(**kw) -> StromConfig:
    kw.setdefault("engine", "python")
    kw.setdefault("queue_depth", 8)
    kw.setdefault("num_buffers", 16)
    return StromConfig(**kw)


@pytest.fixture()
def ctx_on(data_file):
    c = StromContext(_cfg(hot_cache_bytes=16 * MiB, hot_cache_admit="always"))
    yield c
    c.close()


@pytest.fixture()
def ctx_off():
    c = StromContext(_cfg())
    yield c
    c.close()


class TestHotCacheUnit:
    """The LRU itself: interval hits, budget eviction, refcount lifetimes,
    second-touch — no engine involved."""

    @staticmethod
    def _bytes(n, seed=0):
        return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8)

    def test_admit_lookup_roundtrip(self):
        hc = HotCache(4 * MiB, admit="always")
        data = self._bytes(1 * MiB)
        assert hc.admit("f", 0, 1 * MiB, data) == 1 * MiB
        hits, misses, pins = hc.lookup("f", 0, 1 * MiB)
        assert misses == []
        assert len(hits) == 1
        lo, hi, view = hits[0]
        assert (lo, hi) == (0, 1 * MiB)
        np.testing.assert_array_equal(view, data)
        hc.unpin(pins)

    def test_partial_hit_split(self):
        """An overlapping request splits into exact hit windows and exact
        miss gaps — the ranges the delivery layer serves vs submits."""
        hc = HotCache(4 * MiB, admit="always")
        data = self._bytes(1 * MiB, seed=1)
        hc.admit("f", 4096, 4096 + 1 * MiB, data)
        hits, misses, pins = hc.lookup("f", 0, 2 * MiB)
        assert [(lo, hi) for lo, hi, _ in hits] == [(4096, 4096 + 1 * MiB)]
        assert misses == [(0, 4096), (4096 + 1 * MiB, 2 * MiB)]
        np.testing.assert_array_equal(hits[0][2], data)
        # sub-range of a cached entry is a pure view hit
        hc.unpin(pins)
        hits, misses, pins = hc.lookup("f", 8192, 8192 + 4096)
        assert misses == []
        np.testing.assert_array_equal(hits[0][2], data[4096:8192])
        hc.unpin(pins)

    def test_disjoint_admission_trims_overlap(self):
        """Re-admitting an overlapping range only fills the gaps (entries
        stay disjoint; no double-billing of the budget)."""
        hc = HotCache(8 * MiB, admit="always")
        a = self._bytes(1 * MiB, seed=2)
        hc.admit("f", 0, 1 * MiB, a)
        b = self._bytes(2 * MiB, seed=3)
        admitted = hc.admit("f", 0, 2 * MiB, b)
        assert admitted == 1 * MiB  # only the uncovered second half
        hits, misses, pins = hc.lookup("f", 0, 2 * MiB)
        assert misses == []
        got = np.concatenate([v for _, _, v in hits])
        np.testing.assert_array_equal(got[:1 * MiB], a)       # original kept
        np.testing.assert_array_equal(got[1 * MiB:], b[1 * MiB:])
        hc.unpin(pins)
        assert hc.bytes == 2 * MiB

    def test_eviction_under_byte_pressure(self):
        hc = HotCache(2 * MiB, admit="always")
        for i in range(4):  # 4 x 1MiB through a 2MiB budget
            hc.admit(f"f{i}", 0, 1 * MiB, self._bytes(1 * MiB, seed=i))
        assert hc.bytes <= 2 * MiB
        assert hc.evictions >= 2
        # oldest evicted, newest resident (LRU order)
        assert hc.lookup("f0", 0, 1 * MiB)[1] == [(0, 1 * MiB)]
        hits, misses, pins = hc.lookup("f3", 0, 1 * MiB)
        assert misses == []
        hc.unpin(pins)

    def test_refcount_protects_pinned_entry(self):
        """An entry evicted while pinned keeps its buffer alive (and
        correct) until the LAST unpin — the in-flight put/memcpy can never
        read a recycled slab."""
        hc = HotCache(1 * MiB, admit="always")
        data = self._bytes(1 * MiB, seed=7)
        hc.admit("f", 0, 1 * MiB, data)
        hits, _, pins = hc.lookup("f", 0, 1 * MiB)
        entry = pins[0]
        # budget pressure: the only victim is pinned -> eviction must skip
        # it, the new entry is dropped, the pinned buffer survives
        assert hc.admit("g", 0, 1 * MiB, self._bytes(1 * MiB, seed=8)) == 0
        np.testing.assert_array_equal(hits[0][2], data)
        assert entry.buf is not None
        # explicit clear() also skips pinned entries
        hc.clear()
        np.testing.assert_array_equal(hits[0][2], data)
        hc.unpin(pins)
        # unpinned now: pressure can evict it
        assert hc.admit("g", 0, 1 * MiB,
                        self._bytes(1 * MiB, seed=8)) == 1 * MiB
        assert hc.lookup("f", 0, 1 * MiB)[1] == [(0, 1 * MiB)]

    def test_dead_entry_freed_on_last_unpin(self):
        pool_released = []

        class FakePool:
            def acquire(self, n):
                return np.zeros(n, dtype=np.uint8)

            def release(self, buf):
                pool_released.append(buf.nbytes)

        hc = HotCache(1 * MiB, admit="always", pool=FakePool())
        hc.admit("f", 0, 1 * MiB, self._bytes(1 * MiB))
        _, _, pins = hc.lookup("f", 0, 1 * MiB)
        hc.clear()  # evicted-while-pinned: slab NOT released yet
        assert pool_released == []
        hc.unpin(pins)  # last unpin frees
        assert pool_released == [1 * MiB]

    def test_second_touch_admission(self):
        hc = HotCache(4 * MiB, admit="second_touch")
        data = self._bytes(1 * MiB, seed=9)
        assert hc.admit("f", 0, 1 * MiB, data) == 0       # first touch: observe
        assert hc.admit("f", 0, 1 * MiB, data) == 1 * MiB  # second: admit
        hits, misses, pins = hc.lookup("f", 0, 1 * MiB)
        assert misses == []
        hc.unpin(pins)
        # force=True (the readahead path) bypasses the ledger
        assert hc.admit("g", 0, 4096, self._bytes(4096), force=True) == 4096

    def test_view_full_hit_only(self):
        hc = HotCache(4 * MiB, admit="always")
        data = self._bytes(1 * MiB, seed=11)
        hc.admit("f", 4096, 4096 + 1 * MiB, data)
        assert hc.view("f", 0, 4096 + 1 * MiB) is None  # not fully covered
        got = hc.view("f", 8192, 8192 + 64 * KiB)
        assert got is not None
        view, entry = got
        np.testing.assert_array_equal(view, data[4096: 4096 + 64 * KiB])
        assert entry.refs == 1
        hc.unpin([entry])
        assert entry.refs == 0

    def test_oversized_admission_skipped(self):
        hc = HotCache(1 * MiB, admit="always")
        assert hc.admit("f", 0, 2 * MiB, self._bytes(2 * MiB)) == 0
        assert hc.bytes == 0

    def test_budget_charged_at_slab_size_class(self):
        """The budget bills what the slab ALLOCATOR hands back (size class;
        2MiB-rounded under huge pages), not the logical length — resident
        memory must actually respect hot_cache_bytes."""
        from strom.delivery.buffers import size_class

        hc = HotCache(4 * MiB, admit="always")
        n = 600 * KiB  # off-class: rounds up to 640KiB (128KiB steps)
        hc.admit("f", 0, n, self._bytes(n))
        assert hc.bytes == size_class(n) > n

        class HugePool:
            huge = True

            def acquire(self, k):
                return np.zeros(k, dtype=np.uint8)

            def release(self, buf):
                pass

        hp = HotCache(4 * MiB, admit="always", pool=HugePool())
        hp.admit("f", 0, 128 * KiB, self._bytes(128 * KiB))
        assert hp.bytes == 2 * MiB  # one huge page per entry
        # two huge-charged entries fill the 4MiB budget; the third evicts
        hp.admit("g", 0, 128 * KiB, self._bytes(128 * KiB))
        hp.admit("h", 0, 128 * KiB, self._bytes(128 * KiB))
        assert hp.bytes <= 4 * MiB
        assert hp.evictions >= 1


class TestContextParity:
    """Cache-on vs cache-off delivered bytes are bit-identical across
    repeat/overlapping reads (the acceptance criterion's parity half)."""

    def test_pread_repeat_epochs(self, ctx_on, ctx_off, data_file):
        path, data = data_file
        rng = np.random.default_rng(0)
        windows = [(int(o), int(n)) for o, n in zip(
            rng.integers(0, len(data) - 256 * KiB, 12),
            rng.integers(1, 256 * KiB, 12))]
        for _epoch in range(3):
            for off, n in windows:
                a = np.asarray(memoryview(ctx_on.pread(path, off, n)))
                b = np.asarray(memoryview(ctx_off.pread(path, off, n)))
                np.testing.assert_array_equal(a, b)
                np.testing.assert_array_equal(a, data[off: off + n])
        stats = ctx_on.stats()["cache"]
        assert stats["cache_hit_bytes"] > 0  # epochs 2-3 served from RAM

    def test_partial_hit_request_split(self, ctx_on, data_file):
        """A request overlapping a cached range serves the hit from RAM and
        reads only the miss runs — bytes still exact."""
        path, data = data_file
        ctx_on.pread(path, 0, 1 * MiB)  # admits [0, 1MiB)
        got = ctx_on.pread(path, 512 * KiB, 1 * MiB)  # half hit, half miss
        np.testing.assert_array_equal(
            np.asarray(memoryview(got)),
            data[512 * KiB: 512 * KiB + 1 * MiB])
        s = ctx_on.stats()["cache"]
        assert s["cache_hit_bytes"] >= 512 * KiB
        assert s["cache_miss_bytes"] >= 512 * KiB

    def test_full_hit_skips_engine(self, ctx_on, data_file):
        path, data = data_file
        ctx_on.pread(path, 0, 2 * MiB)
        miss0 = ctx_on.stats()["cache"]["cache_miss_bytes"]
        got = ctx_on.pread(path, 0, 2 * MiB)  # repeat: full hit
        np.testing.assert_array_equal(np.asarray(memoryview(got)),
                                      data[: 2 * MiB])
        assert ctx_on.stats()["cache"]["cache_miss_bytes"] == miss0

    def test_extent_list_parity(self, ctx_on, ctx_off, data_file, tmp_path):
        """ExtentList gathers key the cache on PHYSICAL (path, offset):
        batch-relative logical offsets must still hit across differently
        composed requests."""
        path, data = data_file
        p2 = tmp_path / "second.bin"
        data2 = np.random.default_rng(5).integers(0, 256, 1 * MiB,
                                                  dtype=np.uint8)
        data2.tofile(p2)
        el1 = ExtentList([(path, 0, 256 * KiB), (str(p2), 0, 256 * KiB)])
        # same physical bytes, different logical composition + order
        el2 = ExtentList([(str(p2), 0, 128 * KiB), (path, 64 * KiB, 64 * KiB)])
        golden1 = np.concatenate([data[: 256 * KiB], data2[: 256 * KiB]])
        golden2 = np.concatenate([data2[: 128 * KiB],
                                  data[64 * KiB: 128 * KiB]])
        for _ in range(2):
            for el, golden in ((el1, golden1), (el2, golden2)):
                a = np.asarray(memoryview(ctx_on.pread(el)))
                b = np.asarray(memoryview(ctx_off.pread(el)))
                np.testing.assert_array_equal(a, b)
                np.testing.assert_array_equal(a, golden)
        assert ctx_on.stats()["cache"]["cache_hit_bytes"] > 0

    def test_memcpy_ssd2host_parity(self, ctx_on, ctx_off, data_file):
        path, data = data_file
        for _ in range(2):
            a = ctx_on.memcpy_ssd2host(path, offset=4096, length=1 * MiB)
            b = ctx_off.memcpy_ssd2host(path, offset=4096, length=1 * MiB)
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, data[4096: 4096 + 1 * MiB])


class TestPipelineParity:
    """Cache-on vs cache-off PIPELINE batches are bit-identical across two
    epochs (tier-1 acceptance), on the decode-free loader whose batches are
    pure engine gathers."""

    @pytest.fixture(scope="class")
    def pdec_shard(self, tmp_path_factory):
        td = tmp_path_factory.mktemp("hc_pdec")
        n, size = 24, 16
        raw = np.random.default_rng(3).integers(
            0, 256, (n, size, size, 3), dtype=np.uint8)
        path = str(td / "imgs.pdec")
        raw.tofile(path)
        np.save(path + ".labels.npy",
                np.arange(n, dtype=np.int32) % 7)
        with open(path + ".meta.json", "w") as f:
            json.dump({"image_size": size, "n": n}, f)
        return path, raw

    def test_two_epochs_bit_identical(self, pdec_shard):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from strom.pipelines import make_predecoded_vision_pipeline

        path, raw = pdec_shard
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("dp",))
        sharding = NamedSharding(mesh, P("dp", None, None, None))
        ctx_on = StromContext(_cfg(hot_cache_bytes=8 * MiB,
                                   hot_cache_admit="always",
                                   readahead_window_batches=2))
        ctx_off = StromContext(_cfg())
        try:
            bpe = raw.shape[0] // 8
            def epochs(ctx):
                out = []
                with make_predecoded_vision_pipeline(
                        ctx, [path], batch=8, image_size=16,
                        sharding=sharding, seed=11) as pipe:
                    for _ in range(2 * bpe):
                        imgs, lbls = next(pipe)
                        out.append((np.asarray(imgs), np.asarray(lbls)))
                return out
            on, off = epochs(ctx_on), epochs(ctx_off)
            for (ia, la), (ib, lb) in zip(on, off):
                np.testing.assert_array_equal(ia, ib)
                np.testing.assert_array_equal(la, lb)
            # the warm epoch actually served from the cache
            assert ctx_on.stats()["cache"]["cache_hit_bytes"] > 0
        finally:
            ctx_on.close()
            ctx_off.close()


class TestReadahead:
    def test_warm_yields_to_demand(self, ctx_on, data_file):
        """The readahead path must NEVER issue a demand-blocking read: with
        a demand gather in flight, warm() returns without touching the
        engine and counts the yield."""
        path, _ = data_file
        y0 = ctx_on.stats()["cache"]["cache_readahead_yields"]
        with ctx_on._demand_gate():
            assert ctx_on.warm(path, [Segment(0, 0, 1 * MiB)]) == 0
        s = ctx_on.stats()["cache"]
        assert s["cache_readahead_yields"] == y0 + 1
        assert s["cache_readahead_bytes"] == 0

    def test_warm_skips_cached_and_admits_misses(self, ctx_on, data_file):
        path, data = data_file
        ctx_on.pread(path, 0, 1 * MiB)  # cached (admit=always)
        warmed = ctx_on.warm(path, [Segment(0, 0, 2 * MiB)])
        assert warmed == 1 * MiB  # only the uncached second half read
        miss0 = ctx_on.stats()["cache"]["cache_miss_bytes"]
        got = ctx_on.pread(path, 0, 2 * MiB)  # now a full hit
        np.testing.assert_array_equal(np.asarray(memoryview(got)),
                                      data[: 2 * MiB])
        assert ctx_on.stats()["cache"]["cache_miss_bytes"] == miss0

    def test_readahead_thread_warms_window(self, ctx_on, data_file):
        path, data = data_file
        ra = Readahead(
            ctx_on, lambda: [(path, [Segment(0, 0, 1 * MiB)], 0)],
            interval_s=0.005)
        try:
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if ctx_on.stats()["cache"]["cache_readahead_bytes"] >= 1 * MiB:
                    break
                time.sleep(0.01)
            else:
                pytest.fail("readahead never warmed the window")
        finally:
            ra.close()
        miss0 = ctx_on.stats()["cache"]["cache_miss_bytes"]
        got = ctx_on.pread(path, 0, 1 * MiB)
        np.testing.assert_array_equal(np.asarray(memoryview(got)),
                                      data[: 1 * MiB])
        assert ctx_on.stats()["cache"]["cache_miss_bytes"] == miss0

    def test_broken_window_fn_counted_not_silent(self, ctx_on):
        """A window_fn that raises must not kill the thread NOR vanish:
        cache_readahead_errors distinguishes 'broken' from 'nothing to
        warm' (both read as readahead_bytes == 0)."""
        def boom():
            raise RuntimeError("window_fn broke")

        ra = Readahead(ctx_on, boom, interval_s=0.001)
        try:
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if ctx_on.stats()["cache"]["cache_readahead_errors"]:
                    break
                time.sleep(0.005)
            else:
                pytest.fail("readahead error never counted")
        finally:
            ra.close()

    def test_disabled_cache_serves_and_warms_nothing(self, ctx_on,
                                                     data_file):
        """The enabled gate (bench phase scoping): a disabled cache is
        bypassed end to end — no serving, no admission, no warming — and
        re-enabling restores it."""
        path, data = data_file
        ctx_on.hot_cache.enabled = False
        got = ctx_on.pread(path, 0, 1 * MiB)
        np.testing.assert_array_equal(np.asarray(memoryview(got)),
                                      data[: 1 * MiB])
        s = ctx_on.stats()["cache"]
        assert s["cache_hit_bytes"] == 0 and s["cache_miss_bytes"] == 0
        assert s["cache_admitted_bytes"] == 0
        assert ctx_on.warm(path, [Segment(0, 0, 1 * MiB)]) == 0
        assert ctx_on.stats()["cache"]["cache_readahead_bytes"] == 0
        ctx_on.hot_cache.enabled = True
        ctx_on.pread(path, 0, 1 * MiB)
        assert ctx_on.stats()["cache"]["cache_admitted_bytes"] == 1 * MiB

    def test_thread_safety_under_concurrent_prefetcher(self, data_file):
        """Demand readers (a prefetcher's worker threads) racing the
        readahead warmer and each other: every delivered byte must stay
        exact while admission/eviction churn underneath."""
        path, data = data_file
        # small budget: eviction churns while readers hold views
        ctx = StromContext(_cfg(hot_cache_bytes=2 * MiB,
                                hot_cache_admit="always",
                                delivery_workers=4))
        errors: list = []

        def reader(seed: int) -> None:
            rng = np.random.default_rng(seed)
            try:
                for _ in range(30):
                    off = int(rng.integers(0, len(data) - 512 * KiB))
                    n = int(rng.integers(1, 512 * KiB))
                    got = np.asarray(memoryview(ctx.pread(path, off, n)))
                    np.testing.assert_array_equal(got, data[off: off + n])
            except Exception as e:  # pragma: no cover - failure path
                errors.append(e)

        ra = Readahead(
            ctx, lambda: [(path, [Segment(0, 0, 1 * MiB)], 0),
                          (path, [Segment(2 * MiB, 0, 1 * MiB)], 0)],
            interval_s=0.001)
        threads = [threading.Thread(target=reader, args=(i,))
                   for i in range(4)]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
        finally:
            ra.close()
            ctx.close()
        assert not errors, errors


class TestObsExposure:
    def test_metrics_and_stats_routes_expose_cache(self, ctx_on, data_file):
        """Cache counters ride /metrics (typed per the PR 3 exposition
        rules: HELP + counter/gauge TYPE) and /stats."""
        import urllib.request

        from strom.obs.server import MetricsServer

        path, _ = data_file
        ctx_on.pread(path, 0, 1 * MiB)
        ctx_on.pread(path, 0, 1 * MiB)
        srv = MetricsServer(ctx_on.stats, port=0)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/metrics", timeout=10) as r:
                text = r.read().decode()
            assert "# HELP strom_cache_cache_hit_bytes" in text
            assert "# TYPE strom_cache_cache_hit_bytes counter" in text
            assert "# TYPE strom_cache_cache_hit_ratio gauge" in text
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/stats", timeout=10) as r:
                doc = json.loads(r.read().decode())
            cache = doc["sections"]["cache"]
            assert cache["cache_hit_bytes"] > 0
            assert 0.0 < cache["cache_hit_ratio"] <= 1.0
        finally:
            srv.close()

    def test_cache_spans_on_event_ring(self, data_file):
        from strom.obs.events import ring

        path, _ = data_file
        ctx = StromContext(_cfg(hot_cache_bytes=8 * MiB,
                                hot_cache_admit="always"))
        try:
            t0 = ring.now_us()
            ctx.pread(path, 0, 1 * MiB)
            ctx.pread(path, 0, 1 * MiB)
            names = {e["name"] for e in ring.snapshot()
                     if e.get("cat") == "cache" and e["ts_us"] >= t0}
            assert "cache.admit" in names
            assert "cache.serve" in names
        finally:
            ctx.close()


def test_sampler_peek_is_epoch_aware():
    """peek() exports the upcoming window without moving the cursor and
    crosses the epoch boundary into the next permutation."""
    from strom.pipelines.sampler import EpochShuffleSampler

    s = EpochShuffleSampler(12, 4, seed=3)
    it = iter(s)
    first = next(it)
    # cursor now at batch 1 of epoch 0; peek 4 batches = rest of epoch 0
    # (2 batches) + head of epoch 1 (2 batches)
    window = s.peek(4)
    assert len(window) == 4
    upcoming = [next(it) for _ in range(4)]
    for w, u in zip(window, upcoming):
        np.testing.assert_array_equal(w, u)
    # the epoch-0 permutation covered all records exactly once
    seen = np.sort(np.concatenate([first] + upcoming[:2]))
    np.testing.assert_array_equal(seen, np.arange(12))
