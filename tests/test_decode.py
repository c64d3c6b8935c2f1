"""Decode-path parity (ISSUE 2 tentpole): reduced-scale JPEG decode chosen
from the SOF header, direct-to-slot decode workers, and overlapped
per-device shard delivery — each golden-tested against the path it
replaces (full-scale decode / np.stack / serial puts), plus the per-sample
decode-failure policy and the cv2 global-thread-count restore."""

import numpy as np
import pytest

cv2 = pytest.importorskip("cv2")

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from strom.config import StromConfig
from strom.delivery.core import StromContext
from strom.formats.jpeg import (DecodePool, decode_jpeg, make_train_transform,
                                parse_jpeg_dims, random_resized_crop,
                                reduced_denom)
from strom.parallel.mesh import make_mesh
from strom.utils.stats import global_stats


def smooth_jpeg(h, w, quality=95):
    """Low-frequency gradient image: JPEG encodes it near-losslessly, so the
    full-scale and reduced-scale decode paths agree within a small pixel
    tolerance (noise images would measure codec error, not the geometry)."""
    yy, xx = np.mgrid[0:h, 0:w]
    img = np.stack([yy * 255 / max(h - 1, 1),
                    xx * 255 / max(w - 1, 1),
                    (yy + xx) * 255 / max(h + w - 2, 1)],
                   axis=-1).astype(np.uint8)
    ok, buf = cv2.imencode(".jpg", img, [cv2.IMWRITE_JPEG_QUALITY, quality])
    assert ok
    return img, buf.tobytes()


def noise_jpeg(rng, h, w):
    img = rng.integers(0, 256, (h, w, 3), dtype=np.uint8)
    ok, buf = cv2.imencode(".jpg", img, [cv2.IMWRITE_JPEG_QUALITY, 90])
    assert ok
    return buf.tobytes()


def philox(seed, row):
    return np.random.Generator(np.random.Philox(key=[seed, row]))


# ------------------------------------------------------- SOF header parsing
class TestSofParser:
    @pytest.mark.parametrize("h,w", [(8, 8), (48, 64), (201, 317),
                                     (512, 512), (1024, 768)])
    def test_dims_match_decode(self, h, w):
        _, data = smooth_jpeg(h, w)
        assert parse_jpeg_dims(data) == (h, w)
        assert decode_jpeg(data).shape[:2] == (h, w)

    def test_progressive_sof2(self):
        img, _ = smooth_jpeg(120, 90)
        ok, buf = cv2.imencode(".jpg", img, [cv2.IMWRITE_JPEG_PROGRESSIVE, 1])
        assert ok
        assert parse_jpeg_dims(buf.tobytes()) == (120, 90)

    def test_ndarray_input(self):
        _, data = smooth_jpeg(40, 60)
        assert parse_jpeg_dims(np.frombuffer(data, np.uint8)) == (40, 60)

    def test_non_jpeg_returns_none(self):
        assert parse_jpeg_dims(b"definitely not a jpeg") is None
        img, _ = smooth_jpeg(16, 16)
        ok, png = cv2.imencode(".png", img)
        assert ok
        assert parse_jpeg_dims(png.tobytes()) is None

    def test_truncated_header_returns_none(self):
        _, data = smooth_jpeg(64, 64)
        assert parse_jpeg_dims(data[:4]) is None

    def test_denom_rule(self):
        # inputs are CROP dims: the reduced crop must still cover the target
        assert reduced_denom(1800, 1800, 224) == 8
        assert reduced_denom(500, 600, 224) == 2
        assert reduced_denom(300, 300, 224) == 1
        # the SHORTER side gates eligibility
        assert reduced_denom(4000, 100, 224) == 1
        assert reduced_denom(448, 448, 224) == 2
        assert reduced_denom(100, 100, 0) == 1


# --------------------------------------------- group 1: reduced-scale parity
class TestReducedScaleParity:
    def test_reduced_decode_shapes(self):
        _, data = smooth_jpeg(201, 317)
        for d in (2, 4, 8):
            img = decode_jpeg(data, reduced=d)
            # libjpeg reduced sizes are ceil(dim/d)
            assert img.shape == (-(-201 // d), -(-317 // d), 3)

    @pytest.mark.parametrize("h,w,size", [(512, 512, 64), (448, 640, 56),
                                          (256, 256, 96)])
    def test_matches_full_scale_within_tolerance(self, h, w, size):
        """Golden parity: reduced-scale decode + rescaled crop geometry
        lands within a small pixel tolerance of the full-scale path, with
        identical shape/dtype and an identical RNG stream."""
        _, data = smooth_jpeg(h, w)
        tf_full = make_train_transform(size, reduced_scale=False)
        tf_red = make_train_transform(size, reduced_scale=True)
        hits0 = sum(global_stats.counter(f"decode_reduced_hits_{d}").value
                    for d in (2, 4, 8))
        for seed in range(6):
            ra, rb = philox(1, seed), philox(1, seed)
            full = tf_full(data, ra)
            red = tf_red(data, rb)
            assert red.shape == full.shape == (size, size, 3)
            assert red.dtype == full.dtype == np.uint8
            diff = np.abs(full.astype(int) - red.astype(int))
            assert diff.mean() < 4.0 and diff.max() < 32, \
                (seed, diff.mean(), diff.max())
            # the two paths consumed the same number of RNG draws —
            # checkpoint-resume determinism does not depend on the knob
            assert ra.random() == rb.random()
        # the reduced path actually engaged across the seeds
        assert sum(global_stats.counter(f"decode_reduced_hits_{d}").value
                   for d in (2, 4, 8)) > hits0

    def test_hit_counters_bump(self):
        """Near-full-image crops of a 512^2 source cover a 32^2 target at
        1/8 scale, so the denom-8 counter must fire."""
        _, data = smooth_jpeg(512, 512)
        before = global_stats.counter("decode_reduced_hits_8").value
        make_train_transform(32, reduced_scale=True,
                             scale=(0.95, 1.0))(data, philox(0, 0))
        assert global_stats.counter("decode_reduced_hits_8").value == before + 1

    def test_small_crop_rides_full_path(self):
        """A crop below size*2 on its shorter side must NOT decode reduced —
        it would be upscaled from 1/d pixels where the full path downsamples
        real ones (quality, not just speed)."""
        _, data = smooth_jpeg(100, 100)  # crops can never reach 96*2
        snaps = {d: global_stats.counter(f"decode_reduced_hits_{d}").value
                 for d in (2, 4, 8)}
        out = make_train_transform(96, reduced_scale=True)(data, philox(0, 1))
        assert out.shape == (96, 96, 3)
        for d, v in snaps.items():
            assert global_stats.counter(f"decode_reduced_hits_{d}").value == v


# ------------------------------------------- group 2: direct-to-slot decode
class TestSlotDecode:
    def test_out_path_bit_identical_to_alloc_path(self, rng):
        img = rng.integers(0, 256, (100, 80, 3), dtype=np.uint8)
        for seed in range(8):  # both flip branches get exercised
            ref = random_resized_crop(img, 32, philox(2, seed))
            out = np.empty((32, 32, 3), np.uint8)
            got = random_resized_crop(img, 32, philox(2, seed), out=out)
            assert got is out
            np.testing.assert_array_equal(got, ref)

    def test_map_into_bit_identical_to_stack(self, rng):
        blobs = [noise_jpeg(rng, 60 + 7 * i, 90 - 5 * i) for i in range(6)]
        tf = make_train_transform(32)
        with DecodePool(3) as pool:
            ref = np.stack(pool.map(tf, blobs,
                                    [philox(3, i) for i in range(6)]))
            out = np.empty((6, 32, 32, 3), np.uint8)
            pool.map_into(tf, blobs, [philox(3, i) for i in range(6)], out)
        np.testing.assert_array_equal(out, ref)

    def test_decode_failure_zeroes_row_not_batch(self, rng):
        blobs = [noise_jpeg(rng, 50, 50), b"definitely not a jpeg",
                 noise_jpeg(rng, 50, 50)]
        tf = make_train_transform(16)
        before = global_stats.counter("decode_errors").value
        with DecodePool(2) as pool:
            out = np.full((3, 16, 16, 3), 255, np.uint8)
            pool.map_into(tf, blobs, [philox(4, i) for i in range(3)], out)
            assert pool.decode_errors == 1
        assert out[0].any()      # good rows decoded
        assert not out[1].any()  # bad row zeroed
        assert out[2].any()
        assert global_stats.counter("decode_errors").value == before + 1

    def test_map_keeps_abort_semantics(self, rng):
        """The legacy stack path (plain map) still aborts on garbage — the
        zero-substitution policy is a slot-path (map_into) contract."""
        with DecodePool(2) as pool:
            with pytest.raises(ValueError):
                pool.map(decode_jpeg, [b"garbage"])


# --------------------------------------- group 3: overlapped shard delivery
@pytest.fixture(scope="module")
def ctx():
    c = StromContext(StromConfig(engine="python", queue_depth=8,
                                 num_buffers=8))
    yield c
    c.close()


@pytest.fixture(scope="module")
def mesh():
    return make_mesh({"dp": 8}, devices=jax.devices()[:8])


@pytest.fixture(scope="module")
def wds_tar(tmp_path_factory):
    from tests.test_formats import make_wds_shard

    rng = np.random.default_rng(11)
    td = tmp_path_factory.mktemp("decode_wds")
    samples = []
    for i in range(16):
        # mixed sizes: some eligible for reduced decode at size 32, some not
        h = 40 + 8 * i
        samples.append((f"s{i:04d}", {"jpg": noise_jpeg(rng, h, h + 10),
                                      "cls": str(i % 10).encode()}))
    p = str(td / "shard.tar")
    make_wds_shard(p, samples)
    return p


class TestOverlappedDelivery:
    def _pipeline(self, ctx, mesh, tar, **kw):
        from strom.pipelines import make_wds_vision_pipeline

        return make_wds_vision_pipeline(
            ctx, [tar], batch=8, image_size=32,
            sharding=NamedSharding(mesh, P("dp", None, None, None)),
            shuffle=False, decode_workers=4, seed=5, **kw)

    def _batches(self, pipe, n=2):
        out = []
        with pipe:
            for _ in range(n):
                imgs, lbls = next(pipe)
                out.append((np.asarray(imgs).copy(), np.asarray(lbls).copy()))
        return out

    def test_overlapped_puts_match_serial(self, ctx, mesh, wds_tar):
        """The completion-ordered per-device puts assemble the same global
        array as decode-everything-then-put-serially."""
        ref = self._batches(self._pipeline(ctx, mesh, wds_tar,
                                           decode_to_slot=False,
                                           decode_overlap_put=False))
        got = self._batches(self._pipeline(ctx, mesh, wds_tar,
                                           decode_to_slot=True,
                                           decode_overlap_put=True))
        for (ri, rl), (gi, gl) in zip(ref, got):
            np.testing.assert_array_equal(ri, gi)
            np.testing.assert_array_equal(rl, gl)

    def test_slot_without_overlap_matches_stack(self, ctx, mesh, wds_tar):
        ref = self._batches(self._pipeline(ctx, mesh, wds_tar,
                                           decode_to_slot=False,
                                           decode_overlap_put=False))
        got = self._batches(self._pipeline(ctx, mesh, wds_tar,
                                           decode_to_slot=True,
                                           decode_overlap_put=False))
        for (ri, rl), (gi, gl) in zip(ref, got):
            np.testing.assert_array_equal(ri, gi)
            np.testing.assert_array_equal(rl, gl)

    def test_replicated_sharding_overlap(self, ctx, wds_tar):
        """Fully-replicated batch: every device owns every row (overlapping
        groups), the hardest completion-accounting case."""
        mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
        sharding = NamedSharding(mesh, P(None, None, None, None))
        from strom.pipelines import make_wds_vision_pipeline

        def build(**kw):
            return make_wds_vision_pipeline(
                ctx, [wds_tar], batch=4, image_size=32, sharding=sharding,
                shuffle=False, decode_workers=2, seed=5, **kw)

        ref = self._batches(build(decode_to_slot=False,
                                  decode_overlap_put=False), n=1)
        got = self._batches(build(decode_overlap_put=True), n=1)
        np.testing.assert_array_equal(ref[0][0], got[0][0])

    def test_slot_bytes_counter_and_stats_surface(self, ctx, mesh, wds_tar):
        before = global_stats.counter("decode_slot_bytes").value
        self._batches(self._pipeline(ctx, mesh, wds_tar), n=1)
        assert global_stats.counter("decode_slot_bytes").value > before
        dec = ctx.stats()["decode"]
        assert dec["decode_slot_bytes"] > 0
        assert dec["decode_batch_count"] > 0
        # the decode section rides the same Prometheus exposition as the
        # engine counters
        from strom.utils.stats import sections_prometheus

        text = sections_prometheus(ctx.stats())
        assert "strom_decode_decode_slot_bytes" in text
        assert "strom_decode_decode_batch_us_bucket" in text

    def test_decode_errors_surfaced_on_pipeline(self, ctx, mesh,
                                                tmp_path_factory):
        """A corrupt member yields a zero image row and a counted error —
        the batch (and the run) survives."""
        from tests.test_formats import make_wds_shard

        rng = np.random.default_rng(13)
        td = tmp_path_factory.mktemp("decode_err")
        samples = []
        for i in range(8):
            blob = b"CORRUPT" * 64 if i == 3 else noise_jpeg(rng, 48, 48)
            samples.append((f"s{i:04d}", {"jpg": blob,
                                          "cls": str(i).encode()}))
        tar = str(td / "bad.tar")
        make_wds_shard(tar, samples)
        with self._pipeline(ctx, mesh, tar) as pipe:
            imgs, _ = next(pipe)
            imgs_np = np.asarray(imgs)
            # >= 1, not == 1: the prefetcher may already be decoding the
            # next epoch's batch (same corrupt sample) when we look
            assert pipe.decode_errors >= 1
        assert not imgs_np[3].any()          # substituted zero image
        assert imgs_np[2].any() and imgs_np[4].any()


# --------------------------------------------------- cv2 global state hygiene
class TestCv2ThreadRestore:
    def test_close_restores_thread_count(self):
        prev = cv2.getNumThreads()
        try:
            cv2.setNumThreads(3)
            pool = DecodePool(2)
            pool.close()
            assert cv2.getNumThreads() == 3
            pool.close()  # idempotent: a second close must not re-restore
        finally:
            cv2.setNumThreads(prev)


# ------------------------------------------- group 4: decode worker processes
# One algorithm, two executors: the built-in transform decodes in worker
# processes when its bytes and rows lie in the pool's shared memory, and
# must give the thread path's bytes from the same Philox keys.
PROC_TF = make_train_transform(32)


def _truncated(data: bytes) -> bytes:
    """The member cut before its first scan: the header parses, every
    decoder refuses it (ValueError), the row is zeroed."""
    return data[:data.index(b"\xff\xda")]


@pytest.fixture(scope="module")
def mixed_batch(tmp_path_factory):
    """Baseline, progressive and one truncated member, in a tar shard:
    (blobs, tar path)."""
    from tests.test_formats import make_wds_shard

    rng = np.random.default_rng(17)
    blobs = []
    for i in range(10):
        h, w = 40 + 9 * i, 70 + 5 * i
        img = cv2.GaussianBlur(
            rng.integers(0, 256, (h, w, 3), dtype=np.uint8), (5, 5), 2)
        flags = [cv2.IMWRITE_JPEG_QUALITY, 90]
        if i % 3 == 1:
            flags += [cv2.IMWRITE_JPEG_PROGRESSIVE, 1]
        ok, buf = cv2.imencode(".jpg", img, flags)
        assert ok
        blobs.append(buf.tobytes())
    blobs[6] = _truncated(blobs[6])
    p = str(tmp_path_factory.mktemp("proc_wds") / "mixed.tar")
    make_wds_shard(p, [(f"s{i:04d}", {"jpg": b, "cls": str(i).encode()})
                       for i, b in enumerate(blobs)])
    return blobs, p


@pytest.fixture(scope="module")
def proc_pool():
    with DecodePool(2, transform=PROC_TF) as pool:
        yield pool


@pytest.fixture(scope="module")
def thread_pool():
    with DecodePool(2) as pool:
        yield pool


def _keys(n, serial=3):
    return [(2 ** 31 + 9, (serial << 32) + i) for i in range(n)]


def _decode_batch(path, pool, ctx, blobs, tar, tf=PROC_TF, size=32):
    """Decode *blobs* through one of vision.py's batch paths on *pool*;
    shared memory when the pool offloads *tf*. Returns the rows."""
    from strom.formats.wds import WdsShardSet
    from strom.pipelines.vision import (_decode_put_overlapped,
                                        _decode_put_streamed)

    n = len(blobs)
    shared = pool.offloads(tf)
    images = pool.slab((n, size, size, 3)) if shared \
        else np.empty((n, size, size, 3), np.uint8)
    images[...] = 255
    dev_items = [(jax.devices()[0], (0, n))]
    row_pos = {i: i for i in range(n)}
    buf = None
    try:
        if path == "streamed":
            ss = WdsShardSet([tar], ctx=ctx)
            el = ss.batch_extents(range(n), ["jpg", "cls"])
            sizes = [(s.members["jpg"].size, s.members["cls"].size)
                     for s in ss.samples]
            buf = pool.buffer(el.size) if shared else None
            shards, labels = _decode_put_streamed(
                ctx, pool, tf, el, sizes, _keys(n), images, dev_items,
                row_pos, buf=buf, shared=shared)
            assert labels == list(range(n))
            return np.asarray(shards[0]).copy()
        items = blobs
        if shared:
            buf = pool.buffer(sum(len(b) for b in blobs))
            items, pos = [], 0
            for b in blobs:
                buf[pos: pos + len(b)] = np.frombuffer(b, np.uint8)
                items.append(buf[pos: pos + len(b)])
                pos += len(b)
        if path == "overlapped":
            shards = _decode_put_overlapped(
                ctx, pool, tf, items, _keys(n), images, dev_items,
                row_pos, shared=shared)
            return np.asarray(shards[0]).copy()
        pool.map_into(tf, items, _keys(n), images)
        return images.copy()
    finally:
        if shared:
            pool.recycle(images, buf)


class TestDecodeProcesses:
    @pytest.mark.parametrize("path", ["map_into", "overlapped", "streamed"])
    def test_threads_and_processes_byte_identical(self, path, ctx,
                                                  mixed_batch, proc_pool,
                                                  thread_pool):
        """Baseline, progressive and a truncated member: the two executors
        deliver the same bytes, and each zeroes the truncated row and
        counts it once in decode_errors."""
        blobs, tar = mixed_batch
        got = {}
        for name, pool in (("threads", thread_pool),
                           ("processes", proc_pool)):
            e0, pe0 = global_stats.counter("decode_errors").value, \
                pool.decode_errors
            p0 = global_stats.counter("decode_proc_samples").value
            got[name] = _decode_batch(path, pool, ctx, blobs, tar)
            assert global_stats.counter("decode_errors").value == e0 + 1
            assert pool.decode_errors == pe0 + 1
            engaged = global_stats.counter("decode_proc_samples").value - p0
            assert engaged == (len(blobs) if name == "processes" else 0)
        assert not got["processes"][6].any()
        assert all(got["processes"][i].any() for i in range(10) if i != 6)
        np.testing.assert_array_equal(got["threads"], got["processes"])

    def test_routing_by_transform_and_memory(self, proc_pool, mixed_batch):
        """The pool offloads only its own built-in transform, and only
        rows in its shared memory; the rest land on threads, counted."""
        blobs = mixed_batch[0][:4]
        custom = make_train_transform(32)  # same algorithm, not the pool's
        for fn, out in ((custom, proc_pool.slab((4, 32, 32, 3))),
                        (PROC_TF, np.empty((4, 32, 32, 3), np.uint8))):
            t0 = global_stats.counter("decode_thread_samples").value
            p0 = global_stats.counter("decode_proc_samples").value
            proc_pool.map_into(fn, blobs, _keys(4), out)
            assert global_stats.counter("decode_thread_samples").value \
                == t0 + 4
            assert global_stats.counter("decode_proc_samples").value == p0
            if not isinstance(out, np.ndarray) or out.base is not None:
                proc_pool.recycle(out)
        assert not DecodePool(1).offloads(PROC_TF)

    def test_philox_keys_and_fresh_generators_travel(self):
        from strom.formats.jpeg import as_generator, philox_key

        assert philox_key((5, 7)) == (5, 7)
        fresh = philox(2 ** 31 + 1, (4 << 32) + 2)
        assert philox_key(fresh) == (2 ** 31 + 1, (4 << 32) + 2)
        used = philox(1, 2)
        used.random()
        assert philox_key(used) is None          # drawn from: threads
        assert philox_key(np.random.default_rng(0)) is None
        a, b = as_generator((3, 4)), philox(3, 4)
        assert a.random() == b.random()

    def test_pipeline_routes_builtin_to_processes(self, ctx, mesh, wds_tar):
        """A pipeline's own transform decodes in processes; a custom
        transform and an attached DecodedCache stay on threads."""
        from strom.pipelines import make_wds_vision_pipeline

        def build(c, **kw):
            return make_wds_vision_pipeline(
                c, [wds_tar], batch=8, image_size=32,
                sharding=NamedSharding(mesh, P("dp", None, None, None)),
                shuffle=False, decode_workers=2, seed=5,
                prefetch_depth=1, **kw)

        def engaged(c, **kw):
            t0 = global_stats.counter("decode_thread_samples").value
            p0 = global_stats.counter("decode_proc_samples").value
            with build(c, **kw) as pipe:
                next(pipe)
            return (global_stats.counter("decode_thread_samples").value - t0,
                    global_stats.counter("decode_proc_samples").value - p0)

        threads, procs = engaged(ctx)
        assert procs >= 8 and threads == 0
        dec = ctx.stats(sections=["decode"])["decode"]
        assert dec["decode_proc_samples"] >= 8
        assert dec["decode_proc_parent_us"] > 0
        from strom.pipelines.vision import default_train_transform

        threads, procs = engaged(ctx, transform=default_train_transform(32))
        assert threads >= 8 and procs == 0
        cached = StromContext(StromConfig(engine="python", queue_depth=8,
                                          num_buffers=8,
                                          hot_cache_bytes=16 << 20,
                                          hot_cache_admit="always"))
        try:
            threads, procs = engaged(cached, decode_cache=True)
        finally:
            cached.close()
        assert threads >= 8 and procs == 0

    def test_killed_worker_fails_its_batch_and_restarts(self, mixed_batch):
        import os
        import signal

        from strom.formats.decode_procs import DecodeWorkerError

        blobs = [b for i, b in enumerate(mixed_batch[0]) if i != 6] * 12
        n = len(blobs)
        r0 = global_stats.counter("decode_proc_restarts").value
        with DecodePool(1, transform=PROC_TF) as pool:
            (victim,) = pool._procs.pids
            out = pool.slab((n, 32, 32, 3))
            buf = pool.buffer(sum(len(b) for b in blobs))
            items, pos = [], 0
            for b in blobs:
                buf[pos: pos + len(b)] = np.frombuffer(b, np.uint8)
                items.append(buf[pos: pos + len(b)])
                pos += len(b)
            # one finished task: the worker is past start-up (READY
            # precedes its first result), so its death is a restart
            pool.map_into(PROC_TF, items[:1], _keys(1), out[:1])
            futs = [pool.submit_into(PROC_TF, items[i], k, out[i])
                    for i, k in enumerate(_keys(n))]
            os.kill(victim, signal.SIGKILL)
            with pytest.raises(DecodeWorkerError):
                for f in futs:
                    f.result(timeout=60)
            assert global_stats.counter("decode_proc_restarts").value \
                == r0 + 1
            # the replacement decodes the next batch into the same slab
            pool.map_into(PROC_TF, items[:4], _keys(4), out[:4])
            (fresh,) = pool._procs.pids
            assert fresh != victim and out[:4].any()
            pool.recycle(out, buf)
            del out, buf, items

    def test_close_leaves_no_process_and_no_segment(self, mixed_batch):
        import os

        blobs = mixed_batch[0][:3]
        pool = DecodePool(2, transform=PROC_TF)
        pids, tag = pool._procs.pids, pool._procs.tag
        out = pool.slab((3, 32, 32, 3))
        buf = pool.buffer(sum(len(b) for b in blobs))
        items, pos = [], 0
        for b in blobs:
            buf[pos: pos + len(b)] = np.frombuffer(b, np.uint8)
            items.append(buf[pos: pos + len(b)])
            pos += len(b)
        pool.map_into(PROC_TF, items, _keys(3), out)
        pool.recycle(out, buf)
        del out, buf, items

        def segments():
            with open("/proc/self/maps") as f:
                maps = sum(tag + "." in line for line in f)
            fds = sum(tag + "." in os.readlink(f"/proc/self/fd/{fd}")
                      for fd in os.listdir("/proc/self/fd")
                      if os.path.exists(f"/proc/self/fd/{fd}"))
            return maps + fds

        assert segments() > 0 and all(os.path.exists(f"/proc/{p}")
                                      for p in pids)
        pool.close()
        assert segments() == 0
        assert not any(os.path.exists(f"/proc/{p}") for p in pids)

    def test_worker_spans_feed_decode_ms_per_img(self, proc_pool,
                                                 mixed_batch):
        """One ``cat=decode`` X span per image, on the worker's lane, at
        the worker's start and wall time: what the benchmark's
        ``decode_ms_per_img`` reader averages."""
        import time
        from types import SimpleNamespace

        from benchmark.metrics import decode_ms_per_img
        from strom.obs.events import ring

        if not ring.enabled:
            pytest.skip("event ring disabled")
        blobs = mixed_batch[0]
        n = len(blobs)
        out = proc_pool.slab((n, 32, 32, 3))
        buf = proc_pool.buffer(sum(len(b) for b in blobs))
        items, pos = [], 0
        for b in blobs:
            buf[pos: pos + len(b)] = np.frombuffer(b, np.uint8)
            items.append(buf[pos: pos + len(b)])
            pos += len(b)
        t0 = ring.now_us()
        w0 = time.perf_counter()
        proc_pool.map_into(PROC_TF, items, _keys(n), out)
        wall_us = (time.perf_counter() - w0) * 1e6
        t1 = ring.now_us()
        proc_pool.recycle(out, buf)
        pids = set(proc_pool._procs.pids)
        spans = [e for e in ring.snapshot()
                 if e.get("cat") == "decode" and e["ph"] == "X"
                 and e["tid"] in pids and t0 <= e["ts_us"] <= t1]
        assert len(spans) == n
        assert all(0 < e["dur_us"] < wall_us for e in spans)
        assert all(e["ts_us"] + e["dur_us"] <= t1 for e in spans)
        ms = decode_ms_per_img.read(SimpleNamespace(layer={"events": spans}))
        assert ms == pytest.approx(
            sum(e["dur_us"] for e in spans) / n / 1e3)

    def test_process_runs_fuse_and_feed_ewma(self, proc_pool, mixed_batch):
        """Fused runs reach the workers as one task each, and the EWMA
        follows the workers' wall per image; at ~1.5 ms per image a
        128-sample batch fuses at least 2 images per task."""
        blobs = [b for i, b in enumerate(mixed_batch[0]) if i != 6]
        n = len(blobs)
        proc_pool._img_us = 50.0  # fast images: fuse
        run = proc_pool.run_size(n)
        assert run > 1
        out = proc_pool.slab((n, 32, 32, 3))
        buf = proc_pool.buffer(sum(len(b) for b in blobs))
        items, pos = [], 0
        for b in blobs:
            buf[pos: pos + len(b)] = np.frombuffer(b, np.uint8)
            items.append(buf[pos: pos + len(b)])
            pos += len(b)
        f0 = global_stats.counter("decode_fused_runs").value
        proc_pool._img_us = 1e6  # any process task pulls it down
        futs = [proc_pool.submit_run_into(PROC_TF, items[i: i + run],
                                          _keys(n)[i: i + run],
                                          [out[j] for j in
                                           range(i, min(i + run, n))])
                for i in range(0, n, run)]
        for f in futs:
            f.result(timeout=60)
        proc_pool.recycle(out, buf)
        assert global_stats.counter("decode_fused_runs").value > f0
        assert 0 < proc_pool._img_us < 1e6  # the workers' measurement
        proc_pool._img_us = 1500.0
        with DecodePool(13) as wide:
            wide._img_us = 1500.0
            assert wide.run_size(128) >= 2

    def test_region_lookup_at_adjacent_mappings(self):
        """Two shared regions can be adjacent in the address space: an
        address at one's end is the next one's start, never the first
        region's (a wrong answer hands a live slab out twice)."""
        from types import SimpleNamespace

        from strom.formats.decode_procs import DecodeProcs

        a = SimpleNamespace(addr=4096, size=4096)
        b = SimpleNamespace(addr=8192, size=4096)
        procs = DecodeProcs.__new__(DecodeProcs)
        procs._regions = {1: a, 2: b}
        for hint in (a, b, None):
            procs._hint = [hint, hint]
            assert procs._find(8192, 0) is b
            assert procs._find(8192 - 16, 16) is a
            assert procs._find(8192 - 16, 17) is None  # straddles both
            assert procs._find(12288, 0) is None

    @pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning")
    def test_reaper_failure_fails_the_batch_not_hangs(self, mixed_batch):
        """A fault in completion handling fails every pending task (the
        batch raises) and later calls raise too; nothing waits forever."""
        from strom.formats.decode_procs import DecodeWorkerError

        blobs = mixed_batch[0][:4]
        with DecodePool(1, transform=PROC_TF) as pool:
            def broken(*a):
                raise RuntimeError("injected")

            pool._procs._handle = broken
            out = pool.slab((4, 32, 32, 3))
            buf = pool.buffer(sum(len(b) for b in blobs))
            items, pos = [], 0
            for b in blobs:
                buf[pos: pos + len(b)] = np.frombuffer(b, np.uint8)
                items.append(buf[pos: pos + len(b)])
                pos += len(b)
            futs = [pool.submit_into(PROC_TF, items[i], k, out[i])
                    for i, k in enumerate(_keys(4))]
            with pytest.raises(DecodeWorkerError):
                for f in futs:
                    f.result(timeout=60)
            with pytest.raises(DecodeWorkerError):
                pool.submit_into(PROC_TF, items[0], _keys(1)[0], out[0])
            del out, buf, items

    def test_worker_that_never_starts_fails_calls(self, mixed_batch,
                                                  monkeypatch, tmp_path):
        """Construction does not wait for the workers' imports; a worker
        that dies before it is ready is not restarted: its tasks and every
        later call raise."""
        from strom.formats import decode_procs
        from strom.formats.decode_procs import DecodeWorkerError

        monkeypatch.setattr(decode_procs, "_WORKER",
                            str(tmp_path / "missing_worker.py"))
        blobs = mixed_batch[0][:2]
        with DecodePool(1, transform=PROC_TF) as pool:
            out = pool.slab((2, 32, 32, 3))
            buf = pool.buffer(sum(len(b) for b in blobs))
            items, pos = [], 0
            for b in blobs:
                buf[pos: pos + len(b)] = np.frombuffer(b, np.uint8)
                items.append(buf[pos: pos + len(b)])
                pos += len(b)
            with pytest.raises(DecodeWorkerError, match="exited with code"):
                for _ in range(100):  # until the failure is seen
                    f = pool.submit_into(PROC_TF, items[0], _keys(1)[0],
                                         out[0])
                    f.result(timeout=60)
            del out, buf, items
