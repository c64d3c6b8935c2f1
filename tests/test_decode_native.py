"""Decode path v2 (ISSUE 12 tentpole): native libjpeg-turbo binding parity
against the cv2 path (bit-exact for full/reduced decode, bit-exact interior
for ROI), progressive (SOF2) routing, fused-run dispatch, the decoded-output
cache, span gating with telemetry off, and the build-probe fallback on a
host without usable libjpeg-turbo headers."""

import os
import subprocess
import sys

import numpy as np
import pytest

cv2 = pytest.importorskip("cv2")

from strom.formats import jpeg as J
from strom.formats.jpeg import (DECODE2_FIELDS, DecodePool, decode_jpeg,
                                make_train_transform, parse_jpeg_dims,
                                parse_jpeg_info)
from strom.utils.stats import global_stats

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

needs_native = pytest.mark.skipif(not J.native_available(),
                                  reason="native jpeg binding not built "
                                         "(no libjpeg-turbo headers)")


def enc(img, quality=90, progressive=False):
    flags = [cv2.IMWRITE_JPEG_QUALITY, quality]
    if progressive:
        flags += [cv2.IMWRITE_JPEG_PROGRESSIVE, 1]
    ok, buf = cv2.imencode(".jpg", img, flags)
    assert ok
    return buf.tobytes()


def noise(rng, h, w):
    return rng.integers(0, 256, (h, w, 3), dtype=np.uint8)


def cv2_rgb(data, reduced=1):
    flag = {1: cv2.IMREAD_COLOR, 2: cv2.IMREAD_REDUCED_COLOR_2,
            4: cv2.IMREAD_REDUCED_COLOR_4,
            8: cv2.IMREAD_REDUCED_COLOR_8}[reduced]
    img = cv2.imdecode(np.frombuffer(data, np.uint8), flag)
    return cv2.cvtColor(img, cv2.COLOR_BGR2RGB)


def philox(seed, row):
    return np.random.Generator(np.random.Philox(key=[seed, row]))


# ------------------------------------------------- SOF info (progressive fix)
class TestParseInfo:
    def test_baseline_not_progressive(self):
        rng = np.random.default_rng(0)
        info = parse_jpeg_info(enc(noise(rng, 80, 100)))
        assert info == (80, 100, False)

    def test_progressive_flag_golden(self):
        """The ISSUE 12 satellite golden fixture: a progressive (SOF2)
        member must carry the flag — the ROI router branches on it, since
        partial-scanline decode silently yields WRONG pixels on multi-scan
        files (no error, just corrupt training data)."""
        rng = np.random.default_rng(1)
        data = enc(noise(rng, 120, 90), progressive=True)
        info = parse_jpeg_info(data)
        assert info is not None and info.progressive
        assert (info.h, info.w) == (120, 90)
        # the dims-only wrapper keeps its historical contract
        assert parse_jpeg_dims(data) == (120, 90)

    def test_non_jpeg_none(self):
        assert parse_jpeg_info(b"not a jpeg at all") is None
        rng = np.random.default_rng(2)
        ok, png = cv2.imencode(".png", noise(rng, 16, 16))
        assert parse_jpeg_info(png.tobytes()) is None


# ------------------------------------------------------ native decode parity
@needs_native
class TestNativeParity:
    @pytest.mark.parametrize("h,w", [(64, 64), (201, 317), (448, 448),
                                     (95, 101)])
    def test_full_decode_bit_exact(self, h, w):
        rng = np.random.default_rng(h * w)
        data = enc(noise(rng, h, w))
        np.testing.assert_array_equal(J.decode_native(data), cv2_rgb(data))

    def test_grayscale_bit_exact(self):
        rng = np.random.default_rng(9)
        gray = rng.integers(0, 256, (70, 90), dtype=np.uint8)
        ok, buf = cv2.imencode(".jpg", gray, [cv2.IMWRITE_JPEG_QUALITY, 90])
        data = buf.tobytes()
        np.testing.assert_array_equal(J.decode_native(data), cv2_rgb(data))

    @pytest.mark.parametrize("d", [2, 4, 8])
    def test_reduced_bit_exact(self, d):
        rng = np.random.default_rng(d)
        data = enc(noise(rng, 403, 321))
        np.testing.assert_array_equal(J.decode_native(data, reduced=d),
                                      cv2_rgb(data, reduced=d))

    def test_out_param(self):
        rng = np.random.default_rng(11)
        data = enc(noise(rng, 60, 80))
        out = np.empty((60, 80, 3), np.uint8)
        got = J.decode_native(data, out=out)
        assert got is out
        np.testing.assert_array_equal(out, cv2_rgb(data))
        with pytest.raises(ValueError):
            J.decode_native(data, out=np.empty((59, 80, 3), np.uint8))

    @pytest.mark.parametrize("y,x,h,w", [
        (37, 53, 120, 200),   # interior rect
        (0, 0, 400, 600),     # whole frame as an ROI
        (0, 0, 16, 16),       # top-left corner
        (384, 584, 16, 16),   # bottom-right corner
        (100, 0, 50, 600),    # full-width band
        (0, 100, 400, 50),    # full-height band
        (399, 0, 1, 600),     # last row
    ])
    def test_roi_bit_exact_interior(self, y, x, h, w):
        """The returned rect (granted-edge columns excluded by the x
        margin) is bit-exact against a full decode — the property the
        transform-level parity rests on."""
        rng = np.random.default_rng(77)
        data = enc(noise(rng, 400, 600), quality=92)
        full = cv2_rgb(data)
        rect = J.decode_native(data, roi=(y, x, h, w))
        assert rect.shape == (h, w, 3)
        np.testing.assert_array_equal(rect, full[y: y + h, x: x + w])

    @pytest.mark.parametrize("d", [2, 4])
    def test_roi_composes_with_reduced(self, d):
        rng = np.random.default_rng(5)
        data = enc(noise(rng, 400, 600))
        full = cv2_rgb(data, reduced=d)
        rh, rw = full.shape[:2]
        y, x, h, w = rh // 4, rw // 4, rh // 2, rw // 2
        rect = J.decode_native(data, reduced=d, roi=(y, x, h, w))
        np.testing.assert_array_equal(rect, full[y: y + h, x: x + w])

    def test_roi_progressive_raises(self):
        """Defense in depth below the router: the C side refuses an ROI on
        a progressive source instead of returning wrong pixels."""
        rng = np.random.default_rng(6)
        data = enc(noise(rng, 128, 128), progressive=True)
        with pytest.raises(ValueError):
            J.decode_native(data, roi=(10, 10, 32, 32))
        # full decode of the same progressive member is fine and exact
        np.testing.assert_array_equal(J.decode_native(data), cv2_rgb(data))

    def test_roi_out_of_bounds_raises(self):
        rng = np.random.default_rng(7)
        data = enc(noise(rng, 64, 64))
        with pytest.raises(ValueError):
            J.decode_native(data, roi=(0, 0, 65, 64))

    def test_garbage_raises_valueerror(self):
        with pytest.raises(ValueError):
            J.decode_native(b"\xff\xd8definitely not entropy data")
        with pytest.raises(ValueError):
            J.decode_native(b"no soi marker here whatsoever")


# ------------------------------------------------- transform-level parity
@needs_native
class TestTransformV2:
    def _data(self, h=448, w=448, seed=3):
        rng = np.random.default_rng(seed)
        return enc(noise(rng, h, w))

    def test_native_matches_cv2_path_bit_exact(self):
        data = self._data()
        tf_old = make_train_transform(224, native=False, roi=False)
        tf_nat = make_train_transform(224, native=True, roi=False)
        for seed in range(8):
            ra, rb = philox(1, seed), philox(1, seed)
            np.testing.assert_array_equal(tf_old(data, ra), tf_nat(data, rb))
            # identical RNG consumption: checkpoint-resume determinism
            # does not depend on the knob
            assert ra.random() == rb.random()

    def test_roi_matches_full_path_bit_exact(self):
        data = self._data()
        tf_old = make_train_transform(224, native=False, roi=False)
        tf_roi = make_train_transform(224, native=True, roi=True)
        hits0 = global_stats.counter("decode_roi_hits").value
        rows0 = global_stats.counter("decode_roi_rows_skipped").value
        for seed in range(8):
            ra, rb = philox(2, seed), philox(2, seed)
            np.testing.assert_array_equal(tf_old(data, ra), tf_roi(data, rb))
            assert ra.random() == rb.random()
        assert global_stats.counter("decode_roi_hits").value > hits0
        assert global_stats.counter("decode_roi_rows_skipped").value > rows0

    def test_roi_composed_with_reduced_within_tolerance(self):
        """A high-res source engages reduced_denom AND the ROI on the
        reduced plane; parity vs the (reduced, non-ROI) path is bit-exact,
        and vs full-scale stays within the established codec tolerance."""
        rng = np.random.default_rng(21)
        # smooth gradient: near-lossless encode, same reasoning as
        # test_decode.smooth_jpeg
        yy, xx = np.mgrid[0:1024, 0:1024]
        img = np.stack([yy * 255 // 1023, xx * 255 // 1023,
                        (yy + xx) * 255 // 2046], axis=-1).astype(np.uint8)
        data = enc(img, quality=95)
        tf_red = make_train_transform(64, native=False, roi=False)
        tf_roi = make_train_transform(64, native=True, roi=True)
        red_hits0 = sum(global_stats.counter(f"decode_reduced_hits_{d}").value
                        for d in (2, 4, 8))
        for seed in range(4):
            ra, rb = philox(3, seed), philox(3, seed)
            a, b = tf_red(data, ra), tf_roi(data, rb)
            np.testing.assert_array_equal(a, b)
            assert ra.random() == rb.random()
        # the reduced path actually engaged under ROI
        assert sum(global_stats.counter(f"decode_reduced_hits_{d}").value
                   for d in (2, 4, 8)) > red_hits0

    def test_progressive_member_routed_to_full_decode(self):
        rng = np.random.default_rng(8)
        data = enc(noise(rng, 300, 300), progressive=True)
        tf_old = make_train_transform(128, native=False, roi=False)
        tf_roi = make_train_transform(128, native=True, roi=True)
        hits0 = global_stats.counter("decode_roi_hits").value
        for seed in range(4):
            ra, rb = philox(4, seed), philox(4, seed)
            np.testing.assert_array_equal(tf_old(data, ra), tf_roi(data, rb))
        # ROI never engaged on the progressive member
        assert global_stats.counter("decode_roi_hits").value == hits0


# ---------------------------------------------------------- fused dispatch
class TestFusedDispatch:
    def _blobs(self, n=12):
        rng = np.random.default_rng(13)
        return [enc(noise(rng, 80 + 8 * i, 100)) for i in range(n)]

    def test_run_size_rules(self):
        with DecodePool(2, fuse_runs=False) as p:
            assert p.run_size(64) == 1
        with DecodePool(2, fuse_runs=True) as p:
            assert p.run_size(1) == 1
            p._img_us = 50.0  # fast images -> want big runs
            # balance cap: every worker still sees >= 2 runs
            assert p.run_size(64) == -(-64 // (p.workers * 2))
            p._img_us = 1e6   # slow images -> no fusing worth it
            assert p.run_size(64) == 1

    def test_fused_map_into_bit_identical(self):
        blobs = self._blobs()
        tf = make_train_transform(32, native=False)
        ref = np.empty((12, 32, 32, 3), np.uint8)
        out = np.empty((12, 32, 32, 3), np.uint8)
        with DecodePool(3, fuse_runs=False) as p:
            p.map_into(tf, blobs, [philox(5, i) for i in range(12)], ref)
        runs0 = global_stats.counter("decode_fused_runs").value
        with DecodePool(3, fuse_runs=True) as p:
            p._img_us = 50.0  # force fusing regardless of host speed
            assert p.run_size(12) > 1
            p.map_into(tf, blobs, [philox(5, i) for i in range(12)], out)
        np.testing.assert_array_equal(ref, out)
        assert global_stats.counter("decode_fused_runs").value > runs0

    def test_fused_run_error_policy_per_sample(self):
        blobs = self._blobs(6)
        blobs[2] = b"definitely not a jpeg"
        tf = make_train_transform(16, native=False)
        before = global_stats.counter("decode_errors").value
        with DecodePool(2, fuse_runs=True) as p:
            p._img_us = 50.0
            out = np.full((6, 16, 16, 3), 255, np.uint8)
            p.map_into(tf, blobs, [philox(6, i) for i in range(6)], out)
            assert p.decode_errors == 1
        assert not out[2].any()          # bad row zeroed
        assert out[1].any() and out[3].any()  # run neighbors decoded
        assert global_stats.counter("decode_errors").value == before + 1

    def test_run_timing_feeds_ewma(self):
        blobs = self._blobs(8)
        tf = make_train_transform(32, native=False)
        with DecodePool(2, fuse_runs=True) as p:
            p._img_us = 1e9  # run 1: absurd seed, corrected by measurement
            p.map_into(tf, blobs, [philox(7, i) for i in range(8)],
                       np.empty((8, 32, 32, 3), np.uint8))
            # wait: run_size==1 path uses submit_into (no EWMA update);
            # drive a fused run explicitly
            p._img_us = 50.0
            p.map_into(tf, blobs, [philox(7, i) for i in range(8)],
                       np.empty((8, 32, 32, 3), np.uint8))
            assert 0 < p._img_us < 1e6  # converged toward reality


# ------------------------------------------------ span gating (satellite)
class TestSpanGating:
    def test_no_ring_events_when_disabled(self):
        from strom.obs.events import ring

        blobs = [enc(noise(np.random.default_rng(15), 40, 40))]
        tf = make_train_transform(16, native=False)
        prev = ring.enabled
        ring.enabled = False
        try:
            assert DecodePool._worker_span(None) is None
            n0 = ring.events_written
            with DecodePool(1) as p:
                p.map_into(tf, blobs, [philox(8, 0)],
                           np.empty((1, 16, 16, 3), np.uint8))
            assert ring.events_written == n0
        finally:
            ring.enabled = prev
        # enabled again: the decode span flows as before
        if prev:
            n0 = ring.events_written
            with DecodePool(1) as p:
                p.map_into(tf, blobs, [philox(8, 0)],
                           np.empty((1, 16, 16, 3), np.uint8))
            assert ring.events_written > n0


# ------------------------------------------------------ decoded-output cache
class TestDecodedCache:
    def _cache(self, mb=8):
        from strom.delivery.hotcache import HotCache

        return HotCache(mb * 1024 * 1024, admit="always")

    def test_roundtrip_and_counters(self):
        from strom.formats.decoded_cache import DecodedCache

        hc = self._cache()
        dc = DecodedCache(hc, fingerprint="rgb8/test")
        rng = np.random.default_rng(17)
        img = noise(rng, 50, 60)
        key = dc.key("/data/shard.tar", 1024, 9999)
        assert dc.get(key, 50, 60) is None
        assert dc.misses == 1
        assert dc.offer(key, img) == img.nbytes
        got = dc.get(key, 50, 60)
        assert got is not None
        view, pin = got
        np.testing.assert_array_equal(view, img)
        assert pin.refs == 1  # pinned for the crop+resize window
        dc.release(pin)
        assert pin.refs == 0
        assert dc.hits == 1 and dc.hit_bytes == img.nbytes

    def test_fingerprint_splits_keys(self):
        from strom.formats.decoded_cache import DecodedCache

        hc = self._cache()
        a = DecodedCache(hc, fingerprint="rgb8/turbo")
        b = DecodedCache(hc, fingerprint="rgb8/cv2")
        img = noise(np.random.default_rng(18), 20, 20)
        a.offer(a.key("s.tar", 0, 100), img)
        assert b.get(b.key("s.tar", 0, 100), 20, 20) is None

    def test_disabled_cache_serves_nothing(self):
        from strom.formats.decoded_cache import DecodedCache

        hc = self._cache()
        hc.enabled = False
        dc = DecodedCache(hc)
        assert not dc.enabled

    def test_tenant_partition_bounds_decoded_set(self):
        """Decoded frames charge the owning tenant's partition (ISSUE 7
        composition): a tenant at its cap self-evicts its own decoded
        entries and can never displace another tenant's."""
        from strom.formats.decoded_cache import DecodedCache

        hc = self._cache(64)
        img = noise(np.random.default_rng(19), 128, 128)  # 48KiB
        charge = hc._charge(img.nbytes)
        hc.set_partition("t1", 2 * charge)
        dc = DecodedCache(hc, tenant="t1")
        for i in range(4):
            dc.offer(dc.key("s.tar", i * 1000, i * 1000 + 500), img)
        parts = hc.partitions()
        assert parts["t1"]["bytes"] <= 2 * charge


# --------------------------------------------- pipeline-level decode cache
@pytest.fixture(scope="module")
def vision_setup(tmp_path_factory):
    import io
    import tarfile

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from strom.parallel.mesh import make_mesh

    rng = np.random.default_rng(23)
    td = tmp_path_factory.mktemp("decode2_wds")
    p = str(td / "shard.tar")
    with tarfile.open(p, "w") as tf:
        for i in range(16):
            blob = enc(noise(rng, 64 + 4 * i, 80))
            for name, data in ((f"s{i:04d}.jpg", blob),
                               (f"s{i:04d}.cls", str(i % 10).encode())):
                info = tarfile.TarInfo(name)
                info.size = len(data)
                tf.addfile(info, io.BytesIO(data))
    mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
    return p, NamedSharding(mesh, P("dp", None, None, None))


class TestPipelineDecodeCache:
    def _batches(self, ctx, tar, sharding, n=4, **kw):
        from strom.pipelines import make_wds_vision_pipeline

        out = []
        with make_wds_vision_pipeline(
                ctx, [tar], batch=8, image_size=32, sharding=sharding,
                shuffle=False, decode_workers=2, seed=5, **kw) as pipe:
            for _ in range(n):
                imgs, lbls = next(pipe)
                out.append((np.asarray(imgs).copy(),
                            np.asarray(lbls).copy()))
        return out

    def test_cache_on_bit_identical_and_serves_epoch2(self, vision_setup):
        from strom.config import StromConfig
        from strom.delivery.core import StromContext

        tar, sharding = vision_setup
        ctx = StromContext(StromConfig(engine="python", queue_depth=8,
                                       num_buffers=8,
                                       hot_cache_bytes=64 * 1024 * 1024,
                                       hot_cache_admit="always"))
        try:
            # reduced off on both sides: the cached path serves full-
            # fidelity pixels, so bit-identity holds against the
            # full-decode path (the reduced path is an approximation)
            ref = self._batches(ctx, tar, sharding,
                                decode_reduced_scale=False,
                                decode_cache=False)
            h0 = global_stats.counter("decode_cache_hits").value
            a0 = global_stats.counter("decode_cache_admitted_bytes").value
            got = self._batches(ctx, tar, sharding,
                                decode_reduced_scale=False,
                                decode_cache=True)
            for (ri, rl), (gi, gl) in zip(ref, got):
                np.testing.assert_array_equal(ri, gi)
                np.testing.assert_array_equal(rl, gl)
            # 4 batches x 8 rows over a 16-sample set = 2 epochs: epoch 1
            # admits, epoch 2 serves decoded pixels from RAM
            assert global_stats.counter(
                "decode_cache_admitted_bytes").value > a0
            assert global_stats.counter("decode_cache_hits").value >= h0 + 16
        finally:
            ctx.close()

    def test_plan_probe_skips_image_gather(self, vision_setup):
        """Decoded-cache fast path (ISSUE 13 satellite): epoch >= 2 probes
        the cache BEFORE extent planning — hit samples never gather their
        image member (labels + misses only), batches stay bit-identical to
        the full-gather path, and the gathered-byte counter collapses."""
        from strom.config import StromConfig
        from strom.delivery.core import StromContext

        tar, sharding = vision_setup
        ctx = StromContext(StromConfig(engine="python", queue_depth=8,
                                       num_buffers=8,
                                       hot_cache_bytes=64 * 1024 * 1024,
                                       hot_cache_admit="always"))
        try:
            ref = self._batches(ctx, tar, sharding,
                                decode_reduced_scale=False,
                                decode_cache=False)
            ph0 = global_stats.counter("decode_cache_plan_hits").value
            ssd0 = global_stats.counter("ssd2tpu_bytes").value
            got = self._batches(ctx, tar, sharding,
                                decode_reduced_scale=False,
                                decode_cache=True)
            for (ri, rl), (gi, gl) in zip(ref, got):
                np.testing.assert_array_equal(ri, gi)
                np.testing.assert_array_equal(rl, gl)
            # 4 batches x 8 rows over 16 samples = 2 epochs: epoch 2's 16
            # rows (prefetch may run ahead) hit at PLAN time
            assert global_stats.counter(
                "decode_cache_plan_hits").value >= ph0 + 16
            assert global_stats.counter(
                "decode_cache_plan_skipped_bytes").value > 0
            # the cache-on pass gathered roughly half the bytes of the
            # cache-off pass (epoch 2 fetched labels only)
            cache_on_bytes = global_stats.counter("ssd2tpu_bytes").value \
                - ssd0
            full = sum(os.path.getsize(tar) for _ in (0,))
            assert cache_on_bytes < full * 2  # 4 batches ~ 2 epochs worth
        finally:
            ctx.close()

    def test_knobs_surface_in_stats_and_metrics(self, vision_setup):
        from strom.config import StromConfig
        from strom.delivery.core import StromContext
        from strom.utils.stats import sections_prometheus

        tar, sharding = vision_setup
        ctx = StromContext(StromConfig(engine="python", queue_depth=8,
                                       num_buffers=8))
        try:
            self._batches(ctx, tar, sharding, n=2)
            dec = ctx.stats(sections=["decode"])["decode"]
            for k in ("decode_native_imgs", "decode_fused_runs",
                      "decode_roi_hits", "decode_roi_rows_skipped",
                      "decode_cache_hits", "decode_cache_misses"):
                assert k in dec
            text = sections_prometheus(ctx.stats())
            assert "strom_decode_decode_fused_runs" in text
            assert "strom_decode_decode_roi_rows_skipped" in text
        finally:
            ctx.close()


# --------------------------------------------- build-probe fallback (subproc)
class TestBuildProbeFallback:
    def test_poisoned_include_path_falls_back_to_cv2(self, tmp_path):
        """ISSUE 12 satellite: on a host whose libjpeg-turbo headers are
        unusable, the engine still builds, import succeeds,
        ``decode_native is None``, and the cv2 decode path works. The
        poison is a shadowing jpeglib.h that #errors; the build lands in
        an isolated STROM_CORE_BUILD_DIR so the real .so is untouched."""
        poison = tmp_path / "poison"
        poison.mkdir()
        (poison / "jpeglib.h").write_text("#error poisoned include path\n")
        build = tmp_path / "build"
        env = dict(os.environ,
                   STROM_JPEG_CFLAGS=f"-I{poison}",
                   STROM_CORE_BUILD_DIR=str(build),
                   JAX_PLATFORMS="cpu")
        code = """
import numpy as np
from strom._core.build import ensure_built, jpeg_probe
assert jpeg_probe() is False, "poisoned probe must fail"
so = ensure_built()
import os
assert os.path.exists(so)
import ctypes
assert ctypes.CDLL(so).sc_jpeg_available() == 0
from strom.formats import jpeg as J
assert J.decode_native is None, "decode_native must resolve to None"
assert J.native_available() is False
# the cv2 path still decodes; the transform still works end to end
import cv2
img = np.random.default_rng(0).integers(0, 256, (64, 64, 3), dtype=np.uint8)
ok, buf = cv2.imencode(".jpg", img)
tf = J.make_train_transform(32, native=True, roi=True)  # knob on, lib absent
out = tf(buf.tobytes(), np.random.Generator(np.random.Philox(key=[0, 0])))
assert out.shape == (32, 32, 3)
print("FALLBACK_OK")
"""
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=300,
                              cwd=_ROOT)
        assert proc.returncode == 0, proc.stderr
        assert "FALLBACK_OK" in proc.stdout


# ----------------------------------------------------- field single-sourcing
def test_decode2_fields_are_counters_or_phase_keys():
    """Every DECODE2_FIELDS member is either a live global counter the
    decode path feeds or a rate/ratio the decode-v2 phase computes — the
    tuple is the single source the bench copy loop, compare_rounds and
    bench_sentinel all read."""
    phase_only = {"decode_native_img_per_s", "decode_cv2_img_per_s",
                  "decode_native_vs_cv2", "decode_cache_cold_img_per_s",
                  "decode_cache_warm_img_per_s",
                  "decode_cache_warm_vs_cold"}
    counters = set(DECODE2_FIELDS) - phase_only
    for k in counters:
        # touching the counter creates it if missing; the point is the
        # NAME is identical to what the producers feed (lint enforces the
        # near-duplicate half, this pins exact membership)
        assert global_stats.counter(k).value >= 0


# -------------------------------------- decode worker processes, native path
# the pipelines' default transform at a size where ROI and reduced-scale
# decode both engage on these members
NATIVE_TF = make_train_transform(64)


@pytest.fixture(scope="module")
def native_batch(tmp_path_factory):
    """Baseline members (some wide enough for 1/2 decode), progressive
    members (ROI refused: full decode) and one truncated member, in a tar
    shard: (blobs, tar path)."""
    from tests.test_decode import _truncated
    from tests.test_formats import make_wds_shard

    rng = np.random.default_rng(29)
    blobs = []
    for i in range(9):
        h, w = 200 + 40 * i, 260 + 30 * i
        img = cv2.GaussianBlur(noise(rng, h, w), (7, 7), 2)
        blobs.append(enc(img, progressive=(i % 4 == 2)))
    blobs[5] = _truncated(blobs[5])
    p = str(tmp_path_factory.mktemp("native_proc_wds") / "native.tar")
    make_wds_shard(p, [(f"s{i:04d}", {"jpg": b, "cls": str(i).encode()})
                       for i, b in enumerate(blobs)])
    return blobs, p


@pytest.fixture(scope="module")
def native_pools():
    from strom.config import StromConfig
    from strom.delivery.core import StromContext

    ctx = StromContext(StromConfig(engine="python", queue_depth=8,
                                   num_buffers=8))
    with DecodePool(2) as threads, \
            DecodePool(2, transform=NATIVE_TF) as procs:
        yield ctx, threads, procs
    ctx.close()


@needs_native
@pytest.mark.parametrize("path", ["map_into", "overlapped", "streamed"])
def test_native_roi_threads_and_processes_byte_identical(path, native_batch,
                                                         native_pools):
    """Native + ROI + reduced decode, progressive and truncated members:
    worker processes deliver the thread path's bytes and feed the same
    counters (their tallies travel back with each task)."""
    from strom.formats.jpeg_core import COUNTERS
    from tests.test_decode import _decode_batch

    blobs, tar = native_batch
    ctx, threads, procs = native_pools
    assert procs.offloads(NATIVE_TF) and not threads.offloads(NATIVE_TF)
    got, tallies = {}, {}
    for name, pool in (("threads", threads), ("processes", procs)):
        names = COUNTERS + ("decode_errors",)
        c0 = {k: global_stats.counter(k).value for k in names}
        got[name] = _decode_batch(path, pool, ctx, blobs, tar, NATIVE_TF, 64)
        tallies[name] = {k: global_stats.counter(k).value - c0[k]
                         for k in names}
    np.testing.assert_array_equal(got["threads"], got["processes"])
    assert tallies["threads"] == tallies["processes"]
    assert tallies["processes"]["decode_roi_hits"] > 0
    assert tallies["processes"]["decode_errors"] == 1
    assert not got["processes"][5].any()
