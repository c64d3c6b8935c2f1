"""JPEG host decode + ImageNet-style transforms (SURVEY.md §7.2 step 7:
"JPEG (host decode worker pool)").

The engine lands compressed bytes in host slabs; decode runs on a thread pool
(cv2 releases the GIL inside imdecode, so threads scale) and the decoded
uint8 tensor is what gets `device_put`.  Keeping decode on host mirrors the
division of labor in the reference's consumer (PG-Strom decompresses on GPU —
strom-tpu instead keeps the TPU's MXU for the model and spends host cores on
decode; the "0 data-stall" overlap hides both).  Consumer: the ResNet-50
pipeline (BASELINE config #2, BASELINE.json:8).

Decode-path scheduling (ISSUE 2 tentpole):

- **Reduced-scale decode**: when the SAMPLED crop at 1/d scale still covers
  the target (d in 2/4/8; encoded dims read from the SOF header by
  :func:`parse_jpeg_dims` without decoding), decode via cv2's
  ``IMREAD_REDUCED_COLOR_{2,4,8}`` — libjpeg skips the corresponding IDCT
  work, up to 64x less at 1/8. The crop geometry is sampled in FULL-res
  coordinates BEFORE the denominator is chosen (RNG stream identical either
  way) and rescaled onto the reduced image; a crop that would need
  upscaling at 1/d rides a smaller d or the full path, so the knob is
  quality-neutral.  Counters: ``decode_reduced_hits_{2,4,8}``.
- **Direct-to-slot decode**: every transform takes an optional ``out=`` row
  (the final size x size x 3 destination inside a preallocated batch array)
  so the resize lands its pixels straight into the batch slot — no
  ``np.stack`` pass over the batch, no per-row output temporaries.
  :meth:`DecodePool.map_into` drives it; ``decode_slot_bytes`` counts the
  bytes delivered this way.
- **Per-sample failure policy** (slot path): a ``ValueError`` decode failure
  zeroes the row and bumps ``decode_errors`` instead of aborting the whole
  batch — one truncated JPEG in a million-sample epoch is data loss of one
  sample, not of the run.

Decode path v2 (ISSUE 12 tentpole; knobs ``decode_native`` /
``decode_fuse_runs`` / ``decode_roi`` / ``decode_cache``):

- **Native turbo bindings**: :data:`decode_native` resolves lazily to a
  ctypes wrapper over ``sc_jpeg_decode`` in strom/_core (libjpeg-turbo,
  build-probed — None when the headers are absent and every caller keeps
  the cv2 path). One C call decodes straight to RGB in a caller buffer:
  no cv2 per-call Mat setup, no BGR intermediate + cvtColor pass. Full
  decode is bit-exact against cv2 (both ride libjpeg-turbo's islow IDCT).
- **ROI / partial-MCU decode**: the crop rectangle is already fixed in
  full-res coordinates BEFORE decode (:func:`sample_rrc_geometry`), so the
  native path decodes only the crop's scanlines (``jpeg_skip_scanlines``)
  and iMCU columns (``jpeg_crop_scanline``), composing with the existing
  ``reduced_denom`` rule — RNG stream and quality semantics unchanged.
  Progressive (SOF2) members are routed to the full decode: the
  partial-scanline API silently produces wrong pixels on multi-scan files
  (:func:`parse_jpeg_info` carries the flag).
- **Fused-run dispatch**: :meth:`DecodePool.submit_run_into` decodes a run
  of samples per pool task, amortizing the per-task queue/contextvar/span
  overhead that dominates at ~1ms/image; run length auto-tunes from the
  pool's per-image decode-time EWMA (seeded off the same timing stream the
  ``decode_batch`` histogram records) and is capped for load balance.
- **Decoded-output cache**: with a :class:`~strom.formats.decoded_cache.
  DecodedCache` attached (pipelines build one over the hot cache when
  ``decode_cache`` is on), the transform serves post-decode full-frame
  pixels from RAM on repeat epochs and admits them on first decode —
  epoch >= 2 runs at predecoded speed (see decoded_cache.py for keying and
  budget accounting).
"""

from __future__ import annotations

import concurrent.futures
import ctypes
import os
import threading
import time
from typing import Callable, Iterable, NamedTuple, Sequence

import numpy as np

from strom.formats.decoded_cache import ServedFrame
from strom.obs.events import ring
from strom.utils.stats import global_stats
from strom.utils.locks import make_lock

try:
    import cv2

    _HAVE_CV2 = True
# stromlint: ignore[swallowed-exceptions] -- capability probe: cv2 can
# fail to import with non-ImportError (missing libGL raises OSError);
# either way the flag flips and every decode path branches on it
except Exception:  # pragma: no cover - cv2 is present in the target image
    _HAVE_CV2 = False

try:
    from PIL import Image
    import io

    _HAVE_PIL = True
# stromlint: ignore[swallowed-exceptions] -- capability probe, same
# contract as the cv2 probe above: the flag is the observable outcome
except Exception:  # pragma: no cover
    _HAVE_PIL = False


# -- SOF header parsing (no decode) -----------------------------------------

# SOF0..SOF15 carry frame dimensions, except DHT (C4), JPG (C8), DAC (CC)
_SOF_MARKERS = frozenset(range(0xC0, 0xD0)) - {0xC4, 0xC8, 0xCC}
# the multi-scan (progressive) subset: SOF2/6 (Huffman), SOF10/14
# (arithmetic). These decode fine at full/reduced scale, but the turbo
# partial-scanline API (jpeg_crop_scanline/jpeg_skip_scanlines) silently
# produces WRONG pixels on them — the router must send progressive members
# down the full-decode path (ISSUE 12 satellite).
_PROGRESSIVE_MARKERS = frozenset({0xC2, 0xC6, 0xCA, 0xCE})

# bench-JSON columns the decode-v2 phase set emits (cli._decode2_phases),
# single-sourced so the driver's per-arm copy loop (bench.py), the
# compare_rounds "decode v2" section and the bench_sentinel gates cannot
# drift from the producer — the same contract CACHE_BENCH_FIELDS enforces
DECODE2_FIELDS = (
    "decode_native_img_per_s",
    "decode_cv2_img_per_s",
    "decode_native_vs_cv2",
    "decode_native_imgs",
    "decode_native_fallbacks",
    "decode_fused_runs",
    "decode_fused_samples",
    "decode_roi_hits",
    "decode_roi_rows_skipped",
    "decode_cache_cold_img_per_s",
    "decode_cache_warm_img_per_s",
    "decode_cache_warm_vs_cold",
    "decode_cache_hits",
    "decode_cache_hit_bytes",
    "decode_cache_admitted_bytes",
)


class JpegInfo(NamedTuple):
    """SOF frame header facts: dimensions plus the progressive flag the
    ROI router branches on."""

    h: int
    w: int
    progressive: bool


def parse_jpeg_info(data: bytes | np.ndarray) -> JpegInfo | None:
    """Frame dims + progressive flag from a JPEG's SOF header, walking
    marker segments only — no entropy decode, no IDCT. Returns None for
    anything that is not parseable JPEG (PNG members, truncated headers):
    callers fall back to the full-scale decode path, which raises its own
    clear error."""
    if isinstance(data, np.ndarray):
        b = data.view(np.uint8).reshape(-1)
    else:
        b = np.frombuffer(data, dtype=np.uint8)
    n = b.shape[0]
    if n < 4 or b[0] != 0xFF or b[1] != 0xD8:
        return None
    i = 2
    while i + 3 < n:
        if b[i] != 0xFF:
            return None  # desynced: not walking marker segments anymore
        marker = int(b[i + 1])
        if marker == 0xFF:  # fill byte before a marker
            i += 1
            continue
        if marker == 0x01 or 0xD0 <= marker <= 0xD7:  # standalone TEM/RSTn
            i += 2
            continue
        if marker in (0xD9, 0xDA):  # EOI / SOS before any SOF: give up
            return None
        seg_len = (int(b[i + 2]) << 8) | int(b[i + 3])
        if seg_len < 2:
            return None
        if marker in _SOF_MARKERS:
            if i + 9 > n:
                return None
            h = (int(b[i + 5]) << 8) | int(b[i + 6])
            w = (int(b[i + 7]) << 8) | int(b[i + 8])
            if h <= 0 or w <= 0:
                return None
            return JpegInfo(h, w, marker in _PROGRESSIVE_MARKERS)
        i += 2 + seg_len
    return None


def parse_jpeg_dims(data: bytes | np.ndarray) -> tuple[int, int] | None:
    """(height, width) from a JPEG's SOF header (see
    :func:`parse_jpeg_info`, which also carries the progressive flag)."""
    info = parse_jpeg_info(data)
    return None if info is None else (info.h, info.w)


def reduced_denom(h: int, w: int, size: int) -> int:
    """Largest decode denominator d in (8, 4, 2) at which an (h, w) crop
    still covers the size×size target: min(h, w) >= size * d. Callers pass
    the CROP rectangle's dimensions, not the encoded image's — a reduced
    decode whose crop region lands below the target size would be bilinearly
    UPSCALED where the full path downsamples real pixels, a silent training
    -quality regression. 1 = decode full scale."""
    if size <= 0:
        return 1
    shorter = min(h, w)
    for d in (8, 4, 2):
        if shorter >= size * d:
            return d
    return 1


# -- native libjpeg-turbo binding (ISSUE 12 tentpole) ------------------------

# lazy resolution state: None = resolved-and-absent, callable = resolved;
# the sentinel means "not tried yet". The benign race (two threads both
# resolving) costs one duplicate CDLL of an already-built .so — no lock, so
# resolution can never entangle with the core build lock hierarchy.
_NATIVE_UNRESOLVED = object()
_native_decode: "Callable | None | object" = _NATIVE_UNRESOLVED


def _resolve_native() -> "Callable | None":
    """The decode_native callable, or None when the native binding is
    unavailable (no libjpeg-turbo headers at build time, no compiler, a
    poisoned include path, ...). Import of this module never builds or
    loads anything — the first *access* of ``decode_native`` does."""
    global _native_decode
    if _native_decode is not _NATIVE_UNRESOLVED:
        return _native_decode  # type: ignore[return-value]
    fn: "Callable | None" = None
    try:
        from strom._core.build import ensure_built

        lib = ctypes.CDLL(ensure_built())
        if lib.sc_jpeg_available() == 1:
            lib.sc_jpeg_decode.restype = ctypes.c_int
            lib.sc_jpeg_decode.argtypes = [
                ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p,
                ctypes.c_uint64, ctypes.c_int64, ctypes.c_int32,
                ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
                ctypes.c_int32, ctypes.POINTER(ctypes.c_int32)]

            def fn(data, *, reduced=1, roi=None, out=None,  # type: ignore[misc]
                   _lib=lib):
                return _decode_native_call(_lib, data, reduced=reduced,
                                           roi=roi, out=out)
    # stromlint: ignore[swallowed-exceptions] -- capability probe, same
    # contract as the cv2/PIL probes above: build/link/dlopen failure of
    # the OPTIONAL native path resolves to None and callers keep cv2
    except Exception:
        fn = None
    _native_decode = fn
    return fn


def native_available() -> bool:
    """True when :data:`decode_native` resolves to a live binding."""
    return _resolve_native() is not None


# horizontal widening for ROI decodes: jpeg_crop_scanline grants an
# iMCU-aligned superset, but fancy upsampling lacks context at the granted
# boundary — its left/rightmost output columns can differ from a full
# decode. Requesting 2 extra columns each side keeps the RETURNED rect
# strictly interior (where partial decode is bit-exact against full),
# except at true image edges, where full decode has no context either.
_ROI_X_MARGIN = 2


def _decode_native_call(lib, data, *, reduced: int = 1,
                        roi: "tuple[int, int, int, int] | None" = None,
                        out: "np.ndarray | None" = None) -> np.ndarray:
    """ctypes shim over ``sc_jpeg_decode``. With *roi* = (y, x, h, w) in
    SCALED (post-*reduced*) coordinates, decodes only the crop's scanlines
    / iMCU columns and returns exactly the requested (h, w, 3) rect (a view
    into a fresh decode buffer). Without, returns the full (scaled) frame,
    into *out* when given. Raises ValueError on anything undecodable —
    same contract as :func:`decode_jpeg`, so the pool's per-sample failure
    policy applies unchanged."""
    buf = np.frombuffer(data, dtype=np.uint8) \
        if isinstance(data, (bytes, bytearray, memoryview)) \
        else data.view(np.uint8).reshape(-1)
    if not buf.flags.c_contiguous:
        buf = np.ascontiguousarray(buf)
    info = parse_jpeg_info(buf)
    if info is None:
        raise ValueError("not a decodable image")
    oh, ow = -(-info.h // reduced), -(-info.w // reduced)
    got = (ctypes.c_int32 * 4)()
    if roi is None:
        dst = out
        if dst is None:
            dst = np.empty((oh, ow, 3), dtype=np.uint8)
        elif dst.shape != (oh, ow, 3) or dst.dtype != np.uint8 \
                or not dst.flags.c_contiguous:
            raise ValueError("out must be a C-contiguous uint8 array of "
                             f"shape {(oh, ow, 3)}")
        rc = lib.sc_jpeg_decode(buf.ctypes.data, buf.size, dst.ctypes.data,
                                dst.nbytes, ow * 3, reduced,
                                0, 0, 0, 0, got)
        if rc != 0 or (got[0], got[1]) != (oh, ow):
            raise ValueError(f"native jpeg decode failed (rc={rc})")
        return dst
    y, x, h, w = roi
    if not (0 <= y and 0 <= x and h > 0 and w > 0
            and y + h <= oh and x + w <= ow):
        raise ValueError(f"roi {roi} outside scaled frame {(oh, ow)}")
    rx = max(x - _ROI_X_MARGIN, 0)
    rw = min(x + w + _ROI_X_MARGIN, ow) - rx
    # granted width exceeds the request by at most one iMCU each side —
    # up to 32px with h_samp_factor 4 (4:1:1/4:1:0 chroma), so budget 62
    # extra columns; rows pack at the granted width (stride <= 0 in the
    # C ABI) and the capacity check there rejects anything wider
    flat = np.empty(h * (rw + 64) * 3, dtype=np.uint8)
    rc = lib.sc_jpeg_decode(buf.ctypes.data, buf.size, flat.ctypes.data,
                            flat.nbytes, 0, reduced, y, rx, h, rw, got)
    if rc != 0:
        raise ValueError(f"native jpeg roi decode failed (rc={rc})")
    gh, gw, gx0, _ = got
    img = flat[: gh * gw * 3].reshape(gh, gw, 3)
    return img[:, x - gx0: x - gx0 + w]


def __getattr__(name: str):
    """PEP 562: ``jpeg.decode_native`` resolves the native binding on first
    access (None when absent — the ISSUE 12 build-probe fallback contract)
    without import-time build cost."""
    if name == "decode_native":
        return _resolve_native()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def decode_jpeg(data: bytes | np.ndarray, *, reduced: int = 1) -> np.ndarray:
    """Decode JPEG/PNG bytes → HWC uint8 RGB array.

    *reduced* in (2, 4, 8) decodes JPEGs at 1/reduced scale (libjpeg
    skips the corresponding IDCT work); the caller owns rescaling any
    crop geometry onto the reduced image (:func:`make_train_transform`).
    """
    if _HAVE_CV2:
        flag = {1: cv2.IMREAD_COLOR,
                2: cv2.IMREAD_REDUCED_COLOR_2,
                4: cv2.IMREAD_REDUCED_COLOR_4,
                8: cv2.IMREAD_REDUCED_COLOR_8}[reduced]
        buf = np.frombuffer(data, dtype=np.uint8) if isinstance(data, (bytes, memoryview)) \
            else data.view(np.uint8).reshape(-1)
        img = cv2.imdecode(buf, flag)
        if img is None:
            raise ValueError("not a decodable image")
        return cv2.cvtColor(img, cv2.COLOR_BGR2RGB)
    if _HAVE_PIL:
        raw = data.tobytes() if isinstance(data, np.ndarray) else bytes(data)
        try:
            with Image.open(io.BytesIO(raw)) as im:
                if reduced > 1:
                    # draft mode: JPEG power-of-2 reduced decode, same trick
                    im.draft("RGB", (max(1, im.width // reduced),
                                     max(1, im.height // reduced)))
                return np.asarray(im.convert("RGB"))
        except Exception as e:  # UnidentifiedImageError etc. → one contract
            raise ValueError("not a decodable image") from e
    raise RuntimeError("no JPEG decoder available (need cv2 or PIL)")


def _resize(img: np.ndarray, h: int, w: int) -> np.ndarray:
    if _HAVE_CV2:
        return cv2.resize(img, (w, h), interpolation=cv2.INTER_LINEAR)
    return np.asarray(Image.fromarray(img).resize((w, h), Image.BILINEAR))


def _resize_into(img: np.ndarray, size: int,
                 out: np.ndarray | None) -> np.ndarray:
    """Bilinear resize to size x size, into *out* when given (cv2 writes the
    pixels straight into the destination row — the zero-copy half of the
    slot-decode story)."""
    if out is None:
        return _resize(img, size, size)
    if _HAVE_CV2:
        cv2.resize(img, (size, size), dst=out,
                   interpolation=cv2.INTER_LINEAR)
    else:
        out[:] = _resize(img, size, size)
    return out


def _flip_h(dst: np.ndarray, out: np.ndarray | None) -> np.ndarray:
    """Horizontal flip; in place (cv2.flip supports src==dst) on the slot
    path, a fresh contiguous mirror otherwise — values identical."""
    if out is None:
        return np.ascontiguousarray(dst[:, ::-1])
    if _HAVE_CV2:
        cv2.flip(dst, 1, dst=dst)
    else:
        dst[:] = dst[:, ::-1].copy()
    return dst


def center_crop_resize(img: np.ndarray, size: int,
                       *, resize_shorter: int | None = None) -> np.ndarray:
    """Eval transform: resize shorter side (default size*1.15), center crop."""
    shorter = resize_shorter or int(size * 1.15)
    h, w = img.shape[:2]
    scale = shorter / min(h, w)
    img = _resize(img, max(size, round(h * scale)), max(size, round(w * scale)))
    h, w = img.shape[:2]
    top, left = (h - size) // 2, (w - size) // 2
    return img[top: top + size, left: left + size]


def sample_rrc_geometry(h: int, w: int, rng: np.random.Generator,
                        *, scale: tuple[float, float] = (0.08, 1.0),
                        ratio: tuple[float, float] = (3 / 4, 4 / 3)
                        ) -> tuple[int, int, int, int]:
    """(top, left, crop_h, crop_w) of an Inception-style random area/aspect
    crop in (h, w) coordinates; falls back to the center square. Pure RNG +
    arithmetic — the full-scale and reduced-scale decode paths both sample
    here in FULL-resolution coordinates, so their random streams (and
    therefore checkpoint-resume determinism) are identical."""
    area = h * w
    for _ in range(10):
        target = area * rng.uniform(*scale)
        log_r = rng.uniform(np.log(ratio[0]), np.log(ratio[1]))
        ar = np.exp(log_r)
        cw = round(np.sqrt(target * ar))
        ch = round(np.sqrt(target / ar))
        if 0 < cw <= w and 0 < ch <= h:
            top = int(rng.integers(0, h - ch + 1))
            left = int(rng.integers(0, w - cw + 1))
            return top, left, ch, cw
    side = min(h, w)
    return (h - side) // 2, (w - side) // 2, side, side


def random_resized_crop(img: np.ndarray, size: int, rng: np.random.Generator,
                        *, scale: tuple[float, float] = (0.08, 1.0),
                        ratio: tuple[float, float] = (3 / 4, 4 / 3),
                        out: np.ndarray | None = None) -> np.ndarray:
    """Train transform: Inception-style random area/aspect crop → size×size,
    plus a horizontal flip coin. With *out*, the result lands in the given
    row (bit-identical values to the allocating path)."""
    h, w = img.shape[:2]
    top, left, ch, cw = sample_rrc_geometry(h, w, rng, scale=scale,
                                            ratio=ratio)
    dst = _resize_into(img[top: top + ch, left: left + cw], size, out)
    if rng.random() < 0.5:
        return _flip_h(dst, out)
    return np.ascontiguousarray(dst) if out is None else dst


def _scale_crop(top: int, left: int, ch: int, cw: int,
                fh: int, fw: int, rh: int, rw: int
                ) -> tuple[int, int, int, int]:
    """Map a full-resolution crop rectangle onto a reduced decode of actual
    shape (rh, rw) (libjpeg reduced sizes are ceil(dim/d), so the exact
    ratio comes from the decoded image, not the nominal denominator).
    Clamped non-empty."""
    sy, sx = rh / fh, rw / fw
    r0 = min(int(round(top * sy)), rh - 1)
    c0 = min(int(round(left * sx)), rw - 1)
    r1 = max(r0 + 1, min(int(round((top + ch) * sy)), rh))
    c1 = max(c0 + 1, min(int(round((left + cw) * sx)), rw))
    return r0, c0, r1 - r0, c1 - c0


def make_train_transform(size: int, *, reduced_scale: bool = True,
                         scale: tuple[float, float] = (0.08, 1.0),
                         ratio: tuple[float, float] = (3 / 4, 4 / 3),
                         native: bool = True,
                         roi: bool = True,
                         dcache=None) -> Callable[..., np.ndarray]:
    """Transform(jpeg_bytes, rng, out=None, ckey=None) -> size×size×3 uint8.

    With *reduced_scale*, the crop rectangle is sampled FIRST (in full-res
    coordinates from the SOF header's dimensions — identical RNG stream to
    the full path), then the largest decode denominator at which that crop
    still covers the size×size target is chosen (:func:`reduced_denom` on
    the CROP dims: a crop that would land below the target at 1/d must not
    be upscaled from a reduced decode) and the rectangle is rescaled onto
    the reduced image. Non-JPEG members (no SOF) ride the full path.

    Decode path v2 (ISSUE 12): with *native* (and the binding built),
    decode runs through :data:`decode_native` — bit-exact against cv2 for
    full/reduced decode, falling back to cv2 per-sample on any native
    error. With *roi* on top, only the crop's scanlines/iMCU columns are
    decoded (`decode_roi_hits` / `decode_roi_rows_skipped`), skipped for
    progressive members and crops spanning the frame. With *dcache* (a
    :class:`strom.formats.decoded_cache.DecodedCache`) and a *ckey*, the
    decoded FULL frame is served from / admitted to the hot cache, so a
    repeat epoch pays only crop+resize — note this serves full-fidelity
    pixels where the reduced path would have approximated, identical to
    the ``reduced_scale=False`` path. Every knob off reproduces the
    pre-v2 transform bit-identically."""

    def tf(data, rng: np.random.Generator,
           out: np.ndarray | None = None, ckey=None) -> np.ndarray:
        if isinstance(data, ServedFrame):
            # plan-time decoded-cache hit (ISSUE 13 satellite): the image
            # member was never gathered — *data* IS the pinned full frame.
            # Same RNG draws as the in-transform cached branch below
            # (geometry, then one flip coin), so resume determinism and
            # the bit-identity contract hold whichever path a sample takes.
            img = data.img
            try:
                fh, fw = img.shape[:2]
                top, left, ch, cw = sample_rrc_geometry(
                    fh, fw, rng, scale=scale, ratio=ratio)
                dst = _resize_into(img[top: top + ch, left: left + cw],
                                   size, out)
            finally:
                data.release()
            if rng.random() < 0.5:
                return _flip_h(dst, out)
            return np.ascontiguousarray(dst) if out is None else dst
        info = parse_jpeg_info(data) if (reduced_scale or native
                                         or dcache is not None) else None
        if info is None:
            return random_resized_crop(decode_jpeg(data), size, rng,
                                       scale=scale, ratio=ratio, out=out)
        fh, fw = info.h, info.w
        top, left, ch, cw = sample_rrc_geometry(fh, fw, rng, scale=scale,
                                                ratio=ratio)

        def finish(dst):
            # one flip draw in every path, AFTER the resize — the RNG
            # stream is identical across full/reduced/native/roi/cached
            if rng.random() < 0.5:
                return _flip_h(dst, out)
            return np.ascontiguousarray(dst) if out is None else dst

        nat = _resolve_native() if native else None
        # decoded-output cache (front 4): serve post-decode pixels from
        # RAM; on a miss decode the FULL frame (cache fidelity = full-res
        # pixels; forgoing ROI/reduced on the admitting pass is what buys
        # epoch >= 2 the predecoded-speed serve)
        if dcache is not None and ckey is not None and dcache.enabled:
            hit = dcache.get(ckey, fh, fw)
            if hit is not None:
                img, pin = hit
                try:
                    dst = _resize_into(img[top: top + ch, left: left + cw],
                                       size, out)
                finally:
                    dcache.release(pin)
                return finish(dst)
            img = None
            if nat is not None:
                try:
                    img = nat(data)
                    global_stats.add("decode_native_imgs")
                except ValueError:
                    global_stats.add("decode_native_fallbacks")
            if img is None:
                img = decode_jpeg(data)
            dcache.offer(ckey, img)
            return finish(_resize_into(
                img[top: top + ch, left: left + cw], size, out))

        denom = reduced_denom(ch, cw, size) if reduced_scale else 1
        if denom == 1:
            rh, rw = fh, fw
            r0, c0, rch, rcw = top, left, ch, cw
        else:
            # libjpeg reduced sizes are ceil(dim/d) — computable without
            # decoding, so the ROI path can plan scaled coordinates upfront
            rh, rw = -(-fh // denom), -(-fw // denom)
            r0, c0, rch, rcw = _scale_crop(top, left, ch, cw, fh, fw,
                                           rh, rw)
        img = None
        if nat is not None:
            # ROI engages when partial decode actually skips work; a crop
            # spanning the frame rides the plain (full/reduced) decode.
            # Progressive members never take ROI (wrong pixels — see
            # parse_jpeg_info); full/reduced native decode handles them.
            roi_ok = roi and not info.progressive \
                and (rch < rh or rcw < rw)
            try:
                if roi_ok:
                    rect = nat(data, reduced=denom,
                               roi=(r0, c0, rch, rcw))
                    global_stats.add("decode_native_imgs")
                    global_stats.add("decode_roi_hits")
                    global_stats.add("decode_roi_rows_skipped", rh - rch)
                    if denom > 1:
                        global_stats.add(f"decode_reduced_hits_{denom}")
                    return finish(_resize_into(rect, size, out))
                img = nat(data, reduced=denom)
                global_stats.add("decode_native_imgs")
            except ValueError:
                # per-sample fallback: a member the native path rejects
                # (exotic colorspace, arithmetic coding build, truncation
                # the two libraries tolerate differently) rides cv2 — the
                # counter keeps "native silently off" diagnosable
                global_stats.add("decode_native_fallbacks")
                img = None
        if img is None:
            img = decode_jpeg(data, reduced=denom)
        if denom > 1:
            global_stats.add(f"decode_reduced_hits_{denom}")
        dst = _resize_into(img[r0: r0 + rch, c0: c0 + rcw], size, out)
        return finish(dst)

    return tf


class DecodePool:
    """Thread pool mapping decode+transform over batches of member payloads.

    Worker count is clamped to the host's core count (decode has no I/O
    waits to hide; extra threads only add GIL churn and context switches).

    cv2's internal threading is disabled while a pool lives (parallelism
    comes from this pool, not from within one image); the prior thread count
    is snapshotted at construction and restored in :meth:`close` so library
    users embedding a pipeline don't inherit a globally-mutated cv2.
    (Overlapping pool lifetimes restore whatever the LAST close sees —
    cv2 keeps one global setting, there is nothing finer to restore.)

    Fused-run dispatch (ISSUE 12 tentpole, *fuse_runs*): one pool task
    decodes a RUN of samples, amortizing the per-task future/queue/
    contextvar/span overhead that dominates at ~1ms images. Run length
    auto-tunes from a per-image decode-time EWMA the fused workers
    maintain (the same timing stream the ``decode_batch`` histogram
    aggregates) against a fixed per-task work target, capped so every
    worker still sees >= 2 runs per batch. ``fuse_runs=False`` (or run
    length 1) keeps the one-task-per-sample shape bit-identically.
    """

    # per-task decode-work target: enough decode per dispatch that the
    # ~tens-of-us task overhead amortizes below ~2%, small enough that
    # run granularity doesn't serialize a batch's tail
    _RUN_TARGET_US = 4000.0

    def __init__(self, workers: int = 8, *, fuse_runs: bool = True):
        self._cv2_threads_prev: int | None = None
        if _HAVE_CV2:
            self._cv2_threads_prev = cv2.getNumThreads()
            cv2.setNumThreads(0)
        # decode is pure CPU (no I/O waits to hide), so workers beyond the
        # core count only thrash: measured 177ms vs 126ms per 64-image batch
        # at 8 vs 2 workers on a 2-core host — oversubscription cost ate
        # more than the reduced-scale decode win. Clamp, don't trust the
        # caller's guess about this host.
        workers = max(1, min(workers, os.cpu_count() or workers))
        self.workers = workers
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix="strom-decode")
        self.decode_errors = 0
        self._err_lock = make_lock("app.jpeg_errs")
        self.fuse_runs = fuse_runs
        # live per-task work target (ISSUE 19 satellite): instance-level
        # so the autotuner's decode_run_target_us knob steers run
        # granularity on a running pool; class default = the measured
        # sweet spot
        self.run_target_us = float(self._RUN_TARGET_US)
        # EWMA of per-image decode+transform micros, seeded at 1ms (the
        # measured pre-v2 cost on the bench host); updated by fused runs
        self._img_us = 1000.0
        self._closed = False

    @staticmethod
    def _worker_span(req):
        """The per-sample decode span: request-linked when the submitter
        was inside a traced request (ISSUE 8 — *req* is captured at SUBMIT
        time, because the worker thread has no contextvar of its own),
        else the plain ring span — or None when the ring is disabled
        (ISSUE 12 satellite: span construction is pure overhead with
        telemetry off, and the fused-run micro numbers must not pay it).
        Either span bills the worker's CPU time to the totals, a clock
        read every ``CPU_LAP_SPANS`` images: wall well above CPU says the
        decode threads wait for a core."""
        if req is not None:
            return req.span("decode.worker", cat="decode", cpu=True)
        if not ring.enabled:
            return None
        return ring.span("decode.worker", cat="decode", cpu=True)

    def run_size(self, n: int) -> int:
        """Fused-run length for an *n*-sample batch: enough samples per
        task to hit the work target at the current per-image EWMA, capped
        for load balance. 1 = fusing off (the pre-v2 dispatch shape)."""
        if not self.fuse_runs or n <= 1:
            return 1
        with self._err_lock:
            per_img = self._img_us
        want = int(self.run_target_us / max(per_img, 1.0))
        cap = -(-n // (self.workers * 2))
        return max(1, min(want, cap))

    def map(self, fn: Callable[..., np.ndarray],
            items: Iterable, *extra: Sequence) -> list[np.ndarray]:
        from strom.obs import request as _request

        req = _request.current()

        def traced(*a) -> np.ndarray:
            # worker span on the shared timeline: per-sample decode+transform
            # (the legacy allocating path; the slot path traces in
            # _one_sample); None = telemetry off, skip the span entirely
            cm = self._worker_span(req)
            if cm is None:
                return fn(*a)
            with cm:
                return fn(*a)

        return list(self._pool.map(traced, items, *extra))

    # -- direct-to-slot mapping --------------------------------------------
    def _call(self, fn: Callable[..., np.ndarray], item, rng,
              row: np.ndarray, ckey) -> None:
        if ckey is None:
            fn(item, rng, out=row)
        else:
            fn(item, rng, out=row, ckey=ckey)

    def _one_sample(self, fn: Callable[..., np.ndarray], item, rng,
                    row: np.ndarray, req, ckey) -> None:
        try:
            cm = self._worker_span(req)
            if cm is None:  # telemetry off: no span object, no now_us
                self._call(fn, item, rng, row, ckey)
            else:
                with cm:
                    self._call(fn, item, rng, row, ckey)
        except ValueError:
            # per-sample failure policy: a truncated/corrupt member costs
            # one zero image and a counter bump, not the whole batch
            row[...] = 0
            with self._err_lock:
                self.decode_errors += 1
            global_stats.add("decode_errors")

    def _one_into(self, fn: Callable[..., np.ndarray], item,
                  rng, row: np.ndarray, req=None, ckey=None) -> None:
        self._one_sample(fn, item, rng, row, req, ckey)

    def _run_into(self, fn: Callable[..., np.ndarray], items: Sequence,
                  rngs: Sequence, rows: Sequence, req, ckeys) -> None:
        """One pool task decoding a run of samples (the failure policy per
        sample, exactly like the single-sample path). Feeds the per-image
        EWMA :meth:`run_size` tunes from."""
        t0 = time.perf_counter()
        for i, (item, rng) in enumerate(zip(items, rngs)):
            self._one_sample(fn, item, rng, rows[i], req,
                             None if ckeys is None else ckeys[i])
        n = len(items)
        per_img = (time.perf_counter() - t0) * 1e6 / max(n, 1)
        with self._err_lock:
            self._img_us += 0.2 * (per_img - self._img_us)
        if n > 1:
            global_stats.add("decode_fused_runs")
            global_stats.add("decode_fused_samples", n)

    def submit_into(self, fn: Callable[..., np.ndarray], item, rng,
                    row: np.ndarray, ckey=None) -> concurrent.futures.Future:
        """One decode+transform job writing its result into *row* (the
        failure policy applied) — the unit the overlapped per-device
        delivery completes on."""
        from strom.obs import request as _request

        return self._pool.submit(self._one_into, fn, item, rng, row,
                                 _request.current(), ckey)

    def submit_run_into(self, fn: Callable[..., np.ndarray],
                        items: Sequence, rngs: Sequence, rows: Sequence,
                        ckeys: "Sequence | None" = None
                        ) -> concurrent.futures.Future:
        """A fused run: ONE pool task decoding items[i] into rows[i] for
        the whole run (ISSUE 12 tentpole) — per-task dispatch overhead is
        paid once per run instead of once per sample."""
        from strom.obs import request as _request

        return self._pool.submit(self._run_into, fn, items, rngs, rows,
                                 _request.current(), ckeys)

    def map_into(self, fn: Callable[..., np.ndarray], items: Sequence,
                 rngs: Sequence, out: np.ndarray,
                 ckeys: "Sequence | None" = None) -> np.ndarray:
        """Map fn(item, rng, out=out[i]) over the batch, every worker
        writing straight into its slot row; contiguous runs fuse into one
        task each per :meth:`run_size`. Returns *out*."""
        n = len(items)
        run = self.run_size(n)
        if run <= 1:
            futs = [self.submit_into(fn, item, rng, out[i],
                                     None if ckeys is None else ckeys[i])
                    for i, (item, rng) in enumerate(zip(items, rngs))]
        else:
            futs = [self.submit_run_into(
                        fn, items[i: i + run], rngs[i: i + run],
                        [out[j] for j in range(i, min(i + run, n))],
                        None if ckeys is None else ckeys[i: i + run])
                    for i in range(0, n, run)]
        for f in futs:
            f.result()
        return out

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._pool.shutdown(wait=True)
        if _HAVE_CV2 and self._cv2_threads_prev is not None:
            cv2.setNumThreads(self._cv2_threads_prev)

    def __enter__(self) -> "DecodePool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
