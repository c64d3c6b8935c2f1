"""The JPEG decode + RandomResizedCrop algorithm, importable on its own.

Everything here is pure numpy / cv2 / ctypes (PIL where cv2 is absent):
no strom import, so a decode worker process (strom/formats/decode_worker.py)
loads this file by path and runs the identical algorithm without pulling in
the delivery stack. :mod:`strom.formats.jpeg` re-exports every public name
and adds what needs the rest of strom: the decoded-output cache branch, the
native binding's build-and-resolve step, and :class:`DecodePool`.

Counters the algorithm feeds (``decode_native_imgs``, ``decode_roi_hits``,
``decode_reduced_hits_{2,4,8}``, ...) go through the caller's *count*
callable: the parent passes ``global_stats.add``, a worker tallies locally
and ships the tallies back with each finished task.
"""

from __future__ import annotations

import ctypes
from typing import Callable, NamedTuple

import numpy as np

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
# down the full-decode path.
_PROGRESSIVE_MARKERS = frozenset({0xC2, 0xC6, 0xCA, 0xCE})

# the counters the algorithm feeds through *count*, in a fixed order (the
# decode workers ship their tallies back as one packed tuple of this shape)
COUNTERS = ("decode_native_imgs", "decode_native_fallbacks",
            "decode_roi_hits", "decode_roi_rows_skipped",
            "decode_reduced_hits_2", "decode_reduced_hits_4",
            "decode_reduced_hits_8")


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


# -- native libjpeg-turbo binding -------------------------------------------

def bind_native(lib) -> "Callable | None":
    """The decode_native callable over a loaded strom/_core library, or
    None when it was built without libjpeg-turbo."""
    if lib.sc_jpeg_available() != 1:
        return None
    lib.sc_jpeg_decode.restype = ctypes.c_int
    lib.sc_jpeg_decode.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p,
        ctypes.c_uint64, ctypes.c_int64, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, ctypes.POINTER(ctypes.c_int32)]

    def fn(data, *, reduced=1, roi=None, out=None, _lib=lib):
        return _decode_native_call(_lib, data, reduced=reduced, roi=roi,
                                   out=out)

    return fn


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


def decode_jpeg(data: bytes | np.ndarray, *, reduced: int = 1) -> np.ndarray:
    """Decode JPEG/PNG bytes → HWC uint8 RGB array.

    *reduced* in (2, 4, 8) decodes JPEGs at 1/reduced scale (libjpeg
    skips the corresponding IDCT work); the caller owns rescaling any
    crop geometry onto the reduced image (:func:`train_crop`).
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


def finish_crop(dst: np.ndarray, rng: np.random.Generator,
                out: np.ndarray | None) -> np.ndarray:
    """The flip coin every crop path draws once, AFTER the resize — the RNG
    stream is identical across full/reduced/native/roi/cached paths."""
    if rng.random() < 0.5:
        return _flip_h(dst, out)
    return np.ascontiguousarray(dst) if out is None else dst


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
    return finish_crop(dst, rng, out)


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


def _no_count(name: str, n: int = 1) -> None:
    return None


def train_crop(data, rng: np.random.Generator,
               out: np.ndarray | None = None, *, size: int,
               reduced_scale: bool = True,
               scale: tuple[float, float] = (0.08, 1.0),
               ratio: tuple[float, float] = (3 / 4, 4 / 3),
               native: bool = True, roi: bool = True,
               nat: "Callable | None" = None,
               count: Callable[..., None] = _no_count) -> np.ndarray:
    """Decode + RandomResizedCrop + flip of one JPEG into a size×size×3
    uint8 row (into *out* when given): the built-in train transform without
    a decoded-output cache (see :func:`strom.formats.jpeg.
    make_train_transform` for the knobs). *nat* is the resolved native
    binding (None: cv2), *count* receives every counter bump."""
    info = parse_jpeg_info(data) if (reduced_scale or native) else None
    if info is None:
        return random_resized_crop(decode_jpeg(data), size, rng,
                                   scale=scale, ratio=ratio, out=out)
    fh, fw = info.h, info.w
    top, left, ch, cw = sample_rrc_geometry(fh, fw, rng, scale=scale,
                                            ratio=ratio)
    denom = reduced_denom(ch, cw, size) if reduced_scale else 1
    if denom == 1:
        rh, rw = fh, fw
        r0, c0, rch, rcw = top, left, ch, cw
    else:
        # libjpeg reduced sizes are ceil(dim/d) — computable without
        # decoding, so the ROI path can plan scaled coordinates upfront
        rh, rw = -(-fh // denom), -(-fw // denom)
        r0, c0, rch, rcw = _scale_crop(top, left, ch, cw, fh, fw, rh, rw)
    img = None
    if nat is not None:
        # ROI engages when partial decode actually skips work; a crop
        # spanning the frame rides the plain (full/reduced) decode.
        # Progressive members never take ROI (wrong pixels — see
        # parse_jpeg_info); full/reduced native decode handles them.
        roi_ok = roi and not info.progressive and (rch < rh or rcw < rw)
        try:
            if roi_ok:
                rect = nat(data, reduced=denom, roi=(r0, c0, rch, rcw))
                count("decode_native_imgs")
                count("decode_roi_hits")
                count("decode_roi_rows_skipped", rh - rch)
                if denom > 1:
                    count(f"decode_reduced_hits_{denom}")
                return finish_crop(_resize_into(rect, size, out), rng, out)
            img = nat(data, reduced=denom)
            count("decode_native_imgs")
        except ValueError:
            # per-sample fallback: a member the native path rejects
            # (exotic colorspace, arithmetic coding build, truncation
            # the two libraries tolerate differently) rides cv2 — the
            # counter keeps "native silently off" diagnosable
            count("decode_native_fallbacks")
            img = None
    if img is None:
        img = decode_jpeg(data, reduced=denom)
    if denom > 1:
        count(f"decode_reduced_hits_{denom}")
    dst = _resize_into(img[r0: r0 + rch, c0: c0 + rcw], size, out)
    return finish_crop(dst, rng, out)
