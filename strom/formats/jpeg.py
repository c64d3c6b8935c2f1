"""JPEG host decode + ImageNet-style transforms (SURVEY.md §7.2 step 7:
"JPEG (host decode worker pool)").

The engine lands compressed bytes in host slabs; decode runs on a host
worker pool and the decoded uint8 tensor is what gets `device_put`.  Keeping
decode on host mirrors the division of labor in the reference's consumer
(PG-Strom decompresses on GPU — strom-tpu instead keeps the TPU's MXU for
the model and spends host cores on decode; the "0 data-stall" overlap hides
both).  Consumer: the ResNet-50 pipeline (BASELINE config #2,
BASELINE.json:8).

The pool has two executors for one algorithm (the algorithm itself lives in
:mod:`strom.formats.jpeg_core`). The native decode, ``cv2.resize`` and
``cv2.flip`` release the interpreter lock, but the Python around each image
(header parse, crop geometry, RNG, counters, span, future) holds it, and on
threads that work competes with the training loop for the one lock that
launches its step: on a TPU v5e host 13 decode threads stretched the
ResNet-50 step's dispatch from 7 to 126 ms. So the built-in train transform
runs in worker processes (:mod:`strom.formats.decode_procs`), with image
bytes and output rows in shared memory; everything the processes cannot
take stays on threads (see :class:`DecodePool`).

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

Decode path v2 (the pipelines always take the native
binding, fused runs and ROI decode; ``decode_cache`` is a config knob):

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
  of samples per pool task, amortizing the per-task dispatch overhead;
  run length auto-tunes from the pool's per-image decode-time EWMA (on
  processes, the workers' own wall time per image) and is capped for load
  balance.
- **Decoded-output cache**: with a :class:`~strom.formats.decoded_cache.
  DecodedCache` attached (pipelines build one over the hot cache when
  ``decode_cache`` is on), the transform serves post-decode full-frame
  pixels from RAM on repeat epochs and admits them on first decode —
  epoch >= 2 runs at predecoded speed (see decoded_cache.py for keying and
  budget accounting). Its frames live in this process, so such a
  transform decodes on threads.
"""

from __future__ import annotations

import concurrent.futures
import ctypes
import math
import os
import time
from typing import Callable, Iterable, Sequence

import numpy as np

from strom.formats.decoded_cache import ServedFrame
from strom.formats.jpeg_core import (  # noqa: F401  (the public names)
    _HAVE_CV2, _resize_into, bind_native, center_crop_resize, decode_jpeg,
    finish_crop, parse_jpeg_dims, parse_jpeg_info, random_resized_crop,
    reduced_denom, sample_rrc_geometry, train_crop)
from strom.obs.events import ring
from strom.utils.stats import global_stats
from strom.utils.locks import make_lock

if _HAVE_CV2:
    import cv2


# columns of the checked-in pre-chip benchmark records that
# tools/compare_rounds.py reads for its "decode v2" section and
# tools/bench_sentinel.py gates on
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


# -- native libjpeg-turbo binding (ISSUE 12 tentpole) ------------------------

# lazy resolution state: None = resolved-and-absent, callable = resolved;
# the sentinel means "not tried yet". The benign race (two threads both
# resolving) costs one duplicate CDLL of an already-built .so — no lock, so
# resolution can never entangle with the core build lock hierarchy.
_NATIVE_UNRESOLVED = object()
_native_decode: "Callable | None | object" = _NATIVE_UNRESOLVED
# the library the live binding was loaded from: decode worker processes
# load the same file
_native_lib = ""


def _resolve_native() -> "Callable | None":
    """The decode_native callable, or None when the native binding is
    unavailable (no libjpeg-turbo headers at build time, no compiler, a
    poisoned include path, ...). Import of this module never builds or
    loads anything — the first *access* of ``decode_native`` does."""
    global _native_decode, _native_lib
    if _native_decode is not _NATIVE_UNRESOLVED:
        return _native_decode  # type: ignore[return-value]
    fn: "Callable | None" = None
    try:
        from strom._core.build import ensure_built

        path = ensure_built()
        fn = bind_native(ctypes.CDLL(path))
        if fn is not None:
            _native_lib = path
    # stromlint: ignore[swallowed-exceptions] -- capability probe, same
    # contract as the cv2/PIL probes: build/link/dlopen failure of the
    # OPTIONAL native path resolves to None and callers keep cv2
    except Exception:
        fn = None
    _native_decode = fn
    return fn


def native_available() -> bool:
    """True when :data:`decode_native` resolves to a live binding."""
    return _resolve_native() is not None


def __getattr__(name: str):
    """PEP 562: ``jpeg.decode_native`` resolves the native binding on first
    access (None when absent — the ISSUE 12 build-probe fallback contract)
    without import-time build cost."""
    if name == "decode_native":
        return _resolve_native()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


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
    pre-v2 transform bit-identically.

    Without *dcache* the transform carries its parameters as ``tf.spec``:
    a :class:`DecodePool` given it rebuilds the same transform in its
    worker processes (:func:`strom.formats.jpeg_core.train_crop`)."""
    scale, ratio = tuple(scale), tuple(ratio)

    def crop(data, rng, out):
        return train_crop(data, rng, out, size=size,
                          reduced_scale=reduced_scale, scale=scale,
                          ratio=ratio, native=native, roi=roi,
                          nat=_resolve_native() if native else None,
                          count=global_stats.add)

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
            return finish_crop(dst, rng, out)
        if dcache is None or ckey is None or not dcache.enabled:
            return crop(data, rng, out)
        info = parse_jpeg_info(data)
        if info is None:
            return random_resized_crop(decode_jpeg(data), size, rng,
                                       scale=scale, ratio=ratio, out=out)
        fh, fw = info.h, info.w
        top, left, ch, cw = sample_rrc_geometry(fh, fw, rng, scale=scale,
                                                ratio=ratio)
        # decoded-output cache (front 4): serve post-decode pixels from
        # RAM; on a miss decode the FULL frame (cache fidelity = full-res
        # pixels; forgoing ROI/reduced on the admitting pass is what buys
        # epoch >= 2 the predecoded-speed serve)
        hit = dcache.get(ckey, fh, fw)
        if hit is not None:
            img, pin = hit
            try:
                dst = _resize_into(img[top: top + ch, left: left + cw],
                                   size, out)
            finally:
                dcache.release(pin)
            return finish_crop(dst, rng, out)
        img = None
        nat = _resolve_native() if native else None
        if nat is not None:
            try:
                img = nat(data)
                global_stats.add("decode_native_imgs")
            except ValueError:
                global_stats.add("decode_native_fallbacks")
        if img is None:
            img = decode_jpeg(data)
        dcache.offer(ckey, img)
        return finish_crop(_resize_into(
            img[top: top + ch, left: left + cw], size, out), rng, out)

    if dcache is None:
        tf.spec = {"size": size, "reduced_scale": bool(reduced_scale),
                   "scale": scale, "ratio": ratio, "native": bool(native),
                   "roi": bool(roi)}
    return tf


def philox_key(rng) -> "tuple[int, int] | None":
    """The (key0, key1) a per-sample RNG travels as: a key tuple as given,
    or the key of a fresh ``Philox`` generator (nothing drawn yet); None
    for anything else, which a worker process could not rebuild."""
    if isinstance(rng, tuple):
        return rng
    bg = getattr(rng, "bit_generator", None)
    if not isinstance(bg, np.random.Philox):
        return None
    st = bg.state
    if st["buffer_pos"] != 4 or st["has_uint32"] or \
            st["state"]["counter"].any():
        return None
    k0, k1 = st["state"]["key"]
    return int(k0), int(k1)


def as_generator(rng) -> np.random.Generator:
    """A per-sample RNG as the transform takes it: a Philox key tuple
    becomes its generator, anything else passes through."""
    if isinstance(rng, tuple):
        return np.random.Generator(np.random.Philox(key=list(rng)))
    return rng


class DecodePool:
    """Decode+transform over batches of member payloads, on worker
    processes or threads.

    **Processes** (*transform* given and built by
    :func:`make_train_transform` without a decoded cache, so it carries
    ``tf.spec``): *workers* decode processes start now, in parallel
    (:class:`strom.formats.decode_procs.DecodeProcs`), and every
    ``submit_into`` / ``submit_run_into`` / ``map_into`` call with that
    transform runs there when its bytes and rows lie in the pool's shared
    memory (:meth:`slab`, :meth:`buffer`; :meth:`recycle` returns them)
    and its RNGs are Philox keys or fresh Philox generators. The training
    process keeps its interpreter lock for the loop; counters
    ``decode_proc_samples``, ``decode_proc_restarts`` and
    ``decode_proc_parent_us`` (the parent's own time per task, submit plus
    completion) say how that went.

    **Threads**: everything else — a custom transform (it may not pickle),
    a transform over a decoded cache or a served frame (their pixels live
    in this process), rows outside shared memory, and the legacy
    :meth:`map`. ``decode_thread_samples`` counts these. One algorithm,
    two executors, routed by what each call brings; no switch.

    Worker count is clamped to the host's core count (decode has no I/O
    waits to hide; extra workers only add churn and context switches).

    cv2's internal threading is disabled while a pool lives (parallelism
    comes from this pool, not from within one image); the prior thread count
    is snapshotted at construction and restored in :meth:`close` so library
    users embedding a pipeline don't inherit a globally-mutated cv2.
    (Overlapping pool lifetimes restore whatever the LAST close sees —
    cv2 keeps one global setting, there is nothing finer to restore.)

    Fused-run dispatch (ISSUE 12 tentpole, *fuse_runs*): one pool task
    decodes a RUN of samples, amortizing the per-task dispatch overhead.
    Run length auto-tunes from a per-image decode-time EWMA (thread runs
    time themselves; process runs report the workers' wall per image)
    against a fixed per-task work target, capped so every worker still
    sees >= 2 runs per batch. ``fuse_runs=False`` (or run length 1) keeps
    the one-task-per-sample shape bit-identically.

    *slabs* bounds the shared regions kept per kind (output slabs, member
    bytes): pipelines give their prefetch depth plus one.
    """

    # per-task decode-work target: enough decode per dispatch that the
    # ~tens-of-us task overhead amortizes below ~2%, small enough that
    # run granularity doesn't serialize a batch's tail
    _RUN_TARGET_US = 4000.0
    # on worker processes a task's overhead is the parent's: a message each
    # way, handled under its interpreter lock (~0.3 ms a task on a TPU v5e
    # host), so a task carries twice the work (5 images at ~1.5 ms)
    _PROC_RUN_TARGET_US = 8000.0

    def __init__(self, workers: int = 8, *, fuse_runs: bool = True,
                 transform: "Callable | None" = None, slabs: int = 3):
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
        self.run_target_us = float(self._PROC_RUN_TARGET_US
                                   if getattr(transform, "spec", None)
                                   else self._RUN_TARGET_US)
        # EWMA of per-image decode+transform micros, seeded at 1ms (the
        # measured pre-v2 cost on the bench host); updated by fused runs
        self._img_us = 1000.0
        self._closed = False
        self._procs = None
        self._proc_tf = None
        spec = getattr(transform, "spec", None)
        if spec is not None:
            from strom.formats.decode_procs import DecodeProcs

            lib = _native_lib if spec["native"] and native_available() \
                else ""
            self._procs = DecodeProcs(workers, spec, lib, self._proc_done,
                                      ring=slabs)
            self._proc_tf = transform

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

        def traced(item, rng=None, *rest) -> np.ndarray:
            # worker span on the shared timeline: per-sample decode+transform
            # (the legacy allocating path; the slot path traces in
            # _one_sample); None = telemetry off, skip the span entirely
            global_stats.add("decode_thread_samples")
            a = (item,) if rng is None else (item, as_generator(rng), *rest)
            cm = self._worker_span(req)
            if cm is None:
                return fn(*a)
            with cm:
                return fn(*a)

        return list(self._pool.map(traced, items, *extra))

    # -- shared memory for the process executor ------------------------------
    def offloads(self, fn: Callable) -> bool:
        """True when calls with *fn* run in this pool's worker processes
        (given bytes and rows in its shared memory)."""
        return self._procs is not None and fn is self._proc_tf

    def slab(self, shape: tuple) -> np.ndarray:
        """A uint8 array of *shape* in shared memory, for output rows."""
        return self._procs.lease("rows", math.prod(shape)).reshape(shape)

    def buffer(self, nbytes: int) -> np.ndarray:
        """A flat uint8 array of *nbytes* in shared memory, for member
        bytes (a streamed gather's destination)."""
        return self._procs.lease("bytes", nbytes)

    def recycle(self, *arrays: "np.ndarray | None",
                fence: Sequence = ()) -> None:
        """Return :meth:`slab` / :meth:`buffer` arrays; each is handed out
        again only after every array in *fence* (the device arrays put
        from it) is ready."""
        for a in arrays:
            if a is not None:
                self._procs.give_back(a, fence)

    def _proc_done(self, n: int, zeroed: int, wall_s: float) -> None:
        """A process task finished (the reaper thread, which adds the
        counters): the pool's error tally and the per-image EWMA, as
        :meth:`_one_sample` / :meth:`_run_into` keep them on threads."""
        with self._err_lock:
            self.decode_errors += zeroed
            self._img_us += 0.2 * (wall_s * 1e6 / n - self._img_us)

    def _offload(self, fn: Callable, items: Sequence, rngs: Sequence,
                 rows: Sequence, req) -> "concurrent.futures.Future | None":
        if not self.offloads(fn):
            return None
        keys = [philox_key(r) for r in rngs]
        if any(k is None for k in keys):
            return None
        return self._procs.submit(items, keys, rows, req)

    # -- direct-to-slot mapping --------------------------------------------
    def _call(self, fn: Callable[..., np.ndarray], item, rng,
              row: np.ndarray, ckey) -> None:
        if ckey is None:
            fn(item, rng, out=row)
        else:
            fn(item, rng, out=row, ckey=ckey)

    def _one_sample(self, fn: Callable[..., np.ndarray], item, rng,
                    row: np.ndarray, req, ckey) -> None:
        global_stats.add("decode_thread_samples")
        rng = as_generator(rng)
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

        req = _request.current()
        fut = self._offload(fn, (item,), (rng,), (row,), req)
        if fut is not None:
            return fut
        return self._pool.submit(self._one_into, fn, item, rng, row, req,
                                 ckey)

    def submit_run_into(self, fn: Callable[..., np.ndarray],
                        items: Sequence, rngs: Sequence, rows: Sequence,
                        ckeys: "Sequence | None" = None
                        ) -> concurrent.futures.Future:
        """A fused run: ONE pool task decoding items[i] into rows[i] for
        the whole run (ISSUE 12 tentpole) — per-task dispatch overhead is
        paid once per run instead of once per sample."""
        from strom.obs import request as _request

        req = _request.current()
        fut = self._offload(fn, items, rngs, rows, req)
        if fut is not None:
            return fut
        return self._pool.submit(self._run_into, fn, items, rngs, rows,
                                 req, ckeys)

    def map_into(self, fn: Callable[..., np.ndarray], items: Sequence,
                 rngs: Sequence, out: np.ndarray,
                 ckeys: "Sequence | None" = None) -> np.ndarray:
        """Map fn(item, rng, out=out[i]) over the batch, every worker
        writing straight into its slot row; contiguous runs fuse into one
        task each per :meth:`run_size`. Returns *out* once every task has
        finished (the first failure is raised after the rest retire: they
        write into *out*)."""
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
        concurrent.futures.wait(futs)
        for f in futs:
            f.result()
        return out

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self._procs is not None:
            self._procs.close()
        self._pool.shutdown(wait=True)
        if _HAVE_CV2 and self._cv2_threads_prev is not None:
            cv2.setNumThreads(self._cv2_threads_prev)

    def __enter__(self) -> "DecodePool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
