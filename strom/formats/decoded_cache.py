"""Decoded-output cache (ISSUE 12 tentpole, front 4): predecoded-on-the-fly.

The hot cache (strom/delivery/hotcache.py) serves repeat COMPRESSED bytes
from RAM, but a JPEG pipeline still pays the full entropy-decode + IDCT on
every epoch — the wall BENCH_r05 measured at ~6.5x the predecoded arm. This
adapter admits first-epoch decode OUTPUT (post-decode, pre-transform
full-frame RGB8 pixels) into the same :class:`~strom.delivery.hotcache.
HotCache`, so epoch >= 2 pays only crop + resize per sample: the predecoded
arm's economics without the offline staging pass.

Design points:

- **Keys.** ``("jpegdec", shard_path, member_lo, member_hi, fingerprint)``
  — the member's PHYSICAL extent (stable across epochs, exactly like the
  extent cache's keys) plus a decode-params fingerprint (decoder engine +
  colorspace), so pixels decoded under different semantics can never serve
  each other. The byte range within a key is ``[0, h*w*3)`` with h/w read
  from the member's SOF header — self-describing at both admit and lookup
  without a stored header.
- **Fidelity.** Cached pixels are FULL-frame, full-resolution decodes: a
  cache hit serves pixels identical to the ``reduced_scale=False`` path
  (bit-identical to the full-decode transform), never the reduced-decode
  approximation. The admitting pass therefore decodes full even where
  ROI/reduced would have engaged — that one-epoch cost is what buys every
  later epoch the RAM serve.
- **Budget + partitions.** Entries ride the shared HotCache budget and
  slab pool like every other tenant (slab-size-class billed), and charge
  the owning pipeline's tenant partition (ISSUE 7) — a decode-cache-happy
  tenant self-evicts before it can displace another tenant's hot set.
  Admission follows the cache's policy (second-touch observes the first
  epoch, admits the second; ``always`` admits on first decode — the bench
  pair's mode).
- **Pinning.** A served frame stays pinned for exactly the crop+resize
  window (the caller releases), the same lifetime handshake every other
  cache reader uses — eviction can never recycle a slab mid-transform.

Counters (``decode_cache_*``) are kept separate from the extent cache's
``cache_*`` set (lookups run ``record=False``): mixing them would distort
the hit ratio the warm/cold epoch analysis reads.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Any

import numpy as np

from strom.utils.locks import make_lock
from strom.utils.stats import global_stats

_DIMS_CAP = 1 << 16  # bounded (ckey -> (h, w)) ledger for plan-time probes


class ServedFrame:
    """A decoded frame served from the cache at PLAN time (ISSUE 13
    satellite): carries the pinned full-frame view straight into the decode
    pool in place of the JPEG bytes that were never gathered. The transform
    (and the batch's error path) release it; release is idempotent — the
    pin drops exactly once however many paths race to clean up."""

    __slots__ = ("img", "_pin", "_dcache")

    def __init__(self, img: np.ndarray, pin, dcache: "DecodedCache"):
        self.img = img
        self._pin = pin
        self._dcache = dcache

    def release(self) -> None:
        self._dcache._release_frame(self)


class DecodedCache:
    """Thin, counter-bearing adapter between the JPEG transform and a
    :class:`~strom.delivery.hotcache.HotCache` partition holding decoded
    frames. Thread-safe: the tally lock (``cache.decoded``) is a leaf
    held only for counter updates, never across cache calls."""

    def __init__(self, cache, *, tenant: "str | None" = None,
                 fingerprint: str = "rgb8", scope=None):
        self._hot_cache = cache
        self._tenant = tenant
        self._fp = fingerprint
        self._lock = make_lock("cache.decoded")
        self._scope = scope if scope is not None else global_stats
        self.hits = 0
        self.misses = 0
        self.hit_bytes = 0
        self.admitted_bytes = 0
        # plan-time probe support (ISSUE 13 satellite): frame dims per key,
        # learned at offer/get — the pre-gather probe has no JPEG header to
        # read h/w from, so it consults this bounded ledger instead
        self._dims: "OrderedDict[Any, tuple[int, int]]" = OrderedDict()
        self.plan_hits = 0
        self.plan_skipped_bytes = 0

    @property
    def enabled(self) -> bool:
        """Follows the backing cache's phase gate: a disabled hot cache
        serves/admits no decoded frames either."""
        return self._hot_cache is not None and self._hot_cache.enabled

    def key(self, path: str, lo: int, hi: int) -> tuple:
        """Cache key for the member occupying file bytes [lo, hi) of
        *path* — extent-stable across epochs, fingerprint-split across
        decode semantics."""
        return ("jpegdec", path, lo, hi, self._fp)

    def get(self, ckey: Any, h: int, w: int):
        """(pinned (h, w, 3) view, pin) on a hit, None on a miss. The
        caller MUST :meth:`release` the pin once it stops reading the
        view (after the crop+resize)."""
        n = h * w * 3
        got = self._hot_cache.view(ckey, 0, n, record=False)
        self._note_dims(ckey, h, w)
        if got is None:
            with self._lock:
                self.misses += 1
            self._scope.add("decode_cache_misses")
            return None
        buf, entry = got
        with self._lock:
            self.hits += 1
            self.hit_bytes += n
        self._scope.add("decode_cache_hits")
        self._scope.add("decode_cache_hit_bytes", n)
        return buf.reshape(h, w, 3), entry

    def _note_dims(self, ckey: Any, h: int, w: int) -> None:
        with self._lock:
            self._dims[ckey] = (h, w)
            self._dims.move_to_end(ckey)
            while len(self._dims) > _DIMS_CAP:
                self._dims.popitem(last=False)

    def probe(self, ckey: Any, skipped_bytes: int = 0
              ) -> "ServedFrame | None":
        """Plan-time probe (ISSUE 13 satellite): a pinned
        :class:`ServedFrame` when the FULL frame for *ckey* is resident —
        the caller then skips gathering the image member entirely (labels +
        misses only reach the engine) and hands the frame to the transform
        in place of the bytes. None when the frame (or its dims ledger
        entry) is absent: the member is gathered and the in-transform
        serve/offer path runs as before — a stale ledger can only cost a
        wasted gather, never wrong pixels. *skipped_bytes* (the member size
        the hit avoids gathering) feeds the observability counters."""
        if not self.enabled:
            return None
        with self._lock:
            dims = self._dims.get(ckey)
        if dims is None:
            return None
        h, w = dims
        got = self._hot_cache.view(ckey, 0, h * w * 3, record=False)
        if got is None:
            return None
        buf, entry = got
        with self._lock:
            self.hits += 1
            self.hit_bytes += h * w * 3
            self.plan_hits += 1
            self.plan_skipped_bytes += skipped_bytes
        self._scope.add("decode_cache_hits")
        self._scope.add("decode_cache_hit_bytes", h * w * 3)
        self._scope.add("decode_cache_plan_hits")
        if skipped_bytes:
            self._scope.add("decode_cache_plan_skipped_bytes",
                            skipped_bytes)
        return ServedFrame(buf.reshape(h, w, 3), entry, self)

    def export(self, path: str, lo: int, hi: int,
               fingerprint: "str | None" = None
               ) -> "tuple[int, int, bytes] | None":
        """Peer-serving lookup (ISSUE 20): ``(h, w, rgb bytes)`` for the
        member at [*lo*, *hi*) of *path* when the full decoded frame is
        resident, else None. Unlike :meth:`probe` the pixels are COPIED
        out (the peer server writes them to a socket after the call
        returns, far outside any pin window) and the requester's decode
        *fingerprint* must match ours — pixels decoded under different
        semantics never cross the wire either."""
        if not self.enabled:
            return None
        if fingerprint and fingerprint != self._fp:
            return None
        ckey = ("jpegdec", path, lo, hi, self._fp)
        with self._lock:
            dims = self._dims.get(ckey)
        if dims is None:
            return None
        h, w = dims
        got = self._hot_cache.view(ckey, 0, h * w * 3, record=False)
        if got is None:
            return None
        buf, entry = got
        try:
            out = bytes(buf)
        finally:
            self._hot_cache.unpin((entry,))
        with self._lock:
            self.hits += 1
            self.hit_bytes += h * w * 3
        self._scope.add("decode_cache_hits")
        self._scope.add("decode_cache_hit_bytes", h * w * 3)
        return h, w, out

    def release(self, pin) -> None:
        self._hot_cache.unpin((pin,))

    def _release_frame(self, frame: ServedFrame) -> None:
        """Idempotent ServedFrame release: the pin drops exactly once even
        when the transform's finally and the batch abort path both run."""
        with self._lock:
            pin, frame._pin = frame._pin, None
        if pin is not None:
            self._hot_cache.unpin((pin,))

    def offer(self, ckey: Any, img: np.ndarray) -> int:
        """Offer a decoded full frame for admission (subject to the
        cache's policy, budget, and the owning tenant's partition).
        Returns bytes admitted (0 = refused/duplicate)."""
        if img.ndim == 3:
            self._note_dims(ckey, img.shape[0], img.shape[1])
        flat = np.ascontiguousarray(img).reshape(-1)
        admitted = self._hot_cache.admit(ckey, 0, flat.size, flat,
                                         tenant=self._tenant)
        if admitted:
            with self._lock:
                self.admitted_bytes += admitted
            self._scope.add("decode_cache_admitted_bytes", admitted)
        return admitted
