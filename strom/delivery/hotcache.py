"""Hot-set host cache + epoch-aware readahead (ISSUE 4 tentpole).

The pipelines re-gather the same bytes from NVMe on *every* epoch and every
repeat request, even when the working set fits in host RAM. This module adds
the missing caching axis from the ROADMAP north star: an extent-keyed,
byte-budgeted, refcounted LRU over slab-pool-backed host buffers that the
delivery layer (`StromContext._read_segments`) consults before engine
submission — a full hit never touches the engine (the bytes memcpy from RAM
straight toward ``device_put``); a partial hit splits the request so only
the miss runs are submitted. ≙ the page-cache tier the reference bypasses
by design (SURVEY.md §2.1 "Page-cache fallback"), rebuilt in userspace where
O_DIRECT means the kernel's own cache never sees these bytes.

Design points, in the order they bit previous subsystems:

- **Stable keys.** Entries key on ``(physical path, byte range)`` AFTER
  extent/stripe expansion, not on caller segments: an ExtentList is rebuilt
  per batch with batch-relative logical offsets, and coalescing merges
  fragments differently depending on shuffle order — physical ranges are
  the only identity that repeats across epochs. Interval arithmetic (not
  whole-entry equality) serves overlaps, so epoch N+1's differently-split
  request still hits epoch N's entries.
- **Second-touch admission** (``hot_cache_admit="second_touch"``): the first
  epoch only *observes* (a block-granular touch ledger, bounded LRU), the
  second admits — one-shot scans never displace the hot set. Force-admit
  (``"always"``) is the knob for known-repeating workloads; readahead
  always force-admits (warming IS the prediction).
- **Refcounted eviction.** Entries are pinned while anything reads them — a
  serve-memcpy, or a ``device_put`` sourced zero-copy from the cached slab
  (the full-hit fast path in ``memcpy_ssd2tpu``). Eviction under byte
  pressure skips pinned entries and an evicted-while-pinned entry only
  returns its slab to the pool on the LAST unpin, so a recycled slab can
  never be overwritten mid-put (the same lifetime handshake as
  SlabPool.release, SURVEY.md §7.4 hard part #3).
- **Readahead yields to demand.** The epoch-aware readahead thread pulls the
  sampler's upcoming-batch window (``EpochShuffleSampler.peek`` — it crosses
  the epoch boundary, so the next epoch's head warms while the tail of this
  one trains) and warms cache misses in slices of the engine's in-flight
  budget (``queue_depth * block_size``), checking for in-flight demand reads
  before every slice: a demand gather never queues behind more than one
  readahead slice, and an active demand read aborts the warming pass
  entirely (``cache_readahead_yields``).

Observability: ``cache_hit/miss/admitted/evicted/readahead`` counters and
the ``cache_hit_ratio`` gauge in the global registry (typed via
``all_counter_names`` for /metrics), the ``cache`` section of
``StromContext.stats()`` (→ /stats and Prometheus exposition), and
``cat="cache"`` spans in the event ring (serve/admit/readahead on the
timeline next to the reads they replace).
"""

from __future__ import annotations

import bisect
import threading
from collections import OrderedDict
from typing import Any, Callable, Iterable

import numpy as np

from strom.delivery.buffers import HUGE_PAGE, alloc_aligned, size_class
from strom.utils.locks import make_lock

ADMIT_POLICIES = ("second_touch", "always")

# columns (warm/cold epoch pair) of the checked-in pre-chip benchmark
# records that tools/compare_rounds.py reads for its "cache" section
CACHE_BENCH_FIELDS = (
    "cold_images_per_s",
    "warm_images_per_s",
    "warm_vs_cold",
    "cache_hit_bytes",
    "cache_miss_bytes",
    "cache_admitted_bytes",
    "cache_readahead_bytes",
    "cache_epoch_steps",
)


class _Entry:
    """One cached physical range: ``buf[:hi-lo]`` holds file bytes [lo, hi)
    of ``skey``. ``refs`` pins it against eviction; ``dead`` marks an entry
    evicted while pinned (slab freed on last unpin). ``charge`` is what the
    byte budget is billed — the backing slab's ALLOCATED size (size class,
    2MiB-rounded under huge pages), not the logical length, so resident
    memory actually respects ``hot_cache_bytes``."""

    __slots__ = ("skey", "lo", "hi", "buf", "refs", "dead", "charge",
                 "tenant", "demote")

    def __init__(self, skey: Any, lo: int, hi: int, buf: np.ndarray,
                 charge: int, tenant: "str | None" = None):
        self.skey = skey
        self.lo = lo
        self.hi = hi
        self.buf = buf
        self.refs = 0
        self.dead = False
        self.charge = charge
        # owning tenant for partition accounting (ISSUE 7): None = charged
        # to the shared budget only (single-tenant behavior unchanged)
        self.tenant = tenant
        # evicted under byte pressure with a spill tier attached (ISSUE 13):
        # the freeing caller demotes the bytes to NVMe before returning the
        # slab to the pool. clear() leaves it False — a cleared cache drops,
        # it does not spill (the bench epoch pairs depend on that).
        self.demote = False

    @property
    def nbytes(self) -> int:
        return self.hi - self.lo


class HotCache:
    """Extent-keyed, byte-budgeted, refcounted LRU of host byte ranges.

    Thread-safe: metadata mutates under one lock; the actual byte copies
    happen outside it with the source entries pinned. Buffers come from the
    delivery slab pool when one is supplied (recycled, NUMA-placed,
    engine-registered slabs) and fall back to fresh aligned allocations.
    """

    def __init__(self, max_bytes: int, *, pool=None,
                 admit: str = "second_touch", block_bytes: int = 1 << 20,
                 touch_capacity: int = 1 << 16, scope=None):
        if max_bytes <= 0:
            raise ValueError("max_bytes must be positive")
        if admit not in ADMIT_POLICIES:
            raise ValueError(f"admit must be one of {ADMIT_POLICIES}, "
                             f"got {admit!r}")
        if block_bytes <= 0:
            raise ValueError("block_bytes must be positive")
        self.max_bytes = max_bytes
        self.admit_policy = admit
        self._block = block_bytes
        self._pool = pool
        # NVMe spill tier (ISSUE 13): when attached (StromContext wires a
        # strom.delivery.spill.SpillTier for spill_bytes > 0), entries
        # evicted under byte pressure demote there instead of vanishing —
        # the delivery consult then serves them from the spill file with
        # zero source-engine reads. None = single-tier behavior unchanged.
        self.spill = None
        # phase gate: a disabled cache serves/admits/warms nothing (entries
        # are kept). Tests use it to compare cached and uncached passes on
        # one context; library contexts stay always-on.
        self.enabled = True
        self._lock = make_lock("cache.meta")
        # skey -> entries sorted by lo (disjoint ranges per skey)
        self._index: dict[Any, list[_Entry]] = {}
        # LRU: oldest first; value is the entry (key is its id())
        self._lru: "OrderedDict[int, _Entry]" = OrderedDict()
        # block-granular touch ledger for second-touch admission, bounded
        # LRU so a giant cold scan can't grow it without limit
        self._touched: "OrderedDict[tuple, None]" = OrderedDict()
        self._touch_cap = touch_capacity
        self.bytes = 0
        # per-tenant partitions (ISSUE 7 tentpole): tenant -> byte cap
        # within the shared budget, charged at admit time. A tenant at its
        # cap evicts ITS OWN unpinned LRU entries first; only if that
        # frees nothing is the admission dropped — one tenant's working
        # set can never displace every other tenant's.
        self._partitions: dict[str, int] = {}
        self._tenant_bytes: dict[str, int] = {}
        # telemetry scope (ISSUE 6): the owning context's label scope, so a
        # tenant's cache traffic is distinguishable on /metrics; None = the
        # global registry (single-tenant behavior unchanged)
        from strom.utils.stats import global_stats

        self._scope = scope if scope is not None else global_stats
        # instance tallies (authoritative for stats()); the same names are
        # mirrored into the telemetry scope (scoped series + global
        # aggregate) so /metrics typing and bench deltas work without
        # bespoke plumbing
        self.hit_bytes = 0
        self.miss_bytes = 0
        self.hits = 0
        self.misses = 0
        self.admitted_bytes = 0
        self.evictions = 0
        self.evicted_bytes = 0
        self.readahead_bytes = 0
        self.readahead_yields = 0
        self.readahead_errors = 0

    # -- allocation ---------------------------------------------------------
    def _charge(self, n: int) -> int:
        """Budget charge for an *n*-byte entry: the backing slab's allocated
        size — pool slabs round to their size class (and to 2MiB under huge
        pages), so billing logical bytes would let resident memory overshoot
        ``max_bytes`` by the rounding factor (and silently break the
        ``bound_depth`` pool reservation sized on this budget)."""
        c = size_class(n)
        if getattr(self._pool, "huge", False):
            c = (c + HUGE_PAGE - 1) // HUGE_PAGE * HUGE_PAGE
        return c

    def _alloc(self, n: int) -> np.ndarray:
        if self._pool is not None:
            return self._pool.acquire(n)
        return alloc_aligned(n)

    def _free(self, buf: np.ndarray) -> None:
        if self._pool is not None:
            self._pool.release(buf)
        # else: GC unmaps

    # -- lookup / pinning ---------------------------------------------------
    def lookup(self, skey: Any, lo: int, hi: int, *, record: bool = True,
               count_misses: bool = True
               ) -> tuple[list[tuple[int, int, np.ndarray]],
                          list[tuple[int, int]], list[_Entry]]:
        """Split [lo, hi) of *skey* into cached and missing ranges.

        Returns ``(hits, misses, pinned)``: hits are ``(h_lo, h_hi, view)``
        where *view* is a zero-copy window of the backing slab; *pinned*
        holds the entries backing those views with their refcount raised —
        the caller MUST :meth:`unpin` them once it stops reading the views
        (after the memcpy, or after a device_put sourced from them retires).
        ``record=False`` skips the hit/miss counters (readahead probes must
        not inflate the demand hit ratio). ``count_misses=False`` defers
        ONLY the miss counters to the caller (:meth:`note_miss`) — the
        spill-tier consult (ISSUE 13) uses it so a RAM miss the spill file
        serves never shows up as ``cache_miss_bytes``.
        """
        hits: list[tuple[int, int, np.ndarray]] = []
        misses: list[tuple[int, int]] = []
        pinned: list[_Entry] = []
        with self._lock:
            entries = self._index.get(skey, ())
            pos = lo
            i = bisect.bisect_right(entries, lo, key=lambda e: e.lo) - 1 \
                if entries else 0
            i = max(i, 0)
            while pos < hi and i < len(entries):
                e = entries[i]
                if e.hi <= pos:
                    i += 1
                    continue
                if e.lo >= hi:
                    break
                if e.lo > pos:
                    misses.append((pos, e.lo))
                    pos = e.lo
                s, t = max(pos, e.lo), min(hi, e.hi)
                e.refs += 1
                pinned.append(e)
                self._lru.move_to_end(id(e))
                hits.append((s, t, e.buf[s - e.lo: t - e.lo]))
                pos = t
                i += 1
            if pos < hi:
                misses.append((pos, hi))
            if record:
                hb = sum(t - s for s, t, _ in hits)
                self.hit_bytes += hb
                self.hits += len(hits)
                if count_misses:
                    self.miss_bytes += sum(t - s for s, t in misses)
                    self.misses += len(misses)
        if record:
            if hits:
                self._scope.add("cache_hits", len(hits))
                self._scope.add("cache_hit_bytes",
                                 sum(t - s for s, t, _ in hits))
            if misses and count_misses:
                self._scope.add("cache_misses", len(misses))
                self._scope.add("cache_miss_bytes",
                                 sum(t - s for s, t in misses))
        return hits, misses, pinned

    def note_miss(self, nbytes: int, n: int = 1) -> None:
        """Count a TRUE miss (no RAM entry, no spill entry) whose counting
        :meth:`lookup` deferred via ``count_misses=False``."""
        if nbytes <= 0:
            return
        with self._lock:
            self.miss_bytes += nbytes
            self.misses += n
        self._scope.add("cache_misses", n)
        self._scope.add("cache_miss_bytes", nbytes)

    def view(self, skey: Any, lo: int, hi: int, *, record: bool = True
             ) -> tuple[np.ndarray, _Entry] | None:
        """A single pinned zero-copy view when ONE entry covers the whole
        [lo, hi) — the full-hit fast path ``memcpy_ssd2tpu`` device_puts
        from directly. Caller must :meth:`unpin` after the put retires."""
        with self._lock:
            entries = self._index.get(skey, ())
            if not entries:
                return None
            i = bisect.bisect_right(entries, lo, key=lambda e: e.lo) - 1
            if i < 0:
                return None
            e = entries[i]
            if not (e.lo <= lo and hi <= e.hi):
                return None
            e.refs += 1
            self._lru.move_to_end(id(e))
            if record:
                self.hit_bytes += hi - lo
                self.hits += 1
        if record:
            self._scope.add("cache_hits")
            self._scope.add("cache_hit_bytes", hi - lo)
        return e.buf[lo - e.lo: hi - e.lo], e

    def unpin(self, entries: Iterable[_Entry]) -> None:
        """Drop pins taken by :meth:`lookup`/:meth:`view`; frees the slab
        of any entry that was evicted while pinned. Dead entries NEVER
        demote to the spill tier: pressure eviction only picks unpinned
        victims, so a dead entry can only come from invalidate()/clear()
        — and spilling at unpin time could republish bytes a concurrent
        invalidation (a write landed on the file) just purged."""
        dead: list[np.ndarray] = []
        with self._lock:
            for e in entries:
                e.refs -= 1
                if e.dead and e.refs == 0:
                    dead.append(e.buf)
                    e.buf = None  # type: ignore[assignment]
        for buf in dead:
            self._free(buf)

    def _demote_and_free(self, e: _Entry, buf: np.ndarray) -> None:
        """Outside-the-lock half of eviction (ISSUE 13): offer the evicted
        bytes to the spill tier (when attached and the eviction wanted it),
        then hand the slab back to the pool. Spill failures are counted,
        never raised — losing a demotion means a future source re-read, the
        exact behavior of the spill-less cache."""
        sp = self.spill
        if e.demote and sp is not None and e.skey is not None:
            try:
                sp.offer(e.skey, e.lo, e.hi, buf[: e.nbytes],
                         tenant=e.tenant)
            # stromlint: ignore[swallowed-exceptions] -- advisory demotion:
            # a full/closed spill file degrades to the pre-spill eviction
            # (drop), and the error is counted below
            except Exception:
                self._scope.add("spill_errors")
        self._free(buf)

    # -- admission / eviction -----------------------------------------------
    def _blocks(self, skey: Any, lo: int, hi: int) -> list[tuple]:
        return [(skey, b) for b in range(lo // self._block,
                                         (hi - 1) // self._block + 1)]

    def _touch(self, blocks: list[tuple]) -> bool:
        """Mark blocks touched; True when EVERY block had been touched
        before (the second-touch admission test)."""
        seen = all(b in self._touched for b in blocks)
        for b in blocks:
            self._touched[b] = None
            self._touched.move_to_end(b)
        while len(self._touched) > self._touch_cap:
            self._touched.popitem(last=False)
        return seen

    def set_partition(self, tenant: str, max_bytes: int) -> None:
        """Cap *tenant*'s resident bytes at *max_bytes* (0 removes the
        partition; the tenant then shares the global budget unpartitioned).
        Existing entries keep their charge — enforcement applies from the
        next admission."""
        with self._lock:
            if max_bytes <= 0:
                self._partitions.pop(tenant, None)
            else:
                self._partitions[tenant] = int(max_bytes)

    def partitions(self) -> dict:
        """{tenant: {"max_bytes", "bytes"}} — the /tenants route's cache
        column."""
        with self._lock:
            return {t: {"max_bytes": m,
                        "bytes": self._tenant_bytes.get(t, 0)}
                    for t, m in self._partitions.items()}

    def admit(self, skey: Any, lo: int, hi: int, data: np.ndarray, *,
              force: bool = False, tenant: "str | None" = None) -> int:
        """Offer file bytes [lo, hi) of *skey* (``data`` holds them) for
        admission. Subject to the admission policy (unless *force*), the
        byte budget (LRU eviction of unpinned entries makes room) and
        disjointness (already-cached subranges are skipped). Returns bytes
        actually admitted."""
        n = hi - lo
        if n <= 0 or self._charge(n) > self.max_bytes:
            return 0
        with self._lock:
            if not force and self.admit_policy == "second_touch" \
                    and not self._touch(self._blocks(skey, lo, hi)):
                return 0
        # gaps only (keeps per-skey entries disjoint); lookup pins the
        # overlapped entries — unpin immediately, we only needed the holes
        _, gaps, pinned = self.lookup(skey, lo, hi, record=False)
        self.unpin(pinned)
        admitted = 0
        for g_lo, g_hi in gaps:
            admitted += self._insert(skey, g_lo, g_hi,
                                     data[g_lo - lo: g_hi - lo],
                                     tenant=tenant)
        if admitted:
            with self._lock:
                self.admitted_bytes += admitted
            self._scope.add("cache_admitted_bytes", admitted)
        return admitted

    def _insert(self, skey: Any, lo: int, hi: int, data: np.ndarray, *,
                tenant: "str | None" = None) -> int:
        n = hi - lo
        charge = self._charge(n)
        buf = self._alloc(n)
        buf[:n] = data[:n]
        # evicted-but-unpinned slabs collected under the lock, demoted to
        # the spill tier and returned to the pool AFTER it releases:
        # spill pwrites block and pool.release takes the slab-pool lock,
        # which ranks BEFORE the cache lock in the canonical hierarchy
        # (scheduler -> engine -> slab pool -> hot cache -> stats/ring) —
        # the same free-outside-the-lock shape unpin() has
        to_free: list[tuple[_Entry, np.ndarray]] = []
        with self._lock:
            # partition enforcement (ISSUE 7): a tenant over its carve-out
            # first evicts its OWN unpinned entries (self-displacement —
            # other tenants' hot sets are untouchable via this path), and
            # admission is refused if its cap still can't fit the entry
            refused = False
            cap = self._partitions.get(tenant) if tenant is not None else None
            if cap is not None:
                if charge > cap:
                    refused = True
                else:
                    while self._tenant_bytes.get(tenant, 0) + charge > cap:
                        victim = next(
                            (e for e in self._lru.values()
                             if e.refs == 0 and e.tenant == tenant), None)
                        if victim is None:
                            break
                        to_free.extend(self._evict_locked(victim))
                    if self._tenant_bytes.get(tenant, 0) + charge > cap:
                        refused = True
            # make room in the shared budget (skip pinned entries: never
            # free a slab with an in-flight reader/put)
            while not refused and self.bytes + charge > self.max_bytes:
                victim = next((e for e in self._lru.values() if e.refs == 0),
                              None)
                if victim is None:
                    break
                to_free.extend(self._evict_locked(victim))
            if refused or self.bytes + charge > self.max_bytes:
                drop = buf  # over partition / everything left pinned
            else:
                # a concurrent admit may have covered part of this gap
                # between our lookup and now; keep entries disjoint
                entries = self._index.setdefault(skey, [])
                i = bisect.bisect_right(entries, lo, key=lambda e: e.lo)
                prev_ok = i == 0 or entries[i - 1].hi <= lo
                next_ok = i == len(entries) or entries[i].lo >= hi
                if not (prev_ok and next_ok):
                    drop = buf
                else:
                    e = _Entry(skey, lo, hi, buf, charge, tenant)
                    entries.insert(i, e)
                    self._lru[id(e)] = e
                    self.bytes += charge
                    if tenant is not None:
                        self._tenant_bytes[tenant] = \
                            self._tenant_bytes.get(tenant, 0) + charge
                    drop = None
        for victim, victim_buf in to_free:
            self._demote_and_free(victim, victim_buf)
        if drop is not None:
            self._free(drop)
            return 0
        return n

    def _evict_locked(self, e: _Entry, *, demote: bool = True
                      ) -> list[tuple[_Entry, np.ndarray]]:
        """Remove *e* from the index/LRU (lock held). Returns the
        (entry, slab) pairs to demote+free — the CALLER runs
        :meth:`_demote_and_free` after releasing the cache lock (spill
        pwrites and pool.release must not run under it; the hierarchy
        orders the slab-pool lock before this one). A still-pinned entry
        returns nothing here; its last unpin frees WITHOUT demoting
        (see unpin). ``demote=False``
        (clear()) drops without spilling."""
        self._lru.pop(id(e), None)
        entries = self._index.get(e.skey)
        if entries is not None:
            i = bisect.bisect_right(entries, e.lo, key=lambda x: x.lo) - 1
            if 0 <= i < len(entries) and entries[i] is e:
                entries.pop(i)
            if not entries:
                del self._index[e.skey]
        self.bytes -= e.charge
        if e.tenant is not None:
            left = self._tenant_bytes.get(e.tenant, 0) - e.charge
            if left > 0:
                self._tenant_bytes[e.tenant] = left
            else:
                self._tenant_bytes.pop(e.tenant, None)
        self.evictions += 1
        self.evicted_bytes += e.nbytes
        self._scope.add("cache_evictions")
        self._scope.add("cache_evicted_bytes", e.nbytes)
        e.demote = demote and self.spill is not None
        if e.refs == 0:
            buf, e.buf = e.buf, None  # type: ignore[assignment]
            return [(e, buf)]
        e.dead = True  # last unpin frees (never demotes: see unpin)
        return []

    def invalidate(self, skey: Any) -> int:
        """Drop every entry of *skey* — and of any DERIVED tuple key that
        embeds it (the decoded-output cache keys frames as
        ``("jpegdec", path, lo, hi, fp)``: pixels decoded from the old
        bytes must go too) — WITHOUT demoting: the backing bytes changed
        (a write landed on the file), so neither tier may keep serving
        them. Returns entries dropped. Pinned entries leave the index
        immediately; their slabs free on the last unpin."""
        to_free: list[tuple[_Entry, np.ndarray]] = []
        dropped = 0
        with self._lock:
            keys = [k for k in self._index
                    if k == skey or (isinstance(k, tuple) and skey in k)]
            for k in keys:
                for e in list(self._index.get(k, ())):
                    dropped += 1
                    to_free.extend(self._evict_locked(e, demote=False))
        for _e, buf in to_free:
            self._free(buf)
        if self.spill is not None:
            self.spill.invalidate(skey)
        return dropped

    def clear(self) -> None:
        """Drop every entry AND the touch ledger (a cleared cache forgets
        its observations too — the cold/warm bench pair depends on this).
        Pinned entries leave the index immediately (no new lookup can hit
        them) but their slabs free on the last unpin."""
        to_free: list[tuple[_Entry, np.ndarray]] = []
        with self._lock:
            for e in list(self._lru.values()):
                to_free.extend(self._evict_locked(e, demote=False))
            self._touched.clear()
        for _e, buf in to_free:
            self._free(buf)

    # -- readahead accounting ----------------------------------------------
    def note_readahead(self, nbytes: int) -> None:
        with self._lock:
            self.readahead_bytes += nbytes
        self._scope.add("cache_readahead_bytes", nbytes)

    def note_yield(self) -> None:
        with self._lock:
            self.readahead_yields += 1
        self._scope.add("cache_readahead_yields")

    def note_error(self) -> None:
        """A readahead tick died (window_fn raised, source vanished): the
        thread keeps running, but 'readahead silently broken' must be
        distinguishable from 'nothing to warm' (readahead_bytes 0 alone
        cannot tell the two apart)."""
        with self._lock:
            self.readahead_errors += 1
        self._scope.add("cache_readahead_errors")

    # -- introspection ------------------------------------------------------
    @property
    def entries(self) -> int:
        with self._lock:
            return len(self._lru)

    def manifest(self, *, max_entries: int = 4096) -> list[list]:
        """Resident path-keyed ranges, newest-first, as JSON-stable
        ``[path, lo, hi]`` triples — the warm-state hints a StepToken can
        carry across a restart (ISSUE 14, strom/ckpt/jobstate.py).
        Derived tuple keys (decoded frames) are skipped: they are decode
        OUTPUT, not re-readable source ranges."""
        out: list[list] = []
        with self._lock:
            for e in reversed(self._lru.values()):
                if len(out) >= max_entries:
                    break
                if isinstance(e.skey, str):
                    out.append([e.skey, e.lo, e.hi])
        return out

    def stats(self) -> dict:
        """The ``cache`` section of ``StromContext.stats()`` — full metric
        names as keys so the sections exposition types the counters via the
        global registry mirror (PR 3 exposition rules)."""
        with self._lock:
            served = self.hit_bytes + self.miss_bytes
            ratio = self.hit_bytes / served if served else 0.0
            out = {
                "cache_budget_bytes": self.max_bytes,
                "cache_bytes": self.bytes,
                "cache_entries": len(self._lru),
                "cache_hit_bytes": self.hit_bytes,
                "cache_miss_bytes": self.miss_bytes,
                "cache_hits": self.hits,
                "cache_misses": self.misses,
                "cache_admitted_bytes": self.admitted_bytes,
                "cache_evictions": self.evictions,
                "cache_evicted_bytes": self.evicted_bytes,
                "cache_readahead_bytes": self.readahead_bytes,
                "cache_readahead_yields": self.readahead_yields,
                "cache_readahead_errors": self.readahead_errors,
                "cache_hit_ratio": round(ratio, 4),
            }
        self._scope.set_gauge("cache_hit_ratio", out["cache_hit_ratio"])
        return out


class Readahead:
    """Epoch-aware readahead: warm the upcoming-batch window into the cache.

    *window_fn* returns an iterable of ``(source, segments, base_offset)``
    read requests describing the next ``readahead_window_batches`` batches
    (pipelines build it from ``EpochShuffleSampler.peek``, which crosses the
    epoch boundary — the next epoch's head warms while this one drains).
    Each tick re-pulls the window, so the thread tracks the sampler as the
    prefetcher advances it; fully-warm windows back off to a longer sleep.

    All warming goes through ``StromContext.warm``, which serves only
    MISSES, force-admits what it reads, and yields to demand reads between
    engine-budget-sized slices — this thread can therefore never turn a
    demand gather into a queue-depth casualty (asserted in
    tests/test_hotcache.py).
    """

    def __init__(self, ctx, window_fn: Callable[..., Iterable[tuple]], *,
                 interval_s: float = 0.02, tenant: "str | None" = None,
                 window_batches: int = 0):
        import inspect

        self._ctx = ctx
        self._window_fn = window_fn
        self._interval = interval_s
        # live window size (ISSUE 19 satellite): the autotuner's
        # readahead_window_batches knob writes here and the next tick
        # builds that many batches — window fns taking an argument receive
        # it, zero-arg fns (fixed windows) keep their own count
        self.window_batches = int(window_batches)
        try:
            self._fn_takes_n = bool(
                inspect.signature(window_fn).parameters)
        except (TypeError, ValueError):  # builtins/partials w/o signature
            self._fn_takes_n = False
        # the pipeline this thread warms FOR: admitted entries charge that
        # tenant's cache partition (the ENGINE reads still ride the shared
        # background "readahead" tenant — ownership and scheduling differ)
        self._tenant = tenant
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="strom-readahead")
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.is_set():
            cache = getattr(self._ctx, "hot_cache", None)
            if cache is None or not cache.enabled:
                # gate BEFORE window_fn: building the window (sampler.peek
                # + per-batch extents) is exactly the CPU the disabled
                # phases must not pay on a 1-core box
                self._stop.wait(self._interval * 5)
                continue
            warmed = 0
            try:
                window = (self._window_fn(self.window_batches)
                          if self._fn_takes_n else self._window_fn())
                for source, segments, base_offset in window:
                    if self._stop.is_set():
                        break
                    warmed += self._ctx.warm(source, segments, base_offset,
                                             tenant=self._tenant)
            except Exception:
                # advisory path: a racing pipeline/context close (or a
                # transient engine error) must neither kill the thread nor
                # spew into the consumer's stderr — but it must be COUNTED,
                # or a broken window_fn reads as "nothing to warm"
                cache = getattr(self._ctx, "hot_cache", None)
                if cache is not None:
                    cache.note_error()
            self._stop.wait(self._interval if warmed else self._interval * 5)

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)
