"""Completion-driven intra-batch streaming (ISSUE 5 tentpole).

The blocking hot path runs each batch as gather-ALL → decode-ALL → put-ALL:
every sample in the batch waits for the slowest extent before the first
decode starts, so completions sit idle in the ring while decode workers
starve (the ingest-wait bucket the stall attribution keeps billing the JPEG
arm). :class:`StreamingGather` removes that barrier: it plans a batch gather
exactly like ``StromContext._read_segments`` (same striped-alias resolution,
coalescing, stripe windows, extent-aware ordering — shared via
``_plan_chunks``), submits it through the engine's async vectored API
(``submit_vectored``/``poll``/``drain``, ISSUE 5 engine layer), and surfaces
CHUNK-granular dest-range completions the moment they land — hot-cache hits
count as INSTANT completions (served before the engine sees a single op).
The vision pipelines map completed ranges onto samples and hand each sample
to the decode pool the moment its extents are in, so read, decode, and
device_put overlap at extent granularity *within* one batch, not just
across batches.

Ordering / lifecycle rules (documented in ARCHITECTURE.md "Intra-batch
streaming"):

- Completions are UNORDERED across chunks (the whole point); each dest byte
  completes exactly once — ranges from distinct completions never overlap,
  so per-sample byte accounting is a plain countdown.
- The gather owns the engine's transfer path from construction to
  close/finish: the delivery engine lock (per-ring locks on the multi
  engine) is held for the token's lifetime, and the demand gate is entered
  so readahead yields exactly as it does to a blocking gather.
- Hot-cache pins taken while serving hits are dropped before construction
  returns (the bytes are already memcpy'd into *dest*); admission offers
  for miss chunks happen per-completion, so an early extent can serve the
  NEXT batch's lookup while this batch's tail is still in flight.
- ``close()`` is idempotent and safe mid-flight: the engine token is
  cancelled (every in-flight piece reaped — no completion may outlive the
  gather), locks and the demand gate release, and no slab pin survives.
"""

from __future__ import annotations

import contextlib
import errno
import time
from typing import Sequence

import numpy as np

from strom.delivery.shard import Segment
from strom.engine.base import (DeadlineExceeded, EngineError,
                               EngineStallError)
from strom.obs import request as _request
from strom.obs.events import ring as _events_ring

# columns of the checked-in pre-chip benchmark records that
# tools/compare_rounds.py reads for its "streaming" section.
STREAM_FIELDS = (
    "stream_batches",
    "stream_inflight_peak",
    "stream_instant_bytes",
    "stream_samples_early",
    "stream_first_decode_lat_p50_us",
    "stream_first_decode_lat_mean_us",
    "stream_tail_extent_p50_us",
    "stream_tail_extent_mean_us",
)


class StreamingGather:
    """One completion-driven gather of *segments* from *source* into *dest*.

    Protocol::

        g = ctx.stream_segments(source, segments, dest)
        try:
            while not g.done:
                for lo, hi in g.poll():   # dest byte ranges, landed
                    ...dispatch work on dest[lo:hi]...
            g.finish()                    # integrity check + stats
        finally:
            g.close()                     # idempotent; cancels if unfinished

    ``poll`` first returns the cache-served (instant) ranges, then engine
    completions as chunks retire. ``finish`` raises the gather's error (the
    same EngineError surface as ``_read_segments``) only after every
    in-flight piece has retired.
    """

    def __init__(self, ctx, source, segments: Sequence[Segment],
                 dest: np.ndarray, base_offset: int = 0, *, scope=None,
                 tenant: str | None = None):
        self._ctx = ctx
        # telemetry scope (ISSUE 6): pipelines pass their label scope so two
        # tenants' streamed gathers surface distinguishable stream_* series;
        # default: the context's scope (single-tenant behavior unchanged)
        self._scope = scope if scope is not None else ctx.scope
        # scheduler tenant (ISSUE 7): explicit name wins, else the scope's
        # tenant label — so a pipeline built with scope={"tenant": "t0"}
        # queues (and bills cache partitions) as t0 with no extra plumbing
        if tenant is None:
            tenant = getattr(self._scope, "labels", {}).get("tenant")
        self._tenant = tenant
        self._dflat = dest if dest.ndim == 1 and dest.dtype == np.uint8 \
            else dest.reshape(-1).view(np.uint8)
        self._closed = False
        self._finished = False
        self._token = None
        self._admitted = 0
        self.t0_us = _events_ring.now_us()
        self._first_c_us: int | None = None
        self._last_c_us: int | None = None
        # engine-path resources: demand gate (readahead yields to us) +
        # the engine arbiter — a scheduler grant for the miss bytes when a
        # scheduler exists (queued/budgeted like any tenant op), else the
        # legacy delivery engine lock. Held while the token is live and
        # RELEASED AT GATHER DRAIN (the moment the last piece retires, in
        # poll/finish/close — ISSUE 7 satellite): finish-side bookkeeping
        # and admission offers never extend engine ownership, matching the
        # release point the streamed pipeline path already had.
        self._stack = contextlib.ExitStack()
        self._engine_released = False
        # causal request tracing (ISSUE 8): join the enclosing request
        # (the streamed batch assembly mints one around make_batch) or
        # mint our own for direct stream_segments callers — the sched
        # grant, engine token, cache serve/admit and stream spans below
        # all carry its req_id; an owned request finishes at release.
        req = _request.current()
        self._own_req = req is None
        self.req = req if req is not None \
            else _request.Request("gather", self._tenant)
        if self._own_req:
            self.req.set_deadline_s(ctx.config.request_deadline_s or None)
        # resilience (ISSUE 9): per-chunk failure recovery + hedged reads
        # ride the context's breaker/failover layer. The token runs
        # fail_fast=False — a failed chunk retires as a negative
        # completion (recovered below on the fallback path) while the
        # REST of the gather keeps flowing, instead of one bad extent
        # killing the whole batch.
        self._resil = getattr(ctx, "_resilience", None)
        self._completed: set[int] = set()   # engine-served chunk indices
        self._recovered: set[int] = set()   # fallback-served after failure
        self._hedged: set[int] = set()      # fallback-served by a hedge
        self._hedge_tried: set[int] = set()  # one hedge per chunk, ever
        self._breaker_fed = False  # one breaker outcome per gather
        self._failed: dict[int, int] = {}   # unrecovered: ci -> errno
        self._recovery_attempted = False
        self._last_prog_t = time.monotonic()  # hedge quiet clock
        self._stall_t0 = time.monotonic()     # watchdog clock: REAL progress
        self._stall_bytes = -1                # piece progress the clock saw
        try:
            with _request.attach(self.req):
                chunks, idx_paths = ctx._plan_chunks(source, segments,
                                                     base_offset)
                self._idx_paths = idx_paths
                cache = ctx._hot_cache
                if cache is not None and not cache.enabled:
                    cache = None
                self._cache = cache
                self._instant: list[tuple[int, int]] = []
                hit_bytes = 0
                # peer tier included (ISSUE 15): peer-served ranges surface
                # as INSTANT completions exactly like cache hits — the
                # consult handles cache=None for peered cacheless contexts
                if (cache is not None or ctx._peer_tier is not None) \
                        and chunks:
                    chunks, hit_bytes, self._instant = ctx._consult_cache(
                        cache, chunks, idx_paths, self._dflat,
                        tenant=self._tenant)
                self._chunks = chunks
                self._miss_planned = sum(ln for (_, _, _, ln) in chunks)
                self.total_bytes = self._miss_planned + hit_bytes
                self.instant_bytes = hit_bytes
                if hit_bytes:
                    self._scope.add("stream_instant_bytes", hit_bytes)
                if chunks:
                    self._stack.enter_context(ctx._demand_gate())
                    if ctx.scheduler is not None:
                        self._stack.enter_context(
                            ctx.scheduler.grant(self._tenant,
                                                self._miss_planned))
                    else:
                        # stromlint: ignore[lock-order] -- engine ownership
                        # intentionally spans the token's lifetime (the
                        # gather owns the transfer path construction ->
                        # drain); released at _release_engine the moment
                        # the last piece retires, and every wait under it
                        # is bounded by the gather watchdog
                        # (EngineStallError in poll/finish)
                        self._stack.enter_context(ctx._engine_lock)
                    self._token = ctx.engine.submit_vectored(
                        chunks, dest, retries=ctx.config.io_retries,
                        req_id=self.req.id,
                        deadline=getattr(self.req, "deadline", None),
                        fail_fast=False)
                self._scope.add("stream_batches")
        except BaseException as e:
            self._stack.close()
            self._closed = True
            if self._own_req:
                self.req.mark_error(e)
                self.req.finish()
            raise

    @property
    def done(self) -> bool:
        """Every byte accounted for: instants drained and every chunk
        served — by the engine token, a fallback recovery, or a winning
        hedge. ``finish`` must still be called."""
        if self._instant:
            return False
        tok = self._token
        if tok is None:
            return True
        if tok.done:
            # a token that died at submit (engine death) leaves chunks
            # unaccounted: one fallback-recovery pass still owes ranges
            return self._recovery_attempted or tok._err is None \
                or self._resil is None or not self._unaccounted()
        # token still live (e.g. a stuck loser): done once every chunk is
        # individually accounted — finish() cancels the remainder
        return not self._unaccounted() and not self._failed

    def _unaccounted(self) -> list[int]:
        served = self._completed | self._recovered | self._hedged
        return [ci for ci in range(len(self._chunks))
                if ci not in served and ci not in self._failed]

    def _mark_progress(self) -> None:
        now = _events_ring.now_us()
        if self._first_c_us is None:
            self._first_c_us = now
        self._last_c_us = now
        self._last_prog_t = time.monotonic()
        self._stall_t0 = self._last_prog_t

    def _chunk_fallback(self, ci: int) -> bool:
        """Read chunk *ci* on the fallback path straight into dest.
        True on success. (Recovered bytes are NOT offered for cache
        admission: the primary path just failed around them — proving
        them stable is the next clean read's job.)"""
        if self._resil is None:
            return False
        fi, fo, do, ln = self._chunks[ci]
        path = self._idx_paths.get(fi)
        if path is None:
            return False
        ok = self._resil.read_chunk_fallback(
            path, fo, ln, self._dflat[do: do + ln])
        if ok:
            self._scope.add("failover_reads")
            self._scope.add("failover_bytes", ln)
        return ok

    def _feed_breaker(self, *, ok: bool) -> None:
        """One breaker outcome per GATHER, not per chunk: the demand path
        records per-gather too, and a streamed batch serving 10^4 chunks
        with a handful recovered must not read as a 100% error rate to
        the rolling window (a failure-count trip, not an error-rate
        trip). First outcome wins; failures are fed at recovery time,
        the success at finish."""
        if self._resil is None or self._resil.breaker is None \
                or self._breaker_fed:
            return
        self._breaker_fed = True
        if ok:
            self._resil.breaker.record_success()
        else:
            self._resil.breaker.record_failure()

    def poll(self, min_completions: int = 1,
             timeout_s: float | None = None) -> list[tuple[int, int]]:
        """Landed dest ranges since the last call. The first call returns
        the cache-served ranges immediately (instant completions); later
        calls reap the engine — failed chunks are recovered on the
        fallback path inline, and a gather quiet past the adaptive hedge
        threshold re-reads its stragglers there too (first completion
        wins). ``min_completions=0`` never blocks."""
        if self._closed:
            return []
        if self._instant:
            out, self._instant = self._instant, []
            self._mark_progress()
            return out
        tok = self._token
        if tok is None:
            return []
        out: list[tuple[int, int]] = []
        if not tok.done:
            hedge = self._resil.hedge if self._resil is not None else None
            wait_s = timeout_s
            if min_completions > 0 and hedge is not None:
                # wake at the hedge threshold: a quiet gather must get its
                # hedge decision even when the caller asked for a long wait
                quiet = time.monotonic() - self._last_prog_t
                to_hedge = max(hedge.threshold_s() - quiet, 0.005)
                wait_s = to_hedge if wait_s is None \
                    else min(wait_s, to_hedge)
            for c in self._ctx.engine.poll(tok, min_completions, wait_s):
                fi, fo, do, ln = self._chunks[c.index]
                if c.index in self._hedged or c.index in self._recovered:
                    # the fallback already served (and emitted) this chunk:
                    # this late primary completion is the race's loser —
                    # its range must not reach the consumer twice (a
                    # duplicate would double-decrement the pump's
                    # per-sample byte countdown) and its bytes are not
                    # offered for cache admission
                    if c.index in self._hedged and c.result >= 0:
                        # both sides of the hedge race moved the bytes:
                        # the loser's are the waste, whoever they belong to
                        self._scope.add("hedge_wasted_bytes", ln)
                    continue
                if c.result < 0:
                    # per-chunk failover (ISSUE 9): one bad extent no
                    # longer kills the batch — unless the deadline already
                    # expired (a late lifeboat honors nothing)
                    if not isinstance(tok._err, DeadlineExceeded) \
                            and self._chunk_fallback(c.index):
                        self._recovered.add(c.index)
                        self._mark_progress()
                        out.append((do, do + ln))
                        self._feed_breaker(ok=False)
                    else:
                        self._failed[c.index] = -c.result
                        # an unrecovered failure is a breaker outcome too
                        # (a deadline miss is the REQUEST's contract, not
                        # evidence about engine health)
                        if not isinstance(tok._err, DeadlineExceeded):
                            self._feed_breaker(ok=False)
                    continue
                self._completed.add(c.index)
                if hedge is not None:
                    hedge.observe(time.monotonic() - self._last_prog_t)
                self._mark_progress()
                out.append((do, do + ln))
                if self._cache is not None:
                    # admission offer per completion (second-touch policy
                    # decides): the bytes just landed in dest — one
                    # memcpy, never an extra read, and an early extent can
                    # serve the next batch's lookup while this batch's
                    # tail is still in flight
                    path = self._idx_paths.get(fi)
                    if path is not None:
                        self._admitted += self._cache.admit(
                            path, fo, fo + ln, self._dflat[do: do + ln],
                            tenant=self._tenant)
            if not out and min_completions > 0 and hedge is not None \
                    and not tok.done:
                quiet = time.monotonic() - self._last_prog_t
                if quiet >= hedge.threshold_s():
                    out.extend(self._fire_hedges())
        if tok.done and tok._err is not None and self._resil is not None \
                and not self._recovery_attempted:
            # token died at submit (engine death mid-gather): one
            # fallback pass over the never-completed chunks
            out.extend(self._recover_unaccounted())
        if not out and min_completions > 0 and not tok.done:
            # the pump loop (`while not g.done: g.poll(...)`) caps every
            # engine wait at its own short slices, so the ENGINE-level
            # watchdog can never fire from here — this gather-level one
            # turns a silent forever-hang into the diagnosable error
            # (finish()'s watchdog only covers callers that reach finish).
            # PIECE progress resets the clock: one huge chunk streaming
            # at full speed retires no chunk for minutes and must not
            # read as a stall.
            if tok.bytes_done != self._stall_bytes:
                self._stall_bytes = tok.bytes_done
                self._stall_t0 = time.monotonic()
            elif time.monotonic() - self._stall_t0 \
                    >= self._ctx.config.engine_wait_timeout_s:
                self._ctx.engine._note_stall("stream.poll")
                raise EngineStallError(
                    self._ctx.config.engine_wait_timeout_s,
                    list(tok._pending), "stream.poll")
        if tok.done:
            # gather drained: hand the engine back NOW — the caller may
            # keep polling instants / defer finish() without holding the
            # arbiter against other tenants (ISSUE 7 satellite)
            self._release_engine()
        elif self.done:
            # every chunk served but the token still owns in-flight loser
            # pieces (hedge winners over a wedged primary): cancel FIRST —
            # a live token owns the engine's gather path, and handing the
            # grant to the next tenant would let its gather consume the
            # losers' completions while their dest writes are still
            # kernel-owned. cancel's reap is bounded by the watchdog.
            with contextlib.suppress(Exception):
                self._ctx.engine.cancel(tok)
            self._release_engine()
        return out

    def _fire_hedges(self) -> list[tuple[int, int]]:
        """Hedge the straggler chunks on the fallback path (ISSUE 9
        tentpole #4): each incomplete chunk is re-read into a scratch
        buffer and the scratch copy wins (counted hedges_won; poll reaps
        completions on this same thread, so a chunk unaccounted here
        cannot have a delivered primary). The losing primary pieces are
        cancelled at finish(); a loser completing before that is
        discarded in poll, where its bytes count hedge_wasted_bytes —
        the race's double-moved bytes.
        Each chunk is hedged AT MOST ONCE per gather — a straggler whose
        fallback read also fails must not refire on every poll (a hedge
        storm through the serialized lifeboat, and a meaningless
        hedges_fired count).

        The winner's paste can overlap a still-in-flight loser write only
        for a wedged-but-landing primary piece, and both sides read the
        same immutable file range — byte-identical content, so the overlap
        cannot tear a value; the loser's COMPLETION (the only thing that
        could re-publish the range) is discarded above."""
        if self._resil is None:
            return []
        from strom.delivery.buffers import alloc_aligned

        scope = self._scope
        out: list[tuple[int, int]] = []
        # hedge only chunks with primary pieces IN FLIGHT: a quiet gap
        # must not serially re-read the whole not-yet-submitted gather
        # tail on the fallback (the primary will still submit all of it)
        try:
            live = self._token.pending_chunk_indices()
        # stromlint: ignore[swallowed-exceptions] -- a token without
        # pending-index support just disables hedge TARGETING this round
        # (zero chunks hedge, visible as hedges_fired staying flat); it is
        # a capability probe, not an error channel
        except Exception:
            live = set()
        for ci in self._unaccounted():
            if ci not in live or ci in self._hedge_tried:
                continue
            fi, fo, do, ln = self._chunks[ci]
            path = self._idx_paths.get(fi)
            if path is None:
                continue
            self._hedge_tried.add(ci)
            scope.add("hedges_fired")
            scratch = alloc_aligned(ln)
            if not self._resil.read_chunk_fallback(path, fo, ln,
                                                   scratch[:ln]):
                continue
            self._dflat[do: do + ln] = scratch[:ln]
            self._hedged.add(ci)
            scope.add("hedges_won")
            self._mark_progress()
            out.append((do, do + ln))
        # even an all-miss pass resets the quiet clock: the next hedge
        # decision waits a full threshold instead of re-entering per poll
        self._last_prog_t = time.monotonic()
        return out

    def _recover_unaccounted(self) -> list[tuple[int, int]]:
        self._recovery_attempted = True
        out: list[tuple[int, int]] = []
        if isinstance(self._token._err, DeadlineExceeded):
            for ci in self._unaccounted():
                self._failed[ci] = errno.ETIMEDOUT
            return out
        for ci in self._unaccounted():
            fi, fo, do, ln = self._chunks[ci]
            if self._chunk_fallback(ci):
                self._recovered.add(ci)
                self._mark_progress()
                out.append((do, do + ln))
                self._feed_breaker(ok=False)
            else:
                self._failed[ci] = self._token._err.errno or errno.EIO
                self._feed_breaker(ok=False)
        return out

    def finish(self) -> int:
        """Run the gather to full accounting, verify it, emit the stream
        span + counters, release the engine lock/demand gate. Returns
        total bytes (cache hits included). Raises the gather's first
        UNRECOVERED error — after every in-flight piece has retired,
        except for hedge losers and deadline expiry, where the remainder
        is CANCELLED (reaped bounded) before this returns."""
        if self._finished:
            return self.total_bytes
        tok = self._token
        stall_s = self._ctx.config.engine_wait_timeout_s
        last_prog = time.monotonic()
        key = None
        try:
            while tok is not None and not self.done:
                if isinstance(tok._err, DeadlineExceeded):
                    break  # fail fast: the cancel below reaps in-flight
                got = self.poll(min_completions=1, timeout_s=1.0)
                # bytes_done included: a single long chunk making steady
                # PIECE progress (reap/resubmit at constant queue depth)
                # must not read as a stall just because no CHUNK retires
                # within the watchdog
                now_key = (len(self._completed), len(self._recovered),
                           len(self._hedged), len(self._failed),
                           len(tok._pending), tok.bytes_done)
                if got or now_key != key:
                    key = now_key
                    last_prog = time.monotonic()
                elif time.monotonic() - last_prog >= stall_s:
                    self._ctx.engine._note_stall("stream.finish")
                    raise EngineStallError(stall_s, list(tok._pending),
                                           "stream.finish")
        except EngineError as e:
            # cancel BEFORE the caller can react: the kernel/worker owns
            # the in-flight pieces' dest bytes, and an abandoned wedged
            # token unwedging later would land writes into a recycled
            # batch slab (cancel's reap is itself bounded by the watchdog)
            if tok is not None and not tok.done:
                with contextlib.suppress(Exception):
                    self._ctx.engine.cancel(tok)
            self.req.mark_error(e)
            self._release()
            if isinstance(e, (DeadlineExceeded, EngineStallError)):
                raise
            raise EngineError(e.errno, f"ssd2tpu {e.strerror}") from None
        if tok is not None and not tok.done:
            # hedge losers / deadline leftovers: first completion won, the
            # primary's still-in-flight pieces are cancelled (reaped
            # bounded — no engine write outlives the gather)
            with contextlib.suppress(Exception):
                self._ctx.engine.cancel(tok)
        self._release_engine()
        deadline_miss = tok is not None \
            and isinstance(tok._err, DeadlineExceeded) \
            and (self._failed or self._unaccounted())
        if self._failed or deadline_miss:
            err = tok._err if tok is not None and tok._err is not None \
                else EngineError(errno.EIO,
                                 f"{len(self._failed)} chunk(s) failed")
            self.req.mark_error(err)
            self._release()
            if isinstance(err, (DeadlineExceeded, EngineStallError)):
                raise err
            raise EngineError(err.errno or errno.EIO,
                              f"ssd2tpu {err.strerror}") from None
        if tok is not None:
            if self._hedged or self._recovered:
                missing = self._unaccounted()
                if missing:
                    err = EngineError(
                        errno.EIO, f"ssd2tpu streamed gather left "
                                   f"{len(missing)} chunk(s) unserved")
                    self.req.mark_error(err)
                    self._release()
                    raise err
            elif tok.bytes_done != self._miss_planned:
                # cheap insurance, same as _read_segments: any engine
                # accounting bug surfaces loudly, not as a zero-tailed
                # batch (byte-exact only when every chunk was engine-
                # served; fallback-served chunks are accounted per chunk)
                err = EngineError(
                    errno.EIO, f"ssd2tpu streamed read {tok.bytes_done} "
                               f"bytes, planned {self._miss_planned}")
                self.req.mark_error(err)
                self._release()
                raise err
        # the gather served every chunk: the breaker hears the success
        # (recoveries already fed their failure above — first outcome wins)
        if tok is not None:
            self._feed_breaker(ok=True)
        self._release()
        self._scope.add("ssd2tpu_bytes", self.total_bytes)
        return self.total_bytes

    def _release_engine(self) -> None:
        """Drop the engine-path resources (demand gate + scheduler grant /
        engine lock). Idempotent; called the moment the token drains —
        every in-flight piece retired — so the stats/span/admission tail
        of finish() runs with the engine already handed to the next
        tenant in the fair drain."""
        if self._engine_released:
            return
        self._engine_released = True
        self._stack.close()

    def _release(self) -> None:
        if self._finished:
            return
        self._finished = True
        self._closed = True
        tok = self._token
        if tok is not None:
            self._scope.gauge("stream_inflight_peak").max(tok.inflight_peak)
            # keep the stall attribution's `read` bucket lit on streamed
            # batches: the async token never passes through read_vectored's
            # instrumented wrappers, so the engine window is billed here
            end = self._last_c_us if self._last_c_us is not None \
                else _events_ring.now_us()
            self.req.record("stream.read", "read", self.t0_us,
                            max(end - self.t0_us, 0),
                            {"ops": len(self._chunks),
                             "bytes": self._miss_planned})
        if self._first_c_us is not None and self._last_c_us is not None:
            # the spread the old barrier serialized on: how long the
            # slowest extent lagged the first completion — with streaming,
            # work done during this window is the win
            self._scope.observe_us("stream_tail_extent",
                                   self._last_c_us - self._first_c_us)
        if self._admitted:
            self.req.record("cache.admit", "cache", self.t0_us,
                            _events_ring.now_us() - self.t0_us,
                            {"bytes": self._admitted})
        self.req.record("stream.gather", "stream", self.t0_us,
                        _events_ring.now_us() - self.t0_us,
                        {"bytes": self.total_bytes,
                         "instant_bytes": self.instant_bytes,
                         "ops": len(self._chunks)})
        self._release_engine()
        if self._own_req:
            self.req.finish()

    def close(self) -> None:
        """Idempotent teardown. A live token is CANCELLED: every in-flight
        piece is reaped before the engine lock releases, so no completion
        (and no engine write into *dest*) outlives the gather — the
        leaked-pin/leaked-completion contract tests assert this."""
        if self._finished:
            return
        if self._token is not None and not self._token.done:
            with contextlib.suppress(Exception):
                self._ctx.engine.cancel(self._token)
        self._release_engine()
        self._release()

    def __enter__(self) -> "StreamingGather":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
