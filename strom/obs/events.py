"""Bounded, thread-safe event ring: the timeline half of observability.

Every instrumented hot path (engine gathers, device_put, decode workers,
prefetch transitions, pipeline ``__next__``, train steps) records spans and
instants here. Design constraints, in order:

- **cheap enough to leave on**: one ``perf_counter`` read per edge, a tuple
  store into a preallocated slot list under a short lock — no per-event
  allocation beyond the tuple, no I/O, no formatting. Spans are recorded as
  ONE complete event at exit (ts + dur), not begin/end pairs, halving ring
  pressure.
- **bounded**: fixed capacity, drop-oldest (ring overwrite) under pressure;
  ``events_dropped`` counts the overwrites so a truncated timeline is
  visible, never silent.
- **causal**: every event carries (ts_us, dur_us, tid, category, name,
  args) on one shared monotonic clock, so :mod:`strom.obs.stall` can
  attribute a consumer's wait to what the pipeline was doing at that instant.
- **counted**: every span also adds to per-name totals (count, wall µs,
  and thread-CPU µs where the site bills it) that a wrap never drops, so
  a rate or mean over a window is the delta of two
  :meth:`EventRing.totals` readings however many events the ring
  overwrote in between.
- **anchored**: :meth:`EventRing.anchor` opens a ``jax.profiler``
  annotation and a ring span of one name at the same instants; its start
  and end pairs map the ring's clock onto a profiler trace's, so every
  ring span of the profiled window can be placed on the device timeline.

Event categories (the ``cat`` field) are the stall-attribution vocabulary:
``read`` (engine gathers), ``decode`` (JPEG worker spans), ``put``
(host->HBM dispatch), ``ingest_wait`` (consumer blocked on the pipeline),
``step`` (one train step, the attribution window). Everything else is
freeform context.
"""

from __future__ import annotations

import contextlib
import threading
import time
from strom.utils.locks import make_lock

# instant events use dur_us = -1 so snapshot() can tell them apart without a
# second per-event field; flow events (the Chrome-trace s/t/f arrows that
# connect one request's spans across threads — ISSUE 8) ride the same slot
# with their own sentinels, keeping the hot tuple shape unchanged
_INSTANT = -1.0
_FLOW = {"s": -2.0, "t": -3.0, "f": -4.0}
_FLOW_PH = {v: k for k, v in _FLOW.items()}
# a span that bills CPU reads its thread's CPU clock on the first and then
# every CPU_LAP_SPANS-th such span of the thread, and bills the lap to that
# span: the read is a system call (6 us on a TPU v5e host VM, against
# 0.09 us for perf_counter), and a sum over many spans is the same
CPU_LAP_SPANS = 16


class EventRing:
    """Fixed-capacity ring of (ts_us, dur_us, tid, cat, name, args) tuples.

    One module-level instance (:data:`ring`) is shared process-wide, the same
    singleton shape as ``strom.utils.stats.global_stats`` — instrumentation
    sites write unconditionally and tools snapshot when asked.
    """

    def __init__(self, capacity: int = 1 << 16, *, enabled: bool = True):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self._cap = capacity
        self._slots: list[tuple | None] = [None] * capacity
        self._idx = 0          # total events ever written (monotonic)
        self._dropped = 0      # events overwritten after the first wrap
        self._lock = make_lock("ring.events")
        # span name -> [count, wall us, thread-CPU us]; never dropped
        self._totals: dict[str, list] = {}
        self._cpu_marks = threading.local()
        self._t0 = time.perf_counter()
        self.enabled = enabled

    # -- clock --------------------------------------------------------------
    def now_us(self) -> float:
        """Microseconds since ring creation (the trace's time base)."""
        return (time.perf_counter() - self._t0) * 1e6

    # -- emission -----------------------------------------------------------
    def _append(self, ev: tuple, cpu_us: float | None = None) -> None:
        """Store *ev*; a span (*cpu_us* not None) also adds to its name's
        totals under the same lock."""
        with self._lock:
            i = self._idx
            if self._slots[i % self._cap] is not None:
                self._dropped += 1
            self._slots[i % self._cap] = ev
            self._idx = i + 1
            if cpu_us is None:
                return
            t = self._totals.get(ev[4])
            if t is None:
                self._totals[ev[4]] = [1, ev[1], cpu_us]
            else:
                t[0] += 1
                t[1] += ev[1]
                t[2] += cpu_us

    def us_at(self, t: float) -> float:
        """Ring microseconds of a ``time.perf_counter()`` reading. On Linux
        that clock is ``CLOCK_MONOTONIC``, one clock for every process of
        the host, so a reading taken in a worker process maps too."""
        return (t - self._t0) * 1e6

    def complete(self, ts_us: float, dur_us: float, cat: str, name: str,
                 args: dict | None = None, cpu_us: float = 0.0,
                 tid: int | None = None) -> None:
        """Record a finished span (chrome 'X' event) and add it to its
        name's totals; *cpu_us* is the thread CPU time the site measured
        over the span, where it measures one. *tid* names the lane of a
        span measured elsewhere (a decode worker's pid); default the
        calling thread."""
        if not self.enabled:
            return
        self._append((ts_us, dur_us,
                      threading.get_ident() if tid is None else tid, cat,
                      name, args), cpu_us)

    def instant(self, name: str, cat: str = "",
                args: dict | None = None) -> None:
        """Record a point event (chrome 'i' event)."""
        if not self.enabled:
            return
        self._append((self.now_us(), _INSTANT, threading.get_ident(), cat,
                      name, args))

    def flow(self, phase: str, flow_id: int, name: str,
             cat: str = "") -> None:
        """Record a flow event (chrome 's'/'t'/'f'): consecutive events of
        one *flow_id* render as arrows connecting the spans that enclose
        them — the per-request causal chain (strom/obs/request.py)."""
        if not self.enabled:
            return
        self._append((self.now_us(), _FLOW[phase], threading.get_ident(),
                      cat, name, {"id": int(flow_id)}))

    def cpu_lap_us(self) -> float:
        """The calling thread's CPU microseconds since its previous lap
        (since it started, at its first): one ``thread_time`` read. A
        worker that laps at the end of each task bills every task its own
        CPU plus the worker's overhead since the task before."""
        now = time.thread_time()
        prev = getattr(self._cpu_marks, "t", 0.0)
        self._cpu_marks.t = now
        return (now - prev) * 1e6

    def span_cpu_us(self) -> float:
        """What a span that bills CPU adds to its totals: a
        :meth:`cpu_lap_us` on every :data:`CPU_LAP_SPANS`-th such span of
        the calling thread, else 0.0."""
        n = getattr(self._cpu_marks, "spans", 0)
        self._cpu_marks.spans = n + 1
        return 0.0 if n % CPU_LAP_SPANS else self.cpu_lap_us()

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "", args: dict | None = None, *,
             cpu: bool = False):
        """Record the with-block as one complete event (recorded even when
        the block raises — a failed gather is exactly the span you want on
        the timeline). *cpu* adds :meth:`span_cpu_us` at the block's end
        to the name's totals."""
        if not self.enabled:
            yield
            return
        t0 = self.now_us()
        try:
            yield
        finally:
            self.complete(t0, self.now_us() - t0, cat, name, args,
                          self.span_cpu_us() if cpu else 0.0)

    @contextlib.contextmanager
    def anchor(self, name: str):
        """A ``jax.profiler.TraceAnnotation`` and a ring span of the same
        *name* around the with-block, opened and closed at the same
        instants: the pair a profiler trace and a ring dump share. Open
        it around a profiled window; the line through its start and end
        pairs maps ring microseconds onto the profiler's clock."""
        from jax.profiler import TraceAnnotation

        # the ring span opens just after the annotation and closes just
        # before it: the two differ by the clock reads between them
        with TraceAnnotation(name), self.span(name, "anchor"):
            yield

    # -- inspection ---------------------------------------------------------
    def totals(self) -> dict[str, tuple[int, float, float]]:
        """Per span name: (count, wall us, thread-CPU us) of every span
        recorded since the ring was made or cleared, wrapped or not."""
        with self._lock:
            return {n: (t[0], t[1], t[2]) for n, t in self._totals.items()}

    @property
    def events_dropped(self) -> int:
        with self._lock:
            return self._dropped

    @property
    def events_written(self) -> int:
        """Total events ever recorded (monotonic, survives wraps): the
        flight recorder's ring-pressure signal — a delta between two
        samples is the event rate, where ``len(ring)`` saturates at
        capacity the moment the ring wraps."""
        with self._lock:
            return self._idx

    @property
    def high_water(self) -> int:
        """Max retained occupancy so far (== capacity once wrapped): how
        close the ring has come to dropping history."""
        with self._lock:
            return min(self._idx, self._cap)

    @property
    def capacity(self) -> int:
        return self._cap

    def __len__(self) -> int:
        with self._lock:
            return min(self._idx, self._cap)

    def snapshot(self) -> list[dict]:
        """The retained events as dicts, oldest first (ts-sorted within the
        retained window). The lock is held only for a C-level list copy —
        a scrape of a full ring must not stall every hot-path writer for
        the duration of 64Ki dict constructions."""
        with self._lock:
            slots = list(self._slots)
            idx = self._idx
            dropped = self._dropped
        n = min(idx, self._cap)
        evs = [slots[i % self._cap] for i in range(idx - n, idx)]
        out = []
        for ev in evs:
            if ev is None:  # cleared ring / not yet wrapped
                continue
            ts, dur, tid, cat, name, args = ev
            if dur in _FLOW_PH:
                d = {"ts_us": ts, "tid": tid, "cat": cat, "name": name,
                     "ph": _FLOW_PH[dur],
                     "id": (args or {}).get("id", 0)}
                out.append(d)
                continue
            d = {"ts_us": ts, "tid": tid, "cat": cat, "name": name,
                 "ph": "i" if dur == _INSTANT else "X"}
            if dur != _INSTANT:
                d["dur_us"] = dur
            if args:
                d["args"] = args
            out.append(d)
        # completion order == exit order for spans; sort by START time so
        # consumers see a timeline (nested spans exit before their parents)
        out.sort(key=lambda e: e["ts_us"])
        if dropped:
            out.insert(0, {"ts_us": out[0]["ts_us"] if out else 0.0,
                           "tid": 0, "cat": "meta", "name": "events_dropped",
                           "ph": "i", "args": {"count": dropped}})
        return out

    def clear(self) -> None:
        with self._lock:
            self._slots = [None] * self._cap
            self._idx = 0
            self._dropped = 0
            self._totals = {}


# the process-wide ring every instrumentation site writes to
ring = EventRing()
