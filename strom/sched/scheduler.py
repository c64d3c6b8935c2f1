"""Shared I/O scheduler: many tenants, one engine fleet (ISSUE 7 tentpole).

Before this module, a `StromContext` assumed one consumer: every gather
took the delivery engine lock for its whole duration (`StreamingGather`
held it construction→finish), so a second pipeline's 2KB metadata read
queued behind a first pipeline's 100MB epoch gather. The paper frames the
DMA engine as a *shared, kernel-managed* resource — per-process locks are
exactly what it replaces — and PR 5's async `submit_vectored`/`poll` plus
PR 6's tenant-labeled telemetry are the substrate a shared arbiter needs.

:class:`IoScheduler` is that arbiter. The per-transfer engine lock stops
existing for scheduled contexts; in its place:

- **Per-tenant queues, priority classes.** Tenants register (or are
  auto-registered on first use); each grant request enters its tenant's
  FIFO. Classes are strict among budget-ready work — ``interactive`` >
  ``training`` > ``background`` — so a live client's op never waits out
  training backlog, and readahead (always ``background``) never delays
  either. A class whose every queued tenant is budget-throttled yields
  the engine to lower classes rather than idling it (work conservation);
  it is picked first again the moment its budget refills.

- **Weighted fair drain (deficit round-robin over queued ops).** Within
  a class, the tenant furthest *behind* its weighted fair share drains
  next: every grant charges ``nbytes / weight`` of virtual service time,
  and ``_pick_locked`` always picks the queued tenant with the minimum.
  A newly-active tenant joins at the current service baseline (no
  infinite catch-up), which is DRR with byte quanta in its
  limit: a weight-2 tenant gets 2 bytes drained for every 1 of a
  weight-1 tenant, and a light tenant's deficit keeps it at the head.

- **Engine queue-depth slots as the shared currency.** Exclusive grants
  hand the engine's whole in-flight window to one request at a time, and
  the delivery layer splits big gathers into slices of a few in-flight
  budgets (``sched_slice_bytes``, see :meth:`read_chunks`) so ownership
  turns over every few queue-depth windows — a greedy tenant's gather is
  preemptible at slice boundaries, bounding any other tenant's queue
  wait at ~one slice instead of one epoch. Engines that already
  arbitrate internally (``concurrent_gathers``: the multi-ring engine's
  per-ring locks) keep their concurrency: grants there are
  non-exclusive — budgets and accounting still apply, queueing doesn't.

- **Budgets + admission control** (:mod:`strom.sched.budget`): byte/IOPS
  token buckets peeked while picking (a throttled tenant is skipped, not
  billed) and taken at grant; slab-pool admission queues background
  allocations while the pool is past the high-water mark.

Observability: every grant lands ``sched_granted_ops/bytes`` and a
``sched_queue_wait_us`` histogram in the tenant's scope (labeled on
/metrics, PR 6) plus the unlabeled aggregate; ``sched_throttle_waits``
counts throttled grant episodes (one per grant that waited on budget
refill); the live server's ``/tenants`` route renders
:meth:`tenants_info`.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Callable, Sequence

from strom.engine.base import DeadlineExceeded
from strom.utils.locks import make_condition
from strom.sched.budget import AdmissionGate
from strom.sched.tenant import PRIORITIES, PRIORITY_ORDER, Tenant

# per-tenant column suffixes (prefixed mt_<tenant>_) of the checked-in
# pre-chip benchmark records that tools/compare_rounds.py reads for its
# "multi-tenant" section.
SCHED_FIELDS = (
    "items_per_s",
    "vs_solo",
    "sched_queue_wait_p50_us",
    "sched_queue_wait_p99_us",
    "sched_granted_ops",
    "sched_granted_bytes",
    "sched_throttle_waits",
    "engine_op_lat_p99_us",
)

_DEFAULT_TENANT = "default"


class _Waiter:
    """One queued grant request (scheduler-lock-owned)."""

    __slots__ = ("tenant", "nbytes", "prio", "enq_t", "granted", "wait_s",
                 "throttled", "owner_ident")

    def __init__(self, tenant: Tenant, nbytes: int, prio: int, enq_t: float):
        self.tenant = tenant
        self.nbytes = nbytes
        self.prio = prio
        self.enq_t = enq_t
        self.granted = False
        self.wait_s = 0.0
        # one sched_throttle_waits tick per throttled grant EPISODE: the
        # flag keeps repeated dispatch passes / poll ticks over the same
        # still-throttled head-of-queue from re-counting it
        self.throttled = False
        # thread that ACQUIRED the grant (release may land on another — a
        # streamed gather releases at drain on the pump side): the key the
        # held_by_me() re-entrancy probe charges/refunds (ISSUE 14)
        self.owner_ident = 0


class IoScheduler:
    """Fair arbiter over one engine's transfer path.

    *clock* / throttle waiting are injectable for deterministic tests.
    """

    def __init__(self, engine, config, *, pool=None, scope=None,
                 clock: Callable[[], float] = time.monotonic):
        from strom.utils.stats import global_stats

        self.engine = engine
        self.config = config
        self._scope = scope if scope is not None else global_stats
        self._clock = clock
        # engines with internal per-ring arbitration keep their concurrency:
        # grants are non-exclusive there (budgets/accounting still apply)
        self.exclusive = not getattr(engine, "concurrent_gathers", False)
        # live slice-size override (ISSUE 16 autotuner); None = config/auto
        self.slice_bytes_override: int | None = None
        self._cond = make_condition("sched.arbiter")
        self._tenants: dict[str, Tenant] = {}
        self._current: _Waiter | None = None
        # grants outstanding per ACQUIRING thread ident (ISSUE 14): the
        # re-entrancy probe the engine-routed spill I/O consults before
        # queueing — a thread already holding (or working under) a grant
        # must never enqueue a nested one (self-deadlock on an exclusive
        # engine). Cross-thread releases refund the acquirer's entry.
        self._held_by: dict[int, int] = {}
        # service baseline: a tenant going active joins at this vtime, so
        # an idle tenant can't bank unbounded credit (classic WFQ rule)
        self._vbase = 0.0
        self.admission = AdmissionGate(
            pool, getattr(config, "sched_high_water", 0.9),
            scope=self._scope, clock=clock)
        # SLO hook (ISSUE 8): a callable name -> bool set by the owning
        # context (ctx.slo.burning) so /tenants rows flag tenants that are
        # burning their error budget — the scheduler stays SLO-agnostic
        self.slo_hook: "Callable[[str], bool] | None" = None
        # resilience hook (ISSUE 9): a callable () -> dict set by the
        # owning context (ctx.resilience.stats) so /tenants shows the
        # breaker/failover degraded state next to the queue rows — the
        # scheduler stays failure-policy-agnostic
        self.resilience_info: "Callable[[], dict] | None" = None
        self._default = self.register(_DEFAULT_TENANT, _label=False)

    # -- tenant registry ----------------------------------------------------
    def register(self, name: str, *, priority: str = "training",
                 weight: int = 1, byte_rate: float = 0,
                 byte_burst: float | None = None, iops: float = 0,
                 hot_cache_bytes: int = 0, _label: bool = True) -> Tenant:
        """Register (or fetch) tenant *name*. Re-registering an existing
        name returns the live handle unchanged — queue state and budget
        balances survive, so a daemon client reconnecting can't zero a
        tenant's debt. ``_label=False`` keeps the context's own scope
        (the default tenant: single-tenant metrics stay unlabeled)."""
        with self._cond:
            t = self._tenants.get(name)
            if t is not None:
                return t
            scope = self._scope.scoped(tenant=name) if _label else self._scope
            t = Tenant(name, priority=priority, weight=weight, scope=scope,
                       byte_rate=byte_rate, byte_burst=byte_burst, iops=iops,
                       hot_cache_bytes=hot_cache_bytes, clock=self._clock)
            t.vtime = self._vbase
            self._tenants[name] = t
            return t

    def is_registered(self, name: str) -> bool:
        with self._cond:
            return name in self._tenants

    def tenant(self, name: str | None = None) -> Tenant:
        if name is None:
            return self._default
        with self._cond:
            t = self._tenants.get(name)
        # auto-register on first use: a pipeline labeled tenant="t7" just
        # works (default class/weight, no budgets); explicit register()
        # beforehand is how budgets/priorities are customized
        return t if t is not None else self.register(name)

    def resolve(self, tenant: "Tenant | str | None") -> Tenant:
        if isinstance(tenant, Tenant):
            return tenant
        return self.tenant(tenant)

    def tenants_info(self) -> dict:
        """{name: row} for every registered tenant plus the admission
        gate's state — the /tenants route body."""
        with self._cond:
            tenants = list(self._tenants.values())
        rows = {}
        for t in tenants:
            row = t.info()
            if self.slo_hook is not None:
                # burn-rate flag from the SLO engine (ISSUE 8): a throttled
                # / slow tenant is visible where the operator already looks
                with contextlib.suppress(Exception):
                    row["slo_burning"] = bool(self.slo_hook(t.name))
            rows[t.name] = row
        out = {"tenants": rows,
               "admission": self.admission.state(),
               "exclusive": self.exclusive,
               "engine": getattr(self.engine, "name", "?")}
        if self.resilience_info is not None:
            # degraded-state visibility (ISSUE 9): breaker state, failover
            # availability and hedge threshold, on the page the operator
            # already watches for tenant health
            with contextlib.suppress(Exception):
                out["resilience"] = self.resilience_info()
        return out

    # -- the fair-drain core ------------------------------------------------
    def _enqueue_locked(self, w: _Waiter) -> None:
        """Append a waiter to its tenant's queue. A tenant (re)activating
        from idle joins at the current service baseline — idle time banks
        no credit (the WFQ start-time rule): deficit accrues only while
        queued, so a long-idle tenant can't return and monopolize."""
        t = w.tenant
        if not t.queue and not t.active and t.vtime < self._vbase:
            t.vtime = self._vbase
        t.queue.append(w)
        t.queued_bytes += w.nbytes

    def _pick_locked(self) -> tuple[_Waiter | None, float | None]:
        """(next grantable waiter, earliest budget-ready delay). Strict
        priority between classes; min virtual service time (weighted fair /
        deficit) within one. Budgets are PEEKED here — a throttled tenant
        is skipped this pass and its ready time bounds the retry wait —
        and taken only by the caller for the waiter actually granted."""
        min_delay: float | None = None
        for cls in range(len(PRIORITIES)):
            cand = [t for t in self._tenants.values()
                    if t.queue and t.queue[0].prio == cls]
            # furthest behind its weighted share first
            for t in sorted(cand, key=lambda t: (t.vtime, t.name)):
                w = t.queue[0]
                d = max(t.byte_bucket.peek(w.nbytes),
                        t.iops_bucket.peek(1))
                if d > 0:
                    self._note_throttled_locked(w)
                    min_delay = d if min_delay is None else min(min_delay, d)
                    continue
                return w, min_delay
            # every queued tenant of this class is budget-throttled: fall
            # through to the next class. Strict priority orders RUNNABLE
            # work; a budget-exhausted class must not idle the engine while
            # ready lower-class work queues (work conservation). min_delay
            # bounds the dispatch retry, so the moment the budget refills
            # the higher class is picked first again.
        return None, min_delay

    @staticmethod
    def _note_throttled_locked(w: _Waiter) -> None:
        """Count a throttled grant episode exactly once per waiter —
        sched_throttle_waits is a bench column (SCHED_FIELDS) compared
        round-over-round, so it must measure budget pressure, not how many
        dispatch passes happened to observe it."""
        if w.throttled:
            return
        w.throttled = True
        w.tenant.throttle_waits += 1
        w.tenant.scope.add("sched_throttle_waits")

    def _commit_grant_locked(self, w: _Waiter) -> None:
        """Grant bookkeeping shared by the exclusive dispatcher and the
        non-exclusive (internally-arbitrated engine) path: dequeue, take
        the budgets peeked earlier, charge weighted virtual service (the
        global baseline tracks the max so newly-active tenants join behind
        nobody), count."""
        t = w.tenant
        t.queue.popleft()
        t.queued_bytes -= w.nbytes
        t.byte_bucket.take(w.nbytes)
        t.iops_bucket.take(1)
        t.vtime += w.nbytes / t.weight
        if t.vtime > self._vbase:
            self._vbase = t.vtime
        t.active += 1
        t.granted_ops += 1
        t.granted_bytes += w.nbytes
        w.granted = True

    def _dispatch_locked(self) -> float | None:
        """Grant the next waiter if the engine is free. Returns the retry
        delay when everything grantable is budget-throttled."""
        if self._current is not None:
            return None
        w, delay = self._pick_locked()
        if w is None:
            return delay
        self._commit_grant_locked(w)
        self._current = w
        self._cond.notify_all()
        return None

    def acquire(self, tenant: "Tenant | str | None" = None,
                nbytes: int = 0, *, priority: str | None = None) -> _Waiter:
        """Queue for (and block until) an engine grant. Returns the waiter
        handle to pass to :meth:`release`. Non-exclusive engines grant
        immediately (budgets still charged, waits still possible)."""
        from strom.obs import request as _request
        from strom.obs.events import ring as _ring

        t = self.resolve(tenant)
        prio = PRIORITY_ORDER[priority] if priority is not None \
            else PRIORITY_ORDER[t.priority]
        w = _Waiter(t, max(int(nbytes), 0), prio, self._clock())
        enq_us = _ring.now_us()
        # deadline propagation (ISSUE 9): a queue wait that cannot grant
        # before the request deadline dequeues and fails fast — a gather
        # nobody is still waiting for must not consume a grant. Deadlines
        # ride time.monotonic (the engine's clock), not the injectable
        # scheduler clock — fake-clock tests don't mint deadlines.
        req0 = _request.current()
        req_deadline = getattr(req0, "deadline", None) \
            if req0 is not None else None

        def _expired() -> bool:
            return req_deadline is not None \
                and time.monotonic() >= req_deadline

        def _abort_locked() -> None:
            try:
                t.queue.remove(w)
                t.queued_bytes -= w.nbytes
            except ValueError:
                pass
            t.scope.set_gauge("sched_queue_depth", len(t.queue))
            t.scope.add("deadline_exceeded")
            self._cond.notify_all()

        with self._cond:
            self._enqueue_locked(w)
            t.scope.set_gauge("sched_queue_depth", len(t.queue))
            if not self.exclusive:
                # internal-arbitration engines: charge budgets in queue
                # order but don't serialize — budget throttles still wait
                while t.queue[0] is not w or \
                        max(t.byte_bucket.peek(w.nbytes),
                            t.iops_bucket.peek(1)) > 0:
                    if _expired():
                        _abort_locked()
                        raise DeadlineExceeded(
                            f"queued on tenant '{t.name}' (throttled)")
                    if t.queue[0] is w:
                        d = max(t.byte_bucket.peek(w.nbytes),
                                t.iops_bucket.peek(1))
                        self._note_throttled_locked(w)
                        self._cond.wait(min(d, 0.05))
                    else:
                        self._cond.wait(0.01)
                self._commit_grant_locked(w)
                self._cond.notify_all()
            else:
                delay = self._dispatch_locked()
                while self._current is not w:
                    if _expired():
                        _abort_locked()
                        raise DeadlineExceeded(
                            f"queued on tenant '{t.name}' behind "
                            f"{len(t.queue)} op(s)")
                    wait_s = delay
                    if req_deadline is not None:
                        left = max(req_deadline - time.monotonic(), 0.001)
                        wait_s = left if wait_s is None \
                            else min(wait_s, left)
                    self._cond.wait(wait_s)
                    delay = self._dispatch_locked()
            t.scope.set_gauge("sched_queue_depth", len(t.queue))
            w.owner_ident = threading.get_ident()
            self._held_by[w.owner_ident] = \
                self._held_by.get(w.owner_ident, 0) + 1
        w.wait_s = max(self._clock() - w.enq_t, 0.0)
        t.scope.observe_us("sched_queue_wait", w.wait_s * 1e6)
        t.scope.add("sched_granted_ops")
        if w.nbytes:
            t.scope.add("sched_granted_bytes", w.nbytes)
        # causal request tracing (ISSUE 8): the queue wait becomes a span
        # on the request's lane (throttled verdict included — the exemplar
        # store and SLO engine key off it), billed to the request that
        # queued, not just the tenant aggregate
        req = _request.current()
        if req is not None:
            req.note_queue_wait(w.wait_s * 1e6, throttled=w.throttled)
            req.record("sched.queue", "sched", enq_us,
                       _ring.now_us() - enq_us,
                       args={"tenant": t.name, "bytes": w.nbytes,
                             "throttled": w.throttled},
                       parent=req.parent_of())
        if self.exclusive and t.scope is not self._scope:
            # exclusive ownership means no concurrent submitter: steer the
            # engine's per-op accounting (engine_op_lat_us histogram,
            # engine_inflight gauge — PR 6) through the TENANT's scope for
            # the grant, so per-tenant engine latency lands labeled on
            # /metrics with zero per-op plumbing; restored at release
            self.engine.set_scope(t.scope)
        return w

    def release(self, w: _Waiter) -> None:
        if self.exclusive:
            self.engine.set_scope(self._scope)
        with self._cond:
            w.tenant.active -= 1
            left = self._held_by.get(w.owner_ident, 0) - 1
            if left > 0:
                self._held_by[w.owner_ident] = left
            else:
                self._held_by.pop(w.owner_ident, None)
            if self.exclusive and self._current is w:
                self._current = None
                self._dispatch_locked()
            self._cond.notify_all()

    # -- re-entrancy probes (ISSUE 14: engine-routed spill I/O) -------------
    def held_by_me(self) -> bool:
        """True when the CALLING thread acquired a grant that is still
        outstanding — a nested enqueue from it would self-deadlock on an
        exclusive engine."""
        with self._cond:
            return self._held_by.get(threading.get_ident(), 0) > 0

    def engine_idle(self) -> bool:
        """Advisory: no exclusive grant outstanding right now. The
        engine-routed spill WRITE path requires it — a demote fired from a
        mid-gather admission (the streamed pump thread, whose gather's
        grant is held by ANOTHER thread) must fall back to the buffered fd
        rather than queue behind a grant its own progress is supposed to
        release. Races are safe in both directions: a stale True just
        queues normally; a stale False takes the fallback."""
        if not self.exclusive:
            return True
        return self._current is None

    @contextlib.contextmanager
    def grant(self, tenant: "Tenant | str | None" = None, nbytes: int = 0,
              *, priority: str | None = None):
        """``with sched.grant(tenant, nbytes):`` — the scheduler-era
        spelling of ``with ctx._engine_lock:``."""
        from strom.obs import request as _request
        from strom.obs.events import ring as _ring

        # request AND parent captured at ENTRY: the exit may run on another
        # thread (a streamed gather releases at drain, on the pump side)
        # where the contextvar isn't set and parent_of() would read the
        # wrong thread's open-span stack
        req = _request.current()
        parent = req.parent_of() if req is not None else None
        w = self.acquire(tenant, nbytes, priority=priority)
        grant_us = _ring.now_us()
        try:
            yield w
        finally:
            self.release(w)
            if req is not None:
                # the engine-ownership window on the request's lane: how
                # long this request held (its share of) the arbiter
                req.record("sched.grant", "sched", grant_us,
                           _ring.now_us() - grant_us,
                           args={"tenant": w.tenant.name,
                                 "bytes": w.nbytes},
                           parent=parent)

    # -- sliced gather execution (the delivery hot path) --------------------
    def _slice_bytes(self) -> int:
        # live-tunable override (ISSUE 16 autotuner): the config is frozen,
        # so the tuner writes here; None defers to config/auto. Read fresh
        # per call — a move takes effect on the next slice boundary.
        ov = getattr(self, "slice_bytes_override", None)
        if ov is not None and ov >= 0:
            return int(ov)
        sb = getattr(self.config, "sched_slice_bytes", -1)
        if sb >= 0:
            return sb
        # auto: a few in-flight budgets per grant — deep enough that the
        # queue-depth pipeline amortizes the grant handoff, shallow enough
        # that engine ownership turns over at interactive timescales
        return 4 * self.config.queue_depth * self.config.block_size

    def iter_slices(self, chunks: Sequence[tuple[int, int, int, int]]):
        """Split a gather's chunk list into slices of ~``sched_slice_bytes``
        (grant granularity). Chunk order is preserved and chunks are never
        split, so the engine sees the exact ops the plan produced — only
        the lock-ownership boundaries move."""
        limit = self._slice_bytes()
        if limit <= 0:
            yield list(chunks)
            return
        batch: list[tuple[int, int, int, int]] = []
        b = 0
        for c in chunks:
            batch.append(c)
            b += c[3]
            if b >= limit:
                yield batch
                batch, b = [], 0
        if batch:
            yield batch

    def read_chunks(self, chunks: Sequence[tuple[int, int, int, int]],
                    dest, *, tenant: "Tenant | str | None" = None,
                    retries: int = 1, priority: str | None = None) -> int:
        """Execute a planned gather under fair scheduling: one engine
        grant per slice, so a concurrent tenant's op queues behind at most
        ~``sched_slice_bytes`` of this gather instead of all of it.
        Byte-identical to ``engine.read_vectored(chunks, dest)`` (slices
        preserve chunk order; dest ranges are disjoint)."""
        from strom.obs import request as _request

        t = self.resolve(tenant)
        req = _request.current()
        req_deadline = getattr(req, "deadline", None) \
            if req is not None else None
        total = 0
        for si, sl in enumerate(self.iter_slices(chunks)):
            if req_deadline is not None \
                    and time.monotonic() >= req_deadline:
                # deadline between slices (ISSUE 9): the gather stops at a
                # slice boundary — it is never more than ~one slice late
                # past its deadline, and the engine is handed straight to
                # the next tenant in the fair drain
                t.scope.add("deadline_exceeded")
                raise DeadlineExceeded(
                    f"gather stopped at slice {si} "
                    f"({total} bytes landed)")
            nbytes = sum(ln for (_, _, _, ln) in sl)
            with self.grant(t, nbytes, priority=priority), \
                    _request.span("engine.slice", cat="read",
                                  args={"slice": si, "ops": len(sl),
                                        "bytes": nbytes}):
                total += self.engine.read_vectored(sl, dest, retries=retries)
        return total

    def write_chunks(self, chunks: Sequence[tuple[int, int, int, int]],
                     src, *, tenant: "Tenant | str | None" = None,
                     retries: int = 1, priority: str | None = None) -> int:
        """Write twin of :meth:`read_chunks` (ISSUE 13): execute a planned
        scatter — (file_index, file_offset, src_offset, length) chunks out
        of *src* — under the same fair scheduling. One grant per slice, so
        a checkpoint save's multi-GiB write stream is preemptible at slice
        boundaries exactly like an epoch gather: a concurrent tenant's read
        queues behind at most ~``sched_slice_bytes`` of it. Budgets and
        priorities apply unchanged (bytes are bytes to the token buckets,
        whichever direction they flow)."""
        from strom.obs import request as _request

        t = self.resolve(tenant)
        req = _request.current()
        req_deadline = getattr(req, "deadline", None) \
            if req is not None else None
        total = 0
        for si, sl in enumerate(self.iter_slices(chunks)):
            if req_deadline is not None \
                    and time.monotonic() >= req_deadline:
                t.scope.add("deadline_exceeded")
                raise DeadlineExceeded(
                    f"write stopped at slice {si} ({total} bytes landed)")
            nbytes = sum(ln for (_, _, _, ln) in sl)
            with self.grant(t, nbytes, priority=priority), \
                    _request.span("engine.slice", cat="write",
                                  args={"slice": si, "ops": len(sl),
                                        "bytes": nbytes}):
                total += self.engine.write_vectored(sl, src, retries=retries)
        return total

    # -- drain (daemon shutdown / tenant teardown) --------------------------
    def drain(self, tenant: "Tenant | str | None" = None,
              timeout_s: float = 30.0) -> bool:
        """Wait until *tenant* has no queued requests and no active
        grants. True when drained, False on timeout."""
        t = self.resolve(tenant)
        deadline = self._clock() + timeout_s
        with self._cond:
            while t.queue or t.active:
                left = deadline - self._clock()
                if left <= 0:
                    return False
                self._cond.wait(min(left, 0.05))
        return True

    def drain_all(self, timeout_s: float = 30.0) -> list[str]:
        """Drain every registered tenant; returns the names that did NOT
        drain in time (empty = clean). The daemon's graceful-shutdown
        path runs this before the flight recorder's handler chain."""
        with self._cond:
            names = list(self._tenants)
        deadline = self._clock() + timeout_s
        stuck = []
        for name in names:
            left = max(deadline - self._clock(), 0.01)
            if not self.drain(name, timeout_s=left):
                stuck.append(name)
        return stuck

    def stats(self) -> dict:
        """Flat numeric leaves for the ``sched`` section of
        ``StromContext.stats()`` (→ /metrics via sections_prometheus)."""
        with self._cond:
            tenants = list(self._tenants.values())
        return {
            "sched_tenants": len(tenants),
            "sched_queued_ops": sum(len(t.queue) for t in tenants),
            "sched_queued_bytes": sum(t.queued_bytes for t in tenants),
            "sched_active_grants": sum(t.active for t in tenants),
            "sched_granted_ops": sum(t.granted_ops for t in tenants),
            "sched_granted_bytes": sum(t.granted_bytes for t in tenants),
            "sched_throttle_waits": sum(t.throttle_waits for t in tenants),
            "sched_exclusive": self.exclusive,
            "slab_pool_admission_waits": self.admission.waits,
        }
