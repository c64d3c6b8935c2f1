"""Per-tenant SLO engine: declarative targets + multi-window burn rates.

The scheduler (PR 7) can bound a tenant's queue wait; nothing so far can
say whether a tenant is MEETING its objective or how fast it is spending
its error budget. This module is the standard SRE shape, kept in-process:

- **Targets** are declarative per tenant (:class:`SloTarget`): a request
  is *good* when its wall latency and accumulated scheduler queue wait
  both sit under the target thresholds and it didn't error; the objective
  is "at least ``objective_pct`` % of requests good".
- **Burn rate** = (observed bad fraction) / (allowed bad fraction),
  computed over TWO sliding windows — fast (default 5 min) and slow
  (default 1 h), bucketed at ``bucket_s`` granularity so memory is a few
  hundred ints per tenant. A tenant is **burning** when BOTH windows
  exceed the alert threshold: the fast window catches the page-worthy
  spike, the slow window keeps a brief blip from paging (the classic
  multi-window multi-burn-rate rule).
- Surfaced three ways: ``slo_*`` gauges in each tenant's telemetry scope
  (labeled on /metrics, aggregate = worst tenant), the live server's
  ``/slo`` route (:meth:`SloEngine.report`), and a scheduler hook that
  flags burning tenants on ``/tenants`` rows.

Requests feed the engine through the request-tracing observer hook
(:func:`strom.obs.request.add_observer` — StromContext wires one per
context); the clock is injectable so window math is unit-testable.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from strom.utils.locks import make_lock

# per-tenant gauge names the engine writes into tenant scopes (labeled on
# /metrics) — single-sourced for the lint, same contract as FLIGHT_FIELDS
SLO_FIELDS = (
    "slo_burn_fast",
    "slo_burn_slow",
    "slo_good_pct",
    "slo_burning",
)

# columns of the checked-in pre-chip benchmark records that
# tools/compare_rounds.py reads for its "request latency / SLO" section
SLO_BENCH_FIELDS = (
    "req_lat_p50_us",
    "req_lat_p99_us",
    "slo_ok",
)


@dataclasses.dataclass(frozen=True)
class SloTarget:
    """Declarative per-tenant objective. Defaults are deliberately loose —
    an unconfigured tenant should burn only when something is genuinely
    wrong, not because a default guessed its hardware."""

    gather_p99_us: float = 2_000_000.0   # request wall above this = bad
    queue_wait_p99_us: float = 1_000_000.0  # accumulated sched wait cap
    objective_pct: float = 99.0          # % of requests that must be good
    goodput_pct: float = 0.0             # min stall-attribution goodput
                                         # (0 = not enforced): informational
                                         # — report() compares it against
                                         # the context's goodput_fn and
                                         # flags goodput_ok per tenant

    @property
    def budget_frac(self) -> float:
        return max(1.0 - self.objective_pct / 100.0, 1e-6)


class SloEngine:
    """Sliding-window good/bad accounting per tenant."""

    #: burn-rate alert threshold (both windows must exceed it): 1.0 means
    #: "spending budget exactly as fast as allowed"; >1 is overspend
    BURN_THRESHOLD = 1.0

    def __init__(self, *, fast_s: float = 300.0, slow_s: float = 3600.0,
                 bucket_s: float = 10.0, clock=time.monotonic,
                 default_target: "SloTarget | None" = None,
                 goodput_fn=None):
        self.fast_s = float(fast_s)
        self.slow_s = float(slow_s)
        self.bucket_s = max(float(bucket_s), 0.1)
        self._clock = clock
        self._default = default_target or SloTarget()
        # optional: a callable returning the context's current stall-
        # attribution goodput_pct (None = unknown) for goodput targets
        self._goodput_fn = goodput_fn
        self._targets: dict[str, SloTarget] = {}
        self._lock = make_lock("obs.slo")
        # tenant -> deque of [bucket_index, good, bad], oldest first,
        # trimmed to the slow window
        self._buckets: dict[str, deque] = {}

    # -- configuration -------------------------------------------------------
    def set_target(self, tenant: str, **kw) -> SloTarget:
        """Override (or refine) one tenant's target; unknown kwargs raise
        (a typo'd threshold silently defaulting is an unmonitored SLO)."""
        with self._lock:
            base = self._targets.get(tenant, self._default)
            t = dataclasses.replace(base, **kw)
            self._targets[tenant] = t
            return t

    def target(self, tenant: str) -> SloTarget:
        with self._lock:
            return self._targets.get(tenant, self._default)

    # -- ingest --------------------------------------------------------------
    def observe(self, tenant: str, latency_us: float, *,
                queue_wait_us: float = 0.0, error: bool = False) -> None:
        t = self.target(tenant)
        bad = (error or latency_us > t.gather_p99_us
               or queue_wait_us > t.queue_wait_p99_us)
        bi = int(self._clock() / self.bucket_s)
        with self._lock:
            dq = self._buckets.get(tenant)
            if dq is None:
                dq = self._buckets[tenant] = deque()
            if not dq or dq[-1][0] != bi:
                dq.append([bi, 0, 0])
                self._trim_locked(dq, bi)
            dq[-1][1 + int(bad)] += 1

    def observe_request(self, req) -> None:
        """The request-tracing observer entry point (wired per context).
        Only data-path requests count against the gather-latency
        objective: a "step" request's wall is mostly consumer compute."""
        if req.kind == "step":
            return
        self.observe(req.tenant, req.dur_us,
                     queue_wait_us=req.queue_wait_us,
                     error=req.error is not None)

    def _trim_locked(self, dq: deque, now_bi: int) -> None:
        horizon = now_bi - int(self.slow_s / self.bucket_s) - 1
        while dq and dq[0][0] < horizon:
            dq.popleft()

    # -- window math ---------------------------------------------------------
    def _window_locked(self, dq: deque, window_s: float, now_bi: int
                       ) -> tuple[int, int]:
        lo = now_bi - int(window_s / self.bucket_s)
        good = bad = 0
        for bi, g, b in reversed(dq):
            if bi < lo:
                break
            good += g
            bad += b
        return good, bad

    def burn_rates(self, tenant: str) -> tuple[float, float]:
        """(fast-window, slow-window) burn rates: bad-fraction over the
        window divided by the tenant's error budget. 0.0 = no traffic or
        no badness."""
        t = self.target(tenant)
        bi = int(self._clock() / self.bucket_s)
        with self._lock:
            dq = self._buckets.get(tenant)
            if not dq:
                return 0.0, 0.0
            out = []
            for w in (self.fast_s, self.slow_s):
                good, bad = self._window_locked(dq, w, bi)
                n = good + bad
                out.append((bad / n / t.budget_frac) if n else 0.0)
        return out[0], out[1]

    def burning(self, tenant: str) -> bool:
        fast, slow = self.burn_rates(tenant)
        return fast > self.BURN_THRESHOLD and slow > self.BURN_THRESHOLD

    def tenants(self) -> list[str]:
        with self._lock:
            return sorted(set(self._buckets) | set(self._targets))

    # -- surfacing -----------------------------------------------------------
    def report(self) -> dict:
        """The ``/slo`` route body: one row per observed tenant, and the
        SLO_FIELDS gauges refreshed into each tenant's telemetry scope so
        /metrics carries the same numbers as labeled series."""
        from strom.utils.stats import global_stats

        bi = int(self._clock() / self.bucket_s)
        goodput = None
        if self._goodput_fn is not None:
            try:
                goodput = self._goodput_fn()
            # stromlint: ignore[swallowed-exceptions] -- None is the
            # documented 'goodput unknown' report state; the fn rides
            # ctx.stats(), which a closing context may legally refuse
            except Exception:
                goodput = None
        rows: dict[str, dict] = {}
        worst_fast = worst_slow = 0.0
        worst_good_pct = 100.0
        any_burning = False
        for name in self.tenants():
            t = self.target(name)
            fast, slow = self.burn_rates(name)
            with self._lock:
                dq = self._buckets.get(name) or ()
                good, bad = self._window_locked(deque(dq), self.slow_s, bi)
            n = good + bad
            good_pct = round(100.0 * good / n, 3) if n else 100.0
            burning = fast > self.BURN_THRESHOLD and slow > self.BURN_THRESHOLD
            rows[name] = {
                "target": dataclasses.asdict(t),
                "requests": n,
                "bad": bad,
                "slo_good_pct": good_pct,
                "slo_burn_fast": round(fast, 4),
                "slo_burn_slow": round(slow, 4),
                "slo_burning": burning,
                "goodput_pct": goodput,
                "goodput_ok": (goodput is None or t.goodput_pct <= 0
                               or goodput >= t.goodput_pct),
            }
            scope = global_stats.scoped(
                tenant=name if name != "default" else None)
            scope.set_gauge("slo_burn_fast", round(fast, 4))
            scope.set_gauge("slo_burn_slow", round(slow, 4))
            scope.set_gauge("slo_good_pct", good_pct)
            scope.set_gauge("slo_burning", int(burning))
            worst_fast = max(worst_fast, fast)
            worst_slow = max(worst_slow, slow)
            worst_good_pct = min(worst_good_pct, good_pct)
            any_burning = any_burning or burning
        # the unlabeled aggregate must be the WORST tenant, not whichever
        # tenant's scoped write-through happened last — an alert on the
        # unlabeled slo_burning gauge must never miss a burning tenant
        if rows:
            global_stats.set_gauge("slo_burn_fast", round(worst_fast, 4))
            global_stats.set_gauge("slo_burn_slow", round(worst_slow, 4))
            global_stats.set_gauge("slo_good_pct", worst_good_pct)
            global_stats.set_gauge("slo_burning", int(any_burning))
        return {"windows_s": {"fast": self.fast_s, "slow": self.slow_s},
                "burn_threshold": self.BURN_THRESHOLD,
                "tenants": rows}

    def ok(self) -> bool:
        """True when no tenant is burning (the bench's ``slo_ok`` column)."""
        return not any(self.burning(t) for t in self.tenants())

    def stats(self) -> dict:
        """Flat leaves for the ``slo`` section of ``StromContext.stats()``."""
        names = self.tenants()
        burns = [self.burn_rates(t) for t in names]
        return {
            "slo_tenants": len(names),
            "slo_tenants_burning": sum(int(f > self.BURN_THRESHOLD
                                           and s > self.BURN_THRESHOLD)
                                       for f, s in burns),
            "slo_worst_burn_fast": round(max((f for f, _ in burns),
                                             default=0.0), 4),
            "slo_worst_burn_slow": round(max((s for _, s in burns),
                                             default=0.0), 4),
        }
