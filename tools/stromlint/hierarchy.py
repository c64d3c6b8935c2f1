"""The canonical lock hierarchy (ISSUE 11) — the single declared order.

The spine is the five bands the repo's concurrency story is built
around::

    scheduler  →  engine  →  slab pool  →  hot cache  →  stats/ring

A thread holding a lock may only acquire locks of strictly HIGHER rank
(further right). Auxiliary bands slot between the spine's members:
front-door serialization (``app.*``) before everything, the resilience
and fault-injection layers (``resil.*`` / ``faults.*``) between the
scheduler and the engine they wrap, and the observability leaves
(``obs.*``) just before ``stats/ring``. Every lock the runtime
constructs via ``strom.utils.locks.make_lock(name)`` must appear here —
the lock-order pass fails on a declaration it cannot rank, so this table
stays exhaustive by construction, and the runtime witness (which learns
order from actual execution) can be diffed against it.

Two pseudo-locks model ownership windows that are not raw mutexes:
``sched.grant`` (holding an engine grant — a ``with scheduler.grant():``
body) and ``engine.internal`` (any engine method call: engines take
their own internal locks, so calling one while holding a lock ranked at
or past the engine band is an inversion).
"""

from __future__ import annotations

import re

# the documented spine, in order (ARCHITECTURE.md "Lock discipline")
CANONICAL = ("scheduler", "engine", "slab pool", "hot cache", "stats/ring")

LOCK_RANKS = {
    # -- band: app (front door; outside the spine, before everything) -------
    "app.ctx": 0,              # strom.__init__ process-default context
    "app.uring_lib": 1,        # native lib load (takes app.core_build)
    "app.core_build": 2,       # _core build/cache lock
    "app.server_cache": 3,     # MetricsServer exposition cache
    "app.files": 4,            # ctx file registry (takes engine internals)
    "app.tenant_reg": 5,       # ctx tenant registration (takes sched)
    "app.steps_cache": 6,      # ctx stall-attribution TTL cache
    "app.demand": 7,           # demand-read gate counter
    "app.put": 8,              # serialize_device_put
    "app.prefetch": 9,         # Prefetcher queue state
    "app.vision_futs": 11,     # streamed-batch decode future list
    "app.jpeg_errs": 12,       # DecodePool error tally
    "app.parquet_footer": 13,  # footer read-once (takes engine reads)
    "app.ckpt_async": 14,      # AsyncCheckpointer writer bookkeeping
                               # (ISSUE 14; holds only for latch/future
                               # swaps — commits run outside it)
    "app.tune": 15,            # Autotuner counters/state (ISSUE 16): a
                               # leaf in practice — metrics_fn and
                               # knob.set both run OUTSIDE it (metrics
                               # walks the context's stats locks)
    "app.jpeg_procs": 16,      # DecodeProcs workers/regions/pending tasks:
                               # never held across a socket send, a spawn
                               # or a fence wait
    # -- band: scheduler -----------------------------------------------------
    "sched.arbiter": 20,       # IoScheduler._cond (the fair-drain core)
    "sched.admission": 21,     # AdmissionGate._cond
    "sched.grant": 22,         # PSEUDO: holding an engine grant
    "budget.bucket": 23,       # TokenBucket balance (taken under arbiter)
    # -- resilience wraps the engine (fallback holds while engine reads) ----
    "resil.fallback": 30,      # fallback engine creation + fi map
    "resil.fallback_serial": 31,  # one fallback gather at a time
    "resil.breaker": 32,       # circuit-breaker window
    "resil.hedge": 33,         # hedge latency reservoir
    # -- fault injection wraps the engine too --------------------------------
    "faults.proxy": 36,        # FaultyEngine bookkeeping
    "faults.plan": 37,         # FaultPlan decide/unwind
    # -- band: engine --------------------------------------------------------
    "engine.transfer": 40,     # ctx._engine_lock (whole-transfer serial)
    "engine.multi_reg": 41,    # MultiRing file registry
    "engine.multi_ring": 42,   # per-ring transfer locks
    "engine.python": 44,       # PythonEngine in-flight counter
    "engine.uring_dest": 45,   # uring dest-registration table
    "engine.internal": 46,     # PSEUDO: any engine method call
    # -- band: slab pool -----------------------------------------------------
    "slab.pool": 50,
    # -- band: hot cache -----------------------------------------------------
    "dist.directory": 55,      # ExtentDirectory dead-set/ring/epoch swap
                               # (ISSUE 20): a leaf — listdir and marker
                               # writes happen outside it, and the tier
                               # releases dist.peer before mark_dead so
                               # the two never nest
    "dist.peer": 56,           # PeerTier conn-pool checkout (ISSUE 15):
                               # NEVER held across socket I/O — the fetch
                               # checks a connection out, releases, does
                               # the wire round-trip, re-takes to return
                               # it; under it only counters move
    "dist.server": 57,         # PeerServer serve tallies (ISSUE 15): a
                               # leaf held around counter updates after
                               # the billed local read returned — never
                               # across the grant, the tiers, or the
                               # socket send
    "cache.decoded": 58,       # DecodedCache tallies (ISSUE 12): a leaf
                               # held only for counter updates, ranked
                               # before cache.meta so a tally-then-admit
                               # sequence could nest legally if it ever
                               # needed to (it doesn't today)
    "cache.spill": 59,         # SpillTier index/allocator (ISSUE 13): a
                               # sibling tier consulted AFTER cache.meta
                               # releases (never nested under it — spill
                               # pwrites/preads run outside every cache
                               # lock), writing only stats under itself
    "cache.meta": 60,
    # -- observability (leaves, but may write stats under themselves) --------
    "obs.flight": 70,
    "obs.history": 71,
    "obs.slo": 72,
    "obs.exemplars": 73,
    "obs.request_observers": 74,
    "obs.request": 75,
    "obs.federation": 76,      # ClusterView state; NEVER held across a
                               # scrape socket (poll_now fetches first,
                               # locks after), writes stats under itself
    "ops.graph": 78,           # CompiledOpGraph tally lock (ISSUE 19): a
                               # leaf on the decode pool workers guarding
                               # per-op counters only, flushed into the
                               # stats band (rank 80+) under itself
    # -- band: stats/ring (the terminal leaves) ------------------------------
    "stats.registries": 80,    # module-level registry set
    "stats.registry": 81,      # per-registry name tables
    "stats.series": 82,        # per-counter/gauge/histogram
    "ring.events": 85,         # event-ring slots
}

# context-manager methods whose with-body holds a pseudo-lock
CM_HOLDS = {
    "grant": "sched.grant",
    "engine_exclusive": "sched.grant",
}

# call summaries: a call matching (module_re, receiver_re, method_re)
# transiently acquires the named lock — the cross-subsystem acquisitions
# a with-statement walk alone cannot see (pool.release under the cache
# lock, engine reads under the fallback serializer, ...).
CALL_ACQUIRES = (
    (r".*", r"(^|\.)(_?slab_pool|pool)$", r"^(acquire|release)$",
     "slab.pool"),
    # HotCache's indirections to its backing pool
    (r"delivery/hotcache\.py$", r"^self$", r"^(_free|_alloc)$",
     "slab.pool"),
    (r".*", r"(^|\.)(_?hot_cache|cache)$",
     r"^(lookup|admit|unpin|view|clear)$", "cache.meta"),
    (r".*", r"(^|\.)(engine|inner|fb|child)$",
     r"^(read_vectored|submit_vectored|submit|submit_raw|poll|drain|"
     r"cancel|wait|close|register_file|unregister_file|register_dest|"
     r"unregister_dest|unregister_dest_addr)$", "engine.internal"),
    (r".*", r"(^|\.)(_?scheduler|sched)$",
     r"^(grant|acquire|release|register|tenant|resolve|drain|drain_all|"
     r"tenants_info)$", "sched.arbiter"),
    (r".*", r"(scope|_stats|global_stats)$",
     r"^(add|observe_us|set_gauge|counter|gauge|histogram|timer_us|"
     r"snapshot|scopes_snapshot)$", "stats.registry"),
    (r".*", r"(^|\.)(ring|_ring|_events_ring)$",
     r"^(complete|instant|flow|span|snapshot)$", "ring.events"),
    (r".*", r"(^|\.)(_?plan)$", r"^(decide|unwind)$", "faults.plan"),
)

_COMPILED = [(re.compile(mre), re.compile(rre), re.compile(fre), name)
             for mre, rre, fre, name in CALL_ACQUIRES]


def rank(name: str) -> "int | None":
    return LOCK_RANKS.get(name)


def call_summary(module_rel: str, receiver: "str | None",
                 method: "str | None") -> "str | None":
    """The lock a call transiently acquires per CALL_ACQUIRES, or None."""
    if receiver is None or method is None:
        return None
    for mre, rre, fre, name in _COMPILED:
        if mre.search(module_rel) and rre.search(receiver) \
                and fre.match(method):
            return name
    return None
