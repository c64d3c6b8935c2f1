"""Configuration for strom-tpu.

The reference exposes its knobs as kernel-module insmod parameters plus CLI
flags on the ``utils/`` benchmark programs (SURVEY.md §5 "Config/flag system";
reference cite UNVERIFIED — reference mount was empty, see SURVEY.md §0).
strom-tpu's equivalent is a frozen dataclass with ``STROM_*`` environment
variable overrides, passed explicitly through the public API.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any

KiB = 1024
MiB = 1024 * KiB

_ENV_PREFIX = "STROM_"


def _env_cast(value: str, typ: Any) -> Any:
    if typ is bool:
        return value.strip().lower() in ("1", "true", "yes", "on")
    if typ is int:
        v = value.strip().lower()
        mult = 1
        for suffix, m in (("kib", KiB), ("mib", MiB), ("k", KiB), ("m", MiB)):
            if v.endswith(suffix):
                v = v[: -len(suffix)]
                mult = m
                break
        return int(v) * mult
    if typ is float:
        return float(value.strip())
    if typ is str:
        return value
    if typ == tuple[str, ...]:
        return tuple(p for p in value.split(",") if p)
    return value


@dataclasses.dataclass(frozen=True)
class StromConfig:
    """Engine + delivery configuration.

    Defaults mirror the reference benchmark shape: 128KiB transfer chunks at
    queue depth 32 (SURVEY.md §2.1 ``utils/nvme_test``: "O_DIRECT sequential
    read, 128KiB blocks" — BASELINE.json:7).
    """

    # I/O engine
    block_size: int = 128 * KiB        # per-op transfer size (chunking unit)
    queue_depth: int = 32              # max in-flight ops per engine
    num_buffers: int = 64              # staging pool slots
    buffer_size: int = 0               # 0 → same as block_size
    o_direct: bool | None = None       # None → auto-probe per file
    engine: str = "auto"               # "auto" | "uring" | "python"
    mlock: bool = True                 # pin staging pool (best effort)
    register_buffers: bool = True      # io_uring fixed buffers
    coop_taskrun: bool = True          # IORING_SETUP_COOP_TASKRUN: run
                                       # completion task work at ring entry
                                       # instead of IPI-ing the submitter
                                       # (5.19+; auto-falls back when absent)
    engine_rings: int = 1              # independent io_uring rings: gathers
                                       # route per file (RAID0 member i →
                                       # ring i mod N, the userspace twin of
                                       # per-device blk-mq queues) and
                                       # concurrent transfers interleave.
                                       # >1 wins only where members are
                                       # distinct NVMe devices; neutral on a
                                       # one-disk box (BASELINE.md §C)
    sqpoll: bool = False               # IORING_SETUP_SQPOLL: kernel thread
                                       # polls the SQ — zero syscalls per
                                       # submitted batch, at the cost of a
                                       # busy kernel thread. Wins only when
                                       # spare cores exist; auto-falls back
                                       # when the kernel refuses it, and
                                       # supersedes coop_taskrun when active
    ring_recovery_s: float = 0.0       # MultiRingEngine quarantine recovery
                                       # cooldown: > 0 rebuilds a quarantined
                                       # member after this many seconds and
                                       # replays its dest-slab registrations
                                       # (READ_FIXED survives recovery);
                                       # 0 keeps ISSUE-9 sticky quarantine

    # delivery
    prefetch_depth: int = 2            # batches dispatched ahead of consumption
    prefetch_auto: bool = False        # auto-tune prefetch depth: grow on
                                       # data stalls, shrink when the queue
                                       # runs fully ready (lead time ample);
                                       # prefetch_depth is the STARTING depth
    prefetch_max_depth: int = 16       # auto-tune ceiling (further bounded by
                                       # slab-pool capacity per batch)
    delivery_workers: int = 2          # threads pushing host->HBM
    # segment coalescing: merge caller fragments (tar members, record runs,
    # shard-plan segments) that are contiguous in both file and dest space
    # into fewer, larger engine ops before submission; merged ops split at
    # this cap so a coalesced run still pipelines and still stripes across
    # RAID0 members. 0 disables coalescing entirely.
    coalesce_max_bytes: int = 32 * MiB
    # striped-read overlap window: member ops are submitted as per-member
    # sequential runs within windows of this many bytes (segments for window
    # N+1 enter the queue while window N's completions drain). -1 = auto
    # (queue_depth * block_size: the in-flight budget, so every member stays
    # busy within one window); 0 = keep chunk-granular logical order.
    stripe_window_bytes: int = -1
    slab_pool_bytes: int = 512 * MiB   # recycled host slabs (0 = off); only
                                       # used on backends where device_put
                                       # copies (i.e. not the jax CPU backend)
    slab_mlock_bytes: int = 0          # mlock recycled slabs up to this many
                                       # bytes (0 = never pin pool slabs);
                                       # past the cap slabs stay unpinned
    huge_pages: bool = False           # back staging slabs with MAP_HUGETLB
                                       # 2MiB pages (needs reserved hugepages;
                                       # silently falls back to 4KiB pages)
    # intra-transfer streaming: overlap disk reads of chunk k+1 with the
    # host->HBM transfer of chunk k (double-buffered slab ring) for transfers
    # >= overlap_min_bytes. 0 disables streaming.
    overlap_chunk_bytes: int = 128 * MiB
    overlap_min_bytes: int = 256 * MiB
    # one host->HBM transfer at a time: concurrent device_puts share the same
    # host link. Chosen before any chip measurement; ROADMAP A2 re-decides it
    # from the chip's own numbers.
    serialize_device_put: bool = True

    # host JPEG decode path (vision pipelines — strom/pipelines/vision.py):
    # decode_reduced_scale: when the SAMPLED crop at 1/d scale still covers
    # the target (min(crop_h, crop_w) >= size*d; d in 2/4/8; encoded dims
    # read from the SOF header without decoding), decode at 1/d via
    # IMREAD_REDUCED_COLOR_* — libjpeg skips the corresponding IDCT work
    # (up to 64x at 1/8). Crop geometry is sampled in full-res coordinates
    # BEFORE the denominator is chosen, so the augmentation RNG stream is
    # identical either way, and a crop that would need upscaling at 1/d
    # rides a smaller denominator or the full path (quality-neutral).
    decode_reduced_scale: bool = True
    # decode_to_slot: decode workers write their final size^2 x 3 rows
    # straight into a preallocated batch array (transforms take out=),
    # eliminating the np.stack full-batch copy and per-row temporaries.
    decode_to_slot: bool = True
    # decode_overlap_put: device_put each device's row group as soon as its
    # rows finish decoding (completion-ordered), overlapping host->HBM
    # transfer with the remaining decode instead of decoding the whole
    # union then transferring serially. Implies decode_to_slot mechanics.
    decode_overlap_put: bool = True

    # decode path v2 (strom/formats/jpeg.py): the
    # libjpeg-turbo binding, fused-run dispatch and ROI decode are always
    # on (cv2 where the binding did not build).
    # decode_cache: predecoded-on-the-fly — admit first-epoch decode
    # OUTPUT (full-frame RGB8, keyed by member extent + decode-params
    # fingerprint) into the hot cache, so epoch >= 2 pays only
    # crop+resize per sample. Needs hot_cache_bytes > 0 to do anything;
    # entries bill the shared cache budget/slab pool and the owning
    # tenant's partition like every other cache tenant. Off by default:
    # the decoded working set is ~5x the compressed bytes, an explicit
    # capacity decision.
    decode_cache: bool = False

    # intra-batch streaming (strom/delivery/stream.py — ISSUE 5 tentpole):
    # the JPEG vision batch path submits its gather through the engine's
    # async vectored API and hands each sample to the decode pool the
    # moment its extents complete (hot-cache hits count as instant
    # completions) — read, decode, and per-device put overlap at extent
    # granularity WITHIN a batch instead of running gather-ALL → decode-ALL
    # → put-ALL. Requires decode_to_slot + decode_overlap_put mechanics
    # (falls back to the barrier path when a custom transform lacks out=).
    # Batches are bit-identical either way (--no-stream is the A/B flag).
    stream_intra_batch: bool = True

    # hot-set host cache (strom/delivery/hotcache.py — ISSUE 4 tentpole):
    # an extent-keyed, byte-budgeted, refcounted LRU of physical byte
    # ranges in slab-pool-backed host buffers, consulted by the delivery
    # layer BEFORE engine submission — repeat traffic (epoch 2+, repeat
    # requests) serves from RAM instead of re-gathering from NVMe. 0 = off.
    hot_cache_bytes: int = 0
    # admission policy: "second_touch" (first epoch observes via a
    # block-granular touch ledger, the second admits — one-shot scans never
    # displace the hot set) or "always" (force-admit on first read: the
    # knob for known-repeating workloads)
    hot_cache_admit: str = "second_touch"
    # touch-ledger quantum: admission tracking is block-granular so the
    # second-touch test is stable across epochs even though coalescing
    # splits the same bytes differently per shuffle order
    hot_cache_block_bytes: int = 1 * MiB
    # epoch-aware readahead: warm the sampler's next N batches into the hot
    # cache from a background thread that uses idle engine queue budget and
    # yields to demand reads (0 = off; needs hot_cache_bytes > 0 to matter)
    readahead_window_batches: int = 0
    # NVMe spill tier (strom/delivery/spill.py — ISSUE 13 tentpole):
    # hot-cache entries evicted under byte pressure demote to a dedicated
    # spill file of this many bytes instead of vanishing, and the delivery
    # consult serves them from there — a RAM → NVMe → source hierarchy
    # (decoded-cache entries demote too, making the spill file a second
    # decoded tier). 0 = off; needs hot_cache_bytes > 0 to do anything.
    spill_bytes: int = 0
    # directory the spill file lives in ("" = the system temp dir); it is
    # created per context and unlinked at close — spilled bytes are a
    # cache, not a durability promise
    spill_dir: str = ""
    # spill-tier I/O rides the engines (ISSUE 14 satellite, ROADMAP item 2
    # residual b): demotion pwrites and spill-serve preads route through
    # the context's engine write/read path — O_DIRECT on the spill file,
    # scheduler-granted as the BACKGROUND class so spill traffic never
    # outranks demand reads. Requires the scheduler (sched_enabled); ops
    # that would nest inside an outstanding exclusive grant (a demote
    # fired from a mid-gather admission) take the legacy buffered-fd
    # fallback instead of deadlocking — both routes are counted
    # (spill_engine_ops / spill_fallback_ops). False = the pre-ISSUE-14
    # page-cache pread/pwrite path everywhere (the A/B flag).
    spill_engine_io: bool = True
    # transparent spill compression (ISSUE 19 front 3): demoted ranges are
    # compressed with the probed LZ4-class codec (strom/utils/codec.py)
    # when that pays — already-compressed bytes (JPEG members, snappy
    # parquet chunks) store raw — and decompress on serve. Spilled bytes
    # shrink at unchanged served-data bit-identity; compressed entries
    # can't ride the sendfile(2) zero-copy peer export (they fall back to
    # the decompress-and-send path). Off = the pre-compression tier,
    # byte for byte (the --spill-compress A/B flag).
    spill_compress: bool = False

    # multi-tenant I/O scheduler (strom/sched — ISSUE 7 tentpole): the
    # shared arbiter that replaces the per-transfer engine lock. Tenants
    # (pipelines, daemon clients, readahead) submit gathers into per-tenant
    # queues with priority classes (interactive > training > background);
    # a weighted fair drain grants the engine one slice at a time, with
    # per-tenant byte/IOPS token buckets and slab-pool admission control.
    # Off = the pre-scheduler behavior (one lock per whole transfer).
    sched_enabled: bool = True
    # grant granularity: a gather is executed as slices of this many bytes,
    # one engine grant each, so a concurrent tenant's op queues behind at
    # most one slice instead of a whole epoch gather. -1 = auto (4x the
    # engine in-flight budget, queue_depth * block_size); 0 = no slicing
    # (whole-gather grants, the old lock scope under fair queueing).
    sched_slice_bytes: int = -1
    # slab-pool admission high-water mark (fraction of slab_pool_bytes):
    # BACKGROUND-class allocations (readahead warm slabs) queue while the
    # pool sits above it instead of OOM-ing demand tenants out of slabs.
    # 0 disables admission control.
    sched_high_water: float = 0.9

    # distributed data plane (strom/dist — ISSUE 15 tentpole): the peer
    # extent service's knobs. A context with peers attached
    # (ctx.attach_peers) probes them in the delivery consult AFTER local
    # RAM/spill and BEFORE the engine: an extent hot on another host
    # arrives over the socket instead of a duplicate SSD read. Fetch
    # failures fall back to the local engine (never fatal); a dead peer
    # trips a per-peer circuit breaker.
    dist_peer_timeout_s: float = 0.5   # per-fetch connect/recv timeout: a
                                       # slow peer costs at most this
                                       # before the local engine serves
    dist_server_max_conns: int = 8     # bounded peer-server concurrency;
                                       # excess connects queue in accept
    dist_send_zc: bool = False         # zero-copy peer serving (ISSUE 16):
                                       # serve cache hits straight from the
                                       # pinned view (no np.empty bounce),
                                       # spill hits via sendfile(2), and —
                                       # when the kernel grants SO_ZEROCOPY
                                       # — MSG_ZEROCOPY sends with errqueue
                                       # completion waits. Off = byte-
                                       # identical pre-PR copy path
    # transparent peer-response compression (ISSUE 19 front 3): fetches
    # advertise the probed codec in the request framing and a willing
    # server answers with a compressed hit frame when that pays (raw
    # otherwise). Old peers see an unknown op and drop the conn — the
    # client notices once and latches that peer back to the plain ops
    # (the same downgrade contract as trace_ok). Off = the pre-PR wire,
    # byte for byte (the --peer-compress A/B flag).
    peer_compress: bool = False
    # peer fabric v2 (ISSUE 20): batched pipelined transport + connection
    # pool + shared-key auth. Batching packs up to this many extents into
    # one OP_GET_BATCH round trip (0 = off: the v1 one-extent-per-RTT
    # wire, the bench's unbatched A/B arm); old peers latch back per the
    # usual downgrade ladder. The pool keeps this many persistent conns
    # per peer (overflow rides ephemeral conns); a failed conn is
    # discarded so a restarted peer gets fresh re-probed ones.
    dist_batch_max_extents: int = 64
    dist_conn_pool_size: int = 2
    # shared-key auth: when non-empty every new peer conn must pass an
    # HMAC-SHA256 challenge/response before its first request; wrong or
    # missing key is refused cleanly (peer_auth_rejects). Empty = the
    # open loopback wire, byte for byte.
    dist_auth_key: str = ""

    # closed-loop knob autotuner (ISSUE 16, strom/tune/): coordinate descent
    # over the live knob surfaces (prefetch depth, sched slice, cache
    # budget) against goodput, with guarded revert and an SLO-burn hold.
    tune: bool = False                 # arm the tuner thread in the context
    tune_interval_s: float = 1.0       # settle window between tuner moves
    tune_guard_frac: float = 0.10      # revert a move that costs more than
                                       # this fraction of the objective
    tune_profile: str = ""             # JSON profile path: loaded (applied)
                                       # at attach when it exists, saved on
                                       # close — the cli --profile flag

    # NUMA affinity (multi-socket hosts): pin submitting threads to the NVMe's
    # home node, mbind staging slabs there, optionally steer the device IRQs
    # (needs root). Off by default; no-op on UMA boxes (strom/utils/numa.py).
    numa_affinity: bool = False
    numa_node: int = -1                # -1 = auto-discover from the device
    irq_affinity: bool = False

    # residency-aware hybrid reads: probe per-range page-cache residency
    # (cachestat(2), else mincore) and serve WARM ranges through the buffered
    # fd — a memcpy from the cache — instead of re-reading them from media
    # O_DIRECT (SURVEY.md §0.5 mechanism #5, §2.1 "Page-cache fallback").
    # Cold ranges are unchanged: one probe syscall per gather segment; mixed
    # segments probe in groups bounded at 256 per segment (the
    # residency_probes counter watches the probe volume). Observable via the
    # cached_bytes / media_bytes engine counters — ADVISORY under memory
    # pressure: residency is snapshotted upfront per gather, so pages
    # evicted between probe and read still count as cached_bytes (the route
    # chosen, not where bytes were ultimately served; integrity unaffected).
    residency_hybrid: bool = True

    # RAID0 (software striped reader over N member files/devices)
    raid_chunk: int = 512 * KiB

    # failure handling: transparent per-chunk resubmits before erroring
    io_retries: int = 1
    # retry backoff (ISSUE 9 tentpole, strom/engine/resilience.py): a
    # failed transient piece waits base * 2^attempts (jittered, capped at
    # max) before its resubmit instead of hammering the device that just
    # failed it; the per-gather BUDGET bounds total resubmits per transfer
    # so a sick device can never turn one gather into a retry storm.
    io_retry_backoff_s: float = 0.005
    io_retry_backoff_max_s: float = 0.2
    io_retry_budget: int = 64
    # engine wait watchdog: the generic gather paths (read_vectored wait
    # loop, token drain, cancel reaps) bound any single completion wait at
    # this and raise a diagnosable EngineStallError naming the stuck tags
    # instead of blocking forever (was a hard-coded 30 s).
    engine_wait_timeout_s: float = 30.0
    # default request deadline in seconds (0 = none): every demand gather's
    # traced Request carries it; scheduler queue waits, engine poll waits
    # and retry backoffs stop at the deadline and the gather fails fast
    # with DeadlineExceeded instead of blowing the tenant's SLO. Per-call
    # deadlines (pread/memcpy deadline_s=) override.
    request_deadline_s: float = 0.0
    # per-engine circuit breaker (strom/delivery/resilient.py): trips OPEN
    # on error rate >= breaker_error_rate over a rolling window holding
    # >= breaker_min_events outcomes; while open, demand reads reroute to
    # the python fallback engine; after breaker_cooldown_s, half-open
    # probes (breaker_half_open_successes consecutive to close) recover.
    breaker_enabled: bool = True
    breaker_window_s: float = 10.0
    breaker_min_events: int = 8
    breaker_error_rate: float = 0.5
    breaker_cooldown_s: float = 5.0
    breaker_half_open_successes: int = 3
    # hedged reads (streamed gathers): a gather quiet for longer than
    # max(hedge_min_s, hedge_multiplier * rolling-p99 completion latency)
    # re-reads its incomplete chunks on the fallback path; first
    # completion wins, the loser is cancelled. 0 multiplier+min disables.
    hedge_enabled: bool = True
    hedge_min_s: float = 0.05
    hedge_multiplier: float = 3.0

    # fault injection (tests/hardening; 0 = off)
    fault_every: int = 0
    # seeded rule-based fault plan (ISSUE 9 tentpole, strom/faults/): a
    # path to a JSON plan file, an inline JSON object string, or a named
    # preset ("chaos[:seed]"). Non-empty wraps the engine in FaultyEngine
    # at context construction, injecting deterministic errno / short-read
    # / bit-flip / latency / stuck / engine-death faults per the plan's
    # matchers — any workload or test runs under reproducible chaos.
    fault_plan: str = ""

    # observability
    metrics_port: int = 0              # >0: StromContext serves /metrics
                                       # (Prometheus), /stats (JSON),
                                       # /trace (event-ring dump) and
                                       # /flight (on-demand flight capture)
                                       # on 127.0.0.1:<port> for the
                                       # context's lifetime
                                       # (strom/obs/server.py). 0 = no
                                       # server.
    # flight recorder (strom/obs/flight.py — ISSUE 6 tentpole): a non-empty
    # flight_dir starts a watchdog thread for the context's lifetime that
    # samples step progress / slab occupancy / engine in-flight / ring
    # high-water into a small ring, and dumps an atomic crash bundle
    # (Chrome trace + stats snapshot + per-thread stacks + last-N samples)
    # there on SIGTERM, unhandled exception, or no step progress for longer
    # than flight_stall_s ("" = recorder off; /flight on the live server
    # still captures on demand).
    flight_dir: str = ""
    flight_stall_s: float = 30.0       # no-progress watchdog threshold in
                                       # seconds; <= 0 disables the stall
                                       # trigger (signal/exception dumps
                                       # stay armed)
    # lock-order witness (ISSUE 11, strom/utils/locks.py): every lock the
    # engine/sched/delivery/obs subsystems construct through make_lock
    # becomes a WitnessLock that records per-thread acquisition order into
    # a process-wide graph and raises LockOrderError (plus a flight-bundle
    # dump) the moment two locks are ever taken in both orders — the
    # runtime cross-check of the static hierarchy tools/stromlint
    # enforces. Off = plain threading.Lock, zero overhead. Enable via
    # STROM_DEBUG_LOCKS=1 (covers module-level locks created at import)
    # or this flag (enabled before the context constructs its subsystems).
    debug_locks: bool = False
    # snapshot history (strom/obs/history.py — ISSUE 8 tentpole): when the
    # live server is on, a background thread samples the global registry
    # (scoped series included) every history_interval_s into a bounded
    # ring served on /history — true rate() math (tools/strom_top.py)
    # without an external TSDB. <= 0 disables the sampler (the /history
    # route then 404s); no live server = no sampler either way.
    history_interval_s: float = 2.0

    def __post_init__(self) -> None:
        if self.buffer_size == 0:
            object.__setattr__(self, "buffer_size", self.block_size)
        if self.block_size <= 0 or self.block_size % 512:
            raise ValueError(f"block_size must be a positive multiple of 512, got {self.block_size}")
        if self.buffer_size < self.block_size:
            raise ValueError("buffer_size must be >= block_size")
        if self.queue_depth <= 0:
            raise ValueError("queue_depth must be positive")
        if self.engine_rings < 1:
            raise ValueError("engine_rings must be >= 1")
        if self.num_buffers <= 0:
            raise ValueError("num_buffers must be positive")
        if self.engine not in ("auto", "uring", "python"):
            raise ValueError(f"unknown engine {self.engine!r}")
        if self.overlap_chunk_bytes and self.overlap_chunk_bytes % 4096:
            raise ValueError("overlap_chunk_bytes must be a multiple of 4096 "
                             "(O_DIRECT alignment and dtype itemsize)")
        if self.coalesce_max_bytes < 0:
            raise ValueError("coalesce_max_bytes must be >= 0 (0 = off)")
        if self.stripe_window_bytes < -1:
            raise ValueError("stripe_window_bytes must be >= 0 (0 = off) "
                             "or exactly -1 (auto)")
        if self.prefetch_max_depth < 1:
            raise ValueError("prefetch_max_depth must be >= 1")
        if self.metrics_port < 0 or self.metrics_port > 65535:
            raise ValueError("metrics_port must be in [0, 65535] (0 = off)")
        if self.hot_cache_bytes < 0:
            raise ValueError("hot_cache_bytes must be >= 0 (0 = off)")
        if self.hot_cache_admit not in ("second_touch", "always"):
            raise ValueError("hot_cache_admit must be 'second_touch' or "
                             f"'always', got {self.hot_cache_admit!r}")
        if self.hot_cache_block_bytes <= 0 or self.hot_cache_block_bytes % 4096:
            raise ValueError("hot_cache_block_bytes must be a positive "
                             "multiple of 4096")
        if self.readahead_window_batches < 0:
            raise ValueError("readahead_window_batches must be >= 0 (0 = off)")
        if self.spill_bytes < 0:
            raise ValueError("spill_bytes must be >= 0 (0 = off)")
        if self.sched_slice_bytes < -1:
            raise ValueError("sched_slice_bytes must be >= 0 (0 = no "
                             "slicing) or exactly -1 (auto)")
        if not 0.0 <= self.sched_high_water <= 1.0:
            raise ValueError("sched_high_water must be in [0, 1] (0 = off)")
        if self.io_retry_backoff_s < 0 or self.io_retry_backoff_max_s < 0:
            raise ValueError("io_retry_backoff(_max)_s must be >= 0")
        if self.io_retry_budget < 0:
            raise ValueError("io_retry_budget must be >= 0")
        if self.engine_wait_timeout_s <= 0:
            raise ValueError("engine_wait_timeout_s must be > 0")
        if self.request_deadline_s < 0:
            raise ValueError("request_deadline_s must be >= 0 (0 = none)")
        if not 0.0 < self.breaker_error_rate <= 1.0:
            raise ValueError("breaker_error_rate must be in (0, 1]")
        if self.dist_peer_timeout_s <= 0:
            raise ValueError("dist_peer_timeout_s must be > 0")
        if self.dist_server_max_conns < 1:
            raise ValueError("dist_server_max_conns must be >= 1")
        if self.dist_batch_max_extents < 0:
            raise ValueError("dist_batch_max_extents must be >= 0 (0 = "
                             "unbatched transport)")
        if self.dist_conn_pool_size < 1:
            raise ValueError("dist_conn_pool_size must be >= 1")
        if self.ring_recovery_s < 0:
            raise ValueError("ring_recovery_s must be >= 0 (0 = sticky)")
        if self.tune_interval_s <= 0:
            raise ValueError("tune_interval_s must be > 0")
        if not 0.0 < self.tune_guard_frac <= 1.0:
            raise ValueError("tune_guard_frac must be in (0, 1]")

    @property
    def resolved_stripe_window_bytes(self) -> int:
        """The effective striped-overlap window: -1 resolves to the engine's
        in-flight budget (queue_depth × block_size)."""
        if self.stripe_window_bytes >= 0:
            return self.stripe_window_bytes
        return self.queue_depth * self.block_size

    @classmethod
    def from_env(cls, **overrides: Any) -> "StromConfig":
        """Build a config from ``STROM_*`` env vars, with explicit overrides winning."""
        kwargs: dict[str, Any] = {}
        for field in dataclasses.fields(cls):
            env_key = _ENV_PREFIX + field.name.upper()
            if env_key in os.environ:
                typ = field.type
                if field.name == "o_direct":
                    kwargs[field.name] = _env_cast(os.environ[env_key], bool)
                elif typ in ("int", int):
                    kwargs[field.name] = _env_cast(os.environ[env_key], int)
                elif typ in ("bool", bool):
                    kwargs[field.name] = _env_cast(os.environ[env_key], bool)
                elif typ in ("float", float):
                    kwargs[field.name] = _env_cast(os.environ[env_key], float)
                elif typ in ("str", str):
                    kwargs[field.name] = os.environ[env_key]
        kwargs.update(overrides)
        return cls(**kwargs)


DEFAULT_CONFIG = StromConfig()
