"""memcpy_ssd2tpu — the hot path.

Reference hot path (SURVEY.md §3.3; reference cite UNVERIFIED — empty mount,
SURVEY.md §0): MEMCPY_SSD2GPU_ASYNC chunks a file range, resolves extents
(raid0 math included), submits NVMe READs whose PRPs point at pinned GPU
pages, and MEMCPY_WAIT joins the completion countdown.  strom-tpu equivalent,
per BASELINE.json:5: plan per-device byte ranges from the requested
`NamedSharding`, io_uring-read them O_DIRECT into page-aligned host slabs
(zero bounce), `jax.device_put` each slab to its device (host→HBM DMA owned
by the TPU runtime), and assemble the global `jax.Array` with
`jax.make_array_from_single_device_arrays`.
"""

from __future__ import annotations

import concurrent.futures
import contextlib
import dataclasses
import errno
import io
import math
import os
import queue
import threading
import time
from typing import Any, Sequence

import numpy as np

from strom.config import StromConfig
from strom.delivery.buffers import SlabPool, alloc_aligned
from strom.delivery.coalesce import coalesce_chunks, coalesce_segments
from strom.delivery.extents import ExtentList
from strom.delivery.handle import DMAHandle, deferred_handle
from strom.delivery.hotcache import HotCache
from strom.delivery.shard import DevicePlan, Segment, dedupe_plans, plan_sharded_read
from strom.engine import make_engine
from strom.engine.base import (DeadlineExceeded, Engine, EngineError,
                               EngineStallError)
from strom.engine.raid0 import (count_stripe_windows, plan_stripe_reads,
                                plan_stripe_windows)
from strom.obs import request as _request
from strom.obs.events import ring as _events_ring
from strom.utils.locks import make_lock
from strom.utils.stats import global_stats


@dataclasses.dataclass(frozen=True)
class StripedFile:
    """A logical file striped RAID0-style over member files/devices.

    Userspace twin of the reference's in-kernel md-raid0 decode: identical
    chunk math, applied before submission instead of inside the kmod
    (SURVEY.md §2.2 "md-raid0 decode").
    """

    members: tuple[str, ...]
    chunk: int
    # logical size override: a file striped with zero padding to a full
    # stripe width (engine/raid0.stripe_file) reports its TRUE size here, so
    # formats with trailing metadata (parquet footers) see the real EOF and
    # record counting (rawbin) never counts padding as data
    size_bytes: int | None = None

    @property
    def size(self) -> int:
        if self.size_bytes is not None:
            return self.size_bytes
        # cached: size is consulted per pread/memcpy (via source_size), and
        # re-opening the sidecar each time is both a syscall tax and a window
        # for a mid-run rewrite to shift the perceived EOF
        cached = getattr(self, "_size_cache", None)
        if cached is not None:
            return cached
        sizes = [os.stat(m).st_size for m in self.members]
        usable = min(sizes) // self.chunk * self.chunk
        capacity = usable * len(self.members)
        size = capacity
        # sets written by stripe_file carry their true size in a sidecar;
        # honoring it here closes the silent-zero-pad trap even when the
        # caller forgot to pass size= at registration
        from strom.engine.raid0 import SIZE_SIDECAR_SUFFIX

        try:
            with open(self.members[0] + SIZE_SIDECAR_SUFFIX) as f:
                claimed = int(f.read())
            # a stale sidecar (members re-striped underneath it) could claim
            # anything; only trust a value the members can actually hold
            if 0 < claimed <= capacity:
                size = claimed
        except (OSError, ValueError):
            pass
        object.__setattr__(self, "_size_cache", size)
        return size


# anything memcpy_ssd2tpu / pread can read from
Source = str | StripedFile | ExtentList


# jitted helpers for streamed assembly, created lazily (this module must not
# import jax at import time) and cached so jax's compile cache keys stay
# stable across calls
_jit_cache: dict = {}


def _alloc_on_device(shape: tuple, dtype, device):
    """Allocate a zeroed device buffer WITHOUT host->device traffic (the
    zeros kernel runs on the device)."""
    import jax
    import jax.numpy as jnp

    fn = _jit_cache.get(("zeros", device))
    if fn is None:
        sharding = jax.sharding.SingleDeviceSharding(device) \
            if device is not None else None
        fn = jax.jit(lambda s, dt: jnp.zeros(s, dt),
                     static_argnums=(0, 1), out_shardings=sharding)
        _jit_cache[("zeros", device)] = fn
    return fn(tuple(shape), jnp.dtype(dtype))


def _paste(buf, piece, off: int):
    """Donated in-place paste of *piece* at leading index *off*: XLA aliases
    the donated buffer, so assembling N bytes from pieces peaks at ~N +
    piece_size on device — an on-device jnp.concatenate of the pieces would
    peak at ~2N."""
    import jax
    from jax import lax

    fn = _jit_cache.get("paste")
    if fn is None:
        fn = jax.jit(lambda b, p, o: lax.dynamic_update_slice(
            b, p, (o,) + (0,) * (b.ndim - 1)), donate_argnums=(0,))
        _jit_cache["paste"] = fn
    return fn(buf, piece, off)


def _reshape_donated(buf, shape: tuple):
    import jax

    fn = _jit_cache.get("reshape")
    if fn is None:
        fn = jax.jit(lambda b, s: b.reshape(s), static_argnums=(1,),
                     donate_argnums=(0,))
        _jit_cache["reshape"] = fn
    return fn(buf, tuple(shape))


# TPU's int32 element offsets: a streamed transfer with this many elements
# or more cannot paste into a flat buffer (see _assembly_shape)
_INT32_LIMIT = 2 ** 31


def _assembly_shape(n_elems: int, piece_elems: int) -> tuple | None:
    """Device buffer shape a streamed transfer of *n_elems* assembles into
    from pieces of *piece_elems* (the last may be shorter); None when no
    shape is safe and the transfer must not stream.

    Flat below 2^31 elements. At 2^31 and above, [n_elems // 128, 128]: on
    TPU a paste into a flat buffer of 2^31+ elements overflows
    dynamic_update_slice's int32 offset and is dropped (PR 21), while row
    offsets stay small. 128 is the TPU's lane width, so the rows are not
    padded, and the reshape to a 1-D result is a bitcast (the TPU compiler
    reports no temporary for (2^24, 128) -> (2^31,); a (16, 2^27) buffer
    needs a 2 GiB one). Pieces must be whole rows, so a transfer whose
    length or piece size is not a multiple of 128 elements gets None."""
    if n_elems < _INT32_LIMIT:
        return (n_elems,)
    if n_elems % 128 or piece_elems % 128 or n_elems // 128 >= _INT32_LIMIT:
        return None
    return (n_elems // 128, 128)


def split_segments(segments: Sequence[Segment], chunk: int
                   ) -> list[tuple[int, int, list[Segment]]]:
    """Cut a dest-contiguous segment list into pieces of <= *chunk* dest
    bytes: [(piece_dest_base, piece_nbytes, [Segment(dest rebased to 0)])].

    The pieces tile the dest space in order, so a streamed transfer can read
    piece k+1 while piece k's host->HBM transfer is in flight and concatenate
    the delivered pieces back into the full array. Pure function (unit-tested
    in tests/test_streaming.py)."""
    segs = sorted(segments, key=lambda s: s.dest_offset)
    total = sum(s.length for s in segs)
    pieces: list[tuple[int, int, list[Segment]]] = []
    base = 0
    si = 0
    within = 0  # consumed bytes of segs[si]
    while base < total:
        take = min(chunk, total - base)
        out: list[Segment] = []
        need = take
        while need > 0:
            s = segs[si]
            part = min(need, s.length - within)
            out.append(Segment(s.file_offset + within,
                               (s.dest_offset + within) - base, part))
            within += part
            need -= part
            if within == s.length:
                si += 1
                within = 0
        pieces.append((base, take, out))
        base += take
    return pieces


def source_size(source: Source) -> int:
    return source.size if isinstance(source, (StripedFile, ExtentList)) \
        else os.stat(source).st_size


class SourceIO(io.RawIOBase):
    """Minimal seekable file-like over any delivery Source (StripedFile,
    ExtentList, or path), reading through ``ctx.pread``. For library code
    that wants a file object against engine-backed sources — e.g. indexing a
    tar or reading Parquet metadata on a striped set.

    Small reads are served from a *readahead* window (one engine round-trip
    per window, not per read): a tar header walk issues one 512-byte read
    per member, which naively costs an engine submit/wait + fresh slab each
    — ~100k round-trips to index a 50k-sample shard. Bulk payload bytes
    should still flow through gather reads, not this adapter."""

    def __init__(self, ctx: "StromContext", source: Source,
                 readahead: int = 1 << 20):
        self._ctx = ctx
        self._source = source
        self._size = source_size(ctx.resolve_source(source))
        self._pos = 0
        self._ra = max(readahead, 1)
        self._buf = b""
        self._buf_off = 0  # source offset of _buf[0]

    def readable(self) -> bool:
        return True

    def seekable(self) -> bool:
        return True

    def seek(self, offset: int, whence: int = io.SEEK_SET) -> int:
        try:
            base = {io.SEEK_SET: 0, io.SEEK_CUR: self._pos,
                    io.SEEK_END: self._size}[whence]
        except KeyError:
            raise ValueError(f"unsupported whence {whence}") from None
        pos = base + offset
        if pos < 0:
            # io.IOBase semantics: fail here, not as a confusing EngineError
            # from a later pread at a negative offset
            raise ValueError(f"negative seek position {pos}")
        self._pos = pos
        return self._pos

    def tell(self) -> int:
        return self._pos

    def read(self, n: int = -1) -> bytes:
        if n < 0:
            n = self._size - self._pos
        n = min(n, self._size - self._pos)
        if n <= 0:
            return b""
        lo = self._pos - self._buf_off
        if not (0 <= lo and lo + n <= len(self._buf)):
            fetch = min(max(n, self._ra), self._size - self._pos)
            self._buf = self._ctx.pread(self._source, self._pos,
                                        fetch).tobytes()
            self._buf_off = self._pos
            lo = 0
        data = self._buf[lo: lo + n]
        self._pos += len(data)
        return data


class _SpillEngineIo:
    """Engine router for spill-tier I/O (ISSUE 14 satellite, ROADMAP item
    2 residual b): demotion writes and spill-serve reads ride the
    context's engine path — O_DIRECT on the spill file, scheduler-granted
    as the BACKGROUND class, billed to the "spill" tenant — instead of
    page-cache pread/pwrite. ``write``/``read`` return False whenever
    enqueueing is unsafe or fails, and the tier falls back to its buffered
    fd (counted: ``spill_fallback_ops``): unsafe means the calling thread
    already holds a scheduler grant, or — writes only — ANY exclusive
    grant is outstanding (a demote fired from a mid-gather admission on
    the pump thread must not queue behind a grant its own progress
    releases). The two-phase allocate/publish discipline is unchanged:
    none of this runs under the tier lock."""

    def __init__(self, ctx, path: str):
        self._ctx = ctx
        self._path = path
        self._closed = False
        # registered EAGERLY (the file exists — the tier created it), so
        # no lazy-registration lock is needed. O_DIRECT preferred,
        # probed down PER REGISTRATION to buffered where the spill dir's
        # fs refuses it (tmpfs) — never to the context's configured
        # o_direct, which may itself be a hard True the spill fs can't
        # honor, and never leaving a half-registered pair behind.
        def _reg(writable: bool) -> int:
            try:
                return ctx.engine.register_file(path, o_direct=True,
                                                writable=writable)
            except OSError:
                return ctx.engine.register_file(path, o_direct=False,
                                                writable=writable)

        self._wfi = _reg(True)
        try:
            self._rfi = _reg(False)
        except BaseException:
            with contextlib.suppress(Exception):
                ctx.engine.unregister_file(self._wfi)
            raise

    def _safe(self, *, write: bool) -> bool:
        sched = self._ctx._scheduler
        if sched is None or self._closed or self._ctx._closed:
            return False
        if sched.held_by_me():
            return False
        return not write or sched.engine_idle()

    def write(self, data: np.ndarray, off: int) -> bool:
        if not self._safe(write=True):
            return False
        try:
            self._ctx._scheduler.write_chunks(
                [(self._wfi, off, 0, data.nbytes)], data, tenant="spill",
                retries=self._ctx.config.io_retries, priority="background")
            return True
        # stromlint: ignore[swallowed-exceptions] -- advisory route: any
        # engine-path failure degrades to the buffered-fd fallback (the
        # bytes still land) and is counted below
        except Exception:
            self._ctx.scope.add("spill_errors")
            return False

    def read(self, dest: np.ndarray, off: int, n: int) -> bool:
        if not self._safe(write=False):
            return False
        try:
            got = self._ctx._scheduler.read_chunks(
                [(self._rfi, off, 0, n)], dest, tenant="spill",
                retries=self._ctx.config.io_retries, priority="background")
            return got == n
        # stromlint: ignore[swallowed-exceptions] -- advisory route, same
        # degrade-to-fallback contract as write(); counted
        except Exception:
            self._ctx.scope.add("spill_errors")
            return False

    def close(self) -> None:
        self._closed = True
        for fi in (self._wfi, self._rfi):
            with contextlib.suppress(Exception):
                self._ctx.engine.unregister_file(fi)


class StromContext:
    """Owns the engine, file-registration cache and delivery executor.

    One per process is typical (module-level default, see strom/__init__.py);
    tests create isolated instances.
    """

    def __init__(self, config: StromConfig | None = None,
                 engine: Engine | None = None, *,
                 metrics_port: int | None = None,
                 scope: "dict | None | object" = None):
        self.config = config or StromConfig.from_env()
        self._witness_enabled_here = False
        if self.config.debug_locks:
            # enable BEFORE the engine and every subsystem lock below is
            # constructed, so their make_lock calls return WitnessLocks
            # (ISSUE 11; module-level locks created at import time need
            # STROM_DEBUG_LOCKS=1 instead). close() reverts — a
            # diagnostic context must not leave every later context in
            # the process paying witness overhead it never asked for.
            from strom.utils import locks as _locks

            self._witness_enabled_here = not _locks.witness_enabled()
            _locks.enable_witness(True)
            if self.config.flight_dir:
                # a cycle's bundle lands where the operator already asked
                # crash bundles to go (env STROM_FLIGHT_DIR still wins
                # for recorder-less runs — it seeded locks at import)
                _locks.set_flight_dir(self.config.flight_dir)
        self.engine = engine or make_engine(self.config)
        # fault injection (ISSUE 9 tentpole, strom/faults): a configured
        # fault plan wraps the engine in the FaultyEngine proxy BEFORE
        # anything (scheduler, resilience, locks) binds it — every read
        # this context issues runs under the plan's deterministic chaos
        if self.config.fault_plan:
            from strom.faults import FaultPlan, FaultyEngine

            if not isinstance(self.engine, FaultyEngine):
                self.engine = FaultyEngine(
                    self.engine, FaultPlan.from_spec(self.config.fault_plan))
        # telemetry scope (ISSUE 6 tentpole): a dict of labels becomes a
        # label-scoped child view of the global registry — every delivery
        # counter/histogram written through it lands in BOTH the scoped
        # series (a Prometheus-labeled twin on /metrics) and the unlabeled
        # aggregate. None = the identity scope (global registry, the
        # single-tenant behavior). A prebuilt ScopedStats passes through so
        # several contexts can share one tenant scope.
        if scope is None:
            self.scope = global_stats
        elif isinstance(scope, dict):
            self.scope = global_stats.scoped(**scope)
        else:
            self.scope = scope
        self.engine.set_scope(self.scope)
        self._files: dict[str, int] = {}
        # writable registrations (ISSUE 13 write path): separate indexes —
        # the read side keeps its O_RDONLY fds and probe state
        self._wfiles: dict[str, int] = {}
        # path → StripedFile aliases (register_striped): lets format readers
        # that traffic in path-keyed extents (tar members, Parquet column
        # chunks) ride RAID0 without knowing about striping
        self._striped: dict[str, StripedFile] = {}
        # FIEMAP extent map per registered file: list[Extent] when mapped,
        # None when the fs can't say (tmpfs, old kernels) — probed once
        self._extent_maps: dict[str, list | None] = {}
        self._files_lock = make_lock("app.files")
        self._executor = concurrent.futures.ThreadPoolExecutor(
            max_workers=max(2, self.config.delivery_workers),
            thread_name_prefix="strom-delivery")
        # per-device-group tasks within ONE sharded transfer; a separate pool
        # from _executor because async transfers run their whole run() there —
        # submitting group tasks to the same pool could deadlock with every
        # worker occupied by a transfer waiting on its own groups
        self._group_executor = concurrent.futures.ThreadPoolExecutor(
            max_workers=max(2, self.config.delivery_workers),
            thread_name_prefix="strom-groups")
        # engine ops are pipelined internally; serialize whole-transfer use of
        # the engine so concurrent handles don't interleave queue-depth
        # budgets. Multi-ring engines serialize internally PER RING instead
        # (concurrent_gathers) — locking here would re-serialize the very
        # transfers the rings exist to interleave.
        self._engine_lock = contextlib.nullcontext() \
            if getattr(self.engine, "concurrent_gathers", False) \
            else make_lock("engine.transfer")
        # process-lifetime unique tags: stale completions from a failed
        # transfer can never alias a later transfer's ops
        self._tag_counter = 0
        if self.config.numa_affinity:
            from strom.utils.numa import NumaAffinity

            self._numa = NumaAffinity(node=self.config.numa_node,
                                      steer_irqs=self.config.irq_affinity)
        else:
            self._numa = None
        self._slab_pool = SlabPool(
            self.config.slab_pool_bytes,
            pin=self.config.slab_mlock_bytes > 0,
            max_mlock_bytes=self.config.slab_mlock_bytes,
            huge=self.config.huge_pages,
            on_alloc=self._on_slab_alloc) \
            if self.config.slab_pool_bytes > 0 else None
        # multi-tenant I/O scheduler (ISSUE 7 tentpole, strom/sched): the
        # shared arbiter that replaces the per-transfer engine lock —
        # per-tenant queues with priority classes, weighted fair drain at
        # slice granularity, byte/IOPS budgets, slab-pool admission
        # control. Every scheduled gather below (_read_segments slices,
        # StreamingGather grants, the readahead's background-class warm
        # reads) routes through it; sched_enabled=False keeps the
        # pre-scheduler lock-per-transfer behavior.
        self._scheduler = None
        self._tenant_reg_lock = make_lock("app.tenant_reg")
        if self.config.sched_enabled:
            from strom.sched.scheduler import IoScheduler

            self._scheduler = IoScheduler(self.engine, self.config,
                                          pool=self._slab_pool,
                                          scope=self.scope)
        # resilience layer (ISSUE 9 tentpole, strom/delivery/resilient.py):
        # per-engine circuit breaker + python-engine failover + hedge
        # control. Every demand gather's outcome feeds the breaker; while
        # it is open, reads reroute to the fallback path and the degraded
        # state is visible on /stats["resilience"], /tenants and the
        # breaker_* gauges. The flight-recorder trip hook is attached
        # after the recorder exists (below).
        from strom.delivery.resilient import ResilientIo

        self._resilience = ResilientIo(self.config, self.engine,
                                       scope=self.scope)
        if self._scheduler is not None:
            self._scheduler.resilience_info = self._resilience.stats
        # per-tenant SLO engine (ISSUE 8 tentpole, strom/obs/slo.py):
        # every finished traced request feeds good/bad window accounting;
        # burn rates surface on /slo, as slo_* gauges per tenant scope,
        # and as the slo_burning flag on /tenants rows (scheduler hook).
        from strom.obs.slo import SloEngine

        self._slo = SloEngine(goodput_fn=self._current_goodput)
        # requests minted by THIS context carry this token; the observer
        # list is process-global, so without the filter two live contexts
        # would feed each other's SLO engines (phantom tenant rows, a
        # healthy context's slo_ok flipped by its neighbor's slow gathers)
        self._req_owner: object = object()

        def _observe(req, _slo=self._slo, _own=self._req_owner):
            if req.owner is None or req.owner is _own:
                _slo.observe_request(req)

        self._slo_observer = _observe
        if self._scheduler is not None:
            self._scheduler.slo_hook = self._slo.burning
        # hot-set host cache (ISSUE 4 tentpole, strom/delivery/hotcache.py):
        # repeat traffic serves from RAM instead of re-gathering from NVMe.
        # Buffers come from the slab pool (NUMA-placed, engine-registered);
        # bound_depth subtracts hot_cache_bytes from the pool budget so
        # prefetch auto-depth and the cache never double-commit slab memory.
        self._hot_cache = HotCache(
            self.config.hot_cache_bytes, pool=self._slab_pool,
            admit=self.config.hot_cache_admit,
            block_bytes=self.config.hot_cache_block_bytes,
            scope=self.scope) \
            if self.config.hot_cache_bytes > 0 else None
        # NVMe spill tier (ISSUE 13 tentpole, strom/delivery/spill.py):
        # evicted-but-warm cache entries demote to a dedicated spill file
        # instead of vanishing; the cache consult serves them back with
        # zero source-engine reads (RAM -> NVMe -> source hierarchy).
        self._spill = None
        if self.config.spill_bytes > 0 and self._hot_cache is not None:
            import tempfile

            from strom.delivery.spill import SpillTier

            sdir = self.config.spill_dir or tempfile.gettempdir()
            os.makedirs(sdir, exist_ok=True)
            self._spill = SpillTier(
                os.path.join(sdir,
                             f"strom-spill-{os.getpid()}-{id(self):x}.bin"),
                self.config.spill_bytes, scope=self.scope,
                compress=self.config.spill_compress)
            if self.config.spill_engine_io and self._scheduler is not None:
                # spill I/O rides the engines (ISSUE 14 satellite):
                # O_DIRECT + background-class grants; attached after the
                # tier so registration sees the created file. The router
                # is ADVISORY — if even buffered registration fails, the
                # tier keeps its legacy fd path (counted, not fatal:
                # a spill tier must never abort context construction)
                try:
                    self._spill.set_io(_SpillEngineIo(
                        self, self._spill.path))
                except OSError:
                    self.scope.add("spill_errors")
            self._hot_cache.spill = self._spill
        # distributed data plane (ISSUE 15 tentpole, strom/dist): the peer
        # extent service. serve_peers() starts the exporter (other hosts
        # read THIS host's hot extents over the socket); attach_peers()
        # wires the client tier the delivery consult probes after local
        # RAM/spill and before the engine. Both None = single-host
        # behavior unchanged.
        self._peer_tier = None
        self._peer_server = None
        self._decoded_cache = None
        # cluster observability plane (ISSUE 18, strom/obs/federation.py):
        # attach_cluster() on the coordinator polls every worker's /stats,
        # merges them and watches fleet health; None = no /cluster route
        self._cluster = None
        # closed-loop knob autotuner (ISSUE 16 tentpole, strom/tune):
        # armed below after every knob surface exists; None until
        # attach_tuner() (config.tune=False = no controller, no thread,
        # every knob byte-identical to the hand configuration)
        self._tuner = None
        # live pipeline surfaces the tuner can steer (ISSUE 19 satellite):
        # pipelines register their decode pool / readahead here at build;
        # standard_knobs() turns whatever is present into knobs. Last
        # registration of a kind wins (one live pipeline per context is
        # the common shape; a rebuilt pipeline re-registers).
        self._tunables: dict = {}
        # in-flight DEMAND gathers (not readahead): the readahead thread
        # checks this between engine-budget-sized slices and yields, so a
        # consumer's read never queues behind more than one warming slice
        self._demand_lock = make_lock("app.demand")
        self._demand_reads = 0
        # one host->HBM stream at a time (see StromConfig.serialize_device_put)
        self._put_lock = make_lock("app.put") if self.config.serialize_device_put \
            else contextlib.nullcontext()
        # live observability endpoint (strom/obs/server.py): /metrics,
        # /stats, /trace on 127.0.0.1 for the context's lifetime. Explicit
        # metrics_port overrides the config knob; 0 from config = off, an
        # explicit 0 asks the OS for an ephemeral port (server.port tells).
        self._metrics_server = None
        # stats()["steps"] attributes only events from THIS context's
        # lifetime: the ring is process-global and never cleared, so an
        # unwindowed summary in a multi-phase process would intersect a
        # later phase's spans against an EARLIER phase's step windows
        self._obs_t0_us = _events_ring.now_us()
        # steps-section cache: full-ring attribution costs ~170ms on a
        # 1-core box, so a scraper polling /metrics must not pay (and
        # steal from decode workers) more than once per TTL
        self._steps_cache: tuple[float, dict] | None = None
        self._steps_cache_lock = make_lock("app.steps_cache")
        # per-bucket stall totals already published as global counters
        # (ISSUE 18 / ROADMAP 5 residual): each steps recompute pushes the
        # window's GROWTH into stall_<bucket>_us counters, so /history's
        # rate() turns the attribution into per-second burn the autotuner
        # can steer on. Guarded by _steps_cache_lock.
        self._stall_published: dict[str, float] = {}
        # flight recorder (ISSUE 6 tentpole, strom/obs/flight.py): with a
        # flight_dir configured, a watchdog samples progress/pressure for
        # the context's lifetime and dumps an atomic crash bundle on
        # SIGTERM / unhandled exception / no-step-progress — the post-
        # mortem for runs that die the way BENCH_r05 did (rc=124, nothing
        # to diagnose). Created BEFORE the live server so /flight can
        # serve the recorder's sample history, not just a point capture.
        self._flight = None
        if self.config.flight_dir:
            from strom.obs.flight import FlightRecorder

            self._flight = FlightRecorder(
                self.config.flight_dir, ctx=self,
                stall_s=self.config.flight_stall_s)
        if self._flight is not None and self._resilience.breaker is not None:
            # breaker trip → flight bundle (ISSUE 9 satellite): the moment
            # the engine is declared sick is exactly the moment an operator
            # wants the trace/stacks/counters that led up to it
            flight = self._flight

            def _dump_on_trip(note: str, _f=flight) -> None:
                with contextlib.suppress(Exception):
                    _f.dump("breaker_trip", note=note)

            self._resilience.breaker.on_trip = _dump_on_trip
        port = self.config.metrics_port if metrics_port is None else metrics_port
        self._history = None
        if port is not None and (port > 0 or metrics_port == 0):
            from strom.obs.server import MetricsServer

            # snapshot history (ISSUE 8 tentpole, strom/obs/history.py):
            # rides with the live server — a process someone can scrape is
            # a process someone will want rates from. Created first so the
            # /history route is live the moment the port is.
            if self.config.history_interval_s > 0:
                from strom.obs.history import StatsHistory

                self._history = StatsHistory(
                    interval_s=self.config.history_interval_s)
            try:
                self._metrics_server = MetricsServer(
                    self.stats, port=port, flight=self._flight, ctx=self)
            except Exception:
                # a failed bind must not leak the sampler/watchdog threads
                # just started for a context that will never exist
                if self._history is not None:
                    self._history.close()
                if self._flight is not None:
                    self._flight.close()
                raise
        # registered LAST: a process-global observer pointing at a context
        # whose __init__ failed would pin the half-built context (and feed
        # its SLO engine from every later request) for the process lifetime
        _request.add_observer(self._slo_observer)
        # knob autotuner (ISSUE 16): armed last — every knob surface
        # (scheduler, cache) exists and the observability endpoint is
        # already live to expose stats()["tune"]. config.tune_profile
        # warm-starts the search from a previous run's converged point.
        if self.config.tune:
            self.attach_tuner(
                profile_path=self.config.tune_profile or None)
        self._closed = False

    @property
    def metrics_server(self):
        """The live endpoint when one was requested (``.port`` carries the
        bound port), else None."""
        return self._metrics_server

    @property
    def flight_recorder(self):
        """The flight recorder when ``flight_dir`` is configured, else
        None (the /flight route still captures on demand without one)."""
        return self._flight

    @property
    def hot_cache(self) -> HotCache | None:
        """The hot-set cache when ``hot_cache_bytes > 0``, else None."""
        return self._hot_cache

    @property
    def spill_tier(self):
        """The NVMe spill tier when ``spill_bytes > 0`` (and a hot cache
        exists), else None (strom/delivery/spill.py)."""
        return self._spill

    @property
    def slo(self):
        """The per-tenant SLO engine (always on — targets default loose;
        customize via ``ctx.slo.set_target(tenant, ...)``)."""
        return self._slo

    @property
    def history(self):
        """The snapshot-history ring when the live server is on (and
        ``history_interval_s > 0``), else None."""
        return self._history

    def _current_goodput(self) -> "float | None":
        """The stall-attribution goodput for SLO goodput targets (rides
        the steps section's TTL cache, so /slo scrapes stay cheap)."""
        try:
            return self.stats(sections=["steps"])["steps"].get("goodput_pct")
        # stromlint: ignore[swallowed-exceptions] -- None IS the documented
        # 'goodput unknown' value (the SLO engine skips goodput targets on
        # it); a closing context mid-scrape is a legal way to not know
        except Exception:
            return None

    def _stall_deltas_locked(self, summary: dict) -> "dict[str, int]":
        """Growth of each stall-attribution bucket's total since the last
        publication, as ``stall_<bucket>_us`` counter increments (caller
        holds ``_steps_cache_lock``; the window is the whole retained ring,
        so a drop-oldest wrap can SHRINK a total — clamp to zero growth and
        re-anchor rather than publish a negative counter delta)."""
        out: dict[str, int] = {}
        for b, v in summary.get("buckets", {}).items():
            total = float(v.get("total_us", 0.0))
            last = self._stall_published.get(b, 0.0)
            if total > last:
                out[f"stall_{b}_us"] = int(total - last)
            self._stall_published[b] = total
        return out

    @property
    def scheduler(self):
        """The multi-tenant I/O scheduler when ``sched_enabled``, else
        None (strom/sched/scheduler.py)."""
        return self._scheduler

    @property
    def resilience(self):
        """The breaker/failover/hedge layer (strom/delivery/resilient.py)."""
        return self._resilience

    def register_tenant(self, name: str, *, priority: str = "training",
                        weight: int = 1, byte_rate: float = 0,
                        byte_burst: float | None = None, iops: float = 0,
                        hot_cache_bytes: int = 0):
        """Register a tenant with the scheduler (priority class, fair-drain
        weight, byte/IOPS budgets) and, when the context has a hot cache,
        carve its per-tenant cache partition. Returns the Tenant handle;
        raises when the scheduler is disabled. Pipelines reference the
        tenant by labeling their scope: ``scope={"pipeline": "resnet",
        "tenant": name}``."""
        if self._scheduler is None:
            raise RuntimeError("sched_enabled=False: no scheduler to "
                               "register tenants with")
        # serialized: two concurrent POST /tenants registers of one name
        # must never interleave the is_registered check with the
        # scheduler-register + cache-partition pair — the loser would carve
        # a partition for a handle whose budgets the winner already
        # customized (partial registration, ISSUE 8 satellite)
        with self._tenant_reg_lock:
            if self._scheduler.is_registered(name):
                # re-register returns the live handle UNCHANGED (scheduler
                # contract: queue state and budget balances survive) — so
                # the cache partition must not silently resize either;
                # applying only the hot_cache_bytes of a new config would
                # diverge scheduler and cache state with no indication
                return self._scheduler.tenant(name)
            t = self._scheduler.register(
                name, priority=priority, weight=weight, byte_rate=byte_rate,
                byte_burst=byte_burst, iops=iops,
                hot_cache_bytes=hot_cache_bytes)
            if hot_cache_bytes and self._hot_cache is not None:
                self._hot_cache.set_partition(name, hot_cache_bytes)
                if self._spill is not None:
                    # the spill carve-out mirrors the RAM one (ISSUE 13):
                    # a tenant's demoted working set is bounded the same
                    # way its resident one is
                    self._spill.set_partition(name, hot_cache_bytes)
            return t

    # -- distributed data plane (ISSUE 15, strom/dist) ----------------------
    @property
    def peer_tier(self):
        """The peer extent client when :meth:`attach_peers` wired one,
        else None (strom/dist/peers.py)."""
        return self._peer_tier

    @property
    def peer_server(self):
        """The peer extent exporter when :meth:`serve_peers` started one,
        else None."""
        return self._peer_server

    def serve_peers(self, port: int = 0, host: str = "127.0.0.1") -> str:
        """Start the peer extent service: a bounded threaded TCP server
        exporting this context's hot-cache/spill extents by their
        ``(path, physical offset)`` keys. Served bytes are billed to a
        background-class ``"peer"`` tenant through the scheduler, so peer
        traffic can never starve local demand. Returns the bound
        ``host:port`` (port 0 = ephemeral); idempotent."""
        if self._closed:
            raise RuntimeError("StromContext is closed")
        if self._peer_server is not None:
            return self._peer_server.addr
        if self._scheduler is not None:
            self.register_tenant("peer", priority="background")
        from strom.dist.peers import PeerServer

        self._peer_server = PeerServer(
            self, host=host, port=port,
            max_conns=self.config.dist_server_max_conns)
        return self._peer_server.addr

    def register_tunable(self, kind: str, obj) -> None:
        """Expose a live pipeline surface (``"decode_pool"``,
        ``"readahead"``) so :func:`strom.tune.standard_knobs` can build a
        knob over it; last registration of a *kind* wins."""
        self._tunables[str(kind)] = obj

    def attach_peers(self, peers, owner_fn=None, directory=None) -> None:
        """Wire the peer tier of the delivery consult: *peers* maps a
        peer name to its ``host:port`` (or is a plain address list);
        *owner_fn* maps a dataset path to the peer name expected to have
        it hot (None/unknown = straight to the engine), or *directory* is
        a live :class:`~strom.dist.directory.ExtentDirectory` — the
        consistent-hash owner map that re-owns a dead host's keys across
        membership epochs (ISSUE 20; it outranks *owner_fn*). Fetch
        failures and timeouts fall back to the local engine read — never
        fatal — and a dead peer trips a per-peer circuit breaker (which
        publishes the death to the directory when one is attached).
        Replaces any previously attached tier; the tier registers itself
        as the ``"peer_tier"`` tunable so the autotuner can steer the
        batch size and pool depth."""
        from strom.dist.peers import PeerTier

        if self._peer_tier is not None:
            self._peer_tier.close()
        self._peer_tier = PeerTier(
            peers, owner_fn=owner_fn, directory=directory,
            scope=self.scope,
            timeout_s=self.config.dist_peer_timeout_s,
            plan=getattr(self.engine, "plan", None),
            compress=self.config.peer_compress,
            batch_max_extents=self.config.dist_batch_max_extents,
            conn_pool_size=self.config.dist_conn_pool_size,
            auth_key=self.config.dist_auth_key)
        self.register_tunable("peer_tier", self._peer_tier)

    @property
    def decoded_cache(self):
        """The DecodedCache registered via :meth:`attach_decoded_cache`
        (the vision pipeline's decode-once tier), else None — the peer
        server exports decoded frames from it (ISSUE 20)."""
        return self._decoded_cache

    def attach_decoded_cache(self, dcache) -> None:
        """Register the pipeline's DecodedCache so the peer server can
        serve decoded frames cluster-wide (kind-1 batch keys carrying the
        decode fingerprint); last registration wins."""
        self._decoded_cache = dcache

    def peer_decoded_fetch(self, ckey) -> "np.ndarray | None":
        """Probe the owning peer for a decoded frame by its DecodedCache
        key ``("jpegdec", path, lo, hi, fingerprint)`` → ``(h, w, 3)``
        uint8 RGB or None. The vision pipeline consults this on a local
        decoded-cache miss BEFORE planning the JPEG extent read — a frame
        decoded once is decoded once per cluster."""
        if self._peer_tier is None:
            return None
        try:
            _, path, lo, hi, fp = ckey
        except (TypeError, ValueError):
            return None
        return self._peer_tier.fetch_frame(str(path), int(lo), int(hi),
                                           str(fp))

    @property
    def cluster_view(self):
        """The metrics-federation view when :meth:`attach_cluster` wired
        one (the coordinator's /cluster route), else None
        (strom/obs/federation.py)."""
        return self._cluster

    def attach_cluster(self, hosts, *, interval_s: float = 1.0,
                       stall_s: float = 10.0, **kwargs):
        """Start the cluster observability plane (ISSUE 18): a background
        loop polling each worker's ``/stats`` endpoint (*hosts* maps host
        id → ``ip:port`` metrics address), merging the fleet into one
        aggregate (served on this context's ``/cluster`` route) and
        flagging hosts whose scrape fails or whose progress stalls —
        an unhealthy transition best-effort-triggers the remote host's
        ``/flight?dump=1`` and dumps this context's own flight recorder.
        Replaces any previous view; returns it."""
        if self._closed:
            raise RuntimeError("StromContext is closed")
        from strom.obs.federation import ClusterView

        if self._cluster is not None:
            self._cluster.close()
        self._cluster = ClusterView(
            hosts, recorder=self._flight, interval_s=interval_s,
            stall_s=stall_s, **kwargs)
        return self._cluster

    @property
    def tuner(self):
        """The closed-loop knob autotuner when ``tune=True`` (or
        :meth:`attach_tuner` was called), else None (strom/tune)."""
        return self._tuner

    def attach_tuner(self, knobs=None, *, profile_path: "str | None" = None,
                     start: bool = True):
        """Arm the closed-loop autotuner (ISSUE 16 tentpole, strom/tune)
        over this context's live knob surfaces — scheduler slice bytes and
        cache budget by default, or an explicit *knobs* list (pipelines
        add prefetch depth via :func:`strom.tune.prefetcher_knob`). The
        controller climbs the stall-attribution goodput and HOLDS whenever
        any tenant's SLO is burning or goodput is not yet measurable — it
        never experiments blind or on a tenant already missing its target.
        *profile_path* warm-starts from a saved :class:`strom.tune.Profile`;
        ``start=False`` builds the controller without the driver thread
        (the caller beats it with ``step()``). Idempotent."""
        if getattr(self, "_closed", False):
            raise RuntimeError("StromContext is closed")
        if self._tuner is not None:
            return self._tuner
        from strom.tune import Autotuner, Profile, standard_knobs

        ks = list(knobs) if knobs is not None else standard_knobs(self)
        name = "default"
        if profile_path:
            name = os.path.splitext(os.path.basename(profile_path))[0]
        tuner = Autotuner(
            ks, self._tune_metrics,
            interval_s=self.config.tune_interval_s,
            guard_frac=self.config.tune_guard_frac,
            scope=self.scope, profile_name=name)
        if profile_path and os.path.exists(profile_path):
            tuner.apply_profile(Profile.load(profile_path))
        self._tuner = tuner
        if start:
            tuner.start()
        return tuner

    def _tune_metrics(self) -> dict:
        """The autotuner's objective: stall-attribution goodput (rides the
        steps section's TTL cache). No goodput yet (no step windows) reads
        as a hold — the controller must never experiment without a signal
        to judge the trial by."""
        goodput = self._current_goodput()
        burning = bool(self._slo.stats().get("slo_tenants_burning", 0))
        metrics = {"objective": float(goodput or 0.0),
                   "slo_burning": burning or goodput is None}
        # windowed stall-attribution burn (ISSUE 18 satellite / ROADMAP 5
        # residual): the per-bucket counters the steps recompute publishes,
        # turned into per-second rates by the history ring — the controller
        # sees WHERE the stall time goes, not just the goodput scalar
        if self._history is not None:
            from strom.obs.stall import BUCKETS

            for b in BUCKETS:
                r = self._history.rate(f"stall_{b}_us", window_s=30.0)
                if r is not None:
                    metrics[f"stall_{b}_us_per_s"] = r
        return metrics

    @contextlib.contextmanager
    def engine_exclusive(self, nbytes: int = 0, tenant: str | None = None):
        """Exclusive use of the engine's transfer path for a raw
        engine-level caller (the stress harness, tooling): a scheduler
        grant when one exists, the legacy engine lock otherwise."""
        if self._scheduler is not None:
            with self._scheduler.grant(tenant, nbytes):
                yield
        else:
            with self._engine_lock:
                yield

    @contextlib.contextmanager
    def _demand_gate(self):
        """Marks a DEMAND engine gather in flight (readahead yields to it)."""
        with self._demand_lock:
            self._demand_reads += 1
        try:
            yield
        finally:
            with self._demand_lock:
                self._demand_reads -= 1

    def _demand_active(self) -> bool:
        with self._demand_lock:
            return self._demand_reads > 0

    # -- file registry ------------------------------------------------------
    def file_index(self, path: str, *, writable: bool = False) -> int:
        """Engine file index for *path*, registered lazily. ``writable=True``
        (ISSUE 13) registers a separate read-write index — write ops
        (``ctx.pwrite``, checkpoint saves, dataset writers) ride it; the
        read-only registration (and its o_direct probe state) is left
        untouched."""
        with self._files_lock:
            table = self._wfiles if writable else self._files
            idx = table.get(path)
            if idx is None:
                idx = self.engine.register_file(
                    path, o_direct=self.config.o_direct, writable=writable)
                table[path] = idx
            return idx

    def invalidate_file(self, path: str, *,
                        registrations: bool = True) -> None:
        """Forget everything cached about *path* (ISSUE 13): hot-cache and
        spill entries (the bytes changed — a write landed), the FIEMAP
        extent map, and (``registrations=True``) the engine file
        registrations — required when the path now names a DIFFERENT inode
        (a tmp+rename commit), where a cached fd would keep reading the
        old file forever. In-place writers (:meth:`pwrite`) keep their
        registrations: the inode is the same, only the cached bytes lie."""
        idxs: list[int] = []
        with self._files_lock:
            self._extent_maps.pop(path, None)
            if registrations:
                for table in (self._files, self._wfiles):
                    idx = table.pop(path, None)
                    if idx is not None:
                        idxs.append(idx)
        for idx in idxs:
            with contextlib.suppress(Exception):
                self.engine.unregister_file(idx)
        if self._hot_cache is not None:
            # cascades to the spill tier (a spill tier only exists under a
            # hot cache); derived tuple keys (decoded frames) drop too
            self._hot_cache.invalidate(path)

    def register_striped(self, path: str, striped: "StripedFile | Sequence[str]",
                         chunk: int | None = None,
                         size: int | None = None) -> StripedFile:
        """Alias *path* to a RAID0 striped set: every read addressed to the
        path — including extents a format reader planned against it — is
        stripe-decoded across the members. The userspace twin of mounting a
        filesystem on an md-raid0 array: files keep ordinary names while the
        block layer stripes underneath (SURVEY.md §2.2 "md-raid0 decode").
        """
        if isinstance(striped, StripedFile):
            # don't silently drop the extra args against a prebuilt instance
            if chunk is not None and chunk != striped.chunk:
                raise ValueError(
                    f"chunk={chunk} conflicts with StripedFile.chunk="
                    f"{striped.chunk}; pass one or the other")
            if size is not None:
                striped = dataclasses.replace(striped, size_bytes=size)
        else:
            if chunk is None:
                # the stripe chunk is a property of how the members were
                # WRITTEN; defaulting it (e.g. to the IO block size) would
                # de-interleave with the wrong geometry and return
                # byte-shuffled data with no error
                raise ValueError("chunk is required when registering a "
                                 "member list: it must match the chunk the "
                                 "set was striped with")
            striped = StripedFile(tuple(striped), chunk, size)
        with self._files_lock:
            self._striped[path] = striped
        return striped

    def striped_source(self, path: str) -> StripedFile | None:
        """The StripedFile aliased to *path*, if any."""
        with self._files_lock:
            return self._striped.get(path)

    def resolve_source(self, source: "Source") -> "Source":
        """*source* with any registered striped alias applied."""
        if isinstance(source, str):
            with self._files_lock:
                return self._striped.get(source, source)
        return source

    def _on_slab_alloc(self, base: np.ndarray) -> None:
        """Fresh pool slab: NUMA-place it, then register it with the engine
        so gathers into it ride READ_FIXED (pages pinned once at
        registration, not per IO — the reference pins its DMA window once at
        MAP_GPU_MEMORY for the same reason, SURVEY.md §3.2). Registration
        lives exactly as long as the slab's mmap; recycled slabs stay
        registered."""
        if self._numa is not None:
            self._numa.bind(base)
        if self.engine.register_dest(base) >= 0:
            import weakref

            from strom.delivery.buffers import buf_addr

            # finalizer args must not reference the array (a strong ref would
            # keep the mmap alive and the finalizer would never run): key the
            # unregistration by raw address, fired when the mmap dies
            weakref.finalize(base.base, self.engine.unregister_dest_addr,
                             buf_addr(base))

    @staticmethod
    def _numa_path(source: "Source") -> str | None:
        """A representative file path for NUMA node discovery."""
        if isinstance(source, str):
            return source
        if isinstance(source, StripedFile):
            return source.members[0]
        if isinstance(source, ExtentList) and len(source):
            return source.extents[0].path
        return None

    def device_put(self, arr: np.ndarray, device: Any) -> Any:
        """One host->device dispatch under the context's put policy: the
        `serialize_device_put` lock (concurrent puts interleave poorly on a
        shared host link) and the ``strom.device_put`` span. Pipelines
        route their per-device shard puts here — including the decode
        path's overlapped per-group puts — so every host->HBM byte obeys
        one policy."""
        import jax

        with self._put_lock, _request.span("strom.device_put", cat="put"):
            return jax.device_put(arr, device)

    def extent_map(self, path: str) -> list | None:
        """Cached FIEMAP extent map for *path* (None: unavailable)."""
        with self._files_lock:
            if path in self._extent_maps:
                return self._extent_maps[path]
        from strom.probe.fiemap import fiemap

        try:
            em = fiemap(path)
        except OSError:
            em = None
        with self._files_lock:
            self._extent_maps[path] = em
        return em

    # -- raw range read into a fresh aligned slab ---------------------------
    def _plan_chunks(self, source: "Source", segments: Sequence[Segment],
                     base_offset: int = 0
                     ) -> tuple[list[tuple[int, int, int, int]],
                                dict[int, str]]:
        """Expand logical (file_offset+base_offset → dest_offset) segments
        into the physical (file_index, file_offset, dest_offset, length)
        chunk list an engine gather executes: striped-alias resolution,
        segment/op coalescing, stripe windows, and extent-aware ordering all
        applied. Shared by the blocking read path (:meth:`_read_segments`)
        and the completion-driven streaming path
        (:class:`strom.delivery.stream.StreamingGather`) so their plans can
        never drift. Returns ``(chunks, idx_paths)`` where *idx_paths* maps
        file indexes back to paths (hot-cache keys, FIEMAP lookups)."""
        cfg = self.config
        source = self.resolve_source(source)
        if self._numa is not None:
            # pin THIS thread (the engine submit path runs on it) to the
            # device's home node; once per thread, resolved from the source
            self._numa.ensure_thread(self._numa_path(source))

        if cfg.coalesce_max_bytes and len(segments) > 1:
            # merge caller fragments that are file+dest contiguous BEFORE
            # expansion: a merged logical run stripes/extent-splits as one
            # piece instead of per fragment
            segments = coalesce_segments(segments, cfg.coalesce_max_bytes)

        # member fds resolved once per transfer, not once per extent run (a
        # WDS batch produces one run per sample component)
        member_cache: dict[StripedFile, list[int]] = {}
        idx_paths: dict[int, str] = {}  # file_idx -> path (for FIEMAP lookup)

        def findex(path: str) -> int:
            idx = self.file_index(path)
            idx_paths[idx] = path
            return idx

        def stripe_chunks(sf: StripedFile, file_off: int, dest_off: int,
                          length: int) -> None:
            member_idx = member_cache.get(sf)
            if member_idx is None:
                member_idx = [findex(m) for m in sf.members]
                member_cache[sf] = member_idx
            segs = plan_stripe_reads(file_off, length, len(sf.members),
                                     sf.chunk)
            wb = cfg.resolved_stripe_window_bytes
            if wb > 0 and len(sf.members) > 1 and length > wb:
                # striped-read overlap: per-member sequential runs inside
                # windows of the in-flight budget — ops for window N+1 enter
                # the queue while window N's completions drain, instead of
                # a chunk-granular round-robin hopping members every
                # raid_chunk bytes (see plan_stripe_windows)
                self.scope.add("stripe_windows",
                                 count_stripe_windows(segs, len(sf.members),
                                                      wb))
                segs = plan_stripe_windows(segs, len(sf.members), wb)
                self.scope.set_gauge("stripe_overlap_window_bytes", wb)
            for s in segs:
                chunks.append((member_idx[s.member], s.member_offset,
                               dest_off + (s.logical_offset - file_off),
                               s.length))

        # Expand logical segments to physical (file_index, offset) chunks.
        chunks: list[tuple[int, int, int, int]] = []  # (file_idx, file_off, dest_off, len)
        if isinstance(source, StripedFile):
            for seg in segments:
                stripe_chunks(source, base_offset + seg.file_offset,
                              seg.dest_offset, seg.length)
        elif isinstance(source, ExtentList):
            # striped-alias runs buffer per StripedFile and coalesce BEFORE
            # stripe expansion: adjacent extents over one alias (consecutive
            # column chunks, back-to-back tar members) become one logical
            # run, which then stripes — and windows — as a whole instead of
            # per fragment. Plain-path runs merge later at the op level.
            striped_runs: dict[StripedFile, list[Segment]] = {}
            for seg in segments:
                for r in source.locate(base_offset + seg.file_offset, seg.length,
                                       seg.dest_offset):
                    sf = self.striped_source(r.path)
                    if sf is not None:
                        # extent planned against an aliased path: stripe-decode
                        # it here, exactly where a plain path resolves to an fd
                        striped_runs.setdefault(sf, []).append(
                            Segment(r.offset, r.dest_offset, r.length))
                    else:
                        chunks.append((findex(r.path), r.offset,
                                       r.dest_offset, r.length))
            for sf, runs in striped_runs.items():
                if cfg.coalesce_max_bytes and len(runs) > 1:
                    n_in = len(runs)
                    runs = coalesce_segments(runs, cfg.coalesce_max_bytes)
                    self.scope.add("coalesce_ops_in", n_in)
                    self.scope.add("coalesce_ops_out", len(runs))
                    self.scope.set_gauge("coalesce_ops_in_last", n_in)
                    self.scope.set_gauge("coalesce_ops_out_last", len(runs))
                for s in runs:
                    stripe_chunks(sf, s.file_offset, s.dest_offset, s.length)
        else:
            chunks = [(findex(source), base_offset + s.file_offset,
                       s.dest_offset, s.length) for s in segments]

        if cfg.coalesce_max_bytes and len(chunks) > 1 and not member_cache:
            # op-level coalescing: per-extent-run fragments (tar members,
            # column chunks, record runs) that landed adjacent in both file
            # and dest space become one engine op. Striped gathers are
            # exempt (member ops interleave by design; merging would need
            # non-contiguous dests) — their fragment merging happened at the
            # segment level above, before stripe expansion.
            n_in = len(chunks)
            chunks = coalesce_chunks(chunks, cfg.coalesce_max_bytes)
            self.scope.add("coalesce_ops_in", n_in)
            self.scope.add("coalesce_ops_out", len(chunks))
            self.scope.set_gauge("coalesce_ops_in_last", n_in)
            self.scope.set_gauge("coalesce_ops_out_last", len(chunks))

        if chunks and not member_cache:
            # extent-aware planning for plain-file gathers of every source
            # kind (whole-file reads AND format-reader ExtentLists): group
            # into per-file runs, each submitted in physical-address order.
            # Striped gathers are exempt: the engine submits in list order
            # within a queue-depth window, so regrouping the round-robin
            # member interleave into per-member runs would serialize the
            # very multi-device parallelism RAID0 exists for.
            from strom.delivery.chunk_plan import plan_chunks_multi

            maps = {}
            for fi, p in idx_paths.items():
                em = self.extent_map(p)
                if em:
                    maps[fi] = em
            if maps:
                chunks = plan_chunks_multi(chunks, maps)
        return chunks, idx_paths

    def _consult_cache(self, cache, chunks: list[tuple[int, int, int, int]],
                       idx_paths: dict[int, str],
                       dflat: "np.ndarray | None", *, warm: bool = False,
                       tenant: "str | None" = None
                       ) -> tuple[list[tuple[int, int, int, int]], int,
                                  list[tuple[int, int]]]:
        """Hot-set cache consult (ISSUE 4 tentpole): split every physical
        chunk into cached ranges (memcpy'd from RAM into *dflat* under a pin
        that blocks eviction) and miss runs (the only ops the engine sees).
        Full hit => the engine is skipped entirely. Returns ``(miss_chunks,
        hit_bytes, hit_ranges)`` — *hit_ranges* are the dest [lo, hi) spans
        served from RAM, which the streaming path reports as INSTANT
        completions. ``warm=True`` (readahead) records nothing and never
        copies (*dflat* may be None).

        With a spill tier attached (ISSUE 13), RAM misses probe the spill
        file next: spill-resident ranges pread from local NVMe into *dflat*
        (and re-offer themselves for RAM promotion — the hierarchy works in
        both directions), never reaching the source engine and never
        counting as ``cache_miss_bytes``; only TRUE misses (neither tier)
        do.

        With a peer tier attached (ISSUE 15, ``ctx.attach_peers``), TRUE
        misses probe the PEERS last — RAM → spill → peer → engine: an
        extent hot on another host arrives over the socket (and promotes
        into the local cache) instead of a duplicate SSD read. Peer-served
        bytes count as hits, never as ``cache_miss_bytes``; a fetch
        failure/timeout/open-breaker falls through to the engine. *cache*
        may be None here (a peered context without a hot cache still
        probes peers); ``warm=True`` never probes peers — readahead must
        not generate network traffic."""
        cache_hit = 0
        peer_hit = 0
        t0 = _events_ring.now_us()
        miss_chunks: list[tuple[int, int, int, int]] = []
        hit_ranges: list[tuple[int, int]] = []
        pinned: list = []
        spill = getattr(cache, "spill", None) if cache is not None else None
        peers = self._peer_tier if (not warm and dflat is not None) else None
        spill_served = 0

        # peer fabric v2 (ISSUE 20): true misses are COLLECTED during the
        # tier walk and resolved in one fetch_many after it — a gather's
        # worth of peer candidates rides the batched wire (one round trip
        # per owner chunk) instead of one synchronous exchange per range
        peer_pending: list = []

        def true_miss(fi: int, path, fo: int, do: int, s: int, t: int, *,
                      deferred: bool) -> None:
            """Neither RAM nor spill holds [s, t): queue it for the peer
            tier (resolved in a batch below), engine on miss. *deferred* =
            the cache lookup left miss counting to us (a peer hit must not
            read as a cache miss)."""
            if peers is not None and path is not None:
                peer_pending.append((fi, path, fo, do, s, t, deferred))
                return
            miss_chunks.append((fi, s, do + (s - fo), t - s))
            if deferred and cache is not None and not warm:
                cache.note_miss(t - s)

        for fi, fo, do, ln in chunks:
            path = idx_paths.get(fi)
            if path is None:  # untracked fd: bypass the cache
                miss_chunks.append((fi, fo, do, ln))
                continue
            if cache is None:
                # no hot cache, peers attached: every range is a RAM/spill
                # miss by construction
                true_miss(fi, path, fo, do, fo, fo + ln, deferred=False)
                continue
            hits, misses, pins = cache.lookup(
                path, fo, fo + ln, record=not warm,
                count_misses=spill is None and peers is None)
            pinned.extend(pins)
            for s, t, view in hits:
                if not warm:  # warm mode discards dest: skip the copy
                    dflat[do + (s - fo): do + (t - fo)] = view
                    hit_ranges.append((do + (s - fo), do + (t - fo)))
                cache_hit += t - s
            if spill is None:
                for s, t in misses:
                    true_miss(fi, path, fo, do, s, t,
                              deferred=peers is not None)
                continue
            for s, t in misses:
                sp_hits, sp_misses = spill.lookup(path, s, t,
                                                  record=not warm)
                try:
                    for ss, tt, ent in sp_hits:
                        if warm:
                            # readahead-driven spill→RAM promotion
                            # (ISSUE 14 satellite, ROADMAP item 2
                            # residual c): an upcoming-window range that
                            # is spill-resident promotes NOW — one local
                            # NVMe read on the warm thread instead of a
                            # demand-path serve+promote later. Still
                            # never a source-engine read; failures
                            # degrade to the old skip (the demand path
                            # serves it from spill).
                            n = tt - ss
                            tmp = np.empty(n, np.uint8)
                            try:
                                spill.read_into(ent, ss, tt, tmp)
                                promoted = cache.admit(
                                    path, ss, tt, tmp, force=True,
                                    tenant=tenant)
                            except OSError:
                                promoted = 0
                            if promoted:
                                spill.note_promote(promoted)
                            cache_hit += n
                            continue
                        d_lo = do + (ss - fo)
                        spill.read_into(ent, ss, tt,
                                        dflat[d_lo: d_lo + (tt - ss)])
                        hit_ranges.append((d_lo, d_lo + (tt - ss)))
                        cache_hit += tt - ss
                        spill_served += tt - ss
                        # promote back to RAM (admission policy applies):
                        # hot reuse graduates up the hierarchy, one memcpy
                        cache.admit(path, ss, tt,
                                    dflat[d_lo: d_lo + (tt - ss)],
                                    tenant=tenant)
                finally:
                    spill.unpin([e for _, _, e in sp_hits])
                for ss, tt in sp_misses:
                    true_miss(fi, path, fo, do, ss, tt, deferred=True)
        if peer_pending:
            results = peers.fetch_many(
                [(path, s, t) for _, path, _, _, s, t, _ in peer_pending])
            for (fi, path, fo, do, s, t, deferred), data in zip(
                    peer_pending, results):
                if data is not None:
                    d_lo = do + (s - fo)
                    dflat[d_lo: d_lo + (t - s)] = data
                    hit_ranges.append((d_lo, d_lo + (t - s)))
                    cache_hit += t - s
                    peer_hit += t - s
                    if cache is not None:
                        # promote like a spill hit: the NEXT request is a
                        # RAM hit, and this host can serve it onward
                        cache.admit(path, s, t,
                                    dflat[d_lo: d_lo + (t - s)],
                                    tenant=tenant)
                    continue
                miss_chunks.append((fi, s, do + (s - fo), t - s))
                if deferred and cache is not None and not warm:
                    cache.note_miss(t - s)
        if cache is not None:
            cache.unpin(pinned)
        if spill_served:
            _request.complete(t0, _events_ring.now_us() - t0,
                              "cache", "spill.serve",
                              {"bytes": spill_served})
        if peer_hit:
            # request-tagged (ISSUE 8 contract): which request rode the
            # peer tier instead of re-reading the SSD
            _request.complete(t0, _events_ring.now_us() - t0,
                              "dist", "peer.serve",
                              {"bytes": peer_hit})
        if cache_hit - peer_hit > 0 and not warm:
            # request-tagged (ISSUE 8): which request the RAM-served bytes
            # belonged to — cache hits are why a "slow path" request isn't
            _request.complete(t0, _events_ring.now_us() - t0,
                              "cache", "cache.serve",
                              {"bytes": cache_hit - peer_hit})
        return miss_chunks, cache_hit, hit_ranges

    def _read_segments(self, source: "Source",
                       segments: Sequence[Segment],
                       dest: "np.ndarray | None",
                       base_offset: int = 0, *, _warm: bool = False,
                       tenant: str | None = None,
                       deadline_s: "float | None" = None) -> int:
        """Read (file_offset+base_offset → dest_offset) segments, chunked at
        block_size, pipelined at queue_depth. Returns total bytes read.
        Raises EngineError on any failed or short chunk.

        The hot-set cache (when configured) is consulted AFTER physical
        expansion — (path, physical offset) is the only key that repeats
        across epochs; logical ExtentList offsets are batch-relative and
        coalescing merges differently per shuffle order — and BEFORE engine
        submission: cached ranges memcpy from RAM into *dest*, only the
        miss runs reach the engine (a full hit skips it entirely), and miss
        bytes are offered for admission once the gather lands.

        ``_warm=True`` is the readahead path: cached ranges are skipped
        (*dest* may be None — a slab is allocated only once misses exist),
        misses are read in engine-budget slices that yield to demand
        gathers, every read byte is force-admitted, and a short pass
        returns quietly instead of raising."""
        cfg = self.config
        if _warm:
            chunks, idx_paths = self._plan_chunks(source, segments,
                                                  base_offset)
            cache = self._hot_cache
            if cache is not None and not cache.enabled:
                cache = None
            if cache is not None and chunks:
                chunks, _, _ = self._consult_cache(
                    cache, chunks, idx_paths, None, warm=True,
                    tenant=tenant)
            return self._warm_read_chunks(chunks, dest, idx_paths, tenant)

        # causal request tracing (ISSUE 8): every demand gather is (or
        # joins) a traced request — the span tree below (plan, cache
        # serve, sched queue/grant, engine slices, admits) shares its
        # req_id, and finish feeds req_lat / the exemplar store / the SLO
        # engine. Nested mint sites (a streamed batch) reuse the
        # enclosing request, so this adds one contextvar read there.
        with _request.active("gather", tenant, owner=self._req_owner) as req:
            # deadline propagation (ISSUE 9): explicit per-call deadline,
            # else the config default — armed once on the request and read
            # by the scheduler's queue waits, the engine's poll loops and
            # the retry scheduler (first writer wins across nested mints).
            # Gather-kind requests only: a pipeline's enclosing batch/step
            # request must not inherit a per-GATHER bound measured from
            # its first nested read (a long healthy batch would blow it),
            # nor be poisoned by one nested pread's tight deadline.
            if req is not None and req.kind == "gather":
                req.set_deadline_s(deadline_s if deadline_s is not None
                                   else (cfg.request_deadline_s or None))
            chunks, idx_paths = self._plan_chunks(source, segments,
                                                  base_offset)
            cache = self._hot_cache
            if cache is not None and not cache.enabled:
                cache = None
            cache_hit = 0
            dflat: np.ndarray | None = None
            if (cache is not None or self._peer_tier is not None) and chunks:
                dflat = dest if dest.ndim == 1 and dest.dtype == np.uint8 \
                    else dest.reshape(-1).view(np.uint8)
                chunks, cache_hit, _ = self._consult_cache(
                    cache, chunks, idx_paths, dflat, tenant=tenant)
            return self._demand_read_chunks(chunks, dest, idx_paths, cache,
                                            dflat, cache_hit, tenant)

    def _demand_read_chunks(self, chunks, dest, idx_paths, cache, dflat,
                            cache_hit: int, tenant: str | None) -> int:
        """Demand half of :meth:`_read_segments` after planning + cache
        consult: execute the miss chunks on the engine (scheduler-arbitrated
        when one exists, billed to *tenant*'s queue/budgets), verify byte
        accounting, offer admissions."""
        # The engine executes the whole gather (block_size chunking, queue
        # -depth pipelining, per-chunk retry, EOF topup): ONE boundary
        # crossing per transfer on the C++ engine (SURVEY.md §3.3 hot loop).
        # Under the multi-tenant scheduler (ISSUE 7) the gather runs as
        # fair-drained slices — one engine grant per ~sched_slice_bytes —
        # so a concurrent tenant's op queues behind at most one slice of
        # this transfer; without it, the legacy whole-transfer lock.
        cfg = self.config
        planned = sum(ln for (_, _, _, ln) in chunks)
        total = 0
        if chunks:
            with self._demand_gate(), \
                    _request.span("strom.read_segments", cat="read",
                                  args={"ops": len(chunks),
                                        "bytes": planned}):

                def primary() -> int:
                    if self._scheduler is not None:
                        return self._scheduler.read_chunks(
                            chunks, dest, tenant=tenant,
                            retries=cfg.io_retries)
                    with self._engine_lock:
                        return self.engine.read_vectored(
                            chunks, dest, retries=cfg.io_retries)

                def arbitrate(read_slice):
                    # failover reads stay under the tenant's arbitration —
                    # budgets charged, fair-drain queued, and PRE-EMPTIBLE
                    # at slice granularity like the primary path (PR 7's
                    # starvation bound must hold exactly when the system
                    # is degraded): the breaker reroutes the ENGINE, not
                    # the multi-tenant contract
                    if self._scheduler is None:
                        return read_slice(chunks)
                    total_fb = 0
                    for sl in self._scheduler.iter_slices(chunks):
                        nb = sum(ln for (_, _, _, ln) in sl)
                        with self._scheduler.grant(tenant, nb):
                            total_fb += read_slice(sl)
                    return total_fb

                try:
                    # breaker + failover (ISSUE 9): outcomes feed the
                    # per-engine circuit breaker; the gather that trips it
                    # (and every gather while it is open) reroutes to the
                    # python fallback engine instead of failing the caller
                    if self._resilience is not None:
                        total = self._resilience.execute(
                            primary, chunks, dest, idx_paths,
                            arbitrate=arbitrate)
                    else:
                        total = primary()
                except (DeadlineExceeded, EngineStallError):
                    # typed failures keep their class: callers (and tests)
                    # distinguish a deadline miss / stall diagnosis from a
                    # media error
                    raise
                except EngineError as e:
                    raise EngineError(e.errno,
                                      f"ssd2tpu {e.strerror}") from None
            if total != planned:
                # cheap insurance: any engine accounting bug (short read the
                # engine failed to flag) surfaces loudly instead of as a
                # zero-tailed jax array
                raise EngineError(
                    errno.EIO,
                    f"ssd2tpu read {total} bytes, planned {planned}")
            if cache is not None:
                # admission offer (second-touch policy decides): the engine
                # already landed the bytes in dest, so admitting is one
                # memcpy into a cache-owned slab, never an extra read
                t0a = _events_ring.now_us()
                admitted = 0
                for fi, fo, do, ln in chunks:
                    path = idx_paths.get(fi)
                    if path is not None:
                        admitted += cache.admit(path, fo, fo + ln,
                                                dflat[do: do + ln],
                                                tenant=tenant)
                if admitted:
                    _request.complete(t0a, _events_ring.now_us() - t0a,
                                      "cache", "cache.admit",
                                      {"bytes": admitted})
        self.scope.add("ssd2tpu_bytes", total + cache_hit)
        return total + cache_hit

    def _warm_read_chunks(self, chunks: list[tuple[int, int, int, int]],
                          dest: np.ndarray, idx_paths: dict[int, str],
                          tenant: "str | None" = None) -> int:
        """Readahead engine path: read miss chunks in slices of the
        in-flight budget (queue_depth x block_size), force-admitting each
        slice, yielding to demand gathers between slices — a demand read
        queues behind at most ONE warming slice. Advisory: engine errors
        and short passes end the warm quietly (the demand path will report
        them with full context if they matter)."""
        cache = self._hot_cache
        cfg = self.config
        if cache is None or not chunks:
            return 0
        # dest is allocated LAZILY, only once there are actual misses: in
        # steady state (window fully warm) the readahead poll must cost a
        # cache consult and nothing else — no slab churn, no memcpy
        acquired: np.ndarray | None = None
        if dest is None:
            span = max(do + ln for (_, _, do, ln) in chunks)
            if self._scheduler is not None:
                # slab-pool admission control (ISSUE 7): a warm slab is
                # BACKGROUND-class memory — under high-water pressure it
                # queues (bounded; a failed admit skips this warm pass)
                # instead of crowding demand tenants out of the pool
                if not self._scheduler.admission.admit(span, timeout_s=5.0):
                    return 0
            dest = acquired = self._slab_pool.acquire(span) \
                if self._slab_pool is not None else alloc_aligned(span)
        try:
            dflat = dest if dest.ndim == 1 and dest.dtype == np.uint8 \
                else dest.reshape(-1).view(np.uint8)
            budget = max(cfg.queue_depth * cfg.block_size, cfg.block_size)
            total = 0
            i = 0
            while i < len(chunks):
                if self._demand_active():
                    cache.note_yield()
                    break
                batch: list[tuple[int, int, int, int]] = []
                b = 0
                while i < len(chunks) and b < budget:
                    batch.append(chunks[i])
                    b += chunks[i][3]
                    i += 1
                t0 = _events_ring.now_us()
                try:
                    if self._scheduler is not None:
                        # readahead demotes to the lowest class
                        # automatically: a demand gather of ANY tenant
                        # outranks every warm slice in the fair drain
                        n = self._scheduler.read_chunks(
                            batch, dest, tenant="readahead",
                            retries=cfg.io_retries, priority="background")
                    else:
                        with self._engine_lock:
                            n = self.engine.read_vectored(
                                batch, dest, retries=cfg.io_retries)
                except EngineError:
                    break
                _events_ring.complete(t0, _events_ring.now_us() - t0, "cache",
                                      "cache.readahead", {"bytes": n})
                if n != b:
                    break
                for fi, fo, do, ln in batch:
                    path = idx_paths.get(fi)
                    if path is not None:
                        # admitted bytes charge the OWNING pipeline's
                        # partition (the engine read rode the shared
                        # background "readahead" tenant) — warming must not
                        # bypass the per-tenant cache carve-outs
                        cache.admit(path, fo, fo + ln, dflat[do: do + ln],
                                    force=True, tenant=tenant)
                total += n
        finally:
            if acquired is not None and self._slab_pool is not None:
                self._slab_pool.release(acquired)
        return total

    def alloc_read_buffer(self, source: "Source", nbytes: int) -> np.ndarray:
        """A fresh aligned host buffer for gathers from *source*, NUMA-bound
        the same way ``pread`` binds its slab — the allocation path for
        callers (the streamed batch assembly) that drive the gather
        themselves instead of going through pread."""
        dest = alloc_aligned(nbytes)
        if self._numa is not None and \
                self._numa.resolve(self._numa_path(
                    self.resolve_source(source))) is not None:
            self._numa.bind(dest)
        return dest

    # -- completion-driven streaming gather (ISSUE 5 tentpole) --------------
    def stream_segments(self, source: "Source", segments: Sequence[Segment],
                        dest: np.ndarray, base_offset: int = 0, *,
                        scope=None, tenant: str | None = None):
        """Begin a completion-driven gather of *segments* into *dest*: the
        same plan ``_read_segments`` would execute (striped aliases,
        coalescing, stripe windows, extent-aware ordering, hot-cache
        consult), but submitted through the engine's async vectored API so
        dest ranges surface the moment their extents land — cache hits as
        instant completions, the engine never waited on. Returns a
        :class:`strom.delivery.stream.StreamingGather`; see its docstring
        for the poll/finish/close protocol. The gather owns the engine's
        transfer path (engine lock + demand gate) until finish/close."""
        from strom.delivery.stream import StreamingGather

        if self._closed:
            raise RuntimeError("StromContext is closed")
        return StreamingGather(self, source, segments, dest, base_offset,
                               scope=scope, tenant=tenant)

    def warm(self, source: "Source", segments: Sequence[Segment],
             base_offset: int = 0, *, tenant: "str | None" = None) -> int:
        """Readahead entry point (strom.delivery.hotcache.Readahead): make
        the given ranges cache-resident. Serves nothing — already-cached
        ranges are skipped without a copy, misses are engine-read into a
        throwaway slab and force-admitted. Returns bytes warmed; yields
        (returns 0/short) whenever a demand gather is in flight."""
        if self._hot_cache is None or not self._hot_cache.enabled \
                or self._closed:
            return 0
        if self._demand_active():
            self._hot_cache.note_yield()
            return 0
        if sum(s.length for s in segments) <= 0:
            return 0
        try:
            # dest=None: the warm path allocates a slab only if there are
            # misses to read (a fully-warm window costs a consult, nothing
            # else — see _warm_read_chunks)
            warmed = self._read_segments(source, segments, None, base_offset,
                                         _warm=True, tenant=tenant)
        except (EngineError, OSError, ValueError):
            warmed = 0  # advisory: never turn readahead into a crash
        if warmed:
            self._hot_cache.note_readahead(warmed)
        return warmed

    # -- intra-transfer streaming (read/transfer overlap) -------------------
    def _deliver_streamed(self, source: "Source", segments: Sequence[Segment],
                          base_offset: int, nbytes: int, np_dtype: np.dtype,
                          local_shape: tuple, devices: Sequence[Any],
                          pool, tenant: str | None = None) -> list:
        """Pipeline one transfer internally: the engine reads piece k+1 from
        disk while piece k streams host->HBM, then the pieces are concatenated
        on-device. This is the intra-transfer half of the overlap story —
        round 1 only overlapped ACROSS transfers, and the whole-slab
        read-then-put serialization capped delivered bandwidth at ~55% of raw
        (VERDICT.md missing #1). ≙ the reference consumer's double-buffered
        DMA/compute recycle loop (SURVEY.md §3.5).

        Returns one delivered jax.Array per device in *devices* (replicas get
        the same pieces put to each device)."""
        import jax

        chunk = self.config.overlap_chunk_bytes
        pieces = split_segments(segments, chunk)
        buf_shape = _assembly_shape(nbytes // np_dtype.itemsize,
                                    chunk // np_dtype.itemsize)
        ready: "queue.Queue[tuple[int, np.ndarray] | None]" = queue.Queue(maxsize=2)
        fail: list[BaseException] = []

        def reader() -> None:
            # Reader-side accounting: *idle* time is spent blocked on the
            # consumer (full ready queue, or waiting for a slab the consumer
            # hasn't recycled yet); *read* time is spent in the engine. The
            # disk-side half of the overlap story: a busy link plus an idle
            # reader means the software saturates the link; a busy reader
            # with no idle means the transfer is disk-bound (VERDICT.md r2
            # weak #2 — link_busy_frac alone is one timer wearing two names).
            r_t0 = time.perf_counter()
            idle = 0.0
            read_busy = 0.0
            try:
                for idx, (_, piece_len, piece_segs) in enumerate(pieces):
                    t = time.perf_counter()
                    if pool is not None:
                        slab = pool.acquire(piece_len)  # pool mbinds fresh slabs
                        idle += time.perf_counter() - t
                    else:
                        slab = alloc_aligned(piece_len,
                                             huge=self.config.huge_pages)
                        if self._numa is not None:
                            self._numa.bind(slab)
                    t = time.perf_counter()
                    self._read_segments(source, piece_segs, slab, base_offset,
                                        tenant=tenant)
                    read_busy += time.perf_counter() - t
                    t = time.perf_counter()
                    ready.put((idx, slab))
                    idle += time.perf_counter() - t
                ready.put(None)
            except BaseException as e:  # surfaced on the consumer side
                fail.append(e)
                ready.put(None)
            finally:
                self.scope.add("stream_reader_wall_us",
                               int((time.perf_counter() - r_t0) * 1e6))
                self.scope.add("stream_reader_idle_us", int(idle * 1e6))
                self.scope.add("stream_reader_read_us",
                               int(read_busy * 1e6))

        t = threading.Thread(target=reader, name="strom-stream-reader",
                             daemon=True)
        t.start()
        # Each device assembles into ONE preallocated buffer via donated
        # dynamic_update_slice pastes: peak device memory ~= nbytes + chunk,
        # where accumulating pieces + concatenating would peak at ~2x nbytes.
        bufs = [_alloc_on_device(buf_shape, np_dtype, d) for d in devices]
        row_off = 0
        wall_t0 = time.perf_counter()
        put_busy = 0.0
        try:
            while True:
                item = ready.get()
                if item is None:
                    break
                _, slab = item
                arr_host = slab.view(np_dtype).reshape(-1, *buf_shape[1:])
                with self._put_lock, \
                        _request.span("strom.device_put", cat="put"):
                    put_t0 = time.perf_counter()
                    for i, d in enumerate(devices):
                        piece = jax.device_put(arr_host, d)
                        bufs[i] = _paste(bufs[i], piece, row_off)
                    # serialize: the slab is recycled as soon as the paste
                    # retires, and the read of the NEXT piece overlaps this
                    for b in bufs:
                        b.block_until_ready()
                    put_busy += time.perf_counter() - put_t0
                row_off += arr_host.shape[0]
                if pool is not None:
                    pool.release(slab)
        except BaseException:
            # unblock the reader (bounded queue) before re-raising
            while ready.get() is not None:
                pass
            raise
        finally:
            t.join()
        if fail:
            raise fail[0]
        # Overlap-quality counters: on a link-bound transfer, busy/wall ≈ 1.0
        # means the software kept the host->HBM link saturated throughout.
        self.scope.add("device_put_busy_us",
                       int(put_busy * 1e6))
        self.scope.add("stream_wall_us",
                       int((time.perf_counter() - wall_t0) * 1e6))
        return [_reshape_donated(b, tuple(local_shape)) for b in bufs]

    def _resolve_read_shape(self, source: "Source", offset: int,
                            shape, dtype, length
                            ) -> tuple[tuple[int, ...], np.dtype, int]:
        """(shape, np_dtype, nbytes) for a read request — shared by the
        device and host delivery paths so their length/shape semantics can
        never drift. shape=None → length bytes (length=None → to EOF)."""
        np_dtype = np.dtype(dtype)
        if shape is None:
            if length is None:
                length = source_size(source) - offset
            if length % np_dtype.itemsize:
                raise ValueError(
                    f"length {length} not a multiple of dtype itemsize")
            shape = (length // np_dtype.itemsize,)
        shape = tuple(int(s) for s in shape)
        return shape, np_dtype, math.prod(shape) * np_dtype.itemsize

    # -- the public hot path -------------------------------------------------
    def memcpy_ssd2tpu(self, source: "Source", *,
                       offset: int = 0,
                       shape: Sequence[int] | None = None,
                       dtype: Any = np.uint8,
                       length: int | None = None,
                       sharding: Any = None,
                       device: Any = None,
                       async_: bool = False,
                       pin: bool = False,
                       tenant: str | None = None) -> Any:
        """Read bytes from *source* and deliver them as a jax.Array.

        - shape/dtype: array view of the bytes (row-major on disk). If shape is
          None, length bytes of uint8 (length=None → to EOF).
        - sharding: a jax.sharding.Sharding → global array assembled across the
          mesh; each addressable device reads only its shard's byte ranges.
        - device: single-device destination (exclusive with sharding).
        - async_: return a DMAHandle immediately (≙ MEMCPY_SSD2GPU_ASYNC);
          otherwise return the array (≙ sync MEMCPY_SSD2GPU).
        """
        import jax

        if self._closed:
            raise RuntimeError("StromContext is closed")
        if sharding is not None and device is not None:
            raise ValueError("pass either sharding or device, not both")
        source = self.resolve_source(source)

        if self._numa is not None:
            # resolve the target node BEFORE any slab leaves the pool: a slab
            # allocated pre-resolution would skip its mbind and then recycle
            # with wrong placement for the context's lifetime
            self._numa.resolve(self._numa_path(source))

        shape, np_dtype, nbytes = self._resolve_read_shape(
            source, offset, shape, dtype, length)

        if isinstance(source, str):
            label = f"{source}@{offset}"
        elif isinstance(source, StripedFile):
            label = f"{'+'.join(source.members)}@{offset}"
        else:
            label = f"{source!r}@{offset}"

        def run() -> Any:
            # slab recycling: only when device_put COPIES host bytes (every
            # real accelerator backend; the jax CPU backend aliases instead),
            # and released strictly after the transfer retires. Gate on the
            # TARGET's platform, not the default backend — a CPU destination
            # aliases regardless of what the default device is.
            if sharding is not None:
                target_platform = next(iter(sharding.device_set)).platform
            elif device is not None:
                target_platform = device.platform
            else:
                target_platform = jax.default_backend()
            pool = None if (pin or target_platform == "cpu") else self._slab_pool

            def acquire(n: int) -> np.ndarray:
                if pool is not None:
                    return pool.acquire(n)  # pool mbinds fresh slabs
                arr = alloc_aligned(n, pin=pin, huge=self.config.huge_pages)
                if self._numa is not None:
                    self._numa.bind(arr)
                return arr

            cfg = self.config
            def stream_eligible(n: int) -> bool:
                # safe on every backend: on CPU (device_put aliases host
                # memory) pool is already None, so each piece owns a fresh
                # slab the delivered array keeps alive. A transfer with no
                # safe assembly shape takes the one-put path.
                return (cfg.overlap_chunk_bytes > 0
                        and n >= max(cfg.overlap_min_bytes, cfg.overlap_chunk_bytes)
                        and _assembly_shape(
                            n // np_dtype.itemsize,
                            cfg.overlap_chunk_bytes // np_dtype.itemsize)
                        is not None)

            with _request.span("strom.memcpy_ssd2tpu"):
                if sharding is None:
                    if (self._hot_cache is not None
                            and self._hot_cache.enabled
                            and pool is not None
                            and isinstance(source, str)):
                        # full-hit fast path: the cached slab IS the host
                        # buffer jax serializes from — no dest slab, no
                        # engine, no serve memcpy. The entry stays pinned
                        # until the put RETIRES (block_until_ready), which
                        # is what lets eviction recycle slabs fearlessly;
                        # gated off aliasing backends (pool is None on CPU,
                        # where the delivered array would share bytes with
                        # an evictable slab forever).
                        hit = self._hot_cache.view(source, offset,
                                                   offset + nbytes)
                        if hit is not None:
                            view, entry = hit
                            try:
                                arr_host = view.view(np_dtype).reshape(shape)
                                with self._put_lock, \
                                        _request.span("strom.device_put",
                                                      cat="put"):
                                    out = jax.device_put(arr_host, device)
                                out.block_until_ready()
                            finally:
                                self._hot_cache.unpin([entry])
                            self.scope.add("ssd2tpu_bytes", nbytes)
                            return out
                    if stream_eligible(nbytes):
                        return self._deliver_streamed(
                            source, [Segment(0, 0, nbytes)], offset, nbytes,
                            np_dtype, shape, [device], pool, tenant)[0]
                    dest = acquire(nbytes)
                    self._read_segments(source, [Segment(0, 0, nbytes)],
                                        dest, offset, tenant=tenant)
                    arr_host = dest.view(np_dtype).reshape(shape)
                    with self._put_lock, \
                            _request.span("strom.device_put", cat="put"):
                        out = jax.device_put(arr_host, device)  # device=None → default
                    if pool is not None:
                        out.block_until_ready()
                        pool.release(dest)
                    return out
                plans = plan_sharded_read(shape, np_dtype, sharding)
                groups = dedupe_plans(plans)
                shards = []
                dests = []
                group_items = list(groups.items())

                def deliver_group(segs, group) -> tuple[list, np.ndarray]:
                    dest = acquire(group[0].nbytes)
                    out = []
                    try:
                        self._read_segments(source, list(segs), dest, offset,
                                            tenant=tenant)
                        arr_host = dest.view(np_dtype).reshape(group[0].local_shape)
                        for p in group:
                            with self._put_lock, \
                                    _request.span("strom.device_put",
                                                  cat="put"):
                                out.append(jax.device_put(arr_host, p.device))
                    except BaseException:
                        # recycle the slab on failure: dropping it silently
                        # defeats pool recycling under transient-EIO retry
                        # storms (each retry would fault+mbind fresh pages)
                        if pool is not None:
                            for a in out:  # in-flight puts still read dest
                                with contextlib.suppress(Exception):
                                    a.block_until_ready()
                            pool.release(dest)
                        raise
                    return out, dest

                any_stream = any(stream_eligible(g[0].nbytes)
                                 for _, g in group_items)
                if (len(group_items) > 1 and not any_stream
                        and cfg.delivery_workers > 1):
                    # group-parallel: group k+1's engine read (serialized by
                    # _engine_lock) overlaps group k's host->HBM put — the
                    # only overlap available to small-shard sync transfers,
                    # which the intra-transfer streaming path doesn't cover
                    # (streamed groups keep the sequential arm: they overlap
                    # internally and concurrency would multiply peak memory)
                    futs = [self._group_executor.submit(deliver_group, segs, g)
                            for segs, g in group_items]
                    # drain EVERY future before acting on any error: the old
                    # sequential path could never raise with reads still in
                    # flight, and neither may this one (a caller reacting to
                    # the error — deleting the file, closing the context —
                    # must not race live engine reads)
                    concurrent.futures.wait(futs)
                    first_err = next((f.exception() for f in futs
                                      if f.exception() is not None), None)
                    ok = [f.result() for f in futs if f.exception() is None]
                    if first_err is not None:
                        if pool is not None:
                            # successful groups' slabs go back to the pool
                            # once their puts retire; shards die with us
                            for s, d in ok:
                                for a in s:
                                    a.block_until_ready()
                                pool.release(d)
                        raise first_err
                    for s, d in ok:
                        shards.extend(s)
                        dests.append(d)
                else:
                    for segs, group in group_items:
                        if stream_eligible(group[0].nbytes):
                            shards.extend(self._deliver_streamed(
                                source, list(segs), offset, group[0].nbytes,
                                np_dtype, group[0].local_shape,
                                [p.device for p in group], pool, tenant))
                            continue
                        s, d = deliver_group(segs, group)
                        shards.extend(s)
                        dests.append(d)
                out = jax.make_array_from_single_device_arrays(
                    shape, sharding, shards)
                if pool is not None:
                    for s in shards:
                        s.block_until_ready()
                    for dest in dests:
                        pool.release(dest)
                return out

        if async_:
            return deferred_handle(run, self._executor, nbytes, label)
        return run()

    # -- the delivered path stopped at the device_put boundary --------------
    def memcpy_ssd2host(self, source: "Source", *,
                        offset: int = 0,
                        shape: Sequence[int] | None = None,
                        dtype: Any = np.uint8,
                        length: int | None = None,
                        out: np.ndarray | None = None,
                        tenant: str | None = None) -> np.ndarray:
        """Everything ``memcpy_ssd2tpu`` does UP TO (not including) the
        ``jax.device_put``: striped-alias resolution, extent-aware chunk
        planning, residency routing, and the engine gather — assembled
        zero-copy into the FINAL host array (the staging buffer the blocks
        land in IS the returned array; SURVEY.md §7.4 #1 "the staging buffer
        a block lands in must be the buffer jax serializes from").

        This isolates the framework's host-side cost over a raw engine read:
        on hardware whose host->device link is slower than the SSD, the
        end-to-end delivered/raw ratio measures the link, while
        host-delivered/raw measures the framework (the box-feasible form of
        the >=90%-of-raw target, BASELINE.json:5).

        *out*: preallocated aligned destination of at least the read's size
        (a dest the caller registered with the engine rides READ_FIXED);
        default: a fresh aligned slab.
        """
        if self._closed:
            raise RuntimeError("StromContext is closed")
        source = self.resolve_source(source)
        if self._numa is not None:
            self._numa.resolve(self._numa_path(source))
        shape, np_dtype, nbytes = self._resolve_read_shape(
            source, offset, shape, dtype, length)
        if out is None:
            dest = alloc_aligned(nbytes, huge=self.config.huge_pages)
            if self._numa is not None:
                self._numa.bind(dest)
        else:
            if not out.flags.c_contiguous:
                # reshape(-1) on a strided view would silently produce a
                # COPY: the engine would land bytes the caller never sees,
                # defeating the zero-copy (and READ_FIXED) contract
                raise ValueError("out must be C-contiguous")
            flat = out.reshape(-1).view(np.uint8)
            if flat.nbytes < nbytes:
                raise ValueError(f"out holds {flat.nbytes} bytes, need {nbytes}")
            dest = flat[:nbytes]
        self._read_segments(source, [Segment(0, 0, nbytes)], dest, offset,
                            tenant=tenant)
        return dest.view(np_dtype).reshape(shape)

    # -- host-side range read (format readers: indexes, footers, members) ---
    def pread(self, source: "Source", offset: int = 0,
              length: int | None = None, *,
              tenant: str | None = None,
              deadline_s: "float | None" = None,
              dest: "np.ndarray | None" = None) -> np.ndarray:
        """Read bytes from *source* into a fresh aligned host slab (no device
        transfer). The staging path format readers use for metadata and member
        payloads before decode. *deadline_s* arms a request deadline: the
        read fails fast with DeadlineExceeded instead of waiting out a sick
        engine (default: config ``request_deadline_s``; 0/None = none).
        The deadline binds the gather-kind request this read mints; inside
        an enclosing traced request (a pipeline batch/step) the enclosing
        contract stands and *deadline_s* is not applied. *dest* (a flat
        uint8 array of at least the length) receives the bytes instead of
        a fresh slab: the decode pool's shared memory, for one."""
        if self._closed:
            raise RuntimeError("StromContext is closed")
        source = self.resolve_source(source)
        if length is None:
            length = source_size(source) - offset
        if length == 0:
            return np.empty(0, dtype=np.uint8)
        if dest is not None:
            dest = dest[:length]
        else:
            dest = alloc_aligned(length)
            if self._numa is not None and \
                    self._numa.resolve(self._numa_path(source)) is not None:
                self._numa.bind(dest)
        self._read_segments(source, [Segment(0, 0, length)], dest, offset,
                            tenant=tenant, deadline_s=deadline_s)
        return dest

    # -- the write path (ISSUE 13): host bytes -> SSD through the engine ----
    def write_chunks(self, chunks, src: np.ndarray, *,
                     tenant: "str | None" = None,
                     priority: "str | None" = None) -> int:
        """Execute a planned write scatter — (file_index, file_offset,
        src_offset, length) chunks out of *src* — scheduler-granted when a
        scheduler exists (PR 7 budgets/priority apply to writes), else under
        the legacy engine lock. Feeds the circuit breaker (a sick engine's
        write failures count toward the trip like read failures; writes do
        NOT fail over — a half-written checkpoint on a second engine is
        worse than a loud error, and the tmp+rename commit makes the retry
        unit the whole save). Returns bytes written; raises on short."""
        cfg = self.config
        planned = sum(ln for (_, _, _, ln) in chunks)
        if not chunks:
            return 0
        br = self._resilience.breaker if self._resilience is not None else None
        try:
            with self._demand_gate(), \
                    _request.span("strom.write_chunks", cat="write",
                                  args={"ops": len(chunks),
                                        "bytes": planned}):
                if self._scheduler is not None:
                    total = self._scheduler.write_chunks(
                        chunks, src, tenant=tenant,
                        retries=cfg.io_retries, priority=priority)
                else:
                    with self._engine_lock:
                        total = self.engine.write_vectored(
                            chunks, src, retries=cfg.io_retries)
        except (DeadlineExceeded, EngineStallError):
            raise
        except EngineError as e:
            if br is not None:
                from strom.engine.resilience import classify_errno

                if classify_errno(e.errno or errno.EIO) == "transient":
                    br.record_failure()
            raise EngineError(e.errno, f"host2ssd {e.strerror}") from None
        if br is not None:
            br.record_success()
        if total != planned:
            raise EngineError(errno.EIO,
                              f"host2ssd wrote {total} bytes, "
                              f"planned {planned}")
        self.scope.add("host2ssd_bytes", total)
        return total

    def pwrite(self, path: str, data: "np.ndarray | bytes | memoryview",
               offset: int = 0, *, tenant: "str | None" = None,
               create: bool = True, fsync: bool = False) -> int:
        """Write *data* to ``path[offset:offset+len)`` through the engine
        write path (ISSUE 13) — the write twin of :meth:`pread`. The file
        is created when absent (*create*); *fsync* makes the bytes durable
        before returning (the checkpoint layer's crash-safe commit relies
        on it). Alignment is handled like reads: page-aligned source
        buffers at aligned offsets ride O_DIRECT, anything else falls back
        to the buffered fd inside the engine. Returns bytes written."""
        if self._closed:
            raise RuntimeError("StromContext is closed")
        src = np.frombuffer(data, dtype=np.uint8) \
            if not isinstance(data, np.ndarray) \
            else np.ascontiguousarray(data).reshape(-1).view(np.uint8)
        n = src.nbytes
        if n == 0:
            return 0
        if create and not os.path.exists(path):
            os.close(os.open(path, os.O_WRONLY | os.O_CREAT, 0o644))
        fi = self.file_index(path, writable=True)
        try:
            total = self.write_chunks([(fi, offset, 0, n)], src,
                                      tenant=tenant)
        finally:
            # cached bytes for this path are stale once ANY of the write
            # landed — invalidated AFTER the write (a concurrent read
            # during the write window may have re-admitted pre-write
            # bytes; invalidating first would leave those stale entries
            # servable forever). fds stay valid (same inode), so
            # registrations are kept.
            self.invalidate_file(path, registrations=False)
        if fsync:
            fd = os.open(path, os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)
        return total

    # -- introspection (≙ LIST/INFO_GPU_MEMORY, /proc stats) ----------------
    def buffer_info(self) -> dict:
        return self.engine.buffer_info()

    def stats(self, sections: "Sequence[str] | None" = None) -> dict:
        """Nested per-section stats (the /stats and sections-exposition
        shape). *sections* selects a subset by name — the live endpoint's
        per-section TTL cache uses it so a scrape that only wants counters
        never recomputes the expensive stall-attribution section (ISSUE 6
        satellite). None = every section (the pre-existing contract).
        Known sections: context, decode, stream, steps, cache, spill,
        dist, slab_pool, engine, sched, slo, tune, exemplars, resilience,
        scopes."""
        want = None if sections is None else set(sections)

        def wanted(name: str) -> bool:
            return want is None or name in want

        out: dict = {}
        if wanted("context"):
            out["context"] = {
            "registered_files": len(self._files),
            "ssd2tpu_bytes": global_stats.counter("ssd2tpu_bytes").value,
            # write path (ISSUE 13): bytes landed on media through
            # ctx.pwrite / write_chunks (checkpoint saves, dataset writers)
            "host2ssd_bytes": global_stats.counter("host2ssd_bytes").value,
            # delivery-scheduler observability: op counts before/after
            # coalescing (cumulative + last transfer) and the striped-read
            # overlap window (bytes per window, windows planned)
            "coalesce_ops_in": global_stats.counter("coalesce_ops_in").value,
            "coalesce_ops_out": global_stats.counter("coalesce_ops_out").value,
            "coalesce_ops_in_last":
                global_stats.gauge("coalesce_ops_in_last").value,
            "coalesce_ops_out_last":
                global_stats.gauge("coalesce_ops_out_last").value,
            "stripe_overlap_window_bytes":
                global_stats.gauge("stripe_overlap_window_bytes").value,
            "stripe_windows": global_stats.counter("stripe_windows").value,
            }
        # decode-path observability (vision pipelines; ISSUE 2 tentpole):
        # reduced-scale hit counts per denominator, bytes decoded straight
        # into batch slots, per-sample decode failures absorbed by the
        # zero-image policy, and the decode/put overlap window
        if wanted("decode"):
            dh = global_stats.histogram("decode_batch")
            out["decode"] = {
                "decode_reduced_hits_2":
                    global_stats.counter("decode_reduced_hits_2").value,
                "decode_reduced_hits_4":
                    global_stats.counter("decode_reduced_hits_4").value,
                "decode_reduced_hits_8":
                    global_stats.counter("decode_reduced_hits_8").value,
                "decode_slot_bytes":
                    global_stats.counter("decode_slot_bytes").value,
                "decode_errors": global_stats.counter("decode_errors").value,
                "decode_put_overlap_ms":
                    global_stats.counter("decode_put_overlap_ms").value,
                # decode path v2 (ISSUE 12): native-binding decodes (and
                # per-sample fallbacks to cv2), fused-run dispatch volume,
                # ROI partial decodes with the scanlines they skipped, and
                # decoded-output cache traffic
                "decode_native_imgs":
                    global_stats.counter("decode_native_imgs").value,
                "decode_native_fallbacks":
                    global_stats.counter("decode_native_fallbacks").value,
                "decode_fused_runs":
                    global_stats.counter("decode_fused_runs").value,
                "decode_fused_samples":
                    global_stats.counter("decode_fused_samples").value,
                "decode_roi_hits":
                    global_stats.counter("decode_roi_hits").value,
                "decode_roi_rows_skipped":
                    global_stats.counter("decode_roi_rows_skipped").value,
                "decode_cache_hits":
                    global_stats.counter("decode_cache_hits").value,
                "decode_cache_misses":
                    global_stats.counter("decode_cache_misses").value,
                "decode_cache_hit_bytes":
                    global_stats.counter("decode_cache_hit_bytes").value,
                "decode_cache_admitted_bytes":
                    global_stats.counter("decode_cache_admitted_bytes").value,
                # the two executors (strom/formats/decode_procs.py): samples
                # decoded in worker processes and on threads, worker
                # restarts, and the parent's own time on process tasks
                "decode_proc_samples":
                    global_stats.counter("decode_proc_samples").value,
                "decode_thread_samples":
                    global_stats.counter("decode_thread_samples").value,
                "decode_proc_restarts":
                    global_stats.counter("decode_proc_restarts").value,
                "decode_proc_parent_us":
                    global_stats.counter("decode_proc_parent_us").value,
                "decode_batch_p50_us": dh.percentile(0.50),
                "decode_batch_mean_us": dh.mean_us,
                "decode_batch_total_us": dh.total_us,
                "decode_batch_count": dh.count,
                "decode_batch_hist": list(dh.buckets),
            }
        # intra-batch streaming observability (ISSUE 5 tentpole): batches
        # that took the completion-driven path, the peak async depth, bytes
        # served as instant (cache) completions, the first-decode latency
        # (gather start -> first sample handed to the decode pool) and the
        # tail-extent spread (first -> last completion: the wait the old
        # barrier imposed on EVERY sample; with streaming, work overlapped
        # it). Flat keys, full metric names — same exposition contract as
        # the cache section.
        if wanted("stream"):
            fd = global_stats.histogram("stream_first_decode_lat")
            te = global_stats.histogram("stream_tail_extent")
            out["stream"] = {
                "stream_batches":
                    global_stats.counter("stream_batches").value,
                "stream_inflight_peak":
                    global_stats.gauge("stream_inflight_peak").value,
                "stream_instant_bytes":
                    global_stats.counter("stream_instant_bytes").value,
                "stream_samples_early":
                    global_stats.counter("stream_samples_early").value,
                "stream_first_decode_lat_p50_us": fd.percentile(0.50),
                "stream_first_decode_lat_mean_us": fd.mean_us,
                "stream_first_decode_lat_total_us": fd.total_us,
                "stream_first_decode_lat_count": fd.count,
                "stream_first_decode_lat_hist": list(fd.buckets),
                "stream_tail_extent_p50_us": te.percentile(0.50),
                "stream_tail_extent_mean_us": te.mean_us,
                "stream_tail_extent_total_us": te.total_us,
                "stream_tail_extent_count": te.count,
                "stream_tail_extent_hist": list(te.buckets),
            }
        # per-step stall attribution from the event ring (ISSUE 3 tentpole):
        # goodput_pct + ingest-wait/decode/put/read/compute bucket p50/p99
        # over the step windows retained from THIS context's lifetime —
        # flat keys so the section rides sections_prometheus unchanged.
        # Recomputed at most once per TTL: a full-ring attribution costs
        # ~170ms, which a 10s Prometheus poll must not repeatedly steal
        # from the single core the decode workers share. Section-selective
        # callers (the live endpoint's per-section cache) skip it entirely
        # by leaving "steps" out of *sections*.
        if wanted("steps"):
            from strom.obs import stall

            _STEPS_TTL_S = 2.0
            now = time.monotonic()
            deltas: "dict[str, int] | None" = None
            with self._steps_cache_lock:
                cached = self._steps_cache
                if cached is not None and now - cached[0] < _STEPS_TTL_S:
                    steps = dict(cached[1])
                else:
                    summary = stall.steps_summary(
                        _events_ring.snapshot(), lo_us=self._obs_t0_us)
                    steps = stall.flatten_summary(summary)
                    self._steps_cache = (now, dict(steps))
                    deltas = self._stall_deltas_locked(summary)
            if deltas:
                # counter writes OUTSIDE the cache lock; the delta state
                # above was settled under it, so two racing recomputes
                # can't publish the same growth twice
                for k, d in deltas.items():
                    global_stats.add(k, d)
            steps["events_dropped"] = _events_ring.events_dropped
            out["steps"] = steps
        # hot-set cache observability (ISSUE 4): hit/miss/admission/
        # eviction/readahead counters + hit ratio, keyed with full metric
        # names so the sections exposition types them via the global
        # registry mirror (same contract as the context section)
        if wanted("cache") and self._hot_cache is not None:
            out["cache"] = self._hot_cache.stats()
        if wanted("spill") and self._spill is not None:
            out["spill"] = self._spill.stats()
        # distributed data plane (ISSUE 15): peer-tier client traffic
        # (hits/misses/errors/rtt) + exporter serve counters, keyed by the
        # single-sourced DIST_FIELDS names so the exposition and the bench
        # columns derived from them cannot drift
        if wanted("dist") and (self._peer_tier is not None
                               or self._peer_server is not None):
            d: dict = {}
            if self._peer_tier is not None:
                d.update(self._peer_tier.stats())
            if self._peer_server is not None:
                d.update(self._peer_server.stats())
            out["dist"] = d
        if wanted("slab_pool") and self._slab_pool is not None:
            out["slab_pool"] = self._slab_pool.stats()
        if wanted("engine"):
            out["engine"] = self.engine.stats()
        # multi-tenant scheduler (ISSUE 7): aggregate queue/grant/admission
        # state — per-tenant series reach /metrics as labeled samples via
        # the registry scopes; the /tenants route renders the full rows
        if wanted("sched") and self._scheduler is not None:
            out["sched"] = self._scheduler.stats()
        # per-tenant SLO engine (ISSUE 8): aggregate burn-rate state —
        # per-tenant rows live on /slo, labeled gauges on /metrics
        if wanted("slo"):
            out["slo"] = self._slo.stats()
        # closed-loop autotuner (ISSUE 16): controller state + live knob
        # values, keyed by the single-sourced TUNE_FIELDS names (the /tune
        # route, compare_rounds and strom_top all read this section)
        if wanted("tune") and self._tuner is not None:
            out["tune"] = self._tuner.stats()
        # resilience (ISSUE 9 tentpole): retry/hedge/breaker/failover
        # counters (single-sourced key list RESILIENCE_FIELDS) + the
        # breaker's live state and the fault plan's injection tally when
        # one is wired — the "is this context degraded" section
        if wanted("resilience"):
            from strom.engine.resilience import RESILIENCE_FIELDS

            res: dict = {}
            for k in RESILIENCE_FIELDS:
                if k == "breaker_state":
                    res[k] = global_stats.gauge(k).value
                else:
                    res[k] = global_stats.counter(k).value
            res.update(self._resilience.stats())
            plan_stats = getattr(getattr(self.engine, "plan", None),
                                 "stats", None)
            if plan_stats is not None:
                res["fault_plan"] = plan_stats()
            out["resilience"] = res
        # tail-sampling exemplar store (ISSUE 8): retention counters; the
        # retained span trees themselves ride /flight and crash bundles
        if wanted("exemplars"):
            from strom.obs.exemplars import store as _exemplars

            out["exemplars"] = _exemplars.stats()
        # scoped telemetry (ISSUE 6 tentpole): every label scope's series as
        # {label-string: snapshot} — the JSON twin of the labeled samples
        # /metrics renders; the sections exposition skips it (nested dicts),
        # so labels appear on /metrics exactly once, via the registry.
        if wanted("scopes"):
            out["scopes"] = global_stats.scopes_snapshot()
        return out

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        _request.remove_observer(self._slo_observer)
        # tuner first: its driver thread reads stats()/knob surfaces that
        # are about to be torn down (knobs stay where the search left
        # them — close is not a revert)
        if self._tuner is not None:
            self._tuner.close()
        # cluster view before the servers it scrapes through: its poll
        # thread must stop before the flight recorder it dumps to and the
        # metrics server serving /cluster go away
        if self._cluster is not None:
            self._cluster.close()
        # peer service down first: no new serve can start a cache/spill
        # read (or a scheduler grant) against a closing context, and the
        # consult stops probing peers before the engine goes away
        if self._peer_server is not None:
            self._peer_server.close()
        if self._peer_tier is not None:
            self._peer_tier.close()
        if self._metrics_server is not None:
            self._metrics_server.close()
        if self._history is not None:
            self._history.close()
        if self._flight is not None:
            self._flight.close()
        self._executor.shutdown(wait=True)
        self._group_executor.shutdown(wait=True)
        self._resilience.close()
        self.engine.close()
        if self._spill is not None:
            # after the engine: no gather can be mid-consult anymore
            if self._hot_cache is not None:
                self._hot_cache.spill = None
            self._spill.close()
        if self._witness_enabled_here:
            # revert the witness THIS context turned on: locks already
            # constructed as WitnessLocks keep witnessing (the graph is
            # always live), but later contexts' make_lock sites go back
            # to plain threading.Lock. A context created while this one
            # was open keeps its witnessed locks — edges stay valid.
            from strom.utils.locks import enable_witness

            enable_witness(False)
