"""Vision pipelines over WebDataset shards: ImageNet→ResNet-50 (BASELINE
config #2, BASELINE.json:8) and WebDataset→ViT-B/16 on RAID0 (config #3,
BASELINE.json:9).

Per batch: gather-read the local samples' JPEG members (engine, O_DIRECT),
decode+augment on the host worker pool, device_put each device's rows,
assemble the global sharded array — each host only ever reads and decodes
the rows its own devices consume (SURVEY.md §2.3 "Mesh-sharded delivery").
With the built-in transform and no decoded cache the pool decodes in worker
processes (strom/formats/decode_procs.py): the gather then lands in the
pool's shared memory and the batch's rows are a view of one of its shared
slabs, recycled once the puts from it are ready.

Decode-path scheduling (ISSUE 2 tentpole; knobs `decode_reduced_scale`,
`decode_to_slot`, `decode_overlap_put` in StromConfig): bytes flow
slab → preallocated batch slot → device with no intermediate full-batch
copies — workers decode (reduced-scale when the SOF header allows) straight
into their slot row, and each device's row group is `device_put` the moment
its rows finish decoding (completion-ordered, the per-group analogue of the
streamed delivery in strom/delivery/core.py:_deliver_streamed) instead of
decoding the whole union then transferring serially.
"""

from __future__ import annotations

import bisect
import concurrent.futures
import contextlib
import inspect
import queue as _queue
import threading
import time
from typing import Any, Callable, Sequence

import numpy as np

from strom.delivery.core import StromContext
from strom.delivery.extents import ExtentList
from strom.formats.jpeg import (DecodePool, decode_jpeg,
                                make_train_transform, random_resized_crop)
from strom.obs import request as _request
from strom.formats.wds import WdsShardSet
from strom.pipelines.base import Pipeline, _auto_depth_bounds, resolve_state
from strom.pipelines.sampler import EpochShuffleSampler, SamplerState
from strom.utils.stats import global_stats
from strom.utils.locks import make_lock

# transform(jpeg_bytes, rng[, out=row]) -> HWC uint8; transforms accepting
# an `out=` keyword get direct-to-slot decode (see make_train_transform)
Transform = Callable[..., np.ndarray]


def _make_readahead(ctx: StromContext, sampler: EpochShuffleSampler,
                    extents_for_batch: Callable[[np.ndarray], Any],
                    tenant: "str | None" = None):
    """Epoch-aware readahead for a vision pipeline (ISSUE 4): a background
    thread that pulls the sampler's upcoming-batch window (``peek`` crosses
    the epoch boundary, so next epoch's head warms while this one drains),
    maps each batch to its ExtentList via *extents_for_batch*, and warms
    cache misses through ``ctx.warm`` — which yields to demand gathers.
    None when the hot cache or the readahead window is off."""
    if ctx.hot_cache is None or ctx.config.readahead_window_batches <= 0:
        return None
    from strom.delivery.hotcache import Readahead
    from strom.delivery.shard import Segment

    window_batches = ctx.config.readahead_window_batches

    def window(n: int):
        # n is the Readahead's LIVE window_batches — the autotuner's knob
        # moves it between ticks (ISSUE 19 satellite)
        out = []
        for indices in sampler.peek(max(int(n), 0)):
            el = extents_for_batch(indices)
            if el.size:
                out.append((el, [Segment(0, 0, el.size)], 0))
        return out

    ra = Readahead(ctx, window, tenant=tenant,
                   window_batches=window_batches)
    ctx.register_tunable("readahead", ra)
    return ra


def _chain_close(*closers) -> Callable[[], None] | None:
    """One on_close callable running every non-None closer (readahead dies
    before the decode pool, both before the pipeline returns)."""
    live = [c for c in closers if c is not None]
    if not live:
        return None

    def close() -> None:
        for c in live:
            c()

    return close


def default_train_transform(size: int) -> Transform:
    """Full-scale decode + RandomResizedCrop (the pre-reduced-scale
    behavior, kept for callers that pinned it); pipelines default to
    :func:`strom.formats.jpeg.make_train_transform` instead."""
    def tf(data: bytes, rng: np.random.Generator,
           out: np.ndarray | None = None) -> np.ndarray:
        return random_resized_crop(decode_jpeg(data), size, rng, out=out)

    return tf


def _validate_batch_only(sharding: Any, rank: int = 4) -> None:
    """Image pipelines shard the batch dim only: reject specs that split
    H/W/C at construction with a clear error, instead of failing later
    inside make_array_from_single_device_arrays with an opaque shape
    mismatch (VERDICT.md weak #4)."""
    spec = tuple(sharding.spec) + (None,) * (rank - len(sharding.spec))
    split_inner = [i for i, s in enumerate(spec[1:], start=1) if s is not None]
    if split_inner:
        raise ValueError(
            "vision pipelines deliver batch-dim-sharded images only: "
            f"PartitionSpec {tuple(sharding.spec)} shards inner dim(s) "
            f"{split_inner} (H/W/C must be None/replicated)")


def _local_batch_rows(sharding: Any, batch: int) -> dict:
    """device -> (row_lo, row_hi) of the global batch this host must feed.

    Only valid for batch-dim-only shardings (enforced by
    :func:`_validate_batch_only`); the probe collapses the sharding to its
    batch axis, so each device's index is a contiguous row range."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec0 = sharding.spec[0] if len(sharding.spec) else None
    probe = NamedSharding(sharding.mesh, P(spec0))
    idx_map = probe.addressable_devices_indices_map((batch,))
    out = {}
    for device, index in idx_map.items():
        sl = index[0] if index else slice(None)
        lo, hi, _ = sl.indices(batch)
        out[device] = (lo, hi)
    return out


def _init_group_state(ctx: StromContext, images: np.ndarray,
                      dev_items: Sequence, row_pos: dict,
                      prep: "Callable | None" = None
                      ) -> tuple[list[list[int]], list[int], list]:
    """Per-device completion bookkeeping shared by the overlapped and
    streamed batch paths: which device groups each row feeds, how many
    rows each group still waits on, and pre-put shards for empty row
    ranges (nothing to wait for). *prep* (the compiled OpGraph kernel,
    ISSUE 19) must shape the empty pre-puts too, or their dtype/shape
    diverges from the transformed groups."""
    pos_devs: list[list[int]] = [[] for _ in range(images.shape[0])]
    pending: list[int] = []
    shards: list = [None] * len(dev_items)
    empty = images[0:0] if prep is None else prep(images[0:0])
    for di, (device, (lo, hi)) in enumerate(dev_items):
        for r in range(lo, hi):
            pos_devs[row_pos[r]].append(di)
        pending.append(hi - lo)
        if hi <= lo:  # empty row range: nothing to wait for
            shards[di] = ctx.device_put(empty, device)
    return pos_devs, pending, shards


def _put_rows(ctx: StromContext, rows: np.ndarray, device: Any,
              shared: bool) -> Any:
    """``ctx.device_put`` of one device's row group. Rows in the decode
    pool's shared slab are copied first for a CPU device: the jax CPU
    backend aliases host memory instead of copying it, and the slab is
    rewritten by a later batch."""
    if shared and device.platform == "cpu":
        rows = rows.copy()
    return ctx.device_put(rows, device)


def _note_decode_overlap(scope, t_decode0: float | None,
                         t_first_put: float | None,
                         t_last_decode: float | None) -> None:
    """`decode_batch` histogram + decode/put-overlap counters, emitted
    identically by the overlapped and streamed paths (a fix to the metric
    applies to both or the A/B arms silently diverge). *scope* is the
    pipeline's telemetry scope (scoped series + global aggregate)."""
    if t_decode0 is None or t_last_decode is None:
        return
    scope.observe_us("decode_batch", (t_last_decode - t_decode0) * 1e6)
    if t_first_put is not None and t_last_decode > t_first_put:
        scope.add("decode_put_overlap_ms",
                  int((t_last_decode - t_first_put) * 1000))
        # the overlap window on the timeline: first put fired while decode
        # was still in flight, for this long
        from strom.obs.events import ring

        ring.instant("decode.put_overlap", cat="decode",
                     args={"overlap_ms":
                           round((t_last_decode - t_first_put) * 1e3, 2)})


def _decode_put_overlapped(ctx: StromContext, pool: DecodePool, tf: Transform,
                           blobs: Sequence, rngs: Sequence,
                           images: np.ndarray, dev_items: Sequence,
                           row_pos: dict, scope=None,
                           ckeys: "Sequence | None" = None,
                           prep: "Callable | None" = None,
                           shared: bool = False) -> list:
    """Decode every row into its slot and `device_put` each device's row
    group the moment its rows finish (completion-ordered — the per-group
    analogue of `_deliver_streamed`'s read/transfer overlap: early groups
    ride the host->HBM link while late rows are still on the decode pool).
    Contiguous rows fuse into one pool task each per ``pool.run_size``
    (ISSUE 12): completion granularity coarsens to the run, output bytes
    don't change.

    Returns one put shard per entry of *dev_items*, in order. Observability:
    `decode_batch` histogram (per-batch decode wall), `decode_put_overlap_ms`
    (the window during which puts overlapped in-flight decode). A failed
    task raises only after every task has retired: they write *images*.
    *shared*: *images* is a decode-pool slab (see :func:`_put_rows`)."""
    n = images.shape[0]
    pos_devs, pending, shards = _init_group_state(ctx, images, dev_items,
                                                  row_pos, prep)
    run = pool.run_size(n)
    futs: dict = {}
    if run <= 1:
        for i in range(n):
            futs[pool.submit_into(tf, blobs[i], rngs[i], images[i],
                                  None if ckeys is None else ckeys[i])] = (i,)
    else:
        for i in range(0, n, run):
            grp = tuple(range(i, min(i + run, n)))
            futs[pool.submit_run_into(
                tf, [blobs[j] for j in grp], [rngs[j] for j in grp],
                [images[j] for j in grp],
                None if ckeys is None else [ckeys[j] for j in grp])] = grp
    t0 = time.perf_counter()
    t_first_put = None
    t_last_decode = t0
    for f in concurrent.futures.as_completed(futs):
        if f.exception() is not None:
            # decode ValueErrors are absorbed per-row by the pool; anything
            # else (a transform bug, a lost worker) aborts the batch
            concurrent.futures.wait(futs)
            f.result()
        t_last_decode = time.perf_counter()
        for p in futs[f]:
            for di in pos_devs[p]:
                pending[di] -= 1
                if pending[di] == 0:
                    device, (lo, hi) = dev_items[di]
                    base = row_pos[lo]
                    if t_first_put is None:
                        t_first_put = time.perf_counter()
                    rows = images[base: base + hi - lo]
                    if prep is not None:
                        # fused OpGraph (ISSUE 19): the chain runs per
                        # completed device group, overlapping the remaining
                        # in-flight decode exactly like the put it feeds
                        rows = prep(rows)
                    shards[di] = _put_rows(ctx, rows, device, shared)
    _note_decode_overlap(scope or global_stats, t0, t_first_put,
                         t_last_decode)
    return shards


def _decode_put_streamed(ctx: StromContext, pool: DecodePool, tf: Transform,
                         el, sizes: Sequence[tuple[int, int]],
                         rngs: Sequence, images: np.ndarray,
                         dev_items: Sequence, row_pos: dict, scope=None,
                         ckeys: "Sequence | None" = None,
                         served: "Sequence | None" = None,
                         prep: "Callable | None" = None,
                         buf: "np.ndarray | None" = None,
                         shared: bool = False
                         ) -> tuple[list, list[int]]:
    """Completion-driven batch assembly (ISSUE 5 tentpole): the member
    gather is submitted through ``ctx.stream_segments`` and each sample is
    handed to the decode pool THE MOMENT its extents land (hot-cache hits
    count as instant completions), with per-device shard puts firing
    through the same completion-ordered machinery as
    :func:`_decode_put_overlapped` — read, decode, and put overlapped at
    extent granularity within one batch, instead of gather-ALL → decode-ALL
    → put-ALL.

    *sizes* is ``[(image_bytes, label_bytes)]`` per local row, in the
    logical order *el* concatenates them. Returns ``(img_shards, labels)``
    with identical contents to the barrier path (bit-identity is
    regression-tested): decode order differs, bytes don't.

    Structure: a pump thread drives the gather (poll → per-sample byte
    countdown → decode submit), so the engine's queue refills at read pace
    no matter how long the consumer side spends in device_put; decode
    completions flow back to THIS thread over a queue, which fires each
    device's put the moment its row group finishes decoding.

    *buf* is where the gather lands (the decode pool's shared memory when
    it decodes in processes; default a fresh aligned buffer); *shared* as
    in :func:`_decode_put_overlapped`."""
    from strom.delivery.shard import Segment
    from strom.obs.events import ring

    n = images.shape[0]
    starts: list[int] = []
    ends: list[int] = []
    pos = 0
    for isz, lsz in sizes:
        starts.append(pos)
        pos += isz + lsz
        ends.append(pos)
    remaining = [e - s for s, e in zip(starts, ends)]
    labels: list[int] = [0] * n
    if buf is None:
        buf = ctx.alloc_read_buffer(el, max(el.size, 1))

    pos_devs, pending, shards = _init_group_state(ctx, images, dev_items,
                                                  row_pos, prep)

    events: "_queue.SimpleQueue" = _queue.SimpleQueue()
    stop = threading.Event()
    futs: list = []
    futs_lock = make_lock("app.vision_futs")
    t_decode0: list[float | None] = [None]

    scope = scope or global_stats
    g = ctx.stream_segments(el, [Segment(0, 0, el.size)], buf, scope=scope)
    # the batch's traced request (ISSUE 8): minted by the make_batch
    # wrapper on THIS thread; the pump thread re-enters it so the poll
    # loop's scheduler/cache/decode-dispatch work shares the req_id
    req = _request.current()

    # fused-run dispatch (ISSUE 12): samples whose extents land together
    # decode together — runs are flushed after EVERY poll drain (a lone
    # early sample never waits for company; the streaming overlap is
    # untouched), bounded at run_size so one task can't serialize a
    # fully-instant cache-warm batch on one worker. On worker processes a
    # short run waits for company while the gather is in flight: a task
    # costs the parent two messages, and decode there has cores to spare.
    run = pool.run_size(n)
    hold = pool.offloads(tf)
    ready: list[int] = []

    def mark_ready(i: int) -> None:
        isz, lsz = sizes[i]
        s = starts[i]
        labels[i] = int(buf[s + isz: s + isz + lsz].tobytes() or b"0")
        if t_decode0[0] is None:
            t_decode0[0] = time.perf_counter()
            # gather start -> first decode dispatch: the latency the old
            # barrier padded out to the slowest extent of the batch
            scope.observe_us("stream_first_decode_lat",
                             ring.now_us() - g.t0_us)
        if not g.done:
            # dispatched while later extents were still in flight: the
            # intra-batch overlap, as a counter instead of a guess
            scope.add("stream_samples_early")
        ready.append(i)

    def blob(i: int):
        # a plan-time decoded-cache hit (ISSUE 13 satellite) carries its
        # pinned ServedFrame instead of member bytes (sizes[i][0] == 0 —
        # the image member was never gathered)
        if served is not None and served[i] is not None:
            return served[i]
        return buf[starts[i]: starts[i] + sizes[i][0]]

    def flush_ready() -> None:
        while ready:
            if hold and len(ready) < run and not g.done:
                return
            grp = tuple(ready[:run])
            del ready[: run]
            if len(grp) == 1:
                i = grp[0]
                f = pool.submit_into(tf, blob(i),
                                     rngs[i], images[i],
                                     None if ckeys is None else ckeys[i])
            else:
                f = pool.submit_run_into(
                    tf,
                    [blob(i) for i in grp],
                    [rngs[i] for i in grp], [images[i] for i in grp],
                    None if ckeys is None else [ckeys[i] for i in grp])
            with futs_lock:
                futs.append(f)
            f.add_done_callback(
                lambda fut, g_=grp: events.put(("decoded", g_, fut)))

    def pump() -> None:
        with _request.attach(req):
            _pump()

    def _pump() -> None:
        try:
            # degenerate rows (0-byte image+label members) have no extents
            # to wait for: dispatch them up front, or their countdown never
            # fires and the consumer below blocks forever
            for i in range(n):
                if remaining[i] == 0:
                    mark_ready(i)
            flush_ready()
            while not g.done:
                if stop.is_set():
                    g.close()
                    events.put(("aborted", None))
                    return
                for lo_b, hi_b in g.poll(min_completions=1, timeout_s=0.05):
                    i = max(bisect.bisect_right(starts, lo_b) - 1, 0)
                    while i < n and starts[i] < hi_b:
                        ov = min(hi_b, ends[i]) - max(lo_b, starts[i])
                        if ov > 0:
                            remaining[i] -= ov
                            if remaining[i] == 0:
                                mark_ready(i)
                        i += 1
                flush_ready()
            flush_ready()  # the gather is done: no run waits any longer
            g.finish()
            events.put(("done", None))
        except BaseException as e:  # surfaced on the consumer side
            with contextlib.suppress(Exception):
                g.close()
            events.put(("error", e))

    pt = threading.Thread(target=pump, name="strom-stream-pump", daemon=True)
    pt.start()
    decoded = 0
    gather_done = False
    err: BaseException | None = None
    t_first_put: float | None = None
    t_last_decode: float | None = None
    try:
        while decoded < n or not gather_done:
            kind, *payload = events.get()
            if kind == "decoded":
                grp, fut = payload
                fut.result()  # per-sample decode errors were absorbed by
                # the pool; anything else (a transform bug) aborts the batch
                decoded += len(grp)
                t_last_decode = time.perf_counter()
                for p in grp:
                    for di in pos_devs[p]:
                        pending[di] -= 1
                        if pending[di] == 0:
                            device, (lo, hi) = dev_items[di]
                            base = row_pos[lo]
                            if t_first_put is None:
                                t_first_put = time.perf_counter()
                            rows = images[base: base + hi - lo]
                            if prep is not None:
                                rows = prep(rows)
                            shards[di] = _put_rows(ctx, rows, device,
                                                   shared)
            elif kind == "done":
                gather_done = True
            elif kind == "error":
                err = payload[0]
                break
    except BaseException as e:
        err = e
    finally:
        stop.set()
        pt.join(timeout=30)
        if err is not None:
            # decode workers write into `images` (and read `buf`): both must
            # outlive every in-flight job before the error propagates
            with futs_lock:
                flist = list(futs)
            for f in flist:
                with contextlib.suppress(Exception):
                    f.result()
    if err is not None:
        raise err
    _note_decode_overlap(scope, t_decode0[0], t_first_put, t_last_decode)
    return shards, labels


def make_wds_vision_pipeline(ctx: StromContext, paths: Sequence[str], *,
                             batch: int,
                             image_size: int,
                             sharding: Any,
                             image_ext: str = "jpg",
                             label_ext: str = "cls",
                             transform: Transform | None = None,
                             decode_workers: int = 8,
                             seed: int = 0,
                             shuffle: bool = True,
                             prefetch_depth: int | None = None,
                             auto_prefetch: bool | None = None,
                             decode_reduced_scale: bool | None = None,
                             decode_to_slot: bool | None = None,
                             decode_overlap_put: bool | None = None,
                             decode_cache: bool | None = None,
                             opgraph: Any = None,
                             opgraph_fuse: bool | None = None,
                             stream_intra_batch: bool | None = None,
                             resume_from: "str | SamplerState | object | None" = None,
                             scope: dict | None = None
                             ) -> Pipeline:
    """Infinite stream of (images [B,S,S,3] uint8, labels [B] int32) jax.Array
    pairs sharded per *sharding* (a NamedSharding over a rank-4 image batch;
    labels inherit its batch-dim spec).

    Augmentation is deterministic in (seed, batch serial, row): identical
    across hosts and across checkpoint resume. *resume_from* accepts a
    loader-state path, a SamplerState, or a StepToken (ISSUE 14); a live
    pipeline also restores in place via ``Pipeline.restore(token)``.

    *scope*: telemetry labels for this pipeline (ISSUE 6), refined over the
    context's scope — defaults to ``{"pipeline": "vision"}`` so two
    pipelines on one context surface distinguishable per-scope series on
    /metrics while the unlabeled aggregates stay their sum.

    *opgraph* (ISSUE 19): a :class:`strom.ops.OpGraph` compiled once
    against the decoded sample geometry and run between decode completion
    and ``device_put``. With *opgraph_fuse* (default on) the chain runs per
    completed device group inside the completion-ordered dispatch,
    overlapping remaining decode; ``opgraph_fuse=False`` is the parity
    reference — barrier decode, one batch-wise apply — and produces
    bit-identical batches (the kernel is per-sample deterministic). The
    delivered arrays take the graph's output shape/dtype.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    if not isinstance(sharding, NamedSharding):
        raise TypeError("vision pipelines need a NamedSharding (labels derive "
                        "their spec from its batch axis)")
    if len(sharding.spec) > 4:
        raise ValueError("sharding.spec must have rank <= 4 (B, H, W, C)")
    _validate_batch_only(sharding)
    ss = WdsShardSet(paths, ctx=ctx)
    if len(ss) < batch:
        raise ValueError(f"dataset has {len(ss)} samples < batch {batch}")
    state, fp = resolve_state(tuple(paths), seed=seed, resume_from=resume_from,
                              ctx=ctx)
    sampler = EpochShuffleSampler(len(ss), batch, seed=seed, shuffle=shuffle,
                                  state=state)
    cfg = ctx.config
    reduced = cfg.decode_reduced_scale if decode_reduced_scale is None \
        else decode_reduced_scale
    to_slot = cfg.decode_to_slot if decode_to_slot is None else decode_to_slot
    overlap_put = cfg.decode_overlap_put if decode_overlap_put is None \
        else decode_overlap_put
    # decode path v2: the native turbo binding (cv2 where it did
    # not build), fused-run dispatch and ROI decode are always on; the
    # decoded-output cache is a knob
    use_dcache = cfg.decode_cache if decode_cache is None else decode_cache
    pscope = ctx.scope.scoped(**(scope if scope is not None
                                 else {"pipeline": "vision"}))
    # scheduler tenant (ISSUE 7): a tenant-labeled scope routes every
    # gather this pipeline issues into that tenant's queue (priority,
    # fair-drain weight, budgets, cache partition) — unlabeled pipelines
    # ride the context's default tenant, single-tenant behavior unchanged
    tname = getattr(pscope, "labels", {}).get("tenant")
    # decoded-output cache (ISSUE 12 front 4): only with a hot cache to
    # admit into and only for the built-in transform (custom transforms
    # own their decode; the ckey kwarg is the built-in's contract).
    # Entries charge this pipeline's tenant partition.
    dcache = None
    if use_dcache and transform is None and ctx.hot_cache is not None:
        from strom.formats.decoded_cache import DecodedCache
        from strom.formats import jpeg as _jpeg

        eng = "turbo" if _jpeg.native_available() else "cv2"
        dcache = DecodedCache(ctx.hot_cache, tenant=tname,
                              fingerprint=f"rgb8/{eng}", scope=pscope)
        # peer fabric v2 (ISSUE 20): register the cache so this host's
        # peer server exports decoded frames cluster-wide, and the probe
        # below can pull frames a PEER already decoded
        ctx.attach_decoded_cache(dcache)
    tf = transform or make_train_transform(image_size, reduced_scale=reduced,
                                           dcache=dcache)
    try:
        tf_out_ok = "out" in inspect.signature(tf).parameters
    except (TypeError, ValueError):  # pragma: no cover - exotic callables
        tf_out_ok = False
    # custom transforms without an out= keyword keep the stack path
    to_slot = to_slot and tf_out_ok
    overlap_put = overlap_put and to_slot
    # intra-batch streaming (ISSUE 5): completion-driven read→decode→put
    # dataflow. Rides the slot + overlapped-put mechanics; falls back to
    # the barrier path with either off (bit-identical batches regardless).
    stream = cfg.stream_intra_batch if stream_intra_batch is None \
        else stream_intra_batch
    stream = stream and overlap_put
    # fused per-sample operator graph (ISSUE 19 front 2): compiled once per
    # pipeline; opgraph_fuse=False forces the barrier path so the ONE
    # batch-wise apply below is the only fusion-free reference
    cgraph = None
    if opgraph is not None:
        cgraph = opgraph.compile((image_size, image_size, 3), np.uint8)
        if not (True if opgraph_fuse is None else opgraph_fuse):
            stream = False
            overlap_put = False
    if cgraph is not None:
        from strom.obs.events import ring as _ring

        def prep(rows: np.ndarray) -> np.ndarray:
            with _ring.span("ops.apply", cat="ops",
                            args={"rows": int(rows.shape[0])}):
                return cgraph.apply_batch(rows)
    else:
        prep = None
    label_sharding = NamedSharding(
        sharding.mesh,
        P(sharding.spec[0] if len(sharding.spec) else None))
    out_sample_shape = (cgraph.out_shape if cgraph is not None
                        else (image_size, image_size, 3))
    global_shape = (batch,) + out_sample_shape
    rows_by_device = _local_batch_rows(sharding, batch)
    # the union of rows this host decodes, and each device's slice into it
    local_rows = sorted({r for lo, hi in rows_by_device.values()
                         for r in range(lo, hi)})
    row_pos = {r: i for i, r in enumerate(local_rows)}
    dev_items = list(rows_by_device.items())
    depth = prefetch_depth if prefetch_depth is not None else ctx.config.prefetch_depth
    auto, max_depth = _auto_depth_bounds(
        ctx, auto_prefetch, len(local_rows) * image_size * image_size * 3)
    # the built-in transform decodes in worker processes (the pool sees
    # tf.spec): one shared slab per batch being built or still landing on
    # the device, so the ring is one deeper than the batches in flight
    pool = DecodePool(decode_workers, transform=tf,
                      slabs=(max_depth if auto else depth) + 1)
    ctx.register_tunable("decode_pool", pool)
    shared = to_slot and pool.offloads(tf)
    slab_shape = (len(local_rows), image_size, image_size, 3)

    def shard_view(arr: np.ndarray, lo: int, hi: int) -> np.ndarray:
        # each device's row range is contiguous in the sorted local union,
        # so its shard is a VIEW of the batch slot — no fancy-index copy
        if hi <= lo:
            return arr[0:0]
        base = row_pos[lo]
        return arr[base: base + hi - lo]

    def make_batch(indices: np.ndarray, serial: int) -> tuple[Any, Any]:
        samples = [ss.samples[int(indices[r])] for r in local_rows]
        # Philox keys are two 64-bit words: (seed, serial ‖ row); the pool
        # builds each generator where the sample decodes
        rngs = [(seed, (serial << 32) + r) for r in local_rows]
        # decoded-output cache keys (ISSUE 12): the image member's physical
        # extent — stable across epochs, exactly like the extent cache
        ckeys = None
        served = None
        if dcache is not None:
            ckeys = [dcache.key(s.shard, s.members[image_ext].offset,
                                s.members[image_ext].offset
                                + s.members[image_ext].size)
                     for s in samples]
            if dcache.enabled:
                # decoded-cache fast path (ISSUE 13 satellite): probe the
                # cache BEFORE extent planning — hit samples skip the
                # image-member gather entirely (their pinned frames ride
                # straight to the decode pool; only labels + miss members
                # reach the engine). This is the ROADMAP item 3 residual:
                # warm decoded epochs stop paying the compressed gather
                # the pixels make redundant.
                served = [dcache.probe(ck, s.members[image_ext].size)
                          for ck, s in zip(ckeys, samples)]
                # decoded-frame peer serving (ISSUE 20): a local miss may
                # be hot on the owning peer's DecodedCache — pull the
                # crop-ready RGB over the batch wire, offer it locally,
                # and re-probe (a refused admission just falls back to
                # the gather; never wrong pixels)
                for j, sv in enumerate(served):
                    if sv is not None:
                        continue
                    img = ctx.peer_decoded_fetch(ckeys[j])
                    if img is None:
                        continue
                    dcache.offer(ckeys[j], img)
                    served[j] = dcache.probe(
                        ckeys[j], samples[j].members[image_ext].size)
                if not any(sv is not None for sv in served):
                    served = None
        if served is not None:
            el = ExtentList.concat([
                s.extents([label_ext] if sv is not None
                          else [image_ext, label_ext])
                for s, sv in zip(samples, served)])
            sizes = [(0 if sv is not None else s.members[image_ext].size,
                      s.members[label_ext].size)
                     for s, sv in zip(samples, served)]
        else:
            el = ss.batch_extents([int(indices[r]) for r in local_rows],
                                  [image_ext, label_ext])
            sizes = [(s.members[image_ext].size, s.members[label_ext].size)
                     for s in samples]
        try:
            out = _assemble_batch(el, sizes, rngs, ckeys, served)
            if cgraph is not None:
                # per-op engagement counters, flushed per batch so /metrics
                # tracks the stream (tallies accumulate under ops.graph)
                cgraph.flush_stats(pscope)
            return out
        except BaseException:
            # transforms release their own frames; anything that died
            # before (or instead of) a transform still holds pins —
            # release is idempotent, so sweeping everything is safe
            if served is not None:
                for sv in served:
                    if sv is not None:
                        sv.release()
            raise

    def _assemble_batch(el, sizes, rngs, ckeys, served) -> tuple[Any, Any]:
        if not shared:
            return _assemble_into(el, sizes, rngs, ckeys, served, None, None)
        images = inbuf = None
        fence: Sequence = ()
        try:
            images = pool.slab(slab_shape)
            if el.size:
                inbuf = pool.buffer(el.size)
            out = _assemble_into(el, sizes, rngs, ckeys, served, images,
                                 inbuf)
            fence = (out[0],)  # ready = every put from the slab landed
            return out
        finally:
            pool.recycle(images, inbuf, fence=fence)

    def _assemble_into(el, sizes, rngs, ckeys, served, images, inbuf
                       ) -> tuple[Any, Any]:
        """One batch; *images* / *inbuf* are the pool's shared slab and
        gather buffer when it decodes in processes (else None)."""
        if stream:
            # completion-driven dataflow (ISSUE 5): samples decode the
            # moment their extents land, device groups put the moment their
            # rows decode — no gather barrier anywhere in the batch
            if images is None:
                images = np.empty(slab_shape, dtype=np.uint8)
            img_shards, labels = _decode_put_streamed(
                ctx, pool, tf, el, sizes, rngs, images, dev_items, row_pos,
                scope=pscope, ckeys=ckeys, served=served, prep=prep,
                buf=inbuf, shared=shared)
            labels_np = np.asarray(labels, dtype=np.int32)
            pscope.add("decode_slot_bytes", images.nbytes)
            lbl_shards = [ctx.device_put(shard_view(labels_np, lo, hi), d)
                          for d, (lo, hi) in dev_items]
            imgs = jax.make_array_from_single_device_arrays(
                global_shape, sharding, img_shards)
            lbls = jax.make_array_from_single_device_arrays(
                (batch,), label_sharding, lbl_shards)
            return imgs, lbls

        buf = ctx.pread(el, tenant=tname, dest=inbuf)
        # split the concatenated buffer back into per-sample members; a
        # plan-time decoded-cache hit (isz == 0) rides its ServedFrame in
        # place of bytes that were never gathered
        blobs, labels, pos = [], [], 0
        for i, (isz, lsz) in enumerate(sizes):
            if served is not None and served[i] is not None:
                blobs.append(served[i])
            else:
                blobs.append(buf[pos: pos + isz])
            labels.append(int(buf[pos + isz: pos + isz + lsz].tobytes() or b"0"))
            pos += isz + lsz
        labels_np = np.asarray(labels, dtype=np.int32)

        if to_slot:
            # workers write final rows straight into the batch slot: the
            # np.stack full-batch copy and per-row output temporaries of
            # the legacy path never exist
            if images is None:
                images = np.empty(slab_shape, dtype=np.uint8)
            if overlap_put:
                img_shards = _decode_put_overlapped(
                    ctx, pool, tf, blobs, rngs, images, dev_items, row_pos,
                    scope=pscope, ckeys=ckeys, prep=prep, shared=shared)
            else:
                with pscope.timer_us("decode_batch"):
                    pool.map_into(tf, blobs, rngs, images, ckeys=ckeys)
                # unfused OpGraph reference (ISSUE 19): one batch-wise
                # apply after the decode barrier — same per-sample kernel
                # as the fused dispatch, so outputs are bit-identical
                out = images if prep is None else prep(images)
                img_shards = [_put_rows(ctx, shard_view(out, lo, hi), d,
                                        shared)
                              for d, (lo, hi) in dev_items]
            # billed after the decode completes: an aborted batch never
            # claims slot bytes it didn't deliver (zero-substituted rows DO
            # occupy their slot and are separately counted in decode_errors)
            pscope.add("decode_slot_bytes", images.nbytes)
        else:
            with pscope.timer_us("decode_batch"):
                images = np.stack(pool.map(tf, blobs, rngs))
            if prep is not None:
                images = prep(images)
            img_shards = [ctx.device_put(shard_view(images, lo, hi), d)
                          for d, (lo, hi) in dev_items]
        lbl_shards = [ctx.device_put(shard_view(labels_np, lo, hi), d)
                      for d, (lo, hi) in dev_items]
        imgs = jax.make_array_from_single_device_arrays(
            global_shape, sharding, img_shards)
        lbls = jax.make_array_from_single_device_arrays(
            (batch,), label_sharding, lbl_shards)
        return imgs, lbls

    def traced_make_batch(indices: np.ndarray, serial: int):
        # one traced request per batch build (ISSUE 8): the gather (pread
        # or streamed), scheduler waits, decode jobs and device_puts below
        # all join this request's lane — nested mint sites reuse it
        with _request.active("batch", tname, owner=ctx._req_owner):
            return make_batch(indices, serial)

    # warm this host's member bytes for the upcoming batches (tar payloads
    # re-read every epoch; decode still runs per-step, the NVMe gather not)
    ra = _make_readahead(
        ctx, sampler,
        lambda indices: ss.batch_extents([int(indices[r]) for r in local_rows],
                                         [image_ext, label_ext]),
        tenant=tname)
    return Pipeline(sampler, traced_make_batch, depth=depth, auto_depth=auto,
                    max_depth=max_depth, fingerprint=fp,
                    on_close=_chain_close(ra.close if ra else None, pool.close),
                    decode_pool=pool, scope=pscope,
                    req_owner=ctx._req_owner)


def make_predecoded_vision_pipeline(ctx: StromContext, paths: Sequence[str], *,
                                    batch: int,
                                    image_size: int,
                                    sharding: Any,
                                    seed: int = 0,
                                    shuffle: bool = True,
                                    prefetch_depth: int | None = None,
                                    auto_prefetch: bool | None = None,
                                    resume_from: "str | SamplerState | object | None" = None,
                                    scope: dict | None = None
                                    ) -> Pipeline:
    """Decode-free vision loader over pre-decoded shards (see
    :mod:`strom.formats.predecoded`): batches are pure engine gathers +
    device_put — the packed-token Llama loader's mechanics with pixel
    records — so no host decode competes with the consumer for CPU.
    Normalization/augmentation belongs in the (jitted) train step.

    Yields (images [B,S,S,3] uint8, labels [B] int32) sharded per
    *sharding* (batch-dim only, like every vision pipeline here)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from strom.delivery.core import source_size
    from strom.formats.predecoded import PredecodedShardSet

    if not isinstance(sharding, NamedSharding):
        raise TypeError("vision pipelines need a NamedSharding (labels derive "
                        "their spec from its batch axis)")
    _validate_batch_only(sharding)
    # sizes resolved through the ctx so striped-set aliases (paths that need
    # not exist on disk) work exactly like the llama loader's shards
    shards = PredecodedShardSet(
        tuple(paths), image_size,
        shard_sizes=tuple(source_size(ctx.resolve_source(p)) for p in paths))
    if shards.num_records < batch:
        raise ValueError(f"dataset has {shards.num_records} samples < batch "
                         f"{batch}")
    state, fp = resolve_state(tuple(paths), seed=seed, resume_from=resume_from,
                              ctx=ctx)
    sampler = EpochShuffleSampler(shards.num_records, batch, seed=seed,
                                  shuffle=shuffle, state=state)
    label_sharding = NamedSharding(
        sharding.mesh,
        P(sharding.spec[0] if len(sharding.spec) else None))
    pscope = ctx.scope.scoped(**(scope if scope is not None
                                 else {"pipeline": "predecoded"}))
    tname = getattr(pscope, "labels", {}).get("tenant")
    shape = (batch, image_size, image_size, 3)

    def make_batch(indices: np.ndarray, serial: int) -> tuple[Any, Any]:
        with _request.active("batch", tname, owner=ctx._req_owner):
            el = shards.extents([int(i) for i in indices])
            imgs = ctx.memcpy_ssd2tpu(el, shape=shape, dtype=np.uint8,
                                      sharding=sharding, tenant=tname)
            lbls = jax.device_put(shards.labels(indices), label_sharding)
            return imgs, lbls

    depth = prefetch_depth if prefetch_depth is not None else ctx.config.prefetch_depth
    auto, max_depth = _auto_depth_bounds(
        ctx, auto_prefetch, batch * image_size * image_size * 3)
    # the decode-free arm is a pure engine gather: warming the upcoming
    # record extents turns epoch 2+ into RAM memcpys end to end
    ra = _make_readahead(
        ctx, sampler,
        lambda indices: shards.extents([int(i) for i in indices]),
        tenant=tname)
    return Pipeline(sampler, make_batch, depth=depth, auto_depth=auto,
                    max_depth=max_depth, fingerprint=fp,
                    on_close=ra.close if ra else None, scope=pscope,
                    req_owner=ctx._req_owner)


def make_imagenet_resnet_pipeline(ctx: StromContext, paths: Sequence[str], *,
                                  batch: int, sharding: Any,
                                  image_size: int = 224,
                                  **kw: Any) -> Pipeline:
    """BASELINE config #2: ImageNet raw-JPEG shards → ResNet-50 input pipeline."""
    kw.setdefault("scope", {"pipeline": "resnet"})
    return make_wds_vision_pipeline(ctx, paths, batch=batch,
                                    image_size=image_size, sharding=sharding,
                                    **kw)


def make_vit_wds_pipeline(ctx: StromContext, paths: Sequence[str], *,
                          batch: int, sharding: Any,
                          image_size: int = 224,
                          **kw: Any) -> Pipeline:
    """BASELINE config #3: WebDataset .tar shards → ViT-B/16 training loader.

    Identical mechanics; shard *paths* typically live on a RAID0 set's member
    mounts so the gather fans out across NVMe devices."""
    kw.setdefault("scope", {"pipeline": "vit"})
    return make_wds_vision_pipeline(ctx, paths, batch=batch,
                                    image_size=image_size, sharding=sharding,
                                    **kw)
