"""Pipeline: a prefetched, checkpointable stream of device batches.

Ties together the sampler (which record indices), a batch builder (engine
read → decode → device_put), and the Prefetcher (dispatch-ahead overlap, the
"0 data-stall steps" counter).  Checkpointing hard case: the sampler runs
*ahead* of consumption by the prefetch depth, so saved state is derived from
the consumed count, never from the sampler's own cursor — a resume replays
nothing and skips nothing.
"""

from __future__ import annotations

import concurrent.futures
import time
from typing import Any, Callable, Iterator

import numpy as np

from strom.delivery.prefetch import Prefetcher
from strom.obs import request as _request
from strom.obs.events import ring
from strom.pipelines.sampler import (EpochShuffleSampler, SamplerState,
                                     dataset_fingerprint, load_loader_state,
                                     save_loader_state)


class Pipeline:
    """Iterate device batches; `state()` is always the resume point of the
    *next* unconsumed batch."""

    def __init__(self, sampler: EpochShuffleSampler,
                 make_batch: Callable[[np.ndarray, int], Any], *,
                 depth: int = 2,
                 auto_depth: bool = False,
                 max_depth: int | None = None,
                 fingerprint: dict | None = None,
                 executor: concurrent.futures.Executor | None = None,
                 on_close: Callable[[], None] | None = None,
                 decode_pool: Any | None = None,
                 epoch_sync: bool = False,
                 scope: Any | None = None,
                 req_owner: Any | None = None):
        self.sampler = sampler
        self.fingerprint = fingerprint or {}
        self._on_close = on_close
        # the owning context's request-owner token (ISSUE 8): step requests
        # minted here carry it so only that context's SLO engine ingests them
        self._req_owner = req_owner
        # telemetry scope (ISSUE 6): label-scoped stats view the pipeline's
        # step/prefetch accounting writes through, so concurrent pipelines
        # on one context surface distinguishable per-scope series. None =
        # the global registry (single-tenant behavior unchanged).
        from strom.utils.stats import global_stats

        self.scope = scope if scope is not None else global_stats
        # the DecodePool feeding make_batch, when one exists (vision
        # pipelines): surfaces the per-sample decode-failure counter
        self._decode_pool = decode_pool
        # epoch_sync: barrier every process at epoch boundaries so no host
        # issues next-epoch reads while a straggler is still dispatching the
        # previous epoch's (SURVEY.md §2.3). The barrier sits in the thunk
        # generator — the point where the prefetcher would dispatch the first
        # batch of a new epoch — NOT in __next__: the sampler runs ahead of
        # consumption by the prefetch depth, so a consumer-side barrier would
        # fire after next-epoch I/O was already in flight. Costs one DCN
        # round trip per epoch; off by default for single-host use.
        self._epoch_sync = epoch_sync
        from strom.parallel.multihost import StragglerMonitor

        self.monitor = StragglerMonitor()
        self._last_next: float | None = None
        # retained for restore(): a StepToken restart rebuilds the thunk
        # stream + prefetcher with the same wiring __init__ used
        self._make_batch = make_batch
        self._depth_args = (depth, auto_depth, max_depth)
        self._executor = executor
        st = sampler.state
        self._consumed = st.epoch * sampler.batches_per_epoch + st.batch_in_epoch
        self._seed = st.seed
        self._prefetcher: Prefetcher = self._start_stream(depth)

    def _start_stream(self, depth: int) -> Prefetcher:
        """Build the thunk generator + prefetcher from the sampler's
        CURRENT cursor — __init__'s tail, reused by :meth:`restore`."""
        sampler = self.sampler
        make_batch = self._make_batch
        start = self._consumed
        bpe = sampler.batches_per_epoch

        def thunks() -> Iterator[Callable[[], Any]]:
            # make_batch gets (indices, serial): serial is the global batch
            # number, stable across resume — deterministic augmentation keys
            serial = start
            for indices in sampler:
                if self._epoch_sync and serial % bpe == 0 and serial != start:
                    from strom.parallel.multihost import epoch_barrier

                    epoch_barrier(f"strom-epoch-{serial // bpe}")
                yield lambda idx=indices, s=serial: make_batch(idx, s)
                serial += 1

        _, auto_depth, max_depth = self._depth_args
        return Prefetcher(thunks(), depth=depth,
                          auto_depth=auto_depth,
                          max_depth=max_depth,
                          executor=self._executor,
                          scope=self.scope)

    def __iter__(self) -> "Pipeline":
        return self

    def __next__(self) -> Any:
        # the consumer-blocked window: everything the consumer spends inside
        # the data loader (stall attribution's ingest_wait bucket — the
        # decode/put/read spans overlapping THIS window are what the step
        # was actually waiting on). Each __next__ is a traced "step"
        # request (ISSUE 8): the wait span carries its req_id, and the
        # request feeds the exemplar store so an outlier step's tree is
        # retained — the batch-build requests themselves are minted where
        # the work happens (make_batch, on the prefetcher's threads).
        tname = getattr(self.scope, "labels", {}).get("tenant")
        with _request.active("step", tname, owner=self._req_owner), \
                _request.span("pipeline.next", cat="ingest_wait",
                              args={"step": self._consumed}):
            batch = next(self._prefetcher)
        self._consumed += 1
        # step-progress heartbeat: the flight recorder's watchdog
        # (strom/obs/flight.py) distinguishes "slow but advancing" from
        # "wedged" by watching this counter; scoped, so per-pipeline step
        # rates are also distinguishable on /metrics
        self.scope.add("pipeline_steps")
        # per-host step cadence (consumer compute + any data wait): the raw
        # input to cross-host straggler accounting
        now = time.monotonic()
        if self._last_next is not None:
            self.monitor.record(now - self._last_next)
        self._last_next = now
        return batch

    # -- checkpoint/resume --------------------------------------------------
    def state(self) -> SamplerState:
        bpe = self.sampler.batches_per_epoch
        return SamplerState(epoch=self._consumed // bpe,
                            batch_in_epoch=self._consumed % bpe,
                            seed=self._seed)

    def save_state(self, path: str, extra: dict | None = None) -> None:
        save_loader_state(path, self.state(), self.fingerprint, extra)

    @staticmethod
    def load_state(path: str, fingerprint: dict | None = None
                   ) -> tuple[SamplerState, dict]:
        return load_loader_state(path, fingerprint)

    def token(self, ctx: Any | None = None, *, warm_state: bool = False,
              extra: dict | None = None):
        """The :class:`~strom.ckpt.jobstate.StepToken` of the NEXT
        unconsumed batch (ISSUE 14): sampler position derived from the
        consumed count (same no-replay/no-skip contract as
        :meth:`state`), the global serial, the prefetcher's current
        operating depth, and — with ``warm_state=True`` and a *ctx* —
        the cache/spill manifests as advisory rewarm hints. Cheap enough
        to capture every step when hints are off."""
        from strom.ckpt.jobstate import StepToken, capture_warm_state

        return StepToken(
            sampler=self.state(),
            consumed=self._consumed,
            prefetch_depth=self._prefetcher.depth,
            fingerprint=dict(self.fingerprint),
            warm=capture_warm_state(ctx) if (warm_state and ctx is not None)
            else None,
            extra=dict(extra or {}))

    def restore(self, token) -> "Pipeline":
        """Rewind/fast-forward THIS pipeline to *token*'s position: the
        next delivered batch is exactly the one an uninterrupted run
        would have delivered there (bit-identical stream from then on —
        the harness's contract). In-flight prefetched batches are
        discarded; the prefetcher restarts at the token's depth (the
        auto-depth operating point travels with the job). Accepts a
        StepToken or a bare SamplerState. Returns self."""
        from strom.ckpt.jobstate import StepToken

        if isinstance(token, StepToken):
            st, depth = token.sampler, token.prefetch_depth
            if token.fingerprint and self.fingerprint \
                    and token.fingerprint != self.fingerprint:
                raise ValueError(
                    "StepToken was captured against a different dataset "
                    f"({len(token.fingerprint.get('paths', ()))} shards vs "
                    f"{len(self.fingerprint.get('paths', ()))}); refusing "
                    "to resume")
        else:
            st, depth = token, 0
        if st.seed != self._seed:
            raise ValueError(
                f"token was captured with seed {st.seed} but this pipeline "
                f"shuffles with seed {self._seed}; refusing to resume a "
                "different batch order")
        target = st.epoch * self.sampler.batches_per_epoch \
            + st.batch_in_epoch
        if self._consumed == target \
                and (depth <= 0 or depth == self._prefetcher.depth):
            # already positioned (a pipeline constructed with the token's
            # sampler state, or restored twice): the in-flight prefetch
            # window is dispatching exactly the right serials — keep it
            # instead of discarding and re-issuing those reads
            return self
        self._prefetcher.close()
        self.sampler.state = SamplerState(epoch=st.epoch,
                                          batch_in_epoch=st.batch_in_epoch,
                                          seed=st.seed)
        self._consumed = target
        self._prefetcher = self._start_stream(
            depth if depth > 0 else self._depth_args[0])
        return self

    # -- observability ------------------------------------------------------
    @property
    def data_stall_steps(self) -> int:
        return self._prefetcher.data_stall_steps

    @property
    def steps_delivered(self) -> int:
        return self._prefetcher.steps

    @property
    def prefetch_depth(self) -> int:
        """Current prefetch depth (moves when auto_depth is on)."""
        return self._prefetcher.depth

    @property
    def decode_errors(self) -> int:
        """Samples substituted with a zero image by the per-sample decode
        failure policy (0 for pipelines without a decode pool)."""
        return self._decode_pool.decode_errors \
            if self._decode_pool is not None else 0

    def straggler_report(self, threshold: float = 1.25):
        """Cross-host step-time skew (collective: every process must call)."""
        return self.monitor.report(threshold)

    def close(self) -> None:
        self._prefetcher.close()
        if self._on_close is not None:
            self._on_close()

    def __enter__(self) -> "Pipeline":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _auto_depth_bounds(ctx, auto_prefetch: bool | None,
                       batch_bytes: int) -> tuple[bool, int | None]:
    """(auto_depth, max_depth) for a pipeline: *auto_prefetch* None defers to
    ``ctx.config.prefetch_auto``; when auto, the ceiling is the config's
    prefetch_max_depth further bounded by what the slab pool can stage at
    *batch_bytes* per in-flight batch (strom.delivery.prefetch.bound_depth)."""
    from strom.delivery.prefetch import bound_depth

    auto = ctx.config.prefetch_auto if auto_prefetch is None else auto_prefetch
    if not auto:
        return False, None
    # the hot cache's byte budget lives in the same slab pool the in-flight
    # batches stage through: reserve it so depth growth can't starve the
    # cache (nor the cache starve the prefetch window) — ISSUE 4 satellite
    return True, bound_depth(ctx.config.slab_pool_bytes, batch_bytes,
                             cap=ctx.config.prefetch_max_depth,
                             reserve_bytes=ctx.config.hot_cache_bytes)


def resolve_state(paths: tuple[str, ...], *, seed: int,
                  resume_from: "str | SamplerState | Any | None",
                  ctx=None) -> tuple[SamplerState | None, dict]:
    """Common resume plumbing: fingerprint the shard list and, when resuming,
    validate both the dataset identity and the shuffle seed — a checkpoint
    saved under a different seed describes a different data order. Accepts
    a loader-state path, a bare SamplerState, or a StepToken (ISSUE 14 —
    its embedded fingerprint is validated against the live shard list)."""
    fp = dataset_fingerprint(paths, ctx)
    if resume_from is None:
        return None, fp
    if hasattr(resume_from, "sampler") and hasattr(resume_from, "consumed"):
        # StepToken (duck-typed: pipelines.base must not import strom.ckpt
        # at call time just to isinstance-check). POSITION only: the
        # factory path restores the batch stream; the token's prefetch
        # depth and warm hints are runtime state — adopt them with
        # Pipeline.restore(token) / restore_warm_state(ctx, token.warm)
        # after construction (cheap: restore() no-ops the prefetcher
        # rebuild when the pipeline is already at the token's position)
        if resume_from.fingerprint and resume_from.fingerprint != fp:
            raise ValueError(
                "StepToken was captured against a different dataset "
                f"(saved {len(resume_from.fingerprint.get('paths', ()))} "
                f"shards, now {len(fp['paths'])}); refusing to resume")
        state = resume_from.sampler
    elif isinstance(resume_from, SamplerState):
        state = resume_from
    else:
        state, _ = load_loader_state(resume_from, fp)
    if state.seed != seed:
        raise ValueError(
            f"loader state was saved with seed {state.seed} but the pipeline "
            f"was constructed with seed {seed}; refusing to resume a "
            "different shuffle order")
    return state, fp
