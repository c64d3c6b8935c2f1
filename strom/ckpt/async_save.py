"""Async snapshot-then-commit checkpointing (ISSUE 14 tentpole, front 1).

The blocking :func:`~strom.ckpt.checkpoint.save_checkpoint` stalls the
training thread for the whole save wall — on the llama-small state that is
seconds of NVMe write time the accelerator spends idle. This module splits
the save at the only boundary that matters for that stall:

- **snapshot** (caller's thread, bounded, fast): flatten the pytree and
  pull every leaf to host memory — jax arrays are immutable so device_get
  IS the snapshot; mutable numpy leaves are copied
  (``_host_leaves(snapshot=True)``). Cost: one pass at host-memcpy
  bandwidth, never NVMe. The moment :meth:`AsyncCheckpointer.save`
  returns, training may mutate/replace the state freely.
- **commit** (background writer thread): the exact
  :func:`~strom.ckpt.checkpoint._commit_checkpoint` the blocking save
  runs — double-buffered slab staging with CRC folded into the copy pass,
  multi-chunk engine writes (scheduler-granted as the BACKGROUND class so
  a save stream never outranks training's demand reads), fsync, and the
  tmp+rename commit.

Failure contract: a failed commit NEVER destroys the previous checkpoint
(the rename-is-commit protocol guarantees it), latches the error, dumps a
flight bundle (reason ``ckpt_commit_failed``) when the context has a
flight dir, and raises the latched :class:`CkptError` on the NEXT
:meth:`~AsyncCheckpointer.save` or :meth:`~AsyncCheckpointer.wait` — an
async save may not fail silently, but it also must not fail on a thread
nobody is watching. One in-flight save at a time: a second ``save`` first
waits out the current commit (back-pressure, counted in the stall timer),
so the checkpointer can never queue unbounded snapshots.

``CKPT_ASYNC_FIELDS`` names the columns of the checked-in pre-chip
benchmark records that ``tools/compare_rounds.py`` reads for its "resume"
section (and ``tools/bench_sentinel.py`` gates on
``ckpt_async_stall_p99_us``).
"""

from __future__ import annotations

import concurrent.futures
import contextlib
import os
import time
from typing import Any

from strom.ckpt.checkpoint import (CkptError, _build_manifest,
                                   _commit_checkpoint, _host_leaves,
                                   load_manifest)
from strom.utils.locks import make_lock

# columns of the checked-in pre-chip benchmark records that
# tools/compare_rounds.py reads for its "resume" section (async save).
CKPT_ASYNC_FIELDS = (
    "ckpt_async_saves",
    "ckpt_async_stall_p99_us",
    "ckpt_async_stall_mean_us",
    "ckpt_sync_save_wall_us",
    "ckpt_async_stall_frac",
    "ckpt_async_commit_mb_per_s",
)


class CkptAsyncError(CkptError):
    """A background commit failed; the PREVIOUS checkpoint is intact and
    restorable. Carries the original failure as ``__cause__``."""


class AsyncCheckpointer:
    """Snapshot-then-commit checkpoints to one directory.

    One writer per directory: two checkpointers (or processes) committing
    to the same path would race the pid-keyed tmp staging. ``save`` is the
    training-loop call; ``wait`` joins the in-flight commit; ``last_saved``
    is the manifest of the newest COMMITTED save (None before the first);
    ``last_committed`` the committed directory path. ``close`` drains.

    Telemetry (scoped through *ctx*): ``ckpt_async_saves`` /
    ``ckpt_async_commits`` / ``ckpt_async_failures`` counters and the
    ``ckpt_async_stall_us`` histogram of per-save caller-thread stalls —
    the number the <25%-of-sync-wall acceptance is measured on.
    """

    def __init__(self, ctx, directory: str, *, tenant: "str | None" = None,
                 priority: "str | None" = "background"):
        self._ctx = ctx
        self._dir = os.path.abspath(directory)
        self._tenant = tenant
        self._priority = priority
        self._exec = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="strom-ckpt-commit")
        self._lock = make_lock("app.ckpt_async")
        self._fut: "concurrent.futures.Future | None" = None
        self._error: "BaseException | None" = None
        self._last_manifest: "dict | None" = None
        self._closed = False
        self.saves = 0
        self.commits = 0
        self.failures = 0
        # caller-blocked time per save(), bounded: a trainer saving for
        # weeks must not grow resident memory per save (the full series
        # also lands in the scoped ckpt_async_stall_us histogram)
        import collections

        self.stalls_us: "collections.deque[float]" = \
            collections.deque(maxlen=1024)

    # -- the training-loop call ---------------------------------------------
    def save(self, state: Any, *, extra: "dict | None" = None) -> int:
        """Snapshot *state* on THIS thread and hand the commit to the
        writer. Returns the save serial. Blocks only for the snapshot
        (plus draining a still-running previous commit — back-pressure).
        Raises the latched :class:`CkptAsyncError` if the previous commit
        failed (the old checkpoint is still committed and restorable)."""
        t0 = time.perf_counter()
        if self._closed:
            raise CkptError("AsyncCheckpointer is closed")
        self._join(raise_error=True)
        leaves, _ = _host_leaves(state, snapshot=True)
        manifest = _build_manifest(leaves, extra)
        with self._lock:
            self.saves += 1
            serial = self.saves
            self._fut = self._exec.submit(self._commit, leaves, manifest)
        stall_us = (time.perf_counter() - t0) * 1e6
        self.stalls_us.append(stall_us)
        scope = getattr(self._ctx, "scope", None)
        if scope is not None:
            scope.add("ckpt_async_saves")
            scope.observe_us("ckpt_async_stall_us", stall_us)
        return serial

    def _commit(self, leaves, manifest) -> dict:
        try:
            m = _commit_checkpoint(self._ctx, self._dir, leaves, manifest,
                                   tenant=self._tenant,
                                   priority=self._priority)
        except BaseException as e:
            with self._lock:
                self._error = e
                self.failures += 1
            scope = getattr(self._ctx, "scope", None)
            if scope is not None:
                scope.add("ckpt_async_failures")
            self._dump_flight(e)
            raise
        with self._lock:
            self._last_manifest = m
            self.commits += 1
        scope = getattr(self._ctx, "scope", None)
        if scope is not None:
            scope.add("ckpt_async_commits")
        return m

    def _dump_flight(self, exc: BaseException) -> None:
        """A failed commit is a post-mortem moment: the bundle carries the
        stats/stacks/trace that led up to it (same policy as a breaker
        trip). Best-effort — the error itself is latched regardless."""
        with contextlib.suppress(Exception):
            fr = getattr(self._ctx, "flight_recorder", None)
            if fr is not None:
                fr.dump("ckpt_commit_failed", note=repr(exc))
            elif getattr(self._ctx.config, "flight_dir", ""):
                from strom.obs.flight import dump_capture

                dump_capture(self._ctx.config.flight_dir,
                             reason="ckpt_commit_failed", note=repr(exc),
                             ctx=self._ctx)

    def _join(self, *, raise_error: bool) -> None:
        with self._lock:
            fut = self._fut
        if fut is not None:
            # the future's own exception is re-raised via the latch below
            # (typed, with the "old checkpoint intact" framing), not here
            concurrent.futures.wait([fut])
            with self._lock:
                if self._fut is fut:
                    self._fut = None
        if raise_error:
            with self._lock:
                err, self._error = self._error, None
            if err is not None:
                raise CkptAsyncError(
                    f"async checkpoint commit to {self._dir} failed "
                    f"({err!r}); the previous checkpoint is intact"
                ) from err

    # -- completion surface --------------------------------------------------
    def wait(self) -> "dict | None":
        """Drain the in-flight commit (if any). Raises the latched
        :class:`CkptAsyncError` from a failed one; returns the manifest of
        the newest committed save (None when nothing ever committed)."""
        self._join(raise_error=True)
        with self._lock:
            return self._last_manifest

    def last_committed(self) -> "str | None":
        """Path of the newest COMMITTED checkpoint this process knows of:
        the directory once a commit landed (this checkpointer's or a
        previous process's — a pre-existing committed checkpoint counts),
        else None. Never blocks; an in-flight commit doesn't count until
        its rename lands."""
        with self._lock:
            if self._last_manifest is not None:
                return self._dir
        try:
            load_manifest(self._dir)
            return self._dir
        except CkptError:
            return None

    @property
    def in_flight(self) -> bool:
        with self._lock:
            return self._fut is not None and not self._fut.done()

    @property
    def error(self) -> "BaseException | None":
        """The latched commit failure (cleared when save/wait raises it)."""
        with self._lock:
            return self._error

    def stats(self) -> dict:
        with self._lock:
            st = sorted(self.stalls_us)
            return {
                "ckpt_async_saves": self.saves,
                "ckpt_async_commits": self.commits,
                "ckpt_async_failures": self.failures,
                "ckpt_async_stall_p99_us":
                    round(st[min(int(len(st) * 0.99), len(st) - 1)], 1)
                    if st else 0.0,
                "ckpt_async_stall_mean_us":
                    round(sum(st) / len(st), 1) if st else 0.0,
            }

    def close(self, *, wait: bool = True) -> None:
        """Drain (``wait=True``) and shut the writer down. Swallows
        nothing: a latched failure still raises here unless ``wait=False``
        (teardown-on-error paths)."""
        if self._closed:
            return
        self._closed = True
        try:
            if wait:
                self._join(raise_error=True)
        finally:
            self._exec.shutdown(wait=wait)

    def __enter__(self) -> "AsyncCheckpointer":
        return self

    def __exit__(self, exc_type, *exc) -> None:
        # an exception already unwinding must not be masked by the drain's
        self.close(wait=exc_type is None)


def save_checkpoint_async(ctx, directory: str, state: Any, *,
                          tenant: "str | None" = None,
                          extra: "dict | None" = None,
                          priority: "str | None" = "background"
                          ) -> AsyncCheckpointer:
    """One-shot spelling of the above: snapshot *state* now, commit in the
    background, return the checkpointer (``wait()`` for the manifest).
    Training loops that save repeatedly should hold one
    :class:`AsyncCheckpointer` instead (one writer thread, back-pressure,
    the failure latch across saves)."""
    cp = AsyncCheckpointer(ctx, directory, tenant=tenant, priority=priority)
    cp.save(state, extra=extra)
    return cp
