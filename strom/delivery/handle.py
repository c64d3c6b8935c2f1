"""Async DMA handles — the MEMCPY_WAIT side of the contract.

The reference's async submit returns a DMA task id; STROM_IOCTL__MEMCPY_WAIT
blocks until the interrupt-driven completion path retires every chunk and
surfaces the aggregated status (SURVEY.md §3.3; reference cite UNVERIFIED —
empty mount, SURVEY.md §0).  strom-tpu's handle wraps the full pipeline
(engine reads → host slab → dispatch of host→HBM transfer) and resolves to a
`jax.Array`.  Because jax dispatch is asynchronous, `.result()` returning an
array does NOT block on the HBM copy — compute ordered after it overlaps the
transfer, which is exactly the "completion becomes an XLA token" design
(BASELINE.json:5).
"""

from __future__ import annotations

import concurrent.futures
from typing import Any, Callable

from strom.utils.stats import global_stats


def _count_done(_f) -> None:
    global_stats.add("handles_completed")


class DMAHandle:
    """Future-like handle for an in-flight ssd2tpu copy."""

    def __init__(self, future: concurrent.futures.Future, *, nbytes: int,
                 label: str = ""):
        self._future = future
        self.nbytes = nbytes
        self.label = label
        # a plain function, not a bound method: the future must not hold
        # the handle, or handle and future form a cycle and the delivered
        # array lives until the cyclic GC runs
        future.add_done_callback(_count_done)

    # -- MEMCPY_WAIT equivalents -------------------------------------------
    def done(self) -> bool:
        return self._future.done()

    def wait(self, timeout: float | None = None) -> "DMAHandle":
        """Block until the host-side pipeline retires (reads complete and the
        device transfer is dispatched). Raises the pipeline's error, if any."""
        self._future.result(timeout)
        return self

    def result(self, timeout: float | None = None) -> Any:
        """The delivered jax.Array (sharded when a sharding was requested)."""
        return self._future.result(timeout)

    def exception(self, timeout: float | None = None):
        return self._future.exception(timeout)

    def block_until_ready(self, timeout: float | None = None) -> Any:
        """Full barrier: also waits for the host→HBM transfer itself."""
        arr = self.result(timeout)
        return arr.block_until_ready() if hasattr(arr, "block_until_ready") else arr

    def __repr__(self) -> str:
        state = "done" if self.done() else "pending"
        return f"DMAHandle({self.label or hex(id(self))}, {self.nbytes}B, {state})"


def completed_handle(value: Any, nbytes: int = 0, label: str = "") -> DMAHandle:
    f: concurrent.futures.Future = concurrent.futures.Future()
    f.set_result(value)
    return DMAHandle(f, nbytes=nbytes, label=label)


def deferred_handle(fn: Callable[[], Any], executor: concurrent.futures.Executor,
                    nbytes: int, label: str = "") -> DMAHandle:
    return DMAHandle(executor.submit(fn), nbytes=nbytes, label=label)
