"""Decode worker processes: the executor :class:`strom.formats.jpeg.DecodePool`
runs its built-in train transform on.

Why processes: the native decode, ``cv2.resize`` and ``cv2.flip`` release
the interpreter lock, but the Python around them (header parse, crop
geometry, RNG, counters, spans, futures) does not, and on a decode thread
pool it runs under the lock the training loop needs to launch its step.
In worker processes that work runs under each worker's own lock; the
parent's share per sample is a packed record out and a packed record back.

Mechanics (wire format and the worker loop: strom/formats/decode_worker.py):

- **Workers** start at construction, in parallel, with ``subprocess`` (an
  exec, never a bare fork: the parent may hold the TPU runtime's threads)
  and ``JAX_PLATFORMS=cpu`` in their environment; they import no JAX.
  Construction does not wait for their imports: tasks queue on a
  worker's socket until it reads them, and a worker that dies before it
  is ready fails its tasks and every later call. Each worker has one
  ``SOCK_SEQPACKET`` socket to the parent; a task goes to the live worker
  with the fewest samples outstanding.
- **Shared memory**: image bytes and output rows live in ``memfd`` regions
  the parent creates and passes to every worker as file descriptors.
  A sample names its bytes and its row by (region, offset); rows are
  written in place. Regions recycle through a small ring per kind
  (:meth:`DecodeProcs.lease` / :meth:`DecodeProcs.give_back`): a region is
  handed out again only after the fence given back with it (the device
  arrays put from it) is ready. ``close()`` unmaps and closes every region;
  a memfd has no name, so nothing outlives the processes that map it.
- **Completion**: one reaper thread reads every worker's socket, records a
  ``decode.worker`` span per sample at the worker's own start and wall
  time (``perf_counter`` is ``CLOCK_MONOTONIC``, shared across processes),
  adds the worker's counter tallies to ``global_stats``, and resolves the
  task's future.
- **Failure**: a ``ValueError`` in a worker zeroes its row (the pool's
  per-sample policy); any other exception fails its task. A worker that
  dies fails every task it held with :class:`DecodeWorkerError`, counts in
  ``decode_proc_restarts`` and is replaced.
"""

from __future__ import annotations

import concurrent.futures
import itertools
import json
import mmap
import os
import select
import socket
import subprocess
import sys
import threading
import time
from typing import Callable, Sequence

import numpy as np

from strom.formats import decode_worker as W
from strom.formats.jpeg_core import COUNTERS
from strom.obs.events import ring
from strom.utils.locks import make_condition
from strom.utils.stats import global_stats

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "decode_worker.py")
_EXIT_TIMEOUT_S = 5.0
# a region grows past the request so one slightly larger batch of member
# bytes does not force a fresh region
_GROW = 1.25
_MIB = 1 << 20
_POOLS = itertools.count()


class DecodeWorkerError(RuntimeError):
    """A decode worker process died (or a task in it raised something
    other than ValueError): the batch that held its samples fails."""


def _addr(arr: np.ndarray) -> int:
    return arr.__array_interface__["data"][0]


class _Region:
    __slots__ = ("rid", "kind", "fd", "size", "mm", "arr", "addr", "fence")

    def __init__(self, rid: int, kind: str, nbytes: int, tag: str):
        self.rid = rid
        self.kind = kind
        self.size = nbytes
        self.fd = os.memfd_create(f"{tag}.{kind}", os.MFD_CLOEXEC)
        try:
            os.ftruncate(self.fd, nbytes)
            self.mm = mmap.mmap(self.fd, nbytes)
        except BaseException:
            os.close(self.fd)
            raise
        self.arr = np.frombuffer(self.mm, np.uint8)
        self.addr = _addr(self.arr)
        self.fence: Sequence = ()

    def close(self) -> None:
        self.arr = None
        try:
            self.mm.close()
        except BufferError:
            # a caller still holds a view: the mapping goes with the last
            # view; the fd (the only handle a new worker could map) goes now
            pass
        os.close(self.fd)


class _Worker:
    __slots__ = ("sock", "proc", "pid", "pending", "load", "ready")

    def __init__(self, sock: socket.socket):
        self.sock = sock
        self.proc: subprocess.Popen | None = None
        self.pid = 0
        # task id -> [future, samples, request, submit seconds]
        self.pending: dict = {}
        self.load = 0          # samples outstanding
        self.ready = False


def _load(w: "_Worker") -> int:
    return w.load


def _fence_ready(fence: Sequence) -> bool:
    return all(a.is_ready() for a in fence)


def _wait_fence(fence: Sequence) -> None:
    for a in fence:
        a.block_until_ready()


class DecodeProcs:
    """*workers* decode processes running the built-in train transform
    described by *spec* (``make_train_transform(...).spec``). *native_lib*
    is the strom/_core library path the parent's native decoder came from
    ("" when it has none: the workers decode with cv2 too). *on_task*
    receives (samples, zeroed samples, summed worker wall seconds) for
    every finished task, before its future resolves; the counters
    (``decode_errors``, ``decode_fused_*``, the workers' tallies) are
    added here. *ring* bounds the regions kept per kind."""

    def __init__(self, workers: int, spec: dict, native_lib: str,
                 on_task: Callable[[int, int, float], None], *,
                 ring: int = 3):
        self.spec = dict(spec)
        # names this pool's memfd regions (/proc/<pid>/maps shows them)
        self.tag = f"strom-decode.{next(_POOLS)}"
        self.row_shape = (spec["size"], spec["size"], 3)
        self.row_bytes = spec["size"] * spec["size"] * 3
        self.ring = ring
        self._lib = native_lib
        self._on_task = on_task
        self._cond = make_condition("app.jpeg_procs")
        self._lock = self._cond
        self._ids = itertools.count(1)
        self._regions: dict[int, _Region] = {}
        self._free: dict[str, list[_Region]] = {"rows": [], "bytes": []}
        self._count = {"rows": 0, "bytes": 0}
        # the regions the last item / row lookups hit: a task's bytes sit
        # in one buffer and its rows in one slab
        self._hint: list = [None, None]
        self._workers: list[_Worker] = []
        self._closing = False
        self._error: BaseException | None = None
        self._wake_r, self._wake_w = os.pipe()
        self._env = dict(os.environ, JAX_PLATFORMS="cpu")
        try:
            for _ in range(workers):
                self._spawn()
            self._reaper = threading.Thread(target=self._reap,
                                            name="strom-decode-reaper",
                                            daemon=True)
            self._reaper.start()
        except BaseException:
            self.close()
            raise

    @property
    def pids(self) -> list[int]:
        with self._lock:
            return [w.pid for w in self._workers]

    # -- workers ------------------------------------------------------------
    def _spawn(self) -> "_Worker | None":
        """Start one worker (None once the pool is closing): it maps every
        region that exists now at start-up, and every later one from the
        MAP message :meth:`_map` sends it."""
        ours, theirs = socket.socketpair(socket.AF_UNIX, socket.SOCK_SEQPACKET)
        w = _Worker(ours)
        with self._lock:
            if self._closing:
                ours.close()
                theirs.close()
                return None
            self._workers.append(w)
            # dup'ed under the lock: a region dropped meanwhile cannot
            # hand the child a closed (or reused) descriptor
            regions = [(r.rid, os.dup(r.fd), r.size)
                       for r in self._regions.values()]
        fds = [fd for _, fd, _ in regions]
        try:
            w.proc = subprocess.Popen(
                [sys.executable, _WORKER, str(theirs.fileno()),
                 json.dumps(self.spec), self._lib, json.dumps(regions)],
                pass_fds=[theirs.fileno(), *fds], env=self._env,
                stdin=subprocess.DEVNULL, stdout=subprocess.DEVNULL)
            w.pid = w.proc.pid
        except BaseException:
            with self._lock:
                self._workers.remove(w)
            ours.close()
            raise
        finally:
            theirs.close()
            for fd in fds:
                os.close(fd)
        return w

    def _reap(self) -> None:
        """The reaper thread. Should it ever fail, every task still pending
        fails with it and later calls raise: a batch errors, none hangs."""
        try:
            self._reap_loop()
        except BaseException as e:
            err = DecodeWorkerError(f"decode reaper failed: {e!r}")
            with self._lock:
                self._error = err
                pending = [x for w in self._workers
                           for x in w.pending.values()]
                for w in self._workers:
                    w.pending = {}
            for x in pending:
                x[0].set_exception(err)
            raise

    def _reap_loop(self) -> None:
        poller = select.poll()
        poller.register(self._wake_r, select.POLLIN)
        with self._lock:
            live = {w.sock.fileno(): w for w in self._workers}
        for fd in live:
            poller.register(fd, select.POLLIN)
        while True:
            ready = poller.poll()
            t0 = time.perf_counter()
            # counters and futures of every message this wake read: one
            # add per counter name, then the futures (a batch that hears
            # of its samples already sees their counters)
            tally: dict = {}
            done: list = []
            try:
                for fd, _ in ready:
                    if fd == self._wake_r:
                        return
                    w = live.get(fd)
                    if w is None:
                        continue
                    try:
                        msg = w.sock.recv(W.RECV_BYTES)
                    except OSError:
                        msg = b""
                    if msg:
                        self._handle(w, msg, tally, done)
                        continue
                    poller.unregister(fd)
                    del live[fd]
                    new = self._lost(w)
                    if new is not None:
                        live[new.sock.fileno()] = new
                        poller.register(new.sock.fileno(), select.POLLIN)
            finally:
                self._resolve(tally, done, t0)

    def _resolve(self, tally: dict, done: list, t0: float) -> None:
        if not done:
            return
        tally["decode_proc_parent_us"] = int(
            (sum(sub for _, _, sub in done) + time.perf_counter() - t0) * 1e6)
        for name, v in tally.items():
            if v:
                global_stats.add(name, v)
        for fut, err, _ in done:
            if err is None:
                fut.set_result(None)
            else:
                fut.set_exception(err)

    def _handle(self, w: _Worker, msg: bytes, tally: dict,
                done: list) -> None:
        """One message from *w*: READY, or a finished task whose spans it
        records, whose counters it adds to *tally* and whose future (with
        its error, if any, and its submit seconds) it appends to *done*."""
        kind = msg[0]
        if kind == W.MSG_READY:
            w.ready = True
            return
        task = W.FAIL.unpack_from(msg)[1]
        with self._lock:
            entry = w.pending.pop(task, None)
            if entry is not None:
                w.load -= entry[1]
        if entry is None:
            return
        fut, n, req, submit_s = entry
        if kind == W.MSG_FAIL:
            done.append((fut, DecodeWorkerError(
                msg[W.FAIL.size:].decode(errors="replace")), submit_s))
            return
        _, _, _, cpu_us, *counts = W.DONE.unpack_from(msg)
        spans = req is not None or ring.enabled
        cpu = cpu_us / n
        zeroed, wall_s = 0, 0.0
        for status, start, wall in W.RESULT.iter_unpack(
                memoryview(msg)[W.DONE.size:]):
            zeroed += status != W.ST_OK
            wall_s += wall
            if not spans:
                continue
            if req is not None:
                req.record("decode.worker", "decode", ring.us_at(start),
                           wall * 1e6, cpu_us=cpu, tid=w.pid)
            else:
                ring.complete(ring.us_at(start), wall * 1e6, "decode",
                              "decode.worker", None, cpu, tid=w.pid)
        for name, v in zip(COUNTERS, counts):
            tally[name] = tally.get(name, 0) + v
        tally["decode_proc_samples"] = tally.get("decode_proc_samples", 0) + n
        tally["decode_errors"] = tally.get("decode_errors", 0) + zeroed
        if n > 1:
            tally["decode_fused_runs"] = tally.get("decode_fused_runs", 0) + 1
            tally["decode_fused_samples"] = \
                tally.get("decode_fused_samples", 0) + n
        self._on_task(n, zeroed, wall_s)
        done.append((fut, None, submit_s))

    def _lost(self, w: _Worker) -> "_Worker | None":
        """A worker's socket closed: fail what it held; replace it unless
        the pool is closing or it never got as far as READY."""
        try:
            rc = w.proc.wait(_EXIT_TIMEOUT_S) if w.proc else None
        except subprocess.TimeoutExpired:
            w.proc.kill()
            rc = w.proc.wait()
        with self._lock:
            if w in self._workers:
                self._workers.remove(w)
            pending, w.pending = w.pending, {}
            restart = w.ready and not self._closing
        w.sock.close()
        err = DecodeWorkerError(
            f"decode worker {w.pid} exited with code {rc}, "
            f"{sum(e[1] for e in pending.values())} samples in flight")
        new = None
        if restart:
            # counted, and replaced, before the batch that lost samples
            # hears of it: its next batch finds a full pool
            global_stats.add("decode_proc_restarts")
            try:
                new = self._spawn()
            except OSError as e:
                # no replacement: later submits raise instead of queueing
                # on a pool that can only shrink
                self._error = DecodeWorkerError(f"decode worker restart: {e}")
        elif not w.ready and not self._closing:
            self._error = err  # died starting up: no restart loop
        for e in pending.values():
            e[0].set_exception(err)
        return new

    # -- shared regions -----------------------------------------------------
    def lease(self, kind: str, nbytes: int) -> np.ndarray:
        """A shared uint8 array of *nbytes* from the *kind* ring ("rows":
        output slabs, "bytes": gathered member bytes): a free region whose
        fence is ready, else a new one while the ring has room, else the
        next one given back."""
        nbytes = max(int(nbytes), 1)
        victim = None
        with self._cond:
            while True:
                if self._error is not None:
                    raise self._error
                if self._closing:
                    raise DecodeWorkerError("decode workers are closed")
                free = self._free[kind]
                fit = [r for r in free if r.size >= nbytes]
                if fit:
                    got = min(fit, key=lambda r: (not _fence_ready(r.fence),
                                                  r.size))
                    free.remove(got)
                    break
                if self._count[kind] < self.ring:
                    self._count[kind] += 1
                    got = None
                    break
                if free:  # only smaller regions are free: replace one
                    victim = free.pop(0)
                    del self._regions[victim.rid]
                    got = None
                    break
                self._cond.wait(1.0)  # re-checks close and errors
        if victim is not None:
            _wait_fence(victim.fence)
            self._unmap(victim)
        if got is None:
            try:
                got = self._map(kind, nbytes if kind == "rows" else
                                -(-int(nbytes * _GROW) // _MIB) * _MIB)
            except BaseException:
                with self._cond:
                    self._count[kind] -= 1
                    self._cond.notify_all()
                raise
        else:
            _wait_fence(got.fence)
        got.fence = ()
        return got.arr[:nbytes]

    def give_back(self, arr: np.ndarray, fence: Sequence = ()) -> None:
        """Return a leased array's region to its ring; it is leased again
        only once every array in *fence* is ready."""
        r = self._find(_addr(arr), 0)
        if r is None:
            raise ValueError("not a leased decode region")
        r.fence = tuple(fence)
        with self._cond:
            self._free[r.kind].append(r)
            self._cond.notify_all()

    def _map(self, kind: str, nbytes: int) -> _Region:
        with self._lock:
            rid = next(self._ids)
        r = _Region(rid, kind, nbytes, self.tag)
        with self._lock:
            self._regions[rid] = r
            workers = list(self._workers)
        msg = W.MAP.pack(W.MSG_MAP, rid, nbytes)
        for w in workers:
            try:
                socket.send_fds(w.sock, [msg], [r.fd])
            except OSError:
                pass  # a dead worker: its replacement maps r at spawn
        return r

    def _unmap(self, r: _Region) -> None:
        with self._lock:
            workers = list(self._workers)
            self._hint = [None, None]
        msg = W.UNMAP.pack(W.MSG_UNMAP, r.rid)
        for w in workers:
            try:
                w.sock.send(msg)
            except OSError:
                pass  # a dead worker maps nothing any more
        r.close()

    def _find(self, addr: int, nbytes: int, slot: int = 0
              ) -> "_Region | None":
        """The region holding [addr, addr + nbytes), starting inside it:
        two mappings can be adjacent, and an address at one's end is the
        next one's start. *slot* picks the lookup hint (0 bytes, 1 rows)."""
        r = self._hint[slot]
        if r is not None and r.addr <= addr < r.addr + r.size \
                and addr + nbytes <= r.addr + r.size:
            return r
        for r in list(self._regions.values()):
            if r.addr <= addr < r.addr + r.size \
                    and addr + nbytes <= r.addr + r.size:
                self._hint[slot] = r
                return r
        return None

    # -- tasks --------------------------------------------------------------
    def submit(self, items: Sequence, keys: Sequence, rows: Sequence,
               req=None) -> "concurrent.futures.Future | None":
        """Decode items[i] into rows[i] with the Philox key keys[i] on one
        worker. None (nothing sent) unless every item and row lies in a
        leased region and every row has the transform's shape: the caller
        keeps such samples on its threads."""
        t0 = time.perf_counter()
        recs = []
        for data, key, row in zip(items, keys, rows):
            if not (isinstance(data, np.ndarray) and isinstance(row, np.ndarray)
                    and row.shape == self.row_shape
                    and row.dtype == np.uint8 and data.dtype == np.uint8
                    and data.ndim == 1 and row.flags.c_contiguous
                    and data.flags.c_contiguous):
                return None
            a_in, a_out = _addr(data), _addr(row)
            r_in = self._find(a_in, data.size, 0)
            r_out = self._find(a_out, self.row_bytes, 1)
            if r_in is None or r_out is None:
                return None
            recs.append(W.ITEM.pack(r_in.rid, r_out.rid, a_in - r_in.addr,
                                    data.size, a_out - r_out.addr,
                                    key[0], key[1]))
        n = len(recs)
        fut: concurrent.futures.Future = concurrent.futures.Future()
        with self._lock:
            if self._error is not None:
                raise self._error
            if self._closing or not self._workers:
                raise DecodeWorkerError("decode workers are closed")
            w = min(self._workers, key=_load)
            task = next(self._ids)
            entry = w.pending[task] = [fut, n, req, 0.0]
            w.load += n
        try:
            w.sock.send(W.TASK.pack(W.MSG_TASK, task, n) + b"".join(recs))
        except OSError:
            pass  # the worker died: the reaper fails this task
        # the submit's share of decode_proc_parent_us, which the reaper
        # adds with its own when the task completes
        entry[3] = time.perf_counter() - t0
        return fut

    # -- teardown -----------------------------------------------------------
    def close(self) -> None:
        """Stop every worker (EOF on its socket, then kill after a grace),
        fail tasks still pending, unmap and close every region."""
        with self._cond:
            if self._closing:
                return
            self._closing = True
            workers = list(self._workers)
            self._cond.notify_all()
        reaper = getattr(self, "_reaper", None)
        os.write(self._wake_w, b"x")
        if reaper is not None:
            reaper.join()
        for w in workers:
            w.sock.close()
        for w in workers:
            if w.proc is None:
                continue
            try:
                w.proc.wait(_EXIT_TIMEOUT_S)
            except subprocess.TimeoutExpired:
                w.proc.kill()
                w.proc.wait()
        err = DecodeWorkerError("decode pool closed")
        with self._lock:
            pending = [e for w in workers for e in w.pending.values()]
            for w in workers:
                w.pending = {}
            self._workers = []
            regions = list(self._regions.values())
            self._regions = {}
            self._free = {"rows": [], "bytes": []}
            self._hint = [None, None]
        for e in pending:
            if not e[0].done():
                e[0].set_exception(err)
        for r in regions:
            r.close()
        os.close(self._wake_r)
        os.close(self._wake_w)
