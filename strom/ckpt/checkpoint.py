"""HBM→SSD checkpoint save/restore through the engine write path (ISSUE 13
tentpole, front 2).

The repo's existing :class:`~strom.pipelines.checkpoint.TrainCheckpointer`
delegates the train state to orbax — a generic serializer writing through
the page cache with no relationship to the I/O engine the rest of the data
plane runs on. This module is the engine-native alternative: a train state
(any pytree of arrays) is flattened into one flat ``data.bin`` of
4096-aligned leaf spans and written through ``submit_vectored(op="write")``
— O_DIRECT-aligned via the delivery slab pool, scheduler-granted (a
checkpoint save is a tenant like any other: PR 7 budgets/priority apply,
and a concurrent pipeline's read queues behind at most one write slice),
retry/breaker covered. Restore reads each leaf back with
``memcpy_ssd2tpu`` — the same SSD→accelerator hot path training data rides.

Layout (one checkpoint = one directory)::

    <dir>/manifest.json   # format tag, leaf table (shape/dtype/offset/
                          # nbytes/crc32), total_bytes
    <dir>/data.bin        # leaf bytes, each span 4096-aligned (gaps zero)

Crash safety: everything lands in ``<dir>.tmp-<pid>`` first, data and
manifest are fsync'd, and the directory rename is the COMMIT — a crash at
any earlier point leaves the previous checkpoint (or nothing) intact and a
``.tmp-*`` orphan that never looks like a checkpoint. Integrity: every
leaf carries a CRC32; ``restore_checkpoint(verify=True)`` detects on-media
corruption (a bit-flipped ``data.bin``) with a typed
:class:`CkptCorruptError` instead of silently training from garbage.
"""

from __future__ import annotations

import contextlib
import json
import os
import pickle
import shutil
import zlib
from typing import Any

import numpy as np

from strom.delivery.buffers import alloc_aligned

ALIGN = 4096          # leaf-span alignment: O_DIRECT offset granularity
FORMAT = "strom-ckpt-v1"
_STAGE_BYTES = 32 << 20   # staging slab per write flush

# columns of the checked-in pre-chip benchmark records that
# tools/compare_rounds.py reads for its "write path" section.
CKPT_FIELDS = (
    "ckpt_bytes",
    "ckpt_leaves",
    "ckpt_save_mb_per_s",
    "ckpt_restore_mb_per_s",
    "ckpt_pickle_save_mb_per_s",
    "ckpt_save_vs_pickle",
    "ckpt_roundtrip_ok",
)


class CkptError(RuntimeError):
    pass


class CkptCorruptError(CkptError):
    """A leaf's bytes on media do not match its manifest CRC."""


def _aligned(n: int) -> int:
    return (n + ALIGN - 1) // ALIGN * ALIGN


def _dtype_name(dt) -> str:
    # .name round-trips the accelerator dtypes ("bfloat16", "float8_e4m3fn")
    # where .str degrades them to opaque void ("|V2")
    return np.dtype(dt).name


def _np_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        # accelerator dtypes live in ml_dtypes (a jax dependency)
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _host_leaves(state: Any, *, snapshot: bool = False
                 ) -> tuple[list[np.ndarray], Any]:
    """Flatten *state* and pull every leaf to host memory as a contiguous
    numpy array (jax arrays device_get; scalars become 0-d arrays).

    ``snapshot=True`` (the async save path, strom/ckpt/async_save.py)
    additionally COPIES leaves the caller could mutate in place after this
    returns: jax arrays are immutable — holding the device_get result is
    already a stable snapshot — but a plain numpy leaf (an optimizer step
    counter someone increments, a running metric buffer) is live memory,
    and a background commit reading it mid-train would persist a torn
    state. The copy is the snapshot half of snapshot-then-commit: bounded
    by host memcpy bandwidth, never by NVMe."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(state)
    out = []
    for leaf in leaves:
        a = np.asarray(jax.device_get(leaf))
        if not a.flags["C_CONTIGUOUS"]:
            # ascontiguousarray unconditionally would also promote 0-d
            # scalars to (1,) and break the template shape check
            a = np.ascontiguousarray(a)
        elif snapshot and isinstance(leaf, np.ndarray):
            a = a.copy()
        out.append(a)
    return out, treedef


class _Stager:
    """Double-buffered staging for the checkpoint write stream: leaf spans
    are copied (CRC computed in the same pass — no separate integrity
    sweep over the whole state) into one of two O_DIRECT-aligned slabs
    while the OTHER slab's multi-chunk engine write drains on a writer
    thread — staging memcpy+CRC overlap the NVMe writes, so save wall is
    ~max(copy, write) instead of their sum. The slabs are the aligned
    bounce the caller's (arbitrarily-aligned) host arrays ride to disk."""

    def __init__(self, ctx, fi: int, tenant: "str | None",
                 priority: "str | None" = None):
        import concurrent.futures

        self._ctx = ctx
        self._fi = fi
        self._tenant = tenant
        # scheduler priority class for the engine writes (ISSUE 14): the
        # async checkpointer commits as "background" so a save stream never
        # outranks the training tenants' demand reads in the fair drain
        self._priority = priority
        pool = getattr(ctx, "_slab_pool", None)
        self._pool = pool
        self._bufs = [pool.acquire(_STAGE_BYTES) if pool is not None
                      else alloc_aligned(_STAGE_BYTES) for _ in range(2)]
        self._futs: list = [None, None]
        self._cur = 0
        self._used = 0
        self._chunks: list[tuple[int, int, int, int]] = []
        self._exec = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="strom-ckpt-write")

    def _flush_swap(self) -> None:
        """Hand the current slab to the writer thread and make the other
        one (its previous write drained) current."""
        if not self._chunks:
            return
        i = self._cur
        self._futs[i] = self._exec.submit(
            self._ctx.write_chunks, self._chunks, self._bufs[i],
            tenant=self._tenant, priority=self._priority)
        self._chunks = []
        self._used = 0
        self._cur = 1 - i
        f = self._futs[self._cur]
        if f is not None:
            self._futs[self._cur] = None
            f.result()  # the slab we are about to fill must be retired

    def add(self, file_off: int, a8: np.ndarray) -> int:
        """Stage one leaf's bytes; returns their CRC32 (computed during
        the copy — the bytes are already streaming through the cache)."""
        crc = 0
        pos = 0
        n = a8.nbytes
        buf = None
        while pos < n:
            free = _STAGE_BYTES - self._used
            if free == 0:
                self._flush_swap()
                free = _STAGE_BYTES
            buf = self._bufs[self._cur]
            take = min(free, n - pos)
            piece = a8[pos: pos + take]
            crc = zlib.crc32(piece, crc)
            buf[self._used: self._used + take] = piece
            self._chunks.append((self._fi, file_off + pos, self._used, take))
            self._used += take
            pos += take
        return crc & 0xFFFFFFFF

    def finish(self) -> None:
        """Drain everything (the LAST write included) — raises the first
        writer-thread failure here, before the manifest commits."""
        self._flush_swap()
        for i, f in enumerate(self._futs):
            if f is not None:
                self._futs[i] = None
                f.result()

    def close(self) -> None:
        self._exec.shutdown(wait=True)
        if self._pool is not None:
            for b in self._bufs:
                self._pool.release(b)
        self._bufs = []


def _build_manifest(leaves: "list[np.ndarray]",
                    extra: "dict | None" = None) -> dict:
    """Leaf table + span layout for a flattened state. ``extra`` is an
    opaque caller payload stored INSIDE the manifest (the resume layer
    puts the StepToken there, strom/ckpt/jobstate.py) — committed by the
    same rename as the data, so a checkpoint can never exist without its
    resume point or vice versa."""
    metas = []
    off = 0
    for i, a in enumerate(leaves):
        metas.append({
            "index": i,
            "shape": list(a.shape),
            "dtype": _dtype_name(a.dtype),
            "offset": off,
            "nbytes": int(a.nbytes),
            "crc32": 0,  # filled during staging (one pass over the bytes)
        })
        off += _aligned(max(a.nbytes, 1))
    return {"format": FORMAT, "total_bytes": off,
            "payload_bytes": int(sum(m["nbytes"] for m in metas)),
            "extra": extra or {},
            "leaves": metas}


def save_checkpoint(ctx, directory: str, state: Any, *,
                    tenant: "str | None" = None,
                    extra: "dict | None" = None,
                    priority: "str | None" = None) -> dict:
    """Write *state* (any pytree of arrays) to *directory* through the
    engine write path. Returns the manifest dict (``total_bytes`` is the
    payload size the bench rates). Crash-safe: the directory rename is the
    commit; an existing checkpoint at *directory* is replaced atomically
    (old state survives any crash before the rename lands). *extra* rides
    the manifest (see :func:`_build_manifest`); *priority* is the
    scheduler class the engine writes run under."""
    leaves, _treedef = _host_leaves(state)
    return _commit_checkpoint(ctx, directory, leaves,
                              _build_manifest(leaves, extra),
                              tenant=tenant, priority=priority)


def _commit_checkpoint(ctx, directory: str, leaves: "list[np.ndarray]",
                       manifest: dict, *, tenant: "str | None" = None,
                       priority: "str | None" = None) -> dict:
    """The commit half of a save: stage + engine-write the (already
    host-resident) leaves into ``<dir>.tmp-<pid>``, fsync, and rename —
    shared by the blocking save above and the async checkpointer's writer
    thread (strom/ckpt/async_save.py), so the two paths' crash-safety
    semantics can never drift."""
    metas = manifest["leaves"]
    total = manifest["total_bytes"]
    directory = os.path.abspath(directory)
    tmp = f"{directory}.tmp-{os.getpid()}"
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    try:
        data_path = os.path.join(tmp, "data.bin")
        fd = os.open(data_path, os.O_WRONLY | os.O_CREAT, 0o644)
        try:
            os.ftruncate(fd, total)  # gaps between spans read as zeros
        finally:
            os.close(fd)
        if total:
            # registered directly with the engine, NOT through the ctx
            # path-keyed registry: the tmp path is reused across saves in
            # one process, and a cached fd would write into the PREVIOUS
            # (renamed, committed) inode
            fi = ctx.engine.register_file(data_path,
                                          o_direct=ctx.config.o_direct,
                                          writable=True)
            try:
                st = _Stager(ctx, fi, tenant, priority)
                try:
                    for meta, a in zip(metas, leaves):
                        if meta["nbytes"]:
                            meta["crc32"] = st.add(
                                meta["offset"],
                                a.reshape(-1).view(np.uint8))
                    st.finish()
                finally:
                    st.close()
            finally:
                ctx.engine.unregister_file(fi)
        # durability before the commit rename: data, then manifest, then
        # the directory entries themselves
        for name, payload in (("data.bin", None),
                              ("manifest.json", manifest)):
            p = os.path.join(tmp, name)
            if payload is not None:
                with open(p, "w") as f:
                    json.dump(payload, f)
            fd = os.open(p, os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)
        dfd = os.open(tmp, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
        # commit: rename is atomic; replacing an existing checkpoint keeps
        # the old one live until the new one is fully durable. A FAILED
        # second rename rolls the old checkpoint back into place (neither
        # copy is ever destroyed by an exception); the only residual hole
        # is a hard process crash exactly between the two renames, which
        # leaves the previous checkpoint recoverable at
        # ``<dir>.old-<pid>`` (documented, never silently deleted by a
        # different process's later save)
        if os.path.exists(directory):
            old = f"{directory}.old-{os.getpid()}"
            shutil.rmtree(old, ignore_errors=True)
            os.rename(directory, old)
            try:
                os.rename(tmp, directory)
            except BaseException:
                with contextlib.suppress(OSError):
                    os.rename(old, directory)  # roll back: old state live
                raise
            shutil.rmtree(old, ignore_errors=True)
        else:
            os.rename(tmp, directory)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    parent = os.open(os.path.dirname(directory) or ".", os.O_RDONLY)
    try:
        os.fsync(parent)
    finally:
        os.close(parent)
    # the committed path names a NEW inode: stale fds / cached bytes for a
    # previous checkpoint at this directory must not serve a restore
    ctx.invalidate_file(os.path.join(directory, "data.bin"))
    return manifest


def load_manifest(directory: str) -> dict:
    p = os.path.join(directory, "manifest.json")
    try:
        with open(p) as f:
            manifest = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise CkptError(f"not a checkpoint: {p}: {e}") from None
    if manifest.get("format") != FORMAT:
        raise CkptError(f"unknown checkpoint format "
                        f"{manifest.get('format')!r} at {directory}")
    return manifest


def last_committed(directory: str) -> "tuple[str, dict] | None":
    """The committed checkpoint at *directory* as ``(path, manifest)``, or
    None when nothing committed. Cross-process recovery entry point
    (ISSUE 14): a restarted job calls this FIRST. Handles the one residual
    crash hole of the commit protocol — a hard kill exactly between the
    two renames of a replace-commit leaves *directory* absent and the
    previous checkpoint at ``<dir>.old-<pid>``; that orphan is rolled back
    into place here (the pid in the suffix belongs to the dead process, so
    nobody else can be mid-commit on it)."""
    directory = os.path.abspath(directory)
    try:
        return directory, load_manifest(directory)
    except CkptError:
        pass
    import glob as _glob

    if not os.path.exists(directory):
        for old in sorted(_glob.glob(f"{directory}.old-*")):
            try:
                manifest = load_manifest(old)
            except CkptError:
                continue
            os.rename(old, directory)
            return directory, manifest
    return None


def clean_orphans(directory: str) -> list[str]:
    """Remove ``<dir>.tmp-*`` staging orphans a killed process left behind
    (and any ``.old-*`` made redundant by a live committed checkpoint).
    Returns the paths removed. Never touches the committed checkpoint —
    orphans are, by the commit protocol, never loadable as one. Call
    AFTER :func:`last_committed` (which may still need an ``.old-*``)."""
    directory = os.path.abspath(directory)
    import glob as _glob

    removed = []
    for p in sorted(_glob.glob(f"{directory}.tmp-*")):
        shutil.rmtree(p, ignore_errors=True)
        removed.append(p)
    if os.path.isdir(directory):
        for p in sorted(_glob.glob(f"{directory}.old-*")):
            shutil.rmtree(p, ignore_errors=True)
            removed.append(p)
    return removed


def restore_checkpoint(ctx, directory: str, template: Any, *,
                       verify: bool = False,
                       tenant: "str | None" = None) -> Any:
    """Restore the pytree saved at *directory*, structured like *template*
    (the usual abstract-state contract: the treedef and leaf shapes/dtypes
    come from it and are checked against the manifest). Leaves are
    delivered with ``memcpy_ssd2tpu`` — the training-data hot path, hot
    cache and all. ``verify=True`` additionally host-reads each leaf and
    checks its CRC32 (typed :class:`CkptCorruptError` on mismatch) before
    the bytes are handed to the accelerator."""
    import jax

    manifest = load_manifest(directory)
    t_leaves, treedef = jax.tree_util.tree_flatten(template)
    metas = manifest["leaves"]
    if len(t_leaves) != len(metas):
        raise CkptError(f"template has {len(t_leaves)} leaves, checkpoint "
                        f"has {len(metas)}")
    data_path = os.path.join(directory, "data.bin")
    out = []
    for meta, t_leaf in zip(metas, t_leaves):
        shape = tuple(meta["shape"])
        dtype = _np_dtype(meta["dtype"])
        t_shape = tuple(getattr(t_leaf, "shape", np.shape(t_leaf)))
        if t_shape != shape:
            raise CkptError(f"leaf {meta['index']}: template shape "
                            f"{t_shape} != checkpoint {shape}")
        t_dtype = getattr(t_leaf, "dtype", None)
        if t_dtype is not None and _dtype_name(t_dtype) != meta["dtype"]:
            raise CkptError(f"leaf {meta['index']}: template dtype "
                            f"{_dtype_name(t_dtype)} != checkpoint "
                            f"{meta['dtype']}")
        if meta["nbytes"] == 0:
            out.append(np.empty(shape, dtype=dtype))
            continue
        if verify:
            host = ctx.pread(data_path, offset=meta["offset"],
                             length=meta["nbytes"], tenant=tenant)
            crc = zlib.crc32(host[: meta["nbytes"]]) & 0xFFFFFFFF
            if crc != meta["crc32"]:
                raise CkptCorruptError(
                    f"leaf {meta['index']} CRC mismatch at {data_path}"
                    f"+{meta['offset']}: {crc:#010x} != "
                    f"{meta['crc32']:#010x}")
            arr = jax.device_put(
                host[: meta["nbytes"]].view(dtype).reshape(shape))
        else:
            arr = ctx.memcpy_ssd2tpu(data_path, offset=meta["offset"],
                                     shape=shape, dtype=dtype,
                                     tenant=tenant)
        sh = getattr(t_leaf, "sharding", None)
        if sh is not None:
            arr = jax.device_put(arr, sh)
        if not hasattr(t_leaf, "shape") and np.ndim(t_leaf) == 0:
            # plain python scalar in the template (a step counter): hand
            # back the same kind, not a 0-d device array
            arr = np.asarray(arr).item()
        out.append(arr)
    return jax.tree_util.tree_unflatten(treedef, out)


# -- the baseline the bench compares against ---------------------------------
def save_pickle(path: str, state: Any) -> int:
    """pickle-to-filesystem baseline: device_get the tree and pickle.dump
    it through the page cache (fsync'd, same durability bar). Returns
    bytes written."""
    import jax

    host = jax.tree_util.tree_map(
        lambda a: np.asarray(jax.device_get(a)), state)
    with open(path, "wb") as f:
        pickle.dump(host, f, protocol=4)
        f.flush()
        os.fsync(f.fileno())
    return os.path.getsize(path)


def load_pickle(path: str) -> Any:
    with open(path, "rb") as f:
        return pickle.load(f)
