"""A JPEG decode worker process, and the wire format it shares with its pool.

:class:`strom.formats.decode_procs.DecodeProcs` starts one of these per
decode worker (``python decode_worker.py <fd> <spec> <lib> <regions>``) and
talks to it over one ``AF_UNIX``/``SOCK_SEQPACKET`` socket: one message per
send, boundaries kept, file descriptors passed alongside (``SCM_RIGHTS``).

Image bytes and output rows never cross the socket. Both live in shared
memory regions (``memfd`` segments the pool creates and hands over as file
descriptors); a task names each sample by (region, offset) pairs plus the
Philox key of its RNG, and the worker writes the finished row in place.
The worker rebuilds the pool's transform from its parameters with
:func:`jpeg_core.train_crop`, so every pixel, crop and flip is the one the
thread path draws from the same key.

Run as a script, this file imports only the standard library, numpy, cv2
and ``jpeg_core`` (loaded by path, beside it), and loads the native decoder
straight from the library path it is given: no ``strom`` package import,
no JAX.
"""

from __future__ import annotations

import json
import mmap
import os
import struct
import sys
import time

# parent -> worker
MSG_MAP = 1     # a shared region to map; its fd rides the message
MSG_UNMAP = 2   # drop a region
MSG_TASK = 3    # decode a run of samples
# worker -> parent
MSG_READY = 4   # imports done, native decoder state
MSG_DONE = 5    # a task finished: per-sample status and timing
MSG_FAIL = 6    # a task raised something other than ValueError

MAP = struct.Struct("<BIQ")            # kind, region, size
UNMAP = struct.Struct("<BI")           # kind, region
TASK = struct.Struct("<BQH")           # kind, task, samples
# in region, out region, in offset, in length, out offset, Philox key
ITEM = struct.Struct("<IIQIQQQ")
READY = struct.Struct("<BB")           # kind, native decoder live
DONE = struct.Struct("<BQHd7Q")        # kind, task, samples, CPU us, counters
RESULT = struct.Struct("<Bdd")         # status, start (perf_counter s), wall s
FAIL = struct.Struct("<BQ")            # kind, task; the error text follows

ST_OK = 0
ST_ZEROED = 1   # ValueError: the row was zeroed (the per-sample policy)

RECV_BYTES = 1 << 16


def serve(sock, core, spec: dict, lib_path: str, regions: dict) -> int:
    """The worker loop: map regions, decode tasks, until the parent closes
    its end of *sock*."""
    import socket
    from functools import partial

    import numpy as np

    if core._HAVE_CV2:
        core.cv2.setNumThreads(0)  # one image at a time per process
    nat = None
    if lib_path:
        import ctypes

        nat = core.bind_native(ctypes.CDLL(lib_path))
    tally = dict.fromkeys(core.COUNTERS, 0)

    def count(name: str, n: int = 1) -> None:
        tally[name] += n

    size = int(spec["size"])
    row_bytes = size * size * 3
    crop = partial(core.train_crop, size=size,
                   reduced_scale=bool(spec["reduced_scale"]),
                   scale=tuple(spec["scale"]), ratio=tuple(spec["ratio"]),
                   native=bool(spec["native"]), roi=bool(spec["roi"]),
                   nat=nat, count=count)
    views = {rid: np.frombuffer(mm, np.uint8) for rid, mm in regions.items()}
    sock.send(READY.pack(MSG_READY, nat is not None))
    lap = time.thread_time()
    while True:
        msg, fds, _, _ = socket.recv_fds(sock, RECV_BYTES, 1)
        if not msg:
            return 0
        kind = msg[0]
        if kind == MSG_MAP:
            _, rid, nbytes = MAP.unpack_from(msg)
            views[rid] = np.frombuffer(mmap.mmap(fds[0], nbytes), np.uint8)
            os.close(fds[0])
            continue
        if kind == MSG_UNMAP:
            views.pop(UNMAP.unpack_from(msg)[1], None)
            continue
        _, task, n = TASK.unpack_from(msg)
        out = []
        try:
            for k in range(n):
                ir, orr, ioff, ilen, ooff, k0, k1 = ITEM.unpack_from(
                    msg, TASK.size + k * ITEM.size)
                data = views[ir][ioff: ioff + ilen]
                row = views[orr][ooff: ooff + row_bytes].reshape(size, size,
                                                                 3)
                rng = np.random.Generator(np.random.Philox(key=[k0, k1]))
                t0 = time.perf_counter()
                try:
                    crop(data, rng, row)
                    status = ST_OK
                except ValueError:
                    row[...] = 0
                    status = ST_ZEROED
                out.append(RESULT.pack(status, t0, time.perf_counter() - t0))
        except Exception as e:  # a transform bug fails its task, loudly
            sock.send(FAIL.pack(MSG_FAIL, task)
                      + f"{type(e).__name__}: {e}".encode()[:4096])
            continue
        now = time.thread_time()
        sock.send(DONE.pack(MSG_DONE, task, n, (now - lap) * 1e6,
                            *tally.values()) + b"".join(out))
        lap = now
        for name in tally:
            tally[name] = 0


def main(argv: list[str]) -> int:
    import importlib.util
    import socket

    here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location(
        "strom_jpeg_core", os.path.join(here, "jpeg_core.py"))
    core = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = core
    spec.loader.exec_module(core)
    sock = socket.socket(fileno=int(argv[1]))
    regions = {}
    for rid, fd, nbytes in json.loads(argv[4]):
        regions[rid] = mmap.mmap(fd, nbytes)
        os.close(fd)
    return serve(sock, core, json.loads(argv[2]), argv[3], regions)


if __name__ == "__main__":
    # the script's directory is strom/formats: keep its modules from
    # shadowing top-level names for the rest of this process
    if sys.path and os.path.abspath(sys.path[0]) == \
            os.path.dirname(os.path.abspath(__file__)):
        sys.path.pop(0)
    sys.exit(main(sys.argv))
