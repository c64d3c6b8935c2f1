"""N-process distributed data plane: launcher + per-host worker (ISSUE 15).

``parallel/dryrun.py`` lowers multi-device meshes in ONE process; this
module runs the data plane for real: N worker processes (CPU backend in
the sandbox, ``jax.distributed``-style launch), each owning

- a deterministic shard of the dataset — file-level ownership via the
  same ``multihost.assign_balanced`` every process computes with no
  coordination (sizes → LPT bins → ``bins[rank]``),
- a per-host :class:`StromContext` (engine + hot cache + spill +
  scheduler) that WARMS its owned files into the hot cache, serves them
  to peers over the :mod:`strom.dist.peers` extent service, and probes
  peers for rows whose backing file another host owns — an extent hot on
  host A is served to host B over the socket with host B's engine
  ``bytes_read`` delta = 0 (no duplicate SSD read),
- epoch barriers: ``parallel/multihost.epoch_barrier`` in mesh mode
  (jax.distributed), a rendezvous-file barrier in host mode (the
  jax-free ingest path tests and the dryrun tail use).

Global-batch assembly: every process computes the same seeded global row
order; batch rows map to per-row ``Extent``\\s and each host gathers ONLY
the rows backing its slice — in host mode as a numpy block via
``memcpy_ssd2host`` over the batch's :class:`ExtentList`, in mesh mode as
its addressable shards of ``memcpy_ssd2tpu(..., sharding=P('dp', None))``
assembled into the global array by
``jax.make_array_from_single_device_arrays`` inside the delivery layer.

Bit-identity contract (tests/test_dist.py): each worker sha256-hashes its
consumed rows in order; :func:`reference_shard_hashes` computes the same
hashes from the single-process pipeline's row stream, so any divergence
— shard math, peer bytes, fallback reads — fails loudly.

Run one worker: ``python -m strom.dist.launch --rank R --nproc N ...``;
:func:`launch_local` spawns and joins all N; :func:`measure_ingest` is
the one-call form the dryrun tail uses.
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import json
import os
import subprocess
import sys
import time

import numpy as np

RECORD_DTYPE = np.int32


# -- rendezvous (file-based: works with or without jax.distributed) ---------

def _atomic_write(path: str, text: str) -> None:
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(text)
    os.replace(tmp, path)


def rendezvous(workdir: str, phase: str, rank: int, nproc: int,
               payload: str = "", timeout_s: float = 60.0) -> list[str]:
    """Publish *payload* under ``<phase>_<rank>`` and block until every
    rank has published; returns all payloads in rank order. Doubles as a
    barrier (empty payloads) for the jax-free host mode."""
    os.makedirs(workdir, exist_ok=True)
    _atomic_write(os.path.join(workdir, f"{phase}_{rank}"), payload)
    deadline = time.monotonic() + timeout_s
    out: list[str] = []
    while True:
        out = []
        for r in range(nproc):
            p = os.path.join(workdir, f"{phase}_{r}")
            try:
                with open(p) as f:
                    out.append(f.read())
            except OSError:
                # a rank that died mid-run (kill-a-host injection, ISSUE
                # 20) published a ``dead_<rank>`` marker on its way out:
                # it satisfies every later barrier with an empty payload
                # so the survivors complete instead of timing out
                if os.path.exists(os.path.join(workdir, f"dead_{r}")):
                    out.append("")
                    continue
                break
        if len(out) == nproc:
            return out
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"rendezvous '{phase}': {len(out)}/{nproc} ranks after "
                f"{timeout_s}s")
        time.sleep(0.02)


# -- deterministic shard / sampler math (every process computes the same) ---

def dataset_layout(paths: "list[str]", seq_len: int):
    """(record_counts, cumulative_starts, rec_bytes) over sorted *paths*.
    Records are fixed-size ``seq_len`` int32 rows; trailing partial rows
    are ignored (same truncation the token pipelines apply)."""
    rec_bytes = seq_len * RECORD_DTYPE().itemsize
    counts = [os.path.getsize(p) // rec_bytes for p in paths]
    starts = np.concatenate(([0], np.cumsum(counts))).astype(np.int64)
    return counts, starts, rec_bytes


def owner_of(paths: "list[str]", nproc: int) -> dict:
    """path → owning rank, from the balanced file-size assignment
    (``multihost.assign_balanced`` — deterministic, coordination-free)."""
    from strom.parallel.multihost import assign_balanced

    sizes = [os.path.getsize(p) for p in paths]
    bins = assign_balanced(sizes, nproc)
    return {paths[i]: r for r, b in enumerate(bins) for i in b}


def global_row_order(total: int, need: int, seed: int) -> np.ndarray:
    """The first *need* rows of the seeded epoch-concatenated shuffle —
    the same stream on every process (and in the single-process
    reference), epochs permuted independently."""
    rng = np.random.default_rng(seed)
    chunks = []
    got = 0
    while got < need:
        perm = rng.permutation(total)
        chunks.append(perm)
        got += total
    return np.concatenate(chunks)[:need]


def _row_extent(row: int, paths, starts, rec_bytes):
    f = int(np.searchsorted(starts, row, side="right")) - 1
    return paths[f], int(row - starts[f]) * rec_bytes


def reference_shard_hashes(paths: "list[str]", seq_len: int, nproc: int,
                           batch: int, steps: int, seed: int
                           ) -> list[str]:
    """Per-rank sha256 of the rows each host must consume — the
    single-process pipeline's row stream, computed with plain numpy (no
    engine, no cache, no peers): the bit-identity oracle."""
    counts, starts, rec_bytes = dataset_layout(paths, seq_len)
    arrays = [np.fromfile(p, dtype=RECORD_DTYPE)[: c * seq_len]
              .reshape(c, seq_len) for p, c in zip(paths, counts)]
    order = global_row_order(int(starts[-1]), batch * steps, seed)
    per_host = batch // nproc
    hashes = [hashlib.sha256() for _ in range(nproc)]
    for step in range(steps):
        rows = order[step * batch: (step + 1) * batch]
        for r in range(nproc):
            for row in rows[r * per_host: (r + 1) * per_host]:
                f = int(np.searchsorted(starts, row, side="right")) - 1
                hashes[r].update(arrays[f][row - starts[f]].tobytes())
    return [h.hexdigest() for h in hashes]


# -- the worker --------------------------------------------------------------

def run_worker(args: argparse.Namespace) -> dict:
    """One host of the data plane; returns the result dict it also writes
    to ``<workdir>/result_<rank>.json``."""
    from strom.config import StromConfig
    from strom.delivery.core import StromContext
    from strom.delivery.extents import ExtentList

    rank, nproc = args.rank, args.nproc
    paths = sorted(os.path.join(args.data, f) for f in os.listdir(args.data)
                   if f.endswith(".bin"))
    if not paths:
        raise RuntimeError(f"no .bin shards under {args.data}")
    counts, starts, rec_bytes = dataset_layout(paths, args.seq_len)
    per_host = args.batch // nproc
    if per_host * nproc != args.batch:
        raise ValueError(f"batch {args.batch} not divisible by {nproc}")

    mesh_mode = args.mode == "mesh"
    if mesh_mode:
        # jax.distributed-style launch: rank 0 published the coordinator
        # port during the peer rendezvous (below we need jax BEFORE the
        # context so device_put targets exist)
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices_per_proc}")

    cfg = StromConfig(
        engine=args.engine, queue_depth=8, num_buffers=16,
        hot_cache_bytes=args.hot_cache_bytes, hot_cache_admit="always",
        # the sandbox fixtures live on tmpfs-ish paths; spill off keeps
        # the worker lean (the peer tier serves from RAM here)
        fault_plan=args.fault_plan,
        # ISSUE 19: compressed peer wire — both halves flip together so
        # every server compresses and every client asks (mixed fleets
        # degrade per-peer via the comp_ok latch, exercised in tests)
        peer_compress=args.peer_compress,
        # ISSUE 20: --batch-extents overrides the batched-transport chunk
        # size (0 = the unbatched v1 wire, the bench's A/B arm); -1 keeps
        # the config default
        **({"dist_batch_max_extents": args.batch_extents}
           if args.batch_extents >= 0 else {}),
        # a per-rank flight dir: the coordinator's fleet watchdog dumps a
        # host-stamped bundle here when a peer goes dark
        flight_dir=os.path.join(args.workdir, f"flight_{rank}"))
    # metrics_port=0 (explicit) = ephemeral port: every worker is
    # scrapeable so rank 0's ClusterView can federate the fleet
    ctx = StromContext(cfg, metrics_port=0)
    result: dict = {"rank": rank, "ok": 0}
    try:
        # peer service up, addresses exchanged, ownership → owner_fn
        addr = ctx.serve_peers()
        addrs = rendezvous(args.workdir, "peers", rank, nproc, addr,
                           timeout_s=args.timeout_s)
        peer_map = {r: a for r, a in enumerate(addrs) if r != rank}
        # consistent-hash extent directory (ISSUE 20): every rank builds
        # the identical ring from the shared membership set — the same
        # coordination-free determinism assign_balanced gave the static
        # owner map, plus live re-ownership: a tripped peer's death is
        # published through this workdir and every survivor's throttled
        # poll recomputes the ring (epoch++), so its keys re-route to
        # live owners mid-run
        from strom.dist.directory import ExtentDirectory

        directory = ExtentDirectory(range(nproc), rank,
                                    rendezvous_dir=args.workdir,
                                    poll_interval_s=0.05)
        ctx.attach_peers(peer_map, directory=directory)

        # observability rendezvous: every rank publishes its metrics
        # address; rank 0 federates them all (itself included) into the
        # /cluster view for the run's lifetime
        obs = rendezvous(
            args.workdir, "obs", rank, nproc,
            json.dumps({"metrics":
                        f"127.0.0.1:{ctx.metrics_server.port}",
                        "peer": addr}),
            timeout_s=args.timeout_s)
        if rank == 0:
            hosts = {f"rank{r}": json.loads(o)["metrics"]
                     for r, o in enumerate(obs)}
            ctx.attach_cluster(hosts, interval_s=0.25, stall_s=5.0)

        if mesh_mode:
            import jax

            coord = addrs[0].rsplit(":", 1)[0]
            ports = rendezvous(args.workdir, "coord", rank, nproc,
                               str(_pick_port()) if rank == 0 else "x",
                               timeout_s=args.timeout_s)
            jax.distributed.initialize(
                coordinator_address=f"{coord}:{ports[0]}",
                num_processes=nproc, process_id=rank)

        # warm phase: the owner pays the SSD read for its files ONCE;
        # admission is "always" so every byte lands hot. The barrier
        # after it guarantees ingest-phase peer probes find owners warm.
        for p in paths:
            if directory.ring_owner(p) == rank:
                ctx.pread(p, 0, counts[paths.index(p)] * rec_bytes)
        rendezvous(args.workdir, "warm", rank, nproc,
                   timeout_s=args.timeout_s)

        engine_warm_bytes = ctx.engine.stats().get("bytes_read", 0)
        order = global_row_order(int(starts[-1]), args.batch * args.steps,
                                 args.seed)
        sha = hashlib.sha256()
        asm_us: list[float] = []
        rows_per_epoch = int(starts[-1])
        consumed = 0
        if mesh_mode:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            from strom.parallel.mesh import make_mesh
            from strom.parallel.multihost import epoch_barrier

            n_global = len(jax.devices())
            mesh = make_mesh({"dp": n_global}, devices=jax.devices())
            sharding = NamedSharding(mesh, P("dp", None))
        t0 = time.perf_counter()
        for step in range(args.steps):
            rows = order[step * args.batch: (step + 1) * args.batch]
            ta = time.perf_counter()
            if mesh_mode:
                # the tentpole assembly path: the WHOLE batch as one
                # ExtentList, delivered sharded — each process gathers
                # only the rows backing its addressable devices (through
                # cache → spill → peers → engine), device_puts them, and
                # make_array_from_single_device_arrays stitches the
                # global batch inside memcpy_ssd2tpu
                ext = ExtentList([
                    _row_extent(int(r), paths, starts, rec_bytes)
                    + (rec_bytes,) for r in rows])
                batch_arr = ctx.memcpy_ssd2tpu(
                    ext, shape=(args.batch, args.seq_len),
                    dtype=RECORD_DTYPE, sharding=sharding)
                local = np.concatenate(
                    [np.asarray(s.data) for s in sorted(
                        batch_arr.addressable_shards,
                        key=lambda s: s.index[0].start or 0)])
            else:
                mine = rows[rank * per_host: (rank + 1) * per_host]
                ext = ExtentList([
                    _row_extent(int(r), paths, starts, rec_bytes)
                    + (rec_bytes,) for r in mine])
                local = ctx.memcpy_ssd2host(
                    ext, shape=(per_host, args.seq_len),
                    dtype=RECORD_DTYPE)
            asm_us.append((time.perf_counter() - ta) * 1e6)
            sha.update(np.ascontiguousarray(local).tobytes())
            if (args.die_after_step >= 0 and args.die_rank == rank
                    and step == args.die_after_step):
                # kill-a-host injection (ISSUE 20): publish the death
                # marker (later barriers tolerate us, survivors' ring
                # polls re-own our keys) and vanish with NO cleanup —
                # exactly how a crashed host looks to the fleet
                _atomic_write(os.path.join(args.workdir, f"dead_{rank}"),
                              str(step))
                os._exit(17)
            prev_epoch, consumed = consumed // rows_per_epoch, \
                consumed + args.batch
            if consumed // rows_per_epoch != prev_epoch:
                # epoch boundary: every host finishes the epoch before
                # any host starts the next (SURVEY.md §2.3 barrier duty)
                if mesh_mode:
                    epoch_barrier(f"dist-epoch-{consumed // rows_per_epoch}")
                else:
                    rendezvous(args.workdir,
                               f"epoch{consumed // rows_per_epoch}", rank,
                               nproc, timeout_s=args.timeout_s)
        wall = time.perf_counter() - t0
        # exit barrier: a fast worker must keep its peer server up until
        # EVERY worker finished fetching — closing early turns the tail
        # of a slower host's batch stream into connection-refused
        # fallbacks (correct but slow, and it would understate the
        # peer-hit ratio)
        rendezvous(args.workdir, "done", rank, nproc,
                   timeout_s=args.timeout_s)
        dist = ctx.stats(sections=["dist"]).get("dist", {})
        if rank == 0 and ctx.cluster_view is not None:
            # one last scrape with every worker still alive, then fold
            # the federation gauges into the result measure_ingest reports
            ctx.cluster_view.poll_now()
            result.update(ctx.cluster_view.stats())
        # per-host trace file: tools/trace_report.py merges these into one
        # Perfetto timeline with cross-host flow arrows
        from strom.obs import chrome_trace

        with contextlib.suppress(Exception):
            chrome_trace.dump(
                os.path.join(args.workdir, f"trace_{rank}.json"),
                meta={"host": f"rank{rank}", "peer_addr": addr})
        asm = sorted(asm_us)
        items = args.steps * per_host
        result.update({
            "ok": 1,
            "steps": args.steps,
            "items": items,
            "wall_s": round(wall, 4),
            "items_per_s": round(items / wall, 2) if wall else 0.0,
            "sha256": sha.hexdigest(),
            "ingest_bytes": items * rec_bytes,
            "engine_ingest_bytes":
                ctx.engine.stats().get("bytes_read", 0) - engine_warm_bytes,
            "assembly_wait_p50_us": asm[len(asm) // 2] if asm else 0.0,
            "assembly_wait_p99_us":
                asm[min(len(asm) - 1, int(0.99 * len(asm)))] if asm else 0.0,
            **dist,
        })
    finally:
        ctx.close()
    _atomic_write(os.path.join(args.workdir, f"result_{rank}.json"),
                  json.dumps(result))
    return result


def _pick_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# -- the launcher ------------------------------------------------------------

def launch_local(nproc: int, data_dir: str, workdir: str, *,
                 steps: int = 4, batch: int = 8, seq_len: int = 16,
                 seed: int = 0, engine: str = "python",
                 mode: str = "host", devices_per_proc: int = 1,
                 hot_cache_bytes: int = 64 * 1024 * 1024,
                 fault_plan: str = "", peer_compress: bool = False,
                 batch_extents: int = -1, die_rank: int = -1,
                 die_after_step: int = -1,
                 timeout_s: float = 120.0) -> list[dict]:
    """Spawn *nproc* workers over *data_dir*, join them, return their
    result dicts in rank order. Raises on a worker that died without a
    result (its tail is included). *batch_extents* overrides the batched
    transport's chunk size (0 = unbatched, -1 = config default);
    *die_rank*/*die_after_step* arm the kill-a-host injection (that
    worker exits uncleanly after the given step — its result row reads
    ``ok 0, rc 17``)."""
    os.makedirs(workdir, exist_ok=True)
    for f in os.listdir(workdir):
        # stale rendezvous/result files from a previous run in the same
        # workdir would satisfy (or corrupt) this run's barriers
        if f.startswith(("peers_", "coord_", "warm_", "epoch", "done_",
                         "result_", "obs_", "trace_", "dead_",
                         "ring_dead_")):
            with contextlib.suppress(OSError):
                os.unlink(os.path.join(workdir, f))
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    # workers are CPU-backend processes (host mode loads none; mesh mode a
    # virtual CPU mesh): the parent may hold the chip
    env["JAX_PLATFORMS"] = "cpu"
    procs = [subprocess.Popen(
        [sys.executable, "-m", "strom.dist.launch",
         "--rank", str(r), "--nproc", str(nproc), "--data", data_dir,
         "--workdir", workdir, "--steps", str(steps),
         "--batch", str(batch), "--seq-len", str(seq_len),
         "--seed", str(seed), "--engine", engine, "--mode", mode,
         "--devices-per-proc", str(devices_per_proc),
         "--hot-cache-bytes", str(hot_cache_bytes),
         "--timeout-s", str(timeout_s),
         "--batch-extents", str(batch_extents),
         "--die-rank", str(die_rank),
         "--die-after-step", str(die_after_step)]
        + (["--fault-plan", fault_plan] if fault_plan else [])
        + (["--peer-compress"] if peer_compress else []),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=repo, env=env) for r in range(nproc)]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout_s + 60)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    results = []
    for r, (p, out) in enumerate(zip(procs, outs)):
        path = os.path.join(workdir, f"result_{r}.json")
        try:
            with open(path) as f:
                res = json.load(f)
        except (OSError, json.JSONDecodeError):
            res = {"rank": r, "ok": 0}
        res["rc"] = p.returncode
        if p.returncode != 0 or not res.get("ok"):
            res["tail"] = out[-2000:]
        results.append(res)
    return results


def make_fixture(data_dir: str, *, files: int = 4, records: int = 48,
                 seq_len: int = 16, seed: int = 7) -> list[str]:
    """A small multi-file token fixture (plain ``tofile``, jax-free)."""
    os.makedirs(data_dir, exist_ok=True)
    rng = np.random.default_rng(seed)
    paths = []
    for i in range(files):
        p = os.path.join(data_dir, f"shard{i}.bin")
        rng.integers(0, 32000, (records, seq_len),
                     dtype=RECORD_DTYPE).tofile(p)
        paths.append(p)
    return paths


def measure_ingest(procs: int, workdir: str, *, data_dir: "str | None" = None,
                   steps: int = 4, batch: int = 8, seq_len: int = 16,
                   seed: int = 0, engine: str = "python",
                   mode: str = "host", devices_per_proc: int = 1,
                   fault_plan: str = "", peer_compress: bool = False,
                   batch_extents: int = -1, die_rank: int = -1,
                   die_after_step: int = -1,
                   timeout_s: float = 120.0) -> dict:
    """The whole acceptance in one call: launch *procs* workers, verify
    bit-identity against the single-process reference, fold the measured
    rates + peer traffic into the ``DIST_BENCH_FIELDS`` columns (the
    dryrun tail rides this). With a kill injection armed (*die_rank* >= 0)
    the acceptance covers the SURVIVORS: each must exit clean and
    bit-identical — the dead rank is expected to vanish."""
    if data_dir is None:
        data_dir = os.path.join(workdir, "data")
        make_fixture(data_dir, seq_len=seq_len)
    paths = sorted(os.path.join(data_dir, f) for f in os.listdir(data_dir)
                   if f.endswith(".bin"))
    ref = reference_shard_hashes(paths, seq_len, procs, batch, steps, seed)
    results = launch_local(
        procs, data_dir, os.path.join(workdir, f"run{procs}"),
        steps=steps, batch=batch, seq_len=seq_len, seed=seed, engine=engine,
        mode=mode, devices_per_proc=devices_per_proc, fault_plan=fault_plan,
        peer_compress=peer_compress, batch_extents=batch_extents,
        die_rank=die_rank, die_after_step=die_after_step,
        timeout_s=timeout_s)
    judged = [(i, r) for i, r in enumerate(results) if i != die_rank]
    ok = all(r.get("rc") == 0 and r.get("ok") for _, r in judged) and \
        all(r.get("sha256") == ref[i] for i, r in judged)
    walls = [r.get("wall_s", 0.0) for r in results if r.get("ok")]
    items = sum(r.get("items", 0) for r in results)
    hit = sum(r.get("peer_hit_bytes", 0) for r in results)
    served = sum(r.get("peer_served_bytes", 0) for r in results)
    ingest = sum(r.get("ingest_bytes", 0) for r in results)
    engine_bytes = sum(r.get("engine_ingest_bytes", 0) for r in results)
    # ISSUE 19: server-side compression tallies (raw bytes in, wire bytes
    # out); the wire total replaces compressed spans' logical bytes with
    # what actually crossed the socket
    comp_in = sum(r.get("peer_comp_bytes_in", 0) for r in results)
    comp_out = sum(r.get("peer_comp_bytes_out", 0) for r in results)
    from strom.obs.federation import FED_FIELDS

    rank0 = results[0] if results else {}
    return {
        "dist_ok": int(ok),
        "dist_procs": procs,
        # federation gauges from rank 0's ClusterView (present when the
        # obs rendezvous completed; 0 on degraded/partial runs)
        **{k: rank0.get(k, 0) for k in FED_FIELDS},
        "dist_steps": steps,
        "dist_items_per_s":
            round(items / max(walls), 2) if walls and max(walls) else 0.0,
        "dist_peer_hit_ratio":
            round(hit / ingest, 4) if ingest else 0.0,
        "dist_peer_hit_bytes": hit,
        "dist_peer_served_bytes": served,
        "dist_engine_ingest_bytes": engine_bytes,
        "dist_peer_comp_bytes_in": comp_in,
        "dist_peer_comp_bytes_out": comp_out,
        "dist_peer_wire_bytes": served - comp_in + comp_out,
        "peer_comp_ratio": round(comp_in / comp_out, 4) if comp_out else 0.0,
        "dist_assembly_wait_p99_us": round(max(
            (r.get("assembly_wait_p99_us", 0.0) for r in results),
            default=0.0), 1),
        "dist_peer_rtt_p99_us": round(max(
            (r.get("peer_rtt_p99_us", 0.0) for r in results),
            default=0.0), 1),
        # ISSUE 20 fabric v2 columns: per-extent round-trip cost (worst
        # worker), decoded-frame traffic, and how well the conn pool
        # amortised dials across the whole fleet
        "peer_rtt_per_extent_us": round(max(
            (r.get("peer_rtt_per_extent_us", 0.0) for r in results),
            default=0.0), 1),
        "peer_frame_hit_bytes":
            sum(r.get("peer_frame_hit_bytes", 0) for r in results),
        "peer_conn_reuse_ratio": round(
            sum(r.get("peer_conn_reuses", 0) for r in results)
            / max(sum(r.get("peer_conn_opens", 0)
                      + r.get("peer_conn_reuses", 0) for r in results), 1),
            4),
        "workers": results,
    }


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(description="strom dist worker")
    ap.add_argument("--rank", type=int, required=True)
    ap.add_argument("--nproc", type=int, required=True)
    ap.add_argument("--data", required=True)
    ap.add_argument("--workdir", required=True)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, dest="seq_len", default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", default="python")
    ap.add_argument("--mode", choices=("host", "mesh"), default="host")
    ap.add_argument("--devices-per-proc", type=int,
                    dest="devices_per_proc", default=1)
    ap.add_argument("--hot-cache-bytes", type=int, dest="hot_cache_bytes",
                    default=64 * 1024 * 1024)
    ap.add_argument("--fault-plan", dest="fault_plan", default="")
    ap.add_argument("--peer-compress", dest="peer_compress",
                    action="store_true")
    ap.add_argument("--timeout-s", type=float, dest="timeout_s",
                    default=120.0)
    ap.add_argument("--batch-extents", type=int, dest="batch_extents",
                    default=-1)
    ap.add_argument("--die-rank", type=int, dest="die_rank", default=-1)
    ap.add_argument("--die-after-step", type=int, dest="die_after_step",
                    default=-1)
    args = ap.parse_args(argv)
    res = run_worker(args)
    print(json.dumps(res))
    return 0 if res.get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
