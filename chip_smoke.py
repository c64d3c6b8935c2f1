#!/usr/bin/env python
"""Run strom-tpu's main path once on a TPU and check what comes out.

NVMe -> io_uring/O_DIRECT engine -> host slabs -> device_put -> jax.Array in
HBM -> the training or scan step that consumes it, through the library, in
this one process (a child that touched JAX would fight it for the chip).
Every fixture is generated from a seed under ``.chip_smoke_data/`` in the
checkout (O_DIRECT needs a real filesystem, not tmpfs). Every phase raises on failure; nothing is retried.

    python chip_smoke.py               # one chip: device, bulk delivery,
                                       # ResNet-50, llama+flash, parquet scan
    python chip_smoke.py --four-chips  # four chips: dp=4 sharded delivery
                                       # and the dp=4 llama step vs one chip

The last line of stdout is the contract line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
it is printed only when every phase passed on a TPU.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(ROOT, ".chip_smoke_data")
SEED = 0
MiB = 1 << 20
GiB = 1 << 30
RESNET_MODEL = "resnet50"
LLAMA_MODEL = "small"


def log(msg: str) -> None:
    print(msg, flush=True)


def make_file(path: str, size: int, seed: int = SEED) -> None:
    """*size* seeded random bytes at *path*, written in 64 MiB pieces."""
    import numpy as np

    rng = np.random.default_rng(seed)
    with open(path, "wb") as f:
        left = size
        while left:
            n = min(64 * MiB, left)
            f.write(rng.bytes(n))
            left -= n
        os.fsync(f.fileno())


def assert_matches_file(arr, path: str, offset: int = 0,
                        piece: int = 256 * MiB) -> None:
    """Byte-exact check of a delivered array against the file, in pieces on
    the host (no whole second copy of the file in RAM)."""
    import numpy as np

    host = np.asarray(arr).reshape(-1).view(np.uint8)
    disk = np.memmap(path, dtype=np.uint8, mode="r")
    for lo in range(0, host.size, piece):
        hi = min(lo + piece, host.size)
        if not np.array_equal(host[lo:hi], disk[offset + lo: offset + hi]):
            bad = lo + int(np.argmax(host[lo:hi] != disk[offset + lo:
                                                        offset + hi]))
            raise AssertionError(f"delivered bytes differ from {path} at "
                                 f"byte {offset + bad}")


def drop_cache_hint(path: str) -> None:
    """fsync + posix_fadvise(DONTNEED), so a read reaches the media and not
    the page cache (freshly written pages are dirty and stay cached until
    synced)."""
    from strom.probe.residency import drop_cache

    drop_cache(path)


# -- phases -------------------------------------------------------------------
def device_phase(count: int) -> dict:
    """The devices must be *count* TPUs; no CPU fallback."""
    import importlib.metadata

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found {devs[0].platform!r} "
                         f"devices, not a TPU; nothing was run")
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU devices, JAX "
                         f"found {len(devs)}")
    log(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)} jax={jax.__version__} "
        f"libtpu={importlib.metadata.version('libtpu')}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def engine_phase(path: str) -> None:
    import strom
    from strom._core.build import build_key

    rep = strom.check_file(path)
    log(f"engine: auto chose {strom.context().engine.name}; fixture dir "
        f"{DATA} tier={rep.tier.value} fs={rep.fs_type}")
    log(f"native build key: {build_key()}")


def bulk_phase(path: str, size: int, probe_bytes: int) -> None:
    """BASELINE config 1 into HBM: sync, async, then the slab-recycling
    probe (two streamed transfers back to back with the pool on)."""
    import strom
    from strom.config import StromConfig
    from strom.delivery.core import StromContext

    ctx = strom.context()
    if size < ctx.config.overlap_min_bytes:
        raise ValueError(f"bulk size {size} is below overlap_min_bytes: the "
                         "streamed slab path would not run")
    drop_cache_hint(path)
    t0 = time.perf_counter()
    arr = strom.memcpy_ssd2tpu(path, length=size)
    arr.block_until_ready()
    dt = time.perf_counter() - t0
    assert_matches_file(arr, path)
    log(f"bulk sync: {size} B byte-exact; {size / dt / 1e9:.3f} GB/s "
        f"(smoke figure, not a benchmark)")
    # the process's first device allocation: its peak is the assembly's
    mem = arr.devices().pop().memory_stats() or {}
    log(f"bulk sync: peak device memory in use "
        f"{mem.get('peak_bytes_in_use', 'not reported')} B for a {size} B "
        f"streamed assembly")
    del arr

    drop_cache_hint(path)
    t0 = time.perf_counter()
    handle = strom.memcpy_ssd2tpu(path, length=size, async_=True)
    arr = strom.memcpy_wait(handle)
    arr.block_until_ready()
    dt = time.perf_counter() - t0
    assert_matches_file(arr, path)
    log(f"bulk async: {size} B byte-exact; {size / dt / 1e9:.3f} GB/s "
        f"(smoke figure, not a benchmark)")
    del arr

    # slab recycling: small enough streaming thresholds that both transfers
    # stream, so the second one's slabs come back out of the first one's
    chunk = max(probe_bytes // 8, MiB)
    cfg = StromConfig(overlap_chunk_bytes=chunk, overlap_min_bytes=2 * chunk)
    probe = StromContext(cfg)
    try:
        drop_cache_hint(path)
        first = probe.memcpy_ssd2tpu(path, offset=0, length=probe_bytes)
        second = probe.memcpy_ssd2tpu(path, offset=probe_bytes,
                                      length=probe_bytes)
        pool = probe.stats(sections=["slab_pool"]).get("slab_pool")
        assert_matches_file(first, path, 0)
        assert_matches_file(second, path, probe_bytes)
    finally:
        probe.close()
    if pool is None or pool["hits"] <= 0:
        raise AssertionError(f"slab pool recycled nothing: {pool}")
    log(f"slab recycling: 2 x {probe_bytes} B streamed back to back, both "
        f"byte-exact; pool hits={pool['hits']} misses={pool['misses']}")


def make_wds_tar(path: str, n_samples: int, image_size: int) -> None:
    """A WebDataset tar of *n_samples* seeded random q90 JPEGs, each twice
    *image_size* a side, with a ``.cls`` label beside each."""
    import io
    import tarfile

    import cv2
    import numpy as np

    rng = np.random.default_rng(SEED)
    with tarfile.open(path, "w") as tf:
        for i in range(n_samples):
            img = rng.integers(0, 256, (image_size * 2, image_size * 2, 3),
                               dtype=np.uint8)
            ok, buf = cv2.imencode(".jpg", img, [cv2.IMWRITE_JPEG_QUALITY, 90])
            assert ok
            for name, data in ((f"s{i:06d}.jpg", buf.tobytes()),
                               (f"s{i:06d}.cls", str(i % 1000).encode())):
                info = tarfile.TarInfo(name)
                info.size = len(data)
                tf.addfile(info, io.BytesIO(data))
    drop_cache_hint(path)


def train_loop(pipe, step, steps: int) -> tuple[float, float, int]:
    """One warm-up step (its compile included), then *steps* more on the
    clock, ended by a host fetch of the loss. Returns (loss, steps per
    second, data-stall steps)."""
    float(step(next(pipe)))
    base = pipe.data_stall_steps
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(next(pipe))
    loss = float(loss)
    rate = steps / (time.perf_counter() - t0)
    return loss, rate, pipe.data_stall_steps - base


def resnet_phase(batch: int, image_size: int, steps: int) -> None:
    """BASELINE config 2 from predecoded records: a JPEG tar staged once as
    a ``.pdec`` shard, its first delivered batch against a numpy read of
    the records, then SGD train steps on what the pipeline delivers."""
    import functools

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import strom
    from strom.formats.predecoded import LABELS_SUFFIX, predecode_wds
    from strom.models.resnet import (ResNetConfig, init_params, loss_fn,
                                     normalize_images)
    from strom.parallel.mesh import make_mesh
    from strom.pipelines import make_predecoded_vision_pipeline

    ctx = strom.context()
    tar = os.path.join(DATA, "images.tar")
    make_wds_tar(tar, max(batch * 4, 256), image_size)
    pdec = predecode_wds(ctx, [tar], os.path.join(DATA, "images.pdec"),
                         image_size=image_size)
    drop_cache_hint(pdec)
    record = (image_size, image_size, 3)
    records = np.memmap(pdec, dtype=np.uint8, mode="r").reshape(-1, *record)
    labels = np.load(pdec + LABELS_SUFFIX)
    # the pipeline's epoch-0 order: Philox(seed 0, epoch 0) permutation
    idx = np.random.Generator(np.random.Philox(key=[0, 0])).permutation(
        records.shape[0])[:batch]

    cfg = getattr(ResNetConfig, RESNET_MODEL)()
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    # replicated, as the step gives them back: one compile
    replicated = NamedSharding(mesh, P())
    params, bn_state = jax.device_put(init_params(jax.random.key(0), cfg),
                                      replicated)

    @functools.partial(jax.jit, donate_argnums=(0,), out_shardings=replicated)
    def sgd_step(p, s, images, labels):
        (loss, new_s), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            p, s, normalize_images(images), labels, cfg)
        return jax.tree.map(lambda w, g: w - 1e-3 * g, p, grads), new_s, loss

    def step(b):
        nonlocal params, bn_state
        params, bn_state, loss = sgd_step(params, bn_state, b[0],
                                          b[1] % cfg.num_classes)
        return loss

    with make_predecoded_vision_pipeline(
            ctx, [pdec], batch=batch, image_size=image_size,
            sharding=NamedSharding(mesh, P("dp", None, None, None)),
            prefetch_depth=steps + 2) as pipe:
        imgs, lbls = next(pipe)
        if not (np.array_equal(np.asarray(imgs), records[idx])
                and np.array_equal(np.asarray(lbls), labels[idx])):
            raise AssertionError("first predecoded batch differs from the "
                                 "numpy read of its records")
        log(f"resnet: first batch ({batch} records) equals the numpy read")
        loss, rate, stalls = train_loop(pipe, step, steps)
    if not math.isfinite(loss):
        raise AssertionError(f"resnet train loss is not finite: {loss}")
    log(f"resnet: {RESNET_MODEL} {batch}x{image_size}^2 {steps} steps, "
        f"loss={loss} finite; train {rate * batch:.1f} img/s, {stalls} "
        f"stalls (smoke figures)")


def llama_phase(batch: int, seq_len: int, steps: int) -> None:
    """The llama pipeline feeding the flash train step; the lowered step
    must hold the Mosaic kernel, and flash must match dense on the
    device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import strom
    from strom.models.llama import LlamaConfig
    from strom.ops.flash_attention import _dense_ref, flash_attention
    from strom.parallel.mesh import make_mesh
    from strom.parallel.train import (init_train_state, make_optimizer,
                                      make_train_step)
    from strom.pipelines import make_llama_pipeline

    cfg = getattr(LlamaConfig, LLAMA_MODEL)()
    tok_path = os.path.join(DATA, "tokens.bin")
    rng = np.random.default_rng(SEED)
    rng.integers(0, cfg.vocab, ((steps + 2) * batch * 2, seq_len + 1),
                 dtype=np.int32).tofile(tok_path)
    drop_cache_hint(tok_path)
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    opt = make_optimizer()
    state = init_train_state(jax.random.key(0), cfg, mesh, opt)
    step_fn = make_train_step(cfg, mesh, opt, attn="flash")

    def step(toks):
        nonlocal state
        state, m = step_fn(state, toks)
        return m["loss"]

    sharding = NamedSharding(mesh, P("dp", None))
    with make_llama_pipeline(strom.context(), [tok_path], batch=batch,
                             seq_len=seq_len, sharding=sharding,
                             prefetch_depth=steps + 2) as pipe:
        loss, rate, stalls = train_loop(pipe, step, steps)
    if not math.isfinite(loss):
        raise AssertionError(f"llama train loss is not finite: {loss}")
    log(f"llama: {LLAMA_MODEL} flash {batch}x{seq_len + 1} {steps} steps, "
        f"loss={loss} finite; train {rate * batch * (seq_len + 1):.1f} "
        f"tok/s, {stalls} stalls (smoke figures)")

    toks = jax.ShapeDtypeStruct((batch, seq_len + 1), jnp.int32,
                                sharding=sharding)
    text = step_fn.lower(state, toks).as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError("lowered flash train step holds no "
                             "tpu_custom_call: the kernel was interpreted")
    log("llama: lowered flash train step holds tpu_custom_call")

    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    S = seq_len + 1
    kq, kk, kv = jax.random.split(jax.random.key(SEED), 3)
    q = jax.random.normal(kq, (batch, S, H, Dh), jnp.bfloat16)
    k = jax.random.normal(kk, (batch, S, KV, Dh), jnp.bfloat16)
    v = jax.random.normal(kv, (batch, S, KV, Dh), jnp.bfloat16)
    got = jax.jit(flash_attention)(q, k, v).astype(jnp.float32)
    want = jax.jit(_dense_ref, static_argnums=3)(q, k, v, True).astype(
        jnp.float32)
    # both outputs are rounded to bf16 (8 mantissa bits): a few ulps apart
    # at most, i.e. within 2e-2 absolute plus 2e-2 of the value
    diff = jnp.abs(got - want)
    err = float(jnp.max(diff))
    excess = float(jnp.max(diff - 2e-2 * jnp.abs(want)))
    if not excess <= 2e-2:
        raise AssertionError(f"flash vs dense differ by more than bf16 "
                             f"tolerance: max abs err {err}")
    log(f"llama: flash forward vs dense at B={batch} S={S} H={H} KV={KV} "
        f"Dh={Dh} bf16: max abs err {err:.3e}, within 2e-2 + 2e-2*|dense|")


def parquet_phase(rows: int, row_groups: int) -> None:
    """BASELINE config 5: a predicate scan with the aggregate on the default
    device; integer aggregates must equal a pyarrow/numpy reference."""
    import jax.numpy as jnp
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    import strom
    from strom.ops.pushdown import col
    from strom.pipelines.parquet_scan import parquet_scan_aggregate

    rng = np.random.default_rng(SEED)
    path = os.path.join(DATA, "scan.parquet")
    pq.write_table(pa.table({
        "seq": np.arange(rows, dtype=np.int64),
        "qty": rng.integers(1, 51, rows, dtype=np.int32),
        "disc": rng.integers(0, 11, rows, dtype=np.int32),
        "price": rng.standard_normal(rows).astype(np.float32),
        "payload": rng.integers(0, 1 << 40, rows, dtype=np.int64),
    }), path, row_group_size=rows // row_groups)
    cutoff = rows * 5 // 8

    def map_fn(d):
        hit = (d["qty"] < 24) & (d["disc"] >= 5)
        return {"rows": jnp.sum(jnp.ones_like(d["qty"])),
                "hits": jnp.sum(hit.astype(jnp.int32)),
                "qty_hit": jnp.sum(jnp.where(hit, d["qty"], 0)),
                "disc_sum": jnp.sum(d["disc"])}

    t0 = time.perf_counter()
    got = parquet_scan_aggregate(strom.context(), [path], ["qty", "disc"],
                                 map_fn, predicate=col("seq") < cutoff)
    dt = time.perf_counter() - t0
    got = {k: int(v) for k, v in got.items()}

    ref_t = pq.read_table(path, columns=["seq", "qty", "disc"])
    keep = ref_t["seq"].to_numpy() < cutoff
    qty = ref_t["qty"].to_numpy()[keep].astype(np.int64)
    disc = ref_t["disc"].to_numpy()[keep].astype(np.int64)
    hit = (qty < 24) & (disc >= 5)
    want = {"rows": int(keep.sum()), "hits": int(hit.sum()),
            "qty_hit": int(qty[hit].sum()), "disc_sum": int(disc.sum())}
    if got != want:
        raise AssertionError(f"parquet aggregate {got} != reference {want}")
    log(f"parquet: {rows} rows / {row_groups} groups, seq < {cutoff} pushed "
        f"down, aggregate on the default device {got} == pyarrow/numpy "
        f"reference; {rows / dt:.0f} rows/s (smoke figure)")


def four_chip_phase(path: str, size: int, batch: int, seq_len: int) -> None:
    """dp=4: sharded delivery (each shard on its own device, byte-exact),
    then the llama pipeline feeding a dp=4 step whose loss must equal the
    single-device step's on the same batch."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import strom
    from strom.models.llama import LlamaConfig
    from strom.parallel.mesh import make_mesh
    from strom.parallel.train import (init_train_state, make_optimizer,
                                      make_train_step)
    from strom.pipelines import make_llama_pipeline

    devs = jax.devices()[:4]
    mesh4 = make_mesh({"dp": 4}, devices=devs)
    width = 4096
    shape = (size // width, width)
    arr = strom.memcpy_ssd2tpu(path, shape=shape, dtype=np.uint8,
                               sharding=NamedSharding(mesh4, P("dp", None)))
    shards = arr.addressable_shards
    on = {s.device for s in shards}
    if len(shards) != 4 or len(on) != 4:
        raise AssertionError(f"{len(shards)} shards on {len(on)} distinct "
                             f"devices; dp=4 needs 4 on 4")
    for s in shards:
        rows = s.index[0]
        assert_matches_file(s.data, path, rows.start * width)
    log(f"four chips: dp=4 sharded delivery of {size} B, shards on "
        f"{sorted(d.id for d in on)}, each byte-exact")
    del arr, shards

    cfg = getattr(LlamaConfig, LLAMA_MODEL)()
    tok_path = os.path.join(DATA, "tokens4.bin")
    rng = np.random.default_rng(SEED)
    rng.integers(0, cfg.vocab, (batch * 4, seq_len + 1),
                 dtype=np.int32).tofile(tok_path)
    opt = make_optimizer()
    ctx = strom.context()
    with make_llama_pipeline(ctx, [tok_path], batch=batch, seq_len=seq_len,
                             sharding=NamedSharding(mesh4, P("dp", None))
                             ) as pipe:
        toks4 = next(pipe)
    on = {s.device for s in toks4.addressable_shards}
    if len(on) != 4:
        raise AssertionError(f"llama batch sits on {len(on)} devices, not 4")
    host = np.asarray(toks4)
    state4 = init_train_state(jax.random.key(0), cfg, mesh4, opt)
    _, m4 = make_train_step(cfg, mesh4, opt, attn="flash")(state4, toks4)
    loss4 = float(m4["loss"])
    del state4

    mesh1 = make_mesh({"dp": 1}, devices=devs[:1])
    toks1 = jax.device_put(host, NamedSharding(mesh1, P("dp", None)))
    state1 = init_train_state(jax.random.key(0), cfg, mesh1, opt)
    _, m1 = make_train_step(cfg, mesh1, opt, attn="flash")(state1, toks1)
    loss1 = float(m1["loss"])
    rel = abs(loss4 - loss1) / abs(loss1)
    if not (math.isfinite(loss4) and rel <= 1e-3):
        raise AssertionError(f"dp=4 loss {loss4} vs one chip {loss1} "
                             f"(rel {rel:.2e} > 1e-3)")
    log(f"four chips: llama {LLAMA_MODEL} flash, P('dp') batch {batch}x"
        f"{seq_len + 1} on 4 devices; dp=4 loss {loss4} vs one-chip "
        f"{loss1} (rel diff {rel:.2e} <= 1e-3)")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true", dest="four_chips",
                    help="run only the dp=4 sharded delivery and the dp=4 "
                         "llama step against one chip")
    args = ap.parse_args(argv)

    from strom.utils.compile_cache import cache_entries, enable_compile_cache

    cache = enable_compile_cache()
    device = device_phase(4 if args.four_chips else 1)
    log(f"compile cache: {cache} ({cache_entries(cache)} entries before)")

    shutil.rmtree(DATA, ignore_errors=True)
    os.makedirs(DATA)
    try:
        bulk = os.path.join(DATA, "bulk.bin")
        make_file(bulk, 2 * GiB)
        engine_phase(bulk)
        if args.four_chips:
            four_chip_phase(bulk, 2 * GiB, batch=8, seq_len=2047)
        else:
            bulk_phase(bulk, 2 * GiB, probe_bytes=512 * MiB)
            resnet_phase(batch=64, image_size=224, steps=4)
            llama_phase(batch=8, seq_len=2047, steps=4)
            parquet_phase(rows=8_000_000, row_groups=32)
    finally:
        import strom

        strom.close()
        shutil.rmtree(DATA, ignore_errors=True)
    log(f"compile cache: {cache} ({cache_entries(cache)} entries after)")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
