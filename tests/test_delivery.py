"""memcpy_ssd2tpu end-to-end on the fake 8-device CPU mesh: integrity vs
open().read() golden bytes, sharded assembly, async handles, RAID0 sources
(SURVEY.md §4.2 Integrity + Device delivery rows)."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from strom.config import StromConfig
from strom.delivery.core import StripedFile, StromContext


@pytest.fixture()
def ctx(engine_name):
    c = StromContext(StromConfig(engine=engine_name, queue_depth=16, num_buffers=16))
    yield c
    c.close()


def test_sync_single_device(ctx, data_file):
    path, data = data_file
    arr = ctx.memcpy_ssd2tpu(path, length=len(data) // 2 * 2, dtype=np.uint8)
    assert isinstance(arr, jax.Array)
    np.testing.assert_array_equal(np.asarray(arr), data[: len(data) // 2 * 2])


def test_sync_shaped_dtype(ctx, data_file):
    path, data = data_file
    arr = ctx.memcpy_ssd2tpu(path, shape=(1024, 256), dtype=np.float32)
    golden = data[: 1024 * 256 * 4].view(np.float32).reshape(1024, 256)
    np.testing.assert_array_equal(np.asarray(arr), golden)


def test_sync_offset_read(ctx, data_file):
    path, data = data_file
    arr = ctx.memcpy_ssd2tpu(path, offset=12345, length=4096)
    np.testing.assert_array_equal(np.asarray(arr), data[12345:12345 + 4096])


def test_sharded_batch_axis(ctx, data_file):
    path, data = data_file
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))
    arr = ctx.memcpy_ssd2tpu(path, shape=(16, 1024), dtype=np.uint8, sharding=sharding)
    assert arr.sharding == sharding
    golden = data[: 16 * 1024].reshape(16, 1024)
    np.testing.assert_array_equal(np.asarray(arr), golden)
    # every device holds exactly its shard
    for shard in arr.addressable_shards:
        np.testing.assert_array_equal(np.asarray(shard.data), golden[shard.index])


def test_sharded_2d(ctx, data_file):
    path, data = data_file
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("dp", "tp"))
    sharding = NamedSharding(mesh, P("dp", "tp"))
    arr = ctx.memcpy_ssd2tpu(path, shape=(8, 512), dtype=np.float32, sharding=sharding)
    golden = data[: 8 * 512 * 4].view(np.float32).reshape(8, 512)
    np.testing.assert_array_equal(np.asarray(arr), golden)


def test_replicated(ctx, data_file):
    path, data = data_file
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("dp",))
    sharding = NamedSharding(mesh, P(None))
    arr = ctx.memcpy_ssd2tpu(path, shape=(256,), dtype=np.uint8, sharding=sharding)
    np.testing.assert_array_equal(np.asarray(arr), data[:256])


def test_async_handle(ctx, data_file):
    path, data = data_file
    h = ctx.memcpy_ssd2tpu(path, length=1024 * 1024, async_=True)
    arr = h.result(timeout=30)
    assert h.done()
    np.testing.assert_array_equal(np.asarray(arr), data[: 1024 * 1024])


def test_async_handle_freed_without_cyclic_gc(ctx, data_file):
    """A finished handle and its array go when the last reference does:
    nothing the handle registered on its future points back at it, so
    the cyclic GC is not needed to free a delivered array."""
    import gc
    import weakref

    path, _ = data_file
    gc.disable()
    try:
        h = ctx.memcpy_ssd2tpu(path, length=65536, async_=True)
        h.result(timeout=30)
        ref = weakref.ref(h)
        del h
        assert ref() is None
    finally:
        gc.enable()


def test_async_many_in_flight(ctx, data_file):
    path, data = data_file
    handles = [ctx.memcpy_ssd2tpu(path, offset=i * 65536, length=65536, async_=True)
               for i in range(8)]
    for i, h in enumerate(handles):
        np.testing.assert_array_equal(
            np.asarray(h.result(timeout=30)), data[i * 65536:(i + 1) * 65536])


def test_striped_source(ctx, tmp_path, rng):
    n, chunk = 4, 8192
    logical = rng.integers(0, 256, size=n * chunk * 6, dtype=np.uint8)
    members = []
    for m in range(n):
        mdata = bytearray()
        for ci in range(m, len(logical) // chunk, n):
            mdata.extend(logical[ci * chunk:(ci + 1) * chunk])
        p = tmp_path / f"m{m}.bin"
        p.write_bytes(bytes(mdata))
        members.append(str(p))
    sf = StripedFile(tuple(members), chunk)
    assert sf.size == len(logical)
    arr = ctx.memcpy_ssd2tpu(sf, length=len(logical))
    np.testing.assert_array_equal(np.asarray(arr), logical)


def test_striped_sharded(ctx, tmp_path, rng):
    n, chunk = 2, 4096
    logical = rng.integers(0, 256, size=n * chunk * 8, dtype=np.uint8)
    members = []
    for m in range(n):
        mdata = bytearray()
        for ci in range(m, len(logical) // chunk, n):
            mdata.extend(logical[ci * chunk:(ci + 1) * chunk])
        p = tmp_path / f"sm{m}.bin"
        p.write_bytes(bytes(mdata))
        members.append(str(p))
    sf = StripedFile(tuple(members), chunk)
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))
    shape = (16, len(logical) // 16)
    arr = ctx.memcpy_ssd2tpu(sf, shape=shape, dtype=np.uint8, sharding=sharding)
    np.testing.assert_array_equal(np.asarray(arr), logical.reshape(shape))


def test_ssd2host_plain(ctx, data_file):
    """memcpy_ssd2host: the delivered path stopped at the device_put
    boundary — returns the bytes zero-copy in a host array."""
    path, data = data_file
    arr = ctx.memcpy_ssd2host(path, length=len(data) // 2 * 2)
    assert isinstance(arr, np.ndarray)
    np.testing.assert_array_equal(arr, data[: len(data) // 2 * 2])
    # shaped/dtype/offset forms match the ssd2tpu semantics
    arr = ctx.memcpy_ssd2host(path, shape=(512, 128), dtype=np.float32)
    np.testing.assert_array_equal(
        arr, data[: 512 * 128 * 4].view(np.float32).reshape(512, 128))
    arr = ctx.memcpy_ssd2host(path, offset=12345, length=4096)
    np.testing.assert_array_equal(arr, data[12345:12345 + 4096])


def test_ssd2host_out_buffer(ctx, data_file):
    """out=: the caller's preallocated (registrable) dest IS the returned
    array — zero-copy all the way."""
    from strom.delivery.buffers import alloc_aligned, buf_addr

    path, data = data_file
    n = 1 << 20
    dest = alloc_aligned(n)
    ctx.engine.register_dest(dest)
    arr = ctx.memcpy_ssd2host(path, length=n, out=dest)
    assert buf_addr(arr) == buf_addr(dest)  # same memory, no bounce
    np.testing.assert_array_equal(arr, data[:n])
    # too-small out refuses instead of short-reading
    with pytest.raises(ValueError, match="holds"):
        ctx.memcpy_ssd2host(path, length=n, out=alloc_aligned(n // 2))
    # strided out refuses instead of silently reading into a hidden copy
    with pytest.raises(ValueError, match="contiguous"):
        ctx.memcpy_ssd2host(path, length=n, out=alloc_aligned(2 * n)[::2])


def test_ssd2host_through_striped_alias_is_byte_exact(tmp_path, rng,
                                                      engine_name):
    """memcpy_ssd2host reads a whole file striped RAID0-style over four
    members back through the striped alias, byte for byte."""
    from strom.config import StromConfig
    from strom.delivery.core import StromContext
    from strom.engine.raid0 import stripe_file

    n = 4 << 20  # a multiple of the 256KiB stripe
    data = rng.integers(0, 256, n, dtype=np.uint8)
    p = tmp_path / "ratio_raid.bin"
    data.tofile(p)
    chunk = 64 * 1024
    members = [f"{p}.r{i}of4.c{chunk}" for i in range(4)]
    assert stripe_file(str(p), members, chunk) == n
    ctx = StromContext(StromConfig(engine=engine_name, queue_depth=8,
                                   num_buffers=8))
    try:
        virt = str(tmp_path / "ratio.raid0")
        ctx.register_striped(virt, members, chunk, size=n)
        got = ctx.memcpy_ssd2host(virt, length=n)
        np.testing.assert_array_equal(got, data)
    finally:
        ctx.close()


def test_ssd2host_striped_alias(ctx, tmp_path, rng):
    """The host path rides striped-alias resolution like the device path."""
    n, chunk = 2, 4096
    logical = rng.integers(0, 256, size=n * chunk * 4, dtype=np.uint8)
    members = []
    for m in range(n):
        mdata = bytearray()
        for ci in range(m, len(logical) // chunk, n):
            mdata.extend(logical[ci * chunk:(ci + 1) * chunk])
        p = tmp_path / f"hm{m}.bin"
        p.write_bytes(bytes(mdata))
        members.append(str(p))
    virt = str(tmp_path / "host.raid0")
    ctx.register_striped(virt, members, chunk)
    arr = ctx.memcpy_ssd2host(virt)
    np.testing.assert_array_equal(arr, logical)


def test_short_file_raises(ctx, data_file):
    path, data = data_file
    with pytest.raises(Exception):
        ctx.memcpy_ssd2tpu(path, length=len(data) + 4096)


def test_context_survives_failed_transfer(ctx, data_file):
    """A mid-transfer error must drain in-flight ops, not poison the engine
    for the next transfer (regression: stale completions aliasing new tags)."""
    path, data = data_file
    for _ in range(3):
        with pytest.raises(Exception):
            ctx.memcpy_ssd2tpu(path, length=len(data) + 256 * 1024)
        arr = ctx.memcpy_ssd2tpu(path, length=4096)
        np.testing.assert_array_equal(np.asarray(arr), data[:4096])


def test_module_level_api(data_file, engine_name):
    import strom

    path, data = data_file
    strom.init(StromConfig(engine=engine_name))
    try:
        arr = strom.memcpy_ssd2tpu(path, length=4096)
        np.testing.assert_array_equal(np.asarray(arr), data[:4096])
        h = strom.memcpy_ssd2tpu(path, length=4096, async_=True)
        np.testing.assert_array_equal(np.asarray(strom.memcpy_wait(h)), data[:4096])
        assert strom.buffer_info()["num_buffers"] > 0
        assert strom.stats()["engine"]["bytes_read"] >= 8192
        assert "strom_" in strom.prometheus()
        rep = strom.check_file(path)
        assert rep.size == len(data)
    finally:
        strom.close()


def test_registered_striped_alias(ctx, tmp_path, rng):
    """register_striped: reads addressed to the aliased PATH — directly or
    via an ExtentList a format reader planned against it — stripe-decode
    across the members (the md-raid0 'files keep ordinary names' contract)."""
    from strom.delivery.extents import ExtentList
    from strom.engine.raid0 import stripe_file

    n, chunk = 3, 4096
    data = rng.integers(0, 256, size=n * chunk * 4, dtype=np.uint8)
    src = tmp_path / "logical.bin"
    data.tofile(src)
    members = [str(tmp_path / f"am{i}.bin") for i in range(n)]
    stripe_file(str(src), members, chunk)
    virt = str(tmp_path / "virtual.bin")  # never exists on disk
    ctx.register_striped(virt, members, chunk)

    arr = ctx.memcpy_ssd2tpu(virt, length=len(data))
    np.testing.assert_array_equal(np.asarray(arr), data)

    el = ExtentList([(virt, 100, 5000), (virt, 9000, 300)])
    got = ctx.pread(el)
    np.testing.assert_array_equal(
        got, np.concatenate([data[100:5100], data[9000:9300]]))


def test_sourceio_readahead_windows(ctx, tmp_path, rng):
    """SourceIO must serve tarfile/pyarrow-style access (small reads, seeks
    back and forth, reads straddling the readahead window) correctly, with
    far fewer engine reads than client reads."""
    from strom.delivery.core import SourceIO

    data = rng.integers(0, 256, 100_000, dtype=np.uint8).tobytes()
    p = tmp_path / "sio.bin"
    (p).write_bytes(data)
    f = SourceIO(ctx, str(p), readahead=4096)
    # forward walk in 512B steps: one engine read per 4KiB window
    for off in range(0, 8192, 512):
        assert f.read(512) == data[off: off + 512]
    # seek back (cache miss behind the window) and straddle windows
    f.seek(100)
    assert f.read(8000) == data[100:8100]
    # read past EOF clamps; read at EOF returns b""
    f.seek(99_000)
    assert f.read(5000) == data[99_000:]
    assert f.read(10) == b""
    # SEEK_END / SEEK_CUR
    import io as _io
    f.seek(-100, _io.SEEK_END)
    assert f.read(-1) == data[-100:]
    f.seek(0)
    f.seek(50, _io.SEEK_CUR)
    assert f.read(10) == data[50:60]
    # io.IOBase semantics: negative computed positions and unknown whence
    # raise ValueError here, not a confusing EngineError/KeyError later
    with pytest.raises(ValueError):
        f.seek(-5)
    with pytest.raises(ValueError):
        f.seek(10)
        f.seek(-11, _io.SEEK_CUR)
    with pytest.raises(ValueError):
        f.seek(0, 7)


@pytest.mark.parametrize("rings", [1, 2])
def test_prometheus_engine_histogram(data_file, engine_name, rings):
    """strom.prometheus() must expose the ENGINE's counters and a valid
    cumulative read-latency histogram, not just the global counters (the
    reference exposes exactly these via its /proc node). rings=2: the
    multi-ring aggregation must keep the exposition intact — dashboards
    keyed on these series target exactly those deployments."""
    import strom
    from strom.config import StromConfig

    if rings > 1 and engine_name != "uring":
        pytest.skip("multi-ring is uring-only")
    path, data = data_file
    strom.close()
    strom.init(StromConfig(engine=engine_name, engine_rings=rings,
                           queue_depth=8, num_buffers=8))
    try:
        strom.memcpy_ssd2tpu(path, length=1 << 20).block_until_ready()
        txt = strom.prometheus()
        assert "strom_engine_read_latency_us_bucket" in txt
        assert "strom_engine_bytes_read" in txt
        assert "strom_context_ssd2tpu_bytes" in txt
        # cumulative monotonicity + +Inf == count
        counts = [int(line.rsplit(" ", 1)[1])
                  for line in txt.splitlines()
                  if line.startswith("strom_engine_read_latency_us_bucket")]
        assert counts == sorted(counts) and counts[-1] > 0
        count_line = [l for l in txt.splitlines()
                      if l.startswith("strom_engine_read_latency_us_count")]
        assert int(count_line[0].rsplit(" ", 1)[1]) == counts[-1]
    finally:
        strom.close()


def test_sharded_group_failure_drains_cleanly(ctx, tmp_path, rng):
    """Group-parallel sharded delivery: when one device group's read fails
    (EOF short read), the transfer raises EngineError only after every
    in-flight group drained, and the context stays fully usable."""
    data = rng.integers(0, 256, 64 * 1024, dtype=np.uint8)
    p = str(tmp_path / "short.bin")
    data.tofile(p)
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("dp",))
    sharding = NamedSharding(mesh, P("dp", None))
    from strom.engine.base import EngineError

    # plan 128KiB over 8 groups; the file holds 64KiB, so late groups fail
    with pytest.raises(EngineError):
        ctx.memcpy_ssd2tpu(p, shape=(8, 16 * 1024), dtype=np.uint8,
                           sharding=sharding)
    # the drain contract: at raise time no group read may still be in
    # flight inside the engine
    assert ctx.engine.in_flight() == 0
    # reuse after failure: the engine and executors must be intact
    arr = ctx.memcpy_ssd2tpu(p, shape=(8, 8 * 1024), dtype=np.uint8,
                             sharding=sharding)
    np.testing.assert_array_equal(
        np.asarray(arr).ravel(), data)
