"""T5 pipelines: sampler checkpointing, llama/vision loaders on the fake
8-device mesh, parquet scan fan-out (SURVEY.md §4.2 'Device delivery' and
'Overlap/0-stall' rows)."""

import os

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from strom.config import StromConfig
from strom.delivery.core import StromContext
from strom.parallel.mesh import make_mesh
from strom.pipelines.sampler import (EpochShuffleSampler, SamplerState,
                                     dataset_fingerprint, load_loader_state,
                                     save_loader_state)


@pytest.fixture(scope="module")
def ctx():
    c = StromContext(StromConfig(engine="python", queue_depth=8, num_buffers=8))
    yield c
    c.close()


@pytest.fixture(scope="module")
def mesh():
    return make_mesh({"dp": 8}, devices=jax.devices()[:8])


# ---------------------------------------------------------------- sampler
class TestSampler:
    def test_covers_epoch_exactly(self):
        s = EpochShuffleSampler(100, 10, seed=1)
        it = iter(s)
        seen = np.concatenate([next(it) for _ in range(10)])
        assert sorted(seen) == list(range(100))

    def test_deterministic_and_reshuffled(self):
        a = [next(iter(EpochShuffleSampler(50, 50, seed=7))) for _ in range(1)][0]
        b = next(iter(EpochShuffleSampler(50, 50, seed=7)))
        np.testing.assert_array_equal(a, b)
        it = iter(EpochShuffleSampler(50, 50, seed=7))
        e0, e1 = next(it), next(it)
        assert not np.array_equal(e0, e1)  # epoch 1 reshuffles
        np.testing.assert_array_equal(sorted(e0), sorted(e1))

    def test_resume_mid_epoch(self):
        s1 = EpochShuffleSampler(100, 10, seed=3)
        it1 = iter(s1)
        for _ in range(13):
            next(it1)
        resumed = EpochShuffleSampler(
            100, 10, seed=3,
            state=SamplerState(epoch=1, batch_in_epoch=3, seed=3))
        np.testing.assert_array_equal(next(iter(resumed)), next(it1))

    def test_state_file_roundtrip(self, tmp_path, data_file):
        path, _ = data_file
        fp = dataset_fingerprint((path,))
        st = SamplerState(epoch=2, batch_in_epoch=5, seed=9)
        f = str(tmp_path / "loader.json")
        save_loader_state(f, st, fp, {"k": 1})
        got, extra = load_loader_state(f, fp)
        assert got == st and extra == {"k": 1}
        with pytest.raises(ValueError, match="different dataset"):
            load_loader_state(f, {"paths": ["other"], "sizes": [1]})

    def test_ragged_rejected(self):
        with pytest.raises(ValueError, match="drop_last"):
            EpochShuffleSampler(10, 3, drop_last=False)


# ---------------------------------------------------------- llama pipeline
class TestLlamaPipeline:
    @pytest.fixture(scope="class")
    def token_shards(self, tmp_path_factory):
        rng = np.random.default_rng(11)
        td = tmp_path_factory.mktemp("tokens")
        paths, golden = [], []
        seq = 16  # record = 17 tokens
        for i in range(3):
            # ids < LlamaConfig.tiny().vocab so batches feed the train step
            t = rng.integers(0, 500, 17 * 20 + 5, dtype=np.int32)  # 20 rec + tail
            p = str(td / f"shard{i}.bin")
            t.tofile(p)
            paths.append(p)
            golden.append(t[: 17 * 20].reshape(20, 17))
        return paths, np.concatenate(golden), seq

    def test_sequential_content_golden(self, ctx, mesh, token_shards):
        from strom.pipelines import make_llama_pipeline

        paths, golden, seq = token_shards
        sharding = NamedSharding(mesh, P("dp", None))
        with make_llama_pipeline(ctx, paths, batch=8, seq_len=seq,
                                 sharding=sharding, shuffle=False) as pipe:
            b0 = next(pipe)
            assert b0.shape == (8, 17) and b0.sharding == sharding
            np.testing.assert_array_equal(np.asarray(b0), golden[:8])
            np.testing.assert_array_equal(np.asarray(next(pipe)), golden[8:16])

    def test_shuffled_epoch_covers_all(self, ctx, mesh, token_shards):
        from strom.pipelines import make_llama_pipeline

        paths, golden, seq = token_shards
        # 60 records don't split over 8 devices: replicate (also exercises the
        # planner's read-once-put-everywhere dedupe)
        sharding = NamedSharding(mesh, P(None, None))
        with make_llama_pipeline(ctx, paths, batch=60, seq_len=seq,
                                 sharding=sharding, seed=5) as pipe:
            batch = np.asarray(next(pipe))
        # one full epoch in one batch: same records, different order
        assert not np.array_equal(batch, golden)
        np.testing.assert_array_equal(
            batch[np.lexsort(batch.T[::-1])], golden[np.lexsort(golden.T[::-1])])

    def test_checkpoint_resume_replays_nothing(self, ctx, mesh, token_shards,
                                               tmp_path):
        from strom.pipelines import make_llama_pipeline

        paths, _, seq = token_shards
        sharding = NamedSharding(mesh, P("dp", None))
        f = str(tmp_path / "loader.json")
        with make_llama_pipeline(ctx, paths, batch=8, seq_len=seq,
                                 sharding=sharding, seed=13,
                                 prefetch_depth=3) as pipe:
            for _ in range(3):
                next(pipe)
            pipe.save_state(f)  # prefetcher has run ahead; state must not
            want_next = np.asarray(next(pipe))
        with make_llama_pipeline(ctx, paths, batch=8, seq_len=seq,
                                 sharding=sharding, seed=13,
                                 resume_from=f) as pipe2:
            np.testing.assert_array_equal(np.asarray(next(pipe2)), want_next)

    def test_resume_with_wrong_seed_rejected(self, ctx, mesh, token_shards,
                                             tmp_path):
        from strom.pipelines import make_llama_pipeline

        paths, _, seq = token_shards
        sharding = NamedSharding(mesh, P("dp", None))
        f = str(tmp_path / "loader.json")
        with make_llama_pipeline(ctx, paths, batch=8, seq_len=seq,
                                 sharding=sharding, seed=13) as pipe:
            next(pipe)
            pipe.save_state(f)
        with pytest.raises(ValueError, match="seed 13"):
            make_llama_pipeline(ctx, paths, batch=8, seq_len=seq,
                                sharding=sharding, seed=7, resume_from=f)

    def test_feeds_train_step(self, ctx, mesh, token_shards):
        from strom.models.llama import LlamaConfig
        from strom.parallel.train import (init_train_state, make_optimizer,
                                          make_train_step)
        from strom.pipelines import make_llama_pipeline

        paths, _, seq = token_shards
        tmesh = make_mesh({"dp": 2, "tp": 4}, devices=jax.devices()[:8])
        cfg = LlamaConfig.tiny()
        opt = make_optimizer()
        state = init_train_state(jax.random.PRNGKey(0), cfg, tmesh, opt)
        step = make_train_step(cfg, tmesh, opt)
        with make_llama_pipeline(ctx, paths, batch=8, seq_len=seq,
                                 sharding=NamedSharding(tmesh, P("dp", None))) as pipe:
            for _ in range(2):
                state, metrics = step(state, next(pipe))
        assert np.isfinite(float(metrics["loss"]))
        assert int(state.step) == 2


class TestEpochSync:
    def test_barrier_gates_dispatch(self, monkeypatch):
        """The epoch barrier must fire BEFORE the first next-epoch batch is
        dispatched (the sampler runs ahead of consumption, so a consumer-side
        barrier would let next-epoch I/O start early)."""
        import strom.parallel.multihost as mh
        from strom.pipelines.base import Pipeline

        events = []
        monkeypatch.setattr(mh, "epoch_barrier",
                            lambda name: events.append(("barrier", name)))
        sampler = EpochShuffleSampler(8, 4, seed=0)  # 2 batches/epoch

        def make_batch(idx, serial):
            events.append(("batch", serial))
            return serial

        pipe = Pipeline(sampler, make_batch, depth=1, epoch_sync=True)
        assert [next(pipe) for _ in range(4)] == [0, 1, 2, 3]
        pipe.close()
        # the epoch-1 barrier is appended on the consumer thread before the
        # serial-2 thunk is even submitted to the executor
        bi = events.index(("barrier", "strom-epoch-1"))
        b2 = events.index(("batch", 2))
        assert bi < b2, events


# --------------------------------------------------------- vision pipeline
class TestVisionPipeline:
    @pytest.fixture(scope="class")
    def wds_shards(self, tmp_path_factory):
        import cv2

        from tests.test_formats import make_wds_shard

        rng = np.random.default_rng(21)
        td = tmp_path_factory.mktemp("wds")
        paths = []
        labels = {}
        k = 0
        for s in range(2):
            samples = []
            for i in range(12):
                img = rng.integers(0, 256, (40 + i, 50, 3), dtype=np.uint8)
                ok, buf = cv2.imencode(".jpg", img)
                assert ok
                samples.append((f"s{k:04d}", {"jpg": buf.tobytes(),
                                              "cls": str(k % 10).encode()}))
                labels[f"s{k:04d}"] = k % 10
                k += 1
            p = str(td / f"wds{s}.tar")
            make_wds_shard(p, samples)
            paths.append(p)
        return paths, labels

    def test_batch_shapes_and_labels(self, ctx, mesh, wds_shards):
        from strom.pipelines import make_imagenet_resnet_pipeline

        paths, labels = wds_shards
        sharding = NamedSharding(mesh, P("dp", None, None, None))
        with make_imagenet_resnet_pipeline(
                ctx, paths, batch=8, image_size=32, sharding=sharding,
                shuffle=False, decode_workers=2) as pipe:
            imgs, lbls = next(pipe)
        assert imgs.shape == (8, 32, 32, 3) and imgs.dtype == np.uint8
        assert imgs.sharding == sharding
        assert lbls.shape == (8,)
        np.testing.assert_array_equal(np.asarray(lbls),
                                      [labels[f"s{i:04d}"] for i in range(8)])

    def test_deterministic_augmentation(self, ctx, mesh, wds_shards):
        from strom.pipelines import make_vit_wds_pipeline

        paths, _ = wds_shards
        sharding = NamedSharding(mesh, P("dp", None, None, None))
        outs = []
        for _ in range(2):
            with make_vit_wds_pipeline(ctx, paths, batch=8, image_size=32,
                                       sharding=sharding, seed=3,
                                       decode_workers=2) as pipe:
                outs.append(np.asarray(next(pipe)[0]))
        np.testing.assert_array_equal(outs[0], outs[1])

    def test_rejects_inner_dim_sharding(self, ctx, mesh, wds_shards):
        """VERDICT.md weak #4: splitting H/W/C must fail fast at construction
        with a message naming the constraint, not opaquely inside
        make_array_from_single_device_arrays."""
        from strom.parallel.mesh import make_mesh
        from strom.pipelines import make_imagenet_resnet_pipeline

        paths, _ = wds_shards
        m2 = make_mesh({"dp": 4, "mp": 2}, devices=jax.devices()[:8])
        for bad in (P("dp", None, "mp", None), P("dp", "mp"),
                    P(None, None, None, "mp")):
            with pytest.raises(ValueError, match="batch-dim"):
                make_imagenet_resnet_pipeline(
                    ctx, paths, batch=8, image_size=32,
                    sharding=NamedSharding(m2, bad), decode_workers=2)

    def test_local_batch_rows_matches_indices_map(self, mesh):
        """Property: for every legal batch-only 4-D sharding, the row ranges
        the loader decodes equal what addressable_devices_indices_map says
        each device owns of the REAL global shape."""
        from strom.parallel.mesh import make_mesh
        from strom.pipelines.vision import (_local_batch_rows,
                                            _validate_batch_only)

        m2 = make_mesh({"dp": 4, "mp": 2}, devices=jax.devices()[:8])
        cases = [
            (mesh, P("dp", None, None, None), 16),
            (mesh, P("dp",), 8),                 # short spec, trailing None
            (mesh, P(None, None, None, None), 4),  # fully replicated
            (m2, P("dp", None, None, None), 8),  # mp axis replicates rows
            (m2, P(("dp", "mp"), None, None, None), 16),  # product sharding
        ]
        for m, spec, batch in cases:
            sharding = NamedSharding(m, spec)
            _validate_batch_only(sharding)
            got = _local_batch_rows(sharding, batch)
            shape = (batch, 32, 32, 3)
            expect = sharding.addressable_devices_indices_map(shape)
            assert set(got) == set(expect)
            for device, index in expect.items():
                sl = index[0] if index else slice(None)
                lo, hi, _ = sl.indices(batch)
                assert got[device] == (lo, hi), (spec, batch, device)

    def test_feeds_resnet_step(self, ctx, mesh, wds_shards):
        from strom.models.resnet import ResNetConfig, init_params, loss_fn
        from strom.pipelines import make_imagenet_resnet_pipeline

        paths, _ = wds_shards
        cfg = ResNetConfig.tiny()
        params, state = init_params(jax.random.PRNGKey(0), cfg)
        sharding = NamedSharding(mesh, P("dp", None, None, None))
        with make_imagenet_resnet_pipeline(
                ctx, paths, batch=8, image_size=32, sharding=sharding,
                decode_workers=2) as pipe:
            imgs, lbls = next(pipe)
            from strom.models.resnet import normalize_images

            loss, _ = jax.jit(loss_fn, static_argnames="cfg")(
                params, state, normalize_images(imgs), lbls, cfg)
        assert np.isfinite(float(loss))


# ----------------------------------------------------------- parquet scan
class TestParquetScan:
    @pytest.fixture(scope="class")
    def pq_shards(self, tmp_path_factory):
        import pyarrow as pa
        import pyarrow.parquet as pq

        rng = np.random.default_rng(31)
        td = tmp_path_factory.mktemp("pq")
        paths, frames = [], []
        for s in range(3):
            n = 4000
            vals = rng.normal(size=n)
            ids = np.arange(n, dtype=np.int64)
            table = pa.table({"id": pa.array(ids), "value": pa.array(vals)})
            p = str(td / f"part{s}.parquet")
            pq.write_table(table, p, row_group_size=1000)
            paths.append(p)
            frames.append(vals)
        return paths, np.concatenate(frames)

    def test_count_where_matches_numpy(self, ctx, pq_shards):
        from strom.pipelines import parquet_count_where

        paths, vals = pq_shards
        got = parquet_count_where(ctx, paths, "value", lambda v: v > 0.5)
        assert got == int((vals > 0.5).sum())

    @pytest.mark.parametrize("unit_batch", [2, 5, 100])
    def test_unit_batch_identical_results(self, ctx, pq_shards, unit_batch):
        """Batching row groups per dispatch must not change any aggregate
        (scan map_fns are row-decomposable); 100 > total units exercises the
        everything-in-one-dispatch edge."""
        from strom.pipelines import parquet_count_where

        paths, vals = pq_shards
        got = parquet_count_where(ctx, paths, "value", lambda v: v > 0.5,
                                  unit_batch=unit_batch)
        assert got == int((vals > 0.5).sum())

    def test_unit_batch_rejects_nonpositive(self, ctx, pq_shards):
        from strom.pipelines import parquet_count_where

        paths, _ = pq_shards
        with pytest.raises(ValueError, match="unit_batch"):
            parquet_count_where(ctx, paths, "value", lambda v: v > 0,
                                unit_batch=0)

    def test_zero_units_contributes_zero(self, ctx, pq_shards):
        """A process with no assigned units must produce a zero aggregate of
        the right structure, not raise (multi-host allgather safety)."""
        import jax.numpy as jnp

        from strom.pipelines import parquet_scan_aggregate

        paths, _ = pq_shards  # 3 shards × 4 row groups = 12 units

        def map_fn(cols):
            v = cols["value"]
            return {"sum": jnp.sum(v), "n": jnp.asarray(v.shape[0], jnp.int32)}

        # process 12 of 13: local_units = units[12::13] = []
        out = parquet_scan_aggregate(ctx, paths, ["value"], map_fn,
                                     process_index=12, process_count=13)
        assert out["sum"] == 0.0 and out["n"] == 0

    def test_round_robin_partition_sums_to_whole(self, ctx, pq_shards):
        """Simulated 3-process scan: per-process partials sum to the global."""
        import jax.numpy as jnp

        from strom.pipelines import parquet_scan_aggregate

        paths, vals = pq_shards
        parts = [parquet_scan_aggregate(
                     ctx, paths, ["value"],
                     lambda cols: jnp.sum(cols["value"]),
                     process_index=i, process_count=3) for i in range(3)]
        np.testing.assert_allclose(sum(parts), vals.sum(), rtol=1e-6)

    def test_aggregate_sum_matches(self, ctx, pq_shards):
        import jax.numpy as jnp

        from strom.pipelines import parquet_scan_aggregate

        paths, vals = pq_shards

        def map_fn_sum(cols):
            v = cols["value"]
            return {"sum": jnp.sum(v), "n": jnp.asarray(v.shape[0], jnp.int32)}

        out = parquet_scan_aggregate(ctx, paths, ["value"], map_fn_sum)
        assert out["n"] == len(vals)
        np.testing.assert_allclose(out["sum"], vals.sum(), rtol=1e-6)

    def test_wide_projection_scan(self, ctx, tmp_path):
        """Multi-column (wide) projection — the PG-Strom feature-vector
        shape the bench's WIDE arm uses: every selected column's chunks are
        engine-read and consumed by the aggregate, per-column sums exact."""
        pa = pytest.importorskip("pyarrow")
        import jax.numpy as jnp
        import pyarrow.parquet as pq

        from strom.pipelines import parquet_scan_aggregate

        rng = np.random.default_rng(23)
        cols = {f"f{i}": rng.standard_normal(4_000) for i in range(4)}
        path = str(tmp_path / "wide.parquet")
        pq.write_table(pa.table(cols), path, row_group_size=1_000)
        names = list(cols)

        def map_fn(d):
            return {c: jnp.sum(d[c]) for c in names}

        out = parquet_scan_aggregate(ctx, [path], names, map_fn,
                                     unit_batch=2)
        for c in names:
            # jax sums in float32 (x64 off); a 4k-element sum that cancels
            # toward zero needs an absolute floor alongside rtol
            np.testing.assert_allclose(out[c], cols[c].sum(),
                                       rtol=1e-4, atol=1e-3)

    def test_plain_encoded_scan_rides_direct_decoder(self, ctx, tmp_path):
        """Uncompressed PLAIN fixture (the bench's I/O-bound arm,
        VERDICT.md r4 next #1): the scan result is exact AND every selected
        byte went through the direct frombuffer decoder, none through
        pyarrow (the parquet_plain_bytes / parquet_decode_bytes counters
        prove which path ran)."""
        pa = pytest.importorskip("pyarrow")
        import pyarrow.parquet as pq

        from strom.pipelines import parquet_count_where
        from strom.utils.stats import global_stats

        rng = np.random.default_rng(29)
        vals = rng.standard_normal(12_000).astype(np.float32)
        path = str(tmp_path / "plain.parquet")
        pq.write_table(pa.table({"value": vals}), path,
                       row_group_size=3_000, compression="NONE",
                       use_dictionary=False)
        snap0 = global_stats.snapshot()
        got = parquet_count_where(ctx, [path], "value", lambda v: v > 0,
                                  unit_batch=2)
        snap1 = global_stats.snapshot()
        assert got == int((vals > 0).sum())
        # counter records chunk bytes: the values plus their page headers
        plain = snap1.get("parquet_plain_bytes", 0) \
            - snap0.get("parquet_plain_bytes", 0)
        assert vals.nbytes <= plain < vals.nbytes + 4096
        assert snap1.get("parquet_decode_bytes", 0) \
            == snap0.get("parquet_decode_bytes", 0)

    def test_decode_path_counters_in_prometheus(self, ctx, tmp_path):
        """The decode-path counters are observability surface (≙ the
        reference's /proc counters): after a scan they must appear in the
        Prometheus exposition, not only in the bench JSON."""
        pa = pytest.importorskip("pyarrow")
        import pyarrow.parquet as pq

        from strom.pipelines import parquet_count_where
        from strom.utils.stats import global_stats

        vals = np.random.default_rng(7).standard_normal(8_000) \
            .astype(np.float32)
        path = str(tmp_path / "prom.parquet")
        pq.write_table(pa.table({"value": vals}), path,
                       compression="NONE", use_dictionary=False)
        before = global_stats.snapshot().get("parquet_plain_bytes", 0)
        parquet_count_where(ctx, [path], "value", lambda v: v > 0)
        after = global_stats.snapshot().get("parquet_plain_bytes", 0)
        # THIS scan advanced the counter (key presence alone would pass
        # vacuously: global_stats is process-global and earlier tests have
        # already created the key)
        assert after > before
        assert f"strom_parquet_plain_bytes {after}" in \
            global_stats.prometheus()


class TestLlamaStriped:
    def test_striped_token_shards_golden(self, ctx, tmp_path):
        """Packed-token shards on a RAID0 striped set via path alias:
        sequential batches equal the logical token stream."""
        import os

        from jax.sharding import Mesh

        from strom.engine.raid0 import stripe_file
        from strom.pipelines import make_llama_pipeline

        seq, batch = 31, 8
        tokens = np.arange(8 * batch * (seq + 1), dtype=np.int32)
        plain = tmp_path / "tok.bin"
        tokens.tofile(plain)
        members = [str(tmp_path / f"tm{i}.bin") for i in range(4)]
        stripe_file(str(plain), members, 512)  # 8 chunks -> 2 per member
        virt = str(tmp_path / "tok_striped.bin")
        ctx.register_striped(virt, members, 512,
                             size=os.path.getsize(plain))

        mesh = Mesh(np.array(jax.devices()).reshape(8), ("dp",))
        sharding = NamedSharding(mesh, P("dp", None))
        with make_llama_pipeline(ctx, [virt], batch=batch, seq_len=seq,
                                 sharding=sharding, shuffle=False) as pipe:
            got = np.concatenate([np.asarray(next(pipe)).ravel()
                                  for _ in range(4)])
        np.testing.assert_array_equal(got, tokens[:got.size])


class TestPredecodedPipeline:
    @pytest.fixture(scope="class")
    def pdec_shard(self, tmp_path_factory, ctx):
        """A WDS tar decoded once into a packed uint8 shard."""
        import cv2

        from strom.formats.predecoded import predecode_wds
        from tests.test_formats import make_wds_shard

        rng = np.random.default_rng(31)
        td = tmp_path_factory.mktemp("pdec")
        samples = []
        for i in range(20):
            img = rng.integers(0, 256, (48, 64, 3), dtype=np.uint8)
            ok, buf = cv2.imencode(".jpg", img)
            assert ok
            samples.append((f"s{i:04d}", {"jpg": buf.tobytes(),
                                          "cls": str(i % 7).encode()}))
        tar = str(td / "src.tar")
        make_wds_shard(tar, samples)
        out = predecode_wds(ctx, [tar], str(td / "imgs.pdec"), image_size=32,
                            decode_workers=2)
        return out

    def test_format_roundtrip(self, ctx, pdec_shard):
        """Records are image_size^2*3 bytes, labels ride the sidecar, and
        the extents gather returns exactly the packed record bytes."""
        from strom.formats.predecoded import PredecodedShardSet

        ss = PredecodedShardSet((pdec_shard,), 32)
        assert ss.num_records == 20
        assert ss.record_bytes == 32 * 32 * 3
        np.testing.assert_array_equal(ss.labels(range(20)),
                                      [i % 7 for i in range(20)])
        raw = np.fromfile(pdec_shard, dtype=np.uint8)
        got = np.asarray(memoryview(ctx.pread(ss.extents([3, 4, 11]))))
        rb = ss.record_bytes
        np.testing.assert_array_equal(
            got, np.concatenate([raw[3 * rb: 5 * rb], raw[11 * rb: 12 * rb]]))

    def test_wrong_image_size_rejected(self, pdec_shard):
        from strom.formats.predecoded import PredecodedShardSet

        with pytest.raises(ValueError, match="image_size"):
            PredecodedShardSet((pdec_shard,), 64)

    def test_pipeline_batches_and_determinism(self, ctx, mesh, pdec_shard):
        """Decode-free loader delivers [B,S,S,3] uint8 sharded batches whose
        bytes equal the packed records, labels aligned, deterministic in
        seed."""
        from strom.pipelines import make_predecoded_vision_pipeline

        sharding = NamedSharding(mesh, P("dp", None, None, None))
        raw = np.fromfile(pdec_shard, dtype=np.uint8).reshape(20, 32, 32, 3)
        with make_predecoded_vision_pipeline(
                ctx, [pdec_shard], batch=8, image_size=32, sharding=sharding,
                shuffle=False) as pipe:
            imgs, lbls = next(pipe)
        assert imgs.shape == (8, 32, 32, 3) and imgs.dtype == np.uint8
        assert imgs.sharding == sharding
        np.testing.assert_array_equal(np.asarray(imgs), raw[:8])
        np.testing.assert_array_equal(np.asarray(lbls),
                                      [i % 7 for i in range(8)])
        # shuffled: two pipelines with the same seed agree
        outs = []
        for _ in range(2):
            with make_predecoded_vision_pipeline(
                    ctx, [pdec_shard], batch=8, image_size=32,
                    sharding=sharding, seed=5) as pipe:
                outs.append(np.asarray(next(pipe)[0]))
        np.testing.assert_array_equal(outs[0], outs[1])

    def test_stale_labels_sidecar_rejected(self, ctx, pdec_shard, tmp_path):
        """A predecode interrupted between the records rename and the
        sidecar renames leaves new records with OLD sidecars (sidecars land
        strictly after records — ADVICE.md r3 #1): the loader must refuse
        the count mismatch, not silently mislabel every sample."""
        import shutil

        from strom.formats.predecoded import (LABELS_SUFFIX, META_SUFFIX,
                                              PredecodedShardSet)

        clone = str(tmp_path / "stale.pdec")
        shutil.copyfile(pdec_shard, clone)
        shutil.copyfile(pdec_shard + META_SUFFIX, clone + META_SUFFIX)
        np.save(clone + LABELS_SUFFIX + ".tmp.npy",
                np.zeros(7, np.int32))  # wrong count = stale generation
        os.replace(clone + LABELS_SUFFIX + ".tmp.npy", clone + LABELS_SUFFIX)
        with pytest.raises(ValueError, match="stale"):
            PredecodedShardSet((clone,), 32)

    def test_predecode_leaves_no_tmp_files(self, pdec_shard):
        """The atomic-staging protocol cleans up: no .tmp leftovers beside
        the shard after a successful predecode."""
        d = os.path.dirname(pdec_shard)
        leftovers = [f for f in os.listdir(d) if ".tmp" in f]
        assert leftovers == []

    def test_rejects_inner_dim_sharding(self, ctx, pdec_shard):
        from strom.parallel.mesh import make_mesh
        from strom.pipelines import make_predecoded_vision_pipeline
        import jax

        mesh2 = make_mesh({"dp": 2, "mp": 2}, devices=jax.devices()[:4])
        bad = NamedSharding(mesh2, P("dp", "mp", None, None))
        with pytest.raises(ValueError, match="batch-dim"):
            make_predecoded_vision_pipeline(ctx, [pdec_shard], batch=8,
                                            image_size=32, sharding=bad)

    def test_checkpoint_resume_replays_nothing(self, ctx, mesh, pdec_shard):
        """Mid-epoch resume of the decode-free loader: batches after the
        cursor match an uninterrupted run exactly (images AND labels)."""
        from strom.pipelines import make_predecoded_vision_pipeline

        sharding = NamedSharding(mesh, P("dp", None, None, None))

        def make(resume=None):
            return make_predecoded_vision_pipeline(
                ctx, [pdec_shard], batch=8, image_size=32, sharding=sharding,
                seed=9, resume_from=resume)

        with make() as pipe:
            golden = [next(pipe) for _ in range(4)]
            golden = [(np.asarray(i), np.asarray(l)) for i, l in golden]
        with make() as pipe:
            next(pipe)
            next(pipe)
            state = pipe.state()
            resumed = make(resume=state)
        with resumed as pipe:
            for want_i, want_l in golden[2:]:
                got_i, got_l = next(pipe)
                np.testing.assert_array_equal(np.asarray(got_i), want_i)
                np.testing.assert_array_equal(np.asarray(got_l), want_l)


class TestScanReduction:
    def test_reduce_modes_agree(self, ctx, tmp_path):
        """Both reductions — the XLA-collective scan-mesh sum and the
        allgather fallback — give the same count on the 8-device CPU mesh
        (single process: the collective runs as a local-mesh reduction)."""
        pa = pytest.importorskip("pyarrow")
        import pyarrow.parquet as pq

        from strom.pipelines import parquet_count_where

        rng = np.random.default_rng(77)
        vals = rng.standard_normal(6000)
        p = str(tmp_path / "r.parquet")
        pq.write_table(pa.table({"value": pa.array(vals)}), p,
                       row_group_size=1000)
        truth = int((vals > 0).sum())
        for reduce in ("collective", "allgather"):
            got = parquet_count_where(ctx, [p], "value", lambda v: v > 0,
                                      reduce=reduce)
            assert got == truth, reduce

    def test_reduce_mode_validated(self, ctx, tmp_path):
        pa = pytest.importorskip("pyarrow")
        import pyarrow.parquet as pq

        from strom.pipelines import parquet_count_where

        p = str(tmp_path / "v.parquet")
        pq.write_table(pa.table({"value": pa.array(np.ones(10))}), p)
        with pytest.raises(ValueError, match="reduce"):
            parquet_count_where(ctx, [p], "value", lambda v: v > 0,
                                reduce="psum")


class TestPredecodedStriped:
    def test_striped_predecoded_pipeline(self, ctx, mesh, tmp_path, rng):
        """The decode-once shard striped RAID0-style and read through a path
        alias: batches byte-equal the plain shard's records, labels via the
        alias-named sidecar (config #3's decode-free arm)."""
        import cv2

        from strom.formats.predecoded import (predecode_wds,
                                              stage_striped_predecoded)
        from strom.pipelines import make_predecoded_vision_pipeline
        from tests.test_formats import make_wds_shard

        samples = []
        for i in range(16):
            img = rng.integers(0, 256, (40, 40, 3), dtype=np.uint8)
            ok, buf = cv2.imencode(".jpg", img)
            assert ok
            samples.append((f"s{i:04d}", {"jpg": buf.tobytes(),
                                          "cls": str(i % 5).encode()}))
        tar = str(tmp_path / "src.tar")
        make_wds_shard(tar, samples)
        pdec = predecode_wds(ctx, [tar], str(tmp_path / "imgs.pdec"),
                             image_size=32, decode_workers=2)
        members = [str(tmp_path / f"pm{i}.bin") for i in range(2)]
        virt = stage_striped_predecoded(ctx, pdec, members, 64 * 1024)

        raw = np.fromfile(pdec, dtype=np.uint8).reshape(16, 32, 32, 3)
        sharding = NamedSharding(mesh, P("dp", None, None, None))
        with make_predecoded_vision_pipeline(
                ctx, [virt], batch=8, image_size=32, sharding=sharding,
                shuffle=False) as pipe:
            imgs, lbls = next(pipe)
        np.testing.assert_array_equal(np.asarray(imgs), raw[:8])
        np.testing.assert_array_equal(np.asarray(lbls),
                                      [i % 5 for i in range(8)])
