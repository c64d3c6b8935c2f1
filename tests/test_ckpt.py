"""Engine-native checkpointing (ISSUE 13 tentpole): save/restore round-trip
bit-exactness through the write path, crash-safe commit semantics, CRC
corruption detection, and the pickle baseline's own round-trip."""

import json
import os

import numpy as np
import pytest

from strom.config import StromConfig
from strom.delivery.core import StromContext
from strom.ckpt import (CkptCorruptError, CkptError, load_pickle,
                        restore_checkpoint, save_checkpoint, save_pickle)

jax = pytest.importorskip("jax")
jnp = jax.numpy


@pytest.fixture()
def ctx():
    c = StromContext(StromConfig(engine="python", queue_depth=8,
                                 num_buffers=16,
                                 slab_pool_bytes=64 * 1024 * 1024))
    yield c
    c.close()


def _state():
    return {
        "params": {"w": jnp.arange(1 << 16, dtype=jnp.float32)
                   .reshape(256, 256),
                   "b": jnp.ones((512,), dtype=jnp.bfloat16)},
        "opt": [jnp.full((123, 7), 3.5, dtype=jnp.float32),
                np.arange(11, dtype=np.int64)],
        "empty": np.zeros((0, 4), dtype=np.float32),
        "step": 42,
    }


def _assert_tree_equal(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestRoundTrip:
    @pytest.mark.parametrize("verify", [False, True])
    def test_bit_exact(self, ctx, tmp_path, verify):
        """Save via engine writes, restore via memcpy_ssd2tpu (verify=False)
        / host CRC-checked read (verify=True): bit-exact, dtypes (bfloat16
        included) and python-scalar leaves preserved."""
        state = _state()
        d = str(tmp_path / "ckpt")
        m = save_checkpoint(ctx, d, state)
        assert m["payload_bytes"] > 0
        back = restore_checkpoint(ctx, d, state, verify=verify)
        _assert_tree_equal(state, back)
        assert back["step"] == 42 and isinstance(back["step"], int)

    def test_resave_replaces_atomically(self, ctx, tmp_path):
        """A second save to the same directory replaces the checkpoint (new
        inode): restore sees the NEW state — no stale fd, no stale cache."""
        state = _state()
        d = str(tmp_path / "ckpt")
        save_checkpoint(ctx, d, state)
        state2 = dict(state)
        state2["step"] = 43
        state2["params"] = {"w": state["params"]["w"] + 1,
                            "b": state["params"]["b"]}
        save_checkpoint(ctx, d, state2)
        back = restore_checkpoint(ctx, d, state2)
        _assert_tree_equal(state2, back)
        assert back["step"] == 43

    def test_leaf_spans_are_aligned(self, ctx, tmp_path):
        d = str(tmp_path / "ckpt")
        m = save_checkpoint(ctx, d, _state())
        for leaf in m["leaves"]:
            assert leaf["offset"] % 4096 == 0


class TestFailureModes:
    def test_corrupt_data_detected(self, ctx, tmp_path):
        state = _state()
        d = str(tmp_path / "ckpt")
        m = save_checkpoint(ctx, d, state)
        # flip a byte INSIDE a real leaf span (the inter-span alignment
        # padding is uncovered by design — nothing reads it)
        leaf = next(lf for lf in m["leaves"] if lf["nbytes"] > 16)
        data = os.path.join(d, "data.bin")
        with open(data, "r+b") as f:
            f.seek(leaf["offset"] + 10)
            b0 = f.read(1)
            f.seek(leaf["offset"] + 10)
            f.write(bytes([b0[0] ^ 0x01]))
        ctx.invalidate_file(data)
        with pytest.raises(CkptCorruptError):
            restore_checkpoint(ctx, d, state, verify=True)

    def test_template_shape_mismatch(self, ctx, tmp_path):
        state = _state()
        d = str(tmp_path / "ckpt")
        save_checkpoint(ctx, d, state)
        bad = dict(state)
        bad["opt"] = [jnp.zeros((5, 5), dtype=jnp.float32),
                      state["opt"][1]]
        with pytest.raises(CkptError):
            restore_checkpoint(ctx, d, bad)

    def test_not_a_checkpoint(self, ctx, tmp_path):
        d = tmp_path / "nope"
        d.mkdir()
        with pytest.raises(CkptError):
            restore_checkpoint(ctx, str(d), _state())

    def test_failed_save_leaves_old_checkpoint_intact(self, ctx, tmp_path,
                                                      monkeypatch):
        """A save that dies mid-write (writer failure) cleans its tmp dir
        and leaves the previous committed checkpoint restorable — the
        tmp+rename crash-safety contract."""
        state = _state()
        d = str(tmp_path / "ckpt")
        save_checkpoint(ctx, d, state)

        real = ctx.write_chunks

        def dying(chunks, src, **kw):
            raise OSError("injected writer death")

        monkeypatch.setattr(ctx, "write_chunks", dying)
        with pytest.raises(Exception):
            save_checkpoint(ctx, d, dict(state, step=99))
        monkeypatch.setattr(ctx, "write_chunks", real)
        # tmp orphan cleaned; the OLD checkpoint restores bit-exact
        assert not any(n.startswith("ckpt.tmp")
                       for n in os.listdir(str(tmp_path)))
        back = restore_checkpoint(ctx, d, state, verify=True)
        _assert_tree_equal(state, back)
        assert back["step"] == 42

    def test_manifest_crcs_are_real(self, ctx, tmp_path):
        """The manifest CRCs (computed during staging, ISSUE 13) match an
        independent recomputation from the bytes on disk."""
        import zlib

        state = _state()
        d = str(tmp_path / "ckpt")
        m = save_checkpoint(ctx, d, state)
        with open(os.path.join(d, "manifest.json")) as f:
            assert json.load(f) == m
        with open(os.path.join(d, "data.bin"), "rb") as f:
            blob = f.read()
        for leaf in m["leaves"]:
            got = zlib.crc32(
                blob[leaf["offset"]: leaf["offset"] + leaf["nbytes"]]) \
                & 0xFFFFFFFF
            assert got == leaf["crc32"], leaf


class TestPickleBaseline:
    def test_pickle_roundtrip(self, tmp_path):
        state = _state()
        p = str(tmp_path / "s.pkl")
        n = save_pickle(p, state)
        assert n == os.path.getsize(p) > 0
        _assert_tree_equal(state, load_pickle(p))


def test_ckpt_fields_single_sourced():
    """CKPT_FIELDS names the write-path columns of the checked-in records
    without duplicates (the lint_stats_names *_FIELDS scan rides the
    literal)."""
    from strom.ckpt.checkpoint import CKPT_FIELDS

    assert "ckpt_save_mb_per_s" in CKPT_FIELDS
    assert "ckpt_roundtrip_ok" in CKPT_FIELDS
    assert len(set(CKPT_FIELDS)) == len(CKPT_FIELDS)
