"""The DeepSeek-V2-Lite cell on the CPU at its CPU sizes, judged by the
harness's own comparison: a sound run is correct, a changed token, the
float8 control and half the batch are not; and the counts its per-layer
metrics divide by, against hand counts."""

import numpy as np
import pytest

import jax

from benchmark.harness import core
from benchmark.tests import calibrate_dsv2lite, tiny

CELL = "dsv2lite_tokens_s4096_b8"
MOD = core.load_module(core.resolve(CELL).module_path)
SEED = 2**31 + 13


def test_sound_run_is_correct_and_a_changed_token_is_not(tmp_path,
                                                         monkeypatch):
    line, run = tiny.run(CELL, str(tmp_path), seed=SEED, seconds=0.5)
    assert line["correct"], line["checks"]
    assert run.layer["flops_per_sample"] > 0

    import strom.pipelines

    real = strom.pipelines.make_llama_pipeline

    class Wrapped:
        """The pipeline with one token of every batch changed."""

        def __init__(self, pipe):
            self.pipe = pipe

        def __next__(self):
            return next(self.pipe).at[0, 5].add(1)

        def close(self):
            self.pipe.close()

    monkeypatch.setattr(strom.pipelines, "make_llama_pipeline",
                        lambda *a, **k: Wrapped(real(*a, **k)))
    line, _ = tiny.run(CELL, str(tmp_path), seed=SEED, seconds=0.5)
    assert not line["correct"]
    assert line["checks"]["batch_values_off"]["value"] > 0


def test_control_and_half_batch_fail(tmp_path):
    rows = calibrate_dsv2lite.readings(
        tiny.parts(CELL), [SEED], jax.devices()[:1], str(tmp_path),
        arms=("control", "half_batch"), seconds=0.3)
    by = {r["arm"]: r for r in rows}
    assert not by["control"]["correct"], by["control"]
    assert by["control"]["checks"]["batch_values_off"]["value"] == 0
    assert not by["half_batch"]["correct"], by["half_batch"]


def test_flash_counts_by_hand():
    # batch 1, seq 256 in blocks of 128, one head, Dqk 3, Dv 2: blocks
    # (0,0), (1,0), (1,1) computed, (0,1) skipped
    per = MOD.flash_counts(1, 256, 1, 3, 2, blk=128)
    pairs = 3 * 128 * 128
    assert per["flash_fwd"] == (pairs * (2 * 3 + 2 * 2),
                                256 * (2 * 3 * 2 + 2 * 2 * 2 + 4))
    assert per["flash_bwd_dkv"] == (pairs * 2 * (2 * 3 + 2 * 2),
                                    256 * (2 * 2 * 2 + 3 * 2))
    assert per["flash_bwd_dq"] == (pairs * 2 * (2 * 3 + 2), 256 * 3 * 2)


@pytest.mark.parametrize("seq,blk", [(256, 128), (512, 128), (128, 128)])
def test_flash_counts_never_exceed_the_blocks_the_kernels_run(seq, blk):
    """The kernels' own skip rule (``jk·blk ≤ iq·blk + blk − 1``), walked
    block by block: the count is exactly the blocks run, and below the
    dense product of every block."""
    n = seq // blk
    run = sum(1 for iq in range(n) for jk in range(n)
              if jk * blk <= iq * blk + blk - 1)
    per = MOD.flash_counts(2, seq, 3, 192, 128, blk=blk)
    assert per["flash_fwd"][0] == 2 * 3 * run * blk * blk * 2 * (192 + 128)
    dense = 2 * 3 * seq * seq * 2 * (192 + 128)
    assert per["flash_fwd"][0] <= dense


def test_expert_counts_by_hand():
    c = MOD.model_tuple(dict(d_model=4, expert_ff=3, n_moe_layers=2,
                             n_held=5))
    flops, nbytes = MOD.expert_counts(pairs=7, steps=2, c=c)
    assert flops == 7 * 3 * 2 * 4 * 3 * 3
    assert nbytes == 2 * 2 * 3 * 5 * 4 * 3 * 2 * 3 + 7 * 4 * 2 * 4


def test_roofline_share_is_bounded_by_the_time_it_divides():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert MOD.roofline(500, 20, 5.0, peaks) == pytest.approx(100.0)
    assert MOD.roofline(500, 80, 8.0, peaks) == pytest.approx(100.0)
    assert MOD.roofline(500, 20, 10.0, peaks) == pytest.approx(50.0)
    assert MOD.roofline(500, 20, 0.0, peaks) is None


def test_train_flops_per_sample_at_published_widths():
    parts = core.resolve(CELL)
    c = MOD.model_tuple(MOD.model_fields(parts.config, cpu=False))
    per_token = MOD.forward_flops_per_token(c, 4096)
    # 162 (layer 0) + 4 × 75.4 (expert layers) + 5 × 21 (attention) + 52.4
    # (head) MFLOP
    assert per_token == pytest.approx(620.8e6, rel=2e-3)
    assert MOD.train_flops_per_sample(c, 4096) == 3 * per_token * 4096


def test_classify_and_scope_map():
    hlo = ('  %jvp_flash_fwd_.1 = (bf16[8]) custom-call(%a), '
           'metadata={op_name="jit(step)/jvp(flash_fwd)/pallas_call"}\n'
           '  ROOT %fusion.3 = bf16[4] fusion(%b), metadata={op_name='
           '"jit(step)/while/body/checkpoint/moe.experts/gather" '
           'stack_frame_id=3}\n')
    scopes = MOD.scope_map(hlo)
    assert MOD.classify(scopes["jvp_flash_fwd_.1"]) == "flash_fwd"
    assert MOD.classify(scopes["fusion.3"]) == "moe.experts"
    assert MOD.classify("jit(step)/transpose(jvp(flash_bwd_dkv))") \
        == "flash_bwd_dkv"
    assert MOD.classify("jit(step)/mla/dot_general") is None
    # the chip's grouped products keep their own op_name, not the scope's
    assert MOD.classify("ragged-dot-none") == "moe.experts"
    assert MOD.classify("ragged-dot-metadata") == "moe.experts"


def test_corpus_is_zipf_over_the_held_slice(tmp_path):
    from benchmark.harness import generate

    p = {"records": 64, "record_tokens": 129, "shards": 2, "vocab": 256,
         "zipf_s": 1.0, "data_seed": 3}
    m, made = generate.ensure(str(tmp_path), "tokens", p)
    assert made and len(m["paths"]) == 2
    ids = np.concatenate([np.fromfile(x, np.int32) for x in m["paths"]])
    assert ids.size == 64 * 129 and 0 <= ids.min() and ids.max() < 256
    counts = np.bincount(ids, minlength=256)
    assert counts[0] > counts[1] > counts[3] > counts[15]   # rank order
    again, made = generate.ensure(str(tmp_path), "tokens", p)
    assert not made and again["paths"] == m["paths"]
