"""Pallas flash-attention kernel vs the dense oracle (interpret mode on the
CPU backend — same kernel code the TPU compiles)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from strom.ops.flash_attention import (_dense_ref, _first_live, _kv_block,
                                       _last_live, _q_block, flash_attention,
                                       flash_plan)


def _qkv(rng, B, S, H, KV, Dh, dtype=jnp.float32):
    q = jnp.array(rng.normal(size=(B, S, H, Dh)), dtype)
    k = jnp.array(rng.normal(size=(B, S, KV, Dh)), dtype)
    v = jnp.array(rng.normal(size=(B, S, KV, Dh)), dtype)
    return q, k, v


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("B,S,H,KV,Dh", [(2, 256, 4, 2, 128),
                                             (1, 256, 4, 4, 128)])
    def test_matches_dense(self, causal, B, S, H, KV, Dh):
        q, k, v = _qkv(np.random.default_rng(0), B, S, H, KV, Dh)
        out = flash_attention(q, k, v, causal, 128, 128)  # a 2x2 grid at 256
        ref = _dense_ref(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_matches_model_attention(self):
        """Same semantics as the dense op the model uses."""
        from strom.models.llama import attention

        q, k, v = _qkv(np.random.default_rng(1), 1, 128, 4, 2, 128)
        out = flash_attention(q, k, v, True, 64, 64)
        ref = attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_blocked_vs_single_block(self):
        q, k, v = _qkv(np.random.default_rng(2), 1, 256, 2, 2, 128)
        a = flash_attention(q, k, v, True, 64, 128)
        b = flash_attention(q, k, v, True, 256, 256)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)

    def test_grad_matches_dense(self):
        q, k, v = _qkv(np.random.default_rng(3), 1, 128, 2, 2, 128)

        g1 = jax.grad(lambda q_: jnp.sum(flash_attention(q_, k, v) ** 2))(q)
        g2 = jax.grad(lambda q_: jnp.sum(_dense_ref(q_, k, v, True) ** 2))(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("B,S,H,KV,Dh", [(2, 256, 4, 2, 128),   # GQA, 2x2 blocks
                                             (1, 128, 2, 2, 128)])  # MHA, 1 block
    def test_all_grads_match_dense(self, causal, B, S, H, KV, Dh):
        """The blockwise FA2 backward (dq AND dk AND dv kernels) against the
        dense oracle — the round-1 backward was a dense recompute, so this is
        the test that pins the new kernels down."""
        q, k, v = _qkv(np.random.default_rng(6), B, S, H, KV, Dh)

        def f_flash(q_, k_, v_):
            return jnp.sum(flash_attention(q_, k_, v_, causal, 128, 128) ** 2)

        def f_dense(q_, k_, v_):
            return jnp.sum(_dense_ref(q_, k_, v_, causal) ** 2)

        gq1, gk1, gv1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        gq2, gk2, gv2 = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in ((gq1, gq2, "dq"), (gk1, gk2, "dk"), (gv1, gv2, "dv")):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4, err_msg=name)

    @pytest.mark.slow  # ~4s grid sweep: gradient parity vs dense is
    # covered (fast) above; tier-1 runtime headroom (ISSUE 5 satellite)
    def test_blocked_grads_vs_single_block(self):
        """Block-boundary accumulation in the backward: 64/128 blocking must
        reproduce the single-block result exactly (same math, different grid)."""
        q, k, v = _qkv(np.random.default_rng(7), 1, 256, 2, 2, 128)

        def loss(blocks):
            bq, bk = blocks
            return lambda q_, k_, v_: jnp.sum(
                flash_attention(q_, k_, v_, True, bq, bk) ** 2)

        g1 = jax.grad(loss((64, 128)), argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss((256, 256)), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)

    def test_grad_through_llama_loss(self):
        """End-to-end: next_token_loss gradient with the flash attn_fn is
        finite and close to the dense-path gradient."""
        from strom.models.llama import LlamaConfig, init_params, next_token_loss
        from strom.ops.flash_attention import make_flash_attention

        cfg = LlamaConfig(vocab=256, d_model=256, n_layers=2, n_heads=2,
                          n_kv_heads=2, d_ff=512, rope_theta=10_000.0)
        params = init_params(jax.random.PRNGKey(0), cfg)
        tokens = jnp.array(np.random.default_rng(8).integers(0, 256, (1, 128)),
                           jnp.int32)
        attn = make_flash_attention(block_q=64, block_k=64)
        lf, gf = jax.value_and_grad(
            lambda p: next_token_loss(p, tokens, cfg, attn_fn=attn))(params)
        ld, gd = jax.value_and_grad(
            lambda p: next_token_loss(p, tokens, cfg))(params)
        assert np.isfinite(float(lf))
        assert abs(float(lf) - float(ld)) < 0.05
        # bf16 params/activations: gradients agree to bf16-noise scale
        flat_f = jax.tree_util.tree_leaves(gf)
        flat_d = jax.tree_util.tree_leaves(gd)
        for a, b in zip(flat_f, flat_d):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            denom = max(1e-3, float(np.abs(b).max()))
            assert float(np.abs(a - b).max()) / denom < 0.1

    def test_ragged_seq_rejected(self):
        q, k, v = _qkv(np.random.default_rng(4), 1, 100, 2, 2, 128)
        with pytest.raises(ValueError, match="must divide"):
            flash_attention(q, k, v, True, 64, 64)

    def test_plugs_into_llama_forward(self):
        from strom.models.llama import LlamaConfig, forward, init_params
        from strom.ops.flash_attention import make_flash_attention

        # head_dim 128 so the kernel tiles cleanly; 2 layers keep it fast
        cfg = LlamaConfig(vocab=256, d_model=256, n_layers=2, n_heads=2,
                          n_kv_heads=2, d_ff=512, rope_theta=10_000.0)
        params = init_params(jax.random.PRNGKey(0), cfg)
        tokens = jnp.array(np.random.default_rng(5).integers(0, 256, (1, 128)),
                           jnp.int32)
        dense = forward(params, tokens, cfg)
        flash = forward(params, tokens, cfg,
                        attn_fn=make_flash_attention(block_q=64, block_k=64))
        # bf16 activations through 2 layers: compare at bf16-noise scale
        d, f = np.asarray(dense), np.asarray(flash)
        assert np.abs(d - f).max() < 0.15, np.abs(d - f).max()
        assert np.argmax(d[0, -1]) == np.argmax(f[0, -1])  # same prediction


# (Dqk, Dv, scale): the square head at the default 1/√Dqk, and multi-head
# latent attention's 192-wide queries and keys, 128-wide values, and YaRN's
# scale (DeepSeek-V2: 192^-½ × (0.1·0.707·ln 40 + 1)²).
WIDTHS = [(64, 64, None),
          (192, 128, 192 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1) ** 2)]


def _qkv_split(rng, B, S, H, Dqk, Dv):
    q = jnp.array(rng.normal(size=(B, S, H, Dqk)), jnp.float32)
    k = jnp.array(rng.normal(size=(B, S, H, Dqk)), jnp.float32)
    v = jnp.array(rng.normal(size=(B, S, H, Dv)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("Dqk,Dv,scale", WIDTHS)
def test_forward_with_value_width_and_scale(Dqk, Dv, scale):
    q, k, v = _qkv_split(np.random.default_rng(10), 1, 256, 2, Dqk, Dv)
    out = flash_attention(q, k, v, True, 128, 128, None, scale)
    assert out.shape == (1, 256, 2, Dv)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_dense_ref(q, k, v, True, scale)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("Dqk,Dv,scale", WIDTHS)
def test_backward_with_value_width_and_scale(Dqk, Dv, scale):
    """dq and dk keep the query/key width, dv the value width."""
    q, k, v = _qkv_split(np.random.default_rng(11), 1, 256, 2, Dqk, Dv)

    def f_flash(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, True, 128, 128, None,
                                       scale) ** 2)

    def f_dense(q_, k_, v_):
        return jnp.sum(_dense_ref(q_, k_, v_, True, scale) ** 2)

    got = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, ("dq", "dk", "dv")):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False)])
def test_interpret_mode_per_backend(backend, interpret):
    """Interpreted on the CPU (tests), compiled Mosaic on a TPU."""
    from strom.ops.flash_attention import interpret_mode

    assert interpret_mode(backend) is interpret


@pytest.mark.parametrize("backend", ["gpu", "cuda", "rocm", "METAL"])
def test_interpret_mode_refuses_other_backends(backend):
    """Any other platform raises instead of interpreting in silence."""
    from strom.ops.flash_attention import interpret_mode

    with pytest.raises(RuntimeError, match="neither"):
        interpret_mode(backend)


def test_interpret_mode_defaults_to_the_default_backend():
    from strom.ops.flash_attention import interpret_mode

    assert jax.default_backend() == "cpu"  # conftest pins the CPU backend
    assert interpret_mode() is True


# -- tiles chosen from the shape ---------------------------------------------
def _live_pairs(S, bq, bk, causal):
    """Tile pairs that hold a (query, key) pair the mask keeps, by brute
    force over positions."""
    pos_q, pos_k = np.arange(S), np.arange(S)
    keep = pos_q[:, None] >= pos_k[None, :] if causal else np.ones((S, S), bool)
    return int(keep.reshape(S // bq, bq, S // bk, bk).any(axis=(1, 3)).sum())


@pytest.mark.parametrize("S", [64, 100, 384, 1024, 4096, 6144, 8192])
def test_plan_tiles_divide_seq(S):
    p = flash_plan(S, 192, 128)
    assert S % p.blk_q == 0 and S % p.blk_k == 0, p
    assert p.blk_q <= S and p.blk_k <= S


@pytest.mark.parametrize("S", [64, 100])
def test_plan_short_seq_is_one_tile(S):
    p = flash_plan(S, 64, 64)
    assert (p.blk_q, p.blk_k, p.steps, p.live) == (S, S, 1, 1)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("S,blocks", [(4096, (None, None)), (1024, (None, None)),
                                      (1024, (256, 512)), (1024, (512, 256)),
                                      (512, (128, 128)), (768, (256, 128))])
def test_plan_live_steps_are_the_causal_tile_pairs(S, blocks, causal):
    bq, bk = blocks
    p = flash_plan(S, 192, 128, causal, batch=2, heads=3, block_q=bq,
                   block_k=bk)
    assert p.steps == 6 * (S // p.blk_q) * (S // p.blk_k)
    assert p.live == 6 * _live_pairs(S, p.blk_q, p.blk_k, causal)
    if not causal:
        assert p.skipped_kv_bytes == p.skipped_q_bytes == 0


def test_plan_explicit_blocks_override():
    assert flash_plan(4096, 192, 128, block_q=256, block_k=128)[:2] \
        == (256, 128)
    # one given, the other from the plan; capped at the sequence
    assert flash_plan(4096, 192, 128, block_q=256).blk_q == 256
    assert flash_plan(64, 64, 64, block_q=128, block_k=128)[:2] == (64, 64)


def test_plan_at_the_deepseek_cell():
    """B 8, H 16, S 4,096, Dqk 192, Dv 128: at 512×512 tiles 8,192 grid
    steps a call, 4,608 live, against 131,072 (67,584 live) at 128×128;
    the forward's masked steps skip their k and v tiles (bf16)."""
    at = dict(batch=8, heads=16)
    p512 = flash_plan(4096, 192, 128, block_q=512, block_k=512, **at)
    p128 = flash_plan(4096, 192, 128, block_q=128, block_k=128, **at)
    assert (p512.steps, p512.live) == (8192, 4608)
    assert (p128.steps, p128.live) == (131072, 67584)
    assert p512.skipped_kv_bytes == 128 * 28 * 512 * 320 * 2
    assert p512.skipped_q_bytes == 128 * 28 * 512 * (320 * 2 + 8)
    assert flash_plan(4096, 192, 128, **at)[:4] == (1024, 1024, 2048, 1280)


@pytest.mark.parametrize("dqk,dv,tiles", [(64, 64, (1024, 1024)),
                                          (192, 128, (1024, 1024)),
                                          (256, 256, (1024, 512)),
                                          (576, 512, (512, 512))])
def test_plan_wider_heads_take_smaller_tiles(dqk, dv, tiles):
    """Tile area × head width stays within what fits VMEM at 1024² and
    Dqk 192 + Dv 128: at equal area the squarer pair, then the taller."""
    assert flash_plan(4096, dqk, dv)[:2] == tiles


def _copies(indices):
    """Block copies a pipeline makes over a sequence of grid steps: one
    whenever the block index differs from the previous step's."""
    return sum(1 for n, x in enumerate(indices) if n == 0 or x != indices[n - 1])


@pytest.mark.parametrize("blocks", [(128, 128), (512, 512), (1024, 512),
                                    (512, 1024), (256, 1024), (1024, 256)])
def test_clamped_kv_index_repeats_on_masked_steps(blocks):
    """Forward and dq, grid (…, i, j): on every masked step the kv index is
    the previous step's, so a q row copies only its live kv blocks."""
    S, (bq, bk) = 4096, blocks
    for i in range(S // bq):
        idx = [int(_kv_block(i, j, causal=True, blk_q=bq, blk_k=bk))
               for j in range(S // bk)]
        for j, x in enumerate(idx):
            if j <= _last_live(i, bq, bk):
                assert x == j
            else:
                assert x == idx[j - 1], (i, j, idx)
        assert _copies(idx) == min(S // bk, _last_live(i, bq, bk) + 1)
        assert [int(_kv_block(i, j, causal=False, blk_q=bq, blk_k=bk))
                for j in range(S // bk)] == list(range(S // bk))


@pytest.mark.parametrize("blocks", [(128, 128), (512, 512), (1024, 512),
                                    (512, 1024), (256, 1024), (1024, 256)])
def test_clamped_q_index_repeats_on_masked_steps(blocks):
    """dkv, grid (…, j, g, i): every masked step reads the first live q
    block, the block the step after it needs, so a kv column copies only its
    live q blocks (q, dO, lse and Δ)."""
    S, (bq, bk) = 4096, blocks
    for j in range(S // bk):
        first = _first_live(j, bq, bk)
        idx = [int(_q_block(i, j, causal=True, blk_q=bq, blk_k=bk))
               for i in range(S // bq)]
        for i, x in enumerate(idx):
            assert x == (i if i >= first else first), (j, i, idx)
            if 0 < i <= first:
                assert x == idx[i - 1]
        assert _copies(idx) == S // bq - first
        assert [int(_q_block(i, j, causal=False, blk_q=bq, blk_k=bk))
                for i in range(S // bq)] == list(range(S // bq))


@pytest.mark.parametrize("causal,heads", [(True, (2, 2)), (False, (2, 2)),
                                          (True, (4, 2))],
                         ids=["True", "False", "True-gqa"])
@pytest.mark.parametrize("blocks", [(None, None), (256, 512), (512, 256)],
                         ids=["plan", "256x512", "512x256"])
def test_mla_widths_at_1024_match_dense(blocks, causal, heads):
    """Forward and dq, dk, dv at Dqk 192 / Dv 128 and S 1,024, at the
    plan's tiles and at rectangular ones, against the dense oracle; with
    query groups (H 4 over KV 2), dkv's clamped q map reads each group's
    head."""
    Dqk, Dv, scale = WIDTHS[1]
    H, KV = heads
    rng = np.random.default_rng(12)
    q = jnp.array(rng.normal(size=(1, 1024, H, Dqk)), jnp.float32)
    k = jnp.array(rng.normal(size=(1, 1024, KV, Dqk)), jnp.float32)
    v = jnp.array(rng.normal(size=(1, 1024, KV, Dv)), jnp.float32)
    bq, bk = blocks

    def f_flash(q_, k_, v_):
        return flash_attention(q_, k_, v_, causal, bq, bk, None, scale)

    def f_dense(q_, k_, v_):
        return _dense_ref(q_, k_, v_, causal, scale)

    out, vjp = jax.vjp(f_flash, q, k, v)
    ref, vjp_ref = jax.vjp(f_dense, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    g = jnp.array(np.random.default_rng(13).normal(size=out.shape),
                  jnp.float32)
    for a, b, name in zip(vjp(g), vjp_ref(g), ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4, err_msg=name)
