"""Pallas flash-attention kernel vs the dense oracle (interpret mode on the
CPU backend — same kernel code the TPU compiles)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from strom.ops.flash_attention import _dense_ref, flash_attention


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
        out = flash_attention(q, k, v, causal)
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
            return jnp.sum(flash_attention(q_, k_, v_, causal) ** 2)

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
