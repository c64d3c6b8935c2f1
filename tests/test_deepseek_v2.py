"""DeepSeek-V2 (strom.models.deepseek_v2) against the plain reference
(benchmark/configs/ref_deepseek_v2.py, the one the benchmark cell's check
runs) on seeded random weights at a tiny size that keeps
the published ratios: split query/key heads, a latent, top-3 of 16 experts
with 4 held, two shared experts. Plus the expert share, the dropless layer,
the step's metrics, and the token pipeline at the cell's record length."""

import importlib.util
import os
from functools import partial

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from strom.models import deepseek_v2 as dv
from strom.ops.flash_attention import make_flash_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "ref_deepseek_v2", os.path.join(ROOT, "benchmark", "configs",
                                    "ref_deepseek_v2.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)
TINY32 = dv.DeepseekV2Config.tiny(dtype="float32")


def flash(cfg, block=64):
    return make_flash_attention(block_q=block, block_k=block,
                                scale=cfg.softmax_scale)


def tokens(seed, batch=2, seq=128, vocab=256):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, vocab, (batch, seq + 1)), jnp.int32)


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def test_weights_from_the_key_match_the_reference():
    key = jax.random.key(5)
    got = jax.jit(dv.init_params, static_argnums=1)(key, TINY32)
    want = jax.jit(ref.init_params, static_argnums=1)(key, TINY32)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("block", [64, 32])
def test_logits_loss_and_every_gradient_match_the_reference(block):
    """float32 compute on both sides at HIGHEST precision, so the only
    differences are summation order (blocked softmax in flash, chunked loss,
    sorted expert rows): 1e-4 of each leaf's largest element is some 100
    float32 ulps of headroom over the 1e-6 these read. Two flash block
    sizes: two and four blocks along the 128-token sequence."""
    attn_fn = flash(TINY32, block)
    params = init(TINY32, 1)
    toks = tokens(2)
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            lambda p: dv.next_token_loss(p, toks, TINY32, attn_fn=attn_fn,
                                         remat=True), has_aux=True))(params)
        (ref_loss, ref_counts), ref_grads = jax.jit(jax.value_and_grad(
            lambda p: ref.batch_loss(p, toks, TINY32), has_aux=True))(params)
        logits = jax.jit(lambda p: dv.forward(p, toks[:, :-1], TINY32,
                                              attn_fn=attn_fn))(params)
        ref_logits = jax.jit(jax.vmap(lambda t: ref.logits(params, t, TINY32))
                             )(toks[:, :-1])
    assert rel(logits, ref_logits) < 1e-4
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * abs(float(ref_loss))
    np.testing.assert_array_equal(aux["expert_assignments"], ref_counts)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), r in zip(flat, jax.tree.leaves(ref_grads)):
        assert rel(g, r) < 1e-4, jax.tree_util.keystr(path)


def test_bfloat16_compute_stays_near_the_reference():
    """bfloat16 operands carry 8 bits: the loss of a random model agrees to
    a few parts in a thousand (measured 2e-4 here), not to float32's."""
    cfg = dv.DeepseekV2Config.tiny()
    params = init(cfg, 3)
    toks = tokens(4)
    loss, _ = jax.jit(lambda p: dv.next_token_loss(p, toks, cfg,
                                                   flash(cfg)))(params)
    with jax.default_matmul_precision("highest"):
        ref_loss, _ = jax.jit(lambda p: ref.batch_loss(p, toks, cfg))(params)
    assert abs(float(loss) - float(ref_loss)) < 5e-3 * float(ref_loss)


def init(cfg, seed):
    return jax.jit(dv.init_params, static_argnums=1)(jax.random.key(seed), cfg)


def moe_layer(cfg):
    params = init(cfg, 7)
    lp = jax.tree.map(lambda a: a[0], params["moe"])
    h = jax.random.normal(jax.random.key(8), (96, cfg.d_model), jnp.float32)
    return lp, h


def test_every_rank_s_share_sums_to_the_uncut_layer():
    """Each of the four ranks holds four of the sixteen experts; the sum of
    their routed outputs, with the shared experts (which every rank computes
    alike) counted once, is the uncut reference layer's output."""
    whole = dv.DeepseekV2Config.tiny(dtype="float32", n_held=16)
    lp, h = moe_layer(whole)
    with jax.default_matmul_precision("highest"):
        want, _, want_counts = ref.moe(h, lp, whole, ref._mm(None))
        idx, weight, _ = dv.route(h, lp["router"], whole, batch=1)
        total = dv.swiglu(h, lp["shared_gate"], lp["shared_up"],
                          lp["shared_down"])
        counts = []
        for rank in range(4):
            cfg = dv.DeepseekV2Config.tiny(dtype="float32", n_held=4,
                                           held_lo=4 * rank)
            share = {k: lp[k][4 * rank:4 * rank + 4]
                     for k in ("w_gate", "w_up", "w_down")}
            out, n, _ = dv.held_experts(h, idx, weight, share, cfg)
            total = total + out
            counts.append(n)
    assert rel(total, want) < 1e-5
    np.testing.assert_array_equal(np.concatenate(counts), want_counts)
    assert int(np.sum(counts)) == h.shape[0] * whole.top_k


def test_no_pair_is_dropped_when_one_expert_takes_every_token():
    cfg = dv.DeepseekV2Config.tiny(dtype="float32")
    lp, h = moe_layer(cfg)
    N = h.shape[0]
    others = np.stack([np.random.default_rng(i).choice(
        np.arange(1, cfg.n_experts), cfg.top_k - 1, replace=False)
        for i in range(N)])
    idx = jnp.asarray(np.concatenate([np.zeros((N, 1), int), others], 1),
                      jnp.int32)
    weight = jax.nn.softmax(jax.random.normal(jax.random.key(9),
                                              idx.shape), -1)
    with jax.default_matmul_precision("highest"):
        out, sizes, _ = dv.held_experts(h, idx, weight, lp, cfg)
        want = held_reference(h, idx, weight, lp, cfg)
    assert int(sizes[0]) == N                       # expert 0: every token
    assert int(jnp.sum(sizes)) == int(jnp.sum(idx < cfg.n_held))
    assert rel(out, want) < 1e-5


def held_reference(h, idx, weight, lp, cfg):
    """The held experts' routed output computed densely: every token
    through every held expert, weighted by its gate weight for it."""
    want = jnp.zeros_like(h)
    for e in range(cfg.n_held):
        w = jnp.sum(jnp.where(idx == cfg.held_lo + e, weight, 0.0), axis=-1)
        want = want + w[:, None] * ref.swiglu(
            h, lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e],
            ref._mm(None))
    return want


def picks_with_held(held, cfg, n, seed):
    """[n, top_k] distinct expert ids per token of which exactly *held*
    pairs, spread over random tokens and slots, pick a held expert."""
    rng = np.random.default_rng(seed)
    others = np.arange(cfg.n_held, cfg.n_experts)
    idx = np.stack([rng.choice(others, cfg.top_k, replace=False)
                    for _ in range(n)]) if len(others) else \
        np.stack([rng.permutation(cfg.n_experts)[:cfg.top_k]
                  for _ in range(n)])
    if len(others):
        for p in rng.permutation(n * cfg.top_k)[:held]:
            t, j = divmod(int(p), cfg.top_k)
            idx[t, j] = rng.choice(np.setdiff1d(np.arange(cfg.n_held),
                                                idx[t]))
    assert int(np.sum(idx < cfg.n_held)) == held
    return jnp.asarray(idx, jnp.int32)


# T held pairs of N·k pairs at the tiny size (top-3), where chunk_rows
# gives 16 rows with 4 of 16 held (288 pairs: whole chunks; 300: the tail
# chunk is padded) and one chunk of every pair with all 16 held
@pytest.mark.parametrize("held,n_held,N,C_want", [
    (0, 4, 96, 16), (48, 4, 96, 16), (17, 4, 96, 16), (288, 16, 96, 288),
    (290, 4, 100, 16)],
    ids=["none", "whole_chunks", "one_past_a_chunk", "every_pair",
         "padded_tail"])
def test_chunked_dispatch_matches_the_dense_layer(held, n_held, N, C_want):
    """The chunked dispatch against every token through every held expert,
    float32 at HIGHEST: the output and the gradients of h, the gate weights
    and the three expert weights, at T = 0, a multiple of the chunk, one
    past a chunk, every pair held, and a last chunk past the pair list."""
    cfg = dv.DeepseekV2Config.tiny(dtype="float32", n_held=n_held)
    lp, _ = moe_layer(cfg)
    h = jax.random.normal(jax.random.key(8), (N, cfg.d_model), jnp.float32)
    C = dv.chunk_rows(N * cfg.top_k, cfg)
    assert C == C_want
    assert (N * cfg.top_k) % C == (12 if N == 100 else 0)
    idx = picks_with_held(held, cfg, N, seed=held)
    weight = jax.nn.softmax(jax.random.normal(jax.random.key(10),
                                              idx.shape), -1)
    cot = jax.random.normal(jax.random.key(11), h.shape)
    experts = {k: lp[k] for k in ("w_gate", "w_up", "w_down")}

    def loss(fn, h, weight, experts):
        return jnp.sum(fn(h, idx, weight, experts, cfg) * cot)

    def program(*a):
        return dv.held_experts(*a)[0]

    with jax.default_matmul_precision("highest"):
        out, sizes, chunks = jax.jit(
            lambda h, w, e: dv.held_experts(h, idx, w, e, cfg))(
                h, weight, experts)
        want = held_reference(h, idx, weight, experts, cfg)
        grads = jax.jit(jax.grad(partial(loss, program), argnums=(0, 1, 2)))(
            h, weight, experts)
        want_grads = jax.grad(partial(loss, held_reference),
                              argnums=(0, 1, 2))(h, weight, experts)
    assert int(jnp.sum(sizes)) == held
    assert int(chunks) == -(-held // C)
    if held == 0:
        np.testing.assert_array_equal(out, 0)
        for g in jax.tree.leaves(grads):
            np.testing.assert_array_equal(g, 0)
        return
    assert rel(out, want) < 1e-5
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree.leaves(want_grads)):
        assert rel(g, w) < 1e-5, jax.tree_util.keystr(path)


def unwritten_ragged_dot(real):
    """A grouped product as the TPU's behaves (seen on a v5e): the rows past
    the last group hold whatever was in device memory, in its output and in
    its input's gradient. Here they hold NaN."""

    @jax.custom_vjp
    def product(x, w, sizes):
        past = (jnp.arange(x.shape[0]) >= jnp.sum(sizes))[:, None]
        return jnp.where(past, jnp.nan, real(x, w, sizes))

    def fwd(x, w, sizes):
        return product(x, w, sizes), (x, w, sizes)

    def bwd(res, g):
        x, w, sizes = res
        dx, dw = jax.vjp(lambda a, b: real(a, b, sizes), x, w)[1](g)
        past = (jnp.arange(x.shape[0]) >= jnp.sum(sizes))[:, None]
        return jnp.where(past, jnp.nan, dx), dw, None

    product.defvjp(fwd, bwd)
    return product


def test_rows_outside_the_groups_reach_no_value_or_gradient(monkeypatch):
    """With a grouped product that leaves NaN past its last group, the loss
    and every gradient are finite and equal to the plain product's."""
    params = init(TINY32, 13)
    toks = tokens(14)
    attn_fn = flash(TINY32)

    def loss_and_grads():
        return jax.jit(jax.value_and_grad(lambda p: dv.next_token_loss(
            p, toks, TINY32, attn_fn)[0]))(params)

    want_loss, want = loss_and_grads()
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        unwritten_ragged_dot(jax.lax.ragged_dot))
    loss, grads = loss_and_grads()
    assert float(loss) == float(want_loss)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree.leaves(want)):
        assert np.isfinite(np.asarray(g)).all(), jax.tree_util.keystr(path)
        np.testing.assert_array_equal(g, w, err_msg=jax.tree_util.keystr(path))


def test_published_widths_and_counts():
    cfg = dv.DeepseekV2Config()
    assert (cfg.d_model, cfg.n_heads, cfg.qk_head, cfg.v_head, cfg.kv_lora,
            cfg.expert_ff, cfg.dense_ff, cfg.n_experts, cfg.top_k) == \
        (2048, 16, 192, 128, 512, 1408, 10944, 64, 6)
    # layer 0: 81.0M; each expert layer 100.4M; embedding + head 52.4M
    assert dv.param_count(cfg) == 535_060_992
    assert cfg.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1) ** 2)
    np.testing.assert_allclose(dv.yarn_inv_freq(cfg), ref.yarn_inv_freq(cfg),
                               rtol=1e-7)


def test_step_returns_loss_grad_norm_and_assignments():
    from strom.parallel.train import (init_deepseek_v2_train_state,
                                      make_deepseek_v2_train_step)

    cfg = dv.DeepseekV2Config.tiny()
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    opt = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(4.2e-4))
    state = init_deepseek_v2_train_state(jax.random.key(0), cfg, mesh, opt)
    step = make_deepseek_v2_train_step(cfg, mesh, opt)
    toks = jax.device_put(tokens(6), NamedSharding(mesh, P("dp", None)))
    state, m = step(state, toks)
    state, m = step(state, toks)
    assert step._cache_size() == 1
    assert set(m) == {"loss", "grad_norm", "expert_assignments",
                      "expert_chunks"}
    counts = np.asarray(m["expert_assignments"])
    assert counts.shape == (cfg.n_moe_layers, cfg.n_held)
    assert counts.dtype == np.int32 and 0 < counts.sum() <= 2 * 128 * 3 * 2
    assert np.isfinite(float(m["loss"])) and float(m["grad_norm"]) > 0


def test_step_counts_the_chunks_each_expert_layer_computed():
    """expert_chunks is ⌈T / C⌉ per expert layer, T the layer's held pairs
    and C the chunk rows at the step's 2 × 128 tokens × top-3."""
    from strom.parallel.train import (init_deepseek_v2_train_state,
                                      make_deepseek_v2_train_step)

    cfg = dv.DeepseekV2Config.tiny()
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    opt = optax.adamw(4.2e-4)
    state = init_deepseek_v2_train_state(jax.random.key(1), cfg, mesh, opt)
    step = make_deepseek_v2_train_step(cfg, mesh, opt)
    _, m = step(state, jax.device_put(tokens(15),
                                      NamedSharding(mesh, P("dp", None))))
    C = dv.chunk_rows(2 * 128 * cfg.top_k, cfg)
    held = np.asarray(m["expert_assignments"]).sum(axis=1)
    chunks = np.asarray(m["expert_chunks"])
    assert C == 32 and chunks.dtype == np.int32
    assert held.min() > C and chunks.shape == (cfg.n_moe_layers,)
    np.testing.assert_array_equal(chunks, -(-held // C))


def test_shardings_follow_the_rules_on_a_tp_mesh():
    from strom.parallel.sharding import deepseek_v2_param_specs

    specs = deepseek_v2_param_specs(jax.eval_shape(
        lambda k: dv.init_params(k, TINY32), jax.random.key(0)))
    assert specs["moe"]["wq"] == P("pp", None, "tp")
    assert specs["moe"]["wo"] == P("pp", "tp", None)
    assert specs["moe"]["w_down"] == P("pp", None, "tp", None)
    assert specs["moe"]["router"] == P("pp", None, None)
    assert specs["dense"][0]["w_gate"] == P(None, "tp")
    assert specs["lm_head"] == P(None, "tp")


def test_a_later_token_leaves_earlier_logits_alone():
    """Causal through the flash kernel at Dqk 24, Dv 16: changing the last
    input token moves its own logits and no earlier position's."""
    params = init(TINY32, 11)
    toks = tokens(12)[:, :-1]
    changed = toks.at[:, -1].set((toks[:, -1] + 1) % TINY32.vocab)
    fwd = jax.jit(lambda t: dv.forward(params, t, TINY32, flash(TINY32)))
    a, b = np.asarray(fwd(toks)), np.asarray(fwd(changed))
    np.testing.assert_array_equal(a[:, :-1], b[:, :-1])
    assert np.abs(a[:, -1] - b[:, -1]).max() > 0


def test_token_pipeline_at_the_cell_s_record_length(tmp_path):
    """make_llama_pipeline batches of 4,097-token int32 records (seq 4,096
    and its target) equal a numpy read of the same records."""
    from strom.config import StromConfig
    from strom.delivery.core import StromContext
    from strom.pipelines import make_llama_pipeline
    from strom.pipelines.sampler import EpochShuffleSampler

    rec, n, seed = 4097, 24, 2**31 + 5
    paths = []
    for s in range(2):
        t = np.random.default_rng(s).integers(0, 12800, rec * n // 2,
                                              dtype=np.int32)
        paths.append(str(tmp_path / f"shard{s}.bin"))
        t.tofile(paths[-1])
    records = np.concatenate([np.fromfile(p, np.int32).reshape(-1, rec)
                              for p in paths])
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    order = iter(EpochShuffleSampler(n, 4, seed=seed))
    ctx = StromContext(StromConfig(engine="python"))
    try:
        with make_llama_pipeline(ctx, paths, batch=4, seq_len=4096,
                                 sharding=NamedSharding(mesh, P("dp", None)),
                                 seed=seed) as pipe:
            for _ in range(8):                  # crosses an epoch boundary
                got = np.asarray(next(pipe))
                np.testing.assert_array_equal(got, records[next(order)])
    finally:
        ctx.close()


def test_profile_tool_types_the_expert_layer_s_operations():
    """tools/expert_profile.py types each operation of a compiled module
    by what it fuses: the held experts' backward keeps its sort, chunk
    loops, row gathers and scatter-adds under ``moe.experts``."""
    from benchmark.configs.deepseek_v2_lite_pretrain import (classify,
                                                             scope_map)
    from tools.expert_profile import hlo_types

    lp, h = moe_layer(TINY32)
    idx, weight, _ = dv.route(h, lp["router"], TINY32, batch=1)
    text = jax.jit(jax.grad(lambda h, idx: jnp.sum(dv.held_experts(
        h, idx, weight, lp, TINY32)[0]))).lower(h, idx).compile().as_text()
    types, scopes = hlo_types(text), scope_map(text)
    seen = {t for name, t in types.items()
            if classify(scopes.get(name, name)) == "moe.experts"}
    assert {"sort", "loop", "gather", "scatter"} <= seen, seen
