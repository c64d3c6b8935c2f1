"""Plain reference for DeepSeek-V2 (DeepSeek-AI 2024, arXiv:2405.04434, and
the published ``modeling_deepseek.py``): weights from a key, forward, loss
and gradients in ``jax.numpy`` at float32 under HIGHEST matmul precision,
one sequence at a time. No Pallas, no ragged products, no strom imports.
The expert layers, the held experts inside each and the query blocks of
attention are ``lax.scan`` loops, one compiled body each, so that the
reference at published widths compiles for a TPU in about two minutes.

*c* is any object with the field names of
``strom.models.deepseek_v2.DeepseekV2Config`` (the benchmark builds one from
its configuration file). Departures from the published description:

- RoPE pairs are (i, i + d/2) of the rope slice as it leaves its projection;
  the published code first permutes interleaved pairs into that layout. With
  random weights that is a fixed permutation of the rope columns of ``wq``
  and ``wkv_a``.
- One expert-parallel rank's share, as the program holds it: the routed
  output counts only experts ``held_lo … held_lo + n_held - 1`` (the router
  still scores all ``n_experts``), and the vocabulary is the held rows.
- The balance loss is added to the loss as ``aux_alpha × Σ seq-aux``; the
  published code adds the same gradient through ``AddAuxiliaryLoss``.
- The loss of a record [S+1] is the mean next-token cross-entropy of its
  first S positions; a batch's is the mean over its records.
- *quant*, when given, rounds the operands of every linear layer (the
  attention core and the router stay float32): the float8 control.
- *q_block* computes causal attention over blocks of that many query rows,
  each against the keys up to its last row; *remat* recomputes each layer in
  the backward. Both change memory, not values.

This file has a copy in ``benchmark/configs/ref_deepseek_v2.py`` so that the
benchmark stands alone; ``tests/test_deepseek_v2.py`` keeps them the same.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


# -- weights ------------------------------------------------------------------
def param_shapes(c) -> dict:
    d, H = c.d_model, c.n_heads
    qk = c.qk_nope + c.qk_rope
    shared = c.n_shared * c.expert_ff

    def attn(*lead):
        return {"attn_norm": (*lead, d), "wq": (*lead, d, H * qk),
                "wkv_a": (*lead, d, c.kv_lora + c.qk_rope),
                "kv_norm": (*lead, c.kv_lora),
                "wkv_b": (*lead, c.kv_lora, H * (c.qk_nope + c.v_head)),
                "wo": (*lead, H * c.v_head, d), "mlp_norm": (*lead, d)}

    L, E = c.n_moe_layers, c.n_held
    return {
        "embed": (c.vocab, d),
        "dense": [{**attn(), "w_gate": (d, c.dense_ff),
                   "w_up": (d, c.dense_ff), "w_down": (c.dense_ff, d)}
                  for _ in range(c.n_dense_layers)],
        "moe": {**attn(L), "router": (L, d, c.n_experts),
                "w_gate": (L, E, d, c.expert_ff),
                "w_up": (L, E, d, c.expert_ff),
                "w_down": (L, E, c.expert_ff, d),
                "shared_gate": (L, d, shared), "shared_up": (L, d, shared),
                "shared_down": (L, shared, d)},
        "final_norm": (d,), "lm_head": (d, c.vocab)}


def init_params(key, c) -> dict:
    """One key per leaf in sorted-name order; ``*norm`` leaves one, the
    rest N(0, init_std²)."""
    paths, tree = jax.tree_util.tree_flatten_with_path(
        param_shapes(c), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(paths))
    return jax.tree_util.tree_unflatten(tree, [
        jnp.ones(shape, jnp.float32)
        if str(getattr(path[-1], "key", "")).endswith("norm")
        else jax.random.normal(k, shape, jnp.float32) * c.init_std
        for (path, shape), k in zip(paths, keys)])


# -- pieces -------------------------------------------------------------------
def rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(c):
    """DeepseekV2YarnRotaryEmbedding's frequencies."""
    dim, base = c.qk_rope, c.rope_theta

    def corr(rot):
        return dim * math.log(c.yarn_original_len / (rot * 2 * math.pi)) \
            / (2 * math.log(base))

    lo = max(math.floor(corr(c.yarn_beta_fast)), 0)
    hi = min(math.ceil(corr(c.yarn_beta_slow)), dim - 1)
    if lo == hi:
        hi += 0.001
    pos_freqs = base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    extra, inter = 1.0 / pos_freqs, 1.0 / (c.yarn_factor * pos_freqs)
    mask = 1.0 - jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo)
                          / (hi - lo), 0, 1)
    return inter * (1 - mask) + extra * mask


def rope(x, inv_freq, m):
    """x [S, h, d]: pairs (i, i + d/2) turned by position × inv_freq."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = (jnp.cos(ang) * m)[:, None], (jnp.sin(ang) * m)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, scale, q_block=None):
    """Causal softmax attention; q, k [S, H, Dqk], v [S, H, Dv]. With
    *q_block*, a loop over blocks of that many query rows, each against
    every key under the causal mask and recomputed in the backward."""
    S = q.shape[0]
    blk = q_block or S

    def block(i):
        rows = lax.dynamic_slice_in_dim(q, i * blk, blk)
        s = jnp.einsum("qhd,khd->hqk", rows, k, precision=HI) * scale
        mask = (i * blk + jnp.arange(blk))[:, None] >= jnp.arange(S)[None, :]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    return lax.map(jax.checkpoint(block), jnp.arange(S // blk)).reshape(
        S, *v.shape[1:])


def _mm(quant):
    q = quant or (lambda x: x)

    def mm(a, b):
        return jnp.dot(q(a), q(b), precision=HI)

    return mm


def mla(x, lp, c, mm, q_block):
    S, H = x.shape[0], c.n_heads
    h = rmsnorm(x, lp["attn_norm"], c.norm_eps)
    q = mm(h, lp["wq"]).reshape(S, H, c.qk_nope + c.qk_rope)
    kv_a = mm(h, lp["wkv_a"])
    latent = rmsnorm(kv_a[:, :c.kv_lora], lp["kv_norm"], c.norm_eps)
    kv = mm(latent, lp["wkv_b"]).reshape(S, H, c.qk_nope + c.v_head)
    inv_freq = yarn_inv_freq(c)
    m = yarn_mscale(c.yarn_factor, c.yarn_mscale) \
        / yarn_mscale(c.yarn_factor, c.yarn_mscale_all_dim)
    q_pe = rope(q[..., c.qk_nope:], inv_freq, m)
    k_pe = rope(kv_a[:, None, c.kv_lora:], inv_freq, m)
    q = jnp.concatenate([q[..., :c.qk_nope], q_pe], -1)
    k = jnp.concatenate([kv[..., :c.qk_nope],
                         jnp.broadcast_to(k_pe, (S, H, c.qk_rope))], -1)
    scale = (c.qk_nope + c.qk_rope) ** -0.5 \
        * yarn_mscale(c.yarn_factor, c.yarn_mscale_all_dim) ** 2
    o = attention(q, k, kv[..., c.qk_nope:], scale, q_block)
    return x + mm(o.reshape(S, H * c.v_head), lp["wo"])


def swiglu(h, g, u, d, mm):
    return mm(jax.nn.silu(mm(h, g)) * mm(h, u), d)


def moe(h, lp, c, mm):
    """h [S, d] → (held experts' routed output + shared experts' output,
    seq-aux, pairs per held expert [n_held])."""
    S = h.shape[0]
    scores = jax.nn.softmax(jnp.dot(h, lp["router"], precision=HI), axis=-1)
    weight, idx = lax.top_k(scores, c.top_k)
    weight = weight * c.routed_scaling
    y = swiglu(h, lp["shared_gate"], lp["shared_up"], lp["shared_down"], mm)

    def expert(y, e):
        expert_id, w_gate, w_up, w_down = e
        hit = idx == expert_id                                    # [S, k]
        w = jnp.sum(jnp.where(hit, weight, 0.0), axis=-1)         # [S]
        y = y + w[:, None] * swiglu(h, w_gate, w_up, w_down, mm)
        return y, jnp.sum(hit, dtype=jnp.int32)

    y, counts = lax.scan(expert, y, (c.held_lo + jnp.arange(c.n_held),
                                     lp["w_gate"], lp["w_up"], lp["w_down"]))
    picks = jnp.sum(jax.nn.one_hot(idx, c.n_experts), axis=(0, 1)) \
        / (S * c.top_k / c.n_experts)
    aux = jnp.sum(picks * jnp.mean(scores, axis=0))
    return y, aux, counts


# -- loss ---------------------------------------------------------------------
def hidden(params, inputs, c, mm, q_block=None, remat=False):
    """inputs [S] → (final-normed hidden [S, d], seq-aux per expert layer,
    pairs per held expert [n_moe_layers, n_held])."""
    x = params["embed"][inputs]

    def dense(x, lp):
        x = mla(x, lp, c, mm, q_block)
        h = rmsnorm(x, lp["mlp_norm"], c.norm_eps)
        return x + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], mm)

    def expert(x, lp):
        x = mla(x, lp, c, mm, q_block)
        y, aux, counts = moe(rmsnorm(x, lp["mlp_norm"], c.norm_eps), lp, c,
                             mm)
        return x + y, aux, counts

    if remat:
        dense, expert = jax.checkpoint(dense), jax.checkpoint(expert)
    for lp in params["dense"]:
        x = dense(x, lp)

    def layer(x, lp):
        x, aux, n = expert(x, lp)
        return x, (aux, n)

    x, (auxes, counts) = lax.scan(layer, x, params["moe"])
    return rmsnorm(x, params["final_norm"], c.norm_eps), auxes, counts


def logits(params, inputs, c):
    """inputs [S] → logits [S, vocab]."""
    mm = _mm(None)
    return mm(hidden(params, inputs, c, mm)[0], params["lm_head"])


def sequence_loss(params, record, c, quant=None, q_block=None, remat=False):
    """record [S+1] int → (cross-entropy mean + aux_alpha × Σ seq-aux,
    pairs per held expert [n_moe_layers, n_held])."""
    mm = _mm(quant)
    x, aux, counts = hidden(params, record[:-1], c, mm, q_block, remat)
    out = mm(x, params["lm_head"])
    gold = jnp.take_along_axis(out, record[1:, None], axis=-1)[:, 0]
    ce = jnp.mean(jax.nn.logsumexp(out, axis=-1) - gold)
    return ce + c.aux_alpha * jnp.sum(aux), counts


def batch_loss(params, tokens, c, quant=None, q_block=None):
    """tokens [B, S+1] → (mean of the records' losses, pairs summed over
    the batch [n_moe_layers, n_held])."""
    losses, counts = jax.vmap(
        lambda r: sequence_loss(params, r, c, quant, q_block))(tokens)
    return jnp.mean(losses), jnp.sum(counts, axis=0)
