"""Llama-style decoder-only transformer — the flagship model for the packed
-token pretrain pipeline (BASELINE config #4: "Llama-3-8B packed-token .bin
shards → JAX pretrain dataloader (v5p-8)", BASELINE.json:10).

Pure-JAX functional implementation, TPU-first:
- parameters stacked over layers and iterated with `lax.scan` (one compiled
  block body, fast XLA compiles at depth);
- bfloat16 activations/matmuls on the MXU, float32 softmax/norm accumulation;
- GQA (grouped-query attention) + RoPE + SwiGLU, matching the Llama-3 family;
- tensor-parallel sharding rules for every weight in
  :mod:`strom.parallel.sharding` (Megatron-style column/row split pairs).

The reference has no models (it is an I/O kernel module — SURVEY.md §2.3);
this model exists as the consumer of the data path, mirroring how PG-Strom
consumes the reference's DMA engine (SURVEY.md §3.5).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab: int = 128_256
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14_336
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @classmethod
    def llama3_8b(cls) -> "LlamaConfig":
        return cls()

    @classmethod
    def tiny(cls) -> "LlamaConfig":
        """~2M params; unit tests and compile checks."""
        return cls(vocab=512, d_model=128, n_layers=2, n_heads=4, n_kv_heads=2,
                   d_ff=256, rope_theta=10_000.0)

    @classmethod
    def small(cls) -> "LlamaConfig":
        """~100M params; single-host perf experiments."""
        return cls(vocab=32_000, d_model=768, n_layers=12, n_heads=12,
                   n_kv_heads=4, d_ff=2048)

    def param_count(self) -> int:
        d, f, v, l = self.d_model, self.d_ff, self.vocab, self.n_layers
        hd = self.head_dim
        attn = d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd + self.n_heads * hd * d
        mlp = 3 * d * f
        return v * d + l * (attn + mlp + 2 * d) + d + d * v


def init_params(key: jax.Array, cfg: LlamaConfig) -> dict:
    """Stacked-over-layers parameter pytree (leading dim = n_layers)."""
    d, hd = cfg.d_model, cfg.head_dim
    nh, nkv, f, L = cfg.n_heads, cfg.n_kv_heads, cfg.d_ff, cfg.n_layers
    k = iter(jax.random.split(key, 9))
    dt = cfg.jdtype

    def norm_init(*shape):
        return jnp.ones(shape, dtype=jnp.float32)

    def dense_init(kk, *shape, scale_dim=None):
        scale = 1.0 / math.sqrt(scale_dim if scale_dim is not None else shape[-2])
        return (jax.random.normal(kk, shape, dtype=jnp.float32) * scale).astype(dt)

    return {
        "embed": dense_init(next(k), cfg.vocab, d, scale_dim=d),
        "layers": {
            "attn_norm": norm_init(L, d),
            "wq": dense_init(next(k), L, d, nh * hd),
            "wk": dense_init(next(k), L, d, nkv * hd),
            "wv": dense_init(next(k), L, d, nkv * hd),
            "wo": dense_init(next(k), L, nh * hd, d),
            "mlp_norm": norm_init(L, d),
            "w_gate": dense_init(next(k), L, d, f),
            "w_up": dense_init(next(k), L, d, f),
            "w_down": dense_init(next(k), L, f, d),
        },
        "final_norm": norm_init(d),
        "lm_head": dense_init(next(k), d, cfg.vocab),
    }


def rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale * w).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         inv_freq: jax.Array | None = None) -> jax.Array:
    """Rotary embedding. x: [B, S, H, Dh]; positions: [B, S] (absolute).
    Pairs are (i, i + Dh/2). *inv_freq* [Dh/2] replaces theta's frequencies
    (YaRN's blend, :mod:`strom.models.deepseek_v2`)."""
    hd = x.shape[-1]
    freqs = (theta ** (-jnp.arange(0, hd // 2, dtype=jnp.float32) / (hd // 2))
             if inv_freq is None else inv_freq)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,hd/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, q_offset: jax.Array | int = 0) -> jax.Array:
    """GQA core. q: [B,Sq,H,Dh]; k,v: [B,Sk,KV,Dh]. float32 softmax.

    q_offset: absolute position of q[0] minus that of k[0] — nonzero in ring
    attention where the query block sits mid-sequence."""
    B, Sq, H, Dh = q.shape
    KV = k.shape[2]
    group = H // KV
    qg = q.reshape(B, Sq, KV, group, Dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
    scores = scores / math.sqrt(Dh)
    if causal:
        qpos = jnp.arange(Sq) + q_offset
        kpos = jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    # guard fully-masked rows (produce 0 instead of nan)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(B, Sq, H, Dh)


def block(x: jax.Array, lp: dict, cfg: LlamaConfig, positions: jax.Array,
          attn_fn=None) -> jax.Array:
    B, S, D = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
    q = (h @ lp["wq"]).reshape(B, S, nh, hd)
    k = (h @ lp["wk"]).reshape(B, S, nkv, hd)
    v = (h @ lp["wv"]).reshape(B, S, nkv, hd)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    # attn_fn hook: ring attention (strom.parallel.ring) substitutes here for
    # sequence-parallel long-context runs
    attn = (attn_fn or attention)(q, k, v)
    x = x + attn.reshape(B, S, nh * hd) @ lp["wo"]

    h = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
    gated = jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])
    return x + gated @ lp["w_down"]


def forward(params: dict, tokens: jax.Array, cfg: LlamaConfig,
            positions: jax.Array | None = None, attn_fn=None,
            remat: bool = False) -> jax.Array:
    """tokens [B, S] int32 → logits [B, S, vocab] float32.

    remat=True wraps each layer in `jax.checkpoint`: the backward recomputes
    block activations instead of the scan stacking every intermediate over
    layers — without it a 12-layer step at B16×S2048 wants ~22G of HLO temps
    and OOMs a 16G chip. FLOPs-for-HBM is the standard TPU trade (the brief's
    "use jax.checkpoint / rematerialisation")."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = params["embed"][tokens].astype(cfg.jdtype)

    blk = block if not remat else jax.checkpoint(block, static_argnums=(2, 4))

    def body(carry, lp):
        return blk(carry, lp, cfg, positions, attn_fn), None

    x, _ = lax.scan(body, x, params["layers"])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return (x @ params["lm_head"]).astype(jnp.float32)


def next_token_loss(params: dict, tokens: jax.Array, cfg: LlamaConfig,
                    attn_fn=None, remat: bool = False) -> jax.Array:
    """Mean cross-entropy of predicting tokens[:, 1:] from tokens[:, :-1].

    Computed as a full-length forward + roll/mask rather than slicing to
    S-1: identical values under causality, but every array keeps ONE
    sequence length — which is what lets sequence-parallel sharding divide
    the batch evenly (the loader's seq_len+1 record length must be divisible
    by the sp axis size)."""
    B, L = tokens.shape
    logits = forward(params, tokens, cfg, attn_fn=attn_fn, remat=remat)
    targets = jnp.roll(tokens, -1, axis=1)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    mask = (jnp.arange(L) < L - 1).astype(jnp.float32)  # last column: no target
    return jnp.sum((logz - gold) * mask) / (B * (L - 1))


@partial(jax.jit, static_argnames=("cfg",))
def jit_forward(params: dict, tokens: jax.Array, cfg: LlamaConfig) -> jax.Array:
    return forward(params, tokens, cfg)
