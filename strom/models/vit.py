"""ViT-B/16 — consumer of the WebDataset pipeline (BASELINE config #3:
"WebDataset .tar shards → ViT-B/16 training loader (4×NVMe RAID0)",
BASELINE.json:9).

Pure-JAX functional implementation, TPU-first:
- patchify as one reshape + matmul (a [B,N,P²·3] @ [P²·3,D] MXU matmul, not a
  conv — same math, better fit for the systolic array at P=16);
- encoder layers stacked over depth and iterated with `lax.scan` (one compiled
  block body, like the Llama flagship);
- bfloat16 matmuls, float32 layer-norm/softmax accumulation.

The reference has no models (SURVEY.md §2.3) — consumers exist to close the
loop the way PG-Strom closes the reference's (SURVEY.md §3.5).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from strom.models.llama import attention


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    """Defaults are ViT-B/16 at 224."""

    image_size: int = 224
    patch: int = 16
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_mlp: int = 3072
    num_classes: int = 1000
    dtype: str = "bfloat16"
    norm_eps: float = 1e-6

    @property
    def n_patches(self) -> int:
        return (self.image_size // self.patch) ** 2

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @classmethod
    def tiny(cls) -> "ViTConfig":
        """~300k params; unit tests and compile checks (input 32×32)."""
        return cls(image_size=32, patch=8, d_model=64, n_layers=2, n_heads=4,
                   d_mlp=128, num_classes=10)


def init_params(key: jax.Array, cfg: ViTConfig) -> dict:
    d, L, f = cfg.d_model, cfg.n_layers, cfg.d_mlp
    pdim = cfg.patch * cfg.patch * 3
    k = iter(jax.random.split(key, 12))
    dt = cfg.jdtype

    def dense(kk, *shape, scale_dim=None):
        scale = 1.0 / jnp.sqrt(scale_dim if scale_dim is not None else shape[-2])
        return (jax.random.normal(kk, shape, dtype=jnp.float32) * scale).astype(dt)

    def ln(*shape):
        return {"scale": jnp.ones(shape, jnp.float32),
                "bias": jnp.zeros(shape, jnp.float32)}

    return {
        "patch_embed": dense(next(k), pdim, d),
        "patch_bias": jnp.zeros((d,), dt),
        "cls_token": jnp.zeros((1, 1, d), dt),
        "pos_embed": (jax.random.normal(next(k), (1, cfg.n_patches + 1, d),
                                        dtype=jnp.float32) * 0.02).astype(dt),
        "layers": {
            "ln1": ln(L, d),
            "wqkv": dense(next(k), L, d, 3 * d),
            "wo": dense(next(k), L, d, d),
            "ln2": ln(L, d),
            "w1": dense(next(k), L, d, f),
            "b1": jnp.zeros((L, f), dt),
            "w2": dense(next(k), L, f, d),
            "b2": jnp.zeros((L, d), dt),
        },
        "final_ln": ln(d),
        "head": {"w": dense(next(k), d, cfg.num_classes).astype(jnp.float32),
                 "b": jnp.zeros((cfg.num_classes,), jnp.float32)},
    }


def layer_norm(x: jax.Array, p: dict, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]).astype(x.dtype)


def patchify(images: jax.Array, patch: int) -> jax.Array:
    """[B,H,W,3] → [B, N, patch*patch*3] row-major patches."""
    B, H, W, C = images.shape
    gh, gw = H // patch, W // patch
    x = images.reshape(B, gh, patch, gw, patch, C)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(B, gh * gw, patch * patch * C)


def _block(x: jax.Array, lp: dict, cfg: ViTConfig) -> jax.Array:
    B, S, D = x.shape
    nh, hd = cfg.n_heads, cfg.head_dim
    h = layer_norm(x, lp["ln1"], cfg.norm_eps)
    qkv = (h @ lp["wqkv"]).reshape(B, S, 3, nh, hd)
    q, kk, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    attn = attention(q, kk, v, causal=False)
    x = x + attn.reshape(B, S, D) @ lp["wo"]
    h = layer_norm(x, lp["ln2"], cfg.norm_eps)
    h = jax.nn.gelu(h @ lp["w1"] + lp["b1"]) @ lp["w2"] + lp["b2"]
    return x + h


def forward(params: dict, images: jax.Array, cfg: ViTConfig) -> jax.Array:
    """images [B,H,W,3] (normalized float) → logits [B, classes] float32."""
    B = images.shape[0]
    x = patchify(images.astype(cfg.jdtype), cfg.patch)
    x = x @ params["patch_embed"] + params["patch_bias"]
    cls = jnp.broadcast_to(params["cls_token"], (B, 1, cfg.d_model))
    x = jnp.concatenate([cls, x], axis=1) + params["pos_embed"]

    def body(carry, lp):
        return _block(carry, lp, cfg), None

    x, _ = lax.scan(body, x, params["layers"])
    x = layer_norm(x, params["final_ln"], cfg.norm_eps)
    cls_out = x[:, 0].astype(jnp.float32)
    return cls_out @ params["head"]["w"] + params["head"]["b"]


def loss_fn(params: dict, images: jax.Array, labels: jax.Array,
            cfg: ViTConfig) -> jax.Array:
    from strom.models.resnet import softmax_xent

    return softmax_xent(forward(params, images, cfg), labels)


@partial(jax.jit, static_argnames=("cfg",))
def jit_forward(params: dict, images: jax.Array, cfg: ViTConfig) -> jax.Array:
    return forward(params, images, cfg)
