"""Flash attention as Pallas TPU kernels — the flagship model's hot op.

TPU-first design (per /opt/skills/guides/pallas_guide.md):
- forward grid (B, H, Sq/BLK_Q, Sk/BLK_K), kv-block axis innermost so the
  online-softmax state for one q block lives in VMEM scratch across kv steps;
- q·kᵀ and p·v hit the MXU as [BLK, Dh]×[Dh, BLK] tiles with float32
  accumulation (`preferred_element_type`);
- causal masking at two granularities: whole kv blocks above the diagonal
  are skipped with `pl.when` (no wasted MXU work), and their index maps
  repeat the last block read so the pipeline copies nothing for them; the
  tiles that run mask elementwise with `broadcasted_iota`;
- tiles chosen from the shape (:func:`flash_plan`): a grid step carries a
  fixed cost, so the kernels take the largest tiles that fit VMEM;
- GQA folded into the index maps: q head h reads kv head h // group — no
  materialized kv repeat (the dense path in strom.models.llama reshapes
  instead).

Backward is the blockwise FlashAttention-2 recipe (round 1 used an O(S²)
dense recompute — VERDICT.md weak #5): the forward additionally emits the
per-row logsumexp, and two kernels rebuild P tile-by-tile from (q, k, lse):
  dV_j  = Σ_i P_ijᵀ dO_i
  dS_ij = P_ij ∘ (dO_i V_jᵀ − Δ_i),   Δ_i = rowsum(dO_i ∘ O_i)
  dQ_i  = Σ_j dS_ij K_j · scale,  dK_j = Σ_i dS_ijᵀ Q_i · scale
so no [S, S] tensor ever materializes — O(S) memory in both passes, which is
what makes long-context training (ring/sp composition) viable.

Queries and keys share one head width Dqk; values may have another, Dv
(multi-head latent attention: 192 and 128). A block's last dim is the whole
head width, so 192 needs no padding: Mosaic takes a block dim equal to the
array's. The softmax scale defaults to 1/√Dqk; a caller passes its own (YaRN
scales it).

On the CPU backend the kernels run in interpreter mode so tests exercise the
same code path the TPU compiles; on a TPU they compile to Mosaic; on any other
backend :func:`interpret_mode` refuses rather than interpret in silence.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_BIG = -0.7 * float(jnp.finfo(jnp.float32).max)
_LANES = 128  # f32 scratch tiles are (8, 128); m/l broadcast across lanes


def interpret_mode(backend: str | None = None) -> bool:
    """Whether the Pallas kernels run in interpreter mode on *backend*
    (default: ``jax.default_backend()``): True on ``cpu`` (tests), False on
    ``tpu`` (compiled Mosaic). Any other backend raises — the kernels are
    Mosaic TPU kernels, and interpreting them there would hide the device."""
    backend = backend or jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"Pallas flash kernels target TPU (compiled) or CPU "
                       f"(interpreted); backend {backend!r} is neither")


# Tile candidates, largest first. A grid step costs a fixed ~0.4-0.7 µs (its
# bookkeeping and tile copies) whatever it computes; on a TPU v5e at
# DeepSeek-V2-Lite's attention (S 4,096, Dqk 192, Dv 128) all three kernels
# ran fastest at 1024×1024 among tiles of 256, 512 and 1,024 (PERF.md §6).
_TILES = (1024, 512, 256, 128)
# Tile area × head width (Dqk + Dv) within which all three kernels compile in
# the v5e compiler's default 16 MiB of scoped VMEM. A checked point, not a
# model: compiles for a described v5e, bf16 and float32 alike. At 1024² and
# 192 + 128 the largest asks are dkv's 8.25 MiB (bf16) and dq's 11.25 MiB
# (float32); the wider heads checked step down and fit in both dtypes
# (256 + 256 to 1024×512, 576 + 512 to 512²). The asks do not follow the
# width smoothly: at 1024², bf16 fits 576 + 512 but not 640 + 640, and
# float32 does not fit 256 + 256 (16.5-20.3 MiB).
_VMEM_FIT = 1024 * 1024 * (192 + 128)


class TilePlan(NamedTuple):
    """The kernels' tiling for one call: the tiles, the grid steps they
    imply per kernel, the steps that compute (below the causal diagonal or
    on it), and the bytes of streamed tiles the masked steps do not copy:
    k and v in the forward and in dq (each), q, dO, lse and Δ in dkv."""
    blk_q: int
    blk_k: int
    steps: int
    live: int
    skipped_kv_bytes: int
    skipped_q_bytes: int


def _tiles(seq: int, width: int, block_q: int | None,
           block_k: int | None) -> tuple[int, int]:
    """(blk_q, blk_k). An explicit block is taken as given, capped at
    *seq*. Otherwise each tile is a candidate (capped at *seq*) that divides
    *seq*, and of the pairs within `_VMEM_FIT` at head width *width* the
    largest area wins, then the squarer pair, then the taller."""
    fits = [t for t in dict.fromkeys(min(t, seq) for t in _TILES)
            if seq % t == 0] or [min(_TILES[-1], seq)]  # else the kernel refuses
    qs = [min(block_q, seq)] if block_q else fits
    ks = [min(block_k, seq)] if block_k else fits
    pairs = [(q, k) for q in qs for k in ks if q * k * width <= _VMEM_FIT]
    return max(pairs or [(qs[-1], ks[-1])],
               key=lambda p: (p[0] * p[1], -abs(p[0] - p[1]), p[0]))


def flash_plan(seq: int, dqk: int, dv: int, causal: bool = True, *,
               batch: int = 1, heads: int = 1, itemsize: int = 2,
               block_q: int | None = None, block_k: int | None = None
               ) -> TilePlan:
    """The :class:`TilePlan` of attention over *seq* positions at head
    widths *dqk* / *dv*, for *batch* × *heads* (grid steps and bytes scale
    with both); all three kernels take the same tiles. An explicit
    *block_q* / *block_k* (capped at *seq*) overrides the choice.
    *itemsize* is q, k, v and dO's; it counts bytes and does not change the
    tiles (`_VMEM_FIT`). lse and Δ are float32."""
    bq, bk = _tiles(seq, dqk + dv, block_q, block_k)
    nq, nk = seq // bq, seq // bk
    live = (sum(min(nk, _last_live(i, bq, bk) + 1) for i in range(nq))
            if causal else nq * nk)
    n = batch * heads
    masked = n * (nq * nk - live)
    return TilePlan(bq, bk, n * nq * nk, n * live,
                    masked * bk * (dqk + dv) * itemsize,
                    masked * bq * ((dqk + dv) * itemsize + 8))


def _last_live(i, blk_q: int, blk_k: int):
    """The last kv block that q block *i* sees under the causal mask."""
    return ((i + 1) * blk_q - 1) // blk_k


def _first_live(j, blk_q: int, blk_k: int):
    """The first q block that sees kv block *j* under the causal mask."""
    return (j * blk_k) // blk_q


def _kv_block(i, j, *, causal: bool, blk_q: int, blk_k: int):
    """The kv block that the forward and dq read at grid step (i, j). A
    masked step (above the diagonal) repeats q block i's last live block,
    which the previous step read: an unchanged block index is no copy."""
    return jnp.minimum(j, _last_live(i, blk_q, blk_k)) if causal else j


def _q_block(i, j, *, causal: bool, blk_q: int, blk_k: int):
    """The q block (q, dO, lse and Δ) that dkv reads at grid step (j, i). A
    masked step (before the diagonal) reads kv block j's first live block
    already, so every masked step repeats one index and copies nothing."""
    return jnp.maximum(i, _first_live(j, blk_q, blk_k)) if causal else i


def _on_tile(body, *, causal: bool, iq, jk, blk_q: int, blk_k: int) -> None:
    """Run ``body(masked)`` for tile (iq, jk): under the causal mask only on
    a tile that holds a kept (query, key) pair, masked elementwise. Leaving
    the mask off tiles wholly below the diagonal saved ≤ 1.2 % a kernel at
    1024² on a v5e (PERF.md §6), too little for a second body;
    ``tools/flash_sweep.py --split`` times that variant through *masked*."""
    if not causal:
        body(False)
        return
    pl.when(jk * blk_k <= iq * blk_q + blk_q - 1)(lambda: body(True))


def _causal_mask(x, fill, *, iq, jk, blk_q: int, blk_k: int):
    qpos = iq * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    kpos = jk * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    return jnp.where(qpos >= kpos, x, fill)


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
               causal: bool, scale: float, blk_q: int, blk_k: int):
    iq = pl.program_id(2)
    jk = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(jk == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def step(masked: bool):
        q = q_ref[0, 0]                       # [blk_q, Dqk]
        k = k_ref[0, 0]                       # [blk_k, Dqk]
        v = v_ref[0, 0]                       # [blk_k, Dv]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = _causal_mask(s, _NEG_BIG, iq=iq, jk=jk, blk_q=blk_q,
                             blk_k=blk_k)
        m_prev = m_ref[:, :1]                  # [blk_q, 1]
        bm = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, bm)
        p = jnp.exp(s - m_new)                 # [blk_q, blk_k] f32
        alpha = jnp.exp(m_prev - m_new)        # [blk_q, 1]
        l_ref[:] = jnp.broadcast_to(
            l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha + pv

    _on_tile(step, causal=causal, iq=iq, jk=jk, blk_q=blk_q, blk_k=blk_k)

    @pl.when(jk == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[:] / denom).astype(o_ref.dtype)
        # logsumexp per q row, consumed by the blockwise backward. Stored as
        # a [blk_q, 1] column (same layout trick as m/l: row stats live on
        # sublanes and broadcast across lanes — no in-kernel transpose).
        lse_ref[0, 0] = m_ref[:, :1] + jnp.log(denom)


def _flash_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
               block_q: int | None = None, block_k: int | None = None,
               interpret: bool, scale: float | None = None
               ) -> tuple[jax.Array, jax.Array]:
    """q [B,S,H,Dqk]; k [B,S,KV,Dqk]; v [B,S,KV,Dv] → (out [B,S,H,Dv],
    lse [B,H,S,1]). Layout transposed to head-major [B,H,S,D] for
    MXU-friendly [S,D] tiles. Blocks None → :func:`flash_plan`'s."""
    S, Dqk = q.shape[1], q.shape[3]
    blk_q, blk_k = _tiles(S, Dqk + v.shape[3], block_q, block_k)
    if S % blk_q or S % blk_k:
        raise ValueError(f"seq len {S} must divide by blocks "
                         f"({blk_q},{blk_k})")
    out, lse = _fwd(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                    v.transpose(0, 2, 1, 3), causal=causal,
                    scale=_scale(scale, Dqk), blk_q=blk_q, blk_k=blk_k,
                    interpret=interpret)
    return out.transpose(0, 2, 1, 3), lse


def _fwd(qt, kt, vt, *, causal: bool, scale: float, blk_q: int, blk_k: int,
         interpret: bool):
    """out [B,H,S,Dv] and lse [B,H,S,1] from head-major q [B,H,S,Dqk],
    k [B,KV,S,Dqk] and v [B,KV,S,Dv]."""
    B, H, S, Dqk = qt.shape
    KV, Dv = kt.shape[1], vt.shape[3]
    G = H // KV
    kv_block = functools.partial(_kv_block, causal=causal, blk_q=blk_q,
                                 blk_k=blk_k)

    def kv_rows(d):
        return pl.BlockSpec((1, 1, blk_k, d), lambda b, h, i, j:
                            (b, h // G, kv_block(i, j), 0))

    def q_rows(d):
        return pl.BlockSpec((1, 1, blk_q, d), lambda b, h, i, j: (b, h, i, 0))

    return pl.pallas_call(
        functools.partial(_fa_kernel, causal=causal, scale=scale,
                          blk_q=blk_q, blk_k=blk_k),
        grid=(B, H, S // blk_q, S // blk_k),
        in_specs=[q_rows(Dqk), kv_rows(Dqk), kv_rows(Dv)],
        out_specs=[q_rows(Dv), q_rows(1)],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, Dv), qt.dtype),
            jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, _LANES), jnp.float32),  # m
            pltpu.VMEM((blk_q, _LANES), jnp.float32),  # l
            pltpu.VMEM((blk_q, Dv), jnp.float32),      # acc
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qt, kt, vt)


def _rebuild_p(q, k, lse_col, *, scale, masked, iq, jk, blk_q, blk_k):
    """Rebuild the softmax tile P_ij = exp(q·kᵀ·scale − lse) in f32.
    lse_col: [blk_q, 1] column — broadcasts across lanes like m/l do in the
    forward."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    p = jnp.exp(s - lse_col)
    if masked:
        p = _causal_mask(p, 0.0, iq=iq, jk=jk, blk_q=blk_q, blk_k=blk_k)
    return p


def _fa_bwd_dkv_kernel(q_ref, do_ref, k_ref, v_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_acc, dv_acc, *,
                       causal: bool, scale: float, blk_q: int, blk_k: int):
    # grid (B, KV, Jk, G, Iq): for one kv block, every (group head, q block)
    # pair accumulates into the same dk/dv block, which stays VMEM-resident
    # because its index is constant across the two innermost grid dims.
    jk = pl.program_id(2)
    g = pl.program_id(3)
    iq = pl.program_id(4)
    nq = pl.num_programs(4)
    ng = pl.num_programs(3)

    @pl.when(jnp.logical_and(g == 0, iq == 0))
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def step(masked: bool):
        q = q_ref[0, 0]            # [blk_q, Dqk]
        do = do_ref[0, 0]          # [blk_q, Dv]
        k = k_ref[0, 0]            # [blk_k, Dqk]
        v = v_ref[0, 0]            # [blk_k, Dv]
        p = _rebuild_p(q, k, lse_ref[0, 0], scale=scale, masked=masked,
                       iq=iq, jk=jk, blk_q=blk_q, blk_k=blk_k)
        pb = p.astype(v.dtype)
        # dV_j += P_ijᵀ dO_i
        dv_acc[:] += jax.lax.dot_general(pb, do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        # dP_ij = dO_i V_jᵀ ;  dS = P ∘ (dP − Δ) · scale
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0, 0]) * scale)
        # dK_j += dSᵀ Q_i
        dk_acc[:] += jax.lax.dot_general(ds.astype(q.dtype), q,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    _on_tile(step, causal=causal, iq=iq, jk=jk, blk_q=blk_q, blk_k=blk_k)

    @pl.when(jnp.logical_and(g == ng - 1, iq == nq - 1))
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _fa_bwd_dq_kernel(q_ref, do_ref, k_ref, v_ref, lse_ref, delta_ref,
                      dq_ref, dq_acc, *,
                      causal: bool, scale: float, blk_q: int, blk_k: int):
    # grid (B, H, Iq, Jk): kv blocks innermost, dq accumulates in scratch
    iq = pl.program_id(2)
    jk = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(jk == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def step(masked: bool):
        q = q_ref[0, 0]
        do = do_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        p = _rebuild_p(q, k, lse_ref[0, 0], scale=scale, masked=masked,
                       iq=iq, jk=jk, blk_q=blk_q, blk_k=blk_k)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0, 0]) * scale)
        # dQ_i += dS_ij K_j
        dq_acc[:] += jax.lax.dot_general(ds.astype(k.dtype), k,
                                         (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    _on_tile(step, causal=causal, iq=iq, jk=jk, blk_q=blk_q, blk_k=blk_k)

    @pl.when(jk == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _delta(out: jax.Array, g: jax.Array) -> jax.Array:
    """Δ = rowsum(dO ∘ O) in the kernels' [B,H,S,1] column layout (same as
    lse). Tiny elementwise reduce; XLA fuses it — no kernel needed."""
    return jnp.sum(g.transpose(0, 2, 1, 3).astype(jnp.float32)
                   * out.transpose(0, 2, 1, 3).astype(jnp.float32),
                   axis=-1, keepdims=True)


def _bwd_dkv(qt, dot, kt, vt, lse, delta, *, causal: bool, scale: float,
             blk_q: int, blk_k: int, interpret: bool):
    """dk, dv [B,KV,S,D] from head-major q, dO [B,H,S,D], k, v [B,KV,S,D]
    and the [B,H,S,1] lse and Δ columns."""
    B, H, S, Dqk = qt.shape
    KV, Dv = kt.shape[1], vt.shape[3]
    G = H // KV
    q_block = functools.partial(_q_block, causal=causal, blk_q=blk_q,
                                blk_k=blk_k)

    def q_rows(d):
        return pl.BlockSpec((1, 1, blk_q, d), lambda b, kv, jk, gg, iq:
                            (b, kv * G + gg, q_block(iq, jk), 0))

    def kv_rows(d):
        return pl.BlockSpec((1, 1, blk_k, d),
                            lambda b, kv, jk, gg, iq: (b, kv, jk, 0))

    return pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, causal=causal, scale=scale,
                          blk_q=blk_q, blk_k=blk_k),
        grid=(B, KV, S // blk_k, G, S // blk_q),
        in_specs=[q_rows(Dqk), q_rows(Dv), kv_rows(Dqk), kv_rows(Dv),
                  q_rows(1), q_rows(1)],
        out_specs=[kv_rows(Dqk), kv_rows(Dv)],
        out_shape=[jax.ShapeDtypeStruct((B, KV, S, Dqk), kt.dtype),
                   jax.ShapeDtypeStruct((B, KV, S, Dv), vt.dtype)],
        scratch_shapes=[pltpu.VMEM((blk_k, Dqk), jnp.float32),
                        pltpu.VMEM((blk_k, Dv), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qt, dot, kt, vt, lse, delta)


def _bwd_dq(qt, dot, kt, vt, lse, delta, *, causal: bool, scale: float,
            blk_q: int, blk_k: int, interpret: bool):
    """dq [B,H,S,Dqk], from the same operands as :func:`_bwd_dkv`."""
    B, H, S, Dqk = qt.shape
    KV, Dv = kt.shape[1], vt.shape[3]
    G = H // KV
    kv_block = functools.partial(_kv_block, causal=causal, blk_q=blk_q,
                                 blk_k=blk_k)

    def q_rows(d):
        return pl.BlockSpec((1, 1, blk_q, d),
                            lambda b, h, iq, jk: (b, h, iq, 0))

    def kv_rows(d):
        return pl.BlockSpec((1, 1, blk_k, d), lambda b, h, iq, jk:
                            (b, h // G, kv_block(iq, jk), 0))

    return pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, causal=causal, scale=scale,
                          blk_q=blk_q, blk_k=blk_k),
        grid=(B, H, S // blk_q, S // blk_k),
        in_specs=[q_rows(Dqk), q_rows(Dv), kv_rows(Dqk), kv_rows(Dv),
                  q_rows(1), q_rows(1)],
        out_specs=q_rows(Dqk),
        out_shape=jax.ShapeDtypeStruct((B, H, S, Dqk), qt.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, Dqk), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(qt, dot, kt, vt, lse, delta)


def _flash_bwd(q, k, v, out, lse, g, *, causal: bool,
               block_q: int | None = None, block_k: int | None = None,
               interpret: bool, delta=None, scale: float | None = None):
    """Blockwise backward. With the default delta=None this is the vjp of
    the single-device forward. Passing an explicit (lse, delta) pair makes
    it a BLOCK-PAIR primitive for ring attention: fed the GLOBAL logsumexp
    and Δ of the q rows, the kernels rebuild the globally-normalized tile
    P = exp(q·kᵀ·scale − lse_global) directly, so each (q block, kv block)
    call yields that pair's exact contribution to the global gradients.
    Blocks None → :func:`flash_plan`'s."""
    S, Dqk = q.shape[1], q.shape[3]
    blk_q, blk_k = _tiles(S, Dqk + v.shape[3], block_q, block_k)
    scale = _scale(scale, Dqk)

    qt = q.transpose(0, 2, 1, 3)   # [B,H,S,Dqk]
    kt = k.transpose(0, 2, 1, 3)   # [B,KV,S,Dqk]
    vt = v.transpose(0, 2, 1, 3)   # [B,KV,S,Dv]
    dot = g.transpose(0, 2, 1, 3)  # [B,H,S,Dv]
    if delta is None:
        delta = _delta(out, g)
    args = (qt, dot, kt, vt, lse, delta)
    kw = dict(causal=causal, scale=scale, blk_q=blk_q, blk_k=blk_k,
              interpret=interpret)
    dkt, dvt = _bwd_dkv(*args, **kw)
    dqt = _bwd_dq(*args, **kw)
    return (dqt.transpose(0, 2, 1, 3), dkt.transpose(0, 2, 1, 3),
            dvt.transpose(0, 2, 1, 3))


def _scale(scale: float | None, dqk: int) -> float:
    return 1.0 / math.sqrt(dqk) if scale is None else float(scale)


def _dense_ref(q, k, v, causal, scale=None):
    """f32 dense attention — the parity oracle for tests."""
    B, S, H, Dqk = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, Dqk)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
    s = s * _scale(scale, Dqk)
    if causal:
        pos = jnp.arange(S)
        s = jnp.where((pos[:, None] >= pos[None, :])[None, None, None],
                      s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v)
    return out.reshape(B, S, H, v.shape[3])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, block_q: int | None = None,
                    block_k: int | None = None,
                    interpret: bool | None = None,
                    scale: float | None = None) -> jax.Array:
    """Flash attention. q [B,S,H,Dqk]; k [B,S,KV,Dqk]; v [B,S,KV,Dv]
    (GQA) → [B,S,H,Dv].

    interpret=None → :func:`interpret_mode` of the default backend;
    scale=None → 1/√Dqk; block_q / block_k None → :func:`flash_plan`'s
    tiles, a value → that tile; all three kernels take the same tiles.
    """
    if interpret is None:
        interpret = interpret_mode()
    out, _ = _flash_fwd(q, k, v, causal=causal, block_q=block_q,
                        block_k=block_k, interpret=interpret, scale=scale)
    return out


def _vjp_fwd(q, k, v, causal, block_q, block_k, interpret, scale):
    if interpret is None:
        interpret = interpret_mode()
    out, lse = _flash_fwd(q, k, v, causal=causal, block_q=block_q,
                          block_k=block_k, interpret=interpret, scale=scale)
    return out, (q, k, v, out, lse)


def _vjp_bwd(causal, block_q, block_k, interpret, scale, res, g):
    if interpret is None:
        interpret = interpret_mode()
    q, k, v, out, lse = res
    return _flash_bwd(q, k, v, out, lse, g, causal=causal, block_q=block_q,
                      block_k=block_k, interpret=interpret, scale=scale)


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)


def make_flash_attention(*, block_q: int | None = None,
                         block_k: int | None = None,
                         causal: bool = True, mesh=None,
                         scale: float | None = None):
    """An `attn_fn` for strom.models.llama.forward(..., attn_fn=...).

    With *mesh* (a jitted step over a device mesh), the kernel runs per
    device under ``shard_map``: XLA cannot partition a Mosaic kernel. Batch
    splits on "dp" and kv heads (with their query groups) on "tp", each
    where the mesh has the axis and it divides the size; otherwise that
    dimension is replicated. Without *mesh* the caller is already per
    device (inside a shard_map) or on one device."""

    def attn(q, k, v):
        return flash_attention(q, k, v, causal, block_q, block_k, None, scale)

    if mesh is None:
        return attn
    from jax.sharding import PartitionSpec as P

    def axis(name: str, size: int) -> str | None:
        n = mesh.shape.get(name)
        return name if n and size % n == 0 else None

    def sharded(q, k, v):
        spec = P(axis("dp", q.shape[0]), None, axis("tp", k.shape[2]), None)
        return jax.shard_map(attn, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec, check_vma=False)(q, k, v)

    return sharded
