#!/usr/bin/env python
"""Time each flash kernel alone on a TPU over tile pairs, at one shape.

Usage: python3 tools/flash_sweep.py [--tiles 256,512,1024] [--iters 5]
           [--plain 128x128,1024x1024] [--split 1024x1024]
           [--out sweep.jsonl]

Each arm runs one kernel (``flash_fwd``, ``flash_bwd_dkv``, ``flash_bwd_dq``)
through ``_fwd`` / ``_bwd_dkv`` / ``_bwd_dq`` of ``strom.ops.flash_attention``
on head-major random bf16 inputs, causal, and prints one JSON line: the
median milliseconds of ``--iters`` calls (host clock around
``block_until_ready``, so a call reads a few ms above the kernel's device
time), the share of the compute roofline at the benchmark's count (useful
causal work at 128-row granularity, 197 TFLOP/s), and the largest absolute
difference from the kernel's first arm, the 128×128 tiles with plain maps.

Arms, per kernel: 128×128 with plain maps; every pair of ``--tiles`` as the
module builds it; the pairs of ``--plain`` with plain index maps (every grid
step reads its own block, so masked steps copy, as before the maps repeated
a block there); the pairs of ``--split`` with two kernel bodies, one that
masks elementwise on tiles the diagonal crosses and one without the mask on
tiles wholly below it (the module masks every tile that runs). The defaults
are the DeepSeek-V2-Lite cell's attention: B 8, S 4,096, H 16, Dqk 192,
Dv 128.
"""

from __future__ import annotations

import argparse
import importlib
import itertools
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402

# the module, not the function `strom.ops` re-exports under the same name
fa = importlib.import_module("strom.ops.flash_attention")

KERNELS = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")
PEAK_FLOPS = 197e12  # TPU v5e, bf16


def _pairs(text: str) -> list[tuple[int, int]]:
    return [tuple(int(x) for x in t.split("x")) for t in text.split(",") if t]


def _plain(i, j, **_):
    return j


def _plain_q(i, j, **_):
    return i


def _split_mask(body, *, causal, iq, jk, blk_q, blk_k):
    if not causal:
        body(False)
        return
    live = jk * blk_k <= iq * blk_q + blk_q - 1
    below = jk * blk_k + blk_k - 1 <= iq * blk_q
    pl.when(below)(lambda: body(False))
    pl.when(jnp.logical_and(live, jnp.logical_not(below)))(lambda: body(True))


def _variant(name: str):
    """Swap the module's index maps or tile dispatch for one arm; returns a
    function that puts the module's own back."""
    saved = fa._kv_block, fa._q_block, fa._on_tile
    if name == "plain":
        fa._kv_block, fa._q_block = _plain, _plain_q
    elif name == "split":
        fa._on_tile = _split_mask

    def restore():
        fa._kv_block, fa._q_block, fa._on_tile = saved
    return restore


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--dqk", type=int, default=192)
    ap.add_argument("--dv", type=int, default=128)
    ap.add_argument("--scale", type=float, default=0.1147)
    ap.add_argument("--tiles", default="256,512,1024",
                    help="tile sizes; every (blk_q, blk_k) pair of them runs")
    ap.add_argument("--plain", default="512x512,1024x512,512x1024,1024x1024",
                    help="blk_qxblk_k pairs also run with plain index maps")
    ap.add_argument("--split", default="512x512,1024x1024",
                    help="blk_qxblk_k pairs also run with the split bodies")
    ap.add_argument("--kernels", default=",".join(KERNELS))
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--out", default=None, help="also append lines here")
    a = ap.parse_args(argv)

    B, S, H, Dqk, Dv = a.batch, a.seq, a.heads, a.dqk, a.dv
    interpret = fa.interpret_mode()
    rng = np.random.default_rng(0)

    def arr(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.bfloat16)

    qt, kt, vt = arr(B, H, S, Dqk), arr(B, H, S, Dqk), arr(B, H, S, Dv)
    dot = arr(B, H, S, Dv)
    blk = min(512, S)
    o, lse = jax.jit(lambda q, k, v: fa._fwd(
        q, k, v, causal=True, scale=a.scale, blk_q=blk, blk_k=blk,
        interpret=interpret))(qt, kt, vt)
    delta = jnp.sum(dot.astype(jnp.float32) * o.astype(jnp.float32), -1,
                    keepdims=True)

    n = S // 128
    pairs = B * H * n * (n + 1) // 2 * 128 * 128
    flops = {"flash_fwd": pairs * 2 * (Dqk + Dv),
             "flash_bwd_dkv": pairs * 2 * (2 * Dqk + 2 * Dv),
             "flash_bwd_dq": pairs * 2 * (2 * Dqk + Dv)}
    calls = {"flash_fwd": (fa._fwd, (qt, kt, vt)),
             "flash_bwd_dkv": (fa._bwd_dkv, (qt, dot, kt, vt, lse, delta)),
             "flash_bwd_dq": (fa._bwd_dq, (qt, dot, kt, vt, lse, delta))}

    kernels = a.kernels.split(",")
    tiles = [int(t) for t in a.tiles.split(",")]
    arms = [(k, 128, 128, "plain") for k in kernels]
    arms += [(k, 128, 128, "module") for k in kernels]
    arms += [(k, bq, bk, "module") for k in kernels
             for bq, bk in itertools.product(tiles, tiles)]
    arms += [(k, bq, bk, "plain") for k in kernels
             for bq, bk in _pairs(a.plain) if (bq, bk) != (128, 128)]
    arms += [(k, bq, bk, "split") for k in kernels
             for bq, bk in _pairs(a.split)]

    ref = {}
    out = open(a.out, "a") if a.out else None
    for kernel, bq, bk, variant in arms:
        fn, args = calls[kernel]
        rec = dict(kernel=kernel, blk_q=bq, blk_k=bk, variant=variant,
                   shape=[B, S, H, Dqk, Dv])
        restore = _variant(variant)
        try:
            t0 = time.perf_counter()
            compiled = jax.jit(lambda *x: fn(
                *x, causal=True, scale=a.scale, blk_q=bq, blk_k=bk,
                interpret=interpret)).lower(*args).compile()
            rec["compile_s"] = round(time.perf_counter() - t0, 2)
            got = jax.block_until_ready(compiled(*args))
            got = [np.asarray(x, np.float32)
                   for x in (got if isinstance(got, (list, tuple)) else [got])]
            want = ref.setdefault(kernel, got)
            rec["max_abs_diff"] = max(float(np.max(np.abs(x - y)))
                                      for x, y in zip(got, want))
            rec["ref_max_abs"] = max(float(np.max(np.abs(y))) for y in want)
            ts = []
            for _ in range(a.iters):
                t = time.perf_counter()
                jax.block_until_ready(compiled(*args))
                ts.append(time.perf_counter() - t)
            ms = statistics.median(ts) * 1e3
            rec.update(ms=ms, ms_all=[x * 1e3 for x in ts],
                       roofline_pct=100 * flops[kernel] / PEAK_FLOPS / (ms / 1e3))
        except Exception as e:  # a refused tile is a result: record it
            rec["error"] = f"{type(e).__name__}: {str(e)[:1500]}"
        finally:
            restore()
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
