"""The Pallas kernels of the main path, compiled by the TPU's compiler for a
described (not attached) v5e:2x2 topology, with ``interpret=False``.

Nothing runs: a compile that passes here says the chip's compiler accepts
the kernel at that shape, not that it is correct or fast. The topology is
described inside a module fixture (never at import time) so that only the
xdist worker given this file loads libtpu; the persistent compile cache is
off around these compiles, since entries for a described chip cannot be read
back without one.
"""

import functools
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from strom.ops.flash_attention import flash_attention

# (B, S, H, KV, Dh): Llama-3-8B attention, and the bench's `small` llama
SHAPES = {"llama3_8b": (1, 8192, 32, 8, 128), "small": (8, 2048, 12, 4, 64)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / no compiler: nothing to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _qkv(shape, sharding):
    B, S, H, KV, Dh = shape
    q = jax.ShapeDtypeStruct((B, S, H, Dh), jnp.bfloat16, sharding=sharding)
    kv = jax.ShapeDtypeStruct((B, S, KV, Dh), jnp.bfloat16, sharding=sharding)
    return q, kv, kv


def _fwd(q, k, v, block=128):
    return flash_attention(q, k, v, True, block, block, False)


def _bwd(q, k, v, block=128):
    return jax.grad(lambda *a: _fwd(*a, block).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("shape", list(SHAPES), ids=list(SHAPES))
@pytest.mark.parametrize("fn", [_fwd, _bwd], ids=["fwd", "bwd"])
def test_flash_compiles_for_v5e(one_chip, shape, fn):
    """At 128×128 tiles and at the plan's (blocks None)."""
    for block in (128, None):
        compiled = jax.jit(functools.partial(fn, block=block)).lower(
            *_qkv(SHAPES[shape], one_chip)).compile()
        assert "tpu_custom_call" in compiled.as_text(), block


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
def test_mla_flash_compiles_for_v5e(one_chip, grad):
    """DeepSeek-V2-Lite's attention at the benchmark cell's shape: 192-wide
    queries and keys, 128-wide values, YaRN's scale, batch 8 at 4,096; at
    128×128 tiles and at the plan's (blocks None), which must fit the
    chip's VMEM."""
    q = jax.ShapeDtypeStruct((8, 4096, 16, 192), jnp.bfloat16,
                             sharding=one_chip)
    v = jax.ShapeDtypeStruct((8, 4096, 16, 128), jnp.bfloat16,
                             sharding=one_chip)

    for block in (128, None):
        def fwd(q, k, v, block=block):
            return flash_attention(q, k, v, True, block, block, False, 0.1)

        fn = (lambda *a, fwd=fwd: jax.grad(
            lambda *b: fwd(*b).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(*a)) if grad else fwd
        text = jax.jit(fn).lower(q, q, v).compile().as_text()
        assert "tpu_custom_call" in text, block


@pytest.mark.parametrize("dqk,dv,dtype", [
    pytest.param(256, 256, jnp.bfloat16, id="256-256"),
    pytest.param(576, 512, jnp.bfloat16, id="576-512"),
    pytest.param(192, 128, jnp.float32, id="192-128-float32"),
    pytest.param(256, 256, jnp.float32, id="256-256-float32")])
def test_plan_tiles_fit_vmem_at_wider_heads(one_chip, dqk, dv, dtype):
    """Wider heads than the benchmark's take smaller tiles: the plan's pair
    must still fit the chip's VMEM in all three kernels (the forward runs
    inside the gradient), in float32 as in bf16."""
    q = jax.ShapeDtypeStruct((1, 4096, 2, dqk), dtype, sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 4096, 2, dv), dtype, sharding=one_chip)
    grad = jax.grad(lambda *a: flash_attention(*a, True, None, None, False)
                    .astype(jnp.float32).sum(), argnums=(0, 1, 2))
    text = jax.jit(grad).lower(q, q, v).compile().as_text()
    assert text.count("tpu_custom_call") >= 3


def test_held_experts_compile_for_v5e_without_a_row_per_pair(one_chip):
    """The expert layer at the benchmark cell's shape (32,768 tokens, top-6,
    8 of 64 experts held), forward and backward under ``jax.checkpoint`` as
    the step runs it: the chip's compiler takes the chunk loops around the
    grouped products, and no array has a row for each of the 196,608
    (token, expert) pairs: only the live chunks' rows are gathered. Nor does
    a chunk loop's body copy an expert weight into another layout: the
    transposed weights its backward reads are made once, outside."""
    from strom.models import deepseek_v2 as dv

    cfg = dv.DeepseekV2Config()
    N, d, f, k = 8 * 4096, cfg.d_model, cfg.expert_ff, cfg.top_k

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def loss(h, idx, weight, experts):
        layer = jax.checkpoint(
            lambda h, w, e: dv.held_experts(h, idx, w, e, cfg)[0])
        return jnp.sum(layer(h, weight, experts).astype(jnp.float32))

    experts = {"w_gate": shape(cfg.n_held, d, f),
               "w_up": shape(cfg.n_held, d, f),
               "w_down": shape(cfg.n_held, f, d)}
    text = jax.jit(jax.grad(loss, argnums=(0, 2, 3))).lower(
        shape(N, d), shape(N, k, dtype=jnp.int32),
        shape(N, k, dtype=jnp.float32), experts).compile().as_text()
    assert "ragged-dot" in text
    for width in (d, f):
        assert f"[{N * k},{width}]" not in text, width
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    weight = re.compile(rf"= bf16\[{cfg.n_held},({d},{f}|{f},{d})\]\S* "
                        r"(copy|transpose|fusion)\(")
    comp, copies = None, []
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$", line)
        if head and " = " not in line:
            comp = head.group(1)
        elif comp in bodies and weight.search(line):
            copies.append(line.strip()[:100])
    assert len(bodies) >= 2 and not copies, copies


def test_ring_flash_compiles_on_four_chips(topo):
    """ring x flash under shard_map, sp=4 over the described 2x2 mesh,
    forward and backward."""
    from strom.parallel.ring import make_ring_attention

    mesh = Mesh(np.array(topo.devices[:4]), ("sp",))
    ring = make_ring_attention(mesh, axis="sp", impl="flash",
                               interpret=False)
    sharding = NamedSharding(mesh, P(None, "sp", None, None))
    args = _qkv((1, 4096, 12, 4, 64), sharding)
    grad = jax.grad(lambda *a: ring(*a).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))
    text = jax.jit(grad).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text


def test_flash_on_dp_mesh_runs_per_device(topo, monkeypatch):
    """A Mosaic kernel cannot be partitioned by XLA: on a dp=4 mesh the
    train step's attention must run under shard_map (the chip's compiler
    refuses the unwrapped kernel)."""
    import importlib

    from strom.ops.flash_attention import make_flash_attention

    # the default backend here is the CPU: steer the kernel to Mosaic
    fa = importlib.import_module("strom.ops.flash_attention")
    monkeypatch.setattr(fa, "interpret_mode", lambda backend=None: False)
    mesh = Mesh(np.array(topo.devices[:4]), ("dp",))
    attn = make_flash_attention(mesh=mesh)
    args = _qkv(SHAPES["small"], NamedSharding(mesh, P("dp")))
    text = jax.jit(attn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("nbytes,local_shape", [
    (2 << 30, (2 << 30,)),           # chip_smoke's 2 GiB bulk delivery
    (512 << 20, (131072, 4096)),     # one shard of --four-chips' dp=4 read
])
def test_streamed_assembly_reshape_compiles(one_chip, nbytes, local_shape):
    """The streamed path's final reshape, from the buffer layout
    ``_assembly_shape`` picks to the delivered shape. The chip's compiler
    did not finish (4, 2^27) -> (131072, 4096) in minutes (PR 21)."""
    from strom.config import StromConfig
    from strom.delivery.core import _assembly_shape

    buf = jax.ShapeDtypeStruct(
        _assembly_shape(nbytes, StromConfig().overlap_chunk_bytes), jnp.uint8,
        sharding=one_chip)
    compiled = jax.jit(lambda b: b.reshape(local_shape),
                       donate_argnums=0).lower(buf).compile()
    if len(local_shape) == 1:  # 128-lane rows to 1-D: a bitcast, no copy
        assert compiled.memory_analysis().temp_size_in_bytes == 0
