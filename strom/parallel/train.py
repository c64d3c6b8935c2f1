"""Sharded training steps (pjit over a dp×tp mesh): one function,
:func:`make_step`, makes them for the Llama, Switch-MoE and DeepSeek-V2
models.

This is the consumer the data path feeds: strom loaders deliver token batches
already sharded over ("dp", ...) and the step runs under jit with explicit
parameter shardings — XLA places the ICI collectives (psum of dp gradients,
tp all-reduces) itself.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from strom.models.llama import LlamaConfig, init_params, next_token_loss
from strom.parallel.sharding import param_shardings


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: Any


def make_optimizer(lr: float = 3e-4, weight_decay: float = 0.1,
                   warmup: int = 100) -> optax.GradientTransformation:
    sched = optax.warmup_cosine_decay_schedule(0.0, lr, warmup, 10_000, lr * 0.1)
    return optax.chain(optax.clip_by_global_norm(1.0),
                       optax.adamw(sched, weight_decay=weight_decay))


def _state_shardings(key: jax.Array, init_fn, shardings_fn, mesh: Mesh,
                     optimizer: optax.GradientTransformation) -> TrainState:
    """The sharding of every leaf of the train state: the parameters'
    from *shardings_fn*, the same for the optimizer's param-shaped leaves,
    replicated scalars. The initializer creates the state with these and
    the step gives them back, so its first call compiles the only
    signature it will see."""
    shapes = jax.eval_shape(init_fn, key)
    params = shardings_fn(shapes, mesh)
    replicated = NamedSharding(mesh, P())
    opt_state = optax.tree_map_params(
        optimizer, lambda _, s: s, jax.eval_shape(optimizer.init, shapes),
        params, transform_non_params=lambda _: replicated)
    return TrainState(params=params, opt_state=opt_state, step=replicated)


def _init_state(key: jax.Array, init_fn, shardings_fn, mesh: Mesh,
                optimizer: optax.GradientTransformation) -> TrainState:
    """Jit the initializers with out_shardings so big models never
    materialise unsharded on one device."""
    sh = _state_shardings(key, init_fn, shardings_fn, mesh, optimizer)
    params = jax.jit(init_fn, out_shardings=sh.params)(key)
    opt_state = jax.jit(optimizer.init, out_shardings=sh.opt_state)(params)
    return TrainState(params=params, opt_state=opt_state,
                      step=jax.device_put(jnp.zeros((), jnp.int32), sh.step))


def init_train_state(key: jax.Array, cfg: LlamaConfig, mesh: Mesh,
                     optimizer: optax.GradientTransformation) -> TrainState:
    return _init_state(key, partial(init_params, cfg=cfg), param_shardings,
                       mesh, optimizer)


def _attn_fn(attn: str, sp: bool, mesh: Mesh):
    """The attention a step's model runs: None (the model's dense op),
    the Pallas flash kernel, or with sp=True the ring over the "sp" axis.

    attn="flash": the Pallas flash-attention kernel (blockwise forward AND
    backward, O(S) memory — strom.ops.flash_attention) replaces the dense op
    in every layer. This is the default TPU training path for long sequences;
    "dense" remains for short-sequence parity and debugging.

    sp=True: activations stay sequence-sharded and attention runs the ring
    algorithm (kv blocks rotate over ICI neighbor hops) instead of letting
    XLA all-gather the whole sequence — peak memory O(S/n_sp) per device.
    With attn="flash" the Pallas kernels run INSIDE the ring — each ring step
    is a real flash block (fwd + blockwise bwd), merged by logsumexp."""
    if attn not in ("dense", "flash", "zigzag"):
        raise ValueError(
            f"attn must be 'dense', 'flash' or 'zigzag', got {attn!r}")
    if attn == "zigzag" and not sp:
        raise ValueError("attn='zigzag' is the load-balanced causal RING; "
                         "it needs sp=True")
    if sp:
        from strom.parallel.ring import make_ring_attention

        return make_ring_attention(mesh, axis="sp", impl=attn)
    if attn == "flash":
        from strom.ops.flash_attention import make_flash_attention

        return make_flash_attention(mesh=mesh)
    return None


def make_step(loss_fn, init_fn, shardings_fn, mesh: Mesh,
              optimizer: optax.GradientTransformation, *,
              batch_sharding: NamedSharding, donate: bool = True):
    """Compile a (state, tokens) -> (state, metrics) step for any model.

    ``loss_fn(params, tokens) -> (loss, extra)``: *extra* is a dict of
    further metrics the step returns beside ``loss`` and ``grad_norm``.
    ``init_fn(key) -> params`` and ``shardings_fn(params, mesh)`` fix the
    state's shardings, which the step gives back, so its first call compiles
    the only signature it will see. tokens arrive with *batch_sharding* —
    exactly the sharding strom.pipelines loaders deliver — so no resharding
    happens on entry."""

    def step(state: TrainState, tokens: jax.Array):
        (loss, extra), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, tokens)
        updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        metrics = {"loss": loss, "grad_norm": optax.global_norm(grads), **extra}
        return TrainState(new_params, new_opt, state.step + 1), metrics

    state_shardings = _state_shardings(jax.random.key(0), init_fn,
                                       shardings_fn, mesh, optimizer)
    donate_argnums = (0,) if donate else ()
    return jax.jit(step, in_shardings=(None, batch_sharding),
                   out_shardings=(state_shardings, NamedSharding(mesh, P())),
                   donate_argnums=donate_argnums)


def _token_sharding(mesh: Mesh, sp: bool) -> NamedSharding:
    return NamedSharding(mesh, P("dp", "sp") if sp else P("dp", None))


def make_train_step(cfg: LlamaConfig, mesh: Mesh,
                    optimizer: optax.GradientTransformation, *,
                    attn: str = "dense", sp: bool = False, donate: bool = True):
    """The Llama step (:func:`make_step`); tokens P("dp"[, "sp"]), attention
    as :func:`_attn_fn` picks it."""
    attn_fn = _attn_fn(attn, sp, mesh)

    def loss_fn(params, tokens):
        return next_token_loss(params, tokens, cfg, attn_fn=attn_fn,
                               remat=True), {}

    return make_step(loss_fn, partial(init_params, cfg=cfg), param_shardings,
                     mesh, optimizer, batch_sharding=_token_sharding(mesh, sp),
                     donate=donate)


def init_moe_train_state(key: jax.Array, cfg, mesh: Mesh,
                         optimizer: optax.GradientTransformation) -> TrainState:
    """MoE variant: expert stacks sharded over the mesh's ep axis."""
    from strom.models import moe
    from strom.parallel.sharding import moe_param_shardings

    return _init_state(key, partial(moe.init_params, cfg=cfg),
                       moe_param_shardings, mesh, optimizer)


def make_moe_train_step(cfg, mesh: Mesh,
                        optimizer: optax.GradientTransformation, *,
                        attn: str = "dense", sp: bool = False,
                        donate: bool = True):
    """(state, tokens) -> (state, metrics) for the MoE model: tokens arrive
    P("dp"[, "sp"]); expert weights stay ep-sharded and XLA places the token
    all-to-alls the dispatch einsums imply."""
    from strom.models import moe
    from strom.parallel.sharding import moe_param_shardings

    attn_fn = _attn_fn(attn, sp, mesh)

    def loss_fn(params, tokens):
        return moe.next_token_loss(params, tokens, cfg, attn_fn=attn_fn,
                                   remat=True), {}

    return make_step(loss_fn, partial(moe.init_params, cfg=cfg),
                     moe_param_shardings, mesh, optimizer,
                     batch_sharding=_token_sharding(mesh, sp), donate=donate)


def init_deepseek_v2_train_state(key: jax.Array, cfg, mesh: Mesh,
                                 optimizer: optax.GradientTransformation
                                 ) -> TrainState:
    from strom.models import deepseek_v2
    from strom.parallel.sharding import deepseek_v2_param_shardings

    return _init_state(key, partial(deepseek_v2.init_params, cfg=cfg),
                       deepseek_v2_param_shardings, mesh, optimizer)


def make_deepseek_v2_train_step(cfg, mesh: Mesh,
                                optimizer: optax.GradientTransformation, *,
                                donate: bool = True):
    """The DeepSeek-V2 step: tokens [B, S+1] P("dp") from
    ``make_llama_pipeline``, float32 masters, bfloat16 compute, per-layer
    remat, MLA through the flash kernel at the model's softmax scale.
    Metrics: ``loss``, ``grad_norm``, ``expert_assignments`` ([expert
    layers, held experts] int32: the (token, held expert) pairs computed)
    and ``expert_chunks`` ([expert layers] int32: the chunks of the sorted
    pair list each layer computed, ⌈held pairs / ``chunk_rows``⌉)."""
    from strom.models import deepseek_v2
    from strom.ops.flash_attention import make_flash_attention
    from strom.parallel.sharding import deepseek_v2_param_shardings

    attn_fn = make_flash_attention(mesh=mesh, scale=cfg.softmax_scale)

    def loss_fn(params, tokens):
        return deepseek_v2.next_token_loss(params, tokens, cfg,
                                           attn_fn=attn_fn, remat=True)

    return make_step(loss_fn, partial(deepseek_v2.init_params, cfg=cfg),
                     deepseek_v2_param_shardings, mesh, optimizer,
                     batch_sharding=_token_sharding(mesh, False),
                     donate=donate)


jax.tree_util.register_dataclass(TrainState,
                                 data_fields=["params", "opt_state", "step"],
                                 meta_fields=[])
