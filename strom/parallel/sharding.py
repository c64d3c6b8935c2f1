"""Sharding rules for the flagship model: Megatron-style tensor parallelism
expressed as PartitionSpecs; XLA inserts the ICI collectives (scaling-book
recipe: pick a mesh, annotate shardings, let the compiler do the rest)."""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Per-leaf PartitionSpecs for strom.models.llama params. The stacked layers'
# LEADING axis carries "pp" (pipeline stages each hold n_layers/pp layers);
# on meshes without a pp axis, param_shardings' restrict() degrades it to
# replicated, so non-pipeline steps are unaffected. Column-parallel (output
# dim on tp) feeding row-parallel (input dim on tp) pairs keep activations
# tp-local between the two matmuls; XLA adds the reduce-scatter/all-reduce
# at the end.
_LLAMA_RULES = {
    ("embed",): P(None, "tp"),
    ("layers", "attn_norm"): P("pp"),
    ("layers", "wq"): P("pp", None, "tp"),
    ("layers", "wk"): P("pp", None, "tp"),
    ("layers", "wv"): P("pp", None, "tp"),
    ("layers", "wo"): P("pp", "tp", None),
    ("layers", "mlp_norm"): P("pp"),
    ("layers", "w_gate"): P("pp", None, "tp"),
    ("layers", "w_up"): P("pp", None, "tp"),
    ("layers", "w_down"): P("pp", "tp", None),
    ("final_norm",): P(),
    ("lm_head",): P(None, "tp"),
}


def _path_key(path) -> tuple:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(p.key)
        elif hasattr(p, "idx"):
            out.append(p.idx)
    return tuple(out)


def param_specs(params: dict) -> dict:
    """PartitionSpec pytree matching the llama param pytree."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: _LLAMA_RULES.get(_path_key(path), P()), params)


def _restricted(specs: dict, mesh: Mesh) -> dict:
    """Specs restricted to the mesh's axes: a rule axis the mesh doesn't have
    (e.g. tp on a dp×sp mesh) degrades to replicated on that dim."""

    def restrict(spec: P) -> P:
        return P(*(ax if ax in mesh.axis_names else None for ax in spec))

    return jax.tree.map(lambda spec: NamedSharding(mesh, restrict(spec)),
                        specs, is_leaf=lambda x: isinstance(x, P))


def param_shardings(params: dict, mesh: Mesh) -> dict:
    return _restricted(param_specs(params), mesh)


# MoE variant: attention rules shared with llama; expert FFN stacks carry the
# ep axis on the expert dim (tokens all-to-all into expert shards is XLA's to
# place), tp on the hidden dim within each expert.
_MOE_RULES = {
    **_LLAMA_RULES,
    ("layers", "router"): P("pp"),
    ("layers", "w_gate"): P("pp", "ep", None, "tp"),
    ("layers", "w_up"): P("pp", "ep", None, "tp"),
    ("layers", "w_down"): P("pp", "ep", "tp", None),
}


def moe_param_specs(params: dict) -> dict:
    return jax.tree_util.tree_map_with_path(
        lambda path, _: _MOE_RULES.get(_path_key(path), P()), params)


def moe_param_shardings(params: dict, mesh: Mesh) -> dict:
    return _restricted(moe_param_specs(params), mesh)


# DeepSeek-V2 (strom.models.deepseek_v2): the same column/row pairs on tp;
# stacked expert layers carry "pp" on their leading axis. The routed experts
# are this rank's held share already (expert parallelism is the choice of
# held experts, not a sharded axis), so their expert dim stays whole.
_DSV2_COLUMN = {"wq", "wkv_b", "w_gate", "w_up", "shared_gate", "shared_up"}
_DSV2_ROW = {"wo", "w_down", "shared_down"}


def deepseek_v2_param_specs(params: dict) -> dict:
    def spec(path, leaf) -> P:
        key = _path_key(path)
        if key[0] in ("embed", "lm_head"):
            return P(None, "tp")
        name = key[-1]
        lead = ["pp"] if key[0] == "moe" else []
        body = [None] * (leaf.ndim - len(lead))
        if name in _DSV2_COLUMN:
            body[-1] = "tp"
        elif name in _DSV2_ROW:
            body[-2] = "tp"
        return P(*lead, *body)

    return jax.tree_util.tree_map_with_path(spec, params)


def deepseek_v2_param_shardings(params: dict, mesh: Mesh) -> dict:
    return _restricted(deepseek_v2_param_specs(params), mesh)


def batch_spec(*, sp: bool = False) -> P:
    """Token batches: batch on dp, optionally sequence on sp (long-context
    loaders deliver sequence-sharded batches, SURVEY.md §5)."""
    return P("dp", "sp") if sp else P("dp")
