"""DeepSeek-V2 decoder (DeepSeek-AI 2024, arXiv:2405.04434): multi-head latent
attention (MLA) and a fine-grained expert layer with shared experts, as one
expert-parallel rank holds it.

- **MLA.** Queries come straight from the hidden state (no query
  compression); keys and values from a rank-``kv_lora`` latent with its own
  RMSNorm. Each head's query and key are ``qk_nope + qk_rope`` wide, its value
  ``v_head`` wide; one ``qk_rope``-wide RoPE key is shared by every head. The
  core runs through the flash kernel (:mod:`strom.ops.flash_attention`) with
  its separate value width and the caller's softmax scale.
- **YaRN RoPE.** Frequencies blended between interpolated and original by
  a linear ramp over the correction range, and the softmax scale multiplied
  by ``mscale(factor, mscale_all_dim)²``.
- **The expert layer, as one rank's share.** Float32 router over all
  ``n_experts``, softmax, greedy top-k without renormalisation; the rank
  holds experts ``held_lo … held_lo + n_held - 1`` and computes their part
  of the result for every (token, held expert) pair: the pairs are sorted by
  expert, held ones first, and run through ``lax.ragged_dot``, so no pair is
  dropped and none is padded to a capacity. The sorted list is cut into
  chunks of :func:`chunk_rows` rows, and only the chunks up to the last held
  pair are gathered, computed and scattered back, forward and backward: the
  work follows the pairs this rank holds, not all ``top_k`` picks of every
  token. The shared experts (one SwiGLU of width ``n_shared × expert_ff``)
  run on every token once. What experts held on other ranks would add is
  left out: on one chip the layer has no exchange. The layer returns how
  many pairs each held expert computed and how many chunks it ran.
- **Loss.** Next-token cross-entropy over the held vocabulary rows, in
  token chunks so that only one chunk's logits exist at a time, plus
  ``aux_alpha`` × the sequence-wise balance loss of every expert layer,
  taken over all ``n_experts`` affinities.

Layer 0 (``n_dense_layers``) is a dense SwiGLU layer outside the scan; the
expert layers are stacked and scanned as :mod:`strom.models.llama` does.
Parameters are float32 masters; every block casts its weights to ``dtype``
(the router stays float32) inside its ``jax.checkpoint``, so only one
layer's low-precision copy is alive at a time.

``jax.named_scope`` names the parts the device trace attributes time to:
``mla``, ``moe.route``, ``moe.experts``, ``moe.shared``, ``lm_head``.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from strom.models.llama import rmsnorm, rope


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config:
    """Defaults: DeepSeek-V2-Lite at published widths as one rank of
    eight-way expert parallelism holds it (layer 0 and four expert layers,
    experts 0-7, an eighth of the vocabulary)."""

    vocab: int = 12_800               # rows held here (of vocab_published)
    d_model: int = 2048
    n_heads: int = 16
    qk_nope: int = 128
    qk_rope: int = 64
    v_head: int = 128
    kv_lora: int = 512
    dense_ff: int = 10_944
    n_dense_layers: int = 1
    n_moe_layers: int = 4
    n_experts: int = 64
    top_k: int = 6
    expert_ff: int = 1408
    n_shared: int = 2
    held_lo: int = 0
    n_held: int = 8
    routed_scaling: float = 1.0
    aux_alpha: float = 0.001
    rope_theta: float = 10_000.0
    yarn_factor: float = 40.0
    yarn_original_len: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 0.707
    yarn_mscale_all_dim: float = 0.707
    norm_eps: float = 1e-6
    init_std: float = 0.006
    loss_chunks: int = 8
    dtype: str = "bfloat16"

    @property
    def qk_head(self) -> int:
        return self.qk_nope + self.qk_rope

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.yarn_factor, self.yarn_mscale_all_dim)
        return self.qk_head ** -0.5 * m * m

    @classmethod
    def tiny(cls, **kw) -> "DeepseekV2Config":
        return cls(**{**TINY, **kw})


# Published ratios at CPU-test size: split query/key heads, a latent, top-3
# of 16 experts with 4 held, two shared experts.
TINY = dict(vocab=256, d_model=64, n_heads=4, qk_nope=16, qk_rope=8,
            v_head=16, kv_lora=32, dense_ff=160, n_moe_layers=2,
            n_experts=16, top_k=3, expert_ff=32, n_held=4,
            yarn_original_len=64, loss_chunks=2, init_std=0.05)


# -- YaRN ---------------------------------------------------------------------
def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _correction_dim(rotations: float, dim: int, base: float,
                    max_pos: int) -> float:
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))) \
        / (2 * math.log(base))


def yarn_inv_freq(cfg: DeepseekV2Config) -> jax.Array:
    """[qk_rope/2] frequencies: the original ones where a pair turns more
    than beta_fast times over the original context, the interpolated ones
    (÷ factor) below beta_slow turns, a linear ramp between."""
    dim, base = cfg.qk_rope, cfg.rope_theta
    expo = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extra = 1.0 / base ** expo
    inter = 1.0 / (cfg.yarn_factor * base ** expo)
    lo = max(math.floor(_correction_dim(cfg.yarn_beta_fast, dim, base,
                                        cfg.yarn_original_len)), 0)
    hi = min(math.ceil(_correction_dim(cfg.yarn_beta_slow, dim, base,
                                       cfg.yarn_original_len)), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo)
                    / (hi - lo), 0, 1)
    keep = 1.0 - ramp
    return inter * (1 - keep) + extra * keep


def yarn_cos_scale(cfg: DeepseekV2Config) -> float:
    """The factor on cos and sin: 1 when mscale equals mscale_all_dim."""
    return yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale) \
        / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)


# -- parameters ---------------------------------------------------------------
def param_shapes(cfg: DeepseekV2Config) -> dict:
    """Shape of every parameter; leaves named ``*norm`` start at one, the
    rest at N(0, init_std²). Expert layers stack on a leading axis; the
    routed experts' leaves hold only the ``n_held`` experts of this rank."""
    d, H = cfg.d_model, cfg.n_heads
    L, E = cfg.n_moe_layers, cfg.n_held
    shared = cfg.n_shared * cfg.expert_ff

    def attn(*lead):
        return {"attn_norm": (*lead, d),
                "wq": (*lead, d, H * cfg.qk_head),
                "wkv_a": (*lead, d, cfg.kv_lora + cfg.qk_rope),
                "kv_norm": (*lead, cfg.kv_lora),
                "wkv_b": (*lead, cfg.kv_lora, H * (cfg.qk_nope + cfg.v_head)),
                "wo": (*lead, H * cfg.v_head, d),
                "mlp_norm": (*lead, d)}

    dense = {**attn(), "w_gate": (d, cfg.dense_ff), "w_up": (d, cfg.dense_ff),
             "w_down": (cfg.dense_ff, d)}
    moe = {**attn(L), "router": (L, d, cfg.n_experts),
           "w_gate": (L, E, d, cfg.expert_ff), "w_up": (L, E, d, cfg.expert_ff),
           "w_down": (L, E, cfg.expert_ff, d),
           "shared_gate": (L, d, shared), "shared_up": (L, d, shared),
           "shared_down": (L, shared, d)}
    return {"embed": (cfg.vocab, d),
            "dense": [dict(dense) for _ in range(cfg.n_dense_layers)],
            "moe": moe, "final_norm": (d,), "lm_head": (d, cfg.vocab)}


def init_params(key: jax.Array, cfg: DeepseekV2Config) -> dict:
    """float32 parameters: one key per leaf, in the order ``jax.tree``
    flattens :func:`param_shapes` (sorted names)."""
    shapes = param_shapes(cfg)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(paths))
    leaves = []
    for (path, shape), k in zip(paths, keys):
        if str(getattr(path[-1], "key", "")).endswith("norm"):
            leaves.append(jnp.ones(shape, jnp.float32))
        else:
            leaves.append(jax.random.normal(k, shape, jnp.float32)
                          * cfg.init_std)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def param_count(cfg: DeepseekV2Config) -> int:
    return sum(math.prod(s) for s in jax.tree.leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


def _low(lp: dict, cfg: DeepseekV2Config) -> dict:
    """A layer's matrices in the compute dtype; norms and router float32."""
    return {k: v if k.endswith("norm") or k == "router"
            else v.astype(cfg.jdtype) for k, v in lp.items()}


# -- attention ----------------------------------------------------------------
def mla(x, lp, cfg: DeepseekV2Config, positions, attn_fn):
    """x + MLA(RMSNorm(x)); x [B, S, d]. *attn_fn(q, k, v)* is causal
    attention at ``cfg.softmax_scale``: the flash kernel."""
    B, S, _ = x.shape
    H = cfg.n_heads
    with jax.named_scope("mla"):
        h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        q = (h @ lp["wq"]).reshape(B, S, H, cfg.qk_head)
        q_nope, q_pe = q[..., :cfg.qk_nope], q[..., cfg.qk_nope:]
        kv_a = h @ lp["wkv_a"]
        latent = rmsnorm(kv_a[..., :cfg.kv_lora], lp["kv_norm"], cfg.norm_eps)
        k_pe = kv_a[..., cfg.kv_lora:][:, :, None, :]          # [B,S,1,rope]
        kv = (latent @ lp["wkv_b"]).reshape(B, S, H, cfg.qk_nope + cfg.v_head)
        k_nope, v = kv[..., :cfg.qk_nope], kv[..., cfg.qk_nope:]
        inv_freq, m = yarn_inv_freq(cfg), yarn_cos_scale(cfg)
        q_pe = rope(q_pe, positions, cfg.rope_theta, inv_freq) * m
        k_pe = rope(k_pe, positions, cfg.rope_theta, inv_freq) * m
        q = jnp.concatenate([q_nope, q_pe.astype(q.dtype)], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, (B, S, H, cfg.qk_rope))
             .astype(k_nope.dtype)], axis=-1)
        o = attn_fn(q, k, v)
        return x + o.reshape(B, S, H * cfg.v_head) @ lp["wo"]


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


# -- the expert layer ---------------------------------------------------------
def route(h, router, cfg: DeepseekV2Config, batch: int):
    """h [N, d] → (top-k expert ids [N, k], their weights [N, k] float32,
    seq-aux loss). Gate logits in float32 over all n_experts."""
    with jax.named_scope("moe.route"):
        logits = jnp.dot(h.astype(jnp.float32), router,
                         precision=lax.Precision.HIGHEST)
        scores = jax.nn.softmax(logits, axis=-1)                 # [N, E]
        weight, idx = lax.top_k(scores, cfg.top_k)
        weight = weight * cfg.routed_scaling
        E, k = cfg.n_experts, cfg.top_k
        S = h.shape[0] // batch
        # sequence-wise balance: per sequence, each expert's share of the
        # top-k picks (scaled so uniform is 1) times its mean affinity
        seq = jnp.repeat(jnp.arange(batch), S * k)
        picks = jnp.zeros((batch, E), jnp.float32).at[
            seq, idx.reshape(-1)].add(1.0) / (S * k / E)
        mean_score = jnp.mean(scores.reshape(batch, S, E), axis=1)
        aux = jnp.mean(jnp.sum(picks * mean_score, axis=1))
    return idx, weight, aux


def chunk_rows(pairs: int, cfg: DeepseekV2Config) -> int:
    """Rows per chunk of the sorted pair list: the power of two nearest to
    a sixth of the held pairs a uniform router gives (*pairs* × n_held /
    n_experts), at most *pairs*; all *pairs* in one chunk where every
    expert is held, since then no chunk can be skipped and each would only
    add its fixed cost (its own float32 gradient of every held expert's
    weights). 4,096 at DeepSeek-V2-Lite's 8 of 64 held and 32,768 tokens;
    16 at the tiny config's 288 pairs with 4 of 16 held."""
    if cfg.n_held == cfg.n_experts:
        return pairs
    share = pairs * cfg.n_held / cfg.n_experts / 6
    return min(1 << round(math.log2(max(share, 1.0))), pairs)


def _grouped(x, w, sizes, valid):
    # The TPU's grouped product leaves the rows past the last group
    # unwritten, in its output and in its input's gradient: device memory
    # as it was, NaN at times. Every product's input and output pass
    # through a select, so that nothing from those rows reaches a value or,
    # through the selects' transposes, a gradient (a multiply by zero would
    # keep a NaN).
    x = jnp.where(valid, x, 0)
    return jnp.where(valid, lax.ragged_dot(x, w, sizes), 0)


def _swiglu_gate(g, u, scale):
    # the gate weight *scale* multiplies the input of the (linear) down
    # projection
    return jax.nn.silu(g) * u * scale[:, None].astype(g.dtype)


def _expert_ffn(rows, scale, w_gate, w_up, w_down, sizes, valid):
    """Each row's SwiGLU through its group's expert, times its gate
    weight."""
    act = _swiglu_gate(_grouped(rows, w_gate, sizes, valid),
                       _grouped(rows, w_up, sizes, valid), scale)
    return _grouped(act, w_down, sizes, valid)


# per group g: x[rows of g]ᵀ · dy[rows of g], the grouped product's weight
# gradient ([groups, x's width, dy's width])
_PER_GROUP = lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _expert_ffn_vjp(rows, scale, w, w_t, sizes, valid, dout):
    """:func:`_expert_ffn` recomputed and pulled back by hand: the rows' and
    gate weights' gradients, and the three weights' in float32 (*w*: gate,
    up, down; *w_t*: the same with their last two axes swapped, made once
    outside the chunk loop, so that the rows' gradient products read a
    weight in the layout they need without copying it every chunk)."""
    def grouped(x, w_):
        return jnp.where(valid, lax.ragged_dot(x, w_, sizes), 0)

    def per_group(x, dy):
        return lax.ragged_dot_general(x, dy, sizes, _PER_GROUP,
                                      preferred_element_type=jnp.float32)

    x = jnp.where(valid, rows, 0)
    act, pull = jax.vjp(_swiglu_gate, grouped(x, w[0]), grouped(x, w[1]),
                        scale)
    dout = jnp.where(valid, dout, 0)
    dg, du, dscale = pull(grouped(dout, w_t[2]))
    dg, du = jnp.where(valid, dg, 0), jnp.where(valid, du, 0)
    drows = jnp.where(valid, lax.ragged_dot(dg, w_t[0], sizes)
                      + lax.ragged_dot(du, w_t[1], sizes), 0)
    return drows, dscale, (per_group(x, dg), per_group(x, du),
                           per_group(jnp.where(valid, act, 0), dout))


def _chunk(c, order, sizes, k: int, C: int):
    """Chunk *c* of the sorted pair list: its pair ids [C], their tokens,
    each group's rows inside the chunk [n_held], and which rows lie inside
    a group [C, 1] (only the chunk that holds the last held pair has rows
    past it)."""
    c0 = c * C
    pairs = lax.dynamic_slice_in_dim(order, c0, C)
    ends = jnp.cumsum(sizes)
    local = jnp.clip(ends, c0, c0 + C) - jnp.clip(ends - sizes, c0, c0 + C)
    valid = (c0 + jnp.arange(C) < ends[-1])[:, None]
    return pairs, pairs // k, local, valid


def _live_chunks(sizes, C: int):
    return (jnp.sum(sizes) + C - 1) // C


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _dispatch(h, weight, w_gate, w_up, w_down, order, sizes, k, C):
    return _dispatch_fwd(h, weight, w_gate, w_up, w_down, order, sizes, k,
                         C)[0]


def _dispatch_fwd(h, weight, w_gate, w_up, w_down, order, sizes, k, C):
    """The live chunks' outputs scatter-added by token into a float32
    [N, d]; chunks past the last held pair are never entered."""
    with jax.named_scope("moe.experts"):
        def body(c, y):
            pairs, tok, local, valid = _chunk(c, order, sizes, k, C)
            out = _expert_ffn(h[tok], weight[pairs], w_gate, w_up, w_down,
                              local, valid)
            return y.at[tok].add(out.astype(jnp.float32))

        y = lax.fori_loop(0, _live_chunks(sizes, C), body,
                          jnp.zeros(h.shape, jnp.float32))
    return y, (h, weight, w_gate, w_up, w_down, order, sizes)


def _dispatch_bwd(k, C, res, dy):
    """The live chunks again, each recomputed and pulled back: the rows'
    gradient scatter-added by token, and the held weights' summed over the
    chunks, both in float32. A chunk adds to the weights' gradient only the
    experts it holds rows of (a group's gradient with no rows is not read:
    the grouped product need not write it), so a chunk costs its rows and
    its experts, not every held expert."""
    h, weight, w_gate, w_up, w_down, order, sizes = res
    ends = jnp.cumsum(sizes)
    with jax.named_scope("moe.experts"):
        w = (w_gate, w_up, w_down)
        w_t = tuple(jnp.swapaxes(a, 1, 2) for a in w)

        def body(c, acc):
            dh, dweight, *dw = acc
            pairs, tok, local, valid = _chunk(c, order, sizes, k, C)
            drows, dscale, dchunk = _expert_ffn_vjp(
                h[tok], weight[pairs], w, w_t, local, valid,
                dy[tok].astype(h.dtype))

            def add(g, dw):
                return tuple(lax.dynamic_update_index_in_dim(
                    a, a[g] + jnp.where(local[g] > 0, b[g], 0), g, 0)
                    for a, b in zip(dw, dchunk))

            # the experts with rows in [c·C, c·C + C): a contiguous range
            dw = lax.fori_loop(jnp.sum(ends <= c * C),
                               jnp.sum(ends - sizes < (c + 1) * C), add,
                               tuple(dw))
            return (dh.at[tok].add(drows.astype(jnp.float32)),
                    dweight.at[pairs].add(dscale), *dw)

        zero = [jnp.zeros(a.shape, jnp.float32)
                for a in (h, weight, *w)]
        dh, dweight, *dw = lax.fori_loop(0, _live_chunks(sizes, C), body,
                                         tuple(zero))
        return (dh.astype(h.dtype), dweight.astype(weight.dtype),
                *(g.astype(a.dtype) for g, a in zip(dw, w)), None, None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def held_experts(h, idx, weight, lp, cfg: DeepseekV2Config):
    """The held experts' part of the routed output: h [N, d] → ([N, d],
    pairs computed per held expert [n_held] int32, chunks computed int32).
    Every (token, expert) pair whose expert is held is computed. The pairs
    are sorted by expert, held ones first, and cut into chunks of
    :func:`chunk_rows` rows; only the chunks up to the last held pair are
    gathered, computed through ``ragged_dot`` and scattered back to their
    tokens, forward and backward. The rest of the list does no work."""
    N, k = idx.shape
    C = chunk_rows(N * k, cfg)
    with jax.named_scope("moe.experts"):
        local = idx.reshape(-1) - cfg.held_lo
        group = jnp.where((local >= 0) & (local < cfg.n_held), local,
                          cfg.n_held)
        order = jnp.argsort(group, stable=True)
        order = jnp.pad(order, (0, -(N * k) % C))
        sizes = jnp.sum(group[:, None] == jnp.arange(cfg.n_held), axis=0,
                        dtype=jnp.int32)
        y = _dispatch(h, weight.reshape(-1), lp["w_gate"], lp["w_up"],
                      lp["w_down"], order, sizes, k, C)
        return y.astype(h.dtype), sizes, _live_chunks(sizes, C)


def moe_block(x, lp, cfg: DeepseekV2Config, positions, attn_fn):
    """One expert layer: (x', seq-aux loss, pairs per held expert, chunks
    computed)."""
    lp = _low(lp, cfg)
    x = mla(x, lp, cfg, positions, attn_fn)
    B, S, d = x.shape
    h = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps).reshape(B * S, d)
    idx, weight, aux = route(h, lp["router"], cfg, B)
    routed, sizes, chunks = held_experts(h, idx, weight, lp, cfg)
    with jax.named_scope("moe.shared"):
        shared = swiglu(h, lp["shared_gate"], lp["shared_up"],
                        lp["shared_down"])
    return x + (routed + shared).reshape(B, S, d), aux, sizes, chunks


def dense_block(x, lp, cfg: DeepseekV2Config, positions, attn_fn):
    lp = _low(lp, cfg)
    x = mla(x, lp, cfg, positions, attn_fn)
    h = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
    return x + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])


# -- model and loss -----------------------------------------------------------
def hidden(params, inputs, cfg: DeepseekV2Config, attn_fn,
           remat: bool = False):
    """inputs [B, S] → (final-normed hidden [B, S, d], seq-aux [L],
    pairs per held expert [L, n_held], chunks computed [L])."""
    B, S = inputs.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = params["embed"][inputs].astype(cfg.jdtype)
    dense = jax.checkpoint(dense_block, static_argnums=(2, 4)) if remat \
        else dense_block
    moe = jax.checkpoint(moe_block, static_argnums=(2, 4)) if remat \
        else moe_block
    for lp in params["dense"]:
        x = dense(x, lp, cfg, positions, attn_fn)

    def body(carry, lp):
        y, *per_layer = moe(carry, lp, cfg, positions, attn_fn)
        return y, per_layer

    x, (aux, sizes, chunks) = lax.scan(body, x, params["moe"])
    return rmsnorm(x, params["final_norm"], cfg.norm_eps), aux, sizes, chunks


def forward(params, inputs, cfg: DeepseekV2Config, attn_fn):
    """inputs [B, S] → logits [B, S, vocab] float32 (tests; the loss never
    holds all logits at once)."""
    x = hidden(params, inputs, cfg, attn_fn)[0]
    with jax.named_scope("lm_head"):
        return jnp.dot(x, params["lm_head"].astype(cfg.jdtype),
                       preferred_element_type=jnp.float32)


def _xent_sum(x, head, targets, chunks: int):
    """Σ over tokens of logsumexp − gold logit, *chunks* tokens blocks at a
    time under ``jax.checkpoint``."""
    N, d = x.shape
    xs = x.reshape(chunks, N // chunks, d)
    ts = targets.reshape(chunks, N // chunks)

    @jax.checkpoint
    def one(total, xt):
        xi, ti = xt
        logits = jnp.dot(xi, head, preferred_element_type=jnp.float32)
        gold = jnp.take_along_axis(logits, ti[:, None], axis=-1)[:, 0]
        return total + jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold), None

    total, _ = lax.scan(one, jnp.zeros((), jnp.float32), (xs, ts))
    return total


def next_token_loss(params, tokens, cfg: DeepseekV2Config, attn_fn,
                    remat: bool = False):
    """tokens [B, S+1] (a record: inputs and the next token) → (mean
    cross-entropy + aux_alpha × Σ seq-aux, {"expert_assignments":
    [n_moe_layers, n_held] int32, "expert_chunks": [n_moe_layers] int32})."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    B, S = inputs.shape
    x, aux, sizes, live = hidden(params, inputs, cfg, attn_fn, remat)
    with jax.named_scope("lm_head"):
        chunks = math.gcd(cfg.loss_chunks, B * S)
        ce = _xent_sum(x.reshape(B * S, -1),
                       params["lm_head"].astype(cfg.jdtype),
                       targets.reshape(-1), chunks) / (B * S)
    return ce + cfg.aux_alpha * jnp.sum(aux), {"expert_assignments": sizes,
                                               "expert_chunks": live}
