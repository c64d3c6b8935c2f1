"""DeepSeek-V2-Lite pretraining fed by strom's token pipeline: the consumer
the windows drive, and the plain reference that decides ``correct``.

The program under test is strom's normal path: ``make_llama_pipeline``
gathers 4,097-token int32 records from packed shards into HBM, and the step
from ``strom.parallel.train.make_deepseek_v2_train_step`` trains
``strom.models.deepseek_v2`` on them (MLA through the flash kernel, the held
experts through ``ragged_dot``), AdamW on float32 masters, bfloat16 compute.

The reference (``ref_deepseek_v2.py`` beside this file, which the tier-1
tests load too) imports nothing of strom. It draws its own
weights from the seed, reads the records from the shard files with numpy in
the seeded epoch order, and runs the model in float32 at HIGHEST precision,
one sequence at a time with the gradients accumulated, attention over
512-row query blocks and each layer recomputed in the backward, so that it
fits after the program's state is freed; then AdamW as published.

The corpus (kind ``tokens``, registered into the harness's generator from
here) is Zipf(1.0) token ids over the held vocabulary, concatenated and cut
into fixed windows: concatenate-and-chunk packing.

On a CPU device (the benchmark's own tests; ``run.py`` refuses one) the cell
runs at :data:`CPU_SIZES` (the model at ``strom.models.deepseek_v2.TINY``)
with :data:`CPU_LIMITS`.
"""

from __future__ import annotations

import collections
import functools
import math
import os
import re
import time

import numpy as np

from benchmark.harness import generate
from benchmark.harness.generate import philox
from benchmark.harness.loops import step_metrics, train_window

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKED_STEPS = 3
WINDOW_BATCHES_CHECKED = 3       # drawn from the seed, plus the last one
REF_Q_BLOCK = 512
FLASH_BLOCK = 128
# The CPU tests' sizes besides the model's (the program's tiny model, every
# ratio of the published one kept, widths cut to what the Pallas interpreter
# runs quickly).
CPU_SIZES = {"dataset": dict(records=32, record_tokens=129, shards=2,
                             vocab=256),
             "batch": 2, "seq_len": 128, "q_block": 64}
# At the CPU sizes (calibrate_dsv2lite.py, seeds 3, 2**31+9, 2**31+11) sound
# runs read first-loss gaps <= 0.0001, median-leaf gradient gaps <= 0.00042,
# change gaps <= 0.00023, median-leaf gradient differences <= 0.0065 and
# worst-leaf ones <= 0.0112; the float8 control >= 0.00015, 0.00227, 0.00175,
# 0.077, 0.143; half the batch >= 0.002, 0.0149, 0.0203, 0.416, 0.612. The
# chip's limits are in workloads/dsv2lite_tokens_s4096_b8.json.
CPU_LIMITS = {"batch_values_off": 0, "loss_gap_first": 0.001,
              "grad_gap_median": 0.0015, "change_gap_median": 0.001,
              "grad_diff_median": 0.025, "grad_diff": 0.04}


def _ref():
    """The plain reference beside this file, loaded by path."""
    import importlib.util
    import sys

    name = "bench_ref_deepseek_v2"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(HERE, "ref_deepseek_v2.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return sys.modules[name]


# -- the corpus ---------------------------------------------------------------
def _tokens(out: str, p: dict) -> dict:
    """*shards* files of packed int32 token windows, ``records`` in all:
    Zipf(s) ids over ``vocab`` (id = rank − 1), one Philox stream per
    shard."""
    n, shards, rec = int(p["records"]), int(p["shards"]), \
        int(p["record_tokens"])
    ranks = np.arange(1, int(p["vocab"]) + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -float(p["zipf_s"]))
    cdf /= cdf[-1]
    per = n // shards
    paths = []
    for s in range(shards):
        u = philox(int(p["data_seed"]), s).random(per * rec)
        ids = np.minimum(np.searchsorted(cdf, u, side="right"),
                         len(cdf) - 1).astype(np.int32)
        path = os.path.join(out, f"shard-{s:04d}.bin")
        ids.tofile(path)
        with open(path, "rb+") as f:
            os.fsync(f.fileno())
        paths.append(path)
    return {"paths": paths, "bytes": per * shards * rec * 4,
            "records": per * shards}


generate.KINDS["tokens"] = _tokens


def batch_indices(seed: int, serial: int, n: int, batch: int) -> np.ndarray:
    """The record indices of global batch *serial*: epoch-wise Philox(seed,
    epoch) permutations, whole batches only."""
    epoch, i = divmod(serial, n // batch)
    return philox(seed, epoch).permutation(n)[i * batch:(i + 1) * batch]


class Corpus:
    """The reference's own read of the shard files."""

    def __init__(self, manifest: dict, record_tokens: int):
        self.records = np.concatenate([
            np.memmap(p, np.int32, "r").reshape(-1, record_tokens)
            for p in manifest["paths"]])

    def batch(self, seed: int, serial: int, batch: int) -> np.ndarray:
        return np.asarray(self.records[batch_indices(
            seed, serial, len(self.records), batch)])


# -- sizes and counts ---------------------------------------------------------
def model_fields(config: dict, cpu: bool) -> dict:
    """DeepseekV2Config's fields from the configuration file."""
    rs = config["rope_scaling"]
    f = dict(vocab=config["vocab_size"], d_model=config["hidden_size"],
             n_heads=config["num_attention_heads"],
             qk_nope=config["qk_nope_head_dim"],
             qk_rope=config["qk_rope_head_dim"],
             v_head=config["v_head_dim"], kv_lora=config["kv_lora_rank"],
             dense_ff=config["intermediate_size"],
             n_dense_layers=config["first_k_dense_replace"],
             n_moe_layers=config["layers"] - config["first_k_dense_replace"],
             n_experts=config["n_routed_experts"],
             top_k=config["num_experts_per_tok"],
             expert_ff=config["moe_intermediate_size"],
             n_shared=config["n_shared_experts"],
             held_lo=config["routed_experts_held_lo"],
             n_held=config["routed_experts_held"],
             routed_scaling=float(config["routed_scaling_factor"]),
             aux_alpha=config["aux_loss_alpha"],
             rope_theta=float(config["rope_theta"]),
             yarn_factor=float(rs["factor"]),
             yarn_original_len=rs["original_max_position_embeddings"],
             yarn_beta_fast=float(rs["beta_fast"]),
             yarn_beta_slow=float(rs["beta_slow"]),
             yarn_mscale=rs["mscale"], yarn_mscale_all_dim=rs["mscale_all_dim"],
             norm_eps=config["rms_norm_eps"], init_std=config["init_std"],
             dtype=config["compute_dtype"])
    if cpu:
        from strom.models.deepseek_v2 import TINY

        f.update(TINY)
    return f


def model_tuple(fields: dict):
    """The fields as the reference and the counts read them: a hashable
    record (a static argument of jitted calls), no strom class."""
    return collections.namedtuple("Model", sorted(fields))(**fields)


def forward_flops_per_token(c, seq: int) -> float:
    """Multiply-adds × 2 of one token's forward pass, from the shapes:
    projections, causal attention (a query attends (seq + 1) / 2 keys on
    average), the dense layer, the router, the shared experts, the held
    experts at top_k × n_held / n_experts pairs a token, and the head."""
    d, H = c.d_model, c.n_heads
    proj = 2 * d * (H * (c.qk_nope + c.qk_rope) + c.kv_lora + c.qk_rope) \
        + 2 * c.kv_lora * H * (c.qk_nope + c.v_head) + 2 * H * c.v_head * d
    attn = (seq + 1) * H * (c.qk_nope + c.qk_rope + c.v_head)
    dense = 3 * 2 * d * c.dense_ff
    moe = 2 * d * c.n_experts + 3 * 2 * d * c.n_shared * c.expert_ff \
        + c.top_k * c.n_held / c.n_experts * 3 * 2 * d * c.expert_ff
    layers = c.n_dense_layers + c.n_moe_layers
    return layers * (proj + attn) + c.n_dense_layers * dense \
        + c.n_moe_layers * moe + 2 * d * c.vocab


def train_flops_per_sample(c, seq: int) -> float:
    """Forward and backward of one sequence: three forward passes; the
    recompute is not counted."""
    return 3 * forward_flops_per_token(c, seq) * seq


def flash_counts(batch: int, seq: int, heads: int, dqk: int, dv: int,
                 blk: int = FLASH_BLOCK) -> dict:
    """{kernel: (FLOPs, bytes)} of one call of each flash kernel at causal
    masking: every block on or below the diagonal is computed whole, none
    above it. Forward: q·kᵀ and p·v; dkv: the recompute of P, dO·vᵀ, Pᵀ·dO
    and dSᵀ·q; dq: the recompute of P, dO·vᵀ and dS·k. Bytes (bf16, lse
    float32): q, k, v, o and lse to the forward, dO, dk and dv to dkv, dq to
    dq: each tensor moved once."""
    n = seq // blk
    pairs = batch * heads * n * (n + 1) // 2 * blk * blk
    rows = batch * seq * heads
    return {"flash_fwd": (pairs * 2 * (dqk + dv),
                          rows * (2 * dqk * 2 + 2 * dv * 2 + 4)),
            "flash_bwd_dkv": (pairs * 2 * (2 * dqk + 2 * dv),
                              rows * (2 * dv * 2 + dqk * 2)),
            "flash_bwd_dq": (pairs * 2 * (2 * dqk + dv), rows * dqk * 2)}


def expert_counts(pairs: int, steps: int, c) -> tuple[float, float]:
    """(FLOPs, bytes) of the held experts' products over *steps* steps in
    which *pairs* (token, held expert) pairs were computed: three products
    of 2·d·f each, forward and backward (three times the forward); bytes
    (bf16): each step's held weights read forward and backward and their
    gradients written, and every routed row in and out, forward and
    backward."""
    d, f = c.d_model, c.expert_ff
    flops = pairs * 3 * 2 * d * f * 3
    weights = steps * c.n_moe_layers * 3 * c.n_held * d * f * 2 * 3
    rows = pairs * d * 2 * 4
    return flops, weights + rows


def roofline(flops: float, nbytes: float, seconds: float, peaks: dict
             ) -> float | None:
    """Percent of *seconds* that the least time for *flops* and *nbytes*
    at the chip's peaks fills; None when nothing ran."""
    if seconds <= 0:
        return None
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds


# -- reading the trace --------------------------------------------------------
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*'
                    r'op_name="([^"]*)"')


def scope_map(hlo_text: str) -> dict[str, str]:
    """HLO instruction name → its op_name (the jax scope path)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def classify(op_name: str) -> str | None:
    for kind in ("flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"):
        if kind in op_name:
            return kind
    # the TPU's grouped products are custom calls named for themselves
    # ("ragged-dot-none", "ragged-dot-metadata"), not for the scope they were
    # called in; only the held experts call ragged_dot
    if "moe.experts" in op_name or op_name.startswith("ragged-dot"):
        return "moe.experts"
    return None


def kernel_times(trace_dir: str, platform: str, module: str,
                 scopes: dict[str, str]) -> tuple[dict, dict, dict]:
    """Device time per kind (flash kernels, ``moe.experts`` ops) of the
    step module's operations inside the traced window, as the union of their
    intervals (the CPU nests an operation inside its loop's); the number of
    events per kind; the matched operation names with their times."""
    from jax.profiler import ProfileData

    from benchmark.harness.trace_reduce import (WINDOW_SPAN, find_xplane,
                                                host_spans, union)

    pd = ProfileData.from_file(find_xplane(trace_dir))
    lo, hi = [(a, b) for n, a, b in host_spans(pd) if n == WINDOW_SPAN][0]
    spans, count = collections.defaultdict(list), collections.Counter()
    names: collections.Counter = collections.Counter()
    for plane in pd.planes:
        if platform == "tpu":
            if not re.fullmatch(r"/device:TPU:\d+", plane.name):
                continue
            lines = [ln for ln in plane.lines if ln.name == "XLA Ops"]
            mods = [(e.start_ns, e.start_ns + e.duration_ns)
                    for ln in plane.lines if ln.name == "XLA Modules"
                    for e in ln.events if e.name.startswith(module)]
        else:
            if plane.name != "/host:CPU":
                continue
            lines = [ln for ln in plane.lines if ln.name.startswith("tf_XLA")]
            mods = []
        for ln in lines:
            for e in ln.events:
                a, b = e.start_ns, e.start_ns + e.duration_ns
                if a < lo or b > hi or e.duration_ns <= 0:
                    continue
                stats = dict(e.stats)
                if stats.get("hlo_module", module) != module or (
                        platform != "tpu" and "hlo_op" not in stats):
                    continue
                if mods and not any(x <= a and b <= y for x, y in mods):
                    continue
                name = e.name.split(" = ", 1)[0].lstrip("%")
                kind = classify(scopes.get(name, name))
                if kind:
                    spans[kind].append((a, b))
                    count[kind] += 1
                    names[name] += (b - a) / 1e9
    seconds = {k: sum(b - a for a, b in union(v, lo, hi)) / 1e9
               for k, v in spans.items()}
    return seconds, count, names


# -- the program's step -------------------------------------------------------
def make_optimizer(opt: dict):
    """AdamW after global-norm clipping, at a constant rate."""
    import optax

    return optax.chain(optax.clip_by_global_norm(opt["clip"]),
                       optax.adamw(opt["lr"], b1=opt["b1"], b2=opt["b2"],
                                   weight_decay=opt["weight_decay"]))


def build_program(fields: dict, opt_cfg: dict, mesh, q_block: int):
    """(init(key) -> state, step(state, tokens) -> (state, metrics)) from
    strom.parallel.train, for DeepseekV2Config(**fields)."""
    import jax

    from strom.models.deepseek_v2 import DeepseekV2Config
    from strom.parallel.train import (init_deepseek_v2_train_state,
                                      make_deepseek_v2_train_step)

    cfg = DeepseekV2Config(**fields)
    opt = make_optimizer(opt_cfg)
    step = make_deepseek_v2_train_step(cfg, mesh, opt)

    def init(key):
        return init_deepseek_v2_train_state(key, cfg, mesh, opt)

    return init, step


def fp8_quantizer():
    """The control's lower precision, as float8 training takes it: every
    linear layer's operands rounded to e4m3 on the way in, and their
    gradients to e5m2 on the way back, each with a per-tensor scale."""
    import jax
    import jax.numpy as jnp

    def qdq(x, dtype, top):
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
        return (x / s).astype(dtype).astype(jnp.float32) * s

    @jax.custom_vjp
    def q(x):
        return qdq(x, jnp.float8_e4m3fn, 448.0)

    q.defvjp(lambda x: (q(x), None),
             lambda _, g: (qdq(g, jnp.float8_e5m2, 57344.0),))
    return q


def build_control(fields: dict, opt_cfg: dict, mesh, q_block: int):
    """The control, in :func:`build_program`'s place and with its interface:
    the reference's loss at float8 (:func:`fp8_quantizer`, the precision
    below the configuration's bfloat16), one sequence at a time, with the
    program's optimizer."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from strom.parallel.train import TrainState

    ref = _ref()
    c = model_tuple(fields)
    opt = make_optimizer(opt_cfg)
    quant = fp8_quantizer()
    rep = NamedSharding(mesh, P())

    def init(key):
        params = jax.jit(ref.init_params, static_argnums=1,
                         out_shardings=rep)(key, c)
        return TrainState(params, jax.jit(opt.init)(params),
                          jnp.zeros((), jnp.int32))

    def step(state, tokens):
        def one(acc, record):
            (loss, counts), g = jax.value_and_grad(
                ref.sequence_loss, has_aux=True)(
                    state.params, record, c, quant, q_block, True)
            return jax.tree.map(jnp.add, acc, (loss, g, counts)), None

        zero = (jnp.zeros(()), jax.tree.map(jnp.zeros_like, state.params),
                jnp.zeros((c.n_moe_layers, c.n_held), jnp.int32))
        (loss, grads, counts), _ = jax.lax.scan(one, zero, tokens)
        n = tokens.shape[0]
        grads = jax.tree.map(lambda g: g / n, grads)
        updates, opt_state = opt.update(grads, state.opt_state, state.params)
        return TrainState(optax.apply_updates(state.params, updates),
                          opt_state, state.step + 1), {
            "loss": loss / n, "grad_norm": optax.global_norm(grads),
            "expert_assignments": counts}

    return init, jax.jit(step, donate_argnums=(0,))


def first_moment(opt_state):
    """AdamW's first moment (optax ``ScaleByAdamState.mu``)."""
    import jax

    for leaf in jax.tree.leaves(opt_state,
                                is_leaf=lambda x: hasattr(x, "mu")):
        if hasattr(leaf, "mu"):
            return leaf.mu
    raise ValueError("no first moment in the optimizer state")


def host(tree) -> list[np.ndarray]:
    import jax

    return [np.asarray(x, np.float32) for x in jax.tree.leaves(tree)]


# -- the plain reference's steps ----------------------------------------------
def build_reference(c, opt: dict, q_block: int):
    """ref_step(params, m, v, t, tokens) -> (loss, clipped grads, params',
    m', v', pairs per held expert): the reference's loss one record at a
    time, gradients averaged, then AdamW as optax takes it: clip to global
    norm, m = b1·m + (1−b1)·g, v = b2·v + (1−b2)·g², update
    lr·(m̂/(√v̂ + 1e-8) + wd·p)."""
    import jax
    import jax.numpy as jnp

    ref = _ref()
    seq_grad = jax.jit(jax.value_and_grad(
        lambda p, r: ref.sequence_loss(p, r, c, None, q_block, True),
        has_aux=True))
    add = jax.jit(lambda a, g: jax.tree.map(jnp.add, a, g),
                  donate_argnums=(0,))

    # in place: the reference's weights, summed gradients and moments are
    # 2.1 GB each at published widths, and a copy of all four does not fit
    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def adamw(params, total, m, v, t, n):
        grads = jax.tree.map(lambda g: g / n, total)
        norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        scale = jnp.where(norm < opt["clip"], 1.0, opt["clip"] / norm)
        grads = jax.tree.map(lambda g: g * scale, grads)
        b1, b2 = opt["b1"], opt["b2"]
        m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)

        def upd(p, m_, v_):
            mh, vh = m_ / (1 - b1 ** t), v_ / (1 - b2 ** t)
            return p - opt["lr"] * (mh / (jnp.sqrt(vh) + 1e-8)
                                    + opt["weight_decay"] * p)

        return grads, jax.tree.map(upd, params, m, v), m, v

    def ref_step(params, m, v, t, tokens):
        acc, counts, loss = None, 0, 0.0
        for record in tokens:
            (lv, n), g = seq_grad(params, jnp.asarray(record))
            acc = g if acc is None else add(acc, g)
            counts, loss = counts + np.asarray(n), loss + float(lv)
        grads, params, m, v = adamw(params, acc, m, v, t, len(tokens))
        return loss / len(tokens), grads, params, m, v, counts

    return ref_step


def reference_numbers(ref_step, params, batches) -> dict:
    import jax
    import jax.numpy as jnp

    p0 = host(params)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad1, counts1 = [], None, None
    for t, tokens in enumerate(batches, 1):
        loss, grads, params, m, v, counts = ref_step(params, m, v, t, tokens)
        losses.append(loss)
        if grad1 is None:
            grad1, counts1 = host(grads), counts
        del grads
    change = [a - b for a, b in zip(host(params), p0)]
    return {"losses": losses, "grad1": grad1, "change3": change,
            "counts1": counts1}


def compare_steps(prog: dict, ref: dict) -> dict:
    """As the ResNet cells compare: the first loss's relative gap; for the
    first gradient and the three-step change, per leaf over the larger of
    ‖ref‖ and the median leaf's ‖ref‖, the gap of the norms and the norm of
    the difference (median leaf, and worst). Leaves whose reference gradient
    is under a thousandth of the median non-zero leaf's are left out."""
    def norms(xs):
        return np.array([float(np.linalg.norm(x.astype(np.float64)))
                         for x in xs])

    rn = norms(ref["grad1"])
    keep = rn >= 1e-3 * np.median(rn[rn > 0])
    out = {"loss_gap_first": abs(prog["losses"][0] - ref["losses"][0])
           / abs(ref["losses"][0]),
           "loss_gap": max(abs(a - b) / abs(b) for a, b in
                           zip(prog["losses"], ref["losses"])),
           "leaves_left_out": int((~keep).sum())}
    for name, key in (("grad", "grad1"), ("change", "change3")):
        pn, rn_ = norms(prog[key]), norms(ref[key])
        dn = norms([a - b for a, b in zip(prog[key], ref[key])])
        scale = np.maximum(rn_, float(np.median(rn_[keep])))
        gap, diff = (np.abs(pn - rn_) / scale)[keep], (dn / scale)[keep]
        out.update({f"{name}_gap": float(gap.max()),
                    f"{name}_gap_median": float(np.median(gap)),
                    f"{name}_diff": float(diff.max()),
                    f"{name}_diff_median": float(np.median(diff))})
    return out


# -- the cell -----------------------------------------------------------------
class Cell:
    def __init__(self, run):
        # a checkout without the model or its step stops here, at once,
        # before the corpus is made or a weight is drawn
        from strom.models.deepseek_v2 import DeepseekV2Config  # noqa: F401
        from strom.parallel.train import make_deepseek_v2_train_step  # noqa: F401

        self.run = run
        self.cpu = run.devices[0].platform == "cpu"
        self.fields = model_fields(run.config, self.cpu)
        self.c = model_tuple(self.fields)
        self.opt = run.config["optimizer"]
        self.batch = (CPU_SIZES["batch"] if self.cpu
                      else run.traffic["batch_per_chip"]) * run.chips
        self.seq = CPU_SIZES["seq_len"] if self.cpu else run.traffic["seq_len"]
        self.q_block = CPU_SIZES["q_block"] if self.cpu else REF_Q_BLOCK
        if self.cpu:
            run.limits = dict(CPU_LIMITS)

    def setup(self) -> None:
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        import strom
        from strom.config import StromConfig
        from strom.delivery.core import StromContext
        from strom.pipelines import make_llama_pipeline

        run = self.run
        t0 = time.perf_counter()
        ds = dict(run.config["dataset"], **(CPU_SIZES["dataset"]
                                            if self.cpu else {}))
        self.manifest = run.ensure_data("tokens", ds)
        paths = self.manifest["paths"]
        self.corpus = Corpus(self.manifest, ds["record_tokens"])
        self.mesh = Mesh(np.array(run.devices), ("dp",))
        self.ctx = StromContext(StromConfig(**run.traffic.get("strom", {})))
        rep = strom.check_file(paths[0])
        run.log(f"engine: auto chose {self.ctx.engine.name}; check_file "
                f"tier={rep.tier.value} fs={rep.fs_type}")
        self.pipe = make_llama_pipeline(
            self.ctx, paths, batch=self.batch, seq_len=self.seq,
            sharding=NamedSharding(self.mesh, P("dp", None)), seed=run.seed)
        run.log(f"setup: pipeline built at {time.perf_counter() - t0:.3f} s")
        init, step = build_program(self.fields, self.opt, self.mesh,
                                   self.q_block)
        key = jax.random.key(run.seed % (1 << 63))
        state = init(key)
        self.params0 = host(state.params)
        run.log(f"setup: weights made at {time.perf_counter() - t0:.3f} s")

        tokens = next(self.pipe)
        self.step = step.lower(state, tokens).compile() \
            if hasattr(step, "lower") else step
        hlo = self.step.as_text() if hasattr(self.step, "as_text") else ""
        self.module = hlo.split(",", 1)[0].replace("HloModule ", "").strip()
        self.scopes = scope_map(hlo)
        run.log(f"setup: step compiled at {time.perf_counter() - t0:.3f} s "
                f"(module {self.module or '-'})")
        losses, self.checked, counts = [], [], []
        for k in range(CHECKED_STEPS):
            if k:
                tokens = next(self.pipe)
            self.checked.append((k, np.asarray(tokens)))
            state, m = self.step(state, tokens)
            losses.append(float(m["loss"]))
            counts.append(np.asarray(m["expert_assignments"]))
            if k == 0:
                grad1 = [x / (1 - self.opt["b1"])
                         for x in host(first_moment(state.opt_state))]
        change = [a - b for a, b in zip(host(state.params), self.params0)]
        self.state = state
        self.numbers = {"losses": losses, "grad1": grad1, "change3": change,
                        "counts1": counts[0]}
        run.log(f"setup: checked steps' losses {losses}; held pairs per "
                f"expert layer {[c.sum(axis=1).tolist() for c in counts]} at "
                f"{time.perf_counter() - t0:.3f} s")

    def _loop(self, seconds: float, on_batch=None) -> tuple[dict, list]:
        state = [self.state]
        self.state = None
        pairs = []

        def step(batch):
            state[0], m = self.step(state[0], batch)
            pairs.append(m["expert_assignments"])
            return m["loss"]

        win = train_window(lambda: next(self.pipe), step, seconds, on_batch)
        self.state = state[0]
        return win, [np.asarray(p) for p in pairs]

    def window(self) -> None:
        import jax

        run = self.run
        drawn = philox(run.seed, 7).choice(np.arange(1, 40),
                                           WINDOW_BATCHES_CHECKED,
                                           replace=False)
        keep = {0, *map(int, drawn)}
        self.kept: dict[int, np.ndarray] = {}
        last = [None]

        def on_batch(i, batch):
            if i in keep:
                self.kept[CHECKED_STEPS + i] = batch
            last[0] = (CHECKED_STEPS + i, batch)

        win, pairs = self._loop(run.seconds, on_batch)
        m = step_metrics(win, self.batch)
        loss = float(win["last_loss"])
        run.attempted = m["steps"]
        run.failed = 0 if math.isfinite(loss) else 1
        run.e2e.update(train_samples_per_s=m["train_samples_per_s"],
                       train_step_p95_ms=m["train_step_p95_ms"])
        run.layer.update(window_s=win["t1"] - win["t0"],
                         ingest_wait_s=win["ingest_wait_s"],
                         samples_per_s=m["train_samples_per_s"],
                         flops_per_sample=train_flops_per_sample(self.c,
                                                                 self.seq))
        self.kept[last[0][0]] = last[0][1]
        total = np.sum(pairs, axis=0)
        run.log(f"window: {m['steps']} steps of {self.batch} sequences in "
                f"{win['t1'] - win['t0']:.3f} s, median step "
                f"{m['step_median_ms']:.3f} ms, p95 "
                f"{m['train_step_p95_ms']:.3f} ms, longest "
                f"{m['step_max_ms']:.3f} ms, {m['slow_steps']} steps over "
                f"twice the median, last loss {loss}, ingest wait "
                f"{win['ingest_wait_s']:.3f} s")
        run.log("window: held pairs per step per expert layer "
                f"{(total.sum(axis=1) / len(pairs)).tolist()}; busiest "
                f"expert over the mean {float(total.max() / total.mean()):.3f}")
        self.kept = {s: np.asarray(b) for s, b in sorted(self.kept.items())}
        jax.block_until_ready(self.state)

    def trace_window(self, seconds: float) -> None:
        """More of the same loop, for the profiler; nothing in it is
        measured on the host clock or compared."""
        import jax

        win, pairs = self._loop(seconds)
        self.trace_steps = len(pairs)
        self.trace_pairs = int(np.sum(pairs))
        self.run.log(f"traced window: {len(win['completions'])} steps in "
                     f"{win['t1'] - win['t0']:.3f} s, {self.trace_pairs} held "
                     f"pairs")
        jax.block_until_ready(self.state)

    def release(self) -> None:
        """Free the program's state and loader before the reference runs."""
        self.state = None
        self.step = None
        self.pipe.close()
        self.ctx.close()

    def read_rooflines(self) -> None:
        run = self.run
        seconds, events, names = kernel_times(
            run.trace_dir, run.devices[0].platform, self.module, self.scopes)
        c = self.c
        layers = c.n_dense_layers + c.n_moe_layers
        # per step and layer: the forward, its recompute under remat, and
        # one of each backward kernel
        calls = {"flash_fwd": 2 * layers, "flash_bwd_dkv": layers,
                 "flash_bwd_dq": layers}
        calls = {k: n * self.trace_steps for k, n in calls.items()}
        run.log(f"trace: kernel time {seconds} s; calls {calls}; events "
                f"{dict(events)}; top matched ops {names.most_common(12)}")
        # a declared metric whose operations the trace does not hold is a
        # reader that no longer matches the program: fail, do not go silent
        declared = {m["name"] for m, _ in run.parts.per_layer}
        kinds = {"flash_attention_roofline": list(calls),
                 "moe_experts_roofline": ["moe.experts"]}
        empty = [k for metric, ks in kinds.items() if metric in declared
                 for k in ks if not events.get(k)]
        if empty:
            raise RuntimeError(f"trace: no device events for {empty} in the "
                               f"traced window of {self.trace_steps} steps")
        if run.devices[0].platform == "tpu" and any(
                events[k] != calls[k] for k in calls):
            raise RuntimeError(f"trace: flash kernel events {dict(events)} "
                               f"differ from the calls {calls}")
        per = flash_counts(self.batch // run.chips, self.seq, c.n_heads,
                           c.qk_nope + c.qk_rope, c.v_head)
        flops = sum(calls[k] * per[k][0] for k in per)
        nbytes = sum(calls[k] * per[k][1] for k in per)
        share = roofline(flops, nbytes, sum(seconds.get(k, 0.0) for k in per),
                         run.peaks)
        if share is not None:
            run.layer["flash_attention_roofline"] = share
        flops, nbytes = expert_counts(self.trace_pairs, self.trace_steps, c)
        share = roofline(flops, nbytes, seconds.get("moe.experts", 0.0),
                         run.peaks)
        if share is not None:
            run.layer["moe_experts_roofline"] = share

    def check(self) -> None:
        import jax

        run = self.run
        if run.trace and run.peaks:
            self.read_rooflines()
        t = time.perf_counter()
        got = [(k, b) for k, b in self.checked] + list(self.kept.items())
        ref_batches = {s: self.corpus.batch(run.seed, s, self.batch)
                       for s, _ in got}
        off = sum(int(np.count_nonzero(b != ref_batches[s])) for s, b in got)
        run.check("batch_values_off", off, run.limits["batch_values_off"])
        ref = _ref()
        params = jax.jit(ref.init_params, static_argnums=1)(
            jax.random.key(run.seed % (1 << 63)), self.c)
        run.log("reference: its own weights from the seed differ from the "
                "program's by at most " + repr(max(
                    float(np.max(np.abs(a - b))) for a, b in
                    zip(host(params), self.params0))))
        self.params0 = None
        with jax.default_matmul_precision("highest"):
            numbers = reference_numbers(
                build_reference(self.c, self.opt, self.q_block), params,
                [ref_batches[k] for k, _ in self.checked])
        gaps = compare_steps(self.numbers, numbers)
        flips = int(np.abs(self.numbers["counts1"]
                           - numbers["counts1"]).sum()) // 2
        run.layer["gaps"] = gaps
        run.log(f"reference: every number {gaps}")
        run.log(f"reference: losses {numbers['losses']}; route_flips (held "
                f"pairs that moved in the first step, a lower bound; "
                f"informative only) {flips}; {len(got)} batches compared; "
                f"{gaps['leaves_left_out']} leaves left out; "
                f"{time.perf_counter() - t:.1f} s")
        for name, limit in run.limits.items():
            if name in gaps:
                run.check(name, gaps[name], limit)
