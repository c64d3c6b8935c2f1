#!/usr/bin/env python
"""Split the DeepSeek-V2-Lite step's held-expert device time by operation
type, on a TPU.

Usage: python3 tools/expert_profile.py [--repo PATH] [--seconds 8]
           [--seed N] [--layer-held 64] [--out profile.jsonl]

The step arm sets up the benchmark cell ``dsv2lite_tokens_s4096_b8`` through
the benchmark's own harness and configuration module (its pipeline, token
shards, step and checked steps; no reference), runs the cell's loop for
``--seconds`` untraced and as long again under the profiler, and prints one
JSON line:

- ``step_median_ms``: the untraced loop's median step (host clock);
- ``experts_ms``: the device time a step of the operations the benchmark's
  ``moe_experts_roofline`` reads (``kernel_times``' ``moe.experts``), and
  ``by_type``: the same operations' summed durations a step, by type (sort,
  gather, scatter, the ``ragged-dot`` calls, copies and transposes,
  elementwise and select fusions, and the loops that enclose the others);
  ``top``: the largest of them;
- ``busy_ms``: the device's busy time a step (``trace_reduce``);
- ``held_pairs`` and ``expert_chunks``: per expert layer, from one more
  step's metrics (``expert_chunks`` where the program returns it);
- ``peak_bytes``: the device's peak memory after the arm.

Each ``--layer-held`` value adds an arm that times the layer alone
(``held_experts`` forward and backward under ``jax.checkpoint``, as the step
runs it, at the cell's 32,768 tokens and widths) with that many of the 64
experts held: 64 makes every pair held. ``--repo`` runs another checkout's
``strom`` and ``benchmark`` (one process imports one ``strom``); the corpus
is this checkout's ``benchmark/_data``.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import statistics
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
CELL = "dsv2lite_tokens_s4096_b8"
TOKENS = 8 * 4096               # the cell's tokens a step


def hlo_types(hlo: str) -> dict[str, str]:
    """Instruction name → operation type, for every instruction of the
    compiled module: a fusion is typed by the operations it fuses."""
    comps: dict[str, set] = collections.defaultdict(set)
    instrs: dict[str, tuple[str, str | None, str]] = {}
    comp = None
    for line in hlo.splitlines():
        if " = " not in line:
            m = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$", line)
            if m:
                comp = m.group(1)
            continue
        m = re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*? ([a-z][a-z0-9\-]*)\(",
                     line)
        if not m:
            continue
        name, op = m.groups()
        calls = re.search(r"calls=%?([\w.\-]+)", line)
        comps[comp].add(op)
        instrs[name] = (op, calls.group(1) if calls else None, line)
    out = {}
    for name, (op, calls, line) in instrs.items():
        inside = {op} | comps.get(calls, set())
        if op == "custom-call":
            out[name] = "ragged_dot" if "ragged" in line else "custom_call"
        elif op in ("while", "conditional", "call"):
            out[name] = "loop"
        elif "sort" in inside:
            out[name] = "sort"
        elif "scatter" in inside:
            out[name] = "scatter"
        elif "gather" in inside or "dynamic-slice" in inside:
            out[name] = "gather"
        elif "dot" in inside or "convolution" in inside:
            out[name] = "dot"
        elif "dynamic-update-slice" in inside:
            out[name] = "update_slice"
        elif "reduce" in inside:
            out[name] = "reduce"
        elif inside & {"copy", "transpose"}:
            out[name] = "copy"
        else:
            out[name] = "elementwise"
    return out


def step_arm(args) -> dict:
    import jax
    import numpy as np

    from benchmark.harness import core, trace_reduce
    from benchmark.harness.loops import step_metrics

    core.enable_compile_cache()
    parts = core.resolve(CELL)
    devices = core.require_tpus(1)
    run = core.Run(parts, args.seed, args.seconds, True, devices,
                   core.load_peaks(devices[0].device_kind),
                   data_dir=str(HERE / "benchmark" / "_data"))
    mod = core.load_module(parts.module_path)
    cell = mod.Cell(run)
    cell.setup()
    win, _ = cell._loop(args.seconds)
    with run.traced():
        cell.trace_window(args.seconds)
    n = cell.trace_steps
    seconds, events, names = mod.kernel_times(run.trace_dir, "tpu",
                                              cell.module, cell.scopes)
    busy = trace_reduce.reduce_dir(run.trace_dir)["busy_s"]
    types = hlo_types(cell.step.as_text())
    by_type = collections.Counter()
    for name, s in names.items():
        if mod.classify(cell.scopes.get(name, name)) == "moe.experts":
            by_type[types.get(name, "unknown")] += s * 1e3 / n
    _, m = cell.step(cell.state, next(cell.pipe))
    peak = (devices[0].memory_stats() or {}).get("peak_bytes_in_use")
    cell.release()
    return {
        "repo": str(args.repo), "device": devices[0].device_kind,
        "seed": args.seed,
        "step_median_ms": step_metrics(win, cell.batch)["step_median_ms"],
        "traced_steps": n,
        "busy_ms": busy * 1e3 / n,
        "experts_ms": seconds.get("moe.experts", 0.0) * 1e3 / n,
        "experts_events": events.get("moe.experts", 0),
        "by_type": {k: round(v, 3) for k, v in by_type.most_common()},
        "top": [(nm, types.get(nm), round(s * 1e3 / n, 3))
                for nm, s in names.most_common(15)
                if mod.classify(cell.scopes.get(nm, nm)) == "moe.experts"],
        "held_pairs": np.asarray(m["expert_assignments"]).sum(1).tolist(),
        "expert_chunks": (np.asarray(m["expert_chunks"]).tolist()
                          if "expert_chunks" in m else None),
        "peak_bytes": peak,
    }


def layer_arm(n_held: int, args) -> dict:
    """The expert layer alone at the cell's tokens and widths, forward and
    backward under ``jax.checkpoint`` as the step runs it, *n_held* of the
    experts held, random rows, picks and gate weights: the median ms of
    20 calls on the host clock."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from strom.models import deepseek_v2 as dv

    cfg = dv.DeepseekV2Config(n_held=n_held)
    d, f = cfg.d_model, cfg.expert_ff
    keys = jax.random.split(jax.random.key(args.seed), 6)
    h = jax.random.normal(keys[0], (TOKENS, d), jnp.bfloat16)
    idx = lax.top_k(jax.random.uniform(keys[1], (TOKENS, cfg.n_experts)),
                    cfg.top_k)[1]
    weight = jax.nn.softmax(jax.random.normal(keys[2], idx.shape), -1)
    experts = {name: (jax.random.normal(key, (n_held, *shape)) * 0.006
                      ).astype(jnp.bfloat16)
               for name, key, shape in (("w_gate", keys[3], (d, f)),
                                        ("w_up", keys[4], (d, f)),
                                        ("w_down", keys[5], (f, d)))}
    layer = jax.checkpoint(
        lambda h, w, e: dv.held_experts(h, idx, w, e, cfg)[0])
    run = jax.jit(jax.value_and_grad(
        lambda h, w, e: jnp.sum(layer(h, w, e).astype(jnp.float32)),
        argnums=(0, 1, 2)))
    jax.block_until_ready(run(h, weight, experts))
    times = []
    for _ in range(20):
        t = time.perf_counter()
        jax.block_until_ready(run(h, weight, experts))
        times.append((time.perf_counter() - t) * 1e3)
    return {"repo": str(args.repo), "layer_held": n_held,
            "held_pairs": int(jnp.sum(idx < n_held)),
            "layer_ms": statistics.median(times), "layer_ms_all": times}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--repo", type=Path, default=HERE)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=2147489001)
    ap.add_argument("--layer-held", default="",
                    help="after the step arm, time the layer alone with "
                         "each of these numbers of experts held")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(args.repo.resolve()))
    import jax

    if jax.default_backend() != "tpu":
        print("expert_profile: no TPU; the device times need one",
              file=sys.stderr)
        return 1
    arms = [step_arm] + [lambda a, n=int(x): layer_arm(n, a)
                         for x in args.layer_held.split(",") if x]
    for arm in arms:
        line = json.dumps(arm(args))
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
