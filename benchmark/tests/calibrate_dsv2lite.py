"""The readings that ``workloads/dsv2lite_tokens_s4096_b8.json``'s limits
were set from: ``calibrate.py``'s ``program`` and ``control`` arms (the
reference at float8 in the program's place), and this cell's fault
``half_batch``: the program's step given the first half of each batch.

    python3 benchmark/tests/calibrate_dsv2lite.py --seeds 1 2 ... \\
        [--arms program control half_batch] [--seconds 2]

prints one JSON line per seed and arm. The CPU tests call the same
functions at the cell's CPU sizes.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import core  # noqa: E402
from benchmark.tests import calibrate  # noqa: E402

CELL = "dsv2lite_tokens_s4096_b8"
ARMS = ("program", "control", "half_batch")


def half_batch(mod) -> None:
    real = mod.build_program

    def build(fields, opt, mesh, q_block):
        init, step = real(fields, opt, mesh, q_block)
        return init, lambda state, tokens: step(
            state, tokens[:tokens.shape[0] // 2])

    mod.build_program = build


def readings(parts, seeds, devices, data_dir=core.DATA, arms=ARMS,
             seconds: float = 2.0) -> list[dict]:
    """One JSON row per seed and arm; an arm that cannot run (out of device
    memory, say) gives a row with its ``error`` and the rest go on."""
    out = []
    for seed in seeds:
        for name in arms:
            t = time.perf_counter()
            patch = half_batch if name == "half_batch" else None
            try:
                if patch:
                    with calibrate.patched(parts.module_path, patch):
                        line, run = core.execute(parts, seed, seconds, False,
                                                 devices, None, time.time(),
                                                 data_dir)
                else:
                    with calibrate.arm(parts, name) as p:
                        line, run = core.execute(p, seed, seconds, False,
                                                 devices, None, time.time(),
                                                 data_dir)
                row = {"seed": seed, "arm": name, "correct": line["correct"],
                       "checks": line["checks"], **run.layer["gaps"],
                       **run.e2e}
            except Exception as e:  # noqa: BLE001 - recorded, not hidden
                traceback.print_exc()
                row = {"seed": seed, "arm": name, "error": repr(e)[:2000]}
            row["seconds"] = time.perf_counter() - t
            print(json.dumps(row), flush=True)
            out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--arms", nargs="+", default=list(ARMS))
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    parts = core.resolve(CELL)
    core.enable_compile_cache()
    devices = core.require_tpus(parts.cell["chips"])
    readings(parts, args.seeds, devices, arms=args.arms,
             seconds=args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
