"""Closed-loop knob autotuner (ISSUE 16 policy half).

The mechanism half of the kernel-bypass pass makes the transport cheap; this
package makes the KNOBS that drive it self-setting. An :class:`Autotuner`
runs coordinate descent over live knob surfaces (prefetch depth,
``sched_slice_bytes``, hot-cache budget, ...) against a caller-supplied
objective (goodput / items-per-second), with two safety invariants:

- **guarded step**: a move that costs more than ``guard_frac`` of the
  objective is reverted immediately and the search direction flips;
- **SLO hold**: while any tenant's SLO is burning the tuner reverts its
  in-flight trial and proposes nothing — it never experiments on a
  workload that is already missing its target.

Profiles (the converged knob values) persist as JSON per workload
(``strom-bench daemon --tune --profile``) so a tuned workload starts where the last run
ended instead of re-searching from the hand defaults.
"""

from strom.tune.autotuner import (TUNE_BENCH_FIELDS, TUNE_FIELDS, Autotuner,
                                  Profile, stall_weighted_metrics)
from strom.tune.knobs import Knob, prefetcher_knob, standard_knobs

__all__ = ["Autotuner", "Knob", "Profile", "TUNE_BENCH_FIELDS",
           "TUNE_FIELDS", "prefetcher_knob", "stall_weighted_metrics",
           "standard_knobs"]
