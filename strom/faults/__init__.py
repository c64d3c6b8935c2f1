"""Deterministic fault injection (ISSUE 9 tentpole).

A seeded, rule-based :class:`FaultPlan` decides — reproducibly — which
engine ops fail and how (errno, short read, bit-flip corruption, latency
spike, stuck completion, engine death), and :class:`FaultyEngine` is a
full-API engine proxy that applies those decisions to any wrapped engine.
Wired via ``StromConfig.fault_plan`` (``STROM_FAULT_PLAN``) so any
workload or test runs under deterministic chaos; the resilience layer
(engine retries, circuit breaker + failover, hedged reads) is soak-tested
against exactly these plans.
"""

from strom.faults.plan import Fault, FaultPlan, FaultRule
from strom.faults.proxy import FaultyEngine

__all__ = ["Fault", "FaultPlan", "FaultRule", "FaultyEngine",
           "run_kill_resume"]


def run_kill_resume(*args, **kwargs):
    """Kill/restart recovery harness (ISSUE 14) — lazy re-export: the
    harness pulls in the checkpoint/pipeline stack, which plain fault-plan
    users (and the FaultyEngine wrap inside StromContext.__init__) must
    not pay for at import time."""
    from strom.faults.resume_harness import run_kill_resume as _run

    return _run(*args, **kwargs)
