"""strom-bench — the ``strom-bench`` script (``python -m strom.cli``).

- ``strom-bench daemon`` runs one long-lived :class:`StromContext` with its
  scheduler, serving external tenants over the live HTTP surface.
- ``strom-bench check <path>`` (≙ the reference's ``CHECK_FILE``) reports a
  file's data-path tier.

The benchmark is ``python3 benchmark/run.py`` (see ``BENCHMARK.json``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading


def cmd_daemon(args: argparse.Namespace) -> dict:
    """Long-lived daemon mode (ISSUE 7): one StromContext + scheduler
    serving external tenants over the live HTTP surface — GET /tenants
    inspects queue depth/budget state, POST /tenants registers or drains
    (see strom/obs/server.py). SIGTERM/SIGINT triggers the graceful
    shutdown contract: every registered tenant is DRAINED (no queued
    requests, no active grants — hence no leaked pins or in-flight
    tokens) before the flight recorder's SIGTERM handler chain runs, so
    the crash bundle a supervisor-kill leaves behind describes a
    quiesced, not mid-flight, data plane."""
    import signal as _signal

    from strom.config import StromConfig
    from strom.delivery.core import StromContext

    hot = args.hot_cache_bytes
    cfg = StromConfig.from_env(
        engine=args.engine, flight_dir=args.flight_dir,
        flight_stall_s=args.flight_stall_s,
        # closed-loop autotuner: the daemon is the long-lived
        # process the controller was built for — /tune exposes its state,
        # --profile persists the search
        tune=args.tune, tune_profile=args.profile,
        hot_cache_bytes=hot, hot_cache_admit=args.hot_cache_admit,
        readahead_window_batches=args.readahead_window if hot else 0)
    # explicit port (0 = OS-assigned ephemeral): the daemon ALWAYS serves
    # — a daemon without its /tenants surface would be unreachable
    ctx = StromContext(cfg, metrics_port=args.metrics_port)
    srv = ctx.metrics_server
    stop = threading.Event()
    got: dict = {"sig": None}
    # installed AFTER the context (and its flight recorder): this handler
    # runs FIRST on delivery, the recorder's stays chained behind it
    prev = {s: _signal.getsignal(s)
            for s in (_signal.SIGTERM, _signal.SIGINT)}

    def on_sig(signum, frame):
        got["sig"] = signum
        stop.set()

    for s in prev:
        _signal.signal(s, on_sig)
    print(f"strom daemon ready port={srv.port if srv else 0} "
          f"pid={os.getpid()}", flush=True)
    stop.wait()
    # graceful shutdown: drain every tenant BEFORE the recorder chain
    stuck: list = []
    n_tenants = 0
    if ctx.scheduler is not None:
        stuck = ctx.scheduler.drain_all(timeout_s=args.drain_timeout)
        n_tenants = len(ctx.scheduler.tenants_info()["tenants"])
    print(f"strom daemon drained tenants={n_tenants} stuck={stuck}",
          flush=True)
    # persist the converged knobs BEFORE the signal re-raise below ends
    # the process — the next daemon run warm-starts from them
    if ctx.tuner is not None and args.profile:
        try:
            ctx.tuner.settle()  # don't persist an unevaluated trial value
            ctx.tuner.profile().save(args.profile)
        except OSError as e:
            print(f"tune profile save failed: {e}", file=sys.stderr)
    sig = got["sig"]
    for s, h in prev.items():
        _signal.signal(s, h)
    if sig == _signal.SIGTERM:
        # re-deliver so the chained handlers run in order — the flight
        # recorder dumps its bundle against the still-live context, then
        # its own chain restores the default and the exit status still
        # says killed-by-SIGTERM (the contract supervisors key off). The
        # process dies here; OS teardown reclaims the engine.
        _signal.raise_signal(_signal.SIGTERM)
    elif sig == _signal.SIGINT:
        # same killed-by-signal contract for SIGINT, but the restored
        # python handler would raise KeyboardInterrupt (rc 1 + traceback)
        # instead of dying by signal — install the OS default so the exit
        # status reads killed-by-SIGINT. No recorder chain to honor here:
        # the flight recorder hooks SIGTERM only.
        _signal.signal(_signal.SIGINT, _signal.SIG_DFL)
        _signal.raise_signal(_signal.SIGINT)
    ctx.close()
    return {"bench": "daemon", "port": srv.port if srv else 0,
            "tenants": n_tenants, "stuck": stuck, "signal": sig}


def build_parser() -> argparse.ArgumentParser:
    """The ``strom-bench`` argument parser: ``daemon`` and ``check``."""
    ap = argparse.ArgumentParser(prog="strom-bench")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p_daemon = sub.add_parser(
        "daemon",
        help="long-lived multi-tenant delivery daemon: /metrics /stats "
             "/trace /flight /tenants on --metrics-port (0 = ephemeral, "
             "printed on the ready line); POST /tenants registers/drains "
             "tenants; SIGTERM/SIGINT drains every tenant before the "
             "flight recorder's handler chain runs")
    p_daemon.add_argument("--metrics-port", type=int, default=0,
                          dest="metrics_port")
    p_daemon.add_argument("--engine", default="auto",
                          choices=["auto", "uring", "python"])
    p_daemon.add_argument("--flight-dir", default=os.environ.get(
                              "STROM_FLIGHT_DIR", ""), dest="flight_dir")
    p_daemon.add_argument("--flight-stall-s", type=float, default=30.0,
                          dest="flight_stall_s")
    p_daemon.add_argument("--drain-timeout", type=float, default=10.0,
                          dest="drain_timeout",
                          help="seconds to wait for tenant queues/grants "
                               "to empty on shutdown")
    p_daemon.add_argument("--tune", action="store_true",
                          help="arm the closed-loop knob autotuner "
                               "(strom/tune): coordinate descent over "
                               "scheduler slice / cache budget against "
                               "live goodput, SLO-burn holds; state on "
                               "GET /tune")
    p_daemon.add_argument("--profile", default="",
                          help="tune profile JSON: warm-start the search "
                               "from it when it exists, save the "
                               "converged knobs back on graceful "
                               "shutdown (with --tune)")
    p_daemon.add_argument("--hot-cache", type=int, nargs="?",
                          const=256 * 1024 * 1024, default=0,
                          dest="hot_cache_bytes", metavar="BYTES",
                          help="enable the hot-set host cache with this "
                               "byte budget (no value: 256MiB; default off)")
    p_daemon.add_argument("--hot-cache-admit", default="second_touch",
                          choices=["second_touch", "always"],
                          dest="hot_cache_admit",
                          help="admission policy: second_touch (first epoch "
                               "observes, second serves — scan-resistant) "
                               "or always (force-admit on first read)")
    p_daemon.add_argument("--readahead-window", type=int, default=0,
                          dest="readahead_window", metavar="BATCHES",
                          help="epoch-aware readahead: warm the sampler's "
                               "next N batches into the hot cache from a "
                               "background thread that yields to demand "
                               "reads (0 = off; needs --hot-cache)")

    p_check = sub.add_parser("check", help="≙ CHECK_FILE: report a file's data-path tier")
    p_check.add_argument("path")
    p_check.add_argument("--json", action="store_true")
    return ap


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    if args.cmd == "check":
        from strom.probe import check_file

        try:
            rep = check_file(args.path)
        except OSError as e:
            print(f"strom-bench check: {args.path}: {e.strerror or e}", file=sys.stderr)
            return 2
        d = {"path": rep.path, "size": rep.size, "fs": rep.fs_type,
             "tier": rep.tier.value, "supported": rep.supported,
             "dio": vars(rep.dio), "extents": rep.extents,
             "cached_frac": rep.cached_frac,
             "reasons": list(rep.reasons)}
        print(json.dumps(d, indent=None if args.json else 2))
        return 0
    print(json.dumps(cmd_daemon(args)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
