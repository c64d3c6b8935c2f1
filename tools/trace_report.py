#!/usr/bin/env python
"""Summarize strom Trace Event JSON (from ``--trace-out`` or the live
``/trace`` endpoint): per-span rollups, per-step stall attribution, and
per-request / per-tenant causal rollups (ISSUE 8).

Usage: python tools/trace_report.py trace.json [--no-steps] [--requests N]
       python tools/trace_report.py trace_0.json trace_1.json ...
           [--merged-out merged.json]

Given MULTIPLE trace files (one per host — the dist launcher writes
``trace_<rank>.json`` per worker), the tool merges them into one timeline
(ISSUE 18): per-host clock offsets recovered from the traced peer
exchanges align every file onto host 0's timebase, the cross-host
``reqx`` flow chains (client 's' on the asking host, server 't' spans on
the serving host) are counted and reported as linked/unlinked, and
``--merged-out`` writes ONE Perfetto document — each host a process row,
peer fetches rendered as arrows crossing them.

Sections:
- span rollup: one row per span name (count, total/mean/p50/p99 wall) —
  which subsystems burned how much wall overall;
- stall attribution (default on when step windows exist): per-step
  ingest-wait / decode / put / read / compute buckets and goodput_pct,
  the same accounting ``ctx.stats()["steps"]`` and the bench JSON carry
  (strom/obs/stall.py), printed per step so outlier steps are visible;
- request rollup (when req-tagged spans exist): the slowest N requests
  with their CRITICAL PATH — the longest chain through the causal links
  the request tracing recorded (queue → grant → engine slice → decode →
  put), so a slow request reads as "where its time went", not a span
  soup;
- per-tenant table: request count, p50/p99 latency, throttled/errored
  counts from the ``req.done`` markers.

The file is plain Trace Event Format, so the same trace also loads in
chrome://tracing / https://ui.perfetto.dev for the zoomable version.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from strom.obs import stall  # noqa: E402
from strom.obs.chrome_trace import (_clock_shifts, load_events,  # noqa: E402
                                    merge_host_traces)

# the ONE nearest-rank percentile convention, shared with the bench-JSON
# bucket percentiles computed from the same events (strom/obs/stall.py)
_pct = stall._pct


def span_rollup(events: list[dict]) -> list[tuple]:
    """(name, count, total_ms, mean_us, p50_us, p99_us) per span name,
    total-descending."""
    by_name: dict[str, list[float]] = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        by_name.setdefault(e["name"], []).append(e.get("dur_us", 0.0))
    rows = []
    for name, durs in by_name.items():
        total = sum(durs)
        rows.append((name, len(durs), total / 1e3, total / len(durs),
                     _pct(durs, 0.50), _pct(durs, 0.99)))
    rows.sort(key=lambda r: -r[2])
    return rows


def request_spans(events: list[dict]) -> dict[int, list[dict]]:
    """{req_id: [X spans carrying args.req]}, each list ts-sorted."""
    by_req: dict[int, list[dict]] = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        rid = (e.get("args") or {}).get("req")
        if rid is None:
            continue
        by_req.setdefault(int(rid), []).append(e)
    for spans in by_req.values():
        spans.sort(key=lambda e: e["ts_us"])
    return by_req


def critical_path(spans: list[dict]) -> list[dict]:
    """The longest chain through a request's causal links: walking from
    the request's start, always take the span that begins inside (or
    first after) the covered window and extends it furthest — the
    sequence whose spans an operator must shorten to shorten the request.
    Container spans that enclose the whole request (the batch/gather
    umbrella) are skipped so the chain names the WORK, not the wrapper."""
    if not spans:
        return []
    t_lo = min(e["ts_us"] for e in spans)
    t_hi = max(e["ts_us"] + e.get("dur_us", 0.0) for e in spans)

    def _umbrella(e: dict) -> bool:
        # a wrapper covers (almost) the whole request AND encloses other
        # spans — length alone must not disqualify: a 260ms sched.queue
        # in a 263ms throttled request IS the answer, not a wrapper
        if e.get("dur_us", 0.0) < 0.95 * max(t_hi - t_lo, 1e-9):
            return False
        lo, hi = e["ts_us"], e["ts_us"] + e.get("dur_us", 0.0)
        return any(o is not e and o["ts_us"] >= lo - 1e-9
                   and o["ts_us"] + o.get("dur_us", 0.0) <= hi + 1e-9
                   for o in spans)

    inner = [e for e in spans if not _umbrella(e)] or spans
    inner.sort(key=lambda e: (e["ts_us"], -e.get("dur_us", 0.0)))
    chain: list[dict] = []
    covered = t_lo
    i = 0
    while i < len(inner):
        # candidates starting at or before the covered edge: take the one
        # reaching furthest; none -> jump the gap to the next span
        best = None
        while i < len(inner) and inner[i]["ts_us"] <= covered + 1e-9:
            end = inner[i]["ts_us"] + inner[i].get("dur_us", 0.0)
            if best is None or end > best[0]:
                best = (end, inner[i])
            i += 1
        if best is None:
            best = (inner[i]["ts_us"] + inner[i].get("dur_us", 0.0),
                    inner[i])
            i += 1
        if best[0] > covered or not chain:
            chain.append(best[1])
            covered = max(covered, best[0])
    return chain


def request_rollup(events: list[dict], top: int = 10) -> list[dict]:
    """The slowest *top* requests: wall, span count, and the critical
    path rendered name(ms)→name(ms). Request metadata (tenant, kind,
    throttled) comes from the ``req.done`` instants when present."""
    done = {int(e["args"]["req"]): e["args"] for e in events
            if e.get("name") == "req.done"
            and isinstance(e.get("args"), dict) and "req" in e["args"]}
    rows = []
    for rid, spans in request_spans(events).items():
        t_lo = min(e["ts_us"] for e in spans)
        t_hi = max(e["ts_us"] + e.get("dur_us", 0.0) for e in spans)
        meta = done.get(rid, {})
        wall = meta.get("dur_us", t_hi - t_lo)
        chain = critical_path(spans)
        rows.append({
            "req": rid,
            "tenant": meta.get("tenant", "?"),
            "kind": meta.get("kind", "?"),
            "wall_us": wall,
            "spans": len(spans),
            "throttled": bool(meta.get("throttled")),
            "error": meta.get("error"),
            "path": "→".join(
                f"{e['name']}({e.get('dur_us', 0.0) / 1e3:.1f}ms)"
                for e in chain),
        })
    rows.sort(key=lambda r: -r["wall_us"])
    return rows[:top]


def tenant_table(events: list[dict]) -> list[tuple]:
    """(tenant, requests, p50_ms, p99_ms, throttled, errors) per tenant
    from the req.done markers, request-count-descending. Data-path
    requests only: "step" markers (whose wall is mostly consumer compute)
    are excluded, the same policy Request.finish applies to req_lat — so
    these percentiles agree with /slo and the bench req_lat columns."""
    by_tenant: dict[str, list[dict]] = {}
    for e in events:
        if e.get("name") != "req.done":
            continue
        a = e.get("args") or {}
        if a.get("kind") == "step":
            continue
        by_tenant.setdefault(a.get("tenant", "?"), []).append(a)
    rows = []
    for tenant, metas in by_tenant.items():
        durs = [m.get("dur_us", 0.0) for m in metas]
        rows.append((tenant, len(metas),
                     _pct(durs, 0.50) / 1e3, _pct(durs, 0.99) / 1e3,
                     sum(1 for m in metas if m.get("throttled")),
                     sum(1 for m in metas if m.get("error"))))
    rows.sort(key=lambda r: -r[1])
    return rows


def flow_links(host_events: "dict[str, list[dict]]") -> dict:
    """The cross-host ``reqx`` flow chains: one chain per peer fetch,
    flow id minted on the asking host ('s' phase at send), echoed by the
    serving host's span binders ('t') and closed by the client's 'f'.
    Returns ``{"linked": n, "unlinked": n, "pairs": {(client, server): n}}``
    — *linked* = the id appears on >= 2 hosts (the arrow has both ends;
    an unlinked chain means the peer answered without trace context, an
    old peer or a downgraded one)."""
    by_id: dict[int, dict[str, set]] = {}
    for host, evs in host_events.items():
        for e in evs:
            if e.get("cat") == "reqx" and e.get("ph") in ("s", "t", "f"):
                by_id.setdefault(e.get("id", 0), {}) \
                    .setdefault(host, set()).add(e["ph"])
    linked = unlinked = 0
    pairs: dict[tuple, int] = {}
    for phases_by_host in by_id.values():
        if len(phases_by_host) >= 2:
            linked += 1
            clients = [h for h, ps in phases_by_host.items() if "s" in ps]
            servers = [h for h, ps in phases_by_host.items() if "t" in ps]
            for c in clients:
                for s in servers:
                    if s != c:
                        pairs[(c, s)] = pairs.get((c, s), 0) + 1
        else:
            unlinked += 1
    return {"linked": linked, "unlinked": unlinked, "pairs": pairs}


def _cluster_report(host_events: "dict[str, list[dict]]") -> None:
    shifts = _clock_shifts(host_events)
    print(f"hosts: {len(host_events)}")
    for host, evs in host_events.items():
        spans = sum(1 for e in evs if e.get("ph") == "X")
        print(f"  {host}: {len(evs)} events ({spans} spans), "
              f"clock shift {shifts.get(host, 0.0):+.1f}us")
    links = flow_links(host_events)
    total = links["linked"] + links["unlinked"]
    ratio = links["linked"] / total if total else 0.0
    print(f"peer-fetch flows: {total} ({links['linked']} cross-host "
          f"linked, {links['unlinked']} unlinked; "
          f"linked ratio {ratio:.2f})")
    for (c, s), n in sorted(links["pairs"].items()):
        print(f"  {c} -> {s}: {n} fetches")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="trace_report")
    ap.add_argument("traces", nargs="+", metavar="trace",
                    help="Trace Event JSON (--trace-out / GET /trace); "
                         "several = per-host files to merge (ISSUE 18)")
    ap.add_argument("--no-steps", action="store_true",
                    help="skip the per-step stall attribution section")
    ap.add_argument("--requests", type=int, default=10, metavar="N",
                    help="show the N slowest requests' critical paths "
                         "(0 = skip; default 10)")
    ap.add_argument("--merged-out", default=None, metavar="PATH",
                    dest="merged_out",
                    help="write the merged multi-host Perfetto document "
                         "here (multi-trace mode)")
    args = ap.parse_args(argv)
    host_events: dict[str, list[dict]] = {}
    for path in args.traces:
        host = os.path.splitext(os.path.basename(path))[0]
        try:
            host_events[host] = load_events(path)
        except (OSError, ValueError, KeyError) as e:
            print(f"trace_report: cannot read {path}: {e}", file=sys.stderr)
            return 1
    if not any(host_events.values()):
        print("trace_report: no events in trace", file=sys.stderr)
        return 1
    multi = len(host_events) > 1
    try:
        if multi:
            _cluster_report(host_events)
            if args.merged_out:
                import json

                with open(args.merged_out, "w") as f:
                    json.dump(merge_host_traces(host_events), f)
                print(f"merged trace -> {args.merged_out}")
            print()
        # single-timeline sections over the (shifted) union: cross-host
        # stall attribution is meaningless, so steps stay single-mode only
        shifts = _clock_shifts(host_events) if multi else {}
        events = sorted(
            ({**e, "ts_us": e["ts_us"] + shifts.get(h, 0.0)}
             for h, evs in host_events.items() for e in evs),
            key=lambda e: e["ts_us"])
        _report(events, steps=not args.no_steps and not multi,
                requests=args.requests)
    except BrokenPipeError:  # `| head` is a normal way to use this tool
        return 0
    return 0


def _report(events: list[dict], *, steps: bool, requests: int = 10) -> None:
    rows = span_rollup(events)
    name_w = max([len(r[0]) for r in rows] + [len("span")]) + 2
    print(f"{'span'.ljust(name_w)}{'count':>8}{'total_ms':>12}"
          f"{'mean_us':>12}{'p50_us':>10}{'p99_us':>12}")
    for name, n, total_ms, mean, p50, p99 in rows:
        print(f"{name.ljust(name_w)}{n:>8}{total_ms:>12.2f}"
              f"{mean:>12.1f}{p50:>10.1f}{p99:>12.1f}")

    if steps:
        buckets = stall.step_buckets(events)
        if buckets:
            summary = stall.steps_summary(events)
            print(f"\nsteps: {len(buckets)}  goodput "
                  f"{summary['goodput_pct']}% "
                  "(compute / wall; waits attributed below, ms)")
            print(f"{'step':>5}{'wall':>10}{'ingest_wait':>13}{'decode':>9}"
                  f"{'put':>9}{'read':>9}{'compute':>10}")
            for i, s in enumerate(buckets):
                print(f"{i:>5}{s.wall_us / 1e3:>10.2f}"
                      f"{s.ingest_wait_us / 1e3:>13.2f}"
                      f"{s.decode_us / 1e3:>9.2f}{s.put_us / 1e3:>9.2f}"
                      f"{s.read_us / 1e3:>9.2f}{s.compute_us / 1e3:>10.2f}")
        else:
            print("\n(no step windows in trace: run a training loop over "
                  "a pipeline to get stall attribution)")

    if requests:
        reqs = request_rollup(events, top=requests)
        if reqs:
            print(f"\nslowest requests (top {len(reqs)}; critical path = "
                  "longest causal chain):")
            for r in reqs:
                flags = "".join(f" [{f}]" for f, on in
                                (("throttled", r["throttled"]),
                                 ("error", bool(r["error"]))) if on)
                print(f"  req {r['req']} tenant={r['tenant']} "
                      f"kind={r['kind']} wall={r['wall_us'] / 1e3:.1f}ms "
                      f"spans={r['spans']}{flags}")
                if r["path"]:
                    print(f"    {r['path']}")
        tenants = tenant_table(events)
        if tenants:
            print(f"\n{'tenant':<16}{'requests':>9}{'p50_ms':>9}"
                  f"{'p99_ms':>9}{'throttled':>11}{'errors':>8}")
            for t, n, p50, p99, thr, err in tenants:
                print(f"{t:<16}{n:>9}{p50:>9.1f}{p99:>9.1f}"
                      f"{thr:>11}{err:>8}")


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
