#!/usr/bin/env python
"""Lint the global stats namespace for near-duplicate metric names.

``global_stats`` keys are created on first use, so a typo'd or restyled
name (``coalesce_ops_in`` vs ``coalesceOpsIn`` vs ``coalesce_opsin``)
silently forks a metric: the producer feeds one spelling while dashboards,
bench JSON columns and compare_rounds read the other — both "work", both
read zero half the time. This tool finds every string-literal name passed
to ``global_stats.add / observe_us / set_gauge / counter / gauge /
histogram / timer_us`` and FAILS when two distinct literals normalize to
the same name modulo case and underscores.

Since ISSUE 11 this runs on the stromlint AST core
(tools/stromlint/core.py) instead of regexes: metric names come from real
call expressions (receiver-aware — the global registry OR any scoped
view/threaded scope: ``self.scope``, ``ctx.scope``, ``pscope``,
``self._stats``; scoped writes land in the SAME aggregate namespace, so a
restyled spelling through a scope forks a metric exactly like one through
``global_stats``), f-strings contribute their literal parts, scope LABEL
keys come from real ``.scoped(...)`` keyword arguments, and the
single-sourced ``*_FIELDS``/``*_KEYS``/``*_COUNTERS`` tuples are walked
as assignments rather than bracket-matched text.

Run directly (``python tools/lint_stats_names.py``) or via the tier-1 test
that wires it into the suite (tests/test_lint_stats_names.py). Exit 0 =
clean, 1 = collisions, 2 = usage error.
"""

from __future__ import annotations

import ast
import os
import re
import sys
from collections import defaultdict

# the stromlint AST core (shared parse/walk layer); bootstrap the repo
# root onto sys.path so this file also works when loaded standalone by
# importlib (the tier-1 test does exactly that)
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
try:
    from tools.stromlint import core as _core
except ImportError:  # loaded by path, repo root not importable yet
    sys.path.insert(0, _REPO_ROOT)
    from tools.stromlint import core as _core

# metric-writing methods on the registry / any scoped view
_METRIC_METHODS = frozenset(
    ("add", "observe_us", "set_gauge", "counter", "gauge", "histogram",
     "timer_us"))

# receiver shapes that feed the global namespace: the registry itself, or
# any scope/threaded-scope spelling (self.scope, pscope, op_scope,
# self._stats, ctx.stats — ISSUE 6: every scope write fans into the
# global series, so a restyled spelling through a scope forks a metric
# exactly like one through global_stats)
def _is_metric_receiver(recv: "str | None") -> bool:
    if recv is None:
        return False
    return (recv == "global_stats" or recv.endswith("global_stats")
            or recv.endswith("scope") or recv.endswith("_stats")
            or recv.endswith(".stats"))


# single-sourced metric-name tuples (STALL_FIELDS, CACHE_BENCH_FIELDS,
# STREAM_FIELDS, FLIGHT_FIELDS, SENTINEL_FIELDS, SCHED_FIELDS,
# DIST_FIELDS/DIST_BENCH_FIELDS (strom/dist/peers.py, ISSUE 15),
# FED_FIELDS (strom/obs/federation.py, ISSUE 18), the
# compare_rounds *_KEYS column lists, ...): their
# literals name the SAME series the producers feed, so a restyled
# spelling here forks a dashboard column exactly like a restyled call
# site (ISSUE 4 satellite: bench/report columns are linted tier-1)
_FIELDS_NAME = re.compile(r"^_?[A-Z][A-Z0-9_]*_(?:FIELDS|KEYS|COUNTERS)$")

# source roots that feed the global registry
DEFAULT_ROOTS = ("strom", "tools")

# HTTP route literals in the live server's handlers: `path == "/metrics"`
# comparisons inside strom/obs/server.py. Every one must be documented in
# README.md — an undocumented route is an API nobody can find until they
# read the handler (ISSUE 8 satellite).
_ROUTE_LIT = re.compile(r"^/[a-z_]*$")
SERVER_SOURCE = os.path.join("strom", "obs", "server.py")
ROUTE_DOC = "README.md"


def _literal_of(node: ast.AST) -> "str | None":
    """The metric-name literal of a call's first argument: a plain string,
    or an f-string's literal parts with ``{}`` placeholders (a templated
    name like ``decode_reduced_hits_{denom}`` can still case-collide on
    its literal part)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr):
        parts = []
        for v in node.values:
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                parts.append(v.value)
            else:
                parts.append("{}")
        return "".join(parts)
    return None


def scan_routes(root_dir: str) -> tuple[set[str], list[str]]:
    """(documented routes needed, missing-from-README routes). Routes come
    from ``path == "/..."`` comparison expressions in the server source;
    README.md is matched on the literal route string."""
    src = os.path.join(root_dir, SERVER_SOURCE)
    try:
        with open(src) as f:
            tree = ast.parse(f.read())
    except (OSError, SyntaxError, ValueError):
        return set(), []
    routes: set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Compare) or len(node.ops) != 1:
            continue
        if not isinstance(node.ops[0], (ast.Eq, ast.NotEq)):
            continue
        left = _core.dotted(node.left)
        if left is None or _core.tail_of(left) != "path":
            continue
        comp = node.comparators[0]
        if isinstance(comp, ast.Constant) and isinstance(comp.value, str) \
                and _ROUTE_LIT.match(comp.value):
            routes.add(comp.value)
    routes.discard("/")  # a bare-root comparison is not an API surface
    try:
        with open(os.path.join(root_dir, ROUTE_DOC)) as f:
            readme = f.read()
    except OSError:
        readme = ""
    missing = sorted(r for r in routes if r not in readme)
    return routes, missing


def _dict_keys(node: "ast.AST | None") -> list[str]:
    """String keys of a dict LITERAL, or of the literals in either branch
    of ``a if c else {...}`` / ``a or {...}`` (the pipelines' default
    scopes); dynamic dicts can't be linted."""
    if isinstance(node, ast.IfExp):
        return _dict_keys(node.body) + _dict_keys(node.orelse)
    if isinstance(node, ast.BoolOp):
        return [k for v in node.values for k in _dict_keys(v)]
    if not isinstance(node, ast.Dict):
        return []
    return [k.value for k in node.keys
            if isinstance(k, ast.Constant) and isinstance(k.value, str)]


def _normalize(name: str) -> str:
    return name.replace("_", "").lower()


def scan_sources(root_dir: str, roots=DEFAULT_ROOTS
                 ) -> tuple[dict[str, set[tuple[str, str]]],
                            dict[str, set[tuple[str, str]]]]:
    """(metric_names, label_keys): each {normalized: {(literal, file:line),
    ...}} over every .py under *roots* (relative to *root_dir*). Metric
    names come from registry/scope call expressions AND single-sourced
    *_FIELDS/*_KEYS/*_COUNTERS tuples (FLIGHT_FIELDS, SENTINEL_FIELDS
    included — they name the same series the producers feed); label keys
    come from ``.scoped(...)`` kwargs and live in their own collision
    domain (``pipeline`` vs ``pipe_line`` would fork every labeled series
    on /metrics)."""
    found: dict[str, set[tuple[str, str]]] = defaultdict(set)
    labels: dict[str, set[tuple[str, str]]] = defaultdict(set)
    for mod in _core.load_modules(root_dir, roots):
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                # label dicts at the pipeline API surface (ANY call):
                # scope={"pipeline": ..., "tenant": ...} kwargs flow
                # verbatim into scoped(**d), so their KEYS are label keys
                # exactly like scoped() kwargs
                for kw in node.keywords:
                    if kw.arg == "scope":
                        for key in _dict_keys(kw.value):
                            labels[_normalize(key)].add(
                                (key, f"{mod.rel}:{node.lineno}"))
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute):
                recv = _core.dotted(node.func.value)
                meth = node.func.attr
                if meth in _METRIC_METHODS and _is_metric_receiver(recv) \
                        and node.args:
                    lit = _literal_of(node.args[0])
                    if lit is not None:
                        found[_normalize(lit)].add(
                            (lit, f"{mod.rel}:{node.lineno}"))
                elif meth == "scoped":
                    for kw in node.keywords:
                        if kw.arg is None:
                            # **expansion: a literal dict contributes its
                            # keys; anything dynamic is skipped
                            for key in _dict_keys(kw.value):
                                labels[_normalize(key)].add(
                                    (key, f"{mod.rel}:{node.lineno}"))
                            continue
                        labels[_normalize(kw.arg)].add(
                            (kw.arg, f"{mod.rel}:{node.lineno}"))
            elif isinstance(node, (ast.Assign, ast.AnnAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                if not any(isinstance(t, ast.Name)
                           and _FIELDS_NAME.match(t.id) for t in targets):
                    continue
                if node.value is None:
                    continue
                for sub in ast.walk(node.value):
                    if isinstance(sub, ast.Constant) \
                            and isinstance(sub.value, str) \
                            and "\n" not in sub.value:
                        found[_normalize(sub.value)].add(
                            (sub.value, f"{mod.rel}:{sub.lineno}"))
    return found, labels


def collisions(found: dict[str, set[tuple[str, str]]]
               ) -> list[tuple[str, set[tuple[str, str]]]]:
    """Normalized groups containing more than one DISTINCT literal."""
    out = []
    for norm, uses in sorted(found.items()):
        literals = {lit for lit, _ in uses}
        if len(literals) > 1:
            out.append((norm, uses))
    return out


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    root = argv[0] if argv else _REPO_ROOT
    if not os.path.isdir(root):
        print(f"lint_stats_names: not a directory: {root}", file=sys.stderr)
        return 2
    found, labels = scan_sources(root)
    bad = collisions(found)
    bad_labels = collisions(labels)
    routes, undocumented = scan_routes(root)
    if not bad and not bad_labels and not undocumented:
        print(f"lint_stats_names: {len(found)} distinct metric names + "
              f"{len(labels)} scope label keys, no case/underscore "
              f"collisions; {len(routes)} server routes all documented")
        return 0
    for norm, uses in bad:
        print(f"metric name collision (normalized '{norm}'):",
              file=sys.stderr)
        for lit, where in sorted(uses):
            print(f"  {lit!r} at {where}", file=sys.stderr)
    for norm, uses in bad_labels:
        print(f"scope label key collision (normalized '{norm}'):",
              file=sys.stderr)
        for lit, where in sorted(uses):
            print(f"  {lit!r} at {where}", file=sys.stderr)
    for r in undocumented:
        print(f"undocumented server route: {r!r} handled in "
              f"{SERVER_SOURCE} but absent from {ROUTE_DOC}",
              file=sys.stderr)
    n_bad = len(bad) + len(bad_labels)
    if n_bad:
        print(f"lint_stats_names: {n_bad} collision group(s) — pick ONE "
              "spelling per metric/label", file=sys.stderr)
    if undocumented:
        print(f"lint_stats_names: {len(undocumented)} undocumented "
              f"route(s) — add them to {ROUTE_DOC}", file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main())
