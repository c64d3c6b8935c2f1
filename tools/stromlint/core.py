"""stromlint shared AST core (ISSUE 11 tentpole).

One parse per file, shared by every pass (and by the ported stats-name
lint, tools/lint_stats_names.py): module walking, pragma handling, dotted
expression rendering, ``make_lock``/``make_condition`` declaration
discovery, and the held-lock walker that extracts every statically
visible nested acquisition plus every call made under a held lock.

Pragma format (the ONLY sanctioned suppression spelling)::

    some_code()  # stromlint: ignore[lock-order] -- reason the rule is wrong here

- ``rule`` is one of :data:`RULES` (comma-separate several).
- The ``-- reason`` clause is MANDATORY: a pragma without a written
  justification is itself a finding (rule ``pragma``), so the tree can
  lint clean only when every suppression explains itself.
- A pragma suppresses findings of its rules on its own line, or — for a
  standalone comment line — on the next code line below it (multi-line
  statements anchor findings at their first line).
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re

RULES = (
    "lock-order",
    "blocking-under-lock",
    "thread-lifecycle",
    "errno-exhaustiveness",
    "swallowed-exceptions",
    "pragma",
)

# source roots stromlint audits (tests are exercised separately via
# explicit paths; fixture modules under tests/lint_fixtures must never
# count against the tree)
DEFAULT_ROOTS = ("strom", "tools")

_PRAGMA_RE = re.compile(
    r"#\s*stromlint:\s*ignore\[([a-zA-Z0-9_,\- ]+)\]\s*(?:--\s*(\S.*))?")
_COMMENT_ONLY_RE = re.compile(r"^\s*(#.*)?$")

# with-item / acquisition heuristic for locks that did NOT come from
# make_lock: anything whose final component looks like a mutex. Such a
# lock participating in a nested acquisition is an "undeclared lock"
# finding — the fix is make_lock (which names and ranks it) or a pragma.
_LOCKLIKE_RE = re.compile(r"(^|_)(lock|locks|cond|mutex|sem)(\[\])?$",
                          re.IGNORECASE)


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str          # root-relative
    line: int
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"

    def doc(self) -> dict:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "message": self.message}


class Module:
    """One parsed source file + its pragma index."""

    def __init__(self, path: str, rel: str, source: str):
        self.path = path
        self.rel = rel
        self.source = source
        self.tree = ast.parse(source, filename=rel)
        self.lines = source.split("\n")
        # line -> {rule: reason-or-None}
        self.pragmas: dict[int, dict[str, "str | None"]] = {}
        self._comment_only: set[int] = set()
        for i, line in enumerate(self.lines, start=1):
            if _COMMENT_ONLY_RE.match(line):
                self._comment_only.add(i)
            m = _PRAGMA_RE.search(line)
            if m:
                rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
                reason = (m.group(2) or "").strip() or None
                self.pragmas[i] = {r: reason for r in rules}

    def pragma_for(self, rule: str, line: int) -> "dict | None":
        """The pragma covering findings of *rule* at *line*: same line, or
        standalone pragma comment lines directly above."""
        p = self.pragmas.get(line)
        if p is not None and (rule in p or "all" in p):
            return p
        ln = line - 1
        while ln > 0 and ln in self._comment_only:
            p = self.pragmas.get(ln)
            if p is not None and (rule in p or "all" in p):
                return p
            ln -= 1
        return None

    def suppressed(self, rule: str, line: int) -> bool:
        return self.pragma_for(rule, line) is not None


def iter_py_files(root: str, roots=DEFAULT_ROOTS) -> list[str]:
    files: list[str] = []
    for r in roots:
        p = os.path.join(root, r)
        if os.path.isfile(p):
            files.append(p)
            continue
        for dirpath, _dirs, names in os.walk(p):
            if "__pycache__" in dirpath:
                continue
            files.extend(os.path.join(dirpath, n) for n in sorted(names)
                         if n.endswith(".py"))
    return files


def load_modules(root: str, roots=DEFAULT_ROOTS,
                 paths: "list[str] | None" = None) -> list[Module]:
    """Parse every .py under *roots* (or exactly *paths* when given).
    Unparseable files are skipped — stromlint audits concurrency
    discipline, the interpreter audits syntax."""
    if paths is not None:
        files = []
        for p in paths:
            if os.path.isdir(p):
                files.extend(iter_py_files(p, ("",)))
            else:
                files.append(p)
    else:
        files = iter_py_files(root, roots)
    out = []
    for path in files:
        try:
            with open(path) as f:
                src = f.read()
            rel = os.path.relpath(path, root)
            out.append(Module(path, rel, src))
        except (OSError, SyntaxError, ValueError):
            continue
    return out


def dotted(node: ast.AST) -> "str | None":
    """Render a Name/Attribute/Subscript chain: ``self._lock``,
    ``ctx._engine_lock``, ``self._ring_locks[]``. None for anything
    else (calls, literals)."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = dotted(node.value)
        return None if base is None else f"{base}.{node.attr}"
    if isinstance(node, ast.Subscript):
        base = dotted(node.value)
        return None if base is None else f"{base}[]"
    return None


def tail_of(text: str) -> str:
    """Final component of a dotted rendering, subscript marker dropped."""
    t = text.rsplit(".", 1)[-1]
    return t[:-2] if t.endswith("[]") else t


def locklike(text: "str | None") -> bool:
    return text is not None and bool(_LOCKLIKE_RE.search(tail_of(text)))


# -- make_lock declaration discovery -----------------------------------------

_FACTORIES = ("make_lock", "make_condition", "_make_lock",
              "_make_condition")


class LockModel:
    """Declared locks discovered from ``make_lock("band.role")`` call
    sites: (module-rel, class-or-None, attr) → name, plus a global
    attr→names index for cross-module references (``ctx._engine_lock``
    seen from stream.py resolves through the unique global attr)."""

    def __init__(self) -> None:
        self.decls: dict[tuple[str, "str | None", str], str] = {}
        self.by_attr: dict[str, set[str]] = {}
        # (rel, line, name) per declaration, for exhaustiveness checks
        self.sites: list[tuple[str, int, str]] = []

    def scan(self, modules: "list[Module]") -> None:
        for m in modules:
            self._scan_module(m)

    @staticmethod
    def _factory_name(value: ast.AST) -> "tuple[str, int] | None":
        """(lock name, line) when *value*'s subtree contains a make_lock /
        make_condition call with a literal name (list comprehensions like
        ``[make_lock(..) for _ in range(n)]`` count)."""
        for node in ast.walk(value):
            if isinstance(node, ast.Call):
                fn = node.func
                fname = fn.id if isinstance(fn, ast.Name) else (
                    fn.attr if isinstance(fn, ast.Attribute) else None)
                if fname in _FACTORIES and node.args and \
                        isinstance(node.args[0], ast.Constant) and \
                        isinstance(node.args[0].value, str):
                    return node.args[0].value, node.lineno
        return None

    def _scan_module(self, m: Module) -> None:
        def record(target: ast.AST, name: str, line: int,
                   cls: "str | None") -> None:
            if isinstance(target, ast.Attribute) and \
                    isinstance(target.value, ast.Name) and \
                    target.value.id == "self":
                key = (m.rel, cls, target.attr)
                attr = target.attr
            elif isinstance(target, ast.Name):
                key = (m.rel, None, target.id)
                attr = target.id
            else:
                return
            self.decls[key] = name
            self.by_attr.setdefault(attr, set()).add(name)
            self.sites.append((m.rel, line, name))

        def walk(node: ast.AST, cls: "str | None") -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.ClassDef):
                    walk(child, child.name)
                    continue
                if isinstance(child, ast.Assign):
                    hit = self._factory_name(child.value)
                    if hit:
                        for t in child.targets:
                            record(t, hit[0], hit[1], cls)
                elif isinstance(child, ast.AnnAssign) and child.value:
                    hit = self._factory_name(child.value)
                    if hit:
                        record(child.target, hit[0], hit[1], cls)
                walk(child, cls)

        walk(m.tree, None)

    def resolve(self, m: Module, cls: "str | None",
                text: str) -> "str | None":
        """Declared name for a lock expression rendering, or None."""
        attr = tail_of(text)
        for key in ((m.rel, cls, attr), (m.rel, None, attr)):
            if key in self.decls:
                return self.decls[key]
        names = self.by_attr.get(attr)
        if names and len(names) == 1:
            return next(iter(names))
        return None


# -- the held-lock walker -----------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LockRef:
    text: str                 # source rendering ("self._lock")
    name: "str | None"        # declared make_lock name, or None
    line: int


@dataclasses.dataclass
class LockScan:
    """Per-module lock facts every pass consumes."""

    # (outer, inner) for every statically visible nested acquisition
    pairs: list = dataclasses.field(default_factory=list)
    # (held tuple, ast.Call, class-name) for every call under >=1 held lock
    calls_under: list = dataclasses.field(default_factory=list)
    # acquisitions whose lifetime is not a with-scope:
    # stack.enter_context(lock) / lock.acquire()
    unscoped: list = dataclasses.field(default_factory=list)
    # (class-or-None, func-name) -> {lock names the function acquires
    # somewhere in its body} — used for one-module interprocedural
    # propagation (a `*_locked` helper that frees a slab makes its caller
    # a cache->pool nesting even though the `with` and the free are in
    # different functions)
    func_acquires: dict = dataclasses.field(default_factory=dict)


def scan_locks(m: Module, model: LockModel,
               cm_holds: "dict[str, str] | None" = None,
               call_summary=None) -> LockScan:
    """Walk every function, tracking the with-statement held-lock stack.

    *cm_holds* maps context-manager method names to pseudo-lock names
    (``{"grant": "sched.grant"}``): a ``with x.grant(...):`` body is
    treated as holding that pseudo-lock, so engine ownership windows
    participate in ordering checks even though no raw mutex is visible.

    *call_summary* is ``hierarchy.call_summary``-shaped: ``(module_rel,
    receiver, method) -> lock-name-or-None``. When given, each
    function's transient acquisitions feed ``func_acquires``, and
    same-module ``self.helper()`` calls propagate their helper's
    acquisitions to the caller (one-module fixpoint) — this is what
    catches a ``*_locked`` helper freeing a pool slab on behalf of a
    caller that holds the cache lock.
    """
    cm_holds = cm_holds or {}
    out = LockScan()
    # (cls, func) -> [(receiver, method)] same-module call edges
    func_calls: dict[tuple, list] = {}
    cur_func: list[tuple] = []  # stack of (cls, funcname) keys

    def note_acquire(name: "str | None") -> None:
        if name is not None and cur_func:
            out.func_acquires.setdefault(cur_func[-1], set()).add(name)

    def lock_of(expr: ast.AST, cls: "str | None") -> "LockRef | None":
        text = dotted(expr)
        if text is None:
            return None
        name = model.resolve(m, cls, text)
        if name is None and not locklike(text):
            return None
        return LockRef(text, name, expr.lineno)

    def visit_stmts(stmts, held: tuple, cls: "str | None") -> None:
        for s in stmts:
            visit(s, held, cls)

    def visit(node: ast.AST, held: tuple, cls: "str | None") -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # a nested def runs later, not under the current holds
            cur_func.append((cls, node.name))
            visit_stmts(node.body, (), cls)
            cur_func.pop()
            return
        if isinstance(node, ast.ClassDef):
            visit_stmts(node.body, (), node.name)
            return
        if isinstance(node, (ast.With, ast.AsyncWith)):
            acquired = list(held)
            for item in node.items:
                ref = lock_of(item.context_expr, cls)
                if ref is None and isinstance(item.context_expr, ast.Call):
                    fn = item.context_expr.func
                    meth = fn.attr if isinstance(fn, ast.Attribute) else (
                        fn.id if isinstance(fn, ast.Name) else None)
                    if meth in cm_holds:
                        ref = LockRef(dotted(fn) or meth, cm_holds[meth],
                                      item.context_expr.lineno)
                    else:
                        scan_expr(item.context_expr, tuple(acquired), cls)
                elif ref is None:
                    scan_expr(item.context_expr, tuple(acquired), cls)
                if ref is not None:
                    note_acquire(ref.name)
                    for h in acquired:
                        out.pairs.append((h, ref))
                    acquired.append(ref)
            visit_stmts(node.body, tuple(acquired), cls)
            return
        # statements with nested bodies keep the current holds
        for field in ("body", "orelse", "finalbody", "handlers"):
            sub = getattr(node, field, None)
            if sub:
                for child in sub:
                    if isinstance(child, ast.ExceptHandler):
                        visit_stmts(child.body, held, cls)
                    else:
                        visit(child, held, cls)
        if not any(getattr(node, f, None)
                   for f in ("body", "orelse", "finalbody")):
            scan_expr(node, held, cls)
        else:
            # expression parts of compound statements (test, iter, items)
            for field in ("test", "iter", "subject"):
                sub = getattr(node, field, None)
                if sub is not None:
                    scan_expr(sub, held, cls)

    def scan_expr(node: ast.AST, held: tuple, cls: "str | None") -> None:
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            fn = sub.func
            meth = fn.attr if isinstance(fn, ast.Attribute) else (
                fn.id if isinstance(fn, ast.Name) else None)
            # unscoped acquisitions: enter_context(lock) / lock.acquire()
            if meth == "enter_context" and sub.args:
                ref = lock_of(sub.args[0], cls)
                if ref is not None:
                    out.unscoped.append(ref)
            elif meth == "acquire" and isinstance(fn, ast.Attribute):
                recv = dotted(fn.value)
                if locklike(recv) or (
                        recv is not None
                        and model.resolve(m, cls, recv) is not None):
                    out.unscoped.append(
                        LockRef(recv, model.resolve(m, cls, recv),
                                sub.lineno))
            if isinstance(fn, ast.Attribute):
                recv = dotted(fn.value)
                if call_summary is not None:
                    note_acquire(call_summary(m.rel, recv, meth))
                if recv == "self" and cur_func:
                    func_calls.setdefault(cur_func[-1], []).append(
                        (cls, meth))
            elif isinstance(fn, ast.Name) and cur_func:
                func_calls.setdefault(cur_func[-1], []).append(
                    (cls, fn.id))
            if held:
                out.calls_under.append((held, sub, cls))

    visit_stmts(m.tree.body, (), None)
    # one-module fixpoint: a caller inherits its same-module callees'
    # acquisitions (self.helper() and bare helper() edges)
    changed = True
    while changed:
        changed = False
        for key, edges in func_calls.items():
            mine = out.func_acquires.setdefault(key, set())
            before = len(mine)
            for edge in edges:
                mine |= out.func_acquires.get(edge, set())
            if len(mine) > before:
                changed = True
    return out
