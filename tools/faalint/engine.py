"""faalint engine: single-parse, multi-pass static analysis.

The framework parses each file ONCE into a :class:`FileContext` — the
AST plus the shared indexes every pass consumes (parent links, nodes
bucketed by type, enclosing-function/loop/with maps, constructor-bound
receiver tables) — then runs every registered rule over that one
context.  The legacy ``tools/lint_robustness.py`` re-parsed and
re-walked the tree once per rule family; here the tree is walked once
and the passes share the indexes.

Three layers of verdict control, in order:

* ``# robust: allow`` on the offending line suppresses a finding at
  that line (put the one-line justification in the same comment).  A
  marker that suppresses NOTHING is itself a warning (rule ``S1``) so
  suppressions cannot rot silently.
* the reviewed baseline file (``tools/faalint/baseline.json``): each
  entry pins one known finding ``{path, rule, line, reason}`` and must
  carry a non-empty ``reason``.  Entries that no longer match any
  finding are flagged (rule ``S2``).
* severity: every rule declares ``error`` / ``warning`` / ``info``;
  the CLI fails at ``--fail-on`` (default ``warning``) and above.

Rule identifiers: ``R1``–``R9`` robustness/blocking (R1–R8 migrated
from the legacy lint, R9 the extended-scope blocking rule), ``C1``–
``C3`` concurrency, ``D1``–``D3`` dispatch hazards, ``T1``–``T3``
determinism, ``S1``/``S2`` suppression hygiene, ``R0`` syntax error.
See docs/STATIC_ANALYSIS.md for the catalog and the historical
incident each rule pins.
"""

from __future__ import annotations

import ast
import json
import os
from typing import Callable, Iterable

# repo root: tools/faalint/engine.py -> tools/faalint -> tools -> repo
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PACKAGE = "fast_autoaugment_tpu"

ALLOW_MARKER = "robust: allow"

SEVERITY_RANK = {"info": 0, "warning": 1, "error": 2}

# ----------------------------------------------------------------- scopes
# Directory scopes, one boolean per pass family, derived from the
# file's repo-relative path (or forced via overrides — the legacy
# ``check_source(..., *_scope=)`` shim and the rule-matrix tests).

ARTIFACT_DIRS = ("core", "search", "train", "launch")       # R3, C3
BLOCKING_DIRS = ("core", "launch", "search")                # R4
JIT_SEAM_DIRS = ("train", "search", "serve")                # R5
SERVE_BLOCKING_DIRS = ("serve",)                            # R6
SEARCH_BLOCKING_DIRS = ("search",)                          # R7
TIMING_SEAM_DIRS = ("train", "search", "serve")             # R8
# R9: the R6/R7 unbounded-blocking engine extended to the remaining
# thread code — supervision (core/, launch/), the prefetch pipeline
# (data/) and utility workers (utils/).  serve/ and search/ keep their
# own rule ids (R6/R7); join/get already policed by R4 in core/launch
# are not double-flagged.
EXT_BLOCKING_DIRS = ("core", "launch", "data", "utils")
# D1–D3: the train/search/serve hot paths whose dispatch loops must
# stay off the host-sync / recompile / mixed-commitment pathologies
# (docs/PARITY.md "Step dispatch & device cache").
DISPATCH_DIRS = ("train", "search", "serve")
# T1–T3: the artifact-writing layers (everything funneled through
# write_json_atomic / save_checkpoint).  launch/ is deliberately out:
# lease/heartbeat records are wall-clock + pid stamped BY DESIGN —
# staleness detection is their function, not a determinism bug.
DETERMINISM_DIRS = ("core", "search", "train")
# F1: the shared-directory layers whose file I/O must route through
# the core/fsfault.py fault seam (docs/RESILIENCE.md "Hostile shared
# filesystem") — the seam is core/, so it polices itself out of scope.
FSSEAM_DIRS = ("launch", "search", "control")

SCOPE_DIRS = {
    "artifact": ARTIFACT_DIRS,
    "blocking": BLOCKING_DIRS,
    "jit": JIT_SEAM_DIRS,
    "serve": SERVE_BLOCKING_DIRS,
    "search": SEARCH_BLOCKING_DIRS,
    "timing": TIMING_SEAM_DIRS,
    "ext_blocking": EXT_BLOCKING_DIRS,
    "dispatch": DISPATCH_DIRS,
    "determinism": DETERMINISM_DIRS,
    "fsseam": FSSEAM_DIRS,
    # C1/C2 run package-wide: threads and locks are legal anywhere, so
    # the analysis follows them anywhere
    "concurrency": None,
}


def _in_dirs(relpath: str, dirs: Iterable[str]) -> bool:
    norm = relpath.replace(os.sep, "/")
    return any(
        f"/{d}/" in f"/{norm}" or norm.startswith(f"{d}/")
        for d in (f"{PACKAGE}/{a}" for a in dirs))


def scopes_for(relpath: str, overrides: dict | None = None) -> dict:
    scopes = {}
    for key, dirs in SCOPE_DIRS.items():
        scopes[key] = True if dirs is None else _in_dirs(relpath, dirs)
    if overrides:
        for key, val in overrides.items():
            if val is not None:
                scopes[key] = bool(val)
    return scopes


# ---------------------------------------------------------------- finding
class Finding:
    """One diagnostic.  ``repr`` stays byte-compatible with the legacy
    lint (``path:line: RULE message``) so existing tooling and the
    rule-matrix tests keep parsing it."""

    def __init__(self, path: str, line: int, rule: str, msg: str,
                 severity: str = "error"):
        self.path, self.line, self.rule, self.msg = path, line, rule, msg
        self.severity = severity
        self.baselined = False
        self.baseline_reason: str | None = None

    def __repr__(self):
        return f"{self.path}:{self.line}: {self.rule} {self.msg}"

    def as_dict(self) -> dict:
        d = {"path": self.path, "line": self.line, "rule": self.rule,
             "severity": self.severity, "message": self.msg}
        if self.baselined:
            d["baselined"] = True
            d["baseline_reason"] = self.baseline_reason
        return d


# ----------------------------------------------------------- file context
_THREAD_CTORS = {"Thread", "Timer"}
_QUEUE_CTORS = {"Queue", "SimpleQueue", "LifoQueue", "PriorityQueue",
                "JoinableQueue"}
_WAIT_CTORS = {"Event", "Condition", "Barrier"}
_LOCK_CTORS = {"Lock", "RLock", "Condition", "Semaphore",
               "BoundedSemaphore"}


def _recv_key(node) -> str | None:
    """A trackable receiver: ``name`` or ``obj.attr`` (one level)."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name):
        return f"{node.value.id}.{node.attr}"
    return None


def _ctor_name(call: ast.Call) -> str | None:
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


class FileContext:
    """One parse, one walk, shared indexes.

    ``tree`` is parsed exactly once; a single iterative walk records
    every node (``nodes``), buckets them by type (``by_type``) and
    links children to parents (``parent``).  Everything else the rules
    need — enclosing functions/classes/loops, with-statement ancestry,
    constructor-bound receiver tables — is derived from those indexes
    without touching the source again.
    """

    def __init__(self, src: str, relpath: str, scopes: dict):
        self.src = src
        self.relpath = relpath
        self.scopes = scopes
        self.lines = src.splitlines()
        self.allow_lines = {
            i + 1 for i, ln in enumerate(self.lines) if ALLOW_MARKER in ln}
        self.used_allow_lines: set[int] = set()
        self.syntax_error: SyntaxError | None = None
        self.nodes: list[ast.AST] = []
        self.by_type: dict[type, list] = {}
        self._parent: dict[int, ast.AST | None] = {}
        self._caches: dict[str, object] = {}
        try:
            self.tree = ast.parse(src)
        except SyntaxError as e:
            self.tree = None
            self.syntax_error = e
            return
        stack: list[tuple[ast.AST, ast.AST | None]] = [(self.tree, None)]
        while stack:
            node, parent = stack.pop()
            self._parent[id(node)] = parent
            self.nodes.append(node)
            self.by_type.setdefault(type(node), []).append(node)
            for child in ast.iter_child_nodes(node):
                stack.append((child, node))

    # -- structural helpers ------------------------------------------
    def of(self, *types) -> list:
        out: list = []
        for t in types:
            out.extend(self.by_type.get(t, ()))
        return out

    def parent(self, node) -> ast.AST | None:
        return self._parent.get(id(node))

    def ancestors(self, node):
        cur = self.parent(node)
        while cur is not None:
            yield cur
            cur = self.parent(cur)

    def enclosing(self, node, types) -> ast.AST | None:
        for anc in self.ancestors(node):
            if isinstance(anc, types):
                return anc
        return None

    def enclosing_function(self, node):
        return self.enclosing(node, (ast.FunctionDef, ast.AsyncFunctionDef))

    def enclosing_class(self, node):
        return self.enclosing(node, ast.ClassDef)

    def enclosing_loop(self, node):
        return self.enclosing(node, (ast.For, ast.While, ast.AsyncFor))

    def allowed(self, lineno: int) -> bool:
        """``# robust: allow`` on the line — record the use so the
        stale-suppression pass (S1) knows the marker earns its keep."""
        if lineno in self.allow_lines:
            self.used_allow_lines.add(lineno)
            return True
        return False

    # -- cached receiver tables --------------------------------------
    def _cache(self, key: str, build: Callable):
        if key not in self._caches:
            self._caches[key] = build()
        return self._caches[key]

    def _ctor_bound_keys(self, ctors: set[str]) -> set[str]:
        out: set[str] = set()
        for node in self.of(ast.Assign, ast.AnnAssign):
            value = node.value
            if not isinstance(value, ast.Call) or _ctor_name(value) not in ctors:
                continue
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for tgt in targets:
                key = _recv_key(tgt)
                if key:
                    out.add(key)
        return out

    def blocking_receivers(self) -> set[str]:
        """R4: names (incl. ``self.x``) bound from Thread/Queue
        constructors in this file."""
        return self._cache("r4_recv", lambda: self._ctor_bound_keys(
            _THREAD_CTORS | _QUEUE_CTORS))

    def bounded_receivers(self) -> tuple[set[str], set[str]]:
        """R6/R7/R9: (keys, attribute suffixes) bound from
        Thread/Queue/Event/Condition constructors — the suffix set
        matches cross-object uses (``pending.event.wait()``)."""
        def build():
            keys = self._ctor_bound_keys(
                _THREAD_CTORS | _QUEUE_CTORS | _WAIT_CTORS)
            return keys, {k.split(".")[-1] for k in keys}
        return self._cache("r6_recv", build)

    def lock_receivers(self) -> set[str]:
        """Receivers bound from Lock/RLock/Condition/Semaphore
        constructors (C1/C2 guard detection)."""
        return self._cache("lock_recv",
                           lambda: self._ctor_bound_keys(_LOCK_CTORS))

    def outer_func_of_line(self) -> dict[int, str]:
        """lineno -> OUTERMOST enclosing function name (the legacy R3
        allowlist semantics: the first walk claim wins, which is the
        outer def)."""
        def build():
            out: dict[int, str] = {}
            defs = self.of(ast.FunctionDef, ast.AsyncFunctionDef)

            def depth(d):
                return sum(1 for _ in self.ancestors(d))

            for fn in sorted(defs, key=lambda d: (depth(d), d.lineno)):
                for child in ast.walk(fn):
                    if hasattr(child, "lineno"):
                        out.setdefault(child.lineno, fn.name)
            return out
        return self._cache("func_of_line", build)

    def is_lockish(self, expr) -> bool:
        """Whether a with-item context expression looks like a lock:
        bound from a Lock-family constructor in this file, or named
        like one (``...lock``/``...cond``/``...mutex``)."""
        key = _recv_key(expr)
        if key is None:
            return False
        if key in self.lock_receivers():
            return True
        leaf = key.split(".")[-1].lower()
        return any(s in leaf for s in ("lock", "cond", "mutex"))

    def lock_guarded(self, node) -> bool:
        """Whether `node` sits lexically inside a ``with <lock>:``."""
        for anc in self.ancestors(node):
            if isinstance(anc, (ast.With, ast.AsyncWith)):
                if any(self.is_lockish(item.context_expr)
                       for item in anc.items):
                    return True
        return False


# ------------------------------------------------------------------ rules
class Rule:
    """One pluggable check.  Subclasses set ``id``, ``severity``,
    ``pass_name`` and ``scope_key`` (None = always on) and implement
    :meth:`run` over the shared :class:`FileContext`."""

    id = "R?"
    severity = "error"
    pass_name = "robustness"
    scope_key: str | None = None

    def applies(self, ctx: FileContext) -> bool:
        return self.scope_key is None or bool(ctx.scopes.get(self.scope_key))

    def run(self, ctx: FileContext) -> list[Finding]:  # pragma: no cover
        raise NotImplementedError

    def finding(self, ctx: FileContext, line: int, msg: str) -> Finding:
        return Finding(ctx.relpath, line, self.id, msg, self.severity)


def default_rules() -> list[Rule]:
    """The full registered rule set, one instance per rule id."""
    from . import rules_concurrency, rules_determinism, rules_dispatch, \
        rules_fsseam, rules_robustness

    return (rules_robustness.RULES()
            + rules_concurrency.RULES()
            + rules_dispatch.RULES()
            + rules_determinism.RULES()
            + rules_fsseam.RULES())


LEGACY_RULE_IDS = ("R1", "R2", "R3", "R4", "R5", "R6", "R7", "R8")


# ----------------------------------------------------------------- runner
def check_source(src: str, relpath: str,
                 overrides: dict | None = None,
                 rule_ids: Iterable[str] | None = None,
                 stale_check: bool = False) -> list[Finding]:
    """Lint one source string under `relpath`'s (or the overridden)
    scopes.  Returns the ACTIVE findings (suppressed ones dropped),
    sorted by (line, rule).  `rule_ids` restricts the rule set (the
    legacy shim passes R1–R8); `stale_check` adds S1 findings for
    ``robust: allow`` markers that suppressed nothing (full-repo runs
    only — scope-forced matrix runs would see false stales)."""
    ctx = FileContext(src, relpath, scopes_for(relpath, overrides))
    if ctx.syntax_error is not None:
        e = ctx.syntax_error
        return [Finding(relpath, e.lineno or 0, "R0",
                        f"syntax error: {e.msg}")]
    wanted = None if rule_ids is None else set(rule_ids)
    findings: list[Finding] = []
    for rule in default_rules():
        if wanted is not None and rule.id not in wanted:
            continue
        if not rule.applies(ctx):
            continue
        for f in rule.run(ctx):
            if not ctx.allowed(f.line):
                findings.append(f)
    if stale_check:
        for line in sorted(ctx.allow_lines - ctx.used_allow_lines):
            findings.append(Finding(
                relpath, line, "S1",
                "stale `robust: allow` — this line no longer triggers "
                "any rule; delete the marker (suppressions must not "
                "rot silently)", "warning"))
    findings.sort(key=lambda f: (f.line, f.rule))
    return findings


def iter_package_files(root: str = REPO):
    """(abspath, relpath) for every package .py file, sorted."""
    pkg_root = os.path.join(root, PACKAGE)
    for dirpath, _dirnames, filenames in sorted(os.walk(pkg_root)):
        if "__pycache__" in dirpath:
            continue
        for name in sorted(filenames):
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            yield path, os.path.relpath(path, root)


def default_baseline_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "baseline.json")


def load_baseline(path: str | None) -> list[dict]:
    """The reviewed baseline: ``{"entries": [{path, rule, line,
    reason}, ...]}``.  Every entry MUST carry a non-empty reason — an
    unjustified baseline is just a hidden suppression."""
    if not path or not os.path.exists(path):
        return []
    with open(path) as fh:
        data = json.load(fh)
    entries = data.get("entries", [])
    for e in entries:
        if not str(e.get("reason", "")).strip():
            raise ValueError(
                f"baseline entry without a justification: {e!r} "
                "(every entry needs a one-line reason)")
    return entries


def apply_baseline(findings: list[Finding], entries: list[dict],
                   baseline_path: str) -> list[Finding]:
    """Mark findings matched by baseline entries; append an S2 warning
    for every entry that matched nothing (baseline rot)."""
    used = [False] * len(entries)
    for f in findings:
        for i, e in enumerate(entries):
            if (e.get("path") == f.path and e.get("rule") == f.rule
                    and int(e.get("line", -1)) == f.line):
                f.baselined = True
                f.baseline_reason = str(e.get("reason"))
                used[i] = True
                break
    rel = os.path.relpath(baseline_path, REPO) if baseline_path else "baseline"
    for i, e in enumerate(entries):
        if not used[i]:
            findings.append(Finding(
                rel, 0, "S2",
                f"baseline entry matches no finding and should be "
                f"removed: {e.get('path')}:{e.get('line')} "
                f"{e.get('rule')}", "warning"))
    return findings


def lint_tree(root: str = REPO, baseline_path: str | None = None,
              rule_ids: Iterable[str] | None = None) -> list[Finding]:
    """Full-repo run: every package file, every rule, suppression +
    stale + baseline machinery on.  Returns findings that COUNT
    (baselined ones are marked, not dropped — callers decide)."""
    findings: list[Finding] = []
    for path, rel in iter_package_files(root):
        with open(path) as fh:
            src = fh.read()
        findings.extend(check_source(src, rel, rule_ids=rule_ids,
                                     stale_check=True))
    if baseline_path is None:
        baseline_path = default_baseline_path()
    entries = load_baseline(baseline_path)
    if entries:
        findings = apply_baseline(findings, entries, baseline_path)
    return findings


def failing(findings: list[Finding], fail_on: str = "warning") -> list[Finding]:
    """The findings that make the run fail: at/above the severity
    threshold and not baselined."""
    if fail_on == "never":
        return []
    threshold = SEVERITY_RANK[fail_on]
    return [f for f in findings
            if not f.baselined and SEVERITY_RANK[f.severity] >= threshold]
