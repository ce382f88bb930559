"""Dispatch-hazard rules D1–D3 (JAX-specific, train/search/serve hot
paths).  Every rule pins a pathology this repo has MEASURED on this
host (docs/PARITY.md "Step dispatch & device cache"):

D1  **host-device sync inside a dispatch loop**: ``.item()`` anywhere
    in a loop body, or ``float()``/``int()``/``np.asarray()``/
    ``np.array()``/``jax.device_get()`` applied to a value produced by
    a jitted entry point INSIDE the same loop — each conversion blocks
    the dispatch queue on the device round-trip.  The fixed idiom is
    the PR-4 one: accumulate per-dispatch device values and convert
    once at the epoch boundary.

D2  **compile seam inside a loop body**: a direct ``jax.jit``/
    ``seam_jit``/``instrument_jitted``/``aot_compile`` call lexically
    inside a ``for``/``while`` builds a NEW jitted callable (and its
    first-call compile) per iteration — the 23–55 s compile tax the
    persistent cache exists to kill, re-paid every lap.  Hoist the
    seam call above the loop.

D3  **mixed mesh-commitment into a jitted entry point** (the measured
    17x dispatch-overhead pathology): a loop-carried argument (fed
    back from the jitted call's own result) that is never
    ``jax.device_put``/``place_*``-committed, dispatched alongside a
    committed sibling argument, knocks every call off the C++
    fast path.  Commit the carried state to the mesh before the loop.

D4  **per-request copy on a serving hot path** (serve/ scope only):
    ``np.load``/``np.savez``/``.tobytes()``/``np.array`` (which copies
    unless ``copy=False``) inside a request-handling function — a
    ``do_*``/``_do_*`` method or anything on a ``*Handler*`` class.
    Each is a full-tensor copy (or zlib codec) paid per request; the
    zero-copy wire format (serve/wire.py: ``np.frombuffer`` views in,
    pooled-arena encode out) exists to remove exactly these.  The
    retained npz fallback lane carries ``# robust: allow``.
"""

from __future__ import annotations

import ast

from .engine import Finding, FileContext, Rule

#: the compile-seam entry points whose call RESULT is a jitted callable
_JIT_FACTORIES = {"seam_jit", "instrument_jitted", "aot_compile",
                  "_jit_with_trace_counter"}

#: committing calls: the result lives on the mesh
_COMMIT_CALLS = {"device_put"}
_COMMIT_PREFIXES = ("place_", "shard_")

_CONVERTERS = {"float", "int"}
_NP_CONVERTERS = {"asarray", "array"}


def _callee_name(call: ast.Call) -> str | None:
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


def _is_jit_factory_call(call: ast.Call) -> bool:
    name = _callee_name(call)
    if name in _JIT_FACTORIES:
        return True
    if name == "jit":  # jax.jit(...)
        f = call.func
        return isinstance(f, ast.Attribute) \
            and isinstance(f.value, ast.Name) and f.value.id == "jax"
    # make_*step* factories (make_train_step, make_tta_step, ...)
    return bool(name and name.startswith("make_") and "step" in name)


def _is_commit_call(call: ast.Call) -> bool:
    name = _callee_name(call)
    if name in _COMMIT_CALLS:
        return True
    return bool(name and name.startswith(_COMMIT_PREFIXES))


def _base_name(expr) -> str | None:
    """``metrics['loss']`` / ``state.params`` -> the base Name."""
    while isinstance(expr, (ast.Subscript, ast.Attribute)):
        expr = expr.value
    return expr.id if isinstance(expr, ast.Name) else None


def _target_names(node: ast.Assign) -> set[str]:
    out: set[str] = set()
    for tgt in node.targets:
        elts = tgt.elts if isinstance(tgt, (ast.Tuple, ast.List)) else [tgt]
        for e in elts:
            if isinstance(e, ast.Name):
                out.add(e.id)
    return out


def _function_units(ctx: FileContext):
    """Analysis units: each function def, plus the module top level
    (nodes not inside any function)."""
    units: dict[int, list[ast.AST]] = {}
    keys: dict[int, ast.AST | None] = {}
    for node in ctx.nodes:
        fn = ctx.enclosing_function(node)
        fid = id(fn) if fn is not None else 0
        units.setdefault(fid, []).append(node)
        keys.setdefault(fid, fn)
    return [(keys[fid], nodes) for fid, nodes in units.items()]


class _FunctionFacts:
    """Per-function name classification shared by D1 and D3: which
    names hold jitted callables, which hold mesh-committed values."""

    def __init__(self, nodes: list[ast.AST]):
        self.jitted: set[str] = set()
        self.committed: set[str] = set()
        self.assigns = [n for n in nodes if isinstance(n, ast.Assign)]
        changed = True
        while changed:
            changed = False
            for node in self.assigns:
                value = node.value
                names = _target_names(node)
                if isinstance(value, ast.Call):
                    if _is_jit_factory_call(value) \
                            and not names <= self.jitted:
                        self.jitted |= names
                        changed = True
                    if _is_commit_call(value) \
                            and not names <= self.committed:
                        self.committed |= names
                        changed = True
                # commitment propagates through slicing/attribute
                # access of a committed base (idx = index_dev[e])
                base = _base_name(value)
                if base in self.committed and not names <= self.committed:
                    self.committed |= names
                    changed = True


class HostSyncInDispatchLoop(Rule):
    id = "D1"
    severity = "warning"
    pass_name = "dispatch"
    scope_key = "dispatch"

    def run(self, ctx: FileContext) -> list[Finding]:
        out: list[Finding] = []
        for fn, nodes in _function_units(ctx):
            facts = _FunctionFacts(nodes)
            # names produced by a jitted call, per producing loop
            produced_in_loop: dict[int, set[str]] = {}
            for node in facts.assigns:
                if isinstance(node.value, ast.Call):
                    callee = _base_name(node.value.func) \
                        if not isinstance(node.value.func, ast.Name) \
                        else node.value.func.id
                    if callee in facts.jitted:
                        loop = ctx.enclosing_loop(node)
                        if loop is not None:
                            produced_in_loop.setdefault(
                                id(loop), set()).update(_target_names(node))
            for call in (n for n in nodes if isinstance(n, ast.Call)):
                loop = ctx.enclosing_loop(call)
                if loop is None:
                    continue
                f = call.func
                if isinstance(f, ast.Attribute) and f.attr == "item":
                    out.append(self.finding(
                        ctx, call.lineno,
                        ".item() inside a dispatch loop — a per-"
                        "iteration host-device sync that stalls the "
                        "dispatch queue; accumulate on device and "
                        "convert once at the loop boundary"))
                    continue
                # conversions of values a jitted call produced in the
                # same loop — the per-dispatch readback shape
                device_names = set()
                cur = loop
                while cur is not None:
                    device_names |= produced_in_loop.get(id(cur), set())
                    cur = ctx.enclosing_loop(cur)
                arg_base = _base_name(call.args[0]) if call.args else None
                if arg_base is None or arg_base not in device_names:
                    continue
                conv = None
                if isinstance(f, ast.Name) and f.id in _CONVERTERS:
                    conv = f.id
                elif isinstance(f, ast.Attribute) \
                        and isinstance(f.value, ast.Name):
                    if f.value.id == "np" and f.attr in _NP_CONVERTERS:
                        conv = f"np.{f.attr}"
                    elif f.value.id == "jax" and f.attr == "device_get":
                        conv = "jax.device_get"
                if conv:
                    out.append(self.finding(
                        ctx, call.lineno,
                        f"{conv}() on '{arg_base}' (a jitted-call "
                        "result) inside the dispatch loop that produced "
                        "it — a per-dispatch host-device sync; sum on "
                        "device or convert once at the epoch boundary "
                        "(the PR-4 fix)"))
        return out


class JitInLoop(Rule):
    id = "D2"
    severity = "warning"
    pass_name = "dispatch"
    scope_key = "dispatch"

    def run(self, ctx: FileContext) -> list[Finding]:
        out: list[Finding] = []
        for call in ctx.of(ast.Call):
            if not _is_jit_factory_call(call):
                continue
            name = _callee_name(call)
            if name and name.startswith("make_"):
                continue  # step factories are cheap closures; the jit
                #           happens inside them, at their (linted) site
            if ctx.enclosing_loop(call) is not None:
                out.append(self.finding(
                    ctx, call.lineno,
                    f"compile seam call ({name}) inside a loop body — "
                    "builds a fresh jitted callable (and pays its "
                    "first-call compile) every iteration; hoist it "
                    "above the loop"))
        return out


class MixedCommitDispatch(Rule):
    id = "D3"
    severity = "warning"
    pass_name = "dispatch"
    scope_key = "dispatch"

    def run(self, ctx: FileContext) -> list[Finding]:
        out: list[Finding] = []
        for fn, nodes in _function_units(ctx):
            facts = _FunctionFacts(nodes)
            if not facts.jitted:
                continue
            for node in facts.assigns:
                call = node.value
                if not isinstance(call, ast.Call):
                    continue
                callee = call.func.id if isinstance(call.func, ast.Name) \
                    else _base_name(call.func)
                if callee not in facts.jitted:
                    continue
                if ctx.enclosing_loop(node) is None:
                    continue
                arg_names = {a.id for a in call.args
                             if isinstance(a, ast.Name)}
                carried = arg_names & _target_names(node)
                committed_args = arg_names & facts.committed
                uncommitted_carried = carried - facts.committed
                if committed_args and uncommitted_carried:
                    missing = ", ".join(sorted(uncommitted_carried))
                    out.append(self.finding(
                        ctx, node.lineno,
                        f"jitted call mixes mesh-committed arguments "
                        f"({', '.join(sorted(committed_args))}) with the "
                        f"uncommitted loop-carried state '{missing}' — "
                        "the measured 17x dispatch-overhead pathology "
                        "(docs/PARITY.md): jax.device_put the "
                        "carried state onto the mesh before the loop"))
        return out


#: numpy calls that are a per-request full-copy (or codec) by nature
_D4_NP_CALLS = {"load", "savez", "savez_compressed"}


class PerRequestCopy(Rule):
    id = "D4"
    severity = "warning"
    pass_name = "dispatch"
    scope_key = "serve"

    @staticmethod
    def _is_request_handler(ctx: FileContext, fn) -> bool:
        """Request-handling unit: a ``do_*``/``_do_*`` function, or any
        method of a ``*Handler*`` class (the http.server idiom — helper
        methods like ``_parse_images`` are the same hot path)."""
        name = getattr(fn, "name", "")
        if name.startswith(("do_", "_do_")):
            return True
        cls = ctx.enclosing(fn, (ast.ClassDef,))
        return cls is not None and "Handler" in cls.name

    @staticmethod
    def _copy_pattern(call: ast.Call) -> str | None:
        f = call.func
        if not isinstance(f, ast.Attribute):
            return None
        if f.attr == "tobytes":
            return ".tobytes()"
        if isinstance(f.value, ast.Name) and f.value.id == "np":
            if f.attr in _D4_NP_CALLS:
                return f"np.{f.attr}"
            if f.attr == "array":
                for kw in call.keywords:
                    if kw.arg == "copy" \
                            and isinstance(kw.value, ast.Constant) \
                            and kw.value.value is False:
                        return None  # an explicit view, not a copy
                return "np.array"
        return None

    def run(self, ctx: FileContext) -> list[Finding]:
        out: list[Finding] = []
        for call in ctx.of(ast.Call):
            fn = ctx.enclosing_function(call)
            if fn is None or not self._is_request_handler(ctx, fn):
                continue
            pat = self._copy_pattern(call)
            if pat is None:
                continue
            out.append(self.finding(
                ctx, call.lineno,
                f"{pat} inside request handler '{fn.name}' — a full "
                "per-request tensor copy (or codec) on the serving hot "
                "path; use the zero-copy wire format (serve/wire.py: "
                "np.frombuffer views in, pooled-arena encode out) or "
                "mark the legacy fallback lane `robust: allow`"))
        return out


def RULES() -> list[Rule]:
    return [HostSyncInDispatchLoop(), JitInLoop(), MixedCommitDispatch(),
            PerRequestCopy()]
