"""The six repo-specific contract checkers.

Each checker is a small class with ``rule`` (stable id), ``name`` (slug)
and ``run(project) -> [Finding]``.  All analysis is purely syntactic
(``ast``) plus the comment map — nothing here imports the checked code.

Rule catalogue (see docs/CONTRACTS.md for the long form):

  CL001 ladder-discipline   batched kernel entrypoints may only be called
                            from registered DegradationLadder launch sites
  CL002 integrity-protocol  plane getters must stamp plane_checksum and
                            account bytes through PlaneMemoryManager
  CL003 lock-discipline     fields annotated ``# guarded-by: _lock`` are
                            only touched under ``with self._lock``
  CL004 precision-contract  raw float32 casts in core/ and kernels/ must
                            go through the centralized widening helpers
  CL005 trace-safety        no host control flow on traced values, no
                            nondeterminism in kernel bodies / jitted fns
  CL006 counter-registration every counter key written by the service is
                            declared in COUNTER_REGISTRY
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set, Tuple

from .engine import (Finding, ModuleInfo, Project, collect_registry,
                     dotted_name, enclosing_scopes, qualnames)

FUNC_DEFS = (ast.FunctionDef, ast.AsyncFunctionDef)


def _finding(checker, mod: ModuleInfo, node: ast.AST, message: str,
             context: str = "") -> Finding:
    line = getattr(node, "lineno", 0)
    col = getattr(node, "col_offset", 0)
    return Finding(rule=checker.rule, name=checker.name, path=mod.path,
                   line=line, col=col, message=message, context=context,
                   snippet=mod.line(line))


def _context_of(scopes, quals, node) -> str:
    for s in reversed(scopes.get(node, [])):
        q = quals.get(s)
        if q:
            return q
    return "<module>"


# ---------------------------------------------------------------------------
# CL001 · ladder discipline
# ---------------------------------------------------------------------------

class LadderDisciplineChecker:
    """Batched kernel entrypoints (``*_batched*``) reached from serving
    code must be wrapped in a rung list executed by
    ``DegradationLadder.execute``.  Statically we enforce the registry
    form of that contract: every call site must be lexically inside a
    function listed in ``LADDER_LAUNCH_SITES`` (serve/prune_service.py),
    whose entries are by construction rung builders handed to
    ``self.ladder.execute``."""

    rule = "CL001"
    name = "ladder-discipline"

    SCOPE = ("repro/serve/",)
    SCOPE_FILES = ("repro/core/flow.py",)
    REGISTRY = "LADDER_LAUNCH_SITES"

    def _in_scope(self, path: str) -> bool:
        return any(s in path for s in self.SCOPE) or \
            any(path.endswith(f) for f in self.SCOPE_FILES)

    def run(self, project: Project) -> List[Finding]:
        registry = collect_registry(project, self.REGISTRY) or set()
        findings: List[Finding] = []
        for mod in project.modules:
            if not self._in_scope(mod.path):
                continue
            quals = qualnames(mod.tree)
            scopes = enclosing_scopes(mod.tree)
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                callee = dotted_name(node.func)
                if callee is None or "_batched" not in callee.split(".")[-1]:
                    continue
                stack = scopes.get(node, [])
                allowed = any(quals.get(s) in registry
                              for s in stack if isinstance(s, FUNC_DEFS))
                if allowed:
                    continue
                ctx = _context_of(scopes, quals, node)
                findings.append(_finding(
                    self, mod, node,
                    f"direct call to batched kernel entrypoint '{callee}' "
                    f"outside a registered DegradationLadder launch site "
                    f"(add the enclosing method to {self.REGISTRY} only if "
                    f"it builds rungs for DegradationLadder.execute)",
                    ctx))
        return findings


# ---------------------------------------------------------------------------
# CL002 · integrity protocol
# ---------------------------------------------------------------------------

class IntegrityProtocolChecker:
    """Every plane family and every plane getter in device_stats.py joins
    the integrity protocol: the ``self._stores`` family map must match the
    ``PLANE_FAMILIES`` registry, and each getter (``get`` / ``*_plane``)
    must transitively reach a ``plane_checksum`` stamp and a
    ``PlaneMemoryManager`` byte-accounting call."""

    rule = "CL002"
    name = "integrity-protocol"

    FILE_SUFFIX = "device_stats.py"
    REGISTRY = "PLANE_FAMILIES"
    CACHE_CLASS = "DeviceStatsCache"

    def run(self, project: Project) -> List[Finding]:
        findings: List[Finding] = []
        for mod in project.modules:
            if not mod.path.endswith(self.FILE_SUFFIX):
                continue
            cls = next((n for n in ast.walk(mod.tree)
                        if isinstance(n, ast.ClassDef)
                        and n.name == self.CACHE_CLASS), None)
            if cls is None:
                continue
            findings.extend(self._check_families(mod, cls))
            findings.extend(self._check_getters(mod, cls))
        return findings

    # -- family registry parity ------------------------------------------

    def _check_families(self, mod: ModuleInfo,
                        cls: ast.ClassDef) -> List[Finding]:
        registry = collect_registry(Project([mod]), self.REGISTRY)
        stores_node, store_keys = None, None
        for node in ast.walk(cls):
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                tgt = dotted_name(node.targets[0])
                if tgt == "self._stores" and isinstance(node.value, ast.Dict):
                    stores_node = node
                    store_keys = {k.value for k in node.value.keys
                                  if isinstance(k, ast.Constant)
                                  and isinstance(k.value, str)}
        out: List[Finding] = []
        if registry is None:
            out.append(_finding(
                self, mod, cls,
                f"module does not declare the {self.REGISTRY} plane-family "
                f"registry the integrity protocol is keyed on",
                self.CACHE_CLASS))
            return out
        if stores_node is None or store_keys is None:
            return out
        ctx = f"{self.CACHE_CLASS}.__init__"
        for fam in sorted(store_keys - registry):
            out.append(_finding(
                self, mod, stores_node,
                f"plane family '{fam}' in self._stores is not declared in "
                f"{self.REGISTRY} — new families MUST join the integrity "
                f"protocol (ROADMAP degradation contract)", ctx))
        for fam in sorted(registry - store_keys):
            out.append(_finding(
                self, mod, stores_node,
                f"{self.REGISTRY} declares family '{fam}' but self._stores "
                f"has no such store", ctx))
        return out

    # -- getter protocol coverage ----------------------------------------

    def _check_getters(self, mod: ModuleInfo,
                       cls: ast.ClassDef) -> List[Finding]:
        methods = {n.name: n for n in cls.body if isinstance(n, FUNC_DEFS)}
        module_funcs = {n.name for n in mod.tree.body
                        if isinstance(n, FUNC_DEFS)}

        def calls_in(fn: ast.AST) -> Set[str]:
            out = set()
            for node in ast.walk(fn):
                if isinstance(node, ast.Call):
                    d = dotted_name(node.func)
                    if d:
                        out.add(d)
            return out

        def reachable_calls(fn_name: str) -> Set[str]:
            seen_fns: Set[str] = set()
            calls: Set[str] = set()
            stack = [fn_name]
            while stack:
                cur = stack.pop()
                if cur in seen_fns or cur not in methods:
                    continue
                seen_fns.add(cur)
                for d in calls_in(methods[cur]):
                    calls.add(d)
                    if d.startswith("self."):
                        stack.append(d.split(".")[1])
                    elif "." not in d and d in module_funcs:
                        # module-level helper: include its calls directly
                        helper = next(n for n in mod.tree.body
                                      if isinstance(n, FUNC_DEFS)
                                      and n.name == d)
                        calls.update(calls_in(helper))
            return calls

        out: List[Finding] = []
        for name, fn in sorted(methods.items()):
            if not (name == "get" or name.endswith("_plane")):
                continue
            calls = reachable_calls(name)
            stamps = any(d.split(".")[-1] == "plane_checksum" for d in calls)
            accounts = any(
                d in ("self._admit", "self._touch")
                or (("memory." in d or d.startswith("memory."))
                    and d.split(".")[-1] in ("admit", "touch"))
                for d in calls)
            ctx = f"{self.CACHE_CLASS}.{name}"
            if not stamps:
                out.append(_finding(
                    self, mod, fn,
                    f"plane getter '{name}' never reaches a plane_checksum "
                    f"stamp — staged planes must carry an integrity "
                    f"checksum", ctx))
            if not accounts:
                out.append(_finding(
                    self, mod, fn,
                    f"plane getter '{name}' never accounts bytes through "
                    f"PlaneMemoryManager (self._admit/self._touch)", ctx))
        return out


# ---------------------------------------------------------------------------
# CL003 · lock discipline
# ---------------------------------------------------------------------------

class LockDisciplineChecker:
    """Fields annotated ``# guarded-by: _lock`` on their ``self.X = ...``
    declaration may only be read or written (a) lexically inside a
    ``with self._lock`` block — including functions *defined* inside one,
    which covers the staging closures — or (b) in a private method whose
    in-class call sites are all themselves lock-safe (computed to a fixed
    point).  ``__init__`` is exempt: the object is not shared yet."""

    rule = "CL003"
    name = "lock-discipline"

    ANNOTATION = re.compile(r"guarded-by:\s*_lock")
    LOCK_EXPR = "self._lock"

    def run(self, project: Project) -> List[Finding]:
        findings: List[Finding] = []
        for mod in project.modules:
            if "guarded-by" not in mod.source:
                continue
            for cls in ast.walk(mod.tree):
                if isinstance(cls, ast.ClassDef):
                    findings.extend(self._check_class(mod, cls))
        return findings

    def _guarded_fields(self, mod: ModuleInfo, cls: ast.ClassDef) -> Set[str]:
        guarded: Set[str] = set()
        for node in ast.walk(cls):
            targets: List[ast.AST] = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
                targets = [node.target]
            for t in targets:
                if isinstance(t, ast.Attribute) and \
                        isinstance(t.value, ast.Name) and t.value.id == "self":
                    for ln in (t.lineno, t.lineno - 1):
                        comment = mod.comments.get(ln, "")
                        if self.ANNOTATION.search(comment):
                            guarded.add(t.attr)
        return guarded

    def _is_lock_with(self, node: ast.With) -> bool:
        return any(dotted_name(item.context_expr) == self.LOCK_EXPR
                   for item in node.items)

    def _check_class(self, mod: ModuleInfo, cls: ast.ClassDef) -> List[Finding]:
        guarded = self._guarded_fields(mod, cls)
        if not guarded:
            return []

        methods = [n for n in cls.body if isinstance(n, FUNC_DEFS)]
        # accesses[m] -> [(node, field, inlock)], callsites[callee] -> [(m, inlock)]
        accesses: Dict[str, List[Tuple[ast.AST, str, bool]]] = {}
        callsites: Dict[str, List[Tuple[str, bool]]] = {}

        def visit(node: ast.AST, method: str, inlock: bool) -> None:
            if isinstance(node, ast.With) and self._is_lock_with(node):
                inlock = True
            if isinstance(node, ast.Attribute) and \
                    isinstance(node.value, ast.Name) and \
                    node.value.id == "self" and node.attr in guarded:
                accesses.setdefault(method, []).append(
                    (node, node.attr, inlock))
            if isinstance(node, ast.Call):
                d = dotted_name(node.func)
                if d and d.startswith("self.") and d.count(".") == 1:
                    callsites.setdefault(d.split(".")[1], []).append(
                        (method, inlock))
            for child in ast.iter_child_nodes(node):
                # functions defined inside a locked region run under it
                visit(child, method, inlock)

        for m in methods:
            if m.name == "__init__":
                continue
            for child in ast.iter_child_nodes(m):
                visit(child, m.name, False)

        # fixed point: private methods whose every in-class call site is
        # lock-safe are themselves lock-safe
        locked_only = {m.name for m in methods
                       if m.name.startswith("_") and callsites.get(m.name)}
        changed = True
        while changed:
            changed = False
            for name in sorted(locked_only):
                for caller, inlock in callsites.get(name, []):
                    if not inlock and caller not in locked_only:
                        locked_only.discard(name)
                        changed = True
                        break

        out: List[Finding] = []
        for method, recs in sorted(accesses.items()):
            if method in locked_only:
                continue
            for node, field, inlock in recs:
                if inlock:
                    continue
                out.append(_finding(
                    self, mod, node,
                    f"field '{field}' is declared guarded-by _lock but is "
                    f"accessed outside a `with {self.LOCK_EXPR}` scope "
                    f"(method '{method}' is reachable without the lock)",
                    f"{cls.name}.{method}"))
        return out


# ---------------------------------------------------------------------------
# CL004 · precision contract
# ---------------------------------------------------------------------------

class PrecisionContractChecker:
    """f64 -> f32 narrowing of stats or bounds must go through the
    centralized widening helpers (``round_down_f32`` / ``round_up_f32`` /
    ``cast_stats_f32`` in core/device_stats.py), which guarantee the
    paper's never-prune-a-match direction.  Raw ``.astype(float32)`` and
    ``float32(...)`` calls elsewhere in core/ and kernels/ are errors.
    Exact casts of boolean masks (comparisons, logical ops) are allowed
    structurally; constants like ``np.float32(-np.inf)`` are exact."""

    rule = "CL004"
    name = "precision-contract"

    SCOPE = ("repro/core/", "repro/kernels/")
    # the widening-helper home itself
    EXEMPT_SUFFIXES = ("core/device_stats.py",)

    F32 = ("np.float32", "jnp.float32", "numpy.float32", "jax.numpy.float32")
    WIDENING = ("round_down_f32", "round_up_f32", "cast_stats_f32",
                "cast_bounds_f32")

    def _bool_expr(self, n: ast.AST) -> bool:
        if isinstance(n, (ast.Compare, ast.BoolOp)):
            return True
        if isinstance(n, ast.BinOp):
            return self._bool_expr(n.left) or self._bool_expr(n.right)
        if isinstance(n, ast.UnaryOp):
            return self._bool_expr(n.operand)
        if isinstance(n, ast.Call):
            d = (dotted_name(n.func) or "").split(".")[-1]
            return d in ("logical_and", "logical_or", "logical_not",
                         "logical_xor", "isnan", "isinf", "isfinite",
                         "isclose", "equal", "not_equal") \
                or d in self.WIDENING
        return False

    def _const_like(self, n: ast.AST) -> bool:
        if isinstance(n, ast.Constant):
            return True
        if isinstance(n, ast.UnaryOp):
            return self._const_like(n.operand)
        d = dotted_name(n)
        if d is not None and d.split(".")[-1] in ("inf", "nan", "e", "pi"):
            return True
        return False

    def run(self, project: Project) -> List[Finding]:
        findings: List[Finding] = []
        for mod in project.modules:
            if not any(s in mod.path for s in self.SCOPE):
                continue
            if any(mod.path.endswith(s) for s in self.EXEMPT_SUFFIXES):
                continue
            quals = qualnames(mod.tree)
            scopes = enclosing_scopes(mod.tree)
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                hit = self._flag(node)
                if hit is None:
                    continue
                ctx = _context_of(scopes, quals, node)
                findings.append(_finding(
                    self, mod, node,
                    f"raw float32 cast ({hit}) outside the centralized "
                    f"widening helpers — use round_down_f32 / round_up_f32 "
                    f"/ cast_stats_f32 from core.device_stats so the "
                    f"narrowing direction is explicit", ctx))
        return findings

    def _flag(self, node: ast.Call) -> Optional[str]:
        func = node.func
        # X.astype(float32)
        if isinstance(func, ast.Attribute) and func.attr == "astype" \
                and len(node.args) == 1:
            arg = node.args[0]
            d = dotted_name(arg)
            is_f32 = (d in self.F32) or (
                isinstance(arg, ast.Constant) and arg.value == "float32")
            if is_f32 and not self._bool_expr(func.value):
                return ".astype(float32)"
            return None
        # float32(X) with non-constant X
        d = dotted_name(func)
        if d in self.F32 and node.args and \
                not all(self._const_like(a) for a in node.args):
            return f"{d}(...)"
        return None


# ---------------------------------------------------------------------------
# CL005 · trace safety
# ---------------------------------------------------------------------------

class TraceSafetyChecker:
    """Inside Pallas kernel bodies and jitted functions: no Python
    ``if``/``while`` on traced parameters (static_argnames are exempt),
    no ``float()``/``int()``/``bool()`` on traced values, no ``.item()``,
    no nondeterminism (``time.*``, unseeded ``np.random.*``), and no
    profiler spans (``span``, ``TraceAnnotation``, ``jax.profiler.*``).

    Traced functions are found syntactically: defs whose name ends in
    ``_kernel``, defs passed (by name) as the first argument of
    ``pl.pallas_call`` or wrapped by ``jax.jit(...)`` /
    ``jax.jit(shard_map(...))``, and defs decorated with ``jax.jit`` or
    ``functools.partial(jax.jit, static_argnames=...)``."""

    rule = "CL005"
    name = "trace-safety"

    SCOPE = ("repro/",)
    JIT = ("jax.jit", "jit")
    PARTIAL = ("functools.partial", "partial")
    SHARD = ("shard_map", "jax.experimental.shard_map.shard_map")
    NONDET_PREFIXES = ("time.", "np.random.", "numpy.random.", "random.")
    NONDET_ALLOWED = ("default_rng", "Generator", "SeedSequence", "PRNGKey")
    SPAN_CALLS = ("span", "spans.span", "TraceAnnotation")

    # -- traced-function discovery ---------------------------------------

    def _static_names(self, call: ast.Call,
                      params: List[str]) -> Set[str]:
        out: Set[str] = set()
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                if isinstance(kw.value, ast.Constant) and \
                        isinstance(kw.value.value, str):
                    out.add(kw.value.value)
                for e in ast.walk(kw.value):
                    if isinstance(e, ast.Constant) and \
                            isinstance(e.value, str):
                        out.add(e.value)
            elif kw.arg == "static_argnums":
                for e in ast.walk(kw.value):
                    if isinstance(e, ast.Constant) and \
                            isinstance(e.value, int) and \
                            0 <= e.value < len(params):
                        out.add(params[e.value])
        return out

    def _jit_decorator(self, dec: ast.AST) -> Optional[ast.Call]:
        """Return the jit Call carrying static_argnames, a bare marker
        Call for plain @jax.jit, or None."""
        if dotted_name(dec) in self.JIT:
            return ast.Call(func=dec, args=[], keywords=[])
        if isinstance(dec, ast.Call):
            d = dotted_name(dec.func)
            if d in self.JIT:
                return dec
            if d in self.PARTIAL and dec.args and \
                    dotted_name(dec.args[0]) in self.JIT:
                return dec
        return None

    def _collect_traced(self, mod: ModuleInfo
                        ) -> List[Tuple[ast.AST, Set[str]]]:
        defs: Dict[str, List[ast.AST]] = {}
        for node in ast.walk(mod.tree):
            if isinstance(node, FUNC_DEFS):
                defs.setdefault(node.name, []).append(node)

        traced: Dict[ast.AST, Set[str]] = {}

        def mark(fn: ast.AST, statics: Set[str]) -> None:
            traced.setdefault(fn, set()).update(statics)

        def mark_name(name: Optional[str], statics: Set[str]) -> None:
            for fn in defs.get(name or "", []):
                mark(fn, statics)

        def kernel_statics(fn: ast.AST) -> Set[str]:
            # Pallas kernel bodies take Refs positionally; keyword-only
            # params are compile-time config bound via functools.partial.
            return {a.arg for a in fn.args.kwonlyargs}

        for name, fns in defs.items():
            for fn in fns:
                if name.endswith("_kernel"):
                    mark(fn, kernel_statics(fn))
                params = [a.arg for a in fn.args.args]
                for dec in fn.decorator_list:
                    jit = self._jit_decorator(dec)
                    if jit is not None:
                        mark(fn, self._static_names(jit, params))

        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            d = dotted_name(node.func)
            last = (d or "").split(".")[-1]
            if last == "pallas_call" and node.args:
                target = dotted_name(node.args[0])
                for fn in defs.get(target or "", []):
                    mark(fn, kernel_statics(fn))
            elif d in self.JIT and node.args:
                inner = node.args[0]
                target = dotted_name(inner)
                if target is None and isinstance(inner, ast.Call) and \
                        dotted_name(inner.func) in self.SHARD and inner.args:
                    target = dotted_name(inner.args[0])
                if target is not None:
                    statics: Set[str] = set()
                    for fn in defs.get(target, []):
                        params = [a.arg for a in fn.args.args]
                        statics = self._static_names(node, params)
                    mark_name(target, statics)
        return list(traced.items())

    # -- per-function checks ---------------------------------------------

    def _roots(self, expr: ast.AST) -> Set[str]:
        """Root Name ids an expression reads, excluding reads through
        shape/dtype-like attributes (those are static under trace)."""
        out: Set[str] = set()
        for node in ast.walk(expr):
            if isinstance(node, ast.Name) and \
                    isinstance(node.ctx, ast.Load):
                out.add(node.id)
        # drop roots only reached through static attrs
        for node in ast.walk(expr):
            if isinstance(node, ast.Attribute) and \
                    node.attr in ("shape", "ndim", "size", "dtype"):
                for sub in ast.walk(node.value):
                    if isinstance(sub, ast.Name):
                        out.discard(sub.id)
        return out

    def _is_none_check(self, test: ast.AST) -> bool:
        return (isinstance(test, ast.Compare)
                and all(isinstance(op, (ast.Is, ast.IsNot))
                        for op in test.ops)
                and all(isinstance(c, ast.Constant) and c.value is None
                        for c in test.comparators))

    def run(self, project: Project) -> List[Finding]:
        findings: List[Finding] = []
        for mod in project.modules:
            if not any(s in mod.path for s in self.SCOPE):
                continue
            quals = qualnames(mod.tree)
            for fn, statics in self._collect_traced(mod):
                findings.extend(
                    self._check_fn(mod, fn, statics, quals.get(fn, fn.name)))
        findings.sort(key=lambda f: (f.path, f.line, f.col))
        # a def can be discovered twice (name + pallas_call ref): dedup
        seen, out = set(), []
        for f in findings:
            key = (f.path, f.line, f.col, f.message)
            if key not in seen:
                seen.add(key)
                out.append(f)
        return out

    def _check_fn(self, mod: ModuleInfo, fn: ast.AST, statics: Set[str],
                  ctx: str) -> List[Finding]:
        params = {a.arg for a in fn.args.args} | \
            {a.arg for a in fn.args.kwonlyargs}
        traced_params = params - statics - {"self"}
        out: List[Finding] = []
        for node in ast.walk(fn):
            if isinstance(node, (ast.If, ast.While)) and \
                    not self._is_none_check(node.test):
                hot = self._roots(node.test) & traced_params
                if hot:
                    kind = "if" if isinstance(node, ast.If) else "while"
                    out.append(_finding(
                        self, mod, node,
                        f"Python `{kind}` on traced value(s) "
                        f"{sorted(hot)} inside a traced function — use "
                        f"jnp.where/lax.cond or declare the argument in "
                        f"static_argnames", ctx))
            if isinstance(node, ast.Call):
                d = dotted_name(node.func)
                if d in ("float", "int", "bool") and node.args:
                    hot = self._roots(node.args[0]) & traced_params
                    if hot:
                        out.append(_finding(
                            self, mod, node,
                            f"`{d}()` forces a concrete value from traced "
                            f"value(s) {sorted(hot)} — this fails under "
                            f"jit; keep it an array op", ctx))
                if isinstance(node.func, ast.Attribute) and \
                        node.func.attr == "item" and not node.args:
                    out.append(_finding(
                        self, mod, node,
                        "`.item()` inside a traced function forces a "
                        "device sync / concretization error under jit",
                        ctx))
                if d is not None and (
                        d in self.SPAN_CALLS
                        or d.endswith(".TraceAnnotation")
                        or d.startswith("jax.profiler.")):
                    out.append(_finding(
                        self, mod, node,
                        f"profiler span '{d}' inside a traced function — "
                        f"it runs once at trace time, not per launch; "
                        f"open host spans in the wrapper that calls it",
                        ctx))
                if d is not None:
                    for prefix in self.NONDET_PREFIXES:
                        if d.startswith(prefix) and \
                                d.split(".")[-1] not in self.NONDET_ALLOWED:
                            out.append(_finding(
                                self, mod, node,
                                f"nondeterministic call '{d}' inside a "
                                f"traced function — results get baked in "
                                f"at trace time and break retrace "
                                f"reproducibility", ctx))
                            break
        return out


# ---------------------------------------------------------------------------
# CL006 · counter registration
# ---------------------------------------------------------------------------

class CounterRegistrationChecker:
    """Every string key written into a counter store (``*.counters``,
    ``*.resilience``, ``*.integrity``, ``*.technique``), every key of a
    ``new_*_counters()`` definition dict, and every literal technique name
    passed to ``bump(...)`` must be declared in ``COUNTER_REGISTRY``
    (serve/resilience.py), so fleet_summary() can never silently drop a
    counter family."""

    rule = "CL006"
    name = "counter-registration"

    SCOPE = ("repro/serve/",)
    SCOPE_FILES = ("device_stats.py",)
    REGISTRY = "COUNTER_REGISTRY"
    COUNTER_ATTRS = ("counters", "resilience", "integrity", "technique")

    def _in_scope(self, path: str) -> bool:
        return any(s in path for s in self.SCOPE) or \
            any(path.endswith(f) for f in self.SCOPE_FILES)

    def _is_counter_expr(self, node: ast.AST, aliases: Set[str]) -> bool:
        if isinstance(node, ast.Name):
            return node.id in aliases or node.id in self.COUNTER_ATTRS
        d = dotted_name(node)
        return d is not None and d.split(".")[-1] in self.COUNTER_ATTRS

    def run(self, project: Project) -> List[Finding]:
        registry = collect_registry(project, self.REGISTRY)
        findings: List[Finding] = []
        for mod in project.modules:
            if not self._in_scope(mod.path):
                continue
            quals = qualnames(mod.tree)
            scopes = enclosing_scopes(mod.tree)
            for fn_node, keys in self._collect_keys(mod):
                for node, key in keys:
                    if registry is not None and key in registry:
                        continue
                    where = "" if registry is not None else \
                        " (registry not found in the linted tree)"
                    ctx = _context_of(scopes, quals, node)
                    findings.append(_finding(
                        self, mod, node,
                        f"counter key '{key}' is not declared in "
                        f"{self.REGISTRY} (serve/resilience.py){where} — "
                        f"unregistered keys silently vanish from "
                        f"fleet_summary()", ctx))
        return findings

    def _collect_keys(self, mod: ModuleInfo):
        """Yield (scope_node, [(node, key), ...]) per function/module."""
        results = []

        def handle_scope(scope: ast.AST) -> None:
            aliases: Set[str] = set()
            keys: List[Tuple[ast.AST, str]] = []

            def dict_keys(value: ast.AST) -> List[Tuple[ast.AST, str]]:
                out = []
                if isinstance(value, ast.Dict):
                    for k in value.keys:
                        if isinstance(k, ast.Constant) and \
                                isinstance(k.value, str):
                            out.append((k, k.value))
                elif isinstance(value, ast.Call) and \
                        dotted_name(value.func) == "dict":
                    for kw in value.keywords:
                        if kw.arg is not None:
                            out.append((value, kw.arg))
                return out

            def visit(node: ast.AST) -> None:
                if isinstance(node, FUNC_DEFS) and node is not scope:
                    handle_scope(node)
                    return
                if isinstance(node, ast.Assign):
                    # alias: c = self.counters / t = x.technique.setdefault(..)
                    rhs = node.value
                    rhs_counter = self._is_counter_expr(rhs, aliases) or (
                        isinstance(rhs, ast.Call)
                        and isinstance(rhs.func, ast.Attribute)
                        and rhs.func.attr == "setdefault"
                        and self._is_counter_expr(rhs.func.value, aliases))
                    for t in node.targets:
                        if rhs_counter and isinstance(t, ast.Name):
                            aliases.add(t.id)
                        # counter definition dict: x.counters = {...}
                        if isinstance(t, ast.Attribute) and \
                                t.attr in self.COUNTER_ATTRS:
                            keys.extend(dict_keys(rhs))
                targets: List[ast.AST] = []
                if isinstance(node, ast.Assign):
                    targets = list(node.targets)
                elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                    targets = [node.target]
                for t in targets:
                    if isinstance(t, ast.Subscript) and \
                            self._is_counter_expr(t.value, aliases) and \
                            isinstance(t.slice, ast.Constant) and \
                            isinstance(t.slice.value, str):
                        keys.append((t, t.slice.value))
                if isinstance(node, ast.Call):
                    d_attr = node.func if isinstance(node.func, ast.Attribute) \
                        else None
                    if d_attr is not None and d_attr.attr == "setdefault" \
                            and self._is_counter_expr(d_attr.value, aliases) \
                            and node.args and \
                            isinstance(node.args[0], ast.Constant) and \
                            isinstance(node.args[0].value, str):
                        keys.append((node, node.args[0].value))
                    if d_attr is not None and d_attr.attr == "bump" and \
                            node.args and \
                            isinstance(node.args[0], ast.Constant) and \
                            isinstance(node.args[0].value, str):
                        keys.append((node, node.args[0].value))
                # counter-definition factory: def new_*_counters(): return {...}
                if isinstance(node, ast.Return) and node.value is not None \
                        and isinstance(scope, FUNC_DEFS) and \
                        scope.name.endswith("_counters"):
                    keys.extend(dict_keys(node.value))
                for child in ast.iter_child_nodes(node):
                    visit(child)

            for child in ast.iter_child_nodes(scope):
                visit(child)
            results.append((scope, keys))

        handle_scope(mod.tree)
        return results


ALL_CHECKERS = (
    LadderDisciplineChecker(),
    IntegrityProtocolChecker(),
    LockDisciplineChecker(),
    PrecisionContractChecker(),
    TraceSafetyChecker(),
    CounterRegistrationChecker(),
)
