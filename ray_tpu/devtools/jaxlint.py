"""jax-lint: JAX/XLA tracing-safety rules (rule family ``jax``).

Stdlib-only AST analysis riding rtpu-lint's fingerprint/baseline/
``# rtpu-lint: disable=<rule>`` machinery (``lint.py`` runs both rule
families from one CLI). Every rule is a bug this repo actually shipped
and found by hand in post-review:

  closure-captured-array-into-jit
      an array built in an enclosing/module scope referenced FREE
      inside a jitted function — jit bakes it in as a compile-time
      constant (PR 6: the int8 bench closed over the int8 weight, XLA
      constant-folded it to full width and the "int8" timing silently
      streamed full-precision bytes). Pass arrays as jit ARGUMENTS.
  donation-then-read
      an argument at a ``donate_argnums`` position read again after
      the call in the same function — the buffer was donated; the read
      sees freed/aliased memory (PR 6: the dryrun computed its
      reference loss from params the donating step had consumed).
  host-sync-in-hot-path
      ``.item()``, ``float()``/``int()``/``np.asarray`` on a value a
      device program produced, bare ``device_get``, or a python
      ``if``/``while`` branching on a device value, inside a function
      reachable from a declared hot-path root (engine decode tick,
      train step). The intended once-per-chunk sync carries an inline
      allow-comment; everything else serializes the device pipeline.
  unclamped-dynamic-update-slice
      a ``dynamic_update_slice`` start index that is neither constant
      nor visibly clamped — XLA CLAMPS out-of-range starts instead of
      failing, so an unbounded traced start slides the write window
      backwards over valid data (PR 3's verify window needed scratch
      rows past max_len for exactly this reason).
  pallas-shape-rules
      inside a ``pl.pallas_call`` kernel body: reductions without
      ``keepdims=True`` (sub-2D intermediate), ``jnp.arange`` (1D
      iota), a float ``broadcasted_iota``, or ``reshape`` (cross-lane
      relayout) — the classic Mosaic lowering failures PR 6 worked
      around by hand. The interpreter accepts all of them; the chip's
      compiler (tests/test_chip_compile.py) is the real guard.
  rng-reinit-per-mesh
      ``jax.random.PRNGKey`` called inside a mesh context in a
      sharded-equivalence module — with jax<0.5 non-partitionable
      threefry, jitted RNG VALUES depend on out_shardings, so
      equivalence checks must ``device_put`` ONE host init.

``lint_source(source, module, path)`` returns ``lint.Finding`` rows;
module-scoped tables live in ``invariants.py``.
"""

from __future__ import annotations

import ast
import builtins
from typing import Dict, List, Optional, Set, Tuple

from ray_tpu.devtools import invariants as inv
# JAX_RULES is single-sourced in lint.py (the family/baseline machinery
# keys on it); aliased here so rule code and rule registry can't drift.
from ray_tpu.devtools.lint import (Finding, JAX_RULES as RULES, _dotted,
                                   suppressed)

_BUILTINS = set(dir(builtins))


def _snippet(node: ast.AST, limit: int = 48) -> str:
    try:
        text = ast.unparse(node)
    except Exception:  # noqa: BLE001 — diagnostics only
        text = "<expr>"
    return text if len(text) <= limit else text[: limit - 3] + "..."


class _Scope:
    """One lexical scope: its array-ish bindings and local defs."""

    __slots__ = ("node", "bindings", "defs")

    def __init__(self, node):
        self.node = node
        self.bindings: Dict[str, str] = {}   # name -> "array" | "other"
        self.defs: Dict[str, ast.AST] = {}   # name -> FunctionDef


def _is_array_expr(expr: ast.AST) -> bool:
    """Heuristic: does this binding's RHS construct/transform an array?
    Conservative on purpose — only positively-identified arrays flag the
    closure rule, so false positives stay near zero."""
    for sub in ast.walk(expr):
        if not isinstance(sub, ast.Call):
            continue
        dotted = _dotted(sub.func)
        if dotted is None:
            if isinstance(sub.func, ast.Attribute) and \
                    sub.func.attr in inv.ARRAY_FACTORY_SUFFIXES:
                return True
            continue
        if dotted in inv.ARRAY_FACTORY_CALLS:
            return True
        if dotted.startswith(inv.ARRAY_FACTORY_PREFIXES):
            return True
        if dotted.rsplit(".", 1)[-1] in inv.ARRAY_FACTORY_SUFFIXES:
            return True
    return False


def _bound_names(fn) -> Set[str]:
    """Every name bound anywhere inside ``fn`` (params, assignments,
    loop targets, nested defs, imports) — the complement of 'free'."""
    bound: Set[str] = set()
    args = fn.args
    for a in (args.posonlyargs + args.args + args.kwonlyargs):
        bound.add(a.arg)
    if args.vararg:
        bound.add(args.vararg.arg)
    if args.kwarg:
        bound.add(args.kwarg.arg)
    body = fn.body if isinstance(fn.body, list) else [fn.body]
    for stmt in body:
        for sub in ast.walk(stmt):
            if isinstance(sub, ast.Name) and isinstance(
                    sub.ctx, (ast.Store, ast.Del)):
                bound.add(sub.id)
            elif isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                bound.add(sub.name)
                if sub is not fn:
                    a2 = getattr(sub, "args", None)
                    if a2 is not None:
                        for a in (a2.posonlyargs + a2.args
                                  + a2.kwonlyargs):
                            bound.add(a.arg)
            elif isinstance(sub, ast.Lambda):
                for a in (sub.args.posonlyargs + sub.args.args
                          + sub.args.kwonlyargs):
                    bound.add(a.arg)
            elif isinstance(sub, (ast.Import, ast.ImportFrom)):
                for alias in sub.names:
                    bound.add((alias.asname
                               or alias.name).split(".")[0])
            elif isinstance(sub, ast.ExceptHandler) and sub.name:
                bound.add(sub.name)
    return bound


def _refs_name(expr: ast.AST, names: Set[str],
               skip_fetch: bool = True) -> Optional[str]:
    """First dotted read in ``expr`` matching ``names`` (a device-value
    set). Subtrees under a host-fetch call are excluded: the fetch IS
    the sanctioned sync, its result is host data."""
    todo = [expr]
    while todo:
        sub = todo.pop()
        if skip_fetch and isinstance(sub, ast.Call):
            d = _dotted(sub.func)
            if d is not None and d.rsplit(".", 1)[-1] in \
                    inv.HOST_FETCH_SUFFIXES:
                continue  # do not descend into the fetch's operands
        if isinstance(sub, (ast.Attribute, ast.Name)):
            d = _dotted(sub)
            if d is not None:
                for n in names:
                    if d == n or d.startswith(n + "."):
                        return n
        todo.extend(ast.iter_child_nodes(sub))
    return None


class _JaxLinter:
    def __init__(self, module: str, path: str, source: str):
        self.module = module
        self.path = path
        self.lines = source.splitlines()
        self.findings: List[Finding] = []
        self._scope_names: List[str] = []
        # (fn_node, scope_chain, label) — label names the jit site.
        self._jit_targets: List[Tuple[ast.AST, Tuple[_Scope, ...], str]] = []
        self._seen_jit: Set[int] = set()
        self._kernels: List[Tuple[ast.AST, str]] = []
        self._seen_kernels: Set[int] = set()
        self._functions: Dict[str, List[ast.AST]] = {}

    # ------------------------------------------------------------ utils

    def _emit(self, rule: str, node: ast.AST, message: str,
              scope: Optional[str] = None) -> None:
        # A typoed rule id would be filed under the WRONG family by the
        # baseline writer (RULE_FAMILY defaults to concurrency) and
        # become invisible to --family jax — fail at the source.
        assert rule in RULES, f"unregistered jax rule id {rule!r}"
        line = getattr(node, "lineno", 1)
        if suppressed(self.lines, line, rule):
            return
        self.findings.append(Finding(
            rule, self.path, line,
            scope if scope is not None else ".".join(self._scope_names),
            message))

    # ------------------------------------------------------------- walk

    def run(self, tree: Optional[ast.AST] = None) -> List[Finding]:
        if tree is None:
            try:
                tree = ast.parse("\n".join(self.lines),
                                 filename=self.path)
            except SyntaxError:
                return []  # the concurrency family reports this
        module_scope = _Scope(tree)
        self._walk(tree, (module_scope,), mesh_depth=0)
        self._check_jit_targets()
        self._check_kernels()
        if self.module in inv.JAX_HOT_PATH_ROOTS:
            self._check_hot_paths()
        return self.findings

    def _walk(self, node: ast.AST, scopes: Tuple[_Scope, ...],
              mesh_depth: int) -> None:
        scope = scopes[-1]
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scope.defs[child.name] = child
                self._functions.setdefault(child.name, []).append(child)
                self._maybe_decorated_jit(child, scopes)
                self._scope_names.append(child.name)
                self._check_donation_then_read(child)
                self._walk(child, scopes + (_Scope(child),), mesh_depth)
                self._scope_names.pop()
                continue
            if isinstance(child, ast.ClassDef):
                # Python closures skip class scope: class-level array
                # assigns land in the ENCLOSING scope for lookup, which
                # is exactly the "class-level weight" capture case.
                self._scope_names.append(child.name)
                self._walk(child, scopes, mesh_depth)
                self._scope_names.pop()
                continue
            if isinstance(child, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                value = getattr(child, "value", None)
                if value is not None:
                    kind = "array" if _is_array_expr(value) else "other"
                    targets = (child.targets
                               if isinstance(child, ast.Assign)
                               else [child.target])
                    names: List[str] = []
                    for tgt in targets:
                        if isinstance(tgt, ast.Name):
                            names.append(tgt.id)
                        elif isinstance(tgt, (ast.Tuple, ast.List)):
                            names.extend(e.id for e in tgt.elts
                                         if isinstance(e, ast.Name))
                    for n in names:
                        if kind == "array" or n not in scope.bindings:
                            scope.bindings[n] = kind
                self._walk(child, scopes, mesh_depth)
                continue
            if isinstance(child, (ast.With, ast.AsyncWith)):
                d = 0
                for item in child.items:
                    text = _snippet(item.context_expr, 200).lower()
                    if any(m in text for m in inv.MESH_CONTEXT_MARKERS):
                        d = 1
                self._walk(child, scopes, mesh_depth + d)
                continue
            if isinstance(child, ast.Call):
                self._visit_call(child, scopes, mesh_depth)
            self._walk(child, scopes, mesh_depth)

    # ------------------------------------------------------- call rules

    def _visit_call(self, node: ast.Call, scopes: Tuple[_Scope, ...],
                    mesh_depth: int) -> None:
        dotted = _dotted(node.func) or ""
        tail = dotted.rsplit(".", 1)[-1]
        # jit(X) call sites.
        if dotted in ("jax.jit", "jit") and node.args:
            self._note_jit_target(node.args[0], scopes,
                                  f"jax.jit at line {node.lineno}")
        # pallas_call(kernel | partial(kernel, ...), ...).
        if tail == "pallas_call" and node.args:
            self._note_kernel(node.args[0], scopes)
        # Unclamped dynamic_update_slice starts.
        if tail in ("dynamic_update_slice", "dynamic_update_slice_in_dim"):
            self._check_dus(node, tail)
        # PRNGKey inside a mesh context (declared modules only).
        if (tail == "PRNGKey" and mesh_depth > 0
                and self.module in inv.RNG_SINGLE_INIT_MODULES):
            self._emit(
                "rng-reinit-per-mesh", node,
                "jax.random.PRNGKey called inside a mesh context — "
                "sharded-equivalence paths must device_put ONE host "
                "init (jax<0.5 jitted RNG values depend on "
                "out_shardings)")

    def _check_dus(self, node: ast.Call, tail: str) -> None:
        if tail == "dynamic_update_slice":
            if len(node.args) < 3:
                return
            start = node.args[2]
            starts = start.elts if isinstance(start, ast.Tuple) \
                else [start] + list(node.args[3:])
        else:
            if len(node.args) < 3:
                return
            starts = [node.args[2]]
        for s in starts:
            if isinstance(s, ast.Constant):
                continue
            if isinstance(s, ast.UnaryOp) and \
                    isinstance(s.operand, ast.Constant):
                continue
            clamped = False
            for sub in ast.walk(s):
                if isinstance(sub, ast.Call):
                    d = _dotted(sub.func) or ""
                    if d.rsplit(".", 1)[-1] in inv.DUS_CLAMP_CALLS:
                        clamped = True
                        break
            if not clamped:
                self._emit(
                    "unclamped-dynamic-update-slice", node,
                    f"{tail} start '{_snippet(s)}' is neither constant "
                    "nor clamped — XLA CLAMPS out-of-range starts, so "
                    "an unbounded index silently slides the write over "
                    "valid rows; clamp it or document the bound")

    # ------------------------------------------------------ jit targets

    def _maybe_decorated_jit(self, fn, scopes) -> None:
        for dec in fn.decorator_list:
            d = _dotted(dec) or ""
            if d in ("jax.jit", "jit"):
                self._note_jit_target(fn, scopes, f"@{d}")
                return
            if isinstance(dec, ast.Call):
                dc = _dotted(dec.func) or ""
                if dc in ("jax.jit", "jit"):
                    self._note_jit_target(fn, scopes, f"@{dc}(...)")
                    return
                if dc.rsplit(".", 1)[-1] == "partial" and dec.args:
                    inner = _dotted(dec.args[0]) or ""
                    if inner in ("jax.jit", "jit"):
                        self._note_jit_target(fn, scopes,
                                              f"@partial({inner}, ...)")
                        return

    def _note_jit_target(self, target: ast.AST,
                         scopes: Tuple[_Scope, ...], label: str) -> None:
        fn: Optional[ast.AST] = None
        if isinstance(target, (ast.Lambda, ast.FunctionDef,
                               ast.AsyncFunctionDef)):
            fn = target
        elif isinstance(target, ast.Name):
            for scope in reversed(scopes):
                if target.id in scope.defs:
                    fn = scope.defs[target.id]
                    break
        if fn is None or id(fn) in self._seen_jit:
            return
        self._seen_jit.add(id(fn))
        self._jit_targets.append((fn, scopes, label))

    def _check_jit_targets(self) -> None:
        for fn, scopes, label in self._jit_targets:
            bound = _bound_names(fn)
            body = fn.body if isinstance(fn.body, list) else [fn.body]
            flagged: Set[str] = set()
            for stmt in body:
                for sub in ast.walk(stmt):
                    if isinstance(sub, ast.Name) and \
                            isinstance(sub.ctx, ast.Load):
                        name = sub.id
                        if name in bound or name in _BUILTINS or \
                                name in flagged:
                            continue
                        for scope in reversed(scopes):
                            if name in scope.defs:
                                break
                            kind = scope.bindings.get(name)
                            if kind == "array":
                                flagged.add(name)
                                self._emit(
                                    "closure-captured-array-into-jit",
                                    sub,
                                    f"'{name}' is an array from an "
                                    f"enclosing scope captured by a "
                                    f"jitted function ({label}) — jit "
                                    "bakes it in as a constant (the "
                                    "PR 6 int8 bench constant-folded "
                                    "its closed-over weight to full "
                                    "width); pass it as an argument",
                                    scope=self._fn_scope(fn))
                                break
                            if kind is not None:
                                break
                    elif isinstance(sub, ast.Attribute) and \
                            isinstance(sub.value, ast.Name) and \
                            sub.value.id == "self" and \
                            "self" not in bound and \
                            isinstance(sub.ctx, ast.Load) and \
                            inv.ARRAY_ATTR_RE.fullmatch(sub.attr):
                        key = f"self.{sub.attr}"
                        if key in flagged:
                            continue
                        flagged.add(key)
                        self._emit(
                            "closure-captured-array-into-jit", sub,
                            f"'{key}' captured by a jitted function "
                            f"({label}) — instance arrays referenced "
                            "through a closed-over self become jit "
                            "constants; pass the array as an argument",
                            scope=self._fn_scope(fn))
            del flagged

    @staticmethod
    def _fn_scope(fn) -> str:
        return getattr(fn, "name", "<lambda>")

    # ------------------------------------------------- donation tracking

    def _check_donation_then_read(self, fn) -> None:
        """Within ONE function: track names passed at donated positions
        of a locally-bound donating jit; later reads without a rebind
        are findings."""
        donated_fns: Dict[str, Tuple[int, ...]] = {}
        for stmt in fn.body:
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name):
                idxs = self._donate_indices_in(stmt.value)
                if idxs:
                    donated_fns[stmt.targets[0].id] = idxs
            elif isinstance(stmt, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                for dec in stmt.decorator_list:
                    idxs = self._donate_indices_in(dec)
                    if idxs:
                        donated_fns[stmt.name] = idxs
        if not donated_fns:
            return
        pending: Dict[str, int] = {}  # dotted arg -> donation line

        def clear(name: str) -> None:
            for k in list(pending):
                if k == name or k.startswith(name + "."):
                    del pending[k]

        def scan_expr(expr: ast.AST) -> None:
            """Dotted reads checked at their OUTERMOST chain (so the
            finding names 'state.params', not the inner 'state');
            donation marking happens after a call's args were read."""
            if isinstance(expr, (ast.Name, ast.Attribute)) and \
                    isinstance(getattr(expr, "ctx", ast.Load()),
                               ast.Load):
                d = _dotted(expr)
                if d is not None:
                    for k, call_line in pending.items():
                        if d == k or d.startswith(k + "."):
                            self._emit(
                                "donation-then-read", expr,
                                f"'{d}' was donated at line "
                                f"{call_line} (donate_argnums) and "
                                "read afterwards — the buffer is "
                                "freed/aliased after the call; "
                                "read results, not donated inputs")
                            del pending[k]
                            break
                    return  # the dotted chain is consumed whole
            for sub in ast.iter_child_nodes(expr):
                if isinstance(sub, (ast.FunctionDef,
                                    ast.AsyncFunctionDef, ast.Lambda,
                                    ast.ClassDef)):
                    continue
                scan_expr(sub)
            if isinstance(expr, ast.Call):
                d = _dotted(expr.func)
                if d is not None and d in donated_fns:
                    for i in donated_fns[d]:
                        if i < len(expr.args):
                            an = _dotted(expr.args[i])
                            if an is not None:
                                pending[an] = expr.lineno

        def scan_stmt(stmt: ast.AST) -> None:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda, ast.ClassDef)):
                return
            if isinstance(stmt, ast.Assign):
                scan_expr(stmt.value)
                for tgt in stmt.targets:
                    for sub in ast.walk(tgt):
                        if isinstance(sub, (ast.Name, ast.Attribute)):
                            d = _dotted(sub)
                            if d is not None:
                                clear(d)
                return
            if isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
                if stmt.value is not None:
                    scan_expr(stmt.value)
                d = _dotted(stmt.target)
                if d is not None:
                    clear(d)
                return
            for sub in ast.iter_child_nodes(stmt):
                if isinstance(sub, ast.stmt):
                    scan_stmt(sub)
                else:
                    scan_expr(sub)

        for stmt in fn.body:
            scan_stmt(stmt)

    @staticmethod
    def _donate_indices_in(expr: ast.AST) -> Tuple[int, ...]:
        """donate_argnums indices from any jax.jit call inside expr."""
        for sub in ast.walk(expr):
            if not isinstance(sub, ast.Call):
                continue
            d = _dotted(sub.func) or ""
            if d not in ("jax.jit", "jit") and not (
                    d.rsplit(".", 1)[-1] == "partial" and sub.args
                    and (_dotted(sub.args[0]) or "") in ("jax.jit",
                                                         "jit")):
                continue
            for kw in sub.keywords:
                if kw.arg != "donate_argnums":
                    continue
                v = kw.value
                if isinstance(v, ast.Constant) and \
                        isinstance(v.value, int):
                    return (v.value,)
                if isinstance(v, (ast.Tuple, ast.List)):
                    out = tuple(e.value for e in v.elts
                                if isinstance(e, ast.Constant)
                                and isinstance(e.value, int))
                    if out:
                        return out
                return (0,)
        return ()

    # ------------------------------------------------------ hot paths

    def _check_hot_paths(self) -> None:
        roots = inv.JAX_HOT_PATH_ROOTS[self.module]
        # Intra-module call graph over bare function/method names.
        edges: Dict[str, Set[str]] = {}
        for name, fns in self._functions.items():
            outs: Set[str] = set()
            for fn in fns:
                for sub in ast.walk(fn):
                    if isinstance(sub, ast.Call):
                        d = _dotted(sub.func) or ""
                        t = d.rsplit(".", 1)[-1]
                        if t in self._functions and t != name:
                            outs.add(t)
            edges[name] = outs
        hot: Set[str] = set()
        todo = [r for r in roots if r in self._functions]
        while todo:
            cur = todo.pop()
            if cur in hot:
                continue
            hot.add(cur)
            todo.extend(edges.get(cur, ()))
        for name in sorted(hot):
            for fn in self._functions[name]:
                self._check_hot_fn(fn, name)

    def _check_hot_fn(self, fn, name: str) -> None:
        device: Set[str] = set()

        def producer_call(expr: ast.AST) -> Optional[str]:
            """'device' / 'host' / None for the calls inside expr."""
            found = None
            for sub in ast.walk(expr):
                if not isinstance(sub, ast.Call):
                    continue
                d = _dotted(sub.func) or ""
                t = d.rsplit(".", 1)[-1]
                if t in inv.HOST_FETCH_SUFFIXES:
                    return "host"
                if t in inv.DEVICE_PRODUCER_SUFFIXES or \
                        d.startswith(inv.DEVICE_PRODUCER_PREFIXES):
                    found = "device"
            return found

        def flag(node, what: str) -> None:
            self._emit(
                "host-sync-in-hot-path", node,
                f"{what} in hot-path function '{name}' — the decode/"
                "train hot path syncs the host AT MOST once per chunk "
                "through its counted fetch; route through it or "
                "allow-comment the intended sync", scope=name)

        def scan(node: ast.AST) -> None:
            """Dispatch on the node ITSELF, then recurse — statements
            are checked wherever they sit, not only as direct children
            of the body."""
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda, ast.ClassDef)):
                return
            if isinstance(node, ast.Assign):
                scan(node.value)
                verdict = producer_call(node.value)
                if verdict is None and _refs_name(node.value, device):
                    verdict = "device"
                flat: List[ast.AST] = []
                for tgt in node.targets:
                    if isinstance(tgt, (ast.Tuple, ast.List)):
                        flat.extend(tgt.elts)
                    else:
                        flat.append(tgt)
                for tgt in flat:
                    if isinstance(tgt, ast.Starred):
                        tgt = tgt.value
                    if isinstance(tgt, (ast.Name, ast.Attribute)):
                        d = _dotted(tgt)
                        if d is None:
                            continue
                        if verdict == "device":
                            device.add(d)
                        else:
                            device.discard(d)
                return
            if isinstance(node, (ast.If, ast.While)):
                ref = _refs_name(node.test, device)
                if ref is not None:
                    flag(node, f"python {type(node).__name__.lower()}"
                               f" on device value '{ref}'")
            elif isinstance(node, ast.Call):
                d = _dotted(node.func) or ""
                t = d.rsplit(".", 1)[-1]
                if isinstance(node.func, ast.Attribute) and \
                        node.func.attr in inv.HOST_SYNC_CALL_SUFFIXES:
                    flag(node, f".{node.func.attr}()")
                elif t in inv.HOST_SYNC_CALL_SUFFIXES:
                    flag(node, f"{d}()")
                elif d in ("float", "int") and node.args:
                    ref = _refs_name(node.args[0], device)
                    if ref is not None:
                        flag(node, f"{d}() on device value '{ref}'")
                elif d in ("np.asarray", "np.array", "numpy.asarray",
                           "numpy.array") and node.args:
                    ref = _refs_name(node.args[0], device)
                    if ref is not None:
                        flag(node, f"{d}() on device value '{ref}'")
            for child in ast.iter_child_nodes(node):
                scan(child)

        for stmt in fn.body:
            scan(stmt)

    # -------------------------------------------------------- kernels

    def _note_kernel(self, target: ast.AST,
                     scopes: Tuple[_Scope, ...]) -> None:
        fn: Optional[ast.AST] = None
        label = "pallas_call"
        if isinstance(target, ast.Call):  # functools.partial(kernel, ..)
            d = _dotted(target.func) or ""
            if d.rsplit(".", 1)[-1] == "partial" and target.args:
                target = target.args[0]
        if isinstance(target, (ast.Lambda, ast.FunctionDef)):
            fn = target
        elif isinstance(target, ast.Name):
            label = target.id
            for scope in reversed(scopes):
                if target.id in scope.defs:
                    fn = scope.defs[target.id]
                    break
        if fn is None or id(fn) in self._seen_kernels:
            return
        self._seen_kernels.add(id(fn))
        self._kernels.append((fn, label))

    def _check_kernels(self) -> None:
        for fn, label in self._kernels:
            scope = self._fn_scope(fn)
            body = fn.body if isinstance(fn.body, list) else [fn.body]
            for stmt in body:
                for sub in ast.walk(stmt):
                    if not isinstance(sub, ast.Call):
                        continue
                    d = _dotted(sub.func) or ""
                    # Method calls on non-dotted receivers (x_ref[...]
                    # .reshape(...)) still name their method.
                    t = (sub.func.attr
                         if isinstance(sub.func, ast.Attribute)
                         else d.rsplit(".", 1)[-1])
                    if t == "reshape":
                        self._emit(
                            "pallas-shape-rules", sub,
                            f"reshape inside Pallas kernel '{label}' — "
                            "cross-lane relayouts fail Mosaic lowering; "
                            "restructure with BlockSpecs/broadcasting",
                            scope=scope)
                    elif t == "arange":
                        self._emit(
                            "pallas-shape-rules", sub,
                            f"1D iota (arange) inside Pallas kernel "
                            f"'{label}' — Mosaic requires >=2D; use "
                            "lax.broadcasted_iota", scope=scope)
                    elif (t == "broadcasted_iota" and sub.args
                          and "float" in (_dotted(sub.args[0]) or "")):
                        self._emit(
                            "pallas-shape-rules", sub,
                            f"float iota inside Pallas kernel "
                            f"'{label}' — Mosaic's tpu.iota yields "
                            "integers only; build an int32 iota and "
                            "astype", scope=scope)
                    elif t in inv.PALLAS_REDUCTIONS and (
                            d.startswith(("jnp.", "jax.numpy."))
                            or isinstance(sub.func, ast.Attribute)):
                        kd = next((kw for kw in sub.keywords
                                   if kw.arg == "keepdims"), None)
                        if kd is None or not (
                                isinstance(kd.value, ast.Constant)
                                and kd.value.value is True):
                            self._emit(
                                "pallas-shape-rules", sub,
                                f"reduction '{t}' without "
                                f"keepdims=True inside Pallas kernel "
                                f"'{label}' — sub-2D intermediates "
                                "fail Mosaic lowering", scope=scope)


def lint_source(source: str, module: str, path: str,
                tree: Optional[ast.AST] = None) -> List[Finding]:
    """Run the jax rule family over one module's source. ``tree``
    reuses a caller-side parse (lint_paths parses once per file for
    both families)."""
    return _JaxLinter(module, path, source).run(tree)
