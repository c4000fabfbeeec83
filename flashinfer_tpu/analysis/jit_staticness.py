"""L003 — trace-time configuration reads inside jit boundaries.

``jax.jit`` runs the Python body ONCE per cache key and bakes every
Python-level value into the trace.  An ``os.environ`` / ``os.getenv``
read (or a read of a mutated module-level dict/list) inside a jitted
function is therefore resolved at first trace and pinned by the jit
cache — later env changes are silently ignored for that shape.  The
motivating true positive (ADVICE.md round 5, item 4): compat's
``_top_k_large_ties`` was jitted with ``backend`` static, so
``backend="auto"`` resolved ``FLASHINFER_TPU_TOPK_BACKEND`` inside the
trace, contradicting topk.py's documented eager per-call resolution.

Detection:

- a function is *jitted* if decorated with ``jit``/``jax.jit``/
  ``pjit`` (bare, called, or via ``functools.partial(jax.jit, ...)``),
  or wrapped at assignment (``f = jax.jit(g)`` marks ``g``);
- a function is *env-reading* if its body touches ``os.environ`` /
  ``environ`` or calls ``getenv``, or loads a module-level dict/list/
  set that the SAME module mutates somewhere (a mutated global read at
  trace time is the same staleness bug; never-mutated constant tables
  are exempt);
- taint propagates through calls by callee basename across the whole
  analyzed file set (cross-module: compat's jitted helper calling
  ``topk.top_k_values_indices`` → ``_resolve_backend`` → env read).

Findings anchor at the env-read line (direct) or the call line inside
the jitted function (transitive).  Fix: resolve the configuration
EAGERLY in the un-jitted caller and pass the concrete value through —
then suppress any remaining transitive-reachability report with
``# graft-lint: ok <why the value is already concrete>``.
"""

from __future__ import annotations

import ast
import dataclasses
import os
from typing import Dict, List, Optional, Set, Tuple

from flashinfer_tpu.analysis.core import Finding, Project, SourceFile

CODE = "L003"

_JIT_NAMES = {"jit", "pjit"}
_PARTIAL_NAMES = {"partial"}
_MUTATOR_METHODS = {"append", "extend", "add", "update", "pop", "popitem",
                    "setdefault", "clear", "insert", "remove", "discard"}


def _basename(expr: ast.expr) -> str:
    if isinstance(expr, ast.Attribute):
        return expr.attr
    if isinstance(expr, ast.Name):
        return expr.id
    return ""


def _is_jit_expr(expr: ast.expr) -> bool:
    """`jax.jit`, `jit`, `pjit`, `jax.jit(...)`, or
    `functools.partial(jax.jit, ...)`."""
    if _basename(expr) in _JIT_NAMES:
        return True
    if isinstance(expr, ast.Call):
        if _basename(expr.func) in _JIT_NAMES:
            return True
        if _basename(expr.func) in _PARTIAL_NAMES and expr.args \
                and _is_jit_expr(expr.args[0]):
            return True
    return False


@dataclasses.dataclass
class FnInfo:
    name: str
    file: SourceFile
    node: ast.FunctionDef
    jitted: bool
    env_reads: List[int]            # lines with direct env reads
    global_reads: List[Tuple[int, str]]  # (line, mutated-global name)
    # (callee basename, line, root): root is None for bare-name calls,
    # else the leftmost Name of the attribute chain ("topk" for
    # topk.top_k_values_indices, "jax" for jax.lax.top_k, "self" for
    # method calls) — taint only follows project-internal roots, so an
    # external library sharing a function name cannot false-positive
    calls: List[Tuple[str, int, Optional[str]]]


def _mutated_globals(tree: ast.Module) -> Set[str]:
    """Module-level names bound to a dict/list/set literal (or
    constructor call) that the module also mutates somewhere."""
    candidates: Set[str] = set()
    for node in tree.body:
        if isinstance(node, ast.Assign):
            v = node.value
            mutable = isinstance(v, (ast.Dict, ast.List, ast.Set,
                                     ast.DictComp, ast.ListComp,
                                     ast.SetComp)) or (
                isinstance(v, ast.Call)
                and _basename(v.func) in ("dict", "list", "set",
                                          "defaultdict", "OrderedDict"))
            if mutable:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        candidates.add(t.id)
    if not candidates:
        return set()
    mutated: Set[str] = set()
    for node in ast.walk(tree):
        # d[key] = ... / del d[key] / d[key] += ...
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.Delete)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [getattr(node, "target", None)]
                       if not isinstance(node, ast.Delete)
                       else node.targets)
            for t in targets:
                if isinstance(t, ast.Subscript) \
                        and isinstance(t.value, ast.Name) \
                        and t.value.id in candidates:
                    mutated.add(t.value.id)
        # d.update(...) / l.append(...)
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr in _MUTATOR_METHODS \
                and isinstance(node.func.value, ast.Name) \
                and node.func.value.id in candidates:
            mutated.add(node.func.value.id)
    return mutated


def _collect_functions(sf: SourceFile) -> List[FnInfo]:
    if sf.tree is None:
        return []
    mutated = _mutated_globals(sf.tree)

    def _callable_names(expr: ast.expr) -> Set[str]:
        """Bare Names that plausibly name the traced callable inside a
        jit argument — the name itself, or the FIRST positional arg of
        a composing call, recursively (`jax.jit(jax.shard_map(step,
        ...))` traces `step`; `jax.jit(partial(f, x))` traces `f`).
        Later positional args are data/callback operands, not the
        traced body — marking them too would false-positive L003 on
        any module function sharing such an argument's name."""
        names: Set[str] = set()
        if isinstance(expr, ast.Name):
            names.add(expr.id)
        elif isinstance(expr, ast.Call) and expr.args:
            names |= _callable_names(expr.args[0])
        return names

    # names marked jitted via call wrapping: g = jax.jit(f), a bare
    # jax.jit(shard_map(step, ...)) in a return, etc.
    wrapped: Set[str] = set()
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Call):
            continue
        if _is_jit_expr(node.func):
            args = node.args
        elif _basename(node.func) in _PARTIAL_NAMES and node.args \
                and _is_jit_expr(node.args[0]):
            args = node.args[1:]
        else:
            continue
        for a in args:
            wrapped |= _callable_names(a)

    infos: List[FnInfo] = []
    for node in ast.walk(sf.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        jitted = node.name in wrapped or any(
            _is_jit_expr(d) for d in node.decorator_list)
        env_reads: List[int] = []
        global_reads: List[Tuple[int, str]] = []
        calls: List[Tuple[str, int, Optional[str]]] = []
        # locals shadow module globals; a parameter named like a global
        # is not a global read
        local_names = {a.arg for a in (
            node.args.posonlyargs + node.args.args + node.args.kwonlyargs
            + ([node.args.vararg] if node.args.vararg else [])
            + ([node.args.kwarg] if node.args.kwarg else []))}
        for n in ast.walk(node):
            if isinstance(n, ast.Attribute) and n.attr == "environ":
                env_reads.append(n.lineno)
            elif isinstance(n, ast.Name) and n.id == "environ" \
                    and isinstance(n.ctx, ast.Load):
                env_reads.append(n.lineno)
            elif isinstance(n, ast.Call):
                base = _basename(n.func)
                if base == "getenv":
                    env_reads.append(n.lineno)
                elif base:
                    root: Optional[str] = None
                    if isinstance(n.func, ast.Attribute):
                        head = n.func.value
                        while isinstance(head, ast.Attribute):
                            head = head.value
                        root = head.id if isinstance(head, ast.Name) \
                            else ""
                    calls.append((base, n.lineno, root))
            elif isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load) \
                    and n.id in mutated and n.id not in local_names:
                global_reads.append((n.lineno, n.id))
        infos.append(FnInfo(node.name, sf, node, jitted,
                            sorted(set(env_reads)), global_reads, calls))
    return infos


def run(project: Project) -> List[Finding]:
    all_fns: List[FnInfo] = []
    for sf in project.files:
        all_fns.extend(_collect_functions(sf))

    by_name: Dict[str, List[FnInfo]] = {}
    for fn in all_fns:
        by_name.setdefault(fn.name, []).append(fn)

    # roots taint may follow: bare names (None), methods on self/cls,
    # and attribute access on a project-internal module name
    internal_roots: Set[str] = {"self", "cls"}
    for sf in project.files:
        internal_roots.add(os.path.splitext(sf.basename)[0])
        parent = os.path.basename(os.path.dirname(
            os.path.abspath(sf.path)))
        if parent:
            internal_roots.add(parent)

    def _follows(call: Tuple[str, int, Optional[str]]) -> bool:
        _callee, _line, root = call
        return root is None or root in internal_roots

    # fixpoint: taint = reads trace-time-pinned state directly (env OR
    # a mutated module global), or calls a tainted name
    tainted: Set[str] = {fn.name for fn in all_fns
                         if fn.env_reads or fn.global_reads}
    changed = True
    while changed:
        changed = False
        for fn in all_fns:
            if fn.name in tainted:
                continue
            if any(_follows(c) and c[0] in tainted for c in fn.calls):
                tainted.add(fn.name)
                changed = True

    findings: List[Finding] = []
    for fn in all_fns:
        if not fn.jitted:
            continue
        for line in fn.env_reads:
            findings.append(Finding(
                CODE, fn.file.path, line, fn.name,
                "os.environ/getenv read inside a jit-traced function is "
                "resolved ONCE at trace time and pinned by the jit cache "
                "(the _top_k_large_ties backend-pinning bug) — resolve "
                "the value eagerly outside the jit and pass it in"))
        for line, gname in fn.global_reads:
            findings.append(Finding(
                CODE, fn.file.path, line, fn.name,
                f"read of mutated module-level '{gname}' inside a "
                "jit-traced function is baked in at trace time — later "
                "mutations are silently ignored for cached shapes; pass "
                "the value as an argument instead"))
        seen_callees: Set[str] = set()
        for callee, line, root in fn.calls:
            if callee in tainted and callee not in seen_callees \
                    and _follows((callee, line, root)) \
                    and not any(f.jitted
                                for f in by_name.get(callee, [])):
                seen_callees.add(callee)
                findings.append(Finding(
                    CODE, fn.file.path, line, fn.name,
                    f"call to '{callee}', which (transitively) reads "
                    "process env or a mutated module global — inside "
                    "this jit boundary the read happens at trace time "
                    "and is pinned by the jit cache; hoist the "
                    "resolution out of the jit or suppress with the "
                    "eager-resolution reason if the value is already "
                    "concrete here"))
    return findings
