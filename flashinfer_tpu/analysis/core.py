"""Shared data model for the multi-pass static analyzer.

One ``Finding`` shape serves every pass (including the folded-in wedge
lint, whose ``Finding`` predates this package and fixed the field
names).  A ``Project`` is the unit of analysis: passes that need
cross-file resolution (L001 walks base classes defined in other
modules, L003 propagates env-read taint through cross-module calls)
consult the project-wide indexes instead of re-parsing.

Suppressions: ``# graft-lint: ok <reason>`` on the flagged line or on a
standalone comment line directly above it waives a finding.  A
suppression WITHOUT a reason still waives the finding but is itself
reported as L000 — the same contract the wedge lint proved with W000:
an unreviewable waiver is worse than the finding it hides.
"""

from __future__ import annotations

import ast
import copy
import dataclasses
import os
import re
from typing import Dict, List, Optional, Set, Tuple

GRAFT_SUPPRESS_RE = re.compile(r"#\s*graft-lint:\s*ok\b\s*(.*)")
# the wedge pass's historical spelling: waives only W-codes (scanned by
# the wedge lint itself), but the driver still audits it for reasonless
# comments — an unreviewable waiver is a finding in either spelling
WEDGE_SUPPRESS_RE = re.compile(r"#\s*wedge-lint:\s*ok\b\s*(.*)")


@dataclasses.dataclass(frozen=True)
class Finding:
    code: str
    filename: str
    line: int
    func: str
    message: str

    def __str__(self) -> str:
        return (f"{self.filename}:{self.line} [{self.code}] {self.func}: "
                f"{self.message}")


def project_relpath(path: str) -> str:
    """Stable path key for baselines: the path from the last known
    project-root component on, independent of the CWD the CLI ran in.
    The RIGHTMOST marker match wins across all markers — so a checkout
    directory that happens to be named ``flashinfer_tpu`` cannot hijack
    the key of a ``tests/`` or ``scripts/`` file nested inside it."""
    p = os.path.normpath(os.path.abspath(path)).replace(os.sep, "/")
    best = -1
    for marker in ("/flashinfer_tpu/", "/tests/", "/scripts/",
                   "/examples/"):
        best = max(best, p.rfind(marker))
    if best >= 0:
        return p[best + 1:]
    return os.path.basename(p)


def iter_python_files(paths: List[str]) -> List[str]:
    """The ONE directory walk: every consumer (Project.from_paths, the
    CLI's full-tree/stale-baseline comparisons) must enumerate files
    identically or the comparisons silently diverge."""
    out: List[str] = []
    for path in paths:
        if os.path.isdir(path):
            for dirpath, _dirs, names in os.walk(path):
                for fn in sorted(names):
                    if fn.endswith(".py"):
                        out.append(os.path.join(dirpath, fn))
        else:
            out.append(path)
    return out


@dataclasses.dataclass
class SourceFile:
    path: str
    src: str
    tree: Optional[ast.Module]
    suppressions: Dict[int, str]  # graft: line -> reason ("" = reasonless)
    parse_finding: Optional[Finding] = None
    # wedge-spelled suppressions, recorded ONLY so the driver can audit
    # reasonless ones — they never waive L-codes
    wedge_suppressions: Dict[int, str] = dataclasses.field(
        default_factory=dict)

    @property
    def basename(self) -> str:
        return os.path.basename(self.path)

    def suppression_for(self, line: int) -> Optional[str]:
        """Reason string if `line` (or the comment line directly above)
        carries a graft-lint suppression; None otherwise."""
        for ln in (line, line - 1):
            if ln in self.suppressions:
                return self.suppressions[ln]
        return None


def load_source(src: str, path: str) -> SourceFile:
    suppressions: Dict[int, str] = {}
    wedge_suppressions: Dict[int, str] = {}
    for i, line in enumerate(src.splitlines(), 1):
        m = GRAFT_SUPPRESS_RE.search(line)
        if m:
            suppressions[i] = m.group(1).strip()
        m = WEDGE_SUPPRESS_RE.search(line)
        if m:
            wedge_suppressions[i] = m.group(1).strip()
    try:
        tree = ast.parse(src, path)
        parse_finding = None
    except SyntaxError as e:  # analysis must never crash a build
        tree = None
        parse_finding = Finding("L999", path, e.lineno or 0, "<module>",
                                f"unparseable source: {e.msg}")
    return SourceFile(path, src, tree, suppressions, parse_finding,
                      wedge_suppressions)


def load_file(path: str) -> SourceFile:
    with open(path) as f:
        return load_source(f.read(), path)


class Project:
    """The set of files under analysis plus lazily-built cross-file
    indexes.  Passes receive the whole project so inheritance and call
    chains resolve across modules (within the analyzed set)."""

    def __init__(self, files: List[SourceFile]):
        self.files = files
        self._class_index: Optional[Dict[str, List["ClassInfo"]]] = None
        self._function_index: Optional[
            Dict[str, List["FunctionInfo"]]] = None
        self._pallas_sites: Optional[List["PallasCallSite"]] = None

    @classmethod
    def from_paths(cls, paths: List[str]) -> "Project":
        return cls([load_file(f) for f in iter_python_files(paths)])

    # -- class index (L001) ------------------------------------------------

    @property
    def class_index(self) -> Dict[str, List["ClassInfo"]]:
        if self._class_index is None:
            idx: Dict[str, List[ClassInfo]] = {}
            for sf in self.files:
                if sf.tree is None:
                    continue
                for node in ast.walk(sf.tree):
                    if isinstance(node, ast.ClassDef):
                        info = ClassInfo.from_node(sf, node)
                        idx.setdefault(info.name, []).append(info)
            self._class_index = idx
        return self._class_index

    def resolve_class(self, name: str) -> Optional["ClassInfo"]:
        hits = self.class_index.get(name)
        return hits[0] if hits else None

    # -- function index (the L007–L010 cross-module resolution layer) ------

    @property
    def function_index(self) -> Dict[str, List["FunctionInfo"]]:
        """Every def in the analyzed set, keyed by bare name — the
        project symbol index that lets a pass in one file see the
        planner/kernel defined in another (same resolution scope as the
        class index: name-level, within the analyzed files)."""
        if self._function_index is None:
            idx: Dict[str, List[FunctionInfo]] = {}
            for sf in self.files:
                if sf.tree is None:
                    continue
                for node, qualname in _walk_defs(sf.tree):
                    idx.setdefault(node.name, []).append(
                        FunctionInfo(node.name, qualname, sf, node))
            self._function_index = idx
        return self._function_index

    def resolve_function(
            self, name: str,
            prefer_file: Optional[SourceFile] = None
    ) -> Optional["FunctionInfo"]:
        """The def `name` resolves to: the one in `prefer_file` when it
        defines it (Python name resolution would find the local def
        first), else the unique project-wide def, else None (ambiguous
        names stay unresolved — no guessing across modules)."""
        hits = self.function_index.get(name)
        if not hits:
            return None
        if prefer_file is not None:
            local = [h for h in hits if h.file is prefer_file]
            if len(local) == 1:
                return local[0]
        return hits[0] if len(hits) == 1 else None

    @property
    def pallas_sites(self) -> List["PallasCallSite"]:
        """Every ``pl.pallas_call`` launch in the analyzed set, with its
        statically-resolved contract pieces (shared by L007–L010)."""
        if self._pallas_sites is None:
            self._pallas_sites = collect_pallas_sites(self)
        return self._pallas_sites

    def mro_chain(self, cls: "ClassInfo") -> List["ClassInfo"]:
        """Depth-first base-class chain starting at `cls` — an
        approximation of the MRO sufficient for single-inheritance
        wrapper stacks (name-resolved within the analyzed file set)."""
        chain: List[ClassInfo] = []
        seen = set()

        def _walk(c: ClassInfo) -> None:
            key = (c.file.path, c.name, c.node.lineno)
            if key in seen:
                return
            seen.add(key)
            chain.append(c)
            for base in c.base_names:
                b = self.resolve_class(base)
                if b is not None:
                    _walk(b)

        _walk(cls)
        return chain


def _base_name(expr: ast.expr) -> Optional[str]:
    """Last dotted component of a base-class expression."""
    if isinstance(expr, ast.Name):
        return expr.id
    if isinstance(expr, ast.Attribute):
        return expr.attr
    if isinstance(expr, ast.Call):  # e.g. Generic[...] or a metaclass call
        return _base_name(expr.func)
    if isinstance(expr, ast.Subscript):  # Generic[T]
        return _base_name(expr.value)
    return None


@dataclasses.dataclass
class ClassInfo:
    name: str
    file: SourceFile
    node: ast.ClassDef
    base_names: List[str]
    # alias -> (target name, body index, line); LAST class-level
    # ``alias = target`` assignment wins, like the class body would.
    alias_binds: Dict[str, tuple]
    # method name -> (body index, line) of its LAST class-level def
    method_defs: Dict[str, tuple]

    @classmethod
    def from_node(cls, sf: SourceFile, node: ast.ClassDef) -> "ClassInfo":
        bases = [b for b in (_base_name(e) for e in node.bases) if b]
        aliases: Dict[str, tuple] = {}
        methods: Dict[str, tuple] = {}
        for i, stmt in enumerate(node.body):
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                methods[stmt.name] = (i, stmt.lineno)
            elif isinstance(stmt, ast.Assign) and isinstance(
                    stmt.value, ast.Name):
                for t in stmt.targets:
                    if isinstance(t, ast.Name):
                        aliases[t.id] = (stmt.value.id, i, stmt.lineno)
        return cls(node.name, sf, node, bases, aliases, methods)


# ---------------------------------------------------------------------------
# Cross-module resolution layer (L007–L010): function index, static
# expression helpers, and the shared Pallas launch-site scanner.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FunctionInfo:
    name: str
    qualname: str
    file: SourceFile
    node: ast.AST  # FunctionDef | AsyncFunctionDef

    @property
    def positional_params(self) -> List[str]:
        a = self.node.args
        return [p.arg for p in a.posonlyargs + a.args]

    @property
    def has_vararg(self) -> bool:
        return self.node.args.vararg is not None


def _walk_defs(tree: ast.Module):
    """(def node, dotted qualname) for every function def, in source
    order, nesting encoded in the qualname (``outer.inner``)."""
    out = []

    def _walk(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = f"{prefix}{child.name}"
                out.append((child, q))
                _walk(child, q + ".")
            elif isinstance(child, ast.ClassDef):
                _walk(child, f"{prefix}{child.name}.")
            else:
                _walk(child, prefix)

    _walk(tree, "")
    return out


# basenames that compile a step body into a compile-once callable —
# the ONE registry the serving-contract passes (L011 donation_lifetime,
# L012 static_flow) share, so a future compile wrapper registered here
# is seen by both (registering it in only one pass would silently
# under-report in the other)
JIT_LIKE_NAMES = frozenset({"jit"})


def expr_basename(expr: ast.expr) -> str:
    """Last dotted component: ``pltpu.PrefetchScalarGridSpec`` ->
    ``PrefetchScalarGridSpec``; bare names return themselves."""
    if isinstance(expr, ast.Attribute):
        return expr.attr
    if isinstance(expr, ast.Name):
        return expr.id
    return ""


def expr_root(expr: ast.expr) -> Optional[str]:
    """Leftmost Name of a dotted chain (``np`` for ``np.sum``), the
    Name itself for bare names, None otherwise."""
    while isinstance(expr, ast.Attribute):
        expr = expr.value
    return expr.id if isinstance(expr, ast.Name) else None


def const_int(expr: ast.expr) -> Optional[int]:
    """Fold an integer-constant expression: literals, +,-,*,//,<<, and
    unary minus over them (``64 * 1024 * 1024`` resolves)."""
    if isinstance(expr, ast.Constant) and isinstance(expr.value, int) \
            and not isinstance(expr.value, bool):
        return expr.value
    if isinstance(expr, ast.UnaryOp) and isinstance(expr.op, ast.USub):
        v = const_int(expr.operand)
        return -v if v is not None else None
    if isinstance(expr, ast.BinOp):
        lo, hi = const_int(expr.left), const_int(expr.right)
        if lo is None or hi is None:
            return None
        if isinstance(expr.op, ast.Add):
            return lo + hi
        if isinstance(expr.op, ast.Sub):
            return lo - hi
        if isinstance(expr.op, ast.Mult):
            return lo * hi
        if isinstance(expr.op, ast.FloorDiv):
            return lo // hi if hi else None
        if isinstance(expr.op, ast.LShift):
            return lo << hi
    return None


class FnLocals:
    """Single-assignment resolution for names local to one function:
    ``kernel = functools.partial(_k, ...)`` or ``in_specs = [...]``.
    A name counts as resolvable ONLY when it is assigned exactly once
    and never mutated in place (.append/.extend/.insert / augmented
    assignment) — conditional rebinds and list growth make the static
    count a guess, and a guessed contract check is worse than none."""

    _MUTATORS = {"append", "extend", "insert", "add", "update"}

    def __init__(self, fn_node: ast.AST):
        assigns: Dict[str, List[ast.expr]] = {}
        mutated: Set[str] = set()
        # the scope's own parameters are bindings with UNKNOWN values:
        # they must resolve to None (and block outer-scope fall-through
        # in ChainLocals), never to a shadowed outer assignment
        params: Set[str] = set()
        if isinstance(fn_node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
            a = fn_node.args
            params = {p.arg for p in (a.posonlyargs + a.args
                                      + a.kwonlyargs)}
            for va in (a.vararg, a.kwarg):
                if va is not None:
                    params.add(va.arg)
        for n in ast.walk(fn_node):
            if isinstance(n, ast.Assign):
                for t in n.targets:
                    if isinstance(t, ast.Name):
                        assigns.setdefault(t.id, []).append(n.value)
            elif isinstance(n, ast.AugAssign) and isinstance(
                    n.target, ast.Name):
                mutated.add(n.target.id)
            elif isinstance(n, ast.Call) \
                    and isinstance(n.func, ast.Attribute) \
                    and n.func.attr in self._MUTATORS \
                    and isinstance(n.func.value, ast.Name):
                mutated.add(n.func.value.id)
        self._assigns = assigns
        self._mutated = mutated
        self._params = params

    def value_of(self, name: str) -> Optional[ast.expr]:
        if name in self._params:
            return None
        vals = self._assigns.get(name)
        if vals is None or len(vals) != 1 or name in self._mutated:
            return None
        return vals[0]

    def values_of(self, name: str) -> List[ast.expr]:
        """Every assignment of an unmutated, non-param local — the
        both-arms-of-an-if selection (``kernel = partial(_a, ...)`` /
        ``kernel = partial(_b, ...)``) that ``value_of`` rightly refuses
        to pick a winner from.  Callers FORK one analysis per candidate
        instead of guessing (or skipping)."""
        if name in self._params or name in self._mutated:
            return []
        return list(self._assigns.get(name, ()))

    def seq_elements(self, expr: ast.expr,
                     _depth: int = 0) -> Optional[List[ast.expr]]:
        """Statically-known elements of a list/tuple expression: a
        literal, a concat of statics, or a once-assigned local name."""
        if _depth > 8:
            return None
        if isinstance(expr, (ast.List, ast.Tuple)):
            if any(isinstance(e, ast.Starred) for e in expr.elts):
                return None
            return list(expr.elts)
        if isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.Add):
            lo = self.seq_elements(expr.left, _depth + 1)
            hi = self.seq_elements(expr.right, _depth + 1)
            if lo is None or hi is None:
                return None
            return lo + hi
        if isinstance(expr, ast.Name):
            v = self.value_of(expr.id)
            if v is not None:
                return self.seq_elements(v, _depth + 1)
        return None


_EVAL_INT_FNS = {
    "min": min, "max": max, "abs": abs, "int": int,
    "cdiv": lambda a, b: -(-a // b),
    "round_up": lambda x, m: -(-x // m) * m,
    "next_power_of_two": lambda x: 1 << max(int(x) - 1, 0).bit_length(),
}


def eval_int_expr(expr: Optional[ast.expr], env: Dict[str, int],
                  locals_: Optional[FnLocals] = None,
                  _depth: int = 0) -> Optional[int]:
    """Fold an expression to a concrete int under a known-int env:
    literals, env names (env wins — it carries the binding scenario),
    once-assigned locals, +-*//%min/max/cdiv/round_up.  None, never a
    guess, for anything else — the shared arithmetic behind grid
    trip-count resolution (L016) and chooser scenario plumbing."""
    if expr is None or _depth > 12:
        return None
    v = const_int(expr)
    if v is not None:
        return v
    if isinstance(expr, ast.Name):
        if expr.id in env and isinstance(env[expr.id], int) \
                and not isinstance(env[expr.id], bool):
            return env[expr.id]
        if locals_ is not None:
            return eval_int_expr(locals_.value_of(expr.id), env,
                                 locals_, _depth + 1)
        return None
    if isinstance(expr, ast.UnaryOp) and isinstance(expr.op, ast.USub):
        v = eval_int_expr(expr.operand, env, locals_, _depth + 1)
        return -v if v is not None else None
    if isinstance(expr, ast.BinOp):
        lo = eval_int_expr(expr.left, env, locals_, _depth + 1)
        hi = eval_int_expr(expr.right, env, locals_, _depth + 1)
        if lo is None or hi is None:
            return None
        if isinstance(expr.op, ast.Add):
            return lo + hi
        if isinstance(expr.op, ast.Sub):
            return lo - hi
        if isinstance(expr.op, ast.Mult):
            return lo * hi
        if isinstance(expr.op, ast.FloorDiv):
            return lo // hi if hi else None
        if isinstance(expr.op, ast.Mod):
            return lo % hi if hi else None
        if isinstance(expr.op, ast.LShift):
            return lo << hi
        return None
    if isinstance(expr, ast.Call) and not expr.keywords:
        fn = _EVAL_INT_FNS.get(expr_basename(expr.func))
        if fn is None:
            return None
        args = [eval_int_expr(a, env, locals_, _depth + 1)
                for a in expr.args]
        if any(a is None for a in args):
            return None
        try:
            return int(fn(*args))
        except (TypeError, ValueError, ZeroDivisionError):
            return None
    return None


_PALLAS_CALL_NAMES = {"pallas_call"}
_GRID_SPEC_NAMES = {"PrefetchScalarGridSpec", "GridSpec"}
_PARTIAL_CALL_NAMES = {"partial"}


def _unwrap_partial(
        expr: ast.expr, locals_: FnLocals, _depth: int = 0,
) -> Tuple[Optional[ast.expr], Set[str], int,
           Dict[str, ast.expr], List[ast.expr]]:
    """(innermost callable expr, keyword names bound along the partial
    chain, count of POSITIONALLY-bound partial args — they consume the
    kernel's leading params, kwarg name -> bound VALUE expr, positional
    bound value exprs in order).  Resolves through once-assigned local
    names.  The value exprs are what L014 evaluates (in the launcher's
    scope) to recover static kernel parameters; innermost partial wins
    a kwarg collision, matching functools semantics."""
    bound: Set[str] = set()
    npos = 0
    kw_exprs: Dict[str, ast.expr] = {}
    pos_exprs: List[ast.expr] = []
    while _depth < 8:
        _depth += 1
        if isinstance(expr, ast.Call) \
                and expr_basename(expr.func) in _PARTIAL_CALL_NAMES \
                and expr.args:
            bound |= {k.arg for k in expr.keywords if k.arg}
            for k in expr.keywords:
                if k.arg:  # inner partial (seen later) overrides
                    kw_exprs[k.arg] = k.value
            npos += len(expr.args) - 1
            pos_exprs = list(expr.args[1:]) + pos_exprs
            expr = expr.args[0]
            continue
        if isinstance(expr, ast.Name):
            v = locals_.value_of(expr.id)
            if v is not None and not isinstance(v, ast.Name):
                expr = v
                continue
        break
    return expr, bound, npos, kw_exprs, pos_exprs


@dataclasses.dataclass
class PallasCallSite:
    """One ``pl.pallas_call`` launch and everything about its contract
    that is statically decidable.  ``None`` fields mean "not statically
    countable here" — passes must skip, never guess."""

    file: SourceFile
    enclosing: Optional[FunctionInfo]  # the launcher def
    call: ast.Call                     # the pallas_call(...) itself
    invocation: Optional[ast.Call]     # pallas_call(...)(operands...)
    kernel: Optional[FunctionInfo]     # resolved kernel def
    kernel_bound_kwargs: Set[str]      # kwargs bound via functools.partial
    kernel_bound_posargs: int          # positional partial binds (leading)
    is_prefetch_spec: bool             # PrefetchScalarGridSpec launch
    num_scalar_prefetch: Optional[int]
    grid_rank: Optional[int]
    in_spec_exprs: Optional[List[ast.expr]]
    out_spec_exprs: Optional[List[ast.expr]]
    scratch_exprs: Optional[List[ast.expr]]
    io_aliases_expr: Optional[ast.expr]
    vmem_limit_bytes: Optional[int]
    locals_: FnLocals
    # the bound VALUE exprs behind kernel_bound_kwargs/posargs, and the
    # grid tuple's element exprs — both evaluated (in the launcher's
    # scope) by L014 to seed static kernel parameters.  None grid_exprs
    # mirrors grid_rank=None: not statically visible here.
    kernel_bound_kwarg_exprs: Dict[str, ast.expr] = dataclasses.field(
        default_factory=dict)
    kernel_bound_posarg_exprs: List[ast.expr] = dataclasses.field(
        default_factory=list)
    grid_exprs: Optional[List[ast.expr]] = None
    # when the kernel resolved through a CALLER of the launcher (the
    # trampoline shape: the kernel is a launcher parameter), the bound
    # value exprs live in the caller's scope — evaluate them there, not
    # against the launcher's locals.  None means `locals_` is correct.
    bound_expr_locals: Optional[FnLocals] = None

    @property
    def line(self) -> int:
        return self.call.lineno

    def resolve_trip_counts(
            self, env: Dict[str, int]) -> Optional[List[int]]:
        """Concrete per-axis grid trip counts under a known-int
        environment (a chooser/cost binding's shape scenario), or None
        when any axis stays symbolic.  Grid exprs are evaluated with
        ``eval_int_expr`` — env names win, then once-assigned launcher
        locals, then literal arithmetic; anything else is not a guess."""
        if not self.grid_exprs:
            return None
        trips: List[int] = []
        for e in self.grid_exprs:
            v = eval_int_expr(e, env, self.locals_)
            if v is None or v <= 0:
                return None
            trips.append(v)
        return trips


def _spec_list(expr: Optional[ast.expr],
               locals_: FnLocals) -> Optional[List[ast.expr]]:
    """Spec kwarg -> element list.  A bare BlockSpec/ShapeDtypeStruct
    call (the single-output shorthand) counts as a 1-element list."""
    if expr is None:
        return None
    elems = locals_.seq_elements(expr)
    if elems is not None:
        return elems
    resolved = expr
    if isinstance(expr, ast.Name):
        v = locals_.value_of(expr.id)
        if v is None:
            return None
        elems = locals_.seq_elements(v)
        if elems is not None:
            return elems
        resolved = v
    if isinstance(resolved, ast.Call):
        return [resolved]
    return None


def walk_own_scope(node: ast.AST):
    """Child nodes of `node` excluding the interiors of nested defs
    (the nested def node itself IS yielded)."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        yield n
        if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
            stack.extend(ast.iter_child_nodes(n))


class ChainLocals(FnLocals):
    """FnLocals over a lexical scope chain (innermost def first): a
    launch inside a closure still resolves names bound in the enclosing
    launcher — matching Python's own lookup order."""

    def __init__(self, scopes: List[ast.AST]):
        self._chain = [FnLocals(s) for s in scopes]

    def value_of(self, name: str) -> Optional[ast.expr]:
        for loc in self._chain:
            v = loc.value_of(name)
            if v is not None:
                return v
            # a name bound-but-unresolvable in an inner scope (param,
            # multi-assign, mutation) must not fall through to a stale
            # outer binding
            if name in loc._assigns or name in loc._mutated \
                    or name in loc._params:
                return None
        return None

    def values_of(self, name: str) -> List[ast.expr]:
        for loc in self._chain:
            vals = loc.values_of(name)
            if vals:
                return vals
            if name in loc._assigns or name in loc._mutated \
                    or name in loc._params:
                return []
        return []


def collect_pallas_sites(project: "Project") -> List[PallasCallSite]:
    sites: List[PallasCallSite] = []
    for sf in project.files:
        if sf.tree is None:
            continue

        def _scan(scope: ast.AST, chain: List[ast.AST],
                  qual_prefix: str) -> None:
            for node in walk_own_scope(scope):
                if isinstance(node,
                              (ast.FunctionDef, ast.AsyncFunctionDef)):
                    _scan(node, [node] + chain,
                          f"{qual_prefix}{node.name}.")
                elif isinstance(node, ast.ClassDef):
                    _scan(node, chain, f"{qual_prefix}{node.name}.")
                elif isinstance(node, ast.Call) and expr_basename(
                        node.func) in _PALLAS_CALL_NAMES:
                    enclosing = None
                    for s in chain:
                        if isinstance(s, (ast.FunctionDef,
                                          ast.AsyncFunctionDef)):
                            enclosing = FunctionInfo(
                                s.name, s.name, sf, s)
                            break
                    sites.extend(_build_site(
                        project, sf, enclosing, node,
                        ChainLocals(chain or [sf.tree]),
                        chain[0] if chain else sf.tree))

        _scan(sf.tree, [], "")
    return sites


_MAX_KERNEL_FORKS = 4


def _param_arg_exprs(project: "Project", enclosing: FunctionInfo,
                     pname: str) -> List[Tuple[ast.expr, FnLocals]]:
    """Argument exprs bound to launcher parameter ``pname`` at every
    project-wide call of the launcher, each paired with the CALLER's
    scope locals (a partial chain unwraps in the scope that wrote it).
    Feeds the trampoline kernel shape: ``_launch(kernel, ...)`` where
    the real kernel arrives from the wrapper one frame up."""
    node = enclosing.node
    a = node.args
    pos_params = [p.arg for p in (a.posonlyargs + a.args)]
    idx = pos_params.index(pname) if pname in pos_params else None
    out: List[Tuple[ast.expr, FnLocals]] = []
    for f in project.files:
        if f.tree is None:
            continue

        def _scan(scope: ast.AST, chain: List[ast.AST],
                  tree: ast.AST) -> None:
            for n in walk_own_scope(scope):
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    if n is not node:  # the def itself is not a call site
                        _scan(n, [n] + chain, tree)
                elif isinstance(n, ast.Call) \
                        and expr_basename(n.func) == enclosing.name:
                    expr = None
                    for k in n.keywords:
                        if k.arg == pname:
                            expr = k.value
                    if expr is None and idx is not None \
                            and idx < len(n.args) \
                            and not any(isinstance(p, ast.Starred)
                                        for p in n.args[: idx + 1]):
                        expr = n.args[idx]
                    if expr is not None:
                        out.append((expr, ChainLocals(chain or [tree])))

        _scan(f.tree, [], f.tree)
    return out


def _kernel_candidates(
        project: "Project", sf: SourceFile,
        enclosing: Optional[FunctionInfo], call: ast.Call,
        locals_: FnLocals,
) -> List[Tuple[FunctionInfo, Set[str], int, Dict[str, ast.expr],
                List[ast.expr], Optional[FnLocals]]]:
    """Statically-possible kernels behind the pallas_call's first
    argument.  Beyond the single-resolution ``_unwrap_partial`` chase,
    two shapes FORK one candidate per possibility instead of failing:
    a name assigned once per branch of an if/else (the moe gather
    rowcache-vs-plain kernel select), and a name that is a PARAMETER of
    the enclosing launcher, resolved through every project-wide caller
    (the sampling bisect trampoline).  Each entry is (kernel info,
    bound kwarg names, bound posarg count, kwarg exprs, posarg exprs,
    expr locals or None when the launch scope is already correct)."""
    if not call.args:
        return []
    root = call.args[0]
    exprs: List[Tuple[ast.expr, Optional[FnLocals]]] = [(root, None)]
    if isinstance(root, ast.Name):
        multi = locals_.values_of(root.id)
        if len(multi) > 1:
            if len(multi) > _MAX_KERNEL_FORKS:
                return []  # too many rebinds: stay honestly unresolved
            exprs = [(v, None) for v in multi]
        elif not multi and enclosing is not None:
            callers = _param_arg_exprs(project, enclosing, root.id)
            if callers:
                if len(callers) > _MAX_KERNEL_FORKS:
                    return []
                exprs = [(v, loc) for v, loc in callers]
    out = []
    seen: Set[int] = set()
    for expr, expr_loc in exprs:
        target, bound, npos, kw_exprs, pos_exprs = _unwrap_partial(
            expr, expr_loc if expr_loc is not None else locals_)
        info = None
        if target is not None:
            base = expr_basename(target)
            if base:
                info = project.resolve_function(base, prefer_file=sf)
        if info is None or id(info.node) in seen:
            continue
        seen.add(id(info.node))
        out.append((info, bound, npos, kw_exprs, pos_exprs, expr_loc))
    return out


def _lambda_grid_elts(lam: ast.Lambda,
                      call: ast.Call) -> Optional[List[ast.expr]]:
    """Substitute a grid-builder lambda's call args for its params in
    its tuple body: ``grid = lambda nt: (nt, tiles_n, tiles_k)`` then
    ``grid=grid(num_tiles)`` yields ``(num_tiles, tiles_n, tiles_k)``.
    Positional-only and arity-exact; anything fancier returns None —
    the rank may still be known while the element exprs are not."""
    params = [p.arg for p in lam.args.args]
    if (lam.args.posonlyargs or lam.args.kwonlyargs or lam.args.vararg
            or lam.args.kwarg or lam.args.defaults or call.keywords
            or len(call.args) != len(params)):
        return None
    sub = {p: a for p, a in zip(params, call.args)}

    class _Subst(ast.NodeTransformer):
        def visit_Name(self, n: ast.Name) -> ast.expr:
            return sub.get(n.id, n)

    return [_Subst().visit(copy.deepcopy(e)) for e in lam.body.elts
            ] if isinstance(lam.body, ast.Tuple) else None


def _build_site(project: "Project", sf: SourceFile,
                enclosing: Optional[FunctionInfo], call: ast.Call,
                locals_: FnLocals,
                scope_node: ast.AST) -> List[PallasCallSite]:
    """Sites for one pallas_call — usually one; one per candidate when
    the kernel resolution legitimately forks (branch-selected kernel
    locals, trampoline launchers)."""
    kwargs = {k.arg: k.value for k in call.keywords if k.arg}

    # grid spec: inline call, once-assigned local, or direct kwargs
    spec_call = None
    gs = kwargs.get("grid_spec")
    if isinstance(gs, ast.Name):
        gs = locals_.value_of(gs.id)
    if isinstance(gs, ast.Call) \
            and expr_basename(gs.func) in _GRID_SPEC_NAMES:
        spec_call = gs
    spec_kwargs = ({k.arg: k.value for k in spec_call.keywords if k.arg}
                   if spec_call is not None else kwargs)
    is_prefetch = bool(
        spec_call is not None
        and expr_basename(spec_call.func) == "PrefetchScalarGridSpec")

    nsp = None
    if is_prefetch:
        nsp_expr = spec_kwargs.get("num_scalar_prefetch")
        nsp = const_int(nsp_expr) if nsp_expr is not None else 0
    grid_rank = None
    grid_exprs: Optional[List[ast.expr]] = None
    grid_expr = spec_kwargs.get("grid")
    if isinstance(grid_expr, ast.Name):
        grid_expr = locals_.value_of(grid_expr.id)
    if isinstance(grid_expr, (ast.Tuple, ast.List)):
        grid_rank = len(grid_expr.elts)
        grid_exprs = list(grid_expr.elts)
    elif grid_expr is not None and const_int(grid_expr) is not None:
        grid_rank = 1
        grid_exprs = [grid_expr]
    elif isinstance(grid_expr, ast.Call) \
            and isinstance(grid_expr.func, ast.Name):
        # grid built by a local helper lambda — ``grid = lambda nt:
        # (nt, tiles_n, tiles_k)`` then ``grid=grid(num_tiles)``.  The
        # rank is statically visible whenever EVERY candidate lambda
        # (branch-selected rebinds included) returns a tuple of the
        # same arity; the element exprs are kept only when the lambda
        # is unambiguous, since branch candidates may order the axes
        # differently and a guessed axis order is worse than none.
        builders = locals_.values_of(grid_expr.func.id)
        if builders and all(
                isinstance(b, ast.Lambda)
                and isinstance(b.body, ast.Tuple) for b in builders):
            ranks = {len(b.body.elts) for b in builders}
            if len(ranks) == 1:
                grid_rank = ranks.pop()
                if len(builders) == 1:
                    grid_exprs = _lambda_grid_elts(
                        builders[0], grid_expr)

    in_specs = _spec_list(spec_kwargs.get("in_specs"), locals_)
    out_specs = _spec_list(spec_kwargs.get("out_specs"), locals_)
    scratch = _spec_list(spec_kwargs.get("scratch_shapes"), locals_)
    if scratch is None and "scratch_shapes" not in spec_kwargs \
            and (spec_call is not None or "grid_spec" not in kwargs):
        # an omitted scratch_shapes is statically ZERO scratch refs —
        # leaving it "uncountable" would disable the kernel-arity check
        # for every plain launch; only an UNRESOLVED grid_spec (where
        # the real kwargs are invisible) keeps it unknown
        scratch = []

    # kernel: first positional arg, through partial chains, local
    # names (forking on branch-selected rebinds), and trampoline params
    cands = _kernel_candidates(project, sf, enclosing, call, locals_)
    if not cands:
        bound: Set[str] = set()
        bound_pos = 0
        bound_kw_exprs: Dict[str, ast.expr] = {}
        bound_pos_exprs: List[ast.expr] = []
        if call.args:
            (_target, bound, bound_pos, bound_kw_exprs,
             bound_pos_exprs) = _unwrap_partial(call.args[0], locals_)
        cands = [(None, bound, bound_pos, bound_kw_exprs,
                  bound_pos_exprs, None)]

    # the immediately-applied operand call, if any
    invocation = None
    for n in ast.walk(scope_node):
        if isinstance(n, ast.Call) and n.func is call:
            invocation = n
            break

    vmem = None
    cp = kwargs.get("compiler_params")
    if isinstance(cp, ast.Call):
        for k in cp.keywords:
            if k.arg == "vmem_limit_bytes":
                vmem = const_int(k.value)

    return [PallasCallSite(
        file=sf, enclosing=enclosing, call=call, invocation=invocation,
        kernel=kernel_info, kernel_bound_kwargs=bound,
        kernel_bound_posargs=bound_pos,
        is_prefetch_spec=is_prefetch, num_scalar_prefetch=nsp,
        grid_rank=grid_rank, in_spec_exprs=in_specs,
        out_spec_exprs=out_specs, scratch_exprs=scratch,
        io_aliases_expr=kwargs.get("input_output_aliases"),
        vmem_limit_bytes=vmem, locals_=locals_,
        kernel_bound_kwarg_exprs=bound_kw_exprs,
        kernel_bound_posarg_exprs=bound_pos_exprs,
        grid_exprs=grid_exprs, bound_expr_locals=expr_locals)
        for (kernel_info, bound, bound_pos, bound_kw_exprs,
             bound_pos_exprs, expr_locals) in cands]
