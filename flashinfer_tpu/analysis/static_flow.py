"""L012 — per-step-varying values flowing into compile-once statics.

The serving engine's compile-once ladder (PR 11/12) rests on ONE rule:
the per-step schedule rides as ARGUMENTS, never as statics.  A schedule
value that reaches a trace-keying static — a frozen ``_*Plan``/``*Geom``
dataclass field, a planner kwarg that sizes the plan arrays, a jit
``static_argnums`` slot, or a Python branch inside a jitted body —
recompiles the step every time the value moves: the ≤9-trace budget
silently becomes one trace per step.  PR 10's flight recorder attributes
that failure at RUNTIME (retrace-cause diffs); this pass is the static
complement — the same bug class caught at review time, before a serving
host ever pays the compile.

Taint model (function-local, resolution via ``core.py``):

- **Sources** are seeded in :data:`SCHEDULE_SOURCES` (per-step-varying
  parameters of registered schedule-lowering functions, keyed by
  qualname — mirroring ``pallas_contract.PLANNER_KERNELS``) and
  :data:`SCHEDULE_SOURCE_CALLS` (calls that RETURN a per-step schedule,
  e.g. the engine scheduler's ``_schedule()``).  Request/token counts
  (``len()`` of a schedule list), attribute/subscript reads off tainted
  names, loop variables over tainted iterables, and arithmetic over any
  of those propagate.
- **Sinks**:

  1. a tainted value bound to a plan-shape static of a registered
     planner (``pallas_contract.PLANNER_KERNELS`` names, params in
     :data:`PLAN_SHAPE_STATICS`) — the plan-array SHAPES become
     schedule-dependent and every step retraces;
  2. a tainted value passed into a frozen ``_*Plan``/``*Geom``
     dataclass constructor (or ``dataclasses.replace`` on one) — a
     per-step value frozen into plan statics;
  3. a tainted value at a ``static_argnums``/``static_argnames``
     position of a jit-compiled callable — every distinct value is a
     distinct jit cache entry;
  4. a nested def that is jit-compiled AND branches (``if``/``while``)
     on a tainted closure — the branch keys the trace.

Deliberately NOT tainted: plan()-time parameters of a step that
replans per scheduling decision, the rung (the quantized ladder is the sanctioned static), and
anything outside registered source scopes — a taint pass that guesses
trains people to ignore it.
"""

from __future__ import annotations

import ast
from typing import Dict, FrozenSet, List, Optional, Set, Tuple

from flashinfer_tpu.analysis.core import (JIT_LIKE_NAMES, Finding,
                                          Project, SourceFile,
                                          expr_basename, walk_own_scope)

CODE = "L012"

# qualname -> parameter names that carry the per-step schedule into the
# function.  Registered per function (not per type): taint never leaks
# into unregistered scopes, so plan-time replanning stays unflagged.
SCHEDULE_SOURCES: Dict[str, Tuple[str, ...]] = {
    # the engine's schedule lowering: `segs` is THE per-step schedule
    # (SchedSeg rows); `rung` and `geom` are the sanctioned statics
    "build_engine_work_units": ("segs",),
    # the engine step itself sources its schedule via _schedule() (see
    # SCHEDULE_SOURCE_CALLS) but is registered so the pass walks it
    "ServingEngine.step": (),
}

# call basenames whose RETURN VALUE is a per-step schedule
SCHEDULE_SOURCE_CALLS: FrozenSet[str] = frozenset({"_schedule"})

# planner params that freeze plan-array SHAPES (the rung contract:
# "every array shape is a pure function of the rung, never the
# schedule").  `rung` itself is deliberately absent — the quantized
# ladder is the design.
PLAN_SHAPE_STATICS: FrozenSet[str] = frozenset({
    "num_units_pad", "block_q", "pages_per_chunk", "num_splits",
})

# frozen-static dataclass name patterns (the plan/geom record families)
_PLAN_CLASS_SUFFIXES = ("Plan", "Geom")



def _planner_names() -> FrozenSet[str]:
    from flashinfer_tpu.analysis.pallas_contract import PLANNER_KERNELS

    return frozenset(PLANNER_KERNELS)


def _is_plan_class(name: str) -> bool:
    return any(name.endswith(sfx) for sfx in _PLAN_CLASS_SUFFIXES)


class _Taint:
    """Fixpoint name-level taint over one function's own scope."""

    def __init__(self, fn: ast.AST, sources: Tuple[str, ...]):
        self.fn = fn
        self.tainted: Set[str] = set(sources)
        self._propagate()

    def _propagate(self) -> None:
        # true fixpoint: each round either grows the tainted set or
        # stops, and the set is bounded by the scope's names — so this
        # terminates without an arbitrary iteration cap (a capped loop
        # silently under-taints long forward assignment chains)
        while True:
            before = len(self.tainted)
            for n in walk_own_scope(self.fn):
                if isinstance(n, ast.Assign):
                    if self.expr_tainted(n.value):
                        for t in n.targets:
                            self._taint_target(t)
                elif isinstance(n, ast.AnnAssign):
                    # `n: int = len(segs)` — a type annotation must
                    # not dodge the taint an unannotated assign carries
                    if n.value is not None \
                            and self.expr_tainted(n.value):
                        self._taint_target(n.target)
                elif isinstance(n, ast.NamedExpr):
                    if self.expr_tainted(n.value):
                        self._taint_target(n.target)
                elif isinstance(n, ast.AugAssign):
                    if self.expr_tainted(n.value) and isinstance(
                            n.target, ast.Name):
                        self.tainted.add(n.target.id)
                elif isinstance(n, ast.For):
                    if self.expr_tainted(n.iter):
                        self._taint_target(n.target)
                elif isinstance(n, ast.withitem):
                    # `with tainted() as segs:` binds the schedule too
                    if n.optional_vars is not None \
                            and self.expr_tainted(n.context_expr):
                        self._taint_target(n.optional_vars)
            if len(self.tainted) == before:
                return

    def _taint_target(self, t: ast.expr) -> None:
        if isinstance(t, ast.Name):
            self.tainted.add(t.id)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                self._taint_target(e)
        elif isinstance(t, ast.Starred):
            # `first, *rest = segs` — the starred slice carries the
            # schedule too
            self._taint_target(t.value)

    def expr_tainted(self, expr: ast.expr) -> bool:
        """An expression carries schedule taint when any Name it reads
        is tainted or it calls a registered schedule source."""
        for n in ast.walk(expr):
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load) \
                    and n.id in self.tainted:
                return True
            if isinstance(n, ast.Call) \
                    and expr_basename(n.func) in SCHEDULE_SOURCE_CALLS:
                return True
        return False


def _match_sources(qualname: str) -> Optional[Tuple[str, ...]]:
    if qualname in SCHEDULE_SOURCES:
        return SCHEDULE_SOURCES[qualname]
    return None


def _call_bound_args(call: ast.Call, params: List[str],
                     has_vararg: bool):
    """(param name, value expr) pairs a call binds, positionally and by
    keyword (starred operands end positional mapping)."""
    out = []
    for i, a in enumerate(call.args):
        if isinstance(a, ast.Starred):
            break
        if i < len(params):
            out.append((params[i], a))
        elif not has_vararg:
            break
    for k in call.keywords:
        if k.arg:
            out.append((k.arg, k.value))
    return out


def _frozen_plan_classes(project: Project) -> FrozenSet[str]:
    """Project classes that are frozen dataclasses with a Plan/Geom
    name — the records whose fields are compile-once statics."""
    out: Set[str] = set()
    for name, infos in project.class_index.items():
        if not _is_plan_class(name):
            continue
        for info in infos:
            for dec in info.node.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                if expr_basename(target) == "dataclass":
                    kws = (dec.keywords if isinstance(dec, ast.Call)
                           else [])
                    if any(k.arg == "frozen"
                           and isinstance(k.value, ast.Constant)
                           and k.value.value is True for k in kws):
                        out.add(name)
    return frozenset(out)


def _binds_plan_instance(fn: ast.AST, name: str,
                         plan_classes: FrozenSet[str],
                         _seen: Optional[Set[str]] = None) -> bool:
    """True when `name` is bound in `fn`'s own scope to a plan/geom
    CONSTRUCTION (`_StepPlan(...)`, `Geom.build(...)`, or a
    `dataclasses.replace` of one — the self-rebind
    `plan = replace(plan, ...)` resolves through the name's OTHER
    bindings) — the receiver test the replace sink needs so ordinary
    bookkeeping records never flag.  Unresolvable receivers return
    False: skip, never guess."""
    if _seen is None:
        _seen = set()
    if name in _seen:
        return False
    _seen.add(name)
    for n in walk_own_scope(fn):
        if not (isinstance(n, ast.Assign)
                and isinstance(n.value, ast.Call)):
            continue
        if not any(isinstance(t, ast.Name) and t.id == name
                   for t in n.targets):
            continue
        v = n.value
        base = expr_basename(v.func)
        if base in plan_classes:
            return True
        if isinstance(v.func, ast.Attribute) and v.func.attr == "build" \
                and expr_basename(v.func.value) in plan_classes:
            return True
        if base == "replace" and v.args \
                and isinstance(v.args[0], ast.Name) \
                and _binds_plan_instance(fn, v.args[0].id, plan_classes,
                                         _seen):
            return True
    return False


def _static_positions(call: ast.Call) -> FrozenSet[int]:
    """static_argnums of a jit-like call (int/tuple literals only)."""
    for k in call.keywords:
        if k.arg == "static_argnums":
            v = k.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return frozenset({v.value})
            if isinstance(v, (ast.Tuple, ast.List)):
                out = set()
                for e in v.elts:
                    if isinstance(e, ast.Constant) \
                            and isinstance(e.value, int):
                        out.add(e.value)
                return frozenset(out)
    return frozenset()


def _static_names(call: ast.Call) -> FrozenSet[str]:
    """static_argnames of a jit-like call (str/tuple literals only) —
    the dominant spelling at this repo's jit sites."""
    for k in call.keywords:
        if k.arg == "static_argnames":
            v = k.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                return frozenset({v.value})
            if isinstance(v, (ast.Tuple, ast.List)):
                out = set()
                for e in v.elts:
                    if isinstance(e, ast.Constant) \
                            and isinstance(e.value, str):
                        out.add(e.value)
                return frozenset(out)
    return frozenset()


def _body_positional_params(project: Project, sf: SourceFile,
                            fn: ast.AST, call: ast.Call) -> List[str]:
    """Positional params of the jit call's body function, so a
    positional call-site operand can map onto a static_argnames name —
    a same-scope nested def first, else the project index."""
    if not call.args:
        return []
    base = expr_basename(call.args[0])
    if not base:
        return []
    for n in walk_own_scope(fn):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and n.name == base:
            a = n.args
            return [p.arg for p in a.posonlyargs + a.args]
    info = project.resolve_function(base, prefer_file=sf)
    if info is not None:
        return list(info.positional_params)
    return []


def _class_jit_statics(cls: ast.ClassDef, project: Project,
                       sf: SourceFile) -> Dict[
                           str, Tuple[FrozenSet[int], FrozenSet[str],
                                      List[str]]]:
    """``self.<attr> = jax.jit(..., static_argnums/argnames=...)``
    assignments anywhere in the class — the map a ``self.<attr>(...)``
    call site in a registered method resolves against (the repo's
    dominant compiled-step idiom compiles in plan()/__init__ and calls
    in step()/run()).  A leading ``self`` param of a method body is
    dropped so positional operands map onto the bound signature."""
    out: Dict[str, Tuple[FrozenSet[int], FrozenSet[str], List[str]]] = {}
    for stmt in cls.body:
        if not isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for n in walk_own_scope(stmt):
            if not (isinstance(n, ast.Assign) and len(n.targets) == 1
                    and isinstance(n.value, ast.Call)
                    and expr_basename(n.value.func) in JIT_LIKE_NAMES):
                continue
            t = n.targets[0]
            if not (isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"):
                continue
            statics = _static_positions(n.value)
            snames = _static_names(n.value)
            if not (statics or snames):
                continue
            params: List[str] = []
            if snames and n.value.args:
                base = expr_basename(n.value.args[0])
                for m in cls.body:
                    if isinstance(m, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)) \
                            and m.name == base:
                        a = m.args
                        params = [q.arg for q in a.posonlyargs + a.args]
                        break
                else:
                    params = _body_positional_params(project, sf, stmt,
                                                     n.value)
                if params and params[0] == "self":
                    params = params[1:]
            out[t.attr] = (statics, snames, params)
    return out


def _check_function(project: Project, sf: SourceFile, fn: ast.AST,
                    qualname: str, sources: Tuple[str, ...],
                    plan_classes: FrozenSet[str],
                    findings: List[Finding],
                    cls_statics: Optional[Dict] = None) -> None:
    taint = _Taint(fn, sources)
    planners = _planner_names()
    # names bound to a jit-compiled callable with static positions or
    # names (`step = jax.jit(body, static_argnums=...)` /
    # `static_argnames=...`) — collected up front so call sites
    # anywhere in the scope resolve; the body's positional params let
    # a positional operand map onto a named static
    jitted_statics: Dict[
        str, Tuple[FrozenSet[int], FrozenSet[str], List[str]]] = {}
    for node in walk_own_scope(fn):
        if isinstance(node, ast.Assign) \
                and isinstance(node.value, ast.Call) \
                and expr_basename(node.value.func) in JIT_LIKE_NAMES:
            statics = _static_positions(node.value)
            snames = _static_names(node.value)
            if statics or snames:
                params = _body_positional_params(project, sf, fn,
                                                 node.value) \
                    if snames else []
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        jitted_statics[t.id] = (statics, snames, params)

    for node in walk_own_scope(fn):
        if not isinstance(node, ast.Call):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                _check_jit_body_branches(sf, fn, node, qualname, taint,
                                         findings)
            continue
        base = expr_basename(node.func)

        # sink 1: plan-shape statics of registered planners
        if base in planners:
            info = project.resolve_function(base, prefer_file=sf)
            params = info.positional_params if info is not None else []
            vararg = info.has_vararg if info is not None else True
            for pname, val in _call_bound_args(node, params, vararg):
                if pname in PLAN_SHAPE_STATICS \
                        and taint.expr_tainted(val):
                    findings.append(Finding(
                        CODE, sf.path, val.lineno, qualname,
                        f"per-step schedule value reaches the plan-"
                        f"shape static '{pname}=' of planner "
                        f"'{base}': plan-array shapes must be a pure "
                        "function of the rung, never the schedule — "
                        "this retraces the step every time the "
                        "schedule moves (the compile-once ladder "
                        "silently becomes one trace per step)"))

        # sink 2: frozen plan/geom dataclass constructions
        ctor = base
        if ctor in plan_classes or (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "build"
                and expr_basename(node.func.value) in plan_classes):
            cname = ctor if ctor in plan_classes \
                else expr_basename(node.func.value)
            for k in node.keywords:
                if k.arg and taint.expr_tainted(k.value):
                    findings.append(Finding(
                        CODE, sf.path, k.value.lineno, qualname,
                        f"per-step schedule value frozen into "
                        f"'{cname}.{k.arg}': frozen plan statics key "
                        "the jit cache, so a schedule-varying field "
                        "forces a replan+retrace every step — pass it "
                        "as a traced argument instead"))
            for a in node.args:
                if taint.expr_tainted(a):
                    findings.append(Finding(
                        CODE, sf.path, a.lineno, qualname,
                        f"per-step schedule value frozen into a "
                        f"'{cname}' plan static (positional): frozen "
                        "plan statics key the jit cache — pass it as "
                        "a traced argument instead"))
        elif base == "replace" and node.args \
                and isinstance(node.args[0], ast.Name) \
                and _binds_plan_instance(fn, node.args[0].id,
                                         plan_classes):
            # only a replace whose receiver RESOLVES to a plan/geom
            # construction is a plan sink — replace on ordinary
            # bookkeeping records must not flag (a taint pass that
            # guesses trains people to ignore it)
            for k in node.keywords:
                if k.arg and taint.expr_tainted(k.value):
                    findings.append(Finding(
                        CODE, sf.path, k.value.lineno, qualname,
                        f"per-step schedule value written into plan "
                        f"field '{k.arg}' via dataclasses.replace — "
                        "the replaced plan keys a fresh trace every "
                        "step"))

        # sink 3: tainted values at jit static positions/names — a
        # local `step(...)` or the class-attribute `self._step(...)`
        # idiom (compiled in plan()/__init__, called in step()/run())
        sink3 = None
        if isinstance(node.func, ast.Name) \
                and node.func.id in jitted_statics:
            sink3 = (node.func.id, jitted_statics[node.func.id])
        elif cls_statics and isinstance(node.func, ast.Attribute) \
                and isinstance(node.func.value, ast.Name) \
                and node.func.value.id == "self" \
                and node.func.attr in cls_statics:
            sink3 = ("self." + node.func.attr,
                     cls_statics[node.func.attr])
        if sink3 is not None:
            fname, (positions, snames, params) = sink3
            for i, a in enumerate(node.args):
                if isinstance(a, ast.Starred):
                    break
                pname = params[i] if i < len(params) else None
                if not taint.expr_tainted(a):
                    continue
                if i in positions:
                    findings.append(Finding(
                        CODE, sf.path, a.lineno, qualname,
                        f"per-step schedule value passed at "
                        f"static_argnums position {i} of the jitted "
                        f"'{fname}': every distinct value is a "
                        "fresh trace — make it a traced argument or "
                        "quantize it onto the rung ladder"))
                elif pname is not None and pname in snames:
                    findings.append(Finding(
                        CODE, sf.path, a.lineno, qualname,
                        f"per-step schedule value passed at "
                        f"static_argnames param '{pname}' of the "
                        f"jitted '{fname}': every distinct "
                        "value is a fresh trace — make it a traced "
                        "argument or quantize it onto the rung ladder"))
            for k in node.keywords:
                if k.arg and k.arg in snames \
                        and taint.expr_tainted(k.value):
                    findings.append(Finding(
                        CODE, sf.path, k.value.lineno, qualname,
                        f"per-step schedule value passed at "
                        f"static_argnames param '{k.arg}' of the "
                        f"jitted '{fname}': every distinct "
                        "value is a fresh trace — make it a traced "
                        "argument or quantize it onto the rung ladder"))


def _check_jit_body_branches(sf: SourceFile, outer: ast.AST,
                             body: ast.AST, qualname: str,
                             taint: "_Taint",
                             findings: List[Finding]) -> None:
    """Sink 4: a nested def that is jit-compiled in this scope and
    branches on a tainted closure read."""
    compiled = False
    for n in walk_own_scope(outer):
        if isinstance(n, ast.Call) \
                and expr_basename(n.func) in JIT_LIKE_NAMES \
                and n.args and expr_basename(n.args[0]) == body.name:
            compiled = True
            break
    if not compiled:
        return
    a = body.args
    params = {p.arg for p in a.posonlyargs + a.args + a.kwonlyargs}
    # a body-local that SHADOWS a tainted outer name is the body's own
    # binding, not a schedule closure — exclude stored names, like the
    # L011 capture check's _body_free_reads does
    stored = {n.id for n in ast.walk(body)
              if isinstance(n, ast.Name)
              and not isinstance(n.ctx, ast.Load)}
    for n in ast.walk(body):
        if isinstance(n, (ast.If, ast.While)):
            for m in ast.walk(n.test):
                if isinstance(m, ast.Name) and isinstance(m.ctx, ast.Load) \
                        and m.id in taint.tainted and m.id not in params \
                        and m.id not in stored:
                    findings.append(Finding(
                        CODE, sf.path, n.lineno, qualname,
                        f"jitted body '{body.name}' branches on "
                        f"per-step schedule closure '{m.id}': the "
                        "branch keys the trace, so every schedule "
                        "move recompiles — lower it to lax.cond on a "
                        "traced operand or hoist it to the host "
                        "planner"))
                    break


def run(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    plan_classes = _frozen_plan_classes(project)
    for sf in project.files:
        if sf.tree is None:
            continue

        def _scan(scope: ast.AST, prefix: str,
                  cls_statics: Optional[Dict] = None) -> None:
            for node in walk_own_scope(scope):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    qual = prefix + node.name
                    sources = _match_sources(qual)
                    if sources is not None:
                        _check_function(project, sf, node, qual,
                                        sources, plan_classes, findings,
                                        cls_statics=cls_statics)
                    _scan(node, qual + ".", cls_statics)
                elif isinstance(node, ast.ClassDef):
                    _scan(node, prefix + node.name + ".",
                          _class_jit_statics(node, project, sf))

        _scan(sf.tree, "")
    return findings
