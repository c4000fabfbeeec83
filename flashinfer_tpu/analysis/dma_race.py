"""L014 dma_race — DMA/semaphore happens-before checking inside Pallas
kernel bodies.

The repo's committed-speed backlog lives in hand-rolled double-buffered
DMA mainloops, and the chip wedged for two bench rounds (BENCH_r04/r05
``wedged: true``) on exactly the hang an unbalanced semaphore produces.
This pass executes each resolved kernel body in a tiny concrete model
— the FINAL grid axis runs sequentially for ``N_STEPS`` model steps,
scalar-prefetch loads become opaque terms, unknown guards fork the
world with a memoized truth per canonicalized condition atom — and
checks, per surviving world:

(a) reads of a DMA destination ref not dominated by the matching wait,
(b) overwrite of a buffer slot or copy source while a copy on that
    slot may still be in flight (the double-buffer slot-parity /
    cross-unit-prefetch anti-dependency),
(c) start/wait imbalance on any semaphore along any path — a wait with
    nothing in flight, or copies still in flight after the last grid
    step (the static wedge-prevention proof), and
(d) cross-grid-iteration carries (start in step *i*, wait in *i+1*)
    whose slot is touched in between — reported through (a)/(b) with
    the carry step called out.

Soundness stance (the L007 rule): a kernel the interpreter cannot
execute SKIPS — never false-reports — and skips are counted
(``stats()`` feeds ``obs doctor``).  Conflict decisions use MUST
semantics both ways: a finding needs must-overlap (structurally equal
or concretely intersecting index terms), and a wait retires any
may-matching in-flight copy silently, so an unknown term never turns
into a report.  World forks that a guard's memo cannot distinguish are
merged back as soon as their DMA state (in-flight multiset + ref
stores + kernel-scope env) re-converges, which keeps the
mask/online-update guard combinatorics of the real fused-prefill
mainloops flat.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Dict, List, Optional, Set, Tuple

from flashinfer_tpu.analysis.core import (Finding, FnLocals, FunctionInfo,
                                          PallasCallSite, Project,
                                          const_int, expr_basename,
                                          expr_root)

# model sizes: the final grid axis runs N_STEPS sequential steps (two
# steps exercise a double-buffer handoff, three exercise slot reuse);
# unknown fori_loop bounds enumerate trip counts 0..MAX_TRIP.
N_STEPS = 3
MAX_TRIP = 2
MAX_UNROLL = 8          # concrete fori/range unroll ceiling
MAX_WORLDS = 768        # live worlds after merging, per site
MAX_STMT_PATHS = 4096   # fork paths within one top-level statement
MAX_OPS = 4_000_000     # interpreter fuel per site
_MODEL_INT = 2          # model value for unresolvable static loop bounds


class KernelSkip(Exception):
    """Kernel not statically executable — count, never guess."""


class _NeedChoice(Exception):
    def __init__(self, key, options):
        super().__init__(key)
        self.key = key
        self.options = options


class _DeadWorld(Exception):
    """Binding contradicted an already-memoized guard: path infeasible."""


class _Return(Exception):
    def __init__(self, value):
        super().__init__("return")
        self.value = value


class _Break(Exception):
    pass


class _Continue(Exception):
    pass


# -- values ---------------------------------------------------------------
# Terms are nested tuples (hashable, structurally comparable):
#   ("sym", name)                 opaque scalar (non-final program_id, ...)
#   ("static", name)              unresolved partial-bound kernel param
#   ("load", refkey, idx)         value read from a ref
#   ("op", opname, *args)         uninterpreted arithmetic
#   ("cmp", op, a, b)             comparison (array mask until guarded)
#   ("and"/"or"/"not", ...)       logical combination
#   ("call", name, *args)         uninterpreted call
#   ("attr", value, name)         attribute of an opaque value
# Concrete ints/bools/floats/strings pass through as themselves.


class Ref:
    """A Pallas ref (kernel param, scratch slot, or vararg element)."""

    def __init__(self, key: str):
        self.key = key
        self.label = key

    def __eq__(self, other):
        return isinstance(other, Ref) and other.key == self.key

    def __hash__(self):
        return hash(self.key)

    def __repr__(self):
        return f"Ref({self.label})"


@dataclasses.dataclass(frozen=True)
class DS:
    """pl.ds(start, size)."""
    start: object
    size: object


_FULL = ("fullslice",)
_ELL = ("ellipsis",)


@dataclasses.dataclass(frozen=True)
class View:
    """ref[idx...] as a copy operand / access region."""
    ref: Ref
    idx: tuple

    def describe(self) -> str:
        return self.ref.label


class AtProxy:
    def __init__(self, ref: Ref):
        self.ref = ref


class Copy:
    def __init__(self, src: View, dst: View, sem: View, line: int):
        self.src = src
        self.dst = dst
        self.sem = sem
        self.line = line

    def key(self):
        return (_view_key(self.src), _view_key(self.dst),
                _view_key(self.sem), self.line)


class BoundMethod:
    def __init__(self, recv, name: str):
        self.recv = recv
        self.name = name


class WhenPred:
    def __init__(self, cond):
        self.cond = cond


class Varargs:
    """The kernel's *refs tuple; elements materialize lazily so the
    boolean-static ref layout (has_mask, return_lse, ...) needs no
    launch-side operand count."""

    def __init__(self, name: str):
        self.name = name
        self._refs: Dict[int, Ref] = {}

    def get(self, i: int) -> Ref:
        if i not in self._refs:
            self._refs[i] = Ref(f"*{self.name}[{i}]")
        return self._refs[i]


@dataclasses.dataclass(frozen=True)
class VarargTail:
    base: object  # Varargs
    start: int


class Closure:
    def __init__(self, node, env):
        self.node = node
        self.env = env


class RangeVal:
    def __init__(self, lo: int, hi: int):
        self.lo = lo
        self.hi = hi

    def items(self):
        return list(range(self.lo, self.hi))


def _view_key(v: View):
    return (v.ref.key, tuple(_idx_key(i) for i in v.idx))


def _idx_key(i):
    if isinstance(i, DS):
        return ("ds", _idx_key(i.start), _idx_key(i.size))
    return i


def _value_key(v, cache: Optional[dict] = None):
    """Stable fingerprint for world merging.  ``cache`` (id -> key)
    memoizes container fingerprints within one merge: cloned worlds
    share term DAGs, and uninterpreted-arithmetic chains alias their
    subterms heavily, so an uncached walk is quadratic-and-worse in
    model time.  Safe because every keyed object is held alive by a
    world for the duration of the merge."""
    if isinstance(v, (list, tuple)) and cache is not None:
        hit = cache.get(id(v))
        if hit is not None:
            return hit
    if isinstance(v, Ref):
        return ("ref", v.key)
    elif isinstance(v, View):
        return ("view", _view_key(v))
    elif isinstance(v, AtProxy):
        return ("at", v.ref.key)
    elif isinstance(v, DS):
        return ("ds", _value_key(v.start, cache), _value_key(v.size, cache))
    elif isinstance(v, Copy):
        return ("copy", v.key())
    elif isinstance(v, Closure):
        return ("closure", id(v.node))
    elif isinstance(v, BoundMethod):
        return ("bm", _value_key(v.recv, cache), v.name)
    elif isinstance(v, WhenPred):
        return ("when", _value_key(v.cond, cache))
    elif isinstance(v, Varargs):
        return ("varargs", v.name)
    elif isinstance(v, VarargTail):
        return ("vtail", v.base.name, v.start)
    elif isinstance(v, RangeVal):
        return ("range", v.lo, v.hi)
    elif isinstance(v, (list, tuple)):
        out = ("seq", tuple(_value_key(x, cache) for x in v))
        if cache is not None:
            cache[id(v)] = out
        return out
    elif isinstance(v, (int, float, bool, str)) or v is None:
        return v
    else:
        return ("opaque", repr(v))


# -- environments ---------------------------------------------------------


_MISSING = object()


class ModuleEnv:
    """Module-level constants + helper defs of the kernel's file,
    resolved lazily and shared by every world (values are constant)."""

    def __init__(self, project: Project, file):
        self.project = project
        self.file = file
        self._locals = FnLocals(file.tree) if file.tree else None
        self._cache: Dict[str, object] = {}

    def lookup(self, name: str, world: "World"):
        if name in self._cache:
            return self._cache[name]
        val = _MISSING
        if self._locals is not None:
            expr = self._locals.value_of(name)
            if expr is not None:
                c = const_int(expr)
                if c is not None:
                    val = c
                elif isinstance(expr, ast.Constant) and isinstance(
                        expr.value, (str, float, bool)):
                    val = expr.value
                elif isinstance(expr, ast.UnaryOp) \
                        and isinstance(expr.op, ast.USub) \
                        and isinstance(expr.operand, ast.Constant) \
                        and isinstance(expr.operand.value,
                                       (int, float)) \
                        and not isinstance(expr.operand.value, bool):
                    # `_NEG_INF = -1e30`: the sentinel-constant idiom
                    val = -expr.operand.value
        if val is _MISSING:
            fi = self.project.resolve_function(name, prefer_file=self.file)
            if fi is not None and fi.file is self.file \
                    and "." not in fi.qualname:
                val = Closure(fi.node, self)
        self._cache[name] = val
        return val

    def assign(self, name, value, world):  # pragma: no cover - defensive
        raise KernelSkip("assignment into module scope")


class WorldEnv:
    """The kernel-body scope: storage lives ON the world so closures
    defined before a fork read the forked world's bindings."""

    def __init__(self, parent: ModuleEnv):
        self.parent = parent

    def lookup(self, name: str, world: "World"):
        if name in world.kenv:
            return world.kenv[name]
        return self.parent.lookup(name, world)

    def assign(self, name, value, world):
        world.kenv[name] = value


class LocalEnv:
    """A call-frame scope (helper invocation / guarded-body execution);
    lives within one top-level statement, so a plain dict is safe."""

    def __init__(self, parent):
        self.parent = parent
        self.vars: Dict[str, object] = {}

    def lookup(self, name: str, world: "World"):
        if name in self.vars:
            return self.vars[name]
        return self.parent.lookup(name, world)

    def assign(self, name, value, world):
        self.vars[name] = value


# -- the world ------------------------------------------------------------


@dataclasses.dataclass
class _InFlight:
    copy: Copy
    step: int


class World:
    def __init__(self):
        self.kenv: Dict[str, object] = {}
        self.memo: Dict[tuple, bool] = {}
        self.bindings: Dict[tuple, int] = {}
        self.in_flight: List[_InFlight] = []
        self.stores: Dict[tuple, object] = {}
        self.findings: Set[tuple] = set()  # (line, tag, msg)
        self.activity = 0  # start/wait operations executed so far
        # per-copy records appended by an installed `on_copy_start`
        # hook (L016 byte accounting).  Like `stores`, EXCLUDED from
        # state_key: the merge keeps the higher-activity (max-DMA)
        # representative, so the surviving traffic log is a feasible
        # world's full copy stream, never a mix.
        self.traffic: List[tuple] = []

    def clone(self) -> "World":
        w = World.__new__(World)
        w.kenv = dict(self.kenv)
        w.memo = dict(self.memo)
        w.bindings = dict(self.bindings)
        w.in_flight = list(self.in_flight)
        w.stores = dict(self.stores)
        w.findings = set(self.findings)
        w.activity = self.activity
        w.traffic = list(self.traffic)
        return w

    def state_key(self, _cache: Optional[dict] = None):
        # Deliberately EXCLUDES `stores` and `memo`: stores are a value
        # cache (hazard checks consult only `in_flight`), and memo-only
        # divergence means the guard outcome changed nothing DMA-visible
        # — so worlds forked on compute-only guards (mask codes, causal
        # windows, dequant paths) collapse right after each statement.
        # The merged world keeps one representative's memo/stores: any
        # finding it reports is real for that feasible world; the cost
        # is possible (documented) under-exploration of the dropped
        # polarity, never a false report.
        flight: Dict[tuple, int] = {}
        for e in self.in_flight:
            fk = (e.copy.key(), e.step)
            flight[fk] = flight.get(fk, 0) + 1
        return (
            frozenset(flight.items()),
            frozenset(self.bindings.items()),
            frozenset((k, _value_key(v, _cache))
                      for k, v in self.kenv.items()),
        )

    def seed(self, key, option):
        kind = key[0]
        if kind == "memo":
            self.memo[key[1]] = option
        else:  # ("bind", termkey)
            self.bindings[key[1]] = option
            self._recheck_memo()

    def _recheck_memo(self):
        for atom, val in self.memo.items():
            decided = _fold_atom(atom, self.bindings)
            if decided is not None and decided != val:
                raise _DeadWorld()


# -- term algebra ---------------------------------------------------------


def _is_concrete(v) -> bool:
    return isinstance(v, (int, float, bool, str)) or v is None


_FOLD_OPS = {
    "add": lambda a, b: a + b,
    "sub": lambda a, b: a - b,
    "mul": lambda a, b: a * b,
    "floordiv": lambda a, b: a // b if b else None,
    "mod": lambda a, b: a % b if b else None,
    "min": min,
    "max": max,
    "cdiv": lambda a, b: -(-a // b) if b else None,
}


def _mk_op(name, a, b):
    if isinstance(a, (int, bool)) and isinstance(b, (int, bool)) \
            and name in _FOLD_OPS:
        v = _FOLD_OPS[name](int(a), int(b))
        if v is not None:
            return v
    # identity simplifications keep structural term equality useful
    if name == "add":
        if a == 0:
            return b
        if b == 0:
            return a
    if name == "sub" and b == 0:
        return a
    if name == "mul":
        if a == 0 or b == 0:
            return 0
        if a == 1:
            return b
        if b == 1:
            return a
    if name in ("min", "max") and a == b:
        return a
    return ("op", name, a, b)


def _subst(term, bindings):
    if not bindings or _is_concrete(term) or not isinstance(term, tuple):
        return term
    if term in bindings:
        return bindings[term]
    if term and term[0] in ("op", "cmp", "and", "or", "not", "call"):
        head = term[:2] if term[0] in ("op", "cmp", "call") else term[:1]
        args = [_subst(t, bindings) for t in term[len(head):]]
        if term[0] == "op" and len(args) == 2:
            return _mk_op(term[1], args[0], args[1])
        return head + tuple(args)
    return term


def _min_bound(term) -> Optional[int]:
    """PROVABLE lower bound of an integer term, None when unknown:
    lets ``fori_loop(0, jnp.maximum(n, 1), ...)`` skip the infeasible
    zero-trip world — the real cross-step-prefetch decode kernel relies
    on exactly that clamp to keep its predecessor's DMA consumed."""
    if isinstance(term, (int, bool)):
        return int(term)
    if isinstance(term, tuple) and term[:2] == ("op", "max"):
        bounds = [b for b in (_min_bound(term[2]), _min_bound(term[3]))
                  if b is not None]
        return max(bounds) if bounds else None
    return None


_CMP_CANON = {
    "lt": ("lt", False, False),   # a < b  -> lt(a,b)
    "gt": ("lt", False, True),    # a > b  -> lt(b,a)
    "gte": ("lt", True, False),   # a >= b -> not lt(a,b)
    "lte": ("lt", True, True),    # a <= b -> not lt(b,a)
    "eq": ("eq", False, False),
    "ne": ("eq", True, False),
    "is": ("is", False, False),
    "isnot": ("is", True, False),
}


def _canon_cmp(op, a, b) -> Tuple[tuple, bool]:
    base, neg, swap = _CMP_CANON[op]
    if swap:
        a, b = b, a
    if base in ("eq", "is") and repr(a) > repr(b):
        a, b = b, a
    return (base, a, b), neg


def _fold_atom(atom, bindings) -> Optional[bool]:
    kind = atom[0]
    if kind in ("lt", "eq"):
        a, b = _subst(atom[1], bindings), _subst(atom[2], bindings)
        if isinstance(a, (int, bool)) and isinstance(b, (int, bool)):
            return (a < b) if kind == "lt" else (a == b)
        if kind == "eq" and isinstance(a, str) and isinstance(b, str):
            return a == b
        if kind == "eq" and a == b and not _is_concrete(a):
            return True
        if kind == "lt" and isinstance(b, (int, bool)):
            mb = _min_bound(a)
            if mb is not None and mb >= int(b):
                return False  # a >= mb >= b, so a < b is impossible
        return None
    if kind == "is":
        a, b = _subst(atom[1], bindings), _subst(atom[2], bindings)
        if _is_concrete(a) and _is_concrete(b):
            return type(a) is type(b) and a == b
        if a == b:
            return True  # one term is identical to itself
        return None
    if kind == "truthy":
        v = _subst(atom[1], bindings)
        if isinstance(v, (int, bool)):
            return bool(v)
        return None
    return None


# -- overlap / matching ---------------------------------------------------


def _bounds(i) -> Optional[Tuple[int, int]]:
    """Concrete [lo, hi) interval of one index element, else None."""
    if isinstance(i, (int, bool)):
        return (int(i), int(i) + 1)
    if isinstance(i, DS) and isinstance(i.start, int) \
            and isinstance(i.size, int):
        return (i.start, i.start + i.size)
    return None


def _dim_rel(a, b) -> str:
    """'eq' | 'overlap' | 'disjoint' | 'unknown' for one dim pair."""
    if a == b and (a == _FULL or a == _ELL):
        return "eq"
    if a == _FULL or b == _FULL or a == _ELL or b == _ELL:
        return "overlap"
    ba, bb = _bounds(a), _bounds(b)
    if ba is not None and bb is not None:
        if ba == bb:
            return "eq"
        return "overlap" if ba[0] < bb[1] and bb[0] < ba[1] \
            else "disjoint"
    if a == b:
        return "eq"
    if isinstance(a, DS) and isinstance(b, DS) and a.start == b.start:
        return "overlap"  # same (possibly opaque) start, sizes >= 1
    if isinstance(a, DS) and a.start == b:
        return "overlap"
    if isinstance(b, DS) and b.start == a:
        return "overlap"
    return "unknown"


def _must_overlap(va: View, vb: View) -> bool:
    """True only when the two regions PROVABLY intersect: equal ref and
    every common-prefix dim structurally equal or concretely
    intersecting (a shorter tuple covers the longer's remainder)."""
    if va.ref != vb.ref:
        return False
    for a, b in zip(va.idx, vb.idx):
        if a == _ELL or b == _ELL:
            return True
        if _dim_rel(a, b) in ("disjoint", "unknown"):
            return False
    return True


def _sem_eq(va: View, vb: View) -> bool:
    if va.ref != vb.ref or len(va.idx) != len(vb.idx):
        return False
    return all(_dim_rel(a, b) == "eq" for a, b in zip(va.idx, vb.idx))


def _sem_must_differ(va: View, vb: View) -> bool:
    if va.ref != vb.ref:
        return True
    return any(_dim_rel(a, b) == "disjoint"
               for a, b in zip(va.idx, vb.idx))


# -- the interpreter ------------------------------------------------------

_MODULE_NAMES = frozenset({"jnp", "jax", "np", "pl", "pltpu", "lax",
                           "functools", "math", "partial"})

# pl/pltpu primitives the simulator cannot model yet: raw semaphore
# signalling and scoped scratch.  Encountering one is a SKIP (counted),
# never a guess.
_SKIP_CALLS = frozenset({"semaphore_signal", "semaphore_wait",
                         "semaphore_read", "run_scoped",
                         "make_async_remote_copy"})


class _Sim:
    def __init__(self, project: Project, site: PallasCallSite,
                 kernel: FunctionInfo, final_axis: int):
        self.project = project
        self.site = site
        self.kernel = kernel
        self.final_axis = final_axis
        self.module_env = ModuleEnv(project, kernel.file)
        self.kernel_env = WorldEnv(self.module_env)
        self.ops = 0
        self.step = 0
        # extension points for the L016 cost walk (cost_parity), which
        # re-runs this simulator under a concrete binding scenario:
        # `on_copy_start(world, copy, line)` observes every DMA issue;
        # `load_seed(refname, idx)` supplies concrete scalar-prefetch
        # values (else loads stay symbolic terms); `static_overrides`
        # replaces OPAQUE `_static_env` entries with scenario constants;
        # `max_unroll` is raised so real chunk loops aren't modeled
        # short (the `hi = lo + _MODEL_INT` clamp would silently drop
        # bytes).  All default to L014's exact behavior.
        self.on_copy_start = None
        self.load_seed = None
        self.static_overrides: Dict[str, object] = {}
        self.max_unroll = MAX_UNROLL

    def _fuel(self):
        self.ops += 1
        if self.ops > MAX_OPS:
            raise KernelSkip("interpreter fuel exhausted")

    # -- findings ---------------------------------------------------------

    def _note(self, world: World, line: int, tag: str, msg: str):
        world.findings.add((line, tag, msg))

    def _carry(self, ent: _InFlight) -> str:
        if ent.step != self.step:
            return (f" (cross-grid-iteration carry: started in step "
                    f"{ent.step}, still in flight in step {self.step})")
        return ""

    def _label(self, world: World, ref: Ref) -> str:
        """World-local name for a ref: forked worlds share Ref objects
        (and therefore `label` mutations) across diverged vararg
        layouts, so name lookup must go through THIS world's env."""
        for name, v in world.kenv.items():
            if isinstance(v, Ref) and v.key == ref.key:
                return name
        return ref.label

    def _check_read(self, world: World, view: View, line: int):
        for ent in world.in_flight:
            if _must_overlap(view, ent.copy.dst):
                self._note(
                    world, line, "read-before-wait",
                    f"read of `{self._label(world, view.ref)}` overlaps "
                    f"the destination of the DMA started at line "
                    f"{ent.copy.line} with no dominating wait"
                    + self._carry(ent))

    def _check_write(self, world: World, view: View, line: int):
        for ent in world.in_flight:
            if _must_overlap(view, ent.copy.dst):
                self._note(
                    world, line, "slot-overwrite",
                    f"write to `{self._label(world, view.ref)}` overlaps "
                    f"the destination of the in-flight DMA started at "
                    f"line {ent.copy.line}" + self._carry(ent))
            elif _must_overlap(view, ent.copy.src):
                self._note(
                    world, line, "source-overwrite",
                    f"write to `{self._label(world, view.ref)}` overlaps "
                    f"the SOURCE of the in-flight DMA started at line "
                    f"{ent.copy.line}" + self._carry(ent))

    def _do_start(self, world: World, copy: Copy, line: int):
        self._check_read(world, copy.src, line)
        self._check_write(world, copy.dst, line)
        world.in_flight.append(_InFlight(copy, self.step))
        world.activity += 1
        if self.on_copy_start is not None:
            self.on_copy_start(world, copy, line)

    def _do_wait(self, world: World, copy: Copy, line: int):
        world.activity += 1
        for i, ent in enumerate(world.in_flight):
            if _sem_eq(ent.copy.sem, copy.sem):
                del world.in_flight[i]
                return
        maybes = [i for i, ent in enumerate(world.in_flight)
                  if not _sem_must_differ(ent.copy.sem, copy.sem)]
        if maybes:
            # a may-match retires silently: unknown terms never report
            del world.in_flight[maybes[0]]
            return
        self._note(
            world, line, "wait-imbalance",
            f"wait on semaphore `{self._label(world, copy.sem.ref)}` "
            f"with no copy in flight on any matching slot along this "
            f"path — start/wait imbalance (the BENCH_r04/r05 wedge "
            f"shape)")

    # -- guards -----------------------------------------------------------

    def _resolve_bool(self, v, world: World) -> bool:
        self._fuel()
        v = _subst(v, world.bindings)
        if isinstance(v, (bool, int, float)):
            return bool(v)
        if isinstance(v, tuple):
            if v[0] == "and":
                return self._resolve_bool(v[1], world) \
                    and self._resolve_bool(v[2], world)
            if v[0] == "or":
                return self._resolve_bool(v[1], world) \
                    or self._resolve_bool(v[2], world)
            if v[0] == "not":
                return not self._resolve_bool(v[1], world)
            if v[0] == "cmp":
                atom, neg = _canon_cmp(v[1], v[2], v[3])
                known = _fold_atom(atom, world.bindings)
                if known is not None:
                    return known != neg
                if atom in world.memo:
                    return world.memo[atom] != neg
                raise _NeedChoice(("memo", atom), [True, False])
        atom = ("truthy", v)
        known = _fold_atom(atom, world.bindings)
        if known is not None:
            return known
        if atom in world.memo:
            return world.memo[atom]
        raise _NeedChoice(("memo", atom), [True, False])

    def _bind_int(self, term, world: World, options: List[int]) -> int:
        term = _subst(term, world.bindings)
        if isinstance(term, (int, bool)):
            return int(term)
        key = ("bind", term)
        if term in world.bindings:
            return world.bindings[term]
        raise _NeedChoice(key, options)

    # -- index helpers ----------------------------------------------------

    def _eval_index(self, node: ast.expr, env, world) -> tuple:
        elts = node.elts if isinstance(node, ast.Tuple) else [node]
        out = []
        for e in elts:
            if isinstance(e, ast.Slice):
                if e.lower is None and e.upper is None and e.step is None:
                    out.append(_FULL)
                else:
                    out.append((
                        "slice",
                        None if e.lower is None
                        else self.eval(e.lower, env, world),
                        None if e.upper is None
                        else self.eval(e.upper, env, world),
                        None if e.step is None
                        else self.eval(e.step, env, world)))
            elif isinstance(e, ast.Constant) and e.value is Ellipsis:
                out.append(_ELL)
            else:
                out.append(self.eval(e, env, world))
        return tuple(out)

    # -- expression evaluation -------------------------------------------

    _BINOPS = {ast.Add: "add", ast.Sub: "sub", ast.Mult: "mul",
               ast.FloorDiv: "floordiv", ast.Mod: "mod",
               ast.Div: "div", ast.Pow: "pow", ast.BitAnd: "and",
               ast.BitOr: "or", ast.BitXor: "xor",
               ast.LShift: "shl", ast.RShift: "shr",
               ast.MatMult: "matmul"}
    _CMPOPS = {ast.Lt: "lt", ast.Gt: "gt", ast.LtE: "lte",
               ast.GtE: "gte", ast.Eq: "eq", ast.NotEq: "ne",
               ast.Is: "is", ast.IsNot: "isnot"}

    def eval(self, node: ast.expr, env, world: World):
        self._fuel()
        if isinstance(node, ast.Constant):
            if node.value is Ellipsis:
                return _ELL
            return node.value
        if isinstance(node, ast.Name):
            v = env.lookup(node.id, world)
            if v is _MISSING:
                if node.id in _MODULE_NAMES:
                    return ("mod", node.id)
                return ("sym", node.id)
            return v
        if isinstance(node, ast.Attribute):
            if node.attr == "at":
                base = self.eval(node.value, env, world)
                if isinstance(base, Ref):
                    return AtProxy(base)
                return ("attr", _as_term(base), "at")
            base = self.eval(node.value, env, world)
            if isinstance(base, Copy) and node.attr in ("start", "wait"):
                return BoundMethod(base, node.attr)
            return ("attr", _as_term(base), node.attr)
        if isinstance(node, ast.BinOp):
            a = self.eval(node.left, env, world)
            b = self.eval(node.right, env, world)
            opname = self._BINOPS.get(type(node.op))
            if opname is None:
                return ("op", "unknown", _as_term(a), _as_term(b))
            if opname in _FOLD_OPS or opname in ("add", "sub", "mul",
                                                 "floordiv", "mod"):
                return _mk_op(opname, _as_term(a), _as_term(b))
            if isinstance(a, (int, bool)) and isinstance(b, (int, bool)):
                if opname == "shl":
                    return int(a) << int(b)
                if opname == "shr":
                    return int(a) >> int(b)
                if opname == "and":
                    return int(a) & int(b)
                if opname == "or":
                    return int(a) | int(b)
                if opname == "xor":
                    return int(a) ^ int(b)
            if opname == "and":
                return ("and", _as_term(a), _as_term(b))
            if opname == "or":
                return ("or", _as_term(a), _as_term(b))
            return ("op", opname, _as_term(a), _as_term(b))
        if isinstance(node, ast.UnaryOp):
            v = self.eval(node.operand, env, world)
            if isinstance(node.op, ast.USub):
                if isinstance(v, (int, float)) \
                        and not isinstance(v, bool):
                    return -v
                return _mk_op("sub", 0, _as_term(v))
            if isinstance(node.op, ast.Not):
                if _is_concrete(v):
                    return not v
                return ("not", _as_term(v))
            if isinstance(node.op, ast.Invert):
                # `~mask` is THE jax boolean-not idiom — losing it to an
                # opaque term decorrelates `~prev_prefetched` from
                # `prev_prefetched`, and the infeasible both-true world
                # re-runs a warmup over its predecessor's in-flight
                # prefetch (a false slot-overwrite on the static
                # cross-step decode variant)
                if isinstance(v, bool):
                    return not v
                if isinstance(v, int):
                    return ~v
                return ("not", _as_term(v))
            return ("op", "unary", _as_term(v), 0)
        if isinstance(node, ast.Compare):
            if len(node.ops) != 1:
                return ("sym", "<chained-compare>")
            a = self.eval(node.left, env, world)
            b = self.eval(node.comparators[0], env, world)
            opname = self._CMPOPS.get(type(node.ops[0]))
            if opname is None:
                return ("sym", "<compare>")
            if opname in ("is", "isnot"):
                # the enum-dispatch idiom: `cross_step_prefetch is True`
                # vs `== "static"` vs falsy.  Identity over the model's
                # value domain (interned literals) is type-and-value
                # equality; anything symbolic stays a per-TERM atom so
                # repeated tests of one static stay correlated instead
                # of collapsing into a single shared <compare> symbol.
                if _is_concrete(a) and _is_concrete(b):
                    same = type(a) is type(b) and a == b
                    return same if opname == "is" else not same
                return ("cmp", opname, _as_term(a), _as_term(b))
            if _is_concrete(a) and _is_concrete(b) \
                    and type(a) is type(b):
                return {"lt": a < b, "gt": a > b, "lte": a <= b,
                        "gte": a >= b, "eq": a == b,
                        "ne": a != b}[opname]
            if isinstance(a, (int, bool)) and isinstance(b, (int, bool)):
                a, b = int(a), int(b)
                return {"lt": a < b, "gt": a > b, "lte": a <= b,
                        "gte": a >= b, "eq": a == b,
                        "ne": a != b}[opname]
            return ("cmp", opname, _as_term(a), _as_term(b))
        if isinstance(node, ast.BoolOp):
            terms = [_as_term(self.eval(v, env, world))
                     for v in node.values]
            out = terms[0]
            kind = "and" if isinstance(node.op, ast.And) else "or"
            for t in terms[1:]:
                out = (kind, out, t)
            return out
        if isinstance(node, ast.IfExp):
            # pure two-branch values with an unknown test fork via the
            # shared memo, so `i += 1 if has_mask else 0` stays concrete
            b = self._resolve_bool(
                _as_term(self.eval(node.test, env, world)), world)
            return self.eval(node.body if b else node.orelse, env, world)
        if isinstance(node, ast.Subscript):
            return self._eval_subscript(node, env, world)
        if isinstance(node, ast.Call):
            return self._eval_call(node, env, world)
        if isinstance(node, (ast.Tuple, ast.List)):
            return [self.eval(e, env, world) for e in node.elts]
        if isinstance(node, ast.Lambda):
            return Closure(node, env)
        if isinstance(node, ast.JoinedStr):
            return ("sym", "<fstring>")
        if isinstance(node, ast.Starred):
            raise KernelSkip("starred expression")
        return ("sym", f"<{type(node).__name__}>")

    def _eval_subscript(self, node: ast.Subscript, env, world: World):
        base = self.eval(node.value, env, world)
        if isinstance(base, Varargs):
            sl = node.slice
            if isinstance(sl, ast.Slice):
                if sl.upper is not None or sl.step is not None:
                    raise KernelSkip("vararg slice with upper bound")
                lo = self.eval(sl.lower, env, world) if sl.lower else 0
                if not isinstance(lo, int):
                    raise KernelSkip("vararg slice at unknown offset")
                return VarargTail(base, lo)
            i = self.eval(sl, env, world)
            if not isinstance(i, int):
                raise KernelSkip("vararg indexed by unknown value")
            return base.get(i)
        if isinstance(base, AtProxy):
            return View(base.ref, self._eval_index(node.slice, env, world))
        if isinstance(base, Ref):
            idx = self._eval_index(node.slice, env, world)
            view = View(base, idx)
            self._check_read(world, view, node.lineno)
            skey = (base.key, tuple(_idx_key(i) for i in idx))
            if skey in world.stores:
                return world.stores[skey]
            if self.load_seed is not None:
                seeded = self.load_seed(self._label(world, base), idx)
                if seeded is not None:
                    return seeded
            return ("load", base.key, tuple(_idx_key(i) for i in idx))
        if isinstance(base, (list, tuple)):
            sl = node.slice
            if isinstance(sl, ast.Slice):
                lo = self.eval(sl.lower, env, world) if sl.lower else None
                hi = self.eval(sl.upper, env, world) if sl.upper else None
                if (lo is None or isinstance(lo, int)) \
                        and (hi is None or isinstance(hi, int)):
                    return list(base)[lo:hi]
                return ("sym", "<seq-slice>")
            i = self.eval(sl, env, world)
            if isinstance(i, int) and -len(base) <= i < len(base):
                return base[i]
            return ("sym", "<seq-index>")
        idx = self._eval_index(node.slice, env, world)
        return ("op", "index", _as_term(base),
                tuple(_idx_key(i) for i in idx))

    def _call_closure(self, clo: Closure, args: list, kwargs: dict,
                      world: World):
        self._fuel()
        frame = LocalEnv(clo.env)
        a = clo.node.args
        params = [p.arg for p in a.posonlyargs + a.args]
        defaults = a.defaults or []
        # positional params without a supplied arg take their default
        # (evaluated at call time in the defining scope — close enough
        # for the `def _(j=j)` capture idiom, whose default is a local)
        ndef = len(defaults)
        for i, p in enumerate(params):
            if i < len(args):
                frame.vars[p] = args[i]
            elif p in kwargs:
                frame.vars[p] = kwargs.pop(p)
            else:
                di = i - (len(params) - ndef)
                if 0 <= di < ndef:
                    frame.vars[p] = self.eval(defaults[di], clo.env, world)
                else:
                    frame.vars[p] = ("sym", p)
        for kw, kwd in zip(a.kwonlyargs, a.kw_defaults):
            if kw.arg in kwargs:
                frame.vars[kw.arg] = kwargs.pop(kw.arg)
            elif kwd is not None:
                frame.vars[kw.arg] = self.eval(kwd, clo.env, world)
            else:
                frame.vars[kw.arg] = ("sym", kw.arg)
        if a.vararg is not None:
            frame.vars[a.vararg.arg] = list(args[len(params):])
        if isinstance(clo.node, ast.Lambda):
            return self.eval(clo.node.body, frame, world)
        try:
            self.exec_body(clo.node.body, frame, world)
        except _Return as r:
            return r.value
        return None

    def _eval_call(self, node: ast.Call, env, world: World):
        func = node.func
        base = expr_basename(func)
        root = expr_root(func)

        if base in _SKIP_CALLS:
            raise KernelSkip(f"unmodeled primitive `{base}`")

        def _args():
            out = []
            for a in node.args:
                if isinstance(a, ast.Starred):
                    v = self.eval(a.value, env, world)
                    if not isinstance(v, (list, tuple)):
                        raise KernelSkip(
                            "star-unpack of unknown-length value")
                    out.extend(v)
                else:
                    out.append(self.eval(a, env, world))
            return out

        def _kwargs():
            return {k.arg: self.eval(k.value, env, world)
                    for k in node.keywords if k.arg}

        # list mutation: the real kv_dmas helpers build their copy
        # batches with `dmas.append(make_async_copy(...))`
        if isinstance(func, ast.Attribute) \
                and base in ("append", "extend"):
            recv = self.eval(func.value, env, world)
            if isinstance(recv, list):
                args = _args()
                if base == "append":
                    recv.append(args[0] if args else None)
                elif args and isinstance(args[0], (list, tuple)):
                    recv.extend(args[0])
                else:
                    raise KernelSkip("list.extend of unknown iterable")
                return None
        # method calls on evaluated receivers (copy.start()/.wait())
        if isinstance(func, ast.Attribute) \
                and base in ("start", "wait"):
            recv = self.eval(func.value, env, world)
            if isinstance(recv, Copy):
                if base == "start":
                    self._do_start(world, recv, node.lineno)
                else:
                    self._do_wait(world, recv, node.lineno)
                return None
            raise KernelSkip(f".{base}() on unresolved copy object")
        if base == "make_async_copy":
            args = _args()
            if len(args) != 3:
                raise KernelSkip("make_async_copy arity != 3")
            views = []
            for a in args:
                if isinstance(a, Ref):
                    a = View(a, (_ELL,))
                if not isinstance(a, View):
                    raise KernelSkip("make_async_copy operand is not a "
                                     "resolvable ref view")
                views.append(a)
            return Copy(views[0], views[1], views[2], node.lineno)
        if base == "when" and root in ("pl", None):
            args = _args()
            return WhenPred(_as_term(args[0]) if args else True)
        if base == "ds":
            args = _args()
            if len(args) == 1:
                return DS(0, _as_term(args[0]))
            return DS(_as_term(args[0]), _as_term(args[1]))
        if base == "program_id":
            axis = const_int(node.args[0]) if node.args else None
            if axis is not None and axis % max(self.grid_rank, 1) \
                    == self.final_axis:
                return self.step
            return ("sym", f"pid{axis}")
        if base == "num_programs":
            axis = const_int(node.args[0]) if node.args else None
            if axis is not None and axis % max(self.grid_rank, 1) \
                    == self.final_axis:
                return N_STEPS
            return ("sym", f"nprog{axis}")
        if base == "fori_loop":
            return self._eval_fori(node, env, world)
        if base == "cond" and root in ("lax", "jax"):
            return self._eval_lax_cond(node, env, world)
        if base in ("minimum", "maximum"):
            a, b = (_as_term(v) for v in _args()[:2])
            return _mk_op("min" if base == "minimum" else "max", a, b)
        if base == "where":
            args = _args()
            if len(args) == 3:
                # scalar select with a concrete predicate picks its
                # branch (`jnp.where(b == 0, 0, base_smem[0])` — the
                # cross-step slot-parity seed); a symbolic/array
                # predicate stays an uninterpreted call
                if isinstance(args[0], (bool, int)):
                    return args[1] if args[0] else args[2]
            return ("call", "where",
                    tuple(_as_term(a) for a in args))
        if base in ("rem", "remainder"):
            a, b = (_as_term(v) for v in _args()[:2])
            return _mk_op("mod", a, b)
        if base == "cdiv":
            a, b = (_as_term(v) for v in _args()[:2])
            return _mk_op("cdiv", a, b)
        if base == "logical_and":
            a, b = (_as_term(v) for v in _args()[:2])
            return ("and", a, b)
        if base == "logical_or":
            a, b = (_as_term(v) for v in _args()[:2])
            return ("or", a, b)
        if base == "logical_not":
            return ("not", _as_term(_args()[0]))
        if base == "range" and isinstance(func, ast.Name):
            args = [_as_term(v) for v in _args()]
            if len(args) == 1:
                lo, hi = 0, args[0]
            elif len(args) >= 2:
                lo, hi = args[0], args[1]
            else:
                raise KernelSkip("range() without bounds")
            if not isinstance(lo, int):
                raise KernelSkip("range() with unknown start")
            if not isinstance(hi, int):
                hi = self._bind_int(hi, world, [_MODEL_INT])
            if hi - lo > self.max_unroll:
                if self.on_copy_start is not None:
                    # a short model silently DROPS bytes — in a cost
                    # walk that is a guess, so it must be a skip
                    raise KernelSkip(
                        f"range({hi - lo}) exceeds the cost-walk "
                        f"unroll ceiling {self.max_unroll}")
                hi = lo + _MODEL_INT  # model a long static loop short
            return RangeVal(lo, hi)
        if base == "len" and isinstance(func, ast.Name):
            args = _args()
            if args and isinstance(args[0], (list, tuple)):
                return len(args[0])
            return ("sym", "<len>")
        if base in ("int", "bool", "abs", "float") \
                and isinstance(func, ast.Name):
            args = _args()
            if args and _is_concrete(args[0]):
                try:
                    return {"int": int, "bool": bool, "abs": abs,
                            "float": float}[base](args[0])
                except (TypeError, ValueError):
                    pass
            return ("call", base, _as_term(args[0]) if args else 0)

        # user value in function position: closures, when-predicates
        callee = None
        if isinstance(func, ast.Name):
            callee = env.lookup(func.id, world)
        elif isinstance(func, ast.Call):
            callee = self.eval(func, env, world)
        if isinstance(callee, WhenPred):
            args = _args()
            if len(args) == 1 and isinstance(args[0], Closure):
                if self._resolve_bool(callee.cond, world):
                    return self._call_closure(args[0], [], {}, world)
                return None
            raise KernelSkip("pl.when(...) applied to a non-closure")
        if isinstance(callee, Closure):
            return self._call_closure(callee, _args(), _kwargs(), world)
        if isinstance(callee, BoundMethod):
            if callee.name == "start":
                self._do_start(world, callee.recv, node.lineno)
            else:
                self._do_wait(world, callee.recv, node.lineno)
            return None

        # anything else: uninterpreted.  Refs passed whole count as
        # reads (zeros_like(ref) et al touch at most the metadata, but
        # MUST semantics keeps that from ever reporting falsely).  The
        # receiver of a method call (`qbuf[qslot].reshape(...)`) is a
        # read too — evaluate it so its subscripts get checked.
        args = _args()
        _kwargs()
        if isinstance(func, ast.Attribute) \
                and not isinstance(func.value, ast.Name):
            recv = self.eval(func.value, env, world)
            if isinstance(recv, Ref):
                self._check_read(world, View(recv, (_ELL,)), node.lineno)
            if isinstance(recv, Copy):
                raise KernelSkip("copy object escapes into an "
                                 "uninterpreted method call")
        for a in args:
            if isinstance(a, Ref):
                self._check_read(world, View(a, (_ELL,)), node.lineno)
            if isinstance(a, Copy):
                raise KernelSkip("copy object escapes into an "
                                 "uninterpreted call")
        return ("call", base or "<expr>",
                tuple(_as_term(a) for a in args))

    def _eval_fori(self, node: ast.Call, env, world: World):
        if len(node.args) < 4:
            raise KernelSkip("fori_loop arity < 4")
        lo = self.eval(node.args[0], env, world)
        hi = _as_term(self.eval(node.args[1], env, world))
        body = self.eval(node.args[2], env, world)
        carry = self.eval(node.args[3], env, world)
        if not isinstance(lo, int):
            raise KernelSkip("fori_loop with unknown lower bound")
        if not isinstance(body, Closure):
            raise KernelSkip("fori_loop body is not a local function")
        hi = _subst(hi, world.bindings)
        if isinstance(hi, (int, bool)):
            trips = int(hi) - lo
            if trips > self.max_unroll:
                raise KernelSkip(
                    f"fori_loop with {trips} static iterations")
        else:
            mb = max(0, _min_bound(hi) or 0)
            if mb > MAX_TRIP:
                raise KernelSkip("fori_loop bound too large to model")
            trips = self._bind_int(
                hi, world, list(range(mb, MAX_TRIP + 1))) - lo
        for it in range(lo, lo + max(0, trips)):
            carry = self._call_closure(body, [it, carry], {}, world)
        return carry

    def _eval_lax_cond(self, node: ast.Call, env, world: World):
        pred = _as_term(self.eval(node.args[0], env, world))
        branches = [self.eval(a, env, world) for a in node.args[1:3]]
        operands = [self.eval(a, env, world) for a in node.args[3:]]
        b = self._resolve_bool(pred, world)
        chosen = branches[0] if b else branches[1]
        if not isinstance(chosen, Closure):
            raise KernelSkip("lax.cond branch is not a local function")
        return self._call_closure(chosen, operands, {}, world)

    # -- statements -------------------------------------------------------

    def exec_body(self, stmts: List[ast.stmt], env, world: World):
        for s in stmts:
            self.exec_stmt(s, env, world)

    def _assign_target(self, target: ast.expr, value, env, world: World):
        if isinstance(target, ast.Name):
            env.assign(target.id, value, world)
            if isinstance(value, Ref) and value.label == value.key:
                value.label = target.id
            return
        if isinstance(target, (ast.Tuple, ast.List)):
            elts = target.elts
            if isinstance(value, Varargs):
                value = VarargTail(value, 0)
            if isinstance(value, VarargTail):
                if any(isinstance(e, ast.Starred) for e in elts):
                    raise KernelSkip("starred unpack of kernel varargs")
                value = [value.base.get(value.start + k)
                         for k in range(len(elts))]
            if not isinstance(value, (list, tuple)) \
                    or len(value) != len(elts):
                raise KernelSkip("tuple unpack of unknown-length value")
            for t, v in zip(elts, value):
                self._assign_target(t, v, env, world)
            return
        if isinstance(target, ast.Subscript):
            base = self.eval(target.value, env, world)
            if isinstance(base, AtProxy):
                base = base.ref
            if isinstance(base, Ref):
                idx = self._eval_index(target.slice, env, world)
                view = View(base, idx)
                self._check_write(world, view, target.lineno)
                skey = (base.key, tuple(_idx_key(i) for i in idx))
                world.stores[skey] = _as_term(value) \
                    if _is_concrete(value) or isinstance(value, tuple) \
                    else ("sym", "<stored>")
                return
            if isinstance(base, list):
                return  # python-list mutation: value not tracked
            raise KernelSkip("store through an unresolved subscript")
        if isinstance(target, ast.Starred):
            raise KernelSkip("starred assignment")
        if isinstance(target, ast.Attribute):
            raise KernelSkip("attribute assignment in kernel body")
        raise KernelSkip(f"unhandled assign target "
                         f"{type(target).__name__}")

    def exec_stmt(self, stmt: ast.stmt, env, world: World):
        self._fuel()
        if isinstance(stmt, ast.Expr):
            self.eval(stmt.value, env, world)
            return
        if isinstance(stmt, ast.Assign):
            value = self.eval(stmt.value, env, world)
            for t in stmt.targets:
                self._assign_target(t, value, env, world)
            return
        if isinstance(stmt, ast.AnnAssign):
            if stmt.value is not None:
                self._assign_target(
                    stmt.target, self.eval(stmt.value, env, world),
                    env, world)
            return
        if isinstance(stmt, ast.AugAssign):
            if isinstance(stmt.target, ast.Name):
                load: ast.expr = ast.Name(id=stmt.target.id,
                                          ctx=ast.Load())
            elif isinstance(stmt.target, ast.Subscript):
                # `acc_ref[...] += dot(...)` — the MXU accumulate
                # idiom: desugar to load, fold, store so the read is
                # hazard-checked and the stored term stays typed
                load = ast.Subscript(value=stmt.target.value,
                                     slice=stmt.target.slice,
                                     ctx=ast.Load())
            else:
                raise KernelSkip("augmented assign to non-name")
            cur = self.eval(ast.copy_location(load, stmt), env, world)
            rhs = self.eval(stmt.value, env, world)
            opname = self._BINOPS.get(type(stmt.op))
            if opname in _FOLD_OPS or opname in ("add", "sub", "mul",
                                                 "floordiv", "mod"):
                nv = _mk_op(opname, _as_term(cur), _as_term(rhs))
            else:
                nv = ("op", opname or "unknown", _as_term(cur),
                      _as_term(rhs))
            if isinstance(stmt.target, ast.Name):
                env.assign(stmt.target.id, nv, world)
            else:
                self._assign_target(stmt.target, nv, env, world)
            return
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            when_cond = None
            for dec in stmt.decorator_list:
                if isinstance(dec, ast.Call) \
                        and expr_basename(dec.func) == "when":
                    when_cond = _as_term(
                        self.eval(dec.args[0], env, world))
            clo = Closure(stmt, env)
            if when_cond is not None:
                if self._resolve_bool(when_cond, world):
                    self._call_closure(clo, [], {}, world)
                env.assign(stmt.name, None, world)
            else:
                env.assign(stmt.name, clo, world)
            return
        if isinstance(stmt, ast.If):
            b = self._resolve_bool(
                _as_term(self.eval(stmt.test, env, world)), world)
            self.exec_body(stmt.body if b else stmt.orelse, env, world)
            return
        if isinstance(stmt, ast.For):
            it = self.eval(stmt.iter, env, world)
            if isinstance(it, RangeVal):
                items = it.items()
            elif isinstance(it, (list, tuple)):
                items = list(it)
            else:
                if _mentions_dma(stmt):
                    raise KernelSkip("for-loop over unknown iterable "
                                     "containing DMA operations")
                return
            for item in items:
                try:
                    self._assign_target(stmt.target, item, env, world)
                    self.exec_body(stmt.body, env, world)
                except _Continue:
                    continue
                except _Break:
                    break
            else:
                self.exec_body(stmt.orelse, env, world)
            return
        if isinstance(stmt, ast.While):
            if _mentions_dma(stmt):
                raise KernelSkip("while-loop containing DMA operations")
            return
        if isinstance(stmt, ast.Return):
            raise _Return(None if stmt.value is None
                          else self.eval(stmt.value, env, world))
        if isinstance(stmt, ast.Break):
            raise _Break()
        if isinstance(stmt, ast.Continue):
            raise _Continue()
        if isinstance(stmt, (ast.Pass, ast.Global, ast.Nonlocal,
                             ast.Import, ast.ImportFrom)):
            return
        if isinstance(stmt, ast.Assert):
            self.eval(stmt.test, env, world)
            return
        if isinstance(stmt, (ast.Raise, ast.Try, ast.With,
                             ast.AsyncWith, ast.ClassDef)):
            if _mentions_dma(stmt):
                raise KernelSkip(
                    f"{type(stmt).__name__} containing DMA operations")
            return
        if isinstance(stmt, ast.Delete):
            return
        raise KernelSkip(f"unhandled statement {type(stmt).__name__}")

    # -- top-level driver -------------------------------------------------

    def _run_stmt_forked(self, stmt: ast.stmt,
                         world: World) -> List[World]:
        """Execute one top-level statement, forking on every fresh
        guard choice: each fork seeds the memo/binding and re-executes
        the statement on a clone of the pre-statement state."""
        queue = [world]
        done: List[World] = []
        paths = 0
        while queue:
            paths += 1
            if paths > MAX_STMT_PATHS:
                raise KernelSkip("guard fork explosion in one statement")
            w = queue.pop()
            w2 = w.clone()
            try:
                self.exec_stmt(stmt, self.kernel_env, w2)
                done.append(w2)
            except _NeedChoice as nc:
                for opt in nc.options:
                    w3 = w.clone()
                    try:
                        w3.seed(nc.key, opt)
                    except _DeadWorld:
                        continue
                    queue.append(w3)
            except _DeadWorld:
                continue
        return done

    @staticmethod
    def _merge(worlds: List[World]) -> List[World]:
        # On collision keep the HIGHER-ACTIVITY world: its memo is the
        # one that actually started/waited DMAs, and the grid repeats
        # the same statements next step — so a guard polarity that
        # fired a start this step (e.g. an over-wide warmup that
        # re-fires every step and wedges) stays represented instead of
        # being shadowed by its idle twin.  The kept world is feasible,
        # so this can never create a false report.
        by_key: Dict[tuple, World] = {}
        cache: dict = {}
        for w in worlds:
            k = w.state_key(cache)
            kept = by_key.get(k)
            if kept is None:
                by_key[k] = w
            elif w.activity > kept.activity:
                w.findings |= kept.findings
                by_key[k] = w
            else:
                kept.findings |= w.findings
        return list(by_key.values())

    def _eval_test_forked(self, test: ast.expr,
                          world: World) -> List[Tuple[World, bool]]:
        """Resolve one `if` test on its own, forking only on the test."""
        queue = [world]
        out: List[Tuple[World, bool]] = []
        paths = 0
        while queue:
            paths += 1
            if paths > MAX_STMT_PATHS:
                raise KernelSkip("guard fork explosion in one test")
            w = queue.pop()
            w2 = w.clone()
            try:
                b = self._resolve_bool(
                    _as_term(self.eval(test, self.kernel_env, w2)), w2)
                out.append((w2, b))
            except _NeedChoice as nc:
                for opt in nc.options:
                    w3 = w.clone()
                    try:
                        w3.seed(nc.key, opt)
                    except _DeadWorld:
                        continue
                    queue.append(w3)
            except _DeadWorld:
                continue
        return out

    def _run_block_forked(self, stmts: List[ast.stmt],
                          worlds: List[World]) -> List[World]:
        """Run a statement block over a world set, merging after every
        statement.  Plain `if` statements recurse so each nested
        statement forks independently — without this, a module-sized
        ``if attend:`` block re-executes once per guard COMBINATION
        (exponential fuel) instead of once per guard."""
        for stmt in stmts:
            if isinstance(stmt, ast.If):
                true_ws: List[World] = []
                false_ws: List[World] = []
                for w in worlds:
                    for w2, b in self._eval_test_forked(stmt.test, w):
                        (true_ws if b else false_ws).append(w2)
                nxt: List[World] = []
                if true_ws:
                    nxt.extend(self._run_block_forked(stmt.body, true_ws))
                if false_ws:
                    nxt.extend(
                        self._run_block_forked(stmt.orelse, false_ws)
                        if stmt.orelse else false_ws)
                worlds = self._merge(nxt)
            else:
                nxt = []
                for w in worlds:
                    nxt.extend(self._run_stmt_forked(stmt, w))
                worlds = self._merge(nxt)
            if not worlds:
                raise KernelSkip("every model world died "
                                 "(inconsistent guard model)")
            if len(worlds) > MAX_WORLDS:
                raise KernelSkip("model world explosion")
        return worlds

    def _run_worlds(self) -> List[World]:
        """The small-step walk itself: N_STEPS modeled grid steps over
        every surviving world, returned WITH their per-world state so
        subclasses (the L016 cost model) can read accumulated traffic
        before finding extraction."""
        node = self.kernel.node
        a = node.args
        self.grid_rank = self.site.grid_rank or 1
        statics = _static_env(self.site, self.kernel)
        for name, val in self.static_overrides.items():
            # scenario constants replace only OPAQUE statics: literal
            # binds and the final-grid-axis N_STEPS tie stay the model's
            cur = statics.get(name)
            if cur is None or (isinstance(cur, tuple)
                               and cur[:1] == ("static",)):
                statics[name] = val
        pos_params = [p.arg for p in a.posonlyargs + a.args]

        base = World()
        worlds = [base]
        for step in range(N_STEPS):
            self.step = step
            for w in worlds:
                w.kenv = {}
                for i, p in enumerate(pos_params):
                    if i < self.site.kernel_bound_posargs:
                        w.kenv[p] = statics.get(p, ("static", p))
                    else:
                        w.kenv[p] = Ref(p)
                for kw in a.kwonlyargs:
                    w.kenv[kw.arg] = statics.get(
                        kw.arg, ("static", kw.arg))
                if a.vararg is not None:
                    w.kenv[a.vararg.arg] = Varargs(a.vararg.arg)
            # Ref equality is by key (the param name), so re-creating
            # them per step above is identity-preserving per world.
            worlds = self._run_block_forked(node.body, worlds)
        return worlds

    def run(self) -> List[Finding]:
        worlds = self._run_worlds()
        findings: Set[tuple] = set()
        for w in worlds:
            for ent in w.in_flight:
                w.findings.add((
                    ent.copy.line, "dangling-dma",
                    f"DMA started at line {ent.copy.line} (step "
                    f"{ent.step}) is never waited along some path "
                    f"through the grid — start/wait imbalance that "
                    f"wedges the chip on teardown"))
            findings |= w.findings
        out: List[Finding] = []
        for line, tag, msg in sorted(findings):
            out.append(Finding(
                "L014", self.kernel.file.path, line,
                self.kernel.qualname, f"[{tag}] {msg}"))
        return out


def _as_term(v):
    """Coerce an evaluated value into a hashable term for memo keys and
    arithmetic; refs/views keep their identity keys."""
    if isinstance(v, Ref):
        return ("refval", v.key)
    if isinstance(v, View):
        return ("viewval", _view_key(v))
    if isinstance(v, DS):
        return v
    if isinstance(v, list):
        return tuple(_as_term(x) for x in v)
    if isinstance(v, (Copy, Closure, BoundMethod, WhenPred, Varargs,
                      VarargTail, AtProxy, RangeVal)):
        return ("objval", _value_key(v))
    return v


# -- static parameter seeding --------------------------------------------


def _static_env(site: PallasCallSite,
                kernel: FunctionInfo) -> Dict[str, object]:
    """Partial-bound kernel params evaluated in the launcher's scope:
    literals resolve concretely; a value expr that IS the final grid
    element ties to N_STEPS (the `num_units` coupling every
    cross-unit-prefetch guard needs; a kernel that reads the bound as
    `pl.num_programs(<final axis>)` instead, as the work-unit prefill
    mainloop does for its dynamic trip count, gets the same tie from
    the evaluator); the rest stay opaque statics."""
    out: Dict[str, object] = {}
    grid_last = None
    if site.grid_exprs:
        grid_last = ast.dump(site.grid_exprs[-1])
    # trampoline forks carry bound exprs written in the CALLER's scope
    expr_locals = site.bound_expr_locals or site.locals_

    def _value(name: str, expr: ast.expr):
        if grid_last is not None and ast.dump(expr) == grid_last:
            return N_STEPS
        c = const_int(expr)
        if c is not None:
            return c
        if isinstance(expr, ast.Constant) and isinstance(
                expr.value, (str, float, bool)):
            return expr.value
        if isinstance(expr, ast.Name):
            v = expr_locals.value_of(expr.id)
            if v is not None:
                return _value(name, v)
        if isinstance(expr, ast.UnaryOp) \
                and isinstance(expr.op, ast.USub):
            c = const_int(expr)
            if c is not None:
                return c
        return ("static", name)

    a = kernel.node.args
    pos_params = [p.arg for p in a.posonlyargs + a.args]
    for i, expr in enumerate(site.kernel_bound_posarg_exprs):
        if i < len(pos_params):
            out[pos_params[i]] = _value(pos_params[i], expr)
    for name, expr in site.kernel_bound_kwarg_exprs.items():
        out[name] = _value(name, expr)
    return out


# -- DMA reachability scan ------------------------------------------------


def _mentions_dma(node: ast.AST) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) \
                and n.attr in ("make_async_copy", "start", "wait"):
            return True
        if isinstance(n, ast.Name) and n.id == "make_async_copy":
            return True
    return False


def _kernel_has_dma(project: Project, kernel: FunctionInfo,
                    _depth: int = 0,
                    _seen: Optional[Set[int]] = None) -> bool:
    """Transitive make_async_copy reachability: the kernel body plus
    same-project helpers it calls by name (one name-resolution hop per
    level, depth-capped)."""
    if _seen is None:
        _seen = set()
    if id(kernel.node) in _seen or _depth > 3:
        return False
    _seen.add(id(kernel.node))
    for n in ast.walk(kernel.node):
        if isinstance(n, (ast.Attribute, ast.Name)) \
                and (getattr(n, "attr", None) == "make_async_copy"
                     or getattr(n, "id", None) == "make_async_copy"):
            return True
    for n in ast.walk(kernel.node):
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Name):
            fi = project.resolve_function(
                n.func.id, prefer_file=kernel.file)
            if fi is not None and _kernel_has_dma(
                    project, fi, _depth + 1, _seen):
                return True
    return False


# -- pass driver ----------------------------------------------------------

# The symbolic walk is the analyzer's one genuinely expensive pass
# (seconds over the package tree), and a build runs it several times
# over IDENTICAL sources — the driver, `obs doctor`'s coverage counts,
# and every whole-tree test each construct their own Project.  Memoize
# on file content, not Project identity, so all of them share one walk;
# a single mutated source (the skew tests) misses cleanly.
_MEMO_CAP = 32
_memo: "Dict[tuple, tuple]" = {}


def _memo_key(project: Project):
    return tuple(sorted((sf.path, hash(sf.src)) for sf in project.files))


def _analyze(project: Project):
    """-> (findings, stats) shared by run() and stats() — memoized on
    source content (see _memo above)."""
    key = _memo_key(project)
    hit = _memo.get(key)
    if hit is not None:
        return hit
    result = _analyze_uncached(project)
    if len(_memo) >= _MEMO_CAP:
        _memo.pop(next(iter(_memo)))
    _memo[key] = result
    return result


def _analyze_uncached(project: Project):
    findings: List[Finding] = []
    stats = {"kernels_analyzed": 0, "kernels_skipped": 0,
             "kernels_no_dma": 0, "sites_unresolved": 0,
             "skip_reasons": {}}
    seen: Set[tuple] = set()
    emitted: Set[tuple] = set()
    for site in project.pallas_sites:
        if site.kernel is None:
            stats["sites_unresolved"] += 1
            continue
        key = (id(site.kernel.node), site.call.lineno, site.file.path)
        if key in seen:
            continue
        seen.add(key)
        if not _kernel_has_dma(project, site.kernel):
            stats["kernels_no_dma"] += 1
            continue
        try:
            if site.grid_rank is None:
                raise KernelSkip("grid rank not statically visible")
            sim = _Sim(project, site, site.kernel,
                       final_axis=site.grid_rank - 1)
            for f in sim.run():
                fkey = (f.filename, f.line, f.message)
                if fkey not in emitted:
                    emitted.add(fkey)
                    findings.append(f)
            stats["kernels_analyzed"] += 1
        except KernelSkip as e:
            stats["kernels_skipped"] += 1
            reason = str(e) or "unexecutable kernel"
            stats["skip_reasons"][f"{site.kernel.qualname}"] = reason
    return findings, stats


def run(project: Project) -> List[Finding]:
    findings, _stats = _analyze(project)
    return list(findings)  # memoized — hand out a copy


def stats(project: Project) -> dict:
    """analyzed-vs-skipped kernel counts for ``obs doctor`` — the L013
    no-silent-skip rule applied to kernel bodies."""
    _findings, st = _analyze(project)
    return {**st, "skip_reasons": dict(st["skip_reasons"])}
