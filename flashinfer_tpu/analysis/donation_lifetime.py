"""L011 — donated-buffer lifetime violations at compile-once call sites.

The serving layer's deepest runtime contract (PR 7/8): the fused step
is compiled with ``donate_argnums`` so XLA aliases the KV caches, page
tables, and PRNG key in place — after a ``step(...)`` call the donated
INPUT buffers are dead.  At runtime the violation surfaces as jax's
deleted-buffer error *on the first run that actually reuses the
buffer* (often a cold path: telemetry, an error handler, a rarely-hit
branch).  Every piece is statically decidable from the AST, so this
pass proves the three lifetime contracts at lint time:

1. **Use-after-donate.**  At a call through a donation-compiled
   callable (``self._step = jax.jit(body, donate_argnums=...)`` and
   friends), any later read of a NAME that was passed at a donated
   position — without an intervening rebind — reads a dead buffer.
2. **Donated-and-captured.**  A donated argument whose name the jitted
   body ALSO reads as a closure is aliased on both sides of the
   donation: the traced constant and the donated operand race, and XLA
   may fold the closure copy into the program (a silent stale read,
   not an error).
3. **Both-or-neither shardings.**  A ``jax.jit`` call spelling
   exactly one of ``in_shardings``/``out_shardings`` lets the compiler
   re-derive the missing side and partition the program differently
   than the plan says (``parallel/plan.py`` passes both): a
   cannot-land review failure.

Resolution rides the ``core.py`` layer: donation-compiled callables
resolve through once-assigned locals (``step = jax.jit(...)``),
``self.<attr>`` assignments anywhere in the enclosing class, and
project functions whose single ``return`` is the jit call (the
``build_fused_step`` builder idiom).  Anything not statically
decidable — starred call args, multi-assigned names, dynamic
donate_argnums, reads the straight-line continuation of the call
cannot prove (past the ``if`` arm holding the call, after a
maybe-zero-iteration loop) — is SKIPPED, never guessed.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Dict, FrozenSet, List, Optional, Set, Tuple

from flashinfer_tpu.analysis.core import (JIT_LIKE_NAMES, ChainLocals,
                                          Finding, FnLocals, Project,
                                          SourceFile, expr_basename,
                                          walk_own_scope)

CODE = "L011"

# callables that compile a step body with explicit donation semantics
# (the first positional argument is the body function in every
# spelling) live in the shared core registry, so L012 sees the same set


def _is_jit_like(call: ast.Call) -> bool:
    return expr_basename(call.func) in JIT_LIKE_NAMES


def _const_set(expr: ast.expr, locals_: Optional[FnLocals], pred,
               _depth: int = 0) -> Optional[FrozenSet]:
    """Statically-known elements of a donation expression: a literal
    accepted by `pred`, a tuple/list of them, a once-assigned local
    name, or a conditional between resolvable branches (the
    ``(2, 3) if donate else ()`` idiom — the union is taken: if EITHER
    branch donates, post-call reuse is a bug on that branch).  One
    resolver serves both spellings (donate_argnums ints and
    donate_argnames strs) so they can never diverge in what they
    resolve."""
    if _depth > 6:
        return None
    if isinstance(expr, ast.Constant):
        return frozenset({expr.value}) if pred(expr.value) else None
    if isinstance(expr, (ast.Tuple, ast.List)):
        out: Set = set()
        for e in expr.elts:
            if not (isinstance(e, ast.Constant) and pred(e.value)):
                return None
            out.add(e.value)
        return frozenset(out)
    if isinstance(expr, ast.IfExp):
        lo = _const_set(expr.body, locals_, pred, _depth + 1)
        hi = _const_set(expr.orelse, locals_, pred, _depth + 1)
        if lo is None or hi is None:
            return None
        return lo | hi
    if isinstance(expr, ast.Name) and locals_ is not None:
        v = locals_.value_of(expr.id)
        if v is not None:
            return _const_set(v, locals_, pred, _depth + 1)
    return None


def _is_argnum(v) -> bool:
    return isinstance(v, int) and not isinstance(v, bool)


def _const_int_set(expr: ast.expr, locals_: Optional[FnLocals]
                   ) -> Optional[FrozenSet[int]]:
    return _const_set(expr, locals_, _is_argnum)


def _const_str_set(expr: ast.expr, locals_: Optional[FnLocals]
                   ) -> Optional[FrozenSet[str]]:
    return _const_set(expr, locals_,
                      lambda v: isinstance(v, str))


def _donated_positions(call: ast.Call,
                       locals_: Optional[FnLocals]
                       ) -> Optional[FrozenSet[int]]:
    """Donated argnums of a jit-like call, or None when absent or not
    statically resolvable (an unresolvable donation disables the
    lifetime checks for this callable — skip, never guess)."""
    for k in call.keywords:
        if k.arg == "donate_argnums":
            return _const_int_set(k.value, locals_)
    return None


def _donated_names_kw(call: ast.Call,
                      locals_: Optional[FnLocals]
                      ) -> Optional[FrozenSet[str]]:
    """Donated argnames of a jit-like call (the ``donate_argnames``
    spelling), or None when absent/unresolvable."""
    for k in call.keywords:
        if k.arg == "donate_argnames":
            return _const_str_set(k.value, locals_)
    return None


def _local_def(scope: ast.AST, name: str) -> Optional[ast.AST]:
    """A def named `name` in `scope`'s own body (not nested deeper)."""
    for n in walk_own_scope(scope):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and n.name == name:
            return n
    return None


def _body_free_reads(fn: ast.AST) -> FrozenSet[str]:
    """Names the body READS but neither takes as parameters nor binds
    itself — the closure-capture surface the donated-and-captured
    check intersects with donated call-site names."""
    params: Set[str] = set()
    a = fn.args
    for p in a.posonlyargs + a.args + a.kwonlyargs:
        params.add(p.arg)
    for va in (a.vararg, a.kwarg):
        if va is not None:
            params.add(va.arg)
    loads: Set[str] = set()
    stores: Set[str] = set()
    for n in ast.walk(fn):
        if isinstance(n, ast.Name):
            if isinstance(n.ctx, ast.Load):
                loads.add(n.id)
            else:
                stores.add(n.id)
        elif isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and n is not fn:
            stores.add(n.name)
        elif isinstance(n, (ast.Import, ast.ImportFrom)):
            for alias in n.names:
                stores.add((alias.asname or alias.name).split(".")[0])
    return frozenset(loads - params - stores)


@dataclasses.dataclass(frozen=True)
class _DonatedCallable:
    """One donation-compiled callable: where it was compiled, which
    positions/param-names donate, the body's positional params (to map
    positional operands onto donate_argnames), and the jitted body's
    closure reads."""

    positions: FrozenSet[int]
    names: FrozenSet[str]
    params: Tuple[str, ...]
    body_free: FrozenSet[str]
    jit_line: int
    # True only when the jitted body's free names bind in the CALL
    # SITE's own scope (local `step = jax.jit(_body)` / inline-applied
    # jit): the donated-and-captured name comparison is meaningful
    # there and cross-scope name collisions (builder/class/module
    # bodies) are not — skip, never guess
    same_scope: bool = False


def _positional_params(fn: ast.AST) -> Tuple[str, ...]:
    a = fn.args
    return tuple(p.arg for p in a.posonlyargs + a.args)


def _resolve_jit_site(call: ast.Call, locals_: Optional[FnLocals],
                      scope: ast.AST,
                      same_scope: bool = False) -> Optional[_DonatedCallable]:
    """A jit-like Call -> its donation record (None when donations are
    absent/empty/unresolvable)."""
    pos = _donated_positions(call, locals_)
    names = _donated_names_kw(call, locals_)
    if not pos and not names:
        return None
    body_free: FrozenSet[str] = frozenset()
    params: Tuple[str, ...] = ()
    if call.args:
        base = expr_basename(call.args[0])
        if base:
            body = _local_def(scope, base)
            if body is not None:
                body_free = _body_free_reads(body)
                params = _positional_params(body)
    return _DonatedCallable(pos or frozenset(), names or frozenset(),
                            params, body_free, call.lineno,
                            same_scope=same_scope)


def _decorated_donations(fn_def: ast.AST) -> Optional[_DonatedCallable]:
    """Donation record of a def compiled by decorator —
    ``@functools.partial(jax.jit, donate_argnums=...)`` (the repo's
    dominant jit-decorator idiom); the def's own params are the
    donated positions."""
    for dec in fn_def.decorator_list:
        if not (isinstance(dec, ast.Call)
                and expr_basename(dec.func) == "partial"
                and dec.args
                and expr_basename(dec.args[0]) in JIT_LIKE_NAMES):
            continue
        pos = _donated_positions(dec, None)
        names = _donated_names_kw(dec, None)
        if not pos and not names:
            return None
        return _DonatedCallable(pos or frozenset(), names or frozenset(),
                                _positional_params(fn_def),
                                _body_free_reads(fn_def), dec.lineno)
    return None


def _builder_return_jit(project: Project, name: str,
                        sf: SourceFile) -> Optional[_DonatedCallable]:
    """Resolve ``step = build_x(...); step(...)`` through a project
    function whose single return value is a jit-like call (the
    serve/shard.py builder idiom)."""
    fn = project.resolve_function(name, prefer_file=sf)
    if fn is None:
        return None
    returns = [n for n in walk_own_scope(fn.node)
               if isinstance(n, ast.Return) and n.value is not None]
    if len(returns) != 1:
        return None
    fl = FnLocals(fn.node)
    val = returns[0].value
    if isinstance(val, ast.Name):
        v = fl.value_of(val.id)
        if v is not None:
            val = v
    if isinstance(val, ast.Call) and _is_jit_like(val):
        return _resolve_jit_site(val, fl, fn.node)
    return None


class _ClassDonations:
    """``self.<attr> = jax.jit(..., donate_argnums=...)`` assignments
    collected per class: the attribute map a ``self.<attr>(...)`` call
    site resolves against.  Multiple assignments to one attribute union
    their donations (one body compiled down two branches with
    identical donations)."""

    def __init__(self, cls: ast.ClassDef):
        self.attrs: Dict[str, _DonatedCallable] = {}
        for stmt in cls.body:
            if not isinstance(stmt, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            fl = FnLocals(stmt)
            for n in walk_own_scope(stmt):
                if not (isinstance(n, ast.Assign) and len(n.targets) == 1):
                    continue
                t = n.targets[0]
                if not (isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"):
                    continue
                if not (isinstance(n.value, ast.Call)
                        and _is_jit_like(n.value)):
                    continue
                rec = _resolve_jit_site(n.value, fl, stmt)
                if rec is None:
                    continue
                prev = self.attrs.get(t.attr)
                if prev is not None:
                    rec = _DonatedCallable(
                        prev.positions | rec.positions,
                        prev.names | rec.names,
                        prev.params or rec.params,
                        prev.body_free | rec.body_free, prev.jit_line)
                self.attrs[t.attr] = rec


def _call_site_donations(project: Project, sf: SourceFile,
                         call: ast.Call, chain: List[ast.AST],
                         cls_map: Optional[_ClassDonations]
                         ) -> Optional[_DonatedCallable]:
    f = call.func
    if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name) \
            and f.value.id == "self" and cls_map is not None:
        return cls_map.attrs.get(f.attr)
    if isinstance(f, ast.Name):
        locals_ = ChainLocals(chain)
        v = locals_.value_of(f.id)
        if isinstance(v, ast.Call):
            if _is_jit_like(v):
                return _resolve_jit_site(v, locals_,
                                         chain[0] if chain else sf.tree,
                                         same_scope=True)
            base = expr_basename(v.func)
            if base:
                return _builder_return_jit(project, base, sf)
        # the decorator spelling: a def compiled in place by
        # @functools.partial(jax.jit, donate_argnums=...), called by
        # its own name
        fn_def = None
        for scope in list(chain) + [sf.tree]:
            fn_def = _local_def(scope, f.id)
            if fn_def is not None:
                break
        if fn_def is None:
            info = project.resolve_function(f.id, prefer_file=sf)
            fn_def = info.node if info is not None else None
        if fn_def is not None:
            return _decorated_donations(fn_def)
    if isinstance(f, ast.Call) and _is_jit_like(f):
        # jax.jit(body, donate_argnums=...)(operands) applied inline
        locals_ = ChainLocals(chain)
        return _resolve_jit_site(f, locals_,
                                 chain[0] if chain else sf.tree,
                                 same_scope=True)
    return None


def _donated_arg_names(call: ast.Call,
                       rec: _DonatedCallable
                       ) -> Optional[List[Tuple[int, str, str]]]:
    """(position, name, donating kwarg) for donated args that are bare
    Names — positional operands at donate_argnums positions or at
    positions whose param is in donate_argnames, plus keyword operands
    matching a donated name (position -1); the kwarg records WHICH
    spelling donated, so the finding's fix guidance names a keyword
    that actually exists at the jit site.  None when the call's
    positional layout is not statically mappable (starred operands)."""
    if any(isinstance(a, ast.Starred) for a in call.args):
        return None
    out = []
    for i, a in enumerate(call.args):
        pname = rec.params[i] if i < len(rec.params) else None
        if not isinstance(a, ast.Name):
            continue
        if i in rec.positions:
            out.append((i, a.id, "donate_argnums"))
        elif pname is not None and pname in rec.names:
            out.append((i, a.id, "donate_argnames"))
    for k in call.keywords:
        if k.arg and k.arg in rec.names and isinstance(k.value, ast.Name):
            out.append((-1, k.value.id, "donate_argnames"))
    return out


def _name_events(fn: ast.AST, skip_subtree: ast.AST):
    """(lineno, is_store, name, node) Name events in `fn`'s own scope,
    excluding the donated call's own subtree (its args are loads too)
    and DEFERRED closures (lambda / generator-expression bodies are
    late-binding: they run after any later rebind, so their reads are
    not straight-line reads — skip, never guess; eager list/set/dict
    comprehensions stay in)."""
    skip = {id(n) for n in ast.walk(skip_subtree)}
    for n in walk_own_scope(fn):
        if isinstance(n, (ast.Lambda, ast.GeneratorExp)):
            skip.update(id(x) for x in ast.walk(n))
    events = []
    for n in walk_own_scope(fn):
        if isinstance(n, ast.Name) and id(n) not in skip:
            events.append((n.lineno, not isinstance(n.ctx, ast.Load),
                           n.id, n))
    return events


def _parent_map(root: ast.AST) -> Dict[int, ast.AST]:
    out: Dict[int, ast.AST] = {}
    for node in ast.walk(root):
        for child in ast.iter_child_nodes(node):
            out[id(child)] = node
    return out


_BLOCK_FIELDS = ("body", "orelse", "finalbody")


def _stmt_of(node: ast.AST, parents: Dict[int, ast.AST]) -> ast.AST:
    """The statement holding `node`: the first ancestor (or node
    itself) sitting in a block list of its parent — the unit whose
    RHS-before-LHS evaluation order the revival check must respect."""
    cur = node
    while True:
        p = parents.get(id(cur))
        if p is None:
            return cur
        for field in _BLOCK_FIELDS:
            stmts = getattr(p, field, None)
            if isinstance(stmts, list) and cur in stmts:
                return cur
        cur = p


def _post_call_region(enclosing: ast.AST, call: ast.Call,
                      parents: Dict[int, ast.AST]) -> Set[int]:
    """ids of nodes PROVABLY executed after the donating call ran: the
    suffix of the call statement's own block, ascending only through
    always-executed containers (``with`` bodies, ``try`` finalbodies).
    A read past an ``if`` arm holding the call, past a
    maybe-zero-iteration loop, or in a sibling except handler cannot
    be proven to follow the donation — skip, never guess (the
    fast-path/fallback idiom must stay clean)."""
    region: Set[int] = set()
    cur: ast.AST = call
    while cur is not enclosing:
        p = parents.get(id(cur))
        if p is None:
            break
        in_block = False
        through = False
        for field in _BLOCK_FIELDS:
            stmts = getattr(p, field, None)
            if isinstance(stmts, list) and cur in stmts:
                for s in stmts[stmts.index(cur) + 1:]:
                    region.update(id(x) for x in ast.walk(s))
                in_block = True
                if isinstance(p, (ast.With, ast.AsyncWith, ast.Module,
                                  ast.FunctionDef,
                                  ast.AsyncFunctionDef)) \
                        or (isinstance(p, ast.Try)
                            and (field == "finalbody"
                                 or not p.handlers)):
                    through = True
        if in_block and not through:
            break  # conditional container: later siblings can't prove
        cur = p
    return region


def _block_chain(node: ast.AST, parents: Dict[int, ast.AST]
                 ) -> List[Tuple[int, int, bool]]:
    """(block id, statement index, always-executes) triples from the
    outermost block down to `node`'s own statement — the structured-
    code position a dominance comparison needs.  ``always-executes``
    marks blocks that run unconditionally once their container
    statement is reached — ``with`` bodies, ``try`` finalbodies, and
    handler-less ``try`` bodies (an exception would propagate past any
    later read too) — so a rebind inside one still dominates reads
    past it."""
    chain: List[Tuple[int, int, bool]] = []
    cur = node
    while True:
        p = parents.get(id(cur))
        if p is None:
            break
        for field in _BLOCK_FIELDS:
            stmts = getattr(p, field, None)
            if isinstance(stmts, list) and cur in stmts:
                always = isinstance(p, (ast.With, ast.AsyncWith)) \
                    or (isinstance(p, ast.Try)
                        and (field == "finalbody"
                             or not p.handlers))
                chain.append((id(stmts), stmts.index(cur), always))
        cur = p
    return list(reversed(chain))


def _dominates(store_chain: List[Tuple[int, int, bool]],
               read_chain: List[Tuple[int, int, bool]]) -> bool:
    """True when the store's statement is GUARANTEED to have executed
    by the time control reaches the read: the chains diverge inside a
    shared block with the store earlier, and every level BELOW the
    divergence on the store's side always executes (``with`` bodies).
    A store inside an `if` arm the read is not part of does NOT
    dominate — on the arm-not-taken path the read still sees the dead
    buffer (the cold-path scenario this pass exists to catch)."""
    for d in range(min(len(store_chain), len(read_chain))):
        s_blk, s_idx, _s_alw = store_chain[d]
        r_blk, r_idx, _r_alw = read_chain[d]
        if s_blk != r_blk or s_idx > r_idx:
            return False
        if s_idx == r_idx:
            continue  # nested under the same statement: go deeper
        return all(alw for _b, _i, alw in store_chain[d + 1:])
    return False


def _target_stores(name: str, target: ast.expr) -> bool:
    return any(isinstance(n, ast.Name) and n.id == name
               and not isinstance(n.ctx, ast.Load)
               for n in ast.walk(target))


def _definitely_stores(name: str, stmt: ast.stmt) -> bool:
    """True when executing `stmt` UNCONDITIONALLY rebinds `name`: a
    top-level assignment target, a ``with`` body that definitely
    stores, an if/elif/else chain storing on every path, or a ``try``
    whose finalbody does.  A store nested under a further condition
    (or inside a nested def — a local binding, not a rebind) does NOT
    count: on the path around it the donated buffer is still dead."""
    if isinstance(stmt, ast.Assign):
        return any(_target_stores(name, t) for t in stmt.targets)
    if isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
        return _target_stores(name, stmt.target)
    if isinstance(stmt, (ast.With, ast.AsyncWith)):
        return _block_definitely_stores(name, stmt.body)
    if isinstance(stmt, ast.If):
        return _all_paths_store(stmt, name)
    if isinstance(stmt, ast.Try):
        if _block_definitely_stores(name, stmt.finalbody):
            return True
        # with no except handler, an exception propagates past the
        # read too — so a definite try-body/orelse store counts
        return not stmt.handlers and (
            _block_definitely_stores(name, stmt.body)
            or _block_definitely_stores(name, stmt.orelse))
    return False


def _block_definitely_stores(name: str, stmts: List[ast.stmt]) -> bool:
    return any(_definitely_stores(name, s) for s in stmts)


def _all_paths_store(if_node: ast.If, name: str) -> bool:
    """True when EVERY path through the if/elif/else chain rebinds
    `name`: body definitely stores AND the orelse — recursing through
    an elif — stores on all of ITS paths.  A chain with no final
    ``else`` has a fall-through path that rebinds nothing, so it
    never revives."""
    if not _block_definitely_stores(name, if_node.body):
        return False
    if not if_node.orelse:
        return False
    if len(if_node.orelse) == 1 and isinstance(if_node.orelse[0], ast.If):
        return _all_paths_store(if_node.orelse[0], name)
    return _block_definitely_stores(name, if_node.orelse)


def _check_use_after_donate(sf: SourceFile, enclosing: ast.AST,
                            func_label: str, call: ast.Call,
                            donated: List[Tuple[int, str, str]],
                            findings: List[Finding]) -> None:
    end = call.end_lineno or call.lineno
    events = _name_events(enclosing, call)
    parents = _parent_map(enclosing)
    region = _post_call_region(enclosing, call, parents)
    # a comprehension target binds nothing at function scope — never a
    # rebind; a for-loop target binds only while the loop runs, so it
    # revives reads INSIDE the loop body (chained as the body's first
    # binding) but not past a maybe-zero-iteration loop
    comp_targets: Set[int] = set()
    for_target_owner: Dict[int, ast.AST] = {}
    # an augmented assignment READS its target first: `kv_lens += 1`
    # on a donated name is itself a dead-buffer read (the rebind it
    # performs still revives LATER reads, but cannot excuse its own)
    aug_targets: Set[int] = set()
    for n in ast.walk(enclosing):
        if isinstance(n, (ast.For, ast.AsyncFor)):
            for x in ast.walk(n.target):
                for_target_owner[id(x)] = n
        elif isinstance(n, ast.comprehension):
            comp_targets.update(id(x) for x in ast.walk(n.target))
        elif isinstance(n, ast.AugAssign) \
                and isinstance(n.target, ast.Name):
            aug_targets.add(id(n.target))
    for _pos, name, via in donated:
        # a rebind DOMINATING a later read revives the name — the call
        # statement's own assign target (`x, kcl, vcl = step(...)`) and
        # a straight-line `name = ...` both count; a rebind on only ONE
        # arm of a branch does not (the arm-not-taken path still reads
        # the dead buffer — the rarely-hit-branch scenario), and a
        # store on the READ's own statement does not revive that read
        # (`caches = f(caches)` evaluates its dead RHS first).
        store_chains = []
        for ln, is_store, n, node in events:
            if not (is_store and n == name and ln >= call.lineno):
                continue
            if id(node) in comp_targets:
                continue
            s_stmt = _stmt_of(node, parents)
            owner_for = for_target_owner.get(id(node))
            if owner_for is not None:
                chain = _block_chain(owner_for, parents) \
                    + [(id(owner_for.body), -1, False)]
            else:
                chain = _block_chain(s_stmt, parents)
            store_chains.append((chain, s_stmt))
        # an if/elif/else chain rebinding the name on EVERY path
        # revives everything past it, even though no single arm's
        # store dominates alone (a chain without a final else has a
        # fall-through path and never revives)
        both_arm_ifs = [
            n for n in walk_own_scope(enclosing)
            if isinstance(n, ast.If) and n.lineno >= call.lineno
            and _all_paths_store(n, name)]
        both_arm_chains = [_block_chain(n, parents) for n in both_arm_ifs]
        for lineno, is_store, n, node in sorted(
                events, key=lambda e: (e[0], e[1], e[2])):
            is_aug_read = is_store and id(node) in aug_targets
            if (is_store and not is_aug_read) or n != name \
                    or lineno <= end:
                continue
            if id(node) not in region:
                continue  # not provably after the call: skip
            rstmt = _stmt_of(node, parents)
            rchain = _block_chain(rstmt, parents)
            if any(s_stmt is not rstmt and _dominates(schain, rchain)
                   for schain, s_stmt in store_chains):
                continue  # rebound on every path before this read
            if any(_dominates(ichain, rchain)
                   for ichain in both_arm_chains):
                continue  # both-arm rebind ahead of the read
            findings.append(Finding(
                CODE, sf.path, lineno, func_label,
                f"'{name}' was DONATED at the compile-once step call on "
                f"line {call.lineno} ({via}) and is read again "
                "here: the buffer is dead after the call — thread the "
                "returned state instead, or drop the argument from "
                f"{via}"))
            break  # one finding per donated name keeps baselines stable


def _check_captured(sf: SourceFile, func_label: str, call: ast.Call,
                    donated: List[Tuple[int, str, str]],
                    rec: _DonatedCallable,
                    findings: List[Finding]) -> None:
    if not rec.same_scope:
        return  # cross-scope name comparison would be a guess
    for _pos, name, _via in donated:
        if name in rec.body_free:
            findings.append(Finding(
                CODE, sf.path, call.lineno, func_label,
                f"'{name}' is passed at a donated position but the "
                f"jitted body (compiled at line {rec.jit_line}) ALSO "
                "closes over it: the traced closure constant aliases "
                "the donated operand — pass it as an argument only, or "
                "stop donating it"))


def _check_sharding_contract(sf: SourceFile, findings: List[Finding]
                             ) -> None:
    for n in ast.walk(sf.tree):
        if not (isinstance(n, ast.Call) and _is_jit_like(n)):
            continue
        kw = {k.arg for k in n.keywords
              if k.arg in ("in_shardings", "out_shardings")
              and not (isinstance(k.value, ast.Constant)
                       and k.value.value is None)}
        if len(kw) == 1:
            present = kw.pop()
            missing = ("out_shardings" if present == "in_shardings"
                       else "in_shardings")
            findings.append(Finding(
                CODE, sf.path, n.lineno, expr_basename(n.func),
                f"step compiled with {present}= but no {missing}= — "
                "the both-or-neither contract: a half-specified sharding "
                "set lets the compiler re-derive the missing side and "
                "partition the program differently than the plan says"))


def run(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for sf in project.files:
        if sf.tree is None:
            continue
        _check_sharding_contract(sf, findings)

        def _scan(scope: ast.AST, chain: List[ast.AST],
                  cls_map: Optional[_ClassDonations],
                  label: str) -> None:
            for node in walk_own_scope(scope):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    _scan(node, [node] + chain, cls_map, node.name)
                elif isinstance(node, ast.ClassDef):
                    _scan(node, chain, _ClassDonations(node), label)
                elif isinstance(node, ast.Call):
                    rec = _call_site_donations(project, sf, node, chain,
                                               cls_map)
                    if rec is None:
                        continue
                    donated = _donated_arg_names(node, rec)
                    if not donated:
                        continue  # starred/keyword layout: skip
                    enclosing = chain[0] if chain else sf.tree
                    _check_use_after_donate(sf, enclosing, label, node,
                                            donated, findings)
                    _check_captured(sf, label, node, donated, rec,
                                    findings)

        _scan(sf.tree, [], None, "<module>")
    return findings
