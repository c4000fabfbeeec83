"""Serving-contract analyzer passes (ISSUE 15): L011 donation
lifetime, L012 static-flow, L013 registry completeness, plus the L006
provenance-label extension.

The acceptance regressions run each pass against the REAL serving
modules with one surgical skew injected — a post-call donated-buffer
reuse in parallel/plan.py must flag exactly L011, a schedule value moved
into a plan-shape static in serve/engine_kernels.py exactly L012, a
dropped knob binding exactly L013 — and the unmodified tree must stay
clean under all three (no baseline absorption).
"""

import json
import os
import textwrap

import pytest

from flashinfer_tpu import analysis
from flashinfer_tpu.analysis import (donation_lifetime, registry_coverage,
                                     static_flow, tuning_schema)
from flashinfer_tpu.analysis.core import Project, load_source

PKG_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "flashinfer_tpu"))


def _project(*named_sources):
    return Project([load_source(textwrap.dedent(src), name)
                    for name, src in named_sources])


def _real(relpath):
    return open(os.path.join(PKG_ROOT, relpath)).read()


def _new_pass_findings(project):
    """Findings of the three ISSUE 15 passes, labeled — the "flags
    exactly its pass" assertion reads this."""
    return {
        "L011": donation_lifetime.run(project),
        "L012": static_flow.run(project),
        "L013": registry_coverage.run(project),
    }


# ------------------------------------------- L011 donation_lifetime --


@pytest.mark.quick
def test_l011_flags_post_call_donated_reuse_in_real_step():
    """THE acceptance regression: a copy of parallel/plan.py whose
    sharded step hands back a donated binding after the jitted call
    must flag L011 — and ONLY L011 of the three new passes."""
    real = _real("parallel/plan.py")
    call = ("self._fn(x0, layer_ws, caches, head, head_s, pt, lens, "
            "skey)")
    skew = real.replace(
        f"        return {call}",
        f"        out = {call}\n"
        "        return out[:3] + (lens,) + out[4:]")
    assert skew != real
    by_pass = _new_pass_findings(_project(("parallel/plan.py", skew)))
    assert [f.code for f in by_pass["L011"]] == ["L011"], by_pass
    f = by_pass["L011"][0]
    assert f.func == "__call__" and "lens" in f.message
    assert "DONATED" in f.message
    assert by_pass["L012"] == [] and by_pass["L013"] == []


def test_l011_real_serving_modules_clean():
    """The shipped serve/ + parallel/ donation call sites thread the
    returned state correctly — the pass agrees on the real files."""
    project = Project.from_paths([
        os.path.join(PKG_ROOT, "serve"),
        os.path.join(PKG_ROOT, "parallel"),
    ])
    assert donation_lifetime.run(project) == []


def test_l011_result_rebind_threading_is_clean():
    """`x, kcl = step(x, kcl)` rebinds the donated name at the call
    statement — the canonical threading idiom must not flag."""
    src = """
        import jax

        def drive(x, kcl, pt):
            def _body(a, b, c):
                return a, b
            step = jax.jit(_body, donate_argnums=(1,))
            for _ in range(4):
                x, kcl = step(x, kcl, pt)
            return x + kcl[0] + pt
    """
    assert donation_lifetime.run(_project(("m.py", src))) == []


def test_l011_closure_captured_donated_arg_flagged():
    src = """
        import jax

        def go(x, caches):
            def _body(a, b):
                return a + caches[0]
            step = jax.jit(_body, donate_argnums=(1,))
            return step(x, caches)
    """
    findings = donation_lifetime.run(_project(("m.py", src)))
    assert [f.code for f in findings] == ["L011"], findings
    assert "closes over" in findings[0].message


def test_l011_donate_argnames_and_decorator_spellings():
    """The donate_argnames spelling (keyword AND positional mapped
    through the body's signature) and the
    @functools.partial(jax.jit, donate_argnums=...) decorator idiom
    both resolve to the same lifetime checks."""
    argnames = """
        import jax

        def drive(x, caches):
            def _body(a, caches):
                return a
            step = jax.jit(_body, donate_argnames=("caches",))
            y = step(x, caches)
            return y + caches[0]
    """
    findings = donation_lifetime.run(_project(("m.py", argnames)))
    assert [f.code for f in findings] == ["L011"], findings
    assert "donate_argnames" in findings[0].message
    kw_call = argnames.replace("step(x, caches)", "step(x, caches=caches)")
    findings = donation_lifetime.run(_project(("m.py", kw_call)))
    assert [f.code for f in findings] == ["L011"], findings
    decorated = """
        import functools

        import jax

        @functools.partial(jax.jit, donate_argnums=(1,))
        def step(x, caches):
            return x

        def drive(x, caches):
            y = step(x, caches)
            return y + caches[0]
    """
    findings = donation_lifetime.run(_project(("m.py", decorated)))
    assert [f.code for f in findings] == ["L011"], findings
    threaded = decorated.replace(
        "y = step(x, caches)", "y, caches = step(x, caches), None")
    assert donation_lifetime.run(_project(("m.py", threaded))) == []


def test_l011_builder_return_idiom_resolved():
    """`step = build_x(); step(...)` resolves donations through the
    builder's returned jit — the serve/shard.py idiom."""
    src = """
        import jax

        def build_step(donate=True):
            def _body(x, caches):
                return x, caches
            donate_argnums = (1,) if donate else ()
            return jax.jit(_body, donate_argnums=donate_argnums)

        def drive(x, caches):
            step = build_step()
            y, new_caches = step(x, caches)
            return y + caches[0]
    """
    findings = donation_lifetime.run(_project(("m.py", src)))
    assert [f.code for f in findings] == ["L011"], findings
    assert "caches" in findings[0].message


def test_l011_branch_guarded_call_skips_reads_past_the_branch():
    """A read past an `if` arm holding the donating call cannot be
    proven to follow the donation (the fast-path/fallback idiom) —
    skip, never guess; a read in the SAME arm after the call IS
    provable and flags."""
    guarded = """
        import jax

        def drive(x, caches, cond):
            def _body(a, b):
                return a
            step = jax.jit(_body, donate_argnums=(1,))
            if cond:
                y = step(x, caches)
                return y
            return caches
    """
    assert donation_lifetime.run(_project(("m.py", guarded))) == []
    same_arm = """
        import jax

        def drive(x, caches, cond):
            def _body(a, b):
                return a
            step = jax.jit(_body, donate_argnums=(1,))
            if cond:
                y = step(x, caches)
                return y + caches[0]
            return x
    """
    findings = donation_lifetime.run(_project(("m.py", same_arm)))
    assert [f.code for f in findings] == ["L011"], findings
    assert "caches" in findings[0].message


def test_l011_one_arm_rebind_does_not_mask_cold_path_read():
    """A rebind on only ONE arm of a branch does not revive the name:
    on the arm-not-taken path a later straight-line read still sees
    the dead buffer (the rarely-hit-branch scenario from the module
    docstring) — while a BOTH-arm rebind does revive."""
    one_arm = """
        import jax

        def drive(x, caches, cold):
            def _body(a, b):
                return a
            step = jax.jit(_body, donate_argnums=(1,))
            y = step(x, caches)
            if cold:
                caches = rebuild()
            return y, caches
    """
    findings = donation_lifetime.run(_project(("m.py", one_arm)))
    assert [f.code for f in findings] == ["L011"], findings
    assert "caches" in findings[0].message
    both_arms = """
        import jax

        def drive(x, caches, cold):
            def _body(a, b):
                return a, b
            step = jax.jit(_body, donate_argnums=(1,))
            y, new = step(x, caches)
            if cold:
                caches = rebuild()
            else:
                caches = new
            return y, caches
    """
    assert donation_lifetime.run(_project(("m.py", both_arms))) == []
    elif_no_else = """
        import jax

        def drive(x, caches, c1, c2):
            def _body(a, b):
                return a
            step = jax.jit(_body, donate_argnums=(1,))
            y = step(x, caches)
            if c1:
                caches = mk1()
            elif c2:
                caches = mk2()
            return y, caches
    """
    findings = donation_lifetime.run(_project(("m.py", elif_no_else)))
    assert [f.code for f in findings] == ["L011"], findings
    elif_with_else = elif_no_else.replace(
        "            return y, caches",
        "            else:\n"
        "                caches = mk3()\n"
        "            return y, caches")
    assert donation_lifetime.run(
        _project(("m.py", elif_with_else))) == []
    with_rebind = """
        import jax

        def drive(x, caches, timer):
            def _body(a, b):
                return a
            step = jax.jit(_body, donate_argnums=(1,))
            y = step(x, caches)
            with timer:
                caches = rebuild()
            return y, caches
    """
    # a `with` body always executes: the rebind dominates, no finding
    assert donation_lifetime.run(_project(("m.py", with_rebind))) == []
    nested_conditional_else = """
        import jax

        def drive(x, caches, c, d):
            def _body(a, b):
                return a
            step = jax.jit(_body, donate_argnums=(1,))
            y = step(x, caches)
            if c:
                caches = mk1()
            else:
                log = 1
                if d:
                    caches = mk2()
            return y, caches
    """
    # the else arm stores only under a FURTHER condition: on the
    # c=False, d=False path the read is still dead — must flag
    findings = donation_lifetime.run(
        _project(("m.py", nested_conditional_else)))
    assert [f.code for f in findings] == ["L011"], findings


def test_l011_loop_target_rebind_is_not_a_revival():
    """A for-loop target binds only while the loop runs: it revives
    reads INSIDE the body but not past a maybe-zero-iteration loop —
    and a comprehension target binds nothing at function scope."""
    past_loop = """
        import jax

        def drive(x, caches, zs):
            def _body(a, b):
                return a
            step = jax.jit(_body, donate_argnums=(1,))
            y = step(x, caches)
            for caches in zs:
                use(caches)
            return y, caches
    """
    findings = donation_lifetime.run(_project(("m.py", past_loop)))
    assert [f.code for f in findings] == ["L011"], findings
    comp = """
        import jax

        def drive(x, caches, zs):
            def _body(a, b):
                return a
            step = jax.jit(_body, donate_argnums=(1,))
            y = step(x, caches)
            out = [i for caches in zs for i in caches]
            return y, caches
    """
    findings = donation_lifetime.run(_project(("m.py", comp)))
    assert [f.code for f in findings] == ["L011"], findings


def test_l011_finally_rebind_dominates():
    """A rebind in a try/finally finalbody ALWAYS executes before any
    read past the try — it must revive the donated name."""
    src = """
        import jax

        def drive(x, caches):
            def _body(a, b):
                return a
            step = jax.jit(_body, donate_argnums=(1,))
            y = step(x, caches)
            try:
                log(y)
            finally:
                caches = rebuild()
            return y, caches
    """
    assert donation_lifetime.run(_project(("m.py", src))) == []
    handlerless_body_store = src.replace(
        "            try:\n"
        "                log(y)\n"
        "            finally:\n"
        "                caches = rebuild()",
        "            try:\n"
        "                caches = rebuild()\n"
        "            finally:\n"
        "                log(y)")
    # with NO except handler an exception propagates past the read
    # too, so the try-body rebind is guaranteed at any later read
    assert donation_lifetime.run(
        _project(("m.py", handlerless_body_store))) == []
    try_body_store = src.replace(
        "            try:\n"
        "                log(y)\n"
        "            finally:\n"
        "                caches = rebuild()",
        "            try:\n"
        "                caches = rebuild()\n"
        "            except Exception:\n"
        "                pass")
    # a try-BODY store skipped by a swallowed exception leaves the
    # donated buffer dead at the read: no revival
    findings = donation_lifetime.run(_project(("m.py", try_body_store)))
    assert [f.code for f in findings] == ["L011"], findings


def test_l011_aug_assign_is_a_dead_read_not_a_revival():
    """`kv_lens += 1` on a donated name reads the dead buffer before
    it rebinds — it must flag like the `kv_lens = kv_lens + 1`
    spelling instead of quietly reviving the name."""
    src = """
        import jax

        def drive(x, kv_lens, caches):
            def _body(a, b, c):
                return a, b, c
            step = jax.jit(_body, donate_argnums=(1, 2))
            x, lens2, c2 = step(x, kv_lens, caches)
            kv_lens += 1
            return x, kv_lens, caches
    """
    findings = donation_lifetime.run(_project(("m.py", src)))
    assert sorted(f.message.split("'")[1] for f in findings) \
        == ["caches", "kv_lens"], findings
    assert all(f.code == "L011" for f in findings)


def test_l011_deferred_closure_reads_and_cross_scope_capture_skip():
    """A lambda/genexp body is late-binding (it runs after any later
    rebind) and a builder body's free names bind in the BUILDER's
    scope — both are skip-never-guess, not findings."""
    deferred = """
        import jax

        def drive(x, caches):
            def _body(a, b):
                return a
            step = jax.jit(_body, donate_argnums=(1,))
            y = step(x, caches)
            cb = lambda: caches[0]
            caches = rebuild()
            return y, cb, caches
    """
    assert donation_lifetime.run(_project(("m.py", deferred))) == []
    cross_scope = """
        import jax

        def make():
            kv = load_table()

            def _body(x, a):
                return x + kv
            return jax.jit(_body, donate_argnums=(1,))

        def serve(x, kv):
            step = make()
            x, kv = step(x, kv)
            return x
    """
    assert donation_lifetime.run(_project(("m.py", cross_scope))) == []


def test_l011_same_line_self_rebind_read_flagged():
    """`caches = fn(caches)` after the donation reads the dead buffer
    on its RHS before the LHS rebinds — the same-statement store must
    not mask the read."""
    src = """
        import jax
        import jax.numpy as jnp

        def drive(x, caches):
            def _body(a, b):
                return a
            step = jax.jit(_body, donate_argnums=(1,))
            y = step(x, caches)
            caches = jnp.copy(caches)
            return y, caches
    """
    findings = donation_lifetime.run(_project(("m.py", src)))
    assert [f.code for f in findings] == ["L011"], findings
    assert "caches" in findings[0].message and "dead" in findings[0].message


def test_l011_starred_call_layout_skips():
    """A starred operand list makes positions statically unmappable —
    skip, never guess (the engine's `self._step(*full_args)`)."""
    src = """
        import jax

        def drive(x, caches):
            def _body(a, b):
                return a, b
            step = jax.jit(_body, donate_argnums=(1,))
            args = (x, caches)
            y, _ = step(*args)
            return caches[0]
    """
    assert donation_lifetime.run(_project(("m.py", src))) == []


def test_l011_half_specified_shardings_flagged():
    """The both-or-neither contract, statically, on either side."""
    src = """
        import jax

        def a(fn, in_sh):
            return jax.jit(fn, in_shardings=in_sh)

        def b(fn, out_sh):
            return jax.jit(fn, out_shardings=out_sh)

        def c(fn, in_sh, out_sh):
            return jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
    """
    findings = donation_lifetime.run(_project(("m.py", src)))
    assert [f.code for f in findings] == ["L011", "L011"], findings
    assert any("no out_shardings" in f.message for f in findings)
    assert any("no in_shardings" in f.message for f in findings)


def test_l011_suppression_honored_through_driver():
    src = """
        import jax

        def go(x, caches):
            def _body(a, b):
                return a
            step = jax.jit(_body, donate_argnums=(1,))
            y = step(x, caches)
            # graft-lint: ok caches is a throwaway fixture, rebuilt
            return caches
    """
    findings = analysis.analyze_project(_project(("m.py", src)), bank={})
    assert findings == [], findings


# ------------------------------------------------ L012 static_flow --


@pytest.mark.quick
def test_l012_flags_schedule_value_in_plan_shape_static_real_engine():
    """THE acceptance regression: replacing the rung-static
    `num_units_pad=U` with the schedule-derived `total` in the real
    engine_kernels.py must flag L012 — and ONLY L012."""
    real = _real("serve/engine_kernels.py")
    skew = real.replace(
        "pack_tiles=True, prune=True, num_units_pad=U,\n            )\n\n"
        "    # ---- level 1",
        "pack_tiles=True, prune=True, num_units_pad=total,\n"
        "            )\n\n    # ---- level 1")
    assert skew != real
    by_pass = _new_pass_findings(
        _project(("serve/engine_kernels.py", skew)))
    assert [f.code for f in by_pass["L012"]] == ["L012"], by_pass
    f = by_pass["L012"][0]
    assert f.func == "build_engine_work_units"
    assert "num_units_pad" in f.message and "rung" in f.message
    assert by_pass["L011"] == [] and by_pass["L013"] == []


def test_l012_positional_planner_static_resolved_cross_module():
    """A tainted value bound POSITIONALLY to a planner's block_q param
    resolves through the planner's real signature in another module."""
    real = _real("serve/engine_kernels.py")
    skew = real.replace(
        "np.asarray(pages1, np.int64), np.asarray(kv1_lens, "
        "np.int64),\n            geom.block_q, geom.prefill_ppc, ps,",
        "np.asarray(pages1, np.int64), np.asarray(kv1_lens, "
        "np.int64),\n            segs[0].n, geom.prefill_ppc, ps,")
    assert skew != real
    findings = static_flow.run(_project(
        ("serve/engine_kernels.py", skew),
        ("ops/paged_prefill.py", _real("ops/paged_prefill.py"))))
    assert [f.code for f in findings] == ["L012"], findings
    assert "block_q" in findings[0].message


def test_l012_schedule_value_frozen_into_plan_dataclass():
    src = """
        import dataclasses

        @dataclasses.dataclass(frozen=True)
        class _StepPlan:
            total_q: int

        def build_engine_work_units(segs, *, rung, geom):
            total = segs[-1].row0 + segs[-1].n
            return _StepPlan(total_q=total)
    """
    findings = static_flow.run(_project(("m.py", src)))
    assert [f.code for f in findings] == ["L012"], findings
    assert "_StepPlan.total_q" in findings[0].message


def test_l012_replace_sink_requires_plan_receiver():
    """dataclasses.replace flags only when the receiver resolves to a
    plan/geom construction — ordinary bookkeeping records in a
    registered scope must not flag."""
    src = """
        import dataclasses

        @dataclasses.dataclass(frozen=True)
        class _StepPlan:
            total_q: int

        @dataclasses.dataclass
        class _Req:
            emitted: int

        def build_engine_work_units(segs, *, rung, geom):
            req = _Req(emitted=0)
            req = dataclasses.replace(req, emitted=len(segs))
            plan = _StepPlan(total_q=0)
            plan = dataclasses.replace(plan, total_q=len(segs))
            return req, plan
    """
    findings = static_flow.run(_project(("m.py", src)))
    assert [f.code for f in findings] == ["L012"], findings
    assert "total_q" in findings[0].message
    assert "replace" in findings[0].message


def test_l012_jit_static_argnums_and_branch_sinks():
    src = """
        import jax

        def build_engine_work_units(segs, *, rung, geom):
            nreq = len(segs)
            step = jax.jit(kern, static_argnums=(1,))
            out = step(None, nreq)

            def _body(x):
                if nreq > 2:
                    return x
                return x + 1
            fn = jax.jit(_body)
            return out, fn
    """
    findings = static_flow.run(_project(("m.py", src)))
    codes = sorted((f.code, "static_argnums" in f.message) for f in findings)
    assert codes == [("L012", False), ("L012", True)], findings


def test_l012_static_argnames_sink_flagged():
    """The repo's dominant jit-static spelling: a schedule-tainted
    value reaching a static_argnames param — by keyword AND mapped
    positionally through the body's signature — must flag."""
    src = """
        import jax

        def build_engine_work_units(segs, *, rung, geom):
            def kern(x, n):
                return x
            nreq = len(segs)
            step = jax.jit(kern, static_argnames=("n",))
            a = step(None, n=nreq)
            b = step(None, nreq)
            return a, b
    """
    findings = static_flow.run(_project(("m.py", src)))
    assert [f.code for f in findings] == ["L012", "L012"], findings
    assert all("static_argnames" in f.message and "'n'" in f.message
               for f in findings)


def test_l012_body_local_shadowing_tainted_name_unflagged():
    """A jitted body rebinding a name that is tainted OUTSIDE it
    branches on its own local, not a schedule closure."""
    src = """
        import jax

        def build_engine_work_units(segs, *, rung, geom):
            n = len(segs)

            def _body(x):
                n = x.shape[0]
                if n > 2:
                    return x
                return x + 1
            return jax.jit(_body), n
    """
    assert static_flow.run(_project(("m.py", src))) == []


def test_l012_starred_unpack_carries_taint():
    """`first, *rest = segs` — the starred slice is schedule too."""
    src = """
        import dataclasses

        @dataclasses.dataclass(frozen=True)
        class _StepPlan:
            total_q: int

        def build_engine_work_units(segs, *, rung, geom):
            first, *rest = segs
            return _StepPlan(total_q=len(rest))
    """
    findings = static_flow.run(_project(("m.py", src)))
    assert [f.code for f in findings] == ["L012"], findings
    with_bound = src.replace(
        "            first, *rest = segs\n"
        "            return _StepPlan(total_q=len(rest))",
        "            with lock(segs) as held:\n"
        "                return _StepPlan(total_q=len(held))")
    findings = static_flow.run(_project(("m.py", with_bound)))
    assert [f.code for f in findings] == ["L012"], findings


def test_l012_long_assignment_chain_reaches_fixpoint():
    """Taint must survive an arbitrarily long forward assignment chain
    — a capped fixpoint silently under-taints (one hop per round when
    statements visit in reverse order)."""
    chain = "\n".join(
        f"            v{i + 1} = v{i}" for i in range(12))
    src = f"""
        import dataclasses

        @dataclasses.dataclass(frozen=True)
        class _StepPlan:
            total_q: int

        def build_engine_work_units(segs, *, rung, geom):
            v0 = len(segs)
{chain}
            return _StepPlan(total_q=v12)
    """
    findings = static_flow.run(_project(("m.py", src)))
    assert [f.code for f in findings] == ["L012"], findings


def test_l012_ann_assign_propagates_taint():
    """`n: int = len(segs)` must carry the same taint as the
    unannotated spelling — a type annotation is not a laundering
    step."""
    src = """
        import dataclasses

        @dataclasses.dataclass(frozen=True)
        class _StepPlan:
            total_q: int

        def build_engine_work_units(segs, *, rung, geom):
            total: int = len(segs)
            return _StepPlan(total_q=total)
    """
    findings = static_flow.run(_project(("m.py", src)))
    assert [f.code for f in findings] == ["L012"], findings
    assert "_StepPlan.total_q" in findings[0].message


def test_l012_class_attr_jit_static_resolved():
    """The compiled-step idiom — self._step = jax.jit(...,
    static_argnames=...) in __init__, called in the registered
    step() — resolves through the class-attribute map."""
    src = """
        import jax

        class ServingEngine:
            def __init__(self):
                self._step = jax.jit(self._body,
                                     static_argnames=("n",))

            def _body(self, state, n):
                return state

            def step(self):
                segs = self._schedule()
                return self._step(self.state, n=len(segs))
    """
    findings = static_flow.run(_project(("m.py", src)))
    assert [f.code for f in findings] == ["L012"], findings
    assert "self._step" in findings[0].message
    assert "'n'" in findings[0].message


def test_l012_rung_and_geom_statics_stay_unflagged():
    """The sanctioned statics: rung (the quantized ladder) and geom
    fields are NOT schedule taint — the real planner's own use of
    `num_units_pad=U` (a geom/rung pure function) must stay clean."""
    project = _project(
        ("serve/engine_kernels.py", _real("serve/engine_kernels.py")),
        ("serve/engine.py", _real("serve/engine.py")),
        ("ops/paged_prefill.py", _real("ops/paged_prefill.py")))
    assert static_flow.run(project) == []


def test_l012_unregistered_functions_carry_no_taint():
    """Taint exists only inside registered source scopes: a replan-by-
    design plan() freezing its own parameters is the sanctioned
    pattern and must not flag."""
    src = """
        import dataclasses

        @dataclasses.dataclass(frozen=True)
        class _MixedPlan:
            total_q: int

        class Step:
            def plan(self, qo_lens):
                total_q = int(sum(qo_lens))
                self._plan = _MixedPlan(total_q=total_q)
    """
    assert static_flow.run(_project(("m.py", src))) == []


# ------------------------------------------ L013 registry_coverage --


@pytest.mark.quick
def test_l013_dropped_knob_binding_flags():
    """THE acceptance regression: removing one KNOB_LAUNCHES binding
    (with no waiver) must flag L013 at the knob's register_knob call —
    and ONLY L013."""
    from flashinfer_tpu.analysis.vmem_budget import KNOB_LAUNCHES

    project = Project.from_paths([PKG_ROOT])
    launches = dict(KNOB_LAUNCHES)
    del launches["engine.attention_backend"]
    findings = registry_coverage.run(project, launches=launches)
    assert [f.code for f in findings] == ["L013"], findings
    f = findings[0]
    assert f.func == "engine.attention_backend"
    assert f.filename.endswith("autotuner.py")
    assert "KNOB_LAUNCHES" in f.message
    # the other two passes are unmoved by a registry-only change
    assert donation_lifetime.run(project) == []


def test_l013_zero_unwaivered_registry_gaps():
    """The acceptance criterion verbatim: every registered knob is
    bound or explicitly waived, every serving op spans, every public
    op cost-attributes — zero gaps on the shipped registries."""
    assert registry_coverage.unbound_knobs() == []
    assert registry_coverage.unspanned_serving_ops() == []
    assert registry_coverage.uncovered_api_ops() == ()
    assert registry_coverage.run(Project.from_paths([PKG_ROOT])) == []


def test_l013_dropped_planner_entry_flags():
    from flashinfer_tpu.analysis.pallas_contract import PLANNER_KERNELS

    project = Project.from_paths([PKG_ROOT])
    pk = dict(PLANNER_KERNELS)
    del pk["build_decode_split_units"]
    findings = registry_coverage.run(project, planner_kernels=pk)
    assert findings and all(f.code == "L013" for f in findings), findings
    assert any("_decode_split_kernel_fused_heads" in f.message
               for f in findings)
    assert any("PLANNER_KERNELS" in f.message for f in findings)


def test_l013_waiver_hygiene():
    """A reasonless waiver, a waiver shadowing a live binding, and a
    stale waiver for an unregistered knob are each findings."""
    from flashinfer_tpu.analysis.vmem_budget import (KNOB_LAUNCHES,
                                                     KNOB_WAIVERS)
    from flashinfer_tpu.autotuner import KNOWN_KNOBS

    project = Project.from_paths([PKG_ROOT])
    waivers = dict(KNOB_WAIVERS)
    waivers["parallel.dp"] = "   "                # reasonless
    waivers["fused_prefill.blocks"] = "shadowing"  # has a binding
    waivers["gone.knob"] = "stale"                 # unregistered
    findings = registry_coverage.run(project, waivers=waivers)
    msgs = "\n".join(f.message for f in findings)
    assert all(f.code == "L013" for f in findings), findings
    assert "no reason" in msgs
    assert "BOTH bound" in msgs
    assert "names no registered knob" in msgs
    assert len(findings) == 3, findings


def test_l013_unspanned_serving_op_flags(monkeypatch):
    """Removing one span declaration must surface as an L013 finding
    anchored at obs/spans.py — the doctor's coverage rule, now a lint
    invariant."""
    from flashinfer_tpu.obs import spans

    monkeypatch.delitem(spans.SPAN_CATEGORIES, "engine.kv_migrate")
    project = Project.from_paths([PKG_ROOT])
    findings = [f for f in registry_coverage.run(project)
                if "engine.kv_migrate" in f.message]
    assert [f.code for f in findings] == ["L013"], findings
    assert findings[0].filename.endswith("obs/spans.py")
    assert "flight recorder" in findings[0].message


def test_l013_costs_check_survives_broken_spans(monkeypatch):
    """An import-time failure in obs/spans.py (owned by L999) must not
    silently skip the INDEPENDENT API_OP_COSTS coverage check."""
    import sys

    from flashinfer_tpu.obs import costmodel

    monkeypatch.delitem(costmodel.API_OP_COSTS, "rmsnorm")
    monkeypatch.setitem(sys.modules, "flashinfer_tpu.obs.spans", None)
    findings = registry_coverage.run(Project.from_paths([PKG_ROOT]))
    hits = [f for f in findings if "'rmsnorm'" in f.message]
    assert [f.code for f in hits] == ["L013"], findings
    assert "API_OP_COSTS" in hits[0].message
    assert hits[0].filename.endswith("obs/costmodel.py")


def test_l013_doctor_delegation_is_the_same_implementation():
    """`obs doctor`'s coverage fields delegate to THIS pass: same
    values, one implementation (the ISSUE 15 unification)."""
    import inspect

    from flashinfer_tpu.obs import costmodel
    from flashinfer_tpu.obs.catalog import SERVING_OPS
    from flashinfer_tpu.obs.spans import SPAN_CATEGORIES

    # value parity with the pre-delegation inline set differences
    assert registry_coverage.unspanned_serving_ops() \
        == sorted(SERVING_OPS - set(SPAN_CATEGORIES))
    assert costmodel.uncovered_api_ops() \
        == registry_coverage.uncovered_api_ops()
    # and costmodel's surface IS a delegation, not a second copy
    src = inspect.getsource(costmodel.uncovered_api_ops)
    assert "registry_coverage" in src
    # obs doctor reads the delegated helper too
    import flashinfer_tpu.obs.__main__ as obs_main

    assert "_rc.unspanned_serving_ops()" in inspect.getsource(obs_main)


# ------------------------------- L006 provenance labels (satellite) --


def _staged_config(tmp_path, payload):
    pkg = tmp_path / "pkg"
    (pkg / "tuning_configs").mkdir(parents=True)
    (pkg / "mod.py").write_text("x = 1\n")
    cfg = pkg / "tuning_configs" / "gen.json"
    cfg.write_text(json.dumps(payload))
    return Project.from_paths([str(pkg)]), str(cfg)


@pytest.mark.quick
def test_l006_unlabeled_new_section_flagged(tmp_path):
    project, cfg = _staged_config(tmp_path, {
        "tactics": {},
        "newphase": {
            "tactics": {"rmsnorm.row_block|64_4096_bfloat16": 256},
        },
    })
    findings = tuning_schema.run(project)
    assert [f.code for f in findings] == ["L006"], findings
    assert findings[0].func == "newphase"
    assert "provenance" in findings[0].message


def test_l006_provenance_labels_accepted_and_validated(tmp_path):
    project, _ = _staged_config(tmp_path, {
        "tactics": {},
        "measured_phase": {
            "provenance": "measured",
            # graduation references (ISSUE 20): a "measured" label must
            # join to the bring-up journal + banked rows that produced it
            "journal_id": "bringup-20260807-0",
            "banked_row": ["abc123def456"],
            "tactics": {"rmsnorm.row_block|64_4096_bfloat16": 256},
        },
        "model_phase": {
            "provenance": "model-derived",
            "tactics": {},
        },
    })
    assert tuning_schema.run(project) == []
    project, _ = _staged_config(tmp_path / "bad", {
        "tactics": {},
        "phase": {"provenance": "vibes", "tactics": {}},
    })
    findings = tuning_schema.run(project)
    assert [f.code for f in findings] == ["L006"], findings
    assert "'vibes'" in findings[0].message


def test_l006_legacy_seed_flag_grandfathered(tmp_path):
    """The shipped pre-provenance sections label via `"seed": true` —
    grandfathered, per file and on the real tree.  `"seed": false`
    DISCLAIMS the legacy label and must carry real provenance."""
    project, _ = _staged_config(tmp_path, {
        "tactics": {},
        "prefill": {"seed": True, "tactics": {}},
    })
    assert tuning_schema.run(project) == []
    assert tuning_schema.run(Project.from_paths([PKG_ROOT])) == []
    project, _ = _staged_config(tmp_path / "nonseed", {
        "tactics": {},
        "prefill": {"seed": False, "tactics": {}},
    })
    findings = tuning_schema.run(project)
    assert [f.code for f in findings] == ["L006"], findings
    assert "provenance" in findings[0].message


def test_l006_malformed_tactics_section_still_checked(tmp_path):
    """A section whose tactics table is missing or not an object must
    not dodge the section-level checks: the loader drops it silently
    (a finding of its own) and its provenance is still validated."""
    project, _ = _staged_config(tmp_path, {
        "tactics": {},
        "v5e_kernel": {"provenance": "bogus", "tactics": ["oops"]},
    })
    findings = tuning_schema.run(project)
    msgs = "\n".join(f.message for f in findings)
    assert all(f.code == "L006" for f in findings), findings
    assert "no 'tactics' object" in msgs
    assert "'bogus'" in msgs
    assert len(findings) == 2, findings


# ----------------------------------------------- whole-tree pins --


def test_l011_to_l013_real_tree_clean():
    """Clean-tree pin for the three serving-contract passes on one
    shared Project — with NO baseline absorption."""
    project = Project.from_paths([PKG_ROOT])
    assert donation_lifetime.run(project) == []
    assert static_flow.run(project) == []
    assert registry_coverage.run(project) == []
