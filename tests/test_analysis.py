"""Multi-pass static analyzer (flashinfer_tpu.analysis).

Each pass must flag the EXACT pre-fix ADVICE.md round-5 bug shape it
was built from (true positive), honor reasoned ``# graft-lint: ok``
suppressions (rejecting reasonless ones as L000), and stay quiet on the
fixed/clean shape.  The whole-tree run over ``flashinfer_tpu/`` against
the committed baseline is the tier-1 CI gate: new findings fail the
suite at review time, not at the next advisor round.
"""

import json
import os
import textwrap

import pytest

from flashinfer_tpu import analysis
from flashinfer_tpu.analysis import (alias_rebind, jit_staticness,
                                     obs_coverage, signature_parity,
                                     tuning_schema)
from flashinfer_tpu.analysis.core import Project, load_source

PKG_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "flashinfer_tpu"))


def _project(*named_sources):
    return Project([load_source(textwrap.dedent(src), name)
                    for name, src in named_sources])


# ---------------------------------------------------------------- L001 --

# the ADVICE.md round-5 item-1 shape: the paged base wrapper binds
# `forward = run` at class-definition time; subclasses redefine run
PRE_FIX_ALIAS = """
    class BasePagedWrapper:
        def run(self, q, kv):
            return "base"
        forward = run

    class SinkWrapper(BasePagedWrapper):
        def run(self, q, kv):
            return "base+sink-epilogue"
"""

POST_FIX_ALIAS = PRE_FIX_ALIAS + """\
        forward = run
"""


def test_l001_flags_pre_fix_sink_wrapper_shape():
    findings = alias_rebind.run(_project(("attention.py", PRE_FIX_ALIAS)))
    assert len(findings) == 1, findings
    f = findings[0]
    assert f.code == "L001" and f.func == "SinkWrapper.run"
    assert "forward = run" in f.message and "SinkWrapper" in f.message
    # the runtime truth the lint models: the inherited alias really does
    # call the BASE run
    ns = {}
    exec(textwrap.dedent(PRE_FIX_ALIAS), ns)
    assert ns["SinkWrapper"]().forward(0, 0) == "base"  # the silent bug


def test_l001_rebind_fix_is_clean():
    findings = alias_rebind.run(_project(("attention.py", POST_FIX_ALIAS)))
    assert findings == [], findings
    ns = {}
    exec(textwrap.dedent(POST_FIX_ALIAS), ns)
    assert ns["SinkWrapper"]().forward(0, 0) == "base+sink-epilogue"


def test_l001_resolves_bases_across_files():
    """The real bug spanned prefill.py (alias) and attention.py
    (subclass) — the pass must resolve inheritance project-wide."""
    base = """
        class BasePagedWrapper:
            def run(self, q, kv):
                return "base"
            forward = run
    """
    sub = """
        class BatchAttention(BasePagedWrapper):
            def run(self, q, kv):
                return "holistic"
    """
    findings = alias_rebind.run(
        _project(("prefill.py", base), ("attention.py", sub)))
    assert [f.code for f in findings] == ["L001"]
    assert findings[0].filename == "attention.py"


def test_l001_grandchild_inheriting_redefined_run_flagged():
    """'inheriting a redefined run': the grandchild's forward skips the
    override it actually inherits, even though it defines nothing."""
    src = PRE_FIX_ALIAS + """
    class DerivedOfSink(SinkWrapper):
        pass
    """
    findings = alias_rebind.run(_project(("a.py", src)))
    assert {f.func for f in findings} == {"SinkWrapper.run",
                                          "DerivedOfSink"}


def test_l001_alias_above_def_in_same_class_flagged():
    src = """
        class Base:
            def run(self):
                return "base"

        class Sub(Base):
            forward = run_alias_target  # placeholder, replaced below
            def run(self):
                return "sub"
    """.replace("run_alias_target", "run")
    # `forward = run` above the def binds the INHERITED run... but only
    # resolves at class creation because Base.run exists in scope? No:
    # a bare `run` in a class body only sees names already bound in
    # that body — this exact source raises NameError at runtime, which
    # is the loud variant.  The lint flags the shape statically.
    findings = alias_rebind.run(_project(("a.py", src)))
    assert [f.code for f in findings] == ["L001"]
    assert "ABOVE" in findings[0].message


def test_l001_suppression_honored_and_reasonless_is_l000():
    suppressed = PRE_FIX_ALIAS.replace(
        'def run(self, q, kv):\n            return "base+sink-epilogue"',
        'def run(self, q, kv):  # graft-lint: ok forward overridden in '
        'every leaf\n            return "base+sink-epilogue"')
    assert suppressed != PRE_FIX_ALIAS
    findings = analysis.analyze_project(
        _project(("attention.py", suppressed)), bank={})
    assert [f.code for f in findings] == [], findings
    reasonless = suppressed.replace(
        "# graft-lint: ok forward overridden in every leaf",
        "# graft-lint: ok")
    findings = analysis.analyze_project(
        _project(("attention.py", reasonless)), bank={})
    assert [f.code for f in findings] == ["L000"], findings


def test_l001_real_attention_py_is_clean_post_fix():
    """The shipped fix: BatchAttention / POD / the sink wrapper all
    rebind `forward = run`; the pass agrees across the real files."""
    project = Project.from_paths([
        os.path.join(PKG_ROOT, "prefill.py"),
        os.path.join(PKG_ROOT, "attention.py"),
        os.path.join(PKG_ROOT, "sparse.py"),
        os.path.join(PKG_ROOT, "decode.py"),
        os.path.join(PKG_ROOT, "mla.py"),
    ])
    assert alias_rebind.run(project) == []


def test_forward_dispatches_to_subclass_run():
    """Runtime regression for the satellite fix itself: forward() on
    every attention.py wrapper dispatches to the SUBCLASS run and
    honors its return contract (ADVICE.md item 1)."""
    import flashinfer_tpu as fi

    assert fi.BatchAttention.forward \
        is fi.BatchAttention.run
    assert fi.PODWithPagedKVCacheWrapper.forward \
        is fi.PODWithPagedKVCacheWrapper.run
    assert fi.BatchAttentionWithAttentionSinkWrapper.forward \
        is fi.BatchAttentionWithAttentionSinkWrapper.run
    # and none of them inherited the base paged wrapper's bound alias
    base = fi.BatchPrefillWithPagedKVCacheWrapper
    for cls in (fi.BatchAttention, fi.PODWithPagedKVCacheWrapper,
                fi.BatchAttentionWithAttentionSinkWrapper):
        assert cls.forward is not base.run


# ---------------------------------------------------------------- L002 --

# the ADVICE.md round-5 item-2 shape: window_left inserted positionally
# between logits_soft_cap and q_data_type
PRE_FIX_PLAN = """
    class BatchAttention:
        def plan(self, qo_indptr, kv_indptr, kv_indices, kv_len_arr,
                 num_qo_heads, num_kv_heads, head_dim_qk, head_dim_vo,
                 page_size, causal=False, sm_scale=None,
                 logits_soft_cap=None, window_left=-1,
                 q_data_type=None, kv_data_type=None,
                 use_profiler=False):
            pass

        def run(self, q, paged_kv_cache, out=None, lse=None,
                k_scale=None, v_scale=None, logits_soft_cap=0.0,
                profiler_buffer=None, **kw):
            pass
"""

POST_FIX_PLAN = PRE_FIX_PLAN.replace(
    "logits_soft_cap=None, window_left=-1,",
    "logits_soft_cap=None, *, window_left=-1,")


def test_l002_flags_pre_fix_window_left_insertion():
    findings = signature_parity.run(
        _project(("flashinfer_tpu/attention.py", PRE_FIX_PLAN)))
    assert len(findings) == 1, findings
    f = findings[0]
    assert f.code == "L002"
    assert "window_left" in f.message and "q_data_type" in f.message


def test_l002_keyword_only_fix_is_clean():
    assert POST_FIX_PLAN != PRE_FIX_PLAN
    findings = signature_parity.run(
        _project(("flashinfer_tpu/attention.py", POST_FIX_PLAN)))
    assert findings == [], findings


def test_l002_extra_trailing_positional_flagged():
    src = POST_FIX_PLAN.replace("use_profiler=False):",
                                "use_profiler=False, extra_knob=None):")
    # keyword-only extras are fine ...
    assert signature_parity.run(_project(("flashinfer_tpu/attention.py", src))) == []
    src = PRE_FIX_PLAN.replace(
        "logits_soft_cap=None, window_left=-1,\n"
        "                 q_data_type=None, kv_data_type=None,\n"
        "                 use_profiler=False):",
        "logits_soft_cap=None, q_data_type=None, kv_data_type=None,\n"
        "                 use_profiler=False, extra_knob=None):")
    findings = signature_parity.run(_project(("flashinfer_tpu/attention.py", src)))
    # ... positional ones beyond the reference arity are not
    assert [f.code for f in findings] == ["L002"], findings
    assert "extra_knob" in findings[0].message


def test_l002_vararg_voids_loud_overflow_and_is_flagged():
    """`*args` after a matching prefix swallows a reference caller's
    extra positionals with no error — worse than either a misbind
    (caught above) or a TypeError (the accepted fix); must flag."""
    src = POST_FIX_PLAN.replace(
        "def run(self, q, paged_kv_cache, out=None, lse=None,",
        "def run(self, q, paged_kv_cache, *args, out=None, lse=None,")
    assert "*args" in src
    findings = signature_parity.run(
        _project(("flashinfer_tpu/attention.py", src)))
    assert [f.code for f in findings] == ["L002"], findings
    assert "*args" in findings[0].message


def test_l002_stale_bank_symbol_is_reported():
    """Renaming a banked method must surface, not silently drop its
    parity protection: the file matches but the qualname is gone."""
    src = POST_FIX_PLAN.replace("def run(", "def execute(")
    assert "def execute(" in src
    findings = signature_parity.run(
        _project(("flashinfer_tpu/attention.py", src)))
    assert len(findings) == 1, findings
    assert findings[0].code == "L002"
    assert "not found" in findings[0].message
    assert "BatchAttention.run" in findings[0].func


def test_l002_real_tree_matches_bank():
    """Every recorded symbol in the shipped signature bank matches the
    shipped implementation — the window_left/kv_cache_sf fixes hold."""
    project = Project.from_paths([PKG_ROOT])
    assert signature_parity.run(project) == []


def test_l002_bank_symbols_exist_in_tree():
    """A renamed/deleted method must not silently drop out of parity
    checking: every bank key resolves at its EXACT project-relative
    path in the real tree (a same-basename file elsewhere — e.g.
    parallel/attention.py — must not satisfy the check)."""
    from flashinfer_tpu.analysis.core import project_relpath

    bank = signature_parity.load_bank()
    project = Project.from_paths([PKG_ROOT])
    by_path = {}
    for sf in project.files:
        by_path[project_relpath(sf.path)] = \
            signature_parity._qualname_defs(sf)
    for key in bank:
        path, _, qualname = key.partition(":")
        assert qualname in by_path.get(path, {}), \
            f"bank symbol {key} not found — update the bank or the code"


def test_batch_attention_plan_rejects_positional_window_left():
    """Runtime regression for the satellite fix: the verbatim reference
    positional call shape (dtypes after logits_soft_cap) now fails
    LOUDLY instead of binding a dtype into window_left."""
    import jax.numpy as jnp
    import numpy as np

    import flashinfer_tpu as fi

    w = fi.BatchAttention()
    qo = np.array([0, 1], np.int32)
    kvp = np.array([0, 1], np.int32)
    kvi = np.array([0], np.int32)
    kvl = np.array([1], np.int32)
    with pytest.raises(TypeError):
        # 13th positional is the reference's q_data_type slot — the
        # pre-fix signature bound it into window_left silently
        w.plan(qo, kvp, kvi, kvl, 1, 1, 128, 128, 1, False, None, None,
               jnp.bfloat16)
    # keyword form still works and window_left stays an int
    w.plan(qo, kvp, kvi, kvl, 1, 1, 128, 128, 1, causal=False,
           q_data_type=jnp.bfloat16, window_left=-1)


def test_batch_attention_failed_replan_keeps_soft_cap_in_sync(monkeypatch):
    """A re-plan that fails INSIDE the base planner must not desync the
    logits_soft_cap run() validates against from the still-active
    previous plan (else a run passing the live plan's cap raises and a
    run passing the dead plan's cap is accepted silently)."""
    import jax.numpy as jnp
    import numpy as np

    import flashinfer_tpu as fi

    w = fi.BatchAttention()
    qo = np.array([0, 1], np.int32)
    kvp = np.array([0, 1], np.int32)
    kvi = np.array([0], np.int32)
    kvl = np.array([1], np.int32)
    w.plan(qo, kvp, kvi, kvl, 1, 1, 128, 128, 1, causal=False,
           logits_soft_cap=30.0, q_data_type=jnp.bfloat16)

    def boom(self, *a, **kw):
        raise RuntimeError("planner failure mid-replan")

    monkeypatch.setattr(
        fi.BatchPrefillWithPagedKVCacheWrapper, "plan", boom)
    with pytest.raises(RuntimeError):
        w.plan(qo, kvp, kvi, kvl, 1, 1, 128, 128, 1, causal=False,
               logits_soft_cap=50.0, q_data_type=jnp.bfloat16)
    assert w._plan_soft_cap == 30.0  # still the live plan's cap


# ---------------------------------------------------------------- L003 --

# the ADVICE.md round-5 item-4 shape: a jitted helper with `backend`
# static reaches an env read through the resolver chain
PRE_FIX_TOPK = """
    import functools
    import os

    import jax

    def _resolve_backend(backend):
        if backend == "auto":
            backend = os.environ.get("TOPK_BACKEND", "xla")
        return backend

    def top_k_values_indices(scores, k, backend="auto"):
        if _resolve_backend(backend) == "threshold":
            return "threshold", None
        return "xla", None

    @functools.partial(jax.jit, static_argnames=("k", "backend"))
    def _top_k_large_ties(scores, k, backend):
        return top_k_values_indices(scores, k, backend)
"""


def test_l003_flags_pre_fix_backend_pinning():
    findings = jit_staticness.run(_project(("compat.py", PRE_FIX_TOPK)))
    assert len(findings) == 1, findings
    f = findings[0]
    assert f.code == "L003" and f.func == "_top_k_large_ties"
    assert "top_k_values_indices" in f.message


def test_l003_direct_env_read_in_jitted_function():
    src = """
        import os
        import jax

        @jax.jit
        def f(x):
            if os.environ.get("FLAG", "0") == "1":
                return x + 1
            return x

        def eager(x):
            return os.environ.get("FLAG")  # not jitted: fine
    """
    findings = jit_staticness.run(_project(("m.py", src)))
    assert [f.func for f in findings] == ["f"]
    assert "trace time" in findings[0].message


def test_l003_jit_wrapped_assignment_form():
    src = """
        import os
        import jax

        def g(x):
            return os.getenv("FLAG")

        g_fast = jax.jit(g)
    """
    findings = jit_staticness.run(_project(("m.py", src)))
    assert [f.func for f in findings] == ["g"]


def test_l003_mutated_global_read_flagged_constant_exempt():
    src = """
        import jax

        _CACHE = {}
        _TABLE = {"a": 1}  # never mutated: a constant, exempt

        def warm(k, v):
            _CACHE[k] = v

        @jax.jit
        def f(x):
            return _CACHE.get("cfg", 0) + _TABLE["a"] + x
    """
    findings = jit_staticness.run(_project(("m.py", src)))
    assert len(findings) == 1, findings
    assert "_CACHE" in findings[0].message


def test_l003_mutated_global_taint_propagates_through_calls():
    """A mutated-global read one call deep must taint the jitted
    caller, same as an env read (the config-pinned-in-jit-cache class
    the pass documents)."""
    src = """
        import jax

        _CACHE = {}

        def warm(k, v):
            _CACHE[k] = v

        def get_cfg():
            return _CACHE.get("cfg", 0)

        @jax.jit
        def f(x):
            return get_cfg() + x
    """
    findings = jit_staticness.run(_project(("m.py", src)))
    assert [f.func for f in findings] == ["f"], findings
    assert "get_cfg" in findings[0].message


def test_l003_composed_jit_wrap_marks_inner_callable():
    """The repo's dominant launch shape — jax.jit(shard_map(step, ...))
    — must mark `step` as jitted; the step closures of every sharded
    model are exactly this population."""
    src = """
        import os
        import jax

        def make(mesh, specs):
            def step(params, x):
                if os.environ.get("FLAG"):
                    return x
                return x + 1
            return jax.jit(jax.shard_map(step, mesh=mesh, **specs))
    """
    findings = jit_staticness.run(_project(("m.py", src)))
    assert [f.func for f in findings] == ["step"], findings


def test_l003_data_args_of_composed_jit_wrap_not_marked():
    """Only the traced callable chain (first positional arg at each
    level) is jit-marked — a config/callback operand sharing a module
    function's name must not be reported as jit-traced."""
    src = """
        import os
        import jax
        import functools

        def post_fn(x):  # env-reading module function...
            return os.getenv("FLAG")

        def step(params, x):
            return x

        def make(wrap, cfg):
            # ...passed as DATA here, never traced
            return jax.jit(wrap(step, post_fn))
    """
    findings = jit_staticness.run(_project(("m.py", src)))
    assert findings == [], findings


def test_project_relpath_rightmost_marker_wins():
    """A checkout directory named flashinfer_tpu must not hijack the
    key of a tests/ file nested inside it."""
    from flashinfer_tpu.analysis.core import project_relpath

    assert project_relpath(
        "/home/u/flashinfer_tpu/tests/test_x.py") == "tests/test_x.py"
    assert project_relpath(
        "/home/u/flashinfer_tpu/flashinfer_tpu/ops/k.py"
    ) == "flashinfer_tpu/ops/k.py"


def test_l003_external_library_namesakes_not_tainted():
    """jax.lax.top_k must not inherit taint from a project function
    that happens to be called top_k (the basename-collision FP)."""
    src = """
        import os
        import jax

        def top_k(scores, k):  # project top_k: reads env
            os.environ.get("BACKEND")

        @jax.jit
        def router(logits, k):
            return jax.lax.top_k(logits, k)  # external: clean
    """
    findings = jit_staticness.run(_project(("m.py", src)))
    assert findings == [], findings


def test_l003_eager_resolution_plus_suppression_is_clean():
    """The shipped fix shape: top_k resolves the backend eagerly and the
    jitted helper carries a reasoned suppression for the now-dead
    transitive edge."""
    fixed = PRE_FIX_TOPK.replace(
        "        return top_k_values_indices(scores, k, backend)",
        "        # graft-lint: ok backend pre-resolved eagerly, never auto\n"
        "        return top_k_values_indices(scores, k, backend)")
    assert fixed != PRE_FIX_TOPK
    findings = analysis.analyze_project(
        _project(("compat.py", fixed)), bank={})
    assert findings == [], findings


def test_compat_top_k_resolves_backend_eagerly(monkeypatch):
    """Runtime regression for the satellite fix: with tie_break=LARGE,
    FLASHINFER_TPU_TOPK_BACKEND is honored per-call — the first call's
    resolution must NOT be pinned by the jit cache (ADVICE.md item 4)."""
    import jax.numpy as jnp
    import numpy as np

    import flashinfer_tpu as fi
    from flashinfer_tpu.compat import TopKTieBreak

    # On this input the backends produce a DIFFERENT output order for
    # the same top-3 set, so a pinned backend is observable: xla is
    # value-ordered; threshold emits strict entries in index order of
    # the column-reversed input ([2,4,1,5] -> 4 before 5).
    scores = jnp.asarray(np.array([[5.0, 1.0, 4.0, 2.0]], np.float32))
    monkeypatch.delenv("FLASHINFER_TPU_TOPK_BACKEND", raising=False)
    v1, i1 = fi.top_k(scores, 3, tie_break=TopKTieBreak.LARGE,
                      backend="auto")
    # flip the env var AFTER the first (cached) call — with the bug the
    # first call's in-trace "auto"->xla resolution is replayed from the
    # jit cache and the override is silently ignored
    monkeypatch.setenv("FLASHINFER_TPU_TOPK_BACKEND", "threshold")
    v2, i2 = fi.top_k(scores, 3, tie_break=TopKTieBreak.LARGE,
                      backend="auto")
    assert sorted(np.asarray(i1).ravel().tolist()) \
        == sorted(np.asarray(i2).ravel().tolist()) == [0, 2, 3]
    assert np.asarray(i1).ravel().tolist() == [0, 2, 3]  # xla: by value
    assert np.asarray(v1).ravel().tolist() == [5.0, 4.0, 2.0]
    assert np.asarray(i2).ravel().tolist() == [2, 0, 3]  # threshold
    assert np.asarray(v2).ravel().tolist() == [4.0, 5.0, 2.0]


# ---------------------------------------------------------------- L005 --


def test_l005_flags_uncataloged_decorated_op():
    src = """
        from flashinfer_tpu.api_logging import flashinfer_api

        @flashinfer_api
        def brand_new_op(x):
            return x
    """
    findings = obs_coverage.run(_project(("newmod.py", src)))
    assert [f.code for f in findings] == ["L005"], findings
    assert "brand_new_op" in findings[0].message
    assert "API_OPS" in findings[0].message


def test_l005_cataloged_ops_clean_including_name_kwarg():
    src = """
        from flashinfer_tpu.api_logging import flashinfer_api

        @flashinfer_api
        def rmsnorm(x):
            return x

        @flashinfer_api(name="silu_and_mul")
        def _impl(x):
            return x
    """
    assert obs_coverage.run(_project(("m.py", src))) == []


def test_l005_dynamic_name_is_unverifiable_and_flagged():
    src = """
        from flashinfer_tpu.api_logging import flashinfer_api

        NAME = "rmsnorm"

        @flashinfer_api(name=NAME)
        def op(x):
            return x
    """
    findings = obs_coverage.run(_project(("m.py", src)))
    assert [f.code for f in findings] == ["L005"], findings
    assert "literal" in findings[0].message


def test_l005_suppression_honored_through_driver():
    src = """
        from flashinfer_tpu.api_logging import flashinfer_api

        # graft-lint: ok internal helper, deliberately uncataloged
        def shim():
            @flashinfer_api
            def inner_op(x):
                return x
            return inner_op
    """
    findings = analysis.analyze_project(_project(("m.py", src)), bank={})
    # the suppression sits above the nested def's decorator... it must
    # be on the def line or directly above it, so this one does NOT
    # waive (two lines up) — move it adjacent and it does
    assert [f.code for f in findings] == ["L005"]
    adjacent = src.replace(
        "            @flashinfer_api\n            def inner_op(x):",
        "            @flashinfer_api\n            # graft-lint: ok "
        "internal helper, deliberately uncataloged\n"
        "            def inner_op(x):")
    findings = analysis.analyze_project(
        _project(("m.py", adjacent)), bank={})
    assert findings == [], findings


def test_l005_catalog_matches_the_decorated_tree_exactly():
    """Both directions: every decorated op is cataloged (the CI gate)
    AND every catalog entry corresponds to a real decorated function —
    a stale API_OPS entry would silently shrink the observed surface."""
    import re

    from flashinfer_tpu.obs.catalog import API_OPS

    project = Project.from_paths([PKG_ROOT])
    findings = obs_coverage.run(project, ops=frozenset())
    found = {m.group(1) for f in findings
             for m in [re.search(r"public op '([^']+)'", f.message)] if m}
    assert found == set(API_OPS)
    # and against the real catalog the tree is clean
    assert obs_coverage.run(project) == []


# ------------------------------------------------------------- driver --


def test_wedge_pass_runs_behind_driver():
    src = """
        import jax.numpy as jnp

        def lane_repeat_kernel(x_ref, o_ref):
            o_ref[...] = jnp.repeat(x_ref[...], 4, axis=-1)
    """
    findings = analysis.analyze_project(_project(("k.py", src)), bank={})
    assert [f.code for f in findings] == ["W003"]


def test_graft_suppression_applies_to_wedge_codes_via_driver():
    src = """
        import jax.numpy as jnp

        def lane_repeat_kernel(x_ref, o_ref):
            # graft-lint: ok expander-dot verified on-chip 2026-07-29
            o_ref[...] = jnp.repeat(x_ref[...], 4, axis=-1)
    """
    findings = analysis.analyze_project(_project(("k.py", src)), bank={})
    assert findings == [], findings


def test_unparseable_source_is_l999_not_a_crash():
    findings = analysis.analyze_project(
        _project(("bad.py", "def broken(:\n")), bank={})
    assert [f.code for f in findings] == ["L999"]


@pytest.mark.quick
def test_whole_tree_findings_subset_of_committed_baseline():
    """THE tier-1 CI gate: the shipped tree has no findings beyond the
    committed, triaged baseline — and the baseline carries no stale
    entries silently freeing budget for new bugs of the same shape."""
    findings = analysis.analyze_paths([PKG_ROOT])
    baseline = analysis.load_baseline()
    new, old, stale = analysis.partition_against_baseline(
        findings, baseline)
    assert new == [], "NEW findings not in baseline (fix or triage " \
        "into baseline.json):\n" + "\n".join(str(f) for f in new)
    assert stale == [], f"stale baseline entries to prune: {stale}"


def test_cli_clean_against_baseline_and_fails_without():
    assert analysis.main([PKG_ROOT]) == 0
    # the baseline is non-empty today, so --no-baseline must fail
    if analysis.load_baseline():
        assert analysis.main([PKG_ROOT, "--no-baseline"]) == 1


def test_cli_dump_signatures_smoke(capsys):
    assert analysis.main([PKG_ROOT, "--dump-signatures"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert "flashinfer_tpu/attention.py:BatchAttention.plan" in out
    ref = out["flashinfer_tpu/attention.py:BatchAttention.plan"]
    assert "window_left" in ref["implementation_kwonly"]


def test_baseline_roundtrip(tmp_path):
    findings = analysis.analyze_paths([PKG_ROOT])
    path = str(tmp_path / "baseline.json")
    analysis.write_baseline(findings, path)
    new, old, stale = analysis.partition_against_baseline(
        findings, analysis.load_baseline(path))
    assert new == [] and stale == [] and len(old) == len(findings)


def test_runtime_guard_honors_graft_suppressions():
    """A CI-blessed `# graft-lint: ok <reason>` must also satisfy the
    RUNTIME compile guard (check_module goes through lint_source): a
    suppression that passes CI but hard-blocks hardware compiles in
    strict mode would make the two gates diverge."""
    from flashinfer_tpu.analysis import wedge

    src = textwrap.dedent("""
        import jax.numpy as jnp

        def lane_repeat_kernel(x_ref, o_ref):
            # graft-lint: ok selector-matmul verified on-chip 2026-07-29
            o_ref[...] = jnp.repeat(x_ref[...], 4, axis=-1)
    """)
    assert wedge.lint_source(src, "k.py") == []
    # and reasonless graft form is a W000, exactly like the wedge form
    bare = src.replace(
        "# graft-lint: ok selector-matmul verified on-chip 2026-07-29",
        "# graft-lint: ok")
    assert [f.code for f in wedge.lint_source(bare, "k.py")] == ["W000"]


def test_orphan_reasonless_wedge_suppression_is_w000():
    """A bare '# wedge-lint: ok' that shields NOTHING is still an
    unreviewable waiver (it would silently mute the next W-finding on
    its line) — the driver must report it even though the wedge pass
    only emits W000 for shielding suppressions."""
    src = """
        def plain_helper(x):
            return x + 1  # wedge-lint: ok
    """
    findings = analysis.analyze_project(_project(("m.py", src)), bank={})
    assert [f.code for f in findings] == ["W000"], findings
    # a REASONED orphan is fine (same contract as the graft spelling)
    reasoned = src.replace("# wedge-lint: ok",
                           "# wedge-lint: ok documented-safe pattern")
    findings = analysis.analyze_project(
        _project(("m.py", reasoned)), bank={})
    assert findings == [], findings
    # and no double-report when the bare suppression DOES shield a
    # W-code (the wedge pass's own W000 wins)
    shielding = """
        import jax.numpy as jnp

        def lane_repeat_kernel(x_ref, o_ref):
            o_ref[...] = jnp.repeat(x_ref[...], 4, axis=-1)  # wedge-lint: ok
    """
    findings = analysis.analyze_project(
        _project(("k.py", shielding)), bank={})
    assert [f.code for f in findings] == ["W000"], findings


def test_write_baseline_refuses_reasonless_suppression_findings(
        tmp_path, capsys):
    """--write-baseline must never accept L000/W000: a reasonless
    waiver is definitionally un-triageable and has to be FIXED, not
    baselined into permanent silence."""
    findings = [
        analysis.Finding("L000", "flashinfer_tpu/x.py", 3,
                         "<suppression>", "no reason"),
        analysis.Finding("L003", "flashinfer_tpu/x.py", 9, "f", "env"),
    ]
    path = str(tmp_path / "b.json")
    analysis.write_baseline(findings, path)
    assert "refusing to baseline" in capsys.readouterr().out
    loaded = analysis.load_baseline(path)
    assert ("L003", "flashinfer_tpu/x.py", "f") in loaded
    assert all(code not in ("L000", "W000") for code, _, _ in loaded)
    # a hand-edited L000 entry is ignored on load as well
    data = json.load(open(path))
    data["findings"].append({"code": "L000", "path": "flashinfer_tpu/x.py",
                             "func": "<suppression>", "count": 1})
    json.dump(data, open(path, "w"))
    assert all(code != "L000" for code, _, _ in analysis.load_baseline(path))


# ------------------------------------------------------ L006 tuning_schema --


def _staged_config(tmp_path, payload):
    """A synthetic project dir: one analyzed module + a tuning_configs
    JSON next to it (the pass discovers configs project-relative)."""
    pkg = tmp_path / "pkg"
    (pkg / "tuning_configs").mkdir(parents=True)
    (pkg / "mod.py").write_text("x = 1\n")
    cfg = pkg / "tuning_configs" / "gen.json"
    cfg.write_text(payload if isinstance(payload, str)
                   else json.dumps(payload))
    return Project.from_paths([str(pkg)]), str(cfg)


def test_l006_valid_flat_and_section_entries_pass(tmp_path):
    project, _ = _staged_config(tmp_path, {
        "tactics": {"rmsnorm.row_block|1024_4096_bfloat16": 256},
        "prefill": {
            "seed": True,
            "tactics": {
                "fused_prefill.blocks|8_4096_32_8_128_16": [256, 16],
                "mla_decode.layout|a_b": "split",
            },
        },
    })
    assert tuning_schema.run(project) == []


def test_l006_stale_and_malformed_entries_flagged(tmp_path):
    project, cfg = _staged_config(tmp_path, {
        "tactics": {
            "renamed_op.blocks|8_4096": [128, 8],       # unknown knob
            "fused_prefill.blocks|8_4096": [128],       # wrong arity
            "mla_decode.layout|a": "interleaved",       # not in choices
            "rmsnorm.row_block": 128,                   # no shape part
        },
    })
    findings = tuning_schema.run(project)
    assert [f.code for f in findings] == ["L006"] * 4
    assert all(f.filename == cfg for f in findings)
    by_func = {f.func: f.message for f in findings}
    assert "unknown autotuner knob" in by_func["renamed_op.blocks|8_4096"]
    assert "2 positive ints" in by_func["fused_prefill.blocks|8_4096"]
    assert "choices" in by_func["mla_decode.layout|a"]
    assert "no shape part" in by_func["rmsnorm.row_block"]
    # findings anchor to the key's own line in the JSON
    src = open(cfg).read()
    for f in findings:
        assert json.dumps(f.func) in src.splitlines()[f.line - 1]


def test_l006_unparseable_config_is_a_finding_not_a_crash(tmp_path):
    project, cfg = _staged_config(tmp_path, "{not json")
    findings = tuning_schema.run(project)
    assert [f.code for f in findings] == ["L006"]
    assert "unreadable" in findings[0].message


def test_l006_shipped_configs_clean_and_consumed():
    """The committed tuning_configs files pass the schema gate AND the
    prefill sections actually reach the autotuner's merged table."""
    project = Project.from_paths([PKG_ROOT])
    assert tuning_schema.run(project) == []
    from flashinfer_tpu.autotuner import _flatten_config

    for stem in ("v5e", "v5p"):
        path = os.path.join(PKG_ROOT, "tuning_configs", f"{stem}.json")
        data = json.load(open(path))
        assert data["prefill"]["tactics"], stem  # populated section
        flat = _flatten_config(data)
        for key in data["prefill"]["tactics"]:
            assert key in flat, (stem, key)
        # seed labeling stays explicit until on-chip rows are banked
        assert data["prefill"]["seed"] is True


def test_flatten_config_drops_invalid_entries_and_merges_sections():
    from flashinfer_tpu.autotuner import _flatten_config

    flat = _flatten_config({
        "tactics": {
            "rmsnorm.row_block|k": 128,
            "rmsnorm.row_block|bad": "not-an-int",
            "gone_op.tiles|k": [1, 2],
        },
        "prefill": {"tactics": {"fused_prefill.blocks|k": [128, 8],
                                "rmsnorm.row_block|k": 256}},
    })
    # section entry wins on collision; invalid/unknown entries dropped
    assert flat == {"rmsnorm.row_block|k": 256,
                    "fused_prefill.blocks|k": [128, 8]}


# ---------------------------------------- L007 pallas_contract --


OPS_PREFILL = os.path.join(PKG_ROOT, "ops", "paged_prefill.py")


def _prefill_project(src):
    """The real paged_prefill.py (optionally surgically edited) as a
    one-file project — the acceptance regression runs the pass against
    the REAL planner/kernel/launch, not a toy."""
    return _project(("ops/paged_prefill.py", src))


@pytest.mark.quick
def test_l007_flags_injected_num_scalar_prefetch_skew():
    """THE acceptance regression: deliberately skewing the
    num_scalar_prefetch literal at the fused-prefill launch must fail
    L007 (both the kernel-param check and the plan-operand check)."""
    real = open(OPS_PREFILL).read()
    skew = real.replace("num_scalar_prefetch=11,",
                        "num_scalar_prefetch=10,")
    assert skew != real
    from flashinfer_tpu.analysis import pallas_contract

    findings = pallas_contract.run(_prefill_project(skew))
    assert len(findings) == 2, findings
    assert all(f.code == "L007" for f in findings)
    assert any("names 11 scalar-prefetch ref(s)" in f.message
               for f in findings)
    assert any("passes 11 plan array(s)" in f.message for f in findings)


def test_l007_flags_dropped_plan_array_operand():
    """Dropping one plan array from the launch invocation must fail.
    The operand prefix is shared by BOTH work-unit launchers (the
    attention launch and the ISSUE 14 ingest launch), so the mutation
    breaks both and each must flag independently."""
    real = open(OPS_PREFILL).read()
    drop = real.replace(
        'plan["qslot"], plan["code"], plan["pages"],',
        'plan["code"], plan["pages"],')
    assert drop != real
    from flashinfer_tpu.analysis import pallas_contract

    findings = pallas_contract.run(_prefill_project(drop))
    assert [f.code for f in findings] == ["L007", "L007"], findings
    assert {f.func for f in findings} == {
        "fused_paged_prefill", "fused_paged_prefill_ingest"}, findings
    assert all("plan array(s)" in f.message for f in findings)


def test_l007_flags_plan_key_the_planner_never_emits():
    """Dropping 'qslot' from the planner's returned dict while the
    launch still consumes plan["qslot"] must fail — the cross-function
    (planner -> launch) half of the contract."""
    real = open(OPS_PREFILL).read()
    dropkey = real.replace(
        "first=col(first), wout=col(wout), qslot=col(qslot),",
        "first=col(first), wout=col(wout),")
    assert dropkey != real
    from flashinfer_tpu.analysis import pallas_contract

    findings = pallas_contract.run(_prefill_project(dropkey))
    assert [f.code for f in findings] == ["L007"], findings
    assert "qslot" in findings[0].message
    assert "build_prefill_work_units" in findings[0].message


def test_l007_index_map_arity_and_kernel_arity_fixture():
    src = """
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def _k(x_ref, o_ref, acc_ref, extra_ref):
            o_ref[...] = x_ref[...]

        def launch(x):
            return pl.pallas_call(
                _k,
                grid=(4, 2),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i, j: (0, 0)),
                scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],
            )(x)
    """
    from flashinfer_tpu.analysis import pallas_contract

    findings = pallas_contract.run(_project(("k.py", src)))
    # one index_map arity finding (lambda i vs rank-2 grid) and one
    # kernel arity finding (4 params vs 1+1+1=3)
    assert len(findings) == 2, findings
    assert all(f.code == "L007" for f in findings)
    assert any("index_map takes 1 parameter(s)" in f.message
               for f in findings)
    assert any("takes 4 positional ref(s)" in f.message
               for f in findings)


def test_l007_positional_partial_binds_counted_out():
    """partial(_k, True) consumes the kernel's leading param: the
    3-param kernel launched with 2 specs must NOT be flagged."""
    src = """
        import functools
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def _k(causal, x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def launch(x):
            return pl.pallas_call(
                functools.partial(_k, True),
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            )(x)
    """
    from flashinfer_tpu.analysis import pallas_contract

    assert pallas_contract.run(_project(("k.py", src))) == []


def test_l007_unresolvable_registered_planner_skips():
    """A subset run missing the registered planner's module must skip
    the planner checks, not report — --changed-only analyzes partial
    trees and can only under-report, never false-fail."""
    real = open(OPS_PREFILL).read()
    # strip the planner def so only the launch half is in the project
    launch_only = real.replace("def build_prefill_work_units",
                               "def _renamed_away_planner")
    assert launch_only != real
    from flashinfer_tpu.analysis import pallas_contract

    findings = pallas_contract.run(_prefill_project(launch_only))
    assert findings == [], findings


def test_l007_shadowing_param_does_not_resolve_to_outer_assign():
    """An inner function's parameter must be UNRESOLVABLE, not fall
    through to a shadowed outer once-assigned name — the launch takes
    whatever list the caller passes at runtime."""
    src = """
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def _k(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def outer(x):
            specs = [pl.BlockSpec((8, 128), lambda i: (0, 0)),
                     pl.BlockSpec((8, 128), lambda i: (0, 0))]

            def inner(specs):
                return pl.pallas_call(
                    _k,
                    grid=(4,),
                    in_specs=specs,
                    out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
                )(x)

            return inner([pl.BlockSpec((8, 128), lambda i: (0, 0))])
    """
    from flashinfer_tpu.analysis import pallas_contract

    assert pallas_contract.run(_project(("k.py", src))) == []


def test_l007_cross_module_planner_resolution():
    """Planner in one module, launch in another: the registry check
    resolves through the project symbol index."""
    planner = """
        import numpy as np

        def build_prefill_work_units(n):
            return dict(qstart=np.zeros(n), kvlen=np.zeros(n))
    """
    launch = """
        import functools
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def _fused_prefill_kernel(qstart_ref, kvlen_ref, *refs, bq):
            pass

        def go(plan, q):
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(4,),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec(memory_space=pl.ANY),
                scratch_shapes=[],
            )
            return pl.pallas_call(
                functools.partial(_fused_prefill_kernel, bq=8),
                grid_spec=grid_spec,
                out_shape=q,
            )(plan["qstart"], plan["MISSING"], q)
    """
    from flashinfer_tpu.analysis import pallas_contract

    findings = pallas_contract.run(
        _project(("planner.py", planner), ("launchmod.py", launch)))
    assert [f.code for f in findings] == ["L007"], findings
    assert "MISSING" in findings[0].message


def test_l007_to_l010_real_tree_clean():
    """Clean-tree pin for ALL four kernel-contract passes on one shared
    Project (pallas_sites resolve once): the shipped planner/kernel/
    launch triples agree, no traced-value leaks, shipped configs fit
    VMEM, accumulators are initialized — with NO baseline absorption
    (the passes themselves return nothing)."""
    from flashinfer_tpu.analysis import (kernel_init_guard,
                                         pallas_contract, tracer_leak,
                                         vmem_budget)

    project = Project.from_paths([PKG_ROOT])
    assert pallas_contract.run(project) == []
    assert tracer_leak.run(project) == []
    assert vmem_budget.run(project) == []
    assert kernel_init_guard.run(project) == []


# ------------------------------------------- L008 tracer_leak --


@pytest.mark.quick
def test_l008_flags_traced_control_flow_and_concretization():
    src = """
        import jax
        import numpy as np

        @jax.jit
        def f(x, n):
            if x > 0:
                x = x + 1
            k = int(n)
            y = np.sum(x)
            z = x.item()
            assert x > 0
            return x
    """
    from flashinfer_tpu.analysis import tracer_leak

    findings = tracer_leak.run(_project(("m.py", src)))
    assert len(findings) == 5, findings
    msgs = " | ".join(f.message for f in findings)
    assert "Python if" in msgs
    assert "int()" in msgs
    assert "np.sum()" in msgs
    assert ".item()" in msgs
    assert "assert" in msgs


def test_l008_static_args_shape_and_structure_are_exempt():
    src = """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("n",))
        def g(x, n):
            if n > 2:
                return x
            total, d = x.shape
            if total > 8:
                return x
            has = x is not None
            if has:
                return x
            while d > 1:
                d //= 2
            return x
    """
    from flashinfer_tpu.analysis import tracer_leak

    assert tracer_leak.run(_project(("m.py", src))) == []


def test_l008_pallas_kernel_refs_are_traced_kwonly_static():
    src = """
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def _k(x_ref, o_ref, *, causal):
            if causal:          # partial-bound static: fine
                pass
            if x_ref[0] > 0:    # traced ref read: leak
                pass
            o_ref[...] = x_ref[...]

        def launch(x):
            import functools
            return pl.pallas_call(
                functools.partial(_k, causal=True),
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            )(x)
    """
    from flashinfer_tpu.analysis import tracer_leak

    findings = tracer_leak.run(_project(("k.py", src)))
    assert [f.code for f in findings] == ["L008"], findings
    assert findings[0].func == "_k"


def test_l008_positionally_bound_kernel_static_exempt():
    """partial(_k, True): the leading positional param is a launch
    static, not a traced ref — branching on it must not be flagged."""
    src = """
        import functools
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def _k(causal, x_ref, o_ref):
            if causal:
                o_ref[...] = x_ref[...]

        def launch(x):
            return pl.pallas_call(
                functools.partial(_k, True),
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            )(x)
    """
    from flashinfer_tpu.analysis import tracer_leak

    assert tracer_leak.run(_project(("k.py", src))) == []


# ------------------------------------------- L009 vmem_budget --


def _staged_vmem_project(tmp_path, blocks):
    """A synthetic project holding the REAL fused-prefill launcher and
    one tuning config naming the given blocks for a huge page_size."""
    pkg = tmp_path / "pkg"
    (pkg / "tuning_configs").mkdir(parents=True)
    (pkg / "mod.py").write_text(open(OPS_PREFILL).read())
    cfg = pkg / "tuning_configs" / "v5e.json"
    cfg.write_text(json.dumps({
        "tactics": {
            "fused_prefill.blocks|8_4096_32_8_128_16384": blocks,
        },
    }))
    return Project.from_paths([str(pkg)]), str(cfg)


@pytest.mark.quick
def test_l009_flags_blocks_that_cannot_fit_vmem(tmp_path):
    from flashinfer_tpu.analysis import vmem_budget

    project, cfg = _staged_vmem_project(tmp_path, [8192, 512])
    findings = vmem_budget.run(project)
    assert [f.code for f in findings] == ["L009"], findings
    f = findings[0]
    assert f.filename == cfg
    assert "vmem_limit_bytes=64 MiB" in f.message
    assert "can never compile" in f.message
    # findings anchor to the key's line in the JSON
    assert json.dumps(f.func) in open(cfg).read().splitlines()[f.line - 1]


def test_l009_sane_blocks_pass(tmp_path):
    from flashinfer_tpu.analysis import vmem_budget

    project, _ = _staged_vmem_project(tmp_path, [128, 1])
    assert vmem_budget.run(project) == []


def test_l009_conditional_assignments_min_merge():
    """A write under an If may not execute: the evaluator must keep the
    SMALLEST value on any path, or 'cannot fit' stops being a proof."""
    import ast as ast_mod

    from flashinfer_tpu.analysis.vmem_budget import _Evaluator

    fn = ast_mod.parse(textwrap.dedent("""
        def launcher(total_q, block_q):
            bq = 64
            if total_q > 512:
                bq = block_q
            else:
                bq = 32
    """)).body[0]
    ev = _Evaluator({"total_q": 256, "block_q": 8192}, 2)
    ev.run_body(fn)
    assert ev.env["bq"] == 32  # NOT 8192 (last-write-wins would)


def test_l007_absent_scratch_shapes_counts_as_zero():
    """Omitting scratch_shapes= is statically ZERO scratch refs — the
    kernel-arity check must still run and catch the extra param."""
    src = """
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def _k(x_ref, o_ref, ghost_ref):
            o_ref[...] = x_ref[...]

        def launch(x):
            return pl.pallas_call(
                _k,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            )(x)
    """
    from flashinfer_tpu.analysis import pallas_contract

    findings = pallas_contract.run(_project(("k.py", src)))
    assert any(f.code == "L007" and "3 positional ref(s)" in f.message
               for f in findings), findings


def test_l009_estimate_is_physically_plausible():
    """The symbolic evaluator reproduces the hand-computed scratch
    footprint of the fused-prefill kernel for a known shape."""
    from flashinfer_tpu.analysis.vmem_budget import KNOB_LAUNCHES, _estimate

    project = Project.from_paths([PKG_ROOT])
    est = _estimate(project, KNOB_LAUNCHES["fused_prefill.blocks"],
                    [256, 16], "8_4096_32_8_128_16".split("_"))
    assert est is not None
    total, budget, _launcher = est
    # bq=256 group=4 D=128 chunk=256: qbuf 512K + k/v 256K + obuf 256K
    # + acc 512K + m/l 1M  ≈ 2.6 MB
    assert 2_000_000 < total < 3_500_000, total
    assert budget == 64 * 1024 * 1024


# -------------------------------------- L010 kernel_init_guard --


L010_KERNEL = """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def _acc_kernel(x_ref, o_ref, acc_ref):
        i = pl.program_id(0)

        @pl.when(i != 0)
        def _():
            acc_ref[...] = acc_ref[...] + x_ref[...]

        o_ref[...] = acc_ref[...]

    def launch(x):
        return pl.pallas_call(
            _acc_kernel,
            grid=(4,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],
        )(x)
"""


@pytest.mark.quick
def test_l010_flags_uninitialized_guarded_accumulator():
    from flashinfer_tpu.analysis import kernel_init_guard

    findings = kernel_init_guard.run(_project(("k.py", L010_KERNEL)))
    assert [f.code for f in findings] == ["L010"], findings
    assert "acc_ref" in findings[0].message
    assert "EXCLUDE the first grid step" in findings[0].message


def test_l010_step_zero_init_write_is_clean():
    fixed = L010_KERNEL.replace(
        "        o_ref[...] = acc_ref[...]",
        """\
        @pl.when(i == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        o_ref[...] = acc_ref[...]""")
    assert fixed != L010_KERNEL
    from flashinfer_tpu.analysis import kernel_init_guard

    assert kernel_init_guard.run(_project(("k.py", fixed))) == []


def test_l010_value_guards_are_not_step_guards():
    """`pl.when(num_chunks > 0)` gates work, not steps — it must not
    classify as excluding (the mla_decode/paged_decode idiom)."""
    src = L010_KERNEL.replace("@pl.when(i != 0)",
                              "@pl.when(x_ref[0] > 0)")
    assert src != L010_KERNEL
    from flashinfer_tpu.analysis import kernel_init_guard

    assert kernel_init_guard.run(_project(("k.py", src))) == []


def test_l010_input_output_alias_bounds():
    src = L010_KERNEL.replace(
        "scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],",
        "scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],\n"
        "            input_output_aliases={3: 2},")
    # silence the accumulator finding: this test is about the aliases
    src = src.replace("@pl.when(i != 0)", "@pl.when(i == 0)")
    from flashinfer_tpu.analysis import kernel_init_guard

    findings = kernel_init_guard.run(_project(("k.py", src)))
    assert len(findings) == 2, findings
    msgs = " | ".join(f.message for f in findings)
    assert "key 3 is out of range" in msgs
    assert "value 2 is out of range" in msgs


# ------------------------------------------------- SARIF surface --


# A faithful subset of the SARIF 2.1.0 schema (oasis-tcs/sarif-spec
# Schemata/sarif-schema-2.1.0.json): the required/enum constraints for
# every node the exporter emits.  Validated with jsonschema so a
# structural regression (missing version, results without messages,
# bad level enum) fails here rather than at GitHub upload time.
SARIF_SCHEMA_SUBSET = {
    "type": "object",
    "required": ["version", "runs"],
    "properties": {
        "version": {"enum": ["2.1.0"]},
        "$schema": {"type": "string", "format": "uri"},
        "runs": {
            "type": "array",
            "items": {
                "type": "object",
                "required": ["tool"],
                "properties": {
                    "tool": {
                        "type": "object",
                        "required": ["driver"],
                        "properties": {
                            "driver": {
                                "type": "object",
                                "required": ["name"],
                                "properties": {
                                    "name": {"type": "string"},
                                    "rules": {
                                        "type": "array",
                                        "items": {
                                            "type": "object",
                                            "required": ["id"],
                                        },
                                    },
                                },
                            },
                        },
                    },
                    "results": {
                        "type": "array",
                        "items": {
                            "type": "object",
                            "required": ["message"],
                            "properties": {
                                "message": {
                                    "type": "object",
                                    "required": ["text"],
                                },
                                "level": {"enum": [
                                    "none", "note", "warning", "error"]},
                                "ruleId": {"type": "string"},
                                "locations": {
                                    "type": "array",
                                    "items": {
                                        "type": "object",
                                        "properties": {
                                            "physicalLocation": {
                                                "type": "object",
                                                "properties": {
                                                    "artifactLocation": {
                                                        "type": "object",
                                                        "properties": {
                                                            "uri": {
                                                                "type":
                                                                "string"},
                                                        },
                                                    },
                                                    "region": {
                                                        "type": "object",
                                                        "properties": {
                                                            "startLine": {
                                                                "type":
                                                                "integer",
                                                                "minimum":
                                                                1},
                                                        },
                                                    },
                                                },
                                            },
                                        },
                                    },
                                },
                            },
                        },
                    },
                },
            },
        },
    },
}


@pytest.mark.quick
def test_sarif_output_validates_against_schema():
    import jsonschema

    from flashinfer_tpu.analysis import sarif as sarif_mod

    findings = [
        analysis.Finding("L007", "flashinfer_tpu/ops/x.py", 3, "launch",
                         "skewed"),
        analysis.Finding("L000", "flashinfer_tpu/y.py", 0,
                         "<suppression>", "no reason"),
    ]
    doc = sarif_mod.to_sarif(findings)
    jsonschema.validate(doc, SARIF_SCHEMA_SUBSET)
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "graft-lint"
    assert [r["ruleId"] for r in run["results"]] == ["L007", "L000"]
    # line 0 is clamped to the schema's minimum
    assert run["results"][1]["locations"][0]["physicalLocation"][
        "region"]["startLine"] == 1
    # rules cover exactly the emitted codes
    assert {r["id"] for r in run["tool"]["driver"]["rules"]} \
        == {"L000", "L007"}
    # empty-findings document is also valid (the CI always-upload path)
    jsonschema.validate(sarif_mod.to_sarif([]), SARIF_SCHEMA_SUBSET)


def test_cli_sarif_flag_writes_new_findings(tmp_path, capsys):
    """--sarif writes the NON-baselined findings: a clean single-file
    run produces a valid empty SARIF, and a file with a real finding
    lands in the document (single-file runs keep the tier-1 cost down;
    the whole-tree CLI run is covered by
    test_cli_clean_against_baseline_and_fails_without)."""
    import jsonschema

    out = tmp_path / "out.sarif"
    clean = os.path.join(PKG_ROOT, "attention.py")
    assert analysis.main([clean, "--sarif", str(out)]) == 0
    doc = json.loads(out.read_text())
    jsonschema.validate(doc, SARIF_SCHEMA_SUBSET)
    assert doc["runs"][0]["results"] == []
    # a self-contained wedge fixture surfaces its finding in the doc
    # (the tree's own baselined L003s are transitive — a single-file
    # run cannot see their cross-module callees, so a fixture it is)
    noisy = tmp_path / "wedgy.py"
    noisy.write_text(WEDGY)
    assert analysis.main(
        [str(noisy), "--no-baseline", "--sarif", str(out)]) == 1
    doc = json.loads(out.read_text())
    jsonschema.validate(doc, SARIF_SCHEMA_SUBSET)
    results = doc["runs"][0]["results"]
    assert results and all(r["ruleId"] == "W003" for r in results)
    assert all(r["locations"][0]["physicalLocation"]["artifactLocation"]
               ["uri"] == "wedgy.py" for r in results)


# ------------------------------------------- --changed-only mode --


def _git(repo, *args):
    import subprocess

    r = subprocess.run(
        ["git", "-C", str(repo), "-c", "user.email=t@t",
         "-c", "user.name=t", *args],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    return r.stdout


WEDGY = """
import jax.numpy as jnp


def lane_repeat_kernel(x_ref, o_ref):
    o_ref[...] = jnp.repeat(x_ref[...], 4, axis=-1)
"""


@pytest.mark.quick
def test_changed_only_analyzes_only_the_changed_module(tmp_path, capsys):
    """A one-file diff analyzes only that file's modules: the unchanged
    file's finding must NOT appear, the changed file's must."""
    repo = tmp_path / "repo"
    repo.mkdir()
    _git(repo, "init", "-q")
    (repo / "clean_a.py").write_text("x = 1\n")
    (repo / "clean_b.py").write_text(WEDGY)  # committed: not "changed"
    _git(repo, "add", "-A")
    _git(repo, "commit", "-qm", "seed")
    (repo / "clean_a.py").write_text(WEDGY.replace(
        "lane_repeat_kernel", "other_repeat_kernel"))
    rc = analysis.main([str(repo), "--changed-only", "--no-baseline"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "other_repeat_kernel" in out   # the changed file's finding
    assert "lane_repeat_kernel" not in out  # unchanged file not analyzed
    assert "1 finding(s)" in out


def test_changed_only_config_json_diff_runs_full_analysis(tmp_path,
                                                          capsys):
    """A tuning_configs/*.json-only diff must NOT report 'no analyzed
    files changed' — L006/L009 lint exactly those files, so the CLI
    falls back to full analysis."""
    repo = tmp_path / "repo"
    (repo / "tuning_configs").mkdir(parents=True)
    _git(repo, "init", "-q")
    (repo / "mod.py").write_text(WEDGY)
    (repo / "tuning_configs" / "v5e.json").write_text("{}\n")
    _git(repo, "add", "-A")
    _git(repo, "commit", "-qm", "seed")
    (repo / "tuning_configs" / "v5e.json").write_text(
        '{"tactics": {}}\n')
    rc = analysis.main([str(repo), "--changed-only", "--no-baseline"])
    out = capsys.readouterr().out
    assert rc == 1  # the full run sees mod.py's wedge finding
    assert "no analyzed files changed" not in out
    assert "lane_repeat_kernel" in out


def test_changed_only_clean_diff_exits_zero(tmp_path, capsys):
    repo = tmp_path / "repo"
    repo.mkdir()
    _git(repo, "init", "-q")
    (repo / "mod.py").write_text(WEDGY)
    _git(repo, "add", "-A")
    _git(repo, "commit", "-qm", "seed")
    rc = analysis.main([str(repo), "--changed-only", "--no-baseline"])
    assert rc == 0
    assert "no analyzed files changed" in capsys.readouterr().out


def test_whole_tree_run_reports_deleted_file_stale_entries(tmp_path,
                                                           capsys):
    """A baseline entry naming a file that no longer exists must still
    print as stale on a whole-tree run — that's the deleted/renamed
    module case pruning exists for."""
    import flashinfer_tpu.analysis as analysis_mod

    real = json.load(open(analysis_mod.DEFAULT_BASELINE_PATH))
    real["findings"].append({
        "code": "L003", "path": "flashinfer_tpu/deleted_module.py",
        "func": "gone", "count": 1, "lines_at_capture": [1]})
    fake = tmp_path / "b.json"
    fake.write_text(json.dumps(real))
    rc = analysis.main([PKG_ROOT, "--baseline", str(fake)])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "deleted_module.py" in out
    assert "1 stale" in out


def test_write_baseline_refuses_subset_runs(tmp_path, capsys):
    """--write-baseline on a subset (one file / --changed-only) would
    truncate the committed baseline to what the partial tree shows —
    the CLI must refuse."""
    one = os.path.join(PKG_ROOT, "attention.py")
    out = tmp_path / "b.json"
    rc = analysis.main([one, "--write-baseline",
                        "--baseline", str(out)])
    assert rc == 2
    assert not out.exists()
    assert "whole-tree" in capsys.readouterr().err


def test_subset_run_does_not_report_foreign_stale_entries(capsys):
    """Analyzing one file against the full baseline must not claim
    every other file's baselined findings are stale."""
    one = os.path.join(PKG_ROOT, "attention.py")
    rc = analysis.main([one])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "stale baseline entry (no longer fires" not in out
    assert "0 stale" in out


# ------------------------- satellite: per-run soft-cap rebind --


def _plan_batch_attention(w, soft_cap):
    import numpy as np

    qo = np.array([0, 2, 4], np.int32)
    kvp = np.array([0, 2, 4], np.int32)
    kvi = np.arange(4, dtype=np.int32)
    kvl = np.array([8, 8], np.int32)
    w.plan(qo, kvp, kvi, kvl, 4, 2, 64, 64, 4, causal=True,
           logits_soft_cap=soft_cap)


def _soft_cap_inputs():
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (4, 4, 64), jnp.bfloat16)
    kc = jax.random.normal(jax.random.fold_in(key, 1), (4, 4, 2, 64),
                           jnp.bfloat16)
    vc = jax.random.normal(jax.random.fold_in(key, 2), (4, 4, 2, 64),
                           jnp.bfloat16)
    return q, (kc, vc)


def test_batch_attention_run_honors_differing_soft_cap():
    """ADVICE r5 item 3 (resolved): a per-run logits_soft_cap differing
    from the planned one takes effect for that call — the verbatim
    reference call shape — instead of raising; the plan's own cap is
    restored afterwards."""
    import numpy as np

    import flashinfer_tpu as fi

    q, kv = _soft_cap_inputs()
    w = fi.BatchAttention(kv_layout="NHD")
    _plan_batch_attention(w, 30.0)
    out_30, _ = w.run(q, kv)
    out_50_rebound, _ = w.run(q, kv, logits_soft_cap=50.0)
    # plan restored after the rebound call
    assert w._plan.logits_soft_cap == 30.0
    out_30_again, _ = w.run(q, kv)
    np.testing.assert_array_equal(np.asarray(out_30),
                                  np.asarray(out_30_again))

    # ground truth: a wrapper PLANNED at 50 produces the rebound output
    w50 = fi.BatchAttention(kv_layout="NHD")
    _plan_batch_attention(w50, 50.0)
    out_50_planned, _ = w50.run(q, kv)
    np.testing.assert_array_equal(np.asarray(out_50_rebound),
                                  np.asarray(out_50_planned))
    # and the capped outputs genuinely differ from the 30-cap ones
    assert not np.array_equal(np.asarray(out_30),
                              np.asarray(out_50_rebound))


def test_batch_attention_soft_cap_rebind_counted(monkeypatch):
    import flashinfer_tpu as fi
    from flashinfer_tpu import obs

    monkeypatch.setenv("FLASHINFER_TPU_METRICS", "1")
    obs.reset()
    try:
        q, kv = _soft_cap_inputs()
        w = fi.BatchAttention(kv_layout="NHD")
        _plan_batch_attention(w, 30.0)
        w.run(q, kv, logits_soft_cap=50.0)   # differing: rebinds
        w.run(q, kv, logits_soft_cap=30.0)   # matching: no rebind
        w.run(q, kv)                         # default: inherits, none
        snap = obs.snapshot()
        assert snap["counters"]["plan.soft_cap_rebinds"][
            "{wrapper=BatchAttention}"] == 1
    finally:
        obs.reset()


# ----------------------------- satellite: wedge_lint shim retired --


def test_wedge_lint_shim_is_retired():
    """The PR 4 DeprecationWarning shim is gone (ISSUE 15): the wedge
    lint is importable ONLY from analysis.wedge, and compile_guard's
    runtime hook already goes there directly."""
    import ast as _ast
    import importlib
    import inspect as _inspect

    import pytest as _pytest

    with _pytest.raises(ModuleNotFoundError):
        importlib.import_module("flashinfer_tpu.wedge_lint")
    from flashinfer_tpu import compile_guard

    src = _inspect.getsource(compile_guard)
    assert "from flashinfer_tpu.analysis import wedge" in src
    for node in _ast.walk(_ast.parse(src)):
        if isinstance(node, _ast.ImportFrom):
            assert not any(a.name == "wedge_lint" for a in node.names), \
                "compile_guard must not import the retired shim"


# ---------------------------------- driver: all seventeen passes --


def test_driver_runs_all_seventeen_passes():
    """Registration pin for the grown driver: L001–L017 all behind the
    one driver (a pass that exists but is not in PASSES silently never
    runs — exactly the silent-skip failure mode L013 exists to kill)."""
    from flashinfer_tpu.analysis import (chooser_coverage, cost_parity,
                                         dma_race, donation_lifetime,
                                         kernel_init_guard,
                                         mosaic_lowering, pallas_contract,
                                         registry_coverage, static_flow,
                                         tracer_leak, vmem_budget)

    for p in (pallas_contract, tracer_leak, vmem_budget,
              kernel_init_guard, donation_lifetime, static_flow,
              registry_coverage, dma_race, mosaic_lowering,
              cost_parity, chooser_coverage):
        assert p in analysis.PASSES, p.__name__
    assert len(analysis.PASSES) == 17
