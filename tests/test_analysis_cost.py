"""Cost-parity analyzer passes (ISSUE 19): L016 kernel-vs-costmodel
physics parity and L017 chooser/knob pricing coverage.

The acceptance regressions skew the REAL tree: zeroing the fused-ingest
avoided-Kc cache-write term in ``costmodel.prefill_ingest`` must flag
exactly ONE L016 cost-drift finding (the detector reads the formula
from the mutated snapshot, not the installed package), and disarming
``predict_prefill_ingest_win``'s VMEM prune must flag exactly ONE L017
finding.  The unmodified tree pins ``run(project) == []`` for both
passes with every registered family actually checked — a parity pass
that silently skips is indistinguishable from a clean tree — and L016
findings can never be absorbed by the committed baseline.
"""

import os

import pytest

from flashinfer_tpu import analysis
from flashinfer_tpu.analysis import chooser_coverage, cost_parity
from flashinfer_tpu.analysis.core import Project, load_file, load_source

PKG_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "flashinfer_tpu"))

COSTMODEL = os.path.join(PKG_ROOT, "obs", "costmodel.py")

# the L016 surface: every file holding a bound launcher, plus the
# registry module whose snapshot carries the formulas
_L016_PATHS = [os.path.join(PKG_ROOT, "ops")]
# the L017 surface: registry module + the plan-path callers that wire
# the prune + the knob registry the coverage check spans
_L017_PATHS = [
    COSTMODEL,
    os.path.join(PKG_ROOT, "decode.py"),
    os.path.join(PKG_ROOT, "prefill.py"),
    os.path.join(PKG_ROOT, "autotuner.py"),
]


def _real(path):
    with open(path) as f:
        return f.read()


def _l016_project(costmodel_src):
    files = [load_file(p)
             for p in analysis.iter_python_files(_L016_PATHS)]
    files.append(load_source(costmodel_src, COSTMODEL))
    return Project(files)


def _l017_project(costmodel_src):
    files = [load_source(costmodel_src, COSTMODEL)]
    files += [load_file(p) for p in _L017_PATHS[1:]]
    return Project(files)


# ------------------------------------------------ L016 cost parity --


@pytest.mark.quick
def test_l016_clean_tree_every_family_checks():
    """The shipped kernels agree with their registered cost families
    under every binding scenario — and 'agree' means CHECKED: zero
    skips, so a silently-unmodelable kernel can't masquerade as
    parity.  The worst observed deviation sits inside the one
    declared tolerance band (HND bytes_total, 2%)."""
    project = _l016_project(_real(COSTMODEL))
    assert cost_parity.run(project) == []
    st = cost_parity.stats(project)
    assert st["families_checked"] == 5, st
    assert st["families_skipped"] == 0, st
    assert st["skip_reasons"] == {}, st
    assert 0.0 < st["max_deviation"] <= 0.02, st


@pytest.mark.quick
def test_l016_cache_write_deletion_flags_exactly_one():
    """THE acceptance regression: zero the fused-ingest family's
    quantized-cache write term (the 'avoided Kc re-read' accounting
    PR 14 shipped) and the formula under-writes by the cache pages
    while the kernel still emits them — exactly one machine-proved
    bytes_written drift on the ingest binding, diagnosed against the
    MUTATED formula text, not the installed package."""
    real = _real(COSTMODEL)
    skew = real.replace(
        "    cache_w = 2.0 * total_kv * num_kv_heads * head_dim"
        " * cache_bytes",
        "    cache_w = 0.0")
    assert skew != real
    findings = cost_parity.run(_l016_project(skew))
    assert len(findings) == 1, findings
    f = findings[0]
    assert f.code == "L016"
    assert "[cost-drift]" in f.message
    assert "bytes_written" in f.message
    assert "prefill_ingest" in f.message
    assert "never baseline" in f.message


@pytest.mark.quick
def test_l016_findings_never_baselined():
    """A proved kernel-vs-formula divergence is fixed, not triaged:
    L016 is in the analyzer's unbaselineable set, write_baseline
    refuses to absorb it, and the committed baseline carries no
    L016/L017 budget for one to hide under."""
    assert "L016" in analysis._UNBASELINEABLE
    for (code, _path, _func) in analysis.load_baseline():
        assert code not in ("L016", "L017"), code
    real = _real(COSTMODEL)
    skew = real.replace(
        "    cache_w = 2.0 * total_kv * num_kv_heads * head_dim"
        " * cache_bytes",
        "    cache_w = 0.0")
    findings = cost_parity.run(_l016_project(skew))
    new, _old, _stale = analysis.partition_against_baseline(
        findings, analysis.load_baseline())
    assert new == findings, (new, findings)


# ------------------------------------------- L017 chooser coverage --


@pytest.mark.quick
def test_l017_clean_tree():
    """Both registered choosers prune through the VMEM evaluator and
    are wired at a plan-path call site; every KNOWN_KNOBS surface is
    priced or reasonably waived; every parity binding's family and
    adapter are intact."""
    project = _l017_project(_real(COSTMODEL))
    assert chooser_coverage.run(project) == []
    st = chooser_coverage.stats(project)
    assert st["choosers"] == 2, st
    assert st["bindings"] == 5, st
    assert st["waivers"] >= 18, st  # one per waived knob


@pytest.mark.quick
def test_l017_prune_drop_flags_exactly_one():
    """Disarm predict_prefill_ingest_win's VMEM prune (the guard goes
    dead while the signature keeps the parameter) and the chooser
    prices candidates the compiler could reject — exactly one L017
    finding, anchored at the chooser definition."""
    real = _real(COSTMODEL)
    skew = real.replace(
        "    if feasible is not None and not feasible():",
        "    if False:")
    assert skew != real
    findings = chooser_coverage.run(_l017_project(skew))
    assert len(findings) == 1, findings
    f = findings[0]
    assert f.code == "L017"
    assert "predict_prefill_ingest_win" in f.message
    assert "never prunes" in f.message


@pytest.mark.quick
def test_l017_unwired_call_sites_flag():
    """A prune parameter nobody passes is dead code: strip the
    ``feasible=`` keyword from both plan-path callers and the wiring
    check fires per chooser."""
    decode_src = _real(os.path.join(PKG_ROOT, "decode.py")).replace(
        "feasible=lambda s: _split_vmem_feasible(\n"
        "                                s, shape_key)",
        "")
    prefill_src = _real(os.path.join(PKG_ROOT, "prefill.py")).replace(
        "feasible=lambda: _ingest_vmem_feasible(fused_key)",
        "")
    files = [load_source(_real(COSTMODEL), COSTMODEL),
             load_source(decode_src,
                         os.path.join(PKG_ROOT, "decode.py")),
             load_source(prefill_src,
                         os.path.join(PKG_ROOT, "prefill.py"))]
    findings = chooser_coverage.run(Project(files))
    msgs = [f.message for f in findings]
    assert len(findings) == 2, findings
    assert all(f.code == "L017" for f in findings)
    assert all("passes ``feasible=``" in m for m in msgs), msgs


# --------------------------------------------------- doctor schema --


@pytest.mark.quick
def test_l016_l017_stats_feed_doctor_counts():
    """`obs doctor` renders the cost-parity coverage from the pass
    stats hooks — pin the schema both sides read."""
    d16 = cost_parity.stats(_l016_project(_real(COSTMODEL)))
    for key in ("families_total", "families_checked",
                "families_skipped", "max_deviation", "skip_reasons"):
        assert key in d16, d16
    d17 = chooser_coverage.stats(_l017_project(_real(COSTMODEL)))
    for key in ("choosers", "waivers", "bindings", "findings"):
        assert key in d17, d17


# ------------------------------------------- live prune end-to-end --


@pytest.mark.quick
def test_ingest_feasible_prune_is_a_live_proof():
    """The wired ``feasible`` callback must actually PRICE the launch
    it gates, not fall through to always-True: the fused-ingest prune
    rides the ``fused_prefill.blocks`` evaluation at the tactic the
    launch would run with, so a default tactic keeps the candidate and
    an absurdly oversized tuned (block_q, pages_per_chunk) entry for
    the same key is pruned — False only ever means the L009 lower
    bound exceeded the launch's declared VMEM budget."""
    from flashinfer_tpu.autotuner import AutoTuner
    from flashinfer_tpu.prefill import _ingest_vmem_feasible

    key = (8, 65536, 32, 8, 128, 64)
    assert _ingest_vmem_feasible(key) is True

    tuner = AutoTuner.get()
    tuner._load()
    ck = f"fused_prefill.blocks|{'_'.join(map(str, key))}"
    saved = tuner._cache.get(ck)
    tuner._cache[ck] = (8192, 4096)
    try:
        assert _ingest_vmem_feasible(key) is False, \
            "oversized tuned tactic must be pruned"
    finally:
        if saved is None:
            tuner._cache.pop(ck, None)
        else:
            tuner._cache[ck] = saved
