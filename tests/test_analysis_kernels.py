"""Kernel dataflow analyzer passes (ISSUE 16): L014 DMA/semaphore race
detection and L015 Mosaic-lowerability lint.

Per-hazard synthetic fixtures pin each L014 check class
(read-before-wait, slot-overwrite, wait-imbalance under ``pl.when``,
cross-grid-iteration carry) and each L015 rule, and the acceptance
regressions skew the REAL kernels: deleting the fused-prefill
mainloop's wait loop / breaking its slot parity / widening its warmup
guard must flag exactly L014, un-suppressing the decode static-variant
warmup over its predecessor's in-flight prefetch must flag exactly
L014, and a new rotation-style lane slice must surface as a NEW L015
that the committed ``mosaic_risks`` budget does NOT absorb.  The
unmodified tree stays clean under both passes.
"""

import os
import textwrap

import pytest

from flashinfer_tpu import analysis
from flashinfer_tpu.analysis import dma_race, mosaic_lowering
from flashinfer_tpu.analysis.core import Project, load_source

PKG_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "flashinfer_tpu"))

OPS_PREFILL = os.path.join(PKG_ROOT, "ops", "paged_prefill.py")
OPS_DECODE = os.path.join(PKG_ROOT, "ops", "paged_decode.py")


def _project(*named_sources):
    return Project([load_source(textwrap.dedent(src), name)
                    for name, src in named_sources])


def _real(path):
    return open(path).read()


def _tags(findings):
    return sorted(f.message[1:].split("]", 1)[0] for f in findings)


# a minimal double-buffered DMA kernel scaffold the fixtures specialize
_HEADER = """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
"""

_LAUNCH = """
    def launch(x):
        return pl.pallas_call(
            _k, grid=(4,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.ANY)],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            scratch_shapes=[pltpu.VMEM((2, 8, 128), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))],
        )(x)
"""


# ------------------------------------------------ L014 check fixtures --


@pytest.mark.quick
def test_l014_read_before_wait_fixture():
    src = _HEADER + """
    def _k(x_hbm, o_ref, buf, sem):
        c = pltpu.make_async_copy(x_hbm.at[0], buf.at[0], sem.at[0])
        c.start()
        o_ref[...] = buf[0]
        c.wait()
    """ + _LAUNCH
    findings = dma_race.run(_project(("k.py", src)))
    assert [f.code for f in findings] == ["L014"], findings
    assert _tags(findings) == ["read-before-wait"]
    assert "`buf`" in findings[0].message


@pytest.mark.quick
def test_l014_slot_overwrite_fixture():
    """Second start into the same slot while the first copy may still
    be in flight — the double-buffer parity bug shape."""
    src = _HEADER + """
    def _k(x_hbm, o_ref, buf, sem):
        c0 = pltpu.make_async_copy(x_hbm.at[0], buf.at[0], sem.at[0])
        c0.start()
        c1 = pltpu.make_async_copy(x_hbm.at[1], buf.at[0], sem.at[1])
        c1.start()
        c0.wait()
        c1.wait()
        o_ref[...] = buf[0]
    """ + _LAUNCH
    findings = dma_race.run(_project(("k.py", src)))
    assert [f.code for f in findings] == ["L014"], findings
    assert _tags(findings) == ["slot-overwrite"]


@pytest.mark.quick
def test_l014_wait_imbalance_under_when_fixture():
    """Start guarded by `pl.when(u == 0)`, wait unguarded: every step
    past the first waits on a semaphore nothing signalled — the
    BENCH_r04/r05 wedge shape."""
    src = _HEADER + """
    def _k(x_hbm, o_ref, buf, sem):
        u = pl.program_id(0)
        c = pltpu.make_async_copy(x_hbm.at[0], buf.at[0], sem.at[0])

        @pl.when(u == 0)
        def _():
            c.start()

        c.wait()
        o_ref[...] = buf[0]
    """ + _LAUNCH
    findings = dma_race.run(_project(("k.py", src)))
    assert [f.code for f in findings] == ["L014"], findings
    assert _tags(findings) == ["wait-imbalance"]
    assert "`sem`" in findings[0].message


@pytest.mark.quick
def test_l014_cross_step_carry_clean_then_skewed():
    """The cross-grid prefetch pipeline: each step consumes its
    predecessor's copy and prefetches for its successor.  Correctly
    guarded it is clean; consuming only from step 2 on leaves step 0's
    prefetch in flight under step 1's start — a carry-labeled
    slot-overwrite plus a dangling DMA."""
    clean = _HEADER + """
    def _k(x_hbm, o_ref, buf, sem):
        u = pl.program_id(0)
        nu = pl.num_programs(0)

        @pl.when(u > 0)
        def _():
            pltpu.make_async_copy(
                x_hbm.at[u - 1], buf.at[0], sem.at[0]).wait()

        @pl.when(u + 1 < nu)
        def _():
            pltpu.make_async_copy(
                x_hbm.at[u], buf.at[0], sem.at[0]).start()

        o_ref[...] = x_hbm[0, 0]
    """ + _LAUNCH
    assert dma_race.run(_project(("k.py", clean))) == []

    skew = clean.replace("@pl.when(u > 0)", "@pl.when(u > 1)")
    assert skew != clean
    findings = dma_race.run(_project(("k.py", skew)))
    assert findings and all(f.code == "L014" for f in findings)
    tags = _tags(findings)
    assert "slot-overwrite" in tags and "dangling-dma" in tags
    assert any("cross-grid-iteration carry" in f.message
               for f in findings)


# --------------------------------------- L014 real-file skew probes --


@pytest.mark.quick
def test_l014_wait_deletion_skew_real_fused_prefill():
    """THE acceptance regression: delete the fused-prefill mainloop's
    KV wait loop and the work-unit pipeline reads undelivered slots at
    every step — exactly L014 (and a lot of it)."""
    real = _real(OPS_PREFILL)
    skew = real.replace(
        "    for d in kv_dmas(u, slot):\n"
        "        d.wait()\n"
        "\n"
        "    # the whole GQA group rides one MXU dot",
        "\n"
        "    # the whole GQA group rides one MXU dot")
    assert skew != real
    findings = dma_race.run(
        _project(("flashinfer_tpu/ops/paged_prefill.py", skew)))
    assert findings and all(f.code == "L014" for f in findings)
    tags = set(_tags(findings))
    assert {"read-before-wait", "dangling-dma"} <= tags, tags


def test_l014_unit_bound_skew_real_fused_prefill():
    """The work-unit mainloop reads its unit axis's trip count through
    ``pl.num_programs(1)`` (a value of the call: the plan's count of real
    units), which the model ties to N_STEPS.  Loosen the KV prefetch
    guard by one and the last unit run starts a chunk that no grid step
    waits for."""
    real = _real(OPS_PREFILL)
    head, kernel = real.split("def _fused_prefill_kernel(", 1)
    assert "num_units = pl.num_programs(1)" in kernel
    guard = ("    @pl.when(u + 1 < num_units)\n"
             "    def _():\n"
             "        for d in kv_dmas(nxt, jax.lax.rem(u + 1, 2)):")
    assert kernel.count(guard) == 1
    skew = head + "def _fused_prefill_kernel(" + kernel.replace(
        guard, guard.replace("u + 1 < num_units", "u < num_units"))
    findings = dma_race.run(
        _project(("flashinfer_tpu/ops/paged_prefill.py", skew)))
    assert findings and all(f.code == "L014" for f in findings)
    assert "dangling-dma" in _tags(findings)


def test_l014_slot_parity_skew_real_fused_prefill():
    """Prefetching the NEXT unit into the CURRENT slot (rem(u) instead
    of rem(u+1)) overwrites the buffer the mainloop is about to read."""
    real = _real(OPS_PREFILL)
    skew = real.replace(
        "        for d in kv_dmas(nxt, jax.lax.rem(u + 1, 2)):\n"
        "            d.start()",
        "        for d in kv_dmas(nxt, jax.lax.rem(u, 2)):\n"
        "            d.start()")
    assert skew != real
    findings = dma_race.run(
        _project(("flashinfer_tpu/ops/paged_prefill.py", skew)))
    assert findings and all(f.code == "L014" for f in findings)
    assert "slot-overwrite" in _tags(findings)


def test_l014_sem_balance_skew_real_fused_prefill():
    """Widening the Q warmup guard from (u == 0 AND first) to just
    (first) re-issues the unit-0 Q DMA on later steps — start/wait
    imbalance plus a dangling copy at teardown."""
    real = _real(OPS_PREFILL)
    skew = real.replace(
        "    @pl.when(jnp.logical_and(u == 0, first_ref[0] == 1))\n"
        "    def _():\n"
        "        q_dma(0, qslot_ref[0]).start()",
        "    @pl.when(first_ref[0] == 1)\n"
        "    def _():\n"
        "        q_dma(0, qslot_ref[0]).start()")
    assert skew != real
    findings = dma_race.run(
        _project(("flashinfer_tpu/ops/paged_prefill.py", skew)))
    assert findings and all(f.code == "L014" for f in findings)
    assert "dangling-dma" in _tags(findings)


def test_l014_decode_warmup_suppression_skew():
    """The static cross-step decode variant must NOT warm up when its
    predecessor already prefetched chunk 0 into slot 0.  Dropping the
    `~prev_prefetched` suppression double-starts the slot over the
    in-flight copy — the exact correlated-guard shape the simulator's
    `~`/`is` modeling exists for."""
    real = _real(OPS_DECODE)
    skew = real.replace(
        "@pl.when((num_chunks > 0) & ~prev_prefetched)",
        "@pl.when(num_chunks > 0)")
    assert skew != real
    findings = dma_race.run(
        _project(("flashinfer_tpu/ops/paged_decode.py", skew)))
    assert findings and all(f.code == "L014" for f in findings)
    fused = [f for f in findings
             if f.func == "_decode_kernel_fused_heads"]
    assert fused, findings
    tags = set(_tags(fused))
    assert {"slot-overwrite", "dangling-dma"} <= tags, tags
    assert any("cross-grid-iteration carry" in f.message for f in fused)


# ------------------------------------------------ L015 rule fixtures --


@pytest.mark.quick
def test_l015_rule_fixtures_fire_and_aligned_stays_clean():
    """One kernel per rule outcome: misaligned + strided rotation
    slices, a cast-to-match, and a dynamic gather all flag; the
    lane-aligned twin (128-bound slices, width-1 running stat, literal
    dtype cast) is clean."""
    risky = _HEADER + """
    def _k(x_ref, o_ref):
        xf = x_ref[...]
        x1, x2 = xf[:, :64], xf[:, 64:]
        e1, e2 = xf[:, 0::2], xf[:, 1::2]
        cast = xf.astype(o_ref.dtype)
        g = jnp.take(xf, jnp.argmax(xf, axis=-1), axis=0)
        o_ref[...] = x1 + x2

    def launch(x):
        return pl.pallas_call(
            _k, grid=(4,),
            in_specs=[pl.BlockSpec((8, 256), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((8, 256), lambda i: (0, 0)),
        )(x)
    """
    findings = mosaic_lowering.run(_project(("k.py", risky)))
    assert all(f.code == "L015" for f in findings)
    assert _tags(findings) == ["cast", "gather", "lane-slice",
                               "lane-slice", "strided-lane",
                               "strided-lane"], findings
    # the hazard-free twin: every construct has a committed lowering
    clean = _HEADER + """
    def _k(x_ref, o_ref):
        xf = x_ref[...]
        lo, hi = xf[:, :128], xf[:, 128:]
        stat = xf[:, :1]
        o_ref[...] = (lo + hi).astype(jnp.float32) + stat

    def launch(x):
        return pl.pallas_call(
            _k, grid=(4,),
            in_specs=[pl.BlockSpec((8, 256), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((8, 256), lambda i: (0, 0)),
        )(x)
    """
    assert mosaic_lowering.run(_project(("k.py", clean))) == []


@pytest.mark.quick
def test_l015_rotation_slice_probe_real_fused_prefill():
    """The PR 14 in-register rotation — `xf[:, :half]` / `[half:]` with
    half = head_dim // 2 and the stride-2 interleave — is flagged on
    the real file by L015 and ONLY L015 (L014 has nothing to say about
    a lowering risk)."""
    project = _project(
        ("flashinfer_tpu/ops/paged_prefill.py", _real(OPS_PREFILL)))
    findings = mosaic_lowering.run(project)
    rot = [f for f in findings
           if f.func == "_fused_prefill_ingest_kernel"
           and f.message[1:].split("]")[0] in ("lane-slice",
                                               "strided-lane")]
    assert len(rot) == 4, findings  # both halves + both interleaves
    assert all(f.code == "L015" for f in rot)
    assert any("not provably 0 mod 128" in f.message for f in rot)


def test_l015_new_rotation_slice_not_absorbed_by_baseline():
    """A NEW unaligned lane slice in an already-triaged kernel must
    overflow the committed ``mosaic_risks`` budget and surface as a new
    finding — triage counts cannot silently absorb fresh risks."""
    real = _real(OPS_PREFILL)
    skew = real.replace(
        "            x1, x2 = xf[:, :half], xf[:, half:]",
        "            x1, x2 = xf[:, :half], xf[:, half:]\n"
        "            x2 = x2 + xf[:, 8:]")
    assert skew != real
    findings = mosaic_lowering.run(
        _project(("flashinfer_tpu/ops/paged_prefill.py", skew)))
    new, _old, _stale = analysis.partition_against_baseline(
        findings, analysis.load_baseline())
    assert len(new) == 1 and new[0].code == "L015", new


# ------------------------------------------- clean-tree pins + stats --


def test_l014_whole_tree_clean_no_baseline_involved():
    """The shipped kernels have NO DMA/semaphore findings at the pass
    level — L014 runs baseline-free (a race is fixed, never triaged)."""
    project = Project.from_paths([PKG_ROOT])
    assert dma_race.run(project) == []
    st = dma_race.stats(project)
    assert st["kernels_skipped"] == 0, st
    assert st["kernels_analyzed"] >= 7, st


def test_l015_whole_tree_matches_committed_mosaic_risks():
    """Every current L015 finding is covered by the committed
    ``mosaic_risks`` triage (no new, no stale) — the bring-up checklist
    is exactly in sync with the tree."""
    project = Project.from_paths([PKG_ROOT])
    findings = mosaic_lowering.run(project)
    suppressed = []
    for f in findings:
        sf = next((s for s in project.files
                   if s.path == f.filename), None)
        if sf is not None and sf.suppression_for(f.line):
            continue
        suppressed.append(f)
    new, _old, stale = analysis.partition_against_baseline(
        suppressed, {k: v for k, v in analysis.load_baseline().items()
                     if k[0] == "L015"})
    assert new == [], new
    assert stale == [], stale
    st = mosaic_lowering.stats(project)
    assert st["kernels_linted"] >= 17, st
    assert st["findings_by_rule"]["lane-slice"] >= 3, st
    assert st["findings_by_rule"]["strided-lane"] >= 2, st


def test_l014_l015_stats_feed_doctor_counts():
    """`obs doctor` renders analyzed-vs-skipped kernel counts from the
    pass stats hooks — pin the schema both sides read."""
    project = Project.from_paths([os.path.join(PKG_ROOT, "ops")])
    d = dma_race.stats(project)
    for key in ("kernels_analyzed", "kernels_skipped", "kernels_no_dma",
                "sites_unresolved", "skip_reasons"):
        assert key in d, d
    m = mosaic_lowering.stats(project)
    for key in ("kernels_linted", "sites_unresolved",
                "findings_by_rule"):
        assert key in m, m
    assert set(m["findings_by_rule"]) == set(mosaic_lowering.RULES)
